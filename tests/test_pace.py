"""The steps' pace (``perceiver_tpu/training/pace.py``): the two closing
attrs of ``train/step`` and the slow-step rule, under a pinned clock
(``obs.trace._now``, and ``time.thread_time`` where the thread's own
time is what is read)."""

import types

import pytest

from perceiver_tpu.obs import events as events_mod
from perceiver_tpu.obs import trace
from perceiver_tpu.obs.events import EventLog
from perceiver_tpu.obs.telemetry import Telemetry
from perceiver_tpu.training import pace as pace_mod
from perceiver_tpu.training.pace import StepPace

USUAL = {"train/input_wait": 0.001, "train/shard": 0.001,
         "train/dispatch": 0.002, "train/fence": 0.100,
         "train/log_console": 0.001, "train/log_scalars": 0.002,
         "train/log_telemetry": 0.001}
GAP = 0.0005                    # the loop's lines between two steps
PACE = sum(USUAL.values()) + GAP
LOG_LEAVES = ("train/log_console", "train/log_scalars",
              "train/log_telemetry")


@pytest.fixture
def clock(monkeypatch):
    now = [1000.0]
    monkeypatch.setattr(trace, "_now", lambda: now[0])
    prev = trace.set_timeline(trace.Timeline())
    prev_log = events_mod.set_default_log(EventLog())
    yield now
    events_mod.set_default_log(prev_log)
    trace.set_timeline(prev)


def step(pace, now, number, leaves=USUAL, gap=GAP, during=None):
    """One ``train/step`` as the trainer writes it, the clock moved by
    each leaf's seconds; ``during(name)`` runs inside each leaf."""
    now[0] += gap
    with trace.span("train/step", step=number) as sp:
        pace.step_open()
        plain = [n for n in leaves if n not in LOG_LEAVES]
        for name in plain:
            with trace.span(name):
                now[0] += leaves[name]
                if during is not None:
                    during(name)
        with trace.span("train/log"):
            for name in LOG_LEAVES:
                if name in leaves:
                    with trace.span(name):
                        now[0] += leaves[name]
        pace.step_close(sp, queue_depth=2)
    return sp


def warmed(now, telemetry=None, gc=None, steps=12):
    pace = StepPace(gc, telemetry)
    pace.epoch_start()
    for i in range(steps):
        step(pace, now, i + 1)
    return pace


def slow_events():
    return events_mod.default_log().events("slow_step")


@pytest.mark.parametrize("leaf,extra", [
    ("train/fence", 0.0333),              # the device was late: +30.7%
    ("train/log_console", 0.2),           # a write blocked
    ("train/input_wait", 0.05),           # the loader starved the loop
])
def test_a_step_30_percent_long_is_reported_once_with_its_leaf(
        clock, capsys, tmp_path, leaf, extra):
    telemetry = Telemetry(str(tmp_path))
    pace = warmed(clock, telemetry)
    assert slow_events() == []
    sp = step(pace, clock, 13, {**USUAL, leaf: USUAL[leaf] + extra})
    for i in range(14, 20):               # its neighbours keep the pace
        step(pace, clock, i)
    (event,) = slow_events()
    assert event["step"] == 13 and event["phase"] == leaf
    assert event["interval_s"] == pytest.approx(PACE + extra, abs=1e-6)
    assert event["median_s"] == pytest.approx(PACE, abs=1e-6)
    assert event["excess_s"] == pytest.approx(extra, abs=1e-6)
    assert event["leaves"][leaf] == pytest.approx(USUAL[leaf] + extra)
    assert event["usual"][leaf] == pytest.approx(USUAL[leaf])
    assert event["queue_depth"] == 2 and event["by_design"] is None
    assert event["no_leaf_s"] == pytest.approx(GAP, abs=1e-6)
    assert sp.attrs["interval_s"] == pytest.approx(PACE + extra)
    (line,) = [ln for ln in capsys.readouterr().err.splitlines()
               if ln.startswith("[slow_step]")]
    assert f"step 13: interval {PACE + extra:.4f} s" in line
    assert f"largest excess {leaf} +{extra:.4f} s" in line
    assert "queue_depth 2" in line and "by design" not in line
    # the telemetry's file and its two counters
    assert [e["step"] for e in telemetry.events("slow_step")] == [13]
    registry = telemetry.registry
    assert registry.get("training_slow_steps_total").value == 1
    assert registry.get("training_stall_seconds_total").value == \
        pytest.approx(extra, abs=1e-6)


@pytest.mark.parametrize("what,leaves,gap", [
    ("3% long", {**USUAL, "train/fence": 0.100 + 0.03 * PACE}, GAP),
    ("30% long and under 20 ms",
     {"train/fence": 0.013, "train/dispatch": 0.002}, GAP),
])
def test_a_step_within_the_rule_s_two_margins_is_not_reported(
        clock, what, leaves, gap):
    usual = USUAL if "3%" in what else {"train/fence": 0.008,
                                        "train/dispatch": 0.002}
    pace = StepPace()
    pace.epoch_start()
    for i in range(12):
        step(pace, clock, i + 1, usual)
    sp = step(pace, clock, 13, leaves, gap)
    assert sp.attrs["interval_s"] > 1.02 * (sum(usual.values()) + GAP)
    assert slow_events() == []


def test_an_epoch_s_first_step_is_not_judged(clock):
    pace = warmed(clock)
    pace.epoch_start()                    # an eval, a checkpoint between
    clock[0] += 30.0
    sp = step(pace, clock, 13, {**USUAL, "train/fence": 1.0})
    assert sp.attrs["interval_s"] is None and slow_events() == []
    assert step(pace, clock, 14).attrs["interval_s"] == pytest.approx(PACE)
    # and with fewer than MIN_HISTORY intervals nothing is judged at all
    young = warmed(clock, steps=pace_mod.MIN_HISTORY)    # that many less one
    step(young, clock, 99, {**USUAL, "train/fence": 1.0})
    assert slow_events() == []
    step(young, clock, 100, {**USUAL, "train/fence": 1.0})
    assert [e["step"] for e in slow_events()] == [100]


def test_a_step_that_holds_an_eval_is_slow_by_design_and_says_so(
        clock, capsys, tmp_path):
    telemetry = Telemetry(str(tmp_path))
    pace = warmed(clock, telemetry)
    step(pace, clock, 13, {**USUAL, "train/eval": 0.5})
    (event,) = slow_events()
    assert event["by_design"] == ["train/eval"]
    assert event["phase"] == "train/eval"
    assert "slow by design: train/eval" in capsys.readouterr().err
    # no stall: the counters leave it out, the file has it
    assert telemetry.registry.get("training_slow_steps_total").value == 0
    assert telemetry.registry.get("training_stall_seconds_total").value == 0
    assert len(telemetry.events("slow_step")) == 1


def test_a_collection_is_named_over_the_leaf_it_ran_in(clock):
    gc = types.SimpleNamespace(seconds=0.0)

    def collect(name):
        if name == "train/dispatch":      # the collector ran in this leaf
            clock[0] += 0.120
            gc.seconds += 0.120

    pace = warmed(clock, gc=gc)
    step(pace, clock, 13, during=collect)
    (event,) = slow_events()
    assert event["phase"] == "proc/gc" and event["gc_s"] == \
        pytest.approx(0.120)
    assert event["leaves"]["train/dispatch"] == pytest.approx(0.122)


def test_time_between_two_steps_reads_as_under_no_leaf(clock, capsys):
    pace = warmed(clock)
    step(pace, clock, 13, gap=0.25)       # the host took the thread away
    (event,) = slow_events()
    assert event["phase"] == pace_mod.NO_LEAF
    assert event["no_leaf_s"] == pytest.approx(0.25, abs=1e-6)
    assert "under no leaf 0.2500 s" in capsys.readouterr().err


def test_another_group_size_starts_another_pace(clock):
    pace = warmed(clock)
    now = clock
    now[0] += GAP
    with trace.span("train/step", step=13) as sp:
        pace.step_open()
        with trace.span("train/fence"):
            now[0] += 1.0                 # four steps in one dispatch
        pace.step_close(sp, steps=4)
    assert slow_events() == [] and sp.attrs["interval_s"] is not None


def test_cpu_s_is_well_under_the_wall_time_of_a_step_that_slept(
        clock, monkeypatch):
    """Both clocks pinned, the thread's beside the wall's: asleep the
    wall clock moves alone, at work the two move together."""
    now, cpu = clock, [50.0]
    monkeypatch.setattr(pace_mod.time, "thread_time", lambda: cpu[0])
    pace = StepPace()
    pace.epoch_start()
    with trace.span("train/step", step=1) as sp:
        pace.step_open()
        with trace.span("train/fence"):
            now[0] += 0.2                 # asleep
            cpu[0] += 0.001
        pace.step_close(sp)
    with trace.span("train/step", step=2) as busy:
        pace.step_open()
        with trace.span("train/dispatch"):
            now[0] += 0.05                # at work
            cpu[0] += 0.05
        pace.step_close(busy)
    assert sp.seconds >= 0.2 and sp.attrs["cpu_s"] < 0.05
    assert busy.attrs["cpu_s"] > 0.02      # a thread that worked reads it
    assert busy.attrs["interval_s"] == pytest.approx(
        busy.seconds, abs=0.01)


def test_phases_since_line_are_the_leaves_seconds(clock):
    """One source for the telemetry line's three sums: the step's closed
    leaves, the last line's own logging among them."""
    pace = StepPace()
    pace.epoch_start()
    clock[0] += GAP
    with trace.span("train/step", step=1) as sp:
        pace.step_open()
        for name in ("train/input_wait", "train/shard", "train/dispatch",
                     "train/fence"):
            with trace.span(name):
                clock[0] += USUAL[name]
        with trace.span("train/log"):
            with trace.span("train/log_console"):
                clock[0] += 0.001
            first = pace.phases_since_line(sp)
            with trace.span("train/log_telemetry"):
                clock[0] += 0.004
        pace.step_close(sp)
    assert first == pytest.approx({"input_wait_s": 0.001, "host_s": 0.003,
                                   "fence_s": 0.100})
    with trace.span("train/step", step=2) as sp:
        pace.step_open()
        with trace.span("train/fence"):
            clock[0] += 0.050
        # the first line's logging, and this step's fence
        assert pace.phases_since_line(sp) == pytest.approx(
            {"input_wait_s": 0.0, "host_s": 0.005, "fence_s": 0.050})
        pace.step_close(sp)
    try:
        trace.set_enabled(False)
        with trace.span("train/step", step=3) as off:
            assert pace.phases_since_line(off) == {}
            pace.step_close(off)          # nothing to read, nothing raised
    finally:
        trace.set_enabled(True)
