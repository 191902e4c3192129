"""The five readers of the program's whole timeline
(``benchmarks/layer_metrics/process_timeline.py``: ``setup.import_s``,
``setup.first_steps_s``, ``setup.uncovered_s``, ``train.stall_pct``,
``train.gc_pct``) on a span ring filled by hand under a pinned clock and
a hand-built ``Run``."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from perceiver_tpu.obs import events as events_mod  # noqa: E402
from perceiver_tpu.obs import trace as trace_mod  # noqa: E402

SETUP = {"setup.import_s": 19.0, "setup.state_build_s": 3.0,
         "setup.step_load_s": 7.0, "setup.first_steps_s": 7.0,
         "setup.uncovered_s": 3.3}
OTHER = 0.7         # train/construct, train/data_setup, train/io_setup
OPENED, WINDOW = 40.0, 40.0
NEW = ("setup.import_s", "setup.first_steps_s", "setup.uncovered_s",
       "train.stall_pct", "train.gc_pct")


def reader(name):
    return harness.load_module(
        os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py"),
        "test_metric_" + name.replace(".", "_"))


@pytest.fixture
def clock(monkeypatch):
    """The spans' clock under the test's hand, a ring and an event log
    of its own."""
    now = [0.0]
    monkeypatch.setattr(trace_mod, "_now", lambda: now[0])
    prev = trace_mod.set_timeline(trace_mod.Timeline())
    prev_log = events_mod.set_default_log(events_mod.EventLog())
    yield now
    events_mod.set_default_log(prev_log)
    trace_mod.set_timeline(prev)


def phase(now, name, start, seconds, **attrs):
    now[0] = start
    with trace_mod.span(name, **attrs) as sp:
        now[0] = start + seconds
    return sp


def fill_set_up(now):
    """A process from its start at 0 s to a window that opens at 40 s:
    boot 5 s; two imports of 4 and 7 s with 2 and 5 s of another inside
    each; the runtime's start 3 s; the trainer's spans; holes of 1, 2
    and 0.3 s. ``SETUP`` says what each reader finds."""
    tl = trace_mod.timeline()
    tl.record("proc/boot", start=0.0, end=5.0, loaded="jax,numpy")
    now[0] = 5.0
    with trace_mod.span("proc/import", module="perceiver_tpu.models"):
        phase(now, "proc/import", 6.0, 2.0, module="optax")
        now[0] = 9.0
    tl.record("proc/backend_init", start=10.0, end=13.0, platform="tpu")
    now[0] = 13.0
    with trace_mod.span("proc/import", module="perceiver_tpu.training"):
        phase(now, "proc/import", 14.0, 5.0, module="orbax.checkpoint")
        now[0] = 20.0
    phase(now, "train/construct", 22.0, 0.5)
    phase(now, "train/data_setup", 22.5, 0.1)
    phase(now, "train/io_setup", 22.6, 0.1)
    now[0] = 22.7
    with trace_mod.span("train/build_state"):
        phase(now, "train/model_init", 22.7, 2.0)
        phase(now, "train/restore", 24.7, 1.0)
        now[0] = 25.7
    tl.record("proc/gc", start=23.0, end=23.4, generation=2, collected=9)
    now[0] = 26.0
    with trace_mod.span("train/step", step=1):
        phase(now, "train/input_wait", 26.0, 0.5)
        phase(now, "train/step_load", 26.5, 7.0)
        phase(now, "train/fence", 33.5, 0.5)
        now[0] = 34.0
    phase(now, "train/epoch_end", 34.0, 1.0)
    for i, t in enumerate((35.0, 36.0)):
        now[0] = t
        with trace_mod.span("train/step", step=2 + i):
            phase(now, "train/fence", t, 1.0)
    phase(now, "train/epoch_end", 37.0, 0.5)
    for i, t in enumerate((37.5, 38.5)):
        now[0] = t
        with trace_mod.span("train/step", step=4 + i):
            phase(now, "train/fence", t, 1.0)
    # the hook that opens the window returns after the opening
    phase(now, "train/epoch_end", 39.5, 0.7)


def fill_window(now, long_step=None, first=6):
    """Steps of 1 s from the opening on, ``interval_s`` as the trainer
    writes it; ``long_step`` takes 5 s."""
    t, n = OPENED + 0.2, 0
    while True:
        seconds = 5.0 if first + n == long_step else 1.0
        if t + seconds > OPENED + WINDOW:
            return n
        now[0] = t
        with trace_mod.span("train/step", step=first + n) as sp:
            phase(now, "train/fence", t, seconds)
            sp.attrs["interval_s"] = None if n == 0 else seconds
            sp.attrs["cpu_s"] = 0.01
        t, n = t + seconds, n + 1


def make_run(traced=True, elapsed=WINDOW):
    # perf_counter runs 1000 s ahead of the spans' clock here
    tracer = types.SimpleNamespace(enabled=traced, directory=None,
                                   t0=1039.9999, mono0=39.9999,
                                   mono1=45.0)
    outcome = types.SimpleNamespace(t_open=1000.0 + OPENED,
                                    data={"elapsed_s": elapsed})
    spans = types.SimpleNamespace(items=[
        ("make_batches", 1020.5, 1021.5, {}), ("fit", 1022.0, 1085.0, {})])
    return types.SimpleNamespace(trace=object() if traced else None,
                                 tracer=tracer, outcome=outcome, spans=spans)


@pytest.mark.parametrize("name", sorted(SETUP))
def test_set_up_readers_on_a_filled_timeline(name, clock):
    fill_set_up(clock)
    fill_window(clock)
    assert reader(name).read(make_run()) == pytest.approx(SETUP[name])


def test_the_set_up_parts_add_up_to_the_run_s_set_up(clock, capsys):
    fill_set_up(clock)
    run = make_run()
    parts = {name: reader(name).read(run) for name in SETUP}
    # to the second: what is left is the three small spans around fit()
    assert sum(parts.values()) == pytest.approx(OPENED - OTHER)
    assert abs(sum(parts.values()) - OPENED) < 1.0
    out = capsys.readouterr().out
    assert ("setup.uncovered_s 3.300 s of 40.000 s from the process's "
            "start to the window's opening; import 19.000 + state_build "
            "3.000 + step_load 7.000 + first_steps 7.000 + uncovered 3.300 "
            "= 39.300 s (other spans 0.700 s)") in out
    assert "the ring holds 28 spans, dropped 0" in out
    # by module, self seconds, largest first; what the caller had loaded
    assert ("self seconds by module: (boot) 5.000, orbax.checkpoint 5.000, "
            "(backend_init) 3.000, optax 2.000, perceiver_tpu.models 2.000, "
            "perceiver_tpu.training 2.000; loaded before the program's "
            "first line: jax,numpy") in out
    # the holes by their neighbours, the longest first, and the
    # benchmark's own span beside the one it overlaps
    assert ("longest holes: 2.000 s at t+20.0 after "
            "proc/import[perceiver_tpu.training] before train/construct; "
            "1.000 s at t+9.0 after proc/import[perceiver_tpu.models] "
            "before proc/backend_init; 0.300 s at t+25.7 after "
            "train/build_state before train/step") in out
    assert ("own spans: make_batches 1.000 s (1.000 uncovered), fit "
            "63.000 s (0.300 uncovered)") in out


def test_stall_pct_reads_zero_on_even_intervals(clock, capsys):
    fill_set_up(clock)
    steps = fill_window(clock)
    assert steps == 39
    assert reader("train.stall_pct").read(make_run()) == 0.0
    out = capsys.readouterr().out
    assert f"over {steps - 4} steps of the whole window" in out
    assert "slow steps by the program's rule: none" in out


def test_stall_pct_reads_the_planted_share_of_one_long_step(clock, capsys):
    fill_set_up(clock)
    steps = fill_window(clock, long_step=20)
    events_mod.emit("slow_step", step=20, interval_s=5.0, median_s=1.0,
                    phase="train/log_console")
    events_mod.emit("slow_step", step=3, interval_s=9.0, median_s=1.0,
                    phase="train/fence")        # before the window
    n = steps - 4                               # the first four left out
    mean = (n - 1 + 5.0) / n
    assert reader("train.stall_pct").read(make_run()) == pytest.approx(
        100.0 * (mean - 1.0) / mean)
    # 4 s lost of the 35 the counted steps took
    assert 100.0 * (mean - 1.0) / mean == pytest.approx(100.0 * 4 / 35)
    out = capsys.readouterr().out
    assert ("slow steps by the program's rule: step 20 5000.0 ms "
            "(median 1000.0) train/log_console") in out
    assert "step 3 " not in out


def test_gc_pct_reads_the_window_s_collections(clock, capsys):
    fill_set_up(clock)              # its collection came before the window
    fill_window(clock)
    run = make_run()
    assert reader("train.gc_pct").read(run) == 0.0
    tl = trace_mod.timeline()
    tl.record("proc/gc", start=50.0, end=50.2, generation=1, collected=3)
    tl.record("proc/gc", start=60.0, end=60.3, generation=2, collected=8)
    tl.record("proc/gc", start=90.0, end=90.5, generation=2, collected=1)
    assert reader("train.gc_pct").read(run) == pytest.approx(
        100.0 * 0.5 / WINDOW)
    assert "2 collections over 1 ms in the window, 0.5000 s, the longest " \
           "300.0 ms (generation 2)" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_readers_give_none_and_say_so_when_the_ring_dropped(name, clock,
                                                            capsys):
    trace_mod.set_timeline(trace_mod.Timeline(capacity=40))
    fill_set_up(clock)
    fill_window(clock)
    assert trace_mod.timeline().dropped > 0
    assert reader(name).read(make_run()) is None
    assert f"{name}: not reported, the program's span ring dropped" \
        in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_readers_give_none_without_a_trace_or_the_process_s_spans(
        name, clock, monkeypatch):
    fill_set_up(clock)
    fill_window(clock)
    assert reader(name).read(make_run(traced=False)) is None
    # the parent of the PR that added the spans: the vocabulary lacks
    # them, and a reader raises nothing
    monkeypatch.delattr(trace_mod, "PROCESS_PHASES")
    assert reader(name).read(make_run()) is None


def test_window_readers_give_none_on_a_ring_without_the_pace(clock):
    """Steps without ``interval_s`` (a program before the pace), or too
    few of them: nothing to read."""
    fill_set_up(clock)
    clock[0] = 41.0
    for i in range(10):
        with trace_mod.span("train/step", step=6 + i):
            clock[0] += 1.0
    assert reader("train.stall_pct").read(make_run()) is None
    # a timeline that begins with no boot (tracing switched on late)
    trace_mod.set_timeline(trace_mod.Timeline())
    fill_window(clock)
    for name in ("setup.import_s", "setup.first_steps_s",
                 "setup.uncovered_s"):
        assert reader(name).read(make_run()) is None
