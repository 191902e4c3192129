"""Tests for the observability plane (perceiver_tpu/obs/).

Unit coverage for tracing, the event log, the exposition
parser/aggregator, the HTTP endpoint, and training telemetry; plus two
integration gates — ``scripts/obs_check.py --fast`` as a tier-1
subprocess (the check.py pattern) and the real-socket fleet proof that
a request whose replica is SIGKILLed mid-flight still yields ONE trace
with the failed hop, the retry, and the sibling's spans (slow).
"""

import json
import os
import subprocess
import sys
import threading
import types
import urllib.request

import pytest

from perceiver_tpu.obs import events as events_mod
from perceiver_tpu.obs import trace as trace_mod
from perceiver_tpu.obs.aggregate import merge_expositions
from perceiver_tpu.obs.events import EventLog, validate_event
from perceiver_tpu.obs import promparse
from perceiver_tpu.obs.server import ObsServer
from perceiver_tpu.obs.telemetry import Telemetry, install_signal_profiler
from perceiver_tpu.obs.trace import SpanCollector, TraceBuffer
from perceiver_tpu.serving.metrics import (
    MetricsRegistry,
    escape_label_value,
    unescape_label_value,
)

# --- tracing -----------------------------------------------------------------


def test_trace_phase_vocabulary_is_closed():
    ctx = trace_mod.start_trace(sink=SpanCollector())
    with pytest.raises(ValueError, match="unknown trace phase"):
        ctx.record("warmup")


def test_trace_span_shape_and_duration():
    sink = SpanCollector()
    ctx = trace_mod.start_trace(origin="router", sink=sink)
    span = ctx.record("dispatch", duration_s=0.5, bucket="b4_s16")
    assert span["trace_id"] == ctx.trace_id
    assert span["phase"] == "dispatch"
    assert span["duration_s"] == pytest.approx(0.5)
    assert span["pid"] == os.getpid()
    assert span["origin"] == "router"
    assert span["attrs"] == {"bucket": "b4_s16"}
    assert sink.spans == [span]


def test_trace_buffer_lru_eviction_and_span_bound():
    buf = TraceBuffer(max_traces=2, max_spans_per_trace=3)
    for tid in ("t0", "t1", "t2"):
        buf.add(tid, {"phase": "dispatch"})
    assert buf.get("t0") is None  # LRU-evicted
    assert set(buf.trace_ids()) == {"t1", "t2"}
    for _ in range(5):
        buf.add("t1", {"phase": "dispatch"})
    assert len(buf.get("t1")) == 3  # bounded per trace
    assert buf.dropped_spans == 3
    buf.get("t1")


def test_trace_wire_roundtrip_and_absorb_retags():
    parent_sink = SpanCollector()
    parent = trace_mod.start_trace(origin="router", sink=parent_sink)
    # replica side: rebuild from the RPC envelope, collect locally
    collector = SpanCollector()
    remote = trace_mod.from_wire(parent.wire(), sink=collector,
                                 origin="replica")
    assert remote.trace_id == parent.trace_id
    remote.record("queue_wait", duration_s=0.01)
    remote.record("device", duration_s=0.02)
    # router side: absorb the reply's spans, tagged with the replica id
    parent.absorb(collector.spans, replica="r1")
    absorbed = parent_sink.spans
    assert [s["phase"] for s in absorbed] == ["queue_wait", "device"]
    assert all(s["trace_id"] == parent.trace_id for s in absorbed)
    assert all(s["attrs"]["replica"] == "r1" for s in absorbed)
    # the replica's own copies were not mutated by the tagging
    assert "replica" not in (collector.spans[0].get("attrs") or {})


def test_trace_disabled_short_circuits():
    try:
        trace_mod.set_enabled(False)
        assert trace_mod.start_trace() is None
        assert trace_mod.from_wire({"trace_id": "abc"}) is None
    finally:
        trace_mod.set_enabled(True)


def test_trace_attach_region_records_into_all_members():
    sinks = [SpanCollector(), SpanCollector()]
    ctxs = [trace_mod.start_trace(sink=s) for s in sinks]
    with trace_mod.attach(ctxs + [None]):  # None members are dropped
        with trace_mod.region("pad_or_pack", bucket="b4"):
            pass
    for sink, ctx in zip(sinks, ctxs):
        (span,) = sink.spans
        assert span["phase"] == "pad_or_pack"
        assert span["trace_id"] == ctx.trace_id
        assert span["attrs"] == {"bucket": "b4"}
    # outside the attach block the region is a no-op
    with trace_mod.region("dispatch"):
        pass
    assert all(len(s.spans) == 1 for s in sinks)


def test_default_buffer_swap_restores():
    mine = TraceBuffer(max_traces=4)
    prev = trace_mod.set_default_buffer(mine)
    try:
        ctx = trace_mod.start_trace()
        ctx.record("submit", duration_s=0.0)
        assert mine.get(ctx.trace_id)
    finally:
        assert trace_mod.set_default_buffer(prev) is mine


# --- process-level spans: the timeline ---------------------------------------


@pytest.fixture
def timeline():
    tl = trace_mod.Timeline(capacity=8)
    prev = trace_mod.set_timeline(tl)
    yield tl
    trace_mod.set_timeline(prev)


def test_span_shape_parent_and_step(timeline):
    with trace_mod.span("train/step", step=7) as step:
        with trace_mod.span("train/input_wait", queue_depth=2) as wait:
            pass
        with trace_mod.span("train/dispatch"):
            pass
    with trace_mod.span("train/eval"):
        pass
    wait_s, dispatch, step_s, ev = timeline.spans()  # in order of closing
    assert set(wait_s) == {"id", "parent", "name", "start", "end",
                           "duration_s", "step", "thread", "attrs"}
    assert wait_s["name"] == "train/input_wait"
    assert wait_s["parent"] == dispatch["parent"] == step_s["id"]
    assert wait_s["step"] == dispatch["step"] == step_s["step"] == 7
    assert wait_s["attrs"] == {"queue_depth": 2} and step_s["attrs"] == {}
    assert step_s["parent"] is None and ev["parent"] is None
    assert ev["step"] is None
    assert step_s["start"] <= wait_s["start"] <= wait_s["end"] \
        <= dispatch["start"] <= dispatch["end"] <= step_s["end"]
    assert wait_s["duration_s"] == wait_s["end"] - wait_s["start"]
    assert wait.seconds == wait_s["duration_s"]
    assert step.seconds >= wait.seconds
    assert wait_s["thread"] == threading.get_ident()
    assert [s["name"] for s in timeline.spans("train/eval")] == ["train/eval"]
    assert timeline.spans(since=ev["start"]) == [ev]
    assert ev not in timeline.spans(until=step_s["end"])


def test_span_vocabularies_are_closed():
    with pytest.raises(ValueError, match="unknown span name"):
        trace_mod.span("train/warmup")
    with pytest.raises(ValueError, match="unknown device scope"):
        trace_mod.device_scope("attention")
    assert set(trace_mod.ENCLOSING_SPANS + trace_mod.TILED_PHASES) < set(
        trace_mod.TRAIN_PHASES)
    assert not set(trace_mod.TRAIN_PHASES) & set(trace_mod.PHASES)
    assert not set(trace_mod.PROCESS_PHASES) & set(
        trace_mod.TRAIN_PHASES + trace_mod.PHASES)
    assert all(n.startswith("proc/") for n in trace_mod.PROCESS_PHASES)
    assert len(set(trace_mod.DEVICE_SCOPES)) == len(trace_mod.DEVICE_SCOPES)
    with trace_mod.span("decode_step"):   # request phases are span names too
        pass


def test_timeline_ring_is_bounded_and_counts_what_it_drops(timeline):
    for i in range(11):
        with trace_mod.span("train/dispatch", step=i):
            pass
    assert len(timeline) == 8 and timeline.dropped == 3
    assert [s["step"] for s in timeline.spans()] == list(range(3, 11))
    with pytest.raises(ValueError):
        trace_mod.Timeline(capacity=0)


def test_cancelled_span_stays_out_of_the_ring(timeline):
    with trace_mod.span("train/step", step=1) as step:
        with trace_mod.span("train/input_wait"):
            pass
        step.cancel()
    assert [s["name"] for s in timeline.spans()] == ["train/input_wait"]


def test_record_writes_a_span_after_the_fact(timeline):
    """``Timeline.record``: a span that was over before anybody could
    open it lands in the ring when it is written (after what closed
    before, before what closes later), under the span open on this
    thread, with the bounds it was given; ring only."""
    with trace_mod.span("train/dispatch", step=1):
        pass
    with trace_mod.span("train/step", step=2) as step:
        with trace_mod.span("train/fence") as fence:
            sid = timeline.record("proc/gc", start=5.0, end=5.25,
                                  generation=2, collected=7)
    boot = timeline.record("proc/boot", start=1.0, end=3.0)
    names = [s["name"] for s in timeline.spans()]
    assert names == ["train/dispatch", "proc/gc", "train/fence",
                     "train/step", "proc/boot"]       # the order of writing
    gc_s, boot_s = timeline.spans("proc/gc")[0], timeline.spans()[-1]
    assert (gc_s["id"], gc_s["parent"], gc_s["step"]) == (sid, fence.id, 2)
    assert (gc_s["start"], gc_s["end"], gc_s["duration_s"]) == (5.0, 5.25,
                                                                0.25)
    assert gc_s["attrs"] == {"generation": 2, "collected": 7}
    assert gc_s["thread"] == threading.get_ident()
    assert (boot_s["id"], boot_s["parent"], boot_s["step"]) == (boot, None,
                                                                None)
    # it is no leaf of the step: the step's children are its own spans
    assert [n for n, _ in step.children] == ["train/fence"]
    assert timeline.dropped == 0
    for i in range(8):          # a full ring drops the oldest, recorded or not
        timeline.record("proc/gc", start=float(i), end=i + 0.5)
    assert timeline.dropped == 5 and len(timeline) == 8
    with pytest.raises(ValueError, match="unknown span name"):
        timeline.record("proc/nap", start=0.0, end=1.0)
    try:
        trace_mod.set_enabled(False)
        assert timeline.record("proc/gc", start=0.0, end=1.0) is None
    finally:
        trace_mod.set_enabled(True)
    assert timeline.dropped == 5


def test_enclosing_spans_keep_their_leaves_seconds(timeline):
    with trace_mod.span("train/step", step=3) as step:
        with trace_mod.span("train/input_wait") as wait:
            pass
        with trace_mod.span("train/log") as log:
            with trace_mod.span("train/log_console") as console:
                pass
            with trace_mod.span("train/log_telemetry") as tele:
                pass
    # the step's leaves in order of closing, train/log's handed up
    assert step.children == [("train/input_wait", wait.seconds),
                             ("train/log_console", console.seconds),
                             ("train/log_telemetry", tele.seconds)]
    assert log.children == step.children[1:] and wait.children is None


def test_gc_spans_for_a_long_collection_and_not_for_a_short_one(timeline):
    import gc

    from perceiver_tpu.obs.process import GcSpans

    spans = GcSpans().install()
    try:
        assert spans in gc.callbacks
        gc.collect()
        timeline.__init__(capacity=8)        # what the first sweep wrote
        gc.collect(0)                        # a young generation: no time
        assert timeline.spans("proc/gc") == []
        short = spans.seconds
        assert 0.0 < short < 1.0             # summed all the same
        gc.disable()                         # no sweep while it is made
        try:
            cycles = []
            for _ in range(300_000):         # a large cycle of garbage
                a, b = [], []
                a.append(b), b.append(a)
                cycles.append(a)
            del cycles, a, b
            with trace_mod.span("train/fence") as leaf:
                gc.collect()
        finally:
            gc.enable()
        (written,) = timeline.spans("proc/gc")
        assert written["duration_s"] > 1e-3
        assert written["attrs"]["generation"] == 2
        assert written["attrs"]["collected"] >= 600_000
        assert written["parent"] == leaf.id  # inside the leaf that was open
        assert leaf.start <= written["start"] <= written["end"] <= leaf.end
        assert spans.seconds >= short + written["duration_s"]
    finally:
        spans.uninstall()
    assert spans not in gc.callbacks
    spans.uninstall()                        # twice is no error


def test_import_spans_nest_and_skip_what_was_loaded(timeline, tmp_path,
                                                    monkeypatch):
    """``import_span`` at an import site: ``proc/import`` spans that
    nest where one import pulls another, nothing written for a module
    that was loaded already, nothing installed anywhere."""
    from perceiver_tpu.obs import process

    (tmp_path / "heavy_outer.py").write_text(
        "import time\n"
        "from perceiver_tpu.obs.process import import_span\n"
        "with import_span('heavy_inner'):\n"
        "    import heavy_inner\n"
        "time.sleep(0.003)\n")
    (tmp_path / "heavy_inner.py").write_text("import time\n"
                                             "time.sleep(0.003)\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    before = list(sys.meta_path)
    try:
        with process.import_span("heavy_outer"):
            import heavy_outer  # noqa: F401
        with process.import_span("heavy_outer"):     # loaded: no time
            import heavy_outer  # noqa: F401,F811
    finally:
        for name in ("heavy_outer", "heavy_inner"):
            sys.modules.pop(name, None)
    assert sys.meta_path == before
    inner, outer = timeline.spans()
    assert [inner["attrs"], outer["attrs"]] == [
        {"module": "heavy_inner"}, {"module": "heavy_outer"}]
    assert inner["name"] == outer["name"] == "proc/import"
    assert inner["parent"] == outer["id"] and outer["parent"] is None
    assert outer["duration_s"] - inner["duration_s"] >= 0.003
    # the boot is written once a process
    monkeypatch.setattr(process, "_begun", False)
    process.begin()
    process.begin()
    (boot,) = timeline.spans("proc/boot")
    assert boot["duration_s"] > 0 and "jax" in boot["attrs"]["loaded"]
    try:
        trace_mod.set_enabled(False)
        with process.import_span("json"):            # off: a no-op
            import json  # noqa: F401
    finally:
        trace_mod.set_enabled(True)
    assert len(timeline.spans()) == 3


def test_process_start_is_the_kernel_s(timeline):
    import time

    from perceiver_tpu.obs.process import process_start

    started = process_start()
    assert started is not None
    # the process began before this test did, and not a day ago
    age = time.monotonic() - started
    assert 0.0 < age < 86400.0
    # the kernel's own word for it, to the tick
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    boot_age = time.clock_gettime(time.CLOCK_BOOTTIME) \
        - ticks / os.sysconf("SC_CLK_TCK")
    assert age == pytest.approx(boot_age, abs=0.05)


def test_spans_nest_per_thread(timeline):
    seen = {}

    def worker():
        with trace_mod.span("train/eval") as sp:
            seen["parent"] = sp.parent

    with trace_mod.span("train/step", step=1):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert seen["parent"] is None    # the other thread's step is not its
    assert {s["name"] for s in timeline.spans()} == {"train/step",
                                                     "train/eval"}


def test_timeline_survives_many_writers():
    import sys as _sys

    tl = trace_mod.Timeline(capacity=64)
    prev = trace_mod.set_timeline(tl)
    old = _sys.getswitchinterval()
    _sys.setswitchinterval(1e-5)
    n_threads, n_spans = 16, 200

    def worker():
        for _ in range(n_spans):
            with trace_mod.span("train/dispatch"):
                pass

    try:
        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        _sys.setswitchinterval(old)
        trace_mod.set_timeline(prev)
    # a lost update would break either count
    assert len(tl) == 64
    assert tl.dropped == n_threads * n_spans - 64
    assert len({s["id"] for s in tl.spans()}) == 64


def test_set_enabled_false_makes_span_a_no_op(timeline):
    try:
        trace_mod.set_enabled(False)
        with trace_mod.span("train/step", step=1) as sp:
            with trace_mod.span("no such name at all"):  # not even checked
                pass
            sp.cancel()
        assert sp.seconds == 0.0
    finally:
        trace_mod.set_enabled(True)
    assert timeline.spans() == [] and timeline.dropped == 0


class _FakeJax:
    """Stands in for the loaded ``jax`` module: records annotations."""

    def __init__(self):
        self.entered, self.open = [], 0
        outer = self

        class TraceAnnotation:
            def __init__(self, name, **attrs):
                self.name, self.attrs = name, attrs

            def __enter__(self):
                outer.open += 1
                outer.entered.append((self.name, self.attrs, outer.open))

            def __exit__(self, *exc):
                outer.open -= 1

        self.profiler = types.SimpleNamespace(TraceAnnotation=TraceAnnotation)


def test_annotations_are_leaves_only_and_carry_step_num(timeline,
                                                        monkeypatch):
    fake = _FakeJax()
    monkeypatch.setitem(sys.modules, "jax", fake)
    with trace_mod.span("train/build_state"):
        with trace_mod.span("train/model_init"):
            pass
    with trace_mod.span("train/step", step=4):
        with trace_mod.span("train/input_wait", queue_depth=0):
            pass
        with trace_mod.span("train/dispatch"):
            pass
    assert [(n, a) for n, a, _ in fake.entered] == [
        ("train/model_init", {}),
        ("train/input_wait", {"step_num": 4, "queue_depth": 0}),
        ("train/dispatch", {"step_num": 4})]
    # never one inside another: each was the only annotation open
    assert all(depth == 1 for _, _, depth in fake.entered)
    assert len(timeline.spans()) == 5   # the enclosing two are in the ring


def test_tracing_does_not_import_jax_for_a_span(timeline, monkeypatch):
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    with trace_mod.span("train/dispatch"):
        pass
    assert "jax" not in sys.modules


def test_region_is_a_span_too(timeline, monkeypatch):
    fake = _FakeJax()
    monkeypatch.setitem(sys.modules, "jax", fake)
    sink = SpanCollector()
    ctx = trace_mod.start_trace(sink=sink)
    with trace_mod.attach([ctx]):
        with trace_mod.region("dispatch", bucket="b4"):
            pass
    (ring,) = timeline.spans()
    (request,) = sink.spans
    assert ring["name"] == request["phase"] == "dispatch"
    assert (ring["start"], ring["end"]) == (request["start"], request["end"])
    assert ring["attrs"] == request["attrs"] == {"bucket": "b4"}
    assert [(n, a) for n, a, _ in fake.entered] == [
        ("dispatch", {"bucket": "b4"})]
    with trace_mod.attach([ctx]):
        with pytest.raises(ValueError, match="unknown trace phase"):
            with trace_mod.region("train/dispatch"):
                pass


# --- event log ---------------------------------------------------------------


def test_event_schema_validation():
    log = EventLog()
    event = log.emit("breaker_transition", bucket="b4_s16",
                     old="closed", new="open")
    validate_event(event)  # envelope + typed fields
    with pytest.raises(ValueError, match="unknown event type"):
        log.emit("reactor_meltdown")
    with pytest.raises(ValueError, match="missing required"):
        log.emit("guard_skip")  # no step
    with pytest.raises(ValueError, match="envelope"):
        validate_event({"type": "guard_skip", "step": 1})


def test_decode_phases_and_stream_events_in_vocabulary():
    """ISSUE 14: the decode plane speaks the closed observability
    vocabulary — per-token trace phases (``decode_step`` spans the
    batched device step, ``token_emit`` each stream's token delivery)
    and stream lifecycle events (``stream_open``/``stream_close``).
    A vocabulary miss would make DecodeEngine's tracing raise on the
    first admitted stream."""
    assert "decode_step" in trace_mod.PHASES
    assert "token_emit" in trace_mod.PHASES
    sink = SpanCollector()
    ctx = trace_mod.start_trace(origin="decode", sink=sink)
    ctx.record("decode_step", duration_s=0.001, live=3)
    ctx.record("token_emit", duration_s=0.0, stream="s1", index=0)
    assert [s["phase"] for s in sink.spans] == ["decode_step",
                                                "token_emit"]

    log = EventLog()
    validate_event(log.emit("stream_open", stream="s1", tenant="default"))
    validate_event(log.emit("stream_close", stream="s1", tokens=12,
                            tenant="default"))
    with pytest.raises(ValueError, match="missing required"):
        log.emit("stream_close", stream="s1", tenant="default")  # tokens
    assert [e["type"] for e in log.events()] == ["stream_open",
                                                 "stream_close"]


def test_prefill_phases_and_scheduler_events_in_vocabulary():
    """ISSUE 17: the unified prefill+decode scheduler speaks the
    closed vocabulary too — ``prefill_chunk`` spans each chunked-
    prefill slice of a prompt, ``stream_admitted`` fires on slot+page
    grant, ``prefill_complete`` when the last chunk lands. A
    vocabulary miss would make chunked prefill raise on the first
    admitted prompt."""
    assert "prefill_chunk" in trace_mod.PHASES
    sink = SpanCollector()
    ctx = trace_mod.start_trace(origin="decode", sink=sink)
    ctx.record("prefill_chunk", duration_s=0.001, stream="s1",
               chunk=8, fed=8)
    assert [s["phase"] for s in sink.spans] == ["prefill_chunk"]

    log = EventLog()
    validate_event(log.emit("stream_admitted", stream="s1", pages=4,
                            tenant="default"))
    validate_event(log.emit("prefill_complete", stream="s1",
                            prompt_tokens=9, chunks=2, tenant="default"))
    with pytest.raises(ValueError, match="missing required"):
        log.emit("prefill_complete", stream="s1",
                 tenant="default")  # counts required
    assert [e["type"] for e in log.events()] == ["stream_admitted",
                                                 "prefill_complete"]


def test_event_log_ring_and_jsonl_mirror(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path)
    log.emit("guard_skip", step=7)
    log.emit("exec_cache", bucket="b4_s16", hit=True)
    assert [e["type"] for e in log.events()] == ["guard_skip",
                                                "exec_cache"]
    assert [e["step"] for e in log.events("guard_skip")] == [7]
    with open(path, encoding="utf-8") as f:
        lines = [json.loads(ln) for ln in f]
    assert lines == log.events()
    for event in lines:
        validate_event(event)


def test_event_log_size_rotation(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path, max_bytes=256, max_backups=2)
    for step in range(64):
        log.emit("guard_skip", step=step)
    assert os.path.exists(path)
    assert os.path.exists(path + ".1")
    assert not os.path.exists(path + ".3")  # backups bounded
    assert os.path.getsize(path) <= 256 + 128  # one line of slack
    # the ring ignores rotation entirely
    assert len(log.events("guard_skip")) == 64


def test_default_log_honors_env_dir(tmp_path, monkeypatch):
    monkeypatch.setenv(events_mod.ENV_VAR, str(tmp_path))
    prev = events_mod.set_default_log(None)
    try:
        events_mod.emit("health_transition", old="READY", new="DEGRADED")
        expected = tmp_path / f"events-{os.getpid()}.jsonl"
        assert events_mod.default_log().path == str(expected)
        (line,) = [json.loads(ln) for ln in expected.read_text()
                   .splitlines()]
        assert line["type"] == "health_transition"
    finally:
        events_mod.set_default_log(prev)


# --- exposition parsing / label escaping / aggregation -----------------------


def test_label_value_escape_roundtrip():
    for value in ('plain', 'back\\slash', 'quo"te', 'new\nline',
                  'all\\"\nthree'):
        assert unescape_label_value(escape_label_value(value)) == value


def test_registry_render_parse_roundtrip_with_hostile_labels():
    registry = MetricsRegistry()
    counter = registry.counter("serving_requests_total", "by outcome")
    hostile = 'he said "no"\nand \\ left'
    counter.labels(outcome=hostile).inc(3)
    families = promparse.parse(registry.render())
    (sample,) = families["serving_requests_total"].samples
    assert sample.labels["outcome"] == hostile
    assert sample.value == 3
    assert promparse.check_exposition(registry.render()) == []


def test_conformance_catches_bad_expositions():
    untyped = "serving_mystery_total 3\n"
    assert any("without a # TYPE" in p
               for p in promparse.check_exposition(untyped))
    non_monotone = (
        "# TYPE serving_latency histogram\n"
        'serving_latency_bucket{le="0.1"} 5\n'
        'serving_latency_bucket{le="1"} 3\n'
        'serving_latency_bucket{le="+Inf"} 3\n'
        "serving_latency_count 3\n"
        "serving_latency_sum 1.0\n")
    assert any("not cumulative" in p
               for p in promparse.check_exposition(non_monotone))
    no_inf = (
        "# TYPE serving_latency histogram\n"
        'serving_latency_bucket{le="1"} 3\n'
        "serving_latency_count 3\n"
        "serving_latency_sum 1.0\n")
    assert any("+Inf" in p for p in promparse.check_exposition(no_inf))
    inf_mismatch = (
        "# TYPE serving_latency histogram\n"
        'serving_latency_bucket{le="+Inf"} 4\n'
        "serving_latency_count 3\n"
        "serving_latency_sum 1.0\n")
    assert any("_count" in p
               for p in promparse.check_exposition(inf_mismatch))


def test_merge_expositions_injects_replica_label():
    replica = ("# TYPE serving_bucket_dispatch_total counter\n"
               'serving_bucket_dispatch_total{bucket="b4_s16"} 2\n')
    router = ("# TYPE fleet_size gauge\nfleet_size 2\n")
    merged = merge_expositions({"r0": replica, "r1": replica},
                               extra_texts=(router,))
    assert promparse.check_exposition(merged) == []
    families = promparse.parse(merged)
    dispatch = families["serving_bucket_dispatch_total"]
    assert {s.labels["replica"] for s in dispatch.samples} == {"r0", "r1"}
    assert all(s.labels["bucket"] == "b4_s16" for s in dispatch.samples)
    # router series appended verbatim, unlabeled
    (size,) = families["fleet_size"].samples
    assert "replica" not in size.labels


def test_merge_expositions_rejects_kind_mismatch():
    a = "# TYPE serving_queue_depth gauge\nserving_queue_depth 1\n"
    b = "# TYPE serving_queue_depth counter\nserving_queue_depth 1\n"
    with pytest.raises(promparse.ParseError, match="kind mismatch"):
        merge_expositions({"r0": a, "r1": b})


def test_serving_batcher_registry_conforms():
    """The batcher's serving_* registry renders a clean exposition
    after real traffic (histograms populated, counters ticked)."""
    from perceiver_tpu.serving.batcher import MicroBatcher

    registry = MetricsRegistry()
    batcher = MicroBatcher(lambda batch: [{"ok": True} for _ in batch],
                           max_batch=4, max_delay_ms=1.0,
                           metrics=registry)
    try:
        futures = [batcher.submit({"i": i}) for i in range(6)]
        for fut in futures:
            fut.result(timeout=10)
    finally:
        batcher.close()
    assert promparse.check_exposition(registry.render()) == []


def test_decode_page_pool_gauges_conform_and_aggregate():
    """ISSUE 19 satellite: the decode arenas export occupancy gauges
    (``serving_page_pool_used_pages`` / ``_free_pages``, one sample per
    arena — ``target`` always, ``draft`` when speculation is on) that
    render a clean exposition and survive fleet aggregation with the
    replica label injected."""
    import numpy as np

    from perceiver_tpu.serving.decode import DecodeEngine, DecodeGeometry
    from perceiver_tpu.serving.speculative import SpeculativeConfig
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    task = MaskedLanguageModelTask(
        vocab_size=110, max_seq_len=16, num_latents=4,
        num_latent_channels=8, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_encoder_cross_attention_heads=1,
        num_encoder_self_attention_heads=1,
        num_decoder_cross_attention_heads=1, loss_impl="dense")
    geometry = DecodeGeometry(max_streams=2, num_pages=9, page_size=4,
                              max_seq_len=16, max_chunk=4, spec_k=1)
    engine = DecodeEngine(task, geometry=geometry, auto_step=True,
                          speculative=SpeculativeConfig())
    try:
        h = engine.submit(np.array([5, 7, 9], np.int32),
                          max_new_tokens=3)
        assert h.result(120.0).finished == "complete"
        text = engine.metrics.render()
    finally:
        engine.close()
    assert promparse.check_exposition(text) == []
    families = promparse.parse(text)
    for name in ("serving_page_pool_used_pages",
                 "serving_page_pool_free_pages"):
        arenas = {s.labels["arena"] for s in families[name].samples}
        assert arenas == {"target", "draft"}, (name, arenas)
    # the stream drained, so both arenas read fully free
    used = {s.labels["arena"]: s.value
            for s in families["serving_page_pool_used_pages"].samples}
    assert used == {"target": 0.0, "draft": 0.0}
    free = {s.labels["arena"]: s.value
            for s in families["serving_page_pool_free_pages"].samples}
    assert free["target"] == float(geometry.allocatable_pages)
    assert free["draft"] == float(geometry.allocatable_pages)
    # and the per-replica exposition merges through the fleet
    # aggregator with the replica label injected on every arena sample
    merged = merge_expositions({"r0": text, "r1": text})
    assert promparse.check_exposition(merged) == []
    pool = promparse.parse(merged)["serving_page_pool_used_pages"]
    assert {s.labels["replica"] for s in pool.samples} == {"r0", "r1"}
    assert {s.labels["arena"] for s in pool.samples} == {"target",
                                                         "draft"}


def test_fleet_router_registry_conforms():
    from perceiver_tpu.fleet.router import Router

    router = Router(prober_interval_s=None)
    try:
        assert promparse.check_exposition(router.metrics.render()) == []
    finally:
        router.close()


def test_training_telemetry_registry_conforms(tmp_path):
    telemetry = Telemetry(str(tmp_path))
    telemetry.step(1, 2.5, steps_per_sec=4.0, samples_per_sec=128.0)
    telemetry.guard_skip(2)
    telemetry.slow_step(3, stalled_s=0.25, interval_s=0.5, median_s=0.25)
    telemetry.slow_step(4, stalled_s=None, interval_s=0.5, median_s=0.25)
    assert promparse.check_exposition(telemetry.registry.render()) == []
    assert telemetry.registry.get("training_slow_steps_total").value == 1
    assert telemetry.registry.get(
        "training_stall_seconds_total").value == 0.25
    assert [e["step"] for e in telemetry.events("slow_step")] == [3, 4]


# --- HTTP endpoint -----------------------------------------------------------


def _get(url: str):
    req = urllib.request.Request(url)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, resp.read().decode("utf-8"), \
                resp.headers.get("Content-Type", "")
    except urllib.error.HTTPError as e:  # 4xx/5xx still carry a body
        return e.code, e.read().decode("utf-8"), \
            e.headers.get("Content-Type", "")


def test_obs_server_endpoints():
    registry = MetricsRegistry()
    registry.gauge("fleet_size", "replicas").set(2)
    buf = TraceBuffer(max_spans_per_trace=1)
    ctx = trace_mod.start_trace(sink=buf)
    ctx.record("submit", duration_s=0.001)
    ctx.record("dispatch", duration_s=0.001)    # one span over the bound
    tl = trace_mod.Timeline(capacity=1)
    prev_tl = trace_mod.set_timeline(tl)
    for _ in range(3):
        tl.record("proc/gc", start=0.0, end=1.0)  # two overwritten
    healthy = {"flag": True}
    server = ObsServer(
        metrics_fn=registry.render,
        health_fn=lambda: {"ok": healthy["flag"]},
        trace_buffer=buf)
    try:
        status, body, ctype = _get(f"{server.url}/metrics")
        assert status == 200 and "version=0.0.4" in ctype
        assert promparse.check_exposition(body) == []
        # what the buffer refused and the timeline overwrote, one counter
        assert "\nobs_spans_dropped_total 3\n" in body

        status, body, _ = _get(f"{server.url}/healthz")
        assert status == 200 and json.loads(body)["ok"] is True
        healthy["flag"] = False
        status, _, _ = _get(f"{server.url}/healthz")
        assert status == 503

        status, body, _ = _get(f"{server.url}/traces")
        assert status == 200
        assert json.loads(body)["traces"] == [ctx.trace_id]

        status, body, _ = _get(f"{server.url}/traces/{ctx.trace_id}")
        assert status == 200
        payload = json.loads(body)
        assert payload["trace_id"] == ctx.trace_id
        assert [s["phase"] for s in payload["spans"]] == ["submit"]

        status, _, _ = _get(f"{server.url}/traces/nonexistent")
        assert status == 404
        status, _, _ = _get(f"{server.url}/nope")
        assert status == 404
        # no profile_dir configured -> 501, never a crash
        status, body, _ = _get(f"{server.url}/profile?seconds=1")
        assert status == 501 and "profile_dir" in body
    finally:
        server.close()
        trace_mod.set_timeline(prev_tl)


# --- training telemetry ------------------------------------------------------


def test_telemetry_jsonl_and_counters(tmp_path):
    telemetry = Telemetry(str(tmp_path))
    telemetry.step(10, 1.25, steps_delta=5, steps_per_sec=50.0,
                   samples_per_sec=1600.0, exit_entropy=0.31)
    telemetry.step(20, 1.10, steps_delta=10)
    telemetry.guard_skip(21)
    telemetry.guard_rewind(22)
    telemetry.checkpoint_seal(str(tmp_path / "ckpt-20"))
    telemetry.preempt_checkpoint(23)

    with open(tmp_path / "telemetry.jsonl", encoding="utf-8") as f:
        lines = [json.loads(ln) for ln in f]
    for event in lines:
        validate_event(event)
    steps = [e for e in lines if e["type"] == "train_step"]
    assert [e["step"] for e in steps] == [10, 20]
    assert steps[0]["exit_entropy"] == pytest.approx(0.31)  # extras kept

    registry = telemetry.registry
    assert registry.get("training_steps_total").value == 15
    assert registry.get("training_loss").value == pytest.approx(1.10)
    assert registry.get("training_guard_skips_total").value == 1
    assert registry.get("training_guard_rewinds_total").value == 1
    assert registry.get("training_checkpoint_seals_total").value == 1
    assert registry.get("training_preempt_checkpoints_total").value == 1


def test_telemetry_phase_seconds_fields_and_counters(tmp_path):
    telemetry = Telemetry(str(tmp_path))
    first = telemetry.step(1, 2.0, input_wait_s=0.25, host_s=0.004,
                           fence_s=0.7)
    telemetry.step(2, 1.9, input_wait_s=0.0, host_s=0.006, fence_s=0.72)
    bare = telemetry.step(3, 1.8)      # tracing switched off: left out
    assert (first["input_wait_s"], first["host_s"], first["fence_s"]) == \
        (0.25, 0.004, 0.7)
    assert not {"input_wait_s", "host_s", "fence_s"} & set(bare)
    assert "tokens_per_sec" not in first
    registry = telemetry.registry
    assert registry.get("training_input_wait_seconds_total").value == \
        pytest.approx(0.25)
    assert registry.get("training_host_busy_seconds_total").value == \
        pytest.approx(0.010)
    assert registry.get("training_fence_wait_seconds_total").value == \
        pytest.approx(1.42)
    assert registry.get("training_tokens_per_second") is None
    assert promparse.check_exposition(registry.render()) == []


def test_signal_profiler_install_uninstall(tmp_path):
    import signal

    prev_handler = signal.getsignal(signal.SIGUSR1)
    uninstall = install_signal_profiler(str(tmp_path))
    assert callable(uninstall)
    assert signal.getsignal(signal.SIGUSR1) is not prev_handler
    uninstall()
    assert signal.getsignal(signal.SIGUSR1) is prev_handler


def test_signal_profiler_off_main_thread_degrades(tmp_path):
    result = {}

    def worker():
        result["value"] = install_signal_profiler(str(tmp_path))

    t = threading.Thread(target=worker)
    t.start()
    t.join(10)
    assert result["value"] is None  # manual profiling, no crash


# --- overhead budget ---------------------------------------------------------


def test_tracing_overhead_within_pinned_bounds():
    """The hot-path budget the plane promises: a span record is a dict
    build + list append (<100us, ~2us in practice); the disabled
    ``start_trace`` is one global read (<10us, ~0.1us); a process-level
    ``span`` with no capture running stays under 50us."""
    import time

    ctx = trace_mod.start_trace(sink=SpanCollector())
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        ctx.record("dispatch", duration_s=0.0)
    per_span_us = (time.perf_counter() - t0) / n * 1e6
    try:
        trace_mod.set_enabled(False)
        t0 = time.perf_counter()
        for _ in range(n):
            trace_mod.start_trace()
        disabled_us = (time.perf_counter() - t0) / n * 1e6
    finally:
        trace_mod.set_enabled(True)
    assert per_span_us < 100.0, per_span_us
    assert disabled_us < 10.0, disabled_us
    # a process-level span with no capture running: two clock reads, a
    # ring write and an inactive TraceMe (<50us pinned, ~4us in
    # practice); switched off, one global read
    import jax  # noqa: F401  (loaded, so the span builds its annotation)

    prev = trace_mod.set_timeline(trace_mod.Timeline())
    try:
        t0 = time.perf_counter()
        for _ in range(n):
            with trace_mod.span("train/dispatch", queue_depth=2):
                pass
        span_us = (time.perf_counter() - t0) / n * 1e6
        trace_mod.set_enabled(False)
        t0 = time.perf_counter()
        for _ in range(n):
            with trace_mod.span("train/dispatch"):
                pass
        span_off_us = (time.perf_counter() - t0) / n * 1e6
    finally:
        trace_mod.set_enabled(True)
        trace_mod.set_timeline(prev)
    assert span_us < 50.0, span_us
    assert span_off_us < 10.0, span_off_us
    # a whole logged step as the trainer writes it: the enclosing
    # train/step and train/log, eight leaves, the pace's two attrs and
    # its median (<500us pinned, ~60us in practice: under 0.1% of the
    # shortest cell's 115 ms step)
    from perceiver_tpu.training.pace import StepPace

    leaves = ("train/input_wait", "train/shard", "train/dispatch",
              "train/fence")
    writes = ("train/log_console", "train/log_scalars",
              "train/log_telemetry")
    prev = trace_mod.set_timeline(trace_mod.Timeline())
    try:
        pace = StepPace()
        pace.epoch_start()
        t0 = time.perf_counter()
        for i in range(n):
            with trace_mod.span("train/step", step=i) as step:
                pace.step_open()
                for name in leaves:
                    with trace_mod.span(name):
                        pass
                with trace_mod.span("train/log"):
                    for name in writes:
                        with trace_mod.span(name):
                            pass
                    pace.phases_since_line(step)
                pace.step_close(step, queue_depth=2)
        step_us = (time.perf_counter() - t0) / n * 1e6
        assert trace_mod.timeline().dropped == 0
    finally:
        trace_mod.set_timeline(prev)
    assert step_us < 500.0, step_us


# --- integration gates -------------------------------------------------------


def test_obs_check_fast_gate():
    """``scripts/obs_check.py --fast`` as a literal subprocess gate:
    a real 2-replica fleet under traced traffic proves the e2e trace,
    the aggregated exposition, the event log, the zero-compile budget,
    and the overhead bounds — all in one fresh process."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "obs_check.py"),
         "--fast"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"\n{proc.stdout}\n{proc.stderr}"

    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    by_metric = {ln["metric"]: ln for ln in lines}
    for line in lines:
        assert {"metric", "value", "unit", "vs_baseline",
                "detail"} <= set(line)
    assert by_metric["obs_check"]["value"] == 1.0
    checks = [ln for ln in lines if ln["metric"] != "obs_check"]
    assert len(checks) == 5
    assert all(ln["value"] == 1.0 for ln in checks)
    trace_detail = by_metric["obs_trace_complete"]["detail"]
    assert trace_detail["processes"] >= 2
    deltas = by_metric["obs_zero_compiles"]["detail"][
        "post_warmup_compile_deltas"]
    assert deltas and all(d == 0 for d in deltas.values())


def test_fleet_kill_yields_one_trace_with_retry(tmp_path, monkeypatch):
    """ISSUE acceptance: SIGKILL a replica mid-dispatch and prove ONE
    trace — fetched from the live ``/traces/<id>`` socket — carries
    the failed ``rpc_hop``, the ``retry``, the re-``route``, and the
    sibling's server-side spans, across at least two processes."""
    import numpy as np

    from perceiver_tpu.fleet import Fleet
    from perceiver_tpu.serving.errors import ServingError
    from perceiver_tpu.serving.graphs import build_serve_graph
    from perceiver_tpu.tasks import MaskedLanguageModelTask
    from perceiver_tpu.training.checkpoint import ParamsVersionStore

    task_kwargs = dict(
        vocab_size=110, max_seq_len=32, num_latents=4,
        num_latent_channels=8, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_encoder_cross_attention_heads=1,
        num_encoder_self_attention_heads=1,
        num_decoder_cross_attention_heads=1, loss_impl="dense")
    graph = build_serve_graph(MaskedLanguageModelTask(**task_kwargs))
    store = ParamsVersionStore(str(tmp_path / "store"))
    store.publish("v1", graph.init_params(0), set_current=True)
    spec = {"task_class": "MaskedLanguageModelTask",
            "task_kwargs": task_kwargs,
            "batch_buckets": [4], "seq_buckets": [16],
            "store_dir": store.directory, "version": "v1", "seed": 0}
    monkeypatch.setenv("PERCEIVER_EXEC_CACHE",
                       str(tmp_path / "exec_cache"))

    buf = TraceBuffer(max_traces=512)
    prev_buf = trace_mod.set_default_buffer(buf)
    # r0 SIGKILLs itself mid-dispatch on its 3rd request; r1 is the
    # surviving sibling the router must fail over to
    fleet = Fleet(
        spec, str(tmp_path / "fleet"), replicas=2, max_restarts=3,
        dispatch_timeout_s=10.0,
        per_replica_env={"r0": {
            "PERCEIVER_FAULTS": "replica.crash@at=2"}})
    try:
        obs = fleet.start_obs()
        rng = np.random.default_rng(0)
        retried_id = None
        for _ in range(40):
            arrays = {"input_ids": rng.integers(
                          3, 110, (2, 16)).astype(np.int32),
                      "pad_mask": np.zeros((2, 16), bool)}
            try:
                reply = fleet.submit(arrays)
            except ServingError:
                continue  # typed refusal mid-crash — keep driving
            tid = reply.get("trace_id")
            spans = buf.get(tid) or []
            if any(s["phase"] == "retry" for s in spans):
                retried_id = tid
                break
        assert retried_id is not None, "no request ever hit the crash"

        status, body, _ = _get(f"{obs.url}/traces/{retried_id}")
        assert status == 200, (status, body)
        payload = json.loads(body)
        assert payload["trace_id"] == retried_id
        spans = payload["spans"]
        assert all(s["trace_id"] == retried_id for s in spans)

        by_phase = {}
        for s in spans:
            by_phase.setdefault(s["phase"], []).append(s)
        # the failed hop, the backoff, and the re-route are all there
        failed = [s for s in by_phase["rpc_hop"]
                  if (s.get("attrs") or {}).get("ok") is False]
        ok = [s for s in by_phase["rpc_hop"]
              if (s.get("attrs") or {}).get("ok") is True]
        assert failed and ok, by_phase["rpc_hop"]
        assert "retry" in by_phase
        assert len(by_phase["route"]) >= 2  # picked, failed, re-picked
        # the sibling's server-side spans were absorbed into the SAME
        # trace, tagged with the survivor's id, from another process
        survivor = (ok[0].get("attrs") or {})["replica"]
        assert survivor != (failed[0].get("attrs") or {})["replica"]
        for phase in ("queue_wait", "pad_or_pack", "dispatch", "device"):
            assert phase in by_phase, sorted(by_phase)
            tags = [(s.get("attrs") or {}).get("replica")
                    for s in by_phase[phase]]
            assert survivor in tags, (phase, tags)
        assert len({s["pid"] for s in spans}) >= 2
    finally:
        fleet.close()
        trace_mod.set_default_buffer(prev_buf)
