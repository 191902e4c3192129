"""Device time by scope and the trainer's phases
(``benchmarks/scope_times.py``) and the ten per-layer readers over it, on
the hand-built ``benchmarks/fixtures/scoped_trace.textproto`` (its header
says what it holds) and on a span ring filled under a pinned clock."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness, scope_times as st  # noqa: E402
from benchmarks import trace_reduce as tr  # noqa: E402
from perceiver_tpu.obs import trace as trace_mod  # noqa: E402

FIXTURES = os.path.join(ROOT, "benchmarks", "fixtures")
SCOPED = os.path.join(FIXTURES, "scoped_trace.textproto")
UNSCOPED = os.path.join(FIXTURES, "small_trace.textproto")
US = 1e-6
DEVICE_SHARES = {"model.attn_core_pct": 28.0, "model.dense_pct": 48.0,
                 "model.loss_pct": 8.0, "train.optimizer_pct": 8.0,
                 "model.unscoped_pct": 8.0, "model.remat_pct": 24.0}
SPAN_METRICS = {"train.input_wait_pct": 2.0, "train.host_ms_per_step": 6.0,
                "setup.state_build_s": 3.0, "setup.step_load_s": 7.0}


def reader(name):
    return harness.load_module(
        os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py"),
        "test_metric_" + name.replace(".", "_"))


def trace_dir(tmp_path, fixture):
    """``fixture`` as the ``.xplane.pb`` a profiler run leaves."""
    from jax.profiler import ProfileData

    d = tmp_path / "trace" / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    with open(fixture) as f:
        (d / "host.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(f.read()))
    return str(tmp_path / "trace")


def make_run(directory, mono0=100.0, mono1=110.0, traced=True):
    tracer = types.SimpleNamespace(enabled=traced, directory=directory,
                                   mono0=mono0, mono1=mono1)
    return types.SimpleNamespace(trace=object() if traced else None,
                                 tracer=tracer)


@pytest.fixture
def clock(monkeypatch):
    """The spans' clock under the test's hand, and a ring of its own."""
    now = [0.0]
    monkeypatch.setattr(trace_mod, "_now", lambda: now[0])
    prev = trace_mod.set_timeline(trace_mod.Timeline())
    yield now
    trace_mod.set_timeline(prev)


def phase(now, name, start, seconds, **attrs):
    now[0] = start
    with trace_mod.span(name, **attrs):
        now[0] = start + seconds


def fill_timeline(now):
    """Set-up before a window of 100-110 s, then three steps inside it
    whose host work is 5, 6 and 202 ms (a starved pull), and one step
    after it: 29 spans."""
    now[0] = 10.0
    with trace_mod.span("train/build_state"):
        phase(now, "train/model_init", 10.0, 2.0)
        phase(now, "train/restore", 12.0, 1.0)
        now[0] = 13.0
    phase(now, "train/step_load", 20.0, 4.0)
    phase(now, "train/step_load", 30.0, 3.0)
    for i, (t, wait, log) in enumerate(
            [(101.0, 0.001, 0.001), (103.0, 0.002, 0.001),
             (105.0, 0.197, 0.002), (111.0, 0.5, 0.5)]):
        now[0] = t
        with trace_mod.span("train/step", step=i + 1):
            phase(now, "train/input_wait", t, wait, queue_depth=2)
            phase(now, "train/shard", t + 0.3, 0.001)
            phase(now, "train/dispatch", t + 0.4, 0.002)
            phase(now, "train/fence", t + 0.5, 0.7)
            phase(now, "train/log", t + 1.3, log)
            now[0] = t + 1.4


# --- the trace's protobuf, decoded -------------------------------------------


def test_decoder_reads_ops_and_their_metadata_name_stacks():
    (plane,) = st.load_device_ops(SCOPED)
    assert plane.plane == "/device:TPU:0" and len(plane.events) == 8
    assert plane.events[1] == (500000, 1000000, 2)
    assert plane.names[7].startswith("%fusion.f = bf16[8,32,2,8]")
    assert plane.op_names[2].endswith("attn_core/bqhd,bkhd->bhqk/dot_general:")
    assert plane.op_names[5] == "jit(train_step)/optimizer/mul:"  # by ref
    assert 8 not in plane.op_names


def test_decoder_agrees_with_the_profiler_s_own_reader():
    (plane,) = st.load_device_ops(SCOPED)
    red = tr.reduce(tr.load(SCOPED), window_ns=10000.0)
    (events,) = red.events.values()
    assert sorted((e.start_ns * 1000, e.duration_ns * 1000)
                  for e in events) == pytest.approx(
        sorted((s, d) for s, d, _ in plane.events))
    times = st.reduce_scopes([plane])
    assert times.busy_s == pytest.approx(red.busy_ns / 1e9)


def test_a_trace_without_a_device_plane_gives_nothing(tmp_path):
    host_only = tmp_path / "host.textproto"
    host_only.write_text('planes { id: 2 name: "/host:CPU" }\n')
    assert st.load_device_ops(str(host_only)) == []
    assert st.reduce_scopes([]) is None


@pytest.mark.parametrize("op_name,scopes,cls,which", [
    ("jit(train_step)/jvp()/while/body/closed_call/latent_self_attn/while/"
     "body/closed_call/attn_core/dot_general:",
     ["latent_self_attn", "attn_core"], "attn_core", "forward"),
    ("jit(train_step)/transpose(jvp(enc_cross_attn))/attn_proj/dot_general:",
     ["enc_cross_attn", "attn_proj"], "dense", "backward"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/enc_cross_attn/mlp/erf:",
     ["enc_cross_attn", "mlp"], "dense", "remat"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "latent_self_attn/add_any:", ["latent_self_attn"], "dense", "backward"),
    ("jit(train_step)/jvp(output_adapter)/dot_general:",
     ["output_adapter"], "loss", "forward"),
    ("jit(train_step)/jvp(dec_cross_attn)/attn_core/reduce_max:",
     ["dec_cross_attn", "attn_core"], "attn_core", "forward"),
    ("jit(train_step)/optimizer/mul:", ["optimizer"], "optimizer",
     "forward"),
    ("jit(train_step)/jit(loss)/slice:", [], "unscoped", "forward"),
    ("jit(train_step)/jvp(jit(mlp))/add:", [], "unscoped", "forward"),
    ("jit(train_step)/jvp()/lossy/mlp_like/add:", [], "unscoped", "forward"),
    ("", [], "unscoped", "forward"),
])
def test_name_stack_to_scopes_class_and_pass(op_name, scopes, cls, which):
    assert st.scopes_of(op_name) == scopes
    assert st.classify(op_name) == cls
    assert st.pass_of(op_name) == which


def test_the_reader_s_vocabulary_is_the_program_s():
    assert set(st.SCOPE_CLASS) == set(trace_mod.DEVICE_SCOPES)
    assert set(st.SCOPE_CLASS.values()) | {"unscoped"} == set(st.CLASSES)
    assert set(st.LAYERS) <= set(trace_mod.DEVICE_SCOPES)
    assert set(st.HOST_PHASES) < set(trace_mod.TRAIN_PHASES)
    assert not set(st.HOST_PHASES) & set(trace_mod.ENCLOSING_SPANS)


def test_self_time_goes_to_one_class_and_the_classes_partition_busy():
    times = st.reduce_scopes(st.load_device_ops(SCOPED))
    assert times.devices == 1 and times.scoped
    assert times.busy_s == pytest.approx(6.25 * US)
    assert times.by_class == pytest.approx({
        "attn_core": 1.75 * US, "dense": 3.0 * US, "loss": 0.5 * US,
        "optimizer": 0.5 * US, "unscoped": 0.5 * US})
    assert sum(times.by_class.values()) == pytest.approx(times.busy_s)
    assert times.by_pass == pytest.approx({
        "forward": 3.5 * US, "backward": 1.25 * US, "remat": 1.5 * US})
    # the while's self time is its layer's; its body's is the body's
    assert times.by_layer_pass[("latent_self_attn", "forward")] == \
        pytest.approx(2.5 * US)
    assert times.by_layer_pass[("latent_self_attn", "remat")] == \
        pytest.approx(1.5 * US)
    assert [k.split()[0] for k, _ in times.top_unscoped] == \
        ["copy.e", "fusion.g"]


def test_idle_gap_goes_to_the_leaf_phase_not_to_what_it_holds():
    red = tr.reduce(tr.load(SCOPED), window_ns=10000.0)
    assert red.gaps == [("train/dispatch", pytest.approx(2750.0))]


# --- the readers -------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(DEVICE_SHARES))
def test_device_share_readers_on_the_scoped_trace(name, tmp_path):
    run = make_run(trace_dir(tmp_path, SCOPED))
    assert reader(name).read(run) == pytest.approx(DEVICE_SHARES[name])


def test_layer_shares_and_unscoped_add_up_to_the_busy_time(tmp_path):
    run = make_run(trace_dir(tmp_path, SCOPED))
    parts = [reader(n).read(run) for n in DEVICE_SHARES
             if n != "model.remat_pct"]
    assert sum(parts) == pytest.approx(100.0)


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_readers_on_a_filled_timeline(name, clock, tmp_path):
    fill_timeline(clock)
    run = make_run(str(tmp_path))
    assert reader(name).read(run) == pytest.approx(SPAN_METRICS[name])


@pytest.mark.parametrize("name", sorted({**DEVICE_SHARES, **SPAN_METRICS}))
def test_every_reader_gives_none_without_a_trace(name, clock, tmp_path):
    fill_timeline(clock)
    run = make_run(str(tmp_path), traced=False)
    assert reader(name).read(run) is None


@pytest.mark.parametrize("name", sorted(DEVICE_SHARES))
def test_device_readers_give_none_where_no_operation_is_scoped(
        name, tmp_path):
    # the parent of the PR that added the scopes; and a directory with
    # no trace file at all (a rehearsal on the CPU holds no device plane)
    run = make_run(trace_dir(tmp_path, UNSCOPED))
    assert reader(name).read(run) is None
    assert reader(name).read(make_run(str(tmp_path / "none"))) is None


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_readers_give_none_for_a_program_without_the_timeline(
        name, monkeypatch, tmp_path):
    monkeypatch.delattr(trace_mod, "timeline")
    assert reader(name).read(make_run(str(tmp_path))) is None


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_readers_give_none_and_say_so_when_the_ring_dropped(
        name, clock, tmp_path, capsys):
    trace_mod.set_timeline(trace_mod.Timeline(capacity=12))
    fill_timeline(clock)    # set-up and the first two steps are gone
    assert trace_mod.timeline().dropped > 0
    assert reader(name).read(make_run(str(tmp_path))) is None
    assert f"{name}: not reported" in capsys.readouterr().out


def test_window_readers_keep_reading_after_drops_older_than_the_window(
        clock, tmp_path):
    trace_mod.set_timeline(trace_mod.Timeline(capacity=26))
    fill_timeline(clock)    # only build_state and its two were overwritten
    run = make_run(str(tmp_path))
    assert trace_mod.timeline().dropped == 3
    assert reader("train.host_ms_per_step").read(run) == pytest.approx(6.0)
    assert reader("setup.state_build_s").read(run) is None
