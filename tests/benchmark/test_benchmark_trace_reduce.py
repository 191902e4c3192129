"""The trace reduction on the small hand-built trace in
``benchmarks/fixtures`` (its header says what it holds)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import trace_reduce as tr  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "small_trace.textproto")


@pytest.fixture(scope="module")
def red():
    return tr.reduce(tr.load(FIXTURE), window_ns=10000.0)


def test_busy_union_and_idle_share(red):
    assert red.devices == 1
    assert red.busy_ns == pytest.approx(5500.0)
    assert red.idle_share == pytest.approx(0.45)


def test_self_time_per_operation(red):
    by = {tr.short_name(k).split()[0]: v for k, v in red.op_self_ns.items()}
    assert by == pytest.approx({"while.1": 1500.0, "fusion.a": 2000.0,
                                "fn.1": 1500.0, "copy.b": 500.0})
    calls = {tr.short_name(k).split()[0]: v for k, v in red.op_calls.items()}
    assert calls["fusion.a"] == 2


def test_kernel_time_by_match(red):
    seconds, calls = tr.kernel_seconds(red, ("tpu_custom_call",))
    assert (seconds, calls) == (pytest.approx(1.5e-6), 1)
    assert tr.kernel_seconds(red, ("no_such_kernel",)) == (0.0, 0)


def test_longest_gaps_and_what_the_host_did(red):
    assert red.gaps == [("np.asarray(jax.Array)", pytest.approx(2000.0)),
                        ("bench/submit", pytest.approx(1000.0))]


def test_breakdown_shape(red):
    b = tr.breakdown(red)
    assert b["device_ops"][0] == ["fusion.a bf16[8,16] fusion",
                                  pytest.approx(2e-6)]
    assert len(b["device_ops"]) == 4 and len(b["idle_gaps"]) == 2
    assert all(isinstance(n, str) and isinstance(s, float)
               for n, s in b["device_ops"] + b["idle_gaps"])


def test_window_defaults_to_the_device_events_span():
    red = tr.reduce(tr.load(FIXTURE))
    assert red.window_ns == pytest.approx(8500.0)


def test_short_name_keeps_instruction_shape_and_opcode():
    assert tr.short_name(
        "%m.1 = (f32[2,3]{1,0:T(8,128)}, f32[2]{0}) fusion(f32[2]{0} %x)"
    ) == "m.1 (f32[2,3], f32[2]) fusion"
    assert tr.short_name("jit_step(1)") == "jit_step(1)"


def test_union_handles_overlap_and_containment():
    ev = [tr.Event("a", 0, 10), tr.Event("b", 5, 12), tr.Event("c", 2, 3),
          tr.Event("d", 20, 21)]
    covered, gaps = tr.union_ns(ev)
    assert covered == 13 and gaps == [(12, 20)]
