"""The selective scan's Pallas kernels (``ops/pallas_ssm.py``),
interpreted on the CPU: forward and every gradient against the
position-by-position recurrence at float32 and bfloat16 (one slab, the
cell's layout in small, a padded last chunk), decays that underflow, no
more rounding than the einsum form, which of the two forms a call takes
and why, and how often a remat layer launches the forward kernel. The
einsum form's own cases are in ``tests/test_hybrid_lm.py``."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import perceiver_tpu.ops.remat as remat  # noqa: E402
from perceiver_tpu.ops import ssm  # noqa: E402
from perceiver_tpu.ops.policy import Policy  # noqa: E402
from perceiver_tpu.tasks import HybridLMTask  # noqa: E402
from tests.test_hybrid_lm import (  # noqa: E402
    TOY,
    out_and_cotangents_of_ones,
    recurrence,
    rel,
    scan_inputs,
)
from tests.test_looped_lm import kernel_calls  # noqa: E402

FP32 = Policy.fp32()
HYBRID = remat.HYBRID_REMAT_NAMES

# The smallest shapes the pick admits: chunks of 128 positions, a state
# of 128, eight heads a group in whole slabs of 128 lanes.

FUSED = {
    # eight heads of 16 share one slab; two chunks: a carried state
    "one_slab": dict(seq=256, rows=1, heads=8, width=16, groups=1),
    # the cell's layout in small: two heads of 64 a slab, four slabs a
    # group, two groups, two rows
    "four_slabs": dict(seq=256, rows=2, heads=16, width=64, groups=2),
    # a padded last chunk
    "padded_tail": dict(seq=200, rows=1, heads=8, width=16, groups=1),
}


def as_a_tpu(monkeypatch):
    """The pick as a TPU would make it; off the chip the kernels run
    interpreted (``utils/platform.resolve_interpret``)."""
    monkeypatch.setattr(ssm, "_backend", lambda: "tpu")


def fused_inputs(dtype=jnp.float32, dt_scale=0.1, **shape):
    x, dt, a, b, c = scan_inputs(state=128, dt_scale=dt_scale, **shape)
    # values the compute dtype holds, so that every form starts alike
    return (x.astype(dtype), dt, a, b.astype(dtype) / 4, c.astype(dtype) / 4)


def in_float32(args):
    return tuple(v.astype(jnp.float32) for v in args)


def value_and_grads(fn, args, w):
    """``(weighted sum, output, gradients)`` from one jitted program: a
    case costs its compiles, and each side of a comparison is one."""
    def weighted(*a):
        out = fn(*a)
        return (out.astype(jnp.float32) * w).sum(), out

    (value, out), grads = jit_once(jax.value_and_grad(
        weighted, argnums=range(5), has_aux=True))(*args)
    return value, out, grads


def fused_scan(*a):
    return ssm.ssm_scan(*a, chunk_size=128)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", FUSED)
def test_the_fused_scan_is_the_recurrence(case, dtype, monkeypatch):
    """Forward and every gradient (x, dt, A, B, C) against the
    position-by-position recurrence in float32; bfloat16 operands to
    what bfloat16 products allow."""
    as_a_tpu(monkeypatch)
    args = fused_inputs(dtype, **FUSED[case])
    seq = FUSED[case]["seq"]
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    with ssm.scan_paths.counting() as forms:
        _, got, grads = value_and_grads(fused_scan, args, w)
    assert dict(forms) == {
        f"fused[128x2{'+pad' if seq % 128 else ''}]": 1}
    assert got.shape == args[0].shape and got.dtype == dtype
    _, want, want_grads = value_and_grads(recurrence, in_float32(args), w)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert rel(got, want) < tol
    for g, r, v in zip(grads, want_grads, args):
        assert g.shape == v.shape and g.dtype == v.dtype
        assert rel(g, r) < tol


def test_the_fused_scan_underflows_quietly(monkeypatch):
    """As ``test_decays_that_underflow_do_so_quietly``, on the kernels:
    the mask goes in before the ``exp`` there too, forward and
    backward."""
    as_a_tpu(monkeypatch)
    args = fused_inputs(dt_scale=5.0, a_scale=8.0, **FUSED["one_slab"])
    assert float((args[1] * args[2]).min()) < -40
    got, grads = out_and_cotangents_of_ones(fused_scan, args)
    assert all(bool(jnp.isfinite(g).all()) for g in (got, *grads))
    assert rel(got, jit_once(recurrence)(*args)) < 1e-5
    low = (args[0].astype(jnp.bfloat16), args[1], args[2],
           *(v.astype(jnp.bfloat16) for v in args[3:]))
    got, grads = out_and_cotangents_of_ones(fused_scan, low)
    assert got.dtype == jnp.bfloat16
    assert all(bool(jnp.isfinite(g.astype(jnp.float32)).all())
               for g in (got, *grads))


def test_the_fused_scan_rounds_no_more_than_the_einsums(monkeypatch):
    """At bfloat16 the kernels are no further from the float32
    recurrence than the einsum form is: the forward is the same
    arithmetic to the last bit or two; the backward rounds a cotangent
    only where a product takes it as an operand (on the chip the einsum
    form's products round their float32 operands too, which the CPU's
    do not, hence a quarter of room on the gradients)."""
    args = fused_inputs(jnp.bfloat16, **FUSED["four_slabs"])
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    want, _, want_grads = value_and_grads(recurrence, in_float32(args), w)

    def errors(fn):
        got, _, grads = value_and_grads(fn, args, w)
        return [abs(float(got) - float(want)) / abs(float(want))] + [
            float(jnp.linalg.norm((g.astype(jnp.float32) - r).ravel())
                  / jnp.linalg.norm(r.ravel()))
            for g, r in zip(grads, want_grads)]

    chunked = errors(fused_scan)
    as_a_tpu(monkeypatch)
    fused = errors(fused_scan)     # traced anew, as a TPU would
    assert fused[0] <= chunked[0] + 1e-6
    for f, c in zip(fused[1:], chunked[1:]):
        assert f <= 1.25 * c


# (backend, mesh devices, chunk, state, heads a group, head dim)
SCAN_CHOICES = {
    "nemotron_train": (("tpu", 1, 128, 128, 8, 64), ("fused", None)),
    "heads_of_128": (("tpu", 1, 256, 256, 8, 128), ("fused", None)),
    "eight_heads_of_16": (("tpu", 1, 128, 128, 8, 16), ("fused", None)),
    "cpu": (("cpu", 1, 128, 128, 8, 64), ("chunked", "backend")),
    "gpu": (("gpu", 1, 128, 128, 8, 64), ("chunked", "backend")),
    "dp2_tp2_mesh": (("tpu", 4, 128, 128, 8, 64), ("chunked", "mesh")),
    "chunk_of_16": (("tpu", 1, 16, 128, 8, 64), ("chunked", "shape")),
    "row_shorter_than_a_chunk": (("tpu", 1, 40, 128, 8, 64),
                                 ("chunked", "shape")),
    "state_of_16": (("tpu", 1, 128, 16, 8, 64), ("chunked", "shape")),
    "heads_of_48": (("tpu", 1, 128, 128, 8, 48), ("chunked", "shape")),
    "half_a_slab": (("tpu", 1, 128, 128, 1, 64), ("chunked", "shape")),
    "four_heads_a_group": (("tpu", 1, 128, 128, 4, 64),
                           ("chunked", "shape")),
    # the first reason in CHUNKED_REASONS' order wins
    "backend_before_mesh": (("cpu", 4, 16, 16, 2, 8),
                            ("chunked", "backend")),
    "mesh_before_shape": (("tpu", 4, 16, 16, 2, 8), ("chunked", "mesh")),
}


@pytest.mark.parametrize("case", SCAN_CHOICES)
def test_pick_scan(case):
    (backend, mesh, chunk, state, per, width), want = SCAN_CHOICES[case]
    got = ssm.pick_scan(backend=backend, mesh_devices=mesh, chunk=chunk,
                        state=state, heads_per_group=per, head_dim=width)
    assert got == want
    assert got[1] is None or got[1] in ssm.CHUNKED_REASONS


@pytest.mark.parametrize("case", ["chunk_of_16", "mesh"])
def test_a_call_the_kernels_do_not_take_says_why(case, monkeypatch):
    """On a TPU too the einsums run where the kernels cannot, and the
    tally carries the reason beside the chunks."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    as_a_tpu(monkeypatch)
    args = fused_inputs(**FUSED["one_slab"])
    chunk = 16 if case == "chunk_of_16" else 128
    if case == "mesh":
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        args = tuple(jax.device_put(v, NamedSharding(mesh, P()))
                     for v in args)
    with ssm.scan_paths.counting() as forms:
        got = jit_once(lambda *a: ssm.ssm_scan(*a, chunk_size=chunk))(*args)
    reason = "shape" if case == "chunk_of_16" else "mesh"
    assert dict(forms) == {f"chunked[{chunk}x{256 // chunk},{reason}]": 1}
    assert rel(got, recurrence(*args)) < 1e-5


@pytest.mark.parametrize("kept", [HYBRID, HYBRID[:3]],
                         ids=["ssm_out_kept", "ssm_out_dropped"])
def test_a_remat_layer_launches_the_scans_forward_kernel_once(kept,
                                                              monkeypatch):
    """The fused scan's backward takes the scan's operands alone: with
    ``ssm_out`` kept the recomputed layer does not run the forward
    kernel again (one launch a layer and step); with it dropped the
    layer recomputes it, as it recomputes anything else it does not
    hold. The backward's two kernels run once either way."""
    as_a_tpu(monkeypatch)
    monkeypatch.setattr(remat, "choose_keeps", lambda *a, **k: kept)
    task = HybridLMTask(**{
        **TOY, "hybrid_override_pattern": "M", "mamba_head_dim": 16,
        "n_groups": 1, "ssm_state_size": 128, "chunk_size": 128,
        "max_seq_len": 256, "remat": True})
    model = task.build()
    params = jax.eval_shape(model.init, jax.random.key(0))
    batch = {"input_ids": jax.ShapeDtypeStruct((2, 256), jnp.int32)}
    with ssm.scan_paths.counting() as forms:
        step = jax.make_jaxpr(jax.grad(lambda p, b: task.loss_and_metrics(
            model, p, b, policy=FP32)[0]))(params, batch).jaxpr
    assert set(forms) == {"fused[128x2]"}
    assert kernel_calls(step, "ssm_scan_fwd")[0] == (
        1 if "ssm_out" in kept else 2)
    assert kernel_calls(step, "ssm_scan_bwd_states")[0] == 1
    assert kernel_calls(step, "ssm_scan_bwd")[0] == 2   # the states' too
