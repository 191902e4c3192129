"""The vector-decay delta rule's Pallas kernels
(``ops/pallas_kda_rule.py``; Kimi Delta Attention, ``g`` of
(B, S, H, Dk)), interpreted on the CPU: against ``tests/test_kda.py``'s
position-by-position recurrence and against the einsum form, outputs
and all five gradients (``dg`` a number a head and key channel), at
float32 (to rounding) and at bfloat16 (no further from the float32
recurrence than the einsum form is); one chunk, several, a padded last
one, chunks of one, two and four sub-blocks; decays that underflow;
one head a grid step and two side by side; planted faults; which form
a call site takes and what it says."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

from test_kda import operands, out_and_grads, recurrence, rel  # noqa: E402

from perceiver_tpu.ops import delta_rule as dr  # noqa: E402
from perceiver_tpu.ops import pallas_delta_rule as scalar_kernels  # noqa: E402
from perceiver_tpu.ops import pallas_kda_rule as kernels  # noqa: E402

NAMES = "q k v g beta".split()
# what the float32 comparisons hold the kernels to (measured 2e-7 to
# 2e-6 on these shapes, as the einsum form)
ROUNDING = 2e-5


def lane_operands(seq, *, heads=1, fast=0, per=1):
    """``test_kda.operands`` with heads of 128: whole lanes."""
    return operands(seq, heads, per, dk=128, dv=128, rows=1, fast=fast)


def einsum_rule(chunk):
    """The einsum form at shapes the kernels would take."""
    def rule(*args):
        with pytest.MonkeyPatch.context() as m:
            m.setattr(dr, "fits", lambda *_: False)
            return dr.delta_rule(*args, chunk_size=chunk)
    return rule


def rms_gap(a, b):
    a, b = (x.astype(jnp.float32) for x in (a, b))
    return float(jnp.sqrt(jnp.mean(jnp.square(a - b))
                          / jnp.mean(jnp.square(b))))


# --- the kernels against the recurrence and the einsum form ------------------


@pytest.mark.parametrize("seq,chunk,heads,fast", [
    (64, 64, 1, 0),        # one chunk of four sub-blocks
    (192, 64, 1, 0),       # several chunks: the state is carried
    (100, 64, 1, 0),       # a padded last chunk
    (128, 64, 2, 0),       # two heads a grid step, one inverse for both
    (48, 16, 1, 0),        # chunks of one sub-block: no product before it
    (96, 32, 1, 3),        # chunks of two; three channels underflow
    (64, 32, 4, 0),        # four heads: two grid steps of two
    (32, 16, 3, 0),        # three: a head a step
], ids=["one_chunk", "several_chunks", "padded", "two_heads", "chunk_16",
        "chunk_32_fast_channels", "four_heads", "three_heads"])
def test_the_kernels_are_the_recurrence(seq, chunk, heads, fast):
    args, w = lane_operands(seq, heads=heads, fast=fast)
    with dr.rule_paths.counting() as forms:
        (got, grads), out = out_and_grads(
            lambda *a: dr.delta_rule(*a, chunk_size=chunk), args, w)
    pad = "+pad" if seq % chunk else ""
    assert dict(forms) == {
        f"kernel[{chunk}x{-(-seq // chunk)}{pad}, by channel]": 1}
    (want, want_grads), want_out = out_and_grads(recurrence, args, w)
    (_, ein_grads), ein_out = out_and_grads(einsum_rule(chunk), args, w)
    assert out.shape == want_out.shape == args[2].shape
    assert rel(out, want_out) < ROUNDING and rel(out, ein_out) < ROUNDING
    assert abs(got - want) < ROUNDING * abs(want) + 1e-6
    for name, g, e, r in zip(NAMES, grads, ein_grads, want_grads):
        assert g.shape == r.shape and g.dtype == r.dtype, name
        assert rel(g, r) < ROUNDING and rel(g, e) < ROUNDING, name


@pytest.mark.parametrize("seq,chunk", [(128, 64), (100, 64), (64, 16)],
                         ids=["whole", "padded", "chunk_16"])
def test_bfloat16_kernels_stay_as_near_as_the_einsum_form(seq, chunk):
    """The products' operands in bfloat16, the decays, the sub-blocks'
    own spans, the inverse and the state in float32: no further from
    the float32 recurrence than the einsum form at bfloat16 is (a tenth
    of room: the two round at other places)."""
    args, w = lane_operands(seq)
    low = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
    as_f32 = tuple(x.astype(jnp.float32) for x in low)
    (_, grads), out = out_and_grads(
        lambda *a: dr.delta_rule(*a, chunk_size=chunk), low, w)
    (_, ein_grads), ein = out_and_grads(einsum_rule(chunk), low, w)
    (_, want_grads), want = out_and_grads(recurrence, as_f32, w)
    assert out.dtype == jnp.bfloat16
    assert rms_gap(out, want) < 1.1 * rms_gap(ein, want) < 0.01
    for name, g, e, r in zip(NAMES, grads, ein_grads, want_grads):
        assert g.dtype == e.dtype, name   # g, beta float32; the rest as given
        assert rms_gap(g, r) < 1.1 * rms_gap(e, r) < 0.02, name


@pytest.mark.parametrize("chunk", [16, 64])
def test_decays_that_underflow_stay_quiet_in_the_kernels(chunk):
    """``g`` of -30 a position on three channels a head (the running
    sum reaches -1,920 inside a chunk of 64) and of -200 on one more
    (every span of it underflows): nothing is inf or nan, forward or
    backward, and the result is still the recurrence's."""
    (q, k, v, g, beta), w = lane_operands(128, fast=3)
    args = (q, k, v, g.at[..., 3].set(-200.0), beta)
    (_, grads), out = out_and_grads(
        lambda *a: dr.delta_rule(*a, chunk_size=chunk), args, w)
    (_, want_grads), want = out_and_grads(recurrence, args, w)
    assert all(bool(jnp.isfinite(x).all()) for x in (out, *grads))
    assert rel(out, want) < ROUNDING
    for name, a, b in zip(NAMES, grads, want_grads):
        assert rel(a, b) < ROUNDING, name


def test_a_padded_row_writes_nothing_past_its_end_in_the_kernels():
    longer, _ = lane_operands(128)
    short = tuple(x[:, :100] for x in longer)
    np.testing.assert_allclose(
        dr.delta_rule(*short, chunk_size=64),
        dr.delta_rule(*longer, chunk_size=64)[:, :100], atol=1e-6)


# --- planted faults ----------------------------------------------------------


def _bf16_inverse(a):
    """The blocked inverse with its products on bfloat16 operands."""
    def low(lhs, rhs, dims):
        return jax.lax.dot_general(
            lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16), dims,
            preferred_element_type=jnp.float32)

    with pytest.MonkeyPatch.context() as m:
        m.setattr(scalar_kernels, "_dot32", low)
        return scalar_kernels.blocked_inverse(a)


_running_sum = kernels._running_sum
FAULTS = {
    # g through bfloat16 before the running sum
    "bf16_decay": ("_running_sum", lambda g: _running_sum(
        g.astype(jnp.bfloat16).astype(jnp.float32))),
    # every write at full strength
    "dropped_beta": ("_columns", lambda tile: jnp.ones(
        (128, 128), jnp.float32)),
    # a span that runs backwards is taken for what its exponent gives
    "unmasked_span": ("_span", lambda later, earlier, forwards: jnp.exp(
        jnp.minimum(later - earlier, 5.0))),
    "bf16_inverse": ("blocked_inverse", _bf16_inverse),
}


def forget_traces():
    kernels._rule_forward.clear_cache()
    kernels._rule_backward.clear_cache()


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_float32_comparison(monkeypatch, fault):
    """What ``test_the_kernels_are_the_recurrence`` is there to catch:
    a decay rounded to bfloat16, a write strength dropped, a sub-block's
    span taken without its mask, the inverse's products on bfloat16
    operands: each is ten times and more outside the rounding it holds
    the kernels to, in the output and in a gradient."""
    (q, k, v, g, beta), w = lane_operands(128)
    # the keys share a direction, as after a SiLU: ``A`` is not small
    args = (q, dr.l2_norm(k + 0.1), v, 0.2 * g, beta)

    def rule(*a):
        return kernels.fused_rule(*a, chunk=64)

    (_, want_grads), want = out_and_grads(recurrence, args, w)
    assert rel(jit_once(rule)(*args), want) < ROUNDING
    name, planted = FAULTS[fault]
    monkeypatch.setattr(kernels, name, planted)
    try:
        forget_traces()     # each direction is jitted: traced once a shape
        (_, grads), got = out_and_grads(rule, args, w)
    finally:
        forget_traces()     # ... and the faulty trace must not outlive this
    assert rel(got, want) > 10 * ROUNDING
    assert max(rel(g, r) for g, r in zip(grads, want_grads)) > 10 * ROUNDING


# --- which form a call site takes --------------------------------------------


@pytest.mark.parametrize("dk,dv,per,chunk,dtypes,label", [
    (16, 16, 1, 16, "ff", "chunked[16x3+pad,2 heads a pass, by channel]"),
    (128, 16, 1, 16, "ff", "chunked[16x3+pad,2 heads a pass, by channel]"),
    (128, 128, 2, 16, "ff", "chunked[16x3+pad,4 heads a pass, by channel]"),
    (128, 128, 1, 8, "ff", "chunked[8x5,2 heads a pass, by channel]"),
    (128, 128, 1, 64, "ff", "chunked[40x1,2 heads a pass, by channel]"),
    (128, 128, 1, 16, "fb", "chunked[16x3+pad,2 heads a pass, by channel]"),
    (128, 128, 1, 16, "ff", "kernel[16x3+pad, by channel]"),
    (128, 128, 1, 16, "bb", "kernel[16x3+pad, by channel]"),
], ids=["narrow_heads", "narrow_values", "two_value_heads_a_key_head",
        "chunk_8", "short_row", "mixed_dtypes", "float32", "bfloat16"])
def test_the_shapes_say_which_form_runs(dk, dv, per, chunk, dtypes, label):
    """``fits`` false keeps the einsum path and its label, whatever the
    reason; the answer is the recurrence's either way."""
    (q, k, v, g, beta), _ = operands(40, 2, per, dk=dk, dv=dv, rows=1)
    kinds = {"f": jnp.float32, "b": jnp.bfloat16}
    low = (q.astype(kinds[dtypes[0]]), k.astype(kinds[dtypes[0]]),
           v.astype(kinds[dtypes[1]]))
    with dr.rule_paths.counting() as forms:
        got = jit_once(lambda *a: dr.delta_rule(*a, chunk_size=chunk))(
            *low, g, beta)
    assert dict(forms) == {label: 1}
    assert dr.fits(low[0], low[2], min(chunk, 40), g) == label.startswith(
        "kernel")
    want = recurrence(*(x.astype(jnp.float32) for x in low), g, beta)
    assert rel(got, want) < (ROUNDING if dtypes == "ff" else 0.03)


def test_the_kernels_refuse_what_does_not_tile():
    (q, k, v, g, beta), _ = lane_operands(40)
    with pytest.raises(ValueError, match="do not tile 40 positions"):
        kernels.fused_rule(q, k, v, g, beta, chunk=16)
    with pytest.raises(ValueError, match="a decay of"):
        kernels.fused_rule(q[:, :32], k[:, :32], v[:, :32], g[:, :32, :, 0],
                           beta[:, :32], chunk=16)


def test_the_mixer_runs_the_kernels_at_heads_of_whole_lanes():
    """``kda_mixer_apply`` hands the rule what the kernels take: the
    same output and parameter gradients as with the einsum form."""
    from perceiver_tpu.ops.policy import Policy
    sizes = dict(num_heads=2, head_dim=128)
    p = dr.kda_mixer_init(jax.random.key(0), 32, **sizes)
    # decays of about 0.45 a position, as the benchmark's weights give
    # (at the initialisation's ``A`` of up to 16 the decay's leaves are
    # sums of what cancels to 1e-8 of its terms: the einsum form and the
    # recurrence agree on them to 4e-4 and no closer)
    p["A_log"]["bias"] = jnp.zeros((2,))
    a = jax.random.normal(jax.random.key(5), (1, 64, 32))

    def loss(p, a):
        return jnp.sum(jnp.square(dr.kda_mixer_apply(
            p, a, **sizes, chunk_size=16, policy=Policy.fp32())))

    with dr.rule_paths.counting() as forms:
        got, got_g = jit_once(jax.value_and_grad(loss, argnums=(0, 1)))(p, a)
    assert dict(forms) == {"kernel[16x4, by channel]": 1}
    with pytest.MonkeyPatch.context() as m:
        m.setattr(dr, "fits", lambda *_: False)
        want, want_g = jit_once(jax.value_and_grad(loss, argnums=(0, 1)))(p, a)
    assert abs(got - want) < 1e-5 * abs(want)
    for g, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert rel(g, r) < 1e-4
