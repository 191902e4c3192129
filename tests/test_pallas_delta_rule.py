"""The gated delta rule's Pallas kernels (``ops/pallas_delta_rule.py``),
interpreted on the CPU: against the position-by-position recurrence of
the plain reference and against the einsum form, outputs and all five
gradients, at float32 (to rounding) and at bfloat16 (no further from
the float32 recurrence than the einsum form is); one, two and four value
heads a key head, side by side on the lanes or in sets; one chunk, several, a padded last one; decays that
underflow; the blocked inverse; planted faults; which form a call site
takes and what it says."""

import math
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

from test_gated_delta import (  # noqa: E402
    out_and_grads, recurrence, rel, rule_inputs)

from perceiver_tpu.ops import delta_rule as dr  # noqa: E402
from perceiver_tpu.ops import pallas_delta_rule as kernels  # noqa: E402

NAMES = "q k v g beta".split()
# what the float32 comparisons hold the kernels to
ROUNDING = 3e-5


def lane_inputs(seq, *, key_heads=1, per=2, decay=1.0, rows=1):
    """``rule_inputs`` with heads of 128: whole lanes."""
    return rule_inputs(seq, decay=decay, rows=rows, key_heads=key_heads,
                       heads=key_heads * per, depth=128, width=128)


def einsum_rule(monkeypatch, chunk):
    """The einsum form at shapes the kernels would take."""
    def rule(*args):
        with monkeypatch.context() as m:
            m.setattr(dr, "fits", lambda *_: False)
            return dr.delta_rule(*args, chunk_size=chunk)
    return rule


def rms_gap(a, b):
    a, b = (x.astype(jnp.float32) for x in (a, b))
    return float(jnp.sqrt(jnp.mean(jnp.square(a - b))
                          / jnp.mean(jnp.square(b))))


# --- the kernels against the recurrence and the einsum form ------------------


@pytest.mark.parametrize("seq,chunk,key_heads,per,decay", [
    (64, 64, 1, 2, 1.0),       # one chunk, two value heads a key head
    (192, 64, 1, 2, 1.0),      # several chunks: the state is carried
    (100, 64, 1, 2, 1.0),      # a padded last chunk
    (128, 64, 2, 1, 1.0),      # a value head a key head, two key heads
    (64, 16, 1, 2, 0.05),      # chunks of one block of the inverse; weak
    (96, 32, 1, 2, 6.0),       # chunks of two blocks; strong decays
    (128, 64, 1, 4, 1.0),      # four value heads a key head: two sets
    (64, 32, 1, 4, 1.0),       # four heads side by side on the lanes
], ids=["one_chunk", "several_chunks", "padded", "per_1", "chunk_16_weak",
        "chunk_32_strong", "per_4", "four_side_by_side"])
def test_the_kernels_are_the_recurrence(monkeypatch, seq, chunk, key_heads,
                                        per, decay):
    args = lane_inputs(seq, key_heads=key_heads, per=per, decay=decay)
    w = jax.random.normal(jax.random.key(9), args[2].shape)
    with dr.rule_paths.counting() as forms:
        got, grads = out_and_grads(
            lambda *a: dr.delta_rule(*a, chunk_size=chunk), args, w)
    pad = "+pad" if seq % chunk else ""
    assert dict(forms) == {f"kernel[{chunk}x{-(-seq // chunk)}{pad}]": 1}
    want, want_grads = out_and_grads(recurrence, args, w)
    ein, ein_grads = out_and_grads(einsum_rule(monkeypatch, chunk), args, w)
    assert got.shape == want.shape == args[2].shape
    assert rel(got, want) < ROUNDING and rel(got, ein) < ROUNDING
    for name, g, e, r in zip(NAMES, grads, ein_grads, want_grads):
        assert g.shape == r.shape and g.dtype == r.dtype, name
        assert rel(g, r) < ROUNDING and rel(g, e) < ROUNDING, name


@pytest.mark.parametrize("seq,chunk", [(128, 64), (100, 64), (64, 16)],
                         ids=["whole", "padded", "chunk_16"])
def test_bfloat16_kernels_stay_as_near_as_the_einsum_form(monkeypatch, seq,
                                                          chunk):
    """The products' operands in bfloat16, decays, the inverse and the
    state in float32: no further from the float32 recurrence than the
    einsum form at bfloat16 is (a tenth of room: the two round at other
    places)."""
    args = lane_inputs(seq)
    w = jax.random.normal(jax.random.key(9), args[2].shape)

    def low(rule):
        def fn(q, k, v, g, beta):
            return rule(*(x.astype(jnp.bfloat16) for x in (q, k, v)), g, beta)
        return fn

    got, grads = out_and_grads(
        low(lambda *a: dr.delta_rule(*a, chunk_size=chunk)), args,
        w.astype(jnp.bfloat16))
    ein, ein_grads = out_and_grads(low(einsum_rule(monkeypatch, chunk)),
                                   args, w.astype(jnp.bfloat16))
    want, want_grads = out_and_grads(
        recurrence, args, w.astype(jnp.bfloat16).astype(jnp.float32))
    assert got.dtype == jnp.bfloat16
    assert rms_gap(got, want) < 1.1 * rms_gap(ein, want) < 0.01
    for name, g, e, r in zip(NAMES, grads, ein_grads, want_grads):
        assert g.dtype == r.dtype, name   # g, beta float32; the rest as given
        assert rms_gap(g, r) < 1.1 * rms_gap(e, r) < 0.01, name


@pytest.mark.parametrize("chunk", [16, 64])
def test_decays_that_underflow_stay_quiet_in_the_kernels(chunk):
    """g near -40 a position: the decay over a chunk is exp(-600) and
    less; nothing is inf or nan, forward or backward, and the result is
    still the recurrence's."""
    args = lane_inputs(128, decay=40.0)
    assert float(args[3].min()) < -40
    got, grads = out_and_grads(
        lambda *a: dr.delta_rule(*a, chunk_size=chunk), args,
        jnp.ones_like(args[2]))
    assert all(bool(jnp.isfinite(x).all()) for x in (got, *grads))
    assert rel(got, recurrence(*args)) < 1e-5


def test_a_padded_row_writes_nothing_past_its_end_in_the_kernels():
    longer = lane_inputs(128)
    short = tuple(x[:, :100] for x in longer)
    np.testing.assert_allclose(
        dr.delta_rule(*short, chunk_size=64),
        dr.delta_rule(*longer, chunk_size=64)[:, :100], atol=1e-6)


# --- the inverse -------------------------------------------------------------


@pytest.mark.parametrize("size", [16, 32, 64, 128])
def test_the_blocked_inverse_is_the_doubled_one(size):
    a = jnp.tril(jax.random.normal(jax.random.key(size), (size, size)),
                 -1) / math.sqrt(size)
    got = jit_once(kernels.blocked_inverse)(a)
    np.testing.assert_allclose(got, dr.unit_lower_inverse(a), atol=2e-6)
    np.testing.assert_allclose(got @ (jnp.eye(size) - a), jnp.eye(size),
                               atol=2e-5)
    np.testing.assert_allclose(jnp.triu(got, 1), 0.0, atol=0)


# --- planted faults ----------------------------------------------------------


def _bf16_inverse(a):
    """The blocked inverse with its products on bfloat16 operands."""
    def low(lhs, rhs, dims):
        return jax.lax.dot_general(
            lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16), dims,
            preferred_element_type=jnp.float32)

    with pytest.MonkeyPatch.context() as m:
        m.setattr(kernels, "_dot32", low)
        return FAULTLESS["blocked_inverse"](a)


def _rounded_log_decays(g, chunk):
    return FAULTLESS["log_decays"](
        g.astype(jnp.bfloat16).astype(jnp.float32), chunk)


FAULTLESS = {"log_decays": kernels.log_decays,
             "blocked_inverse": kernels.blocked_inverse}
FAULTS = {
    # T = I: the chunk's own writes do not see each other
    "dropped_term": ("blocked_inverse", lambda a: jnp.tile(
        jnp.eye(a.shape[0], dtype=a.dtype), (1, a.shape[1] // a.shape[0]))),
    "bf16_decay": ("log_decays", _rounded_log_decays),
    "bf16_inverse": ("blocked_inverse", _bf16_inverse),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_float32_comparison(monkeypatch, fault):
    """What ``test_the_kernels_are_the_recurrence`` is there to catch: a
    term of the rule dropped, ``g`` rounded to bfloat16 before the
    running sum, the inverse's products on bfloat16 operands: each is
    ten times and more outside the rounding it holds the kernels to,
    in the output and in a gradient."""
    args = lane_inputs(128, decay=0.2)
    w = jax.random.normal(jax.random.key(9), args[2].shape)

    def rule(*a):
        return kernels.fused_rule(*a, chunk=64)

    want, want_grads = out_and_grads(recurrence, args, w)
    assert rel(jit_once(rule)(*args), want) < ROUNDING
    name, planted = FAULTS[fault]
    monkeypatch.setattr(kernels, name, planted)
    got, grads = out_and_grads(rule, args, w)
    assert rel(got, want) > 10 * ROUNDING
    assert max(rel(g, r) for g, r in zip(grads, want_grads)) > 10 * ROUNDING


# --- which form a call site takes --------------------------------------------


@pytest.mark.parametrize("depth,width,chunk,dtypes,label", [
    (16, 16, 16, "ff", "chunked[16x3+pad,4 heads a pass]"),    # narrow heads
    (128, 16, 16, "ff", "chunked[16x3+pad,4 heads a pass]"),   # narrow values
    (128, 128, 8, "ff", "chunked[8x5,4 heads a pass]"),        # half a block
    (128, 128, 64, "ff", "chunked[40x1,4 heads a pass]"),      # a short row
    (128, 128, 16, "fb", "chunked[16x3+pad,4 heads a pass]"),  # mixed dtypes
    (128, 128, 16, "ff", "kernel[16x3+pad]"),
    (128, 128, 16, "bb", "kernel[16x3+pad]"),
], ids=["narrow_heads", "narrow_values", "chunk_8", "short_row",
        "mixed_dtypes", "float32", "bfloat16"])
def test_the_shapes_say_which_form_runs(depth, width, chunk, dtypes, label):
    """``fits`` false keeps the einsum path and its label, whatever the
    reason; the answer is the recurrence's either way."""
    q, k, v, g, beta = rule_inputs(40, rows=1, key_heads=2, heads=4,
                                   depth=depth, width=width)
    kinds = {"f": jnp.float32, "b": jnp.bfloat16}
    low = (q.astype(kinds[dtypes[0]]), k.astype(kinds[dtypes[0]]),
           v.astype(kinds[dtypes[1]]))
    with dr.rule_paths.counting() as forms:
        got = jit_once(lambda *a: dr.delta_rule(*a, chunk_size=chunk))(
            *low, g, beta)
    assert dict(forms) == {label: 1}
    assert dr.fits(low[0], low[2], min(chunk, 40)) == label.startswith(
        "kernel")
    assert rel(got.astype(jnp.float32), recurrence(q, k, v, g, beta)) < (
        3e-5 if dtypes == "ff" else 0.03)


def test_the_kernels_refuse_what_does_not_tile():
    q, k, v, g, beta = rule_inputs(40)
    with pytest.raises(ValueError, match="do not tile 40 positions"):
        kernels.fused_rule(q, k, v, g, beta, chunk=16)


def test_the_mixer_runs_the_kernels_at_heads_of_whole_lanes():
    """``delta_mixer_apply`` hands the rule what the kernels take: the
    same output and parameter gradients as with the einsum form."""
    from perceiver_tpu.ops.policy import Policy
    sizes = dict(num_key_heads=1, num_value_heads=2, key_head_dim=128,
                 value_head_dim=128)
    p = dr.delta_mixer_init(jax.random.key(0), 32, **sizes)
    a = jax.random.normal(jax.random.key(5), (1, 64, 32))

    def loss(p, a):
        return jnp.sum(jnp.square(dr.delta_mixer_apply(
            p, a, **sizes, chunk_size=16, policy=Policy.fp32())))

    with dr.rule_paths.counting() as forms:
        got, got_g = jit_once(jax.value_and_grad(loss, argnums=(0, 1)))(p, a)
    assert dict(forms) == {"kernel[16x4]": 1}
    with pytest.MonkeyPatch.context() as m:
        m.setattr(dr, "fits", lambda *_: False)
        want, want_g = jit_once(jax.value_and_grad(loss, argnums=(0, 1)))(p, a)
    assert abs(got - want) < 1e-5 * abs(want)
    for g, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert rel(g, r) < 1e-4
