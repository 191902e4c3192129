"""The gated delta rule (``ops/delta_rule.py``): the chunked form
against the position-by-position recurrence of the plain reference,
outputs and every gradient, over chunks that do and do not divide the
row and decays weak and strong; the triangular inverse; how many heads
a pass holds; decays that underflow; the whole mixer against the
reference's; what the call site says it took."""

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

from benchmarks import weights  # noqa: E402
from benchmarks.reference import gated_delta_lm as ref  # noqa: E402

from perceiver_tpu.ops import delta_rule as dr  # noqa: E402
from perceiver_tpu.ops.policy import Policy  # noqa: E402

FP32 = Policy.fp32()
SIZES = dict(num_key_heads=2, num_value_heads=4, key_head_dim=8,
             value_head_dim=16)
CFG = dict(linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=8, linear_value_head_dim=16, norm_eps=1e-6)


def rel(a, b):
    return float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-30)


def rule_inputs(seq, *, decay=1.0, rows=2, key_heads=2, heads=4, depth=8,
                width=16):
    """q, k as the mixer hands them over (l2-normed, q scaled; the keys
    share a direction, as after a SiLU), v, g <= 0 and beta in (0, 1)."""
    k = jax.random.split(jax.random.key(seq), 5)
    q = dr.l2_norm(jax.random.normal(k[0], (rows, seq, key_heads, depth))) \
        / math.sqrt(depth)
    key = dr.l2_norm(jax.random.normal(k[1], (rows, seq, key_heads, depth))
                     + 0.5)
    v = jax.random.normal(k[2], (rows, seq, heads, width))
    g = -decay * jax.nn.softplus(jax.random.normal(k[3], (rows, seq, heads)))
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(k[4], (rows, seq, heads)))
    return q, key, v, g, beta


def recurrence(q, k, v, g, beta):
    per = v.shape[2] // q.shape[2]
    return ref.recurrence(jnp.repeat(q, per, axis=2),
                          jnp.repeat(k, per, axis=2), v, g, beta)


def out_and_grads(fn, args, w):
    """``fn(*args)`` and the gradients of ``(fn(*args) * w).sum()`` for
    every argument, as one compiled function."""
    def both(*a):
        out, vjp = jax.vjp(fn, *a)
        return out, vjp(w)

    return jit_once(both)(*args)


# --- the chunked rule --------------------------------------------------------


@pytest.mark.parametrize("seq,chunk,decay", [
    (40, 16, 0.05), (40, 16, 1.0), (40, 16, 6.0),
    (129, 64, 0.05), (129, 64, 1.0), (129, 64, 6.0),
    (32, 16, 1.0), (7, 16, 1.0), (40, 8, 1.0), (64, 64, 1.0)])
def test_the_chunked_rule_is_the_recurrence(seq, chunk, decay):
    """40 over 16 and 129 over 64 leave a padded last chunk, 7 is
    shorter than one; decays weak (0.05 a position), usual and strong
    (6); outputs and the gradients of all five operands."""
    args = rule_inputs(seq, decay=decay)
    w = jax.random.normal(jax.random.key(9), args[2].shape)
    with dr.rule_paths.counting() as forms:
        got, grads = out_and_grads(
            lambda *a: dr.delta_rule(*a, chunk_size=chunk), args, w)
    size = min(chunk, seq)
    pad = "+pad" if seq % size else ""
    assert dict(forms) == {
        f"chunked[{size}x{-(-seq // size)}{pad},4 heads a pass]": 1}
    want, want_grads = out_and_grads(recurrence, args, w)
    assert got.shape == want.shape == args[2].shape
    assert rel(got, want) < 3e-5
    for name, g, r in zip("q k v g beta".split(), grads, want_grads):
        assert rel(g, r) < 3e-5, name


def test_a_dropped_term_of_the_rule_shows():
    """What the comparison is there to catch: without the write
    strength, without the decay, or as plain linear attention (no
    ``S^T k`` taken off the value) the output is another."""
    q, k, v, g, beta = rule_inputs(40)
    want = recurrence(q, k, v, g, beta)
    got = dr.delta_rule(q, k, v, g, beta, chunk_size=16)
    assert rel(got, want) < 1e-5
    assert rel(dr.delta_rule(q, k, v, g, jnp.ones_like(beta),
                             chunk_size=16), want) > 0.05
    assert rel(dr.delta_rule(q, k, v, jnp.zeros_like(g), beta,
                             chunk_size=16), want) > 0.05
    # the recurrence without the delta term is a decayed sum of k v^T
    per = v.shape[2] // q.shape[2]
    kr, qr = (jnp.repeat(x, per, axis=2) for x in (k, q))
    span = jnp.cumsum(g, 1)
    decay = jnp.exp(jnp.where(
        jnp.tril(jnp.ones((40, 40), bool))[None, :, :, None],
        span[:, :, None] - span[:, None, :], -jnp.inf))
    linear = jnp.einsum("blhd,bshd,blsh,bsh,bshe->blhe", qr, kr, decay, beta,
                        v)
    assert rel(linear, want) > 0.05


def test_decays_that_underflow_do_so_quietly():
    """g near -40 a position: the decay over a chunk is exp(-600), which
    float32 does not hold; nothing is inf or nan, forward or backward,
    and the result is still the recurrence's."""
    args = rule_inputs(40, decay=40.0)
    assert float(args[3].min()) < -40
    got, grads = out_and_grads(
        lambda *a: dr.delta_rule(*a, chunk_size=16), args,
        jnp.ones_like(args[2]))
    assert all(bool(jnp.isfinite(x).all()) for x in (got, *grads))
    assert rel(got, recurrence(*args)) < 1e-5


def test_a_padded_row_writes_nothing_past_its_end():
    """The padding (g = 0, beta = 0) neither decays nor writes: a row of
    40 is the first 40 positions of the same row continued to 48."""
    longer = rule_inputs(48)
    short = tuple(x[:, :40] for x in longer)
    np.testing.assert_allclose(
        dr.delta_rule(*short, chunk_size=16),
        dr.delta_rule(*longer, chunk_size=16)[:, :40], atol=1e-6)


@pytest.mark.parametrize("size", [1, 2, 8, 16, 48, 64])
def test_the_inverse_of_a_unit_lower_matrix(size):
    a = jnp.tril(jax.random.normal(jax.random.key(size), (3, size, size)),
                 -1) / math.sqrt(size)
    got = dr.unit_lower_inverse(a)
    eye = jnp.eye(size)
    np.testing.assert_allclose(got @ (eye - a), jnp.broadcast_to(
        eye, a.shape), atol=2e-4)
    # unit lower triangular itself
    np.testing.assert_allclose(jnp.triu(got, 1), 0.0, atol=0)
    np.testing.assert_allclose(jnp.diagonal(got, axis1=-2, axis2=-1), 1.0,
                               atol=1e-6)


@pytest.mark.parametrize("rows,seq,key_heads,heads,want", [
    (4, 4096, 16, 32, (64, 64, 0, 8)),      # qwen3next_train: four passes
    (2, 72, 2, 4, (64, 2, 56, 4)),          # the rehearsal: one pass
    (1, 4096, 16, 32, (64, 64, 0, 32)),     # a row alone: all the heads
    (64, 4096, 16, 32, (64, 64, 0, 2)),     # never less than a key head's
    (4, 4096, 3, 6, (64, 64, 0, 6)),        # whole key heads that divide
])
def test_how_many_heads_a_pass_holds(rows, seq, key_heads, heads, want):
    assert dr.pick_rule(rows=rows, seq=seq, key_heads=key_heads,
                        value_heads=heads, chunk_size=64) == want


def test_heads_in_several_passes_are_the_same_rule(monkeypatch):
    args = rule_inputs(40, key_heads=4, heads=8)
    whole = jit_once(lambda *a: dr.delta_rule(*a, chunk_size=16))(*args)
    # room for one key head's two value heads a pass: four passes
    monkeypatch.setattr(dr, "PASS_HEAD_CHUNKS", 2 * 3 * 2)
    w = jax.random.normal(jax.random.key(3), whole.shape)
    with dr.rule_paths.counting() as forms:
        got, grads = out_and_grads(
            lambda *a: dr.delta_rule(*a, chunk_size=16), args, w)
    assert dict(forms) == {"chunked[16x3+pad,2 heads a pass]": 1}
    np.testing.assert_allclose(got, whole, atol=1e-6)
    for g, r in zip(grads, out_and_grads(recurrence, args, w)[1]):
        assert rel(g, r) < 3e-5


def test_bfloat16_operands_stay_near_the_float32_rule():
    """The products' operands in bfloat16, decays and the inverse in
    float32: a rounding's distance from the recurrence, not more."""
    q, k, v, g, beta = rule_inputs(64)
    low = jit_once(lambda *a: dr.delta_rule(*a, chunk_size=16))(
        *(x.astype(jnp.bfloat16) for x in (q, k, v)), g, beta)
    assert low.dtype == jnp.bfloat16
    assert rel(low.astype(jnp.float32), recurrence(q, k, v, g, beta)) < 0.03


# --- the mixer ---------------------------------------------------------------


@pytest.fixture(scope="module")
def mixer():
    p = weights.make_weights(jax.eval_shape(
        lambda: dr.delta_mixer_init(jax.random.key(0), 48, **SIZES)), 21)
    # the norm's scale is drawn as ones: move it, so that one read from
    # the wrong place shows
    p["norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(jax.random.key(2),
                                                       (16,))
    a = jax.random.normal(jax.random.key(5), (2, 40, 48))
    return p, a


def test_the_mixers_tree(mixer):
    p, _ = mixer
    assert p["in_proj_qkvz"]["w"].shape == (48, 2 * 16 + 2 * 64)
    assert p["in_proj_ba"]["w"].shape == (48, 2 * 4)
    assert p["conv"]["w"].shape == (4, 2 * 16 + 64) and "bias" not in p["conv"]
    assert p["A_log"]["bias"].shape == p["dt"]["bias"].shape == (4,)
    assert p["norm"]["scale"].shape == (16,)
    assert p["out_proj"]["w"].shape == (64, 48)
    init = dr.delta_mixer_init(jax.random.key(1), 48, **SIZES)
    assert jax.tree.structure(init) == jax.tree.structure(p)
    a = jnp.exp(init["A_log"]["bias"])
    assert float(a.min()) > 0 and float(a.max()) < 16
    np.testing.assert_array_equal(init["dt"]["bias"], 1.0)


def test_the_mixer_against_the_reference(mixer):
    p, a = mixer
    w = jax.random.normal(jax.random.key(6), a.shape)

    def got_fn(p, a):
        return dr.delta_mixer_apply(p, a, **SIZES, chunk_size=16,
                                    policy=FP32)

    def want_fn(p, a):
        return ref.delta_mixer(p, a, CFG, "f32")

    got, got_g = jit_once(jax.value_and_grad(
        lambda p, a: (got_fn(p, a) * w).sum(), argnums=(0, 1)))(p, a)
    want, want_g = jit_once(jax.value_and_grad(
        lambda p, a: (want_fn(p, a) * w).sum(), argnums=(0, 1)))(p, a)
    assert rel(jit_once(got_fn)(p, a), jit_once(want_fn)(p, a)) < 2e-5
    assert abs(got - want) < 2e-5 * abs(want) + 1e-6
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(got_g)[0],
                            jax.tree.leaves(want_g)):
        assert rel(g, r) < 2e-4, jax.tree_util.keystr(path)


@pytest.mark.parametrize("change", ["norm_scale", "gate", "conv_tap",
                                    "dt_bias", "A_log"])
def test_every_part_of_the_mixer_moves_its_output(mixer, change):
    """A scale, the gate, a tap, the decay's two parameters: the
    reference with one of them changed is another function, so the
    comparison above reads each."""
    p, a = mixer
    want = ref.delta_mixer(p, a, CFG, "f32")
    other = jax.tree.map(lambda x: x, p)
    if change == "norm_scale":
        other["norm"]["scale"] = jnp.ones((16,))
    elif change == "gate":      # z's columns: the last 64 of in_proj_qkvz
        other["in_proj_qkvz"]["w"] = p["in_proj_qkvz"]["w"].at[:, 96:].mul(
            0.5)
    elif change == "conv_tap":
        other["conv"]["w"] = p["conv"]["w"].at[0].mul(0.0)
    elif change == "dt_bias":
        other["dt"]["bias"] = p["dt"]["bias"] + 1.0
    else:
        other["A_log"]["bias"] = p["A_log"]["bias"] + 1.0
    assert rel(ref.delta_mixer(other, a, CFG, "f32"), want) > 0.01


def test_a_decay_rounded_to_bfloat16_is_not_the_mixer(mixer):
    """The configuration states the decay in float32: ``g`` rounded to
    bfloat16 before the running sum moves the output by more than the
    float32 forms differ."""
    p, a = mixer
    q, k, v, g, beta = rule_inputs(128, decay=0.2)
    rule = jit_once(recurrence)
    want = rule(q, k, v, g, beta)
    low = rule(q, k, v, g.astype(jnp.bfloat16).astype(jnp.float32), beta)
    exact = jit_once(lambda *a: dr.delta_rule(*a, chunk_size=64))(
        q, k, v, g, beta)
    assert rel(low, want) > 20 * rel(exact, want)


def test_the_gated_norm_norms_first(mixer):
    p, _ = mixer
    o = jax.random.normal(jax.random.key(1), (2, 5, 4, 16))
    z = jax.random.normal(jax.random.key(2), (2, 5, 4, 16))
    got = dr.gated_head_rms_norm(p["norm"], o, z, 1e-6, FP32)
    want = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6) \
        * p["norm"]["scale"] * (z * jax.nn.sigmoid(z))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # a Mamba-2 mixer gates before it norms: another function
    first = o * z * jax.nn.sigmoid(z)
    first = first / jnp.sqrt(jnp.mean(first * first, -1, keepdims=True)
                             + 1e-6) * p["norm"]["scale"]
    assert rel(first, want) > 0.1
