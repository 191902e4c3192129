"""The delta rule with a decay that is a vector a key channel (Kimi
Delta Attention; ``ops/delta_rule.py``, ``g`` of (B, S, H, Dk)): the
chunked form against the recurrence run position by position, forward
and gradients, in float32 to rounding and in bfloat16 inside a stated
tolerance; rows that are no whole chunks; decays that underflow inside
one chunk; a ``g`` broadcast from a number a head against the scalar
rule; what a call says of itself; the whole mixer against the plain
reference's."""

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
from benchmarks.reference import kimi_linear_lm as ref  # noqa: E402

from perceiver_tpu.ops import delta_rule as dr  # noqa: E402
from perceiver_tpu.ops.policy import Policy  # noqa: E402

FP32 = Policy.fp32()


def rel(a, b):
    a, b = (jnp.asarray(x, jnp.float32) for x in (a, b))
    return float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-30)


def operands(seq, key_heads=2, per=1, dk=16, dv=8, rows=2, seed=0,
             fast=0):
    """q, k l2-normed, q scaled; g <= 0 a channel, ``fast`` channels a
    head at -30 a position; beta in (0, 1); a weight for the output."""
    ks = jax.random.split(jax.random.key(seed), 6)
    heads = key_heads * per
    q = dr.l2_norm(jax.random.normal(ks[0], (rows, seq, key_heads, dk))) \
        / np.sqrt(dk)
    k = dr.l2_norm(jax.random.normal(ks[1], (rows, seq, key_heads, dk)))
    v = jax.random.normal(ks[2], (rows, seq, heads, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (rows, seq, heads, dk)))
    g = g.at[..., :fast].set(-30.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, seq, heads)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], v.shape)


def recurrence(q, k, v, g, beta):
    """``S' = Diag(exp(g_t)) S_(t-1); S_t = S' + k_t (beta_t (v_t -
    S'^T k_t))^T; o_t = S_t^T q_t``, one scan over the positions, a
    value head reading its key head's q and k (the plain reference's
    own, ``ref.recurrence``, is held to it below)."""
    per = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(x.astype(jnp.float32), per, 2) for x in (q, k))

    def position(state, at):                      # state (B, H, Dk, Dv)
        q_t, k_t, v_t, g_t, beta_t = at
        state = state * jnp.exp(g_t)[..., None]
        write = beta_t[..., None] * (v_t - (state * k_t[..., None]).sum(-2))
        state = state + k_t[..., None] * write[..., None, :]
        return state, (state * q_t[..., None]).sum(-2)

    rows, _, heads, width = v.shape
    _, o = jax.lax.scan(
        position, jnp.zeros((rows, heads, q.shape[-1], width)),
        tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0)
              for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def out_and_grads(fn, args, w):
    """``((weighted sum, gradients), output)`` from one compiled call."""
    def weighted(*a):
        out = fn(*a)
        return (out.astype(jnp.float32) * w).sum(), out

    (total, out), grads = jit_once(jax.value_and_grad(
        weighted, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return (total, grads), out


# float32: the chunked form is the recurrence rearranged, every product
# at HIGHEST; what is left is the order of float32 sums over 16 to 64
# positions (measured 1e-6 to 2e-6 on these shapes)
F32_TOL = 2e-5


@pytest.mark.parametrize("seq,chunk,key_heads,per", [
    (70, 32, 2, 2),     # two sub-blocks a chunk, the last chunk padded;
                        # two value heads a key head
    (40, 64, 3, 1),     # a row shorter than the chunk: sub-blocks of 8
    (37, 16, 1, 1),     # an odd row, one sub-block a chunk
])
def test_the_chunked_rule_is_the_recurrence(seq, chunk, key_heads, per):
    args, w = operands(seq, key_heads, per)
    with dr.rule_paths.counting() as forms:
        (got, got_g), out = out_and_grads(
            lambda *a: dr.delta_rule(*a, chunk_size=chunk), args, w)
    (want, want_g), ref_out = out_and_grads(recurrence, args, w)
    (form,) = forms
    assert form.startswith("chunked[") and form.endswith(", by channel]")
    assert ("+pad" in form) == bool(seq % min(chunk, seq))
    assert rel(out, ref_out) < F32_TOL
    assert abs(got - want) < F32_TOL * abs(want) + 1e-6
    for name, a, b in zip("q k v g beta".split(), got_g, want_g):
        assert rel(a, b) < F32_TOL, name


def test_decays_that_underflow_inside_a_chunk_stay_finite():
    """``g`` of -30 a position on three channels a head: the running
    sum reaches -1,920 inside a chunk of 64 and ``exp`` of it is a
    quiet 0; no span is split into a factor above 1, so nothing
    overflows and nothing is 0 x inf, forward or backward."""
    args, w = operands(128, key_heads=1, fast=3)
    (got, got_g), out = out_and_grads(
        lambda *a: dr.delta_rule(*a, chunk_size=64), args, w)
    (want, want_g), ref_out = out_and_grads(recurrence, args, w)
    assert all(bool(jnp.isfinite(x).all()) for x in (out, *got_g))
    assert rel(out, ref_out) < F32_TOL
    for name, a, b in zip("q k v g beta".split(), got_g, want_g):
        assert rel(a, b) < F32_TOL, name
    # the fast channels matter: without them the output is another
    slow = (*args[:3], args[3].at[..., :3].set(-0.1), args[4])
    assert rel(jit_once(lambda *a: dr.delta_rule(*a, chunk_size=64))(*slow),
               out) > 0.01


def test_a_number_a_head_broadcast_is_the_scalar_rule():
    """One algebra: ``g`` of (B, S, H) and the same numbers repeated
    over the key channels give one result, by two routes."""
    (q, k, v, g, beta), w = operands(70, 2, 2)
    per_head = g[..., 0]
    with dr.rule_paths.counting() as forms:
        (a, a_g), out_a = out_and_grads(
            lambda *x: dr.delta_rule(*x, chunk_size=32),
            (q, k, v, per_head, beta), w)
        (b, b_g), out_b = out_and_grads(
            lambda q, k, v, g, beta: dr.delta_rule(
                q, k, v, jnp.broadcast_to(g[..., None], (*g.shape, 16)),
                beta, chunk_size=32), (q, k, v, per_head, beta), w)
    assert sorted(forms) == ["chunked[32x3+pad,4 heads a pass, by channel]",
                             "chunked[32x3+pad,4 heads a pass]"]
    assert rel(out_b, out_a) < F32_TOL
    for name, x, y in zip("q k v g beta".split(), b_g, a_g):
        assert rel(x, y) < F32_TOL, name


def test_bfloat16_stays_within_its_tolerance():
    """q, k, v in bfloat16, g and beta float32 as the mixer hands them.
    The products take bfloat16 operands (8 bits of mantissa: 4e-3 a
    value) and sum in float32; measured against the float32 recurrence
    on the same rounded operands the output is off by 6e-3 of its
    largest value and the gradients by up to 2e-2: 3e-2 holds them, and
    a decay rounded to bfloat16 (below) does not pass it."""
    args, w = operands(128, 1, 1, dk=32, dv=32)
    low = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
    as_f32 = tuple(x.astype(jnp.float32) for x in low)
    (_, got_g), out = out_and_grads(
        lambda *a: dr.delta_rule(*a, chunk_size=64), low, w)
    (_, want_g), ref_out = out_and_grads(recurrence, as_f32, w)
    assert out.dtype == jnp.bfloat16
    assert rel(out, ref_out) < 3e-2
    for name, a, b in zip("q k v g beta".split(), got_g, want_g):
        assert rel(a, b) < 3e-2, name


def test_a_decay_rounded_to_bfloat16_fails_the_float32_tolerance():
    """The tolerance sees a wrong precision where the module promises
    float32: ``g`` through bfloat16 moves the float32 result by far
    more than ``F32_TOL``."""
    args, _ = operands(128)
    rule = jit_once(lambda *a: dr.delta_rule(*a, chunk_size=64))
    rounded = (*args[:3], args[3].astype(jnp.bfloat16).astype(jnp.float32),
               args[4])
    assert rel(rule(*rounded), rule(*args)) > 10 * F32_TOL


def test_fewer_heads_go_through_together():
    """A sub-block's spans are Q x 16 x Dk float32 a head-chunk: a pass
    holds a quarter of the head-chunks a scalar decay's does."""
    assert dr.pick_rule(rows=4, seq=4096, key_heads=32, value_heads=32,
                        chunk_size=64, by_channel=True) == (64, 64, 0, 2)
    assert dr.pick_rule(rows=4, seq=4096, key_heads=32, value_heads=32,
                        chunk_size=64) == (64, 64, 0, 8)
    # the kernels take either decay at heads of whole lanes (a vector's
    # at a value head a key head), and neither at narrow heads
    q = jnp.zeros((1, 128, 2, 128), jnp.bfloat16)
    assert dr.fits(q, q, 64, jnp.zeros((1, 128, 2))) and dr.fits(q, q, 64)
    assert dr.fits(q, q, 64, jnp.zeros((1, 128, 2, 128)))
    assert not dr.fits(q[:, :, :1], q, 64, jnp.zeros((1, 128, 2, 128)))
    narrow = jnp.zeros((1, 128, 2, 16), jnp.bfloat16)
    assert not dr.fits(narrow, narrow, 64, jnp.zeros((1, 128, 2, 16)))
    assert not dr.fits(narrow, narrow, 64, jnp.zeros((1, 128, 2)))


def test_the_rule_runs_under_its_own_scope():
    def scopes(*a):
        text = jit_once(lambda *a: dr.delta_rule(*a, chunk_size=16)).lower(
            *a).as_text(debug_info=True)
        return {name for name in ("kda_rule", "delta_rule")
                if f"/{name}/" in text}

    args, _ = operands(32)
    assert scopes(*args) == {"kda_rule"}
    assert scopes(*args[:3], args[3][..., 0], args[4]) == {"delta_rule"}


def test_the_references_recurrence_is_the_scan():
    """``ref.recurrence`` (stretches of 128 positions, each a checkpoint)
    against the one scan above, on a row it pads."""
    args, _ = operands(37, key_heads=1)
    np.testing.assert_allclose(jit_once(ref.recurrence)(*args),
                               jit_once(recurrence)(*args), atol=1e-6)


# --- the mixer ---------------------------------------------------------------

SIZES = dict(num_heads=4, head_dim=8)
CFG = dict(kda_num_heads=4, kda_head_dim=8, norm_eps=1e-5)


@pytest.fixture(scope="module")
def mixer():
    p = weights.make_weights(jax.eval_shape(
        lambda: dr.kda_mixer_init(jax.random.key(0), 32, **SIZES)), 7)
    p["norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.key(1), (8,))
    a = jax.random.normal(jax.random.key(2), (2, 40, 32))
    return p, a, jax.random.normal(jax.random.key(3), a.shape)


def test_the_mixers_tree_is_the_published_one(mixer):
    p, _, _ = mixer
    assert {n: x["w"].shape for n, x in p.items() if "w" in x} == {
        "q": (32, 32), "k": (32, 32), "v": (32, 32), "out": (32, 32),
        "q_conv": (4, 32), "k_conv": (4, 32), "v_conv": (4, 32),
        "f_a": (32, 8), "f_b": (8, 32), "g_a": (32, 8), "g_b": (8, 32),
        "beta": (32, 4)}
    assert p["A_log"]["bias"].shape == (4,)        # a number a head
    assert p["dt"]["bias"].shape == (32,)          # a number a channel
    assert p["norm"]["scale"].shape == (8,)
    init = dr.kda_mixer_init(jax.random.key(0), 32, **SIZES)
    assert jax.tree.structure(init) == jax.tree.structure(p)
    assert bool((init["A_log"]["bias"] < np.log(16.0)).all())


def test_the_mixer_against_the_reference(mixer):
    p, a, w = mixer

    def program(p, a):
        return dr.kda_mixer_apply(p, a, **SIZES, chunk_size=16, eps=1e-5,
                                  policy=FP32)

    def reference(p, a):
        return ref.kda_mixer(p, a, CFG, "f32")

    got, got_g = jit_once(jax.value_and_grad(
        lambda p, a: (program(p, a) * w).sum(), argnums=(0, 1)))(p, a)
    want, want_g = jit_once(jax.value_and_grad(
        lambda p, a: (reference(p, a) * w).sum(), argnums=(0, 1)))(p, a)
    assert rel(jit_once(program)(p, a), jit_once(reference)(p, a)) < F32_TOL
    assert abs(got - want) < F32_TOL * abs(want) + 1e-6
    for (path, x), y in zip(
            jax.tree_util.tree_flatten_with_path(got_g)[0],
            jax.tree.leaves(want_g)):
        assert rel(x, y) < 10 * F32_TOL, jax.tree_util.keystr(path)


@pytest.mark.parametrize("change", [
    "f_b", "g_b", "beta", "A_log", "dt", "q_conv", "norm"])
def test_every_part_of_the_mixer_moves_it(mixer, change):
    p, a, _ = mixer
    want = ref.kda_mixer(p, a, CFG, "f32")
    other = jax.tree.map(lambda x: x, p)
    (name, leaf), = other[change].items()
    other[change][name] = leaf * 0.5 + 0.1
    assert rel(ref.kda_mixer(other, a, CFG, "f32"), want) > 1e-3
