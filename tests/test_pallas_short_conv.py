"""The short convolution's Pallas kernels (``ops/pallas_short_conv.py``),
interpreted on the CPU, against ``l2_norm(silu(causal_conv(...)))`` as the
mixers always had it: outputs and the gradients to ``x``, ``w`` and the
bias, at float32 (to rounding) and at bfloat16 (no further from the
float32 function than XLA's operations are); with and without a bias; no
channel normed, all, and a leading part; rows of several tiles of
positions, so the positions read before and after a tile cross its edge
both ways, and rows no tile divides; a row's first positions; what
``fits`` says, what the tally says, and that where it says no the
mixers' lowered text is the parent's."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

from perceiver_tpu.ops import delta_rule, ssm
from perceiver_tpu.ops import pallas_short_conv as kernels
from perceiver_tpu.ops.delta_rule import l2_norm
from perceiver_tpu.ops.policy import Policy
from perceiver_tpu.ops.ssm import causal_conv
from perceiver_tpu.parallel import make_mesh

FP32 = Policy.fp32()
HEAD = 128
# float32 against float32: sums in another order (measured 1e-6)
ROUNDING = 2e-5

#: ``(channels, scaled, normed)``: no channel normed; all of them, and
#: scaled (a query); a query's, a key's and a value's side by side
KINDS = {"plain": (256, 0, 0), "all_normed": (256, 256, 0),
         "q_k_not_v": (384, 128, 128)}


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """Tiles of 32 positions and 128 channels: a row of 80 is three
    tiles, the last padded."""
    monkeypatch.setattr(kernels, "_POSITIONS", 32)
    monkeypatch.setattr(kernels, "_CHANNELS", 128)


def xla_form(params, x, scaled, normed):
    """The mixers' lines before the kernels, flat: (B, S, C)."""
    y = jax.nn.silu(causal_conv(params, x))
    q, k, v = jnp.split(y, [scaled, scaled + normed], axis=-1)

    def heads(part):
        return part.reshape(*part.shape[:2], -1, HEAD)

    q = (l2_norm(heads(q)) / math.sqrt(HEAD)).astype(y.dtype)
    k = l2_norm(heads(k)).astype(y.dtype)
    return jnp.concatenate([q.reshape(*y.shape[:2], -1),
                            k.reshape(*y.shape[:2], -1), v], axis=-1)


def fused_form(params, x, scaled, normed, first=0):
    """The kernels' parts side by side again: (B, S, C)."""
    return jnp.concatenate(kernels.fused_short_conv(
        params, x, head_dim=HEAD, scaled=scaled, normed=normed, first=first,
        interpret=True), axis=-1)


def operands(seq, channels, dtype, bias, rows=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = {"w": jax.random.normal(keys[1], (4, channels)) * 0.5}
    if bias:
        params["bias"] = jax.random.normal(keys[2], (channels,)) * 0.3
    return (params,
            jax.random.normal(keys[0], (rows, seq, channels)).astype(dtype),
            jax.random.normal(keys[3], (rows, seq, channels)))


def out_and_grads(form, params, x, ct, scaled, normed):
    def loss(params, x):
        out = form(params, x, scaled, normed)
        return (out.astype(jnp.float32) * ct).sum(), out

    (_, out), (dp, dx) = jax.value_and_grad(loss, (0, 1), has_aux=True)(
        params, x)
    return {"out": out, "dx": dx, **{f"d{k}": v for k, v in dp.items()}}


def rel(a, b):
    a, b = (np.asarray(v, np.float32) for v in (a, b))
    return float(np.abs(a - b).max() / np.abs(b).max())


CASES = [(dtype, kind, bias, 80)       # three tiles of 32, the last padded
         for dtype in ("float32", "bfloat16") for kind in KINDS
         for bias in (False, True)] + [
    ("float32", "q_k_not_v", False, 64),      # two whole tiles
    ("bfloat16", "q_k_not_v", True, 64)]


@pytest.mark.parametrize(
    "dtype,kind,bias,seq", CASES,
    ids=[f"{d}-{k}-{'bias' if b else 'no_bias'}-{s}" for d, k, b, s in CASES])
def test_outputs_and_gradients_against_xla(dtype, kind, bias, seq):
    """float32: XLA's operations to rounding. bfloat16: the kernels
    round where XLA's operations round or at fewer places, so against
    the float32 function of the same bfloat16 ``x`` they are no further
    off."""
    channels, scaled, normed = KINDS[kind]
    params, x, ct = operands(seq, channels, jnp.dtype(dtype), bias)
    xla = out_and_grads(xla_form, params, x, ct, scaled, normed)
    got = out_and_grads(fused_form, params, x, ct, scaled, normed)
    assert sorted(got) == sorted(xla)
    exact = xla if dtype == "float32" else out_and_grads(
        xla_form, params, x.astype(jnp.float32), ct, scaled, normed)
    for name in xla:
        assert got[name].shape == xla[name].shape
        assert got[name].dtype == xla[name].dtype
        assert rel(got[name], exact[name]) <= (
            ROUNDING if dtype == "float32"
            else 1.05 * rel(xla[name], exact[name]) + 1e-6), name


def test_a_rows_first_positions_read_zeros_not_the_row_before():
    """Two rows in one call against each row alone, and nothing of row
    1 moves with row 0's last positions, forward or backward."""
    channels, scaled, normed = KINDS["q_k_not_v"]
    params, x, ct = operands(48, channels, jnp.float32, True)
    both = fused_form(params, x, scaled, normed)
    for row in range(2):
        alone = fused_form(params, x[row:row + 1], scaled, normed)
        np.testing.assert_allclose(both[row], alone[0], rtol=1e-6, atol=1e-6)
    moved = fused_form(params, x.at[0, -3:].add(7.0), scaled, normed)
    np.testing.assert_array_equal(moved[1], both[1])
    assert not np.array_equal(moved[0, -3:], both[0, -3:])
    dx = jax.grad(lambda x: (fused_form(params, x, scaled, normed)[1]
                             * ct[1]).sum())(x)
    assert not np.asarray(dx[0]).any() and np.asarray(dx[1, :3]).all()


def test_a_walk_longer_than_a_sublane_tile(monkeypatch):
    """Walks of 32 positions in tiles of 64: the sums a sublane add up
    four vregs a walk."""
    monkeypatch.setattr(kernels, "_POSITIONS", 64)
    monkeypatch.setattr(kernels, "_ROWS", 32)
    channels, scaled, normed = KINDS["q_k_not_v"]
    params, x, ct = operands(150, channels, jnp.float32, True, rows=1)
    want = out_and_grads(xla_form, params, x, ct, scaled, normed)
    got = out_and_grads(fused_form, params, x, ct, scaled, normed)
    for name in want:
        assert rel(got[name], want[name]) < ROUNDING, name


# --- which form a call takes -------------------------------------------------


def test_fits_reads_backend_mesh_dtype_and_shape(monkeypatch):
    x = jnp.zeros((2, 64, 256), jnp.bfloat16)
    assert kernels.fits(x, 4, 256) == "backend"        # the CPU tests' path
    monkeypatch.setattr(kernels, "_backend", lambda: "tpu")
    assert kernels.fits(x, 4, 256) == ""
    assert kernels.fits(x, 4, 256, head_dim=128, scaled=128, normed=128) == ""
    assert kernels.fits(x, 4, 128, 128) == ""      # the second half of x
    assert kernels.fits(x.astype(jnp.float16), 4, 256) == "dtype"
    for why, call in {
            "nine taps": lambda: kernels.fits(x, 9, 256),
            "half a vreg": lambda: kernels.fits(x[..., :192], 4, 192),
            "starts half a vreg in": lambda: kernels.fits(x, 4, 128, 64),
            "runs past x": lambda: kernels.fits(x, 4, 256, 128),
            "a head of 96": lambda: kernels.fits(
                jnp.zeros((2, 64, 384)), 4, 384, head_dim=96, normed=192),
            "half a head scaled": lambda: kernels.fits(
                x, 4, 256, head_dim=256, scaled=128, normed=128),
            "normed, no head": lambda: kernels.fits(x, 4, 256, normed=128),
            "parts of another sum": lambda: kernels.fits(
                x, 4, 256, rest=(128, 64)),
    }.items():
        assert call() == "shape", why

    sharded = jax.device_put(x, jax.NamedSharding(
        make_mesh(2), jax.sharding.PartitionSpec("data")))
    seen = []
    jit_once(lambda x: seen.append(kernels.fits(x, 4, 256)) or x)(sharded)
    assert seen == ["mesh"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_channels_read_where_they_lie_in_a_wider_array(dtype):
    """A convolution over the middle 384 of 640 channels (tiles of 128:
    three tiles in): the parts are those of the slice, and the gradient
    to the wider array is the slice's, zeros beside it."""
    channels, scaled, normed = KINDS["q_k_not_v"]
    params, x, ct = operands(80, channels, dtype, True)
    wide = jnp.concatenate([x[..., :128] * 3, x, x[..., :128] - 1], axis=-1)

    def loss(form, *args):
        return lambda p, x: (form(p, x, scaled, normed, *args).astype(
            jnp.float32) * ct).sum()

    cut = fused_form(params, x, scaled, normed)
    there = fused_form(params, wide, scaled, normed, 128)
    np.testing.assert_array_equal(there, cut)
    (dp, dx), (dp_wide, dx_wide) = (
        jax.grad(fn, (0, 1))(params, arg) for fn, arg in (
            (loss(fused_form), x), (loss(fused_form, 128), wide)))
    assert dx_wide.shape == wide.shape and dx_wide.dtype == wide.dtype
    np.testing.assert_array_equal(dx_wide[..., 128:-128], dx)
    assert not np.asarray(dx_wide[..., :128], np.float32).any()
    assert not np.asarray(dx_wide[..., -128:], np.float32).any()
    for name in dp:
        np.testing.assert_array_equal(dp_wide[name], dp[name])


def parts_of(convs, x, **kwargs):
    with kernels.conv_paths.counting() as counts:
        parts = kernels.short_conv(convs, x, **kwargs)
    (label,) = counts
    return parts, label


@pytest.mark.parametrize("own", [False, True],
                         ids=["one_convolution", "one_a_part"])
def test_short_conv_hands_back_the_same_parts_either_way(monkeypatch, own):
    """The function the mixers call: by heads where a part is normed
    (and everywhere where each part has its own convolution), on the
    kernels as on XLA's operations, and the tally says which ran."""
    params, x, _ = operands(40, 384, jnp.float32, False)
    convs = [{"w": w} for w in jnp.split(params["w"], 3, axis=-1)] if own \
        else [params]
    sizes = dict(head_dim=HEAD, scaled=128, normed=128)
    xla, label = parts_of(convs, x, **sizes)
    assert label == "xla[384ch, norm 256, backend]"
    monkeypatch.setattr(kernels, "_backend", lambda: "tpu")
    fused, label = parts_of(convs, x, **sizes)
    assert label == "fused[384ch, norm 256]"
    assert [p.shape for p in xla] == [p.shape for p in fused] == [
        (2, 40, 1, HEAD), (2, 40, 1, HEAD),
        (2, 40, 1, HEAD) if own else (2, 40, HEAD)]
    for a, b in zip(fused, xla):
        assert rel(a, b) < ROUNDING
    flat = xla_form(params, x, 128, 128)
    for a, b in zip(xla, jnp.split(flat, 3, axis=-1)):
        np.testing.assert_array_equal(a.reshape(b.shape), b)

    (whole,), label = parts_of([params], x)
    assert label == "fused[384ch]" and whole.shape == x.shape
    # ... and told where a caller's slice was cut from, reads it there
    wide = jnp.concatenate([x[..., :128], x], axis=-1)
    there, label = parts_of(convs, wide[..., 128:], cut_from=(wide, 128),
                            **sizes)
    assert label == "fused[384ch, norm 256]"
    for a, b in zip(there, fused):
        np.testing.assert_array_equal(a, b)
    apart, label = parts_of([params], x, rest=(128, 256))
    assert label == "fused[384ch]"
    for a, b in zip(apart, jnp.split(whole, [128], axis=-1)):
        np.testing.assert_array_equal(a, b)
    (whole,), label = parts_of([params], x, head_dim=HEAD, scaled=384)
    assert label == "fused[384ch, norm]" and whole.shape == (2, 40, 3, HEAD)
    _, label = parts_of([{"w": params["w"][:, :192]}], x[..., :192])
    assert label == "xla[192ch, shape]"


# --- where fits says no, the mixers are the parent's programs ----------------


def parent_short_conv(convs, x, *, head_dim=0, scaled=0, normed=0, rest=None,
                      cut_from=None):
    """The three call sites' lines as the parent commit had them
    (``delta_mixer_apply``, ``kda_mixer_apply``, ``ssm_mixer_apply``),
    copied."""
    rows, seq, _ = x.shape
    if len(convs) == 3:
        heads = (rows, seq, scaled // head_dim, head_dim)
        q, k, v = (
            jax.nn.silu(causal_conv(conv, part)).reshape(heads)
            for conv, part in zip(convs, jnp.split(x, 3, axis=-1)))
        q = (l2_norm(q) / math.sqrt(head_dim)).astype(x.dtype)
        k = l2_norm(k).astype(x.dtype)
        return q, k, v
    if not scaled:
        inner, bc, _ = rest
        xbc = jax.nn.silu(causal_conv(convs[0], x))
        x, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
        return x, b, c
    key_dim, num_key_heads, key_head_dim = scaled, scaled // head_dim, head_dim
    qkv = jax.nn.silu(causal_conv(convs[0], x))
    q, k, v = jnp.split(qkv, [key_dim, 2 * key_dim], axis=-1)
    q = (l2_norm(q.reshape(rows, seq, num_key_heads, key_head_dim))
         / math.sqrt(key_head_dim)).astype(qkv.dtype)
    k = l2_norm(k.reshape(rows, seq, num_key_heads, key_head_dim)).astype(
        qkv.dtype)
    return q, k, v


MIXERS = {
    "delta_mixer": (
        delta_rule, delta_rule.delta_mixer_init, delta_rule.delta_mixer_apply,
        dict(num_key_heads=2, num_value_heads=4, key_head_dim=16,
             value_head_dim=16)),
    "kda_mixer": (
        delta_rule, delta_rule.kda_mixer_init, delta_rule.kda_mixer_apply,
        dict(num_heads=2, head_dim=16)),
    "ssm_mixer": (
        ssm, ssm.ssm_mixer_init, ssm.ssm_mixer_apply,
        dict(num_heads=4, head_dim=8, n_groups=2, state_size=16)),
}


@pytest.mark.parametrize("where", ["cpu_backend", "two_device_mesh"])
@pytest.mark.parametrize("mixer", list(MIXERS))
def test_where_fits_says_no_the_mixers_text_is_the_parents(monkeypatch,
                                                           mixer, where):
    module, init, apply, sizes = MIXERS[mixer]
    params = init(jax.random.PRNGKey(0), 32, **sizes)
    u = jnp.zeros((2, 24, 32), jnp.float32)
    if where == "two_device_mesh":
        # as a TPU would see it: the mesh is what says no
        monkeypatch.setattr(kernels, "_backend", lambda: "tpu")
        u = jax.device_put(u, jax.NamedSharding(
            make_mesh(2), jax.sharding.PartitionSpec("data")))

    def lowered():
        def step(params, u):
            return apply(params, u, policy=FP32, **sizes)

        with kernels.conv_paths.counting() as counts:
            text = jit_once(step).lower(params, u).as_text()
        return text, list(counts)

    text, labels = lowered()
    assert all(label.startswith("xla[") and label.endswith(
        ", mesh]" if where == "two_device_mesh" else ", backend]")
        for label in labels) and labels
    monkeypatch.setattr(module, "short_conv", parent_short_conv)
    parents, none = lowered()
    assert not none and "tpu_custom_call" not in text
    assert text == parents
