"""Unit tests for the tensor core (perceiver_tpu.ops)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perceiver_tpu.ops import (
    Policy,
    linear_init,
    linear_apply,
    layer_norm_init,
    layer_norm_apply,
    mlp_init,
    mlp_apply,
    mha_init,
    mha_apply,
    cross_attention_init,
    cross_attention_apply,
    self_attention_init,
    self_attention_apply,
)

FP32 = Policy.fp32()


def test_linear_shapes_and_init_bounds():
    p = linear_init(jax.random.key(0), 16, 32)
    assert p["w"].shape == (16, 32) and p["b"].shape == (32,)
    bound = 1 / np.sqrt(16)
    assert np.all(np.abs(p["w"]) <= bound)
    y = linear_apply(p, jnp.ones((2, 5, 16)), policy=FP32)
    assert y.shape == (2, 5, 32)


def test_layer_norm_matches_numpy():
    p = layer_norm_init(8)
    x = jax.random.normal(jax.random.key(1), (4, 8))
    y = layer_norm_apply(p, x, policy=FP32)
    xn = np.asarray(x)
    expected = (xn - xn.mean(-1, keepdims=True)) / np.sqrt(
        xn.var(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(np.asarray(y), expected, atol=1e-5)


def test_mlp_hidden_width_equals_channels():
    # Reference model.py:20-26 — no 4x expansion.
    p = mlp_init(jax.random.key(0), 12)
    assert p["fc1"]["w"].shape == (12, 12)
    y = mlp_apply(p, jnp.ones((2, 3, 12)), policy=FP32)
    assert y.shape == (2, 3, 12)


def test_mha_init_matches_torch_fan_math():
    """torch xavier-inits the PACKED (3E, E) in_proj in the symmetric
    case — bound sqrt(6/4E) — but each matrix separately (bound from
    its own fans) in the asymmetric case (VERDICT r3 weak #5)."""
    import math

    e = 64
    p = mha_init(jax.random.key(0), q_dim=e, num_heads=8)
    packed_bound = math.sqrt(6.0 / (4 * e))
    for name in ("q", "k", "v"):
        w = p[name]["w"]
        assert float(jnp.abs(w).max()) <= packed_bound + 1e-6, name
        # and it genuinely fills the packed range (not the 2x-smaller
        # per-matrix bound misread as packed)
        assert float(jnp.abs(w).max()) > 0.8 * packed_bound, name

    pa = mha_init(jax.random.key(0), q_dim=e, num_heads=8, k_dim=32,
                  v_dim=48)
    for name, fan_in in (("q", e), ("k", 32), ("v", 48)):
        w = pa[name]["w"]
        sep_bound = math.sqrt(6.0 / (fan_in + e))
        assert float(jnp.abs(w).max()) <= sep_bound + 1e-6, name
        assert float(jnp.abs(w).max()) > 0.8 * sep_bound, name


def test_mha_bf16_backward_has_no_fp32_dots():
    """Under the bf16 policy EVERY attention matmul — including the
    QK backward pair fed by the fp32 softmax cotangent — must run with
    bf16 operands (the TPU executes fp32 dots at a fraction of the
    bf16 MXU rate; the dot audit of analysis/hlo.py found the backward
    pair at ~9% of headline-step FLOPs before the _qk_dot fix)."""
    import re

    from perceiver_tpu.ops.policy import Policy

    p = mha_init(jax.random.key(0), q_dim=32, num_heads=4)
    q = jax.random.normal(jax.random.key(1), (2, 8, 32))
    kv = jax.random.normal(jax.random.key(2), (2, 16, 32))
    bf16 = Policy.bf16()

    def check_no_f32_dots(impl):
        def loss(params, q, kv):
            return mha_apply(params, q, kv, kv, num_heads=4, impl=impl,
                             policy=bf16).astype(jnp.float32).sum()

        text = jax.jit(jax.grad(loss)).lower(p, q, kv).as_text()
        bad = []
        for ln in text.splitlines():
            if "stablehlo.dot_general" not in ln:
                continue
            ops = re.search(r": \(tensor<([^>]+)>, tensor<([^>]+)>\)",
                            ln)
            assert ops is not None, ln
            if "f32" in ops.group(1) or "f32" in ops.group(2):
                bad.append(ln.strip()[:160])
        assert not bad, (impl, bad[:3])
        return loss

    loss = check_no_f32_dots("einsum")
    check_no_f32_dots("chunked")

    # and the bf16 grads stay close to the fp32-policy reference
    fp32 = Policy.fp32()

    def loss32(params, q, kv):
        return mha_apply(params, q, kv, kv, num_heads=4,
                         policy=fp32).sum()

    g16 = jax.grad(loss)(p, q, kv)
    g32 = jax.grad(loss32)(p, q, kv)
    for name in ("q", "k", "v"):
        a, b = g16[name]["w"], g32[name]["w"]
        denom = float(jnp.abs(b).max()) + 1e-9
        assert float(jnp.abs(a - b).max()) / denom < 5e-2, name


def test_mha_output_shape_asymmetric_kv():
    p = mha_init(jax.random.key(0), q_dim=32, num_heads=4, k_dim=131,
                 v_dim=131)
    q = jax.random.normal(jax.random.key(1), (2, 7, 32))
    kv = jax.random.normal(jax.random.key(2), (2, 50, 131))
    y = mha_apply(p, q, kv, kv, num_heads=4, policy=FP32)
    assert y.shape == (2, 7, 32)


def test_mha_key_padding_mask_blocks_positions():
    """Masked kv positions must not influence the output."""
    p = mha_init(jax.random.key(0), q_dim=16, num_heads=2)
    q = jax.random.normal(jax.random.key(1), (1, 3, 16))
    kv = jax.random.normal(jax.random.key(2), (1, 6, 16))
    mask = jnp.array([[False, False, False, True, True, True]])

    y1 = mha_apply(p, q, kv, kv, num_heads=2, key_padding_mask=mask,
                   policy=FP32)
    # Perturb the masked positions wildly; output must be unchanged.
    kv2 = kv.at[:, 3:].set(100.0)
    y2 = mha_apply(p, q, kv2, kv2, num_heads=2, key_padding_mask=mask,
                   policy=FP32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    # And must differ from the unmasked result.
    y3 = mha_apply(p, q, kv, kv, num_heads=2, policy=FP32)
    assert not np.allclose(np.asarray(y1), np.asarray(y3), atol=1e-3)


def test_mha_additive_and_boolean_attn_mask_agree():
    p = mha_init(jax.random.key(0), q_dim=16, num_heads=2)
    x = jax.random.normal(jax.random.key(1), (2, 5, 16))
    bool_mask = jnp.triu(jnp.ones((5, 5), bool), k=1)
    add_mask = jnp.where(bool_mask, -1e30, 0.0)
    y1 = mha_apply(p, x, x, x, num_heads=2, attn_mask=bool_mask, policy=FP32)
    y2 = mha_apply(p, x, x, x, num_heads=2, attn_mask=add_mask, policy=FP32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-6)


def test_mha_matches_torch_multihead_attention():
    """Numerical parity with torch nn.MultiheadAttention (the op the
    reference wraps, model.py:59-74), including asymmetric kdim/vdim
    and key_padding_mask."""
    torch = pytest.importorskip("torch")

    q_dim, kv_dim, heads, lq, lk, b = 32, 48, 4, 5, 11, 3
    tm = torch.nn.MultiheadAttention(embed_dim=q_dim, num_heads=heads,
                                     kdim=kv_dim, vdim=kv_dim,
                                     batch_first=True)
    tm.eval()

    params = {
        "q": {"w": jnp.asarray(tm.q_proj_weight.detach().numpy().T),
              "b": jnp.asarray(tm.in_proj_bias.detach().numpy()[:q_dim])},
        "k": {"w": jnp.asarray(tm.k_proj_weight.detach().numpy().T),
              "b": jnp.asarray(
                  tm.in_proj_bias.detach().numpy()[q_dim:2 * q_dim])},
        "v": {"w": jnp.asarray(tm.v_proj_weight.detach().numpy().T),
              "b": jnp.asarray(
                  tm.in_proj_bias.detach().numpy()[2 * q_dim:])},
        "out": {"w": jnp.asarray(tm.out_proj.weight.detach().numpy().T),
                "b": jnp.asarray(tm.out_proj.bias.detach().numpy())},
    }

    rng = np.random.default_rng(0)
    q = rng.standard_normal((b, lq, q_dim), dtype=np.float32)
    kv = rng.standard_normal((b, lk, kv_dim), dtype=np.float32)
    pad = np.zeros((b, lk), dtype=bool)
    pad[:, -3:] = True

    with torch.no_grad():
        expected, _ = tm(torch.from_numpy(q), torch.from_numpy(kv),
                         torch.from_numpy(kv),
                         key_padding_mask=torch.from_numpy(pad))

    got = mha_apply(params, jnp.asarray(q), jnp.asarray(kv), jnp.asarray(kv),
                    num_heads=heads, key_padding_mask=jnp.asarray(pad),
                    policy=FP32)
    np.testing.assert_allclose(np.asarray(got), expected.numpy(), atol=2e-5)


def test_cross_attention_prenorm_and_shapes():
    p = cross_attention_init(jax.random.key(0), num_q_channels=64,
                             num_kv_channels=131, num_heads=4)
    xq = jax.random.normal(jax.random.key(1), (2, 32, 64))
    xkv = jax.random.normal(jax.random.key(2), (2, 784, 131))
    y = cross_attention_apply(p, xq, xkv, num_heads=4, policy=FP32)
    assert y.shape == (2, 32, 64)


def test_self_attention_shapes():
    p = self_attention_init(jax.random.key(0), num_channels=64, num_heads=4)
    x = jax.random.normal(jax.random.key(1), (2, 32, 64))
    y = self_attention_apply(p, x, num_heads=4, policy=FP32)
    assert y.shape == (2, 32, 64)


def test_bf16_policy_close_to_fp32():
    p = mha_init(jax.random.key(0), q_dim=32, num_heads=4)
    x = jax.random.normal(jax.random.key(1), (2, 8, 32))
    y32 = mha_apply(p, x, x, x, num_heads=4, policy=FP32)
    ybf = mha_apply(p, x, x, x, num_heads=4, policy=Policy.bf16())
    assert ybf.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(y32),
                               np.asarray(ybf, dtype=np.float32),
                               atol=0.1)


def test_packed_qkv_matches_separate_projections():
    """The self-attention packed in-proj (q is k is v) must equal the
    three-matmul path bit-for-bit up to dtype rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from perceiver_tpu.ops.attention import mha_init, mha_apply
    from perceiver_tpu.ops.policy import Policy

    params = mha_init(jax.random.key(0), 32, 4)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 10, 32)),
                    jnp.float32)
    packed = mha_apply(params, x, x, x, num_heads=4, policy=Policy.fp32())
    separate = mha_apply(params, x, x + 0.0, x + 0.0, num_heads=4,
                         policy=Policy.fp32())
    np.testing.assert_allclose(np.asarray(packed), np.asarray(separate),
                               rtol=1e-6, atol=1e-6)
