"""Chunked and Pallas flash attention vs. the reference einsum path.

The Pallas kernel runs in interpreter mode on the CPU test backend —
the identical kernel body that compiles on TPU (SURVEY.md §4 plan (c)).
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

from perceiver_tpu.ops import mha_init, mha_apply
from perceiver_tpu.ops.chunked_attention import (
    chunked_attention,
    pad_mask_to_bias,
)
from perceiver_tpu.ops.pallas_attention import (
    flash_attention,
    flash_attention_channels,
)
from perceiver_tpu.ops.policy import Policy


def _reference_attention(q, k, v, bias=None, scale=None):
    """Materialized-softmax attention on (B, H, L, D) arrays."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        s = s + bias[:, None, None, :]
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w.astype(v.dtype), v)


def _qkv(key, b=2, h=2, lq=16, lk=100, d=24):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (b, h, lq, d)),
            jax.random.normal(kk, (b, h, lk, d)),
            jax.random.normal(kv, (b, h, lk, d)))


class TestChunked:
    def test_matches_reference(self):
        q, k, v = _qkv(jax.random.key(0))
        out = chunked_attention(q, k, v, chunk_size=32)
        np.testing.assert_allclose(out, _reference_attention(q, k, v),
                                   atol=1e-5, rtol=1e-5)

    def test_with_padding_mask(self):
        q, k, v = _qkv(jax.random.key(1))
        pad = jnp.arange(100)[None, :] >= jnp.array([70, 100])[:, None]
        bias = pad_mask_to_bias(pad)
        out = chunked_attention(q, k, v, bias=bias, chunk_size=17)
        ref = _reference_attention(q, k, v, bias=bias)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_gradients_match(self):
        q, k, v = _qkv(jax.random.key(2), lk=64)

        def loss_chunked(q, k, v):
            return chunked_attention(q, k, v, chunk_size=16).sum()

        def loss_ref(q, k, v):
            return _reference_attention(q, k, v).sum()

        g1 = jit_once(jax.grad(loss_chunked, argnums=(0, 1, 2)))(q, k, v)
        g2 = jit_once(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


class TestFlash:
    def test_matches_reference(self):
        q, k, v = _qkv(jax.random.key(3))
        out = flash_attention(q, k, v, block_q=8, block_k=64)
        np.testing.assert_allclose(out, _reference_attention(q, k, v),
                                   atol=1e-5, rtol=1e-5)

    def test_with_padding_mask(self):
        q, k, v = _qkv(jax.random.key(4))
        pad = jnp.arange(100)[None, :] >= jnp.array([70, 100])[:, None]
        bias = pad_mask_to_bias(pad)
        out = flash_attention(q, k, v, bias=bias, block_q=8, block_k=32)
        ref = _reference_attention(q, k, v, bias=bias)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_non_divisible_shapes(self):
        # Lq, Lk, D all off the tile grid → wrapper pads and slices.
        q, k, v = _qkv(jax.random.key(5), lq=13, lk=77, d=20)
        out = flash_attention(q, k, v, block_q=8, block_k=32)
        np.testing.assert_allclose(out, _reference_attention(q, k, v),
                                   atol=1e-5, rtol=1e-5)

    def test_gradients_match(self):
        q, k, v = _qkv(jax.random.key(6), lk=48)

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, block_q=8, block_k=16).sum()

        def loss_ref(q, k, v):
            return _reference_attention(q, k, v).sum()

        g1 = jit_once(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
        g2 = jit_once(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)

    def test_bias_gradient_matches(self):
        """A differentiable (learned) additive key bias must get the
        same gradient as the materialized-softmax path — the VJP must
        not silently zero it."""
        q, k, v = _qkv(jax.random.key(8), lk=48)
        bias0 = jnp.zeros((q.shape[0], k.shape[2]), jnp.float32)

        def loss_flash(b):
            return (flash_attention(q, k, v, bias=b, block_q=8,
                                    block_k=16) ** 2).sum()

        def loss_ref(b):
            return (_reference_attention(q, k, v, bias=b) ** 2).sum()

        g1 = jit_once(jax.grad(loss_flash))(bias0)
        g2 = jit_once(jax.grad(loss_ref))(bias0)
        assert float(jnp.abs(g1).max()) > 0
        np.testing.assert_allclose(g1, g2, atol=1e-4, rtol=1e-4)

    def test_under_jit(self):
        q, k, v = _qkv(jax.random.key(7))
        out = jit_once(lambda *a: flash_attention(*a, block_q=8,
                                                 block_k=64))(q, k, v)
        np.testing.assert_allclose(out, _reference_attention(q, k, v),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("h,d", [(8, 16), (2, 64)])
    def test_shared_lane_blocks_match_reference(self, h, d):
        """Eight heads of 16 and two of 64 share one 128-lane block;
        each must see its own lanes only, incl. with a pad mask and
        through the VJP."""
        q, k, v = _qkv(jax.random.key(9), h=h, lq=32, lk=96, d=d)
        pad = jnp.arange(96)[None, :] >= jnp.array([80, 96])[:, None]
        bias = pad_mask_to_bias(pad)
        out = flash_attention(q, k, v, bias=bias, block_q=16, block_k=32)
        ref = _reference_attention(q, k, v, bias=bias)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

        def loss(q, k, v):
            return (flash_attention(q, k, v, bias=bias, block_q=16,
                                    block_k=32) ** 2).sum()

        def loss_ref(q, k, v):
            return (_reference_attention(q, k, v, bias=bias) ** 2).sum()

        g1 = jit_once(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        g2 = jit_once(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("h,d", [(2, 16), (3, 64), (2, 192)],
                             ids=["two_of_16", "three_of_64",
                                  "two_of_192"])
    def test_heads_off_the_lane_grid_match_reference(self, h, d):
        """Head counts a 128-lane block does not divide, and a head
        dim that is no multiple of 128, are zero-padded to whole lanes
        a head — numerics must hold there too."""
        q, k, v = _qkv(jax.random.key(13), h=h, lq=32, lk=64, d=d)
        out = flash_attention(q, k, v, block_q=16, block_k=32)
        np.testing.assert_allclose(out, _reference_attention(q, k, v),
                                   atol=1e-5, rtol=1e-5)

    def test_skinny_heads_bf16(self):
        """bf16 through a block of eight 16-wide heads."""
        q, k, v = (x.astype(jnp.bfloat16) for x in
                   _qkv(jax.random.key(10), h=8, lq=32, lk=64, d=16))
        out = flash_attention(q, k, v, block_q=16, block_k=32)
        ref = _reference_attention(q.astype(jnp.float32),
                                   k.astype(jnp.float32),
                                   v.astype(jnp.float32))
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(out.astype(jnp.float32), ref,
                                   atol=2e-2, rtol=2e-2)


class TestMhaImpls:
    """All three impls agree through the full projected MHA op."""

    @pytest.mark.parametrize("impl", ["chunked", "flash"])
    def test_impl_matches_einsum(self, impl):
        key = jax.random.key(8)
        params = mha_init(key, q_dim=32, num_heads=4, k_dim=48, v_dim=48)
        policy = Policy.fp32()
        q = jax.random.normal(jax.random.key(9), (2, 10, 32))
        kv = jax.random.normal(jax.random.key(10), (2, 50, 48))
        pad = jnp.arange(50)[None, :] >= jnp.array([35, 50])[:, None]
        ref = mha_apply(params, q, kv, kv, num_heads=4,
                        key_padding_mask=pad, policy=policy)
        out = mha_apply(params, q, kv, kv, num_heads=4,
                        key_padding_mask=pad, policy=policy,
                        impl=impl, kv_chunk_size=16)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_attn_mask_rejected(self):
        params = mha_init(jax.random.key(0), q_dim=16, num_heads=2)
        x = jnp.zeros((1, 4, 16))
        mask = jnp.zeros((4, 4), bool)
        with pytest.raises(NotImplementedError):
            mha_apply(params, x, x, x, num_heads=2, attn_mask=mask,
                      impl="chunked")

    def test_dropout_degrades_to_chunked(self):
        """dropout>0 on the flash impl degrades to the chunked path
        (which streams attention-weight dropout exactly) with a
        one-time warning, instead of raising (VERDICT r5 item 7)."""
        import perceiver_tpu.ops.attention as attn_mod

        params = mha_init(jax.random.key(0), q_dim=16, num_heads=2)
        x = jax.random.normal(jax.random.key(2), (1, 8, 16))
        rng = jax.random.key(1)
        attn_mod._DROPOUT_DEGRADE_WARNED.clear()
        with pytest.warns(UserWarning, match="falling back"):
            out = mha_apply(params, x, x, x, num_heads=2,
                            dropout_rate=0.1, deterministic=False,
                            rng=rng, impl="flash", kv_chunk_size=4)
        ref = mha_apply(params, x, x, x, num_heads=2, dropout_rate=0.1,
                        deterministic=False, rng=rng, impl="chunked",
                        kv_chunk_size=4)
        np.testing.assert_allclose(out, ref, atol=1e-6, rtol=1e-6)
        # the warning fires once per impl per process
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            mha_apply(params, x, x, x, num_heads=2, dropout_rate=0.1,
                      deterministic=False, rng=rng, impl="flash",
                      kv_chunk_size=4)
        # deterministic (eval) calls keep the flash kernel — no dropout
        # is applied, so nothing to degrade for
        mha_apply(params, x, x, x, num_heads=2, dropout_rate=0.1,
                  deterministic=True, impl="flash", kv_chunk_size=4)

    def test_dropout_plus_flash_warns_at_config_time(self):
        """--model.dropout>0 with a non-dropout-capable impl constructs
        fine (the impl degrades to chunked at trace time) but warns
        when the task config is built, so the degrade is visible before
        the first trace."""
        import perceiver_tpu.ops.attention as attn_mod

        from perceiver_tpu.tasks.image import ImageClassifierTask
        attn_mod._DROPOUT_DEGRADE_WARNED.clear()
        with pytest.warns(UserWarning, match="falling back"):
            ImageClassifierTask(image_shape=(28, 28, 1), num_classes=10,
                                dropout=0.1, attention_impl="flash")
        # dropout-capable impls construct silently
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            ImageClassifierTask(image_shape=(28, 28, 1), num_classes=10,
                                dropout=0.1, attention_impl="chunked")


class TestDropoutTracesUnderEveryImpl:
    """A dropout>0 config must trace a train step under EVERY
    attention impl (VERDICT r5 item 7): the non-dropout-capable
    kernels degrade to chunked instead of raising mid-trace."""

    def _tiny_task(self, impl, decoder_impl=None):
        from perceiver_tpu.tasks import MaskedLanguageModelTask

        return MaskedLanguageModelTask(
            vocab_size=96, max_seq_len=32, num_latents=8,
            num_latent_channels=16, num_encoder_layers=1,
            num_encoder_self_attention_layers_per_block=1,
            num_encoder_cross_attention_heads=2,
            num_encoder_self_attention_heads=2,
            num_decoder_cross_attention_heads=2, dropout=0.1,
            attention_impl=impl, decoder_attention_impl=decoder_impl,
            kv_chunk_size=16, loss_impl="dense")

    @pytest.mark.parametrize("impl", [None, "einsum", "chunked",
                                      "flash", "seqpar", "ring",
                                      "ulysses"])
    def test_train_step_traces(self, impl):
        import perceiver_tpu.ops.attention as attn_mod

        from perceiver_tpu.ops.policy import Policy

        attn_mod._DROPOUT_DEGRADE_WARNED.clear()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", UserWarning)
            task = self._tiny_task(impl, decoder_impl="flash")
            if impl in ("seqpar", "ring", "ulysses"):
                from perceiver_tpu.parallel import make_mesh
                model = task.build(mesh=make_mesh(
                    8, seq_parallel=2, model_parallel=1))
            else:
                model = task.build()
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(0)
        batch = {
            "input_ids": jnp.asarray(
                rng.integers(3, 96, (2, 32)), jnp.int32),
            "pad_mask": jnp.zeros((2, 32), bool),
        }

        def step(p):
            def loss_fn(p):
                loss, _ = task.loss_and_metrics(
                    model, p, batch, rng=jax.random.key(3),
                    deterministic=False, policy=Policy.fp32())
                return loss

            return jax.value_and_grad(loss_fn)(p)

        # trace + lower (no compile/run: the degrade fires at trace
        # time, which is where the old NotImplementedError lived)
        jit_once(step).lower(params)


class TestChunkedDropout:
    """Streamed attention dropout in the chunked impl: exact vs. the
    materialized construction with the identical per-chunk masks."""

    def _masked_reference(self, q, k, v, rng, rate, chunk):
        """softmax → apply the SAME per-chunk bernoulli masks →  @ v."""
        scale = 1.0 / (q.shape[-1] ** 0.5)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
        w = jax.nn.softmax(s, axis=-1)
        lk = k.shape[2]
        keeps = []
        for ci in range(lk // chunk):
            dk = jax.random.fold_in(rng, ci)
            keeps.append(jax.random.bernoulli(
                dk, 1.0 - rate, (*w.shape[:3], chunk)))
        keep = jnp.concatenate(keeps, axis=-1)
        w = jnp.where(keep, w / (1.0 - rate), 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", w.astype(v.dtype), v)

    def test_dropout_matches_materialized_masking(self):
        q, k, v = _qkv(jax.random.key(5), lk=96)
        rng = jax.random.key(42)
        out = chunked_attention(q, k, v, chunk_size=32,
                                dropout_rate=0.3, rng=rng)
        ref = self._masked_reference(q, k, v, rng, 0.3, 32)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_dropout_mean_preserved(self):
        """E[dropped attention] == undropped attention (1/(1-p) scaling),
        checked loosely over many independent masks."""
        q, k, v = _qkv(jax.random.key(6), b=1, h=1, lq=4, lk=32, d=8)
        base = chunked_attention(q, k, v, chunk_size=16)
        one = jit_once(lambda r: chunked_attention(
            q, k, v, chunk_size=16, dropout_rate=0.2, rng=r))
        outs = jax.vmap(one)(jax.random.split(jax.random.key(0), 200))
        np.testing.assert_allclose(jnp.mean(outs, axis=0), base, atol=0.08)

    def test_mha_chunked_dropout_accepted_and_differs(self):
        params = mha_init(jax.random.key(0), q_dim=16, num_heads=2)
        x = jax.random.normal(jax.random.key(1), (2, 8, 16))
        out_det = mha_apply(params, x, x, x, num_heads=2, impl="chunked")
        out_drop = mha_apply(params, x, x, x, num_heads=2, impl="chunked",
                             dropout_rate=0.5, deterministic=False,
                             rng=jax.random.key(2))
        assert out_drop.shape == out_det.shape
        assert not np.allclose(out_drop, out_det)

    def test_dropout_gradients_flow(self):
        q, k, v = _qkv(jax.random.key(7), lk=32)

        def loss(q, k, v):
            return chunked_attention(q, k, v, chunk_size=16,
                                     dropout_rate=0.2,
                                     rng=jax.random.key(3)).sum()

        grads = jit_once(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        for g in grads:
            assert jnp.all(jnp.isfinite(g))
            assert jnp.any(g != 0)


class TestQueryChunking:
    def test_q_chunked_matches_reference(self):
        q, k, v = _qkv(jax.random.key(11), lq=37, lk=64)
        out = chunked_attention(q, k, v, chunk_size=16, q_chunk_size=8)
        np.testing.assert_allclose(out, _reference_attention(q, k, v),
                                   atol=1e-5, rtol=1e-5)

    def test_q_chunked_gradients(self):
        q, k, v = _qkv(jax.random.key(12), lq=24, lk=32)

        def loss_a(q, k, v):
            return chunked_attention(q, k, v, chunk_size=8,
                                     q_chunk_size=8).sum()

        def loss_b(q, k, v):
            return _reference_attention(q, k, v).sum()

        g1 = jit_once(jax.grad(loss_a, argnums=(0, 1, 2)))(q, k, v)
        g2 = jit_once(jax.grad(loss_b, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


# --- the fused core against the materialised one ------------------------------

# the two shape families of the benchmark's cells, scaled down:
# latent self-attention (Lq == Lk, head dim 64) and the image
# cross-attention (Lq << Lk, head dim 128, Lk off the key block);
# blocks small enough that queries and keys both take several
FAMILIES = {
    "self_d64": dict(lq=256, lk=256, d=64, block_q=128, block_k=128),
    "cross_d128": dict(lq=128, lk=300, d=128, block_q=128, block_k=128),
}
# valid key counts per batch row: none masked; ragged padding; the
# whole last key block of row 0 padded
KEY_LENGTHS = {"nobias": None, "padded": (0.7, 1.0), "padded_tail": (0.4, 1.0)}
FUSED_CASES = [(f, m, t) for f in FAMILIES for m in KEY_LENGTHS
               for t in ("bfloat16", "float32")]


def _fused_case(family, masking, dtype):
    from perceiver_tpu.ops.attention import _sdpa_core

    cfg = FAMILIES[family]
    lq, lk, d = cfg["lq"], cfg["lk"], cfg["d"]
    kq, kk, kv, kg = jax.random.split(jax.random.key(17), 4)
    # (B, L, H, D), as mha_apply holds the heads
    q, k, v, g = (jax.random.normal(kx, (2, length, 2, d)).astype(dtype)
                  for kx, length in ((kq, lq), (kk, lk), (kv, lk),
                                     (kg, lq)))
    bias = None
    if KEY_LENGTHS[masking] is not None:
        lengths = jnp.asarray([int(f * lk) for f in KEY_LENGTHS[masking]])
        bias = pad_mask_to_bias(jnp.arange(lk)[None, :] >= lengths[:, None])
    scale = 1.0 / d ** 0.5

    def fused(q, k, v):
        # heads side by side on the channel axis, as the model hands
        # them over
        out = flash_attention_channels(
            *(x.reshape(*x.shape[:2], -1) for x in (q, k, v)), num_heads=2,
            bias=bias, scale=scale, block_q=cfg["block_q"],
            block_k=cfg["block_k"])
        return out.reshape(q.shape)

    def materialised(q, k, v):
        b4 = None if bias is None else bias[:, None, None, :]
        return _sdpa_core(scale, 0.0, jnp.float32, q, k, v, b4, None)

    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    return fused, materialised, (q, k, v), g, tol


@pytest.mark.parametrize("family,masking,dtype", FUSED_CASES)
def test_fused_output_matches_materialised_core(family, masking, dtype):
    fused, materialised, qkv, _, tol = _fused_case(family, masking, dtype)
    out, ref = fused(*qkv), materialised(*qkv)
    assert out.dtype == ref.dtype and out.shape == ref.shape
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("family,masking,dtype", FUSED_CASES)
def test_fused_gradients_match_materialised_core(family, masking, dtype):
    """dq, dk, dv of the kernel backward against ``_sdpa_bwd``, under
    one random cotangent."""
    fused, materialised, qkv, g, tol = _fused_case(family, masking, dtype)
    grads = jit_once(lambda *a: jax.vjp(fused, *a)[1](g))(*qkv)
    refs = jit_once(lambda *a: jax.vjp(materialised, *a)[1](g))(*qkv)
    for name, a, b in zip("qkv", grads, refs):
        assert a.dtype == b.dtype, name
        scale = float(jnp.abs(b.astype(jnp.float32)).max())
        np.testing.assert_allclose(
            a.astype(jnp.float32), b.astype(jnp.float32),
            atol=tol * max(scale, 1.0), rtol=tol, err_msg=f"d{name}")


def _pallas_calls(jaxpr):
    """Every pallas_call equation of a jaxpr, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub))
    return found


@pytest.mark.parametrize("h,d", [(8, 16), (2, 64)],
                         ids=["eight_heads_a_block", "two_heads_a_block"])
def test_forward_only_use_emits_no_residual(h, d):
    """Inference pays for one output: the log-sum-exp row leaves the
    kernel only under differentiation, where the backward kernel (one
    call for dq, dk and dv) reads it."""
    q, k, v = _qkv(jax.random.key(21), h=h, lq=128, lk=128, d=d)

    def f(q, k, v):
        return flash_attention(q, k, v)

    (fwd,) = _pallas_calls(jax.make_jaxpr(f)(q, k, v).jaxpr)
    assert len(fwd.outvars) == 1
    calls = _pallas_calls(jax.make_jaxpr(
        lambda *a: jax.vjp(f, *a)[1](jnp.ones_like(q)))(q, k, v).jaxpr)
    assert [len(c.outvars) for c in calls] == [2, 3]
    assert [c.params["name"] for c in calls] == ["flash_attention_fwd",
                                                 "flash_attention_bwd"]
