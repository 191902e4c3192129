"""Pallas fused linear+CE vs the dense oracle (interpreter mode on CPU).

Mirrors tests/test_fused_ce.py: the kernel must reproduce the dense
computation's loss AND all three gradients (hidden, W, b) — including
padded/ragged shapes and zero-weight rows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

from perceiver_tpu.ops.linear import linear_init
from perceiver_tpu.ops.pallas_ce import pallas_linear_cross_entropy
from perceiver_tpu.ops.policy import Policy

from tests.test_fused_ce import _dense_loss

POLICY = Policy.fp32()


def _problem(n=96, c=16, v=53, seed=3):
    rng = np.random.default_rng(seed)
    params = linear_init(jax.random.key(0), c, v)
    hidden = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, n), jnp.int32)
    weight = jnp.asarray(rng.random(n) < 0.2, jnp.float32)
    return params, hidden, labels, weight


@pytest.mark.parametrize("shape", [(96, 16, 53), (64, 8, 300), (40, 24, 130)])
def test_matches_dense_loss_and_grads(shape):
    n, c, v = shape
    params, hidden, labels, weight = _problem(n, c, v)

    def pallas_loss(p, h):
        return pallas_linear_cross_entropy(
            p, h, labels, weight, block_n=32, block_v=128, policy=POLICY)

    dense, (gd_p, gd_h) = jit_once(jax.value_and_grad(
        lambda p, h: _dense_loss(p, h, labels, weight),
        argnums=(0, 1)))(params, hidden)
    fused, (gp_p, gp_h) = jit_once(jax.value_and_grad(
        pallas_loss, argnums=(0, 1)))(params, hidden)

    np.testing.assert_allclose(dense, fused, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gd_h), np.asarray(gp_h),
                               atol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5),
        gd_p, gp_p)


def test_all_weights_zero_is_finite():
    params, hidden, labels, _ = _problem()
    loss = pallas_linear_cross_entropy(
        params, hidden, labels, jnp.zeros(hidden.shape[0]),
        block_n=32, block_v=128, policy=POLICY)
    assert np.isfinite(float(loss)) and float(loss) == 0.0


def test_under_jit_and_grad():
    params, hidden, labels, weight = _problem()

    @jax.jit     # nested under the next line's: the options are the outer's
    def f(p):
        return pallas_linear_cross_entropy(
            p, hidden, labels, weight, block_n=32, block_v=128,
            policy=POLICY)

    g = jit_once(jax.grad(f))(params)
    assert np.isfinite(float(f(params)))
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(g))


def test_mlm_task_pallas_impl_matches_dense():
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    def task_loss(impl):
        task = MaskedLanguageModelTask(
            vocab_size=64, max_seq_len=24, num_latents=8,
            num_latent_channels=16, num_encoder_layers=2,
            num_encoder_self_attention_layers_per_block=2,
            num_encoder_cross_attention_heads=2,
            num_encoder_self_attention_heads=2,
            num_decoder_cross_attention_heads=2, loss_impl=impl,
            ce_chunk_size=32)
        model = task.build()
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(0)
        batch = {
            "input_ids": jnp.asarray(rng.integers(3, 64, (4, 24)),
                                     jnp.int32),
            "pad_mask": jnp.asarray(rng.random((4, 24)) < 0.1),
            "valid": jnp.asarray([True, True, True, False]),
        }
        loss, _ = jit_once(lambda p, b: task.loss_and_metrics(
            model, p, b, rng=jax.random.key(7), deterministic=True,
            policy=POLICY))(params, batch)
        return float(loss)

    np.testing.assert_allclose(task_loss("pallas"), task_loss("dense"),
                               rtol=1e-5)
