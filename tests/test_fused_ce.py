"""Fused / packed linear+CE vs the dense reference computation.

All three MLM loss implementations must produce the same loss value and
the same parameter gradients (SURVEY.md §4 golden-value strategy): the
fused path only changes the order of reduction (chunked fp32 sums), and
the packed path drops rows whose loss weight is exactly zero — which
contribute neither loss nor gradient in the dense computation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

from perceiver_tpu.ops.fused_ce import (
    fused_linear_cross_entropy,
    pack_positions,
)
from perceiver_tpu.ops.linear import linear_init, linear_apply
from perceiver_tpu.ops.policy import Policy
from perceiver_tpu.tasks import MaskedLanguageModelTask
from perceiver_tpu.tasks.base import cross_entropy

POLICY = Policy.fp32()


def _dense_loss(params, hidden, labels, weight):
    logits = linear_apply(params, hidden, policy=POLICY)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.clip(labels, 0)[:, None], 1)[:, 0]
    return (nll * weight).sum() / jnp.maximum(weight.sum(), 1.0)


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(3)
    n, c, v = 96, 16, 53
    params = linear_init(jax.random.key(0), c, v)
    hidden = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, n), jnp.int32)
    weight = jnp.asarray(rng.random(n) < 0.2, jnp.float32)
    return params, hidden, labels, weight


def test_fused_matches_dense(problem):
    params, hidden, labels, weight = problem
    dense, gd = jit_once(jax.value_and_grad(_dense_loss))(
        params, hidden, labels, weight)
    fused, gf = jit_once(jax.value_and_grad(
        lambda p: fused_linear_cross_entropy(p, hidden, labels, weight,
                                             chunk_size=32, policy=POLICY)
    ))(params)
    np.testing.assert_allclose(dense, fused, rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
                 gd, gf)


def test_fused_pads_ragged_chunks(problem):
    params, hidden, labels, weight = problem
    dense = _dense_loss(params, hidden, labels, weight)
    fused = fused_linear_cross_entropy(params, hidden, labels, weight,
                                       chunk_size=40, policy=POLICY)
    np.testing.assert_allclose(dense, fused, rtol=1e-6)


def test_packed_matches_dense(problem):
    params, hidden, labels, weight = problem

    def packed_loss(p):
        h, y, w, _ = pack_positions(hidden, labels, weight, capacity=48)
        return fused_linear_cross_entropy(p, h, y, w, chunk_size=16,
                                          policy=POLICY)

    dense, gd = jit_once(jax.value_and_grad(_dense_loss))(
        params, hidden, labels, weight)
    packed, gp = jit_once(jax.value_and_grad(packed_loss))(params)
    np.testing.assert_allclose(dense, packed, rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
                 gd, gp)


def test_pack_positions_drops_overflow():
    hidden = jnp.ones((8, 4))
    labels = jnp.arange(8, dtype=jnp.int32)
    weight = jnp.ones(8)
    h, y, w, overflow = pack_positions(hidden, labels, weight, capacity=4)
    assert h.shape == (4, 4) and w.sum() == 4
    np.testing.assert_array_equal(y, jnp.arange(4))
    assert int(overflow) == 4  # the dropped rows are counted, not silent


def test_pack_positions_overflow_zero_when_fits():
    weight = jnp.asarray([1.0, 0.0, 1.0, 0.0])
    _, _, _, overflow = pack_positions(jnp.ones((4, 2)),
                                       jnp.zeros(4, jnp.int32), weight,
                                       capacity=2)
    assert int(overflow) == 0
    # and with no contributing rows at all
    _, _, _, overflow = pack_positions(jnp.ones((4, 2)),
                                       jnp.zeros(4, jnp.int32),
                                       jnp.zeros(4), capacity=2)
    assert int(overflow) == 0


def test_mlm_task_reports_overflow_at_small_batch():
    """VERDICT r2 #6: small-B·M debug runs near the capacity boundary
    must surface packed-CE overflow via the metrics dict (and the
    counter must be exact), not corrupt the loss invisibly."""
    task = MaskedLanguageModelTask(
        vocab_size=64, max_seq_len=24, num_latents=8,
        num_latent_channels=16, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_encoder_cross_attention_heads=2,
        num_encoder_self_attention_heads=2,
        num_decoder_cross_attention_heads=2, loss_impl="packed",
        ce_chunk_size=32, packed_capacity=0.01)  # force overflow
    model = task.build()
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {
        "input_ids": jnp.asarray(rng.integers(3, 64, (4, 24)), jnp.int32),
        "pad_mask": jnp.zeros((4, 24), bool),
    }
    loss, metrics = jit_once(lambda p, b: task.loss_and_metrics(
        model, p, b, rng=jax.random.key(7), deterministic=True,
        policy=POLICY))(params, batch)
    assert "ce_overflow" in metrics
    assert int(metrics["ce_overflow"]) > 0
    assert np.isfinite(float(loss))

    # the default (6σ-margin) capacity must report zero overflow
    task_ok = MaskedLanguageModelTask(
        vocab_size=64, max_seq_len=24, num_latents=8,
        num_latent_channels=16, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_encoder_cross_attention_heads=2,
        num_encoder_self_attention_heads=2,
        num_decoder_cross_attention_heads=2, loss_impl="packed",
        ce_chunk_size=32)
    _, metrics = jit_once(lambda p, b: task_ok.loss_and_metrics(
        model, p, b, rng=jax.random.key(7), deterministic=True,
        policy=POLICY))(params, batch)
    assert int(metrics["ce_overflow"]) == 0


def test_hidden_grad_matches(problem):
    """Gradient w.r.t. hidden states (what flows into the decoder)."""
    params, hidden, labels, weight = problem

    def packed_loss(h):
        hp, y, w, _ = pack_positions(h, labels, weight, capacity=64)
        return fused_linear_cross_entropy(params, hp, y, w, chunk_size=32,
                                          policy=POLICY)

    gd = jit_once(jax.grad(
        _dense_loss, argnums=1))(params, hidden, labels, weight)
    gp = jit_once(jax.grad(packed_loss))(hidden)
    np.testing.assert_allclose(gd, gp, atol=1e-6)


@pytest.mark.parametrize("impl", ["fused", "packed"])
def test_mlm_task_loss_impls_agree(impl):
    """End-to-end: the task loss is identical across implementations."""

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

    dense, other = task_loss("dense"), task_loss(impl)
    assert np.isfinite(dense)
    np.testing.assert_allclose(other, dense, rtol=1e-6)
