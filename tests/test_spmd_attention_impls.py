"""Model-integrated shard_map attention impls vs the einsum baseline.

The encoder's cross-attention can run as a shard_map kernel over a
mesh ("seqpar"/"ring"/"ulysses"); the result must match the plain
einsum single-device computation — same params, same rng, same loss.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

from perceiver_tpu.parallel import make_mesh
from perceiver_tpu.tasks import MaskedLanguageModelTask
from perceiver_tpu.ops.policy import Policy

POLICY = Policy.fp32()


def _task(impl=None):
    return MaskedLanguageModelTask(
        vocab_size=96, max_seq_len=32, num_latents=8,
        num_latent_channels=16, num_encoder_layers=2,
        num_encoder_self_attention_layers_per_block=2,
        num_encoder_cross_attention_heads=2,
        num_encoder_self_attention_heads=2,
        num_decoder_cross_attention_heads=2,
        attention_impl=impl, loss_impl="dense")


def _batch(b=4, l=32):
    rng = np.random.default_rng(0)
    return {
        "input_ids": jnp.asarray(rng.integers(3, 96, (b, l)), jnp.int32),
        "pad_mask": jnp.asarray(rng.random((b, l)) < 0.2),
    }


def _loss(task, model, batch):
    params = model.init(jax.random.key(0))
    loss, _ = jit_once(lambda p, b: task.loss_and_metrics(
        model, p, b, rng=jax.random.key(7), deterministic=True,
        policy=POLICY))(params, batch)
    return float(loss)


@pytest.mark.parametrize("impl,seq_parallel", [
    ("seqpar", 4),
    ("ring", 4),
    # ulysses re-shards heads over the seq axis, so the axis size must
    # divide the 2 cross-attention heads
    ("ulysses", 2),
])
def test_matches_einsum_baseline(impl, seq_parallel):
    mesh = make_mesh(8, seq_parallel=seq_parallel, model_parallel=1)
    baseline = _loss(_task(), _task().build(), _batch())
    task = _task(impl)
    got = _loss(task, task.build(mesh=mesh), _batch())
    np.testing.assert_allclose(got, baseline, rtol=2e-5)


def test_spmd_impl_requires_seq_axis():
    task = _task("seqpar")
    with pytest.raises(ValueError, match="seq"):
        task.build()  # no mesh
    with pytest.raises(ValueError, match="seq"):
        task.build(mesh=make_mesh(8))  # mesh without a seq axis


def test_full_train_step_under_jit():
    """grad + AdamW through the shard_map path compiles and runs."""
    import optax

    mesh = make_mesh(8, seq_parallel=2, model_parallel=2)
    task = _task("seqpar")
    model = task.build(mesh=mesh)
    params = model.init(jax.random.key(0))
    tx = optax.adamw(1e-3)
    opt_state = tx.init(params)
    batch = _batch()

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            loss, _ = task.loss_and_metrics(
                model, p, batch, rng=jax.random.key(3),
                deterministic=True, policy=POLICY)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    with mesh:
        _, _, loss = step(params, opt_state)
    assert np.isfinite(float(loss))
