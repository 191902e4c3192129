"""Sharded (pjit/GSPMD) canonical train step.

The single-device train step (``analysis/targets.make_train_step``)
becomes an SPMD program by declaring shardings, not by rewriting
math: params take the tensor-parallel layout from
``parallel/sharding.param_spec``, optimizer moments take the
ZeRO-style layout from ``parallel/sharding.zero_sharding`` (no device
holds a full copy of any large moment), and the batch splits over the
``data`` axis. GSPMD inserts the gradient all-reduces and
tensor-parallel collectives at compile time; the shardcheck passes
(``analysis/shardcheck``) then gate what it inserted — bytes moved per
mesh axis, no large replicated residents, per-shard HBM.

Every ``jax.jit`` here carries explicit ``in_shardings`` /
``out_shardings``: silent propagation is how replication sneaks in,
and the ``unsharded-pjit`` lint rule enforces exactly that on this
module.
"""

from __future__ import annotations

from functools import partial

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perceiver_tpu.parallel.sharding import param_sharding, zero_sharding


def sharded_batch_sharding(batch, mesh: Mesh):
    """Leading-axis (data-parallel) shardings for a batch pytree."""
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P("data")), batch)


def make_sharded_train_step(task, batch, mesh: Mesh):
    """The canonical pjit optimizer step over a data×model mesh:
    forward + backward + AdamW with (params, opt_state) donated, every
    argument and result under an explicit sharding. Returns
    ``(jitted_fn, args)`` with the same calling convention as
    ``make_train_step`` so ``analysis/targets.lower_target`` treats
    both uniformly."""
    import optax

    from perceiver_tpu.obs.trace import device_scope
    from perceiver_tpu.ops.policy import Policy

    model = task.build()
    policy = Policy.bf16()
    params = model.init(jax.random.key(0))
    tx = optax.adamw(1e-3)
    opt_state = tx.init(params)

    p_shard = param_sharding(params, mesh)
    o_shard = zero_sharding(opt_state, mesh)
    b_shard = sharded_batch_sharding(batch, mesh)
    replicated = NamedSharding(mesh, P())

    @partial(jax.jit,
             in_shardings=(p_shard, o_shard, b_shard, replicated),
             out_shardings=(p_shard, o_shard, replicated),
             donate_argnums=(0, 1))
    def train_step(params, opt_state, batch_i, key):
        def loss_fn(p):
            loss, _ = task.loss_and_metrics(
                model, p, batch_i, rng=key, deterministic=False,
                policy=policy)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        with device_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

    return train_step, (params, opt_state, batch, jax.random.key(1))
