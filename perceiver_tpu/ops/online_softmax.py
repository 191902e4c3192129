"""The shared online-softmax body of the Pallas attention kernels.

Ragged cross-attention (``ragged_attention``) and paged decode
attention (``paged_attention``) walk the kv axis block by block and
carry the same three VMEM accumulators: the running row max ``m``, the
running normalizer ``l``, and the unnormalized output accumulator
``acc`` (all fp32; m/l are stored lane-broadcast as ``(rows, 128)``
so the scratch tiles stay hardware-shaped). The rescale-and-
accumulate recurrence is identical across the three kv layouts —
only the score masking differs per kernel — so it lives here once
and each kernel supplies its own masked score block.

These helpers trace inside Pallas kernel bodies: arguments are
kernel refs, not arrays, and every statement must stay Mosaic-legal
(2D iota, lane-broadcast stats, ``preferred_element_type`` on dots).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perceiver_tpu.ops.chunked_attention import NEG_INF

__all__ = [
    "online_softmax_init",
    "online_softmax_update",
    "online_softmax_finish",
]


def online_softmax_init(m_ref, l_ref, acc_ref) -> None:
    """Reset the accumulators at the first kv block (``j == 0``)."""
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)


def online_softmax_update(s, vblk, m_ref, l_ref, acc_ref) -> None:
    """One kv-block step: fold the masked fp32 score block ``s``
    (rows = queries, cols = this block's kv positions) and its value
    block ``vblk`` into the running (m, l, acc) state. Fully-masked
    columns must carry ``NEG_INF`` in ``s`` — they then contribute
    ``exp(NEG_INF - m) == 0`` to both ``l`` and ``acc``."""
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def online_softmax_finish(m_ref, l_ref, acc_ref):
    """Normalize the accumulator at the last kv block. Rows that saw
    only masked columns have ``l == 0`` and normalize to exact zeros
    (the ragged/paged kernels rely on this for empty requests)."""
    return acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)
