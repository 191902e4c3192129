"""Ring attention & sequence-parallel attention over a mesh axis.

The reference has no long-context machinery (SURVEY §5: its answer to
long inputs is the Perceiver latent bottleneck itself). This module
adds the TPU-native long-context layer the brief requires as
first-class: exact softmax attention over sequences sharded across a
mesh axis, with cross-device traffic riding ICI.

Two entry points, both meant to run *inside* ``shard_map`` over a
``jax.sharding.Mesh`` axis (each function sees per-device shards and
uses named-axis collectives):

- ``ring_attention(q, k, v, axis_name=...)`` — q, k, v are all sharded
  along their sequence axes. Each of the ``N`` devices holds a q-shard
  and streams all N k/v-shards through in a ring: compute one block of
  the online-softmax recurrence (Rabe & Staats / FlashAttention), then
  ``lax.ppermute`` the k/v (+ key-bias) block to the next device.
  Peak memory per device is O(Lq/N · Lk/N); the k/v rotation overlaps
  with compute and crosses only neighbor ICI links. This is the
  self-attention path for the long-sequence MLM config
  (BASELINE.md configs[4], seq 2048 on a v5p-16 mesh).

- ``seq_parallel_cross_attention(q, k, v, axis_name=...)`` — q is
  *replicated* (the Perceiver latent array: small), k/v are sharded
  along the input sequence. A ring would make every device redo the
  same full computation, so instead each device attends its local k/v
  block only, producing partial ``(m, l, acc)`` softmax statistics,
  which are combined exactly with one ``pmax`` + two ``psum``s. This
  is the sequence-parallel form of the encoder's cross-attention
  (reference ``model.py:150-160``) for inputs too long for one chip
  (e.g. the 262,144-pixel LArTPC inputs, ``run.py:79``).

Both compute *exact* attention — the block recurrence is algebraically
identical to one softmax over the full key axis. Key-padding masks are
carried as additive fp32 biases over keys (same convention as
``perceiver_tpu.ops.chunked_attention.pad_mask_to_bias``).

Shapes (per device, inside shard_map): q ``(B, H, Lq, D)``,
k/v ``(B, H, Lk, D)``, bias ``(B, Lk)``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from perceiver_tpu.ops.chunked_attention import (
    NEG_INF,
    finalize_softmax,
    fold_block,
)


def _init_stats(b, h, lq, d):
    return (jnp.full((b, h, lq, 1), NEG_INF, jnp.float32),
            jnp.zeros((b, h, lq, 1), jnp.float32),
            jnp.zeros((b, h, lq, d), jnp.float32))


def ring_attention(q, k, v, *, axis_name: str,
                   bias: Optional[jax.Array] = None,
                   scale: Optional[float] = None):
    """Exact attention with q/k/v sharded over ``axis_name``.

    Call inside shard_map. Each device computes its q-shard's attention
    over the FULL key sequence by rotating k/v (+ bias) around the ring
    one hop per step with ``lax.ppermute``.
    """
    n = jax.lax.axis_size(axis_name)
    b, h, lq, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    perm = [(i, (i + 1) % n) for i in range(n)]

    # Fold the resident block first, then (n-1) × (rotate, fold) — the
    # final rotation that would return each block home is never sent.
    m, l, acc = fold_block(q, k, v, bias, scale, *_init_stats(b, h, lq, d))
    if n == 1:
        return finalize_softmax(l, acc, q.dtype)

    def body(carry, _):
        m, l, acc, k_blk, v_blk, b_blk = carry
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        if b_blk is not None:
            b_blk = jax.lax.ppermute(b_blk, axis_name, perm)
        m, l, acc = fold_block(q, k_blk, v_blk, b_blk, scale, m, l, acc)
        return (m, l, acc, k_blk, v_blk, b_blk), None

    (m, l, acc, _, _, _), _ = jax.lax.scan(
        body, (m, l, acc, k, v, bias), None, length=n - 1)
    return finalize_softmax(l, acc, q.dtype)


def seq_parallel_cross_attention(q, k, v, *, axis_name: str,
                                 bias: Optional[jax.Array] = None,
                                 scale: Optional[float] = None):
    """Exact cross-attention with q replicated, k/v sharded over
    ``axis_name``. Call inside shard_map.

    Each device folds only its local k/v block, then the partial
    softmax statistics are combined across the axis:
    ``m_g = pmax(m)``; ``l_g = psum(l · exp(m − m_g))``;
    ``acc_g = psum(acc · exp(m − m_g))``; output ``acc_g / l_g``.
    One max-reduce plus two sum-reduces over ICI, each sized by the
    (small) query array — no k/v ever moves.
    """
    b, h, lq, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    m, l, acc = fold_block(q, k, v, bias, scale, *_init_stats(b, h, lq, d))

    # The global max is a pure numerical-stability shift — the combined
    # softmax is invariant to it, so its gradient is exactly zero.
    # stop_gradient makes that explicit (pmax has no differentiation
    # rule), keeping the whole combine differentiable for training.
    m_g = jax.lax.pmax(jax.lax.stop_gradient(m), axis_name)
    corr = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * corr, axis_name)
    acc_g = jax.lax.psum(acc * corr, axis_name)
    return finalize_softmax(l_g, acc_g, q.dtype)


def make_ring_attention(mesh: Mesh, seq_axis: str = "data", *,
                        batch_axis: Optional[str] = None,
                        scale: Optional[float] = None):
    """shard_map-wrapped ring attention over ``mesh``.

    Returns ``f(q, k, v, bias=None) -> out`` taking GLOBAL arrays
    ``(B, H, L, D)`` with the sequence axis sharded over ``seq_axis``
    (and optionally batch over ``batch_axis``).
    """
    bspec = batch_axis
    qspec = P(bspec, None, seq_axis, None)
    bias_spec = P(bspec, seq_axis)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(qspec, qspec, qspec, bias_spec),
        out_specs=qspec, check_vma=False)
    def _ring(q, k, v, bias):
        return ring_attention(q, k, v, axis_name=seq_axis, bias=bias,
                              scale=scale)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(qspec, qspec, qspec),
        out_specs=qspec, check_vma=False)
    def _ring_nobias(q, k, v):
        return ring_attention(q, k, v, axis_name=seq_axis, scale=scale)

    def f(q, k, v, bias=None):
        if bias is None:
            return _ring_nobias(q, k, v)
        return _ring(q, k, v, bias)

    return f


def make_seq_parallel_cross_attention(mesh: Mesh, seq_axis: str = "data", *,
                                      batch_axis: Optional[str] = None,
                                      scale: Optional[float] = None):
    """shard_map-wrapped sequence-parallel cross-attention over ``mesh``.

    Returns ``f(q, k, v, bias=None) -> out`` for GLOBAL arrays: q
    ``(B, H, Lq, D)`` replicated along ``seq_axis``, k/v ``(B, H, Lk,
    D)`` with Lk sharded over ``seq_axis``. Output is replicated along
    ``seq_axis`` (every device gets the full attended latents).
    """
    bspec = batch_axis
    kv_spec = P(bspec, None, seq_axis, None)
    q_spec = P(bspec, None, None, None)
    bias_spec = P(bspec, seq_axis)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, bias_spec),
        out_specs=q_spec, check_vma=False)
    def _xattn(q, k, v, bias):
        return seq_parallel_cross_attention(
            q, k, v, axis_name=seq_axis, bias=bias, scale=scale)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec, check_vma=False)
    def _xattn_nobias(q, k, v):
        return seq_parallel_cross_attention(
            q, k, v, axis_name=seq_axis, scale=scale)

    def f(q, k, v, bias=None):
        if bias is None:
            return _xattn_nobias(q, k, v)
        return _xattn(q, k, v, bias)

    return f
