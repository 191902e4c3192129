"""All-to-all (DeepSpeed-Ulysses-style) sequence-parallel attention.

The second of the two first-class long-context strategies (the other is
``parallel.ring_attention``): instead of streaming k/v blocks around a
ring, one ``all_to_all`` re-shards the attention inputs from
sequence-sharded to **head**-sharded, each device runs ordinary dense
attention for its ``H/N`` heads over the FULL sequence, and a second
``all_to_all`` restores sequence sharding. The reference has no analog
(SURVEY §5 long-context: none); this is the TPU-native construction —
both transposes are single XLA collectives riding ICI.

Trade-offs vs the ring (why both exist):

- Ulysses moves q, k, v, out exactly once each (4·B·L·H·D/N words per
  device) in two bursts; the ring moves k/v ``N-1`` times in ``N-1``
  overlappable neighbor hops. For self-attention with plenty of heads,
  Ulysses usually wins on step latency; the ring wins when ``H < N``,
  when k/v ≫ q (decoder-style), or when overlap hides the hops.
- Ulysses needs ``H % N == 0`` (head-count divisible by the axis);
  the ring has no head constraint.
- Peak memory: Ulysses holds full-sequence k/v for H/N heads
  (O(B·H/N·L·D)); the ring never materializes more than one k/v block
  (O(B·H·L/N·D)).

Shapes follow the module family convention: per-device inside
``shard_map`` q/k/v are ``(B, H, L/N, D)``; bias is the additive fp32
key bias ``(B, Lk/N)`` (``pad_mask_to_bias`` convention).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from perceiver_tpu.ops.chunked_attention import chunked_attention


def ulysses_attention(q, k, v, *, axis_name: str,
                      bias: Optional[jax.Array] = None,
                      scale: Optional[float] = None,
                      kv_chunk_size: int = 1024):
    """Exact attention with q/k/v sequence-sharded over ``axis_name``.

    Call inside shard_map. Two ``all_to_all``s re-shard heads↔sequence;
    the local softmax streams kv in ``kv_chunk_size`` blocks
    (``ops.chunked_attention``), so per-device peak memory stays
    O(B · H/N · L · D) + O(L · chunk) rather than the quadratic score
    matrix.
    """
    n = jax.lax.axis_size(axis_name)
    b, h, lq_loc, d = q.shape
    if h % n != 0:
        raise ValueError(
            f"ulysses needs num_heads {h} divisible by axis size {n}; "
            "use ring_attention otherwise")

    if n > 1:
        # (B, H, L/N, D) → (B, H/N, L, D): split heads, gather sequence
        a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                                split_axis=1, concat_axis=2, tiled=True)
        q, k, v = a2a(q), a2a(k), a2a(v)
        if bias is not None:
            bias = jax.lax.all_gather(bias, axis_name, axis=1, tiled=True)

    out = chunked_attention(q, k, v, bias=bias, scale=scale,
                            chunk_size=kv_chunk_size)

    if n > 1:
        # (B, H/N, L, D) → (B, H, L/N, D): restore sequence sharding
        out = jax.lax.all_to_all(out, axis_name=axis_name, split_axis=2,
                                 concat_axis=1, tiled=True)
    return out


def make_ulysses_attention(mesh: Mesh, seq_axis: str = "data", *,
                           batch_axis: Optional[str] = None,
                           scale: Optional[float] = None,
                           kv_chunk_size: int = 1024):
    """shard_map-wrapped Ulysses attention over ``mesh``.

    Returns ``f(q, k, v, bias=None) -> out`` taking GLOBAL arrays
    ``(B, H, L, D)`` with the sequence axis sharded over ``seq_axis``
    (and optionally batch over ``batch_axis``), mirroring
    ``make_ring_attention``.
    """
    bspec = batch_axis
    qspec = P(bspec, None, seq_axis, None)
    bias_spec = P(bspec, seq_axis)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(qspec, qspec, qspec, bias_spec),
        out_specs=qspec, check_vma=False)
    def _a2a(q, k, v, bias):
        return ulysses_attention(q, k, v, axis_name=seq_axis, bias=bias,
                                 scale=scale, kv_chunk_size=kv_chunk_size)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(qspec, qspec, qspec),
        out_specs=qspec, check_vma=False)
    def _a2a_nobias(q, k, v):
        return ulysses_attention(q, k, v, axis_name=seq_axis, scale=scale,
                                 kv_chunk_size=kv_chunk_size)

    def f(q, k, v, bias=None):
        if bias is None:
            return _a2a_nobias(q, k, v)
        return _a2a(q, k, v, bias)

    return f
