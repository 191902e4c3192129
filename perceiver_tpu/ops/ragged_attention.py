"""Ragged (padding-free) attention Pallas kernels for packed serving.

The padded serve path pays for every pad token twice per layer: the
encoder cross-attends ``B·S_bucket`` key positions and the decoder
projects ``B·S_bucket`` query rows, where ``S_bucket`` is the bucket
width — on a mixed-length batch most of that is padding (PAPERS:
"Ragged Paged Attention"). The packed path instead concatenates the
requests into one token axis of length ``T = Σ lengths`` and carries
``(row_offsets, lengths)`` sidecars; these kernels make the two
cross-attention directions ragged-aware so cross-request attention and
padded tails contribute **zero** work:

- :func:`ragged_cross_attention` — encoder direction. Per-request
  latent queries ``(R, H, N, D)`` attend the packed token kv
  ``(H, T, D)``. Extends the ``pallas_attention`` flash layout with a
  ``PrefetchScalarGridSpec``: the scalar-prefetched offset/length
  arrays drive the kv-block index map, so each request streams only
  the ``ceil(max_len/block_k)+1`` kv blocks its own span touches
  (clamped block indices repeat a block, which the pipeline elides);
  an in-kernel column mask handles the unaligned span edges. Online
  softmax (m/l/acc in VMEM scratch) exactly as in the flash kernel.
- :func:`ragged_decode_attention` — decoder direction. Packed-token
  queries ``(H, T, D)`` attend their OWN request's latents out of the
  flattened ``(H, R·N, D)`` latent kv, via a block-diagonal mask from
  the per-token ``rows`` array. ``R·N`` is small (latents), so one
  single-pass fp32 softmax per query block suffices — no scan axis.

Both kernels are forward-only (serving), compute their dots on the
input dtype (bf16 under the serve policy) with fp32 accumulation via
``preferred_element_type``, and run in Pallas interpreter mode on
non-TPU backends like the existing kernels, so CPU tests exercise the
identical code path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_tpu.ops.chunked_attention import NEG_INF
from perceiver_tpu.ops.online_softmax import (
    online_softmax_finish,
    online_softmax_init,
    online_softmax_update,
)
from perceiver_tpu.ops.tiling import round_up as _round_up
from perceiver_tpu.utils.platform import resolve_interpret


# --- encoder direction: per-request latent q, ragged packed kv ---------------


def _ragged_cross_kernel(offs_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale: float,
                         block_k: int, nk: int):
    r = pl.program_id(0)
    j = pl.program_id(2)
    start = offs_ref[r]
    length = lens_ref[r]
    end = start + length
    first = start // block_k
    last = jnp.maximum(first, (end - 1) // block_k)
    kb = jnp.minimum(first + j, last)

    @pl.when(j == 0)
    def _():
        online_softmax_init(m_ref, l_ref, acc_ref)

    # steps past the request's own block span are replays of the
    # clamped last block — skip them; zero-length rows do no work at
    # all (their output is the zero acc, normalized by max(l, eps))
    @pl.when((j <= last - first) & (length > 0))
    def _():
        q = q_ref[0, 0]    # (Nqp, Dp)
        kblk = k_ref[0]    # (block_k, Dp)
        vblk = v_ref[0]
        s = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        # mask columns outside [start, end): the unaligned edges of
        # this request's span within the block, and every foreign token
        col = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = s + jnp.where((col >= start) & (col < end), 0.0, NEG_INF)
        online_softmax_update(s, vblk, m_ref, l_ref, acc_ref)

    @pl.when(j == nk - 1)
    def _():
        o_ref[0, 0] = online_softmax_finish(
            m_ref, l_ref, acc_ref).astype(o_ref.dtype)


def ragged_cross_attention(q, k, v, row_offsets, lengths, *,
                           scale: Optional[float] = None,
                           block_k: int = 128,
                           max_len: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Ragged encoder cross-attention over a packed token axis.

    q: (R, H, Nq, D) per-request latent queries; k/v: (H, T, D) packed
    token keys/values; row_offsets/lengths: (R,) int32 — request r owns
    tokens ``[row_offsets[r], row_offsets[r] + lengths[r])``.
    ``max_len`` bounds any single request's length (defaults to T); it
    sets the per-request kv-block count, so pass the real bound — the
    whole bytes win of the ragged layout lives there. Requests with
    ``lengths[r] == 0`` return zeros. Returns (R, H, Nq, D) in q's
    dtype.
    """
    interpret = resolve_interpret(interpret)
    r, h, nq, d = q.shape
    t = k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if max_len is None:
        max_len = t
    dp = _round_up(d, 128)
    nqp = _round_up(nq, 16)
    block_k = _round_up(min(block_k, _round_up(t, 128)), 128)
    tp = _round_up(t, block_k)
    nb_total = tp // block_k
    # one request spans at most ceil(max_len/block_k) + 1 kv blocks
    # (the +1 covers an unaligned start); the grid walks only those
    nk = min(nb_total, -(-max_len // block_k) + 1)

    qp = jnp.pad(q, ((0, 0), (0, 0), (0, nqp - nq), (0, dp - d)))
    kp = jnp.pad(k, ((0, 0), (0, tp - t), (0, dp - d)))
    vp = jnp.pad(v, ((0, 0), (0, tp - t), (0, dp - d)))

    def kv_index(rr, hh, j, offs, lens):
        start = offs[rr]
        end = start + lens[rr]
        first = start // block_k
        last = jnp.maximum(first, (end - 1) // block_k)
        kb = jnp.clip(jnp.minimum(first + j, last), 0, nb_total - 1)
        return (hh, kb, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r, h, nk),
        in_specs=[
            pl.BlockSpec((1, 1, nqp, dp),
                         lambda rr, hh, j, offs, lens: (rr, hh, 0, 0)),
            pl.BlockSpec((1, block_k, dp), kv_index),
            pl.BlockSpec((1, block_k, dp), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, nqp, dp),
            lambda rr, hh, j, offs, lens: (rr, hh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nqp, 128), jnp.float32),
            pltpu.VMEM((nqp, 128), jnp.float32),
            pltpu.VMEM((nqp, dp), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_cross_kernel, scale=float(scale),
                          block_k=block_k, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, h, nqp, dp), q.dtype),
        interpret=interpret,
        name="ragged_cross_attention",
    )(row_offsets.astype(jnp.int32), lengths.astype(jnp.int32),
      qp, kp, vp)
    return out[:, :, :nq, :d]


def ragged_cross_attention_reference(q, k, v, row_offsets, lengths,
                                     scale: Optional[float] = None):
    """Pure-jax reference for :func:`ragged_cross_attention` (tests)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    t = k.shape[1]
    col = jnp.arange(t)
    mask = ((col[None, :] >= row_offsets[:, None]) &
            (col[None, :] < (row_offsets + lengths)[:, None]))  # (R, T)
    logits = jnp.einsum("rhnd,htd->rhnt", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("rhnt,htd->rhnd", probs, v.astype(jnp.float32))
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0.0)
    return out.astype(q.dtype)


# --- decoder direction: packed token q, block-diagonal latent kv -------------


def _ragged_decode_kernel(q_ref, k_ref, v_ref, rows_ref, o_ref, *,
                          scale: float, latents_per_row: int):
    q = q_ref[0]            # (block_q, Dp)
    kl = k_ref[0]           # (RNp, Dp)
    vl = v_ref[0]
    rows = rows_ref[:, :1]  # (block_q, 1) int32
    s = jax.lax.dot_general(
        q, kl, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (block_q, RNp)
    c = jax.lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1)
    s = jnp.where((c // latents_per_row) == rows, s, NEG_INF)
    # single-pass fp32 softmax: the latent kv axis fits one block, and
    # every query row sees exactly latents_per_row finite columns
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(
        p.astype(vl.dtype), vl, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def ragged_decode_attention(q, k, v, rows, *, latents_per_row: int,
                            scale: Optional[float] = None,
                            block_q: int = 256,
                            interpret: Optional[bool] = None):
    """Block-diagonal decoder cross-attention for packed tokens.

    q: (H, T, D) packed-token queries; k/v: (H, R·N, D) flattened
    per-request latents (request r owns rows ``[r·N, (r+1)·N)``,
    ``N = latents_per_row``); rows: (T,) int32 request index of each
    token. Token t attends exactly its own request's N latents.
    Pad-tail tokens should carry a valid row (e.g. clamped to R−1) —
    their outputs are garbage-free but sliced off by the caller.
    Returns (H, T, D) in q's dtype.
    """
    interpret = resolve_interpret(interpret)
    h, t, d = q.shape
    rn = k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    dp = _round_up(d, 128)
    rnp = _round_up(rn, 128)
    block_q = min(block_q, _round_up(t, 16))
    tp = _round_up(t, block_q)
    qp = jnp.pad(q, ((0, 0), (0, tp - t), (0, dp - d)))
    kp = jnp.pad(k, ((0, 0), (0, rnp - rn), (0, dp - d)))
    vp = jnp.pad(v, ((0, 0), (0, rnp - rn), (0, dp - d)))
    # padded query rows get row −1: no latent column matches, the
    # uniform-softmax output is finite and sliced off below
    rows_p = jnp.pad(rows.astype(jnp.int32), (0, tp - t),
                     constant_values=-1)[:, None]  # (Tp, 1)

    out = pl.pallas_call(
        functools.partial(_ragged_decode_kernel, scale=float(scale),
                          latents_per_row=latents_per_row),
        grid=(h, tp // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda hh, iq: (hh, iq, 0)),
            pl.BlockSpec((1, rnp, dp), lambda hh, iq: (hh, 0, 0)),
            pl.BlockSpec((1, rnp, dp), lambda hh, iq: (hh, 0, 0)),
            pl.BlockSpec((block_q, 1), lambda hh, iq: (iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dp),
                               lambda hh, iq: (hh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((h, tp, dp), q.dtype),
        interpret=interpret,
        name="ragged_decode_attention",
    )(qp, kp, vp, rows_p)
    return out[:, :t, :d]


def ragged_decode_attention_reference(q, k, v, rows, *,
                                      latents_per_row: int,
                                      scale: Optional[float] = None):
    """Pure-jax reference for :func:`ragged_decode_attention` (tests)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    rn = k.shape[1]
    c = jnp.arange(rn)
    mask = (c[None, :] // latents_per_row) == rows[:, None]  # (T, RN)
    logits = jnp.einsum("htd,hcd->htc", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = jnp.where(mask[None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("htc,hcd->htd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)
