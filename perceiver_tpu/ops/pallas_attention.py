"""Fused flash-attention Pallas kernel for TPU.

Single-pass online-softmax attention (FlashAttention recurrence) as a
Pallas TPU kernel: for each query block, key/value blocks stream
HBM → VMEM along the innermost grid dimension while running max ``m``,
normalizer ``l``, and unnormalized output ``acc`` live in VMEM scratch.
The (Lq, Lk) logit matrix never hits HBM — softmax, masking, and both
matmuls fuse in one kernel, so HBM traffic is O(Lq·D + Lk·D) instead
of O(Lq·Lk).

This is the hot-op kernel for the encoder cross-attention at large
input length M (reference ``model.py:150-160``): the 512×512 LArTPC
config cross-attends 32 latents against M = 262,144 inputs
(``run.py:79``), and the seq-2048 MLM config (BASELINE.md configs[4])
streams 2048 kv tokens per layer.

Grid layout: ``(B, H, num_q_blocks, num_kv_blocks)`` — the kv axis is
innermost because TPU grids execute sequentially, which is what makes
carrying (m, l, acc) across kv steps in scratch legal.

Two block layouts, selected by head dim:

- standard (``D > 32``): blocks are (L, D) with D padded to 128 lanes.
- transposed (``D <= 32``): blocks are (D, L) — every 64-channel/
  4-head BASELINE config has head dim 16, which the standard layout
  would pad 8x in the lane axis; putting the huge kv axis on lanes and
  the skinny head dim on sublanes (padded only to 16) cuts kv HBM
  traffic ~8x. The (B,H,L,D) -> (B,H,D,L) relayout happens outside the
  kernel, where XLA fuses it into the producing projection matmuls.

Masking is an additive fp32 key bias ``(B, Lk)`` (``NEG_INF`` at
padding), matching the einsum path's ``key_padding_mask`` semantics.
Attention-weight dropout is not supported here (the reference default
is dropout 0.0, ``lightning.py:40``); the einsum path covers the
dropout>0 case.

Backward pass: ``jax.custom_vjp`` whose reverse recomputes attention
with the blockwise-scan implementation
(``perceiver_tpu.ops.chunked_attention``) — exact, and memory-bounded
like the forward.

On non-TPU backends the kernel runs in Pallas interpreter mode, so
tests exercise the identical code path on CPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_tpu.ops.tiling import round_up as _round_up

from perceiver_tpu.ops.chunked_attention import NEG_INF, chunked_attention
from perceiver_tpu.ops.online_softmax import (
    online_softmax_finish,
    online_softmax_init,
    online_softmax_update,
)


def _flash_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, nk: int):
    ib = pl.program_id(0)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        online_softmax_init(m_ref, l_ref, acc_ref)

    q = q_ref[0, 0]  # (block_q, Dp)
    k = k_ref[0, 0]  # (block_k, Dp)
    v = v_ref[0, 0]  # (block_k, Dp)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (block_q, block_k)
    # bias block spans the whole batch (Mosaic requires the sublane dim
    # be 8-divisible or full); select this program's row dynamically
    s = s + bias_ref[pl.ds(ib, 1), :]

    online_softmax_update(s, v, m_ref, l_ref, acc_ref)

    @pl.when(ik == nk - 1)
    def _():
        o_ref[0, 0] = online_softmax_finish(
            m_ref, l_ref, acc_ref).astype(o_ref.dtype)


def _flash_forward(q, k, v, bias, scale: float,
                   block_q: int, block_k: int, interpret: bool):
    b, h, lq, d = q.shape
    lk = k.shape[2]

    # Pad to hardware-friendly tiles. Zero-padding D leaves logits and
    # outputs unchanged; padded kv columns are killed by NEG_INF bias;
    # padded query rows are sliced off after.
    dp = _round_up(d, 128)
    # 16-sublane rounding covers the strictest dtype tile (bf16 needs
    # sublane multiples of 16; fp32 needs 8 — 16 satisfies both), e.g.
    # the 1-query classification decoder under impl="flash"
    block_q = min(block_q, _round_up(lq, 16))
    block_k = _round_up(min(block_k, _round_up(lk, 128)), 128)
    lq_p = _round_up(lq, block_q)
    lk_p = _round_up(lk, block_k)

    q = jnp.pad(q, ((0, 0), (0, 0), (0, lq_p - lq), (0, dp - d)))
    k = jnp.pad(k, ((0, 0), (0, 0), (0, lk_p - lk), (0, dp - d)))
    v = jnp.pad(v, ((0, 0), (0, 0), (0, lk_p - lk), (0, dp - d)))
    if bias is None:
        bias = jnp.zeros((b, lk), jnp.float32)
    bias = jnp.pad(bias.astype(jnp.float32), ((0, 0), (0, lk_p - lk)),
                   constant_values=NEG_INF)

    nq, nk = lq_p // block_q, lk_p // block_k
    grid = (b, h, nq, nk)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dp),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, dp),
                         lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dp),
                         lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
            pl.BlockSpec((b, block_k),
                         lambda ib, ih, iq, ik: (0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dp),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, lq_p, dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 128), jnp.float32),   # normalizer l
            pltpu.VMEM((block_q, dp), jnp.float32),    # unnormalized acc
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v, bias)
    return out[:, :, :lq, :d]


def _flash_kernel_t(q_ref, k_ref, v_ref, bias_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, scale: float, nk: int):
    """Transposed-layout kernel: q/k/v/o are (..., D, L) so the HUGE
    kv axis is the 128-lane minor dim and the skinny head dim (16 for
    every 64-channel/4-head BASELINE config) rides the sublane axis
    unpadded — 8x less HBM traffic than padding D up to 128 lanes."""
    ib = pl.program_id(0)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    qt = q_ref[0, 0]  # (Dp, block_q)
    kt = k_ref[0, 0]  # (Dp, block_k)
    vt = v_ref[0, 0]  # (Dp, block_k)

    s = jax.lax.dot_general(
        qt, kt, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (block_q, block_k)
    s = s + bias_ref[pl.ds(ib, 1), :]

    m_prev = m_ref[:, :1]                                # (block_q, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)

    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
    # acc wants q on the LANE axis; softmax stats have q on SUBLANE.
    # Cross the orientations with one tile-aligned (block_q, 128) →
    # (128, block_q) transpose per kv step (a standard Mosaic relayout;
    # both dims are tile multiples, unlike a (block_q, 1) vector); its
    # rows are all identical, so row 0 broadcasts to any Dp.
    alpha_t = jax.lax.transpose(
        jnp.broadcast_to(alpha, (alpha.shape[0], 128)), (1, 0))
    acc_ref[:] = (acc_ref[:]
                  * jnp.broadcast_to(alpha_t[:1], acc_ref.shape)
                  + jax.lax.dot_general(
                      vt, p.astype(vt.dtype), (((1,), (1,)), ((), ())),
                      preferred_element_type=jnp.float32))  # (Dp, block_q)

    @pl.when(ik == nk - 1)
    def _():
        l_t = jax.lax.transpose(l_ref[:], (1, 0))        # (128, block_q)
        o_ref[0, 0] = (acc_ref[:] /
                       jnp.maximum(jnp.broadcast_to(l_t[:1],
                                                    acc_ref.shape),
                                   1e-30)).astype(o_ref.dtype)


def _flash_forward_t(q, k, v, bias, scale: float,
                     block_q: int, block_k: int, interpret: bool):
    """Forward via the transposed kernel. Takes standard (B, H, L, D)
    arrays; the (D, L) relayout happens outside the kernel where XLA
    fuses it into the producing projection matmuls."""
    b, h, lq, d = q.shape
    lk = k.shape[2]

    # sublane-pad D to the strictest tile (16 covers bf16 and fp32);
    # lane-pad both L axes to their block sizes. Both L blocks are the
    # MINOR dim of their arrays here, so Mosaic requires them to be
    # 128-multiples — round the user's block_q UP (the standard layout
    # only needs sublane-rounding for it).
    dp = _round_up(d, 16)
    block_q = _round_up(min(block_q, _round_up(lq, 128)), 128)
    block_k = _round_up(min(block_k, _round_up(lk, 128)), 128)
    lq_p = _round_up(lq, block_q)
    lk_p = _round_up(lk, block_k)

    qt = jnp.pad(q.swapaxes(2, 3), ((0, 0), (0, 0), (0, dp - d),
                                    (0, lq_p - lq)))
    kt = jnp.pad(k.swapaxes(2, 3), ((0, 0), (0, 0), (0, dp - d),
                                    (0, lk_p - lk)))
    vt = jnp.pad(v.swapaxes(2, 3), ((0, 0), (0, 0), (0, dp - d),
                                    (0, lk_p - lk)))
    if bias is None:
        bias = jnp.zeros((b, lk), jnp.float32)
    bias = jnp.pad(bias.astype(jnp.float32), ((0, 0), (0, lk_p - lk)),
                   constant_values=NEG_INF)

    nq, nk = lq_p // block_q, lk_p // block_k
    grid = (b, h, nq, nk)

    out = pl.pallas_call(
        functools.partial(_flash_kernel_t, scale=scale, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, dp, block_q),
                         lambda ib, ih, iq, ik: (ib, ih, 0, iq)),
            pl.BlockSpec((1, 1, dp, block_k),
                         lambda ib, ih, iq, ik: (ib, ih, 0, ik)),
            pl.BlockSpec((1, 1, dp, block_k),
                         lambda ib, ih, iq, ik: (ib, ih, 0, ik)),
            pl.BlockSpec((b, block_k),
                         lambda ib, ih, iq, ik: (0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, dp, block_q),
                               lambda ib, ih, iq, ik: (ib, ih, 0, iq)),
        out_shape=jax.ShapeDtypeStruct((b, h, dp, lq_p), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 128), jnp.float32),   # normalizer l
            pltpu.VMEM((dp, block_q), jnp.float32),    # acc, q on lanes
        ],
        interpret=interpret,
        name="flash_attention_fwd_t",
    )(qt, kt, vt, bias)
    return out[:, :, :d, :lq].swapaxes(2, 3)


# D at or below this uses the transposed kernel: the padding ratio
# 128/D makes the standard layout waste >=4x HBM bandwidth on kv
_SKINNY_D = 32


def _pick_layout(d: int) -> str:
    """'transposed' or 'standard'; PERCEIVER_TPU_FLASH_LAYOUT overrides
    the D-based auto choice (for on-chip A/B benchmarking)."""
    import os
    env = os.environ.get("PERCEIVER_TPU_FLASH_LAYOUT", "auto")
    if env in ("standard", "transposed"):
        return env
    if env != "auto":
        # a typo'd override would silently measure the auto layout in
        # both arms of a chip-time A/B — reject like any other config
        raise ValueError(
            f"PERCEIVER_TPU_FLASH_LAYOUT={env!r}; expected 'auto', "
            "'standard', or 'transposed'")
    return "transposed" if d <= _SKINNY_D else "standard"


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, bias, scale, block_q, block_k, interpret):
    return _flash_forward_any(q, k, v, bias, scale, block_q, block_k,
                              interpret)


def _flash_forward_any(q, k, v, bias, scale, block_q, block_k, interpret):
    if _pick_layout(q.shape[-1]) == "transposed":
        return _flash_forward_t(q, k, v, bias, scale, block_q, block_k,
                                interpret)
    return _flash_forward(q, k, v, bias, scale, block_q, block_k, interpret)


def _flash_fwd(q, k, v, bias, scale, block_q, block_k, interpret):
    out = _flash_forward_any(q, k, v, bias, scale, block_q, block_k,
                             interpret)
    return out, (q, k, v, bias)


def _flash_bwd(scale, block_q, block_k, interpret, res, g):
    q, k, v, bias = res
    # Exact recompute through the blockwise scan — backward stays
    # memory-bounded on BOTH axes: kv streams through the scan
    # (rematerialized), and the query axis is blocked like the forward
    # kernel grid (matters for the 262k-query decoder config).
    if bias is None:
        _, vjp = jax.vjp(
            lambda a, b_, c: chunked_attention(
                a, b_, c, scale=scale, chunk_size=block_k,
                q_chunk_size=block_q * 8),
            q, k, v)
        return (*vjp(g), None)
    # bias is differentiable (a learned additive key bias trains the
    # same under impl="flash" as under "chunked"/"einsum")
    _, vjp = jax.vjp(
        lambda a, b_, c, bi: chunked_attention(
            a, b_, c, bias=bi, scale=scale, chunk_size=block_k,
            q_chunk_size=block_q * 8),
        q, k, v, bias)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, bias: Optional[jax.Array] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 512,
                    interpret: Optional[bool] = None):
    """Fused attention. q: (B, H, Lq, D); k, v: (B, H, Lk, D);
    bias: optional (B, Lk) additive key bias (NEG_INF at padding).
    Returns (B, H, Lq, D) in q's dtype."""
    from perceiver_tpu.utils.platform import resolve_interpret
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return _flash(q, k, v, bias, float(scale), int(block_q), int(block_k),
                  resolve_interpret(interpret))
