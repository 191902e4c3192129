"""Fused vocab-projection + cross-entropy Pallas kernel for TPU.

The MLM loss's hot op is ``logits = hidden @ W + b`` followed by a
log-softmax over the vocabulary (reference ``lightning.py:223-226``).
Even the chunked XLA implementation (``ops.fused_ce``) materializes
each chunk's ``(chunk, V)`` logits in HBM between the matmul and the
reduction — at vocab 10003 that round-trip dominates the loss path's
time. This kernel keeps every logits tile in VMEM: for each row block,
vocab tiles stream through the MXU while an online-logsumexp carry
(running max ``m``, normalizer ``l``) and the label's logit ``gold``
live in scratch; only the per-row NLL and logsumexp ever reach HBM, so
traffic drops from O(N·V) to O(N·C + C·V).

Backward is two more Pallas kernels with the same tiling, recomputing
logit tiles in VMEM (flash-attention-style rematerialization):

- d_hidden: for each row block, ``softmax − onehot`` tiles stream
  against ``Wᵀ`` (vocab innermost, accumulator in scratch).
- d_W / d_b: for each vocab tile, row blocks stream (rows innermost),
  accumulating ``hiddenᵀ @ dlogits`` and the column sums.

Both reuse the forward's saved logsumexp, so no extra softmax pass.

Grid layouts follow the sequential-TPU-grid rule (carry dimension
innermost; see ``ops.pallas_attention``). On non-TPU backends the
kernels run in interpreter mode, so tests exercise the identical code
path on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_tpu.obs.trace import device_scope
from perceiver_tpu.ops.tiling import round_up as _round_up

NEG = -1e30

# Largest (C, block_v) weight tile, in elements, that the three kernels
# hold in the chip's scoped VMEM next to its f32 accumulator, the
# double-buffered output tile and the (block_n, block_v) logits: the
# v5e compiler takes (512, 1024) and refuses (512, 2048).
_MAX_W_TILE = 512 * 1024


# --- forward: per-row nll and logsumexp --------------------------------------


def _fwd_kernel(h_ref, w_ref, b_ref, y_ref, nll_ref, lse_ref,
                m_ref, l_ref, gold_ref, *, nv: int, block_v: int):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        gold_ref[:] = jnp.zeros_like(gold_ref)

    logits = jax.lax.dot_general(
        h_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b_ref[:]

    cols = iv * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    is_gold = cols == y_ref[:]                       # (BN, BV) via (BN, 1)
    gold = jnp.sum(jnp.where(is_gold, logits, 0.0), axis=1, keepdims=True)
    gold_ref[:] = gold_ref[:] + jnp.broadcast_to(gold, gold_ref.shape)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    l_new = (l_ref[:, :1] * jnp.exp(m_prev - m_new)
             + jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True))
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(iv == nv - 1)
    def _():
        lse = m_ref[:, :1] + jnp.log(jnp.maximum(l_ref[:, :1], 1e-37))
        lse_ref[:] = lse
        nll_ref[:] = lse - gold_ref[:, :1]


# --- backward: d_hidden ------------------------------------------------------


def _bwd_dh_kernel(h_ref, w_ref, b_ref, y_ref, lse_ref, dnll_ref, dh_ref,
                   acc_ref, *, nv: int, block_v: int):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    logits = jax.lax.dot_general(
        h_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b_ref[:]
    p = jnp.exp(logits - lse_ref[:])                  # softmax (BN, BV)
    cols = iv * block_v + jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    dlogits = (p - (cols == y_ref[:]).astype(p.dtype)) * dnll_ref[:]

    acc_ref[:] += jax.lax.dot_general(
        dlogits.astype(w_ref.dtype), w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(iv == nv - 1)
    def _():
        dh_ref[:] = acc_ref[:].astype(dh_ref.dtype)


# --- backward: d_W and d_b ---------------------------------------------------


def _bwd_dw_kernel(h_ref, w_ref, b_ref, y_ref, lse_ref, dnll_ref,
                   dw_ref, db_ref, accw_ref, accb_ref,
                   *, nr: int, block_v: int):
    iv, ir = pl.program_id(0), pl.program_id(1)

    @pl.when(ir == 0)
    def _():
        accw_ref[:] = jnp.zeros_like(accw_ref)
        accb_ref[:] = jnp.zeros_like(accb_ref)

    logits = jax.lax.dot_general(
        h_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b_ref[:]
    p = jnp.exp(logits - lse_ref[:])
    cols = iv * block_v + jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    dlogits = (p - (cols == y_ref[:]).astype(p.dtype)) * dnll_ref[:]

    accw_ref[:] += jax.lax.dot_general(
        h_ref[:], dlogits.astype(h_ref.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    accb_ref[:] = accb_ref[:] + jnp.sum(dlogits, axis=0, keepdims=True)

    @pl.when(ir == nr - 1)
    def _():
        dw_ref[:] = accw_ref[:].astype(dw_ref.dtype)
        db_ref[:] = accb_ref[:].astype(db_ref.dtype)


# --- host-side wrappers ------------------------------------------------------


def _pad_inputs(h, w, b, labels, block_n, block_v):
    n, c = h.shape
    v = w.shape[1]
    np_, vp = _round_up(n, block_n), _round_up(v, block_v)
    h = jnp.pad(h, ((0, np_ - n), (0, 0)))
    w = jnp.pad(w, ((0, 0), (0, vp - v)))
    # padded vocab columns get a NEG bias so exp() kills them in both
    # the normalizer and the softmax of the backward kernels
    b = jnp.pad(b.astype(jnp.float32), (0, vp - v), constant_values=NEG)
    labels = jnp.pad(labels, (0, np_ - n)).astype(jnp.int32)
    return h, w, b.reshape(1, vp), labels.reshape(np_, 1), np_, vp


def _fwd(h, w, b, labels, block_n, block_v, interpret):
    n, c = h.shape
    hp, wp, bp, yp, np_, vp = _pad_inputs(h, w, b, labels, block_n, block_v)
    nr, nv = np_ // block_n, vp // block_v

    nll, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, nv=nv, block_v=block_v),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((block_n, c), lambda ir, iv: (ir, 0)),
            pl.BlockSpec((c, block_v), lambda ir, iv: (0, iv)),
            pl.BlockSpec((1, block_v), lambda ir, iv: (0, iv)),
            pl.BlockSpec((block_n, 1), lambda ir, iv: (ir, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda ir, iv: (ir, 0)),
            pl.BlockSpec((block_n, 1), lambda ir, iv: (ir, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 128), jnp.float32),  # running max
            pltpu.VMEM((block_n, 128), jnp.float32),  # normalizer
            pltpu.VMEM((block_n, 128), jnp.float32),  # gold logit
        ],
        interpret=interpret,
        name="fused_ce_fwd",
    )(hp, wp, bp, yp)
    return nll[:n, 0], lse[:n, 0]


def _bwd(h, w, b, labels, lse, dnll, block_n, block_v, interpret):
    n, c = h.shape
    v = w.shape[1]
    hp, wp, bp, yp, np_, vp = _pad_inputs(h, w, b, labels, block_n, block_v)
    nr, nv = np_ // block_n, vp // block_v
    # padded rows: dnll 0 ⇒ zero dlogits ⇒ no gradient contribution;
    # lse pad 0 is harmless under that zero factor
    lsep = jnp.pad(lse, (0, np_ - n)).reshape(np_, 1)
    dnllp = jnp.pad(dnll, (0, np_ - n)).reshape(np_, 1).astype(jnp.float32)

    row_specs = [
        pl.BlockSpec((block_n, c), lambda ir, iv: (ir, 0)),
        pl.BlockSpec((c, block_v), lambda ir, iv: (0, iv)),
        pl.BlockSpec((1, block_v), lambda ir, iv: (0, iv)),
        pl.BlockSpec((block_n, 1), lambda ir, iv: (ir, 0)),
        pl.BlockSpec((block_n, 1), lambda ir, iv: (ir, 0)),
        pl.BlockSpec((block_n, 1), lambda ir, iv: (ir, 0)),
    ]
    dh = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, nv=nv, block_v=block_v),
        grid=(nr, nv),
        in_specs=row_specs,
        out_specs=pl.BlockSpec((block_n, c), lambda ir, iv: (ir, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, c), h.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, c), jnp.float32)],
        interpret=interpret,
        name="fused_ce_bwd_dh",
    )(hp, wp, bp, yp, lsep, dnllp)

    col_specs = [
        pl.BlockSpec((block_n, c), lambda iv, ir: (ir, 0)),
        pl.BlockSpec((c, block_v), lambda iv, ir: (0, iv)),
        pl.BlockSpec((1, block_v), lambda iv, ir: (0, iv)),
        pl.BlockSpec((block_n, 1), lambda iv, ir: (ir, 0)),
        pl.BlockSpec((block_n, 1), lambda iv, ir: (ir, 0)),
        pl.BlockSpec((block_n, 1), lambda iv, ir: (ir, 0)),
    ]
    dw, db = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, nr=nr, block_v=block_v),
        grid=(nv, nr),
        in_specs=col_specs,
        out_specs=[
            pl.BlockSpec((c, block_v), lambda iv, ir: (0, iv)),
            pl.BlockSpec((8, block_v), lambda iv, ir: (0, iv)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, vp), w.dtype),
            jax.ShapeDtypeStruct((8, vp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((c, block_v), jnp.float32),
            pltpu.VMEM((8, block_v), jnp.float32),
        ],
        interpret=interpret,
        name="fused_ce_bwd_dw",
    )(hp, wp, bp, yp, lsep, dnllp)
    return dh[:n], dw[:, :v], db[0, :v]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _nll_and_lse(h, w, b, labels, block_n, block_v, interpret):
    return _fwd(h, w, b, labels, block_n, block_v, interpret)


def _nll_fwd(h, w, b, labels, block_n, block_v, interpret):
    nll, lse = _fwd(h, w, b, labels, block_n, block_v, interpret)
    return (nll, lse), (h, w, b, labels, lse)


def _nll_bwd(block_n, block_v, interpret, res, cot):
    h, w, b, labels, lse = res
    dnll, _ = cot  # lse is a saved intermediate, not a training output
    dh, dw, db = _bwd(h, w, b, labels, lse, dnll, block_n, block_v,
                      interpret)
    return dh, dw, db.astype(b.dtype), None


_nll_and_lse.defvjp(_nll_fwd, _nll_bwd)


@device_scope("loss")
def pallas_linear_cross_entropy(linear_params, hidden, labels, weight, *,
                                block_n: int = 512, block_v: int = 2048,
                                policy=None, interpret=None):
    """Weighted-mean CE of ``hidden @ w + b`` vs ``labels``, fully fused.

    Same contract as ``ops.fused_ce.fused_linear_cross_entropy``:
    hidden (N, C), labels (N,), weight (N,) fp32 (0 on ignored rows);
    returns ``sum(w·nll) / max(sum(w), 1)``. ``weight``/``labels`` get
    zero gradient (they are masks/targets, not trained).
    """
    from perceiver_tpu.ops.policy import DEFAULT_POLICY
    from perceiver_tpu.utils.platform import resolve_interpret
    policy = policy or DEFAULT_POLICY
    interpret = resolve_interpret(interpret)

    n = hidden.shape[0]
    h = policy.cast_compute(hidden)
    w = policy.cast_param(linear_params["w"])
    b = policy.cast_param(linear_params["b"])
    # 16-sublane rounding covers the strictest dtype tile (bf16 needs
    # 16; fp32 needs 8) for tiny packed-capacity row counts
    block_n = min(block_n, _round_up(n, 16))
    # the vocab tile follows the hidden width: every per-tile buffer of
    # the backward kernels is (C, block_v)
    c = w.shape[0]
    if c * 128 > _MAX_W_TILE:
        raise ValueError(
            f"pallas_linear_cross_entropy: hidden width {c} leaves no "
            f"(C, block_v) tile of at least 128 vocab columns within "
            f"{_MAX_W_TILE} elements of VMEM; use loss_impl='packed'")
    block_v = min(block_v, _MAX_W_TILE // c // 128 * 128,
                  _round_up(w.shape[1], 128))

    nll, _ = _nll_and_lse(h, w, b, labels, int(block_n), int(block_v),
                          bool(interpret))
    weight = weight.astype(jnp.float32)
    weight = jax.lax.stop_gradient(weight)
    return (nll * weight).sum() / jnp.maximum(weight.sum(), 1.0)
