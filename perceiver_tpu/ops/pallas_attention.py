"""Fused flash-attention Pallas kernels for TPU, forward and backward.

The attention core whose scores never leave VMEM. Forward: online
softmax (FlashAttention recurrence) over key blocks — for each query
block, key/value blocks stream HBM → VMEM along the innermost grid
dimension while the running max ``m``, normalizer ``l`` and
unnormalized output ``acc`` live in VMEM scratch; where the keys fit
one block the softmax is taken directly. Backward: one kernel that
recomputes each block's probabilities from ``q``, ``k`` and the saved
per-row log-sum-exp and produces ``dq``, ``dk``, ``dv`` (and the key
bias's gradient). No ``(Lq, Lk)`` array reaches HBM in either pass, so
HBM traffic is O(Lq·D + Lk·D) instead of O(Lq·Lk).

Arithmetic is the materialised core's (``ops/attention._sdpa_core``):
operands in the caller's dtype (bf16 under the default policy) into
every matmul, float32 scores, statistics and accumulators, the softmax
scale folded into the small ``q`` / ``k`` tiles, the probabilities and
``ds`` cast to the operand dtype before their contractions.

Layout. The kernels read and write heads where the projections leave
them: ``(B, L, H·D)`` arrays, heads side by side on the channel axis,
one block of 128 lanes (or ``D``, from 128 up) a program — no
``(B, H, L, D)`` copy of any operand or result, no four-dimensional
array for the compiler to lay out its own way, and no lane padding of
narrow heads in HBM. A block of 128 lanes holds ``128 // D`` heads (two
of 64, eight of 16). The kernel walks them: head ``g``'s scores are
``(q ∘ mask_g)·kᵀ`` over the whole block — the MXU contracts 128 deep
whatever ``D`` is, so the other heads' zeroed lanes cost nothing — and
its ``p·v`` fills the block's 128 columns, of which ``mask_g`` keeps
head ``g``'s. In the backward the masked ``do``, ``q`` and ``k`` tiles
make ``dv``, ``dk`` and ``dq`` land in their own head's lanes, so the
heads' results add up into one lane-dense block. Head dims that
neither divide 128 nor are a multiple of it (or head counts the group
does not divide) are zero-padded to 128 lanes a head.

Orientation. The forward holds scores as ``(block_q, block_k)``: both
matmuls are MXU-native (``q·kᵀ``, ``p·v``) and the key bias is a row.
The backward holds them transposed, ``(block_k, block_q)``: ``k·qᵀ``
and ``v·doᵀ`` are native, ``dv = pᵀ·do`` and ``dk = dsᵀ·q`` become
plain products, the row statistics (log-sum-exp, ``delta``) are
``(1, block_q)`` rows that broadcast along sublanes for free, and only
``dq = ds·k`` needs one transpose of the score tile. The forward
therefore emits the log-sum-exp as a lane-dense ``(1, Lq)`` row.

Grids: forward ``(B, H/G, nq, nk)``, backward ``(B, H/G, nk, nq)`` —
the last axis innermost; TPU grids execute sequentially, which is what
makes carrying accumulators across steps legal. In the backward
``dk``/``dv`` accumulate in scratch over the query blocks, and ``dq``
is one float32 output block per ``(b, head group)`` that stays
resident in VMEM across the whole ``(nk, nq)`` sweep (written straight
out where the keys fit one block).

Masking is an additive fp32 key bias ``(B, Lk)`` (``NEG_INF`` at
padding), matching the einsum path's ``key_padding_mask`` semantics. A
row whose every key is masked attends uniformly, as there; its
backward then weights each key 1 where the einsum path has 1/Lk (the
log-sum-exp absorbs log Lk beside 1e30) — finite, and such a row
carries no loss. A full ``attn_mask`` and attention-weight dropout are
not supported; ``ops/attention.py`` keeps those on the materialised
core.

Causal mode (``causal=True``, square scores, no key bias): query ``i``
sees keys ``0..i``. Blocks that lie wholly above the diagonal are
neither loaded (the index maps hold the last block that is needed, so
the pipeline fetches nothing new) nor computed (``pl.when``); blocks
the diagonal crosses are masked in the kernel from two iotas (the
backward, sub-tile by sub-tile: below); blocks below it run the
unmasked body. Padded keys lie above every real row, so the mask
covers them too. The calls are named
``causal_attention_fwd`` / ``causal_attention_bwd``: a reader that
counts a ``flash_attention_*`` call in full never sees them. A
non-causal call traces exactly what it traced before the mode existed.

Block-diffusion mode (``block_diffusion=(L, B)``, square scores over
``2 L`` positions, no key bias): a row is its noised copy beside its
clean copy, blocks of ``B`` positions, and query ``j`` sees key ``l``

* ``j <  L, l <  L``: where both lie in one block (a noised block sees
  itself, both ways);
* ``j <  L, l >= L``: where ``l``'s block lies before ``j``'s (and the
  clean blocks before it);
* ``j >= L, l >= L``: where ``l``'s block is ``j``'s or lies before it
  (a clean block sees the clean blocks up to itself);
* ``j >= L, l <  L``: never.

Each ``(query tile, key tile)`` is *skipped*, *masked* or *plain*
(``ops/tiling.diffusion_tiles``, from the rules over the tile's index
ranges, on the host): the kinds and the tile to hold while one is skipped reach
the kernel and its index maps as two prefetched scalar tables. With
``L`` a multiple of the tiles the noised-noised quadrant runs its
diagonal tiles only (masked), the noised-clean and clean-clean
quadrants their diagonal tiles masked and the tiles below plain, the
clean-noised quadrant nothing; any other ``L`` is classified the same
way, tile by tile. A masked tile's mask is built from a column of
query numbers against a row of key numbers. Padded positions count as
clean ones past the row's end: no real query sees a padded key. The
calls are named ``block_diffusion_attention_fwd`` /
``block_diffusion_attention_bwd``.

Skipped tiles and sub-tiles. A masked call loads a tile's operands
inside the branch that runs it, so a skipped grid step costs the step
alone (a call with no mask runs every tile and loads at the top). A
plain tile runs whole, in one pass, in both kernels: that is where the
MXU is best fed. The forward runs a masked tile whole too, under its
mask. The backward classifies a *masked* tile of either mask once more
on the host, by the same rules one level down
(``ops/tiling.sub_tile_lists``): its sub-tiles of ``_SUB_TILE`` queries
by ``_SUB_TILE`` keys that hold a visible pair, the masked ones and then
the plain ones, as a list; tiles with one pattern (every diagonal tile
of a quadrant, every diagonal tile of the causal triangle) share one
list. Two more prefetched scalar tables carry where each tile's list
lies and the lists. The kernel walks a masked tile's list in two
``fori_loop``s, one whose body is a masked sub-tile and one whose body
is a plain one — slices of ``q``, ``k``, ``v``, ``do`` and the
statistics taken from their refs, the tile's own arithmetic on them,
the results added into slices of the float32 accumulators — so what a
call traces does not grow with the sub-tiles a tile holds (the tables
do): every call site of an unrolled stack pays for a kernel's traced
size when the step is loaded. At 512 a 1024 x 1024 tile on the diagonal
of the causal triangle, or of the noised-clean and clean-clean
quadrants, runs 3 of its 4 sub-tiles (2 of them masked); one on the
noised-noised diagonal 2 of 4. A sub-tile adds into the accumulators,
so a masked call's backward keeps them whatever its grid. The forward
does not walk lists: each pass of a row's queries over some keys pays
the softmax's serial chain again (running max, exponentials, sum,
rescaled accumulator), which costs as much as the sub-tiles skipped
(chip runs, PERF.md Findings PRs 40 and 41).

So the kernels know three masks, by the name of their calls: none
(``flash_attention_*``, with an optional key bias), the causal
triangle (``causal_attention_*``) and the block-diffusion mask
(``block_diffusion_attention_*``).

On non-TPU backends the kernels run in Pallas interpreter mode, so
tests exercise the identical code path on CPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_tpu.ops.remat import dear
from perceiver_tpu.ops.tiling import (
    MASKED,
    PLAIN,
    diffusion_tiles,
    held_tiles,
    round_up as _round_up,
    sub_tile_lists,
    tile_counts,
)

from perceiver_tpu.ops.chunked_attention import NEG_INF

_NN = (((1,), (0,)), ((), ()))  # A · B
_NT = (((1,), (1,)), ((), ()))  # A · Bᵀ (the MXU loads B transposed)
_TN = (((0,), (0,)), ((), ()))  # Aᵀ · B

_LANES = 128

# Blocks from shapes (chip runs, PERF.md Findings PR 26): keys in one
# block up to 2048 (a plain softmax, no running state, dq written
# straight out), else streamed by 1024; queries by up to 1024, with
# the (block_q, block_k) float32 score tile, several of which are live
# at once, held to 4 MiB under the limit below (a v5e core has 128 MiB;
# at 512 x 2048 a streamed forward already lost a third to spills).
_ONE_BLOCK_K = 2048
_STREAM_BLOCK_K = 1024
_MAX_BLOCK_Q = 1024
_SCORE_TILE = 1024 * 1024
_CAUSAL_BLOCK_K = 1024
# the backward runs a masked tile in sub-tiles of this many queries and
# keys (the whole tile where its side is no multiple). Chip runs,
# PERF.md Findings PRs 40 and 41: 512 beats 256 and 128 (a pass costs
# 0.3-0.5 us beside its work); the forward runs a masked tile whole (a
# pass there also pays a softmax's serial chain, 0.9 us whatever its
# size, and three passes of 512 already lose to the tile)
_SUB_TILE = 512


def pick_blocks(lq: int, lk: int, causal: bool = False):
    """``(block_q, block_k)`` for ``Lq`` queries over ``Lk`` keys. A
    causal call (a block-diffusion call too) streams its keys from
    ``_CAUSAL_BLOCK_K`` up: only blocks can be skipped, and one block of
    2048 keys skips none."""
    lk_p = _round_up(lk, _LANES)
    if causal:
        block_k = min(lk_p, _CAUSAL_BLOCK_K)
    else:
        block_k = lk_p if lk_p <= _ONE_BLOCK_K else _STREAM_BLOCK_K
    block_q = min(_round_up(lq, _LANES), _MAX_BLOCK_Q,
                  _SCORE_TILE // block_k)
    return block_q, block_k


_VMEM_LIMIT = 96 * 1024 * 1024
# the backward's resident float32 dq block, (Lq, 128) per head group
_DQ_RESIDENT_MAX = 16 * 1024 * 1024

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


def _col_to_row(col):
    """(rows, 1) → (1, rows) through one tile-aligned (rows, 128) →
    (128, rows) transpose (a standard Mosaic relayout; a (rows, 1)
    vector is not)."""
    wide = jnp.broadcast_to(col, (col.shape[0], _LANES))
    return jax.lax.transpose(wide, (1, 0))[:1]


def _head_masks(width: int, group: int):
    """One (1, width) lane mask a head of the block; ``[None]`` for a
    block that is one head."""
    if group == 1:
        return [None]
    lane_head = jax.lax.broadcasted_iota(
        jnp.int32, (1, width), 1) // (width // group)
    return [lane_head == g for g in range(group)]


def _only(mask, tile):
    """``tile`` with the other heads' lanes zeroed."""
    return tile if mask is None else jnp.where(mask, tile, 0)


def _merge(mask, new, old):
    """``new`` in this head's lanes, ``old`` in the others'."""
    return new if mask is None or old is None else jnp.where(mask, new, old)


def _causal_mask(s, first_row, first_col, transposed: bool):
    """``s`` with NEG_INF where the key lies after the query. ``s`` is
    (queries, keys), or (keys, queries) where ``transposed``; the
    tile's first query and key index are ``first_row``/``first_col``."""
    q_axis, k_axis = (1, 0) if transposed else (0, 1)
    rows = first_row + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    cols = first_col + jax.lax.broadcasted_iota(jnp.int32, s.shape, k_axis)
    return jnp.where(cols <= rows, s, NEG_INF)


def _when_needed(needed, crossed, body):
    """Run ``body(masked)`` for a causal tile: not at all above the
    diagonal, masked where the diagonal crosses it, plain below."""
    pl.when(jnp.logical_and(needed, crossed))(lambda: body(True))
    pl.when(jnp.logical_and(needed, jnp.logical_not(crossed)))(
        lambda: body(False))


# --- the block-diffusion mask ------------------------------------------------

def _diffusion_mask(s, first_q, first_k, half: int, block: int,
                    transposed: bool):
    """``s`` with NEG_INF where the block-diffusion rules hide the key
    from the query; ``s`` (queries, keys), or (keys, queries) where
    ``transposed``. A column of query numbers meets a row of key
    numbers (the other way round where transposed): per position, the
    clean blocks a query sees end before ``limit`` and its own noised
    block is ``own``; two comparisons a pair."""
    q_axis, k_axis = (1, 0) if transposed else (0, 1)
    q_shape = tuple(s.shape[a] if a == q_axis else 1 for a in (0, 1))
    k_shape = tuple(s.shape[a] if a == k_axis else 1 for a in (0, 1))
    q = first_q + jax.lax.broadcasted_iota(jnp.int32, q_shape, q_axis)
    k = first_k + jax.lax.broadcasted_iota(jnp.int32, k_shape, k_axis)
    q_noised, k_noised = q < half, k < half
    q_block = jax.lax.div(jnp.where(q_noised, q, q - half), block)
    k_block = jax.lax.div(jnp.where(k_noised, k, k - half), block)
    limit = jnp.where(q_noised, q_block, q_block + 1)
    own = jnp.where(q_noised, q_block, -1)
    k_clean = jnp.where(k_noised, jnp.int32(2 ** 30), k_block)
    k_own = jnp.where(k_noised, k_block, -2)
    return jnp.where((k_clean < limit) | (k_own == own), s, NEG_INF)


def _when_kind(kind, body):
    """Run ``body(masked)`` for a tile, or a sub-tile, of ``kind``."""
    pl.when(kind == MASKED)(lambda: body(True))
    pl.when(kind == PLAIN)(lambda: body(False))


# --- a masked tile's sub-tiles ------------------------------------------------

def _sub_tile(block: int) -> int:
    """The side of a masked tile's sub-tiles along a side of ``block``."""
    return _SUB_TILE if block % _SUB_TILE == 0 else block


def _hide(s, first_q, first_k, diffusion, transposed: bool):
    """``s`` under the call's mask: the block-diffusion mask of
    ``diffusion``, or the causal triangle where that is None."""
    if diffusion:
        return _diffusion_mask(s, first_q, first_k, *diffusion, transposed)
    return _causal_mask(s, first_q, first_k, transposed)


def _each_sub_tile(span_ref, entry_ref, tile, sub, body):
    """The masked tile ``tile`` (its place in the sweep's tables), one
    listed sub-tile after another: a loop over its masked sub-tiles,
    then one over its plain ones, the traced body of each one
    sub-tile. ``body(rows, cols, row, col, masked)`` takes the
    sub-tile's queries and keys as slices of the tile's and as their
    first offsets in it."""
    sub_q, sub_k = sub
    first, masked = span_ref[3 * tile], span_ref[3 * tile + 1]

    def walk(start, count, masked: bool):
        def step(i, carry):
            at = 2 * (start + i)
            row = pl.multiple_of(entry_ref[at], sub_q)
            col = pl.multiple_of(entry_ref[at + 1], sub_k)
            body(pl.ds(row, sub_q), pl.ds(col, sub_k), row, col, masked)
            return carry

        jax.lax.fori_loop(0, count, step, 0)

    walk(first, masked, True)
    walk(first + masked, span_ref[3 * tile + 2], False)


def _mask_tables(diffusion, block_q: int, block_k: int, nq: int, nk: int,
                 sub, keys_first: bool):
    """A masked call's prefetched scalar tables, for a sweep that runs
    queries first (the forward) or keys first (the backward): for a
    block-diffusion call the tiles' kinds and the tile to hold at each
    step; where the sweep's masked tiles run in sub-tiles of ``sub``,
    where each one's list lies and the lists
    (``ops/tiling.sub_tile_lists``)."""
    tables = []
    if diffusion:
        kinds = diffusion_tiles(*diffusion, block_q, block_k, nq, nk)
        kinds = kinds.T if keys_first else kinds
        tables = [kinds, held_tiles(kinds)]
    if sub:
        spans, entries = sub_tile_lists(diffusion, block_q, block_k, nq, nk,
                                        *sub)
        tables += [spans.transpose(1, 0, 2) if keys_first else spans,
                   entries]
    return tuple(jnp.asarray(t.ravel()) for t in tables)


# --- forward -----------------------------------------------------------------


def _fwd_kernel(*refs, scale: float, nk: int, group: int, has_bias: bool,
                save_lse: bool, causal: bool = False, block_q: int = 0,
                block_k: int = 0, diffusion=None):
    refs = iter(refs)
    if diffusion:   # the tiles' kinds; the held tiles are the index maps'
        kind_ref, _ = next(refs), next(refs)
    q_ref, k_ref, v_ref = next(refs), next(refs), next(refs)
    bias_ref = next(refs) if has_bias else None
    o_ref = next(refs)
    lse_ref = next(refs) if save_lse else None
    ik = 0
    if nk > 1:
        m_ref, l_ref, acc_ref = refs
        ik = pl.program_id(3)

        @pl.when(ik == 0)
        def _():
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

    def operands():
        """q (block_q, W) scaled, k and v (block_k, W), operand dtype."""
        return q_ref[0] * scale, k_ref[0], v_ref[0]

    # a call with no mask runs every tile; a masked one loads a tile's
    # operands where the tile runs: a skipped step costs its grid step
    loaded = None if causal or diffusion else operands()
    masks = _head_masks(q_ref.shape[-1], group)
    if causal or diffusion:
        first_q = pl.program_id(2) * block_q
        first_k = ik * block_k

    def tile(masked: bool):
        q, k, v = loaded or operands()
        out = None
        for g, mask in enumerate(masks):
            s = jax.lax.dot_general(_only(mask, q), k, _NT,
                                    preferred_element_type=jnp.float32)
            if has_bias:
                s = s + bias_ref[0]   # (1, block_k) key bias row
            if masked:
                s = _hide(s, first_q, first_k, diffusion, False)
            if nk == 1:
                # every key in this block: a plain softmax, no running
                # state
                m = jnp.max(s, axis=-1, keepdims=True)
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=-1, keepdims=True)
                pv = jax.lax.dot_general(p.astype(v.dtype), v, _NN,
                                         preferred_element_type=jnp.float32)
                out = _merge(mask, pv * (1.0 / l), out)
                if save_lse:
                    lse_ref[0, g] = _col_to_row(m + jnp.log(l))
                continue
            m_prev = m_ref[g, :, :1]                         # (block_q, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_ref[g, :, :1] \
                + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])
            acc = acc_ref[:]
            acc_ref[:] = _merge(mask, acc * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, _NN,
                preferred_element_type=jnp.float32), acc)
        if nk == 1:
            o_ref[0] = out.astype(o_ref.dtype)

    if diffusion:
        _when_kind(kind_ref[pl.program_id(2) * nk + ik], tile)
    elif not causal:
        tile(False)
    elif nk == 1:
        tile(True)
    else:
        # the tile holds keys a query of it may see; the diagonal
        # crosses it where its last key lies after its first query
        _when_needed(first_k <= first_q + block_q - 1,
                     first_k + block_k - 1 > first_q, tile)
    if nk == 1:
        return

    @pl.when(ik == nk - 1)
    def _():
        out = None
        for g, mask in enumerate(masks):
            l = l_ref[g, :, :1]
            out = _merge(mask, acc_ref[:] * (1.0 / l), out)
            if save_lse:
                lse_ref[0, g] = _col_to_row(m_ref[g, :, :1] + jnp.log(l))
        o_ref[0] = out.astype(o_ref.dtype)


def _head_group(h: int, d: int):
    """``(heads a block, lanes a head)``: narrow heads that tile 128
    lanes share a block as they are; heads of a multiple of 128 lanes
    are a block each; anything else is zero-padded up to the next 128
    lanes a head (zero columns change neither logits nor outputs)."""
    if d < _LANES and _LANES % d == 0 and h % (_LANES // d) == 0:
        return _LANES // d, d
    return 1, _round_up(d, _LANES)


def _pad_heads(x, h: int, dp: int):
    """(B, L, H·D) → (B, L, H·dp), each head zero-padded to ``dp``."""
    b, l, e = x.shape
    if e == h * dp:
        return x
    x = x.reshape(b, l, h, e // h)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, dp - e // h)))
    return x.reshape(b, l, h * dp)


def _unpad_heads(x, h: int, d: int):
    """The inverse: (B, L, H·dp) → (B, L, H·D)."""
    b, l, e = x.shape
    if e == h * d:
        return x
    return x.reshape(b, l, h, e // h)[..., :d].reshape(b, l, h * d)


def _pad_rows(x, rows: int, value=0.0):
    """Pad axis 1 up to ``rows``."""
    if x.shape[1] == rows:
        return x
    widths = [(0, 0)] * x.ndim
    widths[1] = (0, rows - x.shape[1])
    return jnp.pad(x, widths, constant_values=value)


def _key_bias(bias, b: int, lk: int, lk_p: int):
    """The (B, lk_p) float32 key bias: the caller's, NEG_INF on padded
    key columns (made here where the caller gave none); None where
    there is nothing to mask."""
    if bias is None and lk_p == lk:
        return None
    if bias is None:
        bias = jnp.zeros((b, lk), jnp.float32)
    return _pad_rows(bias.astype(jnp.float32), lk_p, NEG_INF)


def _clip_blocks(lq: int, lk: int, block_q: int, block_k: int,
                 q_rows: int):
    """``(block_q, block_k, lq_p, lk_p)``: the blocks clipped to the
    shapes (queries to a multiple of ``q_rows``, keys to whole lanes)
    and the padded lengths."""
    block_q = min(block_q, _round_up(lq, q_rows))
    block_k = _round_up(min(block_k, _round_up(lk, _LANES)), _LANES)
    return block_q, block_k, _round_up(lq, block_q), _round_up(lk, block_k)


def _geometry(lq: int, lk: int, e: int, h: int, block_q: int,
              block_k: int, q_rows: int):
    """How a call tiles: ``(d, group, dp, width, block_q, block_k,
    lq_p, lk_p)`` — head dim, heads a block, lanes a head, lanes a
    block, and ``_clip_blocks``' blocks and padded lengths."""
    d = e // h
    group, dp = _head_group(h, d)
    return (d, group, dp, group * dp,
            *_clip_blocks(lq, lk, block_q, block_k, q_rows))


def masked_call_tiles(length: int, block_diffusion=None) -> str:
    """What a differentiated masked call over ``length`` positions runs
    at the blocks its shapes pick — the causal triangle's, or the
    block-diffusion mask's where ``block_diffusion = (L, B)`` is given —
    in ``ops/tiling.tile_counts``' words: ``causal tiles plain 6 masked
    4, sub-tiles 12/16``."""
    block_q, block_k, lq_p, lk_p = _clip_blocks(
        length, length, *pick_blocks(length, length, True), _LANES)
    mask = "block_diffusion" if block_diffusion else "causal"
    return f"{mask} tiles " + tile_counts(
        block_diffusion, block_q, block_k, lq_p // block_q, lk_p // block_k,
        _sub_tile(block_q), _sub_tile(block_k))


def _call_name(causal: bool, diffusion, which: str) -> str:
    """The custom call's name: by it a trace's reader knows the mask."""
    mask = "block_diffusion" if diffusion else \
        "causal" if causal else "flash"
    return f"{mask}_attention_{which}"


def _launch(kernel, tables, args, *, name: str, grid, in_specs, out_specs,
            out_shape, scratch, interpret: bool):
    """One kernel over ``grid``. ``tables`` (a masked call's)
    are prefetched to scalar memory ahead of the grid: the kernel's
    first references and the index maps' last arguments."""
    common = dict(out_shape=out_shape, compiler_params=_COMPILER_PARAMS,
                  interpret=interpret, name=name)
    if not tables:
        return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                              out_specs=out_specs, scratch_shapes=scratch,
                              **common)(*args)
    return pl.pallas_call(kernel, grid_spec=pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(tables), grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch), **common)(
            *tables, *args)


def _flash_forward(q, k, v, bias, h: int, scale: float, block_q: int,
                   block_k: int, interpret: bool, save_lse: bool,
                   causal: bool = False, diffusion=None):
    """q (B, Lq, H·D), k/v (B, Lk, H·D) → ``o`` (B, Lq, H·D); with
    ``save_lse`` (the differentiated call) ``o`` in float32 as the
    kernel accumulated it and the per-row log-sum-exp as
    (B, H, 1, Lq) float32."""
    b, lq, e = q.shape
    lk = k.shape[1]
    # 16-sublane rounding covers the strictest dtype tile (bf16 needs
    # sublane multiples of 16; fp32 needs 8), e.g. the 1-query
    # classification decoder; the log-sum-exp row needs whole lanes
    d, group, dp, width, block_q, block_k, lq_p, lk_p = _geometry(
        lq, lk, e, h, block_q, block_k, _LANES if save_lse else 16)
    # padded query rows are sliced off below; padded keys are masked
    q = _pad_rows(_pad_heads(q, h, dp), lq_p)
    k = _pad_rows(_pad_heads(k, h, dp), lk_p)
    v = _pad_rows(_pad_heads(v, h, dp), lk_p)
    # the causal mask covers padded keys: they lie after every real
    # row; the block-diffusion mask's padded keys are clean positions
    # past every real row's
    bias = None if causal or diffusion else _key_bias(bias, b, lk, lk_p)
    nq, nk = lq_p // block_q, lk_p // block_k
    has_bias = bias is not None
    tables = _mask_tables(diffusion, block_q, block_k, nq, nk, None, False)

    def k_index(ib, ih, iq, ik, *tables):
        if causal:
            # above the diagonal hold the last block a query of this
            # block sees: the pipeline fetches nothing it has
            ik = jnp.minimum(ik, (iq * block_q + block_q - 1) // block_k)
        if diffusion:
            ik = tables[1][iq * nk + ik]
        return ib, ik, ih

    in_specs = [
        pl.BlockSpec((1, block_q, width),
                     lambda ib, ih, iq, ik, *_: (ib, iq, ih)),
        pl.BlockSpec((1, block_k, width), k_index),
        pl.BlockSpec((1, block_k, width), k_index),
    ]
    args = [q, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, 1, block_k),
                                     lambda ib, ih, iq, ik: (ib, 0, ik)))
        args.append(bias[:, None, :])
    out_specs = [pl.BlockSpec((1, block_q, width),
                              lambda ib, ih, iq, ik, *_: (ib, iq, ih))]
    out_shape = [jax.ShapeDtypeStruct(
        (b, lq_p, h * dp), jnp.float32 if save_lse else q.dtype)]
    if save_lse:
        out_specs.append(pl.BlockSpec(
            (1, group, 1, block_q),
            lambda ib, ih, iq, ik, *_: (ib, ih, 0, iq)))
        out_shape.append(
            jax.ShapeDtypeStruct((b, h, 1, lq_p), jnp.float32))
    scratch = [] if nk == 1 else [
        pltpu.VMEM((group, block_q, _LANES), jnp.float32),  # running max
        pltpu.VMEM((group, block_q, _LANES), jnp.float32),  # normalizer
        pltpu.VMEM((block_q, width), jnp.float32),   # unnormalized acc
    ]
    out = _launch(
        functools.partial(_fwd_kernel, scale=scale, nk=nk, group=group,
                          has_bias=has_bias, save_lse=save_lse,
                          causal=causal, block_q=block_q, block_k=block_k,
                          diffusion=diffusion),
        tables, args, name=_call_name(causal, diffusion, "fwd"),
        grid=(b, h // group, nq, nk), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape, scratch=scratch,
        interpret=interpret)
    o = _unpad_heads(out[0][:, :lq], h, d)
    return (o, out[1][..., :lq]) if save_lse else o


# --- backward ----------------------------------------------------------------


def _bwd_kernel(*refs, scale: float, nq: int, nk: int, block_q: int,
                group: int, has_bias: bool, causal: bool = False,
                block_k: int = 0, diffusion=None, sub=None):
    refs = iter(refs)
    if diffusion:   # the tiles' kinds; the held tiles are the index maps'
        kind_ref, _ = next(refs), next(refs)
    if sub:         # where a masked tile's list of sub-tiles lies; the lists
        span_ref, entry_ref = next(refs), next(refs)
    q_ref, k_ref, v_ref, do_ref = (next(refs) for _ in range(4))
    kbar_ref, vbar_ref = next(refs), next(refs)
    lse_ref, delta_ref = next(refs), next(refs)
    bias_ref = next(refs) if has_bias else None
    dq_ref, dk_ref, dv_ref = next(refs), next(refs), next(refs)
    db_ref = next(refs) if has_bias else None
    # one block of keys (of queries) and no mask: dq (dk, dv) written
    # straight out; a masked call's tiles and sub-tiles add into
    # accumulators whatever its grid
    dq_direct = nk == 1 and not sub
    dk_direct = nq == 1 and not sub
    if not dk_direct:
        dk_acc, dv_acc = next(refs), next(refs)
        db_acc = next(refs) if has_bias else None
    iq = pl.program_id(3)
    ik = pl.program_id(2)
    if sub:
        # which tiles run is the mask's to say: the accumulators start
        # from zeros at the head of each sweep and every tile adds
        @pl.when(iq == 0)
        def _():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        @pl.when(ik == 0)
        def _():
            rows = pl.ds(pl.multiple_of(iq * block_q, block_q), block_q)
            dq_ref[0, rows, :] = jnp.zeros((block_q, dq_ref.shape[-1]),
                                           dq_ref.dtype)

    def centred(k, at):
        """The keys ``k`` and the values (``v_ref[at]``) about their
        means over the keys, for the two contractions a common
        component would spoil (_flash_backward)."""
        kc = ((k - kbar_ref[0]) * scale).astype(k.dtype)
        return kc, (v_ref[at] - vbar_ref[0]).astype(k.dtype)

    def operands():
        """The tile's: q (scaled) and do (block_q, W), k (block_k, W)
        and the centred k and v, operand dtype."""
        q = q_ref[0] * scale
        k = k_ref[0]
        return q, do_ref[0], k, *centred(k, 0)

    # as in the forward: a masked call loads where a tile runs
    loaded = None if sub else operands()
    masks = _head_masks(q_ref.shape[-1], group)

    def grads(q, do, k, kc, vc, stat, hide=None):
        """``dq, dk, dv, db`` of the queries ``q``/``do`` against the
        keys ``k`` (``kc``, ``vc`` centred); ``stat(ref, g)`` reads head
        ``g``'s row of the queries' log-sum-exp or delta; ``hide`` masks
        the scores."""
        dq = dk = dv = db = None
        for g, mask in enumerate(masks):
            # this head's lanes of the small tiles, zeros elsewhere: its
            # gradients then land in its own lanes and the heads' add up
            qg, dog, kg = _only(mask, q), _only(mask, do), _only(mask, kc)
            # scores transposed, keys on sublanes: (keys, queries)
            s = jax.lax.dot_general(k, qg, _NT,
                                    preferred_element_type=jnp.float32)
            if has_bias:
                s = s + bias_ref[0]   # (block_k, 1) key bias column
            if hide is not None:
                s = hide(s)
            p = jnp.exp(s - stat(lse_ref, g))            # rows (1, queries)
            dp = jax.lax.dot_general(vc, dog, _NT,
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - stat(delta_ref, g))
            dsb = ds.astype(q.dtype)
            dv_g = jax.lax.dot_general(p.astype(do.dtype), dog, _NN,
                                       preferred_element_type=jnp.float32)
            dk_g = jax.lax.dot_general(dsb, qg, _NN,
                                       preferred_element_type=jnp.float32)
            # the one contraction over keys: Mosaic transposes the score
            # tile
            dq_g = jax.lax.dot_general(dsb, kg, _TN,
                                       preferred_element_type=jnp.float32)
            dq = dq_g if dq is None else dq + dq_g
            dk = dk_g if dk is None else dk + dk_g
            dv = dv_g if dv is None else dv + dv_g
            if has_bias:
                # the key bias's gradient: every head's, a column here
                db_g = jnp.sum(ds, axis=1, keepdims=True)
                db = db_g if db is None else db + db_g
        return dq, dk, dv, db

    def sub_tile(rows, cols, row, col, masked: bool):
        k = k_ref[0, cols, :]
        hide = functools.partial(
            _hide, first_q=iq * block_q + row, first_k=ik * block_k + col,
            diffusion=diffusion, transposed=True) if masked else None
        dq, dk, dv, _ = grads(
            q_ref[0, rows, :] * scale, do_ref[0, rows, :], k,
            *centred(k, (0, cols)),
            lambda ref, g: ref[0, g, :, rows], hide)
        at = pl.multiple_of(iq * block_q + row, sub[0])
        dq_ref[0, pl.ds(at, sub[0]), :] += dq
        dk_acc[cols, :] += dk
        dv_acc[cols, :] += dv

    def tile(masked: bool):
        if masked:
            return _each_sub_tile(span_ref, entry_ref, ik * nq + iq, sub,
                                  sub_tile)
        # the whole tile in one pass
        dq, dk, dv, db = grads(*(loaded or operands()),
                               lambda ref, g: ref[0, g])
        if dq_direct:
            dq_ref[0] = dq.astype(dq_ref.dtype)
        else:
            # float32 block of the whole (b, head group), resident
            # across the sweep; every query block sees key block 0
            rows = pl.ds(pl.multiple_of(iq * block_q, block_q), block_q)
            if sub:
                dq_ref[0, rows, :] += dq
            else:
                @pl.when(ik == 0)
                def _():
                    dq_ref[0, rows, :] = dq

                @pl.when(ik > 0)
                def _():
                    dq_ref[0, rows, :] += dq

        if dk_direct:
            dk_ref[0] = dk.astype(dk_ref.dtype)
            dv_ref[0] = dv.astype(dv_ref.dtype)
            if has_bias:
                db_ref[0, 0] = _col_to_row(db)   # a lane-dense row in HBM
            return
        if sub:
            dk_acc[:] += dk
            dv_acc[:] += dv
            return

        @pl.when(iq == 0)
        def _():
            dk_acc[:] = dk
            dv_acc[:] = dv
            if has_bias:
                db_acc[:] = db

        @pl.when(iq > 0)
        def _():
            dk_acc[:] += dk
            dv_acc[:] += dv
            if has_bias:
                db_acc[:] += db

    if diffusion:
        _when_kind(kind_ref[ik * nq + iq], tile)
    elif causal:
        # from the first query block that sees this key block on
        _when_needed(iq * block_q + block_q - 1 >= ik * block_k,
                     ik * block_k + block_k - 1 > iq * block_q, tile)
    else:
        tile(False)
    if dk_direct:
        return

    @pl.when(iq == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
        if has_bias:
            db_ref[0, 0] = _col_to_row(db_acc[:])


def _flash_backward(q, k, v, bias, o, lse, do, h: int, scale: float,
                    block_q: int, block_k: int, interpret: bool,
                    causal: bool = False, diffusion=None):
    """``dq, dk, dv`` (and ``dbias`` (B, Lk) where a bias was given)
    from the saved float32 output and log-sum-exp row; all
    (B, L, H·D)."""
    b, lq, e = q.shape
    lk = k.shape[1]
    d, group, dp, width, block_q, block_k, lq_p, lk_p = _geometry(
        lq, lk, e, h, block_q, block_k, _LANES)
    # ds = p * (dp - delta), with dp = do·v and delta = rowsum(do * o),
    # is a difference of two numbers that share whatever the values
    # have in common, and dq = ds·k then weighs every rounding error
    # left in it by whatever the keys have in common (normed tokens
    # behind one bias have a lot). Both contractions are unchanged by
    # a shift of all keys' rows (sum_k p = 1, sum_k ds = 0), so they
    # take v and k about their means over the keys: what is left to
    # round is the spread, as in the materialised core, whose float32
    # delta cancels exactly. delta is (B, H, 1, Lq) rows beside the
    # log-sum-exp.
    kbar = jnp.mean(k.astype(jnp.float32), axis=1, keepdims=True)
    vbar = jnp.mean(v.astype(jnp.float32), axis=1, keepdims=True)
    delta = (do.astype(jnp.float32) * (o - vbar)).reshape(
        b, lq, h, d).sum(-1)
    delta = _pad_rows(delta, lq_p).swapaxes(1, 2)[:, :, None, :]
    lse = jnp.pad(lse, ((0, 0), (0, 0), (0, 0), (0, lq_p - lq)))
    bias_grad = bias is not None
    q = _pad_rows(_pad_heads(q, h, dp), lq_p)
    do = _pad_rows(_pad_heads(do, h, dp), lq_p)
    k = _pad_rows(_pad_heads(k, h, dp), lk_p)
    v = _pad_rows(_pad_heads(v, h, dp), lk_p)
    kbar, vbar = _pad_heads(kbar, h, dp), _pad_heads(vbar, h, dp)
    bias = None if causal or diffusion else _key_bias(bias, b, lk, lk_p)
    nq, nk = lq_p // block_q, lk_p // block_k
    has_bias = bias is not None
    tables, sub = (), None
    if causal or diffusion:   # the forward's tiles, swept keys first
        sub = _sub_tile(block_q), _sub_tile(block_k)
        tables = _mask_tables(diffusion, block_q, block_k, nq, nk, sub,
                              True)
    dq_direct, dk_direct = nk == 1 and not sub, nq == 1 and not sub
    if not dq_direct and lq_p * width * 4 > _DQ_RESIDENT_MAX:
        raise NotImplementedError(
            f"flash attention backward keeps dq ({lq_p} x {width} "
            f"float32) in VMEM while the keys stream; over "
            f"{_DQ_RESIDENT_MAX} bytes use impl='chunked', or chunk the "
            "queries")

    def seen(ik, iq, *tables):
        """The query block read at grid step (ik, iq): before the first
        that sees this key block, that first one (nothing new to
        fetch); where a table says, the block it holds."""
        if causal and nq > 1:
            iq = jnp.maximum(iq, (ik * block_k) // block_q)
        if diffusion:
            iq = tables[1][ik * nq + iq]
        return iq

    q_spec = pl.BlockSpec(
        (1, block_q, width),
        lambda ib, ih, ik, iq, *t: (ib, seen(ik, iq, *t), ih))
    k_spec = pl.BlockSpec((1, block_k, width),
                          lambda ib, ih, ik, iq, *_: (ib, ik, ih))
    row_spec = pl.BlockSpec(
        (1, group, 1, block_q),
        lambda ib, ih, ik, iq, *t: (ib, ih, 0, seen(ik, iq, *t)))
    mean_spec = pl.BlockSpec((1, 1, width),
                             lambda ib, ih, ik, iq, *_: (ib, 0, ih))
    in_specs = [q_spec, k_spec, k_spec, q_spec, mean_spec, mean_spec,
                row_spec, row_spec]
    args = [q, k, v, do, kbar, vbar, lse, delta]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, block_k, 1),
                                     lambda ib, ih, ik, iq: (ib, ik, 0)))
        args.append(bias[:, :, None])
    if dq_direct:
        # one key block: no query block is skipped, seen() is iq
        dq_spec, dq_dtype = q_spec, q.dtype
    else:
        dq_spec = pl.BlockSpec((1, lq_p, width),
                               lambda ib, ih, ik, iq, *_: (ib, 0, ih))
        dq_dtype = jnp.float32
    out_specs = [dq_spec, k_spec, k_spec]
    out_shape = [jax.ShapeDtypeStruct(q.shape, dq_dtype),
                 jax.ShapeDtypeStruct(k.shape, k.dtype),
                 jax.ShapeDtypeStruct(v.shape, v.dtype)]
    scratch = [] if dk_direct else [
        pltpu.VMEM((block_k, width), jnp.float32),   # dk accumulator
        pltpu.VMEM((block_k, width), jnp.float32),   # dv accumulator
    ]
    if has_bias:
        out_specs.append(pl.BlockSpec(
            (1, 1, 1, block_k), lambda ib, ih, ik, iq: (ib, ih, 0, ik)))
        out_shape.append(jax.ShapeDtypeStruct(
            (b, h // group, 1, lk_p), jnp.float32))
        if nq > 1:
            scratch.append(pltpu.VMEM((block_k, 1), jnp.float32))

    dq, dk, dv, *db = _launch(
        functools.partial(_bwd_kernel, scale=scale, nq=nq, nk=nk,
                          block_q=block_q, group=group,
                          has_bias=has_bias, causal=causal,
                          block_k=block_k, diffusion=diffusion, sub=sub),
        tables, args, name=_call_name(causal, diffusion, "bwd"),
        grid=(b, h // group, nk, nq), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape, scratch=scratch,
        interpret=interpret)

    def trim(x, rows):
        return _unpad_heads(x[:, :rows], h, d)

    dbias = db[0][:, :, 0, :lk].sum(axis=1) if bias_grad else None
    return trim(dq, lq).astype(k.dtype), trim(dk, lk), trim(dv, lk), dbias


# --- the differentiable core -------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, bias, h, scale, block_q, block_k, interpret, causal,
           diffusion):
    # forward-only use: no residual output leaves the kernel
    return _flash_forward(q, k, v, bias, h, scale, block_q, block_k,
                          interpret, False, causal, diffusion)


def _flash_fwd(q, k, v, bias, h, scale, block_q, block_k, interpret,
               causal, diffusion):
    # the residual output stays float32: the backward's delta =
    # rowsum(do * o) must cancel sum_k(dp * p) to float32 rounding, or
    # every key of a row gets the same push and dq drifts along the
    # keys' common component (seen as update_norm_gap, PERF.md PR 26)
    o, lse = _flash_forward(q, k, v, bias, h, scale, block_q, block_k,
                            interpret, True, causal, diffusion)
    # named for a ``remat`` layer's save list (ops/remat.py): with the
    # pair saved the backward does not run this kernel again; the bf16
    # output below is a cast of it and is recomputed, not held too
    o, lse = dear(o, "attn_out"), dear(lse, "attn_out")
    return o.astype(q.dtype), (q, k, v, bias, o, lse)


def _flash_bwd(h, scale, block_q, block_k, interpret, causal, diffusion,
               res, g):
    q, k, v, bias, o, lse = res
    dq, dk, dv, dbias = _flash_backward(
        q, k, v, bias, o, lse, g.astype(q.dtype), h, scale, block_q,
        block_k, interpret, causal, diffusion)
    if dbias is not None:
        # a learned additive key bias trains the same as under
        # "chunked"/"einsum"; a mask's cotangent is dropped by its caller
        dbias = dbias.astype(bias.dtype)
    return dq, dk, dv, dbias


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_channels(q, k, v, *, num_heads: int, bias=None,
                             causal: bool = False, block_diffusion=None,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: Optional[bool] = None):
    """Fused attention on heads as the projections leave them, side by
    side on the channel axis. q: (B, Lq, H·D); k, v: (B, Lk, H·D);
    bias: optional (B, Lk) additive key bias (NEG_INF at padding);
    ``causal``: query i sees keys 0..i (Lq == Lk, no bias);
    ``block_diffusion``: ``(L, B)``, a row of ``L`` noised positions
    beside their ``L`` clean ones in blocks of ``B`` (Lq == Lk == 2 L,
    no bias, not causal; the rules at the head of this file).
    Blocks come from the shapes (``pick_blocks``) unless given.
    Returns (B, Lq, H·D) in q's dtype."""
    from perceiver_tpu.utils.platform import resolve_interpret
    if q.shape[-1] % num_heads:
        raise ValueError(f"{q.shape[-1]} channels do not split into "
                         f"{num_heads} heads")
    if causal and (bias is not None or q.shape[1] != k.shape[1]):
        raise ValueError(
            "causal attention is over square scores with no key bias: "
            f"{q.shape[1]} queries, {k.shape[1]} keys, bias "
            f"{'given' if bias is not None else 'None'}")
    if block_diffusion is not None:
        half, block = (int(n) for n in block_diffusion)
        if causal or bias is not None or half % block \
                or not q.shape[1] == k.shape[1] == 2 * half:
            raise ValueError(
                "block-diffusion attention is over the square scores of "
                f"2 x {half} positions in blocks of {block}, with no key "
                f"bias and no causal mask beside it: {q.shape[1]} queries, "
                f"{k.shape[1]} keys, bias "
                f"{'given' if bias is not None else 'None'}, causal "
                f"{causal}")
        block_diffusion = (half, block)
    if scale is None:
        scale = 1.0 / ((q.shape[-1] // num_heads) ** 0.5)
    auto_q, auto_k = pick_blocks(q.shape[1], k.shape[1],
                                 causal or block_diffusion is not None)
    return _flash(q, k, v, bias, int(num_heads), float(scale),
                  int(auto_q if block_q is None else block_q),
                  int(auto_k if block_k is None else block_k),
                  resolve_interpret(interpret), bool(causal),
                  block_diffusion)


def flash_attention(q, k, v, **kwargs):
    """``flash_attention_channels`` for (B, H, L, D) operands."""
    b, h, lq, d = q.shape

    def channels(x):
        return x.swapaxes(1, 2).reshape(b, x.shape[2], h * d)

    out = flash_attention_channels(channels(q), channels(k), channels(v),
                                   num_heads=h, **kwargs)
    return out.reshape(b, lq, h, d).swapaxes(1, 2)
