"""The gated delta rule (``ops/delta_rule.py``) as Pallas kernels for
TPU, forward and backward: the chunked WY form at the einsum form's
chunk boundaries and in its arithmetic, with every ``Q x Q`` float32
matrix of a chunk, its inverse and the state carried between chunks in
VMEM.

One grid step is one chunk of ``Q`` positions of one key head of one
row: grid ``(rows, key heads, chunks)``, the chunk axis last and
sequential. A step holds the key head's ``q`` and ``k`` as the
projection and the l2 norm leave them, ``(Q, Dk)`` lanes of the
``(B, S, Hk Dk)`` array, the ``(Q, R Dv)`` lanes of its ``R = Hv / Hk``
value heads side by side, and their cumulative log-decay and write
strength as one heads-major float32 tile (``_tile``: a row a set of
heads, the positions on the lanes: the decay tile's columns as they
come), which the kernel transposes for what is needed position-major (a
column a head: the decay tile's rows, the factors of ``k`` and ``v``);
the carried state of the key head's value heads is a ``(Dk, R Dv)``
float32 scratch.

**The value heads of a key head go through side by side**, ``P = 128 //
Q`` at a time where that divides them (``pack_of``): their ``Q x Q``
tiles are the lanes of one ``(Q, P Q)`` tile, so a vreg and the MXU's
columns are full, an elementwise pass and a product of the inverse serve
``P`` heads, and a product that wants one head's tile takes the packed
tile with the other heads' lanes zeroed against the heads' operands
stacked. Products that share an operand are joined, so that the MXU
sees 128 rows or 256 columns where the einsums hand it 64: ``[q; k]
[k; k]^T`` once a key head, ``T [beta v | beta k e^G]`` and ``[W; q
e^G] S`` a value head.

**The inverse** ``T = (I - A)^-1`` is made by blocks of 16
(``blocked_inverse``): the diagonal blocks side by side as one ``(16,
P Q)`` matrix by doubling (``A^16 = 0`` there: four products, a sum and
the next power sharing a right-hand side), then merged pair by pair
with ``T21 = T22 A21 T11`` (two products of ``Q / 2`` rows a level):
eight products for ``P`` heads at ``Q = 64`` where the doubling of a
whole matrix takes ten a head, every product float32 at ``HIGHEST``.
It is the einsum form's matrix to float32 rounding.

Forward ``delta_rule_fwd`` writes ``o`` once and nothing else. The
backward takes ``q, k, v, g, beta`` and ``do`` alone: a pass over the
chunks in order (``delta_rule_bwd_states``) rebuilds the state each
chunk found, float32, and the reversed pass ``delta_rule_bwd``
recomputes each chunk's tiles, carries the state's cotangent backwards
and writes ``dq``, ``dk``, ``dv`` (float32 sums cast once) and, in
float32, the cotangents of ``beta`` and of the cumulative log-decay.
**The inverse's cotangent is analytic**, ``dA = T^T dT T^T`` masked
strictly lower: two products. The chain through the cumulative sum to
``g`` is XLA's, on ``(B, S, Hv)`` float32 arrays.

Rounding is the einsum form's or finer: float32 ``g``, ``beta``, every
decay, ``A``, ``T`` and the carried state (and its cotangent); operands
of a product in the compute dtype where ``ops/delta_rule`` casts them
and where its transposed products take a cotangent as an operand;
float32 accumulation. What a product hands on stays float32 until the
next product takes it (the einsum form rounds ``U`` to the compute
dtype before ``W S`` is taken off it; here it is not). Under a float32
compute dtype every product is float32 at ``HIGHEST``.

On non-TPU backends the kernels run in Pallas interpreter mode, so
tests exercise the identical code path on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_tpu.ops.pallas_attention import _LANES, _NN, _NT, _TN

_F32 = jnp.float32

#: the inverse's diagonal blocks, made by doubling side by side
_BLOCK = 16

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def fits(*, chunk: int, key_dim: int, value_dim: int, dtype) -> bool:
    """Whether the kernels tile a call: heads of whole lanes (a head's
    ``q``, ``k``, ``v`` are blocks of the projections' own arrays),
    chunks of 16, 32, 64 or 128 positions (whole sublanes of a bf16
    block; the inverse's blocks of 16 merge pair by pair), a compute
    dtype of bfloat16 or float32."""
    return (key_dim % _LANES == 0 and value_dim % _LANES == 0
            and chunk in (16, 32, 64, 128)
            and dtype in (jnp.bfloat16, jnp.float32))


def _dot32(lhs, rhs, dims):
    """A float32 product at full precision. (The six bfloat16 passes
    written out by hand, three products that share right-hand terms,
    load the MXU a quarter as often and were no faster on the chip:
    11.6 ms a layer forward for 11.1, PERF.md, PR 43.)"""
    return jax.lax.dot_general(lhs, rhs, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _dot_in(dtype):
    """Products on operands in ``dtype``, summed in float32 (at
    ``HIGHEST`` where ``dtype`` is float32 itself): ``ops/delta_rule``'s
    ``_dot``."""
    if dtype == _F32:
        return _dot32

    def dot(lhs, rhs, dims):
        return jax.lax.dot_general(lhs.astype(dtype), rhs.astype(dtype),
                                   dims, preferred_element_type=_F32)

    return dot


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def pack_of(per: int, chunk: int) -> int:
    """How many of a key head's value heads lie side by side on the
    lanes of one tile: a ``Q x Q`` matrix a head fills ``Q`` of a
    vreg's 128 lanes and of the MXU's 128 columns, so the heads go
    through ``128 // Q`` at a time where that divides them."""
    pack = max(1, min(per, _LANES // chunk))
    while per % pack:
        pack -= 1
    return pack


def _apart(wide):
    """(Q, P Q) matrices side by side -> (P Q, P Q) block diagonal."""
    size, lanes = wide.shape
    if lanes == size:
        return wide
    square = (lanes, lanes)
    return jnp.where(_iota(square, 0) // size == _iota(square, 1) // size,
                     jnp.concatenate([wide] * (lanes // size), axis=0), 0.0)


def blocked_inverse(a):
    """``(I - a)^-1`` for ``a`` strictly lower triangular, float32, by
    blocks of 16: ``ops.delta_rule.unit_lower_inverse``'s matrix. ``a``
    is (Q, Q), ``Q`` 16, 32, 64 or 128, or several such matrices side
    by side, (Q, P Q): every product then serves all ``P``."""
    size, lanes = a.shape
    blocks = size // _BLOCK
    square = (lanes, lanes)
    same_block = _iota(square, 0) // _BLOCK == _iota(square, 1) // _BLOCK

    def spread(stacked):   # (16, P Q) side by side -> block diagonal
        return jnp.where(
            same_block,
            jnp.concatenate([stacked] * (lanes // _BLOCK), axis=0), 0.0)

    # the diagonal blocks side by side, and their inverses by doubling:
    # (I + a)(I + a^2)(I + a^4)(I + a^8), a^16 = 0
    lane_block = _iota((_BLOCK, lanes), 1) % size // _BLOCK
    power = jnp.zeros((_BLOCK, lanes), _F32)
    for b in range(blocks):
        power = jnp.where(lane_block == b,
                          a[b * _BLOCK:(b + 1) * _BLOCK], power)
    total = power + (_iota((_BLOCK, lanes), 0)
                     == _iota((_BLOCK, lanes), 1) % _BLOCK).astype(_F32)
    power = _dot32(power, spread(power), _NN)
    for _ in range(2):   # the sum and the next power share a right-hand side
        both = _dot32(jnp.concatenate([total, power], axis=0),
                      spread(power), _NN)
        total, power = total + both[:_BLOCK], both[_BLOCK:]
    total = total + _dot32(total, spread(power), _NN)
    inverse = jnp.where(
        _iota(a.shape, 0) // _BLOCK == _iota(a.shape, 1) % size // _BLOCK,
        jnp.concatenate([total] * blocks, axis=0), 0.0)
    # a pair of inverted blocks of ``half`` and the block under them:
    # T21 = T22 A21 T11, every pair's second rows in one product
    a_apart = _apart(a)
    half = _BLOCK
    while half < size:
        starts = range(0, size, 2 * half)
        second = jnp.concatenate(
            [inverse[o + half:o + 2 * half] for o in starts], axis=0)
        under = _iota(second.shape, 1) % size // half \
            == 2 * (_iota(second.shape, 0) // half)
        second = second + _dot32(
            jnp.where(under, _dot32(second, a_apart, _NN), 0.0),
            _apart(inverse), _NN)
        inverse = jnp.concatenate(
            [piece for i, o in enumerate(starts)
             for piece in (inverse[o:o + half],
                           second[i * half:(i + 1) * half])], axis=0)
        half *= 2
    return inverse


class _Packed:
    """The tiles of ``pack`` value heads of one chunk, side by side on
    the lanes, (Q, P Q): from the key head's products and the heads'
    own decays and write strengths; what all three kernels recompute.
    ``kk`` (Q, P Q) is ``k k^T`` once a head; k (Q, Dk) and ``values``
    (a (Q, Dv) block a head) in the compute dtype; ``tile`` and
    ``columns`` are the key head's heads-major float32 tile (a row a
    set of ``P`` heads: the log-decays, then the betas) and its
    transpose (``_columns``); the heads are set ``i`` of ``sets``."""

    def __init__(self, kk, k, values, tile, columns, i: int, sets: int, dot):
        chunk, lanes = kk.shape
        self.pack = pack = len(values)
        self.size, self.width = chunk, values[0].shape[1]
        lane_head = _iota((1, lanes), 1) // chunk
        self.masks = [None] if pack == 1 else [
            lane_head == p for p in range(pack)]

        def column(c):     # (Q, 1) a head
            return [columns[p * chunk:(p + 1) * chunk, c:c + 1]
                    for p in range(pack)]

        log_row = tile[i:i + 1, :lanes]
        log_col, self.beta = column(i), column(sets + i)
        at, wrote = _iota(kk.shape, 0), _iota(kk.shape, 1) % chunk
        self.strict = at > wrote
        # the mask goes in before the ``exp``: no ``exp`` of a positive
        self.decay = jnp.exp(jnp.where(
            at >= wrote, self.wide(log_col) - log_row, -jnp.inf))
        self.a = jnp.where(self.strict,
                           -self.wide(self.beta) * kk * self.decay, 0.0)
        self.t = blocked_inverse(self.a)
        self.from_start = [jnp.exp(x) for x in log_col]
        last = [x[chunk - 1:] for x in log_col]                  # (1, 1)
        self.to_end = [jnp.exp(e - x) for e, x in zip(last, log_col)]
        self.whole = [jnp.exp(e) for e in last]
        # (1, Dv): Mosaic broadcasts along lanes or sublanes, not both
        self.whole_lanes = [
            jnp.exp(e + jnp.zeros((1, self.width), _F32)) for e in last]
        self.kf = k.astype(_F32)
        self.vf = [v.astype(_F32) for v in values]
        self.written = jnp.concatenate([
            jnp.concatenate([b * v, (b * e) * self.kf], axis=1)
            for b, v, e in zip(self.beta, self.vf, self.from_start)], axis=0)
        self.u, self.w = [], []
        for p in range(pack):
            uw = dot(self.only(p, self.t), self.written, _NN)    # (Q, Dv + Dk)
            self.u.append(uw[:, :self.width])
            self.w.append(uw[:, self.width:])
        self.k_to_end = [self.kf * x for x in self.to_end]

    def wide(self, columns):
        """(Q, P Q): each head's column in all the lanes of its tile."""
        out = jnp.broadcast_to(columns[0],
                               (self.size, self.pack * self.size))
        for mask, column in zip(self.masks[1:], columns[1:]):
            out = jnp.where(mask, column, out)
        return out

    def only(self, p: int, tile):
        """``tile`` with the other heads' lanes zeroed: as an operand
        it picks head ``p``'s rows of what the heads stack."""
        return tile if self.pack == 1 else jnp.where(self.masks[p], tile, 0.0)

    def rows(self, p: int):
        """Head ``p``'s rows of what the heads stack."""
        return slice(p * self.size, (p + 1) * self.size)


def _zero_at_first(ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ref[:] = jnp.zeros_like(ref)


def _columns(tile):
    """(128, 128) float32, the transpose of a key head's heads-major
    tile: row ``p Q + t`` holds position ``t`` of the ``p``-th head of
    every set, a lane a row of the tile."""
    return jnp.concatenate(
        [tile, jnp.zeros((_LANES - tile.shape[0], _LANES), _F32)], axis=0).T


def _tile_rows(sets: int) -> int:
    """Rows of the heads-major tile: the log-decays and the betas of
    ``sets`` sets of heads, in whole sublanes."""
    return -(-2 * sets // 8) * 8


def _heads(per: int, width: int, pack: int):
    """The lanes of each value head of the key head, a list a set of
    ``pack`` heads."""
    return [[slice((first + p) * width, (first + p + 1) * width)
             for p in range(pack)] for first in range(0, per, pack)]


# --- forward -----------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, tile_ref, o_ref, state_ref, *,
                per: int, width: int, pack: int):
    _zero_at_first(state_ref)
    q, k = q_ref[0], k_ref[0]
    chunk, dtype = q.shape[0], q.dtype
    dot = _dot_in(dtype)
    tile = tile_ref[0, 0, 0]
    columns = _columns(tile)
    both = dot(jnp.concatenate([q, k], axis=0),
               jnp.concatenate([k] * pack, axis=0), _NT)        # (2 Q, P Q)
    qk, kk = both[:chunk], both[chunk:]
    qf = q.astype(_F32)
    for i, lanes in enumerate(_heads(per, width, pack)):
        h = _Packed(kk, k, [v_ref[0, :, x] for x in lanes], tile, columns,
                    i, per // pack, dot)
        scores = qk * h.decay
        new, read = [], []
        for p, x in enumerate(lanes):
            state = state_ref[:, x]
            found = dot(jnp.concatenate([h.w[p], qf * h.from_start[p]],
                                        axis=0), state, _NN)     # (2 Q, Dv)
            new.append(h.u[p] - found[:chunk])
            read.append(found[chunk:])
            state_ref[:, x] = state * h.whole_lanes[p] \
                + dot(h.k_to_end[p], new[p], _TN)
        new = jnp.concatenate(new, axis=0)                       # (P Q, Dv)
        for p, x in enumerate(lanes):
            o_ref[0, :, x] = (read[p] + dot(h.only(p, scores), new, _NN)
                              ).astype(o_ref.dtype)


def _specs(chunks: int, chunk: int, depth: int, per: int, width: int,
           pack: int, reverse: bool):
    """Block specs of a key head's chunk: the ``(Q, Dk)`` block of q
    (k and their cotangents), the ``(Q, R Dv)`` block of v (o, do, dv),
    the heads-major ``(8 n, 128)`` tile of the log-decays and the betas
    (``_tile``) and the ``(R / P, P Q)`` block of the log-decay's
    cotangent that comes as rows, the ``(Dk, R Dv)`` block of the
    states found."""
    def at(ic):
        return chunks - 1 - ic if reverse else ic

    narrow = pl.BlockSpec((1, chunk, depth),
                          lambda ib, ih, ic: (ib, at(ic), ih))
    wide = pl.BlockSpec((1, chunk, per * width),
                        lambda ib, ih, ic: (ib, at(ic), ih))
    tile = pl.BlockSpec((1, 1, 1, _tile_rows(per // pack), _LANES),
                        lambda ib, ih, ic: (ib, ih, at(ic), 0, 0))
    rows = pl.BlockSpec((1, 1, 1, per // pack, pack * chunk),
                        lambda ib, ih, ic: (ib, ih, at(ic), 0, 0))
    found = pl.BlockSpec((1, 1, depth, per * width),
                         lambda ib, ih, ic: (ib, at(ic), 0, ih))
    return narrow, wide, tile, rows, found


def log_decays(g, chunk: int):
    """g (B, Hv, S) float32, heads-major -> the same: the log of the
    decay from each chunk's start to each of its positions, ``<= 0``:
    ``g`` summed inside the chunk."""
    rows, heads, seq = g.shape
    return jnp.cumsum(g.reshape(rows, heads, seq // chunk, chunk),
                      axis=3).reshape(g.shape)


def _side_by_side(x, key_heads: int, chunk: int, pack: int):
    """(B, Hv, S) heads-major -> (B, Hk, chunks, R / P, P Q): a key
    head's value heads ``P`` side by side, a chunk a block."""
    rows, heads, seq = x.shape
    sets = heads // key_heads // pack
    x = x.reshape(rows, key_heads, sets, pack, seq // chunk, chunk)
    return x.transpose(0, 1, 4, 2, 3, 5).reshape(
        rows, key_heads, seq // chunk, sets, pack * chunk)


def _heads_major(x, chunk: int, pack: int):
    """The inverse: (B, Hk, chunks, R / P, P Q) -> (B, Hv, S)."""
    rows, key_heads, chunks, sets, _ = x.shape
    x = x.reshape(rows, key_heads, chunks, sets, pack, chunk)
    return x.transpose(0, 1, 3, 4, 2, 5).reshape(
        rows, key_heads * sets * pack, chunks * chunk)


def _tile(log, beta, key_heads: int, chunk: int, pack: int):
    """(B, Hk, chunks, 8 n, 128): a key head's heads-major tile of a
    chunk: a row a set of ``P`` heads side by side, the log-decays'
    rows, then the betas', zeros to whole sublanes and lanes."""
    both = jnp.concatenate([_side_by_side(x, key_heads, chunk, pack)
                            for x in (log, beta)], axis=3)
    return jnp.pad(both, ((0, 0),) * 3 + (
        (0, _tile_rows(both.shape[3] // 2) - both.shape[3]),
        (0, _LANES - both.shape[4])))


def _operands(q, k, v, log, beta, chunk: int, pack: int):
    """The kernels' views: q, k (B, S, Hk Dk), v (B, S, Hv Dv), and the
    heads-major tiles of the log-decay and beta (both (B, Hv, S))."""
    rows, seq, key_heads, _ = q.shape
    return (q.reshape(rows, seq, -1), k.reshape(rows, seq, -1),
            v.reshape(rows, seq, -1),
            _tile(log, beta, key_heads, chunk, pack))


def _call(q, v, chunk: int, interpret: bool):
    rows, seq, key_heads, depth = q.shape
    heads, width = v.shape[2:]
    per, chunks = heads // key_heads, seq // chunk
    pack = pack_of(per, chunk)
    return (dict(per=per, width=width, pack=pack),
            dict(grid=(rows, key_heads, chunks),
                 scratch_shapes=[pltpu.VMEM((depth, per * width), _F32)],
                 compiler_params=_COMPILER_PARAMS, interpret=interpret),
            functools.partial(_specs, chunks, chunk, depth, per, width, pack))


def _rule_forward(q, k, v, g, beta, chunk: int, interpret: bool):
    kernel, call, specs = _call(q, v, chunk, interpret)
    narrow, wide, tile, _, _ = specs(False)
    operands = _operands(q, k, v, log_decays(g.swapaxes(1, 2), chunk),
                         beta.swapaxes(1, 2), chunk, kernel["pack"])
    o = pl.pallas_call(
        functools.partial(_fwd_kernel, **kernel),
        in_specs=[narrow, narrow, wide, tile],
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(operands[2].shape, v.dtype),
        name="delta_rule_fwd", **call,
    )(*operands)
    return o.reshape(v.shape)


# --- backward ----------------------------------------------------------------


def _states_kernel(k_ref, v_ref, tile_ref, found_ref, state_ref, *,
                   per: int, width: int, pack: int):
    """The state each chunk finds at its start, as the forward carried
    it."""
    _zero_at_first(state_ref)
    found_ref[0, 0] = state_ref[:]
    k = k_ref[0]
    dot = _dot_in(k.dtype)
    tile = tile_ref[0, 0, 0]
    columns = _columns(tile)
    kk = dot(k, jnp.concatenate([k] * pack, axis=0), _NT)       # (Q, P Q)
    for i, lanes in enumerate(_heads(per, width, pack)):
        h = _Packed(kk, k, [v_ref[0, :, x] for x in lanes], tile, columns,
                    i, per // pack, dot)
        for p, x in enumerate(lanes):
            state = state_ref[:, x]
            new = h.u[p] - dot(h.w[p], state, _NN)
            state_ref[:, x] = state * h.whole_lanes[p] \
                + dot(h.k_to_end[p], new, _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, tile_ref, found_ref,
                dq_ref, dk_ref, dv_ref, dtile_ref, drows_ref, dstate_ref, *,
                per: int, width: int, pack: int):
    """One chunk, the chunks in reverse: ``dstate_ref`` holds the
    cotangent of the state at the chunk's end and leaves as that of the
    state the chunk found."""
    _zero_at_first(dstate_ref)
    q, k = q_ref[0], k_ref[0]
    chunk, depth = q.shape
    dot = _dot_in(q.dtype)
    tile, sets = tile_ref[0, 0, 0], per // pack
    columns = _columns(tile)
    q_over_k = jnp.concatenate([q, k], axis=0)                  # (2 Q, Dk)
    k_stacked = jnp.concatenate([k] * pack, axis=0)             # (P Q, Dk)
    both = dot(q_over_k, k_stacked, _NT)                         # (2 Q, P Q)
    qk, kk = both[:chunk], both[chunk:]
    qf = q.astype(_F32)
    is_last = _iota((chunk, 1), 0) == chunk - 1
    lane = _iota((1, _LANES), 1)
    row_set = _iota((sets, 1), 0)

    dboth = jnp.zeros(both.shape, _F32)           # of q k^T over k k^T
    dq = jnp.zeros((chunk, depth), _F32)
    dk = jnp.zeros((chunk, depth), _F32)
    dcolumns = jnp.zeros((_LANES, _LANES), _F32)   # as ``columns``
    drows = jnp.zeros((sets, pack * chunk), _F32)
    for i, lanes in enumerate(_heads(per, width, pack)):
        h = _Packed(kk, k, [v_ref[0, :, x] for x in lanes], tile, columns,
                    i, sets, dot)
        dlogs, dbetas = [], []
        scores = qk * h.decay
        states = [found_ref[0, 0, :, x] for x in lanes]
        new = [u - dot(w, state, _NN)
               for u, w, state in zip(h.u, h.w, states)]
        new_stacked = jnp.concatenate(new, axis=0)               # (P Q, Dv)
        dscores = jnp.zeros(kk.shape, _F32)
        dt = jnp.zeros(kk.shape, _F32)
        dq_decayed, dk_to_end, dwhole, dwritten = [], [], [], []
        for p, x in enumerate(lanes):
            do, state, dstate = do_ref[0, :, x], states[p], dstate_ref[:, x]
            # o = (q e^G) S + scores v'
            # S' = e^G_last S + (k e^(G_last - G))^T v'
            through_o = dot(do, jnp.concatenate([state, new_stacked], axis=0),
                            _NT)                                 # (Q, Dk + P Q)
            dq_decayed.append(through_o[:, :depth])
            dscores = dscores + h.only(p, through_o[:, depth:])
            from_o = dot(jnp.concatenate(
                [qf * h.from_start[p], h.only(p, scores)], axis=1), do, _TN)
            dnew = from_o[depth:][h.rows(p)] \
                + dot(h.k_to_end[p], dstate, _NN)                # (Q, Dv)
            dk_to_end.append(dot(new[p], dstate, _NT))           # (Q, Dk)
            dwhole.append(jnp.sum(
                jnp.sum(dstate * state, axis=1, keepdims=True),
                axis=0, keepdims=True))                          # (1, 1)
            # v' = U - W S;  [U | W] = T [beta v | beta e^G k]
            duw = jnp.concatenate([dnew, -dot(dnew, state, _NT)], axis=1)
            dstate_ref[:, x] = (dstate * h.whole_lanes[p] + from_o[:depth]
                                - dot(h.w[p], dnew, _TN))
            dt = dt + h.only(p, dot(duw, h.written, _NT))        # (Q, P Q)
            dwritten.append(dot(h.only(p, h.t), duw, _TN)[h.rows(p)])
        # T = (I - A)^-1:  dA = T^T dT T^T, strictly lower, a head
        crossed = _dot32(h.t, dt, _TN)                           # (P Q, P Q)
        inner = crossed[h.rows(0)]
        for p in range(1, pack):
            inner = jnp.where(h.masks[p], crossed[h.rows(p)], inner)
        da = jnp.where(h.strict, _dot32(inner, _apart(h.t), _NT), 0.0)
        # A = -beta (k k^T) decay;  scores = (q k^T) decay
        dboth = dboth + jnp.concatenate(
            [dscores * h.decay, -h.wide(h.beta) * da * h.decay], axis=0)
        through_decay = da * h.a + dscores * scores
        through_kk = da * (kk * h.decay)
        for p, x in enumerate(lanes):
            beta, from_start = h.beta[p], h.from_start[p]
            dbv, dbk = dwritten[p][:, :width], dwritten[p][:, width:]
            dv_ref[0, :, x] = (beta * dbv).astype(dv_ref.dtype)
            dk = dk + (beta * from_start) * dbk + h.to_end[p] * dk_to_end[p]
            dq = dq + from_start * dq_decayed[p]
            k_dbk = jnp.sum(dbk * h.kf, axis=1, keepdims=True)   # (Q, 1)
            dbetas.append(
                jnp.sum(dbv * h.vf[p], axis=1, keepdims=True)
                + from_start * k_dbk
                - jnp.sum(h.only(p, through_kk), axis=1, keepdims=True))
            # through e^G, e^(G_last - G) and e^G_last to the log-decay
            through_to_end = jnp.sum(dk_to_end[p] * h.kf, axis=1,
                                     keepdims=True) * h.to_end[p]
            dlogs.append(
                jnp.sum(h.only(p, through_decay), axis=1, keepdims=True)
                + (beta * k_dbk + jnp.sum(dq_decayed[p] * qf, axis=1,
                                          keepdims=True)) * from_start
                - through_to_end
                + jnp.where(is_last,
                            jnp.sum(through_to_end, axis=0, keepdims=True)
                            + dwhole[p] * h.whole[p], 0.0))
        spare = [jnp.zeros((_LANES - pack * chunk, 1), _F32)] \
            if pack * chunk < _LANES else []
        dcolumns = jnp.where(
            lane == i, jnp.concatenate(dlogs + spare, axis=0),
            jnp.where(lane == sets + i,
                      jnp.concatenate(dbetas + spare, axis=0), dcolumns))
        drows = jnp.where(row_set == i,
                          -jnp.sum(through_decay, axis=0, keepdims=True),
                          drows)
    through_k = dot(dboth, k_stacked, _NN)                       # (2 Q, Dk)
    crossed = dot(dboth, q_over_k, _TN)                          # (P Q, Dk)
    for p in range(pack):
        dk = dk + crossed[p * chunk:(p + 1) * chunk]
    dq_ref[0] = (dq + through_k[:chunk]).astype(dq_ref.dtype)
    dk_ref[0] = (dk + through_k[chunk:]).astype(dk_ref.dtype)
    dtile_ref[0, 0, 0] = dcolumns.T[:dtile_ref.shape[3]]
    drows_ref[0, 0, 0] = drows


def _rule_backward(q, k, v, g, beta, do, chunk: int, interpret: bool):
    """``dq, dk, dv, dg, dbeta``."""
    kernel, call, specs = _call(q, v, chunk, interpret)
    pack = kernel["pack"]
    sets = kernel["per"] // pack
    log, chain = jax.vjp(lambda g: log_decays(g, chunk), g.swapaxes(1, 2))
    qw, kw, vw, tile = _operands(q, k, v, log, beta.swapaxes(1, 2), chunk,
                                 pack)

    narrow, wide, tile_spec, _, found_spec = specs(False)
    found = pl.pallas_call(
        functools.partial(_states_kernel, **kernel),
        in_specs=[narrow, wide, tile_spec],
        out_specs=found_spec,
        out_shape=jax.ShapeDtypeStruct(
            (tile.shape[0], tile.shape[2], q.shape[3], vw.shape[2]), _F32),
        name="delta_rule_bwd_states", **call,
    )(kw, vw, tile)

    narrow, wide, tile_spec, rows_spec, found_spec = specs(True)
    dq, dk, dv, dtile, drows = pl.pallas_call(
        functools.partial(_bwd_kernel, **kernel),
        in_specs=[narrow, narrow, wide, wide, tile_spec, found_spec],
        out_specs=[narrow, narrow, wide, tile_spec, rows_spec],
        out_shape=[jax.ShapeDtypeStruct(qw.shape, q.dtype),
                   jax.ShapeDtypeStruct(kw.shape, k.dtype),
                   jax.ShapeDtypeStruct(vw.shape, v.dtype),
                   jax.ShapeDtypeStruct(tile.shape, _F32),
                   jax.ShapeDtypeStruct(
                       (*tile.shape[:3], sets, pack * chunk), _F32)],
        name="delta_rule_bwd", **call,
    )(qw, kw, vw, do.reshape(vw.shape), tile, found)

    dtile = dtile[..., :pack * chunk]
    dlog = _heads_major(dtile[..., :sets, :] + drows, chunk, pack)
    dbeta = _heads_major(dtile[..., sets:2 * sets, :], chunk, pack)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            chain(dlog)[0].swapaxes(1, 2), dbeta.swapaxes(1, 2))


# --- the differentiable rule -------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, chunk, interpret):
    return _rule_forward(q, k, v, g, beta, chunk, interpret)


def _rule_fwd(q, k, v, g, beta, chunk, interpret):
    # the backward takes the operands alone: with the output held by a
    # ``remat`` layer (``delta_out``) the recomputed layer does not run
    # this kernel again
    return (_rule_forward(q, k, v, g, beta, chunk, interpret),
            (q, k, v, g, beta))


def _rule_bwd(chunk, interpret, res, do):
    return _rule_backward(*res, do, chunk, interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def fused_rule(q, k, v, g, beta, *, chunk: int, interpret=None):
    """``o_t = S_t^T q_t`` of the recurrence in ``ops/delta_rule.py``.
    q, k (B, S, Hk, Dk) in the compute dtype; v (B, S, Hv, Dv) in the
    same; g (<= 0), beta (B, S, Hv) float32. S a multiple of ``chunk``,
    the shapes such that ``fits``. Returns (B, S, Hv, Dv) in v's
    dtype."""
    from perceiver_tpu.utils.platform import resolve_interpret
    if q.shape[1] % chunk or not fits(chunk=chunk, key_dim=q.shape[3],
                                      value_dim=v.shape[3], dtype=v.dtype):
        raise ValueError(
            f"the delta-rule kernels do not tile {q.shape[1]} positions in "
            f"chunks of {chunk}, key heads of {q.shape[3]}, value heads of "
            f"{v.shape[3]}, {v.dtype}")
    return _rule(q.astype(v.dtype), k.astype(v.dtype), v, g.astype(_F32),
                 beta.astype(_F32), int(chunk), resolve_interpret(interpret))
