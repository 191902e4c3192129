"""The delta rule with a decay that is a vector a key channel (Kimi
Delta Attention; ``ops/delta_rule.py``, ``g`` of ``(B, S, H, Dk)``) as
Pallas kernels for TPU, forward and backward: the chunked WY form at
the einsum form's chunk boundaries and in its arithmetic
(``_inside_chunks_by_channel`` is the oracle), with a chunk's running
log-decay, its sub-blocks' spans, every ``Q x Q`` float32 matrix, the
inverse and the state carried between chunks in VMEM. A sibling of
``ops/pallas_delta_rule.py`` (a decay that is a number a head), whose
leaf helpers it shares and whose kernels it leaves alone.

One grid step is one chunk of ``Q`` positions of **two heads** of one
row (``heads_a_step``: one where the heads do not pair up or a chunk's
two betas do not share 128 lanes): grid ``(rows, heads / P, chunks)``,
the chunk axis last and sequential. A step holds its heads' ``q``,
``k`` and ``v`` as the projections leave them, ``(Q, P D)`` lanes of
the ``(B, S, H D)`` arrays, their ``g`` as the same block of the
``(B, S, H Dk)`` float32 array, and their write strengths through the
heads-major tile of ``pallas_delta_rule`` (a row of ``P Q`` lanes,
transposed in the kernel for the columns the products want). The
running sum ``G`` of ``g`` inside the chunk is a product with a
triangle of ones, float32 at ``HIGHEST`` (and its chain back to ``g``
the transposed triangle): ``G`` never lies in HBM. The carried state is
a float32 scratch a head, **transposed**, ``(Dv, Dk)``: its decay a
chunk, ``exp(G_last)``, is a number a key channel, a row of lanes.

**Why two heads a step**: a head's chunk is a chain (spans, ``A``, the
inverse, ``U`` and ``W``, the state) whose float32 products at
``HIGHEST`` each wait for the one before; a KDA head has one value head
(``pack_of`` of the scalar-decay kernels finds nothing to put side by
side), so the second head's chain fills the first one's waits, and the
two ``Q x Q`` tiles lie side by side on the lanes of one ``(Q, 2 Q)``
tile for the inverse and for its cotangent (``_side_by_side_lanes``):
eight and two ``HIGHEST`` products a pair of heads where a head a step
takes them a head (1,088 loads of the MXU's right-hand tile a pair
forward for 1,856, 1,712 for 2,672 backward, by the compiler's own
count at the cell's shapes).

**The decayed products** ``P(x)_ij = sum_d x_id k_jd exp(G_id - G_jd)``
(``x = k`` for ``A``, ``x = q`` for the scores), by sub-blocks of 16
positions as the einsum form has them: a sub-block against the
positions before it is one MXU product of ``x exp(G - G_r)`` with
``k exp(G_r - G_j)`` (``r`` the sub-block's first row, both factors at
most 1, operands in the compute dtype); a sub-block against itself
takes its ``16 x 16 x Dk`` spans on the vector units, sixteen passes
(one a column ``j`` of every sub-block at once) of ``exp(G_i - G_j)``
masked **before** the ``exp`` where ``i < j`` (``_span``), times
``k_j`` and ``x_i``, summed over the channels in float32. No exponent
of a positive number is formed anywhere, forward or backward; a decay
that underflows is a quiet 0. The backward walks the same sixteen
passes the other way (a column of a product's cotangent times the
span, to ``x`` along the rows and to ``k_j`` summed over them).

**The log-decay's cotangent needs no span of its own**: ``q`` enters
only as ``q exp(G)`` (in the scores and in the read of the state), so
its share is ``q dq``; ``k`` enters with ``+G`` as the row of ``A`` and
as ``beta k exp(G)``, with ``-G`` as the column of ``A`` and of the
scores and as ``k exp(G_last - G)``: ``k (dk_plus - dk_minus)``; the
last row takes what ``exp(G_last - G)`` and the state's decay hand it.
The first rows ``G_r`` cancel in the mathematics and not in the
arithmetic: each factor of a split span is rounded to the compute dtype
on its own, so row ``r`` takes the difference of the two sides'
products, as autodiff's does, and the sum of ``dG`` from any position
before ``j`` on is exact (without it ``dg`` at bfloat16 lay a third
further from the float32 recurrence than the einsum form's). The
inverse ``T = (I - A)^-1`` is
``pallas_delta_rule.blocked_inverse`` and its cotangent analytic,
``dA = T^T dT T^T``.

**Each direction is a jitted function** (``_rule_forward``,
``_rule_backward``): the four call sites of an unrolled stack share one
trace and one lowering of each kernel. A kernel's traced body is paid
when the step is loaded, warm cache or not, at every call site
(PERF.md, PRs 40, 41): traced a site, two heads a step cost 9 s of a
warm ``setup_s`` of 80 on the chip's host (PR 45).

Forward ``kda_rule_fwd`` writes ``o`` and nothing else. The backward
takes ``q, k, v, g, beta`` and ``do`` alone: ``kda_rule_bwd_states``
rebuilds in order the state each chunk found, ``kda_rule_bwd`` goes
through the chunks reversed, carries the state's cotangent and writes
``dq``, ``dk``, ``dv`` (float32 sums cast once) and float32 ``dg``
``(B, S, H Dk)`` and ``dbeta``.

Rounding is the einsum form's or finer (``ops/delta_rule.py``,
**Precision**): float32 ``g``, ``G``, ``beta``, every decay, the
sub-blocks' own spans and their sums, ``A``, ``T``, the carried state
and its cotangent; operands in the compute dtype where the einsums
cast them and where their transposed products take a cotangent;
float32 accumulation; ``U`` stays float32. Under a float32 compute
dtype every product is float32 at ``HIGHEST``.

**Tried and slower, or dearer to load** (one v5e chip, the shapes of
``kimi_linear_train``: 4 x 4,096, 32 heads of 128, chunks of 64;
PERF.md, PR 45): one head a grid step, 21.5 ms a layer forward and 88.9
for the backward's two kernels where two heads take 21.5 and 58.1; the
passes ``j >= 8`` on the lower half of each sub-block alone (every span
with ``i < j`` is masked, so half of those passes' vregs are computed
and thrown away): 61.8 ms backward for 58.1, the gathers and scatters
of half tiles cost more than the quarter of the vector work they
spare; the sixteen passes as sixteen copies and the kernels traced at
each of the step's four call sites: the same device time, and 9 s more
of a warm 80 s set-up (0.8 s a site and 0.9 with two heads on the
sandbox's CPU for the loop's 0.4); the reshapes between ``(B, S, H,
D)`` and ``(B, S, H D)`` inside the jitted functions: copies, 7 ms a
step, where in the caller XLA folds them into their neighbours.

On non-TPU backends the kernels run in Pallas interpreter mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from perceiver_tpu.ops.pallas_attention import _LANES, _NN, _NT, _TN
from perceiver_tpu.ops.pallas_delta_rule import (
    _BLOCK, _COMPILER_PARAMS, _F32, _apart, _columns, _dot32, _dot_in,
    _heads_major, _iota, _side_by_side, _tile_rows, _zero_at_first,
    blocked_inverse)
from perceiver_tpu.ops.pallas_delta_rule import fits as _tiles

#: positions a sub-block: ``ops.delta_rule.SUB_BLOCK``, the inverse's
#: block
_SUB = _BLOCK


def fits(*, chunk: int, key_dim: int, value_dim: int, heads: int,
         value_heads: int, dtype) -> bool:
    """Whether these kernels tile a call: what ``pallas_delta_rule.fits``
    asks (heads of whole lanes, a chunk of 16 to 128 that the inverse
    merges and the sub-block of 16 divides, bfloat16 or float32) and a
    value head a key head, as a KDA mixer has them."""
    return heads == value_heads and _tiles(
        chunk=chunk, key_dim=key_dim, value_dim=value_dim, dtype=dtype)


def _span(later, earlier, forwards):
    """``exp(later - earlier)`` where the span runs ``forwards`` and 0
    where it does not: the mask goes in before the ``exp``, so no
    exponent of a positive number is formed."""
    return jnp.exp(jnp.where(forwards, later - earlier, -jnp.inf))


def _triangle(size: int, upper: bool = False):
    """Ones on and below the diagonal (above, ``upper``), float32:
    the running sum inside a chunk as a product, and its transpose."""
    row, col = _iota((size, size), 0), _iota((size, size), 1)
    return (row <= col if upper else row >= col).astype(_F32)


def _running_sum(g):
    """g (Q, Dk) float32 -> the log of the decay from the chunk's start
    to each position, ``<= 0``: a product with a triangle of ones."""
    return _dot32(_triangle(g.shape[0]), g, _NN)


def _sub_rows(rows_of, size: int, width: int):
    """(Q, D): one row of each sub-block in all its rows; ``rows_of(r)``
    gives the sub-block's row, (1, D), ``r`` its first."""
    return jnp.concatenate(
        [jnp.broadcast_to(rows_of(r), (_SUB, width))
         for r in range(0, size, _SUB)], axis=0)


def _first_rows(x):
    """(Q, D) -> (Q, D): each sub-block's first row in all its rows."""
    return _sub_rows(lambda r: x[r:r + 1], *x.shape)


def _rows_at(ref, j):
    """Row ``j`` (the pass a loop is at) of each sub-block of a (Q, D)
    scratch buffer, in all its rows."""
    return _sub_rows(lambda r: ref[pl.ds(r + j, 1), :], *ref.shape)


class _Chunk:
    """What all three kernels recompute of one head's chunk: the
    running log-decay and the decays made of it, the decayed products,
    ``A``, ``T``, ``U`` and ``W``, in three parts: what comes before
    the sixteen passes over the sub-blocks' own spans (``__init__``),
    one pass (``spans_pass``: the heads of a grid step share the loop)
    and the rest (``finish``). k (Q, Dk), v (Q, Dv) and q (or None: no
    scores) in the compute dtype, g (Q, Dk) float32, beta (Q, 1).
    ``rows_ref`` (2, Q, Dk) is scratch for what the passes read a row
    at a time."""

    def __init__(self, q, k, v, g, beta, dot, rows_ref):
        size = k.shape[0]
        self.size, self.dot, self.beta = size, dot, beta
        self.firsts = range(_SUB, size, _SUB)   # the later sub-blocks' rows
        self.kf, self.vf = k.astype(_F32), v.astype(_F32)
        self.qf = None if q is None else q.astype(_F32)
        self.row = row = _iota((size, 1), 0)
        square = (size, size)
        # a column's place inside the sub-block of its row
        self.within = _iota(square, 1) - _iota(square, 0) // _SUB * _SUB
        self.lower = _iota(square, 0) >= _iota(square, 1)
        self.strict = _iota(square, 0) > _iota(square, 1)

        self.log = log = _running_sum(g)                       # G
        # from a sub-block's first row to each of its positions
        self.local = log - _first_rows(log)
        self.inside = jnp.exp(self.local)
        # k as a sub-block's first row finds it, the positions before it
        self.found_decay = [_span(log[r:r + 1], log, row < r)
                            for r in self.firsts]
        self.k_found = [self.kf * d for d in self.found_decay]
        rows_ref[0], rows_ref[1] = self.local, self.kf
        self.local_ref, self.k_ref = rows_ref.at[0], rows_ref.at[1]
        #: the sub-blocks' own blocks of ``k k^T`` (and ``q k^T``) decayed
        self.own = (jnp.zeros(square, _F32),) * (1 if q is None else 2)

    def spans_pass(self, j, own, spans_ref=None):
        """Column ``j`` of every sub-block's own block: its spans (kept
        in ``spans_ref`` (16, Q, Dk) for the backward's passes, where
        given), times ``k_j`` and ``x_i``, summed over the channels."""
        span = _span(self.local, _rows_at(self.local_ref, j),
                     self.row % _SUB >= j)
        if spans_ref is not None:
            spans_ref[j] = span
        k_span = _rows_at(self.k_ref, j) * span
        return tuple(
            jnp.where(self.within == j, jnp.sum(
                k_span * x, axis=1, keepdims=True), mine)
            for x, mine in zip((self.kf, self.qf), own))

    def products(self, own):
        """``A`` from the decayed products."""
        size, beta = self.size, self.beta
        # a sub-block against the positions before it: MXU products
        self.k_inside = self.kf * self.inside
        self.q_inside = None if self.qf is None else self.qf * self.inside
        earlier = [self.dot(self.rows_inside(r), self.k_found[i], _NT)
                   for i, r in enumerate(self.firsts)]
        first = jnp.zeros((_SUB, size), _F32)
        self.kk = own[0] + jnp.concatenate(
            [first] + [e[:_SUB] for e in earlier], axis=0)
        if self.qf is not None:
            self.scores = own[1] + jnp.concatenate(
                [first] + [e[_SUB:] for e in earlier], axis=0)

        self.a = jnp.where(self.strict, -beta * self.kk, 0.0)

    def finish(self, t):
        """``U``, ``W`` and the decays to the chunk's end, given the
        inverse ``T``."""
        size, beta, self.t = self.size, self.beta, t
        self.from_start = jnp.exp(self.log)
        last = self.log[size - 1:]                             # (1, Dk)
        self.to_end = jnp.exp(last - self.log)
        self.whole = jnp.exp(last)
        self.k_to_end = self.kf * self.to_end
        self.width = self.vf.shape[1]
        self.written = jnp.concatenate(
            [beta * self.vf, (beta * self.from_start) * self.kf], axis=1)
        uw = self.dot(self.t, self.written, _NN)               # (Q, Dv + Dk)
        self.u, self.w = uw[:, :self.width], uw[:, self.width:]

    def rows_inside(self, r: int):
        """A sub-block's rows of ``k`` (and ``q`` under them) as its
        first row leaves them."""
        rows = [self.k_inside[r:r + _SUB]]
        if self.q_inside is not None:
            rows.append(self.q_inside[r:r + _SUB])
        return jnp.concatenate(rows, axis=0)


def _wide(tiles):
    """The heads' (Q, Q) tiles side by side on the lanes, one (Q, P Q)
    tile: a ``HIGHEST`` product loads the MXU as often whatever its
    width, so the inverse's products and its cotangent's then serve all
    the heads."""
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def _lanes(p: int, width: int):
    """Head ``p``'s lanes of a block of several heads."""
    return slice(p * width, (p + 1) * width)


def _chunks(q_ref, k_ref, v_ref, g_ref, tile_ref, rows_ref, spans_ref=None):
    """A ``_Chunk`` a head of the grid step, the passes over their own
    spans in one loop (a loop, not sixteen copies: a kernel's size is
    paid when the step is traced, at every call site; all the heads in
    it: their chains of products are independent and fill each other's
    waits)."""
    size, dtype = k_ref.shape[1], k_ref.dtype
    heads = rows_ref.shape[0]
    depth, width = k_ref.shape[2] // heads, v_ref.shape[2] // heads
    columns, dot = _columns(tile_ref[0, 0, 0]), _dot_in(dtype)
    chunks = [
        _Chunk(None if q_ref is None else q_ref[0, :, _lanes(p, depth)],
               k_ref[0, :, _lanes(p, depth)], v_ref[0, :, _lanes(p, width)],
               g_ref[0, :, _lanes(p, depth)],
               columns[p * size:(p + 1) * size, :1], dot, rows_ref.at[p])
        for p in range(heads)]

    def one_pass(j, own):
        return tuple(c.spans_pass(
            j, mine, None if spans_ref is None else spans_ref.at[p])
            for p, (c, mine) in enumerate(zip(chunks, own)))

    own = jax.lax.fori_loop(0, _SUB, one_pass, tuple(c.own for c in chunks))
    for c, mine in zip(chunks, own):
        c.products(mine)
    t = blocked_inverse(_wide([c.a for c in chunks]))
    for p, c in enumerate(chunks):
        c.finish(t[:, _lanes(p, size)])
    return chunks


# --- forward -----------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, tile_ref, o_ref, state_ref,
                rows_ref):
    _zero_at_first(state_ref)
    for p, c in enumerate(_chunks(q_ref, k_ref, v_ref, g_ref, tile_ref,
                                  rows_ref)):
        state = state_ref[p]                                   # (Dv, Dk)
        found = c.dot(jnp.concatenate([c.w, c.qf * c.from_start], axis=0),
                      state, _NT)                              # (2 Q, Dv)
        new = c.u - found[:c.size]
        state_ref[p] = state * c.whole + c.dot(new, c.k_to_end, _TN)
        o_ref[0, :, _lanes(p, c.width)] = (
            found[c.size:] + c.dot(c.scores, new, _NN)).astype(o_ref.dtype)


def _specs(chunks: int, chunk: int, depth: int, width: int, pack: int,
           reverse: bool):
    """Block specs of a chunk of the ``P`` heads of a grid step: the
    ``(Q, P Dk)`` block of q (k, g and their cotangents), the
    ``(Q, P Dv)`` block of v (o, do, dv), the heads-major tile of the
    betas, the ``(P, Dv, Dk)`` block of the states found."""
    def at(ic):
        return chunks - 1 - ic if reverse else ic

    narrow = pl.BlockSpec((1, chunk, pack * depth),
                          lambda ib, ih, ic: (ib, at(ic), ih))
    wide = pl.BlockSpec((1, chunk, pack * width),
                        lambda ib, ih, ic: (ib, at(ic), ih))
    tile = pl.BlockSpec((1, 1, 1, _tile_rows(1), _LANES),
                        lambda ib, ih, ic: (ib, ih, at(ic), 0, 0))
    found = pl.BlockSpec((1, pack, 1, width, depth),
                         lambda ib, ih, ic: (ib, ih, at(ic), 0, 0))
    return narrow, wide, tile, found


def heads_a_step(heads: int, chunk: int) -> int:
    """How many heads one grid step takes: two where they pair up and
    their betas share a tile's 128 lanes, else one."""
    return 2 if heads % 2 == 0 and 2 * chunk <= _LANES else 1


def _beta_tile(beta, chunk: int, pack: int):
    """beta (B, S, H) -> (B, H / P, chunks, 8, 128): a step's heads'
    chunk a row of lanes, side by side, zeros to whole sublanes and
    lanes."""
    rows = _side_by_side(beta.swapaxes(1, 2), beta.shape[2] // pack, chunk,
                         pack)
    return jnp.pad(rows, ((0, 0),) * 3 + ((0, _tile_rows(1) - 1),
                                          (0, _LANES - pack * chunk)))


def _call(q, v, heads: int, chunk: int, interpret: bool):
    """q (B, S, H Dk), v (B, S, H Dv): the projections' own arrays."""
    rows, seq = q.shape[:2]
    depth, width = q.shape[2] // heads, v.shape[2] // heads
    chunks = seq // chunk
    pack = heads_a_step(heads, chunk)
    return (pack,
            dict(grid=(rows, heads // pack, chunks),
                 compiler_params=_COMPILER_PARAMS, interpret=interpret),
            functools.partial(_specs, chunks, chunk, depth, width, pack),
            # the carried states, transposed; the rows the passes read
            [pltpu.VMEM((pack, width, depth), _F32),
             pltpu.VMEM((pack, 2, chunk, depth), _F32)])


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _rule_forward(q, k, v, g, beta, heads: int, chunk: int, interpret: bool):
    """q, k, v, g (B, S, H D) as the kernels take them (the reshapes
    from and to (B, S, H, D) stay with the caller, where XLA folds them
    into their neighbours: inside this function they were copies, 7 ms
    a step in ``kimi_linear_train``), beta (B, S, H)."""
    pack, call, specs, scratch = _call(q, v, heads, chunk, interpret)
    narrow, wide, tile, _ = specs(False)
    return pl.pallas_call(
        _fwd_kernel,
        in_specs=[narrow, narrow, wide, narrow, tile],
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(v.shape, v.dtype),
        scratch_shapes=scratch, name="kda_rule_fwd", **call,
    )(q, k, v, g, _beta_tile(beta, chunk, pack))


# --- backward ----------------------------------------------------------------


def _states_kernel(k_ref, v_ref, g_ref, tile_ref, found_ref, state_ref,
                   rows_ref):
    """The state each chunk finds at its start, as the forward carried
    it."""
    _zero_at_first(state_ref)
    found_ref[0, :, 0] = state_ref[:]
    for p, c in enumerate(_chunks(None, k_ref, v_ref, g_ref, tile_ref,
                                  rows_ref)):
        state = state_ref[p]
        new = c.u - c.dot(c.w, state, _NT)
        state_ref[p] = state * c.whole + c.dot(new, c.k_to_end, _TN)


def _column(tile, within, j):
    """Column ``j`` of every sub-block's own block of a (Q, Q) tile,
    (Q, 1)."""
    return jnp.sum(jnp.where(within == j, tile, 0.0), axis=1, keepdims=True)


def _block_sums(x):
    """(Q, D) -> (Q, D): each sub-block's sum over its rows, in all its
    rows."""
    return _sub_rows(
        lambda r: jnp.sum(x[r:r + _SUB], axis=0, keepdims=True), *x.shape)


class _Cotangents:
    """One head's chunk of the reversed pass, in the three parts of its
    ``_Chunk``: down to the cotangents of the decayed products
    (``__init__``), a pass over the sub-blocks' own spans
    (``spans_pass``) and the rest (``finish``). ``state`` (Dv, Dk) is
    the state the chunk found, ``dstate`` the cotangent of the one it
    left; ``self.dstate`` that of the one it found."""

    def __init__(self, c: _Chunk, do, state, dstate):
        self.c, dot = c, c.dot
        q_decayed = c.qf * c.from_start
        new = c.u - dot(c.w, state, _NT)
        # o = (q e^G) S + scores v'
        # S' = e^G_last S + (k e^(G_last - G))^T v'
        self.dq_decayed = dot(do, state, _NN)                  # (Q, Dk)
        self.dscores = jnp.where(c.lower, dot(do, new, _NT), 0.0)
        dnew = dot(c.scores, do, _TN) + dot(c.k_to_end, dstate, _NT)
        self.dk_to_end = dot(new, dstate, _NN)                 # (Q, Dk)
        self.dwhole = jnp.sum(dstate * state, axis=0, keepdims=True)
        # v' = U - W S;  [U | W] = T [beta v | beta e^G k]
        duw = jnp.concatenate([dnew, -dot(dnew, state, _NN)], axis=1)
        self.dstate = dstate * c.whole + dot(
            jnp.concatenate([do.astype(_F32), -dnew], axis=0),
            jnp.concatenate([q_decayed, c.w], axis=0), _TN)
        self.dt = dot(duw, c.written, _NT)                     # (Q, Q)
        dwritten = dot(c.t, duw, _TN)                          # (Q, Dv + Dk)
        self.dbv, self.dbk = dwritten[:, :c.width], dwritten[:, c.width:]
        #: of the sub-blocks' own blocks, to ``k`` and ``q`` along the
        #: rows (with ``+G``) and to ``k`` along the columns (``-G``)
        self.own = (jnp.zeros(c.kf.shape, _F32),) * 3

    def through_inverse(self, crossed):
        """``crossed`` is ``T^T dT T^T``, the cotangent of ``A`` before
        its mask."""
        c = self.c
        da = jnp.where(c.strict, crossed, 0.0)
        self.dkk = -c.beta * da                                # A = -beta kk
        self.dbeta = (
            jnp.sum(self.dbv * c.vf, axis=1, keepdims=True)
            + jnp.sum(self.dbk * c.from_start * c.kf, axis=1, keepdims=True)
            - jnp.sum(da * c.kk, axis=1, keepdims=True))

    def spans_pass(self, j, sums, spans_ref):
        c = self.c
        dk_rows, dq_rows, dk_columns = sums
        span = spans_ref[j]
        of_kk, of_scores = (_column(x, c.within, j)
                            for x in (self.dkk, self.dscores))
        k_span = _rows_at(c.k_ref, j) * span
        through = (of_kk * c.kf + of_scores * c.qf) * span     # (Q, Dk)
        return (dk_rows + of_kk * k_span, dq_rows + of_scores * k_span,
                jnp.where(c.row % _SUB == j, _block_sums(through),
                          dk_columns))

    def finish(self, sums):
        """``dq, dk, dv, dg`` (Q, .) and ``dbeta`` (Q, 1), float32."""
        c, dot = self.c, self.c.dot
        dk_rows, dq_rows, dk_columns = sums
        # a sub-block against the positions before it. The split at the
        # first row r is no part of the mathematics, but each factor is
        # rounded to the compute dtype on its own: r's row of the
        # log-decay takes what the two sides' products leave of each
        # other, so that the sum from any position before j on cancels
        # exactly, as autodiff's does
        dinside = [jnp.zeros((2 * _SUB, c.kf.shape[1]), _F32)]
        dfirsts = jnp.zeros(c.kf.shape, _F32)
        for i, r in enumerate(c.firsts):
            both = jnp.concatenate(
                [self.dkk[r:r + _SUB], self.dscores[r:r + _SUB]], axis=0)
            rows = c.rows_inside(r)
            dinside.append(dot(both, c.k_found[i], _NN))       # (32, Dk)
            dfound = dot(both, rows, _TN)                      # (Q, Dk)
            dk_columns = dk_columns + c.found_decay[i] * dfound
            dfirsts = jnp.where(
                c.row == r,
                jnp.sum(c.k_found[i] * dfound, axis=0, keepdims=True)
                - jnp.sum(rows * dinside[-1], axis=0, keepdims=True), dfirsts)
        dk_rows = dk_rows + c.inside * jnp.concatenate(
            [x[:_SUB] for x in dinside], axis=0)
        dq_rows = dq_rows + c.inside * jnp.concatenate(
            [x[_SUB:] for x in dinside], axis=0)

        dq = dq_rows + c.from_start * self.dq_decayed
        dk_plus = dk_rows + (c.beta * c.from_start) * self.dbk
        through_to_end = c.to_end * self.dk_to_end
        dk_minus = dk_columns + through_to_end
        # through every exp(G) to the running log-decay, then to g
        dlog = c.qf * dq + c.kf * (dk_plus - dk_minus) + dfirsts
        dlog = dlog + jnp.where(
            c.row == c.size - 1,
            jnp.sum(c.kf * through_to_end, axis=0, keepdims=True)
            + self.dwhole * c.whole, 0.0)
        return (dq, dk_plus + dk_minus, c.beta * self.dbv,
                _dot32(_triangle(c.size, upper=True), dlog, _NN), self.dbeta)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, do_ref, tile_ref, found_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dtile_ref, dstate_ref,
                rows_ref, spans_ref):
    """One chunk, the chunks in reverse: ``dstate_ref`` holds the
    cotangent of the state at the chunk's end and leaves as that of the
    state the chunk found."""
    _zero_at_first(dstate_ref)
    chunks = _chunks(q_ref, k_ref, v_ref, g_ref, tile_ref, rows_ref,
                     spans_ref)
    heads = [_Cotangents(c, do_ref[0, :, _lanes(p, c.width)],
                         found_ref[0, p, 0], dstate_ref[p])
             for p, c in enumerate(chunks)]
    # T = (I - A)^-1:  dA = T^T dT T^T, the heads side by side
    size = chunks[0].size
    t_wide = _wide([c.t for c in chunks])
    crossed = _dot32(t_wide, _wide([h.dt for h in heads]), _TN)  # (P Q, P Q)
    inner = _wide([crossed[_lanes(p, size), _lanes(p, size)]
                   for p in range(len(heads))])                # (Q, P Q)
    wide_da = _dot32(inner, _apart(t_wide), _NT)               # (Q, P Q)
    for p, h in enumerate(heads):
        h.through_inverse(wide_da[:, _lanes(p, size)])

    def one_pass(j, sums):
        return tuple(h.spans_pass(j, mine, spans_ref.at[p])
                     for p, (h, mine) in enumerate(zip(heads, sums)))

    sums = jax.lax.fori_loop(0, _SUB, one_pass, tuple(h.own for h in heads))
    dbetas = []
    for p, (h, mine) in enumerate(zip(heads, sums)):
        dstate_ref[p] = h.dstate
        dq, dk, dv, dg, dbeta = h.finish(mine)
        depth, width = _lanes(p, dq.shape[1]), _lanes(p, dv.shape[1])
        dq_ref[0, :, depth] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, depth] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, width] = dv.astype(dv_ref.dtype)
        dg_ref[0, :, depth] = dg
        dbetas.append(dbeta)
    dtile_ref[0, 0, 0] = _as_tile_row(jnp.concatenate(dbetas, axis=0),
                                      dtile_ref.shape[3])


def _as_tile_row(column, rows: int):
    """(P Q, 1) -> (rows, 128): the column as the first row of a
    heads-major tile."""
    wide = jnp.concatenate(
        [column, jnp.zeros((column.shape[0], _LANES - 1), _F32)], axis=1)
    tall = jnp.concatenate(
        [wide, jnp.zeros((_LANES - column.shape[0], _LANES), _F32)], axis=0) \
        if column.shape[0] < _LANES else wide
    return tall.T[:rows]


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _rule_backward(q, k, v, g, beta, do, heads: int, chunk: int,
                   interpret: bool):
    """``dq, dk, dv, dg, dbeta``, shaped as the operands
    (``_rule_forward``)."""
    pack, call, specs, scratch = _call(q, v, heads, chunk, interpret)
    tile = _beta_tile(beta, chunk, pack)
    rows, seq = q.shape[:2]

    narrow, wide, tile_spec, found_spec = specs(False)
    found = pl.pallas_call(
        _states_kernel,
        in_specs=[narrow, wide, narrow, tile_spec],
        out_specs=found_spec,
        out_shape=jax.ShapeDtypeStruct(
            (rows, heads, seq // chunk, v.shape[2] // heads,
             q.shape[2] // heads), _F32),
        scratch_shapes=scratch, name="kda_rule_bwd_states", **call,
    )(k, v, g, tile)

    narrow, wide, tile_spec, found_spec = specs(True)
    dq, dk, dv, dg, dtile = pl.pallas_call(
        _bwd_kernel,
        in_specs=[narrow, narrow, wide, narrow, wide, tile_spec, found_spec],
        out_specs=[narrow, narrow, wide, narrow, tile_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct(tile.shape, _F32)],
        # ... and each pass's spans, kept for the passes back
        scratch_shapes=scratch + [pltpu.VMEM(
            (pack, _SUB, chunk, q.shape[2] // heads), _F32)],
        name="kda_rule_bwd", **call,
    )(q, k, v, g, do, tile, found)

    dbeta = _heads_major(dtile[..., :1, :pack * chunk], chunk, pack)
    return dq, dk, dv, dg, dbeta.swapaxes(1, 2)


# --- the differentiable rule -------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _rule(q, k, v, g, beta, heads, chunk, interpret):
    return _rule_forward(q, k, v, g, beta, heads, chunk, interpret)


def _rule_fwd(q, k, v, g, beta, heads, chunk, interpret):
    # the backward takes the operands alone, as ``pallas_delta_rule``'s
    return (_rule_forward(q, k, v, g, beta, heads, chunk, interpret),
            (q, k, v, g, beta))


def _rule_bwd(heads, chunk, interpret, res, do):
    return _rule_backward(*res, do, heads, chunk, interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def fused_rule(q, k, v, g, beta, *, chunk: int, interpret=None):
    """``o_t = S_t^T q_t`` of the recurrence in ``ops/delta_rule.py``
    with ``S_t = Diag(exp(g_t)) S_(t-1)`` before the write. q, k, v
    (B, S, H, D) in the compute dtype, a value head a key head; g
    (<= 0) (B, S, H, Dk) and beta (B, S, H) float32. S a multiple of
    ``chunk``, the shapes such that ``fits``. Returns (B, S, H, Dv) in
    v's dtype."""
    from perceiver_tpu.utils.platform import resolve_interpret
    if q.shape[1] % chunk or g.shape != q.shape or not fits(
            chunk=chunk, key_dim=q.shape[3], value_dim=v.shape[3],
            heads=q.shape[2], value_heads=v.shape[2], dtype=v.dtype):
        raise ValueError(
            f"the vector-decay kernels do not tile {q.shape[1]} positions "
            f"in chunks of {chunk}, {q.shape[2]} key heads of {q.shape[3]}, "
            f"{v.shape[2]} value heads of {v.shape[3]}, {v.dtype}, a decay "
            f"of {g.shape}")
    def flat(x):   # (B, S, H, D) -> (B, S, H D): the projections' own
        return x.reshape(*x.shape[:2], -1)

    return _rule(flat(q.astype(v.dtype)), flat(k.astype(v.dtype)), flat(v),
                 flat(g.astype(_F32)), beta.astype(_F32), q.shape[2],
                 int(chunk), resolve_interpret(interpret)).reshape(v.shape)
