"""The Mamba-2 selective scan (``ops/ssm.py``) as Pallas kernels for
TPU, forward and backward: the SSD chunked form at the einsum form's
chunk boundaries and in its arithmetic, with everything of size
``Q x Q`` a head and the state carried between chunks in VMEM.

One grid step is one chunk of ``Q`` positions of one group of one row:
grid ``(rows, groups, chunks)``, the chunk axis last and sequential
(the carried state is a VMEM scratch). A step holds the group's ``x``
as the projection leaves it, ``(Q, heads a group x P)`` with the heads
side by side on the lanes, ``B`` and ``C`` ``(Q, N)``, and the group's
``dt`` and cumulative log-decay **heads-major**, ``(heads a group, Q)``
float32: the positions on the lanes, one vreg for eight heads. What is
needed position-major (a column a head: the decay tile's rows, the
factors of ``x``) comes from one ``128 x 128`` transpose a step of the
packed rows ``dt | log-decay | decay from the start | decay to the
end`` (the two ``exp`` taken on the small heads-major tile).

The state is held transposed, ``(N, heads a group x P)``: reading it
(``C`` times the state found) and each chunk's own (``B^T`` times what
the chunk wrote) are one dense product a group. Inside a chunk the
heads are walked a slab of 128 lanes (``128 // P`` heads) at a time, as
``ops/pallas_attention`` walks narrow heads: a head's ``Q x Q`` decay
tile is ``exp`` of the log-decay's column less its row, masked
**before** the ``exp``, times ``C B^T`` (formed once a group), cast to
the compute dtype and multiplied by the slab's ``dt x`` with the other
heads' lanes zeroed, so the heads' results add up into one lane-dense
block.

Forward ``ssm_scan_fwd`` writes ``y`` once and nothing else. The
backward takes ``x, dt, A, B, C`` and ``dy`` alone (not ``y``): a pass
over the chunks in order (``ssm_scan_bwd_states``, a third of the
forward's products) rebuilds the state each chunk found, float32, and
the reversed pass ``ssm_scan_bwd`` recomputes each chunk's tiles and
carries the state's cotangent backwards. It writes ``dx``, ``dB``,
``dC`` (float32 sums cast once) and, heads-major in float32, the
cotangent of ``dt`` through ``dt x`` and of the cumulative log-decay;
the chain through the cumulative sum to ``dt`` and ``A`` is XLA's, on
``(B, S, H)`` float32 arrays.

Rounding is the einsum form's or finer: float32 ``dt``, decays,
cumulative sums and carried state (and its cotangent); operands of a
product in the compute dtype where ``ops/ssm._chunked_scan`` casts them
(``scores x decay``, ``dt x``, what a chunk wrote, the state found) and
where its transposed products take a cotangent as an operand; float32
accumulation. What a product hands on stays float32 until the next
product takes it (autodiff's cotangent of a value cast to the compute
dtype is rounded to it; here it is not), and the contributions to
``dB`` and to ``dC`` add in float32 and are rounded once.

On non-TPU backends the kernels run in Pallas interpreter mode, so
tests exercise the identical code path on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the lanes of a vreg, the products' dimension numbers and the mask that
# zeroes a slab's other heads: the attention kernels' own
from perceiver_tpu.ops.pallas_attention import _LANES, _NN, _NT, _TN, _only

_F32 = jnp.float32

#: the rows of the packed heads-major tile, ``per`` heads each
_DT, _LOG, _FROM_START, _TO_END = range(4)

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def fits(*, chunk: int, state: int, per: int, width: int) -> bool:
    """Whether the kernels tile a call: chunks of whole lanes (the
    decay tile, the heads-major rows), a state of whole lanes, a
    group's heads in whole slabs of 128 lanes (heads of ``width`` that
    divide 128) and whole sublanes, its four packed rows a head in the
    128 that one transpose turns."""
    return (chunk % _LANES == 0 and state % _LANES == 0
            and _LANES % width == 0 and (per * width) % _LANES == 0
            and per % 8 == 0 and 4 * per <= _LANES)


def _dot(lhs, rhs, dims):
    return jax.lax.dot_general(lhs, rhs, dims, preferred_element_type=_F32)


def _columns(dt_hm, log_hm):
    """(Q, 128) float32: lane ``k per + h`` holds row ``k`` of head
    ``h`` (``_DT``, ``_LOG``, ``_FROM_START``, ``_TO_END``) by
    position. Every exponent is a sum of ``dt A <= 0``."""
    per, q = log_hm.shape
    rows = [dt_hm, log_hm, jnp.exp(log_hm),
            jnp.exp(log_hm[:, q - 1:] - log_hm)]
    if 4 * per < _LANES:
        rows.append(jnp.zeros((_LANES - 4 * per, q), _F32))
    return jnp.concatenate(rows, axis=0).T


class _Slabs:
    """The lanes of a group's heads, a slab of 128 at a time."""

    def __init__(self, per: int, width: int):
        self.per, self.width = per, width
        self.heads = _LANES // width          # heads a slab
        self.count = per // self.heads        # slabs a group
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
        self.lane = lane
        self.masks = ([None] if self.heads == 1 else
                      [lane // width == i for i in range(self.heads)])

    def lanes(self, j: int):
        return slice(j * _LANES, (j + 1) * _LANES)

    def spread(self, cols, row: int, j: int):
        """(Q, 128): each head of slab ``j`` has row ``row``'s column
        in all its lanes."""
        first = row * self.per + j * self.heads
        out = jnp.broadcast_to(cols[:, first:first + 1],
                               (cols.shape[0], _LANES))
        for i in range(1, self.heads):
            out = jnp.where(self.masks[i],
                            cols[:, first + i:first + i + 1], out)
        return out

    def each(self, j: int):
        """``(head of the group, its lane mask)`` of slab ``j``."""
        return [(j * self.heads + i, mask)
                for i, mask in enumerate(self.masks)]


def _causal(q: int):
    """(Q, Q) bool: position ``l`` (a row) reads what ``s <= l``
    wrote."""
    return (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))


def _decay(cols, log_hm, per: int, h: int, causal):
    """Head ``h``'s (Q, Q) decay from ``s`` to ``l``: the mask goes in
    before the ``exp``, so no ``exp`` of a positive number is taken."""
    span = cols[:, _LOG * per + h:_LOG * per + h + 1] - log_hm[h:h + 1, :]
    return jnp.exp(jnp.where(causal, span, -jnp.inf))


def _advance(state_ref, b, wrote, whole):
    """The carried state to the chunk's end: decayed over the whole
    chunk, plus the chunk's own (``B^T`` times what it wrote)."""
    state_ref[:] = state_ref[:] * whole + _dot(b, wrote, _TN)


# --- forward -----------------------------------------------------------------


def _fwd_kernel(x_ref, dt_ref, log_ref, b_ref, c_ref, y_ref, state_ref, *,
                per: int, width: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[:] = jnp.zeros_like(state_ref)

    x, b, c = x_ref[0], b_ref[0], c_ref[0]
    dtype, q = x.dtype, x.shape[0]
    log_hm = log_ref[0, 0]
    cols = _columns(dt_ref[0, 0], log_hm)
    slabs = _Slabs(per, width)
    causal = _causal(q)
    scores = _dot(c, b, _NT)                              # (Q, Q), l x s
    found = _dot(c, state_ref[:].astype(dtype), _NN)      # (Q, W)
    wrote, whole = [], []
    for j in range(slabs.count):
        lanes = slabs.lanes(j)
        xdt = x[:, lanes].astype(_F32) * slabs.spread(cols, _DT, j)
        xdt_low = xdt.astype(dtype)
        y = None
        for h, mask in slabs.each(j):
            weights = (scores * _decay(cols, log_hm, per, h, causal)
                       ).astype(dtype)
            y_h = _dot(weights, _only(mask, xdt_low), _NN)
            y = y_h if y is None else y + y_h
        from_start = slabs.spread(cols, _FROM_START, j)
        y_ref[0, :, lanes] = (y + found[:, lanes] * from_start
                              ).astype(y_ref.dtype)
        wrote.append((xdt * slabs.spread(cols, _TO_END, j)).astype(dtype))
        whole.append(from_start[q - 1:])
    _advance(state_ref, b, jnp.concatenate(wrote, axis=1),
             jnp.concatenate(whole, axis=1))


def _specs(chunks: int, chunk: int, per: int, lanes: int, state: int,
           reverse: bool):
    """Block specs of a group's chunk: the ``(Q, lanes)`` block of x
    (y, dy, dx), the heads-major ``(per, Q)`` block of dt (the
    log-decay and their cotangents), the ``(Q, N)`` block of B (C and
    their cotangents), the ``(N, lanes)`` block of the state found."""
    def at(ic):
        return chunks - 1 - ic if reverse else ic

    wide = pl.BlockSpec((1, chunk, lanes),
                        lambda ib, ig, ic: (ib, at(ic), ig))
    rows = pl.BlockSpec((1, 1, per, chunk),
                        lambda ib, ig, ic: (ib, ig, 0, at(ic)))
    narrow = pl.BlockSpec((1, chunk, state),
                          lambda ib, ig, ic: (ib, at(ic), ig))
    found = pl.BlockSpec((1, 1, 1, state, lanes),
                         lambda ib, ig, ic: (ib, ig, at(ic), 0, 0))
    return wide, rows, narrow, found


def _head_major(v, groups: int):
    """(B, S, H) -> (B, G, heads a group, S)."""
    rows, seq, heads = v.shape
    return v.reshape(rows, seq, groups, heads // groups).transpose(0, 2, 3, 1)


def _position_major(v):
    """The inverse: (B, G, heads a group, S) -> (B, S, H)."""
    rows, groups, per, seq = v.shape
    return v.transpose(0, 3, 1, 2).reshape(rows, seq, groups * per)


def log_decays(dt, a, chunk: int):
    """(B, S, H) float32: the log of the decay from each chunk's start
    to each of its positions, ``<= 0``: ``dt A`` summed inside the
    chunk."""
    rows, seq, heads = dt.shape
    return jnp.cumsum((dt * a).reshape(rows, seq // chunk, chunk, heads),
                      axis=2).reshape(rows, seq, heads)


def _operands(x, dt, log, b, c):
    """The kernels' views: x (B, S, H P), dt and the log-decay
    heads-major, B and C (B, S, G N)."""
    rows, seq, heads, width = x.shape
    groups = b.shape[2]
    return (x.reshape(rows, seq, heads * width),
            _head_major(dt, groups), _head_major(log, groups),
            b.reshape(rows, seq, -1), c.reshape(rows, seq, -1))


def _scan_forward(x, dt, a, b, c, chunk: int, interpret: bool):
    rows, seq, heads, width = x.shape
    groups, state = b.shape[2:]
    per, chunks = heads // groups, seq // chunk
    lanes = per * width
    wide, hm, narrow, _ = _specs(chunks, chunk, per, lanes, state, False)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, per=per, width=width),
        grid=(rows, groups, chunks),
        in_specs=[wide, hm, hm, narrow, narrow],
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct((rows, seq, heads * width), x.dtype),
        scratch_shapes=[pltpu.VMEM((state, lanes), _F32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="ssm_scan_fwd",
    )(*_operands(x, dt, log_decays(dt, a, chunk), b, c))
    return y.reshape(x.shape)


# --- backward ----------------------------------------------------------------


def _states_kernel(x_ref, dt_ref, log_ref, b_ref, found_ref, state_ref, *,
                   per: int, width: int):
    """The state each chunk finds at its start, as the forward carried
    it."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[:] = jnp.zeros_like(state_ref)

    found_ref[0, 0, 0] = state_ref[:]
    x, b = x_ref[0], b_ref[0]
    q = x.shape[0]
    cols = _columns(dt_ref[0, 0], log_ref[0, 0])
    slabs = _Slabs(per, width)
    wrote, whole = [], []
    for j in range(slabs.count):
        xdt = x[:, slabs.lanes(j)].astype(_F32) * slabs.spread(cols, _DT, j)
        wrote.append((xdt * slabs.spread(cols, _TO_END, j)).astype(x.dtype))
        whole.append(slabs.spread(cols, _FROM_START, j)[q - 1:])
    _advance(state_ref, b, jnp.concatenate(wrote, axis=1),
             jnp.concatenate(whole, axis=1))


def _bwd_kernel(x_ref, dy_ref, dt_ref, log_ref, b_ref, c_ref, found_ref,
                dx_ref, ddt_ref, dlog_ref, db_ref, dc_ref, dstate_ref, *,
                per: int, width: int):
    """One chunk, the chunks in reverse: ``dstate_ref`` holds the
    cotangent of the state at the chunk's end and leaves as that of the
    state the chunk found."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[:] = jnp.zeros_like(dstate_ref)

    x, dy, b, c = x_ref[0], dy_ref[0], b_ref[0], c_ref[0]
    dtype, q = x.dtype, x.shape[0]
    log_hm = log_ref[0, 0]
    cols = _columns(dt_ref[0, 0], log_hm)
    slabs = _Slabs(per, width)
    causal = _causal(q)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    head_row = jax.lax.broadcasted_iota(jnp.int32, (per, 1), 0)
    scores = _dot(c, b, _NT)                              # (Q, Q), l x s
    found, dstate = found_ref[0, 0, 0], dstate_ref[:]     # (N, W) float32
    found_low, dstate_low = found.astype(dtype), dstate.astype(dtype)
    read = _dot(c, found_low, _NN)                        # (Q, W)
    # what the chunk wrote reaches the state at its end alone
    dwrote = _dot(b, dstate_low, _NN)                     # (Q, W)

    dscores = jnp.zeros((q, q), _F32)
    col_sums = jnp.zeros((q, _LANES), _F32)   # lane h: dlog, per + h: ddt
    row_sums = jnp.zeros((per, q), _F32)      # dlog, what comes as a row
    dread, wrote, whole = [], [], []
    for j in range(slabs.count):
        lanes = slabs.lanes(j)
        xs, g = x[:, lanes].astype(_F32), dy[:, lanes]
        gf = g.astype(_F32)
        dt_e = slabs.spread(cols, _DT, j)
        from_start = slabs.spread(cols, _FROM_START, j)
        to_end = slabs.spread(cols, _TO_END, j)
        xdt = xs * dt_e
        xdt_low, wrote_f = xdt.astype(dtype), xdt * to_end
        dw = dwrote[:, lanes]
        whole_e = from_start[q - 1:]                       # (1, 128)
        # through the decays from the start (read) and to the end
        # (wrote), by position; and through the decay over the whole
        # chunk (the carried state, every decay to the end), a head
        through_pos = gf * (read[:, lanes] * from_start) - dw * wrote_f
        through_whole = (
            jnp.sum(dstate[:, lanes] * found[:, lanes], axis=0,
                    keepdims=True) * whole_e
            + jnp.sum(dw * wrote_f, axis=0, keepdims=True))  # (1, 128)
        dxdt_low = None
        for h, mask in slabs.each(j):
            decay = _decay(cols, log_hm, per, h, causal)
            weights = (scores * decay).astype(dtype)
            g_h = _only(mask, g)
            dweights = _dot(g_h, xdt_low, _NT)                 # l x s
            dscores_h = dweights * decay
            dscores = dscores + dscores_h
            through_decay = dscores_h * scores
            d_h = _dot(weights, g_h, _TN)                  # (Q, 128), s
            dxdt_low = d_h if dxdt_low is None else dxdt_low + d_h
            col = (jnp.sum(through_decay, axis=1, keepdims=True)
                   + jnp.sum(_only(mask, through_pos), axis=1,
                             keepdims=True))               # (Q, 1)
            col_sums = jnp.where(slabs.lane == h, col, col_sums)
            row = (jnp.where(last, jnp.sum(_only(mask, through_whole),
                                           axis=1, keepdims=True), 0.0)
                   - jnp.sum(through_decay, axis=0, keepdims=True))
            row_sums = jnp.where(head_row == h, row, row_sums)
        dxdt = dw * to_end + dxdt_low
        dx_ref[0, :, lanes] = (dxdt * dt_e).astype(dx_ref.dtype)
        through_x = dxdt * xs
        for h, mask in slabs.each(j):
            col_sums = jnp.where(
                slabs.lane == per + h,
                jnp.sum(_only(mask, through_x), axis=1, keepdims=True),
                col_sums)
        dread.append((gf * from_start).astype(dtype))
        wrote.append(wrote_f.astype(dtype))
        whole.append(whole_e)

    dread, wrote = (jnp.concatenate(v, axis=1) for v in (dread, wrote))
    dscores = dscores.astype(dtype)
    dc_ref[0] = (_dot(dscores, b, _NN)
                 + _dot(dread, found_low, _NT)).astype(dc_ref.dtype)
    db_ref[0] = (_dot(dscores, c, _TN)
                 + _dot(wrote, dstate_low, _NT)).astype(db_ref.dtype)
    dstate_ref[:] = (dstate * jnp.concatenate(whole, axis=1)
                     + _dot(c, dread, _TN))
    col_sums = col_sums.T                                  # (128, Q)
    dlog_ref[0, 0] = row_sums + col_sums[:per]
    ddt_ref[0, 0] = col_sums[per:2 * per]


def _scan_backward(x, dt, a, b, c, dy, chunk: int, interpret: bool):
    """``dx, ddt, da, db, dc``."""
    rows, seq, heads, width = x.shape
    groups, state = b.shape[2:]
    per, chunks = heads // groups, seq // chunk
    lanes = per * width
    kernel = dict(per=per, width=width)
    call = dict(grid=(rows, groups, chunks),
                scratch_shapes=[pltpu.VMEM((state, lanes), _F32)],
                compiler_params=_COMPILER_PARAMS, interpret=interpret)
    log, chain = jax.vjp(lambda dt, a: log_decays(dt, a, chunk), dt, a)
    xw, dt_hm, log_hm, bw, cw = _operands(x, dt, log, b, c)

    wide, hm, narrow, found_spec = _specs(chunks, chunk, per, lanes, state,
                                          False)
    found = pl.pallas_call(
        functools.partial(_states_kernel, **kernel),
        in_specs=[wide, hm, hm, narrow],
        out_specs=found_spec,
        out_shape=jax.ShapeDtypeStruct(
            (rows, groups, chunks, state, lanes), _F32),
        name="ssm_scan_bwd_states", **call,
    )(xw, dt_hm, log_hm, bw)

    wide, hm, narrow, found_spec = _specs(chunks, chunk, per, lanes, state,
                                          True)
    dx, ddt, dlog, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, **kernel),
        in_specs=[wide, wide, hm, hm, narrow, narrow, found_spec],
        out_specs=[wide, hm, hm, narrow, narrow],
        out_shape=[jax.ShapeDtypeStruct(xw.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt_hm.shape, _F32),
                   jax.ShapeDtypeStruct(dt_hm.shape, _F32),
                   jax.ShapeDtypeStruct(bw.shape, b.dtype),
                   jax.ShapeDtypeStruct(cw.shape, c.dtype)],
        name="ssm_scan_bwd", **call,
    )(xw, dy.reshape(xw.shape), dt_hm, log_hm, bw, cw, found)

    ddt_log, da = chain(_position_major(dlog))
    return (dx.reshape(x.shape), _position_major(ddt) + ddt_log, da,
            db.reshape(b.shape), dc.reshape(c.shape))


# --- the differentiable scan -------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(x, dt, a, b, c, chunk, interpret):
    return _scan_forward(x, dt, a, b, c, chunk, interpret)


def _scan_fwd(x, dt, a, b, c, chunk, interpret):
    # the backward takes the operands alone: with the output held by a
    # ``remat`` layer (``ssm_out``) the recomputed layer does not run
    # this kernel again
    return _scan_forward(x, dt, a, b, c, chunk, interpret), (x, dt, a, b, c)


def _scan_bwd(chunk, interpret, res, dy):
    return _scan_backward(*res, dy, chunk, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def fused_scan(x, dt, a, b, c, *, chunk: int, interpret=None):
    """``y_t = C_t s_t`` of the recurrence in ``ops/ssm.py``, without
    ``D x``. x (B, S, H, P) in the compute dtype; dt (B, S, H) float32,
    >= 0; a (H,) float32, < 0; b, c (B, S, G, N) in x's dtype. S a
    multiple of ``chunk``, the shapes such that ``fits``. Returns
    (B, S, H, P) in x's dtype."""
    from perceiver_tpu.utils.platform import resolve_interpret
    groups, state = b.shape[2:]
    per = x.shape[2] // groups
    if x.shape[1] % chunk or not fits(chunk=chunk, state=state, per=per,
                                      width=x.shape[3]):
        raise ValueError(
            f"the scan kernels do not tile {x.shape[1]} positions in "
            f"chunks of {chunk}, {per} heads of {x.shape[3]} a group, "
            f"state {state}")
    return _scan(x, dt.astype(_F32), a.astype(_F32), b.astype(x.dtype),
                 c.astype(x.dtype), int(chunk), resolve_interpret(interpret))
