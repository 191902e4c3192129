"""The linear mixers' short convolution, its SiLU and the l2 norm of a
head's channels as one Pallas pass forward and one backward
(``ops/ssm.causal_conv``, ``jax.nn.silu`` and ``ops/delta_rule.l2_norm``
are the same function as XLA operations, and the oracle in the tests)::

    pre[t] = sum_k w[k] x[t - (K - 1) + k] + b     (depthwise, causal;
                                                    zeros before a row)
    y = silu(pre)
    out = y / sqrt(sum(y^2 over a head) + eps)     (a query's and a key's
          / sqrt(D)                                 channels; a query's)

``x`` (B, S, C) in the compute dtype, ``w`` (K, C), ``b`` (C,) or none,
heads of ``D`` channels side by side. XLA runs this as a padded float32
copy of ``x``, a pass a tap, and the norm's own passes, and its backward
keeps the taps' float32 copies: 13 to 25 times the bytes of one read of
``x`` and one write of the result (PERF.md, PR 46).

**A call a part.** The channels come in up to three parts (a query's,
scaled; a key's, normed; the rest), each a Pallas call of its own kind
that reads its channels of ``x`` where they lie (``x`` may be a wider
array, a projection's whole product: the block index starts ``first``
channels in, so neither the mixer's slice of the product nor the slices
of the result into q, k and v are ever written) and writes its own
array; the backward takes each part's cotangent as it comes from the
rule and writes ``dx`` a part, which XLA lays side by side once.

**Forward** ``short_conv_fwd``. Grid ``(rows, tiles of the part's
channels, tiles of positions)``. A step holds one tile of ``x`` and the
block of 16 positions before it (zeros before a row's first position:
no padded copy is written), walks the tile a few sublanes at a time
(``_ROWS``: what a walk holds stays in registers), forms the float32
sum of the taps from sublane rotations of the walk's rows behind the
eight before them, SiLU and the norm a head of whole lanes, and writes
the tile once in ``x``'s dtype.

**Backward** ``short_conv_bwd``. A step holds the same tile of ``x``
with the 16 positions before **and** after it, and the cotangent's tile
with the 16 after it (zeros past a row's end). It walks the tile from
its end, makes ``pre``, ``y`` and the norm's sum again, forms ``dpre``,
and writes ``dx[u] = sum_k w[k] dpre[u + (K - 1) - k]`` from the walk's
``dpre`` and the first eight rows of the walk after it (carried; the
tile's last walk takes them from the blocks after the tile). ``dw`` and
``db`` are float32 sums a sublane, carried through the walks and added
into one ``(K + 1, 8, tile)`` block a row and tile of channels over the
sequential axis of positions; XLA sums the rows and the eight sublanes.
Residuals are ``x`` and the parameters: nothing float32 of ``x``'s size
reaches HBM.

**Rounding.** float32 from the first product to the last sum; the
result and ``dx`` are rounded to ``x``'s dtype once. The XLA form rounds
the convolution's sum to the compute dtype before SiLU and SiLU's result
before the norm: here neither is.

**Which runs** is ``fits``'s to say from what the call can observe (a
TPU backend, operands on one device, whole lanes), never a name or a
knob; ``short_conv`` is the one function the three mixers call, and
``conv_paths`` tallies what each call site took.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# (``jax.experimental.pallas`` is imported where a kernel is built, not
# here: the mixers import this module for ``short_conv``, and a process
# that never takes the kernels, every CPU run, does not pay Pallas's
# import, 3.4 s on the sandbox)
from perceiver_tpu.ops.attention import mesh_devices
from perceiver_tpu.ops.tally import Tally

_F32 = jnp.float32
_LANES = 128
#: positions a block before or after a tile: whole sublanes of a bf16
#: block; the eight nearest the tile are read
_EDGE = 16
#: positions a tile (a shorter row is one tile), the widest tile of
#: channels, positions a walk inside a tile (read where a call is made
#: and handed to the jitted directions among their static arguments)
_POSITIONS, _CHANNELS, _ROWS = 512, 512, 16

#: what the convolution took at each call site: ``fused[8192ch, norm
#: 4096]`` (the kernels; the first 4,096 channels normed),
#: ``xla[6144ch, backend]`` (XLA's operations, and why not the kernels)
conv_paths = Tally()


def _backend() -> str:
    """The backend ``fits`` reads (a seam: a test that says ``tpu``
    here gets the kernels, interpreted)."""
    return jax.default_backend()


def channel_tile(widest: int, channels: int, head_dim: int,
                 *starts: int) -> int:
    """The widest tile of channels up to ``widest``, of whole heads and
    whole lanes, that divides a part's ``channels`` and where they
    start, in ``x`` and in the taps; 0 where there is none."""
    tile = widest
    while tile >= _LANES:
        if all(n % tile == 0 for n in (channels, *starts)) \
                and tile % max(head_dim, 1) == 0:
            return tile
        tile //= 2
    return 0


def fits(x, taps: int, channels: int, first: int = 0, *, head_dim: int = 0,
         scaled: int = 0, normed: int = 0, rest=None) -> str:
    """Why a call does not take the kernels (``backend``, ``mesh``,
    ``dtype``, ``shape``), or ``""`` where it does: a TPU backend,
    operands on one device (a Pallas call has no partitioning rule),
    bfloat16 or float32, every part's channels (``channels`` of ``x``'s
    from ``first`` on) and its heads in whole lanes where they lie, no
    more taps than the eight positions read before a tile."""
    if _backend() != "tpu":
        return "backend"
    if mesh_devices(x) != 1:
        return "mesh"
    if x.dtype not in (jnp.bfloat16, jnp.float32):
        return "dtype"
    if scaled + normed and (not head_dim or head_dim % _LANES):
        return "shape"
    parts = _parts(channels, head_dim, scaled, normed, rest)
    widths = [width for _, width, _, _ in parts]
    if not 1 <= taps <= 8 or first + channels > x.shape[-1] \
            or min(widths) < 0 or sum(widths) != channels:
        return "shape"
    if not all(channel_tile(_CHANNELS, width, head, first + start, start)
               for start, width, head, _ in parts):
        return "shape"
    return ""


# --- what a walk computes ----------------------------------------------------


def _shifted(before, rows, taps: int):
    """``rows`` (n, C) float32 as each tap reads them, behind the eight
    positions ``before`` them: ``[x[t], x[t - 1], ..]``, position
    ``t - s`` brought to ``t`` by a rotation of the sublanes."""
    from jax.experimental.pallas import tpu as pltpu

    ext = jnp.concatenate([before, rows], axis=0)
    return [rows] + [pltpu.roll(ext, s, 0)[8:] for s in range(1, taps)]


def _tap_sum(shifted, w, bias: bool):
    """``pre`` from ``_shifted``'s copies: tap ``K - 1 - s`` reads the
    position ``s`` back."""
    taps = len(shifted)
    pre = functools.reduce(jnp.add, (
        w[taps - 1 - s:taps - s] * x for s, x in enumerate(shifted)))
    return pre + w[taps:taps + 1] if bias else pre


def _silu(pre):
    sig = 1.0 / (1.0 + jnp.exp(-pre))
    return pre * sig, sig


def _by_head(fn, head: int, *values):
    """``fn`` a head of ``head`` lanes, the results side by side."""
    width = values[0].shape[1]
    if width == head:
        return fn(*values)
    return jnp.concatenate(
        [fn(*(v[:, h:h + head] for v in values))
         for h in range(0, width, head)], axis=1)


def _activate(pre, head: int, scale, eps: float):
    """``silu``, then the l2 norm a head times ``scale`` where ``head``
    is set."""
    y, _ = _silu(pre)
    if not head:
        return y

    def norm(y):
        return y * (jax.lax.rsqrt(
            jnp.sum(y * y, axis=1, keepdims=True) + eps) * scale)

    return _by_head(norm, head, y)


def _activate_back(pre, dout, head: int, scale, eps: float):
    """The cotangent of ``pre`` from that of ``_activate``'s result."""
    y, sig = _silu(pre)
    if head:
        def norm_back(y, dout):
            r = jax.lax.rsqrt(jnp.sum(y * y, axis=1, keepdims=True) + eps)
            along = jnp.sum(dout * y, axis=1, keepdims=True)
            return (dout - y * (r * r * along)) * (r * scale)

        dout = _by_head(norm_back, head, y, dout)
    return dout * (sig * (1.0 + pre * (1.0 - sig)))


def _last_eight(ref, start):
    """The eight positions before ``start`` (a multiple of ``_ROWS``,
    at least ``_ROWS``) of a tile, float32."""
    from jax.experimental import pallas as pl

    return ref[0, pl.ds(pl.multiple_of(start - _EDGE, _EDGE), _EDGE), :
               ].astype(_F32)[_EDGE - 8:]


# --- the kernels -------------------------------------------------------------


def _forward_kernel(x_ref, before_ref, w_ref, out_ref, *, taps, bias, rows,
                    head, scale, eps):
    from jax.experimental import pallas as pl

    w = w_ref[...]
    edge = jnp.where(pl.program_id(2) == 0, 0.0,
                     before_ref[0].astype(_F32))[_EDGE - 8:]

    def walk(r, _):
        start = pl.multiple_of(r * rows, rows)
        before = jnp.where(
            r == 0, edge, _last_eight(x_ref, jnp.maximum(start, rows)))
        pre = _tap_sum(_shifted(
            before, x_ref[0, pl.ds(start, rows), :].astype(_F32), taps),
            w, bias)
        out_ref[0, pl.ds(start, rows), :] = _activate(
            pre, head, scale, eps).astype(out_ref.dtype)
        return _

    jax.lax.fori_loop(0, x_ref.shape[1] // rows, walk, 0)


def _backward_kernel(x_ref, before_ref, after_ref, ct_ref, ct_after_ref,
                     w_ref, dx_ref, dw_ref, *, taps, bias, rows, head, scale,
                     eps):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    position = pl.program_id(2)
    first, last = position == 0, position == pl.num_programs(2) - 1
    w = w_ref[...]
    edge = jnp.where(first, 0.0, before_ref[0].astype(_F32))[_EDGE - 8:]
    size = x_ref.shape[1]
    walks = size // rows

    def dpre_of(shifted, ct_rows):
        return _activate_back(_tap_sum(shifted, w, bias), ct_rows, head,
                              scale, eps)

    # the walk after the tile's last: the first positions of the next
    # tile, nothing past a row's end
    after = dpre_of(
        _shifted(_last_eight(x_ref, size), after_ref[0].astype(_F32), taps),
        jnp.where(last, 0.0, ct_after_ref[0].astype(_F32)))[:8]

    def by_sublane(v):     # (rows, C) -> (8, C), a sum a sublane
        return functools.reduce(
            jnp.add, [v[n:n + 8] for n in range(0, rows, 8)])

    def walk(n, carried):
        after, sublane_sums = carried
        r = walks - 1 - n
        start = pl.multiple_of(r * rows, rows)
        before = jnp.where(
            r == 0, edge, _last_eight(x_ref, jnp.maximum(start, rows)))
        shifted = _shifted(
            before, x_ref[0, pl.ds(start, rows), :].astype(_F32), taps)
        dpre = dpre_of(shifted, ct_ref[0, pl.ds(start, rows), :].astype(_F32))
        ext = jnp.concatenate([dpre, after], axis=0)
        dx = w[taps - 1:taps] * dpre
        for s in range(1, taps):
            dx = dx + w[taps - 1 - s:taps - s] * pltpu.roll(
                ext, rows + 8 - s, 0)[:rows]
        dx_ref[0, pl.ds(start, rows), :] = dx.astype(dx_ref.dtype)
        new = [by_sublane(dpre * x) for x in reversed(shifted)]
        if bias:
            new.append(by_sublane(dpre))
        return dpre[:8], tuple(a + b for a, b in zip(sublane_sums, new))

    zeros = jnp.zeros((8, x_ref.shape[2]), _F32)
    _, sublane_sums = jax.lax.fori_loop(
        0, walks, walk, (after, (zeros,) * w.shape[0]))

    @pl.when(first)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for k, value in enumerate(sublane_sums):
        dw_ref[0, k] += value


# --- the calls: one a part, each reading ``x`` where it lies -----------------


def _parts(channels: int, head_dim: int, scaled: int, normed: int,
           rest=None):
    """``(first channel, width, head, scale)`` of each part of a
    convolution's channels, those of no width left out: the scaled
    (a query's), the normed (a key's), then the others, in parts of
    ``rest`` where the caller wants them apart."""
    out, start = [], 0
    for width, head, scale in (
            (scaled, head_dim, 1.0 / math.sqrt(head_dim or 1)),
            (normed, head_dim, 1.0),
            *((width, 0, 1.0)
              for width in rest or (channels - scaled - normed,))):
        if width:
            out.append((start, width, head, scale))
        start += width
    return out


def _position_tile(seq: int, positions: int, walk: int):
    """``(positions a tile, padded positions)``: ``positions`` a tile,
    or the whole of a shorter row in whole walks."""
    tile = min(positions, seq + -seq % _EDGE)
    tile += -tile % max(walk, _EDGE)
    return tile, seq + -seq % tile


def _specs(tile: int, channels: int, offset: int, edge_blocks: int):
    """Block specs of a tile of positions of ``x``, the edge before it
    and the edge after it, ``offset`` tiles of channels into the array;
    grid ``(row, tile of channels, tile of positions)``."""
    from jax.experimental import pallas as pl

    per = tile // _EDGE
    return (pl.BlockSpec((1, tile, channels),
                         lambda b, j, i: (b, i, j + offset)),
            pl.BlockSpec((1, _EDGE, channels), lambda b, j, i: (
                b, jnp.maximum(i * per - 1, 0), j + offset)),
            pl.BlockSpec((1, _EDGE, channels), lambda b, j, i: (
                b, jnp.minimum((i + 1) * per, edge_blocks - 1), j + offset)))


def _call(kernel, name: str, interpret: bool, **static):
    """``pl.pallas_call`` of a direction's kernel: the position axis
    last and sequential."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def call(**shapes):
        return pl.pallas_call(
            functools.partial(kernel, **static), **shapes,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=64 * 1024 * 1024),
            interpret=interpret, name=name)

    return pl, call


def _pad(x, padded: int):
    return x if x.shape[1] == padded else jnp.pad(
        x, ((0, 0), (0, padded - x.shape[1]), (0, 0)))


@functools.partial(jax.jit, static_argnums=tuple(range(2, 11)))
def _conv_forward(x, w, first: int, taps: int, head_dim: int, scaled: int,
                  normed: int, rest, eps: float, tiles, interpret: bool):
    rows, seq = x.shape[:2]
    positions, widest, walk = tiles
    tile, padded = _position_tile(seq, positions, walk)
    x = _pad(x, padded)
    out = []
    for start, width, head, scale in _parts(w.shape[1], head_dim, scaled,
                                            normed, rest):
        channels = channel_tile(widest, width, head, first + start, start)
        here, before, _ = _specs(tile, channels, (first + start) // channels,
                                 padded // _EDGE)
        pl, call = _call(
            _forward_kernel, "short_conv_fwd", interpret, taps=taps,
            bias=w.shape[0] > taps, rows=min(walk, tile), head=head,
            scale=scale, eps=eps)
        out.append(call(
            grid=(rows, width // channels, padded // tile),
            in_specs=[here, before, pl.BlockSpec(
                (w.shape[0], channels),
                lambda b, j, i, at=start // channels: (0, j + at))],
            out_specs=pl.BlockSpec((1, tile, channels),
                                   lambda b, j, i: (b, i, j)),
            out_shape=jax.ShapeDtypeStruct((rows, padded, width), x.dtype)
        )(x, x, w)[:, :seq])
    return tuple(out)


@functools.partial(jax.jit, static_argnums=tuple(range(3, 12)))
def _conv_backward(x, w, cts, first: int, taps: int, head_dim: int,
                   scaled: int, normed: int, rest, eps: float, tiles,
                   interpret: bool):
    rows, seq, wide = x.shape
    positions, widest, walk = tiles
    tile, padded = _position_tile(seq, positions, walk)
    x = _pad(x, padded)
    sums = w.shape[0]
    dxs, dws = [], []
    for (start, width, head, scale), ct in zip(
            _parts(w.shape[1], head_dim, scaled, normed, rest), cts):
        channels = channel_tile(widest, width, head, first + start, start)
        blocks = padded // _EDGE
        here, before, after = _specs(tile, channels,
                                     (first + start) // channels, blocks)
        part, _, part_after = _specs(tile, channels, 0, blocks)
        ct = _pad(ct.astype(x.dtype), padded)
        pl, call = _call(
            _backward_kernel, "short_conv_bwd", interpret, taps=taps,
            bias=sums > taps, rows=min(walk, tile), head=head, scale=scale,
            eps=eps)
        dx, dw = call(
            grid=(rows, width // channels, padded // tile),
            in_specs=[here, before, after, part, part_after, pl.BlockSpec(
                (sums, channels),
                lambda b, j, i, at=start // channels: (0, j + at))],
            out_specs=[part, pl.BlockSpec((1, sums, 8, channels),
                                          lambda b, j, i: (b, 0, 0, j))],
            out_shape=[jax.ShapeDtypeStruct((rows, padded, width), x.dtype),
                       jax.ShapeDtypeStruct((rows, sums, 8, width), _F32)]
        )(x, x, x, ct, ct, w)
        dxs.append(dx[:, :seq])
        dws.append(dw.sum((0, 2)))
    dx = dxs[0] if len(dxs) == 1 else jnp.concatenate(dxs, axis=-1)
    if wide > w.shape[1]:    # the channels of ``x`` that are not this
        dx = jnp.pad(dx, ((0, 0), (0, 0),       # convolution's: no gradient
                          (first, wide - first - w.shape[1])))
    return dx, dws[0] if len(dws) == 1 else jnp.concatenate(dws, axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv(x, w, static):
    return _conv_forward(x, w, *static)


def _conv_fwd(x, w, static):
    return _conv_forward(x, w, *static), (x, w)


def _conv_bwd(static, operands, cts):
    return _conv_backward(*operands, cts, *static)


_conv.defvjp(_conv_fwd, _conv_bwd)


def fused_short_conv(params, x, *, head_dim: int = 0, scaled: int = 0,
                     normed: int = 0, rest=None, first: int = 0,
                     eps: float = 1e-6, interpret=None):
    """The kernels on one convolution's tree (``w`` (K, C), ``bias``
    (C,) where it has one) and the ``C`` channels of ``x`` (B, S, .)
    from ``first`` on, read where they lie. Comes back in flat parts
    (B, S, width), those of no width left out: the first ``scaled``
    channels l2-normed a head of ``head_dim`` and over
    ``sqrt(head_dim)``, the next ``normed`` l2-normed, the others (in
    parts of ``rest`` where it is given). What ``short_conv`` runs
    where ``fits`` lets it; a test calls it outright."""
    from perceiver_tpu.utils.platform import resolve_interpret

    taps = params["w"].shape[0]
    w = params["w"].astype(_F32)
    if "bias" in params:
        w = jnp.concatenate([w, params["bias"].astype(_F32)[None]])
    return _conv(x, w, (
        int(first), taps, int(head_dim), int(scaled), int(normed),
        rest and tuple(rest), float(eps), (_POSITIONS, _CHANNELS, _ROWS),
        resolve_interpret(interpret)))


def _split(x, widths):
    """``x``'s channels in consecutive parts of ``widths``; ``x``
    itself where there is one."""
    if len(widths) == 1:
        return [x]
    return jnp.split(x, [sum(widths[:n]) for n in range(1, len(widths))],
                     axis=-1)


def short_conv(convs, x, *, head_dim: int = 0, scaled: int = 0,
               normed: int = 0, rest=None, eps: float = 1e-6, cut_from=None):
    """``silu(conv(x))`` of a mixer's short convolutions and the l2 norms
    after it. ``convs``: the convolutions' trees, each over its own
    consecutive channels of ``x`` (B, S, C) (one over all of them, or
    one a part). Comes back in parts, those of no width left out: the
    first ``scaled`` channels by heads (B, S, H, ``head_dim``),
    l2-normed a head and divided by ``sqrt(head_dim)`` (a query); the
    next ``normed`` by heads, l2-normed (a key); the others as they
    lie, (B, S, .), in parts of the widths ``rest`` where it is given,
    or by heads too where each part has a convolution of its own.
    ``cut_from``: ``(array, first channel)`` where ``x`` is a
    caller's slice of a wider array (a projection's product): the
    kernels read the channels there and the slice is never made.

    The kernels where ``fits`` says so, a pass a part; elsewhere XLA's
    operations as the mixers always had them (``causal_conv``,
    ``silu``, ``l2_norm``, the same lowered text); ``conv_paths`` counts
    which."""
    from perceiver_tpu.ops.delta_rule import l2_norm
    from perceiver_tpu.ops.ssm import causal_conv

    channels = x.shape[-1]
    own = len(convs) > 1            # a convolution a part
    taps = {c["w"].shape[0] for c in convs}
    source, first = cut_from or (x, 0)
    kinds = _parts(channels, head_dim, scaled, normed, rest)
    why = "shape" if len(taps) > 1 or len({"bias" in c for c in convs}) > 1 \
        else fits(source, min(taps), channels, first, head_dim=head_dim,
                  scaled=scaled, normed=normed, rest=rest)
    norm = scaled + normed
    conv_paths.add(
        f"{'xla' if why else 'fused'}[{channels}ch"
        + (", norm" + (f" {norm}" if norm < channels else "") if norm else "")
        + (f", {why}" if why else "") + "]")

    def by_heads(part):
        return part.reshape(*part.shape[:2], -1, head_dim)

    if not why:
        one = convs[0] if not own else {
            name: jnp.concatenate([c[name] for c in convs], axis=-1)
            for name in convs[0]}
        return tuple(
            by_heads(part) if head or own else part
            for part, (_, _, head, _) in zip(fused_short_conv(
                one, source, head_dim=head_dim, scaled=scaled, normed=normed,
                rest=rest, first=first, eps=eps), kinds))
    widths = [width for _, width, _, _ in kinds]
    if own:
        parts = [by_heads(jax.nn.silu(causal_conv(c, part)))
                 for c, part in zip(convs, _split(x, widths))]
    else:
        parts = _split(jax.nn.silu(causal_conv(convs[0], x)), widths)
    out = []
    for part, (start, _, head, _) in zip(parts, kinds):
        if head:
            part = l2_norm(part if own else by_heads(part), eps)
            if start < scaled:
                part = part / math.sqrt(head_dim)
            part = part.astype(x.dtype)
        out.append(part)
    return tuple(out)
