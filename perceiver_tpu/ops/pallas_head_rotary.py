"""The attention layers' RMSNorm over a head's channels and the rotary
positions after it as one Pallas pass forward and one backward, on the
projection's product (``ops/attention.head_rms_norm`` and
``ops/fourier.rope_apply`` are the same function as XLA operations, and
the oracle in the tests)::

    n = x * rsqrt(mean(x^2 over a head) + eps) * w     (w the tree's scale
                                                        or 1 + its bias)
    y[j]       = n[j] cos[j]       - n[j + R/2] sin[j]        (j < R/2)
    y[j + R/2] = n[j + R/2] cos[.] + n[j]       sin[j + R/2]

``x`` (B, L, H x D) in the compute dtype, heads of ``D`` channels side by
side as the projection leaves them; the tables ``(cos, sin)`` of (L', R),
``L' >= L``, turn ``R <= D`` channels of every head from ``offset`` on
(0: ``rope_apply``'s partial rotary factor; ``D - R``: latent
attention's query heads, ``[nope | rope]``), the other channels pass.
Either half may be left out: the norm alone, the rotation alone. XLA
runs the pair as nine fusions that keep the float32 copy of ``x`` by
heads alive, 13 to 26 times the bytes of one read of ``x`` and one write
of the result; its backward as many again (PERF.md, PR 50).

**Forward** ``head_rotary_fwd``. Grid ``(tiles of positions, tiles of
whole heads, rows)``: the tables' tile changes with the first axis alone
and is fetched once for all the heads and rows. A step holds one tile of
``x``, read where it lies (``x`` may be a wider array, a projection's
packed product: the block index starts ``first`` channels in; and heads
may lie ``stride`` channels apart, a query beside its gate, when a tile
is one head), walks it a few sublanes at a time (``_ROWS``), norms a
head of whole lanes and turns the lanes of its rotated span: the span is
whole lanes wide, the partner of a channel comes by a rotation of the
lanes (``pltpu.roll``), and the sign and the zeros outside the ``R``
channels are the tables', which arrive ready a span (``_span_tables``).

**Backward** ``head_rotary_bwd``. The same grid: reads ``x`` and the
cotangent, turns the cotangent back (the transpose of the rotation: the
same lanes' rotation against tables rolled by XLA, exact for any
tables, the rotation by the negative angle for ``rope_tables``'),
makes ``rstd`` again, writes ``dx`` once, and the scale's gradient as
float32 sums a sublane, one ``(8, D)`` block a grid step, which XLA
adds. Residuals are ``x``, the scale and the tables: nothing float32 of
``x``'s size reaches HBM. The tables take no gradient.

**Rounding.** float32 from ``x`` to ``y``; the result and ``dx`` are
rounded to ``x``'s dtype once. The XLA form rounds the normed value to
the compute dtype before it is turned: here it is not (finer, never
coarser).

**Which runs** is ``fits``'s to say from what the call can observe (a
TPU backend, operands on one device, heads of whole lanes, a rotated
span inside one vector of lanes or of whole vectors), never a name or a
knob; ``head_norm_rotary`` is the one function the attention layers
call, and ``rotary_paths`` tallies what each call site took.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# (``jax.experimental.pallas`` is imported where a kernel is built, as in
# ``ops/pallas_short_conv.py``: a process that never takes the kernels
# does not pay its import)
from perceiver_tpu.ops.attention import head_rms_norm, mesh_devices
from perceiver_tpu.ops.fourier import rope_apply
from perceiver_tpu.ops.pallas_short_conv import _F32, _LANES, _by_head, _pad
from perceiver_tpu.ops.policy import DEFAULT_POLICY, Policy
from perceiver_tpu.ops.tally import Tally
from perceiver_tpu.ops.tiling import round_up

#: positions a tile (a shorter row is one tile), the widest tile of
#: channels, positions a walk inside a tile (read where a call is made
#: and handed to the jitted directions among their static arguments)
_POSITIONS, _CHANNELS, _ROWS = 1024, 512, 128

#: what the norm and the rotation took at each call site:
#: ``fused[32x128 norm+rot128]`` (the kernels: 32 heads of 128, normed,
#: 128 channels of each turned), ``fused[20x256 rot64@192]`` (64 from
#: channel 192 on), ``xla[1x64 rot64, shape]`` (XLA's operations, and
#: why not the kernels)
rotary_paths = Tally()


def _backend() -> str:
    """The backend ``fits`` reads (a seam: a test that says ``tpu``
    here gets the kernels, interpreted)."""
    return jax.default_backend()


def _span(head_dim: int, rotated: int, offset: int):
    """``(first lane, width)`` of the whole lanes of a head that hold
    its ``rotated`` channels from ``offset`` on; a width of 0 where
    they neither lie inside one vector of lanes nor fill whole ones."""
    start = offset - offset % _LANES
    width = round_up(offset - start + rotated, _LANES)
    if rotated % 2 or offset + rotated > head_dim \
            or width not in (_LANES, rotated):
        return start, 0
    return start, width


def head_tile(widest: int, heads: int, head_dim: int, first: int,
              stride: int) -> int:
    """Channels a tile: the most whole heads up to ``widest`` channels
    that divide the ``heads`` and where they start in ``x``; one head
    where the heads lie apart; 0 where there is none."""
    if stride != head_dim:
        return 0 if first % head_dim or stride % head_dim else head_dim
    tile = max(widest // head_dim, 1)
    while tile > 1 and (heads % tile or first % (tile * head_dim)):
        tile -= 1
    return 0 if first % (tile * head_dim) else tile * head_dim


def fits(x, heads: int, head_dim: int, rotated: int = 0, offset: int = 0,
         first: int = 0, stride: int = 0) -> str:
    """Why a call does not take the kernels (``backend``, ``mesh``,
    ``dtype``, ``shape``), or ``""`` where it does: a TPU backend,
    operands on one device (a Pallas call has no partitioning rule),
    bfloat16 or float32, heads of whole lanes that tile where they lie
    in ``x`` (``heads`` of ``head_dim`` channels from ``first`` on, each
    ``stride`` after the last), and the ``rotated`` channels from
    ``offset`` on inside one vector of lanes or filling whole ones."""
    if _backend() != "tpu":
        return "backend"
    if mesh_devices(x) != 1:
        return "mesh"
    if x.dtype not in (jnp.bfloat16, jnp.float32):
        return "dtype"
    stride = stride or head_dim
    if x.ndim != 3 or heads < 1 or head_dim % _LANES or stride < head_dim \
            or first + (heads - 1) * stride + head_dim > x.shape[-1] \
            or not head_tile(_CHANNELS, heads, head_dim, first, stride) \
            or (rotated and not _span(head_dim, rotated, offset)[1]):
        return "shape"
    return ""


# --- what a walk computes ----------------------------------------------------


def _turn(x, tables, start: int, half: int):
    """A head's lanes (n, D) float32 with its span turned: ``tables``
    ``(cos, down, up)`` or, where one rotation of the lanes brings both
    partners, ``(cos, both)``, each (n, span)."""
    from jax.experimental.pallas import tpu as pltpu

    width = tables[0].shape[1]
    span = x if width == x.shape[1] else x[:, start:start + width]
    out = span * tables[0] + pltpu.roll(span, half, 1) * tables[1]
    if len(tables) == 3:
        out = out + pltpu.roll(span, width - half, 1) * tables[2]
    if width == x.shape[1]:
        return out
    return jnp.concatenate(
        [part for part in (x[:, :start], out, x[:, start + width:])
         if part.shape[1]], axis=1)


def _rstd(x, eps: float):
    return jax.lax.rsqrt(
        jnp.sum(x * x, axis=1, keepdims=True) * (1.0 / x.shape[1]) + eps)


def _tables_of(ref, start, rows: int, width: int):
    """The walk's rows of the tables, which lie side by side."""
    from jax.experimental import pallas as pl

    both = ref[pl.ds(start, rows), :]
    return [both[:, n:n + width] for n in range(0, both.shape[1], width)]


def _operands(refs, norm: bool, turned: bool):
    refs = list(refs)
    return (refs.pop(0) if norm else None,
            refs.pop(0) if turned else None, refs)


# --- the kernels -------------------------------------------------------------


def _forward_kernel(x_ref, *refs, head, norm, span, half, eps, rows):
    from jax.experimental import pallas as pl

    scale_ref, table_ref, (y_ref,) = _operands(refs, norm, bool(span[1]))
    scale = scale_ref[...] if norm else None

    def walk(r, _):
        at = pl.multiple_of(r * rows, rows)
        tables = span[1] and _tables_of(table_ref, at, rows, span[1])

        def one(x):
            if norm:
                x = x * _rstd(x, eps) * scale
            return _turn(x, tables, span[0], half) if span[1] else x

        y_ref[0, pl.ds(at, rows), :] = _by_head(
            one, head, x_ref[0, pl.ds(at, rows), :].astype(_F32)
        ).astype(y_ref.dtype)
        return _

    jax.lax.fori_loop(0, x_ref.shape[1] // rows, walk, 0)


def _backward_kernel(x_ref, ct_ref, *refs, head, norm, span, half, eps,
                     rows):
    from jax.experimental import pallas as pl

    scale_ref, table_ref, outs = _operands(refs, norm, bool(span[1]))
    dx_ref = outs[0]
    scale = scale_ref[...] if norm else None

    def by_sublane(v):     # (rows, D) -> (8, D), a sum a sublane
        return functools.reduce(
            jnp.add, [v[n:n + 8] for n in range(0, rows, 8)])

    def walk(r, sums):
        at = pl.multiple_of(r * rows, rows)
        tables = span[1] and _tables_of(table_ref, at, rows, span[1])
        new = []          # a head's share of the scale's gradient

        def one(x, ct):
            if span[1]:
                ct = _turn(ct, tables, span[0], half)
            if not norm:
                return ct
            rstd = _rstd(x, eps)
            unit = x * rstd
            new.append(by_sublane(ct * unit))
            ct = ct * scale
            along = jnp.sum(ct * unit, axis=1, keepdims=True)
            return (ct - unit * (along * (1.0 / head))) * rstd

        dx_ref[0, pl.ds(at, rows), :] = _by_head(
            one, head, x_ref[0, pl.ds(at, rows), :].astype(_F32),
            ct_ref[0, pl.ds(at, rows), :].astype(_F32)).astype(dx_ref.dtype)
        return functools.reduce(jnp.add, new, sums)

    sums = jax.lax.fori_loop(
        0, x_ref.shape[1] // rows, walk,
        jnp.zeros((8, head), _F32) if norm else 0)
    if norm:
        outs[1][0, 0] = sums


# --- the calls ---------------------------------------------------------------


def _position_tile(seq: int, positions: int, walk: int):
    """``(positions a tile, padded positions, positions a walk)``:
    ``positions`` a tile, or the whole of a shorter row in whole walks
    of whole sublanes of a bf16 block."""
    walk = min(walk, round_up(seq, 16))
    tile = round_up(min(positions, seq), walk)
    return tile, round_up(seq, tile), walk


def _span_tables(tables, seq: int, padded: int, head_dim: int, offset: int,
                 back: bool):
    """``(first lane, width, R / 2, tables)``: the span of a head's
    lanes that the tables ``(cos, sin)`` of (L', R) turn, and the
    tables as the kernels read them, (padded, 2 or 3 spans) float32:
    ``cos`` (1 outside the ``R`` channels), then what multiplies the
    span rolled ``R / 2`` lanes up and, where the span is wider than
    the channels, ``R / 2`` down (0 outside them); ``back``: those of
    the transpose, the same rotations of the cotangent's lanes."""
    if tables is None:
        return 0, 0, 0, None
    cos, sin = (jnp.asarray(t, _F32)[:seq] for t in tables)
    rotated = cos.shape[1]
    half = rotated // 2
    start, width = _span(head_dim, rotated, offset)
    lanes = (offset - start, width - rotated - (offset - start))

    def spread(table, fill=0.0):
        return jnp.pad(table, ((0, padded - seq), lanes),
                       constant_values=fill)

    zeros = jnp.zeros_like(sin[:, :half])
    # ``down`` meets the lanes rolled up by R/2 (channel j - R/2 at j:
    # the second halves'), ``up`` those rolled the other way
    down = spread(jnp.concatenate([zeros, sin[:, half:]], axis=1))
    up = spread(jnp.concatenate([-sin[:, :half], zeros], axis=1))
    if back:
        down, up = jnp.roll(up, half, 1), jnp.roll(down, -half, 1)
    parts = [spread(cos, 1.0), down + up] if width == rotated \
        else [spread(cos, 1.0), down, up]
    return start, width, half, jnp.concatenate(parts, axis=1)


def _call(kernel, name: str, interpret: bool, **static):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def call(**shapes):
        return pl.pallas_call(
            functools.partial(kernel, **static), **shapes,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3,
                vmem_limit_bytes=64 * 1024 * 1024),
            interpret=interpret, name=name)

    return pl, call


def _plan(x, scale, tables, heads, head_dim, offset, first, stride, tiles,
          back):
    """What both directions share: the padded ``x``, the grid, the
    block specs of ``x`` where it lies, of a direction's own arrays, of
    the scale and the tables, and the kernels' static arguments."""
    from jax.experimental import pallas as pl

    seq = x.shape[1]
    positions, widest, walk = tiles
    tile, padded, walk = _position_tile(seq, positions, walk)
    channels = head_tile(widest, heads, head_dim, first, stride)
    start, width, half, spans = _span_tables(
        tables, seq, padded, head_dim, offset, back)
    # block indices: the first tile's, and from a tile to the next (one
    # head a tile where the heads lie apart)
    at, step = first // channels, max(stride // channels, 1)
    specs = dict(
        here=pl.BlockSpec((1, tile, channels),
                          lambda i, j, b: (b, i, at + j * step)),
        own=pl.BlockSpec((1, tile, channels), lambda i, j, b: (b, i, j)))
    operands, operand_specs = [], []
    if scale is not None:
        operands.append(scale.astype(_F32).reshape(1, head_dim))
        operand_specs.append(
            pl.BlockSpec((1, head_dim), lambda i, j, b: (0, 0)))
    if spans is not None:
        operands.append(spans)
        operand_specs.append(
            pl.BlockSpec((tile, spans.shape[1]), lambda i, j, b: (i, 0)))
    static = dict(head=head_dim, norm=scale is not None,
                  span=(start, width), half=half, rows=walk)
    grid = (padded // tile, heads * head_dim // channels, x.shape[0])
    return _pad(x, padded), padded, grid, specs, operands, operand_specs, \
        static


@functools.partial(jax.jit, static_argnums=tuple(range(3, 11)))
def _rotary_forward(x, scale, tables, heads: int, head_dim: int, offset: int,
                    first: int, stride: int, eps: float, tiles,
                    interpret: bool):
    seq = x.shape[1]
    x, padded, grid, specs, operands, operand_specs, static = _plan(
        x, scale, tables, heads, head_dim, offset, first, stride, tiles,
        False)
    _, call = _call(_forward_kernel, "head_rotary_fwd", interpret, eps=eps,
                    **static)
    return call(
        grid=grid, in_specs=[specs["here"], *operand_specs],
        out_specs=specs["own"],
        out_shape=jax.ShapeDtypeStruct(
            (x.shape[0], padded, heads * head_dim), x.dtype)
    )(x, *operands)[:, :seq]


@functools.partial(jax.jit, static_argnums=tuple(range(4, 12)))
def _rotary_backward(x, scale, tables, ct, heads: int, head_dim: int,
                     offset: int, first: int, stride: int, eps: float, tiles,
                     interpret: bool):
    rows, seq, wide = x.shape
    x, padded, grid, specs, operands, operand_specs, static = _plan(
        x, scale, tables, heads, head_dim, offset, first, stride, tiles, True)
    pl, call = _call(_backward_kernel, "head_rotary_bwd", interpret, eps=eps,
                     **static)
    shape = jax.ShapeDtypeStruct((rows, padded, heads * head_dim), x.dtype)
    out_specs, out_shape = [specs["own"]], [shape]
    if scale is not None:
        out_specs.append(pl.BlockSpec((1, 1, 8, head_dim),
                                      lambda i, j, b: (i, j, b, 0)))
        out_shape.append(jax.ShapeDtypeStruct(
            (*grid[:2], rows * 8, head_dim), _F32))
    dx, *sums = call(
        grid=grid, in_specs=[specs["here"], specs["own"], *operand_specs],
        out_specs=out_specs, out_shape=out_shape
    )(x, _pad(ct.astype(x.dtype), padded), *operands)
    dx = dx[:, :seq]
    if stride != head_dim:    # the channels between the heads: no gradient
        dx = jnp.pad(dx.reshape(rows, seq, heads, head_dim), (
            (0, 0), (0, 0), (0, 0), (0, stride - head_dim))).reshape(
                rows, seq, -1)
    if wide > dx.shape[-1]:   # the channels of ``x`` beside the heads': none
        dx = jnp.pad(dx, ((0, 0), (0, 0),
                          (first, wide - first - dx.shape[-1])))
    return dx, sums[0].sum((0, 1, 2)).astype(scale.dtype) if sums else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotary(x, scale, tables, static):
    return _rotary_forward(x, scale, tables, *static)


def _rotary_fwd(x, scale, tables, static):
    return _rotary_forward(x, scale, tables, *static), (x, scale, tables)


def _rotary_bwd(static, operands, ct):
    dx, dscale = _rotary_backward(*operands, ct, *static)
    return dx, dscale, None


_rotary.defvjp(_rotary_fwd, _rotary_bwd)


def fused_head_rotary(x, heads: int, head_dim: int, *, scale=None,
                      eps: float = 1e-6, rope=None, offset: int = 0,
                      first: int = 0, stride: int = 0, interpret=None):
    """The kernels on the ``heads`` heads of ``head_dim`` channels of
    ``x`` (B, L, .) from channel ``first`` on, each ``stride`` channels
    after the last (0: side by side), read where they lie: normed under
    ``scale`` (``head_dim``,) where it is given, then turned by
    ``rope``'s tables where they are, from a head's channel ``offset``
    on. (B, L, heads x head_dim) in ``x``'s dtype comes back. What
    ``head_norm_rotary`` runs where ``fits`` lets it; a test calls it
    outright."""
    from perceiver_tpu.utils.platform import resolve_interpret

    return _rotary(x, scale, rope and tuple(rope), (
        int(heads), int(head_dim), int(offset), int(first),
        int(stride or head_dim), float(eps), (_POSITIONS, _CHANNELS, _ROWS),
        resolve_interpret(interpret)))


def norm_scale(params):
    """The factor of a norm's tree, float32: its ``scale`` or, where it
    is zero-centred, ``1 + bias`` (``ops/norm.rms_norm_apply``'s)."""
    return params["scale"].astype(_F32) if "scale" in params \
        else 1.0 + params["bias"].astype(_F32)


def head_norm_rotary(x, num_heads: int, *, norm=None, eps: float = 1e-6,
                     rope=None, offset: int = 0,
                     policy: Policy = DEFAULT_POLICY, cut_from=None):
    """``x`` (B, L, H x D), heads side by side: an RMSNorm over each
    head's channels under ``norm``'s tree (``head_rms_norm``) where one
    is given, then ``rope``'s tables ``(cos, sin)`` of (L', R) turning
    ``R`` channels of each head from ``offset`` on (``rope_apply``; at
    an offset, the split, the rotation of the tail and the
    concatenation latent attention's queries had) where they are.
    ``cut_from``: ``(array, first channel, stride)`` where ``x`` is a
    caller's slice of a wider array (a packed projection's product; a
    head's query beside its gate, ``stride`` channels from one head's
    first to the next's): the kernels read the channels there and the
    slice is never made.

    The kernels where ``fits`` says so; elsewhere XLA's operations as
    the layers always had them, the same lowered text; ``rotary_paths``
    counts which."""
    head_dim = x.shape[-1] // num_heads
    rotated = 0 if rope is None else rope[0].shape[-1]
    source, first, stride = cut_from or (x, 0, head_dim)
    why = fits(source, num_heads, head_dim, rotated, offset, first, stride)
    if not why and norm is not None and x.dtype != policy.compute_dtype:
        why = "dtype"    # the norm's result is the policy's, not ``x``'s
    if not why and rotated and rope[0].shape[0] < x.shape[1]:
        why = "shape"    # fewer rows than positions: XLA's to refuse
    rotary_paths.add(
        f"{'xla' if why else 'fused'}[{num_heads}x{head_dim} "
        + "+".join(filter(None, (
            norm is not None and "norm",
            rotated and f"rot{rotated}" + (f"@{offset}" if offset else ""))))
        + (f", {why}" if why else "") + "]")
    if not why:
        return fused_head_rotary(
            source, num_heads, head_dim,
            scale=None if norm is None else norm_scale(norm), eps=eps,
            rope=rope, offset=offset, first=first, stride=stride)
    if norm is not None:
        x = head_rms_norm(norm, x, num_heads, eps, policy)
    if rope is None:
        return x
    if not offset:
        return rope_apply(x, *rope, num_heads)
    rows, seq, _ = x.shape
    still, turning = jnp.split(x.reshape(rows, seq, num_heads, -1),
                               [offset], axis=-1)
    turned = rope_apply(turning.reshape(rows, seq, -1), *rope, num_heads)
    return jnp.concatenate(
        [still, turned.reshape(turning.shape)], -1).reshape(x.shape)
