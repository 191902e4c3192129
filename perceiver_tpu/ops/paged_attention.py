"""Ragged paged attention: per-row (kv_len, query_len) over a shared
KV page pool (PAPERS: "Ragged Paged Attention").

Autoregressive decode keeps one KV cache entry per *consumed* token.
A rectangle per stream — ``(R, max_seq, H, Dh)`` — wastes HBM on
every stream shorter than the longest and fragments nothing-shaped
holes when streams leave mid-flight. The paged layout instead shares
one fixed pool of ``num_pages`` blocks of ``page_size`` tokens::

    k_pages, v_pages : (num_pages, page_size, H, Dh)   the shared pool
    page_tables      : (R, pages_per_stream) int32     logical→physical
    kv_lens          : (R,) int32                      tokens cached
    query_lens       : (R,) int32                      queries this step

Stream ``r``'s token ``t`` lives at physical page
``page_tables[r, t // page_size]``, slot ``t % page_size`` — so a
host-side allocator can hand any free page to any stream and recycle
freed pages without moving a byte (``serving/decode.PagePool``).

:func:`ragged_paged_attention` is the Pallas kernel family's entry:
grid ``(R, H, pages_per_stream)``, page table + both length vectors
ride scalar prefetch so the kv index map walks **only request r's own
page list**; steps past ``ceil(kv_len / page_size)`` replay the
clamped last page, which the pipeline elides, and compute under them
is predicated off. Rows are *ragged on both axes*: a chunked-prefill
row brings ``query_len > 1`` fresh queries, a decode row exactly one
— both execute in the same call, which is what lets the unified
serving step (``serving/decode.py``) run mixed prefill + decode
traffic through ONE compiled executable. ``causal=True`` aligns the
windows right: query ``i`` of row ``r`` attends kv positions
``< kv_lens[r] - (query_lens[r] - 1 - i)`` (the last query sees the
whole cache, earlier chunk queries see one token less each).
Perceiver latent rebuilds use the non-causal mode (latents attend
every cached token). Query rows past ``query_lens[r]`` and rows with
empty windows return exact zeros.

Online softmax shares its body with the flash and ragged kernels
(``ops/online_softmax.py``). Accumulation order is the logical page
order, independent of physical placement — so two placements of the
same stream (contiguous vs scrambled) produce **bitwise identical**
outputs, the property the decode parity tests pin.

Layout note: the kernel wants the token axis on the sublane dim, so
the wrapper relayouts pages to ``(P, H, page_size, Dp)`` (one
transpose + lane pad per call). The pools here are small — tens of
KiB for the canonical configs — so this stays cheap and O(1) per
step; a production TPU build would allocate the pool in kernel
layout directly and skip the copy.

:func:`ragged_paged_attention_reference` is the pure-jax gather
reference; it uses ``lax.select`` (never ``jnp.where``) because the
sharded decode serve graph lowers it, and jnp.where's jitted wrapper
makes module text drift with process history (see
serving/graphs.py).

:func:`paged_decode_attention` / ``_reference`` are kept as thin
decode-shaped delegates (all queries valid, non-causal) so existing
call sites and the engine's latent rebuild exercise the ragged code
path in production.

Both run in Pallas interpreter mode on non-TPU backends, so CPU
tests exercise the identical code path.
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


def _ragged_paged_kernel(tables_ref, kv_lens_ref, q_lens_ref, q_ref,
                         k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                         scale: float, page_size: int, n_steps: int,
                         nqp: int, causal: bool):
    r = pl.program_id(0)
    j = pl.program_id(2)
    kv_len = kv_lens_ref[r]

    @pl.when(j == 0)
    def _():
        online_softmax_init(m_ref, l_ref, acc_ref)

    # steps past the row's used pages replay the clamped last page
    # (see kv index map) — skip them; zero-length rows do no work and
    # finish with exact-zero outputs. The causal window of the LAST
    # query is the full cache, so kv_len bounds both modes.
    @pl.when(j * page_size < kv_len)
    def _():
        q = q_ref[0, 0]        # (Nqp, Dp)
        kblk = k_ref[0, 0]     # (page_size, Dp)
        vblk = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        col = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (nqp, page_size), 1)
        if causal:
            # query i sees kv positions < kv_len - (q_len - 1 - i):
            # chunk queries are the cache's newest tokens, so earlier
            # ones must not see their successors. Padding rows
            # (i >= q_len) get windows past kv_len — garbage there is
            # finite and the wrapper zeroes those rows.
            qi = jax.lax.broadcasted_iota(
                jnp.int32, (nqp, page_size), 0)
            limit = kv_len - (q_lens_ref[r] - 1 - qi)
        else:
            limit = kv_len
        s = s + jnp.where(col < limit, 0.0, NEG_INF)
        online_softmax_update(s, vblk, m_ref, l_ref, acc_ref)

    @pl.when(j == n_steps - 1)
    def _():
        o_ref[0, 0] = online_softmax_finish(
            m_ref, l_ref, acc_ref).astype(o_ref.dtype)


def ragged_paged_attention(q, k_pages, v_pages, page_tables, kv_lens,
                           query_lens=None, *, causal: bool = False,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """Attention of per-row ragged queries over a paged KV pool.

    q: (R, H, Nq, D) queries — row ``r``'s first ``query_lens[r]``
    query rows are live, the rest are padding; k_pages/v_pages:
    (num_pages, page_size, H, D) shared pool; page_tables:
    (R, pages_per_stream) int32; kv_lens: (R,) int32 — row r attends
    its first ``kv_lens[r]`` cached tokens, walked through its own
    page list. ``query_lens=None`` means every query row is live
    (the decode latent-rebuild shape). ``causal=True`` right-aligns
    the windows: query ``i`` sees kv positions
    ``< kv_lens[r] - (query_lens[r] - 1 - i)``. Table entries beyond
    the used pages may be arbitrary (clamped, never contribute).
    Padding query rows, rows with ``kv_lens[r] == 0``, and causal
    queries with empty windows return exact zeros. Returns
    (R, H, Nq, D) in q's dtype.
    """
    interpret = resolve_interpret(interpret)
    r, h, nq, d = q.shape
    num_pages, page_size = k_pages.shape[:2]
    pps = page_tables.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    dp = _round_up(d, 128)
    nqp = _round_up(nq, 16)
    kv_lens = kv_lens.astype(jnp.int32)
    qlens = (jnp.full((r,), nq, jnp.int32) if query_lens is None
             else query_lens.astype(jnp.int32))

    qp = jnp.pad(q, ((0, 0), (0, 0), (0, nqp - nq), (0, dp - d)))
    # pool → kernel layout (P, H, page_size, Dp): token axis on the
    # sublane dim, head axis blockable at size 1 (see module docstring)
    kp = jnp.pad(jnp.transpose(k_pages, (0, 2, 1, 3)),
                 ((0, 0), (0, 0), (0, 0), (0, dp - d)))
    vp = jnp.pad(jnp.transpose(v_pages, (0, 2, 1, 3)),
                 ((0, 0), (0, 0), (0, 0), (0, dp - d)))

    def kv_index(rr, hh, j, tables, lens, qls):
        # clamp to the last used page: replayed blocks are elided by
        # the pipeline, and compute under them is predicated off
        used = jnp.maximum(
            (lens[rr] + page_size - 1) // page_size, 1)
        jj = jnp.minimum(j, used - 1)
        page = jnp.clip(tables[rr, jj], 0, num_pages - 1)
        return (page, hh, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(r, h, pps),
        in_specs=[
            pl.BlockSpec(
                (1, 1, nqp, dp),
                lambda rr, hh, j, tables, lens, qls: (rr, hh, 0, 0)),
            pl.BlockSpec((1, 1, page_size, dp), kv_index),
            pl.BlockSpec((1, 1, page_size, dp), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, nqp, dp),
            lambda rr, hh, j, tables, lens, qls: (rr, hh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nqp, 128), jnp.float32),
            pltpu.VMEM((nqp, 128), jnp.float32),
            pltpu.VMEM((nqp, dp), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_paged_kernel, scale=float(scale),
                          page_size=page_size, n_steps=pps, nqp=nqp,
                          causal=bool(causal)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, h, nqp, dp), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(page_tables.astype(jnp.int32), kv_lens, qlens, qp, kp, vp)
    out = out[:, :, :nq, :d]
    return _zero_invalid_queries(out, kv_lens, qlens, causal)


def _zero_invalid_queries(out, kv_lens, qlens, causal: bool):
    """Exact zeros for padding query rows and empty attention windows
    — those rows accumulate finite garbage in the kernel (NEG_INF is
    finite by design, so fully-masked score blocks never NaN)."""
    r, _, nq, _ = out.shape
    qi = jnp.arange(nq, dtype=jnp.int32)
    if causal:
        limit = kv_lens[:, None] - (qlens[:, None] - 1 - qi[None, :])
    else:
        limit = jnp.broadcast_to(kv_lens[:, None], (r, nq))
    valid = (qi[None, :] < qlens[:, None]) & (limit > 0)
    return jax.lax.select(
        jnp.broadcast_to(valid[:, None, :, None], out.shape),
        out, jnp.zeros_like(out))


def ragged_paged_attention_reference(q, k_pages, v_pages, page_tables,
                                     kv_lens, query_lens=None, *,
                                     causal: bool = False,
                                     scale: Optional[float] = None):
    """Pure-jax reference for :func:`ragged_paged_attention`.

    Gathers each row's pages into a dense (R, pps·page_size, H, D)
    view and runs masked fp32 attention. This is also the impl the
    sharded (dp2×tp2) decode target lowers — GSPMD partitions gathers
    and einsums, not Pallas calls — hence ``lax.select`` throughout.
    """
    r, h, nq, d = q.shape
    num_pages, page_size = k_pages.shape[:2]
    pps = page_tables.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    kv_lens = kv_lens.astype(jnp.int32)
    qlens = (jnp.full((r,), nq, jnp.int32) if query_lens is None
             else query_lens.astype(jnp.int32))
    tables = jnp.clip(page_tables.astype(jnp.int32), 0, num_pages - 1)
    k = jnp.take(k_pages, tables.reshape(-1), axis=0).reshape(
        r, pps * page_size, k_pages.shape[2], d)
    v = jnp.take(v_pages, tables.reshape(-1), axis=0).reshape(
        r, pps * page_size, v_pages.shape[2], d)
    col = jnp.arange(pps * page_size, dtype=jnp.int32)
    qi = jnp.arange(nq, dtype=jnp.int32)
    if causal:
        limit = kv_lens[:, None] - (qlens[:, None] - 1 - qi[None, :])
    else:
        limit = jnp.broadcast_to(kv_lens[:, None], (r, nq))
    mask = col[None, None, :] < limit[:, :, None]      # (R, Nq, T)
    logits = jnp.einsum("rhnd,rthd->rhnt", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = jax.lax.select(
        jnp.broadcast_to(mask[:, None, :, :], logits.shape),
        logits, jnp.full_like(logits, NEG_INF))
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("rhnt,rthd->rhnd", probs, v.astype(jnp.float32))
    valid = (qi[None, :] < qlens[:, None]) & (limit > 0)
    out = jax.lax.select(
        jnp.broadcast_to(valid[:, None, :, None], out.shape),
        out, jnp.zeros_like(out))
    return out.astype(q.dtype)


def tile_for_windows(page_tables, kv_lens, windows: int):
    """Tile ragged-paged operands so each row scores ``windows``
    right-aligned KV prefixes in one kernel call.

    Row ``r`` of the input becomes rows ``r*windows .. r*windows +
    windows - 1`` of the output: window ``j`` replays row r's own page
    walk against its first ``max(kv_lens[r] - (windows - 1 - j), 0)``
    cached tokens, so window ``windows - 1`` sees the full cache (the
    plain decode view) and window ``j`` hides the newest
    ``windows - 1 - j`` tokens. Speculative verify
    (serving/decode.py) is the consumer: after scattering a stream's
    feedback token plus ``k`` drafted tokens in one chunk, the
    target's prediction *at* drafted position ``i`` is exactly the
    full-cache view minus the drafts from ``i`` on — so one ragged
    call over the tiled rows scores every drafted position of every
    stream. No pages are copied: only the table rows repeat and the
    length vector fans out. Returns ``(page_tables, kv_lens)`` shaped
    ``(R*windows, pages_per_stream)`` / ``(R*windows,)``.
    """
    if windows < 1:
        raise ValueError(f"windows must be >= 1, got {windows}")
    r = page_tables.shape[0]
    tables = jnp.repeat(page_tables, windows, axis=0)
    back = jnp.arange(windows - 1, -1, -1, dtype=jnp.int32)
    lens = jnp.maximum(
        kv_lens.astype(jnp.int32)[:, None] - back[None, :], 0)
    return tables, lens.reshape(r * windows)


def paged_decode_attention(q, k_pages, v_pages, page_tables, lengths, *,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """Decode attention over a paged KV pool — the decode-shaped
    delegate of :func:`ragged_paged_attention` (every query row live,
    non-causal): q's Nq axis is the latent axis of the rebuild, all
    latents attend row r's first ``lengths[r]`` cached tokens."""
    return ragged_paged_attention(
        q, k_pages, v_pages, page_tables, lengths,
        scale=scale, interpret=interpret)


def paged_decode_attention_reference(q, k_pages, v_pages, page_tables,
                                     lengths, *,
                                     scale: Optional[float] = None):
    """Pure-jax reference for :func:`paged_decode_attention` — the
    decode-shaped delegate of
    :func:`ragged_paged_attention_reference`."""
    return ragged_paged_attention_reference(
        q, k_pages, v_pages, page_tables, lengths, scale=scale)
