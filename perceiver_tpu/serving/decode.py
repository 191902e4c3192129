"""Autoregressive decode: one AOT-compiled step over a paged KV pool.

Every other serving path in the repo is single-shot encode→decode;
this module adds the streaming scenario (ROADMAP item 2) with the
perf shape as the contract: **per-token cost is O(1) in generated
length**, because each step re-reads a fixed-shape donated carry
instead of re-encoding the growing prefix.

The carry — donated to the step executable and re-donated every
step — is::

    {"kv": {"k1","v1"[,"kn","vn"]}   (num_pages, page_size, H, Dh)
     "lengths":     (R,) int32        tokens cached per stream slot
     "page_tables": (R, PPS) int32    logical→physical page map}

``k1/v1`` cache the *encoder cross-attention K/V projections* of
each consumed token for the unshared first layer; ``kn/vn`` for the
weight-shared ``layer_n`` (only when ``num_layers > 1``). That is
the whole loop-carried state of a Perceiver-IO decode: latents are
cheap (N×C per stream) and recomputed from the pools each step,
which keeps the cache *per-token* and therefore pageable — the same
block machinery as the ragged serve path (PAPERS: "Ragged Paged
Attention"; the stepped-executable framing follows "Compiler-First
State Space Duality and Portable O(1) Autoregressive Caching").

One step consumes a per-row *ragged chunk* of tokens — up to
``max_chunk`` prompt tokens for a prefilling row, exactly one for a
decoding row, zero for an idle slot — and emits the model's
prediction for each row's next position:

1. embed ``tokens[r, :qlens[r]]`` at positions ``lengths[r] + j``;
2. project each chunk token's K/V per kv set and scatter into the
   pools at ``(page_tables[r, pos // page_size], pos % page_size)``
   — invalid lanes are redirected to the reserved trash page 0;
3. rebuild latents ONCE per step: ``layer_1`` + scanned ``layer_n``,
   each cross-attending the pools through the ragged paged kernel
   (:func:`~perceiver_tpu.ops.paged_attention.paged_decode_attention`,
   the decode-shaped delegate of ``ragged_paged_attention``) at
   per-row ``kv_len = lengths[r] + qlens[r]``;
4. decode one query row at position ``lengths[r] + qlens[r]`` →
   vocab logits → greedy ``next_token`` (+ top-k sidecar).

Chunked prefill therefore reuses the same executable: a stream's
prompt feeds through in ``max_chunk``-token slices co-scheduled with
in-flight decode rows under one per-step token budget
(``batcher.ContinuousBatchScheduler.plan_chunks``), so the engine
owns exactly ONE compiled signature, token N costs the same as token
1, and time-to-first-token collapses from one latent rebuild *per
prompt token* to one per chunk — ``tests/test_decode.py`` pins the
dispatches per token as a count, and zero compiles after warm-up.

``DecodeEngine`` drives the step host-side: a page allocator
(:class:`PagePool`), unified continuous batching (streams join and
leave mid-flight via ``batcher.ContinuousBatchScheduler`` — freed
pages recycle with no fragmentation because any page serves any
stream), per-stream token callbacks / blocking iterators, tracing
(``prefill_chunk`` / ``decode_step`` / ``token_emit`` spans), typed
events (``stream_open`` / ``stream_admitted`` / ``prefill_complete``
/ ``stream_close``), and metrics. Shedding follows the batcher
conventions: an over-capacity or expired request resolves to a typed
:class:`~perceiver_tpu.serving.batcher.Overloaded` value; a request
that can *never* fit the geometry raises
:class:`~perceiver_tpu.serving.engine.RequestTooLarge` at submit.

Unlike ``serving/engine.py`` (sync-free by lint), this module is a
consumer layer: it owns the one deliberate device sync per step
(materializing ``next_token``), exactly like ``serving/api.py``.
"""

from __future__ import annotations

import dataclasses
import queue as queue_mod
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from perceiver_tpu.cache import aot_compile
from perceiver_tpu.obs import events as events_mod
from perceiver_tpu.obs import trace as trace_mod
from perceiver_tpu.ops.policy import Policy, DEFAULT_POLICY
from perceiver_tpu.serving.batcher import (
    ContinuousBatchScheduler,
    Overloaded,
)
from perceiver_tpu.serving.engine import (
    RequestTooLarge,
    resolve_exec_cache,
)
from perceiver_tpu.serving.errors import BatchError, Unavailable
from perceiver_tpu.serving.metrics import MetricsRegistry, PagePoolGauges
from perceiver_tpu.serving.prefix_cache import (
    PrefixCacheConfig,
    PrefixIndex,
    ensure_private_page,
)
from perceiver_tpu.serving.speculative import (
    SpeculativeConfig,
    greedy_accept,
)
from perceiver_tpu.serving.tenancy import (
    DEFAULT_TENANT,
    TenantRegistry,
    TenantSpec,
)


@dataclasses.dataclass(frozen=True)
class DecodeGeometry:
    """The fixed shape of one decode executable: stream slots × paged
    pool. Everything the step compiles against derives from here, so
    the exec-cache key forks on any change (tests/test_exec_cache.py
    pins the pages × page_size fork)."""

    max_streams: int
    num_pages: int          # includes the reserved trash page 0
    page_size: int
    max_seq_len: int        # cap on prompt + generated (position table)
    top_k: int = 3
    max_chunk: int = 8      # prompt tokens one prefill chunk may carry
    spec_k: int = 0         # drafted tokens verified per step (0 = off)

    def __post_init__(self):
        if self.max_streams < 1:
            raise ValueError(f"max_streams must be >= 1, got "
                             f"{self.max_streams}")
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got "
                             f"{self.page_size}")
        if self.num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is the reserved trash "
                f"page), got {self.num_pages}")
        if self.max_seq_len < 1:
            raise ValueError(f"max_seq_len must be >= 1, got "
                             f"{self.max_seq_len}")
        if not 1 <= self.max_chunk <= self.max_seq_len:
            raise ValueError(
                f"max_chunk must be in [1, max_seq_len], got "
                f"{self.max_chunk}")
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {self.spec_k}")
        if self.spec_k and self.spec_k + 1 > self.max_chunk:
            raise ValueError(
                f"spec_k {self.spec_k} needs {self.spec_k + 1} chunk "
                f"lanes (feedback + drafts) but max_chunk is "
                f"{self.max_chunk}")

    @property
    def pages_per_stream(self) -> int:
        """Page-table width: enough pages to reach ``max_seq_len``."""
        return -(-self.max_seq_len // self.page_size)

    @property
    def allocatable_pages(self) -> int:
        return self.num_pages - 1

    def pages_for(self, cached_tokens: int) -> int:
        """Pages a stream holding ``cached_tokens`` KV entries needs."""
        return max(1, -(-cached_tokens // self.page_size))

    @property
    def descriptor(self) -> str:
        # spec_k suffixes only when speculation is compiled in, so
        # every pre-existing exec-cache key (and every pinned budget
        # keyed on the descriptor) is byte-identical at spec_k == 0
        base = (f"r{self.max_streams}_p{self.num_pages}x{self.page_size}"
                f"_s{self.max_seq_len}_q{self.max_chunk}")
        return f"{base}_k{self.spec_k}" if self.spec_k else base


class PagePool:
    """Host-side refcounted free-list allocator over page indices.

    Page 0 is reserved (the trash page inactive slots scatter into)
    and never handed out. Any free page serves any stream, so recycle
    never fragments: ``free`` simply pushes pages back on the list.
    Pages carry a reference count so immutable prefix pages can be
    shared across streams (serving/prefix_cache.py): ``alloc`` hands
    out pages at refcount 1, ``incref`` adds a holder, and ``free`` is
    a decref that only returns the page to the free list when the last
    holder lets go. The allocated map is tracked to make double-free /
    aliasing bugs loud instead of silently corrupting a neighbour
    stream's cache.
    """

    # externally guarded: a PagePool has no lock of its own — every
    # alloc/free happens inside the owning engine's critical sections
    # (racecheck validates the declaration; the owner's _GUARDED
    # registry covers the call sites)
    _GUARDED_BY = "DecodeEngine._lock"

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 reserved)")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        # LIFO: pop() returns low indices first, so fresh allocations
        # reuse just-freed pages (cache-friendly, and makes the
        # recycle tests deterministic)
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._refs: Dict[int, int] = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def allocated_pages(self) -> int:
        return len(self._refs)

    @property
    def _allocated(self) -> set:
        """Allocated page-id view (kept for tests / introspection)."""
        return set(self._refs)

    def refcount(self, page: int) -> int:
        """Holders of ``page`` (0 when the page is on the free list)."""
        return self._refs.get(page, 0)

    def alloc(self, n: int) -> List[int]:
        if n < 1:
            raise ValueError(f"alloc of {n} pages")
        if n > len(self._free):
            raise ValueError(
                f"pool exhausted: {n} pages requested, "
                f"{len(self._free)} free")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def incref(self, pages: Sequence[int]) -> None:
        """Add one holder to each page (prefix sharing / publication)."""
        for p in pages:
            if p not in self._refs:
                raise ValueError(
                    f"incref of unallocated page {p} (allocated: "
                    f"{sorted(self._refs)})")
            self._refs[p] += 1

    def free(self, pages: Sequence[int]) -> None:
        """Drop one holder per page; recycle pages that hit zero."""
        for p in pages:
            if p not in self._refs:
                raise ValueError(
                    f"double-free or foreign page {p} (allocated: "
                    f"{sorted(self._refs)})")
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)


@dataclasses.dataclass(frozen=True)
class DecodeGraph:
    """The decode step plus everything needed to compile and carry it.

    ``fn(params, carry, tokens, qlens) -> (carry', outputs)`` with
    ``tokens (R, max_chunk) int32`` and ``qlens (R,) int32`` — row r
    consumes its first ``qlens[r]`` token lanes this step (1 for a
    decode row, up to ``max_chunk`` for a prefill chunk, 0 idle);
    ``carry`` is donate_argnums=(1,) — every leaf aliases an output
    (pools/lengths are updated in place, page_tables pass through),
    so the step's HBM high-water mark is ONE copy of the cache.
    """

    model: object
    fn: Callable
    geometry: DecodeGeometry
    policy: Policy
    pool_dtype: object
    num_kv_sets: int
    head_dim: int
    num_heads: int
    vocab_size: int
    donate_argnums: tuple = (1,)
    output_names: tuple = ("next_token", "topk_ids", "topk_scores")

    def init_params(self, seed: int = 0):
        import jax

        return self.model.init(jax.random.key(seed))

    def init_carry(self) -> Dict[str, object]:
        import jax.numpy as jnp

        g = self.geometry
        pool = (g.num_pages, g.page_size, self.num_heads, self.head_dim)
        kv = {}
        for name in (("k1", "v1") if self.num_kv_sets == 1
                     else ("k1", "v1", "kn", "vn")):
            kv[name] = jnp.zeros(pool, self.pool_dtype)
        return {
            "kv": kv,
            "lengths": jnp.zeros((g.max_streams,), jnp.int32),
            "page_tables": jnp.zeros(
                (g.max_streams, g.pages_per_stream), jnp.int32),
        }


def build_decode_graph(model, geometry: DecodeGeometry, *,
                       policy: Policy = DEFAULT_POLICY,
                       attn_impl: str = "pallas") -> DecodeGraph:
    """Build the decode step for a ``PerceiverMLM``-shaped model.

    ``attn_impl``: ``"pallas"`` is the production kernel (interpret
    mode on CPU); ``"reference"`` the pure-jax gather path — the
    sharded (dp2×tp2) canonical target lowers the reference because
    GSPMD partitions gathers/einsums, not Pallas calls.
    """
    import jax
    import jax.numpy as jnp

    from perceiver_tpu.models.perceiver import (
        cross_attention_layer_apply,
        self_attention_block_apply,
    )
    from perceiver_tpu.ops.attention import cross_attention_kv
    from perceiver_tpu.ops.linear import linear_apply
    from perceiver_tpu.ops.mlp import mlp_apply
    from perceiver_tpu.ops.norm import layer_norm_apply
    from perceiver_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_decode_attention_reference,
        tile_for_windows,
    )

    if attn_impl not in ("pallas", "reference"):
        raise ValueError(f"unknown attn_impl {attn_impl!r}")
    encoder, decoder = model.encoder, model.decoder
    n_lat, channels = encoder.latent_shape
    enc_heads = encoder.num_cross_attention_heads
    dec_heads = decoder.num_cross_attention_heads
    n_layers = encoder.num_layers
    model_max_seq = decoder.output_adapter.output_shape[0]
    if geometry.max_seq_len > model_max_seq:
        raise ValueError(
            f"geometry.max_seq_len {geometry.max_seq_len} exceeds the "
            f"model's position table {model_max_seq}")
    if channels % enc_heads:
        raise ValueError(
            f"channels {channels} not divisible by num heads {enc_heads}")
    head_dim = channels // enc_heads
    r = geometry.max_streams
    ps = geometry.page_size
    pps = geometry.pages_per_stream
    max_seq = geometry.max_seq_len
    pool_dtype = policy.compute_dtype
    vocab = decoder.output_adapter.num_classes \
        if hasattr(decoder.output_adapter, "num_classes") else None
    attn = (paged_decode_attention if attn_impl == "pallas"
            else paged_decode_attention_reference)
    q_chunk = geometry.max_chunk
    # speculative verify widens the latent rebuild to W = spec_k + 1
    # right-aligned KV windows per stream (spec_w == 1 is the plain
    # path, kept literally unchanged so its lowering — and with it the
    # exec-cache key and every pinned analysis budget — cannot drift)
    spec_w = geometry.spec_k + 1
    # flat-gather index base for the per-stream page lookup (static)
    row_base = jnp.arange(r, dtype=jnp.int32) * pps

    def fn(params, carry, tokens, qlens):
        enc_p = params["encoder"]
        lengths = carry["lengths"]
        tables = carry["page_tables"]
        offs = jnp.arange(q_chunk, dtype=jnp.int32)
        # lane j of row r lands at position lengths[r] + j; lanes past
        # qlens[r] are dead and redirect to the trash page below
        pos = jnp.clip(lengths[:, None] + offs[None, :],
                       0, max_seq - 1)                       # (R, Q)
        valid = offs[None, :] < qlens[:, None]               # (R, Q)

        # 1. embed every chunk lane at its in-stream position
        emb = encoder.input_adapter.apply_packed(
            enc_p["input_adapter"], tokens, pos,
            policy=policy)                                   # (R, Q, C)

        # 2. the O(chunk) cache update: scatter each lane's K/V into
        # its stream's page walk; dead lanes write the trash page.
        # Valid lanes never collide (positions are distinct per row,
        # pages distinct across rows), and the trash page is never
        # read back (reads are masked at kv_len), so duplicate dead
        # lanes are harmless.
        page = jnp.take(tables.reshape(-1),
                        (row_base[:, None] + pos // ps).reshape(-1))
        page = jax.lax.select(valid.reshape(-1), page,
                              jnp.zeros_like(page))          # (R*Q,)
        slot = (pos % ps).reshape(-1)

        def append(layer_params, kpool, vpool):
            kh, vh = cross_attention_kv(
                layer_params["cross"]["attn"], emb,
                policy=policy)                       # (R, Q, H*Dh)
            kh = kh.reshape(-1, enc_heads, head_dim)
            vh = vh.reshape(-1, enc_heads, head_dim)
            kpool = kpool.at[page, slot].set(kh.astype(kpool.dtype))
            vpool = vpool.at[page, slot].set(vh.astype(vpool.dtype))
            return kpool, vpool

        kv = dict(carry["kv"])
        kv["k1"], kv["v1"] = append(enc_p["layer_1"], kv["k1"], kv["v1"])
        if n_layers > 1:
            kv["kn"], kv["vn"] = append(enc_p["layer_n"],
                                        kv["kn"], kv["vn"])
        new_lengths = lengths + qlens.astype(lengths.dtype)

        # 3. latents from scratch over the paged pools — mirrors
        # serving/graphs._packed_encoder_apply with the ragged kernel
        # swapped for the paged one. Perceiver latents are NON-causal
        # over the cache, so speculative verify cannot reuse one
        # latent set for every drafted position: each of the W windows
        # gets its OWN latent rebuild against a right-aligned KV
        # prefix, folded into the kernel's row axis (no pages copied —
        # tile_for_windows repeats table rows and fans the lengths
        # out). Window W-1 sees the full cache, i.e. exactly the plain
        # decode view.
        if spec_w == 1:
            ver_tables, ver_lens, rows = tables, new_lengths, r
        else:
            ver_tables, ver_lens = tile_for_windows(
                tables, new_lengths, spec_w)
            rows = r * spec_w

        def one_layer(layer_params, kpool, vpool, lat):
            attn_p = layer_params["cross"]["attn"]
            xq = layer_norm_apply(attn_p["norm_q"], lat, policy=policy)
            qh = linear_apply(attn_p["mha"]["q"], xq, policy=policy)
            q = qh.reshape(rows, n_lat, enc_heads, head_dim).transpose(
                0, 2, 1, 3)
            o = attn(q, kpool, vpool, ver_tables, ver_lens,
                     scale=1.0 / (head_dim ** 0.5))
            o = o.transpose(0, 2, 1, 3).reshape(rows, n_lat,
                                                enc_heads * head_dim)
            o = linear_apply(attn_p["mha"]["out"], o, policy=policy)
            y = lat + o
            y = y + mlp_apply(layer_params["cross"]["mlp"], y,
                              policy=policy)
            return self_attention_block_apply(
                layer_params["selfs"], y,
                num_heads=encoder.num_self_attention_heads,
                policy=policy)

        latent = jnp.broadcast_to(
            policy.cast_param(enc_p["latent"])[None],
            (rows, n_lat, channels))
        latent = one_layer(enc_p["layer_1"], kv["k1"], kv["v1"], latent)
        if n_layers > 1:
            layer_n = enc_p["layer_n"]

            def body(c, _):
                return one_layer(layer_n, kv["kn"], kv["vn"],
                                 policy.cast_compute(c)), None

            latent, _ = jax.lax.scan(body, latent, None,
                                     length=n_layers - 1)

        # 4. decode ONE query row per (stream × window): the window's
        # next position — at spec_w == 1 this is the stream's next
        # position, the plain contract
        pd = params["decoder"]
        qpos = jnp.clip(ver_lens, 0, max_seq - 1)
        query = jnp.take(policy.cast_param(pd["query"]), qpos,
                         axis=0)[:, None, :]  # (rows, 1, C)
        hidden = cross_attention_layer_apply(
            pd["cross"], query, latent, num_heads=dec_heads,
            policy=policy)
        logits = linear_apply(pd["output_adapter"]["linear"], hidden,
                              policy=policy)[:, 0]  # (rows, V)
        carry_out = {"kv": kv, "lengths": new_lengths,
                     "page_tables": tables}
        if spec_w == 1:
            scores, topk_ids = jax.lax.top_k(
                logits.astype(jnp.float32), geometry.top_k)
            return carry_out, {
                "next_token": topk_ids[:, 0].astype(jnp.int32),
                "topk_ids": topk_ids.astype(jnp.int32),
                "topk_scores": scores,
            }
        # per-window greedy picks ride the same top_k op as the plain
        # path so tie-breaking is identical: spec_tokens[:, -1] is
        # bit-for-bit the next_token a non-speculative step yields
        logits32 = logits.astype(jnp.float32)
        _, ids_w = jax.lax.top_k(logits32, 1)
        spec_tokens = ids_w[:, 0].reshape(r, spec_w).astype(jnp.int32)
        last = logits32.reshape(r, spec_w, -1)[:, spec_w - 1]
        scores, topk_ids = jax.lax.top_k(last, geometry.top_k)
        return carry_out, {
            "next_token": topk_ids[:, 0].astype(jnp.int32),
            "topk_ids": topk_ids.astype(jnp.int32),
            "topk_scores": scores,
            "spec_tokens": spec_tokens,
        }

    output_names = ("next_token", "topk_ids", "topk_scores")
    if spec_w > 1:
        output_names += ("spec_tokens",)
    return DecodeGraph(
        model=model, fn=fn, geometry=geometry, policy=policy,
        pool_dtype=pool_dtype,
        num_kv_sets=1 if n_layers == 1 else 2,
        head_dim=head_dim, num_heads=enc_heads,
        vocab_size=vocab if vocab is not None else -1,
        output_names=output_names)


# --- streams -----------------------------------------------------------------

_SENTINEL = object()


@dataclasses.dataclass(frozen=True)
class DecodeResult:
    """One finished stream: generated ids + timing."""

    tokens: List[int]
    prompt_len: int
    finished: str                 # "complete" | "cancelled"
    ttft_s: Optional[float]
    cached_tokens: int = 0        # prompt span served from the prefix cache


class _Stream:
    """Engine-internal per-stream state (guarded by the engine lock)."""

    __slots__ = ("sid", "seq", "prompt", "max_new", "pages_needed",
                 "on_token", "ctx", "enqueued_at", "deadline", "slot",
                 "pages", "fed", "next_input", "generated", "tokens_q",
                 "done", "outcome", "error", "ttft_s", "submitted_at",
                 "prefill_chunks", "cached_tokens", "shared_pages",
                 "draft_pages", "draft_fed", "spec_on", "acc_ema",
                 "tenant")

    def __init__(self, sid, prompt, max_new, pages_needed, on_token,
                 ctx, now, deadline, tenant=DEFAULT_TENANT):
        self.tenant = tenant
        self.sid = sid
        self.seq = int(sid[1:])  # admission order (FIFO chunk planning)
        self.prefill_chunks = 0
        self.cached_tokens = 0   # prefix-cache hit span (page-aligned)
        self.shared_pages = 0    # leading table entries shared via the index
        self.draft_pages: List[int] = []  # draft-arena pages (speculative)
        self.draft_fed = 0       # known tokens committed to the draft cache
        self.spec_on = False     # drafting this stream (may fall back)
        self.acc_ema = 1.0       # acceptance-rate EMA (fallback trigger)
        self.prompt = prompt
        self.max_new = max_new
        self.pages_needed = pages_needed
        self.on_token = on_token
        self.ctx = ctx
        self.enqueued_at = now
        self.submitted_at = now
        self.deadline = deadline
        self.slot = -1
        self.pages: List[int] = []
        self.fed = 0
        self.next_input = int(prompt[0])
        self.generated: List[int] = []
        self.tokens_q: queue_mod.SimpleQueue = queue_mod.SimpleQueue()
        self.done = threading.Event()
        self.outcome = None           # DecodeResult | Overloaded
        self.error: Optional[BaseException] = None
        self.ttft_s: Optional[float] = None


class StreamHandle:
    """Caller-facing handle for one submitted stream.

    ``tokens()`` is a blocking iterator over generated token ids (ends
    when the stream finishes); ``result(timeout)`` blocks for the
    final :class:`DecodeResult` — or a typed
    :class:`~perceiver_tpu.serving.batcher.Overloaded` value when the
    stream was shed, following the batcher's value-not-exception
    convention. Stream errors re-raise here.
    """

    def __init__(self, stream: _Stream, engine: "DecodeEngine"):
        self._stream = stream
        self._engine = engine
        self.trace_ctx = stream.ctx

    @property
    def stream_id(self) -> str:
        return self._stream.sid

    def tokens(self):
        while True:
            tok = self._stream.tokens_q.get()
            if tok is _SENTINEL:
                return
            yield tok

    def result(self, timeout: Optional[float] = None):
        if not self._stream.done.wait(timeout):
            raise TimeoutError(
                f"stream {self._stream.sid} unfinished after {timeout}s")
        if self._stream.error is not None:
            raise self._stream.error
        return self._stream.outcome

    def done(self) -> bool:
        return self._stream.done.is_set()

    def cancel(self) -> bool:
        return self._engine._cancel(self._stream)


class DecodeEngine:
    """The stepped decode executor: ONE AOT-compiled signature, a
    shared paged KV pool, streams joining and leaving mid-flight.

    ``auto_step=True`` (default) runs a worker thread that steps
    whenever work exists; tests pass ``auto_step=False`` and drive
    :meth:`step` / :meth:`run_until_idle` deterministically.
    """

    # lock discipline (gated by check.py --race): every mutable piece
    # of scheduler state below is touched only under self._lock —
    # self._work is a Condition over the same lock, so 'with
    # self._work:' frames count. params/pool ride along because the
    # step loop swaps/mutates them while streams are in flight.
    _GUARDED = {
        "_streams": "_lock",
        "_tables": "_lock",
        "_lengths": "_lock",
        "_dirty": "_lock",
        "_seq": "_lock",
        "_closed": "_lock",
        "_failed": "_lock",
        "_carry": "_lock",
        "params": "_lock",
        "pool": "_lock",
        "prefix_index": "_lock",
        # speculative draft arena: its own pool / host mirrors / carry,
        # mutated only from the same step critical sections
        "_draft_carry": "_lock",
        "_draft_params": "_lock",
        "draft_pool": "_lock",
        "_draft_tables": "_lock",
        "_draft_lengths": "_lock",
        "_draft_dirty": "_lock",
        # per-tenant page accounting: charged at admission, credited
        # at finish — the quota enforcement ledger
        "_tenant_pages": "_lock",
    }

    def __init__(self, task, params=None, *,
                 geometry: DecodeGeometry,
                 policy: Policy = DEFAULT_POLICY,
                 attn_impl: str = "pallas",
                 exec_cache=None,
                 metrics: Optional[MetricsRegistry] = None,
                 max_queue: int = 64,
                 token_budget: Optional[int] = None,
                 prefix_cache: Optional[PrefixCacheConfig] = None,
                 speculative: Optional[SpeculativeConfig] = None,
                 tenancy: Optional[TenantRegistry] = None,
                 auto_step: bool = True,
                 seed: int = 0):
        import jax
        import jax.numpy as jnp

        if (geometry.spec_k > 0) != (speculative is not None):
            raise ValueError(
                "speculative decoding needs both halves: geometry."
                f"spec_k (got {geometry.spec_k}) compiles the verify "
                "windows, speculative= (got "
                f"{'a config' if speculative is not None else 'None'}) "
                "supplies the draft policy")
        self.task = task
        self.geometry = geometry
        self.policy = policy
        self.speculative = speculative
        # host-side tenancy: quotas/weights only — never a compiled
        # shape, so the exec-cache key is identical with it on or off
        self.tenancy = tenancy
        self._tenant_pages: Dict[str, int] = {}
        # per-step token pacing: every decode row costs 1, the rest
        # goes to prefill chunks — host-side policy only, never a
        # compiled shape, so it is tunable without a recompile
        self.token_budget = (int(token_budget) if token_budget is not None
                             else geometry.max_streams
                             + geometry.max_chunk)
        self.exec_cache = resolve_exec_cache(exec_cache)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.graph = build_decode_graph(
            task.build(), geometry, policy=policy, attn_impl=attn_impl)
        self.params = params if params is not None \
            else self.graph.init_params(seed)

        m = self.metrics
        self._m_active = m.gauge(
            "serving_decode_streams_active",
            "decode streams currently holding a slot")
        self._m_free_pages = m.gauge(
            "serving_decode_free_pages", "allocatable pages not in use")
        self._m_steps = m.counter(
            "serving_decode_steps_total", "decode step executions")
        self._m_tokens = m.counter(
            "serving_decode_tokens_total", "generated tokens emitted")
        self._m_streams = m.counter(
            "serving_decode_streams_total", "finished streams by outcome")
        self._m_shed = m.counter(
            "serving_decode_shed_total", "streams shed by reason")
        self._m_ttft = m.histogram(
            "serving_decode_ttft_seconds",
            "submit → first generated token")
        self._m_step_latency = m.histogram(
            "serving_decode_step_latency_seconds",
            "one decode step (dispatch + next_token sync)")
        self._m_prefill_chunks = m.counter(
            "serving_decode_prefill_chunks_total",
            "prefill chunks executed by the unified step")
        self._m_prefill_tokens = m.counter(
            "serving_decode_prefill_tokens_total",
            "prompt tokens consumed via chunked prefill")
        self._m_prefix_hits = m.counter(
            "serving_prefix_cache_hits_total",
            "admissions whose prompt matched a cached prefix")
        self._m_prefix_misses = m.counter(
            "serving_prefix_cache_misses_total",
            "admissions with no cached prefix")
        self._m_prefix_hit_tokens = m.counter(
            "serving_prefix_cache_hit_tokens_total",
            "prompt tokens served from shared prefix pages")
        self._m_prefix_evicted = m.counter(
            "serving_prefix_cache_evicted_pages_total",
            "index pages reclaimed by LRU eviction")
        self._m_prefix_pages = m.gauge(
            "serving_prefix_cache_pages",
            "pages currently held by the prefix index")
        self._m_spec_draft = m.counter(
            "serving_spec_draft_tokens_total",
            "draft-model tokens proposed for verification")
        self._m_spec_accepted = m.counter(
            "serving_spec_accepted_tokens_total",
            "drafted tokens the target accepted")
        self._m_spec_verify = m.counter(
            "serving_spec_verify_steps_total",
            "unified steps that verified at least one drafted window")
        self._m_spec_fallback = m.counter(
            "serving_spec_fallback_total",
            "streams dropped to plain decode on acceptance collapse")
        self._m_tenant_pages = m.gauge(
            "serving_tenant_pages_used",
            "KV pages charged to each tenant's quota")
        self._m_tenant_shed = m.counter(
            "serving_tenant_shed_total",
            "streams shed, by tenant and reason")
        self._m_tenant_tokens = m.counter(
            "serving_tenant_tokens_total",
            "generated tokens emitted, by tenant")
        self._m_pool_gauges = PagePoolGauges(m, arena="target")

        r = geometry.max_streams
        self.pool = PagePool(geometry.num_pages, geometry.page_size)
        # prefix sharing is an opt-in host-side discipline over the
        # same arena: enabling it changes no compiled shape — the
        # geometry descriptor (and so the exec-cache key) is identical
        # with the index on or off
        self.prefix_index: Optional[PrefixIndex] = (
            PrefixIndex(self.pool, geometry.page_size, prefix_cache)
            if prefix_cache is not None else None)
        self._m_free_pages.set(self.pool.free_pages)
        self._m_pool_gauges.update(self.pool)
        self._queue = ContinuousBatchScheduler(
            max_depth=max_queue, token_budget=self.token_budget,
            max_chunk=geometry.max_chunk, metrics=m)
        self._streams: List[Optional[_Stream]] = [None] * r
        self._tables = np.zeros((r, geometry.pages_per_stream), np.int32)
        self._lengths = np.zeros((r,), np.int32)
        self._dirty = False
        self._seq = 0
        self._closed = False
        self._failed: Optional[BaseException] = None
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)

        tokens0 = jnp.zeros((r, geometry.max_chunk), jnp.int32)
        qlens0 = jnp.zeros((r,), jnp.int32)
        jitted = jax.jit(self.graph.fn,
                         donate_argnums=self.graph.donate_argnums)
        carry = self.graph.init_carry()
        self._exe, info = aot_compile(
            jitted, (self.params, carry, tokens0, qlens0),
            cache=self.exec_cache,
            donate_argnums=self.graph.donate_argnums,
            label=f"decode:{geometry.descriptor}",
            extra_key=(geometry.descriptor,))
        if self.exec_cache is not None:
            events_mod.emit("exec_cache",  # graphcheck: ignore — exec_cache is bucket-scoped (compile plane, shared across tenants by design)
                            bucket=f"decode:{geometry.descriptor}",
                            hit=bool(info["hit"]))
        # warmup step with every slot idle: the steady state then
        # re-runs an already-warm executable — zero per-step compiles
        carry, out = self._exe(self.params, carry, tokens0, qlens0)
        np.asarray(out["next_token"])
        self._carry = carry

        # speculative draft arena: a second (smaller) stepped
        # executable with its OWN paged pool, page tables, lengths and
        # carry — never shared with the target, because the draft's
        # cache trails/leads the target's by design and prefix-shared
        # target pages must not see draft writes
        self._draft_graph = None
        self._draft_exe = None
        self._draft_carry = None
        self._draft_params = None
        self.draft_pool: Optional[PagePool] = None
        self._draft_tables: Optional[np.ndarray] = None
        self._draft_lengths: Optional[np.ndarray] = None
        self._draft_dirty = False
        self._m_draft_gauges: Optional[PagePoolGauges] = None
        if speculative is not None:
            self._init_draft(speculative, attn_impl)

        self._worker: Optional[threading.Thread] = None
        if auto_step:
            self._worker = threading.Thread(
                target=self._loop, name="decode-engine", daemon=True)
            self._worker.start()

    def _init_draft(self, spec: SpeculativeConfig,
                    attn_impl: str) -> None:
        """Build and warm the draft stepped executable (called from
        ``__init__`` only; the lock is uncontended pre-publication but
        taken anyway so the draft-state discipline holds uniformly)."""
        with self._lock:
            self._init_draft_locked(spec, attn_impl)

    def _init_draft_locked(self, spec: SpeculativeConfig,
                           attn_impl: str) -> None:
        import jax
        import jax.numpy as jnp

        g = self.geometry
        # the draft never verifies — it decodes plain, one stream of
        # proposals at a time — so its graph compiles at spec_k == 0
        draft_geometry = dataclasses.replace(g, spec_k=0)
        draft_task = (spec.draft_task if spec.draft_task is not None
                      else self.task)
        self._draft_graph = build_decode_graph(
            draft_task.build(), draft_geometry, policy=self.policy,
            attn_impl=attn_impl)
        if self._draft_graph.vocab_size != self.graph.vocab_size:
            raise ValueError(
                f"draft vocab {self._draft_graph.vocab_size} != target "
                f"vocab {self.graph.vocab_size} — proposals would not "
                "be target token ids")
        if spec.draft_params is not None:
            self._draft_params = jax.device_put(spec.draft_params)
        elif spec.draft_task is None:
            self._draft_params = self.params  # self-draft
        else:
            self._draft_params = self._draft_graph.init_params(
                spec.draft_seed)
        self.draft_pool = PagePool(g.num_pages, g.page_size)
        r = g.max_streams
        self._draft_tables = np.zeros((r, g.pages_per_stream), np.int32)
        self._draft_lengths = np.zeros((r,), np.int32)
        self._draft_dirty = False
        self._m_draft_gauges = PagePoolGauges(self.metrics, arena="draft")
        self._m_draft_gauges.update(self.draft_pool)
        tokens0 = jnp.zeros((r, g.max_chunk), jnp.int32)
        qlens0 = jnp.zeros((r,), jnp.int32)
        jitted = jax.jit(self._draft_graph.fn,
                         donate_argnums=self._draft_graph.donate_argnums)
        carry = self._draft_graph.init_carry()
        self._draft_exe, info = aot_compile(
            jitted, (self._draft_params, carry, tokens0, qlens0),
            cache=self.exec_cache,
            donate_argnums=self._draft_graph.donate_argnums,
            label=f"draft:{g.descriptor}",
            extra_key=("draft", g.descriptor))
        if self.exec_cache is not None:
            events_mod.emit("exec_cache",  # graphcheck: ignore — exec_cache is bucket-scoped (compile plane, shared across tenants by design)
                            bucket=f"draft:{g.descriptor}",
                            hit=bool(info["hit"]))
        carry, out = self._draft_exe(
            self._draft_params, carry, tokens0, qlens0)
        np.asarray(out["next_token"])
        self._draft_carry = carry

    # -- submission -------------------------------------------------------

    def _tenant_spec(self, tenant: str) -> TenantSpec:
        if self.tenancy is None:
            return TenantSpec(tenant=tenant)
        return self.tenancy.get(tenant)

    def submit(self, prompt_ids, *, max_new_tokens: int,
               timeout_ms: Optional[float] = None,
               on_token: Optional[Callable[[int], None]] = None,
               trace: Optional[trace_mod.TraceContext] = None,
               tenant: Optional[str] = None
               ) -> StreamHandle:
        """Enqueue one stream. Raises :class:`RequestTooLarge` when the
        request can never fit this engine's geometry (or its tenant's
        page quota); raises ``Unavailable("tenant_quota")`` — before
        any compute — when the tenant's held + queued pages leave no
        room; resolves the handle to a typed ``Overloaded`` when
        capacity is transiently unavailable (queue full / admission
        deadline)."""
        g = self.geometry
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        vocab = self.graph.vocab_size
        if vocab > 0 and (prompt.min() < 0 or prompt.max() >= vocab):
            raise ValueError(
                f"prompt ids outside [0, {vocab}) — not a valid token "
                "sequence for this model")
        total = int(prompt.size) + int(max_new_tokens)
        if total > g.max_seq_len:
            raise RequestTooLarge(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) = {total} exceeds the decode "
                f"geometry's max_seq_len {g.max_seq_len}")
        # the last generated token is never fed back, so the cache
        # holds total - 1 tokens at finish
        pages_needed = g.pages_for(total - 1)
        if pages_needed > g.allocatable_pages:
            raise RequestTooLarge(
                f"request needs {pages_needed} pages, pool has only "
                f"{g.allocatable_pages} allocatable "
                f"({g.num_pages} minus the reserved trash page)")
        tenant = tenant or DEFAULT_TENANT
        tspec = self._tenant_spec(tenant)
        if tspec.max_pages is not None and pages_needed > tspec.max_pages:
            raise RequestTooLarge(
                f"request needs {pages_needed} pages but tenant "
                f"{tenant!r} is capped at {tspec.max_pages}")
        now = time.monotonic()
        ctx = trace if trace is not None \
            else trace_mod.start_trace(origin="decode")
        deadline = (now + timeout_ms / 1000.0
                    if timeout_ms is not None else None)
        with self._lock:
            if self._closed:
                raise RuntimeError("decode engine is closed")
            if self._failed is not None:
                raise Unavailable("decode_engine_failed")
            if tspec.max_pages is not None:
                # quota exhaustion sheds HERE — before a slot, a page,
                # or a single device token is spent on the request.
                # held + queued both charge, so a flood tenant cannot
                # park unbounded work in the admission queue either.
                charged = (self._tenant_pages.get(tenant, 0)
                           + self._queue.tenant_queued_cost()
                           .get(tenant, 0))
                if charged + pages_needed > tspec.max_pages:
                    self._m_tenant_shed.labels(
                        tenant=tenant, reason="tenant_quota").inc()
                    events_mod.emit("tenant_shed", tenant=tenant,
                                    reason="tenant_quota")
                    raise Unavailable("tenant_quota", tenant=tenant)
            self._seq += 1
            stream = _Stream(f"s{self._seq}", prompt, int(max_new_tokens),
                             pages_needed, on_token, ctx, now, deadline,
                             tenant=tenant)
            handle = StreamHandle(stream, self)
            if not self._queue.offer(stream, cost=pages_needed,
                                     deadline=deadline, tenant=tenant):
                self._m_shed.labels(reason="queue_full").inc()  # graphcheck: ignore — aggregate shed counter predates tenancy; the tenant split rides serving_tenant_shed_total below
                self._m_tenant_shed.labels(
                    tenant=tenant, reason="queue_full").inc()
                events_mod.emit("tenant_shed", tenant=tenant,
                                reason="queue_full")
                self._resolve_shed(stream, Overloaded(
                    "queue_full", self._queue.depth))
                return handle
            self._work.notify_all()
        return handle

    # -- stepping ---------------------------------------------------------

    def _admit_locked(self, now: float) -> None:
        free_slots = sum(1 for s in self._streams if s is None)
        # index-only pages are reclaimable on demand, so they count
        # toward the admission budget — a full index never starves
        # admission (it just loses its least-recently-hit chains)
        budget = self.pool.free_pages
        if self.prefix_index is not None:
            budget += self.prefix_index.evictable_pages()
        tenant_budgets = None
        if self.tenancy is not None:
            # remaining per-tenant page headroom: entries of a tenant
            # that is out of headroom defer inside take() without
            # blocking anyone else's admission
            tenant_budgets = {}
            for t in self._queue.tenant_queued_cost():
                cap = self._tenant_spec(t).max_pages
                if cap is not None:
                    tenant_budgets[t] = max(
                        0, cap - self._tenant_pages.get(t, 0))
        admitted, shed = self._queue.take(
            budget=budget, slots=free_slots, now=now,
            tenant_budgets=tenant_budgets)
        for stream in shed:
            self._m_shed.labels(reason="deadline").inc()  # graphcheck: ignore — aggregate shed counter predates tenancy; the tenant split rides serving_tenant_shed_total below
            self._m_tenant_shed.labels(
                tenant=stream.tenant, reason="deadline").inc()
            events_mod.emit("tenant_shed", tenant=stream.tenant,
                            reason="deadline")
            self._resolve_shed(stream, Overloaded(
                "deadline", self._queue.depth))
        for stream in admitted:
            slot = next(i for i, s in enumerate(self._streams)
                        if s is None)
            stream.slot = slot
            shared: List[int] = []
            if self.prefix_index is not None:
                t_lk = time.monotonic()
                cached, shared = self.prefix_index.lookup(stream.prompt)
                stream.cached_tokens = cached
                stream.shared_pages = len(shared)
                if stream.ctx is not None:
                    stream.ctx.record(
                        "prefix_lookup", start=t_lk,
                        end=time.monotonic(), stream=stream.sid,
                        cached_tokens=cached, pages=len(shared))
                if cached > 0:
                    self._m_prefix_hits.inc()
                    self._m_prefix_hit_tokens.inc(cached)
                    events_mod.emit("prefix_cache_hit",  # graphcheck: ignore — stream-scoped; stream->tenant join via the stream_open event
                                    stream=stream.sid, tokens=cached,
                                    pages=len(shared))
                else:
                    self._m_prefix_misses.inc()
                    events_mod.emit("prefix_cache_miss",  # graphcheck: ignore — stream-scoped; stream->tenant join via the stream_open event
                                    stream=stream.sid)
            # the cached span is page-aligned and strictly shorter
            # than the prompt, so >= 1 private page is always needed
            # (the partial last page is never shared)
            private_needed = stream.pages_needed - len(shared)
            if (self.prefix_index is not None
                    and private_needed > self.pool.free_pages):
                evicted = self.prefix_index.evict(
                    private_needed - self.pool.free_pages)
                if evicted:
                    self._m_prefix_evicted.inc(evicted)
                    events_mod.emit("prefix_cache_evict", pages=evicted)  # graphcheck: ignore — LRU reclaim frees index-only pages owned by no tenant
            private = self.pool.alloc(private_needed)
            for p in private:
                # CoW discipline: every page this stream will write is
                # exclusively held — shared pages only ever serve reads
                ensure_private_page(self.pool, p)
            stream.pages = shared + private
            stream.fed = stream.cached_tokens
            if self.draft_pool is not None:
                # the draft arena has no prefix sharing (its cache is
                # private per stream) and no eviction — when it can't
                # host the stream, the stream just decodes plain
                if stream.pages_needed <= self.draft_pool.free_pages:
                    stream.draft_pages = self.draft_pool.alloc(
                        stream.pages_needed)
                    stream.spec_on = True
                    stream.draft_fed = 0
                    stream.acc_ema = 1.0
                    self._draft_tables[slot, :] = 0
                    self._draft_tables[slot, :len(stream.draft_pages)] \
                        = stream.draft_pages
                    self._draft_lengths[slot] = 0
                    self._draft_dirty = True
                else:
                    stream.spec_on = False
                self._m_draft_gauges.update(self.draft_pool)
            self._streams[slot] = stream
            self._tables[slot, :] = 0
            self._tables[slot, :len(stream.pages)] = stream.pages
            # positions continue after the cached span: the carry's
            # length row starts at cached_tokens, so the tail chunk
            # prefills (and attends) exactly as a cold stream that
            # had already written those positions
            self._lengths[slot] = stream.cached_tokens
            self._dirty = True
            # quota ledger charges the conservative pages_needed (what
            # admission budgeted), not the prefix-shared actual — two
            # tenants sharing a prefix must not double-spend headroom
            self._tenant_pages[stream.tenant] = (
                self._tenant_pages.get(stream.tenant, 0)
                + stream.pages_needed)
            self._m_tenant_pages.labels(tenant=stream.tenant).set(
                self._tenant_pages[stream.tenant])
            if stream.ctx is not None:
                stream.ctx.record("queue_wait", start=stream.enqueued_at,
                                  end=now, stream=stream.sid,
                                  tenant=stream.tenant)
            events_mod.emit("stream_open", stream=stream.sid,
                            tenant=stream.tenant)
            events_mod.emit("stream_admitted", stream=stream.sid,
                            pages=len(stream.pages),
                            tenant=stream.tenant)
            self._m_active.set(
                sum(1 for s in self._streams if s is not None))
            self._m_free_pages.set(self.pool.free_pages)
            self._m_pool_gauges.update(self.pool)
        if self.prefix_index is not None:
            self._m_prefix_pages.set(self.prefix_index.pages_indexed)

    def step(self) -> int:
        """Run one unified step over every occupied slot (admitting
        queued streams first): decode rows consume their fed-back
        token, prefilling rows consume a budget-planned prompt chunk
        — one executable, one dispatch. Returns the number of active
        streams stepped — 0 means idle. Emits/finishes streams as a
        side effect; callbacks fire outside the engine lock."""
        import jax.numpy as jnp

        emits: List[tuple] = []
        finished: List[_Stream] = []
        with self._lock:
            if self._failed is not None:
                raise Unavailable("decode_engine_failed")
            t0 = time.monotonic()
            self._admit_locked(t0)
            live = [(i, s) for i, s in enumerate(self._streams)
                    if s is not None]
            if not live:
                return 0
            r = self.geometry.max_streams
            decode_live = [(i, s) for i, s in live
                           if s.fed >= len(s.prompt)]
            prefill_live = sorted(
                ((i, s) for i, s in live if s.fed < len(s.prompt)),
                key=lambda e: e[1].seq)  # FIFO by admission order
            # speculative candidates: drafting streams far enough from
            # max_new that accepted drafts can't overshoot (the last
            # verify window's bonus token is the +1)
            spec_cand: List[tuple] = []
            desires: List[int] = []
            if self.speculative is not None:
                for i, s in decode_live:
                    kd = min(self.geometry.spec_k,
                             s.max_new - len(s.generated) - 1)
                    if s.spec_on and kd >= 1:
                        spec_cand.append((i, s))
                        desires.append(kd)
            prefill_tenants = None
            tenant_weights = None
            if self.tenancy is not None and prefill_live:
                prefill_tenants = [s.tenant for _, s in prefill_live]
                tenant_weights = {
                    t: self._tenant_spec(t).weight
                    for t in set(prefill_tenants)}
            grants, plan = self._queue.plan_speculative(
                len(decode_live), desires,
                [len(s.prompt) - s.fed for _, s in prefill_live],
                prefill_tenants, tenant_weights)
            props: Dict[int, List[int]] = {}
            if spec_cand:
                cand = [(i, s, k) for (i, s), k in zip(spec_cand, grants)
                        if k > 0]
                if cand:
                    props = self._draft_propose_locked(cand)
            tokens = np.zeros((r, self.geometry.max_chunk), np.int32)
            qlens = np.zeros((r,), np.int32)
            for i, s in decode_live:
                tokens[i, 0] = s.next_input
                p = props.get(i)
                if p:
                    # verify lanes: feedback token + the drafted run —
                    # one chunk row, exactly like a prefill chunk
                    tokens[i, 1:1 + len(p)] = p
                qlens[i] = 1 + (len(p) if p else 0)
            chunks: Dict[int, int] = {}
            for (i, s), c in zip(prefill_live, plan):
                chunks[i] = c
                if c > 0:
                    tokens[i, :c] = s.prompt[s.fed:s.fed + c]
                    qlens[i] = c
            carry = self._carry
            self._carry = None  # donated: loud failure on re-entry
            if self._dirty:
                carry["page_tables"] = jnp.asarray(self._tables)
                carry["lengths"] = jnp.asarray(self._lengths)
                self._dirty = False
            try:
                carry, out = self._exe(self.params, carry,
                                       jnp.asarray(tokens),
                                       jnp.asarray(qlens))
                # the one deliberate sync of the decode path
                next_tok = np.asarray(out["next_token"])
                spec_tok = (np.asarray(out["spec_tokens"])
                            if props else None)
            except Exception as e:
                self._fail_locked(e)
                raise
            t1 = time.monotonic()
            self._carry = carry
            lengths_before = self._lengths.copy() if props else None
            self._lengths += qlens
            self._m_steps.inc()
            self._m_step_latency.observe(t1 - t0)
            for i, s in live:
                was_prefill = s.fed < len(s.prompt)
                if was_prefill:
                    c = chunks.get(i, 0)
                    if c == 0:
                        continue  # budget-starved this step; keep FIFO
                    s.fed += c
                    s.prefill_chunks += 1
                    self._m_prefill_chunks.inc()
                    self._m_prefill_tokens.inc(c)
                    if s.ctx is not None:
                        s.ctx.record("prefill_chunk", start=t0, end=t1,
                                     stream=s.sid, chunk=c, fed=s.fed,
                                     tenant=s.tenant)
                    if s.fed < len(s.prompt):
                        continue
                    # the chunk that consumed the last prompt token
                    # already produced the first generated token below
                    events_mod.emit("prefill_complete", stream=s.sid,
                                    prompt_tokens=len(s.prompt),
                                    chunks=s.prefill_chunks,
                                    cached_tokens=s.cached_tokens,
                                    tenant=s.tenant)
                    if self.prefix_index is not None:
                        # every full prompt-only page is now written;
                        # publish the ones the index doesn't know yet
                        pub = self.prefix_index.publish(
                            s.prompt, s.pages)
                        if pub:
                            events_mod.emit("prefix_cache_publish",  # graphcheck: ignore — stream-scoped; stream->tenant join via the stream_open event
                                            stream=s.sid, pages=pub)
                        self._m_prefix_pages.set(
                            self.prefix_index.pages_indexed)
                    emitted = [int(next_tok[i])]
                else:
                    p = props.get(i)
                    if p:
                        emitted = self._verify_row_locked(
                            i, s, p, spec_tok, lengths_before, t0, t1)
                    else:
                        s.fed += 1
                        emitted = [int(next_tok[i])]
                        if s.ctx is not None:
                            s.ctx.record("decode_step", start=t0,
                                         end=t1, stream=s.sid,
                                         tenant=s.tenant)
                for tok in emitted:
                    s.generated.append(tok)
                    if s.ttft_s is None:
                        s.ttft_s = t1 - s.submitted_at
                        self._m_ttft.observe(s.ttft_s)
                    if s.ctx is not None:
                        s.ctx.record("token_emit", start=t1, end=t1,
                                     stream=s.sid,
                                     index=len(s.generated) - 1)
                    self._m_tokens.inc()  # graphcheck: ignore — aggregate token counter predates tenancy; the tenant split rides serving_tenant_tokens_total below
                    self._m_tenant_tokens.labels(tenant=s.tenant).inc()
                    emits.append((s, tok))
                s.next_input = emitted[-1]
                if len(s.generated) >= s.max_new:
                    self._finish_locked(s, "complete")
                    finished.append(s)
            self._work.notify_all()
        for s, tok in emits:
            s.tokens_q.put(tok)
            if s.on_token is not None:
                try:
                    s.on_token(tok)
                except Exception as e:  # noqa: BLE001 — fail the stream, not the loop
                    self._cancel(s, error=e)
        for s in finished:
            s.tokens_q.put(_SENTINEL)
            s.done.set()
        return len(live)

    def _draft_propose_locked(self, cand) -> Dict[int, List[int]]:
        """Run up to ``spec_k + 1`` draft-model calls proposing tokens
        for the granted decode rows (``cand``: (slot, stream, grant)).

        The draft's cache is fed each stream's *known* tokens (prompt
        + generated) — independent of the target's prefill progress or
        prefix-cache hits, which is what keeps warm-prefix admissions
        token-exact — then extended one proposal at a time through its
        own stepped executable. ``stream.draft_fed`` tracks the known
        prefix already cached; the call that consumes the last known
        token yields the first proposal. A row still catching up when
        the call cap runs out simply decodes plain this step and
        resumes next cycle, so a long prompt can never stall its
        neighbours' verify round.
        """
        import jax.numpy as jnp

        g = self.geometry
        props: Dict[int, List[int]] = {i: [] for i, _, _ in cand}
        t_d0 = time.monotonic()
        for _ in range(g.spec_k + 1):
            tokens = np.zeros((g.max_streams, g.max_chunk), np.int32)
            qlens = np.zeros((g.max_streams,), np.int32)
            yields: List[int] = []  # rows whose call emits a proposal
            for i, s, grant in cand:
                known = len(s.prompt) + len(s.generated)
                if len(props[i]) >= grant:
                    continue
                if s.draft_fed >= known and not props[i]:
                    # defensive: every known token cached but no
                    # proposal in hand — rewind one and refeed it (the
                    # rewritten KV is identical, only the length moves)
                    s.draft_fed = known - 1
                    self._draft_lengths[i] = known - 1
                    self._draft_dirty = True
                if s.draft_fed < known:
                    feed = min(known - s.draft_fed, g.max_chunk)
                    base = len(s.prompt)
                    for j in range(feed):
                        t = s.draft_fed + j
                        tokens[i, j] = (s.prompt[t] if t < base
                                        else s.generated[t - base])
                    qlens[i] = feed
                    if s.draft_fed + feed == known:
                        yields.append(i)
                else:
                    tokens[i, 0] = props[i][-1]
                    qlens[i] = 1
                    yields.append(i)
            if not qlens.any():
                break
            carry = self._draft_carry
            self._draft_carry = None  # donated: loud on re-entry
            if self._draft_dirty:
                carry["page_tables"] = jnp.asarray(self._draft_tables)
                carry["lengths"] = jnp.asarray(self._draft_lengths)
                self._draft_dirty = False
            try:
                carry, out = self._draft_exe(
                    self._draft_params, carry, jnp.asarray(tokens),
                    jnp.asarray(qlens))
                next_tok = np.asarray(out["next_token"])
            except Exception as e:
                self._fail_locked(e)
                raise
            self._draft_carry = carry
            self._draft_lengths += qlens
            for i, s, grant in cand:
                if qlens[i]:
                    # known prefix only — proposal feeds don't advance
                    s.draft_fed = min(
                        len(s.prompt) + len(s.generated),
                        s.draft_fed + int(qlens[i]))
            for i in yields:
                props[i].append(int(next_tok[i]))
        t_d1 = time.monotonic()
        for i, s, _ in cand:
            if props[i] and s.ctx is not None:
                s.ctx.record("draft", start=t_d0, end=t_d1,
                             stream=s.sid, tokens=len(props[i]))
        return props

    def _verify_row_locked(self, i: int, s: _Stream, p: List[int],
                           spec_tok: np.ndarray,
                           lengths_before: np.ndarray,
                           t0: float, t1: float) -> List[int]:
        """Apply the greedy rejection rule to one verified row and
        roll both arenas back past the first disagreement. Returns the
        tokens to emit (``accepted + 1``, never 0)."""
        kg = len(p)
        w = self.geometry.spec_k + 1
        # window w-1-kg+j is the target's greedy pick AT drafted
        # position j (conditioned on the drafts before it); the last
        # window is the full-cache view — the bonus token
        target_preds = [int(t) for t in spec_tok[i, w - 1 - kg:]]
        a, nxt = greedy_accept(p, target_preds)
        emitted = p[:a] + [int(nxt)]
        # target arena: the step cached feedback + kg drafts; keep
        # feedback + the accepted run. Rejected tails are masked by
        # kv_len immediately and overwritten by later writes, and they
        # only ever landed in refcount-1 private pages (drafted
        # positions are past the prompt), so shared CoW prefix pages
        # are untouched by construction.
        c0 = int(lengths_before[i])
        if a < kg:
            self._lengths[i] = c0 + 1 + a
            self._dirty = True
        # draft arena: its cache holds known + kg-1 proposals; keep
        # the prefix that is now confirmed known-correct
        keep = len(s.prompt) + len(s.generated) + min(a, kg - 1)
        if int(self._draft_lengths[i]) != keep:
            self._draft_lengths[i] = keep
            self._draft_dirty = True
        s.draft_fed = keep
        s.fed += 1 + a
        s.acc_ema = (self.speculative.ema_alpha * (a / kg)
                     + (1.0 - self.speculative.ema_alpha) * s.acc_ema)
        self._m_spec_draft.inc(kg)
        self._m_spec_accepted.inc(a)
        self._m_spec_verify.inc()
        events_mod.emit("spec_verify", stream=s.sid, drafted=kg,  # graphcheck: ignore — stream-scoped; stream->tenant join via the stream_open event
                        accepted=a)
        if s.ctx is not None:
            s.ctx.record("verify", start=t0, end=t1, stream=s.sid,
                         drafted=kg, accepted=a)
        if s.acc_ema < self.speculative.fallback_acceptance:
            # acceptance collapsed: drafted tokens cost real step
            # budget, so flip this stream to plain decode for good
            # and hand its draft pages back
            s.spec_on = False
            self.draft_pool.free(s.draft_pages)
            s.draft_pages = []
            self._draft_tables[i, :] = 0
            self._draft_lengths[i] = 0
            self._draft_dirty = True
            self._m_spec_fallback.inc()
            self._m_draft_gauges.update(self.draft_pool)
            events_mod.emit("spec_fallback", stream=s.sid,  # graphcheck: ignore — stream-scoped; stream->tenant join via the stream_open event
                            acceptance=round(s.acc_ema, 4))
        return emitted

    def run_until_idle(self, max_steps: int = 10_000) -> int:
        """Step until no stream is active or queued (deterministic
        test driver). Returns steps executed."""
        for n in range(max_steps):
            if self.step() == 0:
                return n
        raise RuntimeError(f"not idle after {max_steps} steps")

    def _loop(self) -> None:
        while True:
            with self._work:
                while (not self._closed and self._failed is None
                       and not self._has_work_locked()):
                    self._work.wait(0.05)
                if self._closed or self._failed is not None:
                    return
            try:
                self.step()
            except Exception:  # noqa: BLE001 — streams already failed typed
                return

    def _has_work_locked(self) -> bool:
        return (self._queue.depth > 0
                or any(s is not None for s in self._streams))

    # -- lifecycle / resolution -------------------------------------------

    def _finish_locked(self, s: _Stream, how: str) -> None:
        if s.slot >= 0:
            self.pool.free(s.pages)
            held = self._tenant_pages.get(s.tenant, 0) - s.pages_needed
            if held > 0:
                self._tenant_pages[s.tenant] = held
            else:
                self._tenant_pages.pop(s.tenant, None)
            self._m_tenant_pages.labels(tenant=s.tenant).set(
                max(0, held))
            self._streams[s.slot] = None
            self._tables[s.slot, :] = 0
            self._lengths[s.slot] = 0
            self._dirty = True
            if s.draft_pages:
                self.draft_pool.free(s.draft_pages)
                s.draft_pages = []
                self._draft_tables[s.slot, :] = 0
                self._draft_lengths[s.slot] = 0
                self._draft_dirty = True
                self._m_draft_gauges.update(self.draft_pool)
            self._m_active.set(
                sum(1 for st in self._streams if st is not None))
            self._m_free_pages.set(self.pool.free_pages)
            self._m_pool_gauges.update(self.pool)
        events_mod.emit("stream_close", stream=s.sid,
                        tokens=len(s.generated), tenant=s.tenant)
        self._m_streams.labels(outcome=how).inc()  # graphcheck: ignore — aggregate outcome counter predates tenancy; per-tenant accounting rides serving_tenant_* series
        s.outcome = DecodeResult(
            tokens=list(s.generated), prompt_len=len(s.prompt),
            finished=how, ttft_s=s.ttft_s,
            cached_tokens=s.cached_tokens)

    def _resolve_shed(self, s: _Stream, overloaded: Overloaded) -> None:
        self._m_streams.labels(outcome="shed").inc()  # graphcheck: ignore — aggregate outcome counter predates tenancy; per-tenant sheds ride serving_tenant_shed_total at the callers
        s.outcome = overloaded
        s.tokens_q.put(_SENTINEL)
        s.done.set()

    def _cancel(self, s: _Stream,
                error: Optional[BaseException] = None) -> bool:
        with self._lock:
            if s.done.is_set() or s.outcome is not None:
                return False
            if s.slot < 0:
                self._queue.remove(s)
            self._finish_locked(s, "cancelled")
            s.error = error
            self._work.notify_all()
        s.tokens_q.put(_SENTINEL)
        s.done.set()
        return True

    def _fail_locked(self, e: BaseException) -> None:
        """A step blew up mid-flight: the donated carry may be gone,
        so the engine is dead — fail every stream typed, never hang
        a caller on a future that cannot resolve."""
        self._failed = e
        err = e if isinstance(e, (Unavailable, BatchError)) else \
            BatchError(f"decode step failed: {type(e).__name__}: {e}",
                       cause=e)
        leftovers = [s for s in self._streams if s is not None]
        for s in leftovers:
            self._streams[s.slot] = None
        for s in self._queue.drain_all():
            leftovers.append(s)
        for s in leftovers:
            s.error = err
            s.tokens_q.put(_SENTINEL)
            s.done.set()
        self._work.notify_all()

    def update_params(self, params, draft_params=None) -> None:
        """Swap weights recompile-free — same treedef/shapes → same
        compiled step. Callers quiesce first (the replica cutover's
        inflight guard covers decode dispatches end-to-end); a stream
        admitted after the swap generates entirely under the new tree,
        so no stream ever mixes KV from two versions. Cached prefix
        pages are a function of the weights, so the prefix index is
        flushed here — a retained cache would serve stale KV.

        Under speculative decoding the draft tree swaps in the same
        critical section (the fleet cutover loads BOTH trees before
        calling, so target and draft can never be from different
        versions mid-traffic): pass ``draft_params`` for a separately
        checkpointed draft; a self-drafting engine tracks ``params``
        automatically; otherwise the draft tree is left alone."""
        import jax

        with self._lock:
            self.params = jax.device_put(params)
            if self.speculative is not None:
                if draft_params is not None:
                    self._draft_params = jax.device_put(draft_params)
                elif self.speculative.draft_task is None:
                    self._draft_params = self.params  # self-draft
            if self.prefix_index is not None:
                self.prefix_index.clear()
                self._m_prefix_pages.set(0)

    def flush_prefix_cache(self) -> int:
        """Drop every index-held page (tests / tenant teardown).

        Pages shared by in-flight streams survive under the streams'
        own references; returns pages released by the index."""
        with self._lock:
            if self.prefix_index is None:
                return 0
            released = self.prefix_index.clear()
            self._m_prefix_pages.set(0)
            self._m_free_pages.set(self.pool.free_pages)
            self._m_pool_gauges.update(self.pool)
            return released

    def prefix_cache_stats(self) -> Optional[Dict[str, int]]:
        """Point-in-time index accounting (None when caching is off)."""
        with self._lock:
            if self.prefix_index is None:
                return None
            return {
                "pages_indexed": self.prefix_index.pages_indexed,
                "evictable_pages": self.prefix_index.evictable_pages(),
                "hits": int(self._m_prefix_hits.value_of()),
                "misses": int(self._m_prefix_misses.value_of()),
                "hit_tokens": int(self._m_prefix_hit_tokens.value_of()),
                "evicted_pages": int(self._m_prefix_evicted.value_of()),
            }

    def speculative_stats(self) -> Optional[Dict[str, float]]:
        """Point-in-time speculative accounting (None when off)."""
        with self._lock:
            if self.speculative is None:
                return None
            drafted = self._m_spec_draft.value_of()
            accepted = self._m_spec_accepted.value_of()
            return {
                "drafted_tokens": int(drafted),
                "accepted_tokens": int(accepted),
                "verify_steps": int(self._m_spec_verify.value_of()),
                "fallbacks": int(self._m_spec_fallback.value_of()),
                "acceptance_rate": (accepted / drafted) if drafted
                else 0.0,
                "draft_free_pages": self.draft_pool.free_pages,
            }

    def tenant_page_usage(self) -> Dict[str, int]:
        """Pages currently charged per tenant (the quota ledger) —
        chaos/bench gates sample this to prove isolation held."""
        with self._lock:
            return dict(self._tenant_pages)

    @property
    def active_streams(self) -> int:
        with self._lock:
            return sum(1 for s in self._streams if s is not None)

    @property
    def queue_depth(self) -> int:
        return self._queue.depth

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every accepted stream finished."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        with self._work:
            while self._has_work_locked():
                if deadline is not None and time.monotonic() >= deadline:
                    return False
                self._work.wait(0.05)
        return True

    def metrics_text(self) -> str:
        return self.metrics.render()

    def close(self, timeout: float = 5.0) -> None:
        """Drain, then stop the worker. Streams still unfinished past
        ``timeout`` resolve with a typed ``Unavailable``."""
        with self._lock:
            if self._closed:
                return
        self.drain(timeout)
        with self._lock:
            self._closed = True
            self._work.notify_all()
        if self._worker is not None:
            self._worker.join(timeout)
        with self._lock:
            stranded = [s for s in self._streams if s is not None]
            for s in self._streams:
                if s is not None:
                    self._streams[s.slot] = None
            stranded.extend(self._queue.drain_all())
        err = Unavailable("shutting_down")
        for s in stranded:
            s.error = err
            s.tokens_q.put(_SENTINEL)
            s.done.set()
