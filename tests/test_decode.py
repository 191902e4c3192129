"""Autoregressive decode: paged-pool allocator, admission queue, and
the stepped engine (serving/decode.py, docs/SERVING.md "Autoregressive
decode").

The load-bearing properties:

- **parity**: greedy generation through the paged stepped executable
  equals a full-recompute reference (re-encode the whole prefix every
  token) exactly — token-for-token under fp32 AND bf16 policies;
- **O(1) machinery**: the engine owns ONE compiled signature; streams
  joining and leaving mid-flight cause ZERO new XLA compiles
  (jax.monitoring);
- **allocator**: pages never alias across live streams, recycle fully
  (no leaks), double-free is loud, exhaustion and oversized requests
  produce the typed ``Overloaded`` / ``RequestTooLarge`` vocabulary;
- **continuous batching**: admission is FIFO with page-budget head
  blocking; deadlines shed typed; freed pages re-admit the queue.
"""

import functools
import threading
import time

import numpy as np
import pytest
from conftest import jit_once

import jax.numpy as jnp

from perceiver_tpu.cache import compile_events
from perceiver_tpu.obs import events as events_mod
from perceiver_tpu.obs.events import EventLog
from perceiver_tpu.ops.policy import Policy
from perceiver_tpu.serving.batcher import (
    AdmissionQueue,
    ContinuousBatchScheduler,
    Overloaded,
    TokenBudgetBatcher,
)
from perceiver_tpu.serving.decode import (
    DecodeEngine,
    DecodeGeometry,
    DecodeResult,
    PagePool,
    build_decode_graph,
)
from perceiver_tpu.serving.engine import RequestTooLarge
from perceiver_tpu.tasks.mlm import MaskedLanguageModelTask


VOCAB = 211


def small_task():
    return MaskedLanguageModelTask(
        vocab_size=VOCAB, max_seq_len=48, num_latents=8,
        num_latent_channels=32, num_encoder_layers=2,
        num_encoder_self_attention_layers_per_block=1)


def small_geometry(**kw):
    base = dict(max_streams=4, num_pages=17, page_size=4, max_seq_len=48)
    base.update(kw)
    return DecodeGeometry(**base)


@pytest.fixture(scope="module")
def engine():
    eng = DecodeEngine(small_task(), geometry=small_geometry(),
                       policy=Policy.fp32(), auto_step=False,
                       exec_cache=False)
    yield eng
    eng.close(timeout=2.0)


def _idle(eng):
    """Shared-fixture hygiene: every test leaves the engine empty."""
    assert eng.active_streams == 0
    assert eng.queue_depth == 0
    assert eng.pool.free_pages == eng.geometry.allocatable_pages


# --- PagePool ---------------------------------------------------------------


def test_page_pool_never_hands_out_trash_page():
    pool = PagePool(num_pages=9, page_size=4)
    assert pool.free_pages == 8
    pages = pool.alloc(8)
    assert 0 not in pages
    assert sorted(pages) == list(range(1, 9))


def test_page_pool_alloc_free_conservation_and_no_aliasing():
    rng = np.random.default_rng(0)
    pool = PagePool(num_pages=33, page_size=4)
    live = {}
    for step in range(200):
        if live and (pool.free_pages == 0 or rng.random() < 0.4):
            sid = rng.choice(list(live))
            pool.free(live.pop(sid))
        else:
            n = int(rng.integers(1, 5))
            if n > pool.free_pages:
                continue
            live[step] = pool.alloc(n)
        # invariants on every step: disjoint live sets, conserved total
        held = [p for ps in live.values() for p in ps]
        assert len(held) == len(set(held)), "page aliased across streams"
        assert 0 not in held
        assert pool.free_pages + len(held) == 32, "page leaked"
    for ps in live.values():
        pool.free(ps)
    assert pool.free_pages == 32


def test_page_pool_exhaustion_and_double_free_are_loud():
    pool = PagePool(num_pages=5, page_size=4)
    got = pool.alloc(3)
    with pytest.raises(ValueError, match="exhausted"):
        pool.alloc(2)
    pool.free(got)
    with pytest.raises(ValueError, match="double-free"):
        pool.free(got)


def test_page_pool_recycles_freed_pages():
    pool = PagePool(num_pages=9, page_size=4)
    first = pool.alloc(4)
    pool.free(first)
    second = pool.alloc(4)
    assert set(second) == set(first)  # LIFO recycle, no fragmentation


# --- AdmissionQueue ---------------------------------------------------------


def test_admission_queue_fifo_with_budget_head_blocking():
    q = AdmissionQueue(max_depth=8)
    for name, cost in (("a", 2), ("b", 5), ("c", 1)):
        assert q.offer(name, cost=cost)
    admitted, shed = q.take(budget=3, slots=4)
    # "a" fits; "b" blocks the head even though "c" would fit — FIFO
    # order is the no-starvation guarantee
    assert admitted == ["a"] and shed == []
    assert q.depth == 2
    admitted, _ = q.take(budget=6, slots=4)
    assert admitted == ["b", "c"]


def test_admission_queue_slots_deadline_and_overflow():
    q = AdmissionQueue(max_depth=2)
    assert q.offer("a", cost=1)
    assert q.offer("b", cost=1, deadline=0.0)  # already expired
    assert not q.offer("c", cost=1)  # queue full
    # "a" takes the only slot; the expired "b" sheds in the same call —
    # deadlines are observed even with zero slots/budget left
    admitted, shed = q.take(budget=10, slots=1, now=time.monotonic())
    assert admitted == ["a"] and shed == ["b"]
    assert q.depth == 0


def test_admission_queue_remove_and_drain():
    q = AdmissionQueue(max_depth=4)
    q.offer("a", cost=1)
    q.offer("b", cost=1)
    assert q.remove("a")
    assert not q.remove("zz")
    assert q.drain_all() == ["b"]
    assert q.depth == 0


# --- ContinuousBatchScheduler: unified budget policy -------------------------


def test_scheduler_plan_chunks_budget_math():
    s = ContinuousBatchScheduler(token_budget=8, max_chunk=4)
    # no prefill rows: nothing to plan
    assert s.plan_chunks(3, []) == []
    # decode rows pre-spend 1 each; leftover goes FIFO in max_chunk bites
    assert s.plan_chunks(2, [10, 10, 10]) == [4, 2, 0]
    # fully decode-saturated step: the head prefill row STILL advances
    # one token (anti-starvation) while the rest idle
    assert s.plan_chunks(8, [10, 10]) == [1, 0]
    # a chunk never exceeds the remaining prompt
    assert s.plan_chunks(0, [3, 10]) == [3, 4]
    # no budget configured -> every prefill row gets a full chunk
    unlimited = ContinuousBatchScheduler(max_chunk=4)
    assert unlimited.plan_chunks(5, [10, 2]) == [4, 2]


def test_scheduler_budget_admits_head_rule():
    admits = ContinuousBatchScheduler.budget_admits
    assert admits(0, 999, 8)  # first entry always fits (no wedged head)
    assert admits(3, 5, 8)
    assert not admits(3, 6, 8)


def test_scheduler_validation():
    with pytest.raises(ValueError, match="token_budget"):
        ContinuousBatchScheduler(token_budget=0)
    with pytest.raises(ValueError, match="max_chunk"):
        ContinuousBatchScheduler(max_chunk=0)


def test_admission_queue_and_token_batcher_are_compat_facades():
    """Satellite: the legacy names keep importing and behaving, as thin
    facades over the unified scheduler."""
    assert issubclass(AdmissionQueue, ContinuousBatchScheduler)
    q = AdmissionQueue(max_depth=2)
    assert q.token_budget is None and q.max_chunk == 1
    assert "eprecated" in AdmissionQueue.__doc__
    assert "eprecation" in TokenBudgetBatcher.__doc__
    # the packed batcher's budget rule IS the scheduler's static rule
    done = threading.Event()

    def runner(payloads):
        done.set()
        return [0] * len(payloads)

    tb = TokenBudgetBatcher(runner, token_budget=4, cost_fn=len)
    try:
        fut = tb.submit([1] * 9)  # oversized head still admits
        assert fut.result(timeout=2.0) == 0
        assert done.is_set()
    finally:
        tb.close(timeout=2.0)


# --- geometry ---------------------------------------------------------------


def test_geometry_validation():
    with pytest.raises(ValueError, match="num_pages"):
        DecodeGeometry(max_streams=1, num_pages=1, page_size=4,
                       max_seq_len=16)
    g = small_geometry()
    assert g.pages_per_stream == 12
    assert g.allocatable_pages == 16
    assert g.pages_for(1) == 1
    assert g.pages_for(4) == 1
    assert g.pages_for(5) == 2


def test_geometry_must_fit_model_position_table():
    with pytest.raises(ValueError, match="position table"):
        build_decode_graph(small_task().build(),
                           small_geometry(max_seq_len=64))


# --- engine: parity against full recompute ----------------------------------


@functools.cache
def _reference_next_token(model, policy):
    """The oracle's step as a jitted function of ``(params, ids)``: one
    program a prefix length, shared by every prompt of a model and
    policy, where op by op each operation compiled at each length."""
    from perceiver_tpu.models.perceiver import cross_attention_layer_apply
    from perceiver_tpu.ops.linear import linear_apply

    @jit_once
    def next_token(params, ids):
        latents, _ = model.encoder.apply(params["encoder"], ids,
                                         policy=policy)
        pd = params["decoder"]
        q = policy.cast_param(pd["query"])[ids.shape[1]][None, None]
        hidden = cross_attention_layer_apply(
            pd["cross"], q, latents,
            num_heads=model.decoder.num_cross_attention_heads,
            policy=policy)
        logits = linear_apply(pd["output_adapter"]["linear"], hidden,
                              policy=policy)[0, 0]
        return jnp.argmax(logits.astype(jnp.float32))

    return next_token


def _reference_generate(model, params, policy, prompt, max_new):
    """Full-recompute oracle: re-encode the WHOLE prefix for every
    token, decode one query at the next position. O(T^2) on purpose —
    this is the semantics the paged O(1) path must match exactly."""
    next_token = _reference_next_token(model, policy)
    toks = [int(t) for t in prompt]
    for _ in range(max_new):
        toks.append(int(next_token(params,
                                   jnp.asarray(toks, jnp.int32)[None])))
    return toks[len(prompt):]


@pytest.mark.parametrize("policy_name", ["fp32", "bf16"])
def test_paged_decode_matches_full_recompute(policy_name):
    policy = getattr(Policy, policy_name)()
    eng = DecodeEngine(small_task(), geometry=small_geometry(),
                       policy=policy, auto_step=False, exec_cache=False)
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, VOCAB, size=n).astype(np.int32)
                   for n in (5, 1, 9)]
        handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run_until_idle()
        for h, p in zip(handles, prompts):
            got = h.result(timeout=1.0)
            assert isinstance(got, DecodeResult)
            ref = _reference_generate(eng.graph.model, eng.params,
                                      policy, p, 6)
            assert got.tokens == ref, (
                f"{policy_name} stream diverged: paged {got.tokens} "
                f"vs full-recompute {ref}")
        _idle(eng)
    finally:
        eng.close(timeout=2.0)


@pytest.mark.parametrize("policy_name", ["fp32", "bf16"])
def test_chunked_prefill_parity_across_chunk_sizes(policy_name):
    """Token-exact parity of chunked prefill: the SAME prompt split
    into chunks of 1 (pure stepwise), 4 (mid, uneven final chunk), and
    >= prompt_len (one-shot prefill) generates identical tokens, each
    equal to the full-recompute oracle — the ragged kernel's causal
    cache writes are position-exact regardless of how the prompt was
    sliced across steps."""
    policy = getattr(Policy, policy_name)()
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, VOCAB, size=9).astype(np.int32)
    ref = None
    outs = {}
    for chunk in (1, 4, 9):
        eng = DecodeEngine(small_task(),
                           geometry=small_geometry(max_chunk=chunk),
                           policy=policy, auto_step=False,
                           exec_cache=False)
        try:
            h = eng.submit(prompt, max_new_tokens=5)
            eng.run_until_idle()
            got = h.result(timeout=1.0)
            assert isinstance(got, DecodeResult)
            outs[chunk] = got.tokens
            if ref is None:  # params are seed-deterministic across engines
                ref = _reference_generate(eng.graph.model, eng.params,
                                          policy, prompt, 5)
            _idle(eng)
        finally:
            eng.close(timeout=2.0)
    for chunk, toks in outs.items():
        assert toks == ref, (
            f"{policy_name} max_chunk={chunk} diverged: chunked "
            f"{toks} vs full-recompute {ref}")


@pytest.mark.parametrize("policy_name", ["fp32", "bf16"])
def test_prefix_cache_warm_decode_token_exact(policy_name):
    """ISSUE 18 merge gate: a warm stream (prefix-cache hit — shared
    pages for the cached span, tail through normal chunk prefill)
    generates tokens bitwise identical to a cold prefill of the same
    prompt on a caching-disabled engine, for every cached-span/tail
    split, under fp32 AND bf16, with ZERO new XLA compiles — and stays
    exact after eviction forces a cold re-prefill and re-publication.
    (The cold paged path itself is anchored to the full-recompute
    oracle by test_paged_decode_matches_full_recompute; params are
    seed-deterministic across engines, so cold-engine output IS the
    oracle here. bf16 is the policy where a near-miss would show:
    any KV delta on a shared page flips low-mantissa logits first.)"""
    from perceiver_tpu.serving.prefix_cache import PrefixCacheConfig

    policy = getattr(Policy, policy_name)()
    rng = np.random.default_rng(18)
    seed_prompt = rng.integers(0, VOCAB, size=17).astype(np.int32)
    eng = DecodeEngine(small_task(),
                       geometry=small_geometry(num_pages=33),
                       policy=policy, auto_step=False, exec_cache=False,
                       prefix_cache=PrefixCacheConfig())
    cold_eng = DecodeEngine(small_task(),
                            geometry=small_geometry(num_pages=33),
                            policy=policy, auto_step=False,
                            exec_cache=False)
    try:
        h = eng.submit(seed_prompt, max_new_tokens=2)
        eng.run_until_idle()
        assert h.result(1.0).cached_tokens == 0  # nothing cached yet
        assert eng.prefix_index.pages_indexed == 4  # 17 // 4 full pages

        def run_one(prompt, expect_cached):
            h = eng.submit(prompt, max_new_tokens=5)
            with compile_events() as events:
                eng.run_until_idle()
            assert events == [], f"sharing recompiled: {events}"
            got = h.result(timeout=1.0)
            assert isinstance(got, DecodeResult)
            assert got.cached_tokens == expect_cached
            hc = cold_eng.submit(prompt, max_new_tokens=5)
            cold_eng.run_until_idle()
            cold = hc.result(timeout=1.0)
            assert cold.cached_tokens == 0
            assert got.tokens == cold.tokens, (
                f"{policy_name} warm stream (cached={expect_cached}, "
                f"len={len(prompt)}) diverged: {got.tokens} vs cold "
                f"prefill {cold.tokens}")
            return got

        # every cached-span/tail split: k shared pages + t-token tail
        # through private chunk prefill (incl. tails that themselves
        # span a full page and publish new branches)
        for k, t in ((1, 1), (1, 3), (2, 1), (2, 4), (3, 2)):
            tail = rng.integers(0, VOCAB, size=t).astype(np.int32)
            run_one(np.concatenate([seed_prompt[:4 * k], tail]),
                    expect_cached=4 * k)

        # evict every chain (engine idle: all pages are index-only),
        # then the same prompt re-prefills cold, re-publishes, and
        # hits warm again — all three token-identical
        with eng._lock:
            evicted = eng.prefix_index.evict(
                eng.prefix_index.pages_indexed)
        assert evicted > 0 and eng.prefix_index.pages_indexed == 0
        prompt = np.concatenate(
            [seed_prompt[:8],
             rng.integers(0, VOCAB, size=2).astype(np.int32)])
        cold = run_one(prompt, expect_cached=0)  # post-eviction miss
        rewarm = run_one(prompt, expect_cached=8)  # re-published hit
        assert rewarm.tokens == cold.tokens
        # hygiene: dropping the index refs makes the arena whole again
        eng.flush_prefix_cache()
        assert eng.pool.free_pages == eng.geometry.allocatable_pages
    finally:
        eng.close(timeout=2.0)
        cold_eng.close(timeout=2.0)


def test_chunked_prefill_spans_events_and_metrics():
    """A 9-token prompt through max_chunk=4 prefills in exactly 3
    steps (4+4+1); the completing step emits the first token. The obs
    plane must show it: 3 ``prefill_chunk`` spans with those chunk
    sizes, one ``stream_admitted`` and one ``prefill_complete`` event,
    and the prefill counters advanced."""
    prev = events_mod.set_default_log(EventLog())
    eng = DecodeEngine(small_task(), geometry=small_geometry(max_chunk=4),
                       policy=Policy.fp32(), auto_step=False,
                       exec_cache=False)
    try:
        prompt = (np.arange(9, dtype=np.int32) * 13 + 1) % VOCAB
        h = eng.submit(prompt, max_new_tokens=3)
        eng.run_until_idle()
        assert isinstance(h.result(1.0), DecodeResult)
        log = events_mod.default_log()
        assert [e["stream"] for e in log.events("stream_admitted")] == [
            h.stream_id]
        done = log.events("prefill_complete")
        assert [(e["stream"], e["prompt_tokens"], e["chunks"])
                for e in done] == [(h.stream_id, 9, 3)]
        from perceiver_tpu.obs import trace as trace_mod
        spans = trace_mod.default_buffer().get(h.trace_ctx.trace_id)
        pf = [s for s in spans if s["phase"] == "prefill_chunk"]
        assert [s["attrs"]["chunk"] for s in pf] == [4, 4, 1]
        assert [s["attrs"]["fed"] for s in pf] == [4, 8, 9]
        emits = [s for s in spans if s["phase"] == "token_emit"]
        assert len(emits) == 3
        # first token came out of the completing prefill step, not a
        # later decode-only step: its span end == last chunk's end
        assert emits[0]["end"] == pf[-1]["end"]
        text = eng.metrics_text()
        assert "serving_decode_prefill_chunks_total 3" in text
        assert "serving_decode_prefill_tokens_total 9" in text
        _idle(eng)
    finally:
        eng.close(timeout=2.0)
        events_mod.set_default_log(prev)


def test_token_budget_paces_prefill_but_never_decode():
    """With token_budget=2 and one stream already decoding, a new
    prompt prefills at 1 token/step (head-row minimum) while the
    decoding stream keeps emitting every step — decode rows are never
    stalled behind prefill."""
    prev = events_mod.set_default_log(EventLog())
    eng = DecodeEngine(small_task(), geometry=small_geometry(max_chunk=4),
                       policy=Policy.fp32(), auto_step=False,
                       exec_cache=False, token_budget=2)
    try:
        a = eng.submit(np.asarray([5, 6], np.int32), max_new_tokens=12)
        eng.step()  # a prefills (2 tokens, budget head-min covers it)
        b = eng.submit(np.asarray([7] * 8, np.int32), max_new_tokens=2)
        eng.run_until_idle()
        ra, rb = a.result(1.0), b.result(1.0)
        assert isinstance(ra, DecodeResult) and len(ra.tokens) == 12
        assert isinstance(rb, DecodeResult) and len(rb.tokens) == 2
        done = {e["stream"]: e for e in
                events_mod.default_log().events("prefill_complete")}
        # b's 8-token prompt was throttled to 1 token/step: 8 chunks
        assert done[b.stream_id]["chunks"] == 8
        _idle(eng)
    finally:
        eng.close(timeout=2.0)
        events_mod.set_default_log(prev)


def test_parity_survives_scrambled_page_placement(engine):
    """The same prompt admitted before vs after heavy churn (different
    physical pages) generates identical tokens."""
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, VOCAB, size=7).astype(np.int32)
    first = engine.submit(prompt, max_new_tokens=5)
    engine.run_until_idle()
    # churn the allocator so the replay lands on different pages
    churn = [engine.submit(
        rng.integers(0, VOCAB, size=int(rng.integers(1, 12))),
        max_new_tokens=int(rng.integers(1, 8))) for _ in range(6)]
    engine.run_until_idle()
    again = engine.submit(prompt, max_new_tokens=5)
    engine.run_until_idle()
    for h in churn:
        assert isinstance(h.result(0.5), DecodeResult)
    assert again.result(0.5).tokens == first.result(0.5).tokens
    _idle(engine)


# --- engine: O(1) machinery -------------------------------------------------


def test_streams_join_and_leave_with_zero_new_compiles(engine):
    """The merge-gate property at test scale: after engine warmup,
    arbitrary join/leave churn reuses the ONE compiled step."""
    rng = np.random.default_rng(2)
    handles = []
    with compile_events() as events:
        # wave 1: fill some slots
        for n in (3, 8):
            handles.append(engine.submit(
                rng.integers(0, VOCAB, size=n).astype(np.int32),
                max_new_tokens=10))
        for _ in range(4):
            engine.step()
        # wave 2: join mid-flight while wave 1 still generates
        for n in (1, 5):
            handles.append(engine.submit(
                rng.integers(0, VOCAB, size=n).astype(np.int32),
                max_new_tokens=3))
        engine.run_until_idle()
    assert events == [], f"post-warmup XLA compiles: {events}"
    for h, want in zip(handles, (10, 10, 3, 3)):
        r = h.result(timeout=1.0)
        assert isinstance(r, DecodeResult)
        assert len(r.tokens) == want
    _idle(engine)


# What the deleted wall-clock gates (a load generator's p95 ratios on
# this toy) also held as counts, exact on any backend: one run of joins
# and leaves stepped by hand, and one shared-prefix run of a cold arm,
# a seed and a warm arm.

_CHURN_PLANS = ((6, 24), (6, 12), (4, 14), (6, 16), (6, 10), (4, 9))


@pytest.fixture(scope="module")
def churn_run():
    from perceiver_tpu.obs import trace as trace_mod

    buf = trace_mod.TraceBuffer(max_traces=16, max_spans_per_trace=256)
    prev = trace_mod.set_default_buffer(buf)
    geometry = small_geometry(num_pages=49, max_chunk=4)
    eng = DecodeEngine(small_task(), geometry=geometry,
                       policy=Policy.fp32(), auto_step=False,
                       exec_cache=False)
    rng = np.random.default_rng(30)
    steps_at = [[] for _ in _CHURN_PLANS]
    handles = []

    def submit(i):
        prompt_len, max_new = _CHURN_PLANS[i]
        handles.append(eng.submit(
            rng.integers(0, VOCAB, size=prompt_len).astype(np.int32),
            max_new_tokens=max_new,
            on_token=lambda tok: steps_at[i].append(eng._m_steps.value)))

    try:
        with compile_events() as compiles:
            submit(0), submit(1)
            for _ in range(4):
                eng.step()
            # four slots: the last two wait for a stream to leave
            for i in range(2, len(_CHURN_PLANS)):
                submit(i)
            eng.run_until_idle()
        results = [h.result(timeout=1.0) for h in handles]
        spans = [buf.get(h.trace_ctx.trace_id) for h in handles]
        _idle(eng)
    finally:
        eng.close(timeout=2.0)
        trace_mod.set_default_buffer(prev)
    return {"descriptor": geometry.descriptor, "compiles": list(compiles),
            "results": results, "steps_at": steps_at, "spans": spans}


def _phase(spans, name):
    return [s for s in spans if s["phase"] == name]


def _zero_compiles_and_geometry_key(run):
    assert run["compiles"] == [], run["compiles"]
    for (_, max_new), r in zip(_CHURN_PLANS, run["results"]):
        assert r.finished == "complete" and len(r.tokens) == max_new
    # slots and chunk lanes are key material of the one executable
    assert run["descriptor"] == "r4_p49x4_s48_q4"


def _dispatches_per_token_do_not_grow(run):
    # O(1) as a count: a stream's tokens 1-8 and its last 8 take one
    # dispatch each, however long the stream already is
    for (_, max_new), at in zip(_CHURN_PLANS, run["steps_at"]):
        assert len(at) == max_new
        assert at[8] - at[0] == 8 == at[-1] - at[-9], at


def _one_queue_wait_and_first_decode_a_stream(run):
    for (prompt_len, _), spans in zip(_CHURN_PLANS, run["spans"]):
        assert len(_phase(spans, "queue_wait")) == 1
        first_emit = min(s["end"] for s in _phase(spans, "token_emit"))
        # the step that emitted token 0 is the chunk that completed
        # the prompt: one such step a stream
        chunks = _phase(spans, "prefill_chunk")
        assert [s["end"] for s in chunks].count(first_emit) == 1
        assert len(chunks) == -(-prompt_len // 4)


def _single_chunk_prompt_reports_a_prefill_phase(run):
    singles = [spans for (prompt_len, _), spans
               in zip(_CHURN_PLANS, run["spans"]) if prompt_len <= 4]
    assert len(singles) == 2
    for spans in singles:
        (chunk,) = _phase(spans, "prefill_chunk")
        assert chunk["attrs"]["chunk"] == chunk["attrs"]["fed"] == 4


@pytest.mark.parametrize("count", [
    _zero_compiles_and_geometry_key, _dispatches_per_token_do_not_grow,
    _one_queue_wait_and_first_decode_a_stream,
    _single_chunk_prompt_reports_a_prefill_phase],
    ids=lambda f: f.__name__.strip("_"))
def test_churn_run_counts(churn_run, count):
    count(churn_run)


@pytest.fixture(scope="module")
def shared_prefix_run():
    from perceiver_tpu.serving.prefix_cache import PrefixCacheConfig

    streams, prefix, tail = 8, 16, 6
    eng = DecodeEngine(
        small_task(),
        geometry=small_geometry(max_streams=streams, num_pages=97),
        policy=Policy.fp32(), auto_step=False, exec_cache=False,
        prefix_cache=PrefixCacheConfig())
    rng = np.random.default_rng(31)

    def ids(n):
        return rng.integers(0, VOCAB, size=n).astype(np.int32)

    def arm(prefixes):
        before = eng._m_prefill_tokens.value
        handles = [eng.submit(np.concatenate([p, ids(tail)]),
                              max_new_tokens=3) for p in prefixes]
        eng.run_until_idle()
        return ([h.result(timeout=1.0) for h in handles],
                eng._m_prefill_tokens.value - before)

    shared = ids(prefix)
    try:
        with compile_events() as compiles:
            cold, cold_tokens = arm([ids(prefix) for _ in range(streams)])
            arm([shared])      # the seed publishes the shared chain
            warm, warm_tokens = arm([shared] * streams)
        stats = eng.prefix_cache_stats()
    finally:
        eng.close(timeout=2.0)
    return {"cold": cold, "warm": warm, "cold_tokens": cold_tokens,
            "warm_tokens": warm_tokens, "stats": stats,
            "compiles": list(compiles)}


def _every_warm_stream_hits_the_seed(run):
    assert all(r.cached_tokens == 0 for r in run["cold"])
    hits = [r.cached_tokens for r in run["warm"]]
    assert sum(1 for c in hits if c > 0) / len(hits) == 1.0
    assert sum(hits) == 8 * 16 == 128


def _pages_are_indexed_with_no_compile(run):
    assert run["stats"]["pages_indexed"] > 0
    assert run["compiles"] == [], run["compiles"]


def _warm_arm_prefills_fewer_tokens(run):
    # what the warm/cold time-to-first-token ratio stood for: the
    # cached span is not fed through the step again
    assert run["cold_tokens"] == 8 * (16 + 6)
    assert run["warm_tokens"] == 8 * 6 < run["cold_tokens"]


@pytest.mark.parametrize("count", [
    _every_warm_stream_hits_the_seed, _pages_are_indexed_with_no_compile,
    _warm_arm_prefills_fewer_tokens],
    ids=lambda f: f.__name__.strip("_"))
def test_shared_prefix_run_counts(shared_prefix_run, count):
    count(shared_prefix_run)


def test_steady_state_is_sync_free_except_next_token(engine):
    """One step = one device sync (the next_token materialize); the
    transfer guard in the graph gates covers the lowered step, this
    covers the host loop: lengths/tables upload only when dirty."""
    h = engine.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=4)
    engine.step()  # admission upload happens here (dirty)
    assert engine._dirty is False
    engine.step()
    assert engine._dirty is False  # steady state: no host mirrors moved
    engine.run_until_idle()
    assert isinstance(h.result(0.5), DecodeResult)
    _idle(engine)


# --- engine: typed overload / too-large vocabulary --------------------------


def test_request_too_large_raises_at_submit(engine):
    with pytest.raises(RequestTooLarge, match="max_seq_len"):
        engine.submit(np.arange(40, dtype=np.int32), max_new_tokens=20)
    g = small_geometry(num_pages=3)  # 2 allocatable pages = 8 tokens
    eng = DecodeEngine(small_task(), geometry=g, policy=Policy.fp32(),
                       auto_step=False, exec_cache=False)
    try:
        with pytest.raises(RequestTooLarge, match="pages"):
            eng.submit(np.arange(10, dtype=np.int32), max_new_tokens=10)
    finally:
        eng.close(timeout=2.0)
    _idle(engine)


def test_pool_exhaustion_queues_then_admits_after_frees(engine):
    """More streams than pages: the excess WAITS (FIFO) and admits as
    predecessors finish and their pages recycle — continuous batching,
    not an error."""
    rng = np.random.default_rng(3)
    # each stream needs ceil((12+4-1)/4) = 4 pages; 16 allocatable →
    # 4 fit, the 5th+6th queue
    handles = [engine.submit(
        rng.integers(0, VOCAB, size=12).astype(np.int32),
        max_new_tokens=4) for _ in range(6)]
    engine.step()
    assert engine.active_streams == 4
    assert engine.queue_depth == 2
    engine.run_until_idle()
    for h in handles:
        r = h.result(timeout=1.0)
        assert isinstance(r, DecodeResult) and len(r.tokens) == 4
    _idle(engine)


def test_queue_full_sheds_typed_overloaded():
    eng = DecodeEngine(small_task(), geometry=small_geometry(),
                       policy=Policy.fp32(), auto_step=False,
                       exec_cache=False, max_queue=1)
    try:
        big = np.arange(12, dtype=np.int32)
        # nothing drains between submits (auto_step=False), so exactly
        # one enqueues and the rest shed typed at submit time
        handles = [eng.submit(big, max_new_tokens=4) for _ in range(6)]
        shed = [h.result(0.1) for h in handles
                if h.done() and isinstance(h.result(0.1), Overloaded)]
        assert len(shed) == 5
        assert all(r.reason == "queue_full" for r in shed)
        eng.run_until_idle()
        served = [h.result(1.0) for h in handles]
        assert sum(isinstance(r, DecodeResult) for r in served) == 1
    finally:
        eng.close(timeout=2.0)


def test_admission_deadline_sheds_typed_overloaded(engine):
    rng = np.random.default_rng(4)
    big = rng.integers(0, VOCAB, size=12).astype(np.int32)
    # 4 × ceil((12+5-1)/4) = 4 × 4 pages saturates all 16, so the
    # deadline stream cannot admit until a blocker finishes
    blockers = [engine.submit(big, max_new_tokens=5) for _ in range(4)]
    engine.step()
    assert engine.active_streams == 4
    doomed = engine.submit(big, max_new_tokens=4, timeout_ms=0.01)
    time.sleep(0.02)
    engine.step()  # admission attempt observes the expired deadline
    r = doomed.result(timeout=0.5)
    assert isinstance(r, Overloaded) and r.reason == "deadline"
    engine.run_until_idle()
    for h in blockers:
        assert isinstance(h.result(1.0), DecodeResult)
    _idle(engine)


# --- engine: streaming delivery ---------------------------------------------


def test_on_token_callback_and_iterator_stream_live():
    eng = DecodeEngine(small_task(), geometry=small_geometry(),
                       policy=Policy.fp32(), auto_step=True,
                       exec_cache=False)
    try:
        seen = []
        h = eng.submit(np.asarray([4, 5, 6], np.int32),
                       max_new_tokens=5, on_token=seen.append)
        streamed = list(h.tokens())  # blocking iterator, ends at close
        r = h.result(timeout=2.0)
        assert isinstance(r, DecodeResult)
        assert streamed == r.tokens == seen
        assert len(streamed) == 5
        assert r.ttft_s is not None and r.ttft_s >= 0.0
    finally:
        eng.close(timeout=2.0)


def test_cancel_frees_pages_mid_flight(engine):
    h = engine.submit(np.asarray([1, 2], np.int32), max_new_tokens=30)
    engine.step()
    assert engine.active_streams == 1
    assert h.cancel()
    assert not h.cancel()  # idempotent
    r = h.result(timeout=0.5)
    assert isinstance(r, DecodeResult) and r.finished == "cancelled"
    _idle(engine)


def test_stream_events_and_metrics(engine):
    prev = events_mod.set_default_log(EventLog())
    try:
        h = engine.submit(np.asarray([9], np.int32), max_new_tokens=2)
        engine.run_until_idle()
        assert isinstance(h.result(0.5), DecodeResult)
        log = events_mod.default_log()
        opens = log.events("stream_open")
        closes = log.events("stream_close")
        assert [e["stream"] for e in opens] == [h.stream_id]
        assert [(e["stream"], e["tokens"]) for e in closes] == [
            (h.stream_id, 2)]
    finally:
        events_mod.set_default_log(prev)
    text = engine.metrics_text()
    assert "serving_decode_steps_total" in text
    assert "serving_decode_tokens_total" in text
    assert "serving_decode_ttft_seconds" in text
    _idle(engine)


# --- GenerationServer (text in, streamed text out) --------------------------


def make_tiny_tokenizer():
    from perceiver_tpu.tokenizer import create_tokenizer, train_tokenizer
    from perceiver_tpu.tokenizer.wordpiece import Replace

    corpus = ["the quick brown fox jumps over the lazy dog",
              "the lazy dog sleeps deeply near the quick fox",
              "a quick movie about a lazy brown dog"] * 5
    tok = create_tokenizer(Replace("<br />", " "))
    train_tokenizer(tok, corpus, vocab_size=VOCAB)
    assert tok.get_vocab_size() <= VOCAB
    return tok


def test_generation_server_generate_and_stream():
    from perceiver_tpu.serving.api import Generation, GenerationServer

    eng = DecodeEngine(small_task(), geometry=small_geometry(),
                       policy=Policy.fp32(), auto_step=True,
                       exec_cache=False)
    server = GenerationServer(eng, make_tiny_tokenizer())
    try:
        gen = server.generate("the quick brown", max_new_tokens=4,
                              timeout=10.0)
        assert isinstance(gen, Generation)
        assert len(gen.token_ids) == 4
        assert gen.text.startswith(gen.prompt_text)
        assert gen.ttft_s is not None
        # the incremental path generates the SAME tokens (greedy
        # decode is deterministic regardless of delivery shape)
        pieces = list(server.stream("the quick brown",
                                    max_new_tokens=4))
        assert pieces == [server.token_text(t) for t in gen.token_ids]
    finally:
        server.close(timeout=2.0)
