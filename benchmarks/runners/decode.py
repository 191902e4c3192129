"""Decode runner: generation requests through the program's
``GenerationServer`` over a ``DecodeEngine``, timed from the client's
side.

Clients see what a user sees: ``submit(text, max_new_tokens, on_token)``
and the token ids as they come. Time to first token runs from the
submission to the first ``on_token``; a gap is the time between two
consecutive tokens of one stream. The window is ``--seconds`` long on
the host clock; tokens count when they arrive inside it. The mix's
``clients`` waiting callers each send the next request when the last
has completed (a closed loop).

Once the window has closed and the in-flight streams have finished, the
engine is freed and the float32 reference is run over a seeded sample
of the finished requests, the longest among them: one full forward pass
per served token over its whole prefix, compared by how far the served
token's logit lies below the reference's best.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks import comparisons, traffic, weights
from benchmarks.harness import (
    CACHE_DIR,
    BenchmarkError,
    Context,
    Outcome,
    memory_peak_bytes,
    say,
)


@dataclasses.dataclass
class Sent:
    """One request as the client saw it."""

    request: traffic.Request
    t_submit: float                    # perf_counter
    token_times: List[float] = dataclasses.field(default_factory=list)
    handle: object = None
    result: object = None
    error: Optional[str] = None

    @property
    def tokens(self) -> List[int]:
        return list(getattr(self.result, "tokens", []) or [])


def make_tokenizer(vocab_size: int, build_dir: str):
    """A WordPiece tokenizer whose vocabulary is the special tokens plus
    one word ``w<id>`` per remaining id, so that seeded ids go through
    the server's text interface and come back as themselves (after
    ``chip_smoke.py``). The native engine is built into ``build_dir``
    once per checkout."""
    import os

    from perceiver_tpu.tokenizer import SPECIAL_TOKENS, create_tokenizer
    from perceiver_tpu.tokenizer import native

    os.makedirs(build_dir, exist_ok=True)
    native.load(build_dir)
    tok = create_tokenizer()
    vocab = {t: i for i, t in enumerate(SPECIAL_TOKENS)}
    for i in range(len(SPECIAL_TOKENS), vocab_size):
        vocab[f"w{i}"] = i
    tok.vocab = vocab
    tok.ids_to_tokens = {i: t for t, i in vocab.items()}
    if tok._native_vocab() is None:
        raise BenchmarkError("the native tokenizer is unavailable")
    return tok


def to_text(ids) -> str:
    return " ".join(f"w{int(i)}" for i in ids)


class Clients:
    """Sends the mix's requests and stamps what comes back."""

    def __init__(self, ctx: Context, server, lanes):
        self.ctx, self.server, self.lanes = ctx, server, lanes
        self.sent: List[Sent] = []
        self._lock = threading.Lock()
        self.timeout = float(ctx.mix["request_timeout_s"])

    def send(self, request: traffic.Request, t_submit: float) -> Sent:
        sent = Sent(request, t_submit)
        with self._lock:
            self.sent.append(sent)
        try:
            sent.handle = self.server.submit(
                to_text(request.prompt), max_new_tokens=request.max_new,
                on_token=lambda _tok: sent.token_times.append(
                    time.perf_counter()))
        except Exception as e:  # noqa: BLE001 - a refused request is a failure, counted
            sent.error = f"{type(e).__name__}: {e}"[:200]
        return sent

    def wait(self, sent: Sent) -> None:
        if sent.handle is None:
            return
        try:
            sent.result = sent.handle.result(self.timeout)
        except Exception as e:  # noqa: BLE001 - a timeout or a failed stream is a failure, counted
            sent.error = f"{type(e).__name__}: {e}"[:200]

    def closed_loop(self, t_close: float) -> None:
        def client(lane):
            while time.perf_counter() < t_close:
                with self.ctx.spans.span("client_request"):
                    sent = self.send(next(lane), time.perf_counter())
                    self.wait(sent)

        threads = [threading.Thread(target=client, args=(lane,),
                                    name=f"client-{i}")
                   for i, lane in enumerate(self.lanes)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(self.timeout + 60.0)
            if t.is_alive():
                raise BenchmarkError(f"{t.name} did not finish")


def failure(sent: Sent) -> Optional[str]:
    """Why the request counts as failed, or None."""
    if sent.error:
        return sent.error
    r = sent.result
    if r is None:
        return "no result"
    if getattr(r, "finished", None) != "complete":
        return f"finished as {getattr(r, 'finished', type(r).__name__)}"
    if len(r.tokens) != sent.request.max_new or \
            len(sent.token_times) != sent.request.max_new:
        return (f"{len(r.tokens)} tokens, {len(sent.token_times)} "
                f"delivered, of {sent.request.max_new}")
    return None


def percentile(values, q: float) -> float:
    """The q-th percentile by the nearest rank above, which never
    interpolates beyond a sample."""
    values = np.sort(np.asarray(values, np.float64))
    return float(values[min(len(values) - 1,
                            int(np.ceil(q / 100.0 * len(values))) - 1)])


def window_metrics(sent: List[Sent], t_open: float, t_close: float,
                   seconds: float) -> Dict[str, float]:
    tokens, ttft, gaps = 0, [], []
    for s in sent:
        times = s.token_times
        tokens += sum(1 for t in times if t_open <= t <= t_close)
        if times and times[0] <= t_close:
            ttft.append(times[0] - s.t_submit)
        gaps.extend(b - a for a, b in zip(times, times[1:])
                    if b <= t_close)
    if len(ttft) < 2 or len(gaps) < 2:
        raise BenchmarkError(
            f"the window holds {len(ttft)} first tokens and {len(gaps)} "
            "gaps: nothing to take a tail of")
    say(f"window: {tokens} tokens; {len(ttft)} first tokens (median "
        f"{np.median(ttft) * 1e3:.1f} ms), {len(gaps)} gaps (median "
        f"{np.median(gaps) * 1e3:.1f} ms)")
    return {"decode_tokens_per_s": tokens / seconds,
            "itl_p95_ms": percentile(gaps, 95) * 1e3,
            "ttft_p90_ms": percentile(ttft, 90) * 1e3}


def sample_for_check(sent: List[Sent], seed: int, budget: int) -> List[Sent]:
    """Finished requests to compare: the longest (prompt + output), then
    a seeded draw until about ``budget`` served tokens are covered."""
    done = [s for s in sent if failure(s) is None]
    if not done:
        return []
    done.sort(key=lambda s: -(len(s.request.prompt) + s.request.max_new))
    chosen, rest = [done[0]], done[1:]
    order = np.random.default_rng([int(seed), 7]).permutation(len(rest))
    for i in order:
        if sum(s.request.max_new for s in chosen) >= budget:
            break
        chosen.append(rest[i])
    return chosen


def reference_logits(ctx: Context, shapes, chosen: List[Sent],
                     prec: str = "f32") -> List[np.ndarray]:
    """For each chosen request, the reference's logits at every served
    position, (tokens, vocabulary): position ``n + k`` is decoded from
    the prompt and the first ``k`` served tokens."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import perceiver_io as ref

    cfg = ctx.cfg
    width, batch = cfg["max_seq_len"], ctx.mix["reference_batch"]
    params = weights.make_weights(shapes, ctx.seed)
    fn = jax.jit(lambda p, ids, n: ref.next_token_logits(
        p, ids, n, cfg, prec))
    rows = []
    for r, s in enumerate(chosen):
        seq = np.concatenate([s.request.prompt, s.tokens]).astype(np.int32)
        for k in range(s.request.max_new):
            rows.append((r, seq, len(s.request.prompt) + k))
    out: List[List[np.ndarray]] = [[] for _ in chosen]
    for i in range(0, len(rows), batch):
        part = rows[i:i + batch]
        ids = np.zeros((batch, width), np.int32)
        n = np.ones((batch,), np.int32)
        for j, (_, seq, pos) in enumerate(part):
            ids[j, :pos] = seq[:pos]
            n[j] = pos
        logits = np.asarray(fn(params, jnp.asarray(ids), jnp.asarray(n)))
        for j, (r, _, _) in enumerate(part):
            out[r].append(logits[j])
    return [np.stack(x) for x in out]


def geometry(dep: dict):
    from perceiver_tpu.serving.decode import DecodeGeometry

    return DecodeGeometry(
        max_streams=dep["max_streams"], page_size=dep["page_size"],
        max_seq_len=dep["max_seq_len"], num_pages=dep["num_pages"],
        max_chunk=dep["max_chunk"])


def run(ctx: Context) -> Outcome:
    import os

    import jax

    from perceiver_tpu import cache
    from perceiver_tpu.obs import trace as program_trace
    from perceiver_tpu.ops.policy import Policy
    from perceiver_tpu.serving.api import GenerationServer
    from perceiver_tpu.serving.decode import DecodeEngine
    from perceiver_tpu.serving.prefix_cache import PrefixCacheConfig

    mix, dep, cfg = ctx.mix, ctx.deployment, ctx.cfg
    if ctx.trace:
        # the program's own spans, kept whole: the kernel's roofline
        # reads each step's cached lengths from them
        program_trace.set_default_buffer(program_trace.TraceBuffer(
            max_traces=1 << 16, max_spans_per_trace=1 << 12))
    cls, kwargs = ctx.task.program_task(cfg)
    task = cls(**kwargs)
    shapes = jax.eval_shape(task.build().init, jax.random.key(0))
    ctx.mark("imports done")
    tok = make_tokenizer(cfg["vocab_size"],
                         os.path.join(CACHE_DIR, "tokenizer"))
    ctx.mark("tokenizer ready")
    policy = Policy.bf16() if dep["precision"] == "bf16" else Policy.fp32()
    with ctx.spans.span("build_engine"):
        engine = DecodeEngine(
            task, weights.make_weights(shapes, ctx.seed),
            geometry=geometry(dep), policy=policy,
            attn_impl=dep["attn_impl"],
            prefix_cache=PrefixCacheConfig() if dep["prefix_cache"]
            else None, seed=weights.seed31(ctx.seed))
    ctx.mark("engine built")
    server = GenerationServer(engine, tok)
    clients = Clients(ctx, server,
                      traffic.request_lanes(mix, cfg, ctx.seed))
    try:
        with ctx.spans.span("warmup"):
            warm = [clients.send(r, time.perf_counter())
                    for r in traffic.warmup_requests(mix, cfg, ctx.seed)]
            for s in warm:
                clients.wait(s)
                if failure(s):
                    raise BenchmarkError(f"warm-up failed: {failure(s)}")
        clients.sent.clear()
        ctx.mark("warm-up requests served")
        compiles: List[float] = []
        listener = cache.register_compile_listener(compiles.append)
        ctx.tracer.start()
        t_open = time.perf_counter()
        t_close = t_open + ctx.seconds
        with ctx.spans.span("window"):
            clients.closed_loop(t_close)
        ctx.tracer.stop()
        cache.unregister_compile_listener(listener)
        sent = list(clients.sent)
        registry = engine.metrics
        program_spans = _program_spans(sent) if ctx.trace else {}
        peak = None if ctx.rehearse else memory_peak_bytes()
        say(f"pool: {engine.pool.free_pages} of {dep['num_pages']} pages "
            "free at the window's end")
    finally:
        server.close()
    failures = [f for f in map(failure, sent) if f]
    for f in failures[:5]:
        say(f"failed request: {f}")
    metrics = window_metrics(sent, t_open, t_close, ctx.seconds)
    del engine, server, clients

    chosen = sample_for_check(sent, ctx.seed, mix["check_tokens"])
    t0 = time.perf_counter()
    with ctx.spans.span("reference"):
        logits = reference_logits(ctx, shapes, chosen)
    gaps = [comparisons.widest_logit_gap(lg, s.tokens)
            for lg, s in zip(logits, chosen)]
    served = sum(len(s.tokens) for s in chosen)
    exact = sum(int(np.sum(lg.argmax(axis=1) == np.asarray(s.tokens)))
                for lg, s in zip(logits, chosen))
    say(f"reference: {served} served tokens of {len(chosen)} requests "
        f"(longest {len(chosen[0].request.prompt) if chosen else 0} + "
        f"{chosen[0].request.max_new if chosen else 0}) in "
        f"{time.perf_counter() - t0:.1f} s; {exact} are the reference's "
        f"first choice; logit spread at a position "
        f"{float(np.mean([lg.std() for lg in logits])) if logits else 0:.3f}")
    control_checks = None
    if ctx.control:
        # the lower precision need not decode: at the same positions,
        # the gap of the token it puts first
        low = reference_logits(ctx, shapes, chosen, prec=ctx.control)
        control_checks = [ctx.check("token_logit_gap", max(
            comparisons.widest_logit_gap(lg, lo.argmax(axis=1))
            for lg, lo in zip(logits, low)))]
    checks = [
        ctx.check("token_logit_gap", max(gaps) if gaps else float("inf")),
        ctx.check("failed_requests", len(failures)),
        ctx.check("window_compiles", len(compiles)),
    ]
    return Outcome(
        t_open=t_open, metrics=metrics, attempted=len(sent),
        failed=len(failures) + len(compiles), checks=checks,
        data={"sent": sent, "t_open": t_open, "t_close": t_close,
              "registry": registry, "program_spans": program_spans,
              "control_checks": control_checks},
        memory_peak_bytes=peak)


def _program_spans(sent: List[Sent]) -> Dict[int, list]:
    """The program's spans of each request (``obs/trace.py``), by the
    request's index."""
    from perceiver_tpu.obs import trace as program_trace

    buf = program_trace.default_buffer()
    out = {}
    for s in sent:
        ctx = getattr(s.handle, "trace_ctx", None)
        if ctx is not None:
            out[s.request.index] = buf.get(ctx.trace_id) or []
    return out
