#!/usr/bin/env python
"""Offline Poisson-arrival load generator for the serving engine.

Drives an ``MLMServer`` (or the classifier/segmentation servers) with
open-loop Poisson traffic — arrivals are scheduled ahead of time from
an exponential inter-arrival draw and submitted on time regardless of
completion, the regime that actually exposes queueing/tail behavior
(closed-loop clients self-throttle and hide it). Emits ONE JSON line
in the ``bench.py`` result-line format::

    {"metric": "serving_mlm_requests_per_sec", "value": ..., "unit":
     "req/s", "vs_baseline": null, "detail": {"p50_ms": ..., "p95_ms":
     ..., "p99_ms": ..., ...}}

Runs on any backend; on CPU use ``--preset tiny`` (the default), which
serves a test-sized model — the point of the CPU run is schema + queue
behavior, not throughput. On a chip, drop ``--preset tiny`` to load
the canonical task shapes and optionally ``--checkpoint``.

``--mode`` selects the dispatch path: ``padded`` (rectangular buckets,
the default), ``packed`` (ragged token-budget continuous batching —
docs/SERVING.md "Ragged serving"), or ``both``, which drives the SAME
mixed-length trace through each arm and emits one result line whose
detail carries the padded-vs-packed p50/p95/p99 + waste side by side.
The packed arm asserts ZERO post-warmup XLA compiles via
``jax.monitoring`` — a compile mid-traffic is a bucketing bug and
fails the run.

Examples::

    JAX_PLATFORMS=cpu python scripts/bench_serving.py --requests 200 \
        --rate 100
    JAX_PLATFORMS=cpu python scripts/bench_serving.py --mode both \
        --requests 200 --rate 100
    python scripts/bench_serving.py --task mlm --rate 2000 \
        --duration-s 30 --checkpoint /ckpts/mlm
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from perceiver_tpu.cache import compile_events  # noqa: E402


def _tiny_mlm_task():
    from perceiver_tpu.tasks import MaskedLanguageModelTask
    return MaskedLanguageModelTask(
        vocab_size=110, max_seq_len=64, num_latents=4,
        num_latent_channels=8, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_encoder_cross_attention_heads=1,
        num_encoder_self_attention_heads=1,
        num_decoder_cross_attention_heads=1, loss_impl="dense")


def _full_mlm_task():
    from perceiver_tpu.tasks import MaskedLanguageModelTask
    return MaskedLanguageModelTask(vocab_size=10003, max_seq_len=512)


def _make_tokenizer(vocab_size: int):
    """Self-contained tokenizer (no shipped artifact in this image):
    trained once on the synthetic review corpus."""
    from perceiver_tpu.data.imdb import _synthetic_reviews
    from perceiver_tpu.tokenizer import create_tokenizer, train_tokenizer
    from perceiver_tpu.tokenizer.wordpiece import Replace

    texts, _ = _synthetic_reviews(400, 0)
    tok = create_tokenizer(Replace("<br />", " "))
    train_tokenizer(tok, texts, vocab_size=vocab_size)
    return tok


def _request_texts(n: int, seq_buckets, seed: int):
    """Mixed-length fill-mask requests spanning every seq bucket."""
    from perceiver_tpu.data.imdb import _synthetic_reviews

    texts, _ = _synthetic_reviews(max(n, 16), seed)
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        base = texts[i % len(texts)].replace("<br />", " ")
        words = base.split()
        # repeat to reach a target bucket, then mask a few words
        target = int(rng.choice(seq_buckets))
        while len(words) < target // 2:
            words = words + words
        words = words[:max(3, min(len(words), target - 2))]
        for _ in range(max(1, len(words) // 16)):
            words[int(rng.integers(0, len(words)))] = "[MASK]"
        out.append(" ".join(words))
    return out


def _parse_packed_buckets(spec: str):
    """``"512x16,128x4"`` -> ((512, 16), (128, 4))."""
    out = []
    for part in spec.split(","):
        tokens, rows = part.lower().split("x")
        out.append((int(tokens), int(rows)))
    return tuple(out)


def _run_arm(arm: str, args, task, texts, arrivals, *, seq_buckets,
             batch_buckets, packed_buckets, tokenizer):
    """Build one engine+server (padded or packed), drive the shared
    Poisson trace through it, and return the per-arm detail dict.

    The packed arm counts XLA compile events across the whole traffic
    window — post-warmup compiles are a bucketing bug and make the
    bench exit nonzero.
    """
    from perceiver_tpu.serving import MLMServer, Overloaded, ServingEngine
    from perceiver_tpu.serving.metrics import MetricsRegistry

    packed = arm == "packed"
    print(f"[bench_serving] {arm}: building engine "
          + (f"packed_buckets={packed_buckets}" if packed
             else f"buckets={batch_buckets}x{seq_buckets}"),
          file=sys.stderr)
    t0 = time.perf_counter()
    metrics = MetricsRegistry()
    if packed:
        engine = ServingEngine(task, checkpoint=args.checkpoint,
                               batch_buckets=(), seq_buckets=(),
                               allow_unlisted_buckets=True,
                               packed_buckets=packed_buckets,
                               metrics=metrics)
    else:
        engine = ServingEngine(task, checkpoint=args.checkpoint,
                               batch_buckets=batch_buckets,
                               seq_buckets=seq_buckets, metrics=metrics)
    warmup_s = time.perf_counter() - t0
    print(f"[bench_serving] {arm}: warmup {engine.compile_count} bucket "
          f"executables in {warmup_s:.1f}s", file=sys.stderr)

    server = MLMServer(engine, tokenizer, max_batch=args.max_batch,
                       max_delay_ms=args.max_delay_ms,
                       max_depth=args.max_depth, packed=packed)

    # per-arm trace buffer sized to the whole trace so the span-derived
    # phase breakdown below never loses early requests to LRU eviction
    from perceiver_tpu.obs import trace as trace_mod

    arm_buffer = trace_mod.TraceBuffer(max_traces=len(texts) + 16)
    prev_buffer = trace_mod.set_default_buffer(arm_buffer)

    latencies_ms: list = []
    trace_ids: list = []
    shed = 0
    errors = 0
    lock = threading.Lock()
    futures = []

    def reap(fut, t_submit):
        nonlocal shed, errors
        try:
            result = fut.result()
        except Exception:  # noqa: BLE001 — counted, reported below
            with lock:
                errors += 1
            return
        dt_ms = (time.perf_counter() - t_submit) * 1e3
        ctx = getattr(fut, "trace_ctx", None)
        with lock:
            if isinstance(result, Overloaded):
                shed += 1
            else:
                latencies_ms.append(dt_ms)
                if ctx is not None:
                    trace_ids.append(ctx.trace_id)

    n = len(texts)
    print(f"[bench_serving] {arm}: offering {n} requests at "
          f"{args.rate} req/s (open loop)", file=sys.stderr)
    with compile_events() as compiles:
        start = time.perf_counter()
        for i in range(n):
            delay = start + arrivals[i] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            t_submit = time.perf_counter()
            fut = server.submit(texts[i], timeout_ms=args.timeout_ms)
            waiter = threading.Thread(target=reap, args=(fut, t_submit),
                                      daemon=True)
            waiter.start()
            futures.append(waiter)
        for w in futures:
            w.join(timeout=120)
        wall = time.perf_counter() - start
        server.close()
    trace_mod.set_default_buffer(prev_buffer)

    # span-derived per-phase latency: where each served request's time
    # actually went (queue vs dispatch vs the device materialize sync)
    phase_ms = {"queue_wait": [], "dispatch": [], "device": []}
    with lock:
        for tid in trace_ids:
            for span in arm_buffer.get(tid) or ():
                if span["phase"] in phase_ms:
                    phase_ms[span["phase"]].append(
                        span["duration_s"] * 1e3)

    def phase_pct(values, p):
        if not values:
            return None
        ranked = sorted(values)
        return round(ranked[min(int(p / 100 * len(ranked)),
                                len(ranked) - 1)], 3)

    served = len(latencies_ms)
    lat = np.asarray(sorted(latencies_ms)) if served else np.zeros(1)

    def pct(p):
        return round(float(lat[min(int(p / 100 * served), served - 1)]),
                     3) if served else None

    hist = metrics.get("serving_batch_size")
    occ = metrics.get("serving_batch_occupancy")
    waste = metrics.get("serving_padding_waste_fraction")
    dispatch = metrics.get("serving_bucket_dispatch_total")
    padded_tokens = metrics.get("serving_padded_tokens_total")
    detail = {
        "requests_per_sec": round(served / wall, 1) if wall > 0 else 0.0,
        "p50_ms": pct(50),
        "p95_ms": pct(95),
        "p99_ms": pct(99),
        "served": served,
        "shed": shed,
        "errors": errors,
        "wall_s": round(wall, 3),
        "warmup_s": round(warmup_s, 2),
        "aot_executables": engine.compile_count,
        "post_warmup_compiles": len(compiles),
        "lazy_compiles": int(metrics.get("serving_compile_total")
                             .value_of(phase="lazy")),
        "mean_batch_size": (round(hist.sum / hist.count, 2)
                            if hist and hist.count else None),
        "mean_occupancy": (round(occ.sum / occ.count, 3)
                           if occ and occ.count else None),
        "mean_padding_waste": (round(waste.sum / waste.count, 3)
                               if waste and waste.count else None),
        "padded_tokens_total": {
            labels.get("mode", ""): int(v)
            for labels, v in padded_tokens.items()
        } if padded_tokens else {},
        "bucket_dispatches": {
            labels.get("bucket", ""): int(v)
            for labels, v in dispatch.items()
        } if dispatch else {},
        "phase_breakdown_ms": {
            phase: {"p50": phase_pct(values, 50),
                    "p95": phase_pct(values, 95),
                    "spans": len(values)}
            for phase, values in phase_ms.items()
        },
    }
    return detail


def main() -> int:
    from perceiver_tpu.cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(
        description="Poisson open-loop load generator for the serving "
                    "subsystem")
    ap.add_argument("--task", default="mlm", choices=["mlm"],
                    help="served task front-end (mlm = fill-mask)")
    ap.add_argument("--preset", default="tiny",
                    choices=["tiny", "canonical"],
                    help="tiny: CPU-sized model; canonical: the "
                         "pinned serve shapes (chip-sized)")
    ap.add_argument("--mode", default="padded",
                    choices=["padded", "packed", "both"],
                    help="dispatch path: rectangular buckets, ragged "
                         "packed batching, or a side-by-side comparison "
                         "over the same trace")
    ap.add_argument("--checkpoint", default=None,
                    help="params checkpoint dir (default: fresh init)")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="offered load, requests/second (Poisson)")
    ap.add_argument("--requests", type=int, default=200,
                    help="total requests to offer")
    ap.add_argument("--duration-s", type=float, default=None,
                    help="cap the offered window; overrides --requests "
                         "when both limits conflict")
    ap.add_argument("--batch-buckets", default="1,4,8",
                    help="comma-separated engine batch buckets")
    ap.add_argument("--seq-buckets", default=None,
                    help="comma-separated engine seq buckets (default: "
                         "16,32,64 tiny / 128,256,512 canonical)")
    ap.add_argument("--packed-buckets", default=None,
                    help="comma-separated TOKENSxROWS packed buckets "
                         "(default: 64x2,128x4,512x16 tiny / "
                         "2048x8,8192x32 canonical)")
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--max-delay-ms", type=float, default=4.0)
    ap.add_argument("--max-depth", type=int, default=256)
    ap.add_argument("--timeout-ms", type=float, default=None,
                    help="per-request deadline (default: none)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None,
                    help="also write the result object to this path")
    args = ap.parse_args()

    import jax

    tiny = args.preset == "tiny"
    task = _tiny_mlm_task() if tiny else _full_mlm_task()
    seq_buckets = tuple(
        int(s) for s in (args.seq_buckets.split(",") if args.seq_buckets
                         else (("16", "32", "64") if tiny
                               else ("128", "256", "512"))))
    batch_buckets = tuple(int(b) for b in args.batch_buckets.split(","))
    packed_buckets = _parse_packed_buckets(
        args.packed_buckets if args.packed_buckets
        else ("64x2,128x4,512x16" if tiny else "2048x8,8192x32"))

    rng = np.random.default_rng(args.seed)
    n = args.requests
    inter = rng.exponential(1.0 / args.rate, n)
    arrivals = np.cumsum(inter)
    if args.duration_s is not None:
        arrivals = arrivals[arrivals <= args.duration_s]
        n = len(arrivals)
    texts = _request_texts(n, seq_buckets, args.seed)
    tokenizer = _make_tokenizer(task.vocab_size)

    arms = (("padded", "packed") if args.mode == "both"
            else (args.mode,))
    per_arm = {}
    for arm in arms:
        per_arm[arm] = _run_arm(
            arm, args, task, texts, arrivals, seq_buckets=seq_buckets,
            batch_buckets=batch_buckets, packed_buckets=packed_buckets,
            tokenizer=tokenizer)

    # Acceptance gate: the packed path never compiles under traffic —
    # every dispatch must land in a warmed (tokens, rows) bucket.
    packed_compiles = (per_arm.get("packed") or {}).get(
        "post_warmup_compiles", 0)
    if packed_compiles:
        print(f"[bench_serving] FAIL: packed arm saw {packed_compiles} "
              "post-warmup XLA compile event(s); packed dispatch must "
              "be fully AOT", file=sys.stderr)

    headline = per_arm[arms[-1]]
    detail = {
        "mode": args.mode,
        "offered_rate_rps": round(args.rate, 1),
        "offered_requests": int(n),
        "batch_buckets": list(batch_buckets),
        "seq_buckets": list(seq_buckets),
        "packed_buckets": [list(tb) for tb in packed_buckets],
        "preset": args.preset,
        "platform": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", None),
    }
    if args.mode == "both":
        detail["padded"] = per_arm["padded"]
        detail["packed"] = per_arm["packed"]
        pw, kw = (per_arm["padded"]["padded_tokens_total"],
                  per_arm["packed"]["padded_tokens_total"])
        rect_waste = pw.get("rect", 0)
        packed_waste = kw.get("packed", 0)
        detail["padded_tokens_rect_vs_packed"] = [rect_waste,
                                                  packed_waste]
        if rect_waste:
            detail["packed_waste_ratio"] = round(
                packed_waste / rect_waste, 4)
    else:
        detail.update(per_arm[args.mode])
    metric_name = (f"serving_{args.task}_requests_per_sec"
                   if args.mode == "padded"
                   else f"serving_{args.task}_packed_requests_per_sec")
    result = {
        "metric": metric_name,
        "value": headline["requests_per_sec"],
        "unit": "req/s",
        "vs_baseline": (per_arm["padded"]["requests_per_sec"]
                        if args.mode == "both" else None),
        "detail": detail,
    }
    print(json.dumps(result), flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
    return 1 if packed_compiles else 0


if __name__ == "__main__":
    sys.exit(main())
