#!/usr/bin/env python
"""Streaming-decode load generator: the O(1) paged-KV + TTFT gates.

Drives a ``DecodeEngine`` with a churning open-loop workload — streams
with varied lengths join and leave mid-flight, so the engine's slot
occupancy, page allocation, and unified prefill+decode scheduler all
cycle while the ONE stepped executable keeps replaying. Emits a
``bench.py``-format result line::

    {"metric": "decode_tokens_per_sec", "value": ..., "unit":
     "tokens/s", "vs_baseline": null, "detail": {"p50_ms": ...,
     "ttft_p50_ms": ..., "o1_ratio": ..., "phase_breakdown_ms": ...}}

Three hard gates, each an ``exit 1``:

- **O(1) per-token cost** — the p95 inter-token gap at each stream's
  LAST token must stay within ``--gate-ratio`` (default 1.15×) of the
  p95 gap at token 10. Paged attention reads the same page-table-bound
  footprint at every position; any per-position growth (quadratic
  recompute, cache copies) shows up here.
- **TTFT** — p95 time-to-first-token must stay within
  ``--ttft-gate-ratio`` (default 10×) of the p95 inter-token gap.
  Chunked prefill feeds up to ``--max-chunk`` prompt tokens per step
  co-scheduled with decode traffic, so a prompt costs
  ``ceil(len/chunk)`` steps, not ``len`` steps behind a convoy (the
  r14 regression: 1031 ms TTFT ≈ 150× the 6.7 ms token gap).
- **Zero post-warmup XLA compiles** (``jax.monitoring``) — streams
  joining/leaving, prefill chunks, and decode rows all share one step
  signature; a mid-traffic compile is a geometry-bucketing bug.

``--shared-prefix`` adds a two-arm trace (cold arm of unique
prefixes, then a warm arm sharing one published prefix) with two more
gates: warm-arm cache hit rate >= ``--prefix-hit-gate`` (default 0.9)
and warm TTFT p95 <= ``--prefix-ttft-gate`` (default 0.5) x the cold
arm's — prefix caching must actually skip the cached span's prefill.
In this mode the headline TTFT gate judges the WARM arm (the cold arm
deliberately convoys ``--streams`` unique long-prompt prefills as the
control; its cost is gated relatively via the warm/cold ratio).

``--speculative`` runs a different two-arm trace instead: the same
plans on a plain engine and on a ``--spec-k`` self-draft speculative
engine, gating token-exactness, acceptance rate
(``--spec-accept-gate``), tokens per target step >= ``--spec-gate`` x
the plain arm, and zero post-warmup compiles in both arms
(docs/SERVING.md "Speculative decoding").

``--tenants`` runs the mixed-tenant two-arm trace: the same "gold"
plans solo, then under a quota-capped best-effort "bronze" flood on
one tenancy-enabled engine. Emits per-tenant TTFT/p95/tokens-per-step
and gates zero dropped gold requests, the noisy-neighbor isolation
ratio (``--tenant-isolation-gate``, default 2x solo), at least one
typed bronze ``tenant_quota`` shed, and zero post-warmup compiles
(docs/SERVING.md "Multi-tenancy").

The TTFT phase breakdown is derived from the request trace spans
(``obs/trace.py``): per stream, ``queue_wait`` (admission), the
``prefill_chunk`` steps before the one that completed the prompt, and
``first_decode`` (the step that consumed the last chunk and emitted
token 0) — the same ``phase_breakdown_ms`` shape bench_serving emits.

Runs on any backend; on CPU use ``--preset tiny`` (the default), which
decodes a test-sized model — the point of the CPU run is the gate
trio, not throughput. On a chip, drop ``--preset tiny`` for the
canonical MLM shapes (the ``decode_mixed_mlm_r8_p64x16_q8`` target
geometry scaled to the offered concurrency).

Examples::

    JAX_PLATFORMS=cpu python scripts/bench_decode.py
    JAX_PLATFORMS=cpu python scripts/bench_decode.py --streams 12 \
        --max-new-min 20 --max-new-max 40
    python scripts/bench_decode.py --preset full --streams 64
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from perceiver_tpu.cache import compile_events  # noqa: E402


def _tiny_decode_task(max_seq_len: int):
    from perceiver_tpu.tasks import MaskedLanguageModelTask
    return MaskedLanguageModelTask(
        vocab_size=110, max_seq_len=max_seq_len, num_latents=4,
        num_latent_channels=8, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_encoder_cross_attention_heads=1,
        num_encoder_self_attention_heads=1,
        num_decoder_cross_attention_heads=1, loss_impl="dense")


def _full_decode_task(max_seq_len: int):
    from perceiver_tpu.tasks import MaskedLanguageModelTask
    return MaskedLanguageModelTask(vocab_size=10003,
                                   max_seq_len=max_seq_len)


def _pct(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def _ttft_phases(spans):
    """Split one stream's trace into the TTFT phases (ms).

    ``first_decode`` is the step span that emitted token 0 — by the
    engine's emission rule that is the ``prefill_chunk`` which consumed
    the last prompt slice (or a ``decode_step``, defensively).
    ``prefill_chunks`` sums EVERY chunk step up to and including that
    one, so it is present whenever the stream prefilled at all — the
    completing chunk is deliberately counted in both phases (it both
    fed prompt tokens and emitted token 0). The r17 harvester summed
    only the chunks *before* the completing one, so any prompt that
    prefilled in a single chunk (prompt_len <= max_chunk — the bench
    default) reported no ``prefill_chunks`` phase at all
    (BENCH_r17.json has only queue_wait/first_decode).
    ``queue_wait`` is the admission span. Returns a dict of
    phase -> ms (phases with no span are absent).
    """
    emits = sorted((s for s in spans if s["phase"] == "token_emit"),
                   key=lambda s: s["end"])
    if not emits:
        return {}
    first_emit = emits[0]["end"]
    out = {}
    waits = [s for s in spans if s["phase"] == "queue_wait"]
    if waits:
        out["queue_wait"] = 1e3 * sum(s["duration_s"] for s in waits)
    steps = [s for s in spans
             if s["phase"] in ("prefill_chunk", "decode_step")
             and s["end"] <= first_emit]
    if steps:
        steps.sort(key=lambda s: s["end"])
        out["first_decode"] = 1e3 * steps[-1]["duration_s"]
        chunks = [s for s in steps if s["phase"] == "prefill_chunk"]
        if chunks:
            out["prefill_chunks"] = 1e3 * sum(s["duration_s"]
                                              for s in chunks)
    return out


def _run_speculative(args, task, geometry, plans):
    """The ``--speculative`` two-arm trace.

    Arm A decodes the plans on a plain engine (one token per decode
    step); arm B decodes the SAME plans with ``spec_k`` self-draft
    speculation (the draft shares the target's weights, so greedy
    acceptance is ~1.0 and each verify step can commit up to k+1
    tokens). Four hard gates:

    - **token-exactness** — the spec arm's emitted streams must equal
      the plain arm's, token for token (the rejection rule's whole
      contract: speculation changes latency, never output);
    - **acceptance** — acceptance rate >= ``--spec-accept-gate``;
    - **tokens/step** — the spec arm's tokens per target step must be
      >= ``--spec-gate`` x the plain arm's (the headline win: fewer
      sequential target dispatches for the same tokens);
    - **zero post-warmup compiles** in BOTH arms — drafted lanes ride
      the same stepped signature, so speculation must not widen the
      exec-cache key set mid-traffic.
    """
    from dataclasses import replace

    from perceiver_tpu.serving.decode import DecodeEngine
    from perceiver_tpu.serving.speculative import SpeculativeConfig

    def _arm(spec: bool):
        g = replace(geometry, spec_k=args.spec_k) if spec else geometry
        engine = DecodeEngine(
            task, geometry=g, auto_step=True,
            max_queue=args.streams + 1,
            token_budget=args.token_budget or None,
            speculative=SpeculativeConfig() if spec else None)
        t0 = time.monotonic()
        with compile_events() as compiles:
            handles = []
            for prompt, max_new, _a in plans:
                handles.append(
                    engine.submit(prompt, max_new_tokens=max_new))
                time.sleep(0.01)
            results = [h.result(timeout=600.0) for h in handles]
        wall = time.monotonic() - t0
        steps = engine.metrics.counter(
            "serving_decode_steps_total",
            "decode step executions").value
        stats = engine.speculative_stats()
        engine.close()
        tokens = sum(len(r.tokens) for r in results)
        for (_p, max_new, _a), r in zip(plans, results):
            assert r.finished == "complete", r
            assert len(r.tokens) == max_new
        return {
            "results": results,
            "tokens": tokens,
            "steps": int(steps),
            "tokens_per_step": tokens / max(1, steps),
            "tokens_per_sec": round(tokens / wall, 1),
            "wall_s": round(wall, 2),
            "compiles": len(compiles),
            "stats": stats,
            "descriptor": g.descriptor,
        }

    plain = _arm(spec=False)
    spec = _arm(spec=True)

    ratio = spec["tokens_per_step"] / plain["tokens_per_step"]
    acceptance = (spec["stats"] or {}).get("acceptance_rate", 0.0)
    exact = all(r1.tokens == r2.tokens for r1, r2 in
                zip(plain["results"], spec["results"]))
    ratio_ok = ratio >= args.spec_gate
    accept_ok = acceptance >= args.spec_accept_gate
    compiles_ok = plain["compiles"] == 0 and spec["compiles"] == 0

    import jax
    dev = jax.devices()[0]

    def _arm_detail(arm):
        d = {k: arm[k] for k in ("tokens", "steps", "tokens_per_step",
                                 "tokens_per_sec", "wall_s",
                                 "compiles", "descriptor")}
        d["tokens_per_step"] = round(d["tokens_per_step"], 4)
        return d

    result = {
        "metric": "decode_spec_tokens_per_step_ratio",
        "value": round(ratio, 4),
        "unit": "x",
        "vs_baseline": 1.0,
        "detail": {
            "preset": args.preset,
            "streams": args.streams,
            "prompt_len": args.prompt_len,
            "max_new_range": [args.max_new_min, args.max_new_max],
            "spec_k": args.spec_k,
            "draft": "self",
            "plain": _arm_detail(plain),
            "speculative": _arm_detail(spec),
            "acceptance_rate": round(acceptance, 4),
            "accept_gate": args.spec_accept_gate,
            "drafted_tokens": int(
                (spec["stats"] or {}).get("drafted_tokens", 0)),
            "accepted_tokens": int(
                (spec["stats"] or {}).get("accepted_tokens", 0)),
            "verify_steps": int(
                (spec["stats"] or {}).get("verify_steps", 0)),
            "fallbacks": int(
                (spec["stats"] or {}).get("fallbacks", 0)),
            "token_exact": exact,
            "spec_gate": args.spec_gate,
            "post_warmup_compiles": plain["compiles"]
            + spec["compiles"],
            "platform": dev.platform,
            "device_kind": dev.device_kind,
        },
    }
    line = json.dumps(result)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    if not exact:
        print("[bench_decode] FAIL: speculative arm diverged from the "
              "plain arm — the rejection rule must keep greedy decode "
              "token-exact", file=sys.stderr)
    if not accept_ok:
        print(f"[bench_decode] FAIL: acceptance rate {acceptance:.4f} "
              f"< {args.spec_accept_gate} — the self-draft arm should "
              f"accept nearly everything", file=sys.stderr)
    if not ratio_ok:
        print(f"[bench_decode] FAIL: tokens/step ratio {ratio:.4f} < "
              f"{args.spec_gate}x — speculation is not compressing "
              f"sequential target steps", file=sys.stderr)
    if not compiles_ok:
        print(f"[bench_decode] FAIL: post-warmup XLA compiles (plain "
              f"{plain['compiles']}, spec {spec['compiles']}) — "
              f"drafted lanes changed a step signature mid-traffic",
              file=sys.stderr)
    code = 0 if (exact and accept_ok and ratio_ok and compiles_ok) \
        else 1
    return code, result


def _run_tenants(args, task, geometry, plans):
    """The ``--tenants`` mixed-tenant two-arm trace.

    Arm A (solo) decodes the plans as the "gold" tenant alone; arm B
    (mixed) replays the SAME gold plans while a best-effort "bronze"
    tenant floods the engine with ``--tenant-flood-factor`` extra
    requests per gold submit — far more work than bronze's page quota
    admits, so the surplus must shed with typed
    ``Unavailable("tenant_quota")`` at submit, before any compute.
    Emits per-tenant TTFT/p95/tokens-per-step in the result detail.
    Four hard gates:

    - **zero dropped gold requests** — every gold stream completes
      with its full token count in BOTH arms;
    - **isolation ratio** — gold's mixed-arm TTFT p95 AND inter-token
      gap p95 must each stay <= ``--tenant-isolation-gate`` x its solo
      baseline (the noisy-neighbor budget, chaos-gated
      deterministically by ``scripts/chaos.py --scenario
      noisy_neighbor``);
    - **the flood was real** — bronze must hit its quota at least once
      (a bench where nothing sheds proves nothing);
    - **zero post-warmup compiles** in both arms — tenancy is
      host-side state only (docs/SERVING.md "Multi-tenancy").
    """
    from perceiver_tpu.serving.decode import DecodeEngine
    from perceiver_tpu.serving.errors import Unavailable
    from perceiver_tpu.serving.tenancy import (
        PRIORITY_BEST_EFFORT,
        TenantRegistry,
        TenantSpec,
    )

    from dataclasses import replace

    pages_per = math.ceil((args.prompt_len + args.max_new_max)
                          / geometry.page_size)
    tenancy = TenantRegistry([
        TenantSpec(tenant="gold", weight=3.0),
        # quota sized for ~2 in-flight bronze requests: the flood
        # factor oversubscribes it several times over
        TenantSpec(tenant="bronze", priority=PRIORITY_BEST_EFFORT,
                   weight=1.0, max_pages=2 * pages_per),
    ])
    flood_prompt = np.asarray(plans[0][0], np.int32)
    flood_new = args.max_new_min
    # capacity-plan the pool from the quotas: bronze's page cap bounds
    # its in-flight streams, so the slot axis gets exactly that much
    # flood headroom on top of the gold concurrency — a quota'd tenant
    # must never cost the victim a SLOT, only shed its own surplus
    bronze_req_pages = geometry.pages_for(
        flood_prompt.size + flood_new - 1)
    flood_slots = max(1, (2 * pages_per) // bronze_req_pages)
    geometry = replace(
        geometry,
        max_streams=geometry.max_streams + flood_slots,
        num_pages=geometry.num_pages + flood_slots * bronze_req_pages)

    def _arm(mixed: bool):
        engine = DecodeEngine(
            task, geometry=geometry, auto_step=True,
            max_queue=args.streams * (1 + args.tenant_flood_factor) + 1,
            token_budget=args.token_budget or None,
            tenancy=tenancy)
        emit_times = [[] for _ in plans]

        def tracker(i):
            def on_token(tok):
                emit_times[i].append(time.monotonic())
            return on_token

        t0 = time.monotonic()
        shed = 0
        bronze_handles = []
        with compile_events() as compiles:
            handles = []
            for i, (prompt, max_new, _a) in enumerate(plans):
                if mixed:
                    for _ in range(args.tenant_flood_factor):
                        try:
                            bronze_handles.append(engine.submit(
                                flood_prompt,
                                max_new_tokens=flood_new,
                                tenant="bronze"))
                        except Unavailable as e:
                            assert e.reason == "tenant_quota", e.reason
                            shed += 1
                handles.append(engine.submit(
                    prompt, max_new_tokens=max_new, tenant="gold",
                    on_token=tracker(i)))
                time.sleep(0.01)
            results = [h.result(timeout=600.0) for h in handles]
            bronze_results = [h.result(timeout=600.0)
                              for h in bronze_handles]
        wall = time.monotonic() - t0
        steps = engine.metrics.counter(
            "serving_decode_steps_total",
            "decode step executions").value
        gold_tokens = engine._m_tenant_tokens.value_of(tenant="gold")
        bronze_tokens = engine._m_tenant_tokens.value_of(
            tenant="bronze")
        shed_metric = engine._m_tenant_shed.value_of(
            tenant="bronze", reason="tenant_quota")
        gold_shed_metric = sum(
            engine._m_tenant_shed.value_of(tenant="gold", reason=r)
            for r in ("tenant_quota", "queue_full", "deadline"))
        engine.close()
        dropped = sum(1 for r in results
                      if getattr(r, "finished", None) != "complete")
        gaps = []
        for times in emit_times:
            gaps.extend((1e3 * np.diff(np.asarray(times,
                                                  np.float64))).tolist())
        bronze_done = sum(
            1 for r in bronze_results
            if getattr(r, "finished", None) == "complete")
        return {
            "ttft_ms": [1e3 * r.ttft_s for r in results
                        if getattr(r, "finished", None) == "complete"],
            "gaps_ms": gaps,
            "dropped_gold": dropped,
            "steps": int(steps),
            "wall_s": round(wall, 2),
            "compiles": len(compiles),
            "gold_tokens": int(gold_tokens),
            "bronze_tokens": int(bronze_tokens),
            "bronze_submitted": len(bronze_handles) + shed,
            "bronze_completed": bronze_done,
            "bronze_quota_shed": shed,
            "bronze_shed_metric": int(shed_metric),
            "gold_shed_metric": int(gold_shed_metric),
        }

    solo = _arm(mixed=False)
    mixed = _arm(mixed=True)

    ttft_ratio = _pct(mixed["ttft_ms"], 95) / _pct(solo["ttft_ms"], 95)
    gap_ratio = _pct(mixed["gaps_ms"], 95) / _pct(solo["gaps_ms"], 95)
    dropped_ok = solo["dropped_gold"] == 0 and mixed["dropped_gold"] == 0
    iso_ok = (ttft_ratio <= args.tenant_isolation_gate
              and gap_ratio <= args.tenant_isolation_gate)
    flood_ok = mixed["bronze_quota_shed"] >= 1 \
        and mixed["bronze_shed_metric"] >= mixed["bronze_quota_shed"]
    compiles_ok = solo["compiles"] == 0 and mixed["compiles"] == 0

    def _tenant_detail(arm, tenant):
        if tenant == "gold":
            return {
                "ttft_p50_ms": round(_pct(arm["ttft_ms"], 50), 3),
                "ttft_p95_ms": round(_pct(arm["ttft_ms"], 95), 3),
                "gap_p50_ms": round(_pct(arm["gaps_ms"], 50), 3),
                "gap_p95_ms": round(_pct(arm["gaps_ms"], 95), 3),
                "gap_p99_ms": round(_pct(arm["gaps_ms"], 99), 3),
                "tokens": arm["gold_tokens"],
                "tokens_per_step": round(
                    arm["gold_tokens"] / max(1, arm["steps"]), 4),
                "dropped": arm["dropped_gold"],
                "shed": arm["gold_shed_metric"],
            }
        return {
            "submitted": arm["bronze_submitted"],
            "completed": arm["bronze_completed"],
            "quota_shed": arm["bronze_quota_shed"],
            "tokens": arm["bronze_tokens"],
            "tokens_per_step": round(
                arm["bronze_tokens"] / max(1, arm["steps"]), 4),
        }

    import jax
    dev = jax.devices()[0]
    result = {
        "metric": "decode_tenant_isolation_ratio",
        "value": round(max(ttft_ratio, gap_ratio), 4),
        "unit": "x",
        "vs_baseline": 1.0,
        "detail": {
            "preset": args.preset,
            "geometry": geometry.descriptor,
            "streams": args.streams,
            "flood_factor": args.tenant_flood_factor,
            "bronze_max_pages": 2 * pages_per,
            "isolation_gate": args.tenant_isolation_gate,
            "ttft_ratio": round(ttft_ratio, 4),
            "gap_p95_ratio": round(gap_ratio, 4),
            "solo": {"gold": _tenant_detail(solo, "gold"),
                     "steps": solo["steps"], "wall_s": solo["wall_s"]},
            "mixed": {"gold": _tenant_detail(mixed, "gold"),
                      "bronze": _tenant_detail(mixed, "bronze"),
                      "steps": mixed["steps"],
                      "wall_s": mixed["wall_s"]},
            "post_warmup_compiles": solo["compiles"]
            + mixed["compiles"],
            "platform": dev.platform,
            "device_kind": dev.device_kind,
        },
    }
    line = json.dumps(result)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    if not dropped_ok:
        print(f"[bench_decode] FAIL: dropped gold requests (solo "
              f"{solo['dropped_gold']}, mixed {mixed['dropped_gold']}) "
              f"— a quota'd neighbor must never cost the victim a "
              f"request", file=sys.stderr)
    if not iso_ok:
        print(f"[bench_decode] FAIL: gold degradation under the "
              f"bronze flood exceeds the isolation budget (ttft "
              f"{ttft_ratio:.3f}x, gap p95 {gap_ratio:.3f}x, gate "
              f"{args.tenant_isolation_gate}x)", file=sys.stderr)
    if not flood_ok:
        print(f"[bench_decode] FAIL: bronze never hit its quota "
              f"(shed {mixed['bronze_quota_shed']}, metric "
              f"{mixed['bronze_shed_metric']}) — the flood proved "
              f"nothing", file=sys.stderr)
    if not compiles_ok:
        print(f"[bench_decode] FAIL: post-warmup XLA compiles (solo "
              f"{solo['compiles']}, mixed {mixed['compiles']}) — "
              f"tenancy must stay host-side state only",
              file=sys.stderr)
    code = 0 if (dropped_ok and iso_ok and flood_ok and compiles_ok) \
        else 1
    return code, result


def run(argv=None):
    """The bench body: returns ``(exit_code, result_dict)`` so tests
    can drive it in-process; ``main`` wraps it for the CLI."""
    ap = argparse.ArgumentParser(
        description="streaming decode bench: O(1) paged-KV + TTFT "
                    "gates")
    ap.add_argument("--preset", choices=("tiny", "full"),
                    default="tiny",
                    help="tiny = CPU-sized model (default); full = "
                         "canonical MLM shapes for a chip run")
    ap.add_argument("--streams", type=int, default=24,
                    help="total streams to push through (default 24)")
    ap.add_argument("--max-new-min", type=int, default=40)
    ap.add_argument("--max-new-max", type=int, default=120)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-chunk", type=int, default=8,
                    help="prefill chunk lanes in the unified step "
                         "(default 8)")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="per-step token budget for the scheduler; "
                         "0 = engine default (slots + max_chunk)")
    ap.add_argument("--gate-ratio", type=float, default=1.15,
                    help="p95(last token) must be <= ratio * "
                         "p95(token 10)")
    ap.add_argument("--ttft-gate-ratio", type=float, default=10.0,
                    help="ttft_p95 must be <= ratio * p95 inter-token "
                         "gap")
    ap.add_argument("--gate-token", type=int, default=10,
                    help="early token index the gate compares against")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shared-prefix", action="store_true",
                    help="two-arm shared-prefix trace: a cold arm of "
                         "unique prefixes, then a warm arm whose "
                         "streams share one prefix via the engine's "
                         "prefix cache (docs/SERVING.md)")
    ap.add_argument("--shared-prefix-len", type=int, default=48,
                    help="shared prefix tokens, page-aligned "
                         "(default 48 = 3 pages of 16)")
    ap.add_argument("--prefix-hit-gate", type=float, default=0.9,
                    help="warm-arm cache hit rate must be >= this")
    ap.add_argument("--prefix-ttft-gate", type=float, default=0.5,
                    help="warm ttft p95 must be <= gate * cold ttft "
                         "p95")
    ap.add_argument("--speculative", action="store_true",
                    help="two-arm speculative trace: a plain engine "
                         "and a spec_k self-draft engine decode the "
                         "SAME plans; gates token-exactness, "
                         "acceptance rate, and tokens/verify-step")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per verify step (default 4)")
    ap.add_argument("--spec-gate", type=float, default=1.5,
                    help="speculative tokens/step must be >= gate x "
                         "the plain arm's")
    ap.add_argument("--spec-accept-gate", type=float, default=0.9,
                    help="speculative acceptance rate must be >= this "
                         "(self-draft proposes from the target's own "
                         "weights, so ~1.0)")
    ap.add_argument("--tenants", action="store_true",
                    help="two-arm mixed-tenant trace: a solo 'gold' "
                         "arm, then the same gold plans under a "
                         "quota-capped best-effort 'bronze' flood; "
                         "emits per-tenant TTFT/p95/tokens-per-step "
                         "and gates the isolation ratio "
                         "(docs/SERVING.md \"Multi-tenancy\")")
    ap.add_argument("--tenant-flood-factor", type=int, default=2,
                    help="bronze submissions per gold submit in the "
                         "mixed arm (default 2)")
    ap.add_argument("--tenant-isolation-gate", type=float, default=2.0,
                    help="gold's mixed-arm ttft p95 and gap p95 must "
                         "each stay <= gate x its solo baseline")
    ap.add_argument("--out", default=None,
                    help="also write the result JSON to this path")
    args = ap.parse_args(argv)
    if sum((args.speculative, args.shared_prefix, args.tenants)) > 1:
        ap.error("--speculative, --shared-prefix and --tenants are "
                 "separate traces; run them as separate invocations")

    from perceiver_tpu.obs import trace as trace_mod
    from perceiver_tpu.serving.decode import DecodeEngine, DecodeGeometry

    if args.max_new_min <= args.gate_token:
        ap.error("--max-new-min must exceed --gate-token so every "
                 "stream contributes an early-token sample")

    # continuous batching sizes the slot axis to the offered
    # concurrency (capped), so admission never convoys behind a
    # fixed 8-slot pool — the other half of the r14 TTFT fix
    page_size = 16
    slots = max(1, min(args.streams, 32))
    prefix_span = 0
    if args.shared_prefix:
        prefix_span = args.shared_prefix_len
        if prefix_span < page_size or prefix_span % page_size:
            ap.error("--shared-prefix-len must be a positive multiple "
                     f"of the page size ({page_size})")
    max_seq = prefix_span + args.prompt_len + args.max_new_max
    pages_per = math.ceil(max_seq / page_size)
    num_pages = slots * pages_per + 1
    if args.shared_prefix:
        # headroom so the warm chain stays resident while cold-arm
        # leftovers are evicted on demand (the admission budget counts
        # index-only pages as reclaimable)
        num_pages += 2 * pages_per
    if args.preset == "tiny":
        task = _tiny_decode_task(max_seq)
        geometry = DecodeGeometry(max_streams=slots,
                                  num_pages=num_pages,
                                  page_size=page_size,
                                  max_seq_len=max_seq,
                                  max_chunk=args.max_chunk)
    else:
        task = _full_decode_task(max(512, max_seq))
        geometry = DecodeGeometry(max_streams=slots,
                                  num_pages=num_pages,
                                  page_size=page_size,
                                  max_seq_len=max(512, max_seq),
                                  max_chunk=args.max_chunk)

    rng = np.random.default_rng(args.seed)
    vocab = task.vocab_size

    def _ids(n):
        return rng.integers(3, vocab, (n,)).astype(np.int32)

    def _max_new():
        return int(rng.integers(args.max_new_min, args.max_new_max + 1))

    # plans: (prompt, max_new, arm); "solo" is the classic single-arm
    # trace; shared mode runs cold (unique prefixes) → seed (publishes
    # the shared chain) → warm (every prompt = shared prefix + unique
    # tail) so warm TTFTs measure cache reuse under the same self-load
    if args.shared_prefix:
        shared = _ids(prefix_span)
        plans = [(np.concatenate([_ids(prefix_span),
                                  _ids(args.prompt_len)]),
                  _max_new(), "cold") for _ in range(args.streams)]
        plans.append((np.concatenate([shared, _ids(args.prompt_len)]),
                      _max_new(), "seed"))
        plans.extend(
            (np.concatenate([shared, _ids(args.prompt_len)]),
             _max_new(), "warm") for _ in range(args.streams))
    else:
        plans = [(_ids(args.prompt_len), _max_new(), "solo")
                 for _ in range(args.streams)]

    if args.speculative:
        return _run_speculative(args, task, geometry, plans)

    if args.tenants:
        return _run_tenants(args, task, geometry, plans)

    prefix_cfg = None
    if args.shared_prefix:
        from perceiver_tpu.serving.prefix_cache import PrefixCacheConfig
        prefix_cfg = PrefixCacheConfig()

    t_build = time.monotonic()
    engine = DecodeEngine(
        task, geometry=geometry, auto_step=True,
        max_queue=args.streams + 1,
        token_budget=args.token_budget or None,
        prefix_cache=prefix_cfg)
    print(f"[bench_decode] engine up in "
          f"{time.monotonic() - t_build:.1f}s — geometry "
          f"{geometry.descriptor}", flush=True)

    # per-stream emit timestamps; index in the list == token index
    emit_times = [[] for _ in plans]

    def tracker(i):
        def on_token(tok):
            emit_times[i].append(time.monotonic())
        return on_token

    # a trace buffer big enough that no stream's early spans evict
    # (queue_wait + every prefill chunk + the first emit must survive)
    buf = trace_mod.TraceBuffer(
        max_traces=len(plans) + 8,
        max_spans_per_trace=4 * (max_seq + 4))
    prev_buf = trace_mod.set_default_buffer(buf)
    try:
        handles = [None] * len(plans)

        def _fire(indices):
            for i in indices:
                prompt, max_new, _arm = plans[i]
                # stagger arrivals so slots churn (join/leave
                # mid-flight) instead of running in lockstep waves
                handles[i] = engine.submit(prompt,
                                           max_new_tokens=max_new,
                                           on_token=tracker(i))
                time.sleep(0.01)

        arms = [arm for _, _, arm in plans]
        t0 = time.monotonic()
        with compile_events() as compiles:
            _fire([i for i, a in enumerate(arms) if a in ("cold",
                                                          "solo")])
            seed_idx = [i for i, a in enumerate(arms) if a == "seed"]
            if seed_idx:
                # drain the cold arm so each arm runs under the same
                # self-load, then publish the shared chain before any
                # warm stream can miss it
                for i, a in enumerate(arms):
                    if a == "cold":
                        handles[i].result(timeout=600.0)
                _fire(seed_idx)
                for i in seed_idx:
                    handles[i].result(timeout=600.0)
            _fire([i for i, a in enumerate(arms) if a == "warm"])
            results = [h.result(timeout=600.0) for h in handles]
        wall = time.monotonic() - t0
        prefix_stats = engine.prefix_cache_stats()
        engine.close()

        phase_ms = {}
        admit_times = []
        for h in handles:
            if h.trace_ctx is None:
                continue
            spans = buf.get(h.trace_ctx.trace_id) or []
            for phase, ms in _ttft_phases(spans).items():
                phase_ms.setdefault(phase, []).append(ms)
            for s in spans:
                if s["phase"] == "queue_wait":
                    admit_times.append(s["end"])
    finally:
        trace_mod.set_default_buffer(prev_buf)

    total_tokens = sum(len(r.tokens) for r in results)
    for (prompt, max_new, _arm), r in zip(plans, results):
        assert r.finished == "complete", r
        assert len(r.tokens) == max_new

    # o1 windowing (docs/BENCHMARKING.md "Gate-sample windowing"): a
    # step that admits a late-joining stream also pays the host
    # page-table/length upload and slot churn, so the *other* streams'
    # inter-token gap spanning that admission measures admission cost,
    # not steady-state decode. Those samples are excluded from the
    # token10/last gate windows (raw gaps_ms keeps every sample).
    admit_sorted = np.asarray(sorted(admit_times), np.float64)

    def _admission_inside(lo, hi):
        j = int(np.searchsorted(admit_sorted, lo, side="right"))
        return j < len(admit_sorted) and admit_sorted[j] <= hi

    gaps_ms, early_ms, last_ms = [], [], []
    excluded_early = excluded_last = 0
    for times in emit_times:
        arr = np.asarray(times, np.float64)
        gaps = 1e3 * np.diff(arr)
        gaps_ms.extend(gaps.tolist())
        # gap index g is the interval before token g+1
        if len(gaps) > args.gate_token:
            g = args.gate_token - 1
            if _admission_inside(arr[g], arr[g + 1]):
                excluded_early += 1
            else:
                early_ms.append(float(gaps[g]))
        picked = False
        for g in range(len(gaps) - 1, -1, -1):
            if not _admission_inside(arr[g], arr[g + 1]):
                last_ms.append(float(gaps[g]))
                picked = True
                break
        if not picked:
            excluded_last += 1
    if not early_ms or not last_ms:
        # degenerate trace (every sample excluded): fall back to the
        # unfiltered windows so the gates stay computable
        early_ms = [float(1e3 * np.diff(t)[args.gate_token - 1])
                    for t in map(np.asarray, emit_times)
                    if len(t) > args.gate_token + 1]
        last_ms = [float(1e3 * np.diff(t)[-1])
                   for t in map(np.asarray, emit_times) if len(t) > 1]
    ttft_ms = [1e3 * r.ttft_s for r in results]

    p95_early = _pct(early_ms, 95)
    p95_last = _pct(last_ms, 95)
    p95_gap = _pct(gaps_ms, 95)
    ttft_p95 = _pct(ttft_ms, 95)
    o1_ratio = p95_last / p95_early
    gate_ok = o1_ratio <= args.gate_ratio
    compiles_ok = len(compiles) == 0

    hit_ok = warm_ok = True
    shared_detail = None
    gate_ttft_p95 = ttft_p95
    if args.shared_prefix:
        warm = [r for (_, _, a), r in zip(plans, results) if a == "warm"]
        cold = [r for (_, _, a), r in zip(plans, results) if a == "cold"]
        hits = sum(1 for r in warm if r.cached_tokens > 0)
        hit_rate = hits / max(1, len(warm))
        cold_ttft_p95 = _pct([1e3 * r.ttft_s for r in cold], 95)
        warm_ttft_p95 = _pct([1e3 * r.ttft_s for r in warm], 95)
        warm_cold = warm_ttft_p95 / cold_ttft_p95
        hit_ok = hit_rate >= args.prefix_hit_gate
        warm_ok = warm_cold <= args.prefix_ttft_gate
        shared_detail = {
            "prefix_len": prefix_span,
            "tail_len": args.prompt_len,
            "hit_rate": round(hit_rate, 4),
            "hit_gate": args.prefix_hit_gate,
            "hit_tokens": sum(r.cached_tokens for r in warm),
            "cold_ttft_p95_ms": round(cold_ttft_p95, 3),
            "warm_ttft_p95_ms": round(warm_ttft_p95, 3),
            "warm_cold_ratio": round(warm_cold, 4),
            "warm_cold_gate": args.prefix_ttft_gate,
            "pages_indexed": (prefix_stats or {}).get(
                "pages_indexed", 0),
            "evicted_pages": (prefix_stats or {}).get(
                "evicted_pages", 0),
            "ttft_gate_arm": "warm",
        }
        # the headline TTFT gate judges the WARM arm in shared mode:
        # the cold arm is the control that deliberately convoys
        # `streams` unique long-prompt prefills at once, and its cost
        # is already gated relatively through warm_cold_ratio
        gate_ttft_p95 = warm_ttft_p95
    ttft_ratio = gate_ttft_p95 / p95_gap
    ttft_ok = ttft_ratio <= args.ttft_gate_ratio

    import jax
    dev = jax.devices()[0]
    result = {
        "metric": "decode_tokens_per_sec",
        "value": round(total_tokens / wall, 1),
        "unit": "tokens/s",
        "vs_baseline": None,
        "detail": {
            "preset": args.preset,
            "geometry": geometry.descriptor,
            "streams": args.streams,
            "prompt_len": args.prompt_len,
            "max_chunk": args.max_chunk,
            "token_budget": args.token_budget or None,
            "max_new_range": [args.max_new_min, args.max_new_max],
            "total_tokens": total_tokens,
            "wall_s": round(wall, 2),
            "p50_ms": round(_pct(gaps_ms, 50), 3),
            "p95_ms": round(p95_gap, 3),
            "p99_ms": round(_pct(gaps_ms, 99), 3),
            "ttft_p50_ms": round(_pct(ttft_ms, 50), 3),
            "ttft_p95_ms": round(ttft_p95, 3),
            "ttft_ratio": round(ttft_ratio, 4),
            "ttft_gate": args.ttft_gate_ratio,
            "phase_breakdown_ms": {
                phase: {"p50": round(_pct(values, 50), 3),
                        "p95": round(_pct(values, 95), 3),
                        "spans": len(values)}
                for phase, values in sorted(phase_ms.items())
            },
            f"p95_token{args.gate_token}_ms": round(p95_early, 3),
            "p95_last_token_ms": round(p95_last, 3),
            "o1_ratio": round(o1_ratio, 4),
            "o1_gate": args.gate_ratio,
            "o1_window": {
                "excluded_early": excluded_early,
                "excluded_last": excluded_last,
                "admissions": len(admit_times),
            },
            "post_warmup_compiles": len(compiles),
            "platform": dev.platform,
            "device_kind": dev.device_kind,
        },
    }
    if shared_detail is not None:
        result["detail"]["shared_prefix"] = shared_detail
    line = json.dumps(result)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    if not compiles_ok:
        print(f"[bench_decode] FAIL: {len(compiles)} post-warmup XLA "
              f"compile(s) — streams joining/leaving changed the step "
              f"signature: {compiles[:5]}", file=sys.stderr)
    if not gate_ok:
        print(f"[bench_decode] FAIL: p95 at last token "
              f"{p95_last:.3f}ms > {args.gate_ratio}x p95 at token "
              f"{args.gate_token} ({p95_early:.3f}ms) — per-token cost "
              f"is growing with position", file=sys.stderr)
    if not ttft_ok:
        print(f"[bench_decode] FAIL: ttft p95 {gate_ttft_p95:.3f}ms > "
              f"{args.ttft_gate_ratio}x p95 token gap "
              f"({p95_gap:.3f}ms) — prefill is convoying behind "
              f"decode traffic again", file=sys.stderr)
    if not hit_ok:
        print(f"[bench_decode] FAIL: shared-prefix hit rate "
              f"{shared_detail['hit_rate']} < "
              f"{args.prefix_hit_gate} — warm streams are missing the "
              f"published chain", file=sys.stderr)
    if not warm_ok:
        print(f"[bench_decode] FAIL: warm ttft p95 "
              f"{shared_detail['warm_ttft_p95_ms']}ms > "
              f"{args.prefix_ttft_gate}x cold arm "
              f"({shared_detail['cold_ttft_p95_ms']}ms) — the cached "
              f"span is not skipping prefill", file=sys.stderr)
    code = 0 if (gate_ok and ttft_ok and compiles_ok and hit_ok
                 and warm_ok) else 1
    return code, result


def main(argv=None) -> int:
    from perceiver_tpu.cache import enable_compile_cache

    enable_compile_cache()
    code, _ = run(argv)
    return code


if __name__ == "__main__":
    sys.exit(main())
