#!/usr/bin/env python
"""Observability-plane smoke runner (docs/OBSERVABILITY.md).

Spins up a tiny REAL fleet (router + replica subprocesses) with
tracing on and a JSONL event directory armed, drives traffic through
a client-side :class:`~perceiver_tpu.serving.batcher.MicroBatcher` so
every request crosses every layer of the plane — client queue →
batch form → router route → RPC hop → replica admission → engine
dispatch → device materialize — then proves, in one process:

1. ``obs_trace_complete``: one request's trace, fetched from the live
   ``/traces/<id>`` endpoint, contains the full phase chain across at
   least two processes (client/router pid + replica pid), with the
   replica-side spans tagged by replica id;
2. ``obs_metrics_conformance``: the aggregated ``/metrics`` exposition
   parses and passes the Prometheus 0.0.4 conformance checks (every
   family typed, histogram buckets monotone, ``+Inf`` == ``_count``),
   with both replicas visible under the ``replica`` label next to the
   router's own ``fleet_*`` series;
3. ``obs_events_valid``: every line in every ``events-<pid>.jsonl``
   file validates against the shared event schema, and the files span
   multiple processes;
4. ``obs_zero_compiles``: the traffic run added ZERO XLA compiles on
   any replica (tracing is host-side only — the plane's budget gate);
5. ``obs_tracing_overhead``: recording a span and the disabled-path
   ``start_trace`` both stay under generous pinned bounds.

Emits one JSON line per check (``metric``, ``value``, ``unit``,
``vs_baseline``, ``detail``) plus an ``obs_check`` summary; exits
non-zero iff any check failed.  ``--fast`` shrinks the traffic volume
(tests/test_obs.py runs it as a tier-1 subprocess gate)::

    JAX_PLATFORMS=cpu python scripts/obs_check.py --fast
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# tiny MLM task, mirroring the chaos fleet preset (scripts/chaos.py)
_TASK_KWARGS = dict(
    vocab_size=110, max_seq_len=32, num_latents=4,
    num_latent_channels=8, num_encoder_layers=1,
    num_encoder_self_attention_layers_per_block=1,
    num_encoder_cross_attention_heads=1,
    num_encoder_self_attention_heads=1,
    num_decoder_cross_attention_heads=1, loss_impl="dense")

_REQUIRED_PHASES = ("queue_wait", "batch_form", "route", "rpc_hop",
                    "pad_or_pack", "dispatch", "device")


def _publish_store(tmp: str):
    from perceiver_tpu.serving.graphs import build_serve_graph
    from perceiver_tpu.tasks import MaskedLanguageModelTask
    from perceiver_tpu.training.checkpoint import ParamsVersionStore

    graph = build_serve_graph(MaskedLanguageModelTask(**_TASK_KWARGS))
    store = ParamsVersionStore(os.path.join(tmp, "store"))
    store.publish("v1", graph.init_params(0), set_current=True)
    return store


def _http_get(url: str, timeout: float = 10.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read().decode("utf-8")


def check_trace(obs_url: str, replies) -> dict:
    tids = [r.get("trace_id") for r in replies if isinstance(r, dict)]
    assert tids and all(tids), "replies carried no trace_id"
    status, body = _http_get(f"{obs_url}/traces/{tids[0]}")
    assert status == 200, status
    spans = json.loads(body)["spans"]
    phases = {s["phase"] for s in spans}
    missing = [p for p in _REQUIRED_PHASES if p not in phases]
    assert not missing, f"trace missing phases {missing}: {phases}"
    pids = {s["pid"] for s in spans}
    assert len(pids) >= 2, f"trace never crossed a process: {pids}"
    tagged = [s for s in spans
              if (s.get("attrs") or {}).get("replica")]
    assert tagged, "replica-side spans not tagged with the replica id"
    assert all(s["duration_s"] >= 0 for s in spans), spans
    return {"trace_id": tids[0], "spans": len(spans),
            "phases": sorted(phases), "processes": len(pids),
            "replica_tagged_spans": len(tagged),
            "traced_requests": len(tids)}


def check_metrics(obs_url: str) -> dict:
    from perceiver_tpu.obs import promparse

    status, text = _http_get(f"{obs_url}/metrics")
    assert status == 200, status
    problems = promparse.check_exposition(text)
    assert not problems, problems
    families = promparse.parse(text)
    replicas = {s.labels["replica"]
                for fam in families.values() for s in fam.samples
                if "replica" in s.labels}
    assert len(replicas) >= 2, f"replica label missing: {replicas}"
    # router-level series + a replica-level engine series must share
    # the one exposition (replicas expose engine metrics over RPC)
    for name in ("fleet_requests_total", "fleet_size",
                 "fleet_breaker_state", "serving_bucket_dispatch_total"):
        assert name in families, f"{name} not in the aggregated /metrics"
    status, body = _http_get(f"{obs_url}/healthz")
    assert status == 200, (status, body)
    return {"families": len(families),
            "samples": sum(len(f.samples) for f in families.values()),
            "replica_labels": sorted(replicas), "problems": problems}


def check_events(event_dir: str) -> dict:
    from perceiver_tpu.obs import events as events_mod

    files = sorted(glob.glob(os.path.join(event_dir, "events-*.jsonl")))
    assert len(files) >= 2, f"expected multi-process event files: {files}"
    counts: dict = {}
    total = 0
    for path in files:
        with open(path, encoding="utf-8") as f:
            for line in f:
                event = json.loads(line)
                events_mod.validate_event(event)  # raises on drift
                counts[event["type"]] = counts.get(event["type"], 0) + 1
                total += 1
    assert total > 0, "no events were logged"
    for etype in ("exec_cache", "health_transition"):
        assert etype in counts, f"no {etype} events: {sorted(counts)}"
    return {"files": len(files), "events": total, "by_type": counts}


def check_overhead() -> dict:
    from perceiver_tpu.obs import trace as trace_mod

    ctx = trace_mod.start_trace(origin="bench",
                                sink=trace_mod.SpanCollector())
    n = 5000
    t0 = time.perf_counter()
    for _ in range(n):
        ctx.record("dispatch", duration_s=0.0)
    per_span_us = (time.perf_counter() - t0) / n * 1e6
    trace_mod.set_enabled(False)
    try:
        t0 = time.perf_counter()
        for _ in range(n):
            trace_mod.start_trace()
        disabled_us = (time.perf_counter() - t0) / n * 1e6
    finally:
        trace_mod.set_enabled(True)
    # generous pinned bounds: a span record is dict-building + a list
    # append; the disabled path is one module-global read
    assert per_span_us < 100.0, per_span_us
    assert disabled_us < 10.0, disabled_us
    return {"per_span_us": round(per_span_us, 3),
            "disabled_start_trace_us": round(disabled_us, 4),
            "iterations": n}


def run(tmp: str, *, requests: int) -> list:
    import numpy as np

    from perceiver_tpu.fleet import Fleet
    from perceiver_tpu.obs import events as events_mod
    from perceiver_tpu.obs import trace as trace_mod
    from perceiver_tpu.serving.batcher import MicroBatcher

    event_dir = os.path.join(tmp, "events")
    os.makedirs(event_dir, exist_ok=True)
    os.environ[events_mod.ENV_VAR] = event_dir
    events_mod.set_default_log(None)  # rebuild against the env dir
    os.environ.setdefault("PERCEIVER_EXEC_CACHE",
                          os.path.join(tmp, "exec_cache"))
    trace_mod.set_enabled(True)

    store = _publish_store(tmp)
    spec = {"task_class": "MaskedLanguageModelTask",
            "task_kwargs": _TASK_KWARGS,
            "batch_buckets": [4], "seq_buckets": [16],
            "store_dir": store.directory, "version": "v1", "seed": 0}
    results = []

    def record(metric, value, unit, detail):
        line = {"metric": metric, "value": value, "unit": unit,
                "vs_baseline": None, "detail": detail}
        results.append(line)
        print(json.dumps(line), flush=True)

    def gate(metric, unit, fn, *fn_args):
        try:
            detail = fn(*fn_args)
        except Exception as e:  # noqa: BLE001 — reported as a failed gate
            record(metric, 0.0, unit,
                   {"error": f"{type(e).__name__}: {e}"})
            return
        record(metric, 1.0, unit, detail)

    fleet = Fleet(spec, os.path.join(tmp, "fleet"), replicas=2,
                  dispatch_timeout_s=15.0)
    try:
        obs = fleet.start_obs()
        # post-warmup baseline: replica spin-up compiles (cold exec
        # cache) happen before this snapshot; traffic must add none
        compiles_before = {rid: s.get("compile_events")
                           for rid, s in fleet.statuses().items()}

        batcher = MicroBatcher(
            lambda payloads: [fleet.submit(p) for p in payloads],
            max_batch=4, max_delay_ms=2.0)
        rng = np.random.default_rng(0)
        futures = []
        for _ in range(requests):
            arrays = {"input_ids": rng.integers(
                          3, 110, (2, 16)).astype(np.int32),
                      "pad_mask": np.zeros((2, 16), bool)}
            futures.append(batcher.submit(arrays))
        replies = [f.result(timeout=120) for f in futures]
        compiles_after = {rid: s.get("compile_events")
                          for rid, s in fleet.statuses().items()}
        batcher.close()

        gate("obs_trace_complete", "ok", check_trace, obs.url, replies)
        gate("obs_metrics_conformance", "ok", check_metrics, obs.url)
        gate("obs_events_valid", "ok", check_events, event_dir)

        def zero_compiles():
            deltas = {rid: compiles_after.get(rid, -1)
                      - compiles_before.get(rid, 0)
                      for rid in compiles_before}
            assert all(d == 0 for d in deltas.values()), deltas
            return {"requests": len(replies),
                    "post_warmup_compile_deltas": deltas,
                    "spin_up_compiles": compiles_before}

        gate("obs_zero_compiles", "ok", zero_compiles)
    finally:
        fleet.close()

    gate("obs_tracing_overhead", "ok", check_overhead)
    return results


def main() -> int:
    ap = argparse.ArgumentParser(
        description="observability plane smoke runner")
    ap.add_argument("--fast", action="store_true",
                    help="tier-1 sized traffic volume")
    ap.add_argument("--requests", type=int, default=None,
                    help="override the traffic volume")
    ap.add_argument("--out", default=None,
                    help="also append the result lines to this path")
    args = ap.parse_args()
    requests = args.requests or (8 if args.fast else 24)

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="obs-check-") as tmp:
        results = run(tmp, requests=requests)
    passed = sum(1 for r in results if r["value"] == 1.0)
    summary = {"metric": "obs_check",
               "value": round(passed / max(len(results), 1), 3),
               "unit": "fraction_passed", "vs_baseline": None,
               "detail": {"checks": len(results), "passed": passed,
                          "requests": requests, "fast": bool(args.fast),
                          "wall_s": round(time.perf_counter() - t0, 2)}}
    results.append(summary)
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            for line in results:
                f.write(json.dumps(line) + "\n")
    return 0 if passed == len(results) - 1 else 1


if __name__ == "__main__":
    sys.exit(main())
