#!/usr/bin/env python
"""Cold vs warm time-to-first-dispatch, with the persistent compile
cache (``perceiver_tpu/cache``) as the only variable.

Measures the two startup bills the cache was built to kill:

- ``serving``: ``ServingEngine`` construction + full bucket-grid
  warmup + one dispatched-and-materialized request;
- ``trainer``: the first train-step dispatch
  (``step_flops_and_fn`` AOT path + one executed step).

Each phase runs in a FRESH subprocess — executable caches only matter
across processes, and an in-process re-run would hit jit's own live
cache and prove nothing. The parent never touches JAX (a chip belongs
to one process) and runs its children one after another. JAX's own
persistent compilation cache stays off here (no
``enable_compile_cache()``): it would serve the cold arm. The cold run starts from an empty cache
directory (and populates it); the warm run replays against it. Emits
one ``bench.py``-format JSON line per phase pair::

    {"metric": "serving_warm_start_speedup", "value": ..., "unit":
     "x", "vs_baseline": null, "detail": {"cold_s": ..., "warm_s":
     ..., "warm_xla_compiles": 0, ...}}

On CPU use the (default) tiny preset — the point is the contract
(warm compiles = 0) and the shape of the win, not its chip-scale
magnitude::

    JAX_PLATFORMS=cpu python scripts/bench_startup.py
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _tiny_mlm_task():
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    return MaskedLanguageModelTask(
        vocab_size=128, max_seq_len=64, num_latents=4,
        num_latent_channels=8, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_encoder_cross_attention_heads=1,
        num_encoder_self_attention_heads=1,
        num_decoder_cross_attention_heads=1, loss_impl="dense")


def _canonical_mlm_task():
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    return MaskedLanguageModelTask(vocab_size=10003, max_seq_len=512)


def _buckets(preset: str):
    if preset == "tiny":
        return (1, 4), (16, 32)
    return (1, 8, 32), (128, 512)


def _compile_event_counter():
    from perceiver_tpu.cache import register_compile_listener

    events = []
    register_compile_listener(events.append)
    return events


def _phase_serving(cache_dir: str, preset: str) -> dict:
    import numpy as np

    from perceiver_tpu.serving import ServingEngine, materialize

    task = _tiny_mlm_task() if preset == "tiny" else _canonical_mlm_task()
    batch_buckets, seq_buckets = _buckets(preset)
    t0 = time.perf_counter()
    engine = ServingEngine(task, batch_buckets=batch_buckets,
                           seq_buckets=seq_buckets, exec_cache=cache_dir,
                           warmup=False)
    # events scoped to the warmup+dispatch contract — params init
    # above legitimately compiles small host-side ops either way
    events = _compile_event_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0
    rng = np.random.default_rng(0)
    ids = rng.integers(3, task.vocab_size,
                       (batch_buckets[0], seq_buckets[0])).astype(np.int32)
    arrays = {"input_ids": ids,
              "pad_mask": np.zeros(ids.shape, bool)}
    materialize(engine.dispatch(arrays), engine.graph)
    m = engine.metrics
    return {
        "ttfd_s": time.perf_counter() - t0,
        "warmup_s": warmup_s,
        "buckets": len(engine.buckets),
        "xla_compiles": len(events),
        "engine_compiles": engine.compile_count,
        "exec_cache_hits": m.get("serving_exec_cache_hits_total").value,
        "exec_cache_misses": m.get(
            "serving_exec_cache_misses_total").value,
    }


def _phase_trainer(cache_dir: str, preset: str) -> dict:
    import jax

    from perceiver_tpu.analysis.targets import make_train_step
    from perceiver_tpu.cache import default_cache
    from perceiver_tpu.utils.flops import step_flops_and_fn

    task = _tiny_mlm_task() if preset == "tiny" else _canonical_mlm_task()
    import numpy as np

    batch = 8 if preset == "tiny" else 64
    rng = np.random.default_rng(0)
    data = {
        "input_ids": rng.integers(
            3, task.vocab_size,
            (batch, task.max_seq_len)).astype(np.int32),
        "pad_mask": np.zeros((batch, task.max_seq_len), bool),
    }
    step, args = make_train_step(task, data)
    cache = default_cache(cache_dir)
    events = _compile_event_counter()
    t0 = time.perf_counter()
    flops, fn = step_flops_and_fn(step, *args, cache=cache,
                                  cache_label="bench_startup:train")
    out = fn(*args)
    jax.block_until_ready(out)
    return {
        "first_step_s": time.perf_counter() - t0,
        "step_flops": flops,
        "xla_compiles": len(events),
        "exec_cache_hits": cache.stats.hits,
        "exec_cache_misses": cache.stats.misses,
    }


_PHASES = {"serving": _phase_serving, "trainer": _phase_trainer}


def _run_child(phase: str, cache_dir: str, preset: str) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--cache-dir", cache_dir, "--preset", preset]
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          cwd=_REPO, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"phase {phase} failed:\n{proc.stdout}\n{proc.stderr}")
    # last stdout line is the phase's JSON record
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser(
        description="cold vs warm time-to-first-dispatch bench")
    ap.add_argument("--preset", default="tiny",
                    choices=["tiny", "canonical"],
                    help="tiny: CPU-sized model (default); canonical: "
                         "the pinned MLM serve/train shapes")
    ap.add_argument("--cache-dir", default=None,
                    help="cache directory (default: a fresh temp dir, "
                         "removed afterwards unless --keep-cache)")
    ap.add_argument("--keep-cache", action="store_true",
                    help="leave the populated cache dir behind")
    ap.add_argument("--out", default=None,
                    help="also append the result lines to this path")
    ap.add_argument("--phase", default=None, choices=sorted(_PHASES),
                    help=argparse.SUPPRESS)  # internal: child mode
    args = ap.parse_args()

    if args.phase:
        # child mode: one measurement in THIS process, JSON to stdout
        print(json.dumps(_PHASES[args.phase](args.cache_dir,
                                             args.preset)), flush=True)
        return 0

    cache_dir = args.cache_dir or tempfile.mkdtemp(prefix="exec-cache-")
    os.makedirs(cache_dir, exist_ok=True)
    results = []
    try:
        for phase in ("serving", "trainer"):
            print(f"[bench_startup] {phase}: cold run ...",
                  file=sys.stderr, flush=True)
            cold = _run_child(phase, cache_dir, args.preset)
            print(f"[bench_startup] {phase}: warm run ...",
                  file=sys.stderr, flush=True)
            warm = _run_child(phase, cache_dir, args.preset)
            key = "ttfd_s" if phase == "serving" else "first_step_s"
            detail = {
                "preset": args.preset,
                "cold_s": round(cold[key], 4),
                "warm_s": round(warm[key], 4),
                "cold_xla_compiles": cold["xla_compiles"],
                "warm_xla_compiles": warm["xla_compiles"],
                "warm_exec_cache_hits": warm["exec_cache_hits"],
                "warm_exec_cache_misses": warm["exec_cache_misses"],
            }
            if phase == "serving":
                detail["buckets"] = cold["buckets"]
            result = {
                "metric": f"{phase}_warm_start_speedup",
                "value": round(cold[key] / max(warm[key], 1e-9), 3),
                "unit": "x",
                "vs_baseline": None,
                "detail": detail,
            }
            results.append(result)
            print(json.dumps(result), flush=True)
    finally:
        if not args.keep_cache and args.cache_dir is None:
            shutil.rmtree(cache_dir, ignore_errors=True)
    if args.out:
        with open(args.out, "a") as f:
            for result in results:
                f.write(json.dumps(result) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
