#!/usr/bin/env python
"""Benchmark: IMDB-MLM training throughput on one TPU chip.

Measures the BASELINE.md primary metric — tokens/sec/chip for MLM
pretraining at seq_len=512 with the reference model config (64×64
latents, 3 encoder layers, 6 self-attn layers/block, vocab 10003) —
on full jitted train steps (forward + backward + AdamW update) in
bf16, with the packed fused-CE loss path and several optimizer steps
per dispatch (lax.scan). Prints JSON result lines to stdout, one per
completed config, later lines superseding earlier — the final line is
the one the driver should record.

Config comes from BENCH_BATCH / BENCH_INNER_STEPS / BENCH_LOSS_IMPL
when set (pinned exactly); otherwise a ladder of configs is run
smallest-first, each completed rung flushed immediately.

``BENCH_TASK=img_clf`` switches to the secondary BASELINE.md metric:
MNIST imgs/sec/chip with the ``scripts/img_clf.py`` model config
(32×128 latents, 3 layers, 3 self-attn layers/block, 32 bands).

``vs_baseline`` is null: the reference publishes no throughput numbers
(BASELINE.json "published": {}).

The bench is one process, and it measures a TPU: it exits non-zero
at once when JAX's platform is not ``tpu``. ``BENCH_PLATFORM=cpu`` asks
for a smoke run of the code path on the CPU (its numbers are not
device numbers). A rung that fails is a non-zero exit, not a log line.
"""

import json
import os
import sys
import time
from functools import partial

import numpy as np

from perceiver_tpu.utils.timing import fence

# Rung dicts, most → least aggressive: the pallas streaming-CE rungs
# (C=64 and C=128) over the packed/dense A/B rungs. None of them has a
# chip number of record; the grid that replaces this ladder is
# ROADMAP S1.
_LADDER = [
    dict(batch=512, inner=16, loss="pallas", attn="chunked",
         dec="chunked", remat=True),
    dict(batch=512, inner=16, loss="pallas", attn="chunked",
         dec="chunked", remat=True, channels=128),
    dict(batch=512, inner=8, loss="packed"),
    dict(batch=256, inner=8, loss="packed"),
    dict(batch=128, inner=4, loss="packed"),
    dict(batch=64, inner=1, loss="packed"),
    dict(batch=64, inner=1, loss="dense"),
]

_T0 = time.monotonic()


def _log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def require_platform() -> None:
    """Initialize the backend once and hold it to the platform the run
    is for: ``tpu``, or what ``BENCH_PLATFORM`` names for a smoke run."""
    import jax

    want = os.environ.get("BENCH_PLATFORM", "tpu")
    if want != "tpu":
        jax.config.update("jax_platforms", want)
    device = jax.devices()[0]
    if device.platform != want:
        raise SystemExit(
            f"bench.py measures platform {want!r} but JAX found "
            f"{device.platform!r}; set BENCH_PLATFORM=cpu for a smoke "
            "run of the code path")
    _log(f"backend up: {jax.devices()}")


def _bench_train(task, stacked_batch: dict, *, batch_size: int,
                 inner_steps: int, units_per_step: int, metric: str,
                 unit: str, detail: dict) -> dict:
    """Shared measurement core: jit inner_steps optimizer steps into one
    dispatch (lax.scan), AOT-compile, warm up, time, report."""
    import jax
    import optax

    from perceiver_tpu.ops.policy import Policy
    from perceiver_tpu.utils.flops import (
        device_peak_flops,
        mfu,
        step_flops_and_fn,
    )

    model = task.build()
    policy = Policy.bf16()

    params = model.init(jax.random.key(0))
    tx = optax.adamw(1e-3)
    opt_state = tx.init(params)

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_steps(params, opt_state, stacked, rng):
        """inner_steps optimizer steps in one dispatch (lax.scan)."""

        def one(carry, xs):
            params, opt_state = carry
            batch_i, key_i = xs

            def loss_fn(p):
                loss, _ = task.loss_and_metrics(
                    model, p, batch_i, rng=key_i,
                    deterministic=False, policy=policy)
                return loss

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), loss

        keys = jax.random.split(rng, inner_steps)
        (params, opt_state), losses = jax.lax.scan(
            one, (params, opt_state), (stacked, keys))
        return params, opt_state, losses[-1]

    key = jax.random.key(1)
    # HLO cost analysis counts a while/scan body ONCE, not trip-count
    # times, so the dispatch's reported FLOPs already approximate one
    # optimizer step — use as-is (verified on the CPU backend: the
    # number is invariant in inner_steps).
    _log("tracing + compiling train_steps ...")

    # graphcheck provenance (ISSUE 1): the dtype audit of the very
    # lowering being timed, so every result row carries machine-
    # readable proof of what the matmuls ran in. BENCH_GRAPHCHECK=0
    # skips it (saves the as_text walk on slow hosts).
    graphcheck = {}

    def _audit_lowered(lowered):
        if os.environ.get("BENCH_GRAPHCHECK", "1") == "0":
            return
        try:
            # cost-analysis bytes of the very lowering being timed —
            # the same number the hbm_budget merge gate pins
            # (perceiver_tpu/analysis/hbm_budgets.json), riding the
            # result so every row carries its traffic provenance
            from perceiver_tpu.analysis.targets import (
                cost_bytes_accessed,
            )
            graphcheck["hbm_bytes"] = cost_bytes_accessed(lowered)
            from perceiver_tpu.analysis import hlo
            s = hlo.dot_flop_summary(list(hlo.iter_dots(
                lowered.as_text())))
            graphcheck.update(
                bf16_flop_fraction=s["bf16_flop_fraction"],
                flop_weighted_k_ceiling=s["flop_weighted_k_ceiling"],
                n_dot_general=s["n_dot_general"])
        except Exception as e:  # noqa: BLE001 — provenance only
            graphcheck["error"] = f"{type(e).__name__}: {e}"[:160]

    step_flops, train_steps = step_flops_and_fn(
        train_steps, params, opt_state, stacked_batch, key,
        on_lowered=_audit_lowered)
    _log("compiled; warming up ...")
    # warmup (compile already done when step_flops_and_fn AOT-compiled)
    t_warm = time.perf_counter()
    params, opt_state, loss = train_steps(params, opt_state, stacked_batch,
                                          key)
    fence(loss)
    _log(f"warm ({time.perf_counter() - t_warm:.2f}s); timing ...")

    profile_dir = os.environ.get("BENCH_PROFILE")
    if profile_dir:
        jax.profiler.start_trace(profile_dir)

    try:
        n_dispatch = int(os.environ.get("BENCH_DISPATCHES", "0")) \
            or max(64 // inner_steps, 8)
        n_steps = n_dispatch * inner_steps
        # all dispatch keys up front: an eager jax.random.fold_in
        # inside the timed loop costs host tracing + a dispatch that
        # has nothing to do with step throughput. Iterating the split
        # performs the eager slices HERE, before the clock starts.
        dispatch_keys = list(jax.random.split(key, n_dispatch))
        fence(jax.random.key_data(dispatch_keys[-1]))
        dt = 0.0
        for i in range(n_dispatch):
            key = dispatch_keys[i]
            t_i = time.perf_counter()
            params, opt_state, loss = train_steps(params, opt_state,
                                                  stacked_batch, key)
            jax.block_until_ready(loss)
            dt += time.perf_counter() - t_i
            # the log write stays OUT of the summed segments (slow
            # stderr must not inflate the measurement)
            _log(f"dispatch {i + 1}/{n_dispatch} done (+{dt:.2f}s)")
        # host-fetch of the final loss scalar — it data-depends on
        # every step, so the summed wall clock includes all n_steps
        t_f = time.perf_counter()
        final_loss = fence(loss)
        dt += time.perf_counter() - t_f
        _log(f"fenced: {n_steps} steps in {dt:.2f}s")
    finally:
        # always close the trace; a failing stop must not mask the
        # original error
        if profile_dir:
            try:
                jax.profiler.stop_trace()
                _trace_ok = True
            except Exception as e:  # noqa: BLE001
                _trace_ok = False
                _log(f"stop_trace failed: {e}")
    if profile_dir and _trace_ok:
        _log(f"profile trace written to {profile_dir}")

    steps_per_sec = n_steps / dt
    util = mfu(step_flops, n_steps, dt,
               peak_flops_per_device=device_peak_flops())

    return {
        "metric": metric,
        "value": round(steps_per_sec * units_per_step, 1),
        "unit": unit,
        "vs_baseline": None,
        "detail": {
            **detail,
            "batch_size": batch_size,
            "inner_steps": inner_steps,
            "steps_per_sec": round(steps_per_sec, 3),
            "precision": "bf16",
            "mfu": round(util, 4) if util is not None else None,
            "step_tflops": (round(step_flops / 1e12, 3)
                            if step_flops else None),
            "loss": final_loss,
            "device": str(jax.devices()[0]),
            # truthful evidence labeling (VERDICT r2 #7): what the
            # numbers were actually measured on, machine-readable
            "platform": jax.devices()[0].platform,
            "device_kind": getattr(jax.devices()[0], "device_kind", None),
            # cost-analysis bytes/step of the timed lowering (the
            # hbm_budget gate's metric; None off cost-model backends)
            "hbm_bytes": graphcheck.pop("hbm_bytes", None),
            # lowered-graph dtype provenance (scripts/check.py gates
            # the same numbers at merge; here they ride the result)
            "graphcheck": graphcheck or None,
        },
    }


def _knobs(rung: dict) -> dict:
    """Perf knobs (trace-driven, r05): the levers that cut HBM traffic
    are the streaming CE (loss_impl=pallas, MLM only),
    non-materializing attention (attn=chunked|flash), decoder ditto
    (dec), and remat (recompute instead of storing scan residuals —
    FLOPs are nearly free at this MFU). The RUNG supplies the defaults
    (the ladder's top rung carries the round-5 winner combination);
    BENCH_ATTN_IMPL / BENCH_DEC_IMPL / BENCH_KV_CHUNK / BENCH_REMAT
    override them exactly — sweeps rely on that. Shared TaskConfig
    fields, so every BENCH_TASK honors them; the values are echoed
    into the result detail dict so rows from different knob
    combinations stay distinguishable."""
    remat_env = os.environ.get("BENCH_REMAT")
    return dict(
        attention_impl=(os.environ.get("BENCH_ATTN_IMPL")
                        or rung.get("attn")),
        decoder_attention_impl=(os.environ.get("BENCH_DEC_IMPL")
                                or rung.get("dec")),
        kv_chunk_size=int(os.environ.get("BENCH_KV_CHUNK", "1024")),
        remat=(remat_env == "1" if remat_env is not None
               else bool(rung.get("remat", False))))


def run(rung: dict) -> dict:
    import jax.numpy as jnp

    from perceiver_tpu.tasks import MaskedLanguageModelTask

    batch_size, inner_steps = rung["batch"], rung["inner"]
    loss_impl = rung["loss"]
    seq_len, vocab = 512, 10003
    channels = int(os.environ.get("BENCH_CHANNELS",
                                  str(rung.get("channels", 64))))
    knobs = _knobs(rung)
    task = MaskedLanguageModelTask(
        vocab_size=vocab, max_seq_len=seq_len, loss_impl=loss_impl,
        num_latent_channels=channels, **knobs)
    rng = np.random.default_rng(0)
    stacked = {
        "input_ids": jnp.asarray(rng.integers(
            3, vocab, (inner_steps, batch_size, seq_len)), jnp.int32),
        "pad_mask": jnp.zeros((inner_steps, batch_size, seq_len), bool),
    }
    return _bench_train(
        task, stacked, batch_size=batch_size, inner_steps=inner_steps,
        units_per_step=batch_size * seq_len,
        metric="imdb_mlm_tokens_per_sec_per_chip", unit="tokens/s",
        detail={"seq_len": seq_len, "loss_impl": loss_impl,
                "num_latent_channels": channels, **knobs})


def run_img(rung: dict) -> dict:
    """Secondary BASELINE.md metric: MNIST imgs/sec/chip with the
    ``scripts/img_clf.py`` model config (32×128 latents, 3 layers,
    3 self-attn layers/block, 32 frequency bands)."""
    import jax.numpy as jnp

    from perceiver_tpu.tasks import ImageClassifierTask

    batch_size, inner_steps = rung["batch"], rung["inner"]
    knobs = _knobs(rung)  # CE over 10 classes; no fused-loss variants
    task = ImageClassifierTask(
        image_shape=(28, 28, 1), num_classes=10, num_frequency_bands=32,
        num_latents=32, num_latent_channels=128, num_encoder_layers=3,
        num_encoder_self_attention_layers_per_block=3,
        num_decoder_cross_attention_heads=1, **knobs)
    rng = np.random.default_rng(0)
    stacked = {
        "image": jnp.asarray(rng.normal(
            0, 1, (inner_steps, batch_size, 28, 28, 1)), jnp.float32),
        "label": jnp.asarray(rng.integers(
            0, 10, (inner_steps, batch_size)), jnp.int32),
    }
    return _bench_train(
        task, stacked, batch_size=batch_size, inner_steps=inner_steps,
        units_per_step=batch_size,
        metric="mnist_imgs_per_sec_per_chip", unit="imgs/s",
        detail={"image_shape": [28, 28, 1], **knobs})


def run_seg(rung: dict):
    """``BENCH_TASK=seg``: the 512×512 / 262,144-output-query LArTPC
    segmentation config (``run.py:72-112``) — pixels/sec/chip, the
    decoder-query-chunking + long-kv memory stress config.
    ``BENCH_SEG_SIZE`` overrides the side length (smoke runs use 64;
    pinned values are honored exactly, like every other BENCH_* env)."""
    import jax.numpy as jnp

    from perceiver_tpu.tasks import SegmentationTask

    batch_size, inner_steps = rung["batch"], rung["inner"]
    knobs = _knobs(rung)  # weighted CE over 3 classes; no fused variants
    side = int(os.environ.get("BENCH_SEG_SIZE", "512"))
    task = SegmentationTask(image_shape=(side, side, 1),
                            query_chunk_size=min(16384, side * side),
                            **knobs)
    rng = np.random.default_rng(0)
    stacked = {
        "image": jnp.asarray(
            rng.random((inner_steps, batch_size, side, side, 1)) *
            (rng.random((inner_steps, batch_size, side, side, 1)) < 0.01),
            jnp.float32),
        "label": jnp.asarray(rng.integers(
            0, 3, (inner_steps, batch_size, side, side)), jnp.int32),
    }
    return _bench_train(
        task, stacked, batch_size=batch_size, inner_steps=inner_steps,
        units_per_step=batch_size * side * side,
        metric="lartpc_seg_pixels_per_sec_per_chip", unit="pixels/s",
        detail={"image_shape": [side, side, 1],
                "num_output_queries": side * side, **knobs})


def main():
    from perceiver_tpu.cache import enable_compile_cache

    enable_compile_cache()
    pinned = any(k in os.environ for k in
                 ("BENCH_BATCH", "BENCH_INNER_STEPS", "BENCH_LOSS_IMPL"))
    top = _LADDER[0]
    if pinned:
        # a pinned config carries NO rung knob defaults — exactly the
        # env vars set (BENCH_ATTN_IMPL etc.), nothing more
        configs = [dict(
            batch=int(os.environ.get("BENCH_BATCH", str(top["batch"]))),
            inner=int(os.environ.get("BENCH_INNER_STEPS",
                                     str(top["inner"]))),
            loss=os.environ.get("BENCH_LOSS_IMPL", top["loss"]))]
    else:
        # smallest config first, the dense comparison rung last; each
        # completed rung flushes its JSON line immediately
        rungs = list(reversed(_LADDER))
        configs = ([c for c in rungs if c["loss"] != "dense"]
                   + [c for c in rungs if c["loss"] == "dense"])

    runner = {"img_clf": run_img, "seg": run_seg}.get(
        os.environ.get("BENCH_TASK", ""), run)
    if runner is run_seg and not pinned:
        # the 262k-query config is memory-bound in BATCH, not in
        # inner_steps — its ladder climbs the axis that matters
        configs = [dict(batch=1, inner=1, loss="n/a"),
                   dict(batch=2, inner=1, loss="n/a"),
                   dict(batch=4, inner=1, loss="n/a")]
    elif runner is not run:
        # loss_impl/channels don't apply to these tasks — collapse
        # ladder entries that only differ in them (keep first-seen
        # order and the first-seen rung's attention/remat knobs)
        seen, deduped = set(), []
        for c in configs:
            if (c["batch"], c["inner"]) not in seen:
                seen.add((c["batch"], c["inner"]))
                deduped.append(dict(c, loss="n/a"))
        configs = deduped

    require_platform()

    results = []
    for i, rung in enumerate(configs):
        _log(f"config {i + 1}/{len(configs)}: "
             f"batch={rung['batch']} inner={rung['inner']} "
             f"loss={rung['loss']} "
             f"attn={rung.get('attn')} dec={rung.get('dec')} "
             f"remat={bool(rung.get('remat'))} "
             f"C={rung.get('channels', 64)}")
        # a rung that raises ends the run non-zero: there is no best
        # survivor to report in its place
        result = runner(rung)
        print(json.dumps(result), flush=True)
        results.append(result)
    if len(results) > 1:
        # re-emit the best rung so a last-line parse records the best
        # throughput, not merely the largest completed config
        best = max(results, key=lambda r: r.get("value") or 0)
        print(json.dumps(best), flush=True)


if __name__ == "__main__":
    main()
