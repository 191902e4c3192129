#!/usr/bin/env python
"""Chaos harness: run the fault matrix against a tiny preset and prove
every defense (docs/RESILIENCE.md).

Each scenario arms one deterministic fault (``resilience/faults.py``)
in a FRESH subprocess (the ``PERCEIVER_FAULTS`` env seam — exactly how
a chaos job arms a production binary) and asserts the run still
reaches its target: training hits its target step with
verified-checkpoint resume where resumes are involved, and serving
answers every request with a result or a *typed* error — zero
unhandled exceptions, zero silent data loss. ``kill_save`` goes one
step further and SIGKILLs a training victim mid-checkpoint-save in a
grand-child process (crash-only checkpointing).

Emits one JSON line per scenario::

    {"metric": "chaos_serve_dispatch", "value": 1.0, "unit":
     "survived", "vs_baseline": null, "detail": {"faults_fired": ...,
     "recovery_s": ..., ...}}

plus a ``chaos_matrix`` summary line; exits non-zero iff any scenario
failed. ``--fast`` runs the tier-1 subset
(``tests/test_chaos.py`` mirrors the ``check.py`` subprocess-gate
pattern); ``--fleet``/``--fleet-fast`` run the multi-process fleet
matrix, and ``--dist``/``--dist-fast`` the multi-host matrix
(process-group training recovery, coordinator loss, group-replica
failover, two-phase cutover kill)::

    JAX_PLATFORMS=cpu python scripts/chaos.py --fast
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# The determinism gates (race_*, prefix_evict_under_load) assert
# bitwise token equality between replayed schedules and a serial
# reference. A persistent XLA compilation cache inherited from the
# environment deserializes executables compiled under
# a DIFFERENT flag environment, which shifts near-tied logits on the
# degenerate scenario models — drop it before jax initializes so
# every chaos process compiles its own executables from scratch.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

TARGET_STEP = 6


def _tiny_image_task():
    from perceiver_tpu.tasks import ImageClassifierTask

    return ImageClassifierTask(
        image_shape=(28, 28, 1), num_classes=10, num_frequency_bands=4,
        num_latents=4, num_latent_channels=16, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_decoder_cross_attention_heads=1)


def _make_trainer(tmp: str, tag: str, **overrides):
    from perceiver_tpu.data import MNISTDataModule
    from perceiver_tpu.training import Trainer, TrainerConfig

    dm = MNISTDataModule(data_dir=os.path.join(tmp, "data"),
                         batch_size=16, synthetic_train_size=96,
                         synthetic_test_size=32)
    cfg = dict(max_steps=TARGET_STEP, max_epochs=8,
               num_sanity_val_steps=0, log_every_n_steps=1,
               default_root_dir=os.path.join(tmp, f"logs_{tag}"),
               enable_checkpointing=False, prefetch_batches=0)
    cfg.update(overrides)
    return Trainer(_tiny_image_task(), dm, TrainerConfig(**cfg),
                   optimizer_init={"class_path": "AdamW",
                                   "init_args": {"lr": 1e-3}})


def _finite(state) -> bool:
    import jax
    import numpy as np

    return all(bool(np.isfinite(np.asarray(leaf)).all())
               for leaf in jax.tree.leaves(state.params)
               if np.issubdtype(np.asarray(leaf).dtype, np.floating))


# --- scenarios (run in a fresh subprocess each) ------------------------------


def scenario_loader_crash(tmp: str) -> dict:
    """Prefetch producer raises twice; the supervisor restarts it with
    backoff and the run still reaches its target step."""
    trainer = _make_trainer(tmp, "loader", prefetch_batches=2)
    state = trainer.fit()
    assert int(state.step) == TARGET_STEP, int(state.step)
    assert _finite(state)
    return {"target_step": TARGET_STEP, "reached": int(state.step)}


def scenario_nan_skip(tmp: str) -> dict:
    """Two isolated non-finite steps are skipped (no parameter update,
    counter metric) and training completes with finite params."""
    trainer = _make_trainer(tmp, "nan", nonfinite_policy="skip",
                            nonfinite_streak=3)
    state = trainer.fit()
    assert int(state.step) == TARGET_STEP, int(state.step)
    assert trainer._guard.skipped_total == 2, trainer._guard.skipped_total
    assert trainer._guard.rewinds == 0
    assert _finite(state)
    from perceiver_tpu.obs import events as events_mod

    skip_events = events_mod.default_log().events("guard_skip")
    assert len(skip_events) == 2, skip_events  # one typed event per skip
    return {"target_step": TARGET_STEP, "reached": int(state.step),
            "skipped_steps": trainer._guard.skipped_total,
            "skip_events": len(skip_events)}


def scenario_nan_rewind(tmp: str) -> dict:
    """A streak of bad steps triggers restore of the verified anchor
    checkpoint + deterministic data rewind; the fault window expires
    during the replay and the run completes."""
    trainer = _make_trainer(tmp, "rewind", max_steps=8,
                            nonfinite_policy="skip", nonfinite_streak=3,
                            nonfinite_max_rewinds=2)
    state = trainer.fit()
    assert int(state.step) == 8, int(state.step)
    assert trainer._guard.rewinds >= 1
    assert _finite(state)
    return {"target_step": 8, "reached": int(state.step),
            "rewinds": trainer._guard.rewinds,
            "skipped_steps": trainer._guard.skipped_total}


def _checkpointed_run(tmp: str, tag: str, max_steps: int):
    trainer = _make_trainer(tmp, tag, max_steps=max_steps,
                            enable_checkpointing=True, save_top_k=2)
    state = trainer.fit()
    return trainer, state


def scenario_truncated_ckpt(tmp: str) -> dict:
    """The newest checkpoint's blob is truncated after its manifest was
    sealed (bit rot); resume detects the mismatch, falls back to the
    newest VERIFIED step, and still reaches the target."""
    import warnings

    from perceiver_tpu.training.checkpoint import CheckpointHook

    trainer, _ = _checkpointed_run(tmp, "trunc", max_steps=10)
    ckpt_dir = os.path.join(trainer.log_dir, "checkpoints")
    hook = CheckpointHook(ckpt_dir, monitor="")
    steps = hook._steps()
    assert len(steps) >= 2, steps
    statuses = {s: hook.verify(s) for s in steps}
    assert statuses[steps[0]] == "corrupt", statuses  # fault landed
    assert statuses[steps[1]] == "verified", statuses

    resume = _make_trainer(tmp, "trunc_resume", max_steps=12,
                           resume_from_checkpoint=ckpt_dir)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        state = resume.fit()
    assert any("manifest" in str(w.message) for w in caught), \
        [str(w.message) for w in caught]
    assert int(state.step) == 12, int(state.step)
    return {"steps": {str(k): v for k, v in statuses.items()},
            "resumed_from": steps[1], "reached": int(state.step)}


def scenario_kill_save(tmp: str) -> dict:
    """SIGKILL a training victim mid-checkpoint-save (grand-child
    process, crash-only); resume from what survived — the newest step
    that is committed and not provably corrupt — and reach the target.
    """
    env = dict(os.environ,
               PERCEIVER_FAULTS="ckpt.kill_during_save@at=1",
               PERCEIVER_TPU_OFFLINE="1")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--scenario",
         "kill_save_victim", "--tmp", tmp],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == -signal.SIGKILL, (
        proc.returncode, proc.stdout, proc.stderr)

    from perceiver_tpu.training.checkpoint import CheckpointHook
    log_root = os.path.join(tmp, "logs_killvictim", "default")
    versions = sorted(os.listdir(log_root))
    ckpt_dir = os.path.join(log_root, versions[-1], "checkpoints")
    hook = CheckpointHook(ckpt_dir, monitor="")
    steps = hook._steps()
    assert steps, "victim died before any checkpoint committed"
    survivor = hook._newest_restorable_step()
    assert survivor is not None and hook.verify(survivor) != "corrupt"

    resume = _make_trainer(tmp, "kill_resume", max_steps=survivor + 3,
                           resume_from_checkpoint=ckpt_dir)
    state = resume.fit()
    assert int(state.step) == survivor + 3, int(state.step)
    assert _finite(state)
    return {"victim_rc": proc.returncode, "committed_steps": steps,
            "resumed_from": survivor, "reached": int(state.step)}


def scenario_kill_save_victim(tmp: str) -> dict:
    """(grand-child) train with checkpointing until the armed
    kill-during-save fault SIGKILLs this process."""
    _checkpointed_run(tmp, "killvictim", max_steps=25)
    raise AssertionError("victim survived its kill fault")


def scenario_preempt(tmp: str) -> dict:
    """An injected preemption notice saves full state to
    checkpoints-preempt (manifest-sealed) and stops cleanly; resume
    picks it up and reaches the target."""
    from perceiver_tpu.training.checkpoint import CheckpointHook

    trainer = _make_trainer(tmp, "preempt", max_steps=20)
    trainer.fit()
    stopped_at = trainer.global_step
    assert 0 < stopped_at < 20, stopped_at
    preempt_dir = os.path.join(trainer.log_dir, "checkpoints-preempt")
    hook = CheckpointHook(preempt_dir, monitor="")
    assert hook.verify(stopped_at) == "verified"

    resume = _make_trainer(tmp, "preempt_resume",
                           max_steps=stopped_at + 3,
                           resume_from_checkpoint=preempt_dir)
    state = resume.fit()
    assert int(state.step) == stopped_at + 3, int(state.step)
    return {"preempted_at": stopped_at, "reached": int(state.step)}


def scenario_serve_dispatch(tmp: str) -> dict:
    """Serve-dispatch failures: the batch fails with per-request typed
    errors, the bucket's breaker opens (requests get typed Unavailable
    without hanging), a half-open probe recovers it, and health walks
    READY → UNAVAILABLE → READY. Zero unhandled exceptions."""
    import numpy as np

    from perceiver_tpu.serving import (
        BatchError,
        HealthState,
        MicroBatcher,
        ServingEngine,
        Unavailable,
        materialize,
    )
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    task = MaskedLanguageModelTask(
        vocab_size=128, max_seq_len=32, num_latents=4,
        num_latent_channels=8, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_encoder_cross_attention_heads=1,
        num_encoder_self_attention_heads=1,
        num_decoder_cross_attention_heads=1, loss_impl="dense")
    engine = ServingEngine(task, batch_buckets=(1,), seq_buckets=(16,),
                           breaker_failure_threshold=2,
                           breaker_reset_s=0.25)
    assert engine.health.state is HealthState.READY

    def runner(payloads):
        res = engine.dispatch(payloads[0])
        return [materialize(res, engine.graph)]

    batcher = MicroBatcher(runner, max_batch=1, max_delay_ms=0.5,
                           metrics=engine.metrics)
    rng = np.random.default_rng(0)
    arrays = {"input_ids": rng.integers(3, 128, (1, 16)).astype(np.int32),
              "pad_mask": np.zeros((1, 16), bool)}

    counts = {"ok": 0, "batch_error": 0, "unavailable": 0}
    states_seen = {engine.health.state}
    first_failure_t = None
    recovered_t = None
    deadline = time.monotonic() + 30.0
    try:
        while time.monotonic() < deadline:
            try:
                out = batcher.submit(dict(arrays)).result(timeout=30)
                assert "topk_ids" in out
                counts["ok"] += 1
                if first_failure_t is not None and recovered_t is None:
                    recovered_t = time.monotonic()
                if recovered_t is not None and counts["ok"] >= 3:
                    break
            except Unavailable:
                counts["unavailable"] += 1
                if first_failure_t is None:
                    first_failure_t = time.monotonic()
                time.sleep(0.05)
            except BatchError:
                counts["batch_error"] += 1
                if first_failure_t is None:
                    first_failure_t = time.monotonic()
            states_seen.add(engine.health.state)
    finally:
        batcher.close()
    states_seen.add(engine.health.state)

    assert counts["batch_error"] >= 2, counts      # injected failures
    assert counts["unavailable"] >= 1, counts      # breaker opened
    assert recovered_t is not None, counts         # ...and recovered
    assert engine.health.state is HealthState.READY
    assert HealthState.UNAVAILABLE in states_seen  # sole bucket open
    m = engine.metrics
    assert m.get("serving_failed_batches_total").value >= 2
    assert m.get("serving_unavailable_total").value >= 1
    return {"requests": counts,
            "recovery_s": round(recovered_t - first_failure_t, 4),
            "health_states": sorted(s.name for s in states_seen),
            "failed_batches":
                m.get("serving_failed_batches_total").value}


# --- fleet scenarios (docs/SERVING.md "Fleet") -------------------------------
#
# Each builds a real multi-process fleet (router + supervisor +
# replica subprocesses) inside the scenario child, runs concurrent
# traffic through it while one fault lands, and asserts ZERO dropped
# requests: every submitted request resolves with a result or a typed
# ServingError — never a hang, never a raw traceback.

_FLEET_TASK_KWARGS = dict(
    vocab_size=110, max_seq_len=32, num_latents=4,
    num_latent_channels=8, num_encoder_layers=1,
    num_encoder_self_attention_layers_per_block=1,
    num_encoder_cross_attention_heads=1,
    num_encoder_self_attention_heads=1,
    num_decoder_cross_attention_heads=1, loss_impl="dense")


def _fleet_store(tmp: str, versions=("v1", "v2")):
    """Publish fresh-init params versions into a sealed store."""
    from perceiver_tpu.serving.graphs import build_serve_graph
    from perceiver_tpu.tasks import MaskedLanguageModelTask
    from perceiver_tpu.training.checkpoint import ParamsVersionStore

    graph = build_serve_graph(
        MaskedLanguageModelTask(**_FLEET_TASK_KWARGS))
    store = ParamsVersionStore(os.path.join(tmp, "store"))
    for seed, version in enumerate(versions):
        store.publish(version, graph.init_params(seed),
                      set_current=(seed == 0))
    return store


def _fleet_spec(store) -> dict:
    return {"task_class": "MaskedLanguageModelTask",
            "task_kwargs": _FLEET_TASK_KWARGS,
            "batch_buckets": [4], "seq_buckets": [16],
            "store_dir": store.directory, "version": "v1", "seed": 0}


def _start_fleet(tmp: str, store, *, replicas: int,
                 per_replica_env=None, dispatch_timeout_s: float = 15.0,
                 max_restarts: int = 3, group_size: int = 1):
    from perceiver_tpu.fleet import Fleet

    # replicas share one persistent exec cache: the first spin-up
    # compiles and stores, the rest deserialize (zero-compile)
    os.environ.setdefault("PERCEIVER_EXEC_CACHE",
                          os.path.join(tmp, "exec_cache"))
    spec = _fleet_spec(store)
    if group_size > 1:
        # each fleet replica becomes a process GROUP of this many
        # members (distributed/serving_group.py); per_replica_env keys
        # of the form "r0.m1" then arm a fault on ONE member
        spec["group_size"] = group_size
    return Fleet(spec, os.path.join(tmp, "fleet"),
                 replicas=replicas, max_restarts=max_restarts,
                 dispatch_timeout_s=dispatch_timeout_s,
                 per_replica_env=per_replica_env)


def _fleet_traffic(fleet, *, threads: int, requests: int,
                   interval_s: float = 0.01):
    """Drive concurrent traffic; account for every single request.

    Returns (counts, dropped): ``dropped`` collects anything outside
    the typed contract — a non-ServingError exception, or a typed
    Unavailable carrying no retry_after hint when the fleet claims
    saturation. Zero dropped is every fleet scenario's core assertion.
    """
    import threading as _threading

    import numpy as np

    from perceiver_tpu.serving.errors import ServingError, Unavailable

    counts = {"ok": 0, "unavailable": 0}
    dropped = []
    lock = _threading.Lock()

    def worker(seed: int):
        rng = np.random.default_rng(seed)
        for i in range(requests):
            arrays = {
                "input_ids": rng.integers(
                    3, 110, (2, 16)).astype(np.int32),
                "pad_mask": np.zeros((2, 16), bool)}
            try:
                out = fleet.submit(arrays)
                assert "outputs" in out and "topk_ids" in out["outputs"]
                with lock:
                    counts["ok"] += 1
            except Unavailable as e:
                with lock:
                    if e.retry_after_s > 0:
                        counts["unavailable"] += 1
                    else:
                        dropped.append(f"no retry_after: {e}")
            except ServingError:
                with lock:
                    counts["unavailable"] += 1
            except Exception as e:  # noqa: BLE001 — the dropped bucket
                with lock:
                    dropped.append(f"{type(e).__name__}: {e}")
            time.sleep(interval_s)

    pool = [_threading.Thread(target=worker, args=(s,), daemon=True)
            for s in range(threads)]
    for t in pool:
        t.start()
    for t in pool:
        t.join(300)
    total = counts["ok"] + counts["unavailable"] + len(dropped)
    assert total == threads * requests, (total, threads * requests)
    return counts, dropped


def scenario_fleet_kill_replica(tmp: str) -> dict:
    """kill -9 a replica mid-traffic (the ``replica.crash`` fault
    SIGKILLs it mid-dispatch): the in-flight request transparently
    fails over to a sibling, the supervisor restarts the dead replica
    with backoff, and every request resolves — zero dropped."""
    store = _fleet_store(tmp, versions=("v1",))
    crash_env = {"PERCEIVER_FAULTS": "replica.crash@at=5"}
    fleet = _start_fleet(tmp, store, replicas=3,
                         per_replica_env={"r0": crash_env},
                         dispatch_timeout_s=8.0)
    try:
        counts, dropped = _fleet_traffic(fleet, threads=4, requests=25)
        # the crash counter ticks before the respawn finishes; wait
        # for the replacement to actually rejoin the router
        deadline = time.monotonic() + 60
        while (fleet.supervisor.restarts_of("r0") < 1
               or fleet.size() < 3) and time.monotonic() < deadline:
            time.sleep(0.1)
        crashes = fleet.supervisor.restarts_of("r0")
        retries = fleet.router.metrics.get("fleet_retries_total").value
        size = fleet.size()
        from perceiver_tpu.obs import events as events_mod

        deaths = events_mod.default_log().events("replica_death")
        respawns = events_mod.default_log().events("replica_respawn")
    finally:
        fleet.close()
    assert not dropped, dropped
    assert counts["ok"] >= 90, counts     # the fleet kept serving
    assert crashes >= 1, "victim never crashed"
    assert retries >= 1, "no request failed over"
    assert size == 3, size                # supervisor restarted the slot
    # the typed event log saw the death AND the recovery — the same
    # stream an operator would tail (docs/OBSERVABILITY.md)
    assert any(e["replica"] == "r0" for e in deaths), deaths
    assert any(e["replica"] == "r0" for e in respawns), respawns
    return {"requests": counts, "dropped": len(dropped),
            "replica_crashes": crashes, "router_retries": retries,
            "fleet_size_after": size,
            "death_events": len(deaths), "respawn_events": len(respawns),
            "faults_fired": {"replica.crash": crashes}}


def scenario_fleet_stall(tmp: str) -> dict:
    """A replica's dispatch path stalls (``replica.stall``): the
    router's recv deadline converts the hang into retry-on-sibling,
    repeated deadline hits eject the replica (breaker opens), and a
    half-open traffic probe readmits it once the stall clears. Zero
    dropped, zero hung requests."""
    store = _fleet_store(tmp, versions=("v1",))
    stall_env = {"PERCEIVER_FAULTS": "replica.stall@at=3,count=3,value=4"}
    fleet = _start_fleet(tmp, store, replicas=3,
                         per_replica_env={"r0": stall_env},
                         dispatch_timeout_s=1.5)
    try:
        counts, dropped = _fleet_traffic(fleet, threads=4, requests=25)
        m = fleet.router.metrics
        ejections = m.get("fleet_ejections_total").value
        retries = m.get("fleet_retries_total").value
        status = fleet.statuses().get("r0", {})
        from perceiver_tpu.obs import events as events_mod

        ejection_events = events_mod.default_log().events("fleet_ejection")
    finally:
        fleet.close()
    assert not dropped, dropped
    assert counts["ok"] >= 90, counts
    assert ejections >= 1, "stalled replica was never ejected"
    assert retries >= 3, retries
    fired = status.get("faults_fired", {})
    assert fired.get("replica.stall") == 3, fired
    # the breaker transition surfaced as a typed event, not just a
    # counter — chaos asserts on the operator-facing stream
    assert any(e["replica"] == "r0" for e in ejection_events), \
        ejection_events
    return {"requests": counts, "dropped": len(dropped),
            "ejections": ejections, "router_retries": retries,
            "ejection_events": len(ejection_events),
            "faults_fired": fired}


def scenario_fleet_rollout_corrupt(tmp: str) -> dict:
    """Mid-rollout checkpoint corruption: after the first replica cut
    over to v2, the v2 blobs rot (truncated post-seal). The next
    replica's verified load fails typed, the rollout auto-rolls the
    updated replica back to v1, CURRENT never moves, and traffic never
    drops a request."""
    from perceiver_tpu.fleet import RolloutAborted
    from perceiver_tpu.training.checkpoint import (
        CheckpointIntegrityError,
        verify_step,
    )

    store = _fleet_store(tmp, versions=("v1", "v2"))
    fleet = _start_fleet(tmp, store, replicas=3)
    corrupted = []

    def corrupt_v2_once(rid):
        if corrupted:
            return
        vdir = store.path("v2")
        blobs = [(os.path.getsize(os.path.join(r, f)),
                  os.path.join(r, f))
                 for r, _, fs in os.walk(vdir) for f in fs
                 if "manifest" not in f]
        _, victim = max(blobs)
        with open(victim, "r+b") as f:
            f.truncate(max(os.path.getsize(victim) // 2, 1))
        corrupted.append(rid)

    try:
        import threading as _threading

        background = {"counts": None, "dropped": None}

        def traffic():
            background["counts"], background["dropped"] = \
                _fleet_traffic(fleet, threads=2, requests=40,
                               interval_s=0.02)

        t = _threading.Thread(target=traffic, daemon=True)
        t.start()
        aborted = None
        try:
            fleet.rolling_update("v2",
                                 on_replica_updated=corrupt_v2_once)
        except RolloutAborted as e:
            aborted = e
        t.join(300)
        versions = {rid: s.get("version")
                    for rid, s in fleet.statuses().items()}
        from perceiver_tpu.obs import events as events_mod

        rollout_events = events_mod.default_log().events("rollout_step")
    finally:
        fleet.close()
    assert aborted is not None, "corrupt rollout was not aborted"
    # the abort left a typed rollback trail in the event log
    assert any(e["stage"] == "rollback" for e in rollout_events), \
        rollout_events
    assert isinstance(aborted.cause, CheckpointIntegrityError), \
        aborted.cause
    assert aborted.rolled_back and not aborted.rollback_failed, (
        aborted.rolled_back, aborted.rollback_failed)
    assert set(versions.values()) == {"v1"}, versions
    assert store.current() == "v1"
    assert verify_step(store.path("v2")) == "corrupt"
    counts, dropped = background["counts"], background["dropped"]
    assert counts is not None and not dropped, dropped
    return {"requests": counts, "dropped": len(dropped),
            "rolled_back": aborted.rolled_back,
            "replica_versions": versions,
            "current_after": store.current(),
            "faults_fired": {"ckpt.bitrot(v2)": 1}}


def scenario_fleet_rollout(tmp: str) -> dict:
    """The clean zero-downtime rolling update across 3 replicas: the
    exec cache is pre-warmed, so every replica spin-up performs ZERO
    XLA compiles (per-replica jax.monitoring listener count over RPC);
    under concurrent traffic the v1→v2 cutover completes with zero
    failed requests (router retries absorb the per-replica drain
    windows)."""
    os.environ["PERCEIVER_EXEC_CACHE"] = os.path.join(tmp, "exec_cache")
    store = _fleet_store(tmp, versions=("v1", "v2"))

    # warm the persistent cache in-process with the same spec the
    # replicas will use: their AOT warmup then deserializes
    from perceiver_tpu.serving.engine import ServingEngine
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    warm = ServingEngine(MaskedLanguageModelTask(**_FLEET_TASK_KWARGS),
                         store.load("v1", None),
                         batch_buckets=(4,), seq_buckets=(16,))
    assert warm.compile_count <= 1  # at most the one cold compile

    fleet = _start_fleet(tmp, store, replicas=3)
    try:
        compiles = {rid: s.get("compile_events")
                    for rid, s in fleet.statuses().items()}
        assert len(compiles) == 3, compiles

        import threading as _threading

        background = {}

        def traffic():
            background["counts"], background["dropped"] = \
                _fleet_traffic(fleet, threads=3, requests=40,
                               interval_s=0.02)

        t = _threading.Thread(target=traffic, daemon=True)
        t.start()
        time.sleep(0.3)  # let traffic establish before the rollout
        summary = fleet.rolling_update("v2")
        t.join(300)
        versions = {rid: s.get("version")
                    for rid, s in fleet.statuses().items()}
        from perceiver_tpu.obs import events as events_mod

        rollout_events = events_mod.default_log().events("rollout_step")
    finally:
        fleet.close()
    counts, dropped = background["counts"], background["dropped"]
    assert not dropped, dropped
    # every replica's cutover left the full drain→cutover→undrain
    # trail in the typed event log
    for rid in versions:
        stages = [e["stage"] for e in rollout_events
                  if e["replica"] == rid and e["version"] == "v2"]
        assert stages == ["drain", "cutover", "undrain"], (rid, stages)
    # zero FAILED requests: with siblings always available, retries
    # absorb every drain window — nothing surfaces even as typed errors
    assert counts["unavailable"] == 0, counts
    assert counts["ok"] == 120, counts
    assert summary["updated"] == 3, summary
    assert set(versions.values()) == {"v2"}, versions
    assert store.current() == "v2"
    # the PR-4 unlock, fleet-wide: replica spin-up compiled NOTHING
    assert all(c == 0 for c in compiles.values()), compiles
    return {"requests": counts, "dropped": len(dropped),
            "rollout": summary, "replica_versions": versions,
            "spin_up_xla_compiles": compiles,
            "faults_fired": {}}


# --- multi-host scenarios (docs/RESILIENCE.md / SERVING.md "Multi-host") ----
#
# The dist matrix proves the fault-tolerant multi-host story end to
# end on one machine: process-group training recovery with a
# bitwise-identical stitched loss curve, coordinator loss as a typed
# timebox (never a hang), and sharded group replicas that survive
# losing one host — both under traffic and mid-cutover. Cross-process
# COLLECTIVES are not required (the CPU-backend probe in
# tests/conftest.py gates those); cluster *formation* is pure gRPC and
# runs everywhere, which is exactly what dist_coordinator_loss spans.


def _worker_argv(spec_path: str):
    """argv factory for ``perceiver_tpu.distributed.worker`` members,
    in the shape ``GroupSupervisor`` expects."""

    def spawn_argv(rank, nproc, coordinator, generation):
        return [sys.executable, "-m", "perceiver_tpu.distributed.worker",
                "--spec", spec_path, "--rank", str(rank),
                "--nproc", str(nproc), "--coordinator", coordinator,
                "--generation", str(generation)]

    return spawn_argv


def _telemetry_losses(workdir: str, generation: int) -> dict:
    """step -> loss float from one generation's telemetry JSONL (JSON
    round-trips the float bits, so == below means bitwise equal)."""
    path = os.path.join(workdir, "telemetry", f"g{generation}",
                        "telemetry.jsonl")
    losses = {}
    with open(path) as f:
        for line in f:
            ev = json.loads(line)
            if ev.get("type") == "train_step":
                losses[int(ev["step"])] = ev["loss"]
    return losses


def scenario_dist_coordinator_loss(tmp: str) -> dict:
    """Coordinator dead at bootstrap: every member exits with the TYPED
    rendezvous timeout (exit 77 + ``rendezvous_timeout`` event) inside
    the timebox instead of wedging forever in the gRPC retry loop; a
    clean retry against a live coordinator then forms a real 2-process
    cluster (rendezvous needs no collectives, so this half runs on any
    CPU backend)."""
    from perceiver_tpu.distributed.group import free_port
    from perceiver_tpu.distributed.worker import RENDEZVOUS_EXIT

    workdir = os.path.join(tmp, "coord")
    events_dir = os.path.join(tmp, "events")
    os.makedirs(workdir, exist_ok=True)
    os.makedirs(events_dir, exist_ok=True)
    spec_path = os.path.join(workdir, "spec.json")
    timeout_s = 4.0
    with open(spec_path, "w") as f:
        json.dump({"mode": "bootstrap_only", "workdir": workdir,
                   "rendezvous_timeout_s": timeout_s}, f)
    env = dict(os.environ, PERCEIVER_TPU_OFFLINE="1",
               PERCEIVER_EVENT_LOG=events_dir)
    env.pop("PERCEIVER_FAULTS", None)
    argv = _worker_argv(spec_path)

    def spawn(ranks, nproc, coordinator, generation):
        return [subprocess.Popen(
            argv(rank, nproc, coordinator, generation), env=env,
            cwd=_REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True) for rank in ranks]

    # phase 1 — the COORDINATOR host (rank 0, which would serve the
    # rendezvous endpoint) is dead: the surviving members dial an
    # address nobody will ever listen on and must fail TYPED within
    # the timebox, never hang in the gRPC retry loop
    dead = f"127.0.0.1:{free_port()}"
    t0 = time.monotonic()
    procs = spawn((1, 2), 3, dead, 0)
    outs = [p.communicate(timeout=240)[0] for p in procs]
    phase1_s = time.monotonic() - t0
    codes = [p.returncode for p in procs]
    assert codes == [RENDEZVOUS_EXIT] * 2, (codes, outs)
    assert all("RENDEZVOUS_TIMEOUT" in o for o in outs), outs
    assert phase1_s < 180, phase1_s  # timeboxed, not a hang
    timeout_events = []
    for name in sorted(os.listdir(events_dir)):
        with open(os.path.join(events_dir, name)) as f:
            for line in f:
                ev = json.loads(line)
                if ev.get("type") == "rendezvous_timeout":
                    timeout_events.append(ev)
    assert len(timeout_events) >= 2, timeout_events
    assert all(e["coordinator"] == dead for e in timeout_events), \
        timeout_events

    # phase 2 — clean retry against a LIVE coordinator (rank 0 hosts
    # the coordinator service): the same binary, a fresh generation,
    # and the cluster actually forms
    live = f"127.0.0.1:{free_port()}"
    procs = spawn((0, 1), 2, live, 1)
    outs2 = [p.communicate(timeout=240)[0] for p in procs]
    assert [p.returncode for p in procs] == [0, 0], outs2
    results = []
    for rank in range(2):
        with open(os.path.join(workdir,
                               f"result.g1.r{rank}.json")) as f:
            results.append(json.load(f))
    assert all(r["process_count"] == 2 for r in results), results
    return {"phase1_exit_codes": codes,
            "phase1_wall_s": round(phase1_s, 2),
            "rendezvous_timeout_events": len(timeout_events),
            "retry_process_count": results[0]["process_count"],
            "faults_fired": {"coordinator.dead": 1}}


def scenario_dist_kill_train_host(tmp: str) -> dict:
    """SIGKILL the training host at the dispatch boundary mid-epoch
    (``train.kill``): the group supervisor tears the group down and
    re-forms it as generation 1, which restores the newest
    sha256-verified anchor generation 0 left and replays the
    epoch-seeded stream to that exact position — the stitched per-step
    loss trace is BITWISE-identical to an uninterrupted control run."""
    from perceiver_tpu.distributed.group import GroupSupervisor
    from perceiver_tpu.obs import events as events_mod
    from perceiver_tpu.training.checkpoint import CheckpointHook

    # control and victim generations share one compiled-step cache
    os.environ.setdefault("PERCEIVER_EXEC_CACHE",
                          os.path.join(tmp, "exec_cache"))

    def write_spec(workdir):
        os.makedirs(workdir, exist_ok=True)
        spec_path = os.path.join(workdir, "spec.json")
        with open(spec_path, "w") as f:
            json.dump({"mode": "train", "workdir": workdir,
                       "max_steps": TARGET_STEP,
                       "guard_anchor_every_n_steps": 2,
                       "seed": 42}, f)
        return spec_path

    # control: one uninterrupted run -> the reference loss trace
    control_dir = os.path.join(tmp, "control")
    env = dict(os.environ, PERCEIVER_TPU_OFFLINE="1")
    env.pop("PERCEIVER_FAULTS", None)
    argv = _worker_argv(write_spec(control_dir))
    proc = subprocess.run(argv(0, 1, "127.0.0.1:0", 0), env=env,
                          cwd=_REPO, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, (proc.stdout[-3000:],
                                  proc.stderr[-3000:])
    control = _telemetry_losses(control_dir, 0)
    assert sorted(control) == list(range(1, TARGET_STEP + 1)), control

    # victim: the same job under the group supervisor, with the kill
    # armed in generation 0 ONLY (the member_env seam) so the
    # re-formed generation runs clean
    victim_dir = os.path.join(tmp, "victim")
    sup = GroupSupervisor(
        _worker_argv(write_spec(victim_dir)), 1, workdir=victim_dir,
        member_env=lambda rank, gen: (
            {"PERCEIVER_FAULTS": "train.kill@at=4"} if gen == 0
            else {}),
        name="train-pg")
    try:
        reforms = sup.run(timeout_s=600.0)
    finally:
        sup.close()
    assert reforms == 1, reforms

    g0 = _telemetry_losses(victim_dir, 0)
    g1 = _telemetry_losses(victim_dir, 1)
    anchors_g0 = os.path.join(victim_dir, "anchors", "g0")
    anchor = CheckpointHook(anchors_g0,
                            monitor="").newest_restorable_step()
    assert anchor is not None and anchor >= 1, anchor
    with open(os.path.join(victim_dir, "result.g1.r0.json")) as f:
        result = json.load(f)
    assert result["final_step"] == TARGET_STEP, result
    # generation 1 resumed from EXACTLY the newest verified anchor of
    # generation 0 and logged the consecutive remainder of the run
    assert result["resumed_from"] == anchors_g0, result
    assert sorted(g1) == list(range(anchor + 1, TARGET_STEP + 1)), \
        (anchor, sorted(g1))
    assert sorted(set(g0) | set(g1)) == \
        list(range(1, TARGET_STEP + 1)), (sorted(g0), sorted(g1))
    # the stitched trace matches the control BITWISE: every step either
    # generation logged carries the exact float the uninterrupted run
    # produced (anchor restore + epoch-seeded replay, no drift)
    stitched = dict(g0)
    stitched.update(g1)
    mismatches = {s: (stitched[s], control[s]) for s in stitched
                  if stitched[s] != control[s]}
    assert not mismatches, mismatches
    log = events_mod.default_log()
    leaves = [e for e in log.events("host_leave")
              if e["group"] == "train-pg"]
    reform_events = [e for e in log.events("group_reform")
                     if e["group"] == "train-pg"]
    assert leaves and leaves[0]["exit_code"] != 0, leaves
    assert reform_events and reform_events[0]["generation"] == 1, \
        reform_events
    return {"control_steps": len(control), "killed_after_step": anchor,
            "g0_steps": sorted(g0), "g1_steps": sorted(g1),
            "resumed_from_step": anchor, "reforms": reforms,
            "bitwise_identical": True,
            "faults_fired": {"train.kill": 1}}


def scenario_dist_kill_serve_host(tmp: str) -> dict:
    """kill -9 ONE host of a 2-member sharded replica group mid-
    traffic: the group declares itself dead as a whole (survivors of a
    torn collective can't serve), the fleet supervisor re-forms it as
    a fresh generation, and the router fails traffic over to the
    sibling group throughout — zero dropped requests."""
    store = _fleet_store(tmp, versions=("v1",))
    crash_env = {"PERCEIVER_FAULTS": "replica.crash@at=5"}
    fleet = _start_fleet(tmp, store, replicas=2, group_size=2,
                         per_replica_env={"r0.m0": crash_env},
                         dispatch_timeout_s=8.0)
    try:
        counts, dropped = _fleet_traffic(fleet, threads=4, requests=25)
        # wait for the replacement GROUP to rejoin the router
        deadline = time.monotonic() + 120
        while (fleet.supervisor.restarts_of("r0") < 1
               or fleet.size() < 2) and time.monotonic() < deadline:
            time.sleep(0.1)
        restarts = fleet.supervisor.restarts_of("r0")
        size = fleet.size()
        statuses = fleet.statuses()
        from perceiver_tpu.obs import events as events_mod

        log = events_mod.default_log()
        deaths = log.events("replica_death")
        respawns = log.events("replica_respawn")
        leaves = [e for e in log.events("host_leave")
                  if e["group"] == "r0"]
        joins = [e for e in log.events("host_join")
                 if e["group"] == "r0"]
        reforms = [e for e in log.events("group_reform")
                   if e["group"] == "r0"]
    finally:
        fleet.close()
    assert not dropped, dropped
    assert counts["ok"] >= 90, counts     # the fleet kept serving
    assert restarts >= 1, "victim group never died"
    assert size == 2, size                # the slot was re-formed
    # the replacement is a FULL group again, not a zombie quorum
    assert statuses.get("r0", {}).get("group_size") == 2, statuses
    assert any(e["replica"] == "r0" for e in deaths), deaths
    assert any(e["replica"] == "r0" for e in respawns), respawns
    assert leaves, "no host_leave for the killed member"
    assert len(joins) >= 4, joins         # 2 at spawn + 2 at re-form
    assert reforms and reforms[0]["generation"] >= 1, reforms
    return {"requests": counts, "dropped": len(dropped),
            "group_restarts": restarts, "fleet_size_after": size,
            "host_leave_events": len(leaves),
            "host_join_events": len(joins),
            "group_reform_events": len(reforms),
            "faults_fired": {"replica.crash": restarts}}


def scenario_dist_cutover_kill(tmp: str) -> dict:
    """SIGKILL a group member BETWEEN stage and swap of the two-phase
    cutover (``replica.commit_crash`` fires at commit entry): the
    already-committed member is rolled back to the previous version,
    the rollout aborts typed, the store's CURRENT pointer never moves,
    the supervisor re-forms the group on the old version, and the
    concurrent traffic never drops a request — no client ever observes
    torn params."""
    from perceiver_tpu.distributed.serving_group import GroupCutoverError
    from perceiver_tpu.fleet import RolloutAborted

    store = _fleet_store(tmp, versions=("v1", "v2"))
    crash_env = {"PERCEIVER_FAULTS": "replica.commit_crash@at=0"}
    fleet = _start_fleet(tmp, store, replicas=2, group_size=2,
                         per_replica_env={"r0.m1": crash_env},
                         dispatch_timeout_s=8.0)
    try:
        import threading as _threading

        background = {"counts": None, "dropped": None}

        def traffic():
            background["counts"], background["dropped"] = \
                _fleet_traffic(fleet, threads=2, requests=40,
                               interval_s=0.02)

        t = _threading.Thread(target=traffic, daemon=True)
        t.start()
        time.sleep(0.3)  # let traffic establish before the rollout
        aborted = None
        try:
            fleet.rolling_update("v2")
        except RolloutAborted as e:
            aborted = e
        t.join(300)
        # the supervisor re-forms r0; wait for the whole fleet to
        # converge back onto the OLD version
        deadline = time.monotonic() + 120
        versions = {}
        while time.monotonic() < deadline:
            versions = {rid: s.get("version")
                        for rid, s in fleet.statuses().items()}
            if len(versions) == 2 and set(versions.values()) == {"v1"}:
                break
            time.sleep(0.2)
        from perceiver_tpu.obs import events as events_mod

        log = events_mod.default_log()
        staged = {e["replica"] for e in log.events("cutover_stage")
                  if e["version"] == "v2"
                  and e["replica"].startswith("r0.")}
        acked = {e["replica"] for e in log.events("cutover_ack")
                 if e["version"] == "v2"
                 and e["replica"].startswith("r0.")}
        rollbacks = log.events("cutover_rollback")
        reforms = [e for e in log.events("group_reform")
                   if e["group"] == "r0"]
    finally:
        fleet.close()
    counts, dropped = background["counts"], background["dropped"]
    assert aborted is not None, "cutover kill did not abort the rollout"
    assert isinstance(aborted.cause, GroupCutoverError), aborted.cause
    assert store.current() == "v1"        # CURRENT never moved
    assert set(versions.values()) == {"v1"}, versions
    assert counts is not None and not dropped, dropped
    # two-phase ordering: BOTH members staged before any commit...
    assert staged == {"r0.m0", "r0.m1"}, staged
    # ...m0 committed and acked v2; m1 died at commit entry, so its
    # ack never appears and the group handle rolled the commit back
    assert acked == {"r0.m0"}, acked
    assert any(e["replica"] == "r0" and e["version"] == "v1"
               for e in rollbacks), rollbacks
    assert reforms, "killed group was never re-formed"
    return {"requests": counts, "dropped": len(dropped),
            "current_after": store.current(),
            "replica_versions": versions,
            "staged_members": sorted(staged),
            "acked_members": sorted(acked),
            "rollback_events": len(rollbacks),
            "group_reform_events": len(reforms),
            "rolled_back": aborted.cause.rolled_back,
            "rollback_failed": aborted.cause.rollback_failed,
            "faults_fired": {"replica.commit_crash": 1}}


def scenario_race_admission(tmp: str) -> dict:
    """Decode admission (``serving.batcher.AdmissionQueue``, the
    continuous-batching front door) driven through adversarial seeded
    interleavings: two producer threads offer streams while the
    step-loop consumer takes budget-gated prefixes, with the queue's
    lock swapped for an ``InstrumentedLock`` (every acquisition is a
    scheduler yield point) and the deque wrapped in a ``guarded()``
    proxy that raises the instant any access happens off-lock.
    Asserts conservation — every offered stream ends up admitted,
    shed, rejected, or still queued, exactly once — and that each
    seed replays bitwise-identically (the racecheck runtime-harness
    contract, docs/ANALYSIS.md "Racecheck")."""
    import itertools

    from perceiver_tpu.serving.batcher import AdmissionQueue
    from perceiver_tpu.utils.concurrency import (
        InstrumentedLock,
        InterleaveScheduler,
        guarded,
    )

    def run_once(seed: int):
        sched = InterleaveScheduler(seed=seed)
        # deterministic clock: admission/shedding decisions depend only
        # on the seeded schedule, never on wall time
        ticks = itertools.count()
        q = AdmissionQueue(max_depth=8,
                           clock=lambda: next(ticks) * 1e-3)
        lock = InstrumentedLock(sched, name="admission._lock")
        q._lock = lock
        q._queue = guarded(q._queue, lock, label="admission deque")

        offered, rejected = [], []
        admitted, shed = [], []

        def producer(base: int):
            def run():
                for i in range(6):
                    item = f"s{base}-{i}"
                    # every third stream carries an already-expired
                    # deadline so the shed path interleaves too
                    deadline = 0.0 if i % 3 == 2 else None
                    if q.offer(item, cost=1 + (i % 3),
                               deadline=deadline):
                        offered.append(item)
                    else:
                        rejected.append(item)
            return run

        def consumer():
            for _ in range(48):
                a, s = q.take(budget=4, slots=2)
                admitted.extend(a)
                shed.extend(s)
                if (len(offered) + len(rejected) == 12
                        and q.depth == 0):
                    return

        sched.spawn(producer(0), name="producer-0")
        sched.spawn(producer(1), name="producer-1")
        sched.spawn(consumer, name="step-loop")
        sched.run()
        leftover = q.drain_all()
        return (tuple(admitted), tuple(shed), tuple(rejected),
                tuple(leftover), tuple(sched.trace))

    seeds = [4, 7, 1234]
    totals = {"admitted": 0, "shed": 0, "rejected": 0, "leftover": 0}
    for seed in seeds:
        first = run_once(seed)
        admitted, shed, rejected, leftover, _trace = first
        everything = list(admitted) + list(shed) + list(rejected) \
            + list(leftover)
        expect = {f"s{b}-{i}" for b in (0, 1) for i in range(6)}
        assert sorted(everything) == sorted(expect), (
            f"seed {seed}: streams lost or duplicated: {everything}")
        # bitwise-reproducible: the same seed replays the same
        # interleaving, outcomes and all
        assert run_once(seed) == first, f"seed {seed} not deterministic"
        totals["admitted"] += len(admitted)
        totals["shed"] += len(shed)
        totals["rejected"] += len(rejected)
        totals["leftover"] += len(leftover)
    # The injected fault here is the scheduler itself: one adversarial
    # interleaving per seed, each replayed once to prove determinism.
    return {"seeds": seeds, "streams_per_seed": 12,
            "deterministic_replays": len(seeds), **totals,
            "faults_fired": {"race.interleave": len(seeds)}}


def scenario_race_mixed_prefill(tmp: str) -> dict:
    """The unified prefill+decode scheduler
    (``serving.batcher.ContinuousBatchScheduler``) under adversarial
    seeded interleavings: producers offer streams while the step-loop
    consumer alternates ``take`` (slot+page admission) with
    ``plan_chunks`` over the rows it owns — the mixed-phase hot path
    of the chunked-prefill decode engine. Asserts conservation (every
    offered stream admitted, shed, rejected, or left queued exactly
    once), the per-step budget invariant (non-head prefill chunks
    never exceed the leftover budget after decode rows; the FIFO head
    always advances >= 1 token; no chunk exceeds ``max_chunk`` or the
    remaining prompt), completion (every admitted prompt prefills to
    zero remaining and then decodes), and seed-deterministic replay
    (the racecheck runtime-harness contract)."""
    import itertools

    from perceiver_tpu.serving.batcher import ContinuousBatchScheduler
    from perceiver_tpu.utils.concurrency import (
        InstrumentedLock,
        InterleaveScheduler,
        guarded,
    )

    BUDGET, MAX_CHUNK = 4, 3

    def run_once(seed: int):
        sched = InterleaveScheduler(seed=seed)
        ticks = itertools.count()
        q = ContinuousBatchScheduler(max_depth=8, token_budget=BUDGET,
                                     max_chunk=MAX_CHUNK,
                                     clock=lambda: next(ticks) * 1e-3)
        lock = InstrumentedLock(sched, name="scheduler._lock")
        q._lock = lock
        q._queue = guarded(q._queue, lock, label="scheduler deque")

        offered, rejected = [], []
        admitted, shed = [], []
        # consumer-owned mixed-phase state: item -> remaining prompt
        prefill, decoding = {}, {}
        planned_steps = [0]

        def producer(base: int):
            def run():
                for i in range(6):
                    item = f"s{base}-{i}"
                    deadline = 0.0 if i % 3 == 2 else None
                    if q.offer(item, cost=1 + (i % 3),
                               deadline=deadline):
                        offered.append(item)
                    else:
                        rejected.append(item)
            return run

        def consumer():
            for _ in range(64):
                a, s = q.take(budget=4, slots=3 - len(prefill)
                              - len(decoding))
                admitted.extend(a)
                shed.extend(s)
                for item in a:
                    # deterministic prompt length from the stream id
                    prefill[item] = 2 + (int(item[-1]) % 4)
                order = sorted(prefill)  # FIFO by id (deterministic)
                rems = [prefill[i] for i in order]
                plan = q.plan_chunks(len(decoding), rems)
                planned_steps[0] += 1
                # --- the budget invariant, asserted EVERY step ---
                left = max(0, BUDGET - len(decoding))
                assert all(c <= MAX_CHUNK for c in plan), plan
                assert all(c <= r for c, r in zip(plan, rems)), plan
                assert sum(plan[1:]) <= left, (plan, left)
                assert sum(plan) <= left + 1, (plan, left)
                if rems:
                    assert plan[0] >= 1, plan  # head anti-starvation
                for item, c in zip(order, plan):
                    prefill[item] -= c
                    if prefill[item] == 0:
                        del prefill[item]
                        decoding[item] = 2  # decode a couple of steps
                for item in [d for d, n in decoding.items() if n == 0]:
                    del decoding[item]
                for item in decoding:
                    decoding[item] -= 1
                if (len(offered) + len(rejected) == 12
                        and q.depth == 0 and not prefill
                        and not decoding):
                    return

        sched.spawn(producer(0), name="producer-0")
        sched.spawn(producer(1), name="producer-1")
        sched.spawn(consumer, name="step-loop")
        sched.run()
        leftover = q.drain_all()
        assert not prefill, f"prompts stuck mid-prefill: {prefill}"
        return (tuple(admitted), tuple(shed), tuple(rejected),
                tuple(leftover), planned_steps[0],
                tuple(sched.trace))

    seeds = [3, 11, 4321]
    totals = {"admitted": 0, "shed": 0, "rejected": 0, "leftover": 0,
              "planned_steps": 0}
    for seed in seeds:
        first = run_once(seed)
        admitted, shed, rejected, leftover, steps, _trace = first
        everything = list(admitted) + list(shed) + list(rejected) \
            + list(leftover)
        expect = {f"s{b}-{i}" for b in (0, 1) for i in range(6)}
        assert sorted(everything) == sorted(expect), (
            f"seed {seed}: streams lost or duplicated: {everything}")
        assert run_once(seed) == first, f"seed {seed} not deterministic"
        totals["admitted"] += len(admitted)
        totals["shed"] += len(shed)
        totals["rejected"] += len(rejected)
        totals["leftover"] += len(leftover)
        totals["planned_steps"] += steps
    return {"seeds": seeds, "streams_per_seed": 12,
            "token_budget": BUDGET, "max_chunk": MAX_CHUNK,
            "deterministic_replays": len(seeds), **totals,
            "faults_fired": {"race.interleave": len(seeds)}}


def scenario_prefix_evict_under_load(tmp: str) -> dict:
    """Prefix-cache eviction under adversarial page pressure
    (``serving.prefix_cache``): flooder streams with unique prefixes
    publish fresh chains into a tight arena that can only admit by
    LRU-evicting index-only pages, while shared-prefix clients stream
    prompts that should keep hitting the shared chain.

    Two phases, following the race_* scenario pattern (the token
    oracle must not depend on wall-clock thread timing):

    1. **Deterministic token-exactness.** A manually stepped engine is
       driven by seeded admission schedules interleaving shared-prefix
       clients with flooders; every client completion — across hit,
       miss, and post-eviction re-prefill states — must be
       bit-identical to a cold-prefill reference engine with caching
       disabled, and each seed's full completion log must replay
       bitwise-identically.
    2. **Free-threaded liveness.** Real client/flooder threads hammer
       an auto-stepping engine; asserts zero dropped requests (every
       submission resolves to a complete ``DecodeResult``, never a
       shed) and no refcount leak: at drain the index accounts for
       every allocated page, and flushing returns the arena to fully
       free."""
    import threading

    import numpy as np

    from perceiver_tpu.serving.decode import (
        DecodeEngine,
        DecodeGeometry,
        DecodeResult,
    )
    from perceiver_tpu.serving.prefix_cache import PrefixCacheConfig
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    task = MaskedLanguageModelTask(
        vocab_size=110, max_seq_len=48, num_latents=4,
        num_latent_channels=8, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_encoder_cross_attention_heads=1,
        num_encoder_self_attention_heads=1,
        num_decoder_cross_attention_heads=1, loss_impl="dense")
    # tight arena: 3 slots x 4 pages per stream = 12 of 16 allocatable
    # pages in flight, so published chains (2-3 pages each) force LRU
    # eviction within a few flooder admissions
    geometry = DecodeGeometry(max_streams=3, num_pages=17, page_size=4,
                              max_seq_len=48, max_chunk=4)
    engine = DecodeEngine(task, geometry=geometry, auto_step=False,
                          max_queue=64,
                          prefix_cache=PrefixCacheConfig())
    params = engine.params
    reference = DecodeEngine(task, params=params,
                             geometry=geometry, auto_step=True,
                             max_queue=64)

    rng = np.random.default_rng(7)
    shared = rng.integers(3, 100, size=8)          # 2 full pages
    tails = [rng.integers(3, 100, size=3) for _ in range(3)]
    client_prompts = [np.concatenate([shared, t]).astype(np.int32)
                      for t in tails]
    MAX_NEW = 6

    # cold-prefill references, caching disabled — the oracle the
    # cached path must match bit-for-bit
    expect = {}
    for p in client_prompts:
        r = reference.submit(p, max_new_tokens=MAX_NEW).result(120.0)
        assert isinstance(r, DecodeResult) and r.finished == "complete"
        expect[p.tobytes()] = list(r.tokens)
    reference.close()

    # -- phase 1: deterministic token-exactness under eviction churn --
    # Seeded schedules drive the manually stepped engine: shared-
    # prefix clients and unique-prefix flooders admitted in shuffled
    # order with a random number of engine steps between submissions,
    # so warm admissions land mid-decode, mid-flood, and after their
    # chain was evicted and republished.
    seeds = [0, 7]
    hits = exact = 0

    def run_once(seed: int):
        nonlocal hits, exact
        srng = np.random.default_rng(seed)
        frng = np.random.default_rng(10_000 + seed)
        kinds = ["c"] * 12 + ["f"] * 10
        srng.shuffle(kinds)
        handles, ci = [], 0
        for kind in kinds:
            if kind == "c":
                p = client_prompts[ci % len(client_prompts)]
                ci += 1
            else:
                p = frng.integers(3, 100, size=11).astype(np.int32)
            handles.append((kind, p.tobytes(),
                            engine.submit(p, max_new_tokens=MAX_NEW)))
            for _ in range(int(srng.integers(0, 4))):
                engine.step()
        engine.run_until_idle()
        log = []
        for kind, key, h in handles:
            r = h.result(1.0)
            assert isinstance(r, DecodeResult), f"dropped request: {r}"
            assert r.finished == "complete" and len(r.tokens) == MAX_NEW
            if kind == "c":
                assert r.tokens == expect[key], (
                    f"seed {seed}: cache state leaked into tokens: "
                    f"{r.tokens} != {expect[key]} "
                    f"(cached_tokens={r.cached_tokens})")
                exact += 1
                hits += r.cached_tokens > 0
            log.append((kind, tuple(r.tokens), r.cached_tokens))
        # reset cache state so each run starts from an empty index —
        # the schedule, not leftover trie state, is the input
        engine.flush_prefix_cache()
        assert engine.pool.free_pages == geometry.allocatable_pages, (
            f"arena not reclaimable after seed {seed}: "
            f"{engine.pool.free_pages} free of "
            f"{geometry.allocatable_pages}")
        return log

    for seed in seeds:
        first = run_once(seed)
        assert run_once(seed) == first, f"seed {seed} not deterministic"
    det_stats = engine.prefix_cache_stats()
    assert det_stats["evicted_pages"] >= 1, \
        "flood never forced an eviction — pressure too low to test"
    engine.close()

    # -- phase 2: free-threaded liveness (structural invariants only;
    # token equality lives in phase 1 where the schedule is replayable)
    engine = DecodeEngine(task, params=params,
                          geometry=geometry, auto_step=True,
                          max_queue=64, prefix_cache=PrefixCacheConfig())
    results, errors = [], []
    res_lock = threading.Lock()

    def client(worker: int):
        def run():
            try:
                for i in range(6):
                    p = client_prompts[(worker + i) % len(client_prompts)]
                    r = engine.submit(
                        p, max_new_tokens=MAX_NEW).result(120.0)
                    with res_lock:
                        results.append(("client", r))
            except BaseException as e:  # noqa: BLE001 — surfaced below
                with res_lock:
                    errors.append(e)
        return run

    def flooder():
        frng = np.random.default_rng(1234)
        try:
            for _ in range(10):
                p = frng.integers(3, 100, size=11).astype(np.int32)
                r = engine.submit(
                    p, max_new_tokens=MAX_NEW).result(120.0)
                with res_lock:
                    results.append(("flood", r))
        except BaseException as e:  # noqa: BLE001 — surfaced below
            with res_lock:
                errors.append(e)

    threads = [threading.Thread(target=client(w), name=f"client-{w}")
               for w in range(2)]
    threads.append(threading.Thread(target=flooder, name="flooder"))
    for t in threads:
        t.start()
    for t in threads:
        t.join(300.0)
        assert not t.is_alive(), f"{t.name} hung"
    assert not errors, f"client errors: {errors!r}"

    assert engine.drain(60.0), "engine failed to drain"
    stats = engine.prefix_cache_stats()
    dropped = sum(1 for _, r in results
                  if not isinstance(r, DecodeResult))
    for kind, r in results:
        assert isinstance(r, DecodeResult), f"dropped request: {r}"
        assert r.finished == "complete" and len(r.tokens) == MAX_NEW
        if kind == "client":
            hits += r.cached_tokens > 0
    assert len(results) == 22, f"expected 22 completions: {len(results)}"
    assert hits >= 1, "shared-prefix clients never hit the cache"
    # refcount-leak check: every allocated page is accounted to the
    # index, and dropping the index returns the arena to fully free
    assert engine.pool.allocated_pages == stats["pages_indexed"], (
        f"leaked pages: {engine.pool.allocated_pages} allocated vs "
        f"{stats['pages_indexed']} indexed")
    engine.flush_prefix_cache()
    assert engine.pool.free_pages == geometry.allocatable_pages, (
        f"arena not reclaimable: {engine.pool.free_pages} free of "
        f"{geometry.allocatable_pages}")
    engine.close()
    evicted = det_stats["evicted_pages"] + stats["evicted_pages"]
    return {"clients": 2, "client_requests": exact,
            "flood_requests": 10, "dropped": dropped,
            "client_hits": hits,
            "seeds": seeds, "deterministic_replays": len(seeds),
            "evicted_pages": evicted,
            "hit_tokens": (det_stats["hit_tokens"]
                           + stats["hit_tokens"]),
            "leak_free": True, "token_exact": True,
            "faults_fired": {"prefix.evict_pressure": evicted}}


def scenario_spec_reject_storm(tmp: str) -> dict:
    """Speculative decoding under adversarial 0%-acceptance
    (``serving.speculative``): the draft is a shrunk model with
    randomly initialized weights (``draft_seed`` only — never trained),
    so virtually every drafted token is rejected and every verify step
    rolls the target KV *and* the draft KV back by the full window.
    ``fallback_acceptance=0.0`` pins speculation ON, so the storm never
    de-escalates into plain decode — the rollback path runs for every
    stream on every step.

    Two phases, following the race_*/prefix_evict pattern:

    1. **Deterministic token-exactness.** A manually stepped
       speculative engine driven by seeded admission schedules must
       complete every request bit-identical to a plain (spec_k=0)
       reference engine sharing the same target params — the rejection
       rule's contract that speculation changes latency, never output,
       held at its worst case. Each seed's completion log replays
       bitwise-identically, and after every run BOTH arenas (target
       and draft) must be fully free.
    2. **Free-threaded liveness.** Client threads hammer an
       auto-stepping speculative engine; zero dropped requests, every
       completion still token-exact, and both arenas fully reclaimed
       at drain — a rejected window must never strand a page."""
    import threading
    from dataclasses import replace as _dc_replace

    import numpy as np

    from perceiver_tpu.serving.decode import (
        DecodeEngine,
        DecodeGeometry,
        DecodeResult,
    )
    from perceiver_tpu.serving.speculative import (
        SpeculativeConfig,
        shrink_task,
    )
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    task = MaskedLanguageModelTask(
        vocab_size=110, max_seq_len=32, num_latents=4,
        num_latent_channels=8, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_encoder_cross_attention_heads=1,
        num_encoder_self_attention_heads=1,
        num_decoder_cross_attention_heads=1, loss_impl="dense")
    geometry = DecodeGeometry(max_streams=3, num_pages=17, page_size=4,
                              max_seq_len=32, max_chunk=4, spec_k=3)
    spec_cfg = SpeculativeConfig(draft_task=shrink_task(task),
                                 draft_seed=1234,
                                 fallback_acceptance=0.0)
    engine = DecodeEngine(task, geometry=geometry, auto_step=False,
                          max_queue=64, speculative=spec_cfg)
    params = engine.params
    reference = DecodeEngine(task, params=params,
                             geometry=_dc_replace(geometry, spec_k=0),
                             auto_step=True, max_queue=64)

    rng = np.random.default_rng(11)
    prompts = [rng.integers(3, 100, size=n).astype(np.int32)
               for n in (5, 9, 11, 7)]
    MAX_NEW = 6

    expect = {}
    for p in prompts:
        r = reference.submit(p, max_new_tokens=MAX_NEW).result(120.0)
        assert isinstance(r, DecodeResult) and r.finished == "complete"
        expect[p.tobytes()] = list(r.tokens)
    reference.close()

    def _arenas_free(eng):
        assert eng.pool.free_pages == geometry.allocatable_pages, (
            f"target arena leaked: {eng.pool.free_pages} free of "
            f"{geometry.allocatable_pages}")
        assert (eng.draft_pool.free_pages
                == geometry.allocatable_pages), (
            f"draft arena leaked: {eng.draft_pool.free_pages} free of "
            f"{geometry.allocatable_pages}")

    # -- phase 1: deterministic token-exactness under total rejection --
    seeds = [0, 11]
    exact = 0

    def run_once(seed: int):
        nonlocal exact
        srng = np.random.default_rng(seed)
        handles = []
        for i in range(10):
            p = prompts[i % len(prompts)]
            handles.append((p.tobytes(),
                            engine.submit(p, max_new_tokens=MAX_NEW)))
            for _ in range(int(srng.integers(0, 4))):
                engine.step()
        engine.run_until_idle()
        log = []
        for key, h in handles:
            r = h.result(1.0)
            assert isinstance(r, DecodeResult), f"dropped request: {r}"
            assert r.finished == "complete" and len(r.tokens) == MAX_NEW
            assert r.tokens == expect[key], (
                f"seed {seed}: rejection rollback leaked into tokens: "
                f"{r.tokens} != {expect[key]}")
            exact += 1
            log.append(tuple(r.tokens))
        _arenas_free(engine)
        return log

    for seed in seeds:
        first = run_once(seed)
        assert run_once(seed) == first, f"seed {seed} not deterministic"
    det_stats = engine.speculative_stats()
    assert det_stats["drafted_tokens"] > 0, "draft never proposed"
    assert det_stats["acceptance_rate"] <= 0.2, (
        f"storm not adversarial: acceptance "
        f"{det_stats['acceptance_rate']}")
    assert det_stats["fallbacks"] == 0, \
        "fallback fired despite fallback_acceptance=0.0"
    engine.close()
    rejected = int(det_stats["drafted_tokens"]
                   - det_stats["accepted_tokens"])
    assert rejected >= 1, "no rejection ever rolled back a window"

    # -- phase 2: free-threaded liveness under the same storm --
    engine = DecodeEngine(task, params=params, geometry=geometry,
                          auto_step=True, max_queue=64,
                          speculative=spec_cfg)
    results, errors = [], []
    res_lock = threading.Lock()

    def client(worker: int):
        def run():
            try:
                for i in range(5):
                    p = prompts[(worker + i) % len(prompts)]
                    r = engine.submit(
                        p, max_new_tokens=MAX_NEW).result(120.0)
                    with res_lock:
                        results.append((p.tobytes(), r))
            except BaseException as e:  # noqa: BLE001 — surfaced below
                with res_lock:
                    errors.append(e)
        return run

    threads = [threading.Thread(target=client(w), name=f"client-{w}")
               for w in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300.0)
        assert not t.is_alive(), f"{t.name} hung"
    assert not errors, f"client errors: {errors!r}"

    assert engine.drain(60.0), "engine failed to drain"
    dropped = sum(1 for _, r in results
                  if not isinstance(r, DecodeResult))
    for key, r in results:
        assert isinstance(r, DecodeResult), f"dropped request: {r}"
        assert r.finished == "complete" and len(r.tokens) == MAX_NEW
        # greedy decode is schedule-independent, so exactness holds
        # under free threading too (no cache state to interleave)
        assert r.tokens == expect[key], (
            f"threaded storm leaked into tokens: {r.tokens} != "
            f"{expect[key]}")
    assert len(results) == 15, f"expected 15 completions: {len(results)}"
    _arenas_free(engine)
    live_stats = engine.speculative_stats()
    engine.close()
    rejected += int(live_stats["drafted_tokens"]
                    - live_stats["accepted_tokens"])
    return {"clients": 3, "requests": exact + len(results),
            "dropped": dropped,
            "seeds": seeds, "deterministic_replays": len(seeds),
            "drafted_tokens": int(det_stats["drafted_tokens"]
                                  + live_stats["drafted_tokens"]),
            "rejected_tokens": rejected,
            "acceptance_rate": round(live_stats["acceptance_rate"], 4),
            "leak_free": True, "token_exact": True,
            "faults_fired": {"spec.reject_storm": rejected}}


def scenario_noisy_neighbor(tmp: str) -> dict:
    """Multi-tenant isolation under a quota-busting flood
    (``serving.decode`` + ``serving.tenancy``): a best-effort "flood"
    tenant hammers the shared decode arena with far more work than its
    page quota admits while a standard-priority "victim" tenant runs
    its normal request pattern on the same engine. The isolation
    contract (docs/SERVING.md "Multi-tenancy"): the flood is shed with
    typed ``Unavailable("tenant_quota")`` *before any compute*, and
    the victim's latency stays within a pinned ratio of its solo
    baseline — quota enforcement plus weighted fair-share planning,
    never engine-wide backpressure, absorb the neighbor.

    The engine is manually stepped, so "latency" is *steps* — a
    deterministic clock. Per seed, the victim's submit/step schedule
    is driven by one RNG and the flood's burst sizes by a second, so
    the victim's schedule is bit-identical across the solo and flooded
    runs and the comparison is exact. Asserts, per seed:

    - **zero dropped victim requests**: every victim stream completes
      with the full token count, token-exact vs the solo run (greedy
      decode — interference can move latency, never content);
    - **pinned latency ratio**: flooded victim TTFT (p95, in steps)
      and per-token decode gap (p99) each stay ≤ 2x the solo baseline;
    - **typed flood shed, observably per-tenant**: the flood sees
      ``Unavailable("tenant_quota")`` at submit, the engine's
      ``serving_tenant_shed_total{tenant="flood"}`` counter and
      ``tenant_shed`` events record it, and the victim's shed count
      stays zero — the Prometheus text is the proof artifact;
    - **zero post-warmup compiles** (jax.monitoring) across both
      phases — tenancy is host-side state only;
    - **bitwise seeded replay**: the flooded run's full observable log
      (TTFTs, gaps, tokens, shed counts) replays identically."""
    import numpy as np

    from perceiver_tpu.cache import (
        register_compile_listener,
        unregister_compile_listener,
    )

    from perceiver_tpu.obs import events as events_mod
    from perceiver_tpu.serving.decode import (
        DecodeEngine,
        DecodeGeometry,
        DecodeResult,
    )
    from perceiver_tpu.serving.errors import Unavailable
    from perceiver_tpu.serving.tenancy import (
        PRIORITY_BEST_EFFORT,
        TenantRegistry,
        TenantSpec,
    )
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    task = MaskedLanguageModelTask(
        vocab_size=110, max_seq_len=32, num_latents=4,
        num_latent_channels=8, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_encoder_cross_attention_heads=1,
        num_encoder_self_attention_heads=1,
        num_decoder_cross_attention_heads=1, loss_impl="dense")
    geometry = DecodeGeometry(max_streams=4, num_pages=21, page_size=4,
                              max_seq_len=32, max_chunk=4)
    # victim: standard priority, uncapped pages, 3x fair-share weight.
    # flood: best-effort, page quota sized for ONE in-flight request —
    # every extra burst request must shed at submit, before compute.
    tenancy = TenantRegistry([
        TenantSpec(tenant="victim", weight=3.0),
        TenantSpec(tenant="flood", priority=PRIORITY_BEST_EFFORT,
                   weight=1.0, max_pages=4),
    ])

    rng = np.random.default_rng(5)
    prompts = [rng.integers(3, 100, size=n).astype(np.int32)
               for n in (5, 9, 11, 7)]
    MAX_NEW, N_VICTIM = 6, 6
    RATIO = 2.0  # the pinned noisy-neighbor budget

    compiles = []

    shared_params = [None]

    def run_phase(seed: int, flood: bool):
        engine = DecodeEngine(task, params=shared_params[0],
                              geometry=geometry, tenancy=tenancy,
                              auto_step=False, max_queue=32)
        if shared_params[0] is None:
            shared_params[0] = engine.params
        engine.step()  # idle warmup — compiles counted only after this
        listener = register_compile_listener(compiles.append)
        try:
            step_no = [0]
            vrng = np.random.default_rng(seed)        # victim schedule
            frng = np.random.default_rng(seed + 1000)  # flood bursts
            victim, flood_handles, flood_shed = [], [], [0]

            def submit_victim(prompt):
                rec = {"submit": step_no[0], "token_steps": []}

                def on_token(_tok, rec=rec):
                    rec["token_steps"].append(step_no[0])

                rec["handle"] = engine.submit(
                    prompt, max_new_tokens=MAX_NEW, on_token=on_token,
                    tenant="victim")
                victim.append((prompt.tobytes(), rec))

            def submit_flood_burst():
                for _ in range(int(frng.integers(2, 5))):
                    try:
                        flood_handles.append(engine.submit(
                            prompts[0], max_new_tokens=MAX_NEW,
                            tenant="flood"))
                    except Unavailable as e:
                        assert e.reason == "tenant_quota", e.reason
                        flood_shed[0] += 1

            def step_once():
                step_no[0] += 1
                return engine.step()

            for i in range(N_VICTIM):
                if flood:
                    submit_flood_burst()
                submit_victim(prompts[i % len(prompts)])
                for _ in range(int(vrng.integers(2, 6))):
                    step_once()
            guard = 0
            while step_once():
                guard += 1
                assert guard < 5000, "engine never went idle"

            ttfts, gaps, tokens = [], [], []
            for key, rec in victim:
                r = rec["handle"].result(1.0)
                assert isinstance(r, DecodeResult), \
                    f"victim request dropped: {r!r}"
                assert r.finished == "complete" \
                    and len(r.tokens) == MAX_NEW, (r.finished, r.tokens)
                steps = rec["token_steps"]
                ttfts.append(steps[0] - rec["submit"])
                gaps.extend(b - a for a, b in zip(steps, steps[1:]))
                tokens.append((key, tuple(r.tokens)))
            for h in flood_handles:
                h.result(1.0)  # admitted flood work completes or sheds
            victim_shed = engine._m_tenant_shed.value_of(
                tenant="victim", reason="tenant_quota")
            flood_metric = engine._m_tenant_shed.value_of(
                tenant="flood", reason="tenant_quota")
            prom_text = engine.metrics.render()
            return {"ttfts": tuple(sorted(ttfts)),
                    "gaps": tuple(sorted(gaps)),
                    "tokens": tuple(tokens),
                    "flood_shed": flood_shed[0],
                    "flood_shed_metric": flood_metric,
                    "victim_shed_metric": victim_shed,
                    "prom_text": prom_text}
        finally:
            unregister_compile_listener(listener)
            engine.close()

    def p(xs, q):
        return xs[min(len(xs) - 1, int(q * (len(xs) - 1) + 0.999))]

    # one seed = three full engine phases (solo, flooded, bitwise
    # replay) — the isolation + replay assertions are per-seed, and
    # this scenario rides the tier-1 fast matrix, so wall time matters
    seeds = [7]
    shed_events_before = len(
        events_mod.default_log().events("tenant_shed"))
    totals = {"victim_requests": 0, "flood_shed": 0,
              "ttft_ratio_max": 0.0, "gap_ratio_max": 0.0}
    for seed in seeds:
        solo = run_phase(seed, flood=False)
        noisy = run_phase(seed, flood=True)
        # victim content is interference-proof
        assert noisy["tokens"] == solo["tokens"], (
            f"seed {seed}: flood changed victim tokens")
        # pinned latency budget: TTFT p95 and decode-gap p99, in steps
        ttft_ratio = p(noisy["ttfts"], 0.95) / max(1, p(solo["ttfts"],
                                                        0.95))
        gap_ratio = p(noisy["gaps"], 0.99) / max(1, p(solo["gaps"],
                                                      0.99))
        assert ttft_ratio <= RATIO, (
            f"seed {seed}: victim TTFT p95 {ttft_ratio:.2f}x solo "
            f"(budget {RATIO}x): {noisy['ttfts']} vs {solo['ttfts']}")
        assert gap_ratio <= RATIO, (
            f"seed {seed}: victim decode-gap p99 {gap_ratio:.2f}x solo "
            f"(budget {RATIO}x): {noisy['gaps']} vs {solo['gaps']}")
        # the flood was actually adversarial, and observably shed
        assert noisy["flood_shed"] >= 1, "flood never hit its quota"
        assert noisy["flood_shed_metric"] >= noisy["flood_shed"], (
            "per-tenant shed counter missed submissions")
        assert noisy["victim_shed_metric"] == 0, (
            "victim was quota-shed — isolation broken")
        assert ('serving_tenant_shed_total{reason="tenant_quota",'
                'tenant="flood"}') in noisy["prom_text"], (
            "per-tenant shed series missing from the Prometheus text")
        # bitwise seeded replay of the full flooded run
        replay = run_phase(seed, flood=True)
        for k in ("ttfts", "gaps", "tokens", "flood_shed"):
            assert replay[k] == noisy[k], (
                f"seed {seed}: {k} not deterministic")
        totals["victim_requests"] += len(noisy["tokens"])
        totals["flood_shed"] += noisy["flood_shed"]
        totals["ttft_ratio_max"] = max(totals["ttft_ratio_max"],
                                       round(ttft_ratio, 3))
        totals["gap_ratio_max"] = max(totals["gap_ratio_max"],
                                      round(gap_ratio, 3))
    shed_events = len(events_mod.default_log().events("tenant_shed")) \
        - shed_events_before
    assert shed_events >= totals["flood_shed"], \
        "tenant_shed events missing"
    assert compiles == [], f"post-warmup XLA compiles: {compiles}"
    return {"seeds": seeds, "deterministic_replays": len(seeds),
            "pinned_ratio": RATIO, "victim_dropped": 0,
            "post_warmup_compiles": 0,
            "tenant_shed_events": shed_events, **totals,
            "faults_fired": {"tenant.flood": totals["flood_shed"]}}


# scenario name -> (fault plan armed via PERCEIVER_FAULTS, fn)
_SCENARIOS = {
    "loader_crash": ("loader.exception@at=1,count=2",
                     scenario_loader_crash),
    "nan_skip": ("train.nonfinite@at=2,count=2", scenario_nan_skip),
    "nan_rewind": ("train.nonfinite@at=3,count=5", scenario_nan_rewind),
    "truncated_ckpt": ("ckpt.truncate@at=1", scenario_truncated_ckpt),
    "kill_save": (None, scenario_kill_save),
    "kill_save_victim": (None, scenario_kill_save_victim),  # internal
    "preempt": ("train.preempt@at=3", scenario_preempt),
    "serve_dispatch": ("serve.dispatch@at=1,count=4",
                       scenario_serve_dispatch),
    # race_* arm no fault plan: the "fault" is the adversarial thread
    # interleaving itself (racecheck runtime harness)
    "race_admission": (None, scenario_race_admission),
    "race_mixed_prefill": (None, scenario_race_mixed_prefill),
    # the "fault" is page pressure: a unique-prefix flood that can
    # only admit by evicting the prefix index's LRU chains
    "prefix_evict_under_load": (None, scenario_prefix_evict_under_load),
    # the "fault" is a never-trained draft: ~0% acceptance forces the
    # speculative rollback path on every verify step
    "spec_reject_storm": (None, scenario_spec_reject_storm),
    # the "fault" is a quota-busting best-effort tenant flooding the
    # shared decode arena — isolation, not backpressure, absorbs it
    "noisy_neighbor": (None, scenario_noisy_neighbor),
    # fleet scenarios arm faults per-REPLICA (supervisor env overrides)
    # rather than in the scenario child, so the plan column stays None
    "fleet_kill_replica": (None, scenario_fleet_kill_replica),
    "fleet_stall": (None, scenario_fleet_stall),
    "fleet_rollout_corrupt": (None, scenario_fleet_rollout_corrupt),
    "fleet_rollout": (None, scenario_fleet_rollout),
    # dist scenarios likewise arm faults per-member (group supervisor /
    # fleet per_replica_env seams), never in the scenario child itself
    "dist_coordinator_loss": (None, scenario_dist_coordinator_loss),
    "dist_kill_train_host": (None, scenario_dist_kill_train_host),
    "dist_kill_serve_host": (None, scenario_dist_kill_serve_host),
    "dist_cutover_kill": (None, scenario_dist_cutover_kill),
}
_MATRIX = ["loader_crash", "nan_skip", "nan_rewind", "truncated_ckpt",
           "kill_save", "preempt", "serve_dispatch", "race_admission",
           "race_mixed_prefill", "prefix_evict_under_load",
           "spec_reject_storm", "noisy_neighbor"]
_FAST = ["nan_skip", "serve_dispatch", "race_admission",
         "race_mixed_prefill", "prefix_evict_under_load",
         "spec_reject_storm", "noisy_neighbor"]
_FLEET_MATRIX = ["fleet_kill_replica", "fleet_stall",
                 "fleet_rollout_corrupt", "fleet_rollout"]
_FLEET_FAST = ["fleet_kill_replica"]
_DIST_MATRIX = ["dist_coordinator_loss", "dist_kill_train_host",
                "dist_kill_serve_host", "dist_cutover_kill"]
_DIST_FAST = ["dist_cutover_kill"]


def _run_child(name: str, tmp: str) -> dict:
    plan, _ = _SCENARIOS[name]
    env = dict(os.environ, PERCEIVER_TPU_OFFLINE="1")
    env.pop("PERCEIVER_FAULTS", None)
    if plan:
        env["PERCEIVER_FAULTS"] = plan
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--scenario", name,
         "--tmp", tmp],
        env=env, capture_output=True, text=True, cwd=_REPO, timeout=900)
    if proc.returncode != 0:
        return {"survived": False,
                "error": proc.stderr.strip().splitlines()[-12:]}
    detail = json.loads(proc.stdout.strip().splitlines()[-1])
    detail["survived"] = True
    return detail


def main() -> int:
    ap = argparse.ArgumentParser(description="fault-matrix chaos runner")
    ap.add_argument("--fast", action="store_true",
                    help=f"tier-1 subset {_FAST} instead of the full "
                         "matrix")
    ap.add_argument("--fleet", action="store_true",
                    help=f"the fleet matrix {_FLEET_MATRIX} (multi-"
                         "process router/rollout/failover scenarios)")
    ap.add_argument("--fleet-fast", action="store_true",
                    help=f"tier-1 fleet subset {_FLEET_FAST}")
    ap.add_argument("--dist", action="store_true",
                    help=f"the multi-host matrix {_DIST_MATRIX} "
                         "(process-group training recovery, "
                         "coordinator loss, group-replica failover, "
                         "two-phase cutover kill)")
    ap.add_argument("--dist-fast", action="store_true",
                    help=f"tier-1 multi-host subset {_DIST_FAST}")
    ap.add_argument("--only", nargs="*", default=None,
                    help="run just these scenarios")
    ap.add_argument("--out", default=None,
                    help="also append the result lines to this path")
    ap.add_argument("--scenario", default=None, choices=sorted(_SCENARIOS),
                    help=argparse.SUPPRESS)  # internal: child mode
    ap.add_argument("--tmp", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.scenario:
        # child mode: the fault plan (if any) was armed from the env at
        # import; run one scenario and emit its JSON detail
        from perceiver_tpu.resilience import faults

        detail = _SCENARIOS[args.scenario][1](args.tmp)
        # fleet scenarios report fired counts gathered from their
        # replica processes; don't clobber them with this process's
        detail.setdefault("faults_fired", faults.counts())
        print(json.dumps(detail, default=str), flush=True)
        return 0

    if args.fleet:
        names = _FLEET_MATRIX
    elif args.fleet_fast:
        names = _FLEET_FAST
    elif args.dist:
        names = _DIST_MATRIX
    elif args.dist_fast:
        names = _DIST_FAST
    else:
        names = args.only or (_FAST if args.fast else _MATRIX)
    unknown = [n for n in names
               if n not in _SCENARIOS or n == "kill_save_victim"]
    if unknown:
        ap.error(f"unknown scenario(s) {unknown}")
    results, ok = [], True
    for name in names:
        if name.startswith("race_"):
            default = "adversarial interleaving (seeded scheduler)"
        elif name == "prefix_evict_under_load":
            default = "page pressure (unique-prefix flood)"
        else:
            default = "kill -9 (grand-child)"
        fault = _SCENARIOS[name][0] or default
        print(f"[chaos] {name}: injecting {fault} ...",
              file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory(prefix=f"chaos-{name}-") as tmp:
            detail = _run_child(name, tmp)
        detail["wall_s"] = round(time.perf_counter() - t0, 2)
        survived = detail.pop("survived")
        ok = ok and survived
        line = {"metric": f"chaos_{name}",
                "value": 1.0 if survived else 0.0, "unit": "survived",
                "vs_baseline": None, "detail": detail}
        results.append(line)
        print(json.dumps(line), flush=True)
    summary = {"metric": "chaos_matrix",
               "value": round(sum(r["value"] for r in results)
                              / max(len(results), 1), 3),
               "unit": "fraction_survived", "vs_baseline": None,
               "detail": {"scenarios": len(results),
                          "fast": bool(args.fast)}}
    results.append(summary)
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            for line in results:
                f.write(json.dumps(line) + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
