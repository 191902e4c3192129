"""``scripts/chaos.py --fast`` as a literal subprocess gate — the
check.py pattern (ISSUE 5 satellite): the tier-1 suite proves a fresh
process, armed only through the ``PERCEIVER_FAULTS`` env seam,
survives its fault matrix subset and emits one well-formed JSON record
a scenario."""

import json
import os
import subprocess
import sys


def test_chaos_fast_matrix_survives():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "chaos.py"),
         "--fast"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"\n{proc.stdout}\n{proc.stderr}"

    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    by_metric = {ln["metric"]: ln for ln in lines}
    # one record a scenario, every scenario survived
    for line in lines:
        assert {"metric", "value", "unit", "vs_baseline",
                "detail"} <= set(line)
    assert by_metric["chaos_matrix"]["value"] == 1.0
    scenarios = [ln for ln in lines if ln["metric"] != "chaos_matrix"]
    assert len(scenarios) >= 2
    assert all(ln["value"] == 1.0 for ln in scenarios)
    # the faults really fired (survival by inertness doesn't count)
    assert all(ln["detail"]["faults_fired"] for ln in scenarios)
    # the unified-scheduler interleaving scenario (ISSUE 17) rode the
    # fast tier: mixed prefill/decode admission under seeded schedules
    # with every step's budget invariant asserted and replays bitwise
    mixed = by_metric["chaos_race_mixed_prefill"]["detail"]
    assert mixed["deterministic_replays"] == len(mixed["seeds"])
    assert mixed["admitted"] > 0 and mixed["planned_steps"] > 0
    # prefix-cache eviction under flood (ISSUE 18): unique-prefix
    # pressure forces LRU eviction while shared-prefix clients stream
    # — token-exact vs a cold-prefill reference under seeded replayed
    # schedules, zero dropped under free threads, and the arena fully
    # reclaimable at drain (no refcount leak)
    evict = by_metric["chaos_prefix_evict_under_load"]["detail"]
    assert evict["token_exact"] is True
    assert evict["dropped"] == 0
    assert evict["leak_free"] is True
    assert evict["evicted_pages"] >= 1
    assert evict["client_hits"] >= 1
    assert evict["deterministic_replays"] == len(evict["seeds"])
    assert evict["client_requests"] > 0
    assert evict["faults_fired"].get("prefix.evict_pressure", 0) >= 1
    # speculative rejection storm (ISSUE 19): a never-trained draft
    # drives ~0% acceptance, so every verify step exercises the KV
    # rollback path — token-exact vs a plain-decode reference, zero
    # drops under free threads, both arenas (target + draft) fully
    # reclaimed, and seeded schedules replay bitwise
    storm = by_metric["chaos_spec_reject_storm"]["detail"]
    assert storm["token_exact"] is True
    assert storm["dropped"] == 0
    assert storm["leak_free"] is True
    assert storm["acceptance_rate"] <= 0.2
    assert storm["rejected_tokens"] >= 1
    assert storm["deterministic_replays"] == len(storm["seeds"])
    assert storm["faults_fired"].get("spec.reject_storm", 0) >= 1
    # multi-tenant noisy neighbor (ISSUE 20): a quota-busting
    # best-effort flood on the shared decode arena — the victim loses
    # zero requests, stays within the pinned latency ratio of its solo
    # baseline, the flood sheds typed and tenant-labelled, tenancy
    # mints zero post-warmup compiles, and seeded runs replay bitwise
    nn = by_metric["chaos_noisy_neighbor"]["detail"]
    assert nn["victim_dropped"] == 0
    assert nn["ttft_ratio_max"] <= nn["pinned_ratio"]
    assert nn["gap_ratio_max"] <= nn["pinned_ratio"]
    assert nn["flood_shed"] >= 1
    assert nn["tenant_shed_events"] >= nn["flood_shed"]
    assert nn["post_warmup_compiles"] == 0
    assert nn["deterministic_replays"] == len(nn["seeds"])
    assert nn["faults_fired"].get("tenant.flood", 0) >= 1


def test_chaos_fleet_fast_survives():
    """The fleet failover gate (ISSUE 7): kill -9 a replica under
    live traffic; the supervisor restarts it and the router's
    retry-on-sibling keeps the dropped-request count at exactly zero.
    The full matrix (stall ejection, corrupt-rollout auto-rollback,
    zero-compile rolling update) runs via ``--fleet`` outside tier-1.
    """
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "chaos.py"),
         "--fleet-fast"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"\n{proc.stdout}\n{proc.stderr}"

    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    by_metric = {ln["metric"]: ln for ln in lines}
    for line in lines:
        assert {"metric", "value", "unit", "vs_baseline",
                "detail"} <= set(line)
    assert by_metric["chaos_matrix"]["value"] == 1.0
    kill = by_metric["chaos_fleet_kill_replica"]
    assert kill["value"] == 1.0
    detail = kill["detail"]
    assert detail["dropped"] == 0  # the headline invariant
    assert detail["faults_fired"].get("replica.crash", 0) >= 1
    assert detail["router_retries"] >= 1  # the router actually failed over
    assert detail["fleet_size_after"] == 3  # crashed replica restarted


def test_chaos_dist_fast_survives():
    """The multi-host cutover gate (ISSUE 13): a group member is
    killed between stage and commit during a rolling update; the
    two-phase protocol rolls the group back and the store's CURRENT
    pointer never moves. The full matrix (coordinator loss, bitwise
    train-host recovery, sharded-replica failover) runs via ``--dist``
    outside tier-1.
    """
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "chaos.py"),
         "--dist-fast"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"\n{proc.stdout}\n{proc.stderr}"

    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    by_metric = {ln["metric"]: ln for ln in lines}
    for line in lines:
        assert {"metric", "value", "unit", "vs_baseline",
                "detail"} <= set(line)
    assert by_metric["chaos_matrix"]["value"] == 1.0
    kill = by_metric["chaos_dist_cutover_kill"]
    assert kill["value"] == 1.0
    detail = kill["detail"]
    assert detail["dropped"] == 0
    assert detail["current_after"] == "v1"  # CURRENT never moved
    assert detail["faults_fired"].get("replica.commit_crash", 0) >= 1
