"""Multi-tenant isolation primitives (ISSUE 20): the tenant registry
and its quota arithmetic, per-tenant admission in the unified
scheduler, the decode engine's page-quota ledger, the fleet RPC error
envelope, and demand-proportional replica allocation.

The noisy-neighbor *behaviour* gate lives in scripts/chaos.py
(noisy_neighbor); this module pins the host-side mechanisms it is
built from and the counts of a flooded run, including
seeded InterleaveScheduler races proving the scheduler's per-tenant
page budgets are conserved under adversarial interleavings.
"""

import dataclasses

import numpy as np
import pytest

from perceiver_tpu.serving.batcher import ContinuousBatchScheduler
from perceiver_tpu.serving.errors import (
    SHED_REASONS,
    Unavailable,
    known_reason,
)
from perceiver_tpu.serving.tenancy import (
    DEFAULT_TENANT,
    PRIORITY_BEST_EFFORT,
    PRIORITY_STANDARD,
    TenantRegistry,
    TenantSpec,
    weighted_fair_shares,
)


# --- TenantSpec validation ---------------------------------------------------

def test_tenant_spec_rejects_invalid_fields():
    with pytest.raises(ValueError):
        TenantSpec(tenant="")
    with pytest.raises(ValueError):
        TenantSpec(tenant="a", priority=-1)
    with pytest.raises(ValueError):
        TenantSpec(tenant="a", weight=0.0)
    with pytest.raises(ValueError):
        TenantSpec(tenant="a", max_pages=0)
    with pytest.raises(ValueError):
        TenantSpec(tenant="a", max_inflight=0)
    with pytest.raises(ValueError):
        TenantSpec(tenant="a", rate_per_s=0.0)
    with pytest.raises(ValueError):
        TenantSpec(tenant="a", burst=0)


def test_tenant_spec_is_frozen_with_open_defaults():
    spec = TenantSpec(tenant="gold")
    assert spec.priority == PRIORITY_STANDARD
    assert spec.weight == 1.0
    # None caps = unlimited: a single-tenant deployment needs no knobs
    assert spec.max_pages is None and spec.max_inflight is None
    assert spec.rate_per_s is None and spec.model is None
    with pytest.raises(dataclasses.FrozenInstanceError):
        spec.weight = 2.0


# --- registry fallback + identity --------------------------------------------

def test_registry_unknown_tenant_falls_back_to_default_spec():
    # no default registered: unknown names get an uncapped spec but
    # KEEP their identity (metrics/events still attribute correctly)
    reg = TenantRegistry()
    spec = reg.get("ghost")
    assert spec.tenant == "ghost" and spec.max_pages is None
    assert reg.get(None).tenant == DEFAULT_TENANT

    # a registered default spec donates its caps to unregistered
    # names — identity still stays the caller's
    reg = TenantRegistry([
        TenantSpec(tenant=DEFAULT_TENANT, max_pages=8, weight=2.0),
        TenantSpec(tenant="bronze", priority=PRIORITY_BEST_EFFORT,
                   max_pages=2),
    ])
    ghost = reg.get("ghost")
    assert ghost.tenant == "ghost"
    assert ghost.max_pages == 8 and ghost.weight == 2.0
    assert reg.get("bronze").max_pages == 2
    assert reg.tenants() == ["bronze", DEFAULT_TENANT]


def test_registry_register_replaces_spec():
    reg = TenantRegistry([TenantSpec(tenant="a", max_pages=2)])
    reg.register(TenantSpec(tenant="a", max_pages=5))
    assert reg.get("a").max_pages == 5


# --- weighted fair shares ----------------------------------------------------

def test_weighted_fair_shares_proportional_and_conserving():
    shares = weighted_fair_shares(8, {"a": 3.0, "b": 1.0})
    assert shares == {"a": 6, "b": 2}
    assert sum(shares.values()) == 8
    # deterministic: identical inputs always agree
    assert shares == weighted_fair_shares(8, {"a": 3.0, "b": 1.0})


def test_weighted_fair_shares_largest_remainder_ties_break_by_key():
    # exact shares 2.5/2.5 — the single leftover unit goes to the
    # lexicographically first key, same answer every run
    assert weighted_fair_shares(5, {"a": 1.0, "b": 1.0}) \
        == {"a": 3, "b": 2}


def test_weighted_fair_shares_floor_of_one():
    # a 100:1 weight ratio must not shut the small tenant out while
    # units remain — a zero share is starvation by arithmetic
    shares = weighted_fair_shares(10, {"whale": 100.0, "shrimp": 1.0})
    assert shares == {"whale": 9, "shrimp": 1}


def test_weighted_fair_shares_edges():
    assert weighted_fair_shares(0, {"a": 1.0}) == {"a": 0}
    assert weighted_fair_shares(5, {}) == {}
    with pytest.raises(ValueError):
        weighted_fair_shares(5, {"a": 0.0})


# --- token-bucket rate admission ---------------------------------------------

def test_registry_token_bucket_admits_burst_then_sheds_with_hint():
    reg = TenantRegistry([
        TenantSpec(tenant="r", rate_per_s=2.0, burst=2),
        TenantSpec(tenant="free"),
    ])
    # burst admits, then the bucket is dry with an exact refill hint
    assert reg.admit("r", now=0.0) == (True, 0.0)
    assert reg.admit("r", now=0.0) == (True, 0.0)
    ok, retry = reg.admit("r", now=0.0)
    assert not ok and retry == pytest.approx(0.5)
    # half a second refills exactly one token at 2/s
    assert reg.admit("r", now=0.5) == (True, 0.0)
    ok, retry = reg.admit("r", now=0.5)
    assert not ok and retry == pytest.approx(0.5)
    # unlimited tenants never consult a bucket
    for _ in range(10):
        assert reg.admit("free", now=0.0) == (True, 0.0)


def test_registry_register_resets_rate_bucket():
    reg = TenantRegistry([TenantSpec(tenant="r", rate_per_s=1.0,
                                     burst=1)])
    assert reg.admit("r", now=0.0)[0]
    assert not reg.admit("r", now=0.0)[0]
    reg.register(TenantSpec(tenant="r", rate_per_s=1.0, burst=1))
    assert reg.admit("r", now=0.0)[0]


# --- scheduler: per-tenant budgets in take() ---------------------------------

def _offer_all(q, entries):
    for tenant, i, cost in entries:
        assert q.offer((tenant, i), cost=cost, tenant=tenant)


def test_take_defers_over_quota_tenant_without_head_blocking():
    q = ContinuousBatchScheduler(max_depth=16, clock=lambda: 0.0)
    # flood's entries sit at the HEAD of the queue; with its budget
    # exhausted they defer in place and the victim admits past them
    _offer_all(q, [("flood", 0, 2), ("flood", 1, 2),
                   ("victim", 0, 2), ("victim", 1, 2)])
    budgets = {"flood": 0}
    admitted, shed = q.take(budget=8, slots=4, tenant_budgets=budgets)
    assert admitted == [("victim", 0), ("victim", 1)]
    assert shed == []
    # deferred entries stayed queued, in order, for the next round
    assert q.depth == 2
    budgets = {"flood": 4}
    admitted, _ = q.take(budget=8, slots=4, tenant_budgets=budgets)
    assert admitted == [("flood", 0), ("flood", 1)]
    assert budgets["flood"] == 0


def test_take_fifo_within_tenant_once_deferred():
    q = ContinuousBatchScheduler(max_depth=16, clock=lambda: 0.0)
    # flood has budget for its SECOND entry (cost 1) but not its
    # first (cost 3) — admitting it would reorder the tenant's queue,
    # so once one entry defers, all its later entries defer too
    _offer_all(q, [("flood", 0, 3), ("flood", 1, 1), ("victim", 0, 1)])
    admitted, _ = q.take(budget=8, slots=4,
                         tenant_budgets={"flood": 2})
    assert admitted == [("victim", 0)]
    admitted, _ = q.take(budget=8, slots=4,
                         tenant_budgets={"flood": 4})
    assert admitted == [("flood", 0), ("flood", 1)]


def test_take_absent_tenant_budget_means_unlimited():
    q = ContinuousBatchScheduler(max_depth=16, clock=lambda: 0.0)
    _offer_all(q, [("victim", 0, 3), ("victim", 1, 3)])
    admitted, _ = q.take(budget=8, slots=4, tenant_budgets={"flood": 0})
    assert admitted == [("victim", 0), ("victim", 1)]


# --- scheduler: weighted fair-share chunk planning ---------------------------

def test_plan_chunks_splits_leftover_by_tenant_weight():
    q = ContinuousBatchScheduler(token_budget=8, max_chunk=4)
    # 2 decode rows pre-spend 2; the leftover 6 splits a:4 / b:2, and
    # a's second row gets nothing once a's share is spent — b's slice
    # survives a's greed
    chunks = q.plan_chunks(2, [10, 10, 10],
                           prefill_tenants=["a", "a", "b"],
                           tenant_weights={"a": 2.0, "b": 1.0})
    assert chunks == [4, 0, 2]


def test_plan_chunks_fair_share_is_work_conserving():
    q = ContinuousBatchScheduler(token_budget=8, max_chunk=8)
    # a only needs 2 of its 4-token share; the unclaimed 2 go back
    # out FIFO instead of idling the step
    chunks = q.plan_chunks(0, [2, 10],
                           prefill_tenants=["a", "b"],
                           tenant_weights={"a": 1.0, "b": 1.0})
    assert chunks == [2, 6]
    assert sum(chunks) == 8


def test_plan_chunks_head_row_always_advances():
    q = ContinuousBatchScheduler(token_budget=1, max_chunk=4)
    # decode spends the whole budget; the FIFO-head prefill row still
    # gets its no-livelock token even under fair-share caps
    assert q.plan_chunks(1, [5], prefill_tenants=["flood"],
                         tenant_weights={"flood": 1.0}) == [1]


def test_plan_speculative_grants_before_tenant_shares():
    q = ContinuousBatchScheduler(token_budget=6, max_chunk=4)
    grants, chunks = q.plan_speculative(
        1, [3, 5], [4], prefill_tenants=["a"],
        tenant_weights={"a": 1.0})
    # decode 1 + grants 3, 2 exhaust the budget; the head prefill row
    # still advances its guaranteed token
    assert grants == [3, 2]
    assert chunks == [1]


# --- seeded races: quota conservation under adversarial interleavings --------

def test_take_quota_conservation_under_seeded_races():
    """Two producer tenants and a consumer race offer()/take() under
    seeded InterleaveScheduler schedules. Invariants, every seed:
    the flood tenant's admitted page cost never exceeds its budget,
    nothing is lost or duplicated (admitted + queued == offered), and
    order within each tenant is FIFO. Each seed replays bitwise."""
    from perceiver_tpu.utils.concurrency import InterleaveScheduler

    N, COST, FLOOD_BUDGET = 6, 2, 4

    def run_once(seed):
        sched = InterleaveScheduler(seed=seed)
        q = ContinuousBatchScheduler(max_depth=32, clock=lambda: 0.0)
        admitted = []
        budgets = {"flood": FLOOD_BUDGET}  # persists across take()s

        def producer(tenant):
            def fn():
                for i in range(N):
                    assert q.offer((tenant, i), cost=COST,
                                   tenant=tenant)
                    sched.point(f"offer:{tenant}")
            return fn

        def consumer():
            for _ in range(2 * N):
                got, shed = q.take(budget=2 * COST, slots=2,
                                   tenant_budgets=budgets)
                assert shed == []  # no deadlines in this harness
                admitted.extend(got)
                sched.point("take")

        sched.spawn(producer("victim"), name="victim")
        sched.spawn(producer("flood"), name="flood")
        sched.spawn(consumer, name="engine")
        sched.run()
        # post-race drain: whatever the racing consumer missed
        while True:
            got, _ = q.take(budget=2 * COST, slots=2,
                            tenant_budgets=budgets)
            if not got:
                break
            admitted.extend(got)
        return admitted, q.depth, budgets["flood"], tuple(sched.trace)

    for seed in (3, 11, 4321):
        admitted, depth, flood_left, trace = run_once(seed)
        flood_taken = [i for t, i in admitted if t == "flood"]
        victim_taken = [i for t, i in admitted if t == "victim"]
        # quota conservation: the flood can never admit past its
        # budget no matter how the threads interleave
        assert len(flood_taken) * COST <= FLOOD_BUDGET
        assert flood_left == FLOOD_BUDGET - len(flood_taken) * COST
        # nothing lost, nothing duplicated
        assert len(admitted) + depth == 2 * N
        assert depth == N - len(flood_taken)  # only flood defers
        # FIFO within each tenant
        assert victim_taken == list(range(N))
        assert flood_taken == list(range(len(flood_taken)))
        # bitwise seeded replay: same seed, same interleaving, same
        # admission order
        assert run_once(seed) == (admitted, depth, flood_left, trace)


# --- decode engine: page-quota shed + ledger conservation --------------------

def _tiny_task():
    from perceiver_tpu.tasks import MaskedLanguageModelTask

    return MaskedLanguageModelTask(
        vocab_size=110, max_seq_len=32, num_latents=4,
        num_latent_channels=8, num_encoder_layers=1,
        num_encoder_self_attention_layers_per_block=1,
        num_encoder_cross_attention_heads=1,
        num_encoder_self_attention_heads=1,
        num_decoder_cross_attention_heads=1, loss_impl="dense")


def test_decode_engine_quota_shed_and_ledger_conservation():
    """A capped tenant's second concurrent request sheds typed at
    submit — before a slot, a page, or a device token is spent — and
    after drain the per-tenant page ledger returns to zero with the
    pool fully free (charge/credit conservation)."""
    from perceiver_tpu.obs import events as events_mod
    from perceiver_tpu.serving.decode import (
        DecodeEngine,
        DecodeGeometry,
        DecodeResult,
    )
    from perceiver_tpu.serving.engine import RequestTooLarge

    task = _tiny_task()
    geometry = DecodeGeometry(max_streams=2, num_pages=9, page_size=4,
                              max_seq_len=16, max_chunk=4)
    tenancy = TenantRegistry([
        TenantSpec(tenant="bronze", priority=PRIORITY_BEST_EFFORT,
                   max_pages=2),
    ])
    engine = DecodeEngine(task, geometry=geometry, tenancy=tenancy,
                          auto_step=False, max_queue=8)
    try:
        prompt = np.arange(3, 8, dtype=np.int32)  # 5 tokens, 2 pages

        # a request that can NEVER fit the quota is a sizing error,
        # not a transient shed
        with pytest.raises(RequestTooLarge):
            engine.submit(np.arange(3, 11, dtype=np.int32),
                          max_new_tokens=4, tenant="bronze")

        shed_before = len(events_mod.default_log().events("tenant_shed"))
        h_bronze = engine.submit(prompt, max_new_tokens=3,
                                 tenant="bronze")
        # held + queued already fill the 2-page quota: the second
        # request sheds typed, with the tenant attributed
        with pytest.raises(Unavailable) as exc:
            engine.submit(prompt, max_new_tokens=3, tenant="bronze")
        assert exc.value.reason == "tenant_quota"
        assert exc.value.tenant == "bronze"
        assert exc.value.retry_after_s == \
            SHED_REASONS["tenant_quota"]
        # an uncapped tenant is untouched by bronze's quota
        h_gold = engine.submit(prompt, max_new_tokens=3, tenant="gold")

        engine.run_until_idle()
        for handle in (h_bronze, h_gold):
            r = handle.result(1.0)
            assert isinstance(r, DecodeResult), r
            assert r.finished == "complete" and len(r.tokens) == 3

        # ledger conservation: every page charged at admission was
        # credited back at finish, and the pool is whole again
        assert all(v == 0 for v in engine._tenant_pages.values())
        assert engine.pool.free_pages == geometry.allocatable_pages
        # the shed is observable per tenant: counter + typed event
        assert engine._m_tenant_shed.value_of(
            tenant="bronze", reason="tenant_quota") == 1
        assert engine._m_tenant_tokens.value_of(tenant="gold") == 3
        shed_events = events_mod.default_log().events("tenant_shed")
        assert len(shed_events) == shed_before + 1
        assert shed_events[-1]["tenant"] == "bronze"
        assert shed_events[-1]["reason"] == "tenant_quota"
    finally:
        engine.close()


@pytest.mark.parametrize("arm", ["solo", "mixed"])
def test_gold_loses_nothing_to_a_capped_flood(arm):
    """Four gold streams alone, then the same four while a
    best-effort tenant capped at two requests' pages submits two
    requests before each of them: gold drops and sheds nothing in
    either arm, the flood's surplus sheds typed before any compute,
    and no arm compiles. Counts only: stepped by hand, no clock."""
    from perceiver_tpu.cache import compile_events
    from perceiver_tpu.serving.decode import DecodeEngine, DecodeGeometry

    task = _tiny_task()
    streams, flood = 4, 2
    # 5 + 7 tokens: 3 pages a request; bronze may hold two requests'
    geometry = DecodeGeometry(max_streams=streams + 2,
                              num_pages=(streams + 2) * 3 + 1,
                              page_size=4, max_seq_len=16, max_chunk=4)
    tenancy = TenantRegistry([
        TenantSpec(tenant="gold", weight=3.0),
        TenantSpec(tenant="bronze", priority=PRIORITY_BEST_EFFORT,
                   weight=1.0, max_pages=6)])
    engine = DecodeEngine(task, geometry=geometry, tenancy=tenancy,
                          auto_step=False, max_queue=32,
                          exec_cache=False)
    prompt = np.arange(3, 8, dtype=np.int32)
    gold, bronze, shed = [], [], 0
    try:
        with compile_events() as compiles:
            for _ in range(streams):
                for _ in range(flood if arm == "mixed" else 0):
                    try:
                        bronze.append(engine.submit(
                            prompt, max_new_tokens=7, tenant="bronze"))
                    except Unavailable as e:
                        assert e.reason == "tenant_quota"
                        shed += 1
                gold.append(engine.submit(prompt, max_new_tokens=7,
                                          tenant="gold"))
                engine.step()
            engine.run_until_idle()
        assert compiles == [], compiles
        for handle in gold:
            r = handle.result(1.0)
            assert r.finished == "complete" and len(r.tokens) == 7
        assert sum(engine._m_tenant_shed.value_of(tenant="gold", reason=r)
                   for r in ("tenant_quota", "queue_full",
                             "deadline")) == 0
        assert engine._m_tenant_tokens.value_of(tenant="gold") == 7 * streams
        completed = sum(h.result(1.0).finished == "complete"
                        for h in bronze)
        assert engine._m_tenant_shed.value_of(
            tenant="bronze", reason="tenant_quota") == shed
        if arm == "mixed":
            assert shed >= 1
            assert len(bronze) + shed == flood * streams
            assert 1 <= completed <= flood * streams - shed
        else:
            assert (shed, completed) == (0, 0)
        assert all(v == 0 for v in engine._tenant_pages.values())
    finally:
        engine.close()


# --- fleet: RPC envelope + demand-proportional allocation --------------------

def test_unavailable_tenant_survives_rpc_envelope_round_trip():
    from perceiver_tpu.fleet.rpc import (
        error_envelope,
        raise_remote_error,
    )

    env = error_envelope(Unavailable("tenant_quota", tenant="bronze",
                                     retry_after_s=0.25))
    assert env == {"type": "Unavailable", "reason": "tenant_quota",
                   "bucket": None, "retry_after_s": 0.25,
                   "tenant": "bronze"}
    with pytest.raises(Unavailable) as exc:
        raise_remote_error(env)
    assert exc.value.reason == "tenant_quota"
    assert exc.value.tenant == "bronze"
    assert exc.value.retry_after_s == 0.25


def test_shed_reason_vocabulary_is_closed():
    assert known_reason("tenant_quota")
    # decode-plane sheds cross the fleet boundary prefixed
    assert known_reason("decode_queue_full")
    assert not known_reason("made_up_reason")
    # every vocabulary entry carries a retry hint
    assert all(isinstance(v, float) for v in SHED_REASONS.values())


def test_allocate_replicas_proportional_to_demand():
    from perceiver_tpu.fleet.autoscaler import allocate_replicas

    assert allocate_replicas({"a": 3.0, "b": 1.0}, 4) \
        == {"a": 3, "b": 1}
    # an idle fleet balances instead of collapsing onto one tenant
    assert allocate_replicas({"a": 0.0, "b": 0.0}, 4) \
        == {"a": 2, "b": 2}
    assert allocate_replicas({}, 4) == {}
    alloc = allocate_replicas({"a": 5.0, "b": 2.0, "c": 0.1}, 7)
    assert sum(alloc.values()) == 7
    assert alloc["c"] >= 1  # floor-of-one reaches the autoscaler too
    with pytest.raises(ValueError):
        allocate_replicas({"a": 1.0}, -1)
