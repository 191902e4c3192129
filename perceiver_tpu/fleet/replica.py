"""One fleet replica: a ServingEngine behind an RPC server.

``python -m perceiver_tpu.fleet.replica --spec spec.json`` builds the
task named in the spec, loads its params from a
:class:`~perceiver_tpu.training.checkpoint.ParamsVersionStore` version
(sha256-verified) or fresh-init, warms the engine's AOT buckets (a
warm persistent exec cache makes this **zero-compile** — the PR-4
unlock that makes replica spin-up cheap), then prints ``READY <port>``
on stdout so the supervisor can connect.

RPC ops (see ``fleet/rpc.py`` for the envelope):

``dispatch``        host arrays in, materialized host outputs out.
                    A payload carrying ``packed_ids`` routes to the
                    engine's ragged ``dispatch_packed`` path (spec key
                    ``packed_buckets`` enables it) — the router and
                    RPC envelope are payload-agnostic, so packed and
                    rectangular replicas interchange freely
``status``          health/readiness, in-flight, version, staged
                    version, compile count, breaker summary, fired
                    fault counts
``update_version``  the rolling-update cutover (below)
``stage_version``   phase 1 of the group two-phase cutover: verified
                    load into memory, traffic untouched
``commit_version``  phase 2: quiesce and swap to the staged params
                    (``distributed/serving_group.py`` drives these —
                    a group swaps only after EVERY member staged)
``abort_version``   drop a staged version (stage-phase failure)
``metrics``         Prometheus text exposition
``ping``            liveness no-op
``shutdown``        clean exit

The cutover guard is the replica-side half of the zero-downtime
protocol (docs/SERVING.md "Fleet"): ``update_version`` flips a
``_swapping`` flag (new dispatches are rejected with a typed
``Unavailable("updating")`` the router transparently retries on a
sibling), waits for in-flight dispatches to reach zero, verifies the
target version's manifest, swaps via the engine's recompile-free
``update_params``, then readmits traffic — so **no request is ever
served by a mid-swap replica**: every dispatch runs entirely on the
old params or entirely on the new.

Chaos seams: ``replica.stall`` and ``replica.crash``
(``resilience/faults.py``) fire in the dispatch handler, and
``replica.commit_crash`` at ``commit_version`` entry — the
killed-between-stage-and-swap window the ``dist_cutover_kill``
scenario exercises — all inherited by this process through the
``PERCEIVER_FAULTS`` env var exactly like every other chaos child.

Multi-model hosting (docs/SERVING.md "Multi-tenancy"): the spec key
``models`` (``{model_id: version-or-null}``) plus ``model_store_dir``
(a :class:`~perceiver_tpu.training.checkpoint.MultiModelStore` root)
makes one replica host N device-resident param sets over ONE task
graph — siblings share the primary engine's metrics registry and
content-addressed exec cache, so the second model's engines are cache
hits, not compiles. Every cutover op takes an optional ``model`` and
the guard state (``_inflight``/``_swapping``/``_staged``) is
per-model: updating tenant A's model drains and rejects ONLY model
A's dispatches — tenant B's in-flight streams on the same chips never
notice (the per-tenant rolling-update contract). Dispatch payloads
may carry ``model`` (routes to the matching param set; unknown ids
raise a typed ``Unavailable("unknown_model")``) and ``tenant``
(forwarded to the decode arena's page-quota ledger and metric
labels). Without ``models`` in the spec everything collapses to the
single implicit ``default`` model — the legacy contract, bit for bit.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from typing import Dict, Optional

import numpy as np

from perceiver_tpu.fleet.rpc import RpcServer
from perceiver_tpu.obs import trace as trace_mod
from perceiver_tpu.resilience import faults
from perceiver_tpu.serving.api import materialize, materialize_packed
from perceiver_tpu.serving.batcher import Overloaded
from perceiver_tpu.serving.errors import Unavailable
from perceiver_tpu.serving.tenancy import TenantRegistry, TenantSpec

#: the implicit model id every single-model spec collapses to
DEFAULT_MODEL = "default"


def build_task(spec: dict):
    """Instantiate the spec's task config by class name from
    ``perceiver_tpu.tasks`` (specs are JSON, so the task rides as
    ``{"task_class": ..., "task_kwargs": {...}}``)."""
    import perceiver_tpu.tasks as tasks

    cls = getattr(tasks, spec["task_class"], None)
    if cls is None:
        raise ValueError(f"unknown task class {spec['task_class']!r}")
    return cls(**spec.get("task_kwargs", {}))


class ReplicaServer:
    """Engine + RPC plumbing + the cutover guard for one replica."""

    # lock discipline (gated by check.py --race): the cutover guard
    # state — all per-model now — written by _update/_commit/_abort
    # and read per dispatch; _idle is a Condition over _lock.
    # Deliberately NOT declared: self.versions entries — each is
    # swapped with a single dict-slot assignment only while its model
    # is quiesced (model in _swapping, its _inflight drained to 0), so
    # readers race only against an atomic store.
    _GUARDED = {
        "_inflight": "_lock",
        "_swapping": "_lock",
        "_staged": "_lock",
    }

    def __init__(self, spec: dict):
        self.spec = spec
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        # per-model cutover guards: in-flight dispatch counts, the set
        # of model ids mid-swap, and staged (version, params, draft)
        # tuples held for the two-phase group cutover
        self._inflight: Dict[str, int] = {}
        self._swapping: set = set()
        self._staged: Dict[str, tuple] = {}
        self._stop = threading.Event()
        self._compile_events: list = []
        self._register_compile_listener()

        # decode-arena tenancy (spec key "tenants" = list of TenantSpec
        # kwargs): page quotas and fair-share weights for this
        # replica's decode engines — host-side only, never a shape
        self.tenancy: Optional[TenantRegistry] = None
        if spec.get("tenants"):
            self.tenancy = TenantRegistry(
                [TenantSpec(**t) for t in spec["tenants"]])

        self.task = build_task(spec)
        self.model_store = None
        self.store = None
        if spec.get("model_store_dir"):
            from perceiver_tpu.training.checkpoint import MultiModelStore

            self.model_store = MultiModelStore(spec["model_store_dir"])
        elif spec.get("store_dir"):
            from perceiver_tpu.training.checkpoint import ParamsVersionStore

            self.store = ParamsVersionStore(spec["store_dir"])

        models_spec: Dict[str, Optional[str]] = dict(
            spec.get("models") or {})
        if not models_spec:
            models_spec = {DEFAULT_MODEL: spec.get("version")}
        self.default_model = (DEFAULT_MODEL
                              if DEFAULT_MODEL in models_spec
                              else sorted(models_spec)[0])
        self.engines: Dict[str, object] = {}
        self.decode_engines: Dict[str, object] = {}
        self.versions: Dict[str, Optional[str]] = {}
        self._spec_cfgs: Dict[str, object] = {}
        self._draft_versions: Dict[str, Optional[str]] = {}
        self._prefix_cache_cfg = None
        self._decode_max_new = 16
        # default model builds first: siblings share its metrics
        # registry (one exposition per replica) and its
        # content-addressed exec cache, so an identical graph under a
        # second model id is a cache hit, not a compile
        order = [self.default_model] + sorted(
            m for m in models_spec if m != self.default_model)
        for model in order:
            self._build_model(model, models_spec.get(model))
        self.engine = self.engines[self.default_model]
        self.decode_engine = self.decode_engines.get(self.default_model)
        self._spec_cfg = self._spec_cfgs.get(self.default_model)
        self._draft_version = self._draft_versions.get(
            self.default_model)
        self.server = RpcServer(self.handle,
                                port=int(spec.get("port", 0)),
                                io_timeout=spec.get("io_timeout_s", 60.0))

    def _store_for(self, model: str):
        """The params version store holding ``model``'s trees (None =
        fresh-init replica with no store at all)."""
        if self.model_store is not None:
            return self.model_store.model(model)
        if model == self.default_model:
            return self.store
        return None

    def _build_model(self, model: str, version: Optional[str]) -> None:
        from perceiver_tpu.serving.engine import ServingEngine

        spec = self.spec
        store = self._store_for(model)
        params = None
        if store is not None:
            if version is None:
                version = store.current()
            if version is not None:
                # template-less restore (orbax falls back to on-disk
                # metadata): building an init-params template would
                # compile the random init and break the zero-compile
                # spin-up contract the fleet chaos gate asserts
                params = store.load(version, None)
        self.versions[model] = version
        primary = self.engines.get(self.default_model)
        if primary is None:
            shared_cache = None  # primary resolves the process default
        else:
            # share the primary's cache object; False (not None) when
            # the primary runs uncached, so a sibling never silently
            # re-enables it
            shared_cache = (primary.exec_cache
                            if primary.exec_cache is not None else False)
        engine = ServingEngine(
            self.task, params,
            batch_buckets=tuple(spec.get("batch_buckets", (4,))),
            seq_buckets=tuple(spec.get("seq_buckets", (16,))),
            packed_buckets=tuple(
                tuple(tb) for tb in spec.get("packed_buckets", ())),
            metrics=primary.metrics if primary is not None else None,
            exec_cache=shared_cache,
            breaker_failure_threshold=spec.get(
                "breaker_failure_threshold", 5),
            breaker_reset_s=spec.get("breaker_reset_s", 30.0))
        self.engines[model] = engine
        # opt-in decode engine (spec key "decode" = geometry kwargs):
        # same task tree, same metrics registry — one exposition
        # covers both planes, and the compile listener above counts its
        # step compile in the zero-compile spin-up budget
        if not spec.get("decode"):
            return
        from perceiver_tpu.serving.decode import (
            DecodeEngine,
            DecodeGeometry,
        )
        from perceiver_tpu.serving.prefix_cache import PrefixCacheConfig

        dspec = dict(spec["decode"])
        self._decode_max_new = int(dspec.pop("max_new_tokens_default",
                                             16))
        # host-side pacing knob of the unified prefill+decode
        # scheduler; everything left in dspec is geometry
        token_budget = dspec.pop("token_budget", None)
        # opt-in prefix caching (spec key "prefix_cache" = config
        # kwargs, or true for defaults) — purely host-side page
        # sharing, so it never forks the exec-cache key
        pc = dspec.pop("prefix_cache", None)
        if pc is True:
            pc = PrefixCacheConfig()
        elif isinstance(pc, dict):
            pc = PrefixCacheConfig(**pc)
        self._prefix_cache_cfg = pc
        # opt-in speculative decoding (spec key "speculative";
        # geometry's spec_k stays in dspec — it forks the compiled
        # step). "draft" holds shrink_task overrides (absent =
        # self-draft); "draft_version" names a separately
        # published draft tree in the SAME (per-model) version store.
        sp = dspec.pop("speculative", None)
        spec_cfg = None
        draft_version = None
        if sp:
            from perceiver_tpu.serving.speculative import (
                SpeculativeConfig,
                shrink_task,
            )

            sp = dict(sp) if isinstance(sp, dict) else {}
            draft_version = sp.pop("draft_version", None)
            shrink = sp.pop("draft", None)
            draft_task = None
            if shrink is not None:
                draft_task = shrink_task(
                    self.task, **(shrink if isinstance(shrink, dict)
                                  else {}))
            draft_params = None
            if draft_version is not None:
                if store is None:
                    raise ValueError(
                        "speculative.draft_version needs a params "
                        "version store (store_dir/model_store_dir)")
                draft_params = store.load(draft_version, None)
            spec_cfg = SpeculativeConfig(
                draft_task=draft_task, draft_params=draft_params,
                **sp)
        self._spec_cfgs[model] = spec_cfg
        self._draft_versions[model] = draft_version
        self.decode_engines[model] = DecodeEngine(
            self.task, engine._params_src,
            geometry=DecodeGeometry(**dspec),
            token_budget=token_budget,
            prefix_cache=pc,
            speculative=spec_cfg,
            tenancy=self.tenancy,
            metrics=engine.metrics)

    @property
    def version(self) -> Optional[str]:
        """The default model's live version (legacy single-model
        status/reply field; per-model versions ride in ``models``)."""
        return self.versions.get(self.default_model)

    def _register_compile_listener(self) -> None:
        """Count XLA compiles from before engine construction — the
        fleet's zero-compile-spin-up assertion reads this count over
        RPC (``status``)."""
        from perceiver_tpu.cache import register_compile_listener

        register_compile_listener(self._compile_events.append)

    # -- RPC handler ------------------------------------------------------

    def handle(self, request: dict):
        op = request.get("op")
        if op == "dispatch":
            return self._dispatch(request["arrays"],
                                  request.get("trace"))
        if op == "status":
            return self._status()
        if op == "update_version":
            return self._update_version(request["version"],
                                        request.get("model"))
        if op == "stage_version":
            return self._stage_version(request["version"],
                                       request.get("model"))
        if op == "commit_version":
            return self._commit_version(request["version"],
                                        request.get("model"))
        if op == "abort_version":
            return self._abort_version(request.get("model"))
        if op == "metrics":
            return self.engine.metrics.render()
        if op == "ping":
            return "pong"
        if op == "shutdown":
            self._stop.set()
            return "bye"
        raise ValueError(f"unknown op {op!r}")

    def _dispatch(self, arrays: dict, wire: Optional[dict] = None) -> dict:
        # rehydrate the caller's trace (if it sent one) into a local
        # span collector — the spans ride back in the reply and the
        # router re-keys them into the request's trace
        collector = trace_mod.SpanCollector()
        ctx = trace_mod.from_wire(wire, sink=collector, origin="replica")
        model = arrays.get("model") or self.default_model
        tenant = arrays.get("tenant")
        engine = self.engines.get(model)
        if engine is None:
            # typed: the router excludes this replica and retries a
            # sibling that DOES advertise the model
            raise Unavailable("unknown_model", tenant=tenant)
        admit_start = time.monotonic()
        with self._lock:
            if model in self._swapping:
                # mid-swap FOR THIS MODEL: typed rejection the router
                # retries on a sibling — other models on this replica
                # keep serving through the cutover
                raise Unavailable("updating", retry_after_s=0.05,
                                  tenant=tenant)
            self._inflight[model] = self._inflight.get(model, 0) + 1
        try:
            faults.maybe_stall("replica.stall")
            faults.maybe_kill("replica.crash")
            if ctx is not None:
                # admission (lock/stall wait) is this replica's queue
                ctx.record("queue_wait", start=admit_start)
            # "model"/"tenant" are wire-envelope routing keys, not
            # payload — strip them before the engines' exact-input-set
            # validation rejects the batch
            payload = {k: v for k, v in arrays.items()
                       if k not in ("model", "tenant")}
            with trace_mod.attach([ctx]):
                if "prompt_ids" in payload:
                    outputs = self._decode_dispatch(payload, ctx, model,
                                                    tenant)
                elif "packed_ids" in payload:
                    result = engine.dispatch_packed(payload)
                    with trace_mod.region("device"):
                        outputs = materialize_packed(
                            result, engine.packed_graph)
                else:
                    result = engine.dispatch(payload)
                    with trace_mod.region("device"):
                        outputs = materialize(result, engine.graph)
        finally:
            with self._lock:
                self._inflight[model] -= 1
                self._idle.notify_all()
        reply = {"outputs": outputs,
                 "health": engine.health.state.name,
                 "version": self.versions.get(model),
                 "models": sorted(self.engines)}
        if ctx is not None:
            reply["spans"] = collector.spans
        return reply

    def _decode_dispatch(self, arrays: dict, ctx, model: str,
                         tenant: Optional[str]) -> dict:
        """Run one decode payload (``prompt_ids`` + optional
        ``max_new_tokens``) to completion and return the full token
        array. Token-by-token streaming stays in-process behind
        ``serving/api.GenerationServer`` — the fleet RPC is
        request/response, so a decode replica trades streaming for the
        router's retry/failover semantics. A shed stream surfaces as
        the typed ``Unavailable`` the router transparently retries on
        a sibling."""
        decode_engine = self.decode_engines.get(model)
        if decode_engine is None:
            raise ValueError(
                "replica has no decode engine (enable with the "
                "'decode' spec key)")
        max_new = int(arrays.get("max_new_tokens", self._decode_max_new))
        handle = decode_engine.submit(
            arrays["prompt_ids"], max_new_tokens=max_new, trace=ctx,
            tenant=tenant)
        result = handle.result()
        if isinstance(result, Overloaded):
            raise Unavailable(f"decode_{result.reason}",
                              retry_after_s=0.05, tenant=tenant)
        return {"tokens": np.asarray(result.tokens, np.int32),
                "ttft_s": np.asarray(result.ttft_s or 0.0, np.float64)}

    def _status(self) -> dict:
        metrics = self.engine.metrics
        open_buckets = metrics.get("serving_breaker_open_buckets")
        with self._lock:
            inflight = sum(self._inflight.values())
            model_inflight = dict(self._inflight)
            swapping_models = set(self._swapping)
            swapping = bool(swapping_models)
            staged_tuple = self._staged.get(self.default_model)
            staged = staged_tuple[0] if staged_tuple else None
            model_staged = {m: s[0] for m, s in self._staged.items()}
        return {
            "health": self.engine.health.state.name,
            "ready": (self.engine.ready
                      and self.default_model not in swapping_models),
            "inflight": inflight,
            "swapping": swapping,
            "version": self.version,
            "staged": staged,
            # multi-model surface: which param sets this replica hosts
            # (the router's model-aware _pick consumes "models"), their
            # live versions, and the per-model cutover state
            "models": sorted(self.engines),
            "model_versions": dict(self.versions),
            "model_inflight": model_inflight,
            "model_swapping": sorted(swapping_models),
            "model_staged": model_staged,
            "compile_events": len(self._compile_events),
            "breaker_open_buckets": (int(open_buckets.value)
                                     if open_buckets else 0),
            "faults_fired": faults.counts(),
            # advertised so routers/operators can see which replicas
            # share KV prefixes (None = decode absent or caching off)
            "prefix_cache": (
                {"max_pages": self._prefix_cache_cfg.max_pages}
                if self._prefix_cache_cfg is not None else None),
            # which replicas draft-and-verify, and from which tree
            # (None = decode absent or speculation off)
            "speculative": (
                {"spec_k": self.decode_engine.geometry.spec_k,
                 "self_draft": self._spec_cfg.draft_task is None,
                 "draft_version": self._draft_version}
                if self._spec_cfg is not None else None),
        }

    def _load_draft_for(self, version: str, model: str):
        """The draft tree riding along with ``version`` (two trees,
        ONE cutover): a separately checkpointed draft is published as
        ``<version>-draft`` in the same (per-model) store. Returns
        None when this model doesn't draft from its own checkpoint — a
        self-draft engine tracks the target tree inside
        ``update_params``. Loading happens BEFORE either tree is
        swapped, so a corrupt draft manifest aborts the whole cutover
        typed and the replica keeps serving the old pair."""
        spec_cfg = self._spec_cfgs.get(model)
        if (model not in self.decode_engines or spec_cfg is None
                or spec_cfg.draft_task is None):
            return None
        store = self._store_for(model)
        draft_version = f"{version}-draft"
        if store is None or draft_version not in store.versions():
            return None
        return store.load(draft_version, None)

    def _resolve_model(self, model: Optional[str]) -> str:
        model = model or self.default_model
        if model not in self.engines:
            raise ValueError(f"unknown model {model!r} (hosting: "
                             f"{sorted(self.engines)})")
        return model

    def _update_version(self, version: str,
                        model: Optional[str] = None) -> dict:
        """The cutover for ONE model: quiesce that model → verify →
        swap → readmit. Dispatches against other models never drain
        and never see ``Unavailable("updating")`` — the per-tenant
        rolling-update isolation contract."""
        model = self._resolve_model(model)
        engine = self.engines[model]
        with self._lock:
            if model in self._swapping:
                raise Unavailable("updating", retry_after_s=0.1)
            self._swapping.add(model)
        try:
            with self._lock:
                while self._inflight.get(model, 0) > 0:
                    self._idle.wait(0.05)
            store = self._store_for(model)
            if store is None:
                raise ValueError("replica has no params version store")
            # verified load: raises CheckpointIntegrityError on a
            # corrupt manifest — crosses the wire typed, and the
            # rollout driver turns it into an auto-rollback
            params = store.load(version, engine._params_src)
            # both trees load before EITHER swaps: target and draft
            # can never come from different versions mid-traffic
            draft_params = self._load_draft_for(version, model)
            engine.update_params(params)
            decode_engine = self.decode_engines.get(model)
            if decode_engine is not None:
                decode_engine.update_params(
                    params, draft_params=draft_params)
            self.versions[model] = version
        finally:
            with self._lock:
                self._swapping.discard(model)
        return {"version": self.versions[model], "model": model}

    def _stage_version(self, version: str,
                       model: Optional[str] = None) -> dict:
        """Two-phase cutover, phase 1: verified load of ``version``
        into memory for one model. Serving is untouched — the staged
        tree sits beside the live one until commit or abort.
        Idempotent: re-staging replaces that model's staged tree."""
        model = self._resolve_model(model)
        store = self._store_for(model)
        if store is None:
            raise ValueError("replica has no params version store")
        params = store.load(version, self.engines[model]._params_src)
        # the draft tree stages alongside the target tree — a commit
        # later swaps both inside one quiesced window
        draft_params = self._load_draft_for(version, model)
        with self._lock:
            self._staged[model] = (version, params, draft_params)
        return {"staged": version, "model": model}

    def _commit_version(self, version: str,
                        model: Optional[str] = None) -> dict:
        """Phase 2: quiesce ONE model and swap to its STAGED params.
        The swap itself is the same atomic quiesce → ``update_params``
        → readmit as ``update_version`` — a dispatch racing the commit
        gets the typed ``Unavailable`` retry, never torn params."""
        model = self._resolve_model(model)
        # the killed-between-stage-and-swap chaos window: a SIGKILL
        # here leaves this member staged-but-uncommitted while its
        # siblings may already serve the new version — the group
        # handle's rollback path owns the cleanup
        faults.maybe_kill("replica.commit_crash")
        with self._lock:
            if model in self._swapping:
                raise Unavailable("updating", retry_after_s=0.1)
            staged = self._staged.get(model)
            if staged is None or staged[0] != version:
                have = staged[0] if staged else None
                raise ValueError(
                    f"commit of {version!r} without a matching stage "
                    f"(staged: {have!r}) — the two-phase protocol "
                    f"requires stage_version first")
            self._swapping.add(model)
        try:
            with self._lock:
                while self._inflight.get(model, 0) > 0:
                    self._idle.wait(0.05)
                version, params, draft_params = self._staged.pop(model)
            engine = self.engines[model]
            engine.update_params(params)
            decode_engine = self.decode_engines.get(model)
            if decode_engine is not None:
                decode_engine.update_params(
                    params, draft_params=draft_params)
            self.versions[model] = version
        finally:
            with self._lock:
                self._swapping.discard(model)
        return {"version": self.versions[model], "model": model}

    def _abort_version(self, model: Optional[str] = None) -> dict:
        """Drop one model's staged version (stage-phase failure on a
        sibling)."""
        model = self._resolve_model(model)
        with self._lock:
            staged = self._staged.pop(model, None)
        return {"aborted": staged[0] if staged else None,
                "model": model}

    # -- lifecycle --------------------------------------------------------

    def serve_forever(self) -> None:
        print(f"READY {self.server.port}", flush=True)
        self._stop.wait()
        self.server.close()

    def close(self) -> None:
        self._stop.set()
        for decode_engine in self.decode_engines.values():
            decode_engine.close()
        self.server.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="fleet replica process")
    ap.add_argument("--spec", required=True,
                    help="path to the replica spec JSON")
    args = ap.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)
    replica = ReplicaServer(spec)
    replica.serve_forever()
    return 0


if __name__ == "__main__":
    sys.exit(main())
