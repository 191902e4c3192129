"""Lowered-graph passes: dtype policy, host transfers, donation, and
compile-cache closure, each over the StableHLO of a canonical train
step (``targets.py``).

These gate the exact defect classes previous rounds found by hand:
the round-4 HLO audit caught 9.1% of step FLOPs silently running at
the fp32 MXU rate (dtype_policy), and a host callback in the step
stalls the device and makes the executable uncacheable
(transfer_guard) — both are properties of the lowered module, so they
are checked on the lowered module.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

from perceiver_tpu.analysis import hlo
from perceiver_tpu.analysis.report import (
    DtypeAllow,
    Report,
    TransferAllow,
    Violation,
    apply_dtype_allowlist,
)
from perceiver_tpu.analysis.targets import (
    CANONICAL_TARGETS,
    LoweredStep,
    StepTarget,
    lower_target,
)

# operand dtypes the MXU runs at reduced rate — any matmul-class op
# carrying one of these must be allowlisted with a reason
_SLOW_MATMUL_DTYPES = ("f32", "f64")


def dtype_policy(text: str, *, where: str,
                 allowlist: Sequence[DtypeAllow] = (),
                 require_full_bf16: bool = False,
                 ) -> Tuple[List[Violation], dict]:
    """No fp32/fp64 ``dot_general``/``convolution`` outside the
    allowlist; headline configs additionally pin the FLOP-weighted
    bf16 fraction at exactly 1.0 (the round-4 audit's regression)."""
    violations = []
    dots = list(hlo.iter_dots(text))
    slow = [d for d in dots + list(hlo.iter_convs(text))
            if d["dtype"] in _SLOW_MATMUL_DTYPES]
    _, violating = apply_dtype_allowlist(slow, tuple(allowlist))
    total = sum(d["flops"] for d in dots) or 1.0
    for rec in violating:
        share = (f", {100 * rec['flops'] / total:.1f}% of step dot-FLOPs"
                 if rec.get("flops") else "")
        violations.append(Violation(
            check="dtype_policy", where=where,
            message=f"{rec['dtype']} {rec['op']} {rec['sig']}{share} — "
                    "matmuls must run in bf16 (Policy.bf16 compute "
                    "dtype); cast the operands or add a reasoned "
                    "DtypeAllow to the target"))
    summary = hlo.dot_flop_summary(dots)
    if require_full_bf16 and summary["bf16_flop_fraction"] != 1.0:
        violations.append(Violation(
            check="dtype_policy", where=where,
            message=f"bf16_flop_fraction = "
                    f"{summary['bf16_flop_fraction']} != 1.0 on a "
                    "headline config — some dot FLOPs run at the fp32 "
                    "MXU rate (the round-4 9.1% regression class)"))
    return violations, summary


def transfer_guard(text: str, *, where: str,
                   allowlist: Sequence[TransferAllow] = (),
                   ) -> List[Violation]:
    """No host↔device transfers inside the jitted step: infeed/outfeed/
    send/recv, host-compute offload, or host-callback custom calls.
    A callback embeds a host pointer, so the step could not be stored
    in the executable cache either."""
    violations = []
    budgets = {a.marker: a.max_count for a in allowlist}
    for marker, count in sorted(hlo.count_host_markers(text).items()):
        allowed = budgets.get(marker, 0)
        if count > allowed:
            over = count - allowed
            violations.append(Violation(
                check="transfer_guard", where=where,
                message=f"{over} unallowlisted host-transfer marker(s) "
                        f"{marker!r} in the jitted step (total {count}, "
                        f"allowlisted {allowed}) — host syncs stall the "
                        "device pipeline and make the executable "
                        "uncacheable"))
    return violations


def donation_check(text: str, *, where: str,
                   expected_donated: int) -> List[Violation]:
    """Train-state buffers must be donated AND actually aliased onto
    outputs by lowering (``tf.aliasing_output``). A donated-but-
    unaliased buffer (``jax.buffer_donor``) doubles its HBM footprint
    exactly like forgetting ``donate_argnums``."""
    args = hlo.main_args(text)
    aliased = sum(1 for a in args if a["aliased"])
    donor_only = [a for a in args if a["donor_only"]]
    violations = []
    if aliased < expected_donated:
        violations.append(Violation(
            check="donation_check", where=where,
            message=f"only {aliased}/{expected_donated} train-state "
                    "buffers are donated+aliased in the lowered step — "
                    "params/optimizer state must ride donate_argnums "
                    "or peak HBM carries two copies of the state"))
    for a in donor_only:
        violations.append(Violation(
            check="donation_check", where=where,
            message=f"buffer tensor<{a['type']}> is marked donated but "
                    "lowering found no matching output to alias "
                    "(shape/dtype drift between input and output state)"))
    return violations


def recompile_budget(target: StepTarget,
                     first: Optional[LoweredStep] = None,
                     second: Optional[LoweredStep] = None,
                     ) -> Tuple[List[Violation], str]:
    """The compilation-cache key set must be closed: rebuilding a
    target's task + batch from scratch and re-lowering must reproduce
    the identical step signature (shapes, dtypes, donation layout) and
    an equal task hash. Any drift is a recompile per rebuild on the
    chip — the silent multi-minute stall class."""
    violations = []
    if first is None:
        first = lower_target(target)
    if second is None:
        second = lower_target(target)
    fp1 = hlo.module_fingerprint(first.text)
    fp2 = hlo.module_fingerprint(second.text)
    if fp1 != fp2:
        violations.append(Violation(
            check="recompile_budget", where=target.name,
            message=f"independent rebuilds lowered to different step "
                    f"signatures ({fp1} vs {fp2}) — shape/dtype drift "
                    "in the task config or batch builder means every "
                    "rebuild recompiles"))
    # task hashes are only comparable when both steps were built in
    # THIS process (str hashing is salted per process; a cache-served
    # step carries None and skips the check)
    if first.task_hash is not None and second.task_hash is not None \
            and first.task_hash != second.task_hash:
        violations.append(Violation(
            check="recompile_budget", where=target.name,
            message="task config hash differs across rebuilds — the "
                    "config dataclass carries unstable state, so jit "
                    "treats each instance as a new cache key"))
    return violations, fp1


def cache_key_stability(target: StepTarget,
                        first: Optional[LoweredStep] = None,
                        second: Optional[LoweredStep] = None,
                        ) -> Tuple[List[Violation], str]:
    """Two independent lowerings of a canonical target must hash to
    the SAME full-module text — the persistent executable cache
    (``perceiver_tpu/cache``) keys on that hash, so any trace-time
    leakage into the graph body (time, host RNG, ``id()``-derived
    names) silently zeroes the warm-start hit rate long before it
    shows up anywhere else. ``recompile_budget`` only pins the @main
    signature; this pass pins every byte. When ``first`` came from a
    persistent lowering record, the comparison spans processes — the
    exact reuse the executable cache performs."""
    violations = []
    if first is None:
        first = lower_target(target)
    if second is None:
        second = lower_target(target)
    h1 = hlo.text_hash(first.text)
    h2 = hlo.text_hash(second.text)
    if h1 != h2:
        span = ("a previous process's lowering and a fresh one"
                if first.cached else "two fresh lowerings")
        violations.append(Violation(
            check="cache_key_stability", where=target.name,
            message=f"{span} of this target hash to different module "
                    f"text ({h1[:16]} vs {h2[:16]}) — something leaks "
                    "trace-time state (time/RNG/object ids) into the "
                    "graph, which zeroes the executable-cache hit "
                    "rate; diff the two lowerings to find the "
                    "drifting op"))
    return violations, h1


# --- hbm_budget --------------------------------------------------------------
# Checked-in per-target byte budgets. The round-6 traffic work cut the
# headline step's cost-analysis bytes 38% — this pass is what keeps
# that win from silently eroding: any step whose lowered module
# accesses more bytes than its pinned budget fails the merge gate.

_HBM_MANIFEST = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "hbm_budgets.json")
# budget = pinned_bytes · headroom: room for benign refactors and
# jax-version drift in the cost model, small enough that a real
# regression (a re-materialized residual, an fp32 copy) still trips
_HBM_HEADROOM = 1.05


def load_hbm_budgets(path: Optional[str] = None) -> Dict[str, dict]:
    """Target-name → ``{budget_bytes, pinned_bytes, pinned}`` from the
    checked-in manifest (empty dict when the manifest is absent — every
    canonical target then fails with a missing-budget violation, so a
    deleted manifest cannot read as a clean tree)."""
    try:
        with open(path or _HBM_MANIFEST) as f:
            return json.load(f)["targets"]
    except (OSError, KeyError, ValueError):
        return {}


def write_hbm_budgets(measured: Dict[str, float],
                      path: Optional[str] = None,
                      headroom: float = _HBM_HEADROOM,
                      note: str = "",
                      keep: Optional[Dict[str, dict]] = None) -> dict:
    """Re-baseline: pin each target's measured bytes and derive its
    budget. Only for INTENTIONAL traffic changes — see docs/ANALYSIS.md
    for the re-baseline protocol (the diff of this file is the audit
    trail of every accepted regression or win).

    ``keep`` carries already-pinned entries to copy through verbatim —
    the ``--pin-missing-hbm`` path, which budgets newly added targets
    without silently re-baselining the existing ones."""
    manifest = {
        "_comment": (
            "hbm_budget manifest — XLA cost-analysis 'bytes accessed' "
            "per canonical train step (CPU lowering, scan bodies "
            "counted once). budget_bytes = pinned_bytes x "
            f"{headroom}. Re-baseline via scripts/check.py "
            "--rebaseline-hbm after an intentional change; never edit "
            "budgets by hand to make a regression pass."),
        "targets": dict(sorted({
            **(keep or {}),
            **{name: {
                "budget_bytes": int(value * headroom),
                "pinned_bytes": int(value),
                "pinned": note,
            } for name, value in measured.items()},
        }.items())),
    }
    with open(path or _HBM_MANIFEST, "w") as f:
        json.dump(manifest, f, indent=1)
        f.write("\n")
    return manifest


def hbm_budget(bytes_accessed: Optional[float], *, where: str,
               budgets: Dict[str, dict]) -> List[Violation]:
    """The lowered step's cost-analysis bytes must stay within the
    target's pinned budget. A missing budget is itself a violation —
    every canonical target must be budgeted, or adding a target would
    silently opt it out of the traffic gate."""
    entry = budgets.get(where)
    if entry is None:
        return [Violation(
            check="hbm_budget", where=where,
            message="no byte budget pinned for this target in "
                    "hbm_budgets.json — run scripts/check.py "
                    "--rebaseline-hbm and commit the manifest")]
    if bytes_accessed is None:
        return [Violation(
            check="hbm_budget", where=where,
            message="lowering exposed no cost analysis, so the byte "
                    "budget cannot be checked — run the gate on a "
                    "backend with lowering-time cost analysis (CPU)")]
    budget = float(entry["budget_bytes"])
    if bytes_accessed > budget:
        pinned = float(entry.get("pinned_bytes", budget))
        return [Violation(
            check="hbm_budget", where=where,
            message=f"bytes accessed {bytes_accessed / 1e9:.2f} GB "
                    f"exceeds the pinned budget {budget / 1e9:.2f} GB "
                    f"({100 * (bytes_accessed / pinned - 1):+.1f}% vs "
                    "the pinned baseline) — the step's HBM traffic "
                    "regressed; fix the graph or, for an intentional "
                    "change, re-baseline via scripts/check.py "
                    "--rebaseline-hbm and justify it in the PR")]
    return []


def run_graph_checks(targets: Sequence[StepTarget] = CANONICAL_TARGETS,
                     *, recompile: bool = True, cache=None) -> Report:
    """Lower each target and run all graph passes. ``recompile=False``
    skips the second lowering per target (the fast tier-1 subset).

    ``cache`` reuses persistent lowering records
    (``perceiver_tpu.cache.ExecutableCache``): the text passes then
    gate the recorded lowering — identical to a fresh one by key
    construction — and the double-lowering passes compare it against
    ONE fresh trace, which turns ``cache_key_stability`` into a
    cross-process check and halves (``--graph``) or removes
    (``--graph --fast``) the lowering bill of a warm run."""
    from perceiver_tpu.analysis import shardcheck

    report = Report()
    fingerprints = {}
    budgets = load_hbm_budgets()
    shard_budgets = shardcheck.load_shard_budgets()
    for target in targets:
        lowered = lower_target(target, cache=cache)
        report.extend(hbm_budget(lowered.bytes_accessed,
                                 where=target.name, budgets=budgets))
        report.ran("hbm_budget")
        if target.mesh is not None:
            vs, _inventory = shardcheck.run_shard_passes(
                lowered, budgets=shard_budgets)
            report.extend(vs)
            report.ran("collective_budget")
            report.ran("replication_check")
            report.ran("per_shard_hbm_budget")
        vs, _summary = dtype_policy(
            lowered.text, where=target.name,
            allowlist=target.dtype_allow,
            require_full_bf16=target.headline)
        report.extend(vs)
        report.ran("dtype_policy")
        report.extend(transfer_guard(
            lowered.text, where=target.name,
            allowlist=target.transfer_allow))
        report.ran("transfer_guard")
        report.extend(donation_check(
            lowered.text, where=target.name,
            expected_donated=lowered.expected_donated))
        report.ran("donation_check")
        if recompile:
            # the second lowering is always fresh — when `lowered`
            # came from the cache this compares across processes.
            # want_compiled=False: the stability passes only compare
            # StableHLO text, so mesh targets skip the XLA compile
            second = lower_target(target, want_compiled=False)
            vs, fp = recompile_budget(target, first=lowered,
                                      second=second)
            report.extend(vs)
            report.ran("recompile_budget")
            vs, _h = cache_key_stability(target, first=lowered,
                                         second=second)
            report.extend(vs)
            report.ran("cache_key_stability")
            fingerprints[target.name] = fp
    if recompile:
        # declared signature twins: a target whose whole point is that
        # it lowers onto ANOTHER target's compile key (the multi-tenant
        # decode round — tenancy is host-side state, so admitting a
        # tenant must mint zero new executables). Equality is ASSERTED
        # when both ends were lowered this run, and the twin is
        # excluded from the distinct-targets collapse check below.
        twins = {t.name: t.signature_twin for t in targets
                 if t.signature_twin}
        for name, twin in twins.items():
            if name not in fingerprints or twin not in fingerprints:
                continue  # partial run (e.g. a single-target tier)
            if fingerprints[name] != fingerprints[twin]:
                report.add(Violation(
                    check="recompile_budget", where=name,
                    message=f"declared signature twin of {twin!r} but "
                            f"the fingerprints diverged "
                            f"({fingerprints[name]} vs "
                            f"{fingerprints[twin]}) — the twin config "
                            "now compiles its own executable, which "
                            "for the multi-tenant round means tenant "
                            "admission costs a mid-traffic compile"))
        primary = {n: fp for n, fp in fingerprints.items()
                   if n not in twins}
        if len(set(primary.values())) < len(primary):
            dupes = {n: fp for n, fp in primary.items()
                     if list(primary.values()).count(fp) > 1}
            report.add(Violation(
                check="recompile_budget", where=",".join(sorted(dupes)),
                message=f"distinct targets share a step signature "
                        f"{dupes} — two canonical configs collapsed "
                        "onto one compile key, so one of them is not "
                        "being checked"))
    return report
