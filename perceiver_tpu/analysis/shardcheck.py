"""Shardcheck: sharding-aware passes over lowered SPMD graphs.

Three passes, one failure philosophy (docs/ANALYSIS.md): the
properties SPMD scale-out lives or dies on are statically visible in
the lowered/compiled module, so they are gated there — before a chip
ever runs the program.

``collective_budget``
    GSPMD inserts every collective at compile time, so the pass walks
    the *optimized* HLO (``LoweredStep.compiled_text``) for
    all-reduce / all-gather / reduce-scatter / collective-permute /
    all-to-all, attributes each op's bytes to the mesh-axis subset its
    replica groups span (``hlo.attribute_axis``), and gates the
    per-axis byte totals against the checked-in manifest
    (``shard_budgets.json``). Axis traffic above budget — or on an
    axis with no budget at all — fails the merge: on a real slice the
    data axis is DCN/ICI once per step while the model axis pays per
    layer, so "some new collective appeared" is exactly the class of
    regression that must not land silently.

``replication_check``
    A tensor the sharding rules declared sharded must not materialize
    fully replicated: the pass scans the @main boundary (args +
    results) and mid-graph ``sdy.sharding_constraint`` ops of the
    StableHLO for tensors at or above a size floor whose annotation
    replicates them, modulo a per-target ``ReplicationAllow`` list (the audit
    trail for read-only tables that are replicated by design). This is
    the static form of "the step silently all-gathers the full
    parameter pytree" — the pjit scaling postmortem classic.

``per_shard_hbm_budget``
    The global hbm_budget divided by the mesh: cost-analysis bytes ÷
    device count, pinned per target in the same manifest. Pins the
    figure that actually has to fit one device's HBM, so halving the
    mesh or un-sharding a large buffer cannot hide inside the global
    number.

Re-baseline protocol mirrors hbm_budget: ``scripts/check.py
--rebaseline-shard`` rewrites the manifest from fresh measurements
(``--pin-missing-shard`` budgets only new targets); the manifest diff
is the audit trail of every accepted regression.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from perceiver_tpu.analysis import hlo
from perceiver_tpu.analysis.report import ReplicationAllow, Violation

_SHARD_MANIFEST = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "shard_budgets.json")
# collective placement moves with GSPMD heuristics across jax versions
# more than cost-analysis bytes do, so the headroom is looser than
# hbm_budget's 1.05 — still tight enough that a new per-layer
# all-gather (≥2× on its axis) trips
_SHARD_HEADROOM = 1.10
# tensors under 1 MiB may replicate freely (norm scales, biases,
# descriptors); above it, replication must be declared
DEFAULT_FLOOR_BYTES = 1 << 20


def load_shard_budgets(path: Optional[str] = None) -> Dict[str, dict]:
    """Target-name → manifest entry (``{mesh, collectives, per_shard,
    pinned}``). Empty when absent — every mesh target then fails with
    a missing-budget violation, so a deleted manifest cannot read as a
    clean tree."""
    try:
        with open(path or _SHARD_MANIFEST) as f:
            return json.load(f)["targets"]
    except (OSError, KeyError, ValueError):
        return {}


def write_shard_budgets(measured: Dict[str, dict],
                        path: Optional[str] = None,
                        headroom: float = _SHARD_HEADROOM,
                        note: str = "",
                        keep: Optional[Dict[str, dict]] = None) -> dict:
    """Re-baseline the shard manifest. ``measured`` maps target name →
    ``{"mesh": descriptor, "collectives": {axis: bytes},
    "per_shard": bytes, "ops": {...}}`` (``ops`` is informational and
    copied through). ``keep`` copies already-pinned entries verbatim —
    the ``--pin-missing-shard`` path."""
    def entry(m: dict) -> dict:
        return {
            "mesh": m["mesh"],
            "collectives": {
                axis: {"pinned_bytes": int(b),
                       "budget_bytes": int(b * headroom)}
                for axis, b in sorted(m["collectives"].items())},
            "per_shard": {
                "pinned_bytes": int(m["per_shard"]),
                "budget_bytes": int(m["per_shard"] * headroom)},
            "ops": m.get("ops", {}),
            "pinned": note,
        }

    manifest = {
        "_comment": (
            "shardcheck manifest — per-mesh-axis collective bytes "
            "(optimized HLO, CPU SPMD partitioning) and per-shard "
            "cost-analysis bytes per sharded canonical target. "
            f"budget_bytes = pinned_bytes x {headroom}. Re-baseline "
            "via scripts/check.py --rebaseline-shard after an "
            "intentional change; never edit budgets by hand to make "
            "a regression pass."),
        "targets": dict(sorted({
            **(keep or {}),
            **{name: entry(m) for name, m in measured.items()},
        }.items())),
    }
    with open(path or _SHARD_MANIFEST, "w") as f:
        json.dump(manifest, f, indent=1)
        f.write("\n")
    return manifest


# --- collective inventory / budget -------------------------------------------


def collective_inventory(compiled_text: str, mesh) -> dict:
    """Per-axis collective totals from optimized HLO:
    ``{"collectives": {axis: bytes}, "ops": {axis: {op: count}}}``.
    ``mesh`` is a ``targets.MeshSpec``. Degenerate ops whose replica
    groups are all singletons move no bytes and are skipped."""
    shape, names = list(mesh.shape), list(mesh.axis_names)
    by_axis: Dict[str, int] = {}
    ops: Dict[str, Dict[str, int]] = {}
    for col in hlo.iter_collectives(compiled_text):
        if all(len(g) <= 1 for g in col["groups"]):
            continue
        axis = hlo.attribute_axis(col["groups"], shape, names)
        by_axis[axis] = by_axis.get(axis, 0) + col["bytes"]
        ops.setdefault(axis, {})
        ops[axis][col["op"]] = ops[axis].get(col["op"], 0) + 1
    return {"collectives": by_axis, "ops": ops}


def collective_budget(compiled_text: Optional[str], mesh, *, where: str,
                      budgets: Dict[str, dict],
                      ) -> Tuple[List[Violation], dict]:
    """Per-axis collective bytes must stay within the target's pinned
    budgets; traffic on an unbudgeted axis is itself a violation (a
    brand-new collective class must be pinned, not waved through).
    Returns ``(violations, inventory)``."""
    if compiled_text is None:
        return [Violation(
            check="collective_budget", where=where,
            message="no compiled HLO available for this mesh target — "
                    "lower_target(want_compiled=True) is required; "
                    "collectives only exist post-SPMD-partitioning")], {}
    inventory = collective_inventory(compiled_text, mesh)
    entry = budgets.get(where)
    if entry is None:
        return [Violation(
            check="collective_budget", where=where,
            message="no collective budget pinned for this target in "
                    "shard_budgets.json — run scripts/check.py "
                    "--rebaseline-shard and commit the manifest")], inventory
    violations = []
    pinned_axes = entry.get("collectives", {})
    if entry.get("mesh") != mesh.descriptor:
        violations.append(Violation(
            check="collective_budget", where=where,
            message=f"manifest pinned mesh {entry.get('mesh')!r} but the "
                    f"target now lowers over {mesh.descriptor!r} — "
                    "re-baseline so budgets match the topology"))
    for axis, measured in sorted(inventory["collectives"].items()):
        pin = pinned_axes.get(axis)
        if pin is None:
            violations.append(Violation(
                check="collective_budget", where=where,
                message=f"{measured / 1e6:.2f} MB of collective traffic "
                        f"on unbudgeted mesh axis {axis!r} "
                        f"({inventory['ops'][axis]}) — a new collective "
                        "class appeared; pin it via scripts/check.py "
                        "--rebaseline-shard if intentional"))
            continue
        budget = float(pin["budget_bytes"])
        if measured > budget:
            pinned = float(pin.get("pinned_bytes", budget))
            violations.append(Violation(
                check="collective_budget", where=where,
                message=f"{measured / 1e6:.2f} MB moved on mesh axis "
                        f"{axis!r} exceeds the pinned budget "
                        f"{budget / 1e6:.2f} MB "
                        f"({100 * (measured / pinned - 1):+.1f}% vs "
                        "baseline) — collective traffic regressed "
                        f"({inventory['ops'][axis]}); fix the sharding "
                        "or re-baseline via --rebaseline-shard with "
                        "justification"))
    return violations, inventory


# --- replication / resharding detector ---------------------------------------

# mid-graph sharding constraints print as
#   %1 = sdy.sharding_constraint %0 <@mesh, [{"data"}, {}]>
#       : tensor<512x64xf32>
_MIDGRAPH_SHARDING = re.compile(
    r"sdy\.sharding_constraint \S+ <@[\w.]+, (\[[^\]]*\])[^:\n]*"
    r":\s*tensor<([^>]+)>")


def replication_check(text: str, *, where: str,
                      floor_bytes: int = DEFAULT_FLOOR_BYTES,
                      allowlist: Sequence[ReplicationAllow] = (),
                      ) -> List[Violation]:
    """No tensor ≥ ``floor_bytes`` may be fully replicated at the
    @main boundary or resharded to replicated mid-graph, outside the
    allowlist. Runs on the StableHLO of a pjit-lowered module (where
    every boundary tensor carries ``sdy.sharding``)."""
    axes = hlo.mesh_axes(text)
    suspects: List[Tuple[str, str, str]] = []  # (site, type, sharding)
    for a in hlo.main_args(text):
        suspects.append(("arg", a["type"], a["sharding"]))
    for r in hlo.main_results(text):
        suspects.append(("result", r["type"], r["sharding"]))
    for m in _MIDGRAPH_SHARDING.finditer(text):
        suspects.append(("mid-graph constraint", m.group(2), m.group(1)))
    budgets = {id(a): a.max_count for a in allowlist}
    violations = []
    for site, ty, sharding in suspects:
        if hlo.sharding_factor(sharding, axes) != 1:
            continue
        size = hlo.tensor_bytes(ty)
        if size < floor_bytes:
            continue
        hit = next((a for a in allowlist
                    if a.type == ty and budgets[id(a)] > 0), None)
        if hit is not None:
            budgets[id(hit)] -= 1
            continue
        violations.append(Violation(
            check="replication_check", where=where,
            message=f"{site} tensor<{ty}> ({size / 1e6:.2f} MB) is "
                    "fully replicated — every device holds a whole "
                    "copy despite the declared shardings; shard it "
                    "(parallel/sharding.py) or record a reasoned "
                    "ReplicationAllow on the target"))
    return violations


# --- per-shard HBM budget ----------------------------------------------------


def per_shard_hbm_budget(bytes_accessed: Optional[float], mesh, *,
                         where: str, budgets: Dict[str, dict],
                         ) -> List[Violation]:
    """Cost-analysis bytes ÷ mesh devices must stay within the pinned
    per-shard budget — the figure that has to fit ONE device's HBM."""
    entry = budgets.get(where)
    if entry is None or "per_shard" not in entry:
        return [Violation(
            check="per_shard_hbm_budget", where=where,
            message="no per-shard byte budget pinned for this target "
                    "in shard_budgets.json — run scripts/check.py "
                    "--rebaseline-shard and commit the manifest")]
    if bytes_accessed is None:
        return [Violation(
            check="per_shard_hbm_budget", where=where,
            message="lowering exposed no cost analysis, so the "
                    "per-shard budget cannot be checked — run on a "
                    "backend with lowering-time cost analysis (CPU)")]
    per_shard = bytes_accessed / mesh.n_devices
    pin = entry["per_shard"]
    budget = float(pin["budget_bytes"])
    if per_shard > budget:
        pinned = float(pin.get("pinned_bytes", budget))
        return [Violation(
            check="per_shard_hbm_budget", where=where,
            message=f"per-shard bytes {per_shard / 1e9:.2f} GB "
                    f"(global ÷ {mesh.n_devices}) exceeds the pinned "
                    f"budget {budget / 1e9:.2f} GB "
                    f"({100 * (per_shard / pinned - 1):+.1f}% vs "
                    "baseline) — a buffer stopped sharding or the step "
                    "regressed; fix it or re-baseline via "
                    "--rebaseline-shard with justification")]
    return []


def run_shard_passes(lowered, *, budgets: Dict[str, dict],
                     floor_bytes: int = DEFAULT_FLOOR_BYTES,
                     ) -> Tuple[List[Violation], dict]:
    """All three shardcheck passes over one mesh ``LoweredStep``.
    Returns ``(violations, inventory)`` — the inventory feeds the
    manifest pin paths in scripts/check.py."""
    target = lowered.target
    vs, inventory = collective_budget(
        lowered.compiled_text, target.mesh, where=target.name,
        budgets=budgets)
    vs += replication_check(
        lowered.text, where=target.name, floor_bytes=floor_bytes,
        allowlist=target.replication_allow)
    vs += per_shard_hbm_budget(
        lowered.bytes_accessed, target.mesh, where=target.name,
        budgets=budgets)
    return vs, inventory
