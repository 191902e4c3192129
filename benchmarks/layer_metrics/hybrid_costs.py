"""Operations and bytes of the two mechanisms a hybrid state-space /
mixture-of-experts stack adds (``perceiver_tpu/ops/ssm.py``,
``perceiver_tpu/ops/moe.py``), from the configuration's shapes, and what
their roofline readers share: the least time a step's work could take,
and the whole steps of the device trace with the device time under a
scope inside them. No reader file itself (no ``read``):
``ssm_scan_roofline`` and ``moe_expert_roofline`` import it.

Both rooflines are read over a **scope**, not a kernel's name: the same
work is counted whether XLA, a kernel JAX ships or one of the repo's
own does it. By the rules at the head of ``benchmarks/flops.py``: a
product 2 m n k, a backward pass twice its forward's products,
recomputation not counted.
"""

import functools
import json
import os

from benchmarks import flops, scope_times, trace_reduce


def scan_cost(cfg: dict, rows: int, positions: int, *, backward: bool):
    """(operations, bytes) of one Mamba-2 layer's chunked scan over
    ``rows`` rows of ``positions`` positions, one pass. The products of
    the SSD form at ``chunk_size`` Q: inside a chunk ``C B^T`` a group
    and the scores times ``dt x`` a head, each ``Q x Q`` whole (a dense
    product under the causal mask, as the form defines it: a chunk is
    the unit the mask cannot cut); each chunk's own state and the
    carried state's reading, ``P x N`` a head each. Bytes: x, B, C in the
    compute dtype and dt in float32 read, y written, once; the backward
    reads them and ``dy`` and writes four gradients: twice as many."""
    heads, width = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    groups, state = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    q = min(int(cfg["chunk_size"]), positions)
    ops = rows * positions * 2.0 * (
        groups * q * state + heads * q * width + 2 * heads * width * state)
    moved = rows * positions * (
        2.0 * (2 * heads * width + 2 * groups * state) + 4.0 * heads)
    factor = 2.0 if backward else 1.0
    return factor * ops, factor * moved


def grouped_cost(cfg: dict, assignments: float, *, backward: bool):
    """(operations, bytes) of one expert layer's two grouped products
    over ``assignments`` (token, held expert) rows, one pass. Bytes: the
    held experts' two matrices read once, each row read and written
    once a product, all in the compute dtype; the backward reads the
    matrices and twice the rows and writes the matrices' gradient."""
    c, hidden = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    held = int(cfg.get("held_experts") or cfg["n_routed_experts"])
    ops = assignments * 2.0 * 2 * c * hidden
    matrices = 2.0 * held * 2 * c * hidden
    rows = 2.0 * assignments * 2 * (c + hidden)
    if backward:
        return 2.0 * ops, 2.0 * matrices + 2.0 * rows
    return ops, matrices + rows


def expected_assignments(cfg: dict, tokens: int) -> float:
    """What an even router sends the held experts of one layer."""
    held = int(cfg.get("held_experts") or cfg["n_routed_experts"])
    return tokens * float(cfg["num_experts_per_tok"]) * held \
        / int(cfg["n_routed_experts"])


def traced_step_numbers(run):
    """The numbers of the steps the trainer began inside the traced
    window (its ``train/step`` spans); None where there is no span."""
    spans = scope_times.window_spans(run, "a step's assignments") or []
    return {s["step"] for s in spans if s["name"] == "train/step"} or None


def counted_assignments(run):
    """The mean, over the steps the trainer began inside the traced
    window, of the program's own counter (``moe_assignments`` of the
    trainer's telemetry line: all expert layers of a step together);
    over every line where no span says which steps those were; None
    where there is no such line."""
    path = os.path.join(os.path.dirname(run.tracer.directory), "telemetry",
                        "telemetry.jsonl")
    try:
        with open(path) as f:
            lines = [rec for rec in map(json.loads, f)
                     if "moe_assignments" in rec]
    except (OSError, ValueError):
        return None
    traced = traced_step_numbers(run)
    if traced is not None:
        lines = [rec for rec in lines if rec.get("step") in traced] or lines
    counts = [rec["moe_assignments"] for rec in lines]
    return sum(counts) / len(counts) if counts else None


def least_seconds(run, costs) -> float:
    """The least time the chip could take for ``costs``, an iterable of
    (operations, bytes): each at the bound that binds it."""
    return sum(flops.roofline_seconds(ops, moved, run.peak)[0]
               for ops, moved in costs)


def whole_steps(planes, marker_scope: str = "optimizer"):
    """``(steps, seconds by scope)`` a device, over the whole steps of
    the device trace alone. A step's marker is the operation under
    ``marker_scope`` with the most device time: an instruction outside
    every loop runs once a step, so from its first start to its last
    lie as many whole steps as it ran, less one, whatever part of a
    step the trace began and ended in. The seconds are the self time
    (``scope_times.self_ps_by_metadata``) of the operations that
    started in that span, under each name of their stacks. None where
    the marker ran fewer than twice."""
    steps, by_scope = 0, {}
    for plane in planes:
        spent = {}
        for _, duration, meta in plane.events:
            if marker_scope in scope_times.names_of(
                    plane.op_names.get(meta, "")):
                spent[meta] = spent.get(meta, 0) + duration
        if not spent:
            return None
        marker = max(spent, key=spent.get)
        starts = sorted(start for start, _, meta in plane.events
                        if meta == marker)
        if len(starts) < 2:
            return None
        steps += len(starts) - 1
        inside = [e for e in plane.events if starts[0] <= e[0] < starts[-1]]
        for meta, ps in scope_times.self_ps_by_metadata(inside).items():
            for name in set(scope_times.names_of(
                    plane.op_names.get(meta, ""))):
                by_scope[name] = by_scope.get(name, 0.0) + ps / 1e12
    n = len(planes)
    return (steps / n, {k: v / n for k, v in by_scope.items()}) \
        if n else None


@functools.lru_cache(maxsize=2)
def _whole_steps_of(path: str):
    return whole_steps(scope_times.load_device_ops(path))


def traced_whole_steps(run):
    """``whole_steps`` of the run's trace, or None: no trace, no device
    plane, no marker."""
    if run.trace is None or not run.tracer.enabled:
        return None
    try:
        path = trace_reduce.find_xplane(run.tracer.directory)
    except FileNotFoundError:
        return None
    return _whole_steps_of(path)


def roofline_share(run, name: str, step_costs, what: str):
    """100 x the least time of ``step_costs`` x the whole steps of the
    device trace / the device time under ``name`` inside those steps:
    device work over device time, both from the trace. None where there
    is no trace, no marker or no operation under ``name`` (the parent of
    the PR that added the scope)."""
    found = traced_whole_steps(run)
    if found is None or not run.peak:
        return None
    steps, by_scope = found
    seconds = by_scope.get(name)
    if not seconds:
        return None
    least = steps * least_seconds(run, step_costs)
    print(f"[bench] {what}: {seconds:.4f} s on the device under {name} "
          f"over the {steps:g} whole steps of the trace, least "
          f"{least:.4f} s", flush=True)
    return 100.0 * least / seconds
