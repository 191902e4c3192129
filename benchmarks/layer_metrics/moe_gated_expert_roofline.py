"""Roofline share of the gated experts' grouped products in the traced
window: the least time the chip could take for the **three** products
(gate, up, down) of every expert layer of the trace's whole steps
(``hybrid_costs.whole_steps``: counted from the device trace itself),
forward and backward, from the assignments the steps really made (the
program's counter ``moe_assignments`` in the trainer's telemetry,
averaged over the steps the trainer began inside the traced window and
shared evenly between the expert layers; the expected share of an even
router where no such line can be reached: the line printed says which)
and the held experts' matrices read once a pass
(``block_diffusion_costs.gated_grouped_cost``), over the device time
under the scope ``moe_experts`` inside those steps. The same line says
how many of the window's steps had an expert layer outside its usual
buffer (the program's counter ``moe_full_buffer_layers``). None for a
configuration without gated experts over a block-diffusion row."""

import json
import os

from benchmarks.layer_metrics import block_diffusion_costs as costs
from benchmarks.layer_metrics import hybrid_costs


def full_buffer_steps(run):
    """``(steps with an expert layer in the T x top_k buffer, steps)``
    over the telemetry's lines that carry the counter; None where none
    does."""
    path = os.path.join(os.path.dirname(run.tracer.directory), "telemetry",
                        "telemetry.jsonl")
    try:
        with open(path) as f:
            counts = [rec["moe_full_buffer_layers"]
                      for rec in map(json.loads, f)
                      if "moe_full_buffer_layers" in rec]
    except (OSError, ValueError):
        return None
    return (sum(c > 0 for c in counts), len(counts)) if counts else None


def read(run):
    cfg, rows = run.cfg, run.outcome.data.get("rows")
    if rows is None or not {"block_length", "num_experts",
                            "moe_intermediate_size"} <= set(cfg):
        return None
    layers = int(cfg["num_hidden_layers"])
    counted = hybrid_costs.counted_assignments(run)
    if counted is None:
        a_layer = costs.expected_assignments(
            cfg, rows * 2 * int(cfg["max_seq_len"]))
        source = "expected from an even router"
    else:
        a_layer, source = counted / layers, "the program's counter"
    full = full_buffer_steps(run)
    print(f"[bench] moe_gated_expert_roofline: {a_layer:.0f} assignments a "
          f"layer and step ({source})"
          + ("" if full is None else
             f"; {full[0]} of {full[1]} logged steps had an expert layer "
             "outside its usual buffer"), flush=True)
    step_costs = [costs.gated_grouped_cost(cfg, a_layer, backward=backward)
                  for backward in (False, True)] * layers
    return hybrid_costs.roofline_share(run, "moe_experts", step_costs,
                                       "gated grouped products")
