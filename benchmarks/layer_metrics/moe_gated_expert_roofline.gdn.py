"""``moe_gated_expert_roofline`` for the linear-attention cell: roofline
share of the gated experts' three grouped products (gate, up, down) of
every expert layer of the trace's whole steps, forward and backward,
from the assignments the steps really made (the program's counter
``moe_assignments``, shared evenly between the expert layers; the
expected share of an even router where no such line can be reached: the
line printed says which) and the held experts' matrices read once a
pass (``gated_delta_costs.gated_grouped_cost``), over the device time
under the scope ``moe_experts`` inside those steps; the same line says
how many logged steps had an expert layer outside its usual buffer
(``moe_full_buffer_layers``). A metric of its own name because
``moe_gated_expert_roofline`` lists its cells and a test that is the
benchmark's holds that list to ``sdar_train``; the scope and the count
are the same, the costs this cell's. None for a configuration that is
not a causal stack of gated experts under this family's key names."""

from benchmarks.layer_metrics import gated_delta_costs as costs
from benchmarks.layer_metrics import hybrid_costs
from benchmarks.layer_metrics.moe_gated_expert_roofline import (
    full_buffer_steps,
)


def read(run):
    cfg, rows = run.cfg, run.outcome.data.get("rows")
    if rows is None or not cfg.get("gated_experts") \
            or "hybrid_override_pattern" not in cfg:
        return None
    layers = cfg["hybrid_override_pattern"].count("E")
    counted = hybrid_costs.counted_assignments(run)
    if counted is None:
        a_layer = costs.expected_assignments(
            cfg, rows * int(cfg["max_seq_len"]))
        source = "expected from an even router"
    else:
        a_layer, source = counted / layers, "the program's counter"
    full = full_buffer_steps(run)
    print(f"[bench] moe_gated_expert_roofline.gdn: {a_layer:.0f} assignments "
          f"a layer and step ({source})"
          + ("" if full is None else
             f"; {full[0]} of {full[1]} logged steps had an expert layer "
             "outside its usual buffer"), flush=True)
    step_costs = [costs.gated_grouped_cost(cfg, a_layer, backward=backward)
                  for backward in (False, True)] * layers
    return hybrid_costs.roofline_share(run, "moe_experts", step_costs,
                                       "gated grouped products")
