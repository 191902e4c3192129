"""Roofline share of the grouped products over the held experts in the
traced window: the least time the chip could take for the two products
of every expert layer of the trace's whole steps
(``hybrid_costs.whole_steps``: counted from the device trace itself),
forward and backward, from the assignments the steps really made (the
program's counter ``moe_assignments`` in the trainer's telemetry,
averaged over the steps the trainer began inside the traced window and
shared evenly between the expert layers; the expected share of an even
router where no such line can be reached: the line printed says which)
and the held experts' matrices read once a pass
(``hybrid_costs.grouped_cost``), over the device time under the scope
``moe_experts`` inside those steps."""

from benchmarks.layer_metrics import hybrid_costs


def read(run):
    cfg, rows = run.cfg, run.outcome.data.get("rows")
    if rows is None or "moe_intermediate_size" not in cfg:
        return None
    layers = cfg["hybrid_override_pattern"].count("E")
    counted = hybrid_costs.counted_assignments(run)
    if counted is None:
        a_layer = hybrid_costs.expected_assignments(
            cfg, rows * int(cfg["max_seq_len"]))
        source = "expected from an even router"
    else:
        a_layer, source = counted / layers, "the program's counter"
    print(f"[bench] moe_expert_roofline: {a_layer:.0f} assignments a layer "
          f"and step ({source})", flush=True)
    costs = [hybrid_costs.grouped_cost(cfg, a_layer, backward=backward)
             for backward in (False, True)] * layers
    return hybrid_costs.roofline_share(run, "moe_experts", costs,
                                       "grouped products")
