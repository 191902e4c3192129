"""Roofline share of the chunked gated delta rule in the traced window:
the least time the chip could take for the rule's work of every linear
layer of the trace's whole steps (``gated_delta_costs.rule_cost`` from
the configuration's shapes: the products inside and between chunks of
64, forward and backward; q, k, v, g, beta read and o written once each
pass; ``hybrid_costs.whole_steps`` counts the steps from the device
trace itself), over the device time under the scope ``delta_rule``
inside those steps. The recomputed forward is in the time and not in
the work, as in ``train.mfu_pct``, and so are the products that make
the chunk's triangular inverse. Device time over a count from shapes,
read over the **scope**: whatever implements the rule, einsums or a
kernel, is judged alike; a kernel named ``delta_rule...``, if one ships,
is also printed by name and shape. None for a configuration without such
layers, and where no operation carries the scope."""

from benchmarks import trace_reduce
from benchmarks.layer_metrics import gated_delta_costs, hybrid_costs


def kernels_under(run, kernel: str = "delta_rule") -> dict:
    """{(kernel's name, operand shapes): [calls, seconds]} of the custom
    calls whose instruction name holds ``kernel`` (as the attention
    kernels' readers find theirs); empty where there is none (the einsum
    form) or no trace."""
    out = {}
    for events in getattr(run.trace, "events", {}).values():
        for e in events:
            if kernel not in e.name:
                continue
            name, opcode, _, operands = trace_reduce.instruction(e.name)
            if opcode != "custom-call" or kernel not in name:
                continue
            key = (name.split(".")[0],
                   tuple("x".join(map(str, dims)) for _, dims in operands))
            row = out.setdefault(key, [0, 0.0])
            row[0] += 1
            row[1] += e.duration_ns / 1e9
    return out


def read(run):
    cfg, rows = run.cfg, run.outcome.data.get("rows")
    if rows is None or "linear_num_value_heads" not in cfg:
        return None
    layers = cfg["hybrid_override_pattern"].count("L")
    costs = [gated_delta_costs.rule_cost(cfg, rows, int(cfg["max_seq_len"]),
                                         backward=backward)
             for backward in (False, True)] * layers
    share = hybrid_costs.roofline_share(run, "delta_rule", costs,
                                        "gated delta rule")
    if share is not None:
        for (name, shapes), (calls, seconds) in sorted(
                kernels_under(run).items(), key=lambda kv: -kv[1][1]):
            print(f"[bench] delta_rule kernel {name} {' '.join(shapes)}: "
                  f"{calls} calls, {seconds:.4f} s on the device",
                  flush=True)
    return share
