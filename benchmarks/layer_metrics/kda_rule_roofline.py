"""Roofline share of the chunked delta rule with a vector decay (Kimi
Delta Attention) in the traced window: the least time the chip could
take for the rule's work of every ``K`` layer of the trace's whole steps
(``kda_costs.rule_cost`` from the configuration's shapes: the products
inside and between chunks of 64, forward and backward; q, k, v, the
(B, S, H, D) float32 g and beta read and o written once each pass;
``hybrid_costs.whole_steps`` counts the steps from the device trace
itself), over the device time under the scope ``kda_rule`` inside those
steps. The recomputed forward is in the time and not in the work, as in
``train.mfu_pct``, and so are the products that make the chunk's
triangular inverse and the decays multiplied in before a product.
Device time over a count from shapes, read over the **scope**: whatever
implements the rule, einsums now or a kernel later, is judged on the
same work. None for a configuration without such layers, and where no
operation carries the scope (the parent of the PR that added it)."""

from benchmarks.layer_metrics import hybrid_costs, kda_costs


def read(run):
    cfg, rows = run.cfg, run.outcome.data.get("rows")
    if rows is None or "kda_num_heads" not in cfg:
        return None
    layers = cfg["hybrid_override_pattern"].count("K")
    costs = [kda_costs.rule_cost(cfg, rows, int(cfg["max_seq_len"]),
                                 backward=backward)
             for backward in (False, True)] * layers
    return hybrid_costs.roofline_share(run, "kda_rule", costs,
                                       "KDA rule")
