"""Roofline share of the latent-attention (MLA) layers' causal core in
the traced window: the least time the chip could take for the core's
work **at the published widths** (``kda_costs.latent_core_cost``: ``S
(S + 1) / 2`` pairs a head, score heads of 192 beside value heads of
128, forward and backward; q, k, v, o once each at those widths) of
every ``A`` layer of the trace's whole steps
(``hybrid_costs.whole_steps``), over the device time under the scope
``attn_core`` inside those steps: in a stack whose only attention
layers are latent ones, that scope is theirs. A kernel that takes one
width and runs heads padded to 256 lanes, or a core that takes two, is
judged on the same work: the padding is in the time and not in the
work, as a recomputed forward is. None for a configuration without such
layers or with another attention beside them, and where no operation
carries the scope."""

from benchmarks.layer_metrics import hybrid_costs, kda_costs


def read(run):
    cfg, rows = run.cfg, run.outcome.data.get("rows")
    pattern = cfg.get("hybrid_override_pattern", "")
    if rows is None or "kv_lora_rank" not in cfg or "*" in pattern:
        return None
    costs = [kda_costs.latent_core_cost(cfg, rows, int(cfg["max_seq_len"]),
                                        backward=backward)
             for backward in (False, True)] * pattern.count("A")
    return hybrid_costs.roofline_share(run, "attn_core", costs,
                                       "latent attention core")
