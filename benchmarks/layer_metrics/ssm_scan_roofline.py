"""Roofline share of the chunked selective scan in the traced window:
the least time the chip could take for the scan's work of every Mamba
layer of the trace's whole steps (``hybrid_costs.scan_cost`` from the
configuration's shapes: the products inside and between chunks, forward
and backward; x, B, C, dt read and y written once each pass;
``hybrid_costs.whole_steps`` counts the steps from the device trace
itself), over the device time under the scope ``ssm_scan`` inside those
steps. The recomputed forward is in
the time and not in the work, as in ``train.mfu_pct``. Device time over
a count from shapes: whatever implements the scan is judged alike."""

from benchmarks.layer_metrics import hybrid_costs


def read(run):
    cfg, rows = run.cfg, run.outcome.data.get("rows")
    if rows is None or "mamba_num_heads" not in cfg:
        return None
    layers = cfg["hybrid_override_pattern"].count("M")
    costs = [hybrid_costs.scan_cost(cfg, rows, int(cfg["max_seq_len"]),
                                    backward=backward)
             for backward in (False, True)] * layers
    return hybrid_costs.roofline_share(run, "ssm_scan", costs,
                                       "selective scan")
