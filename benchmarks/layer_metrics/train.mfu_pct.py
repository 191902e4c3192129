"""Model FLOP/s utilization of the window: the operations forward and
backward need for the steps taken (``benchmarks/flops.py``, from shapes,
recomputation not counted), over the window's seconds, over the chips'
published bf16 peak."""

from benchmarks import flops


def read(run):
    data = run.outcome.data
    if "steps" not in data or not run.peak:
        return None
    needed = data["steps"] * flops.train_step_flops(
        run.cfg, data["rows"], run.task.flop_shape(run.cfg))
    peak = run.peak["bf16_flops_per_s"] * run.cell.chips
    return 100.0 * needed / data["elapsed_s"] / peak
