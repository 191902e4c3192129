"""Share of the device's busy time in the traced window under
``loop_stack``: everything inside the passes of a weight-shared decoder
stack (layers, norms, residuals, the attention kernels), forward,
recomputed and backward. None where no operation carries the scope."""

from benchmarks import scope_times


def read(run):
    return scope_times.scope_share(run, "loop_stack") or None
