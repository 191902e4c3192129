"""Share of the device's busy time in the traced window under ``moe``:
everything inside the expert layers (router, sort, gathers, the grouped
products, the shared expert), forward, recomputed and backward. None
where no operation carries the scope."""

from benchmarks import scope_times


def read(run):
    return scope_times.scope_share(run, "moe") or None
