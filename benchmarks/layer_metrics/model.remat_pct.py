"""Share of the device's busy time in the traced window in operations
whose name stack holds JAX's ``rematted_computation``: what ``remat:
true`` computes a second time. It cuts across the layer shares."""

from benchmarks import scope_times


def read(run):
    return scope_times.remat_share(run)
