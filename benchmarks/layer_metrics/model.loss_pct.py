"""Share of the device's busy time in the traced window under ``loss``
or ``output_adapter``: the vocabulary or class projection and the
cross-entropy, forward and backward."""

from benchmarks import scope_times


def read(run):
    return scope_times.class_share(run, "loss")
