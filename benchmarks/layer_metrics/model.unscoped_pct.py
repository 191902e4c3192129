"""Share of the device's busy time in the traced window in operations
under no scope of the program's vocabulary: over a few percent, the
scopes do not cover the step."""

from benchmarks import scope_times


def read(run):
    return scope_times.class_share(run, "unscoped")
