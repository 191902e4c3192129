"""Share of the device's busy time in the traced window under
``exit_loss``: the head projection of every pass, its cross-entropy,
the exit distribution's mixing and the entropy term, forward and
backward. None where no operation carries the scope."""

from benchmarks import scope_times


def read(run):
    return scope_times.scope_share(run, "exit_loss") or None
