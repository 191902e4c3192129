"""Share of the device's busy time in the traced window under
``mtp_loss``, inside ``loss``: the prediction module's reading of the
stack's head and its cross-entropy, forward and backward: what the
second reading costs beside ``model.loss_pct``, which holds both. None
where no operation carries the scope."""

from benchmarks import scope_times


def read(run):
    return scope_times.scope_share(run, "mtp_loss") or None
