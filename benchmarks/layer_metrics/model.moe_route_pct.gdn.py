"""``model.moe_route_pct`` for the linear-attention cell: share of the
device's busy time in the traced window under ``moe_route``: the router,
top-k, the sort of the assignments, the gathers into and out of sorted
order and the weighted combine: the expert layers' time that is not a
matrix product over the experts. A metric of its own name for the reason
``model.moe_pct.gdn`` gives. None where no operation carries the scope."""

from benchmarks import scope_times


def read(run):
    return scope_times.scope_share(run, "moe_route") or None
