"""``model.moe_pct`` for the linear-attention cell: share of the device's
busy time in the traced window under ``moe``, everything inside the
expert layers (the softmax router, sort, gathers, the gated experts'
three grouped products, the gated shared expert), forward, recomputed
and backward. A metric of its own name because ``model.moe_pct`` lists
its cells and a test that is the benchmark's holds that list to
``nemotron_train`` (as ``model.moe_pct.bd``); the reading is the same.
None where no operation carries the scope."""

from benchmarks import scope_times


def read(run):
    return scope_times.scope_share(run, "moe") or None
