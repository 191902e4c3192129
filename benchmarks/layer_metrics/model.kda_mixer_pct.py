"""Share of the device's busy time in the traced window under
``kda_mixer``: everything inside the Kimi Delta Attention mixers (the
q/k/v projection and convolution, the norms of q and k, the low-rank
decay and gate projections, the chunked rule, the gated norm, the
out-projection), forward, recomputed and backward. None where no
operation carries the scope."""

from benchmarks import scope_times


def read(run):
    return scope_times.scope_share(run, "kda_mixer") or None
