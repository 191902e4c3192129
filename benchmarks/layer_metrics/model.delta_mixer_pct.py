"""Share of the device's busy time in the traced window under
``delta_mixer``: everything inside the gated delta-rule mixers (the two
in-projections, the convolution, the norms of q and k, the chunked rule,
the gated norm, the out-projection), forward, recomputed and backward.
None where no operation carries the scope."""

from benchmarks import scope_times


def read(run):
    return scope_times.scope_share(run, "delta_mixer") or None
