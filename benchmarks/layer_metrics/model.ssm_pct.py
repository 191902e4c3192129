"""Share of the device's busy time in the traced window under
``ssm_mixer``: everything inside the Mamba-2 mixers (projections,
convolution, the chunked scan, the gated norm), forward, recomputed and
backward. None where no operation carries the scope."""

from benchmarks import scope_times


def read(run):
    return scope_times.scope_share(run, "ssm_mixer") or None
