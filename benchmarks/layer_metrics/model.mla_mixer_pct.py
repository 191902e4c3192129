"""Share of the device's busy time in the traced window under
``mla_mixer``: everything inside the latent-attention layers (the query
projection, the latent and its norm, the keys and values expanded from
it, the causal core, the out-projection), forward, recomputed and
backward. None where no operation carries the scope."""

from benchmarks import scope_times


def read(run):
    return scope_times.scope_share(run, "mla_mixer") or None
