"""Share of the device's busy time in the traced window under
``kda_rule``, inside ``kda_mixer``: the chunked delta rule with a
vector decay alone (the decays, the solve inside a chunk, the chunk
products, the carried state), forward, recomputed and backward. None
where no operation carries the scope."""

from benchmarks import scope_times


def read(run):
    return scope_times.scope_share(run, "kda_rule") or None
