"""Share of the device's busy time in the traced window under
``bd_noise``: a block-diffusion step's own noising of its rows (the
masking rates, the masks, the noised copy, the loss weights), forward,
recomputed and backward. None where no operation carries the scope."""

from benchmarks import scope_times


def read(run):
    return scope_times.scope_share(run, "bd_noise") or None
