"""Share of the device's busy time in the traced window in the layers
outside their attention cores: ``attn_proj``, ``mlp``, the input
adapter, and the norms and residuals directly under a layer's scope."""

from benchmarks import scope_times


def read(run):
    return scope_times.class_share(run, "dense")
