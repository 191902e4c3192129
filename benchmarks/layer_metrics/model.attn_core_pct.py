"""Share of the device's busy time in the traced window under the scope
``attn_core`` (scores, softmax, probabilities x values), in any layer
and any pass: forward, recomputed and the custom backward."""

from benchmarks import scope_times


def read(run):
    return scope_times.class_share(run, "attn_core")
