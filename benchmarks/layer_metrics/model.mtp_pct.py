"""Share of the device's busy time in the traced window under ``mtp``:
everything inside the multi-token prediction module (its two norms, the
projection of the stack's state beside the next id's embedding, its
latent-attention layer and its expert layer, its last norm), forward,
recomputed and backward. Its reading of the head is ``model.mtp_loss_pct``.
None where no operation carries the scope."""

from benchmarks import scope_times


def read(run):
    return scope_times.scope_share(run, "mtp") or None
