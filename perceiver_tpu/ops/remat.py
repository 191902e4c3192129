"""What crosses the recomputation boundary of a ``remat`` layer.

``remat: true`` means "do not hold a layer's activations": each
attention layer (cross or self, with its MLP) is a ``jax.checkpoint``
of its own (``models/perceiver.PerceiverEncoder``), so the backward
pass recomputes one layer at a time from that layer's input. It does
not mean "hold nothing": values that are dear to compute and cheap to
hold are named where they are made (``dear``) and cross the boundary by
name; the backward recomputes what costs a pass over memory (norms,
GELU, casts, slices, residual sums) and the one product whose saved
copy would cost as much (the out-projection's). No arithmetic changes:
a named value is the value, saved instead of computed again.

``REMAT_NAMES`` is the list in order of time bought per byte held
(chip runs, PERF.md, PR 29: 3.0, 2.1 and 1.0 ms a step and GB in
``lm_train``):

- ``attn_out``: the fused core's float32 output and its log-sum-exp
  row (``ops/pallas_attention._flash_fwd``): saved, the forward kernel
  is not run again. The bf16 output that feeds the out-projection is a
  cast of it and is recomputed. The materialised core makes no such
  value: its backward rebuilds the softmax by design.
- ``qkv``: the projections' product (``ops/attention._project``): the
  packed (B, L, 3E) buffer before it is sliced, the lone q projection
  where the keys and values are hoisted out of the layer.
- ``mlp_hidden``: ``fc1``'s product, before the GELU (``ops/mlp``).

Not on the list, because holding them bought no time on the chip: the
latent after the attention's residual add (the out-projection's product
is redone in the time its saved copy takes to go through the block
scan's stack and back), and the layer's output, which is the next
layer's input and held by the boundary anyway (``layer_in`` in the
tally's line).

Which names are kept is chosen, not configured (``pick_remat_keeps``):
the longest prefix of the list whose bytes, reckoned over all layer
applications from the shapes one traced layer reports, fit beside the
layers' inputs in ``KEEP_SHARE`` of the device's memory as the backend
reports it. A device that reports none (the CPU) keeps the whole list.
"""

from __future__ import annotations

import collections
import contextlib
from typing import Iterator, Mapping, Optional, Tuple

import jax
from jax.ad_checkpoint import checkpoint_name

#: the dear values, dearest per byte first
REMAT_NAMES = ("attn_out", "qkv", "mlp_hidden")

#: The share of the device's memory the kept values and the layers'
#: inputs may take together. From chip runs (PERF.md, Findings, PR 29):
#: ``lm_train`` (24 rows of 1024 x 512 latents, 39 layers) reckons
#: 6.75 GB of a v5e's 16.91 (its budget 10.15) and peaks at 9.7, the
#: 3 GB on top being state, loss and one layer's recomputation; twice
#: its rows keep ``attn_out`` alone, four times its rows nothing.
KEEP_SHARE = 0.6

# Bytes the names report while a layer is traced for its shapes, by
# name; the innermost recorder of a trace counts (``reckoning``).
_RECORDERS = []


def dear(x, name: str):
    """``x``, named: under a ``remat`` layer's checkpoint it is saved if
    ``name`` is kept and recomputed if not; anywhere else an identity
    (no operation is lowered for it)."""
    if name not in REMAT_NAMES:
        raise ValueError(f"{name!r} is not one of {REMAT_NAMES}")
    if _RECORDERS:
        _RECORDERS[-1][name] += x.size * x.dtype.itemsize
    return checkpoint_name(x, name)


@contextlib.contextmanager
def reckoning() -> Iterator[collections.Counter]:
    """Bytes by name of the values named while the block traces."""
    held = collections.Counter()
    _RECORDERS.append(held)
    try:
        yield held
    finally:
        _RECORDERS.remove(held)


def pick_remat_keeps(bytes_by_name: Mapping[str, int], *,
                     layer_in_bytes: int, memory_limit: Optional[int]
                     ) -> Tuple[Tuple[str, ...], Optional[str]]:
    """``(kept, why_not_all)``: the longest prefix of ``REMAT_NAMES``
    whose bytes fit, beside the layers' inputs, in ``KEEP_SHARE`` of
    ``memory_limit``; the reason names the first one dropped. All bytes
    are one device's, over all layer applications. ``memory_limit``
    None (a backend that reports no limit) keeps every name."""
    if memory_limit is None:
        return REMAT_NAMES, None
    budget = KEEP_SHARE * memory_limit
    total = layer_in_bytes
    for i, name in enumerate(REMAT_NAMES):
        total += bytes_by_name.get(name, 0)
        if total > budget:
            return REMAT_NAMES[:i], (
                f"{name} would make {total / 1e9:.2f} GB of "
                f"{budget / 1e9:.2f}")
    return REMAT_NAMES, None


def _memory_limit() -> Optional[int]:
    """The first local device's memory as the backend reports it (a
    seam: tests give a CPU trace a chip's memory)."""
    stats = jax.local_devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


# Trace-time tally of what the ``remat`` encoders traced inside the
# block chose, in the style of ``ops.attention.attention_paths``.
_KEEP_TALLIES = []


@contextlib.contextmanager
def remat_keeps() -> Iterator[list]:
    """The choices made inside the block, one dict an encoder traced:
    ``kept``, ``dropped``, ``why``, ``bytes`` (by name, ``layer_in``
    among them) and ``memory_limit``."""
    choices = []
    _KEEP_TALLIES.append(choices)
    try:
        yield choices
    finally:
        _KEEP_TALLIES.remove(choices)


def choose_keeps(bytes_by_name: Mapping[str, int], layer_in_bytes: int
                 ) -> Tuple[str, ...]:
    """``pick_remat_keeps`` against this process's device, tallied."""
    limit = _memory_limit()
    kept, why = pick_remat_keeps(bytes_by_name,
                                 layer_in_bytes=layer_in_bytes,
                                 memory_limit=limit)
    choice = {
        "kept": kept,
        "dropped": tuple(n for n in REMAT_NAMES if n not in kept),
        "why": why,
        "bytes": {"layer_in": layer_in_bytes,
                  **{n: bytes_by_name.get(n, 0) for n in REMAT_NAMES}},
        "memory_limit": limit,
    }
    for choices in _KEEP_TALLIES:
        choices.append(choice)
    return kept


def format_remat_keeps(choices) -> str:
    """``attn_out,qkv,mlp_hidden + layer_in 6.75 GB of 16.91`` — one
    log line's worth; ``none traced`` where no encoder with ``remat``
    was."""
    parts = []
    for c in choices:
        held = c["bytes"]["layer_in"] + sum(c["bytes"][n] for n in c["kept"])
        of = ("no memory report" if c["memory_limit"] is None
              else f"{c['memory_limit'] / 1e9:.2f}")
        part = (f"{','.join(c['kept']) or 'nothing'} + layer_in "
                f"{held / 1e9:.2f} GB of {of}")
        if c["dropped"]:
            part += f" (dropped {','.join(c['dropped'])}: {c['why']})"
        parts.append(part)
    return "; ".join(parts) or "none traced"
