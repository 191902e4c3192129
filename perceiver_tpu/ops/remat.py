"""What crosses the recomputation boundary of a ``remat`` layer.

``remat: true`` means "do not hold a layer's activations": the backward
pass recomputes one layer at a time from that layer's input. It does
not mean "hold nothing": values that are dear to compute and cheap to
hold are named where they are made (``dear``) and cross the boundary by
name; the backward recomputes what costs a pass over memory (norms,
GELU, casts, slices, residual sums) and the one product whose saved
copy would cost as much (the out-projection's). No arithmetic changes:
a named value is the value, saved instead of computed again.

Three stacks honour the names, each in the way its backward is made:

- ``models/perceiver.PerceiverEncoder``: each attention layer (cross or
  self, with its MLP) is a ``jax.checkpoint`` of its own under
  autodiff, and the kept names are its save list
  (``save_only_these_names``).
- ``models/looped_lm.LoopedLM``: the backward over its passes is
  written by hand (one gradient accumulator, added into by slice), so
  the values go by hand too: the forward takes the kept names' values
  out of a layer application (``taking``) and stacks them beside its
  input, the backward builds the application's vjp with them handed
  back in (``vjp_handing``).

- ``models/hybrid_lm.HybridLM``: its layers differ and are unrolled,
  each a ``jax.checkpoint`` under autodiff whose save list is the kept
  names of its own, longer list (``HYBRID_REMAT_NAMES``: the scan's
  output and the in-projection's product of a state-space layer, the
  routing plan of an expert layer).

Which names those are is one reckoning for all (``choose_keeps``).

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
- ``mlp_hidden``: ``fc1``'s product, before the GELU; in the gated MLP
  the gate's and the up-projection's products, before the SiLU
  (``ops/mlp``).

Not on the list, because holding them bought no time on the chip: the
latent after the attention's residual add (the out-projection's product
is redone in the time its saved copy takes to go through the block
scan's stack and back), and the layer's output, which is the next
layer's input and held by the boundary anyway (``layer_in`` in the
tally's line).

Which names are kept is chosen, not configured (``pick_remat_keeps``):
the longest prefix of the list whose bytes, reckoned over all layer
applications from the shapes one traced layer reports (``named_bytes``),
fit beside the layers' inputs in ``KEEP_SHARE`` of what the device has
left: the memory the backend reports less the bytes it reports in use
while the step is traced, which under ``Trainer._load_step`` are the
built state (parameters and optimizer moments) and the waiting batches.
A device that reports no memory (the CPU) keeps the whole list.
"""

from __future__ import annotations

import collections
import contextlib
from typing import Iterator, Mapping, Optional, Sequence, Tuple

import jax
from jax.ad_checkpoint import checkpoint_name

#: the dear values, dearest per byte first
REMAT_NAMES = ("attn_out", "qkv", "mlp_hidden")

#: the list a stack with state-space and expert layers chooses from
#: (``models/hybrid_lm.py``): the three above, then what its own mixers
#: name. ``ssm_out`` is the selective scan's output (``ops/ssm.py``: the
#: fused scan's hand-written backward takes the scan's operands alone,
#: and the einsum form is a checkpoint of its own, so with the output
#: held the recomputed layer runs neither a second time), ``ssm_in`` the
#: in-projection's product; ``delta_out`` and ``delta_in`` are the same
#: two of a gated delta-rule mixer (``ops/delta_rule.py``: the chunked
#: rule's output, (B, S, value heads x their width) in the compute
#: dtype: the kernels' hand-written backward takes the rule's operands
#: alone, and the einsum form is a checkpoint of its own, so with the
#: output held the recomputed layer runs neither a second time; and the
#: q/k/v/z in-projection's product before it is sliced). The
#: shared expert's hidden layer is an
#: ``mlp_hidden``. The routed experts' hidden layer has no name: it
#: lies inside a ``lax.cond`` over the sorted buffer's size
#: (``ops/moe.py``), and a value that crosses one is held at the size
#: of the larger branch, all that top-k allows; the routed part
#: recomputes it under a checkpoint of its own. ``moe_plan`` is an
#: expert layer's routing plan (``ops/moe.routing_plan``: the chosen
#: experts, the sorted order, the way back by token and the loads, all
#: integers, 16 bytes an assignment): with it held the recomputed layer
#: runs no ``top_k`` and no sort, only the router's product and score,
#: which the weights' gradient needs.
HYBRID_REMAT_NAMES = REMAT_NAMES + ("ssm_out", "ssm_in", "delta_out",
                                    "delta_in", "moe_plan")

#: The share of what the device has left that the kept values and the
#: layers' inputs may take together. From chip runs (PERF.md, Findings,
#: PR 29): ``lm_train`` (24 rows of 1024 x 512 latents, 39 layers)
#: reckons 6.75 GB of a v5e's 16.91 beside 0.94 GB of state (its budget
#: 9.58) and peaks at 9.7, the rest being state, loss and one layer's
#: recomputation; twice its rows keep ``attn_out`` alone, four times
#: its rows nothing. PR 32: ``ouro_train`` (7.35 GB of state) reckons
#: 3.23 GB with ``attn_out`` against a budget of 5.74; ``qkv`` would
#: make 6.45.
KEEP_SHARE = 0.6

# Bytes the names report while a layer is traced for its shapes, by
# name; the innermost recorder of a trace counts (``reckoning``).
_RECORDERS = []

# What a hand-written backward does with a named value where it is
# made, ``(x, name) -> x``; the innermost exchange of a trace acts
# (``taking``, ``vjp_handing``).
_EXCHANGES = []


def dear(x, name: str):
    """``x``, named: under a ``remat`` layer's checkpoint it is saved if
    ``name`` is kept and recomputed if not; under ``taking`` it is taken
    out, under ``vjp_handing`` the kept value stands in its place;
    anywhere else an identity (no operation is lowered for it)."""
    if name not in HYBRID_REMAT_NAMES:
        raise ValueError(f"{name!r} is not one of {HYBRID_REMAT_NAMES}")
    if _RECORDERS:
        _RECORDERS[-1][name] += x.size * x.dtype.itemsize
    x = checkpoint_name(x, name)
    return _EXCHANGES[-1](x, name) if _EXCHANGES else x


@contextlib.contextmanager
def reckoning() -> Iterator[collections.Counter]:
    """Bytes by name of the values named while the block traces."""
    held = collections.Counter()
    _RECORDERS.append(held)
    try:
        yield held
    finally:
        _RECORDERS.remove(held)


def layer_shapes(stacked):
    """The shapes of one layer's parameters, cut from their stack (the
    leading axis a scan runs over): what ``named_bytes`` takes."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), stacked)


def named_bytes(layer, *args) -> collections.Counter:
    """Bytes by name of what one application of ``layer`` names:
    differentiated once for its shapes alone, because a custom VJP
    names values in its forward rule. ``args`` may be shapes."""
    with reckoning() as held:
        jax.eval_shape(lambda *a: jax.vjp(layer, *a)[0], *args)
    return held


# --- the values by hand, for a backward that is written by hand --------------


@contextlib.contextmanager
def _exchanging(exchange):
    _EXCHANGES.append(exchange)
    try:
        yield
    finally:
        _EXCHANGES.remove(exchange)


def taking(names: Sequence[str], layer, *args):
    """``(layer(*args), values)``: the application's output and the
    values it names with a name of ``names``, by name in the order they
    are made. With a name to take, the application is run as the
    forward half of its vjp, so that a custom VJP runs the forward rule
    in which it names what it hands its backward (the fused core's
    float32 output and log-sum-exp: one run of the kernel, in the form
    that hands them out); the linear half has no reader and is dropped
    as dead code. With none it is ``layer(*args)`` and nothing else."""
    if not names:
        return layer(*args), {}

    def tapped(*a):
        taken = {name: [] for name in names}

        def take(x, name):
            if name in taken:
                taken[name].append(x)
            return x

        with _exchanging(take):
            return layer(*a), taken

    out, _, taken = jax.vjp(tapped, *args, has_aux=True)
    return out, taken


@jax.custom_vjp
def _in_place_of(x, kept):
    """``kept``, which is ``x`` as an earlier run made it: nothing that
    only ``x``'s value needed runs again; the gradient is ``x``'s."""
    del x
    return kept


_in_place_of.defvjp(lambda x, kept: (kept, None),
                    lambda _, g: (g, None))


def vjp_handing(values: Mapping[str, Sequence], layer, *args):
    """The vjp function of ``layer`` at ``args`` with ``values`` (what
    ``taking`` took from the same application) standing in place of
    the values the recomputed forward would name: what made them is
    not run again. Under ``jax.checkpoint``, as a ``remat`` layer under
    autodiff is: the forward it recomputes carries
    ``rematted_computation`` in its name stack, the transposed
    operations do not; the primal pass of this vjp has no reader and
    is dropped as dead code."""
    left = {name: list(kept) for name, kept in values.items()}

    def hand(x, name):
        if name not in left:
            return x
        kept = left[name].pop(0)
        if (kept.shape, kept.dtype) != (x.shape, x.dtype):
            raise ValueError(
                f"{name}: kept {kept.dtype}{list(kept.shape)} for a value "
                f"{x.dtype}{list(x.shape)}: not the same application")
        return _in_place_of(x, kept)

    # the exchange is open while the vjp is built, not while ``layer``
    # is first traced alone: a custom VJP's forward rule is traced when
    # the checkpointed layer is differentiated. A function of its own
    # every time: ``jax.checkpoint`` remembers a traced function, and
    # this one closes over ``values``.
    with _exchanging(hand):
        _, vjp = jax.vjp(jax.checkpoint(lambda *a: layer(*a)), *args)
    if any(left.values()):
        raise ValueError(
            f"kept values the application did not name again: "
            f"{ {n: len(v) for n, v in left.items() if v} }")
    return vjp


# --- which names are kept ----------------------------------------------------


def pick_remat_keeps(bytes_by_name: Mapping[str, int], *,
                     layer_in_bytes: int, memory_limit: Optional[int],
                     memory_held: int = 0,
                     names: Tuple[str, ...] = REMAT_NAMES
                     ) -> Tuple[Tuple[str, ...], Optional[str]]:
    """``(kept, why_not_all)``: the longest prefix of ``names`` (the
    list the stack chooses from) whose bytes fit, beside the layers'
    inputs, in ``KEEP_SHARE`` of what ``memory_held`` leaves of
    ``memory_limit``; the reason names the first one dropped. All bytes
    are one device's, over all layer applications. ``memory_limit``
    None (a backend that reports no limit) keeps every name."""
    if memory_limit is None:
        return names, None
    budget = KEEP_SHARE * (memory_limit - memory_held)
    total = layer_in_bytes
    for i, name in enumerate(names):
        total += bytes_by_name.get(name, 0)
        if total > budget:
            why = (f"{name} would make {total / 1e9:.2f} GB of "
                   f"{budget / 1e9:.2f}")
            if memory_held:
                why += (f", {KEEP_SHARE:g} of what {memory_held / 1e9:.2f} "
                        "GB in use leave")
            return names[:i], why
    return names, None


def _memory_limit() -> Optional[int]:
    """The first local device's memory as the backend reports it (a
    seam: tests give a CPU trace a chip's memory)."""
    stats = jax.local_devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def _memory_held() -> int:
    """The bytes the first local device reports in use now, while the
    step is traced: what the step will find there (under
    ``Trainer._load_step`` the built state and the waiting batches). A
    seam as above."""
    stats = jax.local_devices()[0].memory_stats()
    return stats.get("bytes_in_use", 0) if stats else 0


# Trace-time tally of what the ``remat`` stacks traced inside the block
# chose, in the style of ``ops.attention.attention_paths``.
_KEEP_TALLIES = []


@contextlib.contextmanager
def remat_keeps() -> Iterator[list]:
    """The choices made inside the block, one dict a stack traced (an
    encoder, a looped decoder stack): ``kept``, ``dropped``, ``why``,
    ``bytes`` (by name, ``layer_in`` among them), ``memory_limit`` and
    ``memory_held``."""
    choices = []
    _KEEP_TALLIES.append(choices)
    try:
        yield choices
    finally:
        _KEEP_TALLIES.remove(choices)


def choose_keeps(bytes_by_name: Mapping[str, int], layer_in_bytes: int,
                 names: Tuple[str, ...] = REMAT_NAMES) -> Tuple[str, ...]:
    """``pick_remat_keeps`` against this process's device, tallied."""
    limit, held = _memory_limit(), _memory_held()
    kept, why = pick_remat_keeps(bytes_by_name,
                                 layer_in_bytes=layer_in_bytes,
                                 memory_limit=limit, memory_held=held,
                                 names=names)
    choice = {
        "kept": kept,
        "dropped": tuple(n for n in names if n not in kept),
        "why": why,
        "bytes": {"layer_in": layer_in_bytes,
                  **{n: bytes_by_name.get(n, 0) for n in names}},
        "memory_limit": limit,
        "memory_held": held,
    }
    for choices in _KEEP_TALLIES:
        choices.append(choice)
    return kept


def format_remat_keeps(choices) -> str:
    """``attn_out,qkv,mlp_hidden + layer_in 6.75 GB of 16.91`` — one
    log line's worth; ``none traced`` where no stack with ``remat``
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
