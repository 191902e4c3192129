"""Routed-expert layer (the layer ``E`` of ``models/hybrid_lm.py``) as
pure init/apply functions: a router over **all** ``num_experts``
experts, the top ``top_k`` a token, and the experts this layer holds.
The router, the experts and the shared expert are the configuration's::

    s = score(a W_r)                            (float32, all the experts)
    chosen = top_k(s)
    w_i = s_i / (sum_chosen s + 1e-20) * scaling      (s_i * scaling where
                                                       not renormalised)
    out = sum_chosen w_i f_i(a) [+ f_shared(a)]

* ``score``: ``sigmoid`` (``nemotron_h``: with a scaling factor; its
  published layer adds a buffer, ``e_score_correction_bias``, to the
  scores it chooses by, and moves it by a balancing rule its
  ``config.json`` does not give: here it is 0 and not read) or
  ``softmax`` over all the experts (Qwen3-MoE, SDAR: the top-k
  renormalised, ``norm_topk_prob``, no scaling). The ``1e-20`` guards a
  sum of sigmoids; beside a softmax's top-k sum it is nothing in
  float32.
* ``f``: ``relu(a W_up)^2 W_down``, two matrices (a tree with ``up``
  and ``down``), or gated, ``(silu(a W_gate) * (a W_up)) W_down``, three
  (a tree with ``gate`` too).
* a shared expert (a tree with ``shared``: relu-squared, every token)
  or none.

**The layer is told which experts it holds**: ``first_expert`` (an
int, or a scalar of the step) and the number of experts in its
parameter tree (a chip's share under expert parallelism). It routes
over all the experts, computes the part of the result that its own
experts give for the tokens routed to them, adds the shared expert
where there is one, and leaves out what the absent experts would have
added. Nothing stands in for the absent chips or their exchange.

**No token is dropped.** The ``T x top_k`` assignments are sorted by
held expert, the assignments to absent experts last, and the first rows
of that order, as many as the held experts were sent, are computed:
whatever the imbalance, every (token, held expert) pair. The products
over the held experts are grouped (``grouped_product``): each expert
multiplies the rows routed to it and no others.

The buffer the sorted rows are gathered into has a static size, and
**the usual size follows the share** (``usual_rows``). A token chooses
an expert at most once, so an expert is sent at most ``T`` assignments,
a row a token, and an untrained router sends about that to each of the
few experts it favours (0.5 to 1.7 times the even load by the batch
for a share of an untrained softmax router; PERF.md, PR 37). An even
router fills ``top_k x held / experts`` such experts' worth; the buffer
has room for twice that, in whole ones, and never less than one: ``T``
rows for 8 held of 128 at top 6 (2.7 times the even load), ``2 T`` for
16 held of 128 at top 8, where the even load is ``T`` itself. Where
a step's assignments do not fit (``lax.cond`` on their count) the
buffer is ``T x top_k`` rows, everything top-k allows: the same
function at another size, so that the usual step moves thousands of
rows and not a hundred thousand (gathering and weighting 98,304 rows on
every step cost 130 ms of 684; PERF.md, PR 33). The groups end at the
last held assignment, so **the products' work is the router's**: the
rows past it (zeros) belong to no group, no product reads them, the
kernel leaves what it returns there uninitialised, and they are masked
wherever a value leaves the buffer. A token's row goes to its
assignments by a gather and comes back by a scatter-add; autodiff
transposes each into the other.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perceiver_tpu.obs.trace import device_scope
from perceiver_tpu.ops.initializers import torch_linear_uniform
from perceiver_tpu.ops.linear import linear_init
from perceiver_tpu.ops.mlp import (
    gated_mlp_apply,
    relu2_mlp_apply,
    relu2_mlp_init,
)
from perceiver_tpu.ops.policy import DEFAULT_POLICY, Policy
from perceiver_tpu.ops.tally import Tally
from perceiver_tpu.ops.tiling import round_up

#: which form the grouped products of a layer took, with the rows of the
#: sorted buffer at each of its sizes (``megabloxx16384``,
#: ``megabloxx98304``; ``ragged_dot[cpu]x240``; the usual size says so
#: where the share and not the tokens set it,
#: ``megabloxx32768[2 x even share]``), and how many experts of how many
#: the layer holds (``held 8/128``)
moe_paths = Tally()
#: what a layer's router and experts are: ``softmax top 8 renormalised``,
#: ``gated silu x3 products`` or ``relu2 x2 products``, ``no shared
#: expert`` or ``shared expert``
moe_kinds = Tally()

SCORINGS = ("sigmoid", "softmax")

# rows of the sorted buffer a tile of the grouped kernel takes
_TILE_ROWS = 512


def moe_init(key, dim: int, *, num_experts: int, held_experts: int,
             expert_hidden: int, shared_hidden: int, gated: bool = False,
             dtype=jnp.float32):
    """The router over all ``num_experts``, the ``held_experts`` this
    layer holds (stacked on a leading axis; with a ``gate`` matrix each
    where ``gated``) and the shared expert (none at a ``shared_hidden``
    of 0)."""
    kr, ku, kd, ks = jax.random.split(key, 4)
    params = {
        "router": linear_init(kr, dim, num_experts, dtype, bias=False),
        "experts": {
            "up": {"w": torch_linear_uniform(
                ku, (held_experts, dim, expert_hidden), dim, dtype)},
            "down": {"w": torch_linear_uniform(
                kd, (held_experts, expert_hidden, dim), expert_hidden,
                dtype)},
        },
    }
    if gated:
        params["experts"]["gate"] = {"w": torch_linear_uniform(
            jax.random.fold_in(ku, 1), (held_experts, dim, expert_hidden),
            dim, dtype)}
    if shared_hidden:
        params["shared"] = relu2_mlp_init(ks, dim, shared_hidden, dtype)
    return params


# --- the grouped product -----------------------------------------------------


def _backend() -> str:
    """The backend the pick reads (a seam, as ``ops.attention``'s: a
    test that says ``tpu`` here gets the kernel, interpreted)."""
    return jax.default_backend()


def _tile(dim: int) -> int:
    """The multiple of 128 from 512 to 1024 that covers ``dim`` in
    whole tiles with the least left over (the larger of equals)."""
    return min(range(1024, 511, -128), key=lambda t: -dim % t)


def pick_grouped_product():
    """``(kernel, label)``: ``megablox`` (the Pallas kernel JAX ships: a
    grid over the row tiles the groups touch) on a TPU, else
    ``jax.lax.ragged_dot``, and the label says why."""
    backend = _backend()
    if backend == "tpu":
        return True, "megablox"
    return False, f"ragged_dot[{backend}]"


def grouped_product(params, x, group_sizes, *,
                    policy: Policy = DEFAULT_POLICY):
    """``x[rows of group g] @ w[g]`` for every group: ``x`` (M, K)
    sorted by group, ``params["w"]`` (G, K, N), ``group_sizes`` (G,)
    int32. Rows past ``group_sizes.sum()`` belong to no group: what
    comes back there is not defined and is never a number to read."""
    kernel, _ = pick_grouped_product()
    w = policy.cast_param(params["w"])
    x = policy.cast_compute(x)
    if not kernel:
        return jax.lax.ragged_dot(x, w, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    rows, pad = x.shape[0], -x.shape[0] % _TILE_ROWS
    if pad:   # the kernel takes whole row tiles
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = gmm(x, w, group_sizes, x.dtype,
              (_TILE_ROWS, _tile(w.shape[1]), _tile(w.shape[2])),
              None, None, False, jax.default_backend() != "tpu")
    return out[:rows]


# --- the layer ---------------------------------------------------------------


@device_scope("moe_route")
def route(params, a, *, top_k: int, scaling: float,
          scoring: str = "sigmoid", renormalize: bool = True):
    """``(chosen (T, top_k) int32, weights (T, top_k) float32)``: the
    router in float32 over all the experts, ``a`` (T, C); ``scoring``
    one of ``SCORINGS``, the chosen scores divided by their sum where
    ``renormalize``."""
    if scoring not in SCORINGS:
        raise ValueError(f"router scoring {scoring!r} not in {SCORINGS}")
    logits = jnp.einsum(
        "tc,ce->te", a.astype(jnp.float32),
        params["w"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    chosen = jax.lax.top_k(scores, top_k)[1]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if renormalize:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return chosen, picked * scaling


def usual_rows(tokens: int, top_k: int, held: int, experts: int) -> int:
    """Rows of the sorted buffer a usual step takes: ``tokens`` (what
    one expert can be sent) times twice the ``top_k x held / experts``
    an even router fills, in whole ones and never less than one; more
    than one in whole tiles of the grouped kernel, and never more than
    the ``tokens x top_k`` there are."""
    full = max(1, 2 * top_k * held // experts)
    if full == 1:
        return tokens
    return min(tokens * top_k, round_up(full * tokens, _TILE_ROWS))


@functools.partial(jax.checkpoint, static_argnums=(5, 6, 7))
def _routed(experts, a, weights, order, load, rows: int, top_k: int,
            policy: Policy):
    """The held experts' part of the result, (T, C), from the first
    ``rows`` rows of the sorted order: the usual buffer's
    (``load.sum()`` is no more), else all ``T x top_k``. A checkpoint
    of its own, as the scan of ``ops/ssm.py`` is: what its backward
    needs is made again from its arguments when the backward runs, so
    that nothing of a buffer's size waits for it (a value that crosses
    the ``lax.cond`` around this is held at the size of the larger
    branch)."""
    with device_scope("moe_route"):
        order = order[:rows]
        token = order // top_k
        computed = (jnp.arange(rows) < load.sum())[:, None]
        taken = jnp.where(computed, a[token], 0)
        scale = weights.reshape(-1)[order][:, None]
    with device_scope("moe_experts"):
        mlp = gated_mlp_apply if "gate" in experts else relu2_mlp_apply
        y = mlp(
            experts, taken, policy, name=None,
            product=functools.partial(grouped_product, group_sizes=load))
    with device_scope("moe_route"):
        # masked before it is weighted: what lies past the computed
        # rows is no number (no group covers it), and 0 x nan is nan
        # in the weights' gradient
        y = (jnp.where(computed, y, 0).astype(jnp.float32) * scale) \
            .astype(policy.compute_dtype)
        # summed in the compute dtype, as the residual stream is: a
        # float32 copy of the rows would be the largest buffer of the step
        return jnp.zeros(a.shape, y.dtype).at[token].add(y)


@device_scope("moe")
def moe_apply(params, a, *, top_k: int, first_expert=0,
              scaling: float = 1.0, scoring: str = "sigmoid",
              renormalize: bool = True,
              policy: Policy = DEFAULT_POLICY):
    """a (B, S, C) -> ``(out (B, S, C), load)``; ``load`` (held,)
    int32, the assignments each held expert computed. The experts'
    kind and the shared expert are the parameter tree's."""
    shape, dim = a.shape, a.shape[-1]
    a = a.reshape(-1, dim)
    tokens = a.shape[0]
    held = params["experts"]["up"]["w"].shape[0]
    experts = params["router"]["w"].shape[1]
    moe_paths.add(f"held {held}/{experts}")
    moe_kinds.add(f"{scoring} top {top_k}"
                  + (" renormalised" if renormalize else ""))
    moe_kinds.add("gated silu x3 products" if "gate" in params["experts"]
                  else "relu2 x2 products")
    moe_kinds.add("shared expert" if "shared" in params
                  else "no shared expert")
    chosen, weights = route(params["router"], a, top_k=top_k,
                            scaling=scaling, scoring=scoring,
                            renormalize=renormalize)
    with device_scope("moe_route"):
        local = chosen.reshape(-1) - first_expert
        # the absent experts' assignments sort last, as group ``held``
        group = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        load = (group[:, None] == jnp.arange(held)).sum(0, dtype=jnp.int32)

    def routed(rows):
        return lambda *args: _routed(*args, rows, top_k, policy)

    usual = usual_rows(tokens, top_k, held, experts)
    label = pick_grouped_product()[1]
    # counted here: the routed part is traced once a size, however
    # often it is differentiated
    moe_paths.add(f"{label}x{usual}" + (
        "[2 x even share]" if usual > tokens else ""))
    moe_paths.add(f"{label}x{tokens * top_k}")
    out = jax.lax.cond(load.sum() <= usual, routed(usual),
                       routed(tokens * top_k),
                       params["experts"], a, weights, order, load)
    if "shared" in params:
        out = out + relu2_mlp_apply(params["shared"], a, policy)
    return out.reshape(shape), load
