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
* a shared expert (a tree with ``shared``, every token) or none:
  relu-squared (``nemotron_h``), gated with three matrices (a
  ``shared`` with ``gate`` too) and multiplied by a sigmoid of a
  one-column projection of the token (a tree with ``shared_gate``:
  ``qwen3_next``'s ``sigmoid(a w_sg) * f_shared(a)``), or gated with
  three matrices and no such column (``kimi_linear``: the ``shared``
  with ``gate``, no ``shared_gate``).

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
wherever a value leaves the buffer.

**The layer routes once a layer and step.** What says where rows go is
a plan of integers (``routing_plan``: the chosen experts, the sorted
order, the loads and the way back by token), made by two stable sorts
and named ``moe_plan`` for a ``remat`` stack to keep: the recomputed
layer makes the router's product and the scores again (the weights'
gradient needs them) and reads the plan; it runs no second ``top_k``
and no second sort. A token's row goes to its assignments by a gather
(``dispatch``) and the rows come back as each token's sum
(``combine``), each with a hand-written backward that is the other's
forward, so that no pass scatter-adds rows whose indices repeat: a
token's rows are one stretch of the way back, a tile of tokens reads
its stretch by a gather and sums it by a 0/1 product in float32
(``sum_by_token``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from perceiver_tpu.obs.trace import device_scope
from perceiver_tpu.ops.initializers import torch_linear_uniform
from perceiver_tpu.ops.linear import linear_apply, linear_init
from perceiver_tpu.ops.mlp import (
    gated_mlp_apply,
    gated_mlp_init,
    relu2_mlp_apply,
    relu2_mlp_init,
)
from perceiver_tpu.ops.policy import DEFAULT_POLICY, Policy
from perceiver_tpu.ops.remat import dear
from perceiver_tpu.ops.tally import Tally
from perceiver_tpu.ops.tiling import round_up

#: which form the grouped products of a layer took, with the rows of the
#: sorted buffer at each of its sizes (``megabloxx16384``,
#: ``megabloxx98304``; ``ragged_dot[cpu]x240``; the usual size says so
#: where the share and not the tokens set it,
#: ``megabloxx32768[2 x even share]``), how many experts of how many
#: the layer holds (``held 8/128``)
moe_paths = Tally()
#: what a layer's router and experts are: ``softmax top 8 renormalised``,
#: ``gated silu x3 products`` or ``relu2 x2 products``, ``no shared
#: expert``, ``shared expert`` (relu-squared), ``gated shared expert
#: under a sigmoid gate`` or ``gated shared expert, no gate column``;
#: ``weights x2.446`` where the chosen weights are scaled
moe_kinds = Tally()

SCORINGS = ("sigmoid", "softmax")
# relu-squared; gated under a one-column sigmoid gate; gated alone
SHARED_KINDS = ("relu2", "gated", "glu")

# rows of the sorted buffer a tile of the grouped kernel takes
_TILE_ROWS = 512


def moe_init(key, dim: int, *, num_experts: int, held_experts: int,
             expert_hidden: int, shared_hidden: int, gated: bool = False,
             shared_kind: str = "relu2", dtype=jnp.float32):
    """The router over all ``num_experts``, the ``held_experts`` this
    layer holds (stacked on a leading axis; with a ``gate`` matrix each
    where ``gated``) and the shared expert (none at a ``shared_hidden``
    of 0) of ``shared_kind``, one of ``SHARED_KINDS``: relu-squared,
    gated with its one-column ``shared_gate``, or gated without
    (``glu``)."""
    if shared_kind not in SHARED_KINDS:
        raise ValueError(f"shared expert {shared_kind!r} not in "
                         f"{SHARED_KINDS}")
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
    if shared_hidden and shared_kind != "relu2":
        params["shared"] = gated_mlp_init(ks, dim, shared_hidden, dtype)
        if shared_kind == "gated":
            params["shared_gate"] = linear_init(
                jax.random.fold_in(ks, 1), dim, 1, dtype, bias=False)
    elif shared_hidden:
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
    ``renormalize``. ``chosen`` is part of the layer's plan
    (``moe_plan``): a ``remat`` layer that keeps it makes the product
    and the score again, which the weights' gradient needs, and reads
    the chosen scores at the kept ``chosen``: no second ``top_k``."""
    if scoring not in SCORINGS:
        raise ValueError(f"router scoring {scoring!r} not in {SCORINGS}")
    logits = jnp.einsum(
        "tc,ce->te", a.astype(jnp.float32),
        params["w"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    chosen = dear(jax.lax.top_k(scores, top_k)[1], "moe_plan")
    # the chosen scores by a select over the experts' axis: the TPU
    # gathers scalars one at a time (``take_along_axis`` here cost
    # 1.3 ms a layer and pass for 131,072 of them; PERF.md, PR 38)
    picked = jnp.sum(jnp.where(
        chosen[..., None] == jnp.arange(scores.shape[-1]),
        scores[:, None, :], 0), -1)
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


# --- the plan ----------------------------------------------------------------


def _sorted_by(key):
    """``(key sorted, its old places)`` for ``key`` (n,) int32, equal
    keys in their old order: what a stable ``argsort`` gives, with the
    sorted keys beside it."""
    place = jnp.arange(key.shape[0], dtype=jnp.int32)
    return jax.lax.sort((key, place), num_keys=1, is_stable=True)


class Plan(NamedTuple):
    """Where one layer's assignments go and how their rows come back,
    made once a layer and step (``routing_plan``). ``N = T x top_k``;
    assignment ``t top_k + j`` is token ``t``'s ``j``-th choice; the
    sorted buffer's row ``r`` holds assignment ``order[r]``, and its
    first ``load.sum()`` rows are the held experts'."""

    order: jax.Array       # (N,) int32: the assignment a sorted row holds
    load: jax.Array        # (held,) int32: assignments a held expert
    back_row: jax.Array    # (N,) int32: the sorted rows by token
    back_token: jax.Array  # (N,) int32: their tokens; T past the held

    def token(self, rows: int, tokens: int):
        """(rows,) int32: the token of each of the first ``rows`` sorted
        rows, of ``tokens`` tokens."""
        return self.order[:rows] // (self.order.size // tokens)


@device_scope("moe_route")
def routing_plan(chosen, first_expert, held: int) -> Plan:
    """The plan of ``chosen`` (T, top_k) for the ``held`` experts from
    ``first_expert`` on. An assignment's group is its held expert,
    ``held`` for an absent one; ``order`` is what ``jnp.argsort(group,
    stable=True)`` gives, element for element. ``back_row`` lists the
    held experts' rows by token (a token's rows in the sorted order),
    the rest after them under the token ``T``: the way back
    (``sum_by_token``) reads a token tile's rows as one stretch of it.
    Two stable sorts (``_sorted_by``) and a one-hot sum; all named
    ``moe_plan`` (``ops/remat.dear``), so a ``remat`` layer that keeps
    the name makes none of it again."""
    tokens, top_k = chosen.shape
    local = chosen.reshape(-1) - first_expert
    group = jnp.where((local >= 0) & (local < held), local, held)
    group, order = _sorted_by(group)
    load = (group[:, None] == jnp.arange(held)).sum(0, dtype=jnp.int32)
    back_token, back_row = _sorted_by(
        jnp.where(group < held, order // top_k, tokens))
    return Plan(*(dear(x, "moe_plan")
                  for x in (order, load, back_row, back_token)))


# --- rows to the sorted buffer and back --------------------------------------

# tokens a tile of the way back: one side of its one-hot product
_TOKEN_TILE = 128


def _computed(rows: int, total):
    """(rows, 1) bool: the sorted rows some group covers."""
    return (jnp.arange(rows) < total)[:, None]


def _tile_sums(z, plan: Plan, first, tokens: int, window: int):
    """(tiles x _TOKEN_TILE, C) float32: every token's sum of its rows
    of ``z``, where no tile of tokens has more than ``window`` rows:
    ``window`` entries of the way back from each tile's first
    (``first``), their rows of ``z`` gathered, and a 0/1 matrix (token
    of the tile x entry) times them, summed in float32. An entry that
    is no row of the tile reads row 0 and is multiplied by 0."""
    def stretch(x, fill):
        x = jnp.concatenate([x, jnp.full((window,), fill, x.dtype)])
        return jax.vmap(lambda at: jax.lax.dynamic_slice(
            x, (at,), (window,)))(first[:-1])

    tiles = first.size - 1
    # the token ``tokens`` is no token: the rows past the held ones
    token = stretch(plan.back_token, tokens)
    tile = jnp.arange(tiles)[:, None] * _TOKEN_TILE + jnp.arange(_TOKEN_TILE)
    mine = token[:, None, :] == jnp.where(tile < tokens, tile, -1)[:, :, None]
    rows = jnp.where(mine.any(1), stretch(plan.back_row, 0), 0)
    exact = jax.lax.Precision.HIGHEST if z.dtype == jnp.float32 else None
    return jnp.einsum(
        "itw,iwc->itc", mine.astype(z.dtype), z[rows], precision=exact,
        preferred_element_type=jnp.float32).reshape(-1, z.shape[1])


def sum_by_token(z, plan: Plan, tokens: int):
    """(T, C) float32: each token's sum of the rows of ``z`` (rows, C)
    that are its held assignments' (the first ``plan.load.sum()`` of
    the sorted order; what ``z`` holds past them is not read as a
    number). **No scatter-add**: the TPU adds rows whose indices repeat
    one at a time (2.9 ms for 32,768 rows of 2,048; PERF.md, PR 38).
    The rows of a tile of ``_TOKEN_TILE`` tokens are one stretch of the
    way back, as long as the router made it: the stretch is read at a
    static length (``_tile_sums``), the usual one twice what the buffer
    holds a tile, else ``_TOKEN_TILE x top_k``, all that top-k allows
    (``lax.switch`` on the longest tile)."""
    rows, top_k = z.shape[0], plan.order.size // tokens
    tiles = -(-tokens // _TOKEN_TILE)
    total = plan.load.sum()
    # the tiles' first entries: held rows whose token is under the tile
    first = jnp.sum(
        plan.back_token[None, :rows]
        < jnp.minimum(jnp.arange(tiles + 1) * _TOKEN_TILE, tokens)[:, None],
        axis=1, dtype=jnp.int32)
    most = _TOKEN_TILE * top_k
    even = round_up(_TOKEN_TILE * rows // tokens, _TOKEN_TILE)
    windows = sorted({min(even, most), min(2 * even, most), most})
    longest = (first[1:] - first[:-1]).max()
    sums = jax.lax.switch(
        sum(longest > w for w in windows[:-1]),
        [functools.partial(_tile_sums, plan=plan, first=first,
                           tokens=tokens, window=w) for w in windows], z)
    # with no held row at all, row 0 is no number either
    return jnp.where(total > 0, sums[:tokens], 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def dispatch(a, plan: Plan, rows: int, tokens: int):
    """(rows, C): the first ``rows`` rows of the sorted order, each its
    token's row of ``a`` (``tokens``, C); zeros past the last held
    assignment. Backward, a token's gradient is the sum of its held
    assignments' rows (``sum_by_token``), where autodiff of the gather
    would scatter-add rows whose indices repeat."""
    return jnp.where(_computed(rows, plan.load.sum()),
                     a[plan.token(rows, tokens)], 0)


def _dispatch_fwd(a, plan, rows, tokens):
    return dispatch(a, plan, rows, tokens), plan


def _dispatch_bwd(rows, tokens, plan, g):
    with device_scope("moe_route"):
        return sum_by_token(g, plan, tokens).astype(g.dtype), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(y, weights, plan: Plan):
    """(T, C) in ``y``'s dtype: each token's weighted sum of the rows of
    ``y`` (rows, C) its held assignments sit in, ``weights`` (T, top_k)
    float32: a row times its weight is rounded to ``y``'s dtype, a
    token's rows are summed in float32 and rounded once
    (``sum_by_token``). Backward, a row's gradient is its token's row
    of the cotangent times its weight (the gather ``dispatch`` makes),
    and a weight's is its row's dot with it."""
    return _combine_fwd(y, weights, plan)[0]


def _combine_fwd(y, weights, plan):
    scale = weights.reshape(-1)[plan.order[:y.shape[0]]][:, None]
    z = (y.astype(jnp.float32) * scale).astype(y.dtype)
    return (sum_by_token(z, plan, weights.shape[0]).astype(y.dtype),
            (y, scale, plan))


def _combine_bwd(kept, g):
    y, scale, plan = kept
    rows, tokens = y.shape[0], g.shape[0]
    with device_scope("moe_route"):
        computed = _computed(rows, plan.load.sum())
        came = g[plan.token(rows, tokens)].astype(jnp.float32)
        dy = jnp.where(computed, came * scale, 0).astype(y.dtype)
        dots = jnp.sum(jnp.where(computed, y.astype(jnp.float32) * came, 0),
                       -1)
        # an assignment sits in one row: a scatter of distinct places
        dweights = jnp.zeros(plan.order.shape, jnp.float32).at[
            plan.order[:rows]].set(dots, unique_indices=True)
        return dy, dweights.reshape(tokens, -1), None


combine.defvjp(_combine_fwd, _combine_bwd)


@functools.partial(jax.checkpoint, static_argnums=(4, 5))
def _routed(experts, a, weights, plan: Plan, rows: int, policy: Policy):
    """The held experts' part of the result, (T, C), from the first
    ``rows`` rows of the sorted order: the usual buffer's
    (``plan.load.sum()`` is no more), else all ``T x top_k``. A
    checkpoint of its own, as the scan of ``ops/ssm.py`` is: what its
    backward needs is made again from its arguments when the backward
    runs, so that nothing of a buffer's size waits for it (a value that
    crosses the ``lax.cond`` around this is held at the size of the
    larger branch)."""
    with device_scope("moe_route"):
        taken = dispatch(a, plan, rows, a.shape[0])
    with device_scope("moe_experts"):
        mlp = gated_mlp_apply if "gate" in experts else relu2_mlp_apply
        y = mlp(
            experts, taken, policy, name=None,
            product=functools.partial(grouped_product,
                                      group_sizes=plan.load))
    with device_scope("moe_route"):
        # what lies past the computed rows is no number (no group covers
        # it): ``combine`` reads no such row as one, forward or backward
        return combine(y, weights, plan)


@device_scope("moe")
def moe_apply(params, a, *, top_k: int, first_expert=0,
              scaling: float = 1.0, scoring: str = "sigmoid",
              renormalize: bool = True,
              policy: Policy = DEFAULT_POLICY):
    """a (B, S, C) -> ``(out (B, S, C), load)``; ``load`` (held,)
    int32, the assignments each held expert computed. The experts'
    kind and the shared expert's are the parameter tree's."""
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
    moe_kinds.add("no shared expert" if "shared" not in params else
                  "gated shared expert under a sigmoid gate"
                  if "shared_gate" in params else
                  "gated shared expert, no gate column"
                  if "gate" in params["shared"] else "shared expert")
    if scaling != 1.0:
        moe_kinds.add(f"weights x{scaling:g}")
    chosen, weights = route(params["router"], a, top_k=top_k,
                            scaling=scaling, scoring=scoring,
                            renormalize=renormalize)
    plan = routing_plan(chosen, first_expert, held)

    def routed(rows):
        return lambda *args: _routed(*args, rows, policy)

    usual = usual_rows(tokens, top_k, held, experts)
    label = pick_grouped_product()[1]
    # counted here: the routed part is traced once a size, however
    # often it is differentiated
    moe_paths.add(f"{label}x{usual}" + (
        "[2 x even share]" if usual > tokens else ""))
    moe_paths.add(f"{label}x{tokens * top_k}")
    out = jax.lax.cond(plan.load.sum() <= usual, routed(usual),
                       routed(tokens * top_k),
                       params["experts"], a, weights, plan)
    if "shared_gate" in params:
        shared = gated_mlp_apply(params["shared"], a, policy)
        gate = jax.nn.sigmoid(linear_apply(
            params["shared_gate"], a, policy=policy).astype(jnp.float32))
        out = out + (shared.astype(jnp.float32) * gate).astype(out.dtype)
    elif "shared" in params:
        mlp = gated_mlp_apply if "gate" in params["shared"] \
            else relu2_mlp_apply
        out = out + mlp(params["shared"], a, policy)
    return out.reshape(shape), plan.load
