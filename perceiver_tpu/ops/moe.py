"""Routed-expert layer (the ``nemotron_h`` layer ``E``) as pure
init/apply functions: a sigmoid router over **all** ``num_experts``
experts, the top ``top_k`` a token, relu-squared experts without a
gate, one shared expert::

    s = sigmoid(a W_r)                          (float32, all the experts)
    chosen = top_k(s)
    w_i = s_i / (sum_chosen s + 1e-20) * routed_scaling_factor
    out = sum_chosen w_i f_i(a) + f_shared(a),  f(a) = relu(a W_up)^2 W_down

(The published layer adds a buffer, ``e_score_correction_bias``, to the
scores it chooses by, and moves it by a balancing rule its
``config.json`` does not give: here it is 0 and not read.)

**The layer is told which experts it holds**: ``first_expert`` (an
int, or a scalar of the step) and the number of experts in its
parameter tree (a chip's share under expert parallelism). It routes
over all the experts, computes the part of the result that its own
experts give for the tokens routed to them, adds the shared expert, and
leaves out what the absent experts would have added. Nothing stands in
for the absent chips or their exchange.

**No token is dropped.** The ``T x top_k`` assignments are sorted by
held expert, the assignments to absent experts last, and the first rows
of that order, as many as the held experts were sent, are computed:
whatever the imbalance, every (token, held expert) pair. The two
products over the held experts are grouped (``grouped_product``): each
expert multiplies the rows routed to it and no others.

The buffer the sorted rows are gathered into has a static size: one
row a token (``T`` rows: 2.7 times what an even router sends 8 held
experts of 128 at top 6), and where a step's assignments do not fit
(``lax.cond`` on their count) ``T x top_k`` rows, everything top-k
allows: the same function at another size, so that the usual step moves
thousands of rows and not a hundred thousand (gathering and weighting
98,304 rows on every step cost 130 ms of 684; PERF.md, PR 33). The
groups end at the last held assignment, so **the products' work is the
router's**: the rows past it (zeros) belong to no group, no product
reads them, the kernel leaves what it returns there uninitialised, and
they are masked wherever a value leaves the buffer. A token's row goes
to its assignments by a gather and comes back by a scatter-add;
autodiff transposes each into the other.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perceiver_tpu.obs.trace import device_scope
from perceiver_tpu.ops.initializers import torch_linear_uniform
from perceiver_tpu.ops.linear import linear_init
from perceiver_tpu.ops.mlp import relu2_mlp_apply, relu2_mlp_init
from perceiver_tpu.ops.policy import DEFAULT_POLICY, Policy
from perceiver_tpu.ops.tally import Tally

#: which form the grouped products of a layer took, with the rows of the
#: sorted buffer at each of its sizes (``megabloxx16384``,
#: ``megabloxx98304``; ``ragged_dot[cpu]x240``), and how many experts of
#: how many the layer holds (``held 8/128``)
moe_paths = Tally()

# rows of the sorted buffer a tile of the grouped kernel takes
_TILE_ROWS = 512


def moe_init(key, dim: int, *, num_experts: int, held_experts: int,
             expert_hidden: int, shared_hidden: int, dtype=jnp.float32):
    """The router over all ``num_experts``, the ``held_experts`` this
    layer holds (stacked on a leading axis) and the shared expert."""
    kr, ku, kd, ks = jax.random.split(key, 4)
    return {
        "router": linear_init(kr, dim, num_experts, dtype, bias=False),
        "experts": {
            "up": {"w": torch_linear_uniform(
                ku, (held_experts, dim, expert_hidden), dim, dtype)},
            "down": {"w": torch_linear_uniform(
                kd, (held_experts, expert_hidden, dim), expert_hidden,
                dtype)},
        },
        "shared": relu2_mlp_init(ks, dim, shared_hidden, dtype),
    }


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
def route(params, a, *, top_k: int, scaling: float):
    """``(chosen (T, top_k) int32, weights (T, top_k) float32)``: the
    router in float32 over all the experts, ``a`` (T, C)."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "tc,ce->te", a.astype(jnp.float32),
        params["w"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    chosen = jax.lax.top_k(scores, top_k)[1]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scaling
    return chosen, weights


@functools.partial(jax.checkpoint, static_argnums=(5, 6, 7))
def _routed(experts, a, weights, order, load, usual: bool, top_k: int,
            policy: Policy):
    """The held experts' part of the result, (T, C), from the first
    rows of the sorted order: ``T`` of them in the ``usual`` buffer
    (``load.sum()`` is no more), else all ``T x top_k``. A checkpoint
    of its own, as the scan of ``ops/ssm.py`` is: what its backward
    needs is made again from its arguments when the backward runs, so
    that nothing of a buffer's size waits for it (a value that crosses
    the ``lax.cond`` around this is held at the size of the larger
    branch)."""
    with device_scope("moe_route"):
        rows = a.shape[0] * (1 if usual else top_k)
        order = order[:rows]
        token = order // top_k
        computed = (jnp.arange(rows) < load.sum())[:, None]
        taken = jnp.where(computed, a[token], 0)
        scale = weights.reshape(-1)[order][:, None]
    with device_scope("moe_experts"):
        y = relu2_mlp_apply(
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
              scaling: float = 1.0, policy: Policy = DEFAULT_POLICY):
    """a (B, S, C) -> ``(out (B, S, C), load)``; ``load`` (held,)
    int32, the assignments each held expert computed."""
    shape, dim = a.shape, a.shape[-1]
    a = a.reshape(-1, dim)
    tokens = a.shape[0]
    held = params["experts"]["up"]["w"].shape[0]
    moe_paths.add(f"held {held}/{params['router']['w'].shape[1]}")
    chosen, weights = route(params["router"], a, top_k=top_k,
                            scaling=scaling)
    with device_scope("moe_route"):
        local = chosen.reshape(-1) - first_expert
        # the absent experts' assignments sort last, as group ``held``
        group = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        load = (group[:, None] == jnp.arange(held)).sum(0, dtype=jnp.int32)

    def routed(usual):
        return lambda *args: _routed(*args, usual, top_k, policy)

    label = pick_grouped_product()[1]
    # counted here: the routed part is traced once a size, however
    # often it is differentiated
    moe_paths.add(f"{label}x{tokens}")
    moe_paths.add(f"{label}x{tokens * top_k}")
    out = jax.lax.cond(load.sum() <= tokens, routed(True), routed(False),
                       params["experts"], a, weights, order, load)
    out = out + relu2_mlp_apply(params["shared"], a, policy)
    return out.reshape(shape), load
