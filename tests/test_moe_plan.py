"""The expert layers' routing plan and the rows' movement
(``ops/moe.py``): the plan's order is the stable ``argsort``'s, element
for element; ``dispatch`` and ``combine``
with their hand-written backward are autodiff's gather and scatter-add,
values and gradients, at every window the way back can take; the layer
is what it was, for both routers, both kinds of expert and both buffer
sizes; and a ``remat`` step makes the plan once a layer (one ``top_k``,
two sorts, no scatter-add of rows)."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

from perceiver_tpu import tasks
from perceiver_tpu.ops import moe, remat
from perceiver_tpu.ops.mlp import (
    gated_mlp_apply,
    relu2_mlp_apply,
)
from perceiver_tpu.ops.policy import Policy

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FP32, BF16 = Policy.fp32(), Policy.bf16()
T, K, HELD, EXPERTS, FIRST = 80, 3, 4, 16, 4
USUAL = moe.usual_rows(T, K, HELD, EXPERTS)


def rel(a, b):
    return float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-30)


# --- the plan ----------------------------------------------------------------


def chosen_with(held_assignments: int, seed: int = 0):
    """(T, K) choices of which exactly ``held_assignments`` fall on the
    held experts ``FIRST .. FIRST + HELD``: each token's first choices,
    as many as it must take, are distinct held experts."""
    rng = np.random.default_rng(seed)
    absent = [e for e in range(EXPERTS) if not FIRST <= e < FIRST + HELD]
    per_token = np.full(T, held_assignments // T)
    per_token[:held_assignments % T] += 1
    rows = []
    for n in rng.permutation(per_token):
        picks = list(rng.choice(np.arange(FIRST, FIRST + HELD), n, False)) \
            + list(rng.choice(absent, K - n, False))
        rows.append(rng.permutation(picks))
    return jnp.asarray(np.stack(rows), jnp.int32)


def random_chosen(seed: int = 0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.stack(
        [rng.choice(EXPERTS, K, False) for _ in range(T)]), jnp.int32)


PLANS = {
    "random": random_chosen(),
    "one_held_expert": jnp.tile(jnp.asarray([[1, FIRST + 1, 14]]), (T, 1)),
    "none_held": jnp.tile(jnp.asarray([[0, 1, 15]]), (T, 1)),
    "exactly_the_usual_rows": chosen_with(USUAL),
    "one_more_than_usual": chosen_with(USUAL + 1),
    "everything_held": chosen_with(T * K),
}


def group_of(chosen):
    local = np.asarray(chosen).reshape(-1) - FIRST
    return np.where((local >= 0) & (local < HELD), local, HELD)


@pytest.mark.parametrize("case", PLANS)
def test_the_plan_is_the_stable_sorts(case):
    chosen = PLANS[case]
    group = group_of(chosen)
    plan = jit_once(lambda c, first: moe.routing_plan(c, first, HELD))(
        chosen, FIRST)    # the first expert a value of the step
    assert all(x.dtype == jnp.int32 for x in plan)
    np.testing.assert_array_equal(
        plan.order, jnp.argsort(jnp.asarray(group), stable=True))
    np.testing.assert_array_equal(
        plan.load, (group[:, None] == np.arange(HELD)).sum(0))
    # the way back: the held experts' rows by token, a token's rows in
    # the sorted order, each row once; the rest after them, as token T
    order = np.asarray(plan.order)
    total = int((group < HELD).sum())
    back_row = np.asarray(plan.back_row)
    back_token = np.asarray(plan.back_token)
    np.testing.assert_array_equal(np.sort(back_row), np.arange(T * K))
    np.testing.assert_array_equal(np.sort(back_row[:total]),
                                  np.arange(total))
    np.testing.assert_array_equal(back_token[:total],
                                  order[back_row[:total]] // K)
    assert (np.diff(back_token) >= 0).all() and (back_token[total:] == T
                                                 ).all()
    same = np.diff(back_token[:total]) == 0
    assert (np.diff(back_row[:total])[same] > 0).all()
    if case == "exactly_the_usual_rows":
        assert total == USUAL
    if case == "one_more_than_usual":
        assert total == USUAL + 1


@pytest.mark.parametrize("n,most", [(1, 1), (14, 2), (300, 70000),
                                    (4096, 5), (131072, 16)])
def test_sorted_by_is_the_stable_sort(n, most):
    """Few distinct keys up to ``most``, so that most are equal: the
    sorted keys and their old places, equal keys in their old order."""
    key = jnp.asarray(np.random.default_rng(n).integers(
        0, min(most, 5) + 1, n) * (most // min(most, 5)), jnp.int32)
    got_key, got_place = jit_once(moe._sorted_by)(key)
    assert got_key.dtype == got_place.dtype == jnp.int32
    want = np.argsort(np.asarray(key), kind="stable")
    np.testing.assert_array_equal(got_place, want)
    np.testing.assert_array_equal(got_key, np.asarray(key)[want])


# --- the movement ------------------------------------------------------------


def gather_and_scatter_add(y_of, a, weights, plan, rows, dtype):
    """What ``dispatch`` and ``combine`` replace, under autodiff: the
    gather of the sorted rows, the weighting and the scatter-add."""
    order = plan.order[:rows]
    token = order // K
    computed = (jnp.arange(rows) < plan.load.sum())[:, None]
    taken = jnp.where(computed, a[token], 0)
    y = jnp.where(computed, y_of(taken), 0).astype(jnp.float32) \
        * weights.reshape(-1)[order][:, None]
    return jnp.zeros(a.shape, dtype).at[token].add(y.astype(dtype))


@pytest.mark.parametrize("rows", [USUAL, T * K], ids=["usual", "all"])
@pytest.mark.parametrize("case", ["random", "exactly_the_usual_rows",
                                  "none_held"])
def test_dispatch_and_combine_are_the_gather_and_the_scatter_add(case, rows):
    """Values and the gradients for ``a``, the experts' output and the
    weights, in float32; what lies past the computed rows of the
    experts' output is no number and reaches nothing."""
    plan = moe.routing_plan(PLANS[case], FIRST, HELD)
    k = jax.random.split(jax.random.key(5), 4)
    a = jax.random.normal(k[0], (T, 24))
    mix = jax.random.normal(k[1], (24, 24))
    weights = jax.random.uniform(k[2], (T, K))
    w_out = jax.random.normal(k[3], (T, 24))
    nan_past = jnp.where((jnp.arange(rows) < plan.load.sum())[:, None],
                         0.0, jnp.nan)

    def new(a, mix, weights):
        y = jnp.tanh(moe.dispatch(a, plan, rows, T) @ mix) + nan_past
        return (moe.combine(y, weights, plan) * w_out).sum()

    def old(a, mix, weights):
        return (gather_and_scatter_add(
            lambda x: jnp.tanh(x @ mix) + nan_past, a, weights, plan, rows,
            jnp.float32) * w_out).sum()

    got, got_g = jit_once(jax.value_and_grad(
        new, argnums=(0, 1, 2)))(a, mix, weights)
    want, want_g = jit_once(jax.value_and_grad(
        old, argnums=(0, 1, 2)))(a, mix, weights)
    assert abs(got - want) <= 1e-5 * abs(want) + 1e-6
    for g, w in zip(got_g, want_g):
        assert bool(jnp.isfinite(g).all())
        assert rel(g, w) < 1e-5 or float(jnp.abs(w).max()) == 0 == float(
            jnp.abs(g).max())


def crowded_chosen(tokens: int, held_in_first_tile: int):
    """``tokens`` x K choices: each of the first 128 tokens sends
    ``held_in_first_tile`` of its choices to held experts, the others
    none."""
    absent = [e for e in range(EXPERTS) if not FIRST <= e < FIRST + HELD]
    rows = np.tile(np.asarray(absent[:K]), (tokens, 1))
    rows[:128, :held_in_first_tile] = np.arange(
        FIRST, FIRST + held_in_first_tile)
    return jnp.asarray(rows, jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("held_in_first_tile", [1, 2, 3])
def test_the_way_back_at_each_of_its_windows(held_in_first_tile, dtype):
    """512 tokens, a usual buffer of 512 rows: a tile of 128 tokens
    with 128, 256 and 384 rows takes the window of 128, of 256 and the
    one for all that top-k allows; every row is summed, in float32,
    whatever lies past the held rows."""
    tokens = 512
    assert moe.usual_rows(tokens, K, HELD, EXPERTS) == tokens
    plan = moe.routing_plan(crowded_chosen(tokens, held_in_first_tile),
                            FIRST, HELD)
    total = int(plan.load.sum())
    assert total == 128 * held_in_first_tile
    z = jax.random.normal(jax.random.key(8), (tokens, 24)).astype(dtype)
    z = jnp.where((jnp.arange(tokens) < total)[:, None], z, jnp.nan)
    got = jit_once(lambda z: moe.sum_by_token(z, plan, tokens))(z)
    want = np.zeros((tokens, 24), np.float32)
    np.add.at(want, np.asarray(plan.order[:total]) // K,
              np.asarray(z[:total].astype(jnp.float32)))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_no_held_row_at_all_sums_to_zero():
    plan = moe.routing_plan(PLANS["none_held"], FIRST, HELD)
    z = jnp.full((USUAL, 8), jnp.nan)
    assert not np.asarray(moe.sum_by_token(z, plan, T)).any()


def layer_as_it_was(params, a, *, top_k, first_expert, scaling, scoring,
                    renormalize, policy):
    """The expert layer with the two-operand sort, the gather and the
    scatter-add under autodiff, at the ``T x top_k`` buffer."""
    shape = a.shape
    a = a.reshape(-1, shape[-1])
    held = params["experts"]["up"]["w"].shape[0]
    chosen, weights = moe.route(params["router"], a, top_k=top_k,
                                scaling=scaling, scoring=scoring,
                                renormalize=renormalize)
    local = chosen.reshape(-1) - first_expert
    group = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    load = (group[:, None] == jnp.arange(held)).sum(0, dtype=jnp.int32)
    mlp = gated_mlp_apply if "gate" in params["experts"] else relu2_mlp_apply
    out = gather_and_scatter_add(
        lambda x: mlp(params["experts"], x, policy, name=None,
                      product=functools.partial(moe.grouped_product,
                                                group_sizes=load)),
        a, weights, moe.Plan(order, load, None, None), order.size,
        policy.compute_dtype)
    if "shared" in params:
        out = out + relu2_mlp_apply(params["shared"], a, policy)
    return out.reshape(shape)


ROUTERS = {"sigmoid_scaled": dict(scoring="sigmoid", renormalize=True,
                                  scaling=2.5),
           "softmax_renormalised": dict(scoring="softmax", renormalize=True,
                                        scaling=1.0)}


@pytest.mark.parametrize("shared", [80, 0], ids=["shared", "no_shared"])
@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "gated"])
@pytest.mark.parametrize("router", ROUTERS)
def test_the_layer_is_what_it_was(router, gated, shared):
    """Value and every gradient in float32, against the layer under
    autodiff of the gather and the scatter-add."""
    params = moe.moe_init(jax.random.key(2), 48, num_experts=EXPERTS,
                          held_experts=HELD, expert_hidden=40,
                          shared_hidden=shared, gated=gated)
    a = jax.random.normal(jax.random.key(3), (2, 40, 48))
    w_out = jax.random.normal(jax.random.key(4), a.shape)
    kw = dict(top_k=K, first_expert=FIRST, policy=FP32, **ROUTERS[router])
    got, got_g = jit_once(jax.value_and_grad(
        lambda p, a: (moe.moe_apply(p, a, **kw)[0] * w_out).sum(),
        argnums=(0, 1)))(params, a)
    want, want_g = jit_once(jax.value_and_grad(
        lambda p, a: (layer_as_it_was(p, a, **kw) * w_out).sum(),
        argnums=(0, 1)))(params, a)
    assert abs(got - want) <= 2e-5 * abs(want) + 1e-6
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert rel(g, w) < 1e-4


def rehearsal_task(config):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           f"{config}.json")) as f:
        cfg = json.load(f)
    cls = {"hybrid_lm": tasks.HybridLMTask,
           "block_diffusion_lm": tasks.BlockDiffusionLMTask}[cfg["task"]]
    return cls(**{**cfg["model"], **cfg["rehearsal"]["model"]})


CELLS = ["nemotron3_nano_30b", "sdar_30b_a3b"]


@pytest.mark.parametrize("config", CELLS)
def test_the_layers_output_moves_by_a_rounding_at_the_rehearsal_widths(
        config):
    """bfloat16 on float32 parameters, as the cells run: the new layer
    sums a token's rows in float32 and rounds once, the old one rounded
    an addend; they differ by a rounding of the compute dtype."""
    model = rehearsal_task(config).build()
    params = moe.moe_init(
        jax.random.key(6), model.hidden_size,
        num_experts=model.n_routed_experts,
        held_experts=model.num_held_experts,
        expert_hidden=model.moe_intermediate_size,
        shared_hidden=model.moe_shared_expert_intermediate_size,
        gated=model.gated_experts)
    a = jax.random.normal(jax.random.key(7), (2, 64, model.hidden_size))
    kw = dict(top_k=model.num_experts_per_tok,
              first_expert=model.first_expert,
              scaling=model.routed_scaling_factor,
              scoring=model.router_scoring,
              renormalize=model.norm_topk_prob, policy=BF16)
    got, load = moe.moe_apply(params, a, **kw)
    want = layer_as_it_was(params, a, **kw)
    assert got.dtype == want.dtype == jnp.bfloat16 and int(load.sum()) > 0
    # one rounding of bfloat16 is 2^-8 of the value
    assert rel(got.astype(jnp.float32), want.astype(jnp.float32)) < 2 ** -6


# --- the recomputation -------------------------------------------------------


def eqns_of(jaxpr):
    """Every equation of ``jaxpr`` and of what it calls, a
    ``lax.cond``'s branches among them."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) \
                    else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from eqns_of(inner)


@pytest.mark.parametrize("config", CELLS)
def test_a_remat_step_makes_the_plan_once_a_layer(config):
    """The train step of each cell at its rehearsal size, ``remat`` on:
    one ``top_k`` and the plan's two sorts an expert layer in the
    whole step (the plan is kept for the recomputation and
    the backward), and under ``moe_route`` no scatter that adds."""
    task = rehearsal_task(config)
    assert task.remat
    model = task.build()
    layers = model.pattern.count("E")
    params = jax.eval_shape(model.init, jax.random.key(0))
    batch = {"input_ids": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    with remat.remat_keeps() as choices:
        step = jax.jit(jax.grad(lambda p, b: task.loss_and_metrics(
            model, p, b, rng=jax.random.key(1), policy=BF16)[0]))
        text = step.lower(params, batch).as_text()
        jaxpr = jax.make_jaxpr(step)(params, batch).jaxpr
    assert "moe_plan" in choices[0]["kept"]
    assert text.count("chlo.top_k") == layers >= 2
    adds = [eqn for eqn in eqns_of(jaxpr)
            if eqn.primitive.name.startswith("scatter")
            and "add" in eqn.primitive.name
            and "moe_route" in str(eqn.source_info.name_stack)]
    assert not adds
    sorts = [eqn for eqn in eqns_of(jaxpr) if eqn.primitive.name == "sort"]
    assert len(sorts) == 2 * layers
