"""A ``glm4_moe_lite`` stack on ``models/hybrid_lm.py`` (GLM-4.7-Flash:
``AE`` a published layer, and a multi-token prediction module after the
stack): latent attention with a query latent and decoupled rotary
positions, expanded, against the plain reference's from the latent; the
rotation against one written out a position; the share tied to the
model (8 shares of the experts add up to the uncut layer, the shared
expert once); both logits, both losses, their weighted sum and every
leaf's gradient against ``benchmarks/reference/glm_moe_lite_lm.py`` in
float32 and in bf16; the head's and the embedding's gradient as the sum
of the two readings'; the module off; and planted faults that must each
fail a stated tolerance."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import comparisons, weights  # noqa: E402
from benchmarks.reference import glm_moe_lite_lm as ref  # noqa: E402
from benchmarks.reference import perceiver_io as ref_steps  # noqa: E402
from benchmarks.tasks import causal_lm as bench_causal  # noqa: E402

import perceiver_tpu.ops.remat as remat  # noqa: E402
import perceiver_tpu.tasks.hybrid_lm as task_module  # noqa: E402
from perceiver_tpu.models import hybrid_lm  # noqa: E402
from perceiver_tpu.ops import attention, moe  # noqa: E402
from perceiver_tpu.ops.fourier import rope_tables  # noqa: E402
from perceiver_tpu.ops.policy import Policy  # noqa: E402
from perceiver_tpu.tasks import HybridLMTask  # noqa: E402

FP32 = Policy.fp32()
SEQ, VOCAB = 24, 256
# score heads of 12 + 4 beside value heads of 16: equal widths, as the
# published 192 + 64 | 256
TOY = dict(
    vocab_size=VOCAB, hidden_size=48, hybrid_override_pattern="AEAE",
    num_attention_heads=4, num_key_value_heads=4, head_dim=12,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
    qk_rope_head_dim=4, v_head_dim=16, rope_theta=1e6, n_routed_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=24, routed_scaling_factor=1.8,
    router_scoring="sigmoid", norm_topk_prob=True, gated_experts=True,
    shared_expert_kind="glu", norm_eps=1e-5, max_seq_len=SEQ, held_experts=2,
    first_expert=4, ce_chunk_size=16, num_nextn_predict_layers=1,
    mtp_loss_weight=0.3)
# one published layer and the module: what the planted faults run
SHORT = {**TOY, "hybrid_override_pattern": "AE"}
MLA = dict(num_heads=4, kv_lora_rank=16, qk_nope_head_dim=12)
# float32 against the float32 reference: two arrangements of the same
# sums (expanded heads for the latent, chunks for the head's readings,
# sorted rows for masked sums); measured up to 1e-6 on these shapes,
# three seeds
TOL = 2e-5
# bfloat16 against float32, at 48 positions: the losses to 2e-3 of
# themselves (measured 1e-5 to 5e-4 over three seeds), a leaf's
# gradient norm to 0.06 of itself (measured 0.009 to 0.018: a top-k
# choice that flips with rounding moves a router's column)
BF16_LOSS, BF16_LEAF = 2e-3, 0.06


def rel(a, b):
    return float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-30)


def build(cfg, seed=42):
    task = HybridLMTask(**cfg)
    model = task.build()
    params = weights.make_weights(
        jax.eval_shape(model.init, jax.random.key(0)), seed)
    # the norms' scales are drawn as ones: move them
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 + 0.1 * jax.random.normal(
            jax.random.key(x.size), x.shape))
        if path[-1].key == "scale" else x, params)
    return task, model, params


def batch_of(cfg, rows=2):
    ids = jax.random.randint(jax.random.key(1), (rows, SEQ), 0, VOCAB)
    experts = cfg["hybrid_override_pattern"].count("E") + 1
    # a share an expert layer, the module's last
    firsts = jnp.asarray([[4, 10, 0][:experts - 1] + [6]] * rows, jnp.int32)
    return {"input_ids": ids, "first_experts": firsts}


def reference_batch(batch):
    rb = bench_causal.reference_batches(
        [{"input_ids": np.asarray(batch["input_ids"])}], TOY, 0, 1)[0]
    return {**rb, "first_experts": batch["first_experts"]}


@pytest.fixture(scope="module")
def toy():
    return (*build(TOY), batch_of(TOY))


@pytest.fixture(scope="module")
def short():
    return (*build(SHORT), batch_of(SHORT))


def program(task, model, policy=FP32):
    """``(params, batch) -> (loss, (metrics, logits, logits2))``."""
    def run(p, batch):
        loss, metrics = task.loss_and_metrics(model, p, batch, policy=policy)
        labels, _ = task_module.next_token_targets(batch)
        h, z, _ = model.prediction_states(
            p, batch["input_ids"], labels, policy=policy,
            first_experts=batch["first_experts"][0])
        head = p["head"]["w"].astype(policy.compute_dtype)
        return loss, (metrics, *((s @ head).astype(jnp.float32)
                                 for s in (h, z)))
    return run


@pytest.fixture(scope="module")
def step(toy):
    """``(params, batch) -> ((loss, (metrics, logits, logits2)),
    gradients)``, the one jitted float32 program the stack's cases
    share."""
    task, model, _, _ = toy
    return jit_once(jax.value_and_grad(program(task, model), has_aux=True))


@pytest.fixture(scope="module")
def want(toy):
    """The reference's side: both logits, both losses, the sum and its
    gradients."""
    _, _, params, batch = toy
    rb = reference_batch(batch)
    logits, logits2 = jit_once(lambda p: ref.logits(p, rb, TOY))(params)
    (s1, n1), (s2, n2) = jit_once(
        lambda p: ref.loss_sums(p, rb, TOY, "f32"))(params)
    loss, grads = ref_steps.loss_and_grads(
        params, rb, TOY, loss_sum=ref.loss_sum, block=1)
    return {"logits": logits, "logits2": logits2, "main": s1 / n1,
            "mtp": s2 / n2, "counts": (float(n1), float(n2)),
            "loss": loss, "grads": grads}


@pytest.fixture(scope="module")
def short_want(short):
    _, _, params, batch = short
    (s1, n1), (s2, n2) = jit_once(
        lambda p: ref.loss_sums(p, reference_batch(batch), SHORT, "f32"))(
            params)
    return np.asarray([s1 / n1, s2 / n2, s1 / n1 + 0.3 * s2 / n2])


def short_losses(task, model, params, batch):
    _, metrics = jit_once(lambda p, b: task.loss_and_metrics(
        model, p, b, policy=FP32))(params, batch)
    return np.asarray([metrics["main_loss"], metrics["mtp_loss"],
                       metrics["loss"]])


def miss(got, want):
    """The largest relative gap of the three losses."""
    return float(np.abs(got - want).max() / np.abs(want).min())


# --- the tree ----------------------------------------------------------------


def test_the_tree_holds_the_query_latent_and_the_module(toy):
    _, model, params, _ = toy
    assert list(params["layers"]) == ref.layer_names(TOY) == [
        "00_mla", "01_moe", "02_mla", "03_moe"]
    names = {str(getattr(p[-1], "key", p[-1])) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    assert names == {"w", "scale", "embed"}    # what weights._leaf knows
    mla = params["layers"]["00_mla"]["mixer"]
    assert {n: x["w"].shape for n, x in mla.items() if "w" in x} == {
        "q_a": (48, 24), "q_b": (24, 4 * 16), "kv_a": (48, 16 + 4),
        "kv_b": (16, 4 * (12 + 16)), "out": (4 * 16, 48)}
    assert mla["q_a_norm"]["scale"].shape == (24,)
    module = params["mtp"]
    assert set(module) == {"enorm", "hnorm", "eh_proj", "mla", "moe", "norm"}
    assert module["eh_proj"]["w"].shape == (2 * 48, 48)
    assert jax.tree.structure(module["mla"]) == jax.tree.structure(
        params["layers"]["00_mla"])
    assert jax.tree.structure(module["moe"]) == jax.tree.structure(
        params["layers"]["01_moe"])
    assert module["moe"]["mixer"]["experts"]["up"]["w"].shape == (2, 48, 24)
    assert jax.tree.structure(jax.eval_shape(
        model.init, jax.random.key(3))) == jax.tree.structure(params)


def test_the_module_off_is_the_tree_and_the_loss_of_a_stack_alone(
        toy, step):
    task, model, params, batch = toy
    plain_task = dataclasses.replace(task, num_nextn_predict_layers=0)
    plain = plain_task.build()
    key = jax.random.key(7)
    with_module, without = model.init(key), plain.init(key)
    assert "mtp" not in without
    assert set(with_module) - set(without) == {"mtp"}
    for a, b in zip(jax.tree.leaves({k: with_module[k] for k in without}),
                    jax.tree.leaves(without)):
        np.testing.assert_array_equal(a, b)
    stack = {k: v for k, v in params.items() if k != "mtp"}
    loss, metrics = jit_once(lambda p, b: plain_task.loss_and_metrics(
        plain, p, b, policy=FP32))(stack, batch)
    assert set(metrics) == {"loss", "moe_assignments",
                            "moe_load_max_over_mean"}
    got = step(params, batch)[0][1][0]
    assert abs(loss - got["main_loss"]) < 1e-6 * float(loss)
    with pytest.raises(ValueError, match="no prediction module"):
        plain.prediction_states(stack, batch["input_ids"],
                                batch["input_ids"])


@pytest.mark.parametrize("fields,message", [
    ({"kv_lora_rank": 0}, "so does a prediction module"),
    ({"hybrid_override_pattern": "*E", "v_head_dim": 0},
     "so does a prediction module"),
    ({"qk_rope_head_dim": 3}, "rotary positions pair"),
    ({"qk_rope_head_dim": 0}, "rotary positions pair"),
    ({"num_nextn_predict_layers": 2}, "one, or none"),
])
def test_a_module_that_cannot_be_built_is_refused(fields, message):
    with pytest.raises(ValueError, match=message):
        HybridLMTask(**{**TOY, **fields}).build()


def test_the_new_fields_default_to_no_such_thing():
    task = HybridLMTask()
    assert (task.q_lora_rank, task.num_nextn_predict_layers) == (0, 0)
    model = task.build()
    assert (model.q_lora_rank, model.num_nextn_predict_layers) == (0, 0)
    assert hybrid_lm.MTP_KINDS == "AE"


# --- latent attention --------------------------------------------------------


def mixer_case(toy):
    _, model, params, _ = toy
    p = params["layers"]["00_mla"]["mixer"]
    a = jax.random.normal(jax.random.key(5), (2, SEQ, TOY["hidden_size"]))
    tables = tuple(jnp.asarray(t) for t in rope_tables(SEQ, 4, 1e6))
    return p, a, jax.random.normal(jax.random.key(6), a.shape), tables


def same_with_gradient(got_fn, want_fn, p, a, w, tol=TOL):
    def both(fn):
        def weighted(p, a):
            out = fn(p, a)
            return (out * w).sum(), out
        return jit_once(jax.value_and_grad(weighted, argnums=(0, 1),
                                          has_aux=True))(p, a)

    ((_, got_out), got_g), ((_, want_out), want_g) = \
        both(got_fn), both(want_fn)
    worst = rel(got_out, want_out)
    for g, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        worst = max(worst, rel(g, r) / 10)
    return worst, tol


def test_expanded_latent_attention_against_the_latent_form(toy):
    """The program expands keys and values to the heads and hands the
    core its queries, made from the query latent and turned; the
    reference scores a head's query against the latent itself."""
    p, a, w, tables = mixer_case(toy)
    with attention.attention_paths() as paths:
        worst, tol = same_with_gradient(
            lambda p, a: hybrid_lm.mla_apply(
                p, a, **MLA, norm_eps=1e-5, policy=FP32, rope=tables),
            lambda p, a: ref.latent_attention(p, a, TOY, "f32"), p, a, w)
    assert worst < tol
    # equal widths: no two_widths padding
    assert set(paths) == {("latent", "12+4r|16 query latent"),
                          ("materialized", "backend")}


def test_the_call_without_a_query_latent_or_positions_is_as_it_was(toy):
    """``kimi_linear``'s call: ``q`` in one product inside the core's
    projection, nothing turned; the reference without the keys agrees."""
    p, a, w, _ = mixer_case(toy)
    plain = {k: v for k, v in p.items() if not k.startswith("q_")}
    plain["q"] = {"w": jax.random.normal(
        jax.random.key(8), (48, 4 * 16)) / 7.0}
    cfg = {**TOY, "q_lora_rank": 0, "rope_theta": None}
    with attention.attention_paths() as paths:
        worst, tol = same_with_gradient(
            lambda p, a: hybrid_lm.mla_apply(p, a, **MLA, norm_eps=1e-5,
                                             policy=FP32),
            lambda p, a: ref.latent_attention(p, a, cfg, "f32"), plain, a, w)
    assert worst < tol
    assert ("latent", "12+4|16") in paths


def test_the_rotation_against_one_written_out_a_position():
    """``R_t``: the pair (j, j + r/2) of position t's channels turned by
    t theta^(-2j/r), as 2 x 2 rotations in float64; the reference's
    ``rotate`` and the program's tables and ``rope_apply`` both."""
    from perceiver_tpu.ops.fourier import rope_apply
    width, theta = 8, 1e6
    x = np.asarray(jax.random.normal(jax.random.key(2), (2, SEQ, width)),
                   np.float64)
    want = np.empty_like(x)
    for t in range(SEQ):
        for j in range(width // 2):
            angle = t * theta ** (-2.0 * j / width)
            c, s = np.cos(angle), np.sin(angle)
            a, b = x[:, t, j], x[:, t, j + width // 2]
            want[:, t, j], want[:, t, j + width // 2] = \
                a * c - b * s, b * c + a * s
    x32 = jnp.asarray(x, jnp.float32)
    np.testing.assert_allclose(ref.rotate(x32, theta), want, atol=2e-6)
    tables = rope_tables(SEQ, width, theta)
    np.testing.assert_allclose(rope_apply(x32, *tables, 1), want, atol=2e-6)
    assert np.abs(want - x).max() > 0.5         # and it turns


# --- the experts' share ------------------------------------------------------


def expert_layer(p, a, first):
    return moe.moe_apply(p, a, top_k=4, first_expert=first, scaling=1.8,
                         scoring="sigmoid", policy=FP32)


def test_eight_shares_add_up_to_the_uncut_layer():
    """Every chip of 8 holds 2 of 16 experts: the routed parts all the
    shares give, with the shared expert counted once, are the uncut
    reference's layer (the guide's share test)."""
    whole = weights.make_weights(jax.eval_shape(
        lambda: moe.moe_init(jax.random.key(0), 48, num_experts=16,
                             held_experts=16, expert_hidden=24,
                             shared_hidden=24, gated=True,
                             shared_kind="glu")), 5)
    a = jax.random.normal(jax.random.key(2), (2, SEQ, 48))
    uncut = ref.expert_layer(whole, a, {**TOY, "first_expert": 0}, "f32")
    shared_w = [whole["shared"][n]["w"] for n in ("gate", "up", "down")]
    flat = a.reshape(-1, 48)
    shared = ((jax.nn.silu(flat @ shared_w[0]) * (flat @ shared_w[1]))
              @ shared_w[2]).reshape(a.shape)
    layer = jit_once(expert_layer)
    routed, loads = 0.0, 0
    with moe.moe_kinds.counting() as kinds:
        for first in range(0, 16, 2):
            part = {**whole, "experts": jax.tree.map(
                lambda x: x[first:first + 2], whole["experts"])}
            out, load = layer(part, a, first)
            routed, loads = routed + (out - shared), loads + int(load.sum())
    assert {"sigmoid top 4 renormalised", "gated silu x3 products",
            "gated shared expert, no gate column",
            "weights x1.8"} == set(kinds)
    # the reference is given the same share
    np.testing.assert_allclose(
        out, ref.expert_layer(part, a, {**TOY, "first_expert": first},
                              "f32"), rtol=2e-4, atol=2e-5)
    assert loads == 2 * SEQ * 4       # every assignment on some chip, once
    assert rel(routed + shared, uncut) < TOL
    # and a share alone is not the layer: the absent experts are left out
    assert rel(out, uncut) > 0.05


# --- the stack, the module and the losses ------------------------------------


def test_both_logits_against_the_reference(toy, step, want):
    _, _, params, batch = toy
    _, (_, logits, logits2) = step(params, batch)[0]
    assert logits.shape == logits2.shape == (2, SEQ, VOCAB)
    np.testing.assert_allclose(logits, want["logits"], atol=5e-4, rtol=1e-4)
    # the last position's embedding input is a filler id in both
    np.testing.assert_allclose(logits2[:, :-1], want["logits2"][:, :-1],
                               atol=5e-4, rtol=1e-4)
    # causal, the module too: id 15 moves the module from position 14 on
    # (it reads the embedding one on) and the stack from 15 on
    ids = batch["input_ids"]
    moved = {**batch, "input_ids": ids.at[:, 15].set((ids[:, 15] + 1)
                                                     % VOCAB)}
    _, (_, after, after2) = step(params, moved)[0]
    np.testing.assert_allclose(after[:, :15], logits[:, :15], atol=1e-5)
    np.testing.assert_allclose(after2[:, :14], logits2[:, :14], atol=1e-5)
    assert rel(after[:, 15:], logits[:, 15:]) > 1e-3
    assert rel(after2[:, 14], logits2[:, 14]) > 1e-3


def test_both_losses_their_sum_and_every_leaf_against_the_reference(
        toy, step, want):
    _, _, params, batch = toy
    (loss, (metrics, _, _)), grads = step(params, batch)
    assert set(metrics) == {
        "loss", "main_loss", "mtp_loss", "mtp_positions",
        "moe_assignments", "moe_load_max_over_mean"}
    # a row's last position has no next id, its last two none after it
    assert want["counts"] == (2.0 * (SEQ - 1), 2.0 * (SEQ - 2))
    assert float(metrics["mtp_positions"]) == 2 * (SEQ - 2)
    assert abs(metrics["main_loss"] - want["main"]) < TOL * want["main"]
    assert abs(metrics["mtp_loss"] - want["mtp"]) < TOL * want["mtp"]
    # (s1 + 0.3 s2 n1 / n2) / n1 of the reference's blocks of one row is
    # L1 + 0.3 L2
    assert abs(want["loss"] - (want["main"] + 0.3 * want["mtp"])) \
        < TOL * want["loss"]
    assert abs(loss - want["loss"]) < TOL * abs(want["loss"])
    assert comparisons.worst_leaf_gap(
        comparisons.leaf_norms(grads),
        comparisons.leaf_norms(want["grads"])) < 5e-4
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(want["grads"])):
        assert float(jnp.abs(a - b).max()) \
            < 2e-3 * float(jnp.abs(b).max()) + 1e-7, \
            jax.tree_util.keystr(path)
    # five expert layers' loads, the module's among them
    assert 0 < float(metrics["moe_assignments"]) <= 3 * 2 * SEQ * 4


def test_bfloat16_stays_inside_its_tolerance(toy, want):
    task, model, params, batch = toy
    (loss, (metrics, _, _)), grads = jit_once(jax.value_and_grad(
        program(task, model, Policy.bf16()), has_aux=True))(params, batch)
    for name, key in (("loss", "loss"), ("main_loss", "main"),
                      ("mtp_loss", "mtp")):
        assert abs(metrics[name] - want[key]) < BF16_LOSS * want[key], name
    assert comparisons.worst_leaf_gap(
        comparisons.leaf_norms(grads),
        comparisons.leaf_norms(want["grads"])) < BF16_LEAF


def test_the_shared_leaves_take_the_sum_of_both_readings(toy, step):
    """The head and the embedding are read by the stack and by the
    module: their gradient is what each loss gives alone, added at the
    weight; the module's loss reaches the stack through ``x`` (no
    stop-gradient), and the next-token loss never reaches the module."""
    task, model, params, batch = toy

    def one_loss(name):
        return jax.grad(lambda p: task.loss_and_metrics(
            model, p, batch, policy=FP32)[1][name])

    main, ahead = jit_once(lambda p: (
        one_loss("main_loss")(p), one_loss("mtp_loss")(p)))(params)
    grads = step(params, batch)[1]
    for path in (("head", "w"), ("embed", "embed")):
        g, a, b = (t[path[0]][path[1]] for t in (grads, main, ahead))
        assert rel(g, a + 0.3 * b) < TOL
        # each reading alone is a part that cannot be left out
        assert rel(g, a) > 0.02 and rel(g, 0.3 * b) > 0.02
    assert all(float(jnp.abs(x).max()) == 0.0
               for x in jax.tree.leaves(main["mtp"]))
    stack_signal = rel(grads["layers"]["00_mla"]["mixer"]["kv_b"]["w"],
                       main["layers"]["00_mla"]["mixer"]["kv_b"]["w"])
    # what a stop-gradient on x would take away
    assert stack_signal > 100 * TOL


# --- planted faults ----------------------------------------------------------


def test_the_sound_short_model_meets_the_reference(short, short_want):
    assert miss(short_losses(*short), short_want) < TOL


def test_an_unrotated_shared_key_fails_the_tolerance(
        short, short_want, monkeypatch):
    turn = hybrid_lm.head_norm_rotary
    monkeypatch.setattr(
        hybrid_lm, "head_norm_rotary",
        lambda x, heads, **kw: x if heads == 1 else turn(x, heads, **kw))
    assert miss(short_losses(*short), short_want) > 10 * TOL


def test_rotated_nope_channels_fail_the_tolerance(
        short, short_want, monkeypatch):
    """The tables over a head's first channels too, as a partial rotary
    of a plain attention would turn them."""
    from perceiver_tpu.ops.fourier import rope_apply

    queries = hybrid_lm._mla_queries

    def planted(params, a, num_heads, nope, rope, norm_eps, policy):
        q = queries(params, a, num_heads, nope, rope, norm_eps, policy)
        return rope_apply(q, *rope, num_heads)

    monkeypatch.setattr(hybrid_lm, "_mla_queries", planted)
    assert miss(short_losses(*short), short_want) > 10 * TOL


def test_a_module_fed_the_same_id_fails_the_tolerance(
        short, short_want, monkeypatch):
    """``E[t_i]`` in ``E[t_(i+1)]``'s place."""
    sound = hybrid_lm.HybridLM.prediction_states
    monkeypatch.setattr(
        hybrid_lm.HybridLM, "prediction_states",
        lambda self, params, ids, next_ids, **kw: sound(
            self, params, ids, ids, **kw))
    got = short_losses(*short)
    assert abs(got[0] - short_want[0]) < TOL * short_want[0]   # L1 stands
    assert miss(got, short_want) > 10 * TOL


@pytest.mark.parametrize("fault", ["next_id_targets", "own_head"])
def test_a_wrong_second_reading_fails_the_tolerance(
        short, short_want, monkeypatch, fault):
    """The module scored against ``t_(i+1)``, the stack's targets, in
    ``t_(i+2)``'s place; or read through a head of its own."""
    read, calls = task_module.fused_linear_nll, []

    def planted(head, hidden, labels, **kw):
        calls.append(labels)
        if len(calls) == 2 and fault == "next_id_targets":
            labels = calls[0]
        if len(calls) == 2 and fault == "own_head":
            head = {"w": head["w"][:, ::-1]}
        return read(head, hidden, labels, **kw)

    monkeypatch.setattr(task_module, "fused_linear_nll", planted)
    got = short_losses(*short)
    assert len(calls) == 2
    assert abs(got[0] - short_want[0]) < TOL * short_want[0]
    assert miss(got, short_want) > 10 * TOL


@pytest.mark.parametrize("fields", [
    {"mtp_loss_weight": 1.0}, {"routed_scaling_factor": 1.0}])
def test_a_wrong_number_fails_the_tolerance(short, short_want, fields):
    """The module's loss at the weight 1, the chosen experts' weights
    unscaled."""
    task, _, params, batch = short
    task = dataclasses.replace(task, **fields)
    assert miss(short_losses(task, task.build(), params, batch),
                short_want) > 10 * TOL


def test_the_nope_widths_scale_fails_the_tolerance(
        short, short_want, monkeypatch):
    """``1 / sqrt(192)``: the softmax scale is the whole score head's,
    nope and rope channels together."""
    core = hybrid_lm.mha_apply
    monkeypatch.setattr(
        hybrid_lm, "mha_apply",
        lambda *args, q_heads, **kw: core(
            *args, q_heads=q_heads * (16 / 12) ** 0.5, **kw))
    assert miss(short_losses(*short), short_want) > 10 * TOL


# --- remat and the trainer's lines -------------------------------------------


def test_remat_counts_the_modules_layers(toy):
    task, _, params, batch = toy
    model = dataclasses.replace(task, remat=True).build()
    with remat.remat_keeps() as choices, \
            hybrid_lm.prediction_modules.counting() as modules, \
            attention.attention_paths() as paths:
        text = jit_once(lambda p: task.loss_and_metrics(
            model, p, batch, policy=FP32)[0]).lower(params).as_text(
                debug_info=True)
    (choice,) = choices
    assert choice["kept"] == remat.HYBRID_REMAT_NAMES
    rows = 2 * SEQ
    # three latent layers' queries and three shared experts' gate and
    # up, the module's among them; six layers' inputs
    assert choice["bytes"]["qkv"] == 3 * 4 * rows * 4 * 16
    assert choice["bytes"]["mlp_hidden"] == 3 * 4 * rows * 2 * 24
    assert choice["bytes"]["layer_in"] == 6 * 4 * rows * 48
    assert dict(modules) == {
        "depth 1, loss weight 0.3, the stack's head and embedding": 1}
    assert paths[("latent", "12+4r|16 query latent")] == 3
    for scope in ("/mtp/checkpoint/mla_mixer/attn_proj/",
                  "/mtp/checkpoint/moe/", "/loss/mtp_loss/loss/",
                  "/hybrid_stack/checkpoint/mla_mixer/"):
        assert scope in text, scope
    # the module is no layer of the stack
    assert "/hybrid_stack/mtp/" not in text


# --- the normal entry point --------------------------------------------------


def test_the_script_trains_the_stack_with_its_module(tmp_path, capfd):
    """``scripts/hybrid_lm.py fit`` with the tiny YAML, cut to one
    published layer and the module, through ``Trainer.fit()``: what the
    trainer says while the step is loaded, and the step line's
    counters."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import hybrid_lm as cli

    cli.main([
        "fit", "--config", os.path.join(
            ROOT, "scripts", "configs", "glm_moe_lite_lm_1chip.yaml"),
        "--model.hybrid_override_pattern=AE",
        "--data.max_seq_len=40", "--data.batch_size=8",
        "--data.vocab_size=300", "--trainer.fast_dev_run=true",
        "--trainer.accelerator=cpu", "--trainer.precision=32",
        f"--trainer.default_root_dir={tmp_path}"])
    out, err = capfd.readouterr()
    assert "[step_load] attention call sites: latent[12+4r|16 query " \
        "latent]=2 materialized[backend]=2" in err, err
    assert "sigmoid top 4 renormalised=2 weights x1.8=2" in err
    assert "[step_load] prediction modules: depth 1, loss weight 0.3, " \
        "the stack's head and embedding=1" in err
    for counter in ("main_loss=", "mtp_loss=", "mtp_positions="):
        assert counter in out + err, counter
