"""A ``kimi_linear`` stack on ``models/hybrid_lm.py`` (published layers
``KD KE AE``-like patterns): latent attention expanded against the plain
reference's from the latent, and padded to whole lanes against
unpadded; the dense layer and the shared expert without a gate column;
the share tied to the model (32 shares of the experts add up to the
uncut layer, the shared expert once); the stack's logits, loss and
gradients leaf by leaf, each planted fault failing a stated tolerance;
what ``remat`` keeps and what the trainer says through the normal entry
point."""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import comparisons, weights  # noqa: E402
from benchmarks.reference import kimi_linear_lm as ref  # noqa: E402
from benchmarks.reference import perceiver_io as ref_steps  # noqa: E402
from benchmarks.tasks import causal_lm as bench_causal  # noqa: E402

import perceiver_tpu.ops.remat as remat  # noqa: E402
from perceiver_tpu.models import hybrid_lm  # noqa: E402
from perceiver_tpu.ops import attention, delta_rule, moe  # noqa: E402
from perceiver_tpu.ops.policy import Policy  # noqa: E402
from perceiver_tpu.tasks import HybridLMTask  # noqa: E402

FP32 = Policy.fp32()
TOY = dict(
    vocab_size=256, hidden_size=48, hybrid_override_pattern="KDKEAE",
    kda_num_heads=4, kda_head_dim=8, kda_conv_kernel_size=4,
    delta_chunk_size=16, num_attention_heads=4, num_key_value_heads=4,
    head_dim=12, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, intermediate_size=64, n_routed_experts=32,
    num_experts_per_tok=4, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=24, routed_scaling_factor=2.446,
    router_scoring="sigmoid", norm_topk_prob=True, gated_experts=True,
    shared_expert_kind="glu", norm_eps=1e-5, max_seq_len=40, held_experts=4,
    first_expert=8, ce_chunk_size=64)
MLA = dict(num_heads=4, kv_lora_rank=16, qk_nope_head_dim=8)
# float32 against the float32 reference: two arrangements of the same
# sums (chunks for positions, expanded heads for the latent); measured
# 1e-6 to 4e-6 on these shapes
TOL = 2e-5


def rel(a, b):
    return float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-30)


@pytest.fixture(scope="module")
def toy():
    task = HybridLMTask(**TOY)
    model = task.build()
    params = weights.make_weights(
        jax.eval_shape(model.init, jax.random.key(0)), 42)
    # the norms' scales are drawn as ones: move them
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 + 0.1 * jax.random.normal(
            jax.random.key(x.size), x.shape))
        if path[-1].key == "scale" else x, params)
    ids = jax.random.randint(jax.random.key(1), (2, 40), 0, 256)
    return task, model, params, {"input_ids": ids}


@pytest.fixture(scope="module")
def step(toy):
    """``(params, ids) -> ((loss, (metrics, logits)), gradients)``, the
    one jitted program the stack's cases share: the logits ride beside
    the loss, the row of tokens is an operand."""
    task, model, _, _ = toy

    def loss_metrics_logits(p, ids):
        loss, metrics = task.loss_and_metrics(model, p, {"input_ids": ids},
                                              policy=FP32)
        return loss, (metrics, model.apply(p, ids, policy=FP32))

    return jit_once(jax.value_and_grad(loss_metrics_logits, has_aux=True))


def mixer_case(toy, name):
    _, model, params, _ = toy
    p = params["layers"][name]["mixer"]
    a = jax.random.normal(jax.random.key(5), (2, 40, TOY["hidden_size"]))
    return model, p, a, jax.random.normal(jax.random.key(6), a.shape)


def assert_same_with_gradient(got_fn, want_fn, p, a, w, tol=TOL):
    def both(fn):
        def weighted(p, a):
            out = fn(p, a)
            return (out * w).sum(), out
        return jit_once(jax.value_and_grad(weighted, argnums=(0, 1),
                                          has_aux=True))(p, a)

    ((got, got_out), got_g), ((want, want_out), want_g) = \
        both(got_fn), both(want_fn)
    assert rel(got_out, want_out) < tol
    assert abs(got - want) < tol * abs(want) + 1e-6
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(got_g)[0],
                            jax.tree.leaves(want_g)):
        assert rel(g, r) < 10 * tol, jax.tree_util.keystr(path)


# --- the tree ----------------------------------------------------------------


def test_the_tree_is_the_patterns(toy):
    _, model, params, _ = toy
    assert list(params["layers"]) == ref.layer_names(TOY) == [
        "00_kda", "01_mlp", "02_kda", "03_moe", "04_mla", "05_moe"]
    names = {str(getattr(p[-1], "key", p[-1])) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    # what weights._leaf knows
    assert names == {"w", "scale", "bias", "embed"}
    mla = params["layers"]["04_mla"]["mixer"]
    assert {n: x["w"].shape for n, x in mla.items() if "w" in x} == {
        "q": (48, 4 * 12), "kv_a": (48, 16 + 4), "kv_b": (16, 4 * 16),
        "out": (4 * 8, 48)}
    assert mla["kv_norm"]["scale"].shape == (16,)
    assert set(params["layers"]["01_mlp"]["mixer"]) == {"gate", "up", "down"}
    experts = params["layers"]["03_moe"]["mixer"]
    assert set(experts) == {"router", "experts", "shared"}   # no gate column
    assert set(experts["shared"]) == {"gate", "up", "down"}
    assert experts["router"]["w"].shape == (48, 32)
    assert experts["experts"]["gate"]["w"].shape == (4, 48, 24)
    assert jax.tree.structure(jax.eval_shape(
        model.init, jax.random.key(3))) == jax.tree.structure(params)


@pytest.mark.parametrize("fields,message", [
    ({"hybrid_override_pattern": "KXE"}, "K Kimi Delta Attention, A latent"),
    ({"kda_num_heads": 0}, "a pattern with K needs"),
    ({"kv_lora_rank": 0}, "a pattern with A needs"),
    ({"intermediate_size": 0}, "a pattern with D needs"),
    ({"shared_expert_kind": "swish"}, "shared expert"),
])
def test_a_pattern_that_cannot_be_built_is_refused(fields, message):
    with pytest.raises(ValueError, match=message):
        HybridLMTask(**{**TOY, **fields}).build().init(jax.random.key(0))


def test_the_new_fields_default_to_no_such_layer():
    """A ``nemotron_h`` task is what it was: the defaults name no K, A
    or D layer, and a pattern without them needs none of the keys."""
    task = HybridLMTask()
    assert (task.kda_num_heads, task.kv_lora_rank, task.v_head_dim,
            task.intermediate_size) == (0, 0, 0, 0)
    assert not set("KAD") & set(task.hybrid_override_pattern)
    assert hybrid_lm.LAYER_KINDS == {
        "M": "ssm", "E": "moe", "*": "attn", "L": "delta", "K": "kda",
        "A": "mla", "D": "mlp"}


# --- latent attention --------------------------------------------------------


def test_expanded_latent_attention_against_the_latent_form(toy):
    """The program expands keys and values to the heads; the reference
    scores a head's query against the latent itself."""
    _, p, a, w = mixer_case(toy, "04_mla")
    with attention.attention_paths() as paths:
        assert_same_with_gradient(
            lambda p, a: hybrid_lm.mla_apply(p, a, **MLA, norm_eps=1e-5,
                                             policy=FP32),
            lambda p, a: ref.latent_attention(p, a, TOY, "f32"), p, a, w)
    assert paths[("two_widths", "12|8")] and paths[("materialized",
                                                    "backend")]


def test_padded_to_whole_lanes_is_unpadded(toy):
    """The fused kernels (interpreted here) take one width: score heads
    of 12 and value heads of 8 go in as heads of 128 lanes under the
    scale 1 / sqrt(12), and give what the materialized core gives at
    the widths as they are."""
    _, p, a, w = mixer_case(toy, "04_mla")
    a, w = a[:1], w[:1]
    with attention.attention_paths() as paths:
        assert_same_with_gradient(
            lambda p, a: hybrid_lm.mla_apply(p, a, **MLA, norm_eps=1e-5,
                                             policy=FP32, impl="flash"),
            lambda p, a: hybrid_lm.mla_apply(p, a, **MLA, norm_eps=1e-5,
                                             policy=FP32, impl="einsum"),
            p, a, w)
    assert paths[("two_widths", "12|8 as 128")] == 1
    assert paths[("two_widths", "12|8")] == 1


def test_the_latent_call_runs_under_its_scopes(toy):
    _, p, a, _ = mixer_case(toy, "04_mla")
    text = jit_once(lambda p, a: hybrid_lm.mla_apply(
        p, a, **MLA, policy=FP32)).lower(p, a).as_text(debug_info=True)
    assert "/mla_mixer/attn_proj/" in text
    assert "/mla_mixer/attn_core/" in text


# --- the dense layer and the experts -----------------------------------------


def test_the_dense_layer_against_the_reference(toy):
    from perceiver_tpu.ops.mlp import gated_mlp_apply
    _, p, a, w = mixer_case(toy, "01_mlp")
    assert_same_with_gradient(
        lambda p, a: gated_mlp_apply(p, a, FP32),
        lambda p, a: ref.dense_mlp(p, a, TOY, "f32"), p, a, w)


def expert_layer(p, a, first=8):
    return moe.moe_apply(p, a, top_k=4, first_expert=first, scaling=2.446,
                         scoring="sigmoid", policy=FP32)


def test_the_expert_layer_against_the_reference(toy):
    """The whole expert layer: sigmoid router, the top 4 renormalised
    and scaled, the held share of the gated experts, and the shared
    expert with no gate column."""
    _, p, a, w = mixer_case(toy, "03_moe")
    with moe.moe_kinds.counting() as kinds:
        assert_same_with_gradient(
            lambda p, a: expert_layer(p, a)[0],
            lambda p, a: ref.expert_layer(p, a, TOY, "f32"), p, a, w)
    assert set(kinds) == {
        "sigmoid top 4 renormalised", "gated silu x3 products",
        "gated shared expert, no gate column", "weights x2.446"}


@pytest.mark.parametrize("held", [1, 4])
def test_thirty_two_shares_add_up_to_the_uncut_layer(held):
    """Every chip of 32 holds 1 of 32 experts (or 8 chips 4): the routed
    parts all the shares give, with the shared expert counted once, are
    the uncut reference's layer (the guide's share test)."""
    whole = weights.make_weights(jax.eval_shape(
        lambda: moe.moe_init(jax.random.key(0), 48, num_experts=32,
                             held_experts=32, expert_hidden=24,
                             shared_hidden=24, gated=True,
                             shared_kind="glu")), 5)
    a = jax.random.normal(jax.random.key(2), (2, 40, 48))
    uncut = ref.expert_layer(whole, a, {**TOY, "first_expert": 0}, "f32")
    shared = ref.gated_mlp(*(whole["shared"][n]["w"] for n in
                             ("gate", "up", "down")),
                           a.reshape(-1, 48), "f32").reshape(a.shape)
    layer = jit_once(expert_layer)
    routed, loads = 0.0, 0
    for first in range(0, 32, held):
        part = {**whole, "experts": jax.tree.map(
            lambda x: x[first:first + held], whole["experts"])}
        out, load = layer(part, a, first)
        assert load.shape == (held,)
        routed, loads = routed + (out - shared), loads + int(load.sum())
    # the reference is given the same share
    np.testing.assert_allclose(
        out, ref.expert_layer(part, a, {**TOY, "first_expert": first},
                              "f32"), rtol=2e-4, atol=2e-5)
    assert loads == 80 * 4          # every assignment on some chip, once
    assert rel(routed + shared, uncut) < TOL
    # and a share alone is not the layer: the absent experts are left out
    assert rel(out, uncut) > 0.05


# --- planted faults ----------------------------------------------------------


@pytest.mark.parametrize("fault", ["bf16_g", "dropped_beta"])
def test_a_wrong_rule_fails_the_tolerance(toy, monkeypatch, fault):
    """The decay through bfloat16, or a write strength of 1: the KDA
    mixer then misses the reference by far more than ``TOL``, which the
    sound mixer meets (``tests/test_kda.py``)."""
    _, p, a, _ = mixer_case(toy, "00_kda")
    rule = delta_rule.delta_rule

    def planted(q, k, v, g, beta, **kw):
        if fault == "bf16_g":
            g = g.astype(jnp.bfloat16).astype(jnp.float32)
        else:
            beta = jnp.ones_like(beta)
        return rule(q, k, v, g, beta, **kw)

    def mixer(p, a):
        return jit_once(lambda p, a: delta_rule.kda_mixer_apply(
            p, a, num_heads=4, head_dim=8, chunk_size=16, eps=1e-5,
            policy=FP32))(p, a)

    want = jit_once(lambda p, a: ref.kda_mixer(p, a, TOY, "f32"))(p, a)
    assert rel(mixer(p, a), want) < TOL
    monkeypatch.setattr(delta_rule, "delta_rule", planted)
    assert rel(mixer(p, a), want) > 10 * TOL


def test_a_rotated_shared_key_fails_the_tolerance(toy, monkeypatch):
    """``mla_use_nope``: no channel carries a position. Rotary tables on
    the shared key channels give another layer."""
    from perceiver_tpu.ops.fourier import rope_apply, rope_tables
    _, p, a, _ = mixer_case(toy, "04_mla")
    tables = tuple(jnp.asarray(t) for t in rope_tables(40, 4, 1e4))
    core = hybrid_lm.mha_apply

    def planted(params, q, k, v, *, kv_heads, **kw):
        keys = kv_heads[0].reshape(2, 40, 4, 12)
        turned = rope_apply(keys[..., 8:].reshape(2, 40, -1), *tables, 4)
        keys = jnp.concatenate(
            [keys[..., :8], turned.reshape(2, 40, 4, 4)], -1)
        return core(params, q, k, v, kv_heads=(keys.reshape(2, 40, -1),
                                               kv_heads[1]), **kw)

    want = ref.latent_attention(p, a, TOY, "f32")
    monkeypatch.setattr(hybrid_lm, "mha_apply", planted)
    assert rel(hybrid_lm.mla_apply(p, a, **MLA, norm_eps=1e-5, policy=FP32),
               want) > 10 * TOL


def test_the_padded_widths_scale_fails_the_tolerance(toy, monkeypatch):
    """The kernels' own scale is 1 / sqrt(the width they see): on padded
    heads that is the padding's, not the score heads'."""
    import perceiver_tpu.ops.pallas_attention as kernels
    _, p, a, _ = mixer_case(toy, "04_mla")
    a = a[:1]
    fused = kernels.flash_attention_channels
    monkeypatch.setattr(
        kernels, "flash_attention_channels",
        lambda *args, scale=None, **kw: fused(*args, **kw))
    want = ref.latent_attention(p, a, TOY, "f32")
    assert rel(hybrid_lm.mla_apply(p, a, **MLA, norm_eps=1e-5, policy=FP32,
                                   impl="flash"), want) > 10 * TOL


def test_a_gate_column_on_the_shared_expert_fails_the_tolerance(toy):
    """``qwen3_next``'s shared expert is under ``sigmoid(a w_sg)``; this
    family's has no such column, and a tree that holds one is another
    layer."""
    _, p, a, _ = mixer_case(toy, "03_moe")
    want = ref.expert_layer(p, a, TOY, "f32")
    gated = {**p, "shared_gate": {"w": jax.random.normal(
        jax.random.key(9), (48, 1)) / 7.0}}
    assert rel(expert_layer(gated, a)[0], want) > 10 * TOL


# --- the stack ---------------------------------------------------------------


def test_logits_against_the_reference(toy, step):
    _, model, params, batch = toy
    ids = batch["input_ids"]
    got = step(params, ids)[0][1][1]
    want = jit_once(lambda p: ref.logits(p, ids, TOY))(params)
    assert got.shape == want.shape == (2, 40, 256)
    np.testing.assert_allclose(got, want, atol=5e-4, rtol=1e-4)
    # causal: a later token does not move an earlier position
    moved = ids.at[:, 30].set((ids[:, 30] + 1) % 256)
    after = step(params, moved)[0][1][1]
    np.testing.assert_allclose(after[:, :30], got[:, :30], atol=1e-5)
    assert rel(after[:, 30:], got[:, 30:]) > 1e-3


def test_loss_and_gradient_leaf_by_leaf_against_the_reference(toy, step):
    task, model, params, batch = toy
    (loss, (metrics, _)), grads = step(params, batch["input_ids"])
    rb = bench_causal.reference_batches(
        [{"input_ids": np.asarray(batch["input_ids"])}], TOY, 0, 1)[0]
    want_loss, want = ref_steps.loss_and_grads(
        params, rb, TOY, loss_sum=ref.loss_sum, block=1)
    assert abs(loss - want_loss) < 2e-5 * abs(want_loss)
    got_n, want_n = comparisons.leaf_norms(grads), \
        comparisons.leaf_norms(want)
    assert comparisons.worst_leaf_gap(got_n, want_n) < 5e-4
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(want)):
        assert float(jnp.abs(a - b).max()) \
            < 2e-3 * float(jnp.abs(b).max()) + 1e-7, \
            jax.tree_util.keystr(path)
    # a sigmoid router's counters: no full-buffer counter
    assert set(metrics) == {"loss", "moe_assignments",
                            "moe_load_max_over_mean"}
    assert 0 < float(metrics["moe_assignments"]) <= 2 * 80 * 4


def test_a_batch_may_name_each_expert_layers_share(toy):
    task, model, params, batch = toy
    firsts = jnp.asarray([[0, 20]] * 2, jnp.int32)
    got = jit_once(lambda p, b: task.loss_and_metrics(
        model, p, b, policy=FP32)[0])(
            params, {**batch, "first_experts": firsts})
    rb = bench_causal.reference_batches(
        [{"input_ids": np.asarray(batch["input_ids"])}], TOY, 0, 1)[0]
    loss_sum = jit_once(lambda p, b: ref.loss_sum(p, b, TOY, "f32"))
    s, n = loss_sum(params, {**rb, "first_experts": firsts})
    assert abs(got - s / n) < 2e-5 * float(s / n)
    s0, n0 = loss_sum(params, rb)
    assert abs(s / n - s0 / n0) > 1e-5      # another share, another loss


# --- remat -------------------------------------------------------------------


def test_remat_names_what_the_new_layers_make(toy):
    task, _, params, batch = toy
    model = dataclasses.replace(task, remat=True).build()
    with remat.remat_keeps() as choices, \
            delta_rule.rule_paths.counting() as rules:
        jit_once(lambda p: task.loss_and_metrics(
            model, p, batch, policy=FP32)[0]).lower(params)
    assert dict(rules) == {
        "chunked[16x3+pad,4 heads a pass, by channel]": 2}
    (choice,) = choices
    assert choice["kept"] == remat.HYBRID_REMAT_NAMES
    rows = 2 * 40
    # the rule's output, float32 here, two KDA layers; the q/k/v
    # product is made again (ops/delta_rule.py says why)
    assert choice["bytes"]["delta_out"] == 2 * 4 * rows * 4 * 8
    assert choice["bytes"]["delta_in"] == 0
    # the latent layer's q projection is its qkv; the dense layer's and
    # the two shared experts' gate and up are mlp_hidden
    assert choice["bytes"]["qkv"] == 4 * rows * 4 * 12
    assert choice["bytes"]["mlp_hidden"] == 4 * rows * 2 * (64 + 2 * 24)


# --- the normal entry point --------------------------------------------------


def test_the_script_trains_the_pattern(tmp_path, capfd):
    """``scripts/hybrid_lm.py fit`` with the tiny YAML, cut to one layer
    of each new kind (``KDAE``: the CPU compiles ten unrolled layers in
    over a minute) through ``Trainer.fit()``, and what the trainer says
    while the step is loaded."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import hybrid_lm as cli

    cli.main([
        "fit", "--config",
        os.path.join(ROOT, "scripts", "configs", "kimi_linear_lm_1chip.yaml"),
        "--model.hybrid_override_pattern=KDAE",
        "--data.max_seq_len=40", "--data.batch_size=8",
        "--data.vocab_size=300", "--trainer.fast_dev_run=true",
        "--trainer.accelerator=cpu", "--trainer.precision=32",
        f"--trainer.default_root_dir={tmp_path}"])
    out, err = capfd.readouterr()
    assert "[step_load] delta rules: chunked[40x1,4 heads a pass, by " \
        "channel]=1" in err, err
    assert "[step_load] short convolutions: xla[192ch, norm 128, backend]=1" \
        in err
    assert "two_widths[24|16]=1" in err
    assert "[step_load] expert kinds: gated shared expert, no gate " \
        "column=1 gated silu x3 products=1 sigmoid top" in err
    assert "weights x2.446=1" in err
    assert re.search(r"remat keeps: \S*delta_out,\S*moe_plan \+ layer_in",
                     err), err
    assert re.search(r"\[step 1\] loss=\d+\.\d+ .*moe_assignments=", out + err)
    assert not delta_rule.rule_paths._open
