"""A ``qwen3_next`` stack on ``models/hybrid_lm.py`` (pattern
``LELELE*E`` a published period): the gated attention (the query beside
its gate, partial rotary positions, zero-centred q/k norms) and the
gated shared expert under its sigmoid gate against the plain
reference's, each part shown to matter; the zero-centred norm and the
partial rotary tables alone; the share tied to the model (16 shares of
the experts add up to the uncut layer, the shared expert once); the
stack's logits, loss and gradients leaf by leaf; what ``remat`` keeps;
what the trainer says and logs through the normal entry point."""

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
from benchmarks.reference import gated_delta_lm as ref  # noqa: E402
from benchmarks.reference import perceiver_io as ref_steps  # noqa: E402
from benchmarks.tasks import causal_lm as bench_causal  # noqa: E402

import perceiver_tpu.ops.remat as remat  # noqa: E402
from perceiver_tpu.models import hybrid_lm  # noqa: E402
from perceiver_tpu.ops import delta_rule, moe  # noqa: E402
from perceiver_tpu.ops.fourier import rope_apply, rope_tables  # noqa: E402
from perceiver_tpu.ops.mlp import gated_mlp_apply  # noqa: E402
from perceiver_tpu.ops.norm import rms_norm_apply, rms_norm_init  # noqa: E402
from perceiver_tpu.ops.policy import Policy  # noqa: E402
from perceiver_tpu.tasks import HybridLMTask  # noqa: E402

FP32 = Policy.fp32()
TOY = dict(
    vocab_size=256, hidden_size=48, hybrid_override_pattern="LELE*E",
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, delta_chunk_size=16,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    rope_theta=1e4, partial_rotary_factor=0.25, qk_norm=True,
    attn_output_gate=True, n_routed_experts=16, num_experts_per_tok=3,
    moe_intermediate_size=40, moe_shared_expert_intermediate_size=24,
    routed_scaling_factor=1.0, router_scoring="softmax",
    norm_topk_prob=True, gated_experts=True, shared_expert_kind="gated",
    norm_eps=1e-6, zero_centered_norms=True, max_seq_len=40, held_experts=4,
    first_expert=4, ce_chunk_size=64)


def rel(a, b):
    return float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-30)


@pytest.fixture(scope="module")
def toy():
    task = HybridLMTask(**TOY)
    model = task.build()
    params = weights.make_weights(
        jax.eval_shape(model.init, jax.random.key(0)), 42)
    # the mixer's own norm scale is drawn as ones: move it
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
    w = jax.random.normal(jax.random.key(6), a.shape)
    return model, p, a, w


def assert_same_with_gradient(got_fn, want_fn, p, a, w, tol=2e-5):
    def both(fn):    # value, output and gradients: one program a side
        def weighted(p, a):
            out = fn(p, a)
            return (out * w).sum(), out
        return jit_once(jax.value_and_grad(weighted, argnums=(0, 1),
                                          has_aux=True))(p, a)

    ((got, got_out), got_g), ((want, want_out), want_g) = \
        both(got_fn), both(want_fn)
    assert abs(got - want) < tol * abs(want) + 1e-6
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(got_g)[0],
                            jax.tree.leaves(want_g)):
        assert rel(g, r) < 10 * tol, jax.tree_util.keystr(path)
    assert rel(got_out, want_out) < tol


# --- the tree ----------------------------------------------------------------


def test_the_tree_is_the_patterns(toy):
    _, model, params, _ = toy
    assert list(params["layers"]) == ref.layer_names(TOY) == [
        "00_delta", "01_moe", "02_delta", "03_moe", "04_attn", "05_moe"]
    names = {str(getattr(p[-1], "key", p[-1])) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    assert names == {"w", "scale", "bias", "embed"}   # what weights._leaf knows
    # every norm of the stack is zero-centred; the mixer's own is not
    assert set(params["norm"]) == {"bias"}
    assert set(params["layers"]["00_delta"]["norm"]) == {"bias"}
    assert set(params["layers"]["00_delta"]["mixer"]["norm"]) == {"scale"}
    attn = params["layers"]["04_attn"]["mixer"]
    assert attn["q"]["w"].shape == (48, 4 * 2 * 16)    # query beside gate
    assert attn["k"]["w"].shape == (48, 2 * 16)
    assert attn["q_norm"]["bias"].shape == attn["k_norm"]["bias"].shape \
        == (16,)
    experts = params["layers"]["01_moe"]["mixer"]
    assert set(experts) == {"router", "experts", "shared", "shared_gate"}
    assert set(experts["shared"]) == {"gate", "up", "down"}
    assert experts["shared_gate"]["w"].shape == (48, 1)
    assert experts["experts"]["gate"]["w"].shape == (4, 48, 40)
    init = jit_once(model.init)(jax.random.key(3))
    assert jax.tree.structure(init) == jax.tree.structure(params)
    np.testing.assert_array_equal(init["norm"]["bias"], 0.0)


@pytest.mark.parametrize("fields,message", [
    ({"hybrid_override_pattern": "LXE"}, "one of"),
    ({"linear_num_key_heads": 0}, "a pattern with L needs"),
    ({"linear_num_value_heads": 3}, "a multiple of the key heads"),
    ({"shared_expert_kind": "swish"}, "shared expert"),
])
def test_a_pattern_that_cannot_be_built_is_refused(fields, message):
    with pytest.raises(ValueError, match=message):
        HybridLMTask(**{**TOY, **fields}).build().init(jax.random.key(0))


# --- the parts ---------------------------------------------------------------


def test_the_zero_centred_norm():
    x = jax.random.normal(jax.random.key(0), (3, 7, 12)) * 3.0
    w = 0.1 * jax.random.normal(jax.random.key(1), (12,))
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (1 + w)
    np.testing.assert_allclose(
        rms_norm_apply({"bias": w}, x, 1e-6, FP32), want, rtol=1e-5,
        atol=1e-6)
    np.testing.assert_allclose(ref.rms_norm(w, x, 1e-6), want, rtol=1e-5,
                               atol=1e-6)
    # at its initial 0 it is the scale form at its initial 1
    assert set(rms_norm_init(12, zero_centered=True)) == {"bias"}
    np.testing.assert_array_equal(
        rms_norm_apply(rms_norm_init(12, zero_centered=True), x, 1e-6, FP32),
        rms_norm_apply(rms_norm_init(12), x, 1e-6, FP32))


@pytest.mark.parametrize("turned", [4, 8, 16])
def test_partial_rotary_turns_the_first_channels_only(turned):
    x = jax.random.normal(jax.random.key(turned), (2, 9, 3 * 16))
    cos, sin = rope_tables(9, turned, 1e4)
    assert cos.shape == (9, turned)
    got = rope_apply(x, cos, sin, 3).reshape(2, 9, 3, 16)
    want = ref.rope(x.reshape(2, 9, 3, 16), 1e4, turned)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(got[..., turned:],
                                  x.reshape(2, 9, 3, 16)[..., turned:])
    # position 0 turns nothing; a later one turns what it should
    np.testing.assert_allclose(got[:, 0], x.reshape(2, 9, 3, 16)[:, 0],
                               atol=1e-6)
    assert rel(got[:, 5, :, :turned],
               x.reshape(2, 9, 3, 16)[:, 5, :, :turned]) > 0.1


def test_the_gated_attention_against_the_reference(toy):
    model, p, a, w = mixer_case(toy, "04_attn")
    rope = tuple(jnp.asarray(t) for t in rope_tables(40, 4, 1e4))
    assert_same_with_gradient(
        lambda p, a: hybrid_lm.rotary_gqa_apply(
            p, a, num_heads=4, num_kv_heads=2, policy=FP32, rope=rope,
            norm_eps=1e-6, output_gate=True),
        lambda p, a: ref.attention_layer(p, a, TOY, "f32"), p, a, w)


@pytest.mark.parametrize("change", ["gate", "partial_rotary", "rope_theta",
                                    "q_norm", "k_norm"])
def test_every_part_of_the_gated_attention_moves_it(toy, change):
    """The gate, how many channels turn and how fast, each norm's
    weight: a reference without one of them is another function."""
    _, p, a, _ = mixer_case(toy, "04_attn")
    want = ref.attention_layer(p, a, TOY, "f32")
    cfg, other = dict(TOY), jax.tree.map(lambda x: x, p)
    if change == "gate":       # a head's gate: the second half of its 32
        cols = (jnp.arange(4 * 32) % 32) >= 16
        other["q"]["w"] = jnp.where(cols, 0.0, p["q"]["w"])
    elif change == "partial_rotary":
        cfg["partial_rotary_factor"] = 1.0
    elif change == "rope_theta":
        cfg["rope_theta"] = 1e2
    else:
        other[change]["bias"] = p[change]["bias"] + 1.0
    assert rel(ref.attention_layer(other, a, cfg, "f32"), want) > 0.01


def test_the_gate_is_applied_outside_the_core(toy):
    """``o * sigmoid(gate)`` under ``attn_proj`` / ``attn_gate``; the
    core's operations carry neither the gate nor its scope."""
    model, p, a, _ = mixer_case(toy, "04_attn")
    rope = tuple(jnp.asarray(t) for t in rope_tables(40, 4, 1e4))
    text = jit_once(lambda p, a: hybrid_lm.rotary_gqa_apply(
        p, a, num_heads=4, num_kv_heads=2, policy=FP32, rope=rope,
        output_gate=True)).lower(p, a).compile().as_text()
    gated = [ln for ln in text.splitlines() if "attn_gate" in ln]
    assert gated and all("attn_proj/attn_gate" in ln for ln in gated)
    assert not any("attn_core" in ln for ln in gated)
    assert any("logistic" in ln or "exponential" in ln for ln in gated)


def test_the_gated_shared_expert_against_the_reference(toy):
    """The whole expert layer: softmax router, the held share of the
    gated experts, and the shared expert under its sigmoid gate."""
    model, p, a, w = mixer_case(toy, "01_moe")
    with moe.moe_kinds.counting() as kinds:
        assert_same_with_gradient(
            lambda p, a: moe.moe_apply(
                p, a, top_k=3, first_expert=4, scoring="softmax",
                policy=FP32)[0],
            lambda p, a: ref.expert_layer(p, a, TOY, "f32"), p, a, w)
    assert kinds["gated shared expert under a sigmoid gate"]
    assert kinds["gated silu x3 products"]
    assert kinds["softmax top 3 renormalised"]
    assert not kinds["shared expert"] and not kinds["no shared expert"]
    # the shared part alone is the formula
    flat = a.reshape(-1, 48)
    shared = gated_mlp_apply(p["shared"], flat, FP32) \
        * jax.nn.sigmoid(flat @ p["shared_gate"]["w"])
    np.testing.assert_allclose(ref.shared_expert(p, flat, "f32"), shared,
                               rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("change", ["shared_gate", "shared.gate",
                                    "shared.up", "shared.down"])
def test_every_part_of_the_shared_expert_moves_the_layer(toy, change):
    _, p, a, _ = mixer_case(toy, "01_moe")
    want = ref.expert_layer(p, a, TOY, "f32")
    other = jax.tree.map(lambda x: x, p)
    node = other
    for key in change.split("."):
        node = node[key]
    node["w"] = node["w"] * 0.5
    assert rel(ref.expert_layer(other, a, TOY, "f32"), want) > 0.01


def test_the_relu2_shared_expert_is_still_the_trees():
    """A tree with ``shared`` and no ``shared_gate`` is the relu-squared
    shared expert it was (``nemotron_h``), and says so."""
    p = moe.moe_init(jax.random.key(0), 24, num_experts=8, held_experts=8,
                     expert_hidden=16, shared_hidden=32)
    assert set(p["shared"]) == {"up", "down"} and "shared_gate" not in p
    with moe.moe_kinds.counting() as kinds:
        moe.moe_apply(p, jnp.ones((1, 4, 24)), top_k=2, policy=FP32)
    assert kinds["shared expert"] == 1
    assert not kinds["gated shared expert under a sigmoid gate"]


# --- the share tied to the model ---------------------------------------------


@pytest.mark.parametrize("held", [1, 2, 4])
def test_sixteen_shares_add_up_to_the_uncut_layer(held):
    """Every chip of 16 holds 1 of 16 experts (or 8 chips 2, 4 chips
    4): the routed parts all the shares give, with the shared expert
    and its gate counted once, are the uncut reference's layer."""
    whole = weights.make_weights(jax.eval_shape(
        lambda: moe.moe_init(jax.random.key(0), 48, num_experts=16,
                             held_experts=16, expert_hidden=40,
                             shared_hidden=24, gated=True,
                             shared_kind="gated")), 5)
    a = jax.random.normal(jax.random.key(2), (2, 40, 48))
    uncut = ref.expert_layer(whole, a, {**TOY, "first_expert": 0}, "f32")
    flat = a.reshape(-1, 48)
    shared = ref.shared_expert(whole, flat, "f32").reshape(a.shape)
    routed, loads = 0.0, 0
    # the share's first expert is an operand: one program for all shares
    share = jit_once(lambda part, first: moe.moe_apply(
        part, a, top_k=3, first_expert=first, scoring="softmax",
        policy=FP32))
    for first in range(0, 16, held):
        part = {**whole, "experts": jax.tree.map(
            lambda x: x[first:first + held], whole["experts"])}
        out, load = share(part, first)
        assert load.shape == (held,)
        routed, loads = routed + (out - shared), loads + int(load.sum())
        # the reference is given the same share
        np.testing.assert_allclose(
            out, ref.expert_layer(part, a, {**TOY, "first_expert": first},
                                  "f32"), rtol=2e-4, atol=2e-5)
    assert loads == 80 * 3          # every assignment on some chip, once
    assert rel(routed + shared, uncut) < 2e-5
    # and a share alone is not the layer: the absent experts are left out
    assert rel(out, uncut) > 0.05


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
    # the counters of a softmax-routed stack
    assert set(metrics) == {"loss", "moe_assignments",
                            "moe_load_max_over_mean",
                            "moe_full_buffer_layers"}
    assert 0 < float(metrics["moe_assignments"]) <= 3 * 80 * 3
    assert float(metrics["moe_full_buffer_layers"]) == 0.0


def test_a_sigmoid_routed_stack_logs_no_full_buffer_counter():
    """``nemotron_h``'s step is what it was: the counter is a softmax
    router's."""
    task = HybridLMTask(
        vocab_size=64, hidden_size=16, hybrid_override_pattern="ME",
        mamba_num_heads=2, mamba_head_dim=8, n_groups=1, ssm_state_size=8,
        chunk_size=8, num_attention_heads=2, num_key_value_heads=1,
        head_dim=8, n_routed_experts=4, num_experts_per_tok=2,
        moe_intermediate_size=8, moe_shared_expert_intermediate_size=8,
        max_seq_len=16)
    model = task.build()
    params = jax.eval_shape(model.init, jax.random.key(0))
    metrics = jax.eval_shape(lambda p: task.loss_and_metrics(
        model, p, {"input_ids": jnp.zeros((1, 16), jnp.int32)},
        policy=FP32)[1], params)
    assert set(metrics) == {"loss", "moe_assignments",
                            "moe_load_max_over_mean"}


def test_a_batch_may_name_each_expert_layers_share(toy):
    task, model, params, batch = toy
    firsts = jnp.asarray([[0, 8, 12]] * 2, jnp.int32)
    got = jit_once(lambda p, b: task.loss_and_metrics(
        model, p, b, policy=FP32)[0])(
            params, {**batch, "first_experts": firsts})
    rb = bench_causal.reference_batches(
        [{"input_ids": np.asarray(batch["input_ids"])}], TOY, 0, 1)[0]
    loss_sum = jit_once(lambda p, b: ref.loss_sum(p, b, TOY, "f32"))
    s, n = loss_sum(params, {**rb, "first_experts": firsts})
    assert abs(got - s / n) < 2e-5 * float(s / n)
    s0, n0 = loss_sum(params, rb)
    assert abs(s / n - s0 / n0) > 1e-4      # another share, another loss


# --- remat -------------------------------------------------------------------


def test_remat_names_what_a_linear_layer_makes(toy):
    task, _, params, batch = toy
    model = dataclasses.replace(task, remat=True).build()
    with remat.remat_keeps() as choices, \
            delta_rule.rule_paths.counting() as rules:
        loss = jit_once(lambda p: task.loss_and_metrics(
            model, p, batch, policy=FP32)[0]).lower(params)
    del loss
    assert dict(rules) == {"chunked[16x3+pad,4 heads a pass]": 2}
    (choice,) = choices
    assert choice["kept"] == remat.HYBRID_REMAT_NAMES
    assert remat.HYBRID_REMAT_NAMES[3:] == (
        "ssm_out", "ssm_in", "delta_out", "delta_in", "moe_plan")
    rows = 2 * 40
    # the rule's output (value heads x their width) and the q/k/v/z
    # product, float32 here, two linear layers
    assert choice["bytes"]["delta_out"] == 2 * 4 * rows * 4 * 16
    assert choice["bytes"]["delta_in"] == 2 * 4 * rows * (2 * 16 + 2 * 64)
    assert choice["bytes"]["ssm_out"] == 0
    # the gated attention's wide q projection is its qkv
    assert choice["bytes"]["qkv"] == 4 * rows * 4 * 2 * 16


def test_remat_gradients_are_the_plain_ones(toy, step):
    task, _, params, batch = toy
    model = dataclasses.replace(task, remat=True).build()
    kept, kept_g = jit_once(jax.value_and_grad(lambda p: task.loss_and_metrics(
        model, p, batch, policy=FP32)[0]))(params)
    (plain, _), plain_g = step(params, batch["input_ids"])
    assert abs(plain - kept) < 1e-6 * abs(plain)
    for a, b in zip(jax.tree.leaves(kept_g), jax.tree.leaves(plain_g)):
        assert rel(a, b) < 1e-5


# --- the normal entry point --------------------------------------------------


def test_the_script_trains_the_pattern(tmp_path, capfd):
    """``scripts/hybrid_lm.py fit`` with the tiny YAML, cut to one layer
    of each kind (``LE*E``, as the Kimi file cuts its own: what a layer
    costs here is its compile, and the trainer says the same of one
    linear layer as of three) through ``Trainer.fit()``, and what the
    trainer says while the step is loaded."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import hybrid_lm as cli

    cli.main([
        "fit", "--config",
        os.path.join(ROOT, "scripts", "configs", "gated_delta_lm_1chip.yaml"),
        "--model.hybrid_override_pattern=LE*E",
        "--data.max_seq_len=40", "--data.batch_size=8",
        "--data.vocab_size=300", "--trainer.fast_dev_run=true",
        "--trainer.accelerator=cpu", "--trainer.precision=32",
        f"--trainer.default_root_dir={tmp_path}"])
    out, err = capfd.readouterr()
    assert "[step_load] delta rules: chunked[40x1,4 heads a pass]=1" in err, \
        err
    assert "[step_load] expert kinds: gated shared expert under a sigmoid " \
        "gate=2 gated silu x3 products=2 softmax top" in err
    assert re.search(r"remat keeps: \S*delta_out,delta_in,moe_plan \+ "
                     r"layer_in", err), err
    assert "[step_load] short convolutions: xla[256ch, norm 128, backend]=1" \
        in err
    assert "selective scans" not in err
    assert re.search(r"\[step 1\] loss=\d+\.\d+ .*moe_assignments=", out + err)
    assert "moe_full_buffer_layers" in out + err
    assert not delta_rule.rule_paths._open
