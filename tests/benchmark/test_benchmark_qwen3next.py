"""The linear-attention mixture-of-experts cell, ``qwen3next_train``:
program against the plain reference at the configuration's rehearsal
widths on the benchmark's seeded weights (logits, the loss, the gradient
leaf by leaf), one rehearsal of the cell through ``run_cell`` with three
AdamW steps and the control, the shares a run holds, the faults the
limits are there for (a router or a decay in bfloat16, a dropped write
strength), the step's operation count and the rule's cost against hand
counts, the configuration file against the catalog row it was drawn
from, and the six readers."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import (  # noqa: E402
    comparisons,
    flops,
    harness,
    traffic,
    weights,
)
from benchmarks.layer_metrics import (  # noqa: E402
    block_diffusion_costs,
    gated_delta_costs as costs,
    hybrid_costs,
)
from benchmarks.reference import gated_delta_lm as ref  # noqa: E402
from benchmarks.reference import perceiver_io as ref_steps  # noqa: E402

from perceiver_tpu.ops.policy import Policy  # noqa: E402

SEED = 4_200_000_029
FP32 = Policy.fp32()
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"model.delta_mixer_pct", "model.delta_rule_pct",
               "delta_rule_roofline", "model.moe_pct.gdn",
               "model.moe_route_pct.gdn", "moe_gated_expert_roofline.gdn"}


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell("qwen3next_train")


@pytest.fixture(scope="module")
def toy(cell):
    cfg = harness.flat_config(cell.config, rehearse=True)
    bench_task = harness.load_task(cfg["task"])
    cls, kwargs = bench_task.program_task(cfg)
    task = cls(**kwargs)
    model = task.build()
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = weights.make_weights(shapes, SEED)
    # the mixer's own norm scale is drawn as ones: move it, so that a
    # scale read from the wrong place shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 + 0.1 * jax.random.normal(
            jax.random.key(x.size), x.shape))
        if path[-1].key == "scale" else x, params)
    batch = bench_task.make_batch(np.random.default_rng(7), 2, cfg)
    return cfg, bench_task, task, model, params, batch


def test_every_leaf_has_a_rule_and_the_sizes_are_the_toy_ones(toy):
    cfg, _, task, model, params, batch = toy
    assert cfg["hidden_size"] == 64
    assert cfg["hybrid_override_pattern"] == "LE*E"
    assert batch["input_ids"].shape == (2, cfg["max_seq_len"]) == (2, 72)
    assert batch["input_ids"].min() >= 0 and batch["input_ids"].max() < 512
    names = {str(getattr(p[-1], "key", p[-1])) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    assert names == {"w", "scale", "bias", "embed"}  # what weights._leaf knows
    assert list(params["layers"]) == ref.layer_names(cfg) == [
        "00_delta", "01_moe", "02_attn", "03_moe"]
    mixer = params["layers"]["00_delta"]["mixer"]
    assert mixer["in_proj_qkvz"]["w"].shape == (64, 2 * 32 + 2 * 64)
    assert mixer["conv"]["w"].shape == (4, 2 * 32 + 64)
    # weights.py's bias rule: A about 1, dt_bias about 0; a zero-centred
    # norm's parameter 0.02 wide about 0
    assert float(jnp.abs(mixer["A_log"]["bias"]).max()) < 0.1
    assert float(jnp.abs(mixer["dt"]["bias"]).max()) < 0.1
    norm = params["layers"]["00_delta"]["norm"]["bias"]
    assert 0.005 < float(jnp.std(norm)) < 0.04
    experts = params["layers"]["01_moe"]["mixer"]
    assert set(experts["experts"]) == {"gate", "up", "down"}
    assert experts["experts"]["up"]["w"].shape == (4, 64, 40)   # 4 of 16
    assert experts["shared_gate"]["w"].shape == (64, 1)
    assert params["layers"]["02_attn"]["mixer"]["q"]["w"].shape \
        == (64, 4 * 2 * 16)
    assert model.first_expert == 4 and model.num_held_experts == 4
    assert model.delta_chunk_size == 16 and model.partial_rotary_factor == .25


def test_logits_against_the_reference(toy):
    cfg, _, _, model, params, batch = toy
    ids = jnp.asarray(batch["input_ids"])
    logits = jax.jit(lambda p: model.apply(p, ids, policy=FP32))(params)
    want = jax.jit(lambda p: ref.logits(p, ids, cfg))(params)
    assert logits.shape == want.shape == (2, 72, 512)
    np.testing.assert_allclose(logits, want, atol=5e-4, rtol=1e-4)
    # the rotary base, the share of a head it turns and the gated norm
    # each move them: a reference without one of them shows
    for change in ({"rope_theta": 1e2}, {"partial_rotary_factor": 1.0},
                   {"first_expert": 0}):
        other = ref.logits(params, ids, {**cfg, **change})
        assert float(jnp.abs(other - want).max()) > 1e-3, change
    flat = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x)
        if "q_norm" in jax.tree_util.keystr(path) else x, params)
    assert float(jnp.abs(ref.logits(flat, ids, cfg) - want).max()) > 1e-4


def test_loss_and_gradient_leaf_by_leaf_against_the_reference(toy):
    cfg, bench_task, task, model, params, batch = toy
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: task.loss_and_metrics(model, p, batch, policy=FP32)[0]))(
            params)
    rb = bench_task.reference_batches([batch], cfg, 0, 1)[0]
    assert set(rb) == {"input_ids", "labels", "first_experts"}
    np.testing.assert_array_equal(rb["labels"][:, :-1],
                                  batch["input_ids"][:, 1:])
    assert (np.asarray(rb["labels"][:, -1]) == ref_steps.IGNORE).all()
    want_loss, want = ref_steps.loss_and_grads(
        params, rb, cfg, loss_sum=bench_task.loss_sum, block=1)
    assert abs(loss - want_loss) < 2e-5 * abs(want_loss)
    got_n, want_n = comparisons.leaf_norms(grads), \
        comparisons.leaf_norms(want)
    assert comparisons.worst_leaf_gap(got_n, want_n) < 5e-4
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(want)):
        assert float(jnp.abs(a - b).max()) \
            < 2e-3 * float(jnp.abs(b).max()) + 1e-7, \
            jax.tree_util.keystr(path)


def test_a_share_is_what_the_reference_is_given(toy):
    """The reference leaves out the absent experts as the program does:
    with another share of the same router the loss moves, and the
    program follows."""
    cfg, bench_task, task, model, params, batch = toy
    import dataclasses
    batch = {k: v for k, v in batch.items() if k != "first_experts"}
    rb = bench_task.reference_batches([batch], cfg, 5, 1)[0]
    assert "first_experts" not in rb

    def both(first):
        moved = dataclasses.replace(task, first_expert=first)
        got = jax.jit(lambda p: moved.loss_and_metrics(
            moved.build(), p, batch, policy=FP32)[0])(params)
        s, n = ref.loss_sum(params, rb, {**cfg, "first_expert": first},
                            "f32")
        return float(got), float(s / n)

    (got4, want4), (got0, want0) = both(4), both(0)
    assert abs(got4 - want4) < 2e-5 * want4
    assert abs(got0 - want0) < 2e-5 * want0
    assert abs(want4 - want0) > 1e-4


def test_every_batch_of_a_run_names_the_same_shares(cell, toy):
    cfg, bench_task = toy[:2]
    pool = traffic.train_batches(cell.mix["rehearsal"], cfg, SEED,
                                 bench_task.make_batch)
    firsts = pool[0]["first_experts"]
    assert firsts.shape == (2, 2) and firsts.dtype == np.int32
    assert set(firsts.ravel() % cfg["held_experts"]) == {0}
    assert firsts.max() <= cfg["n_routed_experts"] - cfg["held_experts"]
    for made in pool:
        np.testing.assert_array_equal(made["first_experts"], firsts)
    assert not np.array_equal(pool[0]["input_ids"], pool[1]["input_ids"])
    for rb, made in zip(bench_task.reference_batches(pool, cfg, 0, 3), pool):
        np.testing.assert_array_equal(rb["first_experts"],
                                      made["first_experts"])
    # the walk drew ahead from a copy: the pool is what the mix would
    # have made without it
    plain = traffic.train_batches(
        cell.mix["rehearsal"], {**cfg, "held_experts": 16}, SEED,
        bench_task.make_batch)
    for made, other in zip(pool, plain):
        np.testing.assert_array_equal(made["input_ids"], other["input_ids"])
        assert "first_experts" not in other


def test_the_shares_a_run_holds_get_the_even_load(toy):
    """``even_shares`` on the seed's own weights over a pool's batches,
    from the router's loads alone: the program's counter of held
    assignments on those batches lies nearer an even router's (tokens x
    top_k x held / experts a layer), on the batch that lies farthest
    off, than with the configuration's share, over seeds."""
    cfg, bench_task, task, model, _, _ = toy
    source = open(bench_task.__file__).read()
    assert "perceiver_tpu.ops" not in source and "usual_rows" not in source
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    layers = cfg["hybrid_override_pattern"].count("E")
    even = layers * 144 * cfg["num_experts_per_tok"] \
        * cfg["held_experts"] / cfg["n_routed_experts"]
    counted = jax.jit(lambda p, b: task.loss_and_metrics(
        model, p, b, policy=FP32)[1]["moe_assignments"])
    off_first, off_even = [], []
    for seed in range(40, 45):
        rng = np.random.default_rng(seed)
        pool = [traffic.zipf_ids(rng, 512, 0, (2, 72)) for _ in range(3)]
        params = weights.make_weights(shapes, seed)
        # states that share a direction, as a deep stack's do: the
        # router then favours a few experts and the shares differ
        params["embed"]["embed"] = params["embed"]["embed"] + 0.2
        firsts = bench_task.even_shares(params, pool, cfg)
        assert firsts.shape == (layers,) and set(firsts % 4) == {0}
        first, even_ = [], []
        for ids in pool:
            batch = {"input_ids": ids}
            first.append(float(counted(params, batch)))
            even_.append(float(counted(
                params, {**batch,
                         "first_experts": np.tile(firsts, (2, 1))})))
        off_first.append(np.abs(np.asarray(first) - even).max())
        off_even.append(np.abs(np.asarray(even_) - even).max())
    assert np.mean(off_even) < 0.7 * np.mean(off_first), (off_first,
                                                          off_even)
    assert max(off_even) < 0.3 * even, off_even


# --- the faults the limits are there for -------------------------------------


def test_bf16_router_scores_fail_where_float32_is_stated(toy):
    """The configuration states the router in float32. Rounded to
    bfloat16, near-ties flip top-k choices: against the float32
    reference the share of tokens whose chosen set changes is what the
    chip's limits must see."""
    cfg, _, _, _, params, _ = toy
    p = params["layers"]["01_moe"]["mixer"]
    a = jax.random.normal(jax.random.key(0), (4096, 64))
    exact = ref.router_weights(p, a, cfg, "f32") > 0
    low = ref.router_weights(p, a, cfg, "bf16") > 0
    assert (exact.sum(-1) == 3).all()
    flipped = float((exact != low).any(-1).mean())
    assert 0.001 < flipped < 0.2
    w = ref.router_weights(p, a, cfg, "f32")
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-5)  # renormalised


@pytest.mark.parametrize("fault", ["bf16_decay", "no_beta", "no_gate",
                                   "scale_form_norms"])
def test_a_planted_fault_fails_a_limit(cell, toy, fault):
    """A decay rounded to bfloat16 before its running sum, a dropped
    write strength, a dropped output gate, the norms read in the scale
    form: each moves the reference's own loss or first gradient past a
    limit the float32 rehearsal is held to, where the sound program
    passes them all (``test_the_cell_rehearses...``)."""
    cfg, bench_task, _, _, params, batch = toy
    rb = bench_task.reference_batches([batch], cfg, 0, 1)[0]

    def reading():
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: (lambda s, n: s / n)(*ref.loss_sum(p, rb, cfg,
                                                         "f32"))))(params)
        return float(loss), comparisons.leaf_norms(grads)

    want_loss, want_norms = reading()
    saved = {}

    def plant(module, name, fn):
        saved[(module, name)] = getattr(module, name)
        setattr(module, name, fn)

    try:
        if fault == "bf16_decay":
            rule = ref.recurrence
            plant(ref, "recurrence", lambda q, k, v, g, beta: rule(
                q, k, v, g.astype(jnp.bfloat16).astype(jnp.float32), beta))
        elif fault == "no_beta":
            rule = ref.recurrence
            plant(ref, "recurrence", lambda q, k, v, g, beta: rule(
                q, k, v, g, jnp.ones_like(beta)))
        elif fault == "no_gate":
            plant(jax.nn, "sigmoid", lambda x: jnp.ones_like(x))
        else:
            plant(ref, "rms_norm", lambda w, x, eps: x * jax.lax.rsqrt(
                jnp.square(x).mean(-1, keepdims=True) + eps) * w)
        loss, norms = reading()
    finally:
        for (module, name), fn in saved.items():
            setattr(module, name, fn)
    limits = cell.limits["rehearsal"]
    read = {"loss_gap_step1": abs(loss - want_loss) / want_loss,
            "grad_norm_gap": comparisons.worst_leaf_gap(norms, want_norms),
            "grad_norm_gap_rms": comparisons.rms_leaf_gap(norms, want_norms)}
    assert [n for n in read if read[n] > limits[n]], (fault, read)


def test_the_cell_rehearses_and_the_control_fails_it(cell):
    result = harness.run_cell(cell, seed=4_200_000_007, seconds=0.5,
                              trace=False, rehearse=True, t_start=0.0,
                              device=dict(CPU), control="fp8")
    assert result["rehearsal"] is True and result["failed"] == 0
    assert result["rehearsal_checks_ok"] is True, result["checks"]
    assert result["correct"] is False and result["metrics"] == {}
    assert set(result["rehearsal_metrics"]) == {"setup_s",
                                                "train_tokens_per_s"}
    assert set(result["checks"]) == {
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
        "grad_norm_gap", "grad_norm_gap_rms", "update_norm_gap",
        "nonfinite_losses", "window_compiles"}
    control = result["control_checks"]
    program = {k: c["value"] for k, c in result["checks"].items()}
    limits = {**cell.limits, **cell.limits["rehearsal"]}
    failed = [n for n in control if control[n] > limits[n]]
    assert failed, (control, limits)
    assert control["grad_norm_gap_rms"] > 3 * program["grad_norm_gap_rms"]


# --- counts ------------------------------------------------------------------


def test_the_steps_operation_count_is_the_hand_count(cell):
    cfg = harness.flat_config(cell.config, rehearse=False)
    task = harness.load_task("gated_delta_lm")
    assert not hasattr(task, "flop_shape")     # not Perceiver's count
    assert task.tokens_per_row(cfg) == 4096
    parts = task.forward_parts(cfg)
    s, c = 4096, 2048
    # three linear mixers: q, k (2048 each), v, z (4096 each), b, alpha
    # (32 each) in; 4096 out
    assert parts["delta_projections"] == 3 * s * 2 * (
        c * (2 * 2048 + 2 * 4096 + 64) + 4096 * c)
    # the one full layer: the query beside its gate, k, v, out
    assert parts["attention_projections"] == s * 2 * (
        3 * c * 4096 + 2 * c * 512)
    assert parts["causal_attention"] == 4 * (s * (s + 1) / 2) * 4096
    # four expert layers: the router's 512 outputs, the shared expert's
    # three matrices of 512 and its gate's column
    assert parts["router_and_shared"] == 4 * s * 2 * (
        c * 512 + 3 * c * 512 + c)
    # the even share: 4096 x 10 x 32 / 512 = 2,560 assignments a row
    assert parts["routed_experts"] == 4 * 2560 * 2 * 3 * c * 512
    assert parts["head"] == s * 2 * c * 18992
    row = sum(parts.values())
    assert 433.0e6 < row / s < 433.1e6          # 433 MFLOP a token forward
    step = task.train_step_flops(cfg, 4)
    assert step == 4 * 3 * row
    assert 21.2e12 < step < 21.4e12             # 21.3 TFLOP a step
    # the mixers are half the products, the rule itself a twenty-eighth
    linear = parts["delta_projections"] + parts["delta_rule"]
    assert 0.49 < linear / row < 0.52
    assert 0.03 < parts["delta_rule"] / row < 0.04
    assert 0.17 < parts["head"] / row < 0.19


def test_the_rules_cost_is_the_hand_count(cell):
    cfg = harness.flat_config(cell.config, rehearse=False)
    ops, moved = costs.rule_cost(cfg, 4, 4096, backward=False)
    tokens = 4 * 4096
    # a token and key head k k^T and q k^T (2 x 64 x 128 each); a token
    # and value head the two solves (2 x 64 x 256), W S, q S and k^T v'
    # (2 x 128 x 128 each) and the scores times v' (2 x 64 x 128)
    per_token = 16 * 2 * (2 * 64 * 128) + 32 * (
        2 * 64 * 256 + 3 * 2 * 128 * 128 + 2 * 64 * 128)
    assert ops == tokens * per_token
    assert 5.2e6 < per_token < 5.3e6            # 5.2 MFLOP a token, layer
    # q, k (2048 each), v and o (4096 each) in bfloat16; g, beta float32
    assert moved == tokens * (2 * (2 * 2048 + 2 * 4096) + 4 * 64)
    b_ops, b_moved = costs.rule_cost(cfg, 4, 4096, backward=True)
    assert b_ops == 2 * ops and b_moved == 2 * moved
    peak = flops.peaks("TPU v5 lite")
    t, bound = flops.roofline_seconds(ops, moved, peak)
    assert bound == "memory" or bound == "compute"
    assert 0.4e-3 < t < 0.6e-3                  # half a millisecond a pass
    # a row shorter than a chunk is one chunk of its own length
    short = costs.rule_cost({**cfg, "delta_chunk_size": 64}, 1, 16,
                            backward=False)[0]
    assert short == 16 * (16 * 2 * (2 * 16 * 128) + 32 * (
        2 * 16 * 256 + 3 * 2 * 128 * 128 + 2 * 16 * 128))
    # the gated experts' three products, under this family's key names
    assert costs.expected_assignments(cfg, 16384) == 10240
    assert costs.gated_grouped_cost(cfg, 10240, backward=False) \
        == block_diffusion_costs.gated_grouped_cost(
            {**cfg, "num_experts": 512}, 10240, backward=False)
    g_ops, g_moved = costs.gated_grouped_cost(cfg, 10240, backward=False)
    assert g_ops == 10240 * 2 * 3 * 2048 * 512
    assert g_moved == 32 * 3 * 2048 * 512 * 2 + 2 * 10240 * 3 * (2048 + 512)


# --- the configuration -------------------------------------------------------


def test_the_configuration_keeps_every_published_number(cell):
    config = cell.config
    entry = next(c for c in cell.manifest["configs"]
                 if c["name"] == "qwen3_next_80b_a3b")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert config["source"] == entry["source"]
    widths = ("_dim", "_rank", "_size", "channels", "latents")
    assert not any(k.endswith(widths) and k != "vocab_size"
                   and k != "batch_size" for k in config["reduced"])
    assert config["num_hidden_layers"] == 4
    assert config["num_experts"] == 32 and config["vocab_size"] == 18992
    assert config["published"]["num_hidden_layers"] == 48
    assert config["published"]["num_experts"] == 512
    assert config["published"]["vocab_size"] == 151936 == 8 * 18992
    model = config["model"]
    assert model["n_routed_experts"] == 512 and model["held_experts"] == 32
    # one whole period: three linear layers and one full, an expert
    # layer after each
    assert model["hybrid_override_pattern"] == "LELELE*E"
    assert model["hybrid_override_pattern"].count("L") \
        == config["full_attention_interval"] - 1
    assert len(model["hybrid_override_pattern"]) \
        == 2 * config["num_hidden_layers"]
    for key in ("hybrid_override_pattern", "layer_equations",
                "in_projection_layout", "delta_chunk_size",
                "multi_token_prediction", "auxiliary_loss", "init",
                "optimizer.lr", "max_seq_len", "remat"):
        assert key in config["assumed"], key
    assert "16 chips share" in config["deployment"]["stands_for"]
    assert config["parameters"] == 625_667_136
    if not os.path.exists(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    # every width the program runs is the published one
    same = ("hidden_size", "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "num_attention_heads",
            "num_key_value_heads", "head_dim", "moe_intermediate_size",
            "num_experts_per_tok", "rope_theta", "partial_rotary_factor",
            "norm_topk_prob")
    for key in same:
        assert model[key] == row["config"][key], key
    assert model["moe_shared_expert_intermediate_size"] \
        == row["config"]["shared_expert_intermediate_size"]
    assert model["norm_eps"] == row["config"]["rms_norm_eps"]
    assert model["n_routed_experts"] == row["config"]["num_experts"]


def test_the_built_trees_count_is_the_files(cell):
    cfg = harness.flat_config(cell.config, rehearse=False)
    cls, kwargs = harness.load_task(cfg["task"]).program_task(cfg)
    shapes = jax.eval_shape(cls(**kwargs).build().init, jax.random.key(0))

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    assert count(shapes) == cell.config["parameters"] == 625_667_136
    layers = shapes["layers"]
    assert count(layers["00_delta"]["mixer"]) == 33_718_464
    assert count(layers["06_attn"]["mixer"]) == 27_263_488
    assert count(layers["01_moe"]["mixer"]["experts"]) == 32 * 3_145_728
    assert count(layers["01_moe"]["mixer"]) - 32 * 3_145_728 == 4_196_352
    assert count(shapes["embed"]) + count(shapes["head"]) \
        == 2 * 18992 * 2048
    # 16 bytes a parameter in the trainer, 20 in the reference
    assert 10.0e9 < 16 * count(shapes) < 10.02e9
    assert 12.5e9 < 20 * count(shapes) < 12.52e9


# --- the manifest and the readers --------------------------------------------


def test_the_manifest_lists_the_cell_where_its_readers_find_something(cell):
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names == {
        "train.step_ms", "train.mfu_pct", "device.idle_pct.train",
        "train.input_wait_pct", "train.host_ms_per_step",
        "setup.state_build_s", "setup.step_load_s", "model.attn_core_pct",
        "model.loss_pct", "train.optimizer_pct", "model.remat_pct",
        "causal_attention_roofline"} | NEW_METRICS
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "train_tokens_per_s", "setup_s"}
    for m in cell.manifest["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == ["qwen3next_train"]
            assert m["source"] == "device_trace"
            assert m["moves"] == "train_tokens_per_s"
        if m["name"] in ("flash_attention_roofline", "model.dense_pct",
                         "model.unscoped_pct", "moe_expert_roofline",
                         "ssm_scan_roofline", "model.ssm_pct",
                         "model.moe_pct", "model.moe_route_pct",
                         "moe_gated_expert_roofline", "model.moe_pct.bd",
                         "block_diffusion_attention_roofline"):
            assert "qwen3next_train" not in m["workloads"]
    # new entries stand at the end of their lists
    assert cell.manifest["workloads"][-1]["name"] == "qwen3next_train"
    assert cell.manifest["configs"][-1]["name"] == "qwen3_next_80b_a3b"
    assert {m["name"] for m in cell.manifest["per_layer"][-6:]} \
        == NEW_METRICS
    assert cell.chips == 1
    assert not any(w["chips"] == 4 for w in cell.manifest["workloads"])
    # nemotron_train's rows: the two cells differ by the model alone
    nemotron = harness.load_cell("nemotron_train")
    assert cell.mix == nemotron.mix
    assert cell.mix["batch_rows"] * cell.config["model"]["max_seq_len"] \
        == 16384


def _reader(name):
    return harness.load_module(
        os.path.join(ROOT, "benchmarks", "layer_metrics", f"{name}.py"),
        "test_metric_" + name.replace(".", "_"))


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_readers_give_nothing_where_nothing_carries_the_scope(name,
                                                                  cell):
    """No trace, and a program without the scopes or the counters (the
    parent commit): None, never an error."""
    outcome = type("O", (), {"data": {"rows": 4, "steps": 3}})()
    tracer = type("T", (), {"enabled": False, "directory": "/nonexistent/x",
                            "mono0": None, "mono1": None})()
    cfg = harness.flat_config(cell.config, rehearse=False)
    run = type("Run", (), {"trace": None, "outcome": outcome, "cfg": cfg,
                           "tracer": tracer, "peak": {}})()
    assert _reader(name).read(run) is None
    # another family's configuration under the same readers
    run.cfg = {"hidden_size": 8}
    assert _reader(name).read(run) is None
    for other in ("nemotron_train", "sdar_train"):
        run.cfg = harness.flat_config(harness.load_cell(other).config,
                                      rehearse=False)
        run.peak = flops.peaks("TPU v5 lite")
        assert _reader(name).read(run) is None


def _run(cell, tmp_path=None):
    cfg = harness.flat_config(cell.config, rehearse=False)
    outcome = type("O", (), {"data": {"rows": 4, "steps": 80}})()
    tracer = type("T", (), {"enabled": True, "directory": str(
        tmp_path / "trace") if tmp_path else "/nonexistent/x"})()
    return type("Run", (), {"trace": type("R", (), {"events": {}})(),
                            "outcome": outcome, "cfg": cfg,
                            "tracer": tracer,
                            "peak": flops.peaks("TPU v5 lite")})()


def test_the_rules_reader_counts_the_rule_over_its_scope(cell, monkeypatch,
                                                         capsys):
    run, cfg = _run(cell), harness.flat_config(cell.config, rehearse=False)
    by_scope = {"delta_rule": 2.0, "delta_mixer": 3.0}
    monkeypatch.setattr(hybrid_costs, "traced_whole_steps",
                        lambda run: (19.0, by_scope))
    reader = _reader("delta_rule_roofline")
    least = 19 * 3 * sum(
        flops.roofline_seconds(*costs.rule_cost(cfg, 4, 4096, backward=b),
                               run.peak)[0] for b in (False, True))
    share = reader.read(run)
    assert share == pytest.approx(100 * least / 2.0)
    assert 0 < share < 100
    assert "under delta_rule over the 19 whole steps" in capsys.readouterr().out

    # a kernel named for the rule, if one ships, is printed beside it
    class Event:
        def __init__(self, name, ns):
            self.name, self.duration_ns = name, ns

    call = ("%delta_rule_fwd.3 = bf16[4,4096,4096]{2,1,0} custom-call("
            "bf16[4,4096,2048]{2,1,0} %q, bf16[4,4096,2048]{2,1,0} %k), "
            "custom_call_target=\"tpu_custom_call\"")
    run.trace = type("R", (), {"events": {0: [
        Event(call, 3e6), Event(call, 3e6),
        Event("%fusion.9 = f32[8] fusion(f32[8] %delta_rule_x)", 5e6)]}})()
    assert reader.kernels_under(run) == {
        ("delta_rule_fwd", ("4x4096x2048", "4x4096x2048")): [2, 0.006]}
    assert reader.read(run) == pytest.approx(share)
    assert "delta_rule kernel delta_rule_fwd" in capsys.readouterr().out
    # the scope absent (the parent): nothing
    by_scope.clear()
    assert reader.read(run) is None


def test_the_experts_reader_counts_three_products_and_the_counters(
        cell, tmp_path, monkeypatch, capsys):
    run, cfg = _run(cell, tmp_path), harness.flat_config(cell.config,
                                                         rehearse=False)
    by_scope = {"moe_experts": 0.4}
    monkeypatch.setattr(hybrid_costs, "traced_whole_steps",
                        lambda run: (19.0, by_scope))
    monkeypatch.setattr(hybrid_costs.scope_times, "window_spans",
                        lambda run, what: None)
    reader = _reader("moe_gated_expert_roofline.gdn")
    # no telemetry line to reach: the expected share of an even router
    least = 19 * 4 * sum(
        flops.roofline_seconds(*costs.gated_grouped_cost(
            cfg, 10240, backward=b), run.peak)[0] for b in (False, True))
    assert reader.read(run) == pytest.approx(100 * least / 0.4)
    assert "expected from an even router" in capsys.readouterr().out
    tele = tmp_path / "telemetry"
    tele.mkdir()
    with open(tele / "telemetry.jsonl", "w") as f:
        for step, (count, full) in enumerate(
                [(40000.0, 0.0), (48000.0, 1.0), (44000.0, 0.0)], 1):
            f.write(json.dumps({"step": step, "loss": 1.0,
                                "moe_assignments": count,
                                "moe_full_buffer_layers": full}) + "\n")
    least = 19 * 4 * sum(
        flops.roofline_seconds(*costs.gated_grouped_cost(
            cfg, 11000, backward=b), run.peak)[0] for b in (False, True))
    assert reader.read(run) == pytest.approx(100 * least / 0.4)
    out = capsys.readouterr().out
    assert "11000 assignments a layer and step (the program's counter)" \
        in out
    assert "1 of 3 logged steps had an expert layer outside its usual " \
        "buffer" in out
    by_scope.clear()
    assert reader.read(run) is None


@pytest.mark.parametrize("name,scope", [
    ("model.delta_mixer_pct", "delta_mixer"),
    ("model.delta_rule_pct", "delta_rule"),
    ("model.moe_pct.gdn", "moe"),
    ("model.moe_route_pct.gdn", "moe_route")])
def test_a_share_reader_reads_its_scope(name, scope, monkeypatch):
    from benchmarks import scope_times

    asked = []
    monkeypatch.setattr(scope_times, "scope_share",
                        lambda run, s: asked.append(s) or 12.5)
    assert _reader(name).read(object()) == 12.5 and asked == [scope]
    monkeypatch.setattr(scope_times, "scope_share", lambda run, s: 0.0)
    assert _reader(name).read(object()) is None


def test_the_new_scopes_are_the_programs():
    from perceiver_tpu.obs.trace import DEVICE_SCOPES

    assert {"delta_mixer", "delta_rule", "attn_gate"} <= set(DEVICE_SCOPES)
