"""The block-diffusion mixture-of-experts cell, ``sdar_train``: program
against the plain reference at the configuration's rehearsal widths on
the benchmark's seeded weights (logits, the loss, the gradient leaf by
leaf, the noise re-derived from the trainer's seed), one rehearsal of
the cell through ``run_cell`` with three AdamW steps and the control,
the shares a run holds, the step's operation count and the two
mechanisms' costs against hand counts, the configuration file against
the catalog row it was drawn from, and the three readers."""

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
    block_diffusion_costs as costs,
    hybrid_costs,
)
from benchmarks.reference import block_diffusion_lm as ref  # noqa: E402
from benchmarks.reference import perceiver_io as ref_steps  # noqa: E402

from perceiver_tpu.ops.policy import Policy  # noqa: E402

SEED = 3_700_000_029
FP32 = Policy.fp32()
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"block_diffusion_attention_roofline",
               "moe_gated_expert_roofline", "model.bd_noise_pct",
               "model.moe_pct.bd", "model.moe_route_pct.bd"}


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell("sdar_train")


@pytest.fixture(scope="module")
def toy(cell):
    cfg = harness.flat_config(cell.config, rehearse=True)
    bench_task = harness.load_task(cfg["task"])
    cls, kwargs = bench_task.program_task(cfg)
    task = cls(**kwargs)
    model = task.build()
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = weights.make_weights(shapes, SEED)
    # norm scales are drawn as ones: move them, so that a scale read
    # from the wrong place shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 + 0.1 * jax.random.normal(
            jax.random.key(x.size), x.shape))
        if path[-1].key == "scale" else x, params)
    batch = bench_task.make_batch(np.random.default_rng(7), 2, cfg)
    return cfg, bench_task, task, model, params, batch


def test_every_leaf_has_a_rule_and_the_sizes_are_the_toy_ones(toy):
    cfg, _, task, model, params, batch = toy
    assert cfg["hidden_size"] == 64 and cfg["num_hidden_layers"] == 2
    assert batch["input_ids"].shape == (2, cfg["max_seq_len"]) == (2, 72)
    # the mask id is the one special id: the data never draws it
    assert cfg["mask_token_id"] == 0 and cfg["num_special_tokens"] == 1
    assert batch["input_ids"].min() >= 1 and batch["input_ids"].max() < 512
    names = {str(getattr(p[-1], "key", p[-1])) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    assert names == {"w", "scale", "embed"}     # what weights._leaf knows
    assert list(params["layers"]) == ref.layer_names(cfg) == [
        "00_attn", "01_moe", "02_attn", "03_moe"]
    experts = params["layers"]["01_moe"]["mixer"]["experts"]
    assert set(experts) == {"gate", "up", "down"}
    assert experts["up"]["w"].shape == (4, 64, 40)       # 4 of 16 held
    assert "shared" not in params["layers"]["01_moe"]["mixer"]
    assert params["layers"]["00_attn"]["mixer"]["q_norm"]["scale"].shape \
        == (16,)
    assert model.first_expert == 4 and model.num_held_experts == 4
    assert task.block_length == 4 and task.t_min == 1e-3


def test_logits_against_the_reference(toy):
    cfg, _, _, model, params, batch = toy
    ids = jnp.asarray(batch["input_ids"])
    noised, _ = ref.block_noise(jax.random.key(1), ids, cfg)
    both = jnp.concatenate([noised, ids], axis=1)
    logits = model.apply(params, both, policy=FP32,
                         block_diffusion=(72, 4))
    want = ref.logits(params, both, cfg)
    assert logits.shape == want.shape == (2, 144, 512)
    np.testing.assert_allclose(logits, want, atol=2e-4, rtol=1e-4)
    # the mask, the positions and the norms each move them: a reference
    # without one of them shows
    for change in ({"block_length": 8}, {"rope_theta": 1e3}):
        other = ref.logits(params, both, {**cfg, **change})
        assert float(jnp.abs(other - want).max()) > 0.02, change
    flat = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.ones_like(x)
        if "q_norm" in jax.tree_util.keystr(path) else x, params)
    assert float(jnp.abs(ref.logits(flat, both, cfg) - want).max()) > 1e-3


def test_loss_and_gradient_leaf_by_leaf_against_the_reference(toy):
    """The program with the trainer's first step's key against the
    reference on the batch ``reference_batches`` re-derives from the
    trainer's seed."""
    cfg, bench_task, task, model, params, batch = toy
    trainer_seed = 1234
    key = ref_steps.trainer_step_keys(trainer_seed, 1)[0]
    loss, grads = jax.value_and_grad(
        lambda p: task.loss_and_metrics(model, p, batch, rng=key,
                                        deterministic=False,
                                        policy=FP32)[0])(params)
    rb = bench_task.reference_batches([batch], cfg, trainer_seed, 1)[0]
    assert set(rb) == {"input_ids", "noised_ids", "weights", "first_experts"}
    masked = np.asarray(rb["weights"]) > 0
    assert (np.asarray(rb["noised_ids"])[masked] == 0).all()
    np.testing.assert_array_equal(np.asarray(rb["noised_ids"])[~masked],
                                  batch["input_ids"][~masked])
    assert 1.0 <= float(rb["weights"][masked].min()) \
        and float(rb["weights"].max()) <= 1000.0
    want_loss, want = ref_steps.loss_and_grads(
        params, rb, cfg, loss_sum=bench_task.loss_sum, block=1)
    assert abs(loss - want_loss) < 2e-5 * abs(want_loss)
    got_n, want_n = comparisons.leaf_norms(grads), \
        comparisons.leaf_norms(want)
    assert comparisons.worst_leaf_gap(got_n, want_n) < 2e-4
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        assert float(jnp.abs(a - b).max()) \
            < 2e-4 * float(jnp.abs(b).max()) + 1e-7
    # another step's key is another noise: the loss moves
    other = bench_task.reference_batches([batch, batch], cfg, trainer_seed,
                                         2)[1]
    assert not np.array_equal(other["noised_ids"], rb["noised_ids"])


def test_a_share_is_what_the_reference_is_given(toy):
    """The reference leaves out the absent experts as the program does:
    with another share of the same router the loss moves, and the
    program follows."""
    cfg, bench_task, task, model, params, batch = toy
    import dataclasses
    batch = {k: v for k, v in batch.items() if k != "first_experts"}
    key = ref_steps.trainer_step_keys(5, 1)[0]
    rb = bench_task.reference_batches([batch], cfg, 5, 1)[0]
    assert "first_experts" not in rb

    def both(first):
        moved = dataclasses.replace(task, first_expert=first)
        got = moved.loss_and_metrics(moved.build(), params, batch, rng=key,
                                     policy=FP32)[0]
        s, n = ref.loss_sum(params, rb, {**cfg, "first_expert": first},
                            "f32")
        return float(got), float(s / n)

    (got4, want4), (got0, want0) = both(4), both(0)
    assert abs(got4 - want4) < 2e-5 * want4
    assert abs(got0 - want0) < 2e-5 * want0
    assert abs(want4 - want0) > 1e-3


def test_every_batch_of_a_run_names_the_same_shares(cell, toy):
    cfg, bench_task = toy[:2]
    pool = traffic.train_batches(cell.mix["rehearsal"], cfg, SEED,
                                 bench_task.make_batch)
    firsts = pool[0]["first_experts"]
    assert firsts.shape == (2, 2) and firsts.dtype == np.int32
    assert set(firsts.ravel() % cfg["held_experts"]) == {0}
    assert firsts.max() <= cfg["num_experts"] - cfg["held_experts"]
    for made in pool:
        np.testing.assert_array_equal(made["first_experts"], firsts)
        assert made["input_ids"].min() >= 1
    assert not np.array_equal(pool[0]["input_ids"], pool[1]["input_ids"])
    for rb, made in zip(bench_task.reference_batches(pool, cfg, 0, 3), pool):
        np.testing.assert_array_equal(rb["first_experts"],
                                      made["first_experts"])
    # a configuration that holds every expert has no share to name
    whole = {**cfg, "held_experts": cfg["num_experts"]}
    assert "first_experts" not in bench_task.make_batch(
        np.random.default_rng(7), 2, whole)


def test_the_shares_a_run_holds_get_the_even_load(toy):
    """``even_shares`` on the seed's own weights over a pool's batches,
    each under its own step's noise, from the router's loads alone: the
    program's counter of held assignments on those steps lies nearer an
    even router's (positions x top_k x held / experts a layer), on the
    batch that lies farthest off, than with the configuration's share,
    over seeds."""
    cfg, bench_task, task, model, _, _ = toy
    source = open(bench_task.__file__).read()
    assert "perceiver_tpu.ops" not in source and "usual_rows" not in source
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    even = cfg["num_hidden_layers"] * 2 * 144 * cfg["num_experts_per_tok"] \
        * cfg["held_experts"] / cfg["num_experts"]
    counted = jax.jit(lambda p, b, key: task.loss_and_metrics(
        model, p, b, rng=key, policy=FP32)[1]["moe_assignments"])
    off_first, off_even = [], []
    for seed in range(40, 45):
        rng = np.random.default_rng(seed)
        pool = [traffic.zipf_ids(rng, 512, 1, (2, 72)) for _ in range(3)]
        params = weights.make_weights(shapes, seed)
        keys = ref_steps.trainer_step_keys(weights.seed31(seed), 3)
        firsts = bench_task.even_shares(params, pool, cfg, keys)
        assert firsts.shape == (2,) and set(firsts % 4) == {0}
        first, even_ = [], []
        for ids, key in zip(pool, keys):
            batch = {"input_ids": ids}
            first.append(float(counted(params, batch, key)))
            even_.append(float(counted(
                params, {**batch, "first_experts": np.tile(firsts, (2, 1))},
                key)))
        # the batch farthest off: no step does much more than another's
        off_first.append(np.abs(np.asarray(first) - even).max())
        off_even.append(np.abs(np.asarray(even_) - even).max())
    assert np.mean(off_even) < 0.5 * np.mean(off_first), (off_first,
                                                          off_even)
    assert max(off_even) < 0.25 * even, off_even


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


def test_the_cell_rehearses_and_the_control_fails_it(cell):
    result = harness.run_cell(cell, seed=3_700_000_007, seconds=0.5,
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


def test_the_steps_operation_count_is_the_hand_count(cell):
    cfg = harness.flat_config(cell.config, rehearse=False)
    task = harness.load_task("block_diffusion_lm")
    assert not hasattr(task, "flop_shape")     # not Perceiver's count
    # data tokens: the noised copy is not counted twice
    assert task.tokens_per_row(cfg) == 4096
    parts = task.forward_parts(cfg)
    s, d = 4096, 2048
    # a row forward (ISSUE 37): projections over the 2 L positions
    assert parts["attention_projections"] == 6 * 2 * s * 2 * (
        2 * d * 4096 + 2 * d * 512)
    assert parts["router"] == 6 * 2 * s * 2 * d * 128
    # the even share: 8,192 assignments a row and layer, three products
    assert parts["routed_experts"] == 6 * 8192 * 2 * 3 * d * 768
    # the masked core at the visible pairs, L^2 + L B a head
    assert parts["block_diffusion_attention"] \
        == 6 * 4 * (s * s + s * 4) * 4096
    # the head at the expected masked positions, L (1 + t_min) / 2
    assert parts["head"] == pytest.approx(
        2050.048 * 2 * d * 18992, rel=1e-9)
    row = sum(parts.values())
    assert 4.14e12 < row < 4.17e12                          # 4.15 TFLOP
    step = task.train_step_flops(cfg, 2)
    assert step == 2 * 3 * row
    assert 24.8e12 < step < 25.0e12                         # 24.9 TFLOP
    # the masked core is two fifths of the products, the experts a ninth
    assert 0.39 < parts["block_diffusion_attention"] / row < 0.41
    assert 0.10 < parts["routed_experts"] / row < 0.12


def test_the_configuration_keeps_every_published_number(cell):
    config = cell.config
    entry = next(c for c in cell.manifest["configs"]
                 if c["name"] == "sdar_30b_a3b")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert config["source"] == entry["source"]
    widths = ("_dim", "_rank", "_size", "channels", "latents")
    assert not any(k.endswith(widths) and k != "vocab_size"
                   and k != "batch_size" for k in config["reduced"])
    assert config["num_hidden_layers"] == 6
    assert config["num_experts"] == 16 and config["vocab_size"] == 18992
    assert config["published"]["num_hidden_layers"] == 48
    assert config["published"]["num_experts"] == 128
    assert config["published"]["vocab_size"] == 151936 == 8 * 18992
    model = config["model"]
    assert model["num_experts"] == 128 and model["held_experts"] == 16
    assert model["num_hidden_layers"] == config["num_hidden_layers"]
    assert model["block_length"] == 4 and model["t_min"] == 1e-3
    for key in ("block_length", "noise_schedule", "t_min",
                "prediction_position", "qk_norm", "auxiliary_loss",
                "mask_token_id"):
        assert key in config["assumed"], key
    assert "8 chips share" in config["deployment"]["stands_for"]
    assert config["parameters"] == 645_623_296
    if not os.path.exists(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    # every width the program runs is the published one
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_intermediate_size", "num_experts_per_tok",
                "rope_theta", "rms_norm_eps", "norm_topk_prob"):
        assert model[key] == row["config"][key], key
    # the built tree's count is the file's
    cfg = harness.flat_config(config, rehearse=False)
    cls, kwargs = harness.load_task(cfg["task"]).program_task(cfg)
    shapes = jax.eval_shape(cls(**kwargs).build().init, jax.random.key(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == config["parameters"]


def test_the_manifest_lists_the_cell_where_its_readers_find_something(cell):
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names == {
        "train.step_ms", "train.mfu_pct", "device.idle_pct.train",
        "train.input_wait_pct", "train.host_ms_per_step",
        "setup.state_build_s", "setup.step_load_s", "model.attn_core_pct",
        "model.loss_pct", "train.optimizer_pct",
        "model.remat_pct"} | NEW_METRICS
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "train_tokens_per_s", "setup_s"}
    for m in cell.manifest["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == ["sdar_train"]
            assert m["source"] == "device_trace"
            assert m["moves"] == "train_tokens_per_s"
        if m["name"] in ("flash_attention_roofline", "model.dense_pct",
                         "model.unscoped_pct", "causal_attention_roofline",
                         "moe_expert_roofline", "ssm_scan_roofline",
                         "model.ssm_pct", "model.moe_pct",
                         "model.moe_route_pct"):
            assert "sdar_train" not in m["workloads"]
    assert cell.chips == 1 and len(cell.manifest["workloads"]) == 5
    assert not any(w["chips"] == 4 for w in cell.manifest["workloads"])
    assert cell.mix["batch_rows"] * cell.config["model"]["max_seq_len"] \
        == 8192
    assert cell.mix["trace_seconds"] == 10.0
    assert cell.mix["reference_block_rows"] == 1
    assert cell.mix["pool_batches"] == 8 and cell.mix["warmup_steps"] == 2


def _reader(name):
    return harness.load_module(
        os.path.join(ROOT, "benchmarks", "layer_metrics", f"{name}.py"),
        "test_metric_" + name.replace(".", "_"))


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_readers_give_nothing_where_nothing_carries_the_scope(name,
                                                                  cell):
    """No trace, and a program without the scopes, the kernels or the
    counters (the parent commit): None, never an error."""
    outcome = type("O", (), {"data": {"rows": 2, "steps": 3}})()
    tracer = type("T", (), {"enabled": False, "directory": "/nonexistent/x",
                            "mono0": None, "mono1": None})()
    cfg = harness.flat_config(cell.config, rehearse=False)
    run = type("Run", (), {"trace": None, "outcome": outcome, "cfg": cfg,
                           "tracer": tracer, "peak": {}})()
    assert _reader(name).read(run) is None
    # another family's configuration under the same readers
    run.cfg = {"hidden_size": 8}
    assert _reader(name).read(run) is None
    nemotron = harness.load_cell("nemotron_train")
    run.cfg = harness.flat_config(nemotron.config, rehearse=False)
    run.peak = flops.peaks("TPU v5 lite")
    assert _reader(name).read(run) is None


def test_the_two_mechanisms_costs_are_the_hand_counts(cell):
    cfg = harness.flat_config(cell.config, rehearse=False)
    assert costs.visible_pairs(4096, 4) == 4096 * 4096 + 4096 * 4
    # pair by pair at a small size
    sees = np.asarray(ref.visible(24, 4))
    assert costs.visible_pairs(24, 4) == sees.sum()
    ops, moved = costs.attention_cost(2, 4096, 4, 4096, backward=False)
    assert ops == 4 * 2 * (4096 * 4096 + 4096 * 4) * 4096
    assert moved == 2 * 2 * 4096 * 4 * 8192     # q, k, v, o in bfloat16
    b_ops, b_moved = costs.attention_cost(2, 4096, 4, 4096, backward=True)
    assert b_ops == 2.5 * ops and b_moved == 2 * moved
    # a quarter of the full scores' work and an eighth more than half
    # the causal triangle's over the doubled row
    full = flops.flash_attention_cost(2, 8192, 8192, 4096, backward=False)
    assert full[1] == moved and 0.25 < ops / full[0] < 0.2503
    peak = flops.peaks("TPU v5 lite")
    t, bound = flops.roofline_seconds(ops, moved, peak)
    assert bound == "compute" and 2.7e-3 < t < 2.9e-3
    # the gated experts: three products where hybrid_costs counts two
    assert costs.expected_assignments(cfg, 16384) == 16384
    ops, moved = costs.gated_grouped_cost(cfg, 16384, backward=False)
    assert ops == 16384 * 2 * 3 * 2048 * 768
    matrices = 16 * 3 * 2048 * 768 * 2
    assert moved == matrices + 2 * 16384 * 3 * (2048 + 768)
    two = hybrid_costs.grouped_cost(cfg | {"n_routed_experts": 128}, 16384,
                                    backward=False)
    assert ops == 1.5 * two[0] and moved == 1.5 * two[1]
    b_ops, b_moved = costs.gated_grouped_cost(cfg, 16384, backward=True)
    assert b_ops == 2 * ops and b_moved == 2 * moved
    assert costs.expected_assignments({**cfg, "held_experts": None},
                                      100) == 800


def test_the_attention_reader_reads_its_kernels_and_no_others(cell):
    reader = _reader("block_diffusion_attention_roofline")
    fwd = ("%block_diffusion_attention_fwd.7 = (f32[2,8192,4096]{2,1,0}, "
           "f32[2,32,1,8192]{3,2,1,0}) custom-call(s32[64]{0} %t, s32[64]{0} "
           "%u, bf16[2,8192,4096]{2,1,0} %a, bf16[2,8192,4096]{2,1,0} %b, "
           "bf16[2,8192,4096]{2,1,0} %c), "
           "custom_call_target=\"tpu_custom_call\"")
    assert reader.call_of(fwd) == ("block_diffusion_attention_fwd", 2, 8192,
                                   4096, 2)
    bwd = fwd.replace("block_diffusion_attention_fwd.7",
                      "transpose_jvp_block_diffusion_attention_bwd__.3")
    assert reader.call_of(bwd)[0] == "block_diffusion_attention_bwd"
    assert reader.call_of(fwd.replace("block_diffusion_", "causal_")) is None
    assert reader.call_of(
        "%fusion.3 = bf16[2,8192,4096] fusion(bf16[2,8192,4096] "
        "%block_diffusion_attention_fwd.7)") is None
    with pytest.raises(ValueError):
        reader.call_of(fwd.replace("bf16[2,8192,4096]{2,1,0} %b",
                                   "bf16[2,4096,4096]{2,1,0} %b"))
    # and the other masks' readers never see a block-diffusion call
    assert _reader("causal_attention_roofline").call_of(fwd) is None
    assert _reader("flash_attention_roofline").call_of(fwd) is None

    class Event:
        def __init__(self, name, ns):
            self.name, self.duration_ns = name, ns

    class Run:
        peak = flops.peaks("TPU v5 lite")
        cfg = harness.flat_config(cell.config, rehearse=False)
        trace = type("R", (), {"events": {0: [
            Event(fwd, 8e6), Event(fwd, 8e6), Event(bwd, 14e6),
            Event("%fusion.9 = f32[8] fusion(f32[8] %x)", 5e6)]}})()

    share = reader.read(Run())
    pairs = 4096 * 4096 + 4096 * 4
    least = (2 * 4 + 10) * 2 * pairs * 4096 / 197e12
    assert share == pytest.approx(100 * least / 30e-3, rel=1e-3)
    assert 30 < share < 100
    Run.trace = None
    assert reader.read(Run()) is None


def _run(cell, tmp_path=None):
    cfg = harness.flat_config(cell.config, rehearse=False)
    outcome = type("O", (), {"data": {"rows": 2, "steps": 80}})()
    tracer = type("T", (), {"enabled": True, "directory": str(
        tmp_path / "trace") if tmp_path else "/nonexistent/x"})()
    return type("Run", (), {"trace": object(), "outcome": outcome,
                            "cfg": cfg, "tracer": tracer,
                            "peak": flops.peaks("TPU v5 lite")})()


def test_the_experts_reader_counts_three_products_and_the_counters(
        cell, tmp_path, monkeypatch, capsys):
    run, cfg = _run(cell, tmp_path), harness.flat_config(cell.config,
                                                         rehearse=False)
    by_scope = {"moe_experts": 0.4}
    monkeypatch.setattr(hybrid_costs, "traced_whole_steps",
                        lambda run: (19.0, by_scope))
    monkeypatch.setattr(hybrid_costs.scope_times, "window_spans",
                        lambda run, what: None)
    reader = _reader("moe_gated_expert_roofline")
    # no telemetry line to reach: the expected share of an even router
    least = 19 * 6 * sum(
        flops.roofline_seconds(*costs.gated_grouped_cost(
            cfg, 16384, backward=b), run.peak)[0] for b in (False, True))
    assert reader.read(run) == pytest.approx(100 * least / 0.4)
    assert "expected from an even router" in capsys.readouterr().out
    # the program's counters: the steps' own assignments, and the steps
    # that left the usual buffer
    tele = tmp_path / "telemetry"
    tele.mkdir()
    with open(tele / "telemetry.jsonl", "w") as f:
        for step, (count, full) in enumerate(
                [(90000.0, 0.0), (102000.0, 1.0), (96000.0, 0.0)], 1):
            f.write(json.dumps({"step": step, "loss": 1.0,
                                "moe_assignments": count,
                                "moe_full_buffer_layers": full}) + "\n")
    least = 19 * 6 * sum(
        flops.roofline_seconds(*costs.gated_grouped_cost(
            cfg, 16000, backward=b), run.peak)[0] for b in (False, True))
    assert reader.read(run) == pytest.approx(100 * least / 0.4)
    out = capsys.readouterr().out
    assert "16000 assignments a layer and step (the program's counter)" \
        in out
    assert "1 of 3 logged steps had an expert layer outside its usual " \
        "buffer" in out
    assert reader.full_buffer_steps(run) == (1, 3)
    # the scope absent (the parent): nothing
    by_scope.clear()
    assert reader.read(run) is None
