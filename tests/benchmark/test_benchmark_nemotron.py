"""The hybrid state-space / mixture-of-experts cell, ``nemotron_train``:
program against the plain reference at the configuration's rehearsal
widths on the benchmark's seeded weights (logits, the loss, the gradient
leaf by leaf), one rehearsal of the cell through ``run_cell`` with three
AdamW steps and the control, the step's operation count against a hand
count, the configuration file against the catalog row it was drawn
from, and the readers of the two mechanisms."""

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
    scope_times,
    weights,
)
from benchmarks.layer_metrics import hybrid_costs  # noqa: E402
from benchmarks.reference import hybrid_lm as ref  # noqa: E402
from benchmarks.reference import perceiver_io as ref_steps  # noqa: E402

from perceiver_tpu.ops.policy import Policy  # noqa: E402

SEED = 3_300_000_029
FP32 = Policy.fp32()
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"model.ssm_pct", "ssm_scan_roofline", "model.moe_pct",
               "model.moe_route_pct", "moe_expert_roofline"}


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell("nemotron_train")


@pytest.fixture(scope="module")
def toy(cell):
    cfg = harness.flat_config(cell.config, rehearse=True)
    bench_task = harness.load_task(cfg["task"])
    cls, kwargs = bench_task.program_task(cfg)
    task = cls(**kwargs)
    model = task.build()
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = weights.make_weights(shapes, SEED)
    # norm scales and D are drawn as ones: move them, so that a scale
    # read from the wrong place shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 + 0.1 * jax.random.normal(
            jax.random.key(x.size), x.shape))
        if path[-1].key == "scale" else x, params)
    batch = bench_task.make_batch(np.random.default_rng(7), 2, cfg)
    return cfg, bench_task, task, model, params, batch


def test_every_leaf_has_a_rule_and_the_sizes_are_the_toy_ones(toy):
    cfg, _, _, model, params, batch = toy
    assert cfg["hidden_size"] == 64
    assert cfg["hybrid_override_pattern"] == "MEM*E"
    assert batch["input_ids"].shape == (2, cfg["max_seq_len"]) == (2, 72)
    assert 72 % cfg["chunk_size"]          # the padded last chunk is run
    assert batch["input_ids"].min() >= 0 and batch["input_ids"].max() < 512
    names = {str(getattr(p[-1], "key", p[-1])) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    assert names == {"w", "bias", "scale", "embed"}
    assert list(params["layers"]) == ["00_ssm", "01_moe", "02_ssm",
                                      "03_attn", "04_moe"]
    experts = params["layers"]["01_moe"]["mixer"]["experts"]
    assert experts["up"]["w"].shape == (4, 64, 40)       # 4 of 16 held
    assert params["layers"]["01_moe"]["mixer"]["router"]["w"].shape \
        == (64, 16)
    assert params["layers"]["00_ssm"]["mixer"]["conv"]["w"].shape \
        == (4, 64 + 2 * 2 * 16)
    assert model.first_expert == 4 and model.num_held_experts == 4


def test_logits_against_the_reference(toy):
    cfg, _, _, model, params, batch = toy
    ids = jnp.asarray(batch["input_ids"])
    logits = model.apply(params, ids, policy=FP32)
    want = ref.logits(params, ids, cfg)
    assert logits.shape == want.shape == (2, 72, 512)
    np.testing.assert_allclose(logits, want, atol=2e-4, rtol=1e-4)
    # every kind of layer moves them: a reference that skipped one shows
    for skipped in ("M", "E", "*"):
        short = {**cfg, "hybrid_override_pattern": "".join(
            k for k in cfg["hybrid_override_pattern"] if k != skipped)}
        names = ref.layer_names(cfg)
        kept = {n: params["layers"][old] for n, old in zip(
            ref.layer_names(short),
            [n for n, k in zip(names, cfg["hybrid_override_pattern"])
             if k != skipped])}
        other = ref.logits({**params, "layers": kept}, ids, short)
        assert float(jnp.abs(other - want).max()) > 0.05, skipped


def test_loss_and_gradient_leaf_by_leaf_against_the_reference(toy):
    cfg, bench_task, task, model, params, batch = toy
    loss, grads = jax.value_and_grad(
        lambda p: task.loss_and_metrics(model, p, batch, policy=FP32)[0])(
            params)
    rb = bench_task.reference_batches([batch], cfg, 0, 1)[0]
    assert int((rb["labels"] == ref_steps.IGNORE).sum()) == 2
    np.testing.assert_array_equal(rb["labels"][:, :-1],
                                  batch["input_ids"][:, 1:])
    want_loss, want = ref_steps.loss_and_grads(
        params, rb, cfg, loss_sum=bench_task.loss_sum, block=1)
    assert abs(loss - want_loss) < 2e-5 * abs(want_loss)
    got_n, want_n = comparisons.leaf_norms(grads), \
        comparisons.leaf_norms(want)
    assert comparisons.worst_leaf_gap(got_n, want_n) < 2e-4
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        assert float(jnp.abs(a - b).max()) \
            < 2e-4 * float(jnp.abs(b).max()) + 1e-7


def test_a_share_is_what_the_reference_is_given(toy):
    """The reference leaves out the absent experts as the program does:
    with another share of the same router the loss moves, and the
    program follows."""
    cfg, bench_task, task, model, params, batch = toy
    import dataclasses
    # the configuration's share: the batch names none
    batch = {k: v for k, v in batch.items() if k != "first_experts"}
    rb = bench_task.reference_batches([batch], cfg, 0, 1)[0]
    assert "first_experts" not in rb

    def both(first):
        moved = dataclasses.replace(task, first_expert=first)
        got = moved.loss_and_metrics(moved.build(), params, batch,
                                     policy=FP32)[0]
        s, n = ref.loss_sum(params, rb, {**cfg, "first_expert": first},
                            "f32")
        return float(got), float(s / n)

    (got4, want4), (got0, want0) = both(4), both(0)
    assert abs(got4 - want4) < 2e-5 * want4
    assert abs(got0 - want0) < 2e-5 * want0
    assert abs(want4 - want0) > 1e-3


def test_every_batch_of_a_run_names_the_same_shares(cell, toy):
    """The traffic is ``causal_lm``'s ids untouched; each batch adds
    ``first_experts`` (rows, expert layers), whole shares, the same in
    every row and batch of a pool, and the reference's batches carry
    them on."""
    from benchmarks import traffic
    from benchmarks.tasks import causal_lm

    cfg, bench_task = toy[:2]
    pool = traffic.train_batches(cell.mix["rehearsal"], cfg, SEED,
                                 bench_task.make_batch)
    plain = traffic.train_batches(cell.mix["rehearsal"], cfg, SEED,
                                  causal_lm.make_batch)
    firsts = pool[0]["first_experts"]
    assert firsts.shape == (2, 2) and firsts.dtype == np.int32
    assert set(firsts.ravel() % cfg["held_experts"]) == {0}
    assert firsts.max() <= cfg["n_routed_experts"] - cfg["held_experts"]
    for made, ids in zip(pool, plain):
        np.testing.assert_array_equal(made["input_ids"], ids["input_ids"])
        np.testing.assert_array_equal(made["first_experts"], firsts)
    for rb, made in zip(bench_task.reference_batches(pool, cfg, 0, 3), pool):
        np.testing.assert_array_equal(rb["first_experts"],
                                      made["first_experts"])
    # a configuration that holds every expert has no share to name
    whole = {**cfg, "held_experts": cfg["n_routed_experts"]}
    assert "first_experts" not in bench_task.make_batch(
        np.random.default_rng(7), 2, whole)


def test_the_shares_a_run_holds_get_the_even_load(toy):
    """``even_shares`` on the seed's own weights: the program's counter
    of held assignments on the batch the shares were chosen on is nearer
    an even router's (tokens x top_k x held / experts a layer) than with
    the configuration's share, over seeds, and it is the count
    ``even_shares`` made."""
    from benchmarks.tasks import causal_lm

    cfg, bench_task, task, model, _, _ = toy
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    layers = cfg["hybrid_override_pattern"].count("E")
    even = layers * 2 * 72 * cfg["num_experts_per_tok"] \
        * cfg["held_experts"] / cfg["n_routed_experts"]
    counted = jax.jit(lambda p, b: task.loss_and_metrics(
        model, p, b, policy=FP32)[1]["moe_assignments"])
    off_first, off_even = [], []
    for seed in range(40, 45):
        plain = causal_lm.make_batch(np.random.default_rng(seed), 2, cfg)
        params = weights.make_weights(shapes, seed)
        firsts = bench_task.even_shares(params, plain["input_ids"], cfg)
        batch = {**plain, "first_experts": np.tile(firsts, (2, 1))}
        off_first.append(abs(float(counted(params, plain)) - even))
        off_even.append(abs(float(counted(params, batch)) - even))
    assert np.mean(off_even) < 0.4 * np.mean(off_first), (off_first,
                                                          off_even)
    assert max(off_even) < even * 0.15, off_even


def test_bf16_router_scores_fail_where_float32_is_stated(toy):
    """The configuration states the router in float32. Rounded to
    bfloat16, near-ties flip top-k choices: against the float32
    reference the share of tokens whose chosen set changes is what the
    chip's limits must see."""
    cfg, _, _, _, params, batch = toy
    p = params["layers"]["01_moe"]["mixer"]
    a = jax.random.normal(jax.random.key(0), (4096, 64))
    exact = ref.router_weights(p, a, cfg, "f32") > 0
    low = ref.router_weights(p, a, cfg, "bf16") > 0
    flipped = float((exact != low).any(-1).mean())
    assert 0.001 < flipped < 0.2


def test_the_cell_rehearses_and_the_control_fails_it(cell):
    result = harness.run_cell(cell, seed=3_300_000_007, seconds=0.5,
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
    task = harness.load_task("hybrid_lm")
    assert not hasattr(task, "flop_shape")     # not Perceiver's count
    assert task.tokens_per_row(cfg) == 4096
    parts = task.forward_parts(cfg)
    s, d = 4096, 2688
    # MFLOP a token, forward (ISSUE 33)
    assert parts["ssm_projections"] / (4 * s) == 2 * d * (10304 + 4096)
    scan = parts["ssm_scan"] / (4 * s)
    assert scan == 2 * (8 * 128 * 128 + 64 * 128 * 64
                        + 2 * 64 * 64 * 128)
    assert 3.3e6 < scan < 3.5e6                               # 3.4
    mamba = (parts["ssm_projections"] + parts["ssm_scan"]) / (4 * s)
    assert 80.7e6 < mamba < 80.9e6                            # 80.8
    routed = parts["routed_experts"] / (4 * s)
    assert routed == 0.375 * 2 * 2 * d * 1856                 # 7.5
    experts = (parts["router_and_shared"] + parts["routed_experts"]) \
        / (4 * s)
    assert 48.0e6 < experts < 48.2e6                          # 48.1
    assert parts["attention_projections"] / s \
        == 2 * (2 * d * 4096 + 2 * d * 256)                   # 46.8
    assert parts["causal_attention"] == 4 * (s * (s + 1) / 2) * 4096
    assert parts["head"] / s == 2 * d * 16384                 # 88.1
    per_token = sum(parts.values()) / s
    assert 683e6 < per_token < 685e6                          # 684
    step = task.train_step_flops(cfg, 4)
    assert step == 4 * 3 * sum(parts.values())
    assert 33.5e12 < step < 33.7e12                           # 33.6 TFLOP
    # the two mechanisms are three quarters of the products
    share = (parts["ssm_projections"] + parts["ssm_scan"]
             + parts["router_and_shared"] + parts["routed_experts"]) \
        / sum(parts.values())
    assert 0.74 < share < 0.77


def test_the_configuration_keeps_every_published_number(cell):
    config = cell.config
    entry = next(c for c in cell.manifest["configs"]
                 if c["name"] == "nemotron3_nano_30b")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert config["source"] == entry["source"]
    widths = ("_dim", "_rank", "_size", "channels", "latents")
    assert not any(k.endswith(widths) and k != "vocab_size"
                   and k != "batch_size" for k in config["reduced"])
    assert config["num_hidden_layers"] == 9
    assert config["n_routed_experts"] == 8 and config["vocab_size"] == 16384
    assert config["published"]["num_hidden_layers"] == 52
    assert config["published"]["n_routed_experts"] == 128
    assert config["published"]["vocab_size"] == 131072
    model = config["model"]
    assert model["hybrid_override_pattern"] == "MEMEM*EME" \
        == config["hybrid_override_pattern"][:9]
    assert len(config["hybrid_override_pattern"]) == 52
    assert len(model["hybrid_override_pattern"]) \
        == config["num_hidden_layers"]
    # the router keeps its width and its experts a token; 8 are held
    assert model["n_routed_experts"] == 128
    assert model["held_experts"] == config["n_routed_experts"] == 8
    for key in ("vocab_size", "hidden_size", "mamba_num_heads",
                "mamba_head_dim", "n_groups", "ssm_state_size",
                "conv_kernel", "chunk_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "num_experts_per_tok",
                "moe_intermediate_size",
                "moe_shared_expert_intermediate_size",
                "routed_scaling_factor", "norm_eps"):
        assert model[key] == config[key], key
    assert config["deployment"]["train"]["remat"] is model["remat"] is True
    assert "16 chips share each layer" in config["deployment"]["stands_for"]
    for key in ("position_embedding", "e_score_correction_bias", "init",
                "optimizer.lr", "optimizer.weight_decay", "optimizer.betas",
                "max_seq_len"):
        assert key in config["assumed"], key
    # 667.0 M parameters
    d = 2688
    mamba = d * 10304 + 4096 * d + 4 * 6144 + 6144 + 3 * 64 + 4096 + d
    attn = 2 * d * 4096 + 2 * d * 256 + d
    moe = d * 128 + 2 * d * 3712 + d + 8 * 2 * d * 1856
    total = 4 * mamba + attn + 4 * moe + 2 * 16384 * d + d
    assert abs(total - config["parameters"]) < 0.05e6
    if not os.path.exists(CATALOG):
        pytest.skip("no model catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key


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
            assert m["workloads"] == ["nemotron_train"]
            assert m["source"] == "device_trace"
            assert m["moves"] == "train_tokens_per_s"
        if m["name"] in ("flash_attention_roofline", "model.dense_pct",
                         "model.unscoped_pct", "model.loop_stack_pct"):
            assert "nemotron_train" not in m["workloads"]
    assert cell.chips == 1
    assert cell.mix["batch_rows"] * cell.config["model"]["max_seq_len"] \
        == 16384
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


def test_the_scans_cost_is_the_hand_count(cell):
    cfg = harness.flat_config(cell.config, rehearse=False)
    ops, moved = hybrid_costs.scan_cost(cfg, 4, 4096, backward=False)
    # a position: C B^T a group and scores x (dt x) a head over the
    # chunk's 128, a chunk's own state and its reading P x N a head
    assert ops == 4 * 4096 * 2 * (8 * 128 * 128 + 64 * 128 * 64
                                  + 2 * 64 * 64 * 128)
    # x and y 4096 channels, B and C 1024 each, in bfloat16; dt 64 floats
    assert moved == 4 * 4096 * (2 * (2 * 4096 + 2 * 1024) + 4 * 64)
    assert hybrid_costs.scan_cost(cfg, 4, 4096, backward=True) \
        == (2 * ops, 2 * moved)
    peak = flops.peaks("TPU v5 lite")
    t, bound = flops.roofline_seconds(ops, moved, peak)
    assert bound == "memory" and 0.3e-3 < t < 0.5e-3


def test_the_grouped_products_cost_follows_the_assignments(cell):
    cfg = harness.flat_config(cell.config, rehearse=False)
    assert hybrid_costs.expected_assignments(cfg, 16384) == 6144
    ops, moved = hybrid_costs.grouped_cost(cfg, 6144, backward=False)
    assert ops == 6144 * 2 * 2 * 2688 * 1856
    matrices = 8 * 2 * 2688 * 1856 * 2
    assert moved == matrices + 2 * 6144 * 2 * (2688 + 1856)
    twice, _ = hybrid_costs.grouped_cost(cfg, 12288, backward=False)
    assert twice == 2 * ops
    b_ops, b_moved = hybrid_costs.grouped_cost(cfg, 6144, backward=True)
    assert b_ops == 2 * ops and b_moved == 2 * moved
    # every expert held: the whole router
    assert hybrid_costs.expected_assignments(
        {**cfg, "held_experts": None}, 100) == 600


def _run(cell, tmp_path=None):
    cfg = harness.flat_config(cell.config, rehearse=False)
    outcome = type("O", (), {"data": {"rows": 4, "steps": 80}})()
    tracer = type("T", (), {"enabled": True, "directory": str(
        tmp_path / "trace") if tmp_path else "/nonexistent/x"})()
    return type("Run", (), {"trace": object(), "outcome": outcome,
                            "cfg": cfg, "tracer": tracer,
                            "peak": flops.peaks("TPU v5 lite")})()


def test_a_roofline_share_is_least_time_over_scope_time(cell, monkeypatch):
    run, cfg = _run(cell), harness.flat_config(cell.config, rehearse=False)
    by_scope = {"ssm_scan": 0.5, "moe_experts": 0.1}
    monkeypatch.setattr(hybrid_costs, "traced_whole_steps",
                        lambda run: (19.0, by_scope))
    monkeypatch.setattr(hybrid_costs.scope_times, "window_spans",
                        lambda run, what: None)
    least = 19 * 4 * sum(
        flops.roofline_seconds(*hybrid_costs.scan_cost(
            cfg, 4, 4096, backward=b), run.peak)[0] for b in (False, True))
    share = _reader("ssm_scan_roofline").read(run)
    assert share == pytest.approx(100 * least / 0.5)
    assert 0 < share < 100
    # no telemetry line to reach: the expected share of an even router
    least = 19 * 4 * sum(
        flops.roofline_seconds(*hybrid_costs.grouped_cost(
            cfg, 6144, backward=b), run.peak)[0] for b in (False, True))
    assert _reader("moe_expert_roofline").read(run) \
        == pytest.approx(100 * least / 0.1)
    # the scope absent (the parent): nothing
    by_scope.clear()
    assert _reader("ssm_scan_roofline").read(run) is None
    assert _reader("moe_expert_roofline").read(run) is None
    # no trace, or a trace without the marker: nothing
    monkeypatch.setattr(hybrid_costs, "traced_whole_steps", lambda run: None)
    assert _reader("ssm_scan_roofline").read(run) is None
    monkeypatch.undo()
    assert hybrid_costs.traced_whole_steps(run) is None   # no such file


def _plane(first_us, last_us, steps=4):
    """A device plane of ``steps`` steps of 10 us, cut to the
    operations that start in ``[first_us, last_us)``: the scan's
    product (1, once a step), an operation of a loop inside it (2,
    twice a step), the experts' product (3), the optimizer's long
    update (4) and a short one (5)."""
    stack = "jit(train_step)/jit(main)/"
    op_names = {1: stack + "hybrid_stack/ssm_mixer/ssm_scan/dot_general:",
                2: stack + "transpose(jvp(hybrid_stack))/ssm_mixer/"
                           "ssm_scan/while/body/mul:",
                3: stack + "hybrid_stack/moe/moe_experts/custom_call:",
                4: stack + "optimizer/mul:", 5: stack + "optimizer/add:"}
    layout = [(1, 1.0, 2.0), (2, 3.5, 0.5), (2, 4.5, 0.5), (3, 6.0, 1.0),
              (4, 8.0, 1.0), (5, 9.2, 0.2)]
    events = [(int((10 * k + at) * 1e6), int(dur * 1e6), meta)
              for k in range(steps) for meta, at, dur in layout
              if first_us <= 10 * k + at < last_us]
    return scope_times.DeviceOps("/device:TPU:0", events,
                                 {m: f"%op.{m}" for m in op_names}, op_names)


@pytest.mark.parametrize("first_us, last_us, steps", [
    (0, 40, 3), (5.5, 33, 2), (8.5, 40, 2), (0, 18.5, 1)])
def test_steps_and_scope_time_are_the_traces_whole_steps(first_us, last_us,
                                                        steps):
    """Wherever in a step the trace begins and ends, the steps counted
    are the marker's whole periods and the scopes' seconds those of the
    operations inside them: 3 us of scan and 1 us of experts a step."""
    found, by_scope = hybrid_costs.whole_steps([_plane(first_us, last_us)])
    assert found == steps
    assert by_scope["ssm_scan"] == pytest.approx(3e-6 * steps)
    assert by_scope["moe_experts"] == pytest.approx(1e-6 * steps)
    assert by_scope["optimizer"] == pytest.approx(1.2e-6 * steps)


def test_a_trace_of_less_than_a_step_or_without_the_marker_reads_nothing():
    assert hybrid_costs.whole_steps([_plane(0, 9)]) is None
    plane = _plane(0, 40)
    plane.op_names = {m: n.replace("optimizer", "elsewhere")
                      for m, n in plane.op_names.items()}
    assert hybrid_costs.whole_steps([plane]) is None
    assert hybrid_costs.whole_steps([]) is None


def test_the_experts_reader_takes_the_programs_counter(cell, tmp_path,
                                                       monkeypatch):
    tele = tmp_path / "telemetry"
    tele.mkdir()
    with open(tele / "telemetry.jsonl", "w") as f:
        for step, n in enumerate((24000, 25000, 26000, 29000), start=1):
            f.write(json.dumps({"step": step, "loss": 1.0,
                                "moe_assignments": n}) + "\n")
        f.write(json.dumps({"event": "other"}) + "\n")
    run = _run(cell, tmp_path)
    # the steps the trainer began inside the traced window, and no other
    monkeypatch.setattr(
        hybrid_costs.scope_times, "window_spans",
        lambda run, what: [{"name": "train/step", "step": 3},
                           {"name": "train/dispatch", "step": 3},
                           {"name": "train/step", "step": 4}])
    assert hybrid_costs.counted_assignments(run) == 27500
    # no span to say which: every line
    monkeypatch.setattr(hybrid_costs.scope_times, "window_spans",
                        lambda run, what: None)
    assert hybrid_costs.counted_assignments(run) == 26000
    run.tracer.directory = str(tmp_path / "elsewhere" / "trace")
    assert hybrid_costs.counted_assignments(run) is None
