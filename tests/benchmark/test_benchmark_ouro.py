"""The looped language model's cell, ``ouro_train``: program against
the plain reference at the configuration's rehearsal widths on the
benchmark's seeded weights (every pass's logits, the exit
distribution, the loss, the gradient leaf by leaf), one rehearsal of
the cell through ``run_cell``, the control, the step's operation count
against a hand count, the configuration file against the catalog row
it was drawn from, and the causal kernels' reader."""

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

from benchmarks import comparisons, flops, harness, weights  # noqa: E402
from benchmarks.reference import looped_lm as ref  # noqa: E402
from benchmarks.reference import perceiver_io as ref_steps  # noqa: E402

from perceiver_tpu.ops.policy import Policy  # noqa: E402

SEED = 2_800_000_123
FP32 = Policy.fp32()
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell("ouro_train")


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
    # from the wrong norm shows
    params = jax.tree.map(
        lambda x: x * (1.0 + 0.1 * jax.random.normal(
            jax.random.key(x.size), x.shape)) if x.ndim <= 2
        and x.shape[-1] == cfg["hidden_size"] else x, params)
    batch = bench_task.make_batch(np.random.default_rng(7), 2, cfg)
    return cfg, bench_task, task, model, params, batch


def test_every_leaf_has_a_rule_and_the_sizes_are_the_toy_ones(toy):
    cfg, _, _, _, params, batch = toy
    assert cfg["hidden_size"] == 64 and cfg["total_ut_steps"] == 4
    assert batch["input_ids"].shape == (2, cfg["max_seq_len"])
    assert batch["input_ids"].min() >= 0      # the whole vocabulary
    names = {str(getattr(p[-1], "key", p[-1])) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    assert names == {"w", "b", "scale", "embed"}
    assert params["layers"]["mlp"]["up"]["w"].shape == (2, 64, 176)


def test_logits_of_every_pass_and_the_gate_against_the_reference(toy):
    cfg, _, _, model, params, batch = toy
    ids = jnp.asarray(batch["input_ids"])
    logits, p = model.apply(params, ids, policy=FP32)
    want_logits, want_p = ref.pass_logits(params, ids, cfg)
    assert logits.shape == want_logits.shape == (4, 2, 64, 512)
    for t in range(4):      # every pass, not their mean
        np.testing.assert_allclose(logits[t], want_logits[t], atol=2e-4,
                                   rtol=1e-4)
    np.testing.assert_allclose(p, want_p, atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(want_p.sum(0), 1.0, rtol=1e-6)
    # the passes differ: a reference that ran the stack once would show
    assert float(jnp.abs(want_logits[3] - want_logits[0]).max()) > 0.1


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


def test_a_pass_left_out_or_bf16_parameters_fail_the_comparison(toy, cell):
    """What the chip's limits must catch, shown at the toy size against
    the rehearsal limits: one pass fewer, and parameters in bfloat16."""
    cfg, bench_task, task, model, params, batch = toy
    rb = bench_task.reference_batches([batch], cfg, 0, 1)[0]
    want_loss, want = ref_steps.loss_and_grads(
        params, rb, cfg, loss_sum=bench_task.loss_sum, block=1)
    want_n = comparisons.leaf_norms(want)
    limits = {**cell.limits, **cell.limits["rehearsal"]}

    def gaps(loss, grads):
        n = comparisons.leaf_norms(grads)
        return (abs(float(loss) - float(want_loss)) / float(want_loss),
                comparisons.worst_leaf_gap(n, want_n),
                comparisons.rms_leaf_gap(n, want_n))

    import dataclasses
    short = dataclasses.replace(task, total_ut_steps=3)
    three = gaps(*jax.value_and_grad(lambda p: short.loss_and_metrics(
        short.build(), p, batch, policy=FP32)[0])(params))
    assert three[1] > limits["grad_norm_gap"]
    rounded = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
    low = gaps(*jax.value_and_grad(lambda p: task.loss_and_metrics(
        model, p, batch, policy=FP32)[0])(rounded))
    sound = gaps(*jax.value_and_grad(lambda p: task.loss_and_metrics(
        model, p, batch, policy=FP32)[0])(params))
    assert low[2] > 10 * sound[2]


def test_the_cell_rehearses_and_the_control_fails_it(cell):
    result = harness.run_cell(cell, seed=3_200_000_003, seconds=0.5,
                              trace=False, rehearse=True, t_start=0.0,
                              device=dict(CPU), control="fp8")
    assert result["rehearsal"] is True and result["failed"] == 0
    assert result["rehearsal_checks_ok"] is True
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
    task = harness.load_task("causal_lm")
    assert not hasattr(task, "flop_shape")     # not Perceiver's count
    assert task.tokens_per_row(cfg) == 4096
    parts = task.forward_parts(cfg)
    s, c, width, v = 4096, 2048, 5632, 49152
    layer = 2 * (4 * c * c + 3 * c * width)            # 102.76 MFLOP a token
    assert layer == 102_760_448
    assert parts["layer_products"] == 32 * s * layer
    assert parts["causal_attention"] == 32 * 4 * (s * (s + 1) / 2) * c
    assert parts["head"] == 4 * s * 2 * c * v
    step = task.train_step_flops(cfg, 2)
    assert step == 2 * 3 * sum(parts.values())
    assert 113.5e12 < step < 114.1e12                   # ISSUE 28: 113.8
    per_token = step / (2 * s)
    assert 13.8e9 < per_token < 14.0e9
    # the loop and the per-pass head are all of it: 83% and 17%
    head_share = (parts["head"] + parts["exit_gate"]) / sum(parts.values())
    assert 0.17 < head_share < 0.18
    assert flops.flash_attention_cost(2, s, s, c, backward=True,
                                      causal=True)[0] \
        == 10 * 2 * (s * (s + 1) / 2) * c


def test_the_configuration_keeps_every_published_number(cell):
    config = cell.config
    entry = next(c for c in cell.manifest["configs"]
                 if c["name"] == "ouro_2p6b")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert config["source"] == entry["source"]
    widths = ("_dim", "_rank", "channels", "latents")
    assert not any(k.endswith(widths) for k in config["reduced"])
    assert config["num_hidden_layers"] == 8
    assert config["published"]["num_hidden_layers"] == 48
    model = config["model"]
    for key in ("vocab_size", "hidden_size", "num_hidden_layers",
                "num_attention_heads", "head_dim", "intermediate_size",
                "rms_norm_eps", "rope_theta", "total_ut_steps"):
        assert model[key] == config[key], key
    assert config["layer_types"][:8] == ["full_attention"] * 8
    assert config["deployment"]["train"]["remat"] is model["remat"] is True
    assert "six pipeline stages" in config["deployment"]["stands_for"]
    for key in ("norm_placement", "final_norm", "exit_gate",
                "projection_biases", "exit_entropy_beta",
                "optimizer.betas"):
        assert key in config["assumed"], key
    # 612.4 M parameters: the layers, embedding and head, norm and gate
    c, width, v = 2048, 5632, 49152
    layer = 4 * c * c + 3 * c * width + 4 * c
    assert layer == 51_388_416
    assert abs(8 * layer + 2 * v * c + c + c + 1 - config["parameters"]) \
        < 0.05e6
    if not os.path.exists(CATALOG):
        pytest.skip("no model catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
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
        "causal_attention_roofline", "model.loop_stack_pct",
        "model.exit_loss_pct"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "train_tokens_per_s", "setup_s"}
    # the readers that count every call in full, or class scopes the
    # model does not have, stay away from it
    for m in cell.manifest["per_layer"]:
        if m["name"] in ("flash_attention_roofline", "model.dense_pct",
                         "model.unscoped_pct"):
            assert "ouro_train" not in m["workloads"]
    assert cell.mix["batch_rows"] * cell.config["model"]["max_seq_len"] \
        == 8192
    assert cell.mix["trace_seconds"] == 10.0
    assert cell.mix["reference_block_rows"] == 1


def _reader(name):
    return harness.load_module(
        os.path.join(ROOT, "benchmarks", "layer_metrics", f"{name}.py"),
        "test_metric_" + name.replace(".", "_"))


def test_the_causal_reader_reads_its_kernels_and_no_others():
    reader = _reader("causal_attention_roofline")
    fwd = ("%causal_attention_fwd.7 = (f32[2,4096,2048]{2,1,0}, "
           "f32[2,16,1,4096]{3,2,1,0}) custom-call(bf16[2,4096,2048]{2,1,0} "
           "%a, bf16[2,4096,2048]{2,1,0} %b, bf16[2,4096,2048]{2,1,0} %c), "
           "custom_call_target=\"tpu_custom_call\"")
    assert reader.call_of(fwd) == ("causal_attention_fwd", 2, 4096, 2048, 2)
    bwd = fwd.replace("causal_attention_fwd.7",
                      "transpose_jvp_causal_attention_bwd__.3")
    assert reader.call_of(bwd)[0] == "causal_attention_bwd"
    assert reader.call_of(fwd.replace("causal_", "flash_")) is None
    assert reader.call_of("%fusion.3 = bf16[2,4096,2048] fusion("
                          "bf16[2,4096,2048] %causal_attention_fwd.7)") is None
    with pytest.raises(ValueError):
        reader.call_of(fwd.replace("bf16[2,4096,2048]{2,1,0} %b",
                                   "bf16[2,2048,2048]{2,1,0} %b"))
    # and the full-score reader never sees a causal call
    assert _reader("flash_attention_roofline").call_of(fwd) is None

    class Event:
        def __init__(self, name, ns):
            self.name, self.duration_ns = name, ns

    class Run:
        peak = flops.peaks("TPU v5 lite")
        trace = type("R", (), {"events": {0: [
            Event(fwd, 1.67e6), Event(fwd, 1.67e6), Event(bwd, 3.5e6),
            Event("%fusion.9 = f32[8] fusion(f32[8] %x)", 5e6)]}})()

    share = reader.read(Run())
    pairs = 4096 * 4097 / 2
    least = (2 * 4 + 10) * 2 * pairs * 2048 / 197e12
    assert share == pytest.approx(100 * least / 6.84e-3, rel=1e-3)
    assert 30 < share < 100
    Run.trace = None
    assert reader.read(Run()) is None


@pytest.mark.parametrize("name", ["model.loop_stack_pct",
                                  "model.exit_loss_pct"])
def test_the_scope_readers_give_nothing_where_nothing_carries_the_scope(
        name):
    run = type("Run", (), {"trace": None, "outcome": None})()
    assert _reader(name).read(run) is None
