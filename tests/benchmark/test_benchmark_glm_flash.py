"""The GLM-4.7-Flash cell, ``glm_flash_train``: one rehearsal of the
cell through ``run_cell`` with three AdamW steps and the control, the
shares a run holds (five expert layers, the module's last), the step's
operation count by part with both head readings, the configuration file
against the catalog row it was drawn from, the parameter count and the
16- and 20-byte sizes, ``program_task`` refusing a task class that
lacks a key, the two readers, and the manifest's entries for the cell
by membership."""

import dataclasses
import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import flops, harness, scope_times  # noqa: E402
from benchmarks.layer_metrics import kda_costs as costs  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "glm_flash_train"
NEW_METRICS = {"model.mtp_pct": "mtp", "model.mtp_loss_pct": "mtp_loss"}
APPENDED_TO = {"train.input_wait_pct", "train.host_ms_per_step",
               "setup.state_build_s", "setup.step_load_s",
               "model.attn_core_pct", "model.loss_pct",
               "train.optimizer_pct", "model.remat_pct",
               "causal_attention_roofline", "model.mla_mixer_pct"}
FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "scoped_trace.textproto")


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


@pytest.fixture(scope="module")
def cfg(cell):
    return harness.flat_config(cell.config, rehearse=False)


# --- the rehearsal -----------------------------------------------------------


def test_the_cell_rehearses_and_the_control_fails_it(cell):
    result = harness.run_cell(cell, seed=4_800_000_007, seconds=0.5,
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
    # the compared loss is the weighted sum: the reference's loss_sum
    # over blocks of one row is L1 + 0.3 L2 of the program's telemetry
    raw = result["raw"]
    assert "['mtp']['eh_proj']['w']" in raw["leaves"]
    assert len(raw["program"]["losses"]) == 3


def test_every_batch_of_a_run_names_the_same_five_shares(cell):
    """The task file's walk goes on through the module: a share an
    expert layer, the module's last, the same in every row and batch of
    a run, chosen from the router's loads alone."""
    from benchmarks import traffic

    toy = harness.flat_config(cell.config, rehearse=True)
    task = harness.load_task(toy["task"])
    mix = {**cell.mix, **cell.mix["rehearsal"]}
    pool = traffic.train_batches(mix, toy, 4_800_000_011, task.make_batch)
    firsts = pool[0]["first_experts"]
    assert firsts.shape == (mix["batch_rows"], 3)   # AEAE and the module
    assert all((b["first_experts"] == firsts).all() for b in pool)
    assert set(np.unique(firsts) % toy["held_experts"]) == {0}
    assert firsts.max() <= toy["n_routed_experts"] - toy["held_experts"]
    real = harness.flat_config(cell.config, rehearse=False)
    assert real["hybrid_override_pattern"].count("E") \
        + real["num_nextn_predict_layers"] == 5


def test_program_task_refuses_a_class_that_lacks_a_key(cfg, monkeypatch):
    """``program_kwargs`` drops unknown keys in silence, and a program
    without the module accepts the pattern: the task file raises at
    once, as on the parent commit."""
    import perceiver_tpu.tasks as tasks

    task = harness.load_task("glm_moe_lite_lm")
    cls, kwargs = task.program_task(cfg)
    assert cls is tasks.HybridLMTask
    assert kwargs["num_nextn_predict_layers"] == 1
    assert kwargs["q_lora_rank"] == 768 and kwargs["rope_theta"] == 1e6
    assert set(cfg) - set(kwargs) == set(task.BENCHMARK_KEYS)

    # the parent's class: no query latent, no module
    older = dataclasses.make_dataclass("HybridLMTask", [
        (f.name, f.type, f) for f in dataclasses.fields(cls)
        if f.name not in ("q_lora_rank", "num_nextn_predict_layers",
                          "mtp_loss_weight")], frozen=True)
    monkeypatch.setattr(tasks, "HybridLMTask", older)
    with pytest.raises(harness.BenchmarkError,
                       match="no field for mtp_loss_weight, "
                             "num_nextn_predict_layers, q_lora_rank"):
        task.program_task(cfg)


# --- counts ------------------------------------------------------------------


def test_the_steps_operation_count_is_the_hand_count(cfg):
    task = harness.load_task("glm_moe_lite_lm")
    assert not hasattr(task, "flop_shape")     # not Perceiver's count
    assert task.tokens_per_row(cfg) == 4096    # the module's not again
    parts = task.forward_parts(cfg)
    s, c = 4096, 2048
    # a latent layer: the query latent (768) and the queries from it
    # (20 x 256), the latent beside the shared key (512 + 64), keys and
    # values from it (20 x 448), out (5120)
    layer = s * 2 * (c * 768 + 768 * 5120 + c * 576 + 512 * 8960 + 5120 * c)
    assert parts["latent_projections"] == 4 * layer
    assert parts["mtp_latent_projections"] == layer
    # S (S + 1) / 2 pairs a head, 2 x 256 + 2 x 256 a pair
    core = 20 * (s * (s + 1) / 2) * 1024
    assert parts["latent_attention"] == 4 * core
    assert parts["mtp_latent_attention"] == core
    assert core == costs.latent_core_cost(cfg, 1, s, backward=False)[0] \
        == flops.flash_attention_cost(1, s, s, 20 * 256, backward=False,
                                      causal=True)[0]
    # the router's 64 outputs, the shared expert's three matrices of 1536
    outside = s * 2 * (c * 64 + 3 * c * 1536)
    assert parts["router_and_shared"] == 4 * outside
    assert parts["mtp_router_and_shared"] == outside
    # the even share: 4096 x 4 x 8 / 64 = 2,048 assignments a row
    routed = 2048 * 2 * 3 * c * 1536
    assert parts["routed_experts"] == 4 * routed
    assert parts["mtp_routed_experts"] == routed
    assert parts["mtp_eh_proj"] == s * 2 * 4096 * c
    # two readings of one head
    assert parts["head"] == parts["mtp_head"] == s * 2 * c * 19360
    row = sum(parts.values())
    assert 745.5e6 < row / s < 746.5e6          # 746 MFLOP a token forward
    step = task.train_step_flops(cfg, 4)
    assert step == 4 * 3 * row
    assert 36.6e12 < step < 36.8e12             # 36.7 TFLOP a step
    latent = sum(v for k, v in parts.items() if "latent" in k)
    module = sum(v for k, v in parts.items() if k.startswith("mtp_"))
    assert 0.565 < latent / row < 0.58          # 57%
    assert 0.275 < module / row < 0.285         # 28% with its head reading
    assert 0.175 < (module - parts["mtp_head"]) / row < 0.185   # 18% without
    assert 0.10 < parts["head"] / row < 0.11
    # without the module the count is a stack's alone
    plain = task.forward_parts({**cfg, "num_nextn_predict_layers": 0})
    assert sum(plain.values()) == row - module
    assert costs.expected_assignments(cfg, 16384) == 16384 * 4 * 8 / 64


# --- the configuration -------------------------------------------------------


def test_the_configuration_keeps_every_published_number(cell):
    config = cell.config
    entry = next(c for c in cell.manifest["configs"]
                 if c["name"] == "glm_4p7_flash")
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "batch_size", "max_steps", "lr_scheduler", "dataset",
        "weights"])
    assert config["source"] == entry["source"]
    assert entry["file"] == "benchmarks/configs/glm_4p7_flash.json"
    widths = ("_dim", "_rank", "_size", "channels", "latents")
    assert not any(k.endswith(widths) and k != "vocab_size"
                   and k != "batch_size" for k in config["reduced"])
    assert "num_experts_per_tok" not in config["reduced"]
    assert config["num_hidden_layers"] == 4
    assert config["first_k_dense_replace"] == 0
    assert config["n_routed_experts"] == 8 and config["vocab_size"] == 19360
    published = config["published"]
    assert published["num_hidden_layers"] == 47
    assert published["first_k_dense_replace"] == 1
    assert published["n_routed_experts"] == 64
    assert published["vocab_size"] == 154880 == 8 * 19360
    model = config["model"]
    assert model["n_routed_experts"] == 64 and model["held_experts"] == 8
    pattern = model["hybrid_override_pattern"]
    assert pattern == "AE" * config["num_hidden_layers"]
    assert model["num_nextn_predict_layers"] \
        == config["num_nextn_predict_layers"] == 1
    assert model["mtp_loss_weight"] == 0.3
    for key in ("model", "hybrid_override_pattern", "latent_attention",
                "rotary_channel_order", "unpadded_heads",
                "e_score_correction_bias", "multi_token_prediction",
                "auxiliary_loss", "init", "optimizer.lr", "max_seq_len",
                "remat"):
        assert key in config["assumed"], key
    module = config["assumed"]["multi_token_prediction"]
    for point in ("after the final norm", "comes first in the concatenation",
                  "0.3", "mean over the positions", "arXiv:2412.19437"):
        assert point in module, point
    stands_for = config["deployment"]["stands_for"]
    assert "8-chip expert-parallel group" in stands_for
    assert "last pipeline stage" in stands_for
    assert "16,384 x 4 / 64 = 1,024" in stands_for
    assert "first pipeline stage" in config["reduced"][
        "first_k_dense_replace"]
    assert config["deployment"]["train"]["optimizer"]["lr"] == 3e-7
    assert config["parameters"] == 621_840_640
    if not os.path.exists(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-4.7-Flash")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert published[key] == value, key
        else:
            assert config[key] == value, key
    # every width the program runs is the published one
    source = row["config"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "moe_intermediate_size",
                "routed_scaling_factor", "num_experts_per_tok",
                "norm_topk_prob", "rope_theta", "num_nextn_predict_layers"):
        assert model[key] == source[key], key
    assert model["moe_shared_expert_intermediate_size"] \
        == source["moe_intermediate_size"] * source["n_shared_experts"]
    assert model["norm_eps"] == source["rms_norm_eps"]
    assert model["n_routed_experts"] == source["n_routed_experts"]
    assert source["rope_scaling"] is None
    assert source["partial_rotary_factor"] == 1


def test_the_built_trees_count_is_the_files(cell, cfg):
    cls, kwargs = harness.load_task(cfg["task"]).program_task(cfg)
    shapes = jax.eval_shape(cls(**kwargs).build().init, jax.random.key(0))

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    assert count(shapes) == cell.config["parameters"] == 621_840_640
    layers = shapes["layers"]
    assert list(layers) == ["00_mla", "01_moe", "02_mla", "03_moe",
                            "04_mla", "05_moe", "06_mla", "07_moe"]
    mixer = layers["00_mla"]["mixer"]
    assert {n: x["w"].shape for n, x in mixer.items() if "w" in x} == {
        "q_a": (2048, 768), "q_b": (768, 5120), "kv_a": (2048, 576),
        "kv_b": (512, 8960), "out": (5120, 2048)}
    assert count(mixer) == 21_759_232
    assert count(layers["01_moe"]["mixer"]["experts"]) == 8 * 9_437_184
    assert count(layers["01_moe"]["mixer"]) - 8 * 9_437_184 == 9_568_256
    assert "shared_gate" not in layers["01_moe"]["mixer"]
    pair = count(layers["00_mla"]) + count(layers["01_moe"])
    assert pair == 106_829_056
    # the module: eh_proj, one such pair, three norms
    assert count(shapes["mtp"]) == 115_223_808 \
        == 2 * 2048 * 2048 + pair + 3 * 2048
    assert count(shapes["embed"]) + count(shapes["head"]) \
        == 2 * 19360 * 2048
    assert count(shapes) == 4 * pair + count(shapes["mtp"]) \
        + 2 * 19360 * 2048 + 2048
    # within 1% of the issue's 621.8 M; 16 bytes a parameter in the
    # trainer, 20 in the reference
    assert abs(count(shapes) / 621.8e6 - 1) < 0.01
    assert 9.94e9 < 16 * count(shapes) < 9.96e9
    assert 12.43e9 < 20 * count(shapes) < 12.45e9
    # every leaf has a rule in benchmarks/weights.py
    names = {str(getattr(p[-1], "key", p[-1])) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert names <= {"w", "scale", "embed"}


# --- the manifest and the readers --------------------------------------------


def test_the_manifest_lists_the_cell_where_its_readers_find_something(cell):
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names >= {"train.step_ms", "train.mfu_pct",
                     "device.idle_pct.train"} | APPENDED_TO \
        | set(NEW_METRICS)
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "train_tokens_per_s", "setup_s"}
    by_name = {m["name"]: m for m in cell.manifest["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert m["source"] == "device_trace" and m["unit"] == "%"
        assert m["moves"] == "train_tokens_per_s"
        assert m["layer"] == "model step" and m["better"] == "lower"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", f"{name}.py"))
    for name in APPENDED_TO:
        assert CELL in by_name[name]["workloads"]
    # the latent roofline multiplies one layer's cost by the pattern's
    # A layers and would miss the module's; no copy of the expert
    # layer's names is this cell's
    for name, m in by_name.items():
        if name not in set(NEW_METRICS) | APPENDED_TO and "workloads" in m:
            assert CELL not in m["workloads"], name
    entry = next(w for w in cell.manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "glm_4p7_flash", "clm_rows4_seq4096", 1)
    assert len(entry["why"]) <= 200
    assert any(c["name"] == "glm_4p7_flash"
               for c in cell.manifest["configs"])
    assert len(cell.manifest["workloads"]) >= 8
    assert cell.chips == 1
    end_to_end = {m["name"]: m for m in cell.manifest["end_to_end"]}
    assert CELL in end_to_end["train_tokens_per_s"]["workloads"]
    # nemotron_train's, qwen3next_train's and kimi_linear_train's rows:
    # the four cells differ by the model alone
    assert cell.mix == harness.load_cell("nemotron_train").mix \
        == harness.load_cell("kimi_linear_train").mix
    assert cell.mix["batch_rows"] * cell.config["model"]["max_seq_len"] \
        == 16384


def _reader(name):
    return harness.load_module(
        os.path.join(ROOT, "benchmarks", "layer_metrics", f"{name}.py"),
        "test_metric_" + name.replace(".", "_"))


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_reader_gives_nothing_where_nothing_carries_the_scope(name):
    """No trace, and a trace of a program without the scopes (the
    parent commit, every other cell): None, never an error."""
    tracer = type("T", (), {"enabled": False, "directory": "/nonexistent/x",
                            "mono0": None, "mono1": None})()
    run = type("Run", (), {"trace": None, "tracer": tracer})()
    assert _reader(name).read(run) is None
    # a recorded trace whose operations carry other scopes
    times = scope_times.reduce_scopes(scope_times.load_device_ops(FIXTURE))
    assert times.busy_s > 0 and NEW_METRICS[name] not in times.by_scope


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_reader_reads_its_scope_of_a_recorded_trace(name, monkeypatch):
    """The recorded trace with one of its scopes renamed to the new
    one: the reader gives that scope's share of the busy time."""
    scope = NEW_METRICS[name]
    planes = scope_times.load_device_ops(FIXTURE)
    stands_in = "attn_core"
    before = scope_times.reduce_scopes(planes)
    assert before.by_scope[stands_in] > 0
    for plane in planes:
        plane.op_names = {
            k: v.replace(f"/{stands_in}/", f"/{scope}/")
            for k, v in plane.op_names.items()}
    times = scope_times.reduce_scopes(planes)
    monkeypatch.setattr(scope_times, "traced_times", lambda run: times)
    share = _reader(name).read(object())
    assert share == pytest.approx(
        100.0 * before.by_scope[stands_in] / before.busy_s)
    assert 0 < share < 100
    other = next(s for s in NEW_METRICS.values() if s != scope)
    assert _reader(next(n for n, s in NEW_METRICS.items()
                        if s == other)).read(object()) is None


def test_the_new_scopes_are_the_programs():
    from perceiver_tpu.obs.trace import DEVICE_SCOPES

    assert set(NEW_METRICS.values()) <= set(DEVICE_SCOPES)
    # no class of the partition names them: the module's operations go
    # to the classes of the scopes inside it (PERF.md section 7)
    assert not set(NEW_METRICS.values()) & set(scope_times.SCOPE_CLASS)
