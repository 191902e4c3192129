"""The Kimi Linear cell, ``kimi_linear_train``: one rehearsal of the cell
through ``run_cell`` with three AdamW steps and the control, the shares
a run holds, the step's operation count by part and the costs of the
rule and the latent core against hand counts, the configuration file
against the catalog row it was drawn from, the parameter count and the
16- and 20-byte sizes, the five readers, and the manifest's entries for
the cell by membership."""

import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import flops, harness  # noqa: E402
from benchmarks.layer_metrics import hybrid_costs  # noqa: E402
from benchmarks.layer_metrics import kda_costs as costs  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"model.kda_mixer_pct", "model.kda_rule_pct",
               "kda_rule_roofline", "model.mla_mixer_pct",
               "mla_attention_roofline"}
APPENDED_TO = {"train.input_wait_pct", "train.host_ms_per_step",
               "setup.state_build_s", "setup.step_load_s",
               "model.attn_core_pct", "model.loss_pct",
               "train.optimizer_pct", "model.remat_pct"}


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell("kimi_linear_train")


@pytest.fixture(scope="module")
def cfg(cell):
    return harness.flat_config(cell.config, rehearse=False)


# --- the rehearsal -----------------------------------------------------------


def test_the_cell_rehearses_and_the_control_fails_it(cell):
    result = harness.run_cell(cell, seed=4_400_000_007, seconds=0.5,
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


def test_every_batch_of_a_run_names_the_same_even_shares(cell):
    """The task file's walk: a share an expert layer, the same in every
    row and batch of a run, chosen from the router's loads alone."""
    from benchmarks import traffic

    toy = harness.flat_config(cell.config, rehearse=True)
    task = harness.load_task(toy["task"])
    mix = {**cell.mix, **cell.mix["rehearsal"]}
    pool = traffic.train_batches(mix, toy, 4_400_000_011, task.make_batch)
    firsts = pool[0]["first_experts"]
    assert firsts.shape == (mix["batch_rows"], 2)       # KDKEAE: two E
    assert all((b["first_experts"] == firsts).all() for b in pool)
    assert set(np.unique(firsts) % toy["held_experts"]) == {0}
    assert firsts.max() <= toy["n_routed_experts"] - toy["held_experts"]


# --- counts ------------------------------------------------------------------


def test_the_steps_operation_count_is_the_hand_count(cfg):
    task = harness.load_task("kimi_linear_lm")
    assert not hasattr(task, "flop_shape")     # not Perceiver's count
    assert task.tokens_per_row(cfg) == 4096
    parts = task.forward_parts(cfg)
    s, c = 4096, 2304
    # four KDA mixers: q, k, v and out (4096 each), the decay's and the
    # gate's low-rank pair (128 then 4096), beta (32)
    assert parts["kda_projections"] == 4 * s * 2 * (
        4 * c * 4096 + 2 * (c * 128 + 128 * 4096) + c * 32)
    assert parts["kda_rule"] == 4 * costs.rule_cost(
        cfg, 1, s, backward=False)[0]
    # the one latent layer: q (32 x 192), the latent beside the shared
    # key (512 + 64), keys and values from it (32 x 256), out
    assert parts["latent_projections"] == s * 2 * (
        c * 6144 + c * 576 + 512 * 8192 + 4096 * c)
    # at the published widths: 2 x 192 + 2 x 128 a pair and head
    assert parts["latent_attention"] == 32 * (s * (s + 1) / 2) * 640
    assert parts["dense_mlp"] == s * 2 * 3 * c * 9216
    # four expert layers: the router's 256 outputs, the shared expert's
    # three matrices of 1024 (no gate column)
    assert parts["router_and_shared"] == 4 * s * 2 * (c * 256 + 3 * c * 1024)
    # the even share: 4096 x 8 x 8 / 256 = 1,024 assignments a row
    assert parts["routed_experts"] == 4 * 1024 * 2 * 3 * c * 1024
    assert parts["head"] == s * 2 * c * 20480
    row = sum(parts.values())
    assert 736.0e6 < row / s < 736.5e6          # 736 MFLOP a token forward
    step = task.train_step_flops(cfg, 4)
    assert step == 4 * 3 * row
    assert 36.1e12 < step < 36.3e12             # 36.2 TFLOP a step
    # the mixers are 46% of the products, the rule itself a thirtieth
    kda = parts["kda_projections"] + parts["kda_rule"]
    assert 0.45 < kda / row < 0.47
    assert 0.03 < parts["kda_rule"] / row < 0.035
    assert 0.17 < parts["dense_mlp"] / row < 0.18
    assert 0.12 < parts["head"] / row < 0.13


def test_the_rules_cost_is_the_hand_count(cfg):
    ops, moved = costs.rule_cost(cfg, 4, 4096, backward=False)
    q, d, heads = 64, 128, 32
    a_position_and_head = 2 * (
        2 * q * d            # the decayed k k^T and q k^T
        + q * (d + d)        # the solves for U and W
        + 3 * d * d          # W S, q S, k^T v'
        + q * d)             # the masked scores times v'
    assert ops == 4 * 4096 * heads * a_position_and_head
    # q, k, v read and o written in bfloat16; g (a number a channel) and
    # beta float32
    assert moved == 4 * 4096 * heads * (2 * 4 * d + 4 * (d + 1))
    back = costs.rule_cost(cfg, 4, 4096, backward=True)
    assert back == (2 * ops, 2 * moved)
    # a row shorter than the chunk is one chunk of its own length
    short, _ = costs.rule_cost(cfg, 1, 40, backward=False)
    assert short == 40 * heads * 2 * (2 * 40 * d + 40 * 2 * d + 3 * d * d
                                      + 40 * d)
    # as many operations as the scalar rule's count at these shapes
    # would give: a vector decay changes what is multiplied in, not the
    # products
    from benchmarks.layer_metrics import gated_delta_costs
    as_scalar = {**cfg, "linear_num_key_heads": 32,
                 "linear_num_value_heads": 32, "linear_key_head_dim": 128,
                 "linear_value_head_dim": 128}
    assert gated_delta_costs.rule_cost(as_scalar, 4, 4096,
                                       backward=False)[0] == ops


def test_the_latent_cores_cost_is_at_the_published_widths(cfg):
    ops, moved = costs.latent_core_cost(cfg, 4, 4096, backward=False)
    pairs = 4096 * 4097 / 2
    assert ops == 4 * 32 * pairs * (2 * 192 + 2 * 128)
    assert moved == 2 * 4 * 4096 * 32 * (2 * 192 + 2 * 128)
    back, back_moved = costs.latent_core_cost(cfg, 4, 4096, backward=True)
    assert back == 4 * 32 * pairs * (3 * 2 * 192 + 2 * 2 * 128)
    assert back_moved == 2 * moved
    # heads padded to 256 lanes would count 1,024 operations a pair for
    # 640: the padding is not work
    padded = flops.flash_attention_cost(4, 4096, 4096, 32 * 256,
                                        backward=False, causal=True)[0]
    assert padded / ops == pytest.approx(1024 / 640)
    # and at one width the count is flash_attention_cost's
    one = {**cfg, "qk_nope_head_dim": 64, "qk_rope_head_dim": 64}
    for backward in (False, True):
        assert costs.latent_core_cost(one, 4, 4096, backward=backward) \
            == flops.flash_attention_cost(4, 4096, 4096, 32 * 128,
                                          backward=backward, causal=True)


def test_the_experts_cost_counts_three_products(cfg):
    ops, moved = costs.gated_grouped_cost(cfg, 4096, backward=False)
    assert ops == 4096 * 2 * 3 * 2304 * 1024
    assert moved == 2 * 8 * 3 * 2304 * 1024 + 2 * 4096 * 3 * (2304 + 1024)
    assert costs.gated_grouped_cost(cfg, 4096, backward=True)[0] == 2 * ops
    assert costs.expected_assignments(cfg, 16384) == 16384 * 8 * 8 / 256


# --- the configuration -------------------------------------------------------


def test_the_configuration_keeps_every_published_number(cell):
    config = cell.config
    entry = next(c for c in cell.manifest["configs"]
                 if c["name"] == "kimi_linear_48b_a3b")
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == sorted([
        "num_hidden_layers", "num_experts", "vocab_size", "batch_size",
        "max_steps", "lr_scheduler", "dataset", "weights"])
    assert config["source"] == entry["source"]
    widths = ("_dim", "_rank", "_size", "channels", "latents")
    assert not any(k.endswith(widths) and k != "vocab_size"
                   and k != "batch_size" for k in config["reduced"])
    assert config["num_hidden_layers"] == 5
    assert config["num_experts"] == 8 and config["vocab_size"] == 20480
    assert config["published"]["num_hidden_layers"] == 27
    assert config["published"]["num_experts"] == 256
    assert config["published"]["vocab_size"] == 163840 == 8 * 20480
    model = config["model"]
    assert model["n_routed_experts"] == 256 and model["held_experts"] == 8
    # published layers 1 to 5: the leading dense layer and one whole
    # period after it
    pattern = model["hybrid_override_pattern"]
    assert pattern == "KDKEKEAEKE"
    linear = config["linear_attn_config"]
    assert [i + 1 for i in range(5) if pattern[2 * i] == "K"] \
        == linear["kda_layers"][:4] == [1, 2, 3, 5]
    assert [i + 1 for i in range(5) if pattern[2 * i] == "A"] \
        == linear["full_attn_layers"][:1] == [4]
    assert pattern[1::2].count("D") == config["first_k_dense_replace"] == 1
    assert len(pattern) == 2 * config["num_hidden_layers"]
    for key in ("hybrid_override_pattern", "layer_equations",
                "latent_attention", "padded_heads", "kda_gate_rank",
                "e_score_correction_bias", "delta_chunk_size",
                "multi_token_prediction", "auxiliary_loss", "init",
                "optimizer.lr", "max_seq_len", "remat"):
        assert key in config["assumed"], key
    assert "32 chips share" in config["deployment"]["stands_for"]
    assert "16,384 x 8 / 256 = 512" in config["deployment"]["stands_for"]
    assert config["deployment"]["train"]["optimizer"]["lr"] == 3e-7
    assert config["parameters"] == 602_433_408
    if not os.path.exists(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key    # nested groups whole
    # every width the program runs is the published one
    published = row["config"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "intermediate_size",
                "moe_intermediate_size", "routed_scaling_factor"):
        assert model[key] == published[key], key
    assert model["kda_num_heads"] == published["linear_attn_config"][
        "num_heads"]
    assert model["kda_head_dim"] == published["linear_attn_config"][
        "head_dim"]
    assert model["kda_conv_kernel_size"] == published["linear_attn_config"][
        "short_conv_kernel_size"]
    assert model["num_experts_per_tok"] == published["num_experts_per_token"]
    assert model["moe_shared_expert_intermediate_size"] \
        == published["moe_intermediate_size"] * published["num_shared_experts"]
    assert model["norm_eps"] == published["rms_norm_eps"]
    assert model["n_routed_experts"] == published["num_experts"]
    assert model["norm_topk_prob"] == published["moe_renormalize"]
    assert model["router_scoring"] == published["moe_router_activation_func"]
    assert published["mla_use_nope"] and "rope_theta" not in model


def test_the_built_trees_count_is_the_files(cell, cfg):
    cls, kwargs = harness.load_task(cfg["task"]).program_task(cfg)
    shapes = jax.eval_shape(cls(**kwargs).build().init, jax.random.key(0))

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    assert count(shapes) == cell.config["parameters"] == 602_433_408
    layers = shapes["layers"]
    assert list(layers) == [
        "00_kda", "01_mlp", "02_kda", "03_moe", "04_kda", "05_moe",
        "06_mla", "07_moe", "08_kda", "09_moe"]
    assert count(layers["00_kda"]["mixer"]) == 39_514_272
    assert count(layers["06_mla"]["mixer"]) == 29_114_880
    assert count(layers["01_mlp"]["mixer"]) == 3 * 2304 * 9216
    assert count(layers["03_moe"]["mixer"]["experts"]) == 8 * 7_077_888
    assert count(layers["03_moe"]["mixer"]) - 8 * 7_077_888 == 7_667_712
    assert "shared_gate" not in layers["03_moe"]["mixer"]
    assert count(shapes["embed"]) + count(shapes["head"]) \
        == 2 * 20480 * 2304
    # within 1% of the issue's 602.4 M; 16 bytes a parameter in the
    # trainer, 20 in the reference
    assert abs(count(shapes) / 602.4e6 - 1) < 0.01
    assert 9.63e9 < 16 * count(shapes) < 9.65e9
    assert 12.04e9 < 20 * count(shapes) < 12.06e9
    # every leaf has a rule in benchmarks/weights.py
    names = {str(getattr(p[-1], "key", p[-1])) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert names <= {"w", "scale", "bias", "b", "embed"}


# --- the manifest and the readers --------------------------------------------


def test_the_manifest_lists_the_cell_where_its_readers_find_something(cell):
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert names >= {"train.step_ms", "train.mfu_pct",
                     "device.idle_pct.train"} | APPENDED_TO | NEW_METRICS
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "train_tokens_per_s", "setup_s"}
    by_name = {m["name"]: m for m in cell.manifest["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert "kimi_linear_train" in m["workloads"]
        assert m["source"] == "device_trace" and m["unit"] == "%"
        assert m["moves"] == "train_tokens_per_s"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", f"{name}.py"))
    assert by_name["kda_rule_roofline"]["layer"] \
        == by_name["mla_attention_roofline"]["layer"] == "kernels"
    for name in APPENDED_TO:
        assert "kimi_linear_train" in by_name[name]["workloads"]
    # a head padded to 256 lanes would count its zeros as work there,
    # and no copy of the expert layer's three names is this cell's
    for name, m in by_name.items():
        if name not in NEW_METRICS | APPENDED_TO and "workloads" in m:
            assert "kimi_linear_train" not in m["workloads"], name
    assert "kimi_linear_train" not in by_name[
        "causal_attention_roofline"]["workloads"]
    entry = next(w for w in cell.manifest["workloads"]
                 if w["name"] == "kimi_linear_train")
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "kimi_linear_48b_a3b", "clm_rows4_seq4096", 1)
    assert len(entry["why"]) <= 200
    assert any(c["name"] == "kimi_linear_48b_a3b"
               for c in cell.manifest["configs"])
    assert len(cell.manifest["workloads"]) >= 7
    assert cell.chips == 1
    # nemotron_train's and qwen3next_train's rows: the three cells
    # differ by the model alone
    assert cell.mix == harness.load_cell("nemotron_train").mix \
        == harness.load_cell("qwen3next_train").mix
    assert cell.mix["batch_rows"] * cell.config["model"]["max_seq_len"] \
        == 16384


def _reader(name):
    return harness.load_module(
        os.path.join(ROOT, "benchmarks", "layer_metrics", f"{name}.py"),
        "test_metric_" + name.replace(".", "_"))


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_readers_give_nothing_where_nothing_carries_the_scope(name,
                                                                  cfg):
    """No trace, and a program without the scopes (the parent commit):
    None, never an error."""
    outcome = type("O", (), {"data": {"rows": 4, "steps": 3}})()
    tracer = type("T", (), {"enabled": False, "directory": "/nonexistent/x",
                            "mono0": None, "mono1": None})()
    run = type("Run", (), {"trace": None, "outcome": outcome, "cfg": cfg,
                           "tracer": tracer, "peak": {}})()
    assert _reader(name).read(run) is None
    # another family's configuration under the same readers
    run.cfg = {"hidden_size": 8}
    assert _reader(name).read(run) is None
    for other in ("nemotron_train", "qwen3next_train"):
        run.cfg = harness.flat_config(harness.load_cell(other).config,
                                      rehearse=False)
        run.peak = flops.peaks("TPU v5 lite")
        assert _reader(name).read(run) is None


def _run(cfg):
    outcome = type("O", (), {"data": {"rows": 4, "steps": 30}})()
    tracer = type("T", (), {"enabled": True,
                            "directory": "/nonexistent/x"})()
    return type("Run", (), {"trace": type("R", (), {"events": {}})(),
                            "outcome": outcome, "cfg": cfg,
                            "tracer": tracer,
                            "peak": flops.peaks("TPU v5 lite")})()


@pytest.mark.parametrize("name,scope,cost,layers,what", [
    ("kda_rule_roofline", "kda_rule", costs.rule_cost, 4, "KDA rule"),
    ("mla_attention_roofline", "attn_core", costs.latent_core_cost, 1,
     "latent attention core")])
def test_a_roofline_reader_counts_its_work_over_its_scope(
        cfg, monkeypatch, capsys, name, scope, cost, layers, what):
    run = _run(cfg)
    by_scope = {scope: 2.0, "kda_mixer": 3.0}
    monkeypatch.setattr(hybrid_costs, "traced_whole_steps",
                        lambda run: (7.0, by_scope))
    least = 7 * layers * sum(
        flops.roofline_seconds(*cost(cfg, 4, 4096, backward=b),
                               run.peak)[0] for b in (False, True))
    share = _reader(name).read(run)
    assert share == pytest.approx(100 * least / 2.0)
    assert 0 < share < 100
    assert f"{what}: 2.0000 s on the device under {scope} over the 7 " \
        "whole steps" in capsys.readouterr().out
    # the scope absent (the parent): nothing
    by_scope.clear()
    assert _reader(name).read(run) is None


def test_the_latent_reader_leaves_a_stack_with_other_attention_alone(
        cfg, monkeypatch):
    """``attn_core`` is the latent layers' only where no ``*`` layer
    stands beside them."""
    run = _run({**cfg, "hybrid_override_pattern": "KEAE*E"})
    monkeypatch.setattr(hybrid_costs, "traced_whole_steps",
                        lambda run: (7.0, {"attn_core": 2.0}))
    assert _reader("mla_attention_roofline").read(run) is None


@pytest.mark.parametrize("name,scope", [
    ("model.kda_mixer_pct", "kda_mixer"),
    ("model.kda_rule_pct", "kda_rule"),
    ("model.mla_mixer_pct", "mla_mixer")])
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

    assert {"kda_mixer", "kda_rule", "mla_mixer"} <= set(DEVICE_SCOPES)
