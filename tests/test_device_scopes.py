"""The layer scopes on the train step's device operations
(``obs.trace.DEVICE_SCOPES``): every heavy operation of the compiled toy
step lies under the vocabulary, in every pass, and the scopes change
metadata and nothing else."""

import contextlib
import dataclasses
import os
import re
import sys

import jax
import numpy as np
import pytest
from conftest import RUNS_ONCE

from perceiver_tpu.tasks import ImageClassifierTask, MaskedLanguageModelTask
from perceiver_tpu.training import Trainer, TrainerConfig

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the benchmark's own reading of a name stack: what it cannot see in
# the step is not scoped
from benchmarks.scope_times import scopes_of as scopes_in  # noqa: E402

LAYERS = {"input_adapter", "enc_cross_attn", "latent_self_attn",
          "dec_cross_attn", "output_adapter", "loss", "optimizer"}
INNER = {"attn_core", "attn_proj", "mlp"}
HEAVY = ("dot", "exponential", "reduce")

SMALL = dict(num_latents=8, num_latent_channels=16, num_encoder_layers=2,
             num_encoder_self_attention_layers_per_block=2,
             num_encoder_cross_attention_heads=2,
             num_encoder_self_attention_heads=2)
TASKS = {
    "mlm": (MaskedLanguageModelTask(
        vocab_size=110, max_seq_len=32,
        num_decoder_cross_attention_heads=2, **SMALL),
        {"input_ids": np.ones((4, 32), np.int32),
         "pad_mask": np.zeros((4, 32), bool),
         "valid": np.ones((4,), bool)}),
    "img": (ImageClassifierTask(
        image_shape=(8, 8, 1), num_classes=10, num_frequency_bands=4,
        num_decoder_cross_attention_heads=1, **SMALL),
        {"image": np.ones((4, 8, 8, 1), np.float32),
         "label": np.ones((4,), np.int32),
         "valid": np.ones((4,), bool)}),
}


def lower_step(task, batch, tmp_path):
    trainer = Trainer(
        task, None,
        TrainerConfig(default_root_dir=str(tmp_path),
                      enable_checkpointing=False),
        optimizer_init={"class_path": "AdamW", "init_args": {"lr": 1e-3}})
    state = trainer._build_state()
    trainer._make_steps()
    return trainer._train_step.lower(state, batch)


# the same steps with every attention forced onto the fused kernels
# (interpreted here: the kernel bodies are ordinary operations under
# the kernel's name)
FUSED = dict(attention_impl="flash", decoder_attention_impl="flash")


@pytest.fixture(scope="module", params=[
    (name, remat, fused) for name in TASKS for remat in (False, True)
    for fused in (False, True)],
    ids=lambda p: (f"{p[0]}-{'remat' if p[1] else 'plain'}"
                   f"{'-fused' if p[2] else ''}"))
def named_ops(request, tmp_path_factory):
    """(opcode, name stack) of every instruction of the compiled toy
    step that carries one (the compiler's own rewrites carry none)."""
    name, remat, fused = request.param
    task, batch = TASKS[name]
    task = dataclasses.replace(task, remat=remat, **(FUSED if fused else {}))
    lowered = lower_step(task, batch, tmp_path_factory.mktemp("scopes"))
    text = lowered.compile(compiler_options=RUNS_ONCE).as_text()
    ops = re.findall(
        r'= \S+ ([a-z][\w-]*)\(.*?metadata=\{op_name="([^"]*)"', text)
    assert len(ops) > 1000
    return remat, fused, ops


def test_every_heavy_operation_is_under_one_layer(named_ops):
    _, _, ops = named_ops
    heavy = [(code, name) for code, name in ops if code in HEAVY]
    assert len(heavy) > 100
    for code, name in heavy:
        found = scopes_in(name)
        layers = [s for s in found if s in LAYERS]
        inner = [s for s in found if s in INNER]
        assert len(layers) == 1, (code, name)
        assert len(inner) <= 1, (code, name)
        # an inner scope lies inside an attention layer, never alone
        assert not inner or layers[0].endswith("_attn"), (code, name)


def test_attention_core_is_scoped_in_every_pass(named_ops):
    remat, fused, ops = named_ops
    core = [name for code, name in ops
            if code in HEAVY and "attn_core" in scopes_in(name)]
    forward = [n for n in core if "transpose(" not in n]
    # the custom VJP's backward: the recomputed softmax and the four
    # gradient contractions (or the backward kernel) carry the scope
    # of the forward's call
    backward = [n for n in core if "transpose(" in n
                and "rematted_computation" not in n]
    assert forward and backward
    if fused:
        # the kernels' names sit under the scope, in every pass
        assert all("flash_attention_fwd" in n for n in forward)
        # (beside the backward kernel: delta's and the bias's sums)
        assert any("flash_attention_bwd" in n for n in backward)
    else:
        assert any("bhqk,bqhd->bkhd" in n for n in backward)    # dv, dk
    for layer in ("enc_cross_attn", "latent_self_attn", "dec_cross_attn"):
        assert any(layer in scopes_in(n) for n in forward), layer
        assert any(layer in scopes_in(n) for n in backward), layer
    # what ``remat`` recomputes of the core (ops/remat.py): nothing of
    # the fused one, whose saved output and log-sum-exp row cross the
    # boundary by name; the materialised core's forward still, under its
    # layer's scope (its backward rebuilds the softmax by design, and
    # the probabilities are not to be held)
    recomputed = [n for n in core if "rematted_computation" in n]
    assert bool(recomputed) == (remat and not fused)
    for n in recomputed:
        assert {"enc_cross_attn", "latent_self_attn"} & set(scopes_in(n)), n
        # the decoder is outside the checkpointed layers
        assert "dec_cross_attn" not in scopes_in(n), n


def test_optimizer_and_loss_are_scoped(named_ops):
    _, _, ops = named_ops
    names = [name for _, name in ops]
    update = [n for n in names if "optimizer" in scopes_in(n)]
    assert len(update) > 50
    assert not any("jvp(" in n or "transpose(" in n for n in update)
    assert not any(set(scopes_in(n)) - {"optimizer"} for n in update)
    loss = [n for code, n in ops
            if code in HEAVY and "loss" in scopes_in(n)]
    assert any("transpose(" in n for n in loss)
    assert any("transpose(" not in n for n in loss)


@pytest.mark.parametrize("name,remat", [("mlm", True), ("img", False)])
def test_scopes_change_metadata_and_nothing_else(name, remat, tmp_path,
                                                 monkeypatch):
    task, batch = TASKS[name]
    task = dataclasses.replace(task, remat=remat)
    scoped = lower_step(task, batch, tmp_path)
    assert "attn_core" in scoped.as_text(debug_info=True)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = lower_step(task, batch, tmp_path)
    assert "attn_core" not in bare.as_text(debug_info=True)
    assert scoped.as_text() == bare.as_text()


# --- a weight-shared decoder stack run several times ------------------------
# (models/looped_lm.py): loop_stack around the passes, decoder_layer
# around one layer application, exit_gate and exit_loss beside them; the
# inner scopes are the shared ones, so the class readers read this model
# unedited. Its backward pass is written by hand: what it recomputes of
# the forward carries JAX's own mark.

from benchmarks.scope_times import names_of  # noqa: E402
from perceiver_tpu.obs.trace import DEVICE_SCOPES  # noqa: E402
from perceiver_tpu.tasks import CausalLMTask  # noqa: E402

LOOPED = CausalLMTask(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                      num_attention_heads=2, head_dim=16,
                      intermediate_size=48, max_seq_len=128,
                      total_ut_steps=3, remat=True, ce_chunk_size=128)
LOOPED_BATCH = {"input_ids": np.ones((2, 128), np.int32),
                "valid": np.ones((2,), bool)}
LOOPED_LAYERS = {"input_adapter", "loop_stack", "exit_gate", "exit_loss",
                 "optimizer"}


@pytest.fixture(scope="module", params=[False, True],
                ids=["materialized", "fused"])
def looped_ops(request, tmp_path_factory):
    task = dataclasses.replace(
        LOOPED, attention_impl="flash" if request.param else "einsum")
    lowered = lower_step(task, LOOPED_BATCH,
                         tmp_path_factory.mktemp("looped"))
    ops = re.findall(
        r'= \S+ ([a-z][\w-]*)\(.*?metadata=\{op_name="([^"]*)"',
        lowered.compile(compiler_options=RUNS_ONCE).as_text())
    assert len(ops) > 500
    return request.param, ops


def looped_scopes(name):
    return [s for s in names_of(name) if s in DEVICE_SCOPES]


def test_the_new_scopes_are_in_the_vocabulary():
    assert {"loop_stack", "decoder_layer", "exit_gate",
            "exit_loss"} <= set(DEVICE_SCOPES)


def test_looped_every_heavy_operation_is_under_one_layer(looped_ops):
    _, ops = looped_ops
    heavy = [(code, name) for code, name in ops if code in HEAVY]
    assert len(heavy) > 60
    for code, name in heavy:
        found = looped_scopes(name)
        layers = [s for s in found if s in LOOPED_LAYERS]
        assert len(layers) == 1, (code, name)
        if "decoder_layer" in found:
            assert layers == ["loop_stack"], (code, name)
        if set(found) & {"attn_core", "attn_proj", "mlp"}:
            assert "decoder_layer" in found, (code, name)
        if "loss" in found:      # the fused CE's own scope stays inside
            assert layers == ["exit_loss"], (code, name)


def test_looped_passes_are_marked_in_the_hand_written_backward(looped_ops):
    fused, ops = looped_ops
    core = [n for code, n in ops
            if code in HEAVY and "attn_core" in looped_scopes(n)]
    recomputed = [n for n in core if "rematted_computation" in n]
    backward = [n for n in core if "transpose(" in n
                and "rematted_computation" not in n]
    forward = [n for n in core if "transpose(" not in n
               and "rematted_computation" not in n]
    assert forward and backward
    if fused:
        # the kernel's float32 output and log-sum-exp are kept (the CPU
        # keeps every name, ops/remat.py): its forward is not run again
        assert not recomputed
        assert all("causal_attention_fwd" in n for n in forward)
        assert any("causal_attention_bwd" in n for n in backward)
        assert not any("flash_attention_" in n for n in core)
    else:
        # the materialised core names no value: it is rebuilt
        assert recomputed
    mlp = [n for code, n in ops if code == "dot"
           and "mlp" in looped_scopes(n)]
    assert any("rematted_computation" in n for n in mlp)
    assert any("transpose(" in n and "rematted_computation" not in n
               for n in mlp)


def test_looped_exit_loss_gate_and_optimizer_are_scoped(looped_ops):
    _, ops = looped_ops
    loss = [n for code, n in ops
            if code in HEAVY and "exit_loss" in looped_scopes(n)]
    assert any("transpose(" in n for n in loss)
    assert any("transpose(" not in n for n in loss)
    assert any("loss" in looped_scopes(n) for n in loss)
    assert any("exit_gate" in looped_scopes(n) for _, n in ops)
    update = [n for _, n in ops if "optimizer" in looped_scopes(n)]
    assert len(update) > 30
    assert not any(set(looped_scopes(n)) - {"optimizer"} for n in update)


# --- a stack of state-space, expert and attention layers ---------------------
# (models/hybrid_lm.py): hybrid_stack around the layers; ssm_mixer around
# a Mamba-2 mixer with ssm_scan around its chunked scan alone; moe around
# an expert layer with moe_route (router, top-k, sort, gathers, combine)
# and moe_experts (the grouped products) inside; the attention layer and
# the shared expert carry the shared scopes.

from perceiver_tpu.tasks import HybridLMTask  # noqa: E402

HYBRID = HybridLMTask(
    vocab_size=96, hidden_size=32, hybrid_override_pattern="ME*",
    mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    chunk_size=16, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    n_routed_experts=8, held_experts=4, first_expert=2,
    num_experts_per_tok=2, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, max_seq_len=32, remat=True,
    ce_chunk_size=64)
HYBRID_BATCH = {"input_ids": np.ones((2, 32), np.int32),
                "valid": np.ones((2,), bool)}
HYBRID_SCOPES = ("hybrid_stack", "ssm_mixer", "ssm_scan", "moe",
                 "moe_route", "moe_experts")


@pytest.fixture(scope="module")
def hybrid_ops(tmp_path_factory):
    lowered = lower_step(HYBRID, HYBRID_BATCH,
                         tmp_path_factory.mktemp("hybrid"))
    ops = re.findall(
        r'= \S+ ([a-z][\w-]*)\(.*?metadata=\{op_name="([^"]*)"',
        lowered.compile(compiler_options=RUNS_ONCE).as_text())
    assert len(ops) > 300
    return ops


@pytest.mark.parametrize("scope", HYBRID_SCOPES)
def test_hybrid_scope_is_in_the_lowered_steps_name_stacks(hybrid_ops, scope):
    """Each new scope is in the vocabulary and on operations of the
    forward pass, the backward pass and what ``remat`` recomputes."""
    assert scope in DEVICE_SCOPES
    mine = [n for _, n in hybrid_ops if scope in looped_scopes(n)]
    assert mine
    assert any("transpose(" in n for n in mine)
    assert any("rematted_computation" in n for n in mine)
    assert any("transpose(" not in n and "rematted_computation" not in n
               for n in mine)


def test_hybrid_scopes_nest_as_the_readers_take_them(hybrid_ops):
    heavy = [(code, n) for code, n in hybrid_ops if code in HEAVY]
    assert len(heavy) > 40
    for code, name in heavy:
        found = looped_scopes(name)
        if "ssm_scan" in found:
            assert "ssm_mixer" in found, (code, name)
        if set(found) & {"moe_route", "moe_experts"}:
            assert "moe" in found, (code, name)
        if set(found) & {"ssm_mixer", "moe", "attn_core", "attn_proj"}:
            assert "hybrid_stack" in found, (code, name)
        assert not {"ssm_mixer", "moe"} <= set(found), (code, name)
    # the scan's products are under ssm_scan, the in- and out-projection
    # under ssm_mixer alone; the experts' products under moe_experts,
    # the router's under moe_route, the shared expert's under moe alone
    dots = [looped_scopes(n) for code, n in hybrid_ops if code == "dot"]
    assert any("ssm_scan" in f for f in dots)
    assert any("ssm_mixer" in f and "ssm_scan" not in f for f in dots)
    assert any("moe_route" in f for f in dots)
    assert any("moe" in f and "mlp" in f and "moe_experts" not in f
               for f in dots)
    assert any("moe_experts" in f and "mlp" in f for f in dots)
