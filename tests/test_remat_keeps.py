"""What crosses the recomputation boundary of a ``remat`` layer
(``ops/remat.py``): the dear values are kept by name and not computed
again, the cheap ones are; which names are kept is chosen from the
reckoned bytes and what the device has left; the reckoning is what
autodiff saves; the trainer says what was chosen, for the encoder and
for the looped LM's hand-written backward alike; and with nothing kept
the looped LM lowers as if the names were not there."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import RUNS_ONCE
from jax._src.ad_checkpoint import saved_residuals

import perceiver_tpu.models.perceiver as perceiver
import perceiver_tpu.ops.attention as attn
import perceiver_tpu.ops.remat as remat
from perceiver_tpu.ops.policy import Policy
from perceiver_tpu.ops.remat import REMAT_NAMES, pick_remat_keeps
from perceiver_tpu.tasks import (
    BlockDiffusionLMTask,
    CausalLMTask,
    HybridLMTask,
    ImageClassifierTask,
    MaskedLanguageModelTask,
)
from perceiver_tpu.training import Trainer, TrainerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIXES = [REMAT_NAMES[:i] for i in range(len(REMAT_NAMES) + 1)]
FUSED = dict(attention_impl="flash", decoder_attention_impl="flash")


def rehearsal_task(name, **overrides):
    """The benchmark's configuration at its rehearsal sizes."""
    cls = {"perceiver_lm": MaskedLanguageModelTask,
           "perceiver_img": ImageClassifierTask,
           "nemotron3_nano_30b": HybridLMTask,
           "sdar_30b_a3b": BlockDiffusionLMTask}[name]
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           f"{name}.json")) as f:
        config = json.load(f)
    return cls(**{**config["model"], **config["rehearsal"]["model"],
                  **overrides})


BATCHES = {
    "perceiver_lm": {"input_ids": np.ones((4, 64), np.int32),
                     "pad_mask": np.zeros((4, 64), bool),
                     "valid": np.ones((4,), bool)},
    "perceiver_img": {"image": np.ones((4, 8, 8, 3), np.float32),
                      "label": np.ones((4,), np.int32),
                      "valid": np.ones((4,), bool)},
}


def make_trainer(task, tmp_path, mesh=None):
    trainer = Trainer(
        task, None,
        TrainerConfig(default_root_dir=str(tmp_path),
                      enable_checkpointing=False),
        optimizer_init={"class_path": "AdamW", "init_args": {"lr": 1e-3}},
        mesh=mesh)
    state = trainer._build_state()
    trainer._make_steps()
    return trainer, state


# --- (a) what the compiled step computes a second time -----------------------


@pytest.mark.parametrize("name", BATCHES)
def test_dear_values_are_not_computed_again(name, tmp_path):
    """``remat`` and the fused core, interpreted: no kernel call, no
    q/k/v projection and no MLP product is recomputed; norms, the GELU
    and the one product not worth its bytes (the out-projection) are."""
    task = rehearsal_task(name, **FUSED)
    assert task.remat
    trainer, state = make_trainer(task, tmp_path)
    text = trainer._train_step.lower(state, BATCHES[name]).compile(
        compiler_options=RUNS_ONCE).as_text()       # read, never run
    ops = re.findall(
        r'= \S+ ([a-z][\w-]*)\(.*?metadata=\{op_name="([^"]*)"', text)
    again = [(code, n) for code, n in ops if "rematted_computation" in n]
    assert len(again) > 50
    assert not [n for _, n in again if "flash_attention_" in n]
    # at most one product a checkpointed layer (the cross-attention's
    # and the block's scan body, of each of the encoder's layers)
    dots = [n for code, n in again if code == "dot"]
    assert 0 < len(dots) <= 2 * task.num_encoder_layers
    assert all("attn_proj" in n and "/mlp/" not in n for n in dots)
    # the kernels and the products are there, in the other passes
    assert [n for _, n in ops if "flash_attention_fwd" in n]
    assert [n for code, n in ops if code == "dot" and "/mlp/" in n]
    # what a pass over memory buys back is recomputed: both norms of a
    # layer (the attention's and the MLP's) and the GELU
    for layer in ("enc_cross_attn", "latent_self_attn"):
        here = [(code, n) for code, n in again if layer in n]
        assert [n for code, n in here if code == "rsqrt" and "/mlp/" in n]
        assert [n for code, n in here if code == "rsqrt"
                and "/mlp/" not in n]
        # (its erf is shared with the GELU's own backward rule: one
        # instruction after CSE; the products around it are not)
        assert [n for code, n in here if code == "multiply"
                and n.endswith("/mlp/mul")], layer
    assert not [n for _, n in again if "dec_cross_attn" in n]


# --- (c) the choosing function -----------------------------------------------

# lm_train's reckoning on one v5e (PERF.md, PR 29), in bytes
LM = {"attn_out": 1_993_572_352, "qkv": 2_793_406_464,
      "mlp_hidden": 981_467_136}
LM_IN = 981_467_136
V5E = 16_909_336_064


# ouro_train's: 32 layer applications of 2 rows x 4096 x 2048 (PR 32)
OURO = {"attn_out": 32 * (2 * 4096 * 2048 * 4 + 2 * 16 * 4096 * 4),
        "qkv": 32 * 2 * 4096 * 6144 * 2,
        "mlp_hidden": 32 * 2 * (2 * 4096 * 5632 * 2)}
OURO_IN = 32 * 2 * 4096 * 2048 * 2
# parameters and AdamW moments, 12 bytes a parameter: what the device
# reports in use when the trainer loads its step
LM_STATE, IMG_STATE, OURO_STATE = 936_000_000, 287_000_000, 7_349_000_000


def scaled(by):
    return {k: int(v * by) for k, v in LM.items()}, int(LM_IN * by)


# (bytes by name, layer_in, memory limit[, bytes in use])
#     -> (kept, first name dropped)
CHOICES = {
    "ouro_train_beside_its_state": ((OURO, OURO_IN, V5E, OURO_STATE),
                                    (REMAT_NAMES[:1], "qkv")),
    "ouro_train_blind_to_its_state": ((OURO, OURO_IN, V5E),
                                      (REMAT_NAMES[:2], "mlp_hidden")),
    "lm_train_beside_its_state": ((LM, LM_IN, V5E, LM_STATE),
                                  (REMAT_NAMES, None)),
    "img_train_beside_its_state": ((*scaled(0.09), V5E, IMG_STATE),
                                   (REMAT_NAMES, None)),
    "no_memory_report_beside_a_state": ((OURO, OURO_IN, None, OURO_STATE),
                                        (REMAT_NAMES, None)),
    "a_full_device_keeps_nothing": ((LM, LM_IN, V5E, V5E),
                                    ((), "attn_out")),
    "lm_train_whole_list": ((LM, LM_IN, V5E), (REMAT_NAMES, None)),
    "img_train_whole_list": ((*scaled(0.09), V5E), (REMAT_NAMES, None)),
    "no_memory_report": ((*scaled(100), None), (REMAT_NAMES, None)),
    "half_as_many_rows_again": ((*scaled(1.5), V5E), (REMAT_NAMES, None)),
    "three_fifths_more_rows": ((*scaled(1.6), V5E),
                               (REMAT_NAMES[:2], "mlp_hidden")),
    "twice_the_rows": ((*scaled(2), V5E), (REMAT_NAMES[:1], "qkv")),
    "four_times_the_rows": ((*scaled(4), V5E), ((), "attn_out")),
    "a_fifth_of_the_memory": ((LM, LM_IN, V5E // 5), ((), "attn_out")),
    "no_fused_core": (({**LM, "attn_out": 0}, LM_IN, int(V5E * 0.4)),
                      (REMAT_NAMES[:2], "mlp_hidden")),
    "per_shard_under_dp2": ((*scaled(2 / 2), V5E), (REMAT_NAMES, None)),
    "nothing_named": (({}, LM_IN, V5E), (REMAT_NAMES, None)),
}


@pytest.mark.parametrize("case", CHOICES)
def test_pick_remat_keeps(case):
    (held, layer_in, limit, *in_use), (kept, dropped) = CHOICES[case]
    got, why = pick_remat_keeps(held, layer_in_bytes=layer_in,
                                memory_limit=limit, memory_held=sum(in_use))
    assert got == tuple(kept)
    assert (why is None) == (dropped is None)
    if dropped:
        assert why.startswith(f"{dropped} would make ")
        # what was in use is part of the reason where it is part of it
        assert ("in use leave" in why) == bool(sum(in_use))
    # a prefix of the list, and what is kept is within the share of
    # what is left when a limit is known (the inputs are held anyway)
    assert got == REMAT_NAMES[:len(got)]
    if limit is not None and got:
        assert (layer_in + sum(held.get(n, 0) for n in got)
                <= remat.KEEP_SHARE * (limit - sum(in_use)))


def test_the_reason_gives_this_cells_numbers():
    """``ouro_train`` as the issue reckons it: 3.23 GB kept with
    ``attn_out``, 6.45 with ``qkv``, against 0.6 of the 9.56 GB that
    7.35 GB of state leave of 16.91."""
    kept, why = pick_remat_keeps(OURO, layer_in_bytes=OURO_IN,
                                 memory_limit=V5E, memory_held=OURO_STATE)
    assert kept == ("attn_out",)
    assert why == ("qkv would make 6.46 GB of 5.74, 0.6 of what 7.35 GB "
                   "in use leave")
    assert round((OURO_IN + OURO["attn_out"]) / 1e9, 2) == 3.24


def test_the_share_leaves_lm_train_a_margin():
    """The constant is set so that ``lm_train`` takes the whole list
    with room, and four times its rows nothing."""
    whole = LM_IN + sum(LM.values())
    assert 1.15 * whole < remat.KEEP_SHARE * V5E < 4 * (LM_IN + LM["attn_out"])


def test_a_name_outside_the_list_is_refused():
    with pytest.raises(ValueError, match="layer_out"):
        remat.dear(jnp.ones(3), "layer_out")


# --- (d) the reckoning is what autodiff saves --------------------------------


def layer_residual_bytes(model, params, x, pad):
    """Bytes of what the encoder's layers leave for the backward pass:
    every saved value but the function's arguments (a layer's own, cut
    from their stack, among them), constants and what is made before
    the first layer (adapter, hoisted keys and values)."""
    def f(p):
        latent, _ = model.encoder.apply(p, x, pad, policy=Policy.bf16())
        return latent.astype(jnp.float32).sum()

    outside = ("from the argument", "from a literal", "from a constant",
               "output of squeeze", "adapters/", "ops/norm.py", "ops/linear.py", "ops/policy.py")
    return sum(
        aval.size * aval.dtype.itemsize
        for aval, why in saved_residuals(f, params)
        if not any(src in why for src in outside))


@pytest.mark.parametrize("kept", PREFIXES, ids=lambda p: "+".join(p) or "none")
@pytest.mark.parametrize("core", ["fused", "materialized"])
def test_reckoned_bytes_are_what_is_saved(kept, core, monkeypatch):
    task = rehearsal_task("perceiver_lm",
                          **(FUSED if core == "fused" else {}))
    model = task.build()
    params = model.init(jax.random.key(0))["encoder"]
    x, pad = jnp.ones((2, 64), jnp.int32), jnp.zeros((2, 64), bool)
    reckoned = {}

    def choose(held, layer_in):
        reckoned.update(held, layer_in=layer_in)
        return kept

    monkeypatch.setattr(perceiver, "choose_keeps", choose)
    saved = layer_residual_bytes(model, params, x, pad)
    assert saved == reckoned["layer_in"] + sum(reckoned[n] for n in kept)
    # 3 cross-attention layers and 6 self-attention layers of 2 x 16 x 32
    tensor = 2 * 16 * 32 * 2
    assert reckoned["layer_in"] == 9 * tensor
    assert reckoned["qkv"] == (3 + 6 * 3) * tensor
    assert reckoned["mlp_hidden"] == 9 * tensor
    # float32 output and a log-sum-exp row a head; the materialised
    # core makes neither
    assert reckoned["attn_out"] == (
        9 * (2 * tensor + 2 * 2 * 16 * 4) if core == "fused" else 0)


# --- (e) the tally and the trainer's line ------------------------------------


def test_the_trainer_says_what_its_remat_layers_keep(tmp_path, capfd,
                                                     monkeypatch):
    task = rehearsal_task("perceiver_lm")
    trainer, state = make_trainer(task, tmp_path)
    batch = trainer._shard_batch(BATCHES["perceiver_lm"])
    with remat.remat_keeps() as outer:
        trainer._load_step(trainer._train_step, state, batch, "t")
    (choice,) = outer
    assert choice["kept"] == REMAT_NAMES and not choice["dropped"]
    assert choice["memory_limit"] is None and choice["why"] is None
    assert choice["bytes"]["attn_out"] == 0          # the CPU's core
    assert choice["bytes"]["layer_in"] == 9 * 4 * 16 * 32 * 2
    err = capfd.readouterr().err
    assert ("[step_load] attention call sites: materialized[backend]=3\n"
            "[step_load] remat keeps: attn_out,qkv,mlp_hidden + layer_in "
            "0.00 GB of no memory report\n") in err
    # a Perceiver has no mixer with a short convolution
    assert "short convolutions" not in err and "delta rules" not in err
    assert not remat._KEEP_TALLIES and not remat._RECORDERS

    # a chip too small for the list: the line says what went and why
    fits = choice["bytes"]["layer_in"] + choice["bytes"]["qkv"]
    monkeypatch.setattr(remat, "_memory_limit",
                        lambda: int((fits + 1) / remat.KEEP_SHARE))
    trainer, state = make_trainer(task, tmp_path / "small")
    trainer._load_step(trainer._train_step, state, batch, "t")
    err = capfd.readouterr().err
    assert re.search(
        r"\[step_load\] remat keeps: attn_out,qkv \+ layer_in 0\.00 GB of "
        r"0\.00 \(dropped mlp_hidden: mlp_hidden would make "
        r"0\.00 GB of 0\.00\)", err), err


def test_no_remat_traces_no_choice():
    task = rehearsal_task("perceiver_lm", remat=False)
    model = task.build()
    params = jax.eval_shape(model.init, jax.random.key(0))
    with remat.remat_keeps() as choices, remat.reckoning() as held:
        jax.eval_shape(
            lambda p: model.encoder.apply(
                p["encoder"], jnp.ones((2, 64), jnp.int32))[0], params)
    assert not choices
    assert remat.format_remat_keeps(choices) == "none traced"
    # the names are there all the same, as identities
    assert set(held) == {"qkv", "mlp_hidden"}


def test_bytes_are_one_devices_under_a_mesh(tmp_path, monkeypatch):
    """dp2 x tp2: the rows are split over ``data``, so a device holds
    half of what the same batch holds on one chip; the materialised
    core (reason ``mesh``) makes no ``attn_out``."""
    from perceiver_tpu.parallel import make_mesh

    monkeypatch.setattr(attn, "_backend", lambda: "tpu")
    task = rehearsal_task("perceiver_lm", num_latents=512, max_seq_len=512)
    batch = {"input_ids": np.ones((4, 512), np.int32),
             "pad_mask": np.zeros((4, 512), bool),
             "valid": np.ones((4,), bool)}
    reckoned = {}
    for name, mesh in (("one_chip", None),
                       ("dp2_tp2", make_mesh(4, model_parallel=2))):
        trainer, state = make_trainer(task, tmp_path / name, mesh)
        with remat.remat_keeps() as choices, attn.attention_paths() as paths:
            trainer._train_step.lower(state, trainer._shard_batch(batch))
        (choice,) = choices
        reckoned[name] = choice["bytes"], dict(paths)
    one, sites = reckoned["one_chip"]
    assert sites == {("fused", None): 2, ("materialized", "shape"): 1}
    assert one["attn_out"] > 0
    split, sites = reckoned["dp2_tp2"]
    assert sites == {("materialized", "mesh"): 3}
    assert split["attn_out"] == 0
    for name in ("layer_in", "qkv", "mlp_hidden"):
        assert 2 * split[name] == one[name] > 0, name


# --- (f) the looped LM: the same choice, the values by hand ------------------

LOOPED = dict(vocab_size=96, hidden_size=32, num_hidden_layers=2,
              num_attention_heads=2, head_dim=16, intermediate_size=48,
              max_seq_len=128, total_ut_steps=3, remat=True,
              ce_chunk_size=128)
LOOPED_BATCH = {"input_ids": np.ones((2, 128), np.int32),
                "valid": np.ones((2,), bool)}


@pytest.mark.parametrize("core", ["fused", "materialized"])
def test_names_change_nothing_in_the_looped_lm(core, tmp_path, monkeypatch):
    """``models/looped_lm.py`` calls the same ``mha_apply``, ``_project``
    and ``_flash_fwd``; with nothing kept its hand-written backward
    recomputes a whole layer under a bare ``jax.checkpoint``, where a
    name is an identity: its lowered step is the one without the
    names."""
    task = CausalLMTask(
        attention_impl="flash" if core == "fused" else "einsum", **LOOPED)
    monkeypatch.setattr(remat, "_memory_limit", lambda: 0)

    def lowered():
        trainer, state = make_trainer(task, tmp_path)
        with remat.reckoning() as seen, remat.remat_keeps() as choices:
            text = trainer._train_step.lower(state, LOOPED_BATCH).as_text()
        assert [c["kept"] for c in choices] == [()]
        # private functions are numbered as they are made
        return re.sub(r"(@[A-Za-z_]+)_\d+", r"\1", text), set(seen)

    named, seen = lowered()
    assert seen == {"qkv", "mlp_hidden"} | (
        {"attn_out"} if core == "fused" else set())
    monkeypatch.setattr(remat, "checkpoint_name", lambda x, name: x)
    bare, _ = lowered()
    assert named == bare


def test_the_trainer_says_what_the_looped_stack_keeps(tmp_path, capfd,
                                                      monkeypatch):
    """The same line, tally and rule as the encoder's; what the device
    already holds when the step is loaded is part of the choice."""
    task = CausalLMTask(attention_impl="flash", **LOOPED)
    applications = 3 * 2
    tensor = 2 * 128 * 32 * 2           # a row of states, bfloat16
    trainer, state = make_trainer(task, tmp_path)
    with remat.remat_keeps() as outer, attn.attention_paths() as paths:
        trainer._load_step(trainer._train_step, state, LOOPED_BATCH, "t")
    (choice,) = outer
    # the trace for shapes is not a call site: forward and backward
    assert dict(paths) == {("fused", None): 2}
    assert choice["kept"] == REMAT_NAMES and choice["memory_limit"] is None
    assert choice["memory_held"] == 0
    assert choice["bytes"] == {
        "layer_in": applications * tensor,
        # the float32 output and a log-sum-exp row a head
        "attn_out": applications * (2 * tensor + 2 * 2 * 128 * 4),
        "qkv": applications * 3 * tensor,
        "mlp_hidden": applications * 2 * (2 * 128 * 48 * 2)}
    assert ("[step_load] remat keeps: attn_out,qkv,mlp_hidden + layer_in "
            "0.00 GB of no memory report\n") in capfd.readouterr().err

    # a device whose state leaves room for the first name alone
    fits = choice["bytes"]["layer_in"] + choice["bytes"]["attn_out"]
    in_use = 5_000_000
    monkeypatch.setattr(remat, "_memory_held", lambda: in_use)
    monkeypatch.setattr(remat, "_memory_limit",
                        lambda: in_use + int((fits + 1) / remat.KEEP_SHARE))
    trainer, state = make_trainer(task, tmp_path / "small")
    with remat.remat_keeps() as outer:
        trainer._load_step(trainer._train_step, state, LOOPED_BATCH, "t")
    (choice,) = outer
    assert choice["kept"] == ("attn_out",)
    assert choice["dropped"] == ("qkv", "mlp_hidden")
    assert choice["memory_held"] == in_use
    err = capfd.readouterr().err
    assert re.search(
        r"\[step_load\] remat keeps: attn_out \+ layer_in 0\.00 GB of "
        r"0\.01 \(dropped qkv,mlp_hidden: qkv would make 0\.00 GB of 0\.00, "
        r"0\.6 of what 0\.01 GB in use leave\)", err), err
    assert not remat._KEEP_TALLIES and not remat._EXCHANGES


# --- (f) an expert layer's routing plan --------------------------------------


@pytest.mark.parametrize("name", ["nemotron3_nano_30b", "sdar_30b_a3b"])
def test_the_hybrid_stack_keeps_the_routing_plan(name, tmp_path, capfd,
                                                 monkeypatch):
    """``moe_plan`` is a name like any other: reckoned (the chosen
    experts, the sorted order and the way back, int32, 16 bytes an
    assignment, and the loads), kept where it fits, and said on the
    trainer's ``remat keeps`` line; where it does not fit the line
    says that nothing is kept."""
    task = rehearsal_task(name)
    layers = task.build().pattern.count("E")
    batch = {"input_ids": np.ones((2, 32), np.int32)}
    trainer, state = make_trainer(task, tmp_path)
    with remat.remat_keeps() as outer:
        trainer._load_step(trainer._train_step, state, batch, "t")
    (choice,) = outer
    assert choice["kept"][-1] == "moe_plan" == remat.HYBRID_REMAT_NAMES[-1]
    positions = 2 * 32 * (2 if name == "sdar_30b_a3b" else 1)
    assert choice["bytes"]["moe_plan"] == layers * 4 * (
        4 * positions * task.num_experts_per_tok + task.held_experts)
    err = capfd.readouterr().err
    assert re.search(r"remat keeps: \S*,moe_plan \+ layer_in", err), err

    monkeypatch.setattr(remat, "_memory_limit", lambda: 1)
    trainer, state = make_trainer(task, tmp_path / "small")
    trainer._load_step(trainer._train_step, state, batch, "t")
    err = capfd.readouterr().err
    assert "remat keeps: nothing + layer_in" in err
