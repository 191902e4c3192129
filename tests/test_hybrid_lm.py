"""The hybrid state-space / mixture-of-experts stack
(``models/hybrid_lm.py``, ``ops/ssm.py``, ``ops/moe.py``): each mixer's
forward pass and gradient against the plain reference's, the chunked
scan against the position-by-position recurrence (a length that is no
multiple of the chunk, decays that underflow) as einsums (the Pallas
kernels of ``ops/pallas_ssm.py`` have ``tests/test_pallas_ssm.py``),
the share tied to the model (16 shares of the experts add up to the
uncut layer, the shared expert once), no dropped token under a forced
imbalance, grouped queries against repeated keys and values, no
position embedding, what ``remat`` keeps, and what the trainer says and
logs."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import weights  # noqa: E402
from benchmarks.reference import hybrid_lm as ref  # noqa: E402

import perceiver_tpu.ops.remat as remat  # noqa: E402
from perceiver_tpu.models import hybrid_lm  # noqa: E402
from perceiver_tpu.ops import moe, ssm  # noqa: E402
from perceiver_tpu.ops.attention import mha_apply  # noqa: E402
from perceiver_tpu.ops.mlp import relu2_mlp_apply, relu2_mlp_init  # noqa: E402
from perceiver_tpu.ops.policy import Policy  # noqa: E402
from perceiver_tpu.tasks import HybridLMTask  # noqa: E402
from perceiver_tpu.training import Trainer, TrainerConfig  # noqa: E402

FP32 = Policy.fp32()
TOY = dict(
    vocab_size=256, hidden_size=48, hybrid_override_pattern="MEM*E",
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    conv_kernel=4, chunk_size=16, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, n_routed_experts=16,
    num_experts_per_tok=3, moe_intermediate_size=40,
    moe_shared_expert_intermediate_size=80, routed_scaling_factor=2.5,
    norm_eps=1e-5, max_seq_len=40, held_experts=4, first_expert=4,
    ce_chunk_size=64)


def rel(a, b):
    return float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-30)


@pytest.fixture(scope="module")
def toy():
    task = HybridLMTask(**TOY)
    model = task.build()
    params = weights.make_weights(
        jax.eval_shape(model.init, jax.random.key(0)), 33)
    ids = jax.random.randint(jax.random.key(1), (2, 40), 0, 256)
    return task, model, params, {"input_ids": ids}


@pytest.fixture(scope="module")
def plain_step(toy):
    """``((loss, metrics), gradients)`` of the toy as plain autodiff
    gives them, without remat: the fixed side of every case that asks
    whether something else is the same, computed once."""
    task, model, params, batch = toy
    return jit_once(jax.value_and_grad(
        lambda p: task.loss_and_metrics(model, p, batch, policy=FP32),
        has_aux=True))(params)


def scan_inputs(seq, *, dt_scale=1.0, a_scale=1.0, rows=2, heads=4,
                width=8, groups=2, state=16):
    k = jax.random.split(jax.random.key(seq), 5)
    x = jax.random.normal(k[0], (rows, seq, heads, width))
    dt = dt_scale * jax.nn.softplus(jax.random.normal(k[1],
                                                      (rows, seq, heads)))
    a = -a_scale * jnp.exp(0.3 * jax.random.normal(k[2], (heads,)))
    b, c = (jax.random.normal(kk, (rows, seq, groups, state))
            for kk in k[3:])
    return x, dt, a, b, c


def recurrence(x, dt, a, b, c):
    per = x.shape[2] // b.shape[2]
    return ref.recurrence(x, dt, a, jnp.repeat(b, per, axis=2),
                          jnp.repeat(c, per, axis=2))


# --- the chunked scan --------------------------------------------------------


@pytest.mark.parametrize("seq", [40, 32, 7, 129])
def test_the_chunked_scan_is_the_recurrence(seq):
    """40 and 129 are no multiples of the chunk (a padded last chunk),
    7 is shorter than one."""
    args = scan_inputs(seq)
    with ssm.scan_paths.counting() as forms:
        got = jit_once(lambda *a: ssm.ssm_scan(*a, chunk_size=16))(*args)
    chunk = min(16, seq)
    pad = "+pad" if seq % chunk else ""
    assert dict(forms) == {
        f"chunked[{chunk}x{-(-seq // chunk)}{pad},backend]": 1}
    want = jit_once(recurrence)(*args)
    assert got.shape == want.shape == args[0].shape
    assert rel(got, want) < 1e-5


def test_the_scans_gradient_is_the_recurrences():
    args = scan_inputs(40)
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    got = jit_once(jax.grad(
        lambda *a: (ssm.ssm_scan(*a, chunk_size=16) * w).sum(),
        argnums=range(5)))(*args)
    want = jit_once(jax.grad(lambda *a: (recurrence(*a) * w).sum(),
                            argnums=range(5)))(*args)
    for g, r in zip(got, want):
        assert rel(g, r) < 2e-5


def out_and_cotangents_of_ones(scan, args):
    """A scan's output and what its backward gives for a cotangent of
    ones, as one jitted program."""
    def run(*a):
        out, vjp = jax.vjp(scan, *a)
        return out, vjp(jnp.ones_like(out))

    return jit_once(run)(*args)


def test_decays_that_underflow_do_so_quietly():
    """dt A near -40 a position: the decay over a chunk is exp(-600),
    which float32 does not hold; nothing is inf or nan, forward or
    backward, and the result is still the recurrence's."""
    args = scan_inputs(40, dt_scale=5.0, a_scale=8.0)
    assert float((args[1] * args[2]).min()) < -40
    got, grads = out_and_cotangents_of_ones(
        lambda *a: ssm.ssm_scan(*a, chunk_size=16), args)
    assert all(bool(jnp.isfinite(g).all()) for g in (got, *grads))
    assert rel(got, jit_once(recurrence)(*args)) < 1e-5
    # in the compute dtype of the chip too
    low = ssm.ssm_scan(args[0].astype(jnp.bfloat16), args[1], args[2],
                       *(v.astype(jnp.bfloat16) for v in args[3:]),
                       chunk_size=16)
    assert low.dtype == jnp.bfloat16 and bool(jnp.isfinite(low).all())


# --- each mixer against the reference ----------------------------------------


def mixer_case(toy, name):
    _, model, params, batch = toy
    p = params["layers"][name]["mixer"]
    a = jax.random.normal(jax.random.key(5), (2, 40, TOY["hidden_size"]))
    w = jax.random.normal(jax.random.key(6), a.shape)
    return model, p, a, w


def with_gradient(fn, w):
    """``fn(p, a)``'s weighted sum, its output and both gradients as
    one jitted program."""
    def weighted(p, a):
        out = fn(p, a)
        return (out * w).sum(), out

    return jit_once(jax.value_and_grad(weighted, argnums=(0, 1),
                                      has_aux=True))


def assert_programs_agree(got_step, want_step, p, a, tol=2e-5):
    (got, got_out), got_g = got_step(p, a)
    (want, want_out), want_g = want_step(p, a)
    assert abs(got - want) < tol * abs(want) + 1e-6
    for g, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert rel(g, r) < 10 * tol
    assert rel(got_out, want_out) < tol


def assert_same_with_gradient(got_fn, want_fn, p, a, w, tol=2e-5):
    assert_programs_agree(with_gradient(got_fn, w), with_gradient(want_fn, w),
                          p, a, tol)


def test_the_ssm_mixer_against_the_reference(toy):
    model, p, a, w = mixer_case(toy, "00_ssm")
    assert p["in_proj"]["w"].shape == (48, 2 * 64 + 2 * 32 + 8)
    assert p["conv"]["w"].shape == (4, 64 + 2 * 32)
    assert_same_with_gradient(
        lambda p, a: ssm.ssm_mixer_apply(
            p, a, num_heads=8, head_dim=8, n_groups=2, state_size=16,
            chunk_size=16, policy=FP32),
        lambda p, a: ref.mamba_mixer(p, a, TOY, "f32"), p, a, w)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_the_expert_layer_against_the_reference(toy, backend, monkeypatch):
    """Both forms of the grouped product (``ragged_dot``, and the Pallas
    kernel a TPU takes, interpreted here), on the share experts 4..7 of
    16. The kernel's rows past the last group are no numbers: the
    layer's value and gradients are still the reference's."""
    monkeypatch.setattr(moe, "_backend", lambda: backend)
    label = {"cpu": "ragged_dot[cpu]", "tpu": "megablox"}[backend]
    model, p, a, w = mixer_case(toy, "01_moe")
    assert p["experts"]["up"]["w"].shape == (4, 48, 40)
    assert p["router"]["w"].shape == (48, 16)
    with moe.moe_paths.counting() as forms:
        assert_same_with_gradient(
            lambda p, a: moe.moe_apply(
                p, a, top_k=3, first_expert=4, scaling=2.5, policy=FP32)[0],
            lambda p, a: ref.expert_layer(p, a, TOY, "f32"), p, a, w)
    # the usual buffer (a row a token) and the one for everything
    # top-k allows, both traced; this router needs the first
    assert forms[f"{label}x80"] and forms[f"{label}x240"]
    assert forms["held 4/16"]


def test_the_attention_layer_against_the_reference(toy):
    model, p, a, w = mixer_case(toy, "03_attn")
    assert p["k"]["w"].shape == (48, 2 * 16) and "b" not in p["q"]
    assert_same_with_gradient(
        lambda p, a: hybrid_lm.gqa_apply(
            p, a, num_heads=4, num_kv_heads=2, policy=FP32),
        lambda p, a: ref.attention_layer(p, a, TOY, "f32"), p, a, w)


def test_the_relu_squared_mlp_is_its_formula():
    p = relu2_mlp_init(jax.random.key(0), 12, 20)
    assert set(p) == {"up", "down"} and "b" not in p["up"]
    x = jax.random.normal(jax.random.key(1), (5, 12))
    want = jnp.square(jnp.maximum(x @ p["up"]["w"], 0)) @ p["down"]["w"]
    assert rel(relu2_mlp_apply(p, x, FP32), want) < 1e-6
    assert rel(ref.relu2_mlp(p["up"]["w"], p["down"]["w"], x, "f32"),
               want) < 1e-6


# --- the share tied to the model ---------------------------------------------


def test_sixteen_shares_add_up_to_the_uncut_layer(toy):
    """Every chip of 16 holds 1 of the toy's 16 experts (and 4 chips
    hold 4 each): the routed parts all the shares give, with the shared
    expert counted once, are the uncut reference's layer output."""
    _, _, params, _ = toy
    whole = weights.make_weights(jax.eval_shape(
        lambda: moe.moe_init(jax.random.key(0), 48, num_experts=16,
                             held_experts=16, expert_hidden=40,
                             shared_hidden=80)), 5)
    a = jax.random.normal(jax.random.key(2), (2, 40, 48))
    uncut = ref.expert_layer(whole, a, {**TOY, "first_expert": 0}, "f32")
    shared = relu2_mlp_apply(whole["shared"], a, FP32)
    # the share's first expert is an operand: one program a share size
    share = jit_once(lambda part, first: moe.moe_apply(
        part, a, top_k=3, first_expert=first, scaling=2.5, policy=FP32))
    for held in (1, 4):
        routed = 0.0
        for first in range(0, 16, held):
            part = {**whole, "experts": jax.tree.map(
                lambda x: x[first:first + held], whole["experts"])}
            out, load = share(part, first)
            assert load.shape == (held,)
            routed = routed + (out - shared)
        assert rel(routed + shared, uncut) < 2e-5
    # and a share alone is not the layer: the absent experts are left out
    assert rel(out, uncut) > 0.05


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_no_token_is_dropped_under_a_forced_imbalance(toy, backend,
                                                      monkeypatch):
    """A router that sends every token to held expert 5 (and to two
    absent ones): its load is every token, and the layer is still the
    reference's, value and gradients; then one that sends the held
    experts more than a row a token, which takes the larger buffer."""
    monkeypatch.setattr(moe, "_backend", lambda: backend)
    _, _, params, _ = toy
    p = params["layers"]["01_moe"]["mixer"]
    a = jnp.abs(jax.random.normal(jax.random.key(3), (2, 40, 48))) + 0.1
    w = jax.random.normal(jax.random.key(7), a.shape)
    forced = jnp.zeros((48, 16)).at[:, 5].set(4.0).at[:, 0].set(2.0) \
        .at[:, 1].set(1.0).at[:, 2:4].set(-4.0).at[:, 6:].set(-4.0)

    def apply(p, a):
        return moe.moe_apply(p, a, top_k=3, first_expert=4, scaling=2.5,
                             policy=FP32)

    # the router's weights are operands: one pair of programs serves all
    # three routers
    programs = (with_gradient(lambda p, a: apply(p, a)[0], w),
                with_gradient(lambda p, a: ref.expert_layer(p, a, TOY, "f32"),
                              w))

    p = {**p, "router": {"w": forced}}
    load_of = jit_once(lambda p, a: apply(p, a)[1])
    assert load_of(p, a).tolist() == [0, 80, 0, 0]
    assert_programs_agree(*programs, p, a)
    # every assignment top-k allows, all held: the whole of the larger
    # buffer is used
    p = {**p, "router": {"w": forced.at[:, 4].set(3.0).at[:, 6].set(3.5)
                         .at[:, :2].set(-4.0)}}
    load = load_of(p, a)
    assert load.tolist() == [80, 80, 80, 0] and int(load.sum()) == 80 * 3
    assert_programs_agree(*programs, p, a)
    # and between the two: more than a row a token, less than all
    p = {**p, "router": {"w": forced.at[:, 4].set(3.0).at[:, :2].set(-4.0)}}
    load = load_of(p, a)
    assert 80 < int(load.sum()) < 240
    assert_programs_agree(*programs, p, a)


def test_the_router_is_float32_and_its_weights_sum_to_the_scaling(toy):
    _, _, params, _ = toy
    p = params["layers"]["01_moe"]["mixer"]["router"]
    a = jax.random.normal(jax.random.key(4), (80, 48)).astype(jnp.bfloat16)
    chosen, w = moe.route(p, a, top_k=3, scaling=2.5)
    assert chosen.shape == w.shape == (80, 3) and w.dtype == jnp.float32
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)
    want = ref.router_weights({"router": p}, a.astype(jnp.float32), TOY,
                              "f32")
    np.testing.assert_allclose(
        jnp.take_along_axis(want, chosen, axis=-1), w, rtol=1e-5)
    assert int((want > 0).sum()) == 80 * 3


# --- grouped queries, no position embedding ----------------------------------


@pytest.mark.parametrize("impl", ["einsum", "flash"])
def test_grouped_queries_are_repeated_keys_and_values(toy, impl):
    """Query head i reads key/value head i // 2: the same as full
    multi-head attention whose key and value matrices hold each head's
    columns twice."""
    _, _, params, _ = toy
    p = params["layers"]["03_attn"]["mixer"]
    a = jax.random.normal(jax.random.key(7), (2, 128, 48))
    got = hybrid_lm.gqa_apply(p, a, num_heads=4, num_kv_heads=2,
                              policy=FP32, impl=impl)

    def twice(w):   # (C, 2 x 16) -> (C, 4 x 16), head j from head j // 2
        return jnp.repeat(w.reshape(48, 2, 16), 2, axis=1).reshape(48, 64)

    full = {**p, "k": {"w": twice(p["k"]["w"])},
            "v": {"w": twice(p["v"]["w"])}}
    x = hybrid_lm.repeat_kv(jnp.arange(2 * 3 * 4.).reshape(2, 3, 4), 2, 4)
    assert x[0, 0].tolist() == [0, 1, 0, 1, 2, 3, 2, 3]
    want = mha_apply(full, a, a, a, num_heads=4, causal=True, policy=FP32,
                     impl="einsum")
    assert rel(got, want) < 2e-5


def test_attention_has_no_position_embedding(toy):
    """Without rotary embedding a query sees its prefix as a set: the
    last position's output does not change when the earlier positions
    are shuffled, nor any position's when a constant is added to every
    position index (the layer takes none)."""
    _, _, params, _ = toy
    p = params["layers"]["03_attn"]["mixer"]
    a = jax.random.normal(jax.random.key(8), (1, 24, 48))
    out = hybrid_lm.gqa_apply(p, a, num_heads=4, num_kv_heads=2, policy=FP32)
    order = jnp.concatenate([jax.random.permutation(jax.random.key(0), 23),
                             jnp.array([23])])
    shuffled = hybrid_lm.gqa_apply(p, a[:, order], num_heads=4,
                                   num_kv_heads=2, policy=FP32)
    assert rel(shuffled[:, -1], out[:, -1]) < 1e-5
    assert rel(shuffled[:, 1:-1], out[:, 1:-1]) > 1e-2
    import inspect
    assert "rope" not in inspect.signature(hybrid_lm.gqa_apply).parameters


# --- the stack: remat, what the trainer says and logs ------------------------


HYBRID = remat.HYBRID_REMAT_NAMES


@pytest.mark.parametrize("kept", [(), HYBRID[:3], HYBRID],
                         ids=lambda k: "+".join(k) or "none")
def test_remat_changes_no_value_whatever_is_kept(toy, plain_step, kept,
                                                 monkeypatch):
    task, model, params, batch = toy
    reckoned = {}

    def choose(held, layer_in, names):
        reckoned.update(held, layer_in=layer_in, names=names)
        return kept

    monkeypatch.setattr(remat, "choose_keeps", choose)

    t = dataclasses.replace(task, remat=True)
    loss, g = jit_once(jax.value_and_grad(lambda p: t.loss_and_metrics(
        t.build(), p, batch, policy=FP32)[0]))(params)
    (plain, _), plain_g = plain_step
    assert abs(float(loss) - float(plain)) < 1e-5
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(plain_g)):
        assert rel(a, b) < 1e-4
    # what the names would hold, over the five layers, in float32
    rows = 2 * 40 * 4
    assert reckoned["names"] == HYBRID
    assert reckoned["layer_in"] == 5 * rows * 48
    assert reckoned["ssm_out"] == 2 * rows * 64
    assert reckoned["ssm_in"] == 2 * rows * (2 * 64 + 2 * 32 + 8)
    assert "moe_hidden" not in reckoned    # recomputed in any case
    assert reckoned["mlp_hidden"] == 2 * rows * 80
    assert reckoned["qkv"] == rows * 64 and reckoned["attn_out"] == 0


def test_the_names_list_is_the_stacks_own():
    assert HYBRID[:3] == remat.REMAT_NAMES
    assert remat.pick_remat_keeps(
        {"attn_out": 1, "ssm_out": 5, "ssm_in": 50}, layer_in_bytes=4,
        memory_limit=100, memory_held=50, names=HYBRID)[0] == HYBRID[:4]
    assert HYBRID[3:] == ("ssm_out", "ssm_in", "delta_out", "delta_in",
                          "moe_plan")
    # a stack that asks for the three never hears of the others
    assert remat.pick_remat_keeps({}, layer_in_bytes=1, memory_limit=None
                                  )[0] == remat.REMAT_NAMES
    with pytest.raises(ValueError):
        remat.dear(jnp.ones(2), "ssm_state")


@pytest.fixture(scope="module")
def share_programs(toy):
    """The step and both sides' logits with the shares as operands:
    compiled by the first case, reused by the others."""
    task, model, params, batch = toy
    ids = batch["input_ids"]
    return (
        jit_once(jax.value_and_grad(
            lambda p, b: task.loss_and_metrics(model, p, b, policy=FP32),
            has_aux=True)),
        jit_once(lambda p, f: model.apply(p, ids, first_experts=f,
                                         policy=FP32)),
        jit_once(lambda p, f: ref.logits(p, ids, TOY, first_experts=f)))


@pytest.mark.parametrize("firsts", [(4, 4), (0, 12), (8, 0)])
def test_a_share_named_by_the_batch_is_that_share_of_the_configuration(
        toy, plain_step, share_programs, firsts):
    """``first_experts`` with the batch, one first expert an expert
    layer: the loss, its gradient and the loads are those of a model
    built with that share, and one program serves every share."""
    task, model, params, batch = toy
    step, logits, reference_logits = share_programs
    named = {**batch, "first_experts": jnp.tile(
        jnp.asarray(firsts, jnp.int32), (2, 1))}
    (loss, metrics), grads = step(params, named)
    if firsts[0] == firsts[1]:    # the toy's own share, built into it
        assert task.first_expert == firsts[0]
        (want, want_m), want_g = plain_step
        assert float(loss) == pytest.approx(float(want), rel=1e-6)
        assert float(metrics["moe_assignments"]) \
            == float(want_m["moe_assignments"])
        for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_g)):
            assert rel(g, w) < 1e-5
    # the plain reference, told the same shares
    np.testing.assert_allclose(
        logits(params, jnp.asarray(firsts)),
        reference_logits(params, jnp.asarray(firsts)), atol=2e-4, rtol=1e-4)
    assert step._cache_size() == 1


def test_the_metrics_carry_the_assignments_and_the_imbalance(toy,
                                                             plain_step):
    task, model, params, batch = toy
    (_, metrics), _ = plain_step
    assert set(metrics) == {"loss", "moe_assignments",
                            "moe_load_max_over_mean"}
    _, loads = jit_once(lambda p: model.hidden_states(
        p, batch["input_ids"], policy=FP32))(params)
    assert loads.shape == (2, 4)
    assert float(metrics["moe_assignments"]) == float(loads.sum())
    # the first expert layer's load is the reference router's choice of
    # experts 4..7 for the normed state that reaches it
    h = params["embed"]["embed"][batch["input_ids"]]
    p0 = params["layers"]["00_ssm"]
    h = h + ref.mamba_mixer(p0["mixer"], ref.rms_norm(
        p0["norm"]["scale"], h, 1e-5), TOY, "f32")
    p1 = params["layers"]["01_moe"]
    w = ref.router_weights(p1["mixer"], ref.rms_norm(
        p1["norm"]["scale"], h, 1e-5).reshape(-1, 48), TOY, "f32")
    assert loads[0].tolist() == (w[:, 4:8] > 0).sum(0).tolist()
    want = max(float(l.max() / l.mean()) for l in np.asarray(loads, float))
    assert float(metrics["moe_load_max_over_mean"]) == pytest.approx(want)


def test_the_trainer_says_the_forms_and_logs_the_counters(toy, tmp_path,
                                                          capfd):
    task, _, _, batch = toy
    task = dataclasses.replace(task, remat=True)
    tele = tmp_path / "telemetry"
    trainer = Trainer(
        task, None, TrainerConfig(
            default_root_dir=str(tmp_path), enable_checkpointing=False,
            telemetry_dir=str(tele), log_every_n_steps=1, max_steps=2),
        optimizer_init={"class_path": "AdamW", "init_args": {"lr": 1e-3}})
    state = trainer._build_state()
    trainer._make_steps()
    batch = {"input_ids": np.asarray(batch["input_ids"])}
    with remat.remat_keeps() as outer:
        trainer._load_step(trainer._train_step, state, batch, "t")
    (choice,) = outer
    assert choice["kept"] == HYBRID and not choice["dropped"]
    err = capfd.readouterr().err
    assert ("[step_load] attention call sites: materialized[backend]=1\n"
            "[step_load] remat keeps: attn_out,qkv,mlp_hidden,ssm_out,"
            "ssm_in,delta_out,delta_in,moe_plan + layer_in 0.00 GB of no "
            "memory report\n"
            "[step_load] selective scans: chunked[16x3+pad,backend]=2\n"
            "[step_load] short convolutions: xla[128ch, backend]=2\n"
            "[step_load] expert layers: held 4/16=2 ragged_dot[cpu]x240=2 "
            "ragged_dot[cpu]x80=2\n"
            ) in err
    assert not ssm.scan_paths._open and not moe.moe_paths._open


def test_a_pattern_of_other_characters_is_refused():
    with pytest.raises(ValueError, match="pattern"):
        HybridLMTask(**{**TOY, "hybrid_override_pattern": "MEX"}).build()
    with pytest.raises(ValueError, match="experts"):
        HybridLMTask(**{**TOY, "first_expert": 14}).build()
    model = HybridLMTask(**TOY).build()
    assert model.layer_names() == ["00_ssm", "01_moe", "02_ssm", "03_attn",
                                   "04_moe"]
    assert model.num_held_experts == 4
    assert dataclasses.replace(model, held_experts=None, first_expert=0
                               ).num_held_experts == 16


def test_the_cli_builds_the_task_from_its_preset():
    """``scripts/hybrid_lm.py`` in the form of ``scripts/clm.py``: the
    preset parses, the data's vocabulary and row length reach the
    model."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import hybrid_lm as script

    cli = script.main(
        args=["fit", "--config",
              os.path.join(ROOT, "scripts", "configs",
                           "hybrid_lm_1chip.yaml"),
              "--data.vocab_size=300", "--data.max_seq_len=64"], run=False)
    model = cli.config["model"]
    assert model["hybrid_override_pattern"] == "MEMEM*E"
    assert cli.config["experiment"] == "hybrid_lm"
    task, datamodule, _ = cli.instantiate()
    assert isinstance(task, HybridLMTask)
    assert task.vocab_size == datamodule.vocab_size == 300
    assert task.max_seq_len == 64 and task.remat is True
    assert task.held_experts is None and task.build().num_held_experts == 16
    # the published model is the task's defaults
    published = HybridLMTask()
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron3_nano_30b.json")) as f:
        config = json.load(f)
    for key in ("hidden_size", "mamba_num_heads", "mamba_head_dim",
                "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "num_experts_per_tok", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size",
                "routed_scaling_factor", "norm_eps"):
        assert getattr(published, key) == config[key], key
    assert published.hybrid_override_pattern \
        == config["hybrid_override_pattern"]
    assert published.n_routed_experts \
        == config["published"]["n_routed_experts"]
