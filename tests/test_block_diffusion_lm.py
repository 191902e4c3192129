"""The block-diffusion mixture-of-experts language model
(``tasks/block_diffusion_lm.py`` on ``models/hybrid_lm.py``'s ``*E``
layers) and what it brought to the shared ops: the block-diffusion mask
on both attention cores, per-head q/k norms and rotary positions beside
grouped queries, the router and expert kinds of ``ops/moe.py`` and the
rule that sizes its usual buffer. The kernels run interpreted; the
reference is ``benchmarks/reference/block_diffusion_lm.py``."""

import dataclasses
import itertools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once, out_and_grads

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import scope_times  # noqa: E402
from benchmarks.reference import block_diffusion_lm as ref  # noqa: E402

from perceiver_tpu.models import hybrid_lm  # noqa: E402
from perceiver_tpu.ops import attention as attn  # noqa: E402
from perceiver_tpu.ops import moe, tiling  # noqa: E402
from perceiver_tpu.ops import pallas_attention as pa  # noqa: E402
from perceiver_tpu.ops.fourier import rope_tables  # noqa: E402
from perceiver_tpu.ops.mlp import gated_mlp_apply, gated_mlp_init  # noqa: E402
from perceiver_tpu.ops.policy import Policy  # noqa: E402
from perceiver_tpu.tasks import BlockDiffusionLMTask  # noqa: E402
from perceiver_tpu.tasks.block_diffusion_lm import block_noise  # noqa: E402
from perceiver_tpu.training import Trainer, TrainerConfig  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FP32 = Policy.fp32()
TOY = dict(vocab_size=300, hidden_size=48, num_hidden_layers=2,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           num_experts=16, num_experts_per_tok=3, moe_intermediate_size=40,
           rope_theta=1e4, max_seq_len=40, block_length=4, t_min=1e-3,
           mask_token_id=0, held_experts=4, first_expert=4,
           ce_chunk_size=32)


def normal(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(seed), shape, dtype)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def brute_force_visible(half, block):
    """The three rules, pair by pair."""
    sees = np.zeros((2 * half, 2 * half), bool)
    for j, l in itertools.product(range(2 * half), repeat=2):
        if j < half and l < half:
            sees[j, l] = j // block == l // block
        elif j < half:
            sees[j, l] = (l - half) // block < j // block
        elif l >= half:
            sees[j, l] = (l - half) // block <= (j - half) // block
    return sees


# --- the mask ----------------------------------------------------------------


@pytest.mark.parametrize("half,block", [(24, 4), (64, 32), (12, 12)])
def test_the_mask_is_the_three_rules(half, block):
    sees = brute_force_visible(half, block)
    assert (~np.asarray(attn.block_diffusion_mask(half, block))
            == sees).all()
    assert (np.asarray(ref.visible(half, block)) == sees).all()
    # a noised position sees its block and the clean blocks before it,
    # a clean one the clean blocks up to its own: L^2 + L B pairs
    assert sees.sum() == half * half + half * block
    assert sees[half:, :half].sum() == 0 and sees.diagonal().all()


@pytest.mark.parametrize("half,block,block_q,block_k", [
    (512, 4, 128, 128), (512, 32, 256, 128), (192, 4, 128, 128),
    (160, 32, 128, 256), (1000, 4, 256, 512), (64, 64, 128, 128)])
def test_the_tiles_kinds_are_the_masks(half, block, block_q, block_k):
    """``diffusion_tiles`` against the mask itself, tile by tile:
    skipped where no pair is visible, plain where every pair is; with
    ``L`` a multiple of the tiles the noised-noised quadrant runs its
    diagonal alone and the clean-noised quadrant nothing."""
    rows = -(-2 * half // block_q) * block_q
    cols = -(-2 * half // block_k) * block_k
    nq, nk = rows // block_q, cols // block_k
    kinds = tiling.diffusion_tiles(half, block, block_q, block_k, nq, nk)
    # the kernels' own mask over the padded square
    sees = np.asarray(pa._diffusion_mask(
        jnp.zeros((rows, cols)), 0, 0, half, block, False)) == 0
    assert (sees[:2 * half, :2 * half]
            == brute_force_visible(half, block)).all()
    assert not sees[:2 * half, 2 * half:].any()   # no padded key is seen
    transposed = np.asarray(pa._diffusion_mask(
        jnp.zeros((cols, rows)), 0, 0, half, block, True)) == 0
    assert (transposed.T == sees).all()
    for iq, ik in itertools.product(range(nq), range(nk)):
        tile = sees[iq * block_q:(iq + 1) * block_q,
                    ik * block_k:(ik + 1) * block_k]
        want = tiling.SKIPPED if not tile.any() else \
            tiling.PLAIN if tile.all() else tiling.MASKED
        assert kinds[iq, ik] == want, (iq, ik)
    held = tiling.held_tiles(kinds)
    for iq in range(nq):
        runs = np.flatnonzero(kinds[iq])
        assert set(held[iq]) <= set(runs)        # only tiles that run
        assert (held[iq][runs] == runs).all()    # each at its own step
        assert (np.diff(held[iq][runs[0]:]) >= 0).all()
    if half % block_q == 0 and half % block_k == 0 and block_q == block_k:
        n = half // block_q
        assert (kinds[:n, :n] != tiling.SKIPPED).sum() == n      # diagonal
        assert (kinds[n:, :n] == tiling.SKIPPED).all()
        assert (kinds == tiling.PLAIN).sum() == n * (n - 1)      # below, twice


def test_the_calls_carry_their_own_kernel_names_and_refuse_other_masks():
    q = normal(20, (1, 256, 128))

    def names(**kw):
        text = jit_once(jax.grad(lambda q: pa.flash_attention_channels(
            q, q, q, num_heads=1, **kw).sum())).lower(q).as_text(
                debug_info=True)
        return {n for n in ("flash_attention_fwd", "causal_attention_fwd",
                            "block_diffusion_attention_fwd",
                            "block_diffusion_attention_bwd")
                if n in text}

    assert names(block_diffusion=(128, 4)) == {
        "block_diffusion_attention_fwd", "block_diffusion_attention_bwd"}
    assert names(causal=True) == {"causal_attention_fwd"}
    assert names() == {"flash_attention_fwd"}
    assert pa.pick_blocks(8192, 8192, True) == (1024, 1024)
    for kw in (dict(block_diffusion=(128, 4), causal=True),
               dict(block_diffusion=(128, 4), bias=jnp.zeros((1, 256))),
               dict(block_diffusion=(100, 4)),      # not 2 L positions
               dict(block_diffusion=(128, 3))):     # L not in whole blocks
        with pytest.raises(ValueError, match="block-diffusion"):
            pa.flash_attention_channels(q, q, q, num_heads=1, **kw)


@pytest.mark.parametrize("case", [
    dict(block_diffusion=True, want=("fused", None)),
    dict(block_diffusion=True, has_key_padding_mask=True,
         want=("materialized", "attn_mask")),
    dict(block_diffusion=True, lq=64, lk=64,
         want=("materialized", "shape")),
    dict(block_diffusion=True, backend="cpu",
         want=("materialized", "backend")),
], ids=["block_diffusion", "padded", "small", "cpu"])
def test_the_pick_takes_the_mask_as_a_property_of_the_call(case):
    case = dict(case)
    want = case.pop("want")
    kw = dict(backend="tpu", lq=8192, lk=8192, dropout_active=False,
              has_attn_mask=False, mesh_devices=1)
    kw.update(case)
    assert attn.pick_attention_core(**kw) == want


def attention_case(qk_norm=True):
    params = hybrid_lm.gqa_init(jax.random.key(30), 48, 4, 2, 16, qk_norm)
    if qk_norm:   # scales away from 1: one read from the wrong place shows
        params["q_norm"]["scale"] = 1.0 + 0.2 * normal(31, (16,))
        params["k_norm"]["scale"] = 1.0 + 0.2 * normal(32, (16,))
    half = 128
    rope = tuple(np.concatenate([t, t]) for t in rope_tables(half, 16, 1e4))
    kw = dict(num_heads=4, num_kv_heads=2, rope=rope, norm_eps=1e-6,
              block_diffusion=(half, 4), policy=FP32)
    return params, normal(33, (2, 2 * half, 48)), kw


def test_the_attention_layer_on_both_cores_against_the_reference(
        monkeypatch):
    """q/k norms over each head's channels, rotary position ``j mod
    L``, grouped queries, the block-diffusion mask: the fused kernels
    (interpreted), the materialized core and the plain reference agree,
    in value and in every gradient."""
    params, a, kw = attention_case()
    cfg = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               rms_norm_eps=1e-6, rope_theta=1e4, block_length=4)
    w = normal(34, a.shape)
    fused, plain, want = (
        lambda p, a: hybrid_lm.rotary_gqa_apply(p, a, impl="flash", **kw),
        lambda p, a: hybrid_lm.rotary_gqa_apply(p, a, impl="einsum", **kw),
        lambda p, a: ref.attention_layer(p, a, cfg, "f32"))
    out_want, g_want = out_and_grads(want, w, params, a)
    for f in (fused, plain):
        out, got = out_and_grads(f, w, params, a)
        assert rel(out, out_want) < 2e-5
        for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(g_want)):
            assert rel(x, y) < 1e-4
    # on a TPU the pick sends the call site to the kernels
    monkeypatch.setattr(attn, "_backend", lambda: "tpu")
    big = jax.ShapeDtypeStruct((1, 1024, 48), jnp.float32)
    big_kw = dict(kw, block_diffusion=(512, 4), rope=tuple(
        np.concatenate([t, t]) for t in rope_tables(512, 16, 1e4)))
    with attn.attention_paths() as tally:
        jax.eval_shape(lambda a: hybrid_lm.rotary_gqa_apply(
            params, a, **big_kw), big)
    assert dict(tally) == {("fused", None): 1}


def test_a_mask_the_cores_cannot_take_is_refused_by_name():
    params, a, kw = attention_case(qk_norm=False)
    kw = dict(num_heads=4, block_diffusion=(128, 4), policy=FP32)
    with pytest.raises(NotImplementedError, match="block-diffusion"):
        attn.mha_apply(params, a, a, a, impl="chunked", **kw)
    with pytest.raises(NotImplementedError, match="block-diffusion"):
        attn.mha_apply(params, a, a, a, causal=True, **kw)
    with pytest.raises(NotImplementedError, match="block-diffusion"):
        attn.mha_apply(params, a, a, a, attn_mask=jnp.zeros((256, 256)),
                       **kw)
    full = attn.mha_init(jax.random.key(1), 48, 4, bias=False)
    with pytest.raises(NotImplementedError,
                       match="causal=True or block_diffusion"):
        attn.mha_apply(full, a, a, a, num_heads=4, impl="flash",
                       attn_mask=jnp.zeros((256, 256)), policy=FP32)


# --- the expert layer's kinds ------------------------------------------------


def dense_moe(p, a, *, top_k, first, scoring, renormalize, scaling):
    """The layer's formula with every held expert over every token."""
    logits = a @ p["router"]["w"]
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, -1)
    kth = jnp.sort(scores, -1)[:, -top_k][:, None]
    picked = jnp.where(scores >= kth, scores, 0.0)
    if renormalize:
        picked = picked / picked.sum(-1, keepdims=True)
    picked = picked * scaling
    experts = p["experts"]
    out = jnp.zeros_like(a)
    for e in range(experts["up"]["w"].shape[0]):
        up = a @ experts["up"]["w"][e]
        hidden = jax.nn.silu(a @ experts["gate"]["w"][e]) * up \
            if "gate" in experts else jnp.square(jax.nn.relu(up))
        out += picked[:, first + e, None] * (hidden @ experts["down"]["w"][e])
    if "shared" in p:
        shared = jnp.square(jax.nn.relu(a @ p["shared"]["up"]["w"]))
        out += shared @ p["shared"]["down"]["w"]
    return out


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("scoring,renormalize,gated,shared", [
    ("softmax", True, True, 0), ("softmax", False, False, 24),
    ("sigmoid", True, True, 24), ("sigmoid", True, False, 0)],
    ids=["qwen3", "softmax_relu2_shared", "sigmoid_gated_shared",
         "sigmoid_relu2_alone"])
def test_the_router_and_expert_kinds_are_the_trees_and_the_calls(
        backend, scoring, renormalize, gated, shared, monkeypatch):
    monkeypatch.setattr(moe, "_backend", lambda: backend)
    p = moe.moe_init(jax.random.key(0), 32, num_experts=16, held_experts=4,
                     expert_hidden=24, shared_hidden=shared, gated=gated)
    assert ("gate" in p["experts"]) == gated and ("shared" in p) == bool(
        shared)
    a, w = normal(1, (2, 40, 32)), normal(2, (2, 40, 32))
    kw = dict(top_k=3, first=4, scoring=scoring, renormalize=renormalize,
              scaling=1.5)

    def layer(p, a):
        return moe.moe_apply(
            p, a, top_k=3, first_expert=4, scaling=1.5, scoring=scoring,
            renormalize=renormalize, policy=FP32)[0]

    def want(p, a):
        return dense_moe(p, a.reshape(-1, 32), **kw).reshape(a.shape)

    with moe.moe_kinds.counting() as kinds:
        out, got = out_and_grads(layer, w, p, a)
    out_want, g_want = out_and_grads(want, w, p, a)
    assert rel(out, out_want) < 2e-5
    assert set(kinds) == {
        f"{scoring} top 3" + (" renormalised" if renormalize else ""),
        "gated silu x3 products" if gated else "relu2 x2 products",
        "shared expert" if shared else "no shared expert",
        "weights x1.5"}
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(g_want)):
        assert rel(x, y) < 1e-4
    with pytest.raises(ValueError, match="scoring"):
        moe.route(p["router"], a[0], top_k=3, scaling=1.0, scoring="tanh")


def test_the_gated_experts_against_the_reference_layer():
    """``moe_apply`` as the model calls it for a Qwen3-MoE layer, on
    the share experts 4..7 of 16, against the benchmark's reference."""
    p = moe.moe_init(jax.random.key(3), 48, num_experts=16, held_experts=4,
                     expert_hidden=40, shared_hidden=0, gated=True)
    cfg = dict(num_experts_per_tok=3, first_expert=4, norm_topk_prob=True)
    a = normal(4, (2, 40, 48))
    got = moe.moe_apply(p, a, top_k=3, first_expert=4, scoring="softmax",
                        policy=FP32)[0]
    assert rel(got, ref.expert_layer(p, a, cfg, "f32")) < 2e-5
    x = normal(5, (7, 48))
    one = gated_mlp_init(jax.random.key(6), 48, 40)
    assert rel(gated_mlp_apply(one, x, FP32), ref.gated_mlp(
        one["gate"]["w"], one["up"]["w"], one["down"]["w"], x, "f32")) < 1e-5


def test_the_usual_buffer_follows_the_share():
    # nemotron_train: 8 held of 128 at top 6: twice the even load is
    # under a row a token, so a row a token, as it was
    assert moe.usual_rows(16384, 6, 8, 128) == 16384
    # sdar_train: 16 held of 128 at top 8: the even load is T itself,
    # the buffer twice it
    assert moe.usual_rows(16384, 8, 16, 128) == 32768
    assert moe.usual_rows(80, 3, 4, 16) == 80           # 2 x 0.75: one
    assert moe.usual_rows(288, 3, 4, 16) == 288
    assert moe.usual_rows(1024, 3, 6, 16) == 2048       # 2 x 1.125: two
    assert moe.usual_rows(100, 3, 6, 16) == 300         # two, in a tile:
    assert moe.usual_rows(100, 3, 16, 16) == 300        # no more than there are
    assert moe.usual_rows(4096, 2, 1, 64) == 4096


@pytest.mark.parametrize("crowded", [False, True],
                         ids=["usual_buffer", "full_buffer"])
def test_both_sides_of_the_buffers_cond_give_the_layers_result(crowded):
    """A router that sends every token to held experts fills more than
    the usual buffer: the ``T x top_k`` branch computes the same layer,
    and no assignment is dropped."""
    p = moe.moe_init(jax.random.key(7), 32, num_experts=16, held_experts=6,
                     expert_hidden=24, shared_hidden=0, gated=True)
    a = jnp.abs(normal(8, (1, 1024, 32)))  # positive: a column of ones wins
    if crowded:   # the held experts' columns far above the others'
        p["router"]["w"] = p["router"]["w"].at[:, 4:10].set(
            1.0 + 0.1 * normal(12, (32, 6)))
    w = normal(9, a.shape)
    kw = dict(top_k=3, first=4, scoring="softmax", renormalize=True,
              scaling=1.0)

    def layer(p, a):
        return moe.moe_apply(p, a, top_k=3, first_expert=4,
                             scoring="softmax", policy=FP32)

    def with_load(p, a):    # the load rides beside the weighted sum
        out, load = layer(p, a)
        return (out * w).sum(), (out, load)

    with moe.moe_paths.counting() as forms:
        (_, (out, load)), got = jit_once(jax.value_and_grad(
            with_load, (0, 1), has_aux=True))(p, a)
    usual = moe.usual_rows(1024, 3, 6, 16)
    assert usual == 2048                       # the even load is 1152
    assert dict(forms) == {
        "held 6/16": 1, "ragged_dot[cpu]x2048[2 x even share]": 1,
        "ragged_dot[cpu]x3072": 1}
    assert (int(load.sum()) > usual) == crowded
    if crowded:
        assert int(load.sum()) == 1024 * 3     # every assignment is held
    out_want, want = out_and_grads(
        lambda p, a: dense_moe(p, a[0], **kw)[None], w, p, a)
    assert rel(out, out_want) < 2e-5
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert rel(x, y) < 1e-4


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Each of 8 chips holds 2 of 16 experts: the parts of the result
    the shares give (nothing is computed alike on every chip: there is
    no shared expert) sum to what the uncut reference gives for the
    whole layer."""
    whole = moe.moe_init(jax.random.key(10), 48, num_experts=16,
                         held_experts=16, expert_hidden=40, shared_hidden=0,
                         gated=True)
    a = normal(11, (2, 40, 48))
    cfg = dict(num_experts_per_tok=3, norm_topk_prob=True)
    total = jnp.zeros_like(a)
    loads = []
    for share in range(8):
        held = dict(whole, experts=jax.tree.map(
            lambda x: x[2 * share:2 * share + 2], whole["experts"]))
        part, load = moe.moe_apply(held, a, top_k=3, first_expert=2 * share,
                                   scoring="softmax", policy=FP32)
        assert rel(part, ref.expert_layer(
            held, a, cfg, "f32", first=2 * share)) < 2e-5
        total, loads = total + part, loads + [int(load.sum())]
    assert sum(loads) == 80 * 3                   # every assignment once
    assert rel(total, ref.expert_layer(whole, a, cfg, "f32", first=0)) < 2e-5


# --- the model and the task --------------------------------------------------


@pytest.fixture(scope="module")
def toy():
    task = BlockDiffusionLMTask(**TOY)
    model = task.build()
    params = model.init(jax.random.key(0))
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 + 0.1 * jax.random.normal(
            jax.random.key(x.size), x.shape))
        if path[-1].key == "scale" else x, params)
    ids = jax.random.randint(jax.random.key(1), (3, 40), 1, 300)
    return task, model, params, {"input_ids": ids}


def test_the_model_is_two_layers_a_published_one(toy):
    task, model, params, _ = toy
    assert model.pattern == "*E*E" and model.rope_theta == 1e4
    assert list(params["layers"]) == ["00_attn", "01_moe", "02_attn",
                                      "03_moe"]
    attention = params["layers"]["00_attn"]["mixer"]
    assert attention["q_norm"]["scale"].shape == (16,) \
        and attention["k"]["w"].shape == (48, 32) and "b" not in attention["q"]
    experts = params["layers"]["01_moe"]["mixer"]
    assert set(experts) == {"router", "experts"}          # no shared expert
    assert experts["experts"]["gate"]["w"].shape == (4, 48, 40)
    assert experts["router"]["w"].shape == (48, 16)
    with pytest.raises(ValueError, match="mamba"):
        dataclasses.replace(model, pattern="M*E")


def test_what_a_position_sees_is_the_masks(toy):
    """The clean half never reads the noised half; a noised block reads
    no other noised block and no clean block from its own on."""
    task, model, params, batch = toy
    ids = batch["input_ids"][:1]
    noised = jnp.where(jnp.arange(40) % 3 == 0, 0, ids)
    run = jit_once(lambda xt, x: model.hidden_states(
        params, jnp.concatenate([xt, x], 1), policy=FP32,
        block_diffusion=(40, 4))[0])
    base = run(noised, ids)
    other = run(jnp.roll(noised, 5, axis=1), ids)
    assert rel(other[:, 40:], base[:, 40:]) < 1e-6
    # noised block 3 (positions 12..15) changed alone: the other
    # noised blocks and every clean position read the same
    changed = noised.at[:, 12:16].set(7)
    out = run(changed, ids)
    assert rel(out[:, :12], base[:, :12]) < 1e-6
    assert rel(out[:, 16:], base[:, 16:]) < 1e-6
    assert rel(out[:, 12:16], base[:, 12:16]) > 1e-3
    # clean block 3 changed: noised blocks 0..3 read the same, 4.. not
    out = run(noised, ids.at[:, 12:16].set(7))
    assert rel(out[:, :16], base[:, :16]) < 1e-6
    assert rel(out[:, 16:40], base[:, 16:40]) > 1e-4
    with pytest.raises(ValueError, match="block-diffusion"):
        model.hidden_states(params, ids, policy=FP32,
                            block_diffusion=(40, 4))


def test_the_noise_is_a_rate_a_block_and_a_mask_a_position():
    ids = jnp.ones((64, 256), jnp.int32)
    masked, t = block_noise(jax.random.key(3), ids, 4, 1e-3)
    assert masked.shape == t.shape == ids.shape
    t4 = np.asarray(t).reshape(64, 64, 4)
    assert (t4 == t4[..., :1]).all()               # one rate a block
    assert 1e-3 <= t4.min() and t4.max() < 1.0
    assert abs(float(t.mean()) - 0.5) < 0.02       # U(t_min, 1)
    assert abs(float(masked.mean()) - 0.5) < 0.02  # E[m] = E[t]
    # the reference re-derives the same draws from the same key
    cfg = dict(block_length=4, t_min=1e-3, mask_token_id=0)
    noised, weights = ref.block_noise(jax.random.key(3), ids, cfg)
    assert (np.asarray(noised == 0) == np.asarray(masked)).all()
    assert rel(weights, jnp.where(masked, 1.0 / t, 0.0)) < 1e-6


def test_loss_and_every_gradient_against_the_reference(toy):
    task, model, params, batch = toy
    cfg = {**TOY, "rms_norm_eps": 1e-6, "norm_topk_prob": True}
    key = jax.random.key(5)
    noised, weights = ref.block_noise(key, batch["input_ids"], cfg)
    ref_batch = {"input_ids": batch["input_ids"], "noised_ids": noised,
                 "weights": weights}

    def program(p):
        return task.loss_and_metrics(model, p, batch, rng=key,
                                     deterministic=False, policy=FP32)

    def reference(p):
        total, count = ref.loss_sum(p, ref_batch, cfg, "f32")
        return total / count

    (loss, metrics), grads = jit_once(jax.value_and_grad(
        program, has_aux=True))(params)
    want, want_grads = jit_once(jax.value_and_grad(reference))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    got = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), w in zip(got, jax.tree.leaves(want_grads)):
        assert rel(g, w) < 2e-4, jax.tree_util.keystr(path)
    # the counters: what the loss read, and how heavy it was
    assert float(metrics["bd_masked_positions"]) == float(
        (weights > 0).sum())
    assert float(metrics["bd_weight_sum"]) == pytest.approx(
        float(weights.sum()), rel=1e-6)
    assert float(metrics["moe_assignments"]) > 0
    assert float(metrics["moe_full_buffer_layers"]) in (0.0, 1.0, 2.0)
    # a batch that names the shares itself
    firsts = jnp.tile(jnp.asarray([[8, 0]], jnp.int32), (3, 1))
    named, _ = jit_once(lambda p, b: task.loss_and_metrics(
        model, p, b, rng=key, deterministic=False, policy=FP32))(
            params, {**batch, "first_experts": firsts})
    total, count = jit_once(lambda p, b: ref.loss_sum(p, b, cfg, "f32"))(
        params, {**ref_batch, "first_experts": firsts})
    assert abs(float(named) - float(total / count)) < 1e-5 * float(named)
    assert abs(float(named) - float(loss)) > 1e-4


def test_padding_carries_no_loss_and_odd_rows_are_refused(toy):
    task, model, params, batch = toy
    pad = jnp.arange(40)[None, :] >= jnp.asarray([[40], [28], [12]])
    key = jax.random.key(6)
    _, metrics = task.loss_and_metrics(
        model, params, {**batch, "pad_mask": pad}, rng=key, policy=FP32)
    masked, t = block_noise(key, batch["input_ids"], 4, 1e-3)
    assert float(metrics["bd_masked_positions"]) == float(
        (masked & ~pad).sum())
    with pytest.raises(ValueError, match="blocks of 4"):
        task.loss_and_metrics(model, params,
                              {"input_ids": batch["input_ids"][:, :38]},
                              rng=key, policy=FP32)


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
    step = trainer._load_step(trainer._train_step, state, batch, "t")
    err = capfd.readouterr().err
    assert ("[step_load] attention call sites: materialized[backend]=2\n"
            in err)
    # a stack of attention and expert layers alone: no short convolution
    assert "short convolutions" not in err
    # 240 positions a step, 180 assignments if even: twice that is
    # under two experts' worth, so a row a token
    assert ("[step_load] expert layers: held 4/16=2 "
            "ragged_dot[cpu]x240=2 ragged_dot[cpu]x720=2\n"
            "[step_load] expert kinds: gated silu x3 products=2 no shared "
            "expert=2 softmax top 3 renormalised=2\n") in err
    state, metrics = step(state, batch)
    assert {"bd_masked_positions", "bd_weight_sum", "moe_assignments",
            "moe_load_max_over_mean", "moe_full_buffer_layers"} <= set(
                metrics)
    assert not moe.moe_paths._open and not moe.moe_kinds._open


def test_the_noise_has_its_scope_in_the_compiled_step(toy):
    task, model, params, batch = toy

    def step(p, ids, key):
        return jax.grad(lambda p: task.loss_and_metrics(
            model, p, {"input_ids": ids}, rng=key, policy=FP32)[0])(p)

    text = jit_once(step).lower(params, batch["input_ids"],
                               jax.random.key(0)).compile().as_text()
    stacks = [scope_times.names_of(line.split('op_name="')[1].split('"')[0])
              for line in text.splitlines() if 'op_name="' in line]
    assert any("bd_noise" in names for names in stacks)
    assert any({"moe", "moe_experts"} <= set(names) for names in stacks)
    assert any("attn_core" in names for names in stacks)
    # the noise is the step's own and nothing of the stack lies under it
    assert not any({"bd_noise", "hybrid_stack"} <= set(names)
                   for names in stacks)


def test_a_rotary_table_is_written_into_the_step_once(toy):
    """A numpy table is a literal of the step's text at every use (24
    of 8,192 x 128 in ``sdar_train``'s step: 204 MB of text, a program
    no compile cache kept): the layers are handed one array a table."""
    task, model, params, batch = toy
    remat = dataclasses.replace(task, remat=True)

    def step(p, ids, key):
        return jax.grad(lambda p: remat.loss_and_metrics(
            remat.build(), p, {"input_ids": ids}, rng=key,
            policy=FP32)[0])(p)

    text = jit_once(step).lower(params, batch["input_ids"],
                               jax.random.key(0)).as_text()
    tables = [line for line in text.splitlines()
              if "stablehlo.constant" in line
              and line.rstrip().endswith(": tensor<80x16xf32>")]
    assert len(tables) == 2                       # cos and sin


def test_the_cli_builds_the_task_from_its_preset():
    """``scripts/block_diffusion_lm.py`` in the form of the other
    families' scripts: the preset parses, the data's vocabulary and row
    length reach the model; the published model is the task's
    defaults."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import block_diffusion_lm as script

    cli = script.main(
        args=["fit", "--config",
              os.path.join(ROOT, "scripts", "configs",
                           "block_diffusion_lm_1chip.yaml"),
              "--data.vocab_size=300", "--data.max_seq_len=64"], run=False)
    assert cli.config["model"]["block_length"] == 4
    assert cli.config["experiment"] == "block_diffusion_lm"
    task, datamodule, _ = cli.instantiate()
    assert isinstance(task, BlockDiffusionLMTask)
    assert task.vocab_size == datamodule.vocab_size == 300
    assert task.max_seq_len == 64 and task.remat is True
    assert task.held_experts is None and task.build().num_held_experts == 16
    assert task.build().pattern == "*E*E*E*E"
    published = BlockDiffusionLMTask()
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sdar_30b_a3b.json")) as f:
        config = json.load(f)
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "num_experts_per_tok", "moe_intermediate_size",
                "norm_topk_prob", "rope_theta", "rms_norm_eps"):
        assert getattr(published, key) == config[key], key
    for key in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert getattr(published, key) == config["published"][key], key
    assert published.block_length == config["model"]["block_length"]
    assert published.t_min == config["model"]["t_min"]
