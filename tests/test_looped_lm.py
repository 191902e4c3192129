"""The looped causal language model (``models/looped_lm.py``,
``tasks/causal_lm.py``) and what it brought to the shared ops: RMSNorm,
rotary positions, the gated MLP, projections without biases, the fused
kernels' causal mode, the per-position fused CE whose weights take a
gradient, and the weight-shared loop with its hand-written backward."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once, out_and_grads
from jax.extend.core import Literal

import perceiver_tpu.models.looped_lm as looped_lm
import perceiver_tpu.ops.attention as attn
import perceiver_tpu.ops.remat as remat
from perceiver_tpu.models.looped_lm import (
    LoopedLM,
    decoder_layer_apply,
    exit_distribution,
)
from perceiver_tpu.ops.fourier import rope_apply, rope_tables
from perceiver_tpu.ops.fused_ce import fused_linear_nll
from perceiver_tpu.ops.linear import linear_apply, linear_init
from perceiver_tpu.ops.mlp import gated_mlp_apply, gated_mlp_init
from perceiver_tpu.ops.norm import rms_norm_apply, rms_norm_init
from perceiver_tpu.ops.pallas_attention import (
    flash_attention_channels,
    pick_blocks,
)
from perceiver_tpu.ops.policy import Policy
from perceiver_tpu.tasks import CausalLMTask

FP32 = Policy.fp32()
TOY = dict(vocab_size=96, hidden_size=32, num_hidden_layers=2,
           num_attention_heads=2, head_dim=16, intermediate_size=48,
           max_seq_len=24, total_ut_steps=4, ce_chunk_size=40)


def normal(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(seed), shape, dtype)


def rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12))


# --- the ops -----------------------------------------------------------------


def test_rms_norm_is_its_formula_with_float32_statistics():
    x = normal(0, (3, 5, 32)) * 3.0
    scale = 1.0 + 0.1 * normal(1, (32,))
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale
    got = rms_norm_apply({"scale": scale}, x, 1e-6, FP32)
    assert rel(got, want) < 1e-6
    assert set(rms_norm_init(32)) == {"scale"}      # no bias, no mean
    bf = rms_norm_apply({"scale": scale}, x.astype(jnp.bfloat16), 1e-6,
                        Policy.bf16())
    assert bf.dtype == jnp.bfloat16
    assert rel(bf.astype(jnp.float32), want) < 2e-2


def test_rope_turns_each_pair_by_its_angle():
    heads, dim, seq, theta = 2, 16, 12, 1e6
    cos, sin = rope_tables(seq, dim, theta)
    assert cos.shape == sin.shape == (seq, dim) and cos.dtype == np.float32
    x = normal(2, (1, seq, heads * dim))
    got = np.asarray(rope_apply(x, cos, sin, heads)).reshape(
        seq, heads, dim)
    xh = np.asarray(x).reshape(seq, heads, dim)
    for i in (0, 5, 11):
        for j in (0, 3, 7):
            angle = i * theta ** (-2.0 * j / dim)
            a, b = xh[i, :, j], xh[i, :, j + dim // 2]
            np.testing.assert_allclose(
                got[i, :, j], a * math.cos(angle) - b * math.sin(angle),
                rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(
                got[i, :, j + dim // 2],
                b * math.cos(angle) + a * math.sin(angle),
                rtol=1e-5, atol=1e-6)
    # a rotation: norms keep, and q.k depends on the distance alone
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(xh, axis=-1), rtol=1e-5)
    with pytest.raises(ValueError):
        rope_tables(4, 15, theta)


def test_gated_mlp_is_silu_gate_times_up_then_down():
    params = gated_mlp_init(jax.random.key(3), 32, 48)
    assert all(set(p) == {"w"} for p in params.values())
    x = normal(4, (2, 7, 32))
    g, u, d = (params[n]["w"] for n in ("gate", "up", "down"))
    want = ((x @ g) * jax.nn.sigmoid(x @ g) * (x @ u)) @ d
    assert rel(gated_mlp_apply(params, x, FP32), want) < 1e-5


def test_a_linear_tree_without_b_is_a_projection_without_bias():
    params = linear_init(jax.random.key(5), 8, 4, bias=False)
    assert set(params) == {"w"}
    x = normal(6, (3, 8))
    assert rel(linear_apply(params, x, FP32), x @ params["w"]) < 1e-6
    with_bias = linear_init(jax.random.key(5), 8, 4)
    assert rel(linear_apply(with_bias, x, FP32),
               x @ with_bias["w"] + with_bias["b"]) < 1e-6
    assert all(set(p) == {"w"} for p in attn.mha_init(
        jax.random.key(7), 32, 2, bias=False).values())


# --- the causal kernel mode --------------------------------------------------


def causal_reference(q, k, v, heads, block_diffusion=None):
    """The materialised core under a lower-triangular mask, or under
    the block-diffusion mask written out pair by pair from its rules."""
    b, s, e = q.shape
    sees = jnp.tril(jnp.ones((s, s), bool))
    if block_diffusion is not None:
        half, block = block_diffusion
        sees = np.zeros((s, s), bool)
        for j in range(s):
            for l in range(s):   # noqa: E741
                if j < half and l < half:
                    sees[j, l] = j // block == l // block
                elif j < half:
                    sees[j, l] = (l - half) // block < j // block
                elif l >= half:
                    sees[j, l] = (l - half) // block <= (j - half) // block
        with jax.ensure_compile_time_eval():    # the caller may be jitted
            assert (sees == ~np.asarray(
                attn.block_diffusion_mask(half, block))).all()
    bias = jnp.where(jnp.asarray(sees), 0.0, attn.NEG_INF)[None, None]
    split = [x.reshape(b, s, heads, e // heads) for x in (q, k, v)]
    out = attn._sdpa_core(1.0 / math.sqrt(e // heads), 0.0, jnp.float32,
                          *split, bias, None)
    return out.reshape(b, s, e)


@pytest.mark.parametrize("seq,heads,dim,block_q,block_k,block_diffusion", [
    (256, 2, 128, 128, 128, None),    # whole blocks, a block a head
    (200, 2, 64, 128, 128, None),     # a padded last block, two heads a block
    (384, 4, 32, 128, 256, None),     # keys in wider blocks than queries
    (512, 2, 128, 256, 128, None),    # queries in wider blocks than keys
    (300, 2, 128, None, None, None),  # blocks from the shapes, ragged
    (130, 2, 16, 128, 128, None),     # two rows into the second block
    # the third mask: 2 L positions in blocks of B, L no multiple of a tile
    (384, 2, 64, 128, 128, (192, 4)),     # the halves meet inside a tile
    (320, 1, 128, 128, 256, (160, 32)),   # wide blocks, a padded last tile
    (512, 2, 128, 128, 128, (256, 4)),    # L in whole tiles: 10 of 16 run
    (200, 4, 32, None, None, (100, 4)),   # one tile of everything
], ids=["whole", "ragged_d64", "wide_keys", "wide_queries", "picked",
        "barely_two_blocks", "block_diffusion_b4", "block_diffusion_b32",
        "block_diffusion_whole_tiles", "block_diffusion_one_tile"])
def test_causal_kernels_match_the_materialised_core(seq, heads, dim,
                                                    block_q, block_k,
                                                    block_diffusion):
    q, k, v, g = (normal(10 + i, (2, seq, heads * dim)) for i in range(4))
    kw = dict(num_heads=heads, block_q=block_q, block_k=block_k,
              **(dict(causal=True) if block_diffusion is None
                 else dict(block_diffusion=block_diffusion)))
    heads = (heads, block_diffusion)
    got_out, got = out_and_grads(
        lambda *a: flash_attention_channels(*a, **kw), g, q, k, v)
    want_out, want = out_and_grads(
        lambda *a: causal_reference(*a, *heads), g, q, k, v)
    assert rel(got_out, want_out) < 1e-5
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-5


def test_causal_calls_carry_their_own_kernel_names():
    q = normal(20, (1, 256, 128))

    def names(**kw):
        text = jit_once(jax.grad(lambda q: flash_attention_channels(
            q, q, q, num_heads=1, **kw).sum())).lower(q).as_text(
                debug_info=True)
        return {n for n in ("flash_attention_fwd", "flash_attention_bwd",
                            "causal_attention_fwd", "causal_attention_bwd")
                if n in text}

    assert names(causal=True) == {"causal_attention_fwd",
                                  "causal_attention_bwd"}
    assert names() == {"flash_attention_fwd", "flash_attention_bwd"}


def test_causal_blocks_stream_and_the_mode_takes_no_bias():
    assert pick_blocks(4096, 4096, causal=True) == (1024, 1024)
    assert pick_blocks(2048, 2048, causal=True) == (1024, 1024)
    assert pick_blocks(2048, 2048) == (512, 2048)      # as before
    q = normal(21, (1, 128, 128))
    with pytest.raises(ValueError):
        flash_attention_channels(q, q, q, num_heads=1, causal=True,
                                 bias=jnp.zeros((1, 128)))
    with pytest.raises(ValueError):
        flash_attention_channels(q, normal(22, (1, 256, 128)),
                                 normal(22, (1, 256, 128)), num_heads=1,
                                 causal=True)


@pytest.mark.parametrize("case", [
    dict(causal=True, want=("fused", None)),
    dict(causal=True, has_key_padding_mask=True,
         want=("materialized", "attn_mask")),
    dict(causal=True, lq=64, lk=64, want=("materialized", "shape")),
    dict(causal=True, backend="cpu", want=("materialized", "backend")),
], ids=["causal", "causal_padded", "small", "cpu"])
def test_the_pick_takes_causal_as_a_property_of_the_call(case):
    case = dict(case)
    want = case.pop("want")
    kw = dict(backend="tpu", lq=4096, lk=4096, dropout_active=False,
              has_attn_mask=False, mesh_devices=1)
    kw.update(case)
    assert attn.pick_attention_core(**kw) == want


def test_mha_causal_fused_against_materialised_and_the_tally(monkeypatch):
    params = attn.mha_init(jax.random.key(30), 64, 2, bias=False)
    x = normal(31, (2, 512, 64))
    rope = rope_tables(512, 32, 1e4)
    kw = dict(num_heads=2, causal=True, rope=rope, policy=FP32)
    fused = attn.mha_apply(params, x, x, x, impl="flash", **kw)
    plain = attn.mha_apply(params, x, x, x, impl="einsum", **kw)
    assert rel(fused, plain) < 1e-4
    # position 0 sees itself alone: its output is its own value's
    v0 = (x[:, :1] @ params["v"]["w"]) @ params["out"]["w"]
    assert rel(plain[:, :1], v0) < 1e-4
    # on a TPU the pick sends the causal call site to the kernels
    monkeypatch.setattr(attn, "_backend", lambda: "tpu")
    with attn.attention_paths() as tally:
        jax.eval_shape(lambda x: attn.mha_apply(params, x, x, x, **kw), x)
    assert dict(tally) == {("fused", None): 1}
    with pytest.raises(NotImplementedError):
        attn.mha_apply(params, x, x, x, impl="chunked", **kw)
    with pytest.raises(NotImplementedError):
        attn.mha_apply(params, x, x, x, attn_mask=jnp.zeros((512, 512)),
                       **kw)


# --- the fused CE with rows handed back -------------------------------------


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
def test_fused_nll_gradients_to_hidden_head_and_weights(bias):
    n, c, v = 100, 16, 50          # 100 rows in chunks of 32: padded
    head = {"w": normal(40, (c, v)) * 0.3}
    if bias:
        head["b"] = normal(41, (v,)) * 0.1
    hidden, weights = normal(42, (n, c)), jax.nn.softmax(normal(43, (n,)))
    labels = jax.random.randint(jax.random.key(44), (n,), 0, v)

    def fused(head, hidden, weights):
        nll = fused_linear_nll(head, hidden, labels, chunk_size=32,
                               policy=FP32)
        return (weights * nll).sum() / 7.0      # not by sum(w)

    def dense(head, hidden, weights):
        logits = hidden @ head["w"] + head.get("b", 0.0)
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                                   labels[:, None], 1)[:, 0]
        return (weights * nll).sum() / 7.0

    args = (head, hidden, weights)
    fused_loss, got = jit_once(jax.value_and_grad(fused, (0, 1, 2)))(*args)
    dense_loss, want = jit_once(jax.value_and_grad(dense, (0, 1, 2)))(*args)
    assert abs(fused_loss - dense_loss) < 1e-5
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert rel(a, b) < 1e-5
    assert float(jnp.abs(got[2]).min()) > 0      # the weights' gradient


# --- the exit gate -----------------------------------------------------------


def test_exit_distribution_sums_to_one_and_reaches_the_gate():
    z = normal(50, (4, 3, 5)) * 2.0
    p, log_p = exit_distribution(z)
    np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-6)
    lam = jax.nn.sigmoid(z)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-6)
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]),
                               rtol=1e-5)
    np.testing.assert_allclose(p[3], np.prod(1 - lam[:3], axis=0),
                               rtol=1e-5)
    np.testing.assert_allclose(jnp.exp(log_p), p, rtol=1e-6)
    # the last pass's own logit has no say: it takes what is left
    g = jax.grad(lambda z: (exit_distribution(z)[0]
                            * jnp.arange(1.0, 5.0)[:, None, None]).sum())(z)
    assert float(jnp.abs(g[:3]).min()) > 0 and float(jnp.abs(g[3]).max()) == 0


# --- the model and its task --------------------------------------------------


@pytest.fixture(scope="module")
def toy():
    task = CausalLMTask(remat=True, **TOY)
    model = task.build()
    params = jit_once(model.init)(jax.random.key(60))
    # norms away from 1 and a live gate, so that no leaf's gradient
    # hides behind a symmetric start
    params = jax.tree.map(lambda x: x + 0.05 * normal(61, x.shape), params)
    ids = jax.random.randint(jax.random.key(62), (2, 24), 0, 96)
    return task, model, params, {"input_ids": ids}


@pytest.fixture(scope="module")
def plain(toy):
    """``impl -> (loss, gradients)`` as plain autodiff gives them,
    without remat: the fixed side of the backward's cases and of the
    weight sharing's, one program an ``impl``, run once."""
    task, _, params, batch = toy

    @functools.cache
    def loss_and_grads(impl):
        t = dataclasses.replace(task, remat=False, attention_impl=impl)
        return jit_once(jax.value_and_grad(lambda p: t.loss_and_metrics(
            t.build(), p, batch, policy=FP32)[0]))(params)

    return loss_and_grads


def test_the_loss_is_the_expected_nll_minus_the_entropy(toy):
    task, model, params, batch = toy
    loss, metrics = jit_once(lambda p: task.loss_and_metrics(
        model, p, batch, policy=FP32))(params)
    logits, p = jit_once(lambda p: model.apply(
        p, batch["input_ids"], policy=FP32))(params)
    assert logits.shape == (4, 2, 24, 96) and p.shape == (4, 2, 24)
    ids = batch["input_ids"]
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits[:, :, :-1]),
                               ids[None, :, 1:, None], -1)[..., 0]
    p = p[:, :, :-1]
    entropy = -(p * jnp.log(p)).sum(0)
    want = ((p * nll).sum(0) - 0.1 * entropy).mean()
    assert abs(loss - want) < 1e-5
    assert set(metrics) == {"loss", "exit_pass_mean", "exit_entropy",
                            "nll_pass1", "nll_pass2", "nll_pass3",
                            "nll_pass4"}
    np.testing.assert_allclose(metrics["nll_pass2"], nll[1].mean(),
                               rtol=1e-5)
    np.testing.assert_allclose(metrics["exit_entropy"], entropy.mean(),
                               rtol=1e-5)
    np.testing.assert_allclose(
        metrics["exit_pass_mean"],
        (p * jnp.arange(1.0, 5.0)[:, None, None]).sum(0).mean(), rtol=1e-5)
    assert 1.0 < float(metrics["exit_pass_mean"]) < 4.0


def test_padding_and_invalid_rows_carry_no_label(toy):
    task, model, params, batch = toy
    ids = batch["input_ids"]
    pad = jnp.arange(24)[None, :] >= jnp.array([[24], [10]])
    padded = {"input_ids": ids, "pad_mask": pad,
              "valid": jnp.array([True, True])}
    loss_of = jit_once(lambda b: task.loss_and_metrics(   # a program a
        model, params, b, policy=FP32)[0])                 # batch's tree
    loss = loss_of(padded)
    # causal: row 1's first 10 tokens never see what follows them
    short = {"input_ids": jnp.concatenate(
        [ids[1:, :10], jnp.zeros((1, 14), ids.dtype)], 1), "pad_mask": pad[1:]}
    l0, l1 = loss_of({"input_ids": ids[:1]}), loss_of(short)
    assert abs(loss - (23 * l0 + 9 * l1) / 32) < 1e-5
    only0 = loss_of({"input_ids": ids, "valid": jnp.array([True, False])})
    assert abs(only0 - l0) < 1e-5


def test_weight_sharing_the_gradient_is_the_sum_over_the_passes(toy, plain):
    """Four untied copies of the stack holding equal values, applied in
    turn, give per-copy gradients whose sum is the looped model's."""
    task, model, params, batch = toy

    def untied_loss(copies, rest):
        rope = rope_tables(24, 16, 1e6)
        h = rest["embed"]["embed"][batch["input_ids"]]
        states = []
        for layers in copies:
            for i in range(2):
                h = decoder_layer_apply(
                    jax.tree.map(lambda x: x[i], layers), h, num_heads=2,
                    rope=rope, eps=1e-6, policy=FP32)
            h = rms_norm_apply(rest["norm"], h, 1e-6, FP32)
            states.append(h)
        states = jnp.stack(states)
        p, log_p = exit_distribution(model.gate_logits(rest, states))
        logits = states @ rest["head"]["w"]
        ids = batch["input_ids"]
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits[:, :, :-1]),
                                   ids[None, :, 1:, None], -1)[..., 0]
        p, log_p = p[:, :, :-1], log_p[:, :, :-1]
        return ((p * nll).sum(0) + 0.1 * (p * log_p).sum(0)).mean()

    rest = {k: v for k, v in params.items() if k != "layers"}
    copies = [params["layers"]] * 4
    g_copies, g_rest = jit_once(jax.grad(untied_loss, (0, 1)))(copies, rest)
    summed = jax.tree.map(lambda *g: sum(g), *g_copies)
    _, looped = plain(task.attention_impl)
    for a, b in zip(jax.tree.leaves(looped["layers"]),
                    jax.tree.leaves(summed)):
        assert rel(a, b) < 1e-4
    # and no single pass gives it: the sharing is live in every one
    assert rel(looped["layers"]["mlp"]["up"]["w"],
               g_copies[3]["mlp"]["up"]["w"]) > 0.1
    for name in rest:
        for a, b in zip(jax.tree.leaves(looped[name]),
                        jax.tree.leaves(g_rest[name])):
            assert rel(a, b) < 1e-4


def kernel_calls(jaxpr, name, live_out=None):
    """``(calls, inputs read)``: the Pallas calls named ``name`` that a
    live output of ``jaxpr`` needs, a scan's body counted once an
    iteration. Liveness goes through a custom VJP's call as through any
    other (``remat._in_place_of`` reads one of its two arguments), which
    JAX's own dead-code pass over a jaxpr leaves whole for XLA."""
    live_out = [True] * len(jaxpr.outvars) if live_out is None else live_out
    live = {v for v, on in zip(jaxpr.outvars, live_out)
            if on and not isinstance(v, Literal)}
    calls = 0
    for eqn in reversed(jaxpr.eqns):
        outs = [v in live for v in eqn.outvars]
        if not any(outs):
            continue
        read = [True] * len(eqn.invars)
        inner = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
        inner = getattr(inner, "jaxpr", inner)
        if eqn.primitive.name == "pallas_call":
            calls += name in str(eqn.params["name"])
        elif eqn.primitive.name == "scan":
            calls += eqn.params["length"] * kernel_calls(inner, name)[0]
        elif inner is not None:
            n, read = kernel_calls(inner, name, outs)
            calls += n
        live.update(v for v, on in zip(eqn.invars, read)
                    if on and not isinstance(v, Literal))
    return calls, [v in live for v in jaxpr.invars]


PREFIXES = [remat.REMAT_NAMES[:i] for i in range(len(remat.REMAT_NAMES) + 1)]


@pytest.mark.parametrize("kept", PREFIXES, ids=lambda p: "+".join(p) or "none")
@pytest.mark.parametrize("impl", ["einsum", "flash"])
def test_the_hand_written_backward_is_autodiffs(toy, plain, impl, kept,
                                                monkeypatch):
    """Whatever the backward is handed beside the layers' inputs, the
    loss and every gradient are plain autodiff's; a kept value is not
    computed again; and nothing else goes through the stacks."""
    task, model, params, batch = toy
    reckoned = {}

    def choose(held, layer_in):
        reckoned.update(held, layer_in=layer_in)
        return kept

    monkeypatch.setattr(remat, "choose_keeps", choose)

    def loss_and_grads(policy):
        t = dataclasses.replace(task, remat=True, attention_impl=impl)
        return jit_once(jax.value_and_grad(lambda p: t.loss_and_metrics(
            t.build(), p, batch, policy=policy)[0]))

    # one trace gives the float32 step's jaxpr and its program
    traced = loss_and_grads(FP32).trace(params)
    loss, g = traced.lower().compile()(params)
    plain_loss, plain_g = plain(impl)
    assert abs(float(loss) - float(plain_loss)) < 1e-5
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(plain_g)):
        assert rel(a, b) < 1e-4
    # in bfloat16 the accumulator is still float32, like the parameters
    g16 = loss_and_grads(Policy.bf16())(params)[1]
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(g16))
    for a, b in zip(jax.tree.leaves(g16), jax.tree.leaves(g)):
        assert rel(a, b) < 0.1

    # the forward kernel: once an application with its output kept,
    # once more under the backward without
    applications = TOY["total_ut_steps"] * TOY["num_hidden_layers"]
    step = traced.jaxpr.jaxpr
    fused = impl == "flash"
    assert kernel_calls(step, "causal_attention_fwd")[0] == fused * (
        applications if "attn_out" in kept else 2 * applications)
    assert kernel_calls(step, "causal_attention_bwd")[0] == (
        fused * applications)

    # what the backward is handed, to the byte: the applications'
    # inputs and the kept names' values, no copy of a layer's parameters
    lm = dataclasses.replace(model, attention_impl=impl)
    stacks = {}

    def fwd(layer, norm, passes, policy, kept, layers, norm_params, h):
        out, res = looped_lm._loop_stack_fwd(
            layer, norm, passes, policy, kept, layers, norm_params, h)
        stacks.update(zip(("inputs", "values"), res[2]))
        return out

    monkeypatch.setattr(looped_lm, "_loop_stack", fwd)
    jax.eval_shape(lambda p: lm.hidden_states(
        p, batch["input_ids"], policy=FP32), params)

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    assert nbytes(stacks["inputs"]) == reckoned["layer_in"] == (
        applications * 2 * 24 * 32 * 4)
    assert set(stacks["values"]) == set(kept)
    for name in kept:
        assert nbytes(stacks["values"][name]) == reckoned[name], name
    assert reckoned["qkv"] == 3 * reckoned["layer_in"]
    assert reckoned["mlp_hidden"] == applications * 2 * (2 * 24 * 48 * 4)
    assert (reckoned["attn_out"] > reckoned["layer_in"]) == fused


def test_the_model_rejects_what_it_cannot_be():
    with pytest.raises(ValueError):
        LoopedLM(vocab_size=10, hidden_size=32, num_layers=1, num_heads=3,
                 head_dim=16, intermediate_size=8, max_seq_len=8)
    with pytest.raises(ValueError):
        CausalLMTask(attention_impl="ring", **TOY)
    task = CausalLMTask(**TOY)
    with pytest.raises(ValueError):
        task.build().hidden_states(task.build().init(jax.random.key(0)),
                                   jnp.zeros((1, 25), jnp.int32))
    assert task.batch_partition("input_ids", 2, None) == ()


def test_the_trainer_fits_it_and_logs_the_gate(tmp_path):
    """``Trainer(...).fit()`` on the task as on any other: the loss
    falls and the exit telemetry is in every step's line."""
    import json
    import os

    from perceiver_tpu.training import Trainer, TrainerConfig

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 8, (4, 24)).astype(np.int32),
             "valid": np.ones(4, bool)}

    class Data:
        def prepare_data(self): pass
        def setup(self, stage=None): pass
        def train_dataloader(self): return [batch] * 12
        def val_dataloader(self): return []
        test_dataloader = val_dataloader

    tele = os.path.join(tmp_path, "tele")
    trainer = Trainer(
        CausalLMTask(remat=True, **TOY), Data(),
        TrainerConfig(max_epochs=1, precision="32", log_every_n_steps=1,
                      num_sanity_val_steps=0, enable_checkpointing=False,
                      default_root_dir=str(tmp_path), telemetry_dir=tele),
        optimizer_init={"class_path": "AdamW", "init_args": {"lr": 3e-3}})
    trainer.fit()
    with open(os.path.join(tele, "telemetry.jsonl")) as f:
        lines = [json.loads(ln) for ln in f]
    steps = [ln for ln in lines if "loss" in ln and "step" in ln]
    assert len(steps) == 12
    assert steps[-1]["loss"] < steps[0]["loss"] - 0.3
    assert {"nll_pass1", "nll_pass4", "exit_pass_mean",
            "exit_entropy"} <= set(steps[0])


def test_the_cli_builds_the_task_from_its_preset():
    """``scripts/clm.py`` in the form of ``scripts/mlm.py``: the preset
    parses, the data's vocabulary and row length reach the model."""
    import os
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    sys.path.insert(0, os.path.join(root, "scripts"))
    import clm as clm_script

    cli = clm_script.main(
        args=["fit", "--config",
              os.path.join(root, "scripts", "configs",
                           "looped_lm_1chip.yaml"),
              "--data.vocab_size=300", "--data.max_seq_len=64"], run=False)
    model = cli.config["model"]
    assert model["hidden_size"] == 512 and model["total_ut_steps"] == 4
    assert model["num_attention_heads"] * model["head_dim"] == 512
    assert cli.config["experiment"] == "clm"
    task, datamodule, _ = cli.instantiate()
    assert isinstance(task, CausalLMTask)
    assert task.vocab_size == datamodule.vocab_size == 300
    assert task.max_seq_len == 64 and task.remat is True
