"""The hybrid state-space / mixture-of-experts stack
(``models/hybrid_lm.py``, ``ops/ssm.py``, ``ops/moe.py``): each mixer's
forward pass and gradient against the plain reference's, the chunked
scan against the position-by-position recurrence (a length that is no
multiple of the chunk, decays that underflow) on both its executors
(the einsums; the Pallas kernels of ``ops/pallas_ssm.py``, interpreted),
which of the two a call takes, the share tied to the
model (16 shares of the experts add up to the uncut layer, the shared
expert once), no dropped token under a forced imbalance, grouped queries
against repeated keys and values, no position embedding, what ``remat``
keeps, and what the trainer says and logs."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
        got = ssm.ssm_scan(*args, chunk_size=16)
    chunk = min(16, seq)
    pad = "+pad" if seq % chunk else ""
    assert dict(forms) == {
        f"chunked[{chunk}x{-(-seq // chunk)}{pad},backend]": 1}
    want = recurrence(*args)
    assert got.shape == want.shape == args[0].shape
    assert rel(got, want) < 1e-5


def test_the_scans_gradient_is_the_recurrences():
    args = scan_inputs(40)
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    got = jax.grad(lambda *a: (ssm.ssm_scan(*a, chunk_size=16) * w).sum(),
                   argnums=range(5))(*args)
    want = jax.grad(lambda *a: (recurrence(*a) * w).sum(),
                    argnums=range(5))(*args)
    for g, r in zip(got, want):
        assert rel(g, r) < 2e-5


def test_decays_that_underflow_do_so_quietly():
    """dt A near -40 a position: the decay over a chunk is exp(-600),
    which float32 does not hold; nothing is inf or nan, forward or
    backward, and the result is still the recurrence's."""
    args = scan_inputs(40, dt_scale=5.0, a_scale=8.0)
    assert float((args[1] * args[2]).min()) < -40
    got, vjp = jax.vjp(lambda *a: ssm.ssm_scan(*a, chunk_size=16), *args)
    grads = vjp(jnp.ones_like(got))
    assert all(bool(jnp.isfinite(g).all()) for g in (got, *grads))
    assert rel(got, recurrence(*args)) < 1e-5
    # in the compute dtype of the chip too
    low = ssm.ssm_scan(args[0].astype(jnp.bfloat16), args[1], args[2],
                       *(v.astype(jnp.bfloat16) for v in args[3:]),
                       chunk_size=16)
    assert low.dtype == jnp.bfloat16 and bool(jnp.isfinite(low).all())


# --- the fused scan: the kernels, interpreted --------------------------------
# The smallest shapes the pick admits: chunks of 128 positions, a state
# of 128, eight heads a group in whole slabs of 128 lanes.

FUSED = {
    # eight heads of 16 share one slab; two chunks: a carried state
    "one_slab": dict(seq=256, rows=1, heads=8, width=16, groups=1),
    # the cell's layout in small: two heads of 64 a slab, four slabs a
    # group, two groups, two rows
    "four_slabs": dict(seq=256, rows=2, heads=16, width=64, groups=2),
    # a padded last chunk
    "padded_tail": dict(seq=200, rows=1, heads=8, width=16, groups=1),
}


def as_a_tpu(monkeypatch):
    """The pick as a TPU would make it; off the chip the kernels run
    interpreted (``utils/platform.resolve_interpret``)."""
    monkeypatch.setattr(ssm, "_backend", lambda: "tpu")


def fused_inputs(dtype=jnp.float32, dt_scale=0.1, **shape):
    x, dt, a, b, c = scan_inputs(state=128, dt_scale=dt_scale, **shape)
    # values the compute dtype holds, so that every form starts alike
    return (x.astype(dtype), dt, a, b.astype(dtype) / 4, c.astype(dtype) / 4)


def in_float32(args):
    return tuple(v.astype(jnp.float32) for v in args)


def value_and_grads(fn, args, w):
    return jax.value_and_grad(
        lambda *a: (fn(*a).astype(jnp.float32) * w).sum(),
        argnums=range(5))(*args)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", FUSED)
def test_the_fused_scan_is_the_recurrence(case, dtype, monkeypatch):
    """Forward and every gradient (x, dt, A, B, C) against the
    position-by-position recurrence in float32; bfloat16 operands to
    what bfloat16 products allow."""
    as_a_tpu(monkeypatch)
    args = fused_inputs(dtype, **FUSED[case])
    seq = FUSED[case]["seq"]
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    with ssm.scan_paths.counting() as forms:
        got = ssm.ssm_scan(*args, chunk_size=128)
    assert dict(forms) == {
        f"fused[128x2{'+pad' if seq % 128 else ''}]": 1}
    assert got.shape == args[0].shape and got.dtype == dtype
    _, grads = value_and_grads(
        lambda *a: ssm.ssm_scan(*a, chunk_size=128), args, w)
    want = recurrence(*in_float32(args))
    _, want_grads = value_and_grads(recurrence, in_float32(args), w)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert rel(got, want) < tol
    for g, r, v in zip(grads, want_grads, args):
        assert g.shape == v.shape and g.dtype == v.dtype
        assert rel(g, r) < tol


def test_the_fused_scan_underflows_quietly(monkeypatch):
    """As ``test_decays_that_underflow_do_so_quietly``, on the kernels:
    the mask goes in before the ``exp`` there too, forward and
    backward."""
    as_a_tpu(monkeypatch)
    args = fused_inputs(dt_scale=5.0, a_scale=8.0, **FUSED["one_slab"])
    assert float((args[1] * args[2]).min()) < -40
    got, vjp = jax.vjp(lambda *a: ssm.ssm_scan(*a, chunk_size=128), *args)
    grads = vjp(jnp.ones_like(got))
    assert all(bool(jnp.isfinite(g).all()) for g in (got, *grads))
    assert rel(got, recurrence(*args)) < 1e-5
    low = (args[0].astype(jnp.bfloat16), args[1], args[2],
           *(v.astype(jnp.bfloat16) for v in args[3:]))
    got, vjp = jax.vjp(lambda *a: ssm.ssm_scan(*a, chunk_size=128), *low)
    grads = vjp(jnp.ones_like(got))
    assert got.dtype == jnp.bfloat16
    assert all(bool(jnp.isfinite(g.astype(jnp.float32)).all())
               for g in (got, *grads))


def test_the_fused_scan_rounds_no_more_than_the_einsums(monkeypatch):
    """At bfloat16 the kernels are no further from the float32
    recurrence than the einsum form is: the forward is the same
    arithmetic to the last bit or two; the backward rounds a cotangent
    only where a product takes it as an operand (on the chip the einsum
    form's products round their float32 operands too, which the CPU's
    do not, hence a quarter of room on the gradients)."""
    args = fused_inputs(jnp.bfloat16, **FUSED["four_slabs"])
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    want, want_grads = value_and_grads(recurrence, in_float32(args), w)

    def errors(fn):
        got, grads = value_and_grads(fn, args, w)
        return [abs(float(got) - float(want)) / abs(float(want))] + [
            float(jnp.linalg.norm((g.astype(jnp.float32) - r).ravel())
                  / jnp.linalg.norm(r.ravel()))
            for g, r in zip(grads, want_grads)]

    chunked = errors(lambda *a: ssm.ssm_scan(*a, chunk_size=128))
    as_a_tpu(monkeypatch)
    fused = errors(lambda *a: ssm.ssm_scan(*a, chunk_size=128))
    assert fused[0] <= chunked[0] + 1e-6
    for f, c in zip(fused[1:], chunked[1:]):
        assert f <= 1.25 * c


# (backend, mesh devices, chunk, state, heads a group, head dim)
SCAN_CHOICES = {
    "nemotron_train": (("tpu", 1, 128, 128, 8, 64), ("fused", None)),
    "heads_of_128": (("tpu", 1, 256, 256, 8, 128), ("fused", None)),
    "eight_heads_of_16": (("tpu", 1, 128, 128, 8, 16), ("fused", None)),
    "cpu": (("cpu", 1, 128, 128, 8, 64), ("chunked", "backend")),
    "gpu": (("gpu", 1, 128, 128, 8, 64), ("chunked", "backend")),
    "dp2_tp2_mesh": (("tpu", 4, 128, 128, 8, 64), ("chunked", "mesh")),
    "chunk_of_16": (("tpu", 1, 16, 128, 8, 64), ("chunked", "shape")),
    "row_shorter_than_a_chunk": (("tpu", 1, 40, 128, 8, 64),
                                 ("chunked", "shape")),
    "state_of_16": (("tpu", 1, 128, 16, 8, 64), ("chunked", "shape")),
    "heads_of_48": (("tpu", 1, 128, 128, 8, 48), ("chunked", "shape")),
    "half_a_slab": (("tpu", 1, 128, 128, 1, 64), ("chunked", "shape")),
    "four_heads_a_group": (("tpu", 1, 128, 128, 4, 64),
                           ("chunked", "shape")),
    # the first reason in CHUNKED_REASONS' order wins
    "backend_before_mesh": (("cpu", 4, 16, 16, 2, 8),
                            ("chunked", "backend")),
    "mesh_before_shape": (("tpu", 4, 16, 16, 2, 8), ("chunked", "mesh")),
}


@pytest.mark.parametrize("case", SCAN_CHOICES)
def test_pick_scan(case):
    (backend, mesh, chunk, state, per, width), want = SCAN_CHOICES[case]
    got = ssm.pick_scan(backend=backend, mesh_devices=mesh, chunk=chunk,
                        state=state, heads_per_group=per, head_dim=width)
    assert got == want
    assert got[1] is None or got[1] in ssm.CHUNKED_REASONS


@pytest.mark.parametrize("case", ["chunk_of_16", "mesh"])
def test_a_call_the_kernels_do_not_take_says_why(case, monkeypatch):
    """On a TPU too the einsums run where the kernels cannot, and the
    tally carries the reason beside the chunks."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    as_a_tpu(monkeypatch)
    args = fused_inputs(**FUSED["one_slab"])
    chunk = 16 if case == "chunk_of_16" else 128
    if case == "mesh":
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        args = tuple(jax.device_put(v, NamedSharding(mesh, P()))
                     for v in args)
    with ssm.scan_paths.counting() as forms:
        got = jax.jit(lambda *a: ssm.ssm_scan(*a, chunk_size=chunk))(*args)
    reason = "shape" if case == "chunk_of_16" else "mesh"
    assert dict(forms) == {f"chunked[{chunk}x{256 // chunk},{reason}]": 1}
    assert rel(got, recurrence(*args)) < 1e-5


# --- each mixer against the reference ----------------------------------------


def mixer_case(toy, name):
    _, model, params, batch = toy
    p = params["layers"][name]["mixer"]
    a = jax.random.normal(jax.random.key(5), (2, 40, TOY["hidden_size"]))
    w = jax.random.normal(jax.random.key(6), a.shape)
    return model, p, a, w


def assert_same_with_gradient(got_fn, want_fn, p, a, w, tol=2e-5):
    got, got_g = jax.value_and_grad(
        lambda p, a: (got_fn(p, a) * w).sum(), argnums=(0, 1))(p, a)
    want, want_g = jax.value_and_grad(
        lambda p, a: (want_fn(p, a) * w).sum(), argnums=(0, 1))(p, a)
    assert abs(got - want) < tol * abs(want) + 1e-6
    for g, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert rel(g, r) < 10 * tol
    assert rel(got_fn(p, a), want_fn(p, a)) < tol


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
    for held in (1, 4):
        routed = 0.0
        for first in range(0, 16, held):
            part = {**whole, "experts": jax.tree.map(
                lambda x: x[first:first + held], whole["experts"])}
            out, load = moe.moe_apply(part, a, top_k=3, first_expert=first,
                                      scaling=2.5, policy=FP32)
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

    p = {**p, "router": {"w": forced}}
    assert apply(p, a)[1].tolist() == [0, 80, 0, 0]
    assert_same_with_gradient(
        lambda p, a: apply(p, a)[0],
        lambda p, a: ref.expert_layer(p, a, TOY, "f32"), p, a, w)
    # every assignment top-k allows, all held: the whole of the larger
    # buffer is used
    p = {**p, "router": {"w": forced.at[:, 4].set(3.0).at[:, 6].set(3.5)
                         .at[:, :2].set(-4.0)}}
    load = apply(p, a)[1]
    assert load.tolist() == [80, 80, 80, 0] and int(load.sum()) == 80 * 3
    assert_same_with_gradient(
        lambda p, a: apply(p, a)[0],
        lambda p, a: ref.expert_layer(p, a, TOY, "f32"), p, a, w)
    # and between the two: more than a row a token, less than all
    p = {**p, "router": {"w": forced.at[:, 4].set(3.0).at[:, :2].set(-4.0)}}
    load = apply(p, a)[1]
    assert 80 < int(load.sum()) < 240
    assert_same_with_gradient(
        lambda p, a: apply(p, a)[0],
        lambda p, a: ref.expert_layer(p, a, TOY, "f32"), p, a, w)


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
def test_remat_changes_no_value_whatever_is_kept(toy, kept, monkeypatch):
    task, model, params, batch = toy
    reckoned = {}

    def choose(held, layer_in, names):
        reckoned.update(held, layer_in=layer_in, names=names)
        return kept

    monkeypatch.setattr(remat, "choose_keeps", choose)

    def loss_and_grads(on):
        t = dataclasses.replace(task, remat=on)
        return jax.value_and_grad(lambda p: t.loss_and_metrics(
            t.build(), p, batch, policy=FP32)[0])(params)

    (loss, g), (plain, plain_g) = loss_and_grads(True), loss_and_grads(False)
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


@pytest.mark.parametrize("kept", [HYBRID, HYBRID[:3]],
                         ids=["ssm_out_kept", "ssm_out_dropped"])
def test_a_remat_layer_launches_the_scans_forward_kernel_once(kept,
                                                              monkeypatch):
    """The fused scan's backward takes the scan's operands alone: with
    ``ssm_out`` kept the recomputed layer does not run the forward
    kernel again (one launch a layer and step); with it dropped the
    layer recomputes it, as it recomputes anything else it does not
    hold. The backward's two kernels run once either way."""
    from tests.test_looped_lm import kernel_calls
    as_a_tpu(monkeypatch)
    monkeypatch.setattr(remat, "choose_keeps", lambda *a, **k: kept)
    task = HybridLMTask(**{
        **TOY, "hybrid_override_pattern": "M", "mamba_head_dim": 16,
        "n_groups": 1, "ssm_state_size": 128, "chunk_size": 128,
        "max_seq_len": 256, "remat": True})
    model = task.build()
    params = jax.eval_shape(model.init, jax.random.key(0))
    batch = {"input_ids": jax.ShapeDtypeStruct((2, 256), jnp.int32)}
    with ssm.scan_paths.counting() as forms:
        step = jax.make_jaxpr(jax.grad(lambda p, b: task.loss_and_metrics(
            model, p, b, policy=FP32)[0]))(params, batch).jaxpr
    assert set(forms) == {"fused[128x2]"}
    assert kernel_calls(step, "ssm_scan_fwd")[0] == (
        1 if "ssm_out" in kept else 2)
    assert kernel_calls(step, "ssm_scan_bwd_states")[0] == 1
    assert kernel_calls(step, "ssm_scan_bwd")[0] == 2   # the states' too


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


@pytest.mark.parametrize("firsts", [(4, 4), (0, 12), (8, 0)])
def test_a_share_named_by_the_batch_is_that_share_of_the_configuration(
        toy, firsts):
    """``first_experts`` with the batch, one first expert an expert
    layer: the loss, its gradient and the loads are those of a model
    built with that share, and one program serves every share."""
    task, model, params, batch = toy
    named = {**batch, "first_experts": jnp.tile(
        jnp.asarray(firsts, jnp.int32), (2, 1))}
    step = jax.jit(jax.value_and_grad(
        lambda p, b: task.loss_and_metrics(model, p, b, policy=FP32),
        has_aux=True))
    (loss, metrics), grads = step(params, named)
    if firsts[0] == firsts[1]:
        built = dataclasses.replace(task, first_expert=firsts[0])
        (want, want_m), want_g = jax.value_and_grad(
            lambda p: built.loss_and_metrics(
                built.build(), p, batch, policy=FP32), has_aux=True)(params)
        assert float(loss) == pytest.approx(float(want), rel=1e-6)
        assert float(metrics["moe_assignments"]) \
            == float(want_m["moe_assignments"])
        for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_g)):
            assert rel(g, w) < 1e-5
    # the plain reference, told the same shares
    want = ref.logits(params, batch["input_ids"], TOY,
                      first_experts=jnp.asarray(firsts))
    got = model.apply(params, batch["input_ids"],
                      first_experts=jnp.asarray(firsts), policy=FP32)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)
    assert step._cache_size() == 1


def test_the_metrics_carry_the_assignments_and_the_imbalance(toy):
    task, model, params, batch = toy
    _, metrics = task.loss_and_metrics(model, params, batch, policy=FP32)
    assert set(metrics) == {"loss", "moe_assignments",
                            "moe_load_max_over_mean"}
    _, loads = model.hidden_states(params, batch["input_ids"], policy=FP32)
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
