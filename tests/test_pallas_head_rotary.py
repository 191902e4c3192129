"""The head norm and rotary kernels (``ops/pallas_head_rotary.py``),
interpreted on the CPU, against ``rope_apply(head_rms_norm(...))`` as the
attention layers always had it: outputs and the gradients to ``x`` and
the norm's scale, at float32 (to rounding) and at bfloat16 (no further
from the float32 function than XLA's operations are); the norm and the
rotation together, either alone, a zero-centred scale, a part of a head
turned from its first channel and from an offset (latent attention's
queries, against their split form), heads read where they lie in a
packed product and beside their gates, tables longer than the row; what
``fits`` says, what the tally says, that where it says no the site's
program is the parent's, and that the stacks with neither a head norm
nor rotary positions never reach the function."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

from perceiver_tpu.models import hybrid_lm
from perceiver_tpu.ops import attention
from perceiver_tpu.ops import pallas_head_rotary as kernels
from perceiver_tpu.ops.attention import head_rms_norm
from perceiver_tpu.ops.fourier import rope_apply, rope_tables
from perceiver_tpu.ops.policy import Policy
from perceiver_tpu.parallel import make_mesh
from perceiver_tpu.tasks import HybridLMTask

FP32, BF16 = Policy.fp32(), Policy.bf16()
POLICIES = {"float32": FP32, "bfloat16": BF16}
EPS = 1e-6
# float32 against float32: sums in another order (measured 4e-7)
ROUNDING = 5e-6
SEQ = 40


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """Tiles of 32 positions in walks of 16 and of 256 channels: a row
    of 40 is two tiles, the last padded."""
    monkeypatch.setattr(kernels, "_POSITIONS", 32)
    monkeypatch.setattr(kernels, "_CHANNELS", 256)
    monkeypatch.setattr(kernels, "_ROWS", 16)


#: what a call is made of: heads, a head's channels, the channels the
#: tables turn and from which on, the norm's tree (``scale``, or
#: ``bias`` where it is zero-centred), where the heads lie in ``x``
#: (channels before the first, channels from a head's first to the
#: next's, channels after the last head's stride), rows of the tables
#: past the row's positions
KINDS = {
    "norm_rot_32x128": dict(heads=32, dim=128, rot=128, norm="scale"),
    "norm_rot_4x128": dict(heads=4, dim=128, rot=128, norm="scale"),
    "zero_centred_first_64_of_256": dict(heads=2, dim=256, rot=64,
                                         norm="bias"),
    "rotation_alone": dict(heads=4, dim=128, rot=128),
    "last_64_of_256": dict(heads=2, dim=256, rot=64, offset=192),
    "norm_alone": dict(heads=4, dim=128, norm="scale"),
    "whole_vectors_256_of_256": dict(heads=2, dim=256, rot=256, norm="scale"),
    "of_a_packed_product": dict(heads=4, dim=128, rot=128, first=512,
                                after=256),
    "beside_its_gate": dict(heads=2, dim=256, rot=64, norm="bias",
                            stride=512),
    "longer_tables": dict(heads=4, dim=128, rot=128, norm="scale", more=9),
}


def operands(kind, dtype, seq=SEQ, rows=2):
    """``(x, the norm's tree or None, the tables or None, a cotangent's
    weights)`` of a kind's call; ``x`` as wide as the kind lays it."""
    heads, dim = kind["heads"], kind["dim"]
    stride = kind.get("stride", dim)
    wide = kind.get("first", 0) + heads * stride + kind.get("after", 0)
    keys = jax.random.split(jax.random.PRNGKey(heads * dim + wide), 3)
    x = jax.random.normal(keys[0], (rows, seq, wide)).astype(dtype)
    norm = None
    if "norm" in kind:
        w = 0.3 * jax.random.normal(keys[1], (dim,))
        norm = {kind["norm"]: w if kind["norm"] == "bias" else 1.0 + w}
    rope = None
    if "rot" in kind:
        rope = rope_tables(seq + kind.get("more", 0), kind["rot"], 1e4)
    return x, norm, rope, jax.random.normal(keys[2],
                                            (rows, seq, heads * dim))


def cut(x, kind):
    """The heads' channels of the wider ``x``, side by side, as the
    sites slice them."""
    heads, dim = kind["heads"], kind["dim"]
    first, stride = kind.get("first", 0), kind.get("stride", dim)
    x = x[..., first:first + heads * stride]
    return x.reshape(*x.shape[:2], heads, stride)[..., :dim].reshape(
        *x.shape[:2], -1)


def parent_form(x, heads, norm, rope, offset, policy):
    """The sites' lines as the parent commit had them, copied:
    ``mha_apply``'s and ``rotary_gqa_apply``'s (no offset),
    ``_mla_queries``' (the split form)."""
    if norm is not None:
        x = head_rms_norm(norm, x, heads, EPS, policy)
    if rope is None:
        return x
    if not offset:
        return rope_apply(x, *rope, heads)
    rows, seq, _ = x.shape
    still, turning = jnp.split(x.reshape(rows, seq, heads, -1), [offset],
                               axis=-1)
    turned = rope_apply(turning.reshape(rows, seq, -1), *rope, heads)
    return jnp.concatenate(
        [still, turned.reshape(turning.shape)], -1).reshape(x.shape)


def xla_form(kind, policy):
    return lambda x, norm, rope: parent_form(
        cut(x, kind), kind["heads"], norm, rope, kind.get("offset", 0),
        policy)


def fused_form(kind):
    return lambda x, norm, rope: kernels.fused_head_rotary(
        x, kind["heads"], kind["dim"],
        scale=None if norm is None else kernels.norm_scale(norm), eps=EPS,
        rope=rope, offset=kind.get("offset", 0), first=kind.get("first", 0),
        stride=kind.get("stride", 0), interpret=True)


def out_and_grads(form, x, norm, rope, ct):
    def loss(x, norm):
        out = form(x, norm, rope)
        return (out.astype(jnp.float32) * ct).sum(), out

    (_, out), (dx, dnorm) = jax.value_and_grad(loss, (0, 1), has_aux=True)(
        x, norm)
    return {"out": out, "dx": dx,
            **{f"d{k}": v for k, v in (dnorm or {}).items()}}


def rel(a, b):
    a, b = (np.asarray(v, np.float32) for v in (a, b))
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("dtype", list(POLICIES))
def test_outputs_and_gradients_against_xla(dtype, kind):
    """float32: XLA's operations to rounding. bfloat16: the kernels
    round where XLA's operations round or at fewer places, so against
    the float32 function of the same bfloat16 ``x`` they are no further
    off."""
    kind = KINDS[kind]
    x, norm, rope, ct = operands(kind, jnp.dtype(dtype))
    xla = out_and_grads(xla_form(kind, POLICIES[dtype]), x, norm, rope, ct)
    got = out_and_grads(fused_form(kind), x, norm, rope, ct)
    assert sorted(got) == sorted(xla)
    exact = xla if dtype == "float32" else out_and_grads(
        xla_form(kind, FP32), x.astype(jnp.float32), norm, rope, ct)
    for name in xla:
        assert got[name].shape == xla[name].shape
        assert got[name].dtype == xla[name].dtype
        assert rel(got[name], exact[name]) <= (
            ROUNDING if dtype == "float32"
            else 1.05 * rel(xla[name], exact[name]) + 1e-6), name
    if x.shape[-1] > got["out"].shape[-1]:   # nothing beside the heads
        beside = np.asarray(got["dx"], np.float32) * (
            1 - np.asarray(jax.grad(lambda x: cut(x, kind).sum())(x),
                           np.float32))
        assert not beside.any()


def test_a_row_of_several_tiles_and_one_no_tile_divides():
    """150 positions in tiles of 32: the scale's gradient adds up over
    five grid steps a row, the last tile padded."""
    kind = KINDS["zero_centred_first_64_of_256"]
    x, norm, rope, ct = operands(kind, jnp.float32, seq=150, rows=1)
    want = out_and_grads(xla_form(kind, FP32), x, norm, rope, ct)
    got = out_and_grads(fused_form(kind), x, norm, rope, ct)
    for name in want:
        assert rel(got[name], want[name]) < ROUNDING, name


def test_the_transpose_is_exact_for_any_tables():
    """Tables whose halves differ (no rotation): the backward is still
    the forward's transpose, as autodiff's of ``rope_apply`` is."""
    kind = KINDS["last_64_of_256"]
    x, norm, _, ct = operands(kind, jnp.float32)
    rope = tuple(jax.random.normal(jax.random.PRNGKey(n), (SEQ, 64))
                 for n in (5, 6))
    want = out_and_grads(xla_form(kind, FP32), x, norm, rope, ct)
    got = out_and_grads(fused_form(kind), x, norm, rope, ct)
    for name in want:
        assert rel(got[name], want[name]) < ROUNDING, name


# --- which form a call takes -------------------------------------------------


def test_fits_reads_backend_mesh_dtype_and_shape(monkeypatch):
    x = jnp.zeros((2, 64, 1024), jnp.bfloat16)
    assert kernels.fits(x, 8, 128, 128) == "backend"    # the CPU tests' path
    monkeypatch.setattr(kernels, "_backend", lambda: "tpu")
    for heads, dim, rotated, offset, first, stride in [
            (8, 128, 128, 0, 0, 0), (8, 128, 0, 0, 0, 0),
            (4, 256, 64, 0, 0, 0), (4, 256, 64, 192, 0, 0),
            (4, 256, 256, 0, 0, 0), (2, 128, 128, 0, 512, 0),
            (2, 256, 64, 0, 0, 512), (3, 128, 128, 0, 384, 0)]:
        assert kernels.fits(x, heads, dim, rotated, offset, first,
                            stride) == "", (heads, dim, rotated, offset)
    assert kernels.fits(x.astype(jnp.float16), 8, 128, 128) == "dtype"
    for why, call in {
            "a head of 64": lambda: kernels.fits(x, 16, 64, 64),
            "one head of 64 of a wider product": lambda: kernels.fits(
                jnp.zeros((2, 64, 576)), 1, 64, 64, 0, 512),
            "a head of 192": lambda: kernels.fits(x[..., :768], 4, 192, 64),
            "runs past x": lambda: kernels.fits(x, 8, 128, 128, 0, 128),
            "starts inside a head": lambda: kernels.fits(
                x, 4, 128, 128, 0, 64),
            "across two vectors of lanes": lambda: kernels.fits(
                x, 4, 256, 128, 64),
            "past the head": lambda: kernels.fits(x, 4, 256, 128, 192),
            "an odd number turned": lambda: kernels.fits(x, 4, 256, 63),
            "heads that overlap": lambda: kernels.fits(
                x, 4, 256, 64, 0, 0, 128),
            "a stride of no whole head": lambda: kernels.fits(
                x, 2, 256, 64, 0, 0, 384),
    }.items():
        assert call() == "shape", why

    sharded = jax.device_put(x, jax.NamedSharding(
        make_mesh(2), jax.sharding.PartitionSpec("data")))
    seen = []
    jit_once(lambda x: seen.append(kernels.fits(x, 8, 128, 128)) or x)(
        sharded)
    assert seen == ["mesh"]


def site(x, heads, **kwargs):
    with kernels.rotary_paths.counting() as counts:
        out = kernels.head_norm_rotary(x, heads, eps=EPS, **kwargs)
    (label,) = counts
    return out, label


def test_head_norm_rotary_hands_back_the_same_either_way(monkeypatch):
    """The function the layers call, on the kernels as on XLA's
    operations, and the tally says which ran and on what."""
    kind = KINDS["norm_rot_4x128"]
    x, norm, rope, _ = operands(kind, jnp.float32)
    xla, label = site(x, 4, norm=norm, rope=rope, policy=FP32)
    assert label == "xla[4x128 norm+rot128, backend]"
    np.testing.assert_array_equal(
        xla, parent_form(x, 4, norm, rope, 0, FP32))
    monkeypatch.setattr(kernels, "_backend", lambda: "tpu")
    fused, label = site(x, 4, norm=norm, rope=rope, policy=FP32)
    assert label == "fused[4x128 norm+rot128]"
    assert rel(fused, xla) < ROUNDING
    # ... told where a caller's slice was cut from, reads it there
    wide = jnp.concatenate([x * 3, x, x[..., :128]], axis=-1)
    there, label = site(wide[..., 512:1024], 4, norm=norm, rope=rope,
                        policy=FP32, cut_from=(wide, 512, 0))
    assert label == "fused[4x128 norm+rot128]"
    np.testing.assert_array_equal(there, fused)
    for kwargs, want in [
            (dict(rope=rope), "fused[4x128 rot128]"),
            (dict(norm=norm, policy=FP32), "fused[4x128 norm]"),
            # a policy whose norm would hand back another dtype than x's
            (dict(norm=norm, rope=rope, policy=BF16),
             "xla[4x128 norm+rot128, dtype]"),
            (dict(rope=tuple(t[:SEQ - 1] for t in rope)),   # too few rows
             "xla[4x128 rot128, shape]")]:
        if want.endswith("shape]"):
            with pytest.raises((TypeError, ValueError)):   # XLA's to refuse
                site(x, 4, **kwargs)
            continue
        _, label = site(x, 4, **kwargs)
        assert label == want
    last = KINDS["last_64_of_256"]
    x, _, rope, _ = operands(last, jnp.float32)
    fused, label = site(x, 2, rope=rope, offset=192)
    assert label == "fused[2x256 rot64@192]"
    assert rel(fused, parent_form(x, 2, None, rope, 192, FP32)) < ROUNDING
    shared, label = site(x[..., :64], 1, rope=rope)
    assert label == "xla[1x64 rot64, shape]"
    np.testing.assert_array_equal(shared, rope_apply(x[..., :64], *rope, 1))


# --- where fits says no, the sites are the parent's programs -----------------

SITES = {
    "norm_rot": dict(heads=4, dim=16, rot=16, norm="scale"),
    "zero_centred_partial": dict(heads=4, dim=16, rot=4, norm="bias"),
    "rotation_alone": dict(heads=4, dim=16, rot=16),
    "latent_queries_tail": dict(heads=4, dim=12, rot=4, offset=8),
    "shared_key": dict(heads=1, dim=4, rot=4),
    # heads of whole lanes: on a TPU the mesh alone says no
    "norm_rot_2x128": dict(heads=2, dim=128, rot=128, norm="scale"),
}


@pytest.mark.parametrize("where", ["cpu_backend", "two_device_mesh"])
@pytest.mark.parametrize("name", list(SITES))
def test_where_fits_says_no_the_sites_text_is_the_parents(monkeypatch, name,
                                                          where):
    """``rope_apply(head_rms_norm(...))`` to the letter: the jaxpr and
    the lowered text."""
    kind = SITES[name]
    x, norm, rope, _ = operands(kind, jnp.float32, rows=2)
    offset = kind.get("offset", 0)
    if where == "two_device_mesh":
        # as a TPU would see it: the mesh is what says no
        monkeypatch.setattr(kernels, "_backend", lambda: "tpu")
        x = jax.device_put(x, jax.NamedSharding(
            make_mesh(2), jax.sharding.PartitionSpec("data")))

    def ours(x, norm):
        return kernels.head_norm_rotary(
            x, kind["heads"], norm=norm, eps=EPS, rope=rope, offset=offset,
            policy=FP32)

    def parents(x, norm):
        return parent_form(x, kind["heads"], norm, rope, offset, FP32)

    with kernels.rotary_paths.counting() as counts:
        text = jit_once(ours).lower(x, norm).as_text()
    (label,) = counts
    assert label.startswith("xla[") and label.endswith(
        ", mesh]" if where == "two_device_mesh" else ", backend]")
    assert "tpu_custom_call" not in text
    assert text == jit_once(parents).lower(x, norm).as_text().replace(
        "jit_parents", "jit_ours")
    assert str(jax.make_jaxpr(ours)(x, norm)) == str(
        jax.make_jaxpr(parents)(x, norm))


# --- the layers' call sites, on the kernels ----------------------------------


def attention_layer(name):
    """``(apply(params, a), params, a)`` of a layer at heads of whole
    lanes: what each site hands the function, the wider arrays too."""
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    a = jax.random.normal(keys[0], (2, SEQ, 64))
    if name == "packed_self_attention":    # the looped LM's: q, k of [q k v]
        params = attention.mha_init(keys[1], 256, 2, bias=False)
        a = jax.random.normal(keys[0], (2, SEQ, 256))
        rope = rope_tables(SEQ, 128, 1e4)
        return (lambda p, a: attention.mha_apply(
            p, a, a, a, num_heads=2, causal=True, rope=rope, policy=FP32),
            params, a)
    if name == "grouped_queries_beside_gates":   # q's norm a zero-centred
        params = hybrid_lm.gqa_init(       # scale, a quarter of a head turned
            keys[1], 64, 2, 1, 128, qk_norm=True, output_gate=True,
            zero_centered=True)
        params = jax.tree.map(
            lambda x: x + 0.1 * jax.random.normal(keys[2], x.shape), params)
        rope = rope_tables(SEQ, 32, 1e4)
        return (lambda p, a: hybrid_lm.rotary_gqa_apply(
            p, a, num_heads=2, num_kv_heads=1, rope=rope, norm_eps=EPS,
            output_gate=True, policy=FP32), params, a)
    params = hybrid_lm.mla_init(          # latent attention, a query latent
        keys[1], 64, 2, kv_lora_rank=32, qk_nope_head_dim=96,
        qk_rope_head_dim=32, v_head_dim=64, q_lora_rank=48)
    rope = rope_tables(SEQ, 32, 1e4)
    return (lambda p, a: hybrid_lm.mla_apply(
        p, a, num_heads=2, kv_lora_rank=32, qk_nope_head_dim=96,
        norm_eps=EPS, rope=rope, policy=FP32), params, a)


LAYERS = {
    "packed_self_attention": {"fused[2x128 rot128]": 2},
    "grouped_queries_beside_gates": {"fused[1x128 norm+rot32]": 1,
                                     "fused[2x128 norm+rot32]": 1},
    "latent_attention": {"fused[2x128 rot32@96]": 1,
                         "xla[1x32 rot32, shape]": 1},
}


@pytest.mark.parametrize("name", list(LAYERS))
def test_a_layer_on_the_kernels_is_the_layer_on_xla(monkeypatch, name):
    """Outputs and every gradient, float32 to rounding; the tally names
    what each site took."""
    apply, params, a = attention_layer(name)
    ct = jax.random.normal(jax.random.PRNGKey(9), apply(params, a).shape)

    def both():
        with kernels.rotary_paths.counting() as counts:
            (_, out), grads = jit_once(jax.value_and_grad(
                lambda p, a: ((apply(p, a) * ct).sum(), apply(p, a)),
                (0, 1), has_aux=True))(params, a)
        return dict(counts), out, grads

    labels, want, want_grads = both()
    assert all(label.startswith("xla[") for label in labels)
    monkeypatch.setattr(kernels, "_backend", lambda: "tpu")
    labels, got, got_grads = both()
    assert {k: v // 2 for k, v in labels.items()} == LAYERS[name]
    assert rel(got, want) < ROUNDING
    for (path, g), w in zip(jax.tree.leaves_with_path(got_grads),
                            jax.tree.leaves(want_grads)):
        assert rel(g, w) < 10 * ROUNDING, jax.tree_util.keystr(path)


# --- the stacks with neither never reach it ----------------------------------

#: the toys of ``tests/test_hybrid_lm.py`` (``nemotron_h``:
#: ``gqa_apply``, no position embedding, no q/k norm) and
#: ``tests/test_kimi_linear_lm.py`` (latent attention without positions
#: or a query latent)
BYPASS = {
    "nemotron_h": dict(
        vocab_size=256, hidden_size=48, hybrid_override_pattern="MEM*E",
        mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
        conv_kernel=4, chunk_size=16, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, n_routed_experts=16,
        num_experts_per_tok=3, moe_intermediate_size=40,
        moe_shared_expert_intermediate_size=80, routed_scaling_factor=2.5,
        norm_eps=1e-5, max_seq_len=40, held_experts=4, first_expert=4,
        ce_chunk_size=64),
    "kimi_linear": dict(
        vocab_size=256, hidden_size=48, hybrid_override_pattern="KDKEAE",
        kda_num_heads=4, kda_head_dim=8, kda_conv_kernel_size=4,
        delta_chunk_size=16, num_attention_heads=4, num_key_value_heads=4,
        head_dim=12, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, intermediate_size=64,
        n_routed_experts=32, num_experts_per_tok=4,
        moe_intermediate_size=24, moe_shared_expert_intermediate_size=24,
        routed_scaling_factor=2.446, router_scoring="sigmoid",
        norm_topk_prob=True, gated_experts=True, shared_expert_kind="glu",
        norm_eps=1e-5, max_seq_len=40, held_experts=4, first_expert=8,
        ce_chunk_size=64),
}


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("name", list(BYPASS))
def test_a_stack_with_neither_never_reaches_the_function(monkeypatch, name,
                                                         backend):
    """The step's trace, forward and backward, leaves the tally empty:
    not ``xla[...]`` but nothing, whatever the backend says."""
    monkeypatch.setattr(kernels, "_backend", lambda: backend)
    task = HybridLMTask(**BYPASS[name])
    model = task.build()
    params = jax.eval_shape(model.init, jax.random.key(0))
    batch = {"input_ids": jax.ShapeDtypeStruct((2, 40), jnp.int32)}
    with kernels.rotary_paths.counting() as counts:
        jax.eval_shape(jax.grad(lambda p, b: task.loss_and_metrics(
            model, p, b, policy=FP32)[0]), params, batch)
    assert not counts
