"""Mosaic compile-legality net: the Pallas kernels, compiled for a v5e
that is described and not attached (no TPU needed).

The local libtpu AOT-compiles for a ``jax.experimental.topologies``
target, so the chip's own compiler checks block/tile legality and
scoped-VMEM use at test time — what interpreter-mode tests cannot see.
Every case passes ``interpret=False`` itself; nothing here runs.

The topology is described inside a module-scoped fixture, in this
process and only once a test of this file has started: only one process
may hold the TPU library, so nothing may touch ``topologies`` while a
module is imported or collected (every xdist worker imports every test
file). Keep all such compiles in this one file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.filterwarnings("ignore")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no local libtpu build
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _compiles_to_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # Mosaic kernel, not interpreter HLO


def _struct(sharding):
    return functools.partial(jax.ShapeDtypeStruct, sharding=sharding)


# --- flash attention ---------------------------------------------------------


@pytest.mark.parametrize("lq,lk,heads,dim,bias", [
    (512, 512, 8, 64, False),   # two heads a 128-lane block
    (512, 512, 8, 16, True),    # eight heads a block, bias row
    (512, 512, 4, 16, True),    # a block does not divide 4: lane-padded
], ids=["d64", "d16_bias", "d16_padded_bias"])
def test_flash_forward_compiles(one_chip, lq, lk, heads, dim, bias):
    from perceiver_tpu.ops.pallas_attention import (
        flash_attention_channels as flash_attention,
    )

    s = _struct(one_chip)
    q = s((2, lq, heads * dim), jnp.bfloat16)
    k = s((2, lk, heads * dim), jnp.bfloat16)
    if bias:
        _compiles_to_mosaic(
            lambda q, k, v, b: flash_attention(q, k, v, num_heads=heads,
                                               bias=b, interpret=False),
            q, k, k, s((2, lk), jnp.float32))
    else:
        _compiles_to_mosaic(
            lambda q, k, v: flash_attention(q, k, v, num_heads=heads,
                                            interpret=False),
            q, k, k)


@pytest.mark.parametrize("lq,lk,heads,dim,bias", [
    (1024, 2048, 8, 64, True), (1024, 1024, 8, 64, False),
    (2048, 1024, 8, 64, False), (512, 50176, 4, 128, False),
    (512, 512, 4, 128, False), (512, 2048, 8, 16, True)],
    ids=["lm_encoder_cross", "lm_latent_self", "lm_decoder_cross",
         "img_encoder_cross", "img_latent_self", "eight_heads_of_16"])
def test_flash_forward_backward_compiles_at_cell_shapes(one_chip, lq, lk,
                                                        heads, dim, bias):
    """The attention shapes of the benchmark's two configurations
    (Perceiver-LM: 1024 latents, seq 2048, 8 heads of 64; the image
    classifier: 512 latents over 50,176 pixels, 4 heads of 128), with
    the blocks the code picks for them, forward and backward kernels,
    on (B, L, H·D) operands as the model hands them over."""
    from perceiver_tpu.ops.pallas_attention import (
        flash_attention_channels,
    )

    s = _struct(one_chip)
    q = s((2, lq, heads * dim), jnp.bfloat16)
    k = s((2, lk, heads * dim), jnp.bfloat16)
    args = (q, k, k) + ((s((2, lk), jnp.float32),) if bias else ())

    def loss(q, k, v, *b):
        return flash_attention_channels(
            q, k, v, num_heads=heads, bias=b[0] if b else None,
            interpret=False).astype(jnp.float32).sum()

    # the value keeps the forward kernel live beside the backward pass
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("rows,half,block,heads,dim", [
    (2, 4096, 4, 32, 128),    # sdar_train: 1024 x 1024 tiles, 24 of 64 run
    (1, 1000, 4, 8, 64),      # the halves meet inside a tile, two heads a block
    (1, 1024, 32, 4, 128),    # blocks of 32
], ids=["sdar_train", "ragged_d64", "blocks_of_32"])
def test_block_diffusion_forward_backward_compiles(one_chip, rows, half,
                                                   block, heads, dim):
    """The block-diffusion mode (the tiles' kinds and the tiles to hold
    prefetched to scalar memory, the masked tiles' mask from a column
    of query numbers against a row of key numbers) on the chip's own
    compiler, under its own kernel names."""
    from perceiver_tpu.ops.pallas_attention import (
        flash_attention_channels as flash_attention,
    )

    def loss(q, k, v):
        return flash_attention(q, k, v, num_heads=heads,
                               block_diffusion=(half, block),
                               interpret=False).astype(jnp.float32).sum()

    q = _struct(one_chip)((rows, 2 * half, heads * dim), jnp.bfloat16)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "block_diffusion_attention_fwd" in text
    assert "block_diffusion_attention_bwd" in text
    assert "causal_attention" not in text


@pytest.mark.parametrize("rows,seq,heads,dim", [
    (2, 4096, 16, 128),     # ouro_train: 1024 x 1024 blocks, 10 of 16 run
    (2, 1000, 8, 64),       # a padded last block, two heads a lane block
    (1, 2048, 4, 128),      # streamed where the full kernels take one block
    (4, 4096, 16, 256),     # qwen3next_train: heads of 256, two lane blocks
], ids=["ouro_train", "ragged_d64", "two_blocks", "qwen3next_train"])
def test_causal_forward_backward_compiles(one_chip, rows, seq, heads, dim):
    """The causal mode (blocks above the diagonal skipped by clamped
    index maps and ``pl.when``, the crossed ones masked from iotas) on
    the chip's own compiler, under its own kernel names."""
    from perceiver_tpu.ops.pallas_attention import (
        flash_attention_channels as flash_attention,
    )

    def loss(q, k, v):
        return flash_attention(q, k, v, num_heads=heads, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    x = _struct(one_chip)((rows, seq, heads * dim), jnp.bfloat16)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert "tpu_custom_call" in text
    assert "causal_attention_fwd" in text and "causal_attention_bwd" in text
    assert "flash_attention_fwd" not in text


# --- the selective scan ------------------------------------------------------


@pytest.mark.parametrize("rows,seq,heads,dim,groups,state,chunk", [
    (4, 4096, 64, 64, 8, 128, 128),   # nemotron_train: two heads a slab
    (1, 512, 8, 16, 1, 128, 128),     # eight heads share one slab
    (1, 512, 8, 128, 1, 256, 256),    # a slab a head, chunks of 256
], ids=["nemotron_train", "eight_heads_of_16", "heads_of_128"])
def test_ssm_scan_forward_backward_compiles(one_chip, rows, seq, heads, dim,
                                            groups, state, chunk):
    """The fused scan's three kernels (forward; the backward's states
    pass and its reversed pass) at the cell's shapes (4 x 4,096, 64
    heads of 64, 8 groups of state 128, chunks of 128) and at the other
    two ways the heads lie on the lanes."""
    from perceiver_tpu.ops.pallas_ssm import fused_scan

    def loss(*args):
        return fused_scan(*args, chunk=chunk,
                          interpret=False).astype(jnp.float32).sum()

    s = _struct(one_chip)
    bc = s((rows, seq, groups, state), jnp.bfloat16)
    # the value keeps the forward kernel live beside the backward pass
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        s((rows, seq, heads, dim), jnp.bfloat16),
        s((rows, seq, heads), jnp.float32), s((heads,), jnp.float32),
        bc, bc).compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    for name in ("ssm_scan_fwd", "ssm_scan_bwd_states", "ssm_scan_bwd"):
        assert name in text


def test_ssm_scan_kernels_lie_under_the_scans_scope(one_chip, monkeypatch):
    """Picked as the trainer's step picks it (``ops.ssm.ssm_scan`` on a
    TPU), all three kernels carry the scope ``ssm_scan`` in their name
    stacks, the backward's two under ``transpose(``: what
    ``ssm_scan_roofline``, ``model.ssm_pct`` and the pass split read."""
    import re

    import perceiver_tpu.utils.platform as platform
    from perceiver_tpu.ops import ssm

    monkeypatch.setattr(ssm, "_backend", lambda: "tpu")
    monkeypatch.setattr(platform, "default_interpret", lambda: False)

    def loss(*args):
        return ssm.ssm_scan(*args, chunk_size=128).astype(jnp.float32).sum()

    s = _struct(one_chip)
    bc = s((1, 512, 1, 128), jnp.bfloat16)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        s((1, 512, 8, 16), jnp.bfloat16), s((1, 512, 8), jnp.float32),
        s((8,), jnp.float32), bc, bc).compile().as_text()
    stacks = [re.search(r'op_name="([^"]*)"', line).group(1)
              for line in text.splitlines()
              if "tpu_custom_call" in line and "custom-call(" in line]
    assert sorted(stacks) == [
        "jit(loss)/jvp(ssm_scan)/ssm_scan_fwd/pallas_call",
        "jit(loss)/transpose(jvp(ssm_scan))/ssm_scan_bwd/pallas_call",
        "jit(loss)/transpose(jvp(ssm_scan))/ssm_scan_bwd_states/pallas_call"]


# --- the gated delta rule ----------------------------------------------------


@pytest.mark.parametrize("rows,seq,key_heads,heads,dim,chunk,dtype", [
    (4, 4096, 16, 32, 128, 64, jnp.bfloat16),   # qwen3next_train
    (1, 256, 2, 2, 128, 16, jnp.bfloat16),      # one block of the inverse
    (1, 256, 1, 2, 128, 16, jnp.bfloat16),      # two heads on 32 lanes
    (1, 256, 1, 4, 128, 32, jnp.bfloat16),      # four heads side by side
    (1, 256, 1, 4, 256, 128, jnp.float32),      # wide heads, float32
], ids=["qwen3next_train", "chunks_of_16", "two_heads_of_16_lanes",
        "four_heads_a_tile", "float32_heads_of_256"])
def test_delta_rule_forward_backward_compiles(one_chip, rows, seq, key_heads,
                                              heads, dim, chunk, dtype):
    """The delta rule's three kernels (forward; the backward's states
    pass and its reversed pass) at the cell's shapes (4 x 4,096, 16 key
    and 32 value heads of 128, chunks of 64, bf16) and at the edges of
    what ``fits`` lets through."""
    from perceiver_tpu.ops.pallas_delta_rule import fused_rule

    def loss(*args):
        return fused_rule(*args, chunk=chunk,
                          interpret=False).astype(jnp.float32).sum()

    s = _struct(one_chip)
    qk = s((rows, seq, key_heads, dim), dtype)
    gb = s((rows, seq, heads), jnp.float32)
    # the value keeps the forward kernel live beside the backward pass
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        qk, qk, s((rows, seq, heads, dim), dtype), gb, gb
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    for name in ("delta_rule_fwd", "delta_rule_bwd_states",
                 "delta_rule_bwd"):
        assert name in text


def test_delta_rule_kernels_lie_under_the_rules_scope(one_chip, monkeypatch):
    """Picked as the trainer's step picks it (``ops.delta_rule
    .delta_rule`` at shapes that tile), all three kernels carry the
    scope ``delta_rule`` in their name stacks, the backward's two under
    ``transpose(``: what ``delta_rule_roofline``,
    ``model.delta_rule_pct`` and the pass split read."""
    import re

    import perceiver_tpu.utils.platform as platform
    from perceiver_tpu.ops import delta_rule

    monkeypatch.setattr(platform, "default_interpret", lambda: False)

    def loss(*args):
        return delta_rule.delta_rule(
            *args, chunk_size=64).astype(jnp.float32).sum()

    s = _struct(one_chip)
    qk = s((1, 256, 1, 128), jnp.bfloat16)
    gb = s((1, 256, 2), jnp.float32)
    with delta_rule.rule_paths.counting() as forms:
        text = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
                qk, qk, s((1, 256, 2, 128), jnp.bfloat16), gb, gb
            ).compile().as_text()
    assert dict(forms) == {"kernel[64x4]": 1}
    stacks = [re.search(r'op_name="([^"]*)"', line).group(1)
              for line in text.splitlines()
              if "tpu_custom_call" in line and "custom-call(" in line]
    assert sorted(stacks) == [
        "jit(loss)/jvp(delta_rule)/delta_rule_fwd/pallas_call",
        "jit(loss)/transpose(jvp(delta_rule))/delta_rule_bwd/pallas_call",
        "jit(loss)/transpose(jvp(delta_rule))/delta_rule_bwd_states/"
        "pallas_call"]


@pytest.mark.parametrize("rows,seq,heads,dim,chunk,dtype", [
    (4, 4096, 32, 128, 64, jnp.bfloat16),    # kimi_linear_train
    (1, 256, 2, 128, 16, jnp.bfloat16),      # one sub-block a chunk
    (1, 256, 1, 128, 32, jnp.bfloat16),      # two: one product before
    (1, 256, 1, 256, 128, jnp.float32),      # eight; wide heads, float32
], ids=["kimi_linear_train", "chunks_of_16", "chunks_of_32",
        "float32_heads_of_256"])
def test_kda_rule_forward_backward_compiles(one_chip, rows, seq, heads, dim,
                                            chunk, dtype):
    """The vector-decay rule's three kernels (forward; the backward's
    states pass and its reversed pass) at the cell's shapes (4 x 4,096,
    32 heads of 128, chunks of 64, bf16, ``g`` a number a head and key
    channel) and at the edges of what ``fits`` lets through."""
    from perceiver_tpu.ops.pallas_kda_rule import fused_rule

    def loss(*args):
        return fused_rule(*args, chunk=chunk,
                          interpret=False).astype(jnp.float32).sum()

    s = _struct(one_chip)
    qkv = s((rows, seq, heads, dim), dtype)
    # the value keeps the forward kernel live beside the backward pass
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        qkv, qkv, qkv, s((rows, seq, heads, dim), jnp.float32),
        s((rows, seq, heads), jnp.float32)).compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    for name in ("kda_rule_fwd", "kda_rule_bwd_states", "kda_rule_bwd"):
        assert name in text


def test_kda_rule_kernels_lie_under_the_rules_scope(one_chip, monkeypatch):
    """Picked as the trainer's step picks it (``ops.delta_rule
    .delta_rule`` with ``g`` of (B, S, H, Dk) at shapes that tile), all
    three kernels carry the scope ``kda_rule`` in their name stacks,
    the backward's two under ``transpose(``: what
    ``kda_rule_roofline``, ``model.kda_rule_pct`` and the pass split
    read."""
    import re

    import perceiver_tpu.utils.platform as platform
    from perceiver_tpu.ops import delta_rule

    monkeypatch.setattr(platform, "default_interpret", lambda: False)

    def loss(*args):
        return delta_rule.delta_rule(
            *args, chunk_size=64).astype(jnp.float32).sum()

    s = _struct(one_chip)
    qkv = s((1, 256, 2, 128), jnp.bfloat16)
    with delta_rule.rule_paths.counting() as forms:
        text = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
                qkv, qkv, qkv, s((1, 256, 2, 128), jnp.float32),
                s((1, 256, 2), jnp.float32)).compile().as_text()
    assert dict(forms) == {"kernel[64x4, by channel]": 1}
    stacks = [re.search(r'op_name="([^"]*)"', line).group(1)
              for line in text.splitlines()
              if "tpu_custom_call" in line and "custom-call(" in line]
    # (each direction a jitted function: its kernels are traced and
    # lowered once for all the call sites of a step)
    assert sorted(stacks) == [
        "jit(loss)/jvp(kda_rule)/jit(_rule_forward)/kda_rule_fwd/"
        "pallas_call",
        "jit(loss)/transpose(jvp(kda_rule))/jit(_rule_backward)/"
        "kda_rule_bwd/pallas_call",
        "jit(loss)/transpose(jvp(kda_rule))/jit(_rule_backward)/"
        "kda_rule_bwd_states/pallas_call"]


# --- the mixers' short convolution -------------------------------------------


@pytest.mark.parametrize("wide,first,channels,head,scaled,normed,bias", [
    (4096, 0, 4096, 128, 4096, 0, False),      # a KDA layer's q alone
    (4096, 0, 4096, 0, 0, 0, False),           # ... and its v
    (12288, 0, 12288, 128, 4096, 4096, False),  # kimi_linear_train: q, k, v
    (12288, 0, 8192, 128, 2048, 2048, False),  # qwen3next_train: of [q k v z]
    (10304, 4096, 6144, 0, 0, 0, True),        # nemotron_train: of [z xBC dt]
], ids=["kda_q", "kda_v", "kda_qkv", "delta_qkv_of_qkvz", "ssm_xbc_of_zxbcdt"])
def test_short_conv_forward_backward_compiles(one_chip, wide, first, channels,
                                              head, scaled, normed, bias):
    """The short convolution's two kernels at the three mixer cells'
    shapes (4 x 4,096 positions, bf16; a call a part, each reading its
    channels where they lie in the projection's product), and the VMEM
    each asks for (the custom call's own scoped size, after the limit it
    was allowed)."""
    import re

    from perceiver_tpu.ops.pallas_short_conv import fused_short_conv

    def loss(params, x):
        return sum(part.astype(jnp.float32).sum() for part in fused_short_conv(
            params, x, head_dim=head, scaled=scaled, normed=normed,
            first=first, interpret=False))

    s = _struct(one_chip)
    params = {"w": s((4, channels), jnp.float32)}
    if bias:
        params["bias"] = s((channels,), jnp.float32)
    # the value keeps the forward kernels live beside the backward pass
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, s((4, 4096, wide), jnp.bfloat16)).compile().as_text()
    asked, limit = {}, 64 * 2**20
    for line in text.splitlines():
        if "tpu_custom_call" in line and "custom-call(" in line:
            name = re.search(r"short_conv_(fwd|bwd)", line).group(0)
            # two scoped sizes a call: the limit it was allowed, and what
            # it asks (laid after the limit where XLA keeps an operand in
            # the same memory)
            asked.setdefault(name, []).append(min(
                int(n) % limit or limit for n in re.findall(
                    r'\\?"size\\?":\\?"(\d+)', line)))
    print(f"short_conv {channels}ch of {wide}: VMEM asked "
          + ", ".join(f"{k} {max(v) / 2**20:.2f} MiB x{len(v)}"
                      for k, v in sorted(asked.items())))
    parts = bool(scaled) + bool(normed) + (channels > scaled + normed)
    assert {k: len(v) for k, v in asked.items()} == {
        "short_conv_bwd": parts, "short_conv_fwd": parts}
    # far under a v5e's 128 MiB, and under the 16 MiB a call gets unasked
    assert max(max(v) for v in asked.values()) < 16 * 2**20


def test_short_conv_kernels_lie_under_the_mixers_scope(one_chip, monkeypatch):
    """Picked as the trainer's step picks it (``short_conv`` from a
    mixer on a TPU), both kernels carry the mixer's scope in their name
    stacks, the backward's under ``transpose(``: what
    ``model.delta_mixer_pct`` and the pass split read; each direction a
    jitted function, traced once for a step's call sites."""
    import re

    import perceiver_tpu.utils.platform as platform
    from perceiver_tpu.ops import delta_rule, pallas_short_conv
    from perceiver_tpu.ops.policy import Policy

    monkeypatch.setattr(pallas_short_conv, "_backend", lambda: "tpu")
    monkeypatch.setattr(platform, "default_interpret", lambda: False)
    sizes = dict(num_key_heads=1, num_value_heads=2, key_head_dim=128,
                 value_head_dim=128)
    params = jax.eval_shape(lambda: delta_rule.delta_mixer_init(
        jax.random.key(0), 256, **sizes))
    s = _struct(one_chip)

    def loss(params, u):
        return delta_rule.delta_mixer_apply(
            params, u, policy=Policy.bf16(), **sizes).astype(
                jnp.float32).sum()

    with pallas_short_conv.conv_paths.counting() as forms:
        text = jax.jit(jax.grad(loss)).lower(
            jax.tree.map(lambda x: s(x.shape, x.dtype), params),
            s((1, 256, 256), jnp.bfloat16)).compile().as_text()
    assert dict(forms) == {"fused[512ch, norm 256]": 1}
    stacks = [re.search(r'op_name="([^"]*)"', line).group(1)
              for line in text.splitlines()
              if "tpu_custom_call" in line and "custom-call(" in line]
    # a call a part: q, k and v
    assert sorted(s for s in stacks if "short_conv" in s) == [
        "jit(loss)/jvp(delta_mixer)/jit(_conv_forward)/short_conv_fwd/"
        "pallas_call"] * 3 + [
        "jit(loss)/transpose(jvp(delta_mixer))/jit(_conv_backward)/"
        "short_conv_bwd/pallas_call"] * 3


# --- the head norm and rotary positions --------------------------------------


@pytest.mark.parametrize(
    "rows,seq,wide,heads,dim,rotated,offset,norm,first,stride", [
        (2, 8192, 4096, 32, 128, 128, 0, True, 0, 0),      # sdar_train: q
        (2, 8192, 512, 4, 128, 128, 0, True, 0, 0),        # ... and k
        (4, 4096, 8192, 16, 256, 64, 0, True, 0, 512),     # q of [q | gate]
        (2, 4096, 6144, 16, 128, 128, 0, False, 2048, 0),  # k of [q k v]
        (4, 4096, 5120, 20, 256, 64, 192, False, 0, 0),    # [nope | rope]
    ], ids=["sdar_q", "sdar_k", "qwen3next_q_beside_gate", "ouro_k_of_qkv",
            "glm_flash_q_tail"])
def test_head_rotary_forward_backward_compiles(
        one_chip, rows, seq, wide, heads, dim, rotated, offset, norm, first,
        stride):
    """The norm and rotation's two kernels at the attention cells'
    shapes (bf16, the heads read where they lie in the projection's
    product), one call each way."""
    from perceiver_tpu.ops.pallas_head_rotary import fused_head_rotary

    def loss(x, scale, cos, sin):
        return fused_head_rotary(
            x, heads, dim, scale=scale if norm else None, rope=(cos, sin),
            offset=offset, first=first, stride=stride,
            interpret=False).astype(jnp.float32).sum()

    s = _struct(one_chip)
    table = s((seq, rotated), jnp.float32)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        s((rows, seq, wide), jnp.bfloat16), s((dim,), jnp.float32), table,
        table).compile().as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line]
    assert sorted("fwd" if "head_rotary_fwd" in c else "bwd" if
                  "head_rotary_bwd" in c else c for c in calls) == [
                      "bwd", "fwd"]


def test_head_rotary_kernels_lie_under_the_layers_scope(one_chip,
                                                        monkeypatch):
    """Picked as the trainer's step picks it (``head_norm_rotary`` from
    ``rotary_gqa_apply`` on a TPU), both kernels carry ``attn_proj`` in
    their name stacks, the backward's under ``transpose(``: what
    ``scripts/scope_ops.py attn_proj`` and the pass split read; each
    direction a jitted function, traced once for a step's call sites."""
    import re

    import perceiver_tpu.utils.platform as platform
    from perceiver_tpu.models import hybrid_lm
    from perceiver_tpu.ops import pallas_head_rotary
    from perceiver_tpu.ops.fourier import rope_tables
    from perceiver_tpu.ops.policy import Policy

    monkeypatch.setattr(pallas_head_rotary, "_backend", lambda: "tpu")
    monkeypatch.setattr(platform, "default_interpret", lambda: False)
    params = jax.eval_shape(lambda: hybrid_lm.gqa_init(
        jax.random.key(0), 256, 2, 1, 128, qk_norm=True))
    rope = rope_tables(256, 128, 1e6)
    s = _struct(one_chip)

    def loss(params, a):
        return hybrid_lm.rotary_gqa_apply(
            params, a, num_heads=2, num_kv_heads=1, rope=rope,
            policy=Policy.bf16(), impl="einsum").astype(jnp.float32).sum()

    with pallas_head_rotary.rotary_paths.counting() as forms:
        text = jax.jit(jax.grad(loss)).lower(
            jax.tree.map(lambda x: s(x.shape, x.dtype), params),
            s((1, 256, 256), jnp.bfloat16)).compile().as_text()
    assert dict(forms) == {"fused[1x128 norm+rot128]": 1,
                           "fused[2x128 norm+rot128]": 1}
    stacks = [re.search(r'op_name="([^"]*)"', line).group(1)
              for line in text.splitlines()
              if "tpu_custom_call" in line and "custom-call(" in line]
    assert sorted(s for s in stacks if "head_rotary" in s) == [
        "jit(loss)/jvp(attn_proj)/jit(_rotary_forward)/head_rotary_fwd/"
        "pallas_call"] * 2 + [
        "jit(loss)/transpose(jvp(attn_proj))/jit(_rotary_backward)/"
        "head_rotary_bwd/pallas_call"] * 2


# --- fused projection + cross-entropy ----------------------------------------


@pytest.mark.parametrize("rows,channels,vocab", [
    (1024, 64, 10003), (1024, 128, 10003), (1280, 512, 32000)],
    ids=["c64_v10003", "c128_v10003", "c512_v32000"])
def test_pallas_ce_forward_backward_compiles(one_chip, rows, channels,
                                             vocab):
    """Every hidden width the repo ships a config for: the vocab tile
    follows C (a fixed 2048-column tile ran out of VMEM at C=512)."""
    from perceiver_tpu.ops.pallas_ce import pallas_linear_cross_entropy

    s = _struct(one_chip)
    lp = {"w": s((channels, vocab), jnp.float32),
          "b": s((vocab,), jnp.float32)}

    def loss(lp, h, y, wt):
        return pallas_linear_cross_entropy(lp, h, y, wt, interpret=False)

    _compiles_to_mosaic(
        jax.grad(loss, argnums=(0, 1)), lp,
        s((rows, channels), jnp.bfloat16), s((rows,), jnp.int32),
        s((rows,), jnp.float32))


def test_pallas_ce_refuses_a_width_with_no_tile():
    """A hidden width that leaves no 128-column tile raises the
    kernel's own shape error, before any compiler does."""
    from perceiver_tpu.ops.pallas_ce import pallas_linear_cross_entropy

    c = 8192
    lp = {"w": jnp.zeros((c, 256)), "b": jnp.zeros((256,))}
    with pytest.raises(ValueError, match="hidden width 8192"):
        jax.eval_shape(
            lambda h: pallas_linear_cross_entropy(
                lp, h, jnp.zeros((16,), jnp.int32), jnp.ones((16,))),
            jax.ShapeDtypeStruct((16, c), jnp.bfloat16))


# --- paged and ragged attention (decode and packed serve) --------------------


@pytest.mark.parametrize("heads,dim,nq,dtype,causal", [
    (8, 64, 1024, jnp.bfloat16, False),  # decode latent rebuild, LM width
    (8, 64, 256, jnp.bfloat16, True),
    (4, 16, 64, jnp.bfloat16, False),
    (4, 16, 8, jnp.float32, True),
], ids=["h8d64_nq1024", "h8d64_nq256_causal", "h4d16_nq64",
        "h4d16_nq8_f32_causal"])
def test_ragged_paged_attention_compiles(one_chip, heads, dim, nq, dtype,
                                         causal):
    from perceiver_tpu.ops.paged_attention import ragged_paged_attention

    s = _struct(one_chip)
    rows, page, pages_per_stream = 4, 16, 128
    pool = s((rows * pages_per_stream + 1, page, heads, dim), dtype)
    _compiles_to_mosaic(
        lambda q, k, v, t, kl, ql: ragged_paged_attention(
            q, k, v, t, kl, ql, causal=causal, interpret=False),
        s((rows, heads, nq, dim), dtype), pool, pool,
        s((rows, pages_per_stream), jnp.int32), s((rows,), jnp.int32),
        s((rows,), jnp.int32))


@pytest.mark.parametrize("heads,dim,latents,tokens,max_len", [
    (8, 64, 1024, 8192, 2048), (4, 16, 64, 1024, 512)],
    ids=["h8d64_n1024", "h4d16_n64"])
def test_ragged_cross_attention_compiles(one_chip, heads, dim, latents,
                                         tokens, max_len):
    from perceiver_tpu.ops.ragged_attention import ragged_cross_attention

    s = _struct(one_chip)
    rows = 8
    kv = s((heads, tokens, dim), jnp.bfloat16)
    _compiles_to_mosaic(
        lambda q, k, v, o, n: ragged_cross_attention(
            q, k, v, o, n, max_len=max_len, interpret=False),
        s((rows, heads, latents, dim), jnp.bfloat16), kv, kv,
        s((rows,), jnp.int32), s((rows,), jnp.int32))


@pytest.mark.parametrize("heads,dim,latents,tokens", [
    (8, 64, 1024, 8192), (4, 16, 64, 1024)],
    ids=["h8d64_n1024", "h4d16_n64"])
def test_ragged_decode_attention_compiles(one_chip, heads, dim, latents,
                                          tokens):
    from perceiver_tpu.ops.ragged_attention import ragged_decode_attention

    s = _struct(one_chip)
    rows = 8
    kv = s((heads, rows * latents, dim), jnp.bfloat16)
    _compiles_to_mosaic(
        lambda q, k, v, r: ragged_decode_attention(
            q, k, v, r, latents_per_row=latents, interpret=False),
        s((heads, tokens, dim), jnp.bfloat16), kv, kv,
        s((tokens,), jnp.int32))
