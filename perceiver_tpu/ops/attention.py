"""Multi-head attention as einsum over the MXU.

Re-expresses the reference's ``nn.MultiheadAttention`` wrapper
(``perceiver/model.py:59-74``) — including the asymmetric ``kdim``/
``vdim`` path used by cross-attention, ``key_padding_mask`` /
``attn_mask`` forwarding, and dropout on attention weights — as pure
einsum-based functions:

- q is projected from ``q_dim`` (the embedding dim), k from ``k_dim``,
  v from ``v_dim``, all to ``q_dim``; output projection maps back to
  ``q_dim``. This matches torch's separate q/k/v projection weights
  when ``kdim``/``vdim`` differ from ``embed_dim``.
- ``key_padding_mask`` is boolean ``(B, Lk)``, True at padding
  positions (reference ``data/imdb.py:64``); masked logits get a large
  negative additive bias before the fp32 softmax.
- Attention-weight dropout matches torch's placement (after softmax).

Cross-attention (``perceiver/model.py:77-99``) pre-norms both q and kv;
self-attention (``model.py:102-116``) pre-norms its single input. The
embedding dim equals the number of q channels — the reference's stated
simplification vs. the paper (``model.py:78-82``).

Two attention cores. The fused one (``ops/pallas_attention``: Pallas
kernels forward and backward, scores never in HBM) is taken wherever
the call and its shapes allow — ``pick_attention_core`` decides from
what it can observe; the materialised one (``_sdpa_core``: einsums XLA
tiles onto the MXU with scale/mask/softmax fused between them) covers
what the kernels do not: ``attn_mask``, attention-weight dropout,
small shapes, a mesh, every backend but a TPU.

``causal=True`` is a property of the call, not a mask handed in: the
fused kernels have a causal mode (blocks above the diagonal skipped),
so a causal call is picked like any other; the materialised core
builds the triangle itself. ``block_diffusion=(L, B)`` is the call's
property in the same way: a row's ``L`` noised positions beside their
``L`` clean ones in blocks of ``B`` (``block_diffusion_mask`` has the
rules); the fused kernels have that mode too, and the materialised core
takes the mask ``block_diffusion_mask`` builds. Projections without
biases (a tree with no ``b``), a per-head RMSNorm on the projected
queries and keys (a tree with ``q_norm`` and ``k_norm``) and rotary
positions (``rope=(cos, sin)``, applied after it) are what a decoder
stack adds to the same ``mha_apply``.
"""

from __future__ import annotations

import math
import warnings
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from perceiver_tpu.obs.trace import device_scope
from perceiver_tpu.ops.initializers import uniform, xavier_uniform
from perceiver_tpu.ops.linear import linear_init, linear_apply
from perceiver_tpu.ops.norm import (
    layer_norm_apply,
    layer_norm_init,
    rms_norm_apply,
)
from perceiver_tpu.ops.policy import Policy, DEFAULT_POLICY
from perceiver_tpu.ops.remat import dear
from perceiver_tpu.ops.tally import Tally, untallied  # noqa: F401
from perceiver_tpu.ops.tiling import round_up

NEG_INF = -1e30  # large-negative bias; safe in fp32 softmax accumulation


def mha_init(key, q_dim: int, num_heads: int,
             k_dim: Optional[int] = None, v_dim: Optional[int] = None,
             dtype=jnp.float32, bias: bool = True):
    """Init q/k/v/out projections (torch MultiheadAttention scheme).

    torch distinguishes the packed case: with ``kdim == vdim ==
    embed_dim`` it stores one ``in_proj_weight`` of shape (3E, E) and
    xavier-inits THAT (bound √(6/4E)); per-matrix xavier on each E×E
    slice would be √2 larger (VERDICT r3 weak #5). With asymmetric
    dims torch xavier-inits the three matrices separately — matching
    the per-matrix scheme below. ``bias=False`` leaves the four
    biases out of the tree (``linear_apply`` then adds none).
    """
    if q_dim % num_heads != 0:
        raise ValueError(f"q_dim {q_dim} not divisible by num_heads {num_heads}")
    k_dim = q_dim if k_dim is None else k_dim
    v_dim = q_dim if v_dim is None else v_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    out = linear_init(ko, q_dim, q_dim, dtype)
    if k_dim == q_dim and v_dim == q_dim:
        packed_bound = math.sqrt(6.0 / (q_dim + 3 * q_dim))

        def proj(k, shape):
            return uniform(k, shape, packed_bound, dtype)
    else:
        def proj(k, shape):
            return xavier_uniform(k, shape, dtype)
    params = {
        # torch: xavier-uniform projection weights, zero in-proj bias
        "q": {"w": proj(kq, (q_dim, q_dim)),
              "b": jnp.zeros((q_dim,), dtype)},
        "k": {"w": proj(kk, (k_dim, q_dim)),
              "b": jnp.zeros((q_dim,), dtype)},
        "v": {"w": proj(kv, (v_dim, q_dim)),
              "b": jnp.zeros((q_dim,), dtype)},
        "out": {"w": out["w"], "b": jnp.zeros((q_dim,), dtype)},
    }
    if not bias:
        params = {name: {"w": p["w"]} for name, p in params.items()}
    return params


def _split_heads(x, num_heads: int):
    b, l, e = x.shape
    return x.reshape(b, l, num_heads, e // num_heads)


def head_rms_norm(params, x, num_heads: int, eps: float,
                  policy: Policy = DEFAULT_POLICY):
    """RMSNorm over each head's channels of ``x`` (B, L, H·D), one
    scale of ``D`` for all the heads."""
    return rms_norm_apply(params, _split_heads(x, num_heads), eps,
                          policy).reshape(x.shape)


def block_diffusion_mask(half: int, block: int):
    """(2 L, 2 L) bool, True where query ``j`` does **not** see key
    ``l``, for a row of ``L = half`` noised positions beside their
    clean ones, in blocks of ``block`` positions::

        j <  L, l <  L:  sees where block(j) == block(l)
        j <  L, l >= L:  sees where block(l - L) <  block(j)
        j >= L, l >= L:  sees where block(l - L) <= block(j - L)
        j >= L, l <  L:  never

    The mask the materialized core takes, and what the fused kernels'
    own (``ops/pallas_attention._diffusion_mask``) is tested against."""
    index = jnp.arange(2 * half)
    noised = index < half
    blocks = jnp.where(noised, index, index - half) // block
    q_noised, k_noised = noised[:, None], noised[None, :]
    q_block, k_block = blocks[:, None], blocks[None, :]
    sees = jnp.where(
        q_noised,
        jnp.where(k_noised, q_block == k_block, k_block < q_block),
        ~k_noised & (k_block <= q_block))
    return ~sees


# --- materialized-softmax attention core (custom VJP) ------------------------
# The round-5 trace put ~37% of headline-step HBM bytes on the fp32
# [B, H, Lq, Lk] attention probabilities: autodiff saves the softmax
# output (and its bf16 copy feeding the PV dot) as residuals, and the
# encoder's nested lax.scans stack those residuals per layer — a
# 200-500 MB write + read-back per block on the B=512 step. This core
# saves ONLY (qh, kh, vh, bias, rng) and recomputes the probabilities
# in the backward pass — the FlashAttention memory trade expressed on
# the materialized path, where the recompute is two cheap fused
# passes instead of a stacked round trip through HBM. It also keeps
# every grad contraction on bf16 operands under the bf16 policy (the
# fp32 softmax cotangent used to drag the QK backward pair to the
# fp32 MXU rate — ~9% of step FLOPs, from the dot audit of
# analysis/hlo.py:dot_flop_summary).


def _sdpa_probs(scale, dropout_rate, stat_dtype, qh, kh, vh, bias, rng):
    """Post-dropout attention probabilities in ``stat_dtype`` (fp32
    statistics under the default policy). Deterministic in its inputs,
    so forward and backward recomputation agree bitwise — including
    the dropout mask, which is re-drawn from the same ``rng``.

    The softmax scale is folded into ``qh`` BEFORE the dot (the
    standard flash-kernel move): scaling the small (B, Lq, H, D) head
    tensor instead of the (B, H, Lq, Lk) logits drops a full
    logits-sized fp32 multiply + scalar broadcast per softmax
    evaluation — forward and both backward recomputes."""
    del vh
    qs = qh * jnp.asarray(scale, qh.dtype)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qs, kh,
                        preferred_element_type=stat_dtype)
    logits = logits.astype(stat_dtype)
    if bias is not None:
        logits = logits + bias
    probs = jax.nn.softmax(logits, axis=-1)
    if rng is not None and dropout_rate > 0.0:
        keep = jax.random.bernoulli(rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    return probs


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _sdpa_core(scale, dropout_rate, stat_dtype, qh, kh, vh, bias, rng):
    """softmax(scale·QKᵀ + bias) @ V with attention-weight dropout.

    qh/vh: (B, Lq/Lk, H, D); kh: (B, Lk, H, D); bias: additive fp32
    mask broadcastable to (B, H, Lq, Lk), or None; rng: dropout key or
    None. Returns (B, Lq, H, D) in vh's dtype.
    """
    probs = _sdpa_probs(scale, dropout_rate, stat_dtype, qh, kh, vh,
                        bias, rng)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vh.dtype), vh)


def _sdpa_fwd(scale, dropout_rate, stat_dtype, qh, kh, vh, bias, rng):
    out = _sdpa_core(scale, dropout_rate, stat_dtype, qh, kh, vh, bias,
                     rng)
    return out, (qh, kh, vh, bias, rng)


def _sdpa_bwd(scale, dropout_rate, stat_dtype, res, g):
    qh, kh, vh, bias, rng = res
    # recompute the PRE-dropout softmax once; the dropout mask re-draws
    # from the same rng, so forward/backward masks agree bitwise
    sm = _sdpa_probs(scale, 0.0, stat_dtype, qh, kh, vh, bias, None)
    g = g.astype(vh.dtype)
    dp = jnp.einsum("bqhd,bkhd->bhqk", g, vh,
                    preferred_element_type=stat_dtype).astype(stat_dtype)
    if rng is not None and dropout_rate > 0.0:
        keep = jax.random.bernoulli(rng, 1.0 - dropout_rate, sm.shape)
        pd = jnp.where(keep, sm / (1.0 - dropout_rate), 0.0)
        dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
    else:
        pd = sm
    dv = jnp.einsum("bhqk,bqhd->bkhd", pd.astype(vh.dtype), g)
    # softmax backward in fp32 statistics, then bf16 operands for the
    # two grad contractions (the production flash-attention trade).
    # The scale rides the SMALL (B, L, H, D) operands, never the
    # logits-shaped ds (mirrors the forward's q-side fold).
    ds = (dp - jnp.sum(dp * sm, axis=-1, keepdims=True)) * sm
    dsb = ds.astype(qh.dtype)
    s = jnp.asarray(scale, qh.dtype)
    dq = jnp.einsum("bhqk,bkhd->bqhd", dsb, kh * s)
    dk = jnp.einsum("bhqk,bqhd->bkhd", dsb, qh * s)
    # bias is a mask, not a trainable input — no cotangent (callers
    # stop_gradient it); rng is a key, not differentiable
    return dq, dk, dv, None, None


_sdpa_core.defvjp(_sdpa_fwd, _sdpa_bwd)


# impls already warned about this process (the degrade fires inside
# jit traces, so the warning must be trace-time and once per impl)
_DROPOUT_DEGRADE_WARNED = set()


def _warn_dropout_degrade(impl: str) -> None:
    if impl in _DROPOUT_DEGRADE_WARNED:
        return
    _DROPOUT_DEGRADE_WARNED.add(impl)
    warnings.warn(
        f"attention impl={impl!r} does not implement attention-weight "
        "dropout; falling back to impl='chunked' (streams dropout "
        "exactly) for this call. Set --model.dropout=0 to keep the "
        f"{impl!r} kernel.", stacklevel=3)


# The attention-kernel domain, the single source of truth for the
# config-time membership validation in models/perceiver.py and
# tasks/base.py (and the trace-time check in mha_apply below).
SPMD_IMPLS = ("seqpar", "ring", "ulysses")
ATTENTION_IMPLS = (None, "einsum", "chunked", "flash") + SPMD_IMPLS
# output-query ← latent cross-attention: the SPMD impls shard the
# encoder token axis and do not apply (tasks/base.py docstring)
DECODER_ATTENTION_IMPLS = (None, "einsum", "chunked", "flash")
_SPMD_IMPLS = SPMD_IMPLS


# --- which core a call takes --------------------------------------------------
# ``impl=None`` means "pick": the fused kernels where the backend is a
# TPU, no attention-weight dropout is active, there is no ``attn_mask``,
# the operands lie on one device and the shapes are ones the kernels
# tile well; the materialised core otherwise, for the first reason that
# holds. One algorithm with parameters from shape — not a user's choice
# between two equals ("einsum" / "flash" force one for tests and A/B).
# The shape floors are from chip runs (PERF.md, Findings, PR 26): the
# kernels block queries by 128 and pay a fixed cost per call, so they
# gain from about 512 x 512 scores a head upwards and lose below it
# (256 x 256, 1024 queries over 128 keys, 32 latents over any number of
# keys); the head dim sets no floor (16 and 32 gain as 64 and 128 do).

FUSED_MIN_LQ = 128
FUSED_MIN_LK = 512
FUSED_MIN_SCORES = 512 * 512

#: why a call site took the materialised core, in the order checked
MATERIALIZED_REASONS = ("impl", "attn_mask", "dropout", "backend", "mesh",
                        "shape")


def pick_attention_core(*, backend: str, lq: int, lk: int,
                        dropout_active: bool, has_attn_mask: bool,
                        mesh_devices: int, causal: bool = False,
                        has_key_padding_mask: bool = False,
                        block_diffusion: bool = False
                        ) -> Tuple[str, Optional[str]]:
    """``("fused", None)`` or ``("materialized", reason)`` for an
    ``impl=None`` call, from what the call site can observe. ``causal``
    and ``block_diffusion`` are the call's own properties and send it
    nowhere: the kernels have the modes. Only beside a key padding mask
    (those kernels take no bias) do the two make a mask the
    materialised core builds."""
    if has_attn_mask or (
            (causal or block_diffusion) and has_key_padding_mask):
        reason = "attn_mask"
    elif dropout_active:
        reason = "dropout"
    elif backend != "tpu":
        reason = "backend"
    elif mesh_devices > 1:
        # a Pallas call has no partitioning rule: under GSPMD its
        # operands would be all-gathered into a replicated kernel
        reason = "mesh"
    elif (lq < FUSED_MIN_LQ or lk < FUSED_MIN_LK
          or lq * lk < FUSED_MIN_SCORES):
        reason = "shape"
    else:
        return "fused", None
    return "materialized", reason


def _backend() -> str:
    """The backend the pick reads (a seam: tests drive the pick as a
    TPU would take it while the kernels run interpreted)."""
    return jax.default_backend()


def _mesh_of(x):
    """The mesh ``x`` is laid out on, as its type carries it (inside a
    trace too: jit hands the arguments' mesh down); None for an array
    on one device."""
    mesh = getattr(getattr(jax.typeof(x), "sharding", None), "mesh", None)
    return None if mesh is None or mesh.empty else mesh


def mesh_devices(x) -> int:
    """Devices of the mesh ``x`` is laid out on; 1 on one device."""
    mesh = _mesh_of(x)
    return 1 if mesh is None else mesh.size


def data_shards(x) -> int:
    """Devices the rows of ``x`` are split over: the size of the
    ``data`` axis of its mesh (the batch's axis,
    ``parallel/mesh.make_mesh``); 1 on one device. What else a mesh
    splits (heads and hidden units over ``model``) depends on what
    divides, and is not counted on."""
    mesh = _mesh_of(x)
    return 1 if mesh is None else mesh.shape.get("data", 1)


# Trace-time tally of attention call sites, keyed (path, reason):
# ("fused", None), ("materialized", "shape"), ("chunked", None), ...
# A call site inside a scanned or rematerialised layer counts once per
# trace of its body, not once per execution.
_PATHS = Tally()


def attention_paths():
    """Count the attention call sites traced inside the block by the
    core they took, in the style of ``cache.compile_events()``."""
    return _PATHS.counting()


def tally_latent_call(widths: str) -> None:
    """One latent-attention call site (``models/hybrid_lm.mla_apply``)
    by a head's widths and what turns, beside the core it took:
    ``latent[192+64r|256 query latent]`` is score heads of 192 channels
    and 64 rotated ones beside value heads of 256."""
    _PATHS.add(("latent", widths))


# ... and of what the fused call sites with a mask run: their tiles by
# kind and the sub-tiles of the masked ones (``ops/tiling.tile_counts``),
# keyed by the words of the log line.
_MASKED_TILES = Tally()


def masked_attention_tiles():
    """Count the fused call sites with a mask traced inside the block
    by what they run: ``block_diffusion tiles plain 12 masked 12,
    sub-tiles 32/48``."""
    return _MASKED_TILES.counting()


def format_attention_paths(tally, tiles=None) -> str:
    """``fused=39 materialized[shape]=1`` — one log line's worth; with
    the masked call sites' ``tiles``, ``; causal tiles plain 6 masked
    4, sub-tiles 12/16 x2`` after it."""
    paths = " ".join(
        f"{path}[{reason}]={n}" if reason else f"{path}={n}"
        for (path, reason), n in sorted(
            tally.items(), key=lambda kv: (kv[0][0], kv[0][1] or "")))
    return "; ".join([paths] + [f"{what} x{n}"
                                for what, n in sorted((tiles or {}).items())])


@device_scope("attn_proj")
def mha_kv_heads(params, k, v, *, policy: Policy = DEFAULT_POLICY):
    """Project k/v: the loop-invariant half of cross-attention. The
    Perceiver encoder cross-attends the SAME input tokens in every
    weight-shared layer, so the kv projections (and the kv LayerNorm
    upstream, see ``cross_attention_kv``) are identical across the
    layer scan — hoisting them out of the loop removes a per-layer
    recompute AND the per-layer residual stacking of the projected kv
    through the scan. Returns ``(kh, vh)`` shaped (B, Lk, H·D), heads
    side by side on the channel axis as the projection leaves them
    (the fused core reads them so; the others split them), for
    ``mha_apply(..., kv_heads=...)``."""
    return (linear_apply(params["k"], k, policy=policy),
            linear_apply(params["v"], v, policy=policy))


def mha_apply(params, q, k, v, *, num_heads: int,
              key_padding_mask=None, attn_mask=None,
              dropout_rate: float = 0.0, rng=None, deterministic: bool = True,
              policy: Policy = DEFAULT_POLICY, impl: Optional[str] = None,
              kv_chunk_size: int = 1024, spmd=None, kv_heads=None,
              causal: bool = False, rope=None, block_diffusion=None,
              norm_eps: float = 1e-6, output_gate: bool = False,
              q_heads=None):
    """Scaled dot-product multi-head attention.

    q: (B, Lq, q_dim); k: (B, Lk, k_dim); v: (B, Lk, v_dim).
    key_padding_mask: (B, Lk) bool, True at padding.
    attn_mask: (Lq, Lk) or (B, Lq, Lk); bool (True = masked) or additive.
    causal: query i sees keys 0..i (Lq == Lk; no ``attn_mask`` beside
    it); the fused kernels' causal mode or the materialized core's own
    triangle. block_diffusion: ``(L, B)``, the rules of
    ``block_diffusion_mask`` over a row of ``2 L`` positions (Lq == Lk
    == 2 L; no ``attn_mask`` and no ``causal`` beside it), on the same
    two cores. rope: ``(cos, sin)`` tables (L, D) of
    ``ops.fourier.rope_tables``, applied to the projected q and k.
    Where ``params`` holds ``q_norm`` and ``k_norm`` (a scale of ``D``
    each), q and k take an RMSNorm over each head's channels (eps
    ``norm_eps``) before the tables (both in one pass each way, read
    where q and k lie in the projection's product, where
    ``ops/pallas_head_rotary.fits`` allows). ``kv_heads`` come as their caller
    made them: normed and rotated there, if at all, and **their widths
    may differ** (latent attention, MLA: score heads ``q_dim / H`` =
    ``k_dim / H`` wide beside narrower value heads; the scale is the
    score heads'; the result has the value heads' width and
    ``params["out"]`` takes it): the materialized core takes them as
    they are, the fused kernels take one width, so every head is
    zero-padded to the next whole lanes that hold both (zero columns
    change neither scores nor outputs) under the score heads' scale and
    the result cut to the value heads (``two_widths`` in
    ``attention_paths``: ``192|128`` or, padded, ``192|128 as 256``).
    ``q_heads`` (beside ``kv_heads`` only): the queries (B, Lq, H·D) as
    their caller made them too, projected, normed and rotated there
    (latent attention with a query latent or rotary channels);
    ``params["q"]`` is then not read and ``q`` may be None.
    output_gate: the
    query projection is twice as wide, a head's query beside its gate
    (``[q_h | gate_h]`` a head), and the core's output is multiplied by
    ``sigmoid(gate)`` before the output projection, outside the core
    (scope ``attn_gate`` under ``attn_proj``).
    impl: None (pick: the fused kernels where ``pick_attention_core``
    allows, else the materialized core), "einsum" (materialized
    weights, supports dropout and attn_mask), "chunked" (blockwise
    lax.scan, O(Lq·chunk) memory, supports streamed attention dropout),
    "flash" (the fused Pallas TPU kernels, forward and backward;
    interpreter mode off-TPU), or one
    of the shard_map sequence-parallel kernels — "seqpar" (q replicated,
    kv sequence-sharded: the Perceiver cross-attention layout), "ring"
    (all of q/k/v sequence-sharded, ppermute kv rotation), "ulysses"
    (all-to-all heads↔sequence re-sharding). The spmd impls require
    ``spmd=(mesh, seq_axis, batch_axis)`` describing how the token axis
    is laid out (batch_axis may be None).
    Returns (B, Lq, q_dim).
    """
    if impl not in ATTENTION_IMPLS:
        raise ValueError(
            f"unknown attention impl {impl!r}; expected None, 'einsum', "
            "'chunked', 'flash', 'seqpar', 'ring', or 'ulysses'")
    if impl in ("chunked", "flash", *_SPMD_IMPLS):
        if attn_mask is not None:
            raise NotImplementedError(
                f"impl={impl!r} takes no attn_mask: a key_padding_mask "
                "and, on the fused kernels, causal=True or "
                "block_diffusion=(L, B) are the masks it can take")
        if (impl != "chunked" and dropout_rate > 0.0
                and not deterministic):
            # degrade, don't die (VERDICT r5 item 7): the chunked path
            # streams attention-weight dropout exactly, so a dropout>0
            # config trains under every impl — at chunked speed, with
            # a one-time warning instead of a crash
            _warn_dropout_degrade(impl)
            impl = "chunked"
    if impl in _SPMD_IMPLS and spmd is None:
        raise ValueError(
            f"impl={impl!r} needs spmd=(mesh, seq_axis, batch_axis)")
    if (causal or block_diffusion is not None) and (
            attn_mask is not None or impl in ("chunked", *_SPMD_IMPLS)
            or (causal and block_diffusion is not None)):
        raise NotImplementedError(
            "causal and block-diffusion attention run on the fused or "
            "the materialized core and are their own mask: no attn_mask "
            f"and not the other beside it, not impl={impl!r}")

    if q_heads is not None:
        if kv_heads is None or rope is not None or output_gate:
            raise ValueError("q_heads come beside kv_heads, rotated and "
                             "gated by their caller if at all")
        qh, (kh, vh), packed = q_heads, kv_heads, None
    else:
        qh, kh, vh, packed = _project(params, q, k, v, policy, kv_heads)
    if qh.shape[-1] % (2 * num_heads if output_gate else num_heads):
        raise ValueError(f"q_dim {qh.shape[-1]} not divisible by "
                         f"num_heads {num_heads}")
    # where q and k lie in a wider product: (array, first channel,
    # channels from a head's first to the next's; 0: side by side)
    q_from = k_from = gate = None
    if output_gate:
        q_from = (qh, 0, qh.shape[-1] // num_heads)
    elif packed is not None:
        q_from, k_from = (packed, 0, 0), (packed, qh.shape[-1], 0)
    if output_gate:
        with device_scope("attn_proj"), device_scope("attn_gate"):
            qh, gate = (x.reshape(*qh.shape[:2], -1) for x in jnp.split(
                _split_heads(qh, num_heads), 2, axis=-1))
    if "q_norm" in params or rope is not None:
        # (imported here: that module reads this one's ``mesh_devices``)
        from perceiver_tpu.ops.pallas_head_rotary import head_norm_rotary
        with device_scope("attn_proj"):
            qh = head_norm_rotary(
                qh, num_heads, norm=params.get("q_norm"), eps=norm_eps,
                rope=rope, policy=policy, cut_from=q_from)
            if kv_heads is None:
                kh = head_norm_rotary(
                    kh, num_heads, norm=params.get("k_norm"), eps=norm_eps,
                    rope=rope, policy=policy, cut_from=k_from)
    path, reason = impl, None
    if impl is None:
        path, reason = pick_attention_core(
            backend=_backend(), lq=qh.shape[1], lk=kh.shape[1],
            dropout_active=dropout_rate > 0.0 and not deterministic,
            has_attn_mask=attn_mask is not None,
            mesh_devices=mesh_devices(qh), causal=causal,
            has_key_padding_mask=key_padding_mask is not None,
            block_diffusion=block_diffusion is not None)
        if path == "fused":
            impl = "flash"
    elif impl == "einsum":
        path, reason = "materialized", "impl"
    elif impl == "flash":
        path = "fused"
    _PATHS.add((path, reason))
    if vh.shape[-1] != kh.shape[-1]:
        widths = f"{kh.shape[-1] // num_heads}|{vh.shape[-1] // num_heads}"
        _PATHS.add(("two_widths", widths + (
            f" as {_fused_width(kh, vh, num_heads)}" if impl == "flash"
            else "")))
    if impl == "flash":
        out = _fused_core(qh, kh, vh, num_heads, key_padding_mask, causal,
                          block_diffusion)
    else:
        qh, kh, vh = (_split_heads(x, num_heads) for x in (qh, kh, vh))
        if impl in ("chunked", *_SPMD_IMPLS):
            out = _streamed_core(qh, kh, vh, impl, key_padding_mask,
                                 dropout_rate, rng, deterministic,
                                 kv_chunk_size, spmd)
        else:
            if causal:   # True above the diagonal = masked
                attn_mask = ~jnp.tril(jnp.ones(
                    (qh.shape[1], kh.shape[1]), jnp.bool_))
            elif block_diffusion is not None:
                attn_mask = block_diffusion_mask(*block_diffusion)
            out = _materialized_core(qh, kh, vh, key_padding_mask,
                                     attn_mask, dropout_rate, rng,
                                     deterministic, policy)
        out = out.reshape(*out.shape[:2], -1)
    with device_scope("attn_proj"):
        if gate is not None:
            with device_scope("attn_gate"):
                out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(policy.compute_dtype)
        return linear_apply(params["out"], out, policy=policy)


@device_scope("attn_proj")
def _project(params, q, k, v, policy, kv_heads):
    """q/k/v projections, (B, L, H·D) each: heads unsplit; and the
    packed product they are slices of, where there is one."""
    if kv_heads is not None:
        # pre-projected (kh, vh) from mha_kv_heads — the hoisted
        # loop-invariant path; only the q projection runs per call
        return (dear(linear_apply(params["q"], q, policy=policy), "qkv"),
                *kv_heads, None)
    if k is q and v is q:
        # self-attention: pack the three projections into ONE matmul
        # (torch's in_proj). Identical numerics — the concatenated
        # weight produces the same three output blocks — but a single
        # wider MXU op instead of three skinny ones, which matters for
        # dispatch-bound small-channel configs.
        packed = {
            "w": jnp.concatenate([params[n]["w"] for n in ("q", "k", "v")],
                                 axis=1),
        }
        if "b" in params["q"]:
            packed["b"] = jnp.concatenate(
                [params[n]["b"] for n in ("q", "k", "v")])
        # named before it is sliced: one buffer for a ``remat`` layer
        # to hold, not three slices the compiler may copy
        qkv = dear(linear_apply(packed, q, policy=policy), "qkv")
        e = qkv.shape[-1] // 3
        return (*(qkv[..., i * e:(i + 1) * e] for i in range(3)), qkv)
    return (linear_apply(params["q"], q, policy=policy),
            linear_apply(params["k"], k, policy=policy),
            linear_apply(params["v"], v, policy=policy), None)


def _fused_width(k, v, num_heads: int) -> int:
    """Lanes a head of the fused kernels' one width that holds score
    heads ``k`` and value heads ``v`` (B, L, H·D) of two widths."""
    return round_up(max(k.shape[-1], v.shape[-1]) // num_heads, 128)


@device_scope("attn_core")
def _fused_core(q, k, v, num_heads, key_padding_mask, causal=False,
                block_diffusion=None):
    """The fused kernels, on the projections as they are: (B, L, H·D)
    in and out, blocks from the shapes. Score heads and value heads of
    two widths go in zero-padded to one (``_fused_width``) under the
    score heads' scale, and the value heads' width comes out."""
    import perceiver_tpu.ops.chunked_attention as _ca
    import perceiver_tpu.ops.pallas_attention as _pa
    bias = (_ca.pad_mask_to_bias(key_padding_mask)
            if key_padding_mask is not None else None)
    if causal or block_diffusion is not None:
        _MASKED_TILES.add(_pa.masked_call_tiles(q.shape[1], block_diffusion))
    scale = value_width = None
    if v.shape[-1] != k.shape[-1]:
        scale = 1.0 / math.sqrt(q.shape[-1] // num_heads)
        value_width = v.shape[-1] // num_heads
        width = _fused_width(k, v, num_heads)
        q, k, v = (_pa._pad_heads(x, num_heads, width) for x in (q, k, v))
    out = _pa.flash_attention_channels(q, k, v, num_heads=num_heads,
                                       bias=bias, causal=causal,
                                       block_diffusion=block_diffusion,
                                       scale=scale)
    if value_width is not None:
        out = _pa._unpad_heads(out, num_heads, value_width)
    return out


@device_scope("attn_core")
def _streamed_core(qh, kh, vh, impl, key_padding_mask, dropout_rate, rng,
                   deterministic, kv_chunk_size, spmd):
    """The attention core of the impls that stream the keys in XLA:
    chunked and the shard_map kernels. (B, L, H, D) in and out."""
    import perceiver_tpu.ops.chunked_attention as _ca
    bias = (_ca.pad_mask_to_bias(key_padding_mask)
            if key_padding_mask is not None else None)
    scale = 1.0 / (qh.shape[-1] ** 0.5)
    # (B, L, H, D) → (B, H, L, D)
    qt, kt, vt = (x.swapaxes(1, 2) for x in (qh, kh, vh))
    if impl == "chunked":
        drop = dropout_rate if not deterministic else 0.0
        if drop > 0.0 and rng is None:
            # mirror the einsum path (ops/dropout.py): silently
            # skipping configured dropout would be invisible
            raise ValueError("dropout needs an rng when not "
                             "deterministic")
        out = _ca.chunked_attention(qt, kt, vt, bias=bias, scale=scale,
                                    chunk_size=kv_chunk_size,
                                    dropout_rate=drop, rng=rng)
    else:
        from perceiver_tpu.parallel.ring_attention import (
            make_ring_attention,
            make_seq_parallel_cross_attention,
        )
        from perceiver_tpu.parallel.ulysses import (
            make_ulysses_attention,
        )
        mesh, seq_axis, batch_axis = spmd
        if impl == "seqpar":
            f = make_seq_parallel_cross_attention(
                mesh, seq_axis, batch_axis=batch_axis, scale=scale)
        elif impl == "ring":
            f = make_ring_attention(mesh, seq_axis,
                                    batch_axis=batch_axis, scale=scale)
        else:
            f = make_ulysses_attention(
                mesh, seq_axis, batch_axis=batch_axis, scale=scale,
                kv_chunk_size=kv_chunk_size)
        out = f(qt, kt, vt, bias)
    return out.swapaxes(1, 2)


@device_scope("attn_core")
def _materialized_core(qh, kh, vh, key_padding_mask, attn_mask,
                       dropout_rate, rng, deterministic, policy):
    """The einsum attention core (``_sdpa_core``) under its mask bias."""
    # additive fp32 mask bias, broadcastable to (B, H, Lq, Lk): the
    # key-padding NEG_INF bias and any attn_mask fold into one tensor
    # the custom-VJP core treats as a non-trainable constant
    bias = None
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            bias = jnp.where(attn_mask, NEG_INF, 0.0).astype(policy.norm_dtype)
        else:
            bias = attn_mask.astype(policy.norm_dtype)
        if bias.ndim == 2:
            bias = bias[None, None, :, :]
        elif bias.ndim == 3:
            bias = bias[:, None, :, :]
    if key_padding_mask is not None:
        pad = jnp.where(key_padding_mask[:, None, None, :], NEG_INF,
                        0.0).astype(policy.norm_dtype)
        bias = pad if bias is None else bias + pad
    if bias is not None:
        bias = jax.lax.stop_gradient(bias)

    drop = dropout_rate if not deterministic else 0.0
    if drop > 0.0 and rng is None:
        raise ValueError("dropout needs an rng when not deterministic")
    return _sdpa_core(1.0 / math.sqrt(qh.shape[-1]), drop,
                      policy.norm_dtype, qh, kh, vh, bias,
                      rng if drop > 0.0 else None)


# --- pre-norm cross/self attention (reference model.py:77-116) ---------------


def cross_attention_init(key, num_q_channels: int, num_kv_channels: int,
                         num_heads: int, dtype=jnp.float32):
    return {
        "norm_q": layer_norm_init(num_q_channels, dtype),
        "norm_kv": layer_norm_init(num_kv_channels, dtype),
        "mha": mha_init(key, num_q_channels, num_heads,
                        k_dim=num_kv_channels, v_dim=num_kv_channels,
                        dtype=dtype),
    }


def cross_attention_kv(params, x_kv, *, policy: Policy = DEFAULT_POLICY):
    """The loop-invariant half of ``cross_attention_apply``: pre-norm
    the kv tokens and project them, once ((B, Lk, H·D) each). The
    encoder hoists this out of its weight-shared layer scan
    (``models/perceiver.py``) — the kv LayerNorm + projections over
    the full token array were recomputed AND residual-stacked per
    layer before."""
    xkv = layer_norm_apply(params["norm_kv"], x_kv, policy=policy)
    return mha_kv_heads(params["mha"], xkv, xkv, policy=policy)


def cross_attention_apply(params, x_q, x_kv, *, num_heads: int,
                          key_padding_mask=None, attn_mask=None,
                          dropout_rate: float = 0.0, rng=None,
                          deterministic: bool = True,
                          policy: Policy = DEFAULT_POLICY,
                          impl: Optional[str] = None,
                          kv_chunk_size: int = 1024, spmd=None,
                          kv_heads=None):
    """Pre-norm on q AND kv, then MHA (reference model.py:97-99).

    ``kv_heads`` (from ``cross_attention_kv``) supplies the normed,
    projected kv — ``x_kv`` may then be None."""
    xq = layer_norm_apply(params["norm_q"], x_q, policy=policy)
    if kv_heads is not None:
        return mha_apply(params["mha"], xq, None, None,
                         num_heads=num_heads,
                         key_padding_mask=key_padding_mask,
                         attn_mask=attn_mask, dropout_rate=dropout_rate,
                         rng=rng, deterministic=deterministic,
                         policy=policy, impl=impl,
                         kv_chunk_size=kv_chunk_size, spmd=spmd,
                         kv_heads=kv_heads)
    xkv = layer_norm_apply(params["norm_kv"], x_kv, policy=policy)
    return mha_apply(params["mha"], xq, xkv, xkv, num_heads=num_heads,
                     key_padding_mask=key_padding_mask, attn_mask=attn_mask,
                     dropout_rate=dropout_rate, rng=rng,
                     deterministic=deterministic, policy=policy,
                     impl=impl, kv_chunk_size=kv_chunk_size, spmd=spmd)


def self_attention_init(key, num_channels: int, num_heads: int,
                        dtype=jnp.float32):
    return {
        "norm": layer_norm_init(num_channels, dtype),
        "mha": mha_init(key, num_channels, num_heads, dtype=dtype),
    }


def self_attention_apply(params, x, *, num_heads: int,
                         key_padding_mask=None, attn_mask=None,
                         dropout_rate: float = 0.0, rng=None,
                         deterministic: bool = True,
                         policy: Policy = DEFAULT_POLICY,
                         impl: Optional[str] = None,
                         kv_chunk_size: int = 1024):
    """Pre-norm then MHA with q = k = v (reference model.py:110-116)."""
    xn = layer_norm_apply(params["norm"], x, policy=policy)
    return mha_apply(params["mha"], xn, xn, xn, num_heads=num_heads,
                     key_padding_mask=key_padding_mask, attn_mask=attn_mask,
                     dropout_rate=dropout_rate, rng=rng,
                     deterministic=deterministic, policy=policy,
                     impl=impl, kv_chunk_size=kv_chunk_size)
