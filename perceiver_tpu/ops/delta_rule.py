"""Gated delta-rule mixer (Gated DeltaNet, Yang, Kautz & Hatamizadeh,
arXiv:2412.06464; the linear-attention layer of ``qwen3_next``, the
layer ``L`` of ``models/hybrid_lm.py``) as pure init/apply functions.

On a row ``u`` of ``S`` positions, ``Hk`` key heads of ``Dk`` channels
and ``Hv`` value heads of ``Dv`` (value head ``j`` reads key head
``j // (Hv / Hk)``)::

    [q k v z] = u W_qkvz                 (widths Hk Dk, Hk Dk, Hv Dv, Hv Dv)
    [b alpha] = u W_ba                   (Hv, Hv; kept in float32)
    [q k v] = silu(conv([q k v]))        (causal, depthwise, K taps, no bias)
    q = l2norm(q) / sqrt(Dk);  k = l2norm(k)         (a head's channels)
    beta = sigmoid(b);  g = -exp(A_log) softplus(alpha + dt_bias)
    S_t = exp(g_t) S_(t-1);  S_t = S_t + k_t (beta_t (v_t - S_t^T k_t))^T
    o_t = S_t^T q_t                                   (S: Dk x Dv a head)
    y = rms(o) * scale * silu(z)         (over a head's Dv channels: the
                                          norm first, then the gate)
    out = y W_out

The published in-projection interleaves q, k, v and z a key head; here
they lie side by side, ``[q | k | v | z]`` and ``[b | alpha]``: the same
function under a permutation of the matrix's columns.

**The recurrence runs in chunks** of ``chunk_size`` positions ``Q`` (the
WY form). With ``G_i`` the running sum of ``g`` inside a chunk and ``S``
the state the chunk finds::

    A = -strict_lower((beta k) k^T * exp(G_i - G_j))
    T = (I - A)^-1 = (I + A)(I + A^2)(I + A^4) ...     (A is nilpotent)
    U = T (beta v);   W = T (beta k exp(G))
    v' = U - W S
    o = (q exp(G)) S + lower(q k^T * exp(G_i - G_j)) v'
    S <- exp(G_last) S + (k exp(G_last - G))^T v'

What a chunk computes without the state it finds (``U``, ``W``, the
decayed ``q`` and ``k``, the masked scores) is computed for all chunks
at once (``_inside_chunks``); ``v'``, ``o`` and ``S`` go chunk by chunk,
a scan that carries ``S`` (``_across_chunks``). Every exponent formed is
a sum of ``g <= 0`` over a span, masked **before** the ``exp`` where the
span would run backwards: no ``exp`` of a positive number, and a decay
that underflows is a quiet 0.

**Two forms, one arithmetic; the call site's shapes say which runs**
(``fits``): where the operands lie on one device, the heads are whole
lanes (``Dk`` and ``Dv`` multiples of 128), the chunk is 16, 32, 64 or
128 and q, k, v share a compute dtype the kernels take, the rule is the
Pallas kernels of ``ops/pallas_delta_rule.py`` (``kernel[64x64]`` in
``rule_paths``: a chunk's ``Q x Q`` matrices, its inverse and the
carried state stay in VMEM, forward and backward; interpreted off a
TPU); everywhere else it is the einsums below (``chunked[...]``), which
are the kernels' oracle in the tests. A row is padded to whole chunks
either way.

**Precision.** ``g``, ``beta``, every decay, ``A``, the powers of ``A``
and ``T`` are float32, the inverse's products at ``Precision.HIGHEST``
(six bf16 passes on the MXU), and the carried state is float32. The
other products take operands in the compute dtype (bf16) and accumulate
in float32: ``k k^T``, ``q k^T``, ``T (beta v)`` and ``T (beta k
exp(G))`` (``T`` rounded to the compute dtype as an operand), ``W S``,
``(q exp(G)) S`` (the state rounded as an operand), the masked scores
times ``v'`` and ``k^T v'``. Under a float32 policy everything is
float32 at ``HIGHEST``. The kernels keep this or finer, nowhere
coarser: they make the same ``T`` by blocks of 16 (float32 at
``HIGHEST``; equal to float32 rounding), hand ``U`` on in float32 where
the einsums round it to the compute dtype, and keep a cotangent float32
until a product takes it as an operand.

The einsum form's backward is autodiff's of these products, recomputed:
the rule is a ``jax.checkpoint`` of its own a pass of heads
(``pick_rule`` says how many value heads go through together: a chunk's
``Q x Q`` float32 matrices for every head and chunk of a 4 x 4,096 row
at 32 heads are 134 MB each, and autodiff keeps a dozen), so its
residuals live while its own backward runs and no longer. The kernels'
backward is written by hand and takes the rule's operands alone (a
pass rebuilds the state each chunk found, the reversed pass carries its
cotangent; the inverse's cotangent is ``T^T dT T^T``). Either way a
``remat`` layer that holds the rule's output (``dear``, ``delta_out``)
does not run it again for the layer's sake. One scan over the chunks
for all the heads, with the chunks' own work alone in passes, was tried
and was slower on the chip (737 ms a step for 665, 7.5 GB reserved for
5.9: the chunks' work then runs three times, and the time is in the
inverse's ten float32 products, 135 ms of the rule's 253 a step, not in
the scan's steps; PERF.md, PR 42): no rearrangement of XLA operations
keeps a chunk's matrices on the chip, hence the kernels (PERF.md,
PR 43).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perceiver_tpu.obs.trace import device_scope
from perceiver_tpu.ops.attention import mesh_devices
from perceiver_tpu.ops.initializers import uniform
from perceiver_tpu.ops.linear import linear_apply, linear_init
from perceiver_tpu.ops.policy import DEFAULT_POLICY, Policy
from perceiver_tpu.ops.remat import dear
from perceiver_tpu.ops.ssm import causal_conv
from perceiver_tpu.ops.tally import Tally

#: which form the rule took at each call site: ``kernel[64x64]`` (64
#: chunks of 64 positions, the Pallas kernels) or
#: ``chunked[64x64,8 heads a pass]`` (the einsums, eight value heads a
#: checkpointed pass), ``+pad`` after the chunks where the last one is
#: padded
rule_paths = Tally()

# head-chunks (rows x chunks x value heads) a pass may hold: each has a
# dozen Q x Q float32 matrices alive in its backward, 16 KB each at 64
PASS_HEAD_CHUNKS = 2048


def pick_rule(*, rows: int, seq: int, key_heads: int, value_heads: int,
              chunk_size: int):
    """``(chunk, chunks, pad, heads a pass)``, from what the call site
    can observe: the chunk is ``chunk_size`` or the whole of a shorter
    row, the last chunk padded; the value heads go through in passes of
    whole key heads, the most whose head-chunks stay under
    ``PASS_HEAD_CHUNKS`` (never fewer than one key head's)."""
    chunk = min(chunk_size, seq)
    pad = -seq % chunk
    chunks = (seq + pad) // chunk
    per = value_heads // key_heads
    groups = max(1, min(key_heads,
                        PASS_HEAD_CHUNKS // max(1, rows * chunks * per)))
    while key_heads % groups:
        groups -= 1
    return chunk, chunks, pad, groups * per


def fits(q, v, chunk: int) -> bool:
    """Whether a call takes the kernels of ``ops/pallas_delta_rule.py``,
    from what the call site can observe: operands on one device (a
    Pallas call has no partitioning rule), heads of whole lanes, a chunk
    the kernels' inverse merges, q and v in one compute dtype the
    kernels take."""
    from perceiver_tpu.ops import pallas_delta_rule
    return (mesh_devices(q) == 1 and q.dtype == v.dtype
            and pallas_delta_rule.fits(chunk=chunk, key_dim=q.shape[3],
                                       value_dim=v.shape[3], dtype=v.dtype))


def delta_mixer_init(key, dim: int, *, num_key_heads: int,
                     num_value_heads: int, key_head_dim: int,
                     value_head_dim: int, conv_kernel: int = 4,
                     dtype=jnp.float32):
    """The family's initialisation: ``A`` uniform in ``(0, 16)``
    (``A_log`` its log), ``dt_bias`` and the norm's scale 1, the
    convolution as torch's ``Conv1d`` without bias."""
    key_dim = num_key_heads * key_head_dim
    value_dim = num_value_heads * value_head_dim
    k_in, k_ba, k_conv, k_a, k_out = jax.random.split(key, 5)
    return {
        "in_proj_qkvz": linear_init(k_in, dim, 2 * key_dim + 2 * value_dim,
                                    dtype, bias=False),
        "in_proj_ba": linear_init(k_ba, dim, 2 * num_value_heads, dtype,
                                  bias=False),
        "conv": {"w": uniform(k_conv, (conv_kernel, 2 * key_dim + value_dim),
                              1.0 / math.sqrt(conv_kernel), dtype)},
        "dt": {"bias": jnp.ones((num_value_heads,), dtype)},
        "A_log": {"bias": jnp.log(jax.random.uniform(
            k_a, (num_value_heads,), dtype, 1e-3, 16.0))},
        "norm": {"scale": jnp.ones((value_head_dim,), dtype)},
        "out_proj": linear_init(k_out, value_dim, dim, dtype, bias=False),
    }


def l2_norm(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def gated_head_rms_norm(params, o, z, eps: float,
                        policy: Policy = DEFAULT_POLICY):
    """``rms(o) * scale * silu(z)`` over each head's channels (the last
    axis), one scale for all the heads: the norm first, then the gate
    (a Mamba-2 mixer gates first: ``ops.ssm.gated_group_rms_norm``);
    float32 inside."""
    o = o.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
    out = o * rstd * params["scale"].astype(jnp.float32) \
        * jax.nn.silu(z.astype(jnp.float32))
    return out.astype(policy.compute_dtype)


def unit_lower_inverse(a):
    """``(I - a)^-1`` for ``a`` (..., Q, Q) strictly lower triangular,
    float32: ``a^Q = 0``, so the inverse is ``I + a + a^2 + ... =
    (I + a)(I + a^2)(I + a^4) ...`` up to the power that reaches ``Q``:
    two ``Q x Q`` products a factor (the square, the factor's
    product), at ``Precision.HIGHEST``."""
    size = a.shape[-1]

    def dot(x, y):
        return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)

    total, power = jnp.eye(size, dtype=a.dtype) + a, a
    for _ in range(max(0, math.ceil(math.log2(size)) - 1)):
        power = dot(power, power)
        total = total + dot(total, power)
    return total


def _dot(dtype):
    """Products on operands in ``dtype``, summed in float32 (at
    ``HIGHEST`` where ``dtype`` is float32 itself)."""
    exact = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None

    def dot(spec, lhs, rhs):
        return jnp.einsum(spec, lhs.astype(dtype), rhs.astype(dtype),
                          precision=exact,
                          preferred_element_type=jnp.float32)

    return dot


def _inside_chunks(q, k, v, g, beta, chunk: int):
    """What a chunk computes without the state it finds, for some of
    the heads and all chunks at once. q, k (B, S, G, Dk) in the compute
    dtype, a key head each; v (B, S, G, R, Dv), ``R`` value heads a key
    head; g (<= 0), beta (B, S, G, R) float32. ``S`` a multiple of
    ``chunk``. Head-major, (B, chunks, G, R, Q, ...): ``U``, ``W``,
    ``q exp(G)``, the masked decayed scores ``(Q, Q)`` and
    ``k exp(G_last - G)`` in v's dtype, ``exp(G_last)`` float32."""
    rows, seq = v.shape[:2]
    chunks = seq // chunk
    f32, dtype = jnp.float32, v.dtype
    dot = _dot(dtype)

    # c = chunk, l and s = positions inside it (read at l, written at
    # s), g, r, d = key channels, e = value ones: every product a plain
    # batched matmul
    def heads_first(x, to):   # (B, S, G, ...) -> (B, c, G, ...), Q at ``to``
        return jnp.moveaxis(
            x.reshape(rows, chunks, chunk, *x.shape[2:]), 2, to)

    q, k, v = (heads_first(x, -2) for x in (q, k, v))    # (B, c, G, [R,] Q, .)
    beta = heads_first(beta, -1)                         # (B, c, G, R, Q)
    # log of the decay from the chunk's start to each position, <= 0
    log_decay = jnp.cumsum(heads_first(g, -1), axis=-1)
    span = log_decay[..., :, None] - log_decay[..., None, :]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, span, -jnp.inf))    # (B, c, G, R, l, s)

    kk = dot("bcgld,bcgsd->bcgls", k, k)[:, :, :, None]
    a = jnp.where(jnp.tril(lower, -1),
                  -beta[..., None] * kk * decay, 0.0)
    t = unit_lower_inverse(a)                            # (B, c, G, R, l, s)
    from_start = jnp.exp(log_decay)                      # (B, c, G, R, Q)
    k_heads = k[:, :, :, None].astype(f32)               # (B, c, G, 1, Q, d)
    u = dot("bcgrls,bcgrse->bcgrle", t, beta[..., None] * v.astype(f32))
    w = dot("bcgrls,bcgrsd->bcgrld", t,
            (beta * from_start)[..., None] * k_heads)
    scores = dot("bcgld,bcgsd->bcgls", q, k)[:, :, :, None] * decay
    q_decayed = q[:, :, :, None].astype(f32) * from_start[..., None]
    to_end = jnp.exp(log_decay[..., -1:] - log_decay)    # (B, c, G, R, Q)
    k_decayed = k_heads * to_end[..., None]
    return (*(x.astype(dtype) for x in (u, w, q_decayed, scores, k_decayed)),
            from_start[..., -1])


def _across_chunks(u, w, q_decayed, scores, k_decayed, whole):
    """The state from chunk to chunk and what each chunk makes of the
    one it finds, for the heads it is handed: a scan over the chunks of
    ``_inside_chunks``'s values. Returns (B, chunks, G, R, Q, Dv)."""
    dtype = u.dtype
    dot = _dot(dtype)

    def step(state, of_chunk):
        u_c, w_c, q_c, scores_c, k_c, whole_c = of_chunk
        new = u_c - dot("bgrld,bgrde->bgrle", w_c, state)
        out = dot("bgrld,bgrde->bgrle", q_c, state) \
            + dot("bgrls,bgrse->bgrle", scores_c, new)
        state = state * whole_c[..., None, None] \
            + dot("bgrsd,bgrse->bgrde", k_c, new)
        return state, out.astype(dtype)

    rows, _, groups, per, _, width = u.shape
    _, out = jax.lax.scan(
        step, jnp.zeros((rows, groups, per, w.shape[-1], width),
                        jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (
            u, w, q_decayed, scores, k_decayed, whole)))
    return jnp.moveaxis(out, 0, 1)


@device_scope("delta_rule")
def delta_rule(q, k, v, g, beta, *, chunk_size: int = 64):
    """The gated delta rule in chunks of ``chunk_size``: q, k
    (B, S, Hk, Dk), l2-normed, q scaled; v (B, S, Hv, Dv); g (<= 0) and
    beta (B, S, Hv) float32; value head ``j`` reads key head
    ``j // (Hv / Hk)``. Returns (B, S, Hv, Dv) in v's dtype. A row whose
    length is no multiple is padded at its end with ``g = 0`` and
    ``beta = 0`` (no decay, nothing written) and cut again."""
    rows, seq, key_heads, _ = q.shape
    heads, width = v.shape[2:]
    per = heads // key_heads
    chunk, chunks, pad, at_once = pick_rule(
        rows=rows, seq=seq, key_heads=key_heads, value_heads=heads,
        chunk_size=chunk_size)
    fused = fits(q, v, chunk)
    rule_paths.add(f"kernel[{chunk}x{chunks}{'+pad' if pad else ''}]"
                   if fused else
                   f"chunked[{chunk}x{chunks}{'+pad' if pad else ''},"
                   f"{at_once} heads a pass]")
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    g, beta = (x.astype(jnp.float32) for x in (g, beta))
    if fused:
        from perceiver_tpu.ops.pallas_delta_rule import fused_rule
        return fused_rule(q, k, v, g, beta, chunk=chunk)[:, :seq]
    groups = at_once // per                  # key heads a pass
    passes = key_heads // groups

    def by_pass(x, *tail):   # (B, S, Hk [x R], ...) -> (passes, B, S, G, ...)
        x = x.reshape(*x.shape[:2], passes, groups, *tail)
        return jnp.moveaxis(x, 2, 0)

    def all_passes(fn, operands):
        if passes == 1:
            return jax.tree.map(lambda x: x[None],
                                fn(tuple(x[0] for x in operands)))
        return jax.lax.map(fn, operands)

    operands = (by_pass(q, q.shape[-1]), by_pass(k, k.shape[-1]),
                by_pass(v, per, width), by_pass(g, per), by_pass(beta, per))
    # (passes, B, c, G, R, Q, Dv) -> (B, c, Hk, R, Q, Dv)
    out = all_passes(jax.checkpoint(lambda args: _across_chunks(
        *_inside_chunks(*args, chunk))), operands)
    out = jnp.moveaxis(out, 0, 2).reshape(rows, chunks, key_heads,
                                          *out.shape[4:])
    # (B, c, Hk, R, Q, Dv) -> (B, S, Hv, Dv)
    return jnp.moveaxis(out, 4, 2).reshape(
        rows, seq + pad, heads, width)[:, :seq]


@device_scope("delta_mixer")
def delta_mixer_apply(params, u, *, num_key_heads: int, num_value_heads: int,
                      key_head_dim: int, value_head_dim: int,
                      chunk_size: int = 64, eps: float = 1e-6,
                      policy: Policy = DEFAULT_POLICY):
    """u (B, S, C) -> (B, S, C)."""
    rows, seq, _ = u.shape
    key_dim = num_key_heads * key_head_dim
    value_dim = num_value_heads * value_head_dim
    # named before it is sliced: one buffer for a ``remat`` layer to hold
    qkvz = dear(linear_apply(params["in_proj_qkvz"], u, policy=policy),
                "delta_in")
    qkv, z = jnp.split(qkvz, [2 * key_dim + value_dim], axis=-1)
    # the write strength and the decay stay float32 from the product on
    ba = jnp.einsum(
        "bsc,ch->bsh", policy.cast_compute(u),
        policy.cast_param(params["in_proj_ba"]["w"]),
        precision=(jax.lax.Precision.HIGHEST
                   if policy.compute_dtype == jnp.float32 else None),
        preferred_element_type=jnp.float32)
    b, alpha = jnp.split(ba, 2, axis=-1)
    qkv = jax.nn.silu(causal_conv(params["conv"], qkv))
    q, k, v = jnp.split(qkv, [key_dim, 2 * key_dim], axis=-1)
    q = (l2_norm(q.reshape(rows, seq, num_key_heads, key_head_dim))
         / math.sqrt(key_head_dim)).astype(qkv.dtype)
    k = l2_norm(k.reshape(rows, seq, num_key_heads, key_head_dim)).astype(
        qkv.dtype)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(params["A_log"]["bias"].astype(jnp.float32)) \
        * jax.nn.softplus(alpha + params["dt"]["bias"].astype(jnp.float32))
    o = dear(delta_rule(
        q, k, v.reshape(rows, seq, num_value_heads, value_head_dim), g, beta,
        chunk_size=chunk_size), "delta_out")
    y = gated_head_rms_norm(
        params["norm"], o,
        z.reshape(rows, seq, num_value_heads, value_head_dim), eps, policy)
    return linear_apply(params["out_proj"], y.reshape(rows, seq, value_dim),
                        policy=policy)
