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
128 and q, k, v share a compute dtype the kernels take, the rule is
Pallas kernels (``kernel[64x64]`` in ``rule_paths``: a chunk's ``Q x Q``
matrices, its inverse and the carried state stay in VMEM, forward and
backward; interpreted off a TPU): ``ops/pallas_delta_rule.py``'s for a
decay that is a number a head, ``ops/pallas_kda_rule.py``'s for one
that is a vector (below; ``kernel[64x64, by channel]``, and a value
head a key head); everywhere else it is the einsums below
(``chunked[...]``), which are the kernels' oracle in the tests. A row is
padded to whole chunks either way.

**A decay that is a vector** (Kimi Delta Attention, arXiv:2510.26692;
the layer ``K`` of ``models/hybrid_lm.py``, ``kda_mixer_*`` below): ``g``
of ``(B, S, Hv, Dk)``, one number a head **and key channel**, and the
state decays as ``S_t = Diag(exp(g_t)) S_(t-1)`` before the write. The
algebra above holds with ``G_i`` a vector a position, ``exp(G)`` and
``exp(G_last - G)`` scaling ``q`` and ``k`` a channel, but
``exp(G_i - G_j)`` no longer comes out of the sum over channels as a
``Q x Q`` mask: ``A_ij = -beta_i sum_d k_id k_jd exp(G_id - G_jd)``. It
does not factor into ``exp(G_i) exp(-G_j)`` either without the exponent
of a positive number, so a chunk is cut into sub-blocks of ``SUB_BLOCK``
positions (``_inside_chunks_by_channel``): a pair in two sub-blocks
splits its span at the later one's first row ``r``
(``exp(G_i - G_r) exp(G_r - G_j)``, both at most 1; one decayed copy of
``k`` a sub-block, a matrix product), a pair inside one sub-block takes
its ``16 x 16 x Dk`` spans outright (masked before the ``exp``, summed
over the channels in float32). ``g``'s shape says which runs; the
inverse, the scan over the chunks and the passes of heads are the
same. The einsum form (``chunked[..., by channel]`` in ``rule_paths``,
scope ``kda_rule`` either way) takes fewer heads a pass
(``PASS_HEAD_CHUNKS_BY_CHANNEL``: a sub-block's spans for every head
and chunk of a 4 x 4,096 row are 134 MB a head) and is 297 kinds of
small XLA operation at the cell's shapes, 523 ms a step over four
layers: the spans go through HBM a dozen times (PERF.md, PR 44). The
kernels (PR 45) keep the same split, the spans on the vector units in
VMEM, and make the running sum of ``g`` themselves.

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
cotangent; the inverse's cotangent is ``T^T dT T^T``; a vector decay's
log-decay takes ``q dq + k (dk_plus - dk_minus)``, no span of its own),
so neither the checkpoint a pass nor the ``lax.map`` over the passes is
there for them. Either way a
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
from perceiver_tpu.ops.pallas_short_conv import short_conv
from perceiver_tpu.ops.tally import Tally

#: which form the rule took at each call site: ``kernel[64x64]`` (64
#: chunks of 64 positions, the Pallas kernels) or
#: ``chunked[64x64,8 heads a pass]`` (the einsums, eight value heads a
#: checkpointed pass), ``+pad`` after the chunks where the last one is
#: padded, ``, by channel`` last where the decay is a vector a key
#: channel (``kernel[64x64, by channel]``: ``ops/pallas_kda_rule.py``'s)
rule_paths = Tally()

# head-chunks (rows x chunks x value heads) a pass may hold: each has a
# dozen Q x Q float32 matrices alive in its backward, 16 KB each at 64
PASS_HEAD_CHUNKS = 2048
# ... where the decay is a vector a channel: a head-chunk's spans inside
# its sub-blocks are Q x SUB_BLOCK x Dk float32 (512 KB at 64 x 16 x
# 128), and autodiff keeps three or four such
PASS_HEAD_CHUNKS_BY_CHANNEL = 512
# positions a sub-block of a chunk whose decay is a vector
SUB_BLOCK = 16


def pick_rule(*, rows: int, seq: int, key_heads: int, value_heads: int,
              chunk_size: int, by_channel: bool = False):
    """``(chunk, chunks, pad, heads a pass)``, from what the call site
    can observe: the chunk is ``chunk_size`` or the whole of a shorter
    row, the last chunk padded; the value heads go through in passes of
    whole key heads, the most whose head-chunks stay under
    ``PASS_HEAD_CHUNKS`` (never fewer than one key head's;
    ``PASS_HEAD_CHUNKS_BY_CHANNEL`` where the decay is a vector a
    channel, ``by_channel``)."""
    chunk = min(chunk_size, seq)
    pad = -seq % chunk
    chunks = (seq + pad) // chunk
    per = value_heads // key_heads
    most = PASS_HEAD_CHUNKS_BY_CHANNEL if by_channel else PASS_HEAD_CHUNKS
    groups = max(1, min(key_heads, most // max(1, rows * chunks * per)))
    while key_heads % groups:
        groups -= 1
    return chunk, chunks, pad, groups * per


def fits(q, v, chunk: int, g=None) -> bool:
    """Whether a call takes the Pallas kernels, from what the call site
    can observe: operands on one device (a Pallas call has no
    partitioning rule), heads of whole lanes, a chunk the kernels'
    inverse merges, q and v in one compute dtype the kernels take; and
    which kernels is ``g``'s to say: a number a head ((B, S, Hv), or
    not given) ``ops/pallas_delta_rule.py``'s, which pull the decay out
    of the sum over the channels; a number a head and key channel
    ((B, S, Hv, Dk)) ``ops/pallas_kda_rule.py``'s, which also want a
    value head a key head."""
    from perceiver_tpu.ops import pallas_delta_rule, pallas_kda_rule
    if mesh_devices(q) != 1 or q.dtype != v.dtype:
        return False
    sizes = dict(chunk=chunk, key_dim=q.shape[3], value_dim=v.shape[3],
                 dtype=v.dtype)
    if g is None or g.ndim == 3:
        return pallas_delta_rule.fits(**sizes)
    return pallas_kda_rule.fits(**sizes, heads=q.shape[2],
                                value_heads=v.shape[2])


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
                        policy: Policy = DEFAULT_POLICY, gate=jax.nn.silu):
    """``rms(o) * scale * gate(z)`` over each head's channels (the last
    axis), one scale for all the heads: the norm first, then the gate
    (a Mamba-2 mixer gates first: ``ops.ssm.gated_group_rms_norm``),
    ``silu`` or, in a KDA mixer, ``sigmoid``; float32 inside."""
    o = o.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
    out = o * rstd * params["scale"].astype(jnp.float32) \
        * gate(z.astype(jnp.float32))
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


def _heads_first(x, chunk: int, to: int):
    """(B, S, G, ...) -> (B, chunks, G, ...) with a chunk's positions
    at axis ``to``: head-major, every product a plain batched matmul."""
    rows, seq = x.shape[:2]
    return jnp.moveaxis(
        x.reshape(rows, seq // chunk, chunk, *x.shape[2:]), 2, to)


def _inside_chunks(q, k, v, g, beta, chunk: int):
    """What a chunk computes without the state it finds, for some of
    the heads and all chunks at once. q, k (B, S, G, Dk) in the compute
    dtype, a key head each; v (B, S, G, R, Dv), ``R`` value heads a key
    head; g (<= 0), beta (B, S, G, R) float32. ``S`` a multiple of
    ``chunk``. Head-major, (B, chunks, G, R, Q, ...): ``U``, ``W``,
    ``q exp(G)``, the masked decayed scores ``(Q, Q)`` and
    ``k exp(G_last - G)`` in v's dtype, ``exp(G_last)`` float32."""
    f32, dtype = jnp.float32, v.dtype
    dot = _dot(dtype)

    # c = chunk, l and s = positions inside it (read at l, written at
    # s), g, r, d = key channels, e = value ones: every product a plain
    # batched matmul
    q, k, v = (_heads_first(x, chunk, -2)
               for x in (q, k, v))                       # (B, c, G, [R,] Q, .)
    beta = _heads_first(beta, chunk, -1)                 # (B, c, G, R, Q)
    # log of the decay from the chunk's start to each position, <= 0
    log_decay = jnp.cumsum(_heads_first(g, chunk, -1), axis=-1)
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


def _inside_chunks_by_channel(q, k, v, g, beta, chunk: int):
    """``_inside_chunks`` where the decay is a number a key channel:
    g (<= 0) (B, S, G, R, Dk), everything else as there, and so is what
    comes back but ``exp(G_last)``, (B, chunks, G, R, Dk). The decayed
    products ``sum_d x_id k_jd exp(G_id - G_jd)`` (``x`` = k for ``A``,
    q for the scores) go by sub-blocks of ``SUB_BLOCK`` positions (of
    what divides a shorter chunk): a sub-block against the positions
    before it as a matrix product of ``x exp(G - G_r)`` and
    ``k exp(G_r - G_j)``, ``r`` the sub-block's first row; a sub-block
    against itself from its spans outright, a sum over the channels in
    float32. Every exponent is a sum of ``g`` over a span that runs
    forwards, masked before the ``exp`` where it would not."""
    sub = math.gcd(chunk, SUB_BLOCK)
    blocks = chunk // sub
    f32, dtype = jnp.float32, v.dtype
    dot = _dot(dtype)

    # as ``_inside_chunks``; n = sub-block, i and j = positions inside it
    def by_block(x):          # (..., Q, D) -> (..., n, i, D)
        return x.reshape(*x.shape[:-2], blocks, sub, x.shape[-1])

    q, k, v = (_heads_first(x, chunk, -2)
               for x in (q, k, v))                       # (B, c, G, [R,] Q, .)
    beta = _heads_first(beta, chunk, -1)                 # (B, c, G, R, Q)
    # log of the decay from the chunk's start to each position, <= 0
    log_decay = jnp.cumsum(_heads_first(g, chunk, -2),
                           axis=-2)                      # (B, c, G, R, Q, d)
    q_heads, k_heads = (x[:, :, :, None].astype(f32) for x in (q, k))
    # ... and from a sub-block's first row to each of its positions
    first = by_block(log_decay)[..., :1, :]             # (B, c, G, R, n, 1, d)
    local = by_block(log_decay) - first                 # (B, c, G, R, n, i, d)
    # k as a sub-block's first row finds it, the positions before it
    before = (jnp.arange(chunk)[None, :]
              < sub * jnp.arange(blocks)[:, None])[..., None]   # (n, Q, 1)
    k_found = k_heads[..., None, :, :] * jnp.exp(jnp.where(
        before, first - log_decay[..., None, :, :], -jnp.inf))
    # k inside a sub-block as each of its later positions finds it
    inside = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    k_inside = by_block(k_heads)[..., None, :, :] * jnp.exp(jnp.where(
        inside, local[..., :, None, :] - local[..., None, :, :], -jnp.inf))
    diagonal = jnp.eye(blocks, dtype=f32)[:, None, :, None]

    def decayed_products(x):   # (B, c, G, 1, Q, d) -> (B, c, G, R, Q, Q)
        earlier = dot("bcgrnid,bcgrnjd->bcgrnij",
                      by_block(x) * jnp.exp(local), k_found)
        own = jnp.sum(by_block(x)[..., None, :] * k_inside, -1)
        return (earlier.reshape(*earlier.shape[:-1], blocks, sub)
                + own[..., None, :] * diagonal).reshape(
                    *earlier.shape[:4], chunk, chunk)

    strictly_lower = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = jnp.where(strictly_lower,
                  -beta[..., None] * decayed_products(k_heads), 0.0)
    t = unit_lower_inverse(a)                            # (B, c, G, R, l, s)
    from_start = jnp.exp(log_decay)                      # (B, c, G, R, Q, d)
    u = dot("bcgrls,bcgrse->bcgrle", t, beta[..., None] * v.astype(f32))
    w = dot("bcgrls,bcgrsd->bcgrld", t,
            beta[..., None] * from_start * k_heads)
    scores = decayed_products(q_heads)
    q_decayed = q_heads * from_start
    k_decayed = k_heads * jnp.exp(log_decay[..., -1:, :] - log_decay)
    return (*(x.astype(dtype) for x in (u, w, q_decayed, scores, k_decayed)),
            from_start[..., -1, :])


def _across_chunks(u, w, q_decayed, scores, k_decayed, whole):
    """The state from chunk to chunk and what each chunk makes of the
    one it finds, for the heads it is handed: a scan over the chunks of
    ``_inside_chunks``'s values (``whole`` a number a head-chunk) or
    ``_inside_chunks_by_channel``'s (a vector, one a key channel).
    Returns (B, chunks, G, R, Q, Dv)."""
    dtype = u.dtype
    dot = _dot(dtype)
    by_channel = whole.ndim == 5

    def step(state, of_chunk):
        u_c, w_c, q_c, scores_c, k_c, whole_c = of_chunk
        new = u_c - dot("bgrld,bgrde->bgrle", w_c, state)
        out = dot("bgrld,bgrde->bgrle", q_c, state) \
            + dot("bgrls,bgrse->bgrle", scores_c, new)
        state = state * (whole_c[..., None] if by_channel
                         else whole_c[..., None, None]) \
            + dot("bgrsd,bgrse->bgrde", k_c, new)
        return state, out.astype(dtype)

    rows, _, groups, per, _, width = u.shape
    _, out = jax.lax.scan(
        step, jnp.zeros((rows, groups, per, w.shape[-1], width),
                        jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (
            u, w, q_decayed, scores, k_decayed, whole)))
    return jnp.moveaxis(out, 0, 1)


def delta_rule(q, k, v, g, beta, *, chunk_size: int = 64):
    """The gated delta rule in chunks of ``chunk_size``: q, k
    (B, S, Hk, Dk), l2-normed, q scaled; v (B, S, Hv, Dv); beta
    (B, S, Hv) float32; g (<= 0) float32, (B, S, Hv), a number a head
    (scope ``delta_rule``), or (B, S, Hv, Dk), a number a head and key
    channel (scope ``kda_rule``); value head ``j`` reads key head
    ``j // (Hv / Hk)``. Returns (B, S, Hv, Dv) in v's dtype. A row whose
    length is no multiple is padded at its end with ``g = 0`` and
    ``beta = 0`` (no decay, nothing written) and cut again."""
    with device_scope("delta_rule" if g.ndim == 3 else "kda_rule"):
        return _rule(q, k, v, g, beta, chunk_size)


def _rule(q, k, v, g, beta, chunk_size: int):
    rows, seq, key_heads, _ = q.shape
    heads, width = v.shape[2:]
    per = heads // key_heads
    by_channel = g.ndim == 4
    chunk, chunks, pad, at_once = pick_rule(
        rows=rows, seq=seq, key_heads=key_heads, value_heads=heads,
        chunk_size=chunk_size, by_channel=by_channel)
    fused = fits(q, v, chunk, g)
    rule_paths.add(f"kernel[{chunk}x{chunks}{'+pad' if pad else ''}"
                   f"{', by channel' if by_channel else ''}]"
                   if fused else
                   f"chunked[{chunk}x{chunks}{'+pad' if pad else ''},"
                   f"{at_once} heads a pass"
                   f"{', by channel' if by_channel else ''}]")
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    g, beta = (x.astype(jnp.float32) for x in (g, beta))
    if fused:
        from perceiver_tpu.ops import pallas_delta_rule, pallas_kda_rule
        kernels = pallas_kda_rule if by_channel else pallas_delta_rule
        return kernels.fused_rule(q, k, v, g, beta, chunk=chunk)[:, :seq]
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
                by_pass(v, per, width), by_pass(g, per, *g.shape[3:]),
                by_pass(beta, per))
    inside = _inside_chunks_by_channel if by_channel else _inside_chunks
    # (passes, B, c, G, R, Q, Dv) -> (B, c, Hk, R, Q, Dv)
    out = all_passes(jax.checkpoint(lambda args: _across_chunks(
        *inside(*args, chunk))), operands)
    out = jnp.moveaxis(out, 0, 2).reshape(rows, chunks, key_heads,
                                          *out.shape[4:])
    # (B, c, Hk, R, Q, Dv) -> (B, S, Hv, Dv)
    return jnp.moveaxis(out, 4, 2).reshape(
        rows, seq + pad, heads, width)[:, :seq]


def _float32_product(x, w, policy: Policy):
    """``x w`` on operands in the compute dtype, float32 from the sum
    on (``HIGHEST`` under a float32 policy): what a decay or a write
    strength is made from."""
    return jnp.einsum(
        "bsc,ch->bsh", policy.cast_compute(x), policy.cast_param(w),
        precision=(jax.lax.Precision.HIGHEST
                   if policy.compute_dtype == jnp.float32 else None),
        preferred_element_type=jnp.float32)


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
    ba = _float32_product(u, params["in_proj_ba"]["w"], policy)
    b, alpha = jnp.split(ba, 2, axis=-1)
    q, k, v = short_conv([params["conv"]], qkv, head_dim=key_head_dim,
                         scaled=key_dim, normed=key_dim, cut_from=(qkvz, 0))
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


# --- Kimi Delta Attention ----------------------------------------------------
# The layer ``K`` of ``models/hybrid_lm.py`` (Kimi Linear, arXiv:2510.26692):
# ``H`` heads of ``D`` channels for q, k and v alike, a projection and a
# convolution each, the decay a vector a head from a low-rank projection
# (rank ``D``), the output gate from another, a sigmoid-gated norm::
#
#     q, k, v = silu(conv(u W_q)), silu(conv(u W_k)), silu(conv(u W_v))
#     q = l2norm(q) / sqrt(D);  k = l2norm(k)             (a head's channels)
#     g = -exp(A_log[h]) softplus((u W_fa) W_fb + dt_bias)   (float32; a
#                                       number a head and key channel)
#     beta = sigmoid(u W_b)                                  (a head)
#     S' = Diag(exp(g_t)) S_(t-1);  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
#     o_t = S_t^T q_t
#     out = (rms(o) * scale * sigmoid((u W_ga) W_gb)) W_o
#
# The three projections are one product over the matrices side by side
# (the same function). The convolutions are three trees and one call of
# ``short_conv``: where its kernels run (``ops/pallas_short_conv.py``) each
# of q, k and v is a pass that reads its third of the product where it
# lies and holds nothing float32 in HBM; as XLA's operations they stay
# three convolutions, because one's backward holds its taps' float32
# copies of its input, 768 MB each over all 12,288 channels of a
# 4 x 4,096 row where a third at a time is 256 (PERF.md, PR 44).
# A ``remat`` layer is offered the rule's output (``delta_out``) and not
# the projection's product: at 602 M parameters the five-layer stack's
# step did not fit a 16 GB chip with four such 384 MB buffers held
# (a described-v5e compile: 16.0 GB of 15.75; PERF.md, PR 44), and
# making the product again is 1.3% of the step's operations; the memory
# the rule's kernels freed since (PR 45) is recorded, not spent.


def kda_mixer_init(key, dim: int, *, num_heads: int, head_dim: int,
                   conv_kernel: int = 4, dtype=jnp.float32):
    """As ``delta_mixer_init``: ``A`` uniform in ``(0, 16)``, ``dt_bias``
    (a number a channel) and the norm's scale 1, the convolutions as
    torch's ``Conv1d`` without bias."""
    width = num_heads * head_dim
    keys = jax.random.split(key, 13)
    params = {name: linear_init(k, dim, width, dtype, bias=False)
              for name, k in zip(("q", "k", "v"), keys)}
    params.update({
        f"{name}_conv": {"w": uniform(k, (conv_kernel, width),
                                      1.0 / math.sqrt(conv_kernel), dtype)}
        for name, k in zip(("q", "k", "v"), keys[3:])})
    params.update(
        f_a=linear_init(keys[6], dim, head_dim, dtype, bias=False),
        f_b=linear_init(keys[7], head_dim, width, dtype, bias=False),
        g_a=linear_init(keys[8], dim, head_dim, dtype, bias=False),
        g_b=linear_init(keys[9], head_dim, width, dtype, bias=False),
        beta=linear_init(keys[10], dim, num_heads, dtype, bias=False),
        dt={"bias": jnp.ones((width,), dtype)},
        A_log={"bias": jnp.log(jax.random.uniform(
            keys[11], (num_heads,), dtype, 1e-3, 16.0))},
        norm={"scale": jnp.ones((head_dim,), dtype)},
        out=linear_init(keys[12], width, dim, dtype, bias=False))
    return params


@device_scope("kda_mixer")
def kda_mixer_apply(params, u, *, num_heads: int, head_dim: int,
                    chunk_size: int = 64, eps: float = 1e-6,
                    policy: Policy = DEFAULT_POLICY):
    """u (B, S, C) -> (B, S, C)."""
    rows, seq, _ = u.shape
    heads = (rows, seq, num_heads, head_dim)
    width = num_heads * head_dim
    names = ("q", "k", "v")
    qkv = linear_apply(
        {"w": jnp.concatenate([params[n]["w"] for n in names], axis=1)},
        u, policy=policy)
    q, k, v = short_conv([params[f"{n}_conv"] for n in names], qkv,
                         head_dim=head_dim, scaled=width, normed=width)
    # the decay and the write strength stay float32 from the product on
    alpha = _float32_product(
        _float32_product(u, params["f_a"]["w"], policy),
        params["f_b"]["w"], policy)
    g = -jnp.exp(params["A_log"]["bias"].astype(jnp.float32))[:, None] \
        * jax.nn.softplus(alpha + params["dt"]["bias"].astype(
            jnp.float32)).reshape(heads)
    beta = jax.nn.sigmoid(_float32_product(u, params["beta"]["w"], policy))
    o = dear(delta_rule(q, k, v, g, beta, chunk_size=chunk_size),
             "delta_out")
    gate = linear_apply(params["g_b"], linear_apply(
        params["g_a"], u, policy=policy), policy=policy)
    y = gated_head_rms_norm(params["norm"], o, gate.reshape(heads), eps,
                            policy, gate=jax.nn.sigmoid)
    return linear_apply(params["out"], y.reshape(rows, seq, -1),
                        policy=policy)
