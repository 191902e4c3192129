"""Mamba-2 state-space mixer (Dao & Gu, "Transformers are SSMs",
arXiv:2405.21060; the ``nemotron_h`` layer ``M``) as pure init/apply
functions.

On a row ``u`` of ``S`` positions, ``H`` heads of ``P`` channels, ``G``
groups of state size ``N`` (head ``h`` reads group ``h // (H / G)``)::

    [z, xBC, dt] = u W_in                      (widths HP, HP + 2GN, H)
    xBC = silu(conv(xBC) + b_c)                (causal, depthwise, K taps)
    [x, B, C] = xBC
    dt = softplus(dt + dt_bias);  A = -exp(A_log)            (per head)
    s_t = exp(dt_t A) s_(t-1) + dt_t x_t B_t^T               (P x N a head)
    y_t = s_t C_t + D x_t
    y = grouprms(y * silu(z)) * g              (the gate first; RMS over
                                                each group's HP / G channels)
    out = y W_out

The recurrence is computed in chunks of ``chunk_size`` positions (the
SSD form): inside a chunk as products (``C_l B_s^T`` weighted by the
decay from ``s`` to ``l``, times ``dt x``), between chunks as a carried
state (each chunk's own state, decayed and summed by a scan over the
chunks, read by ``C`` with the decay from the chunk's start). The
exponent of every decay formed is a sum of ``dt A <= 0`` over a span
(``exp`` of a cumulative sum's difference, masked **before** the
``exp`` where the span would run backwards), so no ``exp`` of a
positive number appears and a decay that underflows is a quiet 0. The
decays, ``dt`` and the carried state are float32 whatever the compute
dtype; the products take operands in the compute dtype and accumulate
in float32.

Two executors of that one form, picked a call from what it can
observe (``pick_scan``, as ``ops.attention.pick_attention_core`` picks
a core): the Pallas kernels of ``ops/pallas_ssm.py`` (``fused``: a
chunk's ``Q x Q`` decays and scores and the carried state never leave
VMEM, forward or backward) where the backend is a TPU, the operands lie
on one device and the shapes tile; the einsums below (``chunked``)
everywhere else, for the first reason that holds. Same chunk
boundaries, same arithmetic.

The fused scan's backward is written by hand and takes the scan's
operands alone, so a ``remat`` layer that holds the scan's output
(``dear``, ``ssm_out``) does not run the forward kernel a second time.
The einsum form's backward is autodiff's; it is a ``jax.checkpoint`` of
its own: its residuals (a chunk's ``Q x Q`` decays and scores for every
head) live while its own backward runs and no longer, and with its
output held the layer does not run it again for the layer's sake.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perceiver_tpu.obs.trace import device_scope
from perceiver_tpu.ops.attention import mesh_devices
from perceiver_tpu.ops.initializers import uniform
from perceiver_tpu.ops.linear import linear_apply, linear_init
from perceiver_tpu.ops.policy import DEFAULT_POLICY, Policy
from perceiver_tpu.ops.pallas_short_conv import short_conv
from perceiver_tpu.ops.remat import dear
from perceiver_tpu.ops.tally import Tally

#: which executor the scan took at each call site: ``fused[128x32]`` (32
#: chunks of 128 positions, the kernels), ``chunked[16x3+pad,backend]``
#: (the einsums, the last chunk padded, and why not the kernels)
scan_paths = Tally()

#: why a call site took the einsums, in the order checked
CHUNKED_REASONS = ("backend", "mesh", "shape")


def pick_scan(*, backend: str, mesh_devices: int, chunk: int, state: int,
              heads_per_group: int, head_dim: int):
    """``("fused", None)`` or ``("chunked", reason)``, from what the
    call site can observe."""
    from perceiver_tpu.ops.pallas_ssm import fits
    if backend != "tpu":
        reason = "backend"
    elif mesh_devices > 1:
        # a Pallas call has no partitioning rule
        reason = "mesh"
    elif not fits(chunk=chunk, state=state, per=heads_per_group,
                  width=head_dim):
        reason = "shape"
    else:
        return "fused", None
    return "chunked", reason


def _backend() -> str:
    """The backend the pick reads (a seam: a test that says ``tpu``
    here gets the kernels, interpreted)."""
    return jax.default_backend()


def ssm_mixer_init(key, dim: int, *, num_heads: int, head_dim: int,
                   n_groups: int, state_size: int, conv_kernel: int = 4,
                   dt_min: float = 1e-3, dt_max: float = 0.1,
                   dt_floor: float = 1e-4, dtype=jnp.float32):
    """The published initialisation: ``dt`` log-uniform in ``[dt_min,
    dt_max]`` (``dt_bias`` its inverse softplus), ``A`` uniform in
    ``[1, 16]`` (``A_log`` its log), ``D`` and the norm's scale 1, the
    convolution as torch's ``Conv1d`` (uniform, fan-in its taps)."""
    inner = num_heads * head_dim
    conv_dim = inner + 2 * n_groups * state_size
    k_in, k_conv, k_dt, k_a, k_out = jax.random.split(key, 5)
    dt = jnp.exp(jax.random.uniform(k_dt, (num_heads,), dtype)
                 * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
    dt = jnp.maximum(dt, dt_floor)
    return {
        "in_proj": linear_init(k_in, dim, inner + conv_dim + num_heads,
                               dtype, bias=False),
        "conv": {"w": uniform(k_conv, (conv_kernel, conv_dim),
                              1.0 / math.sqrt(conv_kernel), dtype),
                 "bias": jnp.zeros((conv_dim,), dtype)},
        # softplus(dt_bias) = dt
        "dt": {"bias": dt + jnp.log(-jnp.expm1(-dt))},
        "A_log": {"bias": jnp.log(jax.random.uniform(
            k_a, (num_heads,), dtype, 1.0, 16.0))},
        "D": {"scale": jnp.ones((num_heads,), dtype)},
        "norm": {"scale": jnp.ones((inner,), dtype)},
        "out_proj": linear_init(k_out, inner, dim, dtype, bias=False),
    }


def causal_conv(params, x):
    """Depthwise convolution over the last ``K`` positions, with bias
    where the tree has one: ``out[t] = sum_k w[k] x[t - (K - 1) + k] +
    b``; ``x`` (B, S, C), ``w`` (K, C). float32 sums, ``x``'s dtype
    out."""
    w = params["w"].astype(jnp.float32)
    taps, seq = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).astype(jnp.float32)
    out = params["bias"].astype(jnp.float32) if "bias" in params else 0.0
    for k in range(taps):
        out = out + w[k] * padded[:, k:k + seq]
    return out.astype(x.dtype)


def gated_group_rms_norm(params, y, z, groups: int, eps: float,
                         policy: Policy = DEFAULT_POLICY):
    """``grouprms(y * silu(z)) * scale``: the gate first, then RMSNorm
    over each of the ``groups`` groups of channels; float32 inside."""
    v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = v.reshape(*v.shape[:-1], groups, -1)
    rstd = jax.lax.rsqrt(jnp.mean(jnp.square(grouped), -1, keepdims=True)
                         + eps)
    out = (grouped * rstd).reshape(v.shape) \
        * params["scale"].astype(jnp.float32)
    return out.astype(policy.compute_dtype)


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _chunked_scan(x, dt, a, b, c, chunk: int):
    """``y_t = C_t s_t`` of the recurrence above, without ``D x``.
    x (B, S, H, P) in the compute dtype; dt (B, S, H) float32, >= 0;
    a (H,) float32, < 0; b, c (B, S, G, N). S a multiple of ``chunk``.
    Returns (B, S, H, P) in x's dtype."""
    rows, seq, heads, width = x.shape
    groups, state = b.shape[2:]
    per, chunks = heads // groups, seq // chunk
    f32, dtype = jnp.float32, x.dtype

    def dot(spec, lhs, rhs):
        return jnp.einsum(spec, lhs, rhs, preferred_element_type=f32)

    # (B, chunks, Q, G, heads a group, ...): c = chunk, l and s =
    # positions inside it (read at l, written at s), g, r, p, n
    dt = dt.reshape(rows, chunks, chunk, groups, per)
    xdt = x.reshape(rows, chunks, chunk, groups, per, width).astype(f32) \
        * dt[..., None]
    b = b.reshape(rows, chunks, chunk, groups, state)
    c = c.reshape(rows, chunks, chunk, groups, state)
    # log of the decay from the chunk's start to each position, <= 0
    log_decay = jnp.cumsum(dt * a.reshape(groups, per), axis=2)
    log_decay = jnp.moveaxis(log_decay, 2, -1)           # (B, c, G, r, Q)

    # inside a chunk: position l reads what position s <= l wrote
    span = log_decay[..., :, None] - log_decay[..., None, :]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, span, -jnp.inf))   # (B, c, G, r, l, s)
    scores = dot("bclgn,bcsgn->bcgls", c, b)[:, :, :, None] * decay
    y = dot("bcgrls,bcsgrp->bclgrp", scores.astype(dtype),
            xdt.astype(dtype))

    # each chunk's own state at its end: what its positions wrote,
    # decayed to the chunk's last position
    to_end = jnp.exp(log_decay[..., -1:] - log_decay)    # (B, c, G, r, s)
    written = xdt * jnp.moveaxis(to_end, -1, 2)[..., None]
    own = dot("bcsgrp,bcsgn->bcgrpn", written.astype(dtype), b)

    # carried between chunks: the state a chunk finds at its start
    whole = jnp.exp(log_decay[..., -1])                  # (B, c, G, r)

    def carry(state_in, chunk_own):
        own_c, whole_c = chunk_own
        return state_in * whole_c[..., None, None] + own_c, state_in

    _, found = jax.lax.scan(
        carry, jnp.zeros((rows, groups, per, width, state), f32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(whole, 1, 0)))
    found = jnp.moveaxis(found, 0, 1)                    # (B, c, G, r, p, n)
    from_start = jnp.moveaxis(jnp.exp(log_decay), -1, 2)  # (B, c, l, G, r)
    y = y + dot("bclgn,bcgrpn->bclgrp", c, found.astype(dtype)) \
        * from_start[..., None]
    return y.reshape(rows, seq, heads, width).astype(dtype)


@device_scope("ssm_scan")
def ssm_scan(x, dt, a, b, c, *, chunk_size: int):
    """The selective scan in chunks of ``chunk_size``, on the executor
    ``pick_scan`` names; a row whose length is no multiple is padded at
    its end with ``dt = 0`` (no decay, nothing written) and cut
    again."""
    seq = x.shape[1]
    chunk = min(chunk_size, seq)
    pad = -seq % chunk
    path, reason = pick_scan(
        backend=_backend(), mesh_devices=mesh_devices(x), chunk=chunk,
        state=b.shape[3], heads_per_group=x.shape[2] // b.shape[2],
        head_dim=x.shape[3])
    scan_paths.add(f"{path}[{chunk}x{(seq + pad) // chunk}"
                   f"{'+pad' if pad else ''}{',' + reason if reason else ''}]")
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                               * (v.ndim - 2)) for v in (x, dt, b, c))
    if path == "fused":
        from perceiver_tpu.ops.pallas_ssm import fused_scan
        return fused_scan(x, dt, a, b, c, chunk=chunk)[:, :seq]
    return _chunked_scan(x, dt, a, b, c, chunk)[:, :seq]


@device_scope("ssm_mixer")
def ssm_mixer_apply(params, u, *, num_heads: int, head_dim: int,
                    n_groups: int, state_size: int, chunk_size: int = 128,
                    eps: float = 1e-5, policy: Policy = DEFAULT_POLICY):
    """u (B, S, C) -> (B, S, C)."""
    rows, seq, _ = u.shape
    inner, bc = num_heads * head_dim, n_groups * state_size
    # named before it is sliced: one buffer for a ``remat`` layer to hold
    zxbcdt = dear(linear_apply(params["in_proj"], u, policy=policy),
                  "ssm_in")
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc], axis=-1)
    x, b, c = short_conv([params["conv"]], xbc, rest=(inner, bc, bc),
                         cut_from=(zxbcdt, inner))
    x = x.reshape(rows, seq, num_heads, head_dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt"]["bias"].astype(jnp.float32))
    a = -jnp.exp(params["A_log"]["bias"].astype(jnp.float32))
    y = dear(ssm_scan(x, dt, a,
                      b.reshape(rows, seq, n_groups, state_size),
                      c.reshape(rows, seq, n_groups, state_size),
                      chunk_size=chunk_size), "ssm_out")
    y = y.astype(jnp.float32) + x.astype(jnp.float32) \
        * params["D"]["scale"].astype(jnp.float32)[:, None]
    y = gated_group_rms_norm(params["norm"], y.reshape(rows, seq, inner), z,
                             n_groups, eps, policy)
    return linear_apply(params["out_proj"], y, policy=policy)
