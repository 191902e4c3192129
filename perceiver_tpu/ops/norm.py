"""LayerNorm and RMSNorm as pure init/apply functions.

Statistics are computed in fp32 regardless of the compute dtype —
bf16 mean/variance accumulation loses precision the MXU gains nothing
from, and XLA fuses the fp32 reduce into surrounding ops anyway.

The backward pass is a custom VJP that saves the *input* (compute
dtype) plus the fp32 ``(mean, rstd)`` statistics and recomputes the
normalized values, instead of letting autodiff save the fp32
intermediates of the forward chain. On the B=512 headline step those
autodiff residuals are full fp32 copies of every normed activation,
stacked per layer through the encoder's scans — one of the named
HBM sinks in the round-5 trace. The recompute is one fused
elementwise pass; the saved bytes drop from 3 fp32 tensors to one
compute-dtype tensor and two scalar-per-row statistics.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.lax
import jax.numpy as jnp

from perceiver_tpu.ops.policy import Policy, DEFAULT_POLICY


def layer_norm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ln_core(eps, out_dtype, scale, bias, x):
    """(x - mean) * rsqrt(var + eps) * scale + bias, fp32 statistics."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = (y * scale.astype(jnp.float32) + bias.astype(jnp.float32))
    return y.astype(out_dtype)


def _ln_fwd(eps, out_dtype, scale, bias, x):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (xf - mean) * rstd
    y = (xhat * scale.astype(jnp.float32) + bias.astype(jnp.float32))
    # residuals: the input in its own (compute) dtype + per-row fp32
    # stats — NOT the fp32 normalized copies autodiff would save
    return y.astype(out_dtype), (scale, x, mean, rstd)


def _ln_bwd(eps, out_dtype, res, g):
    scale, x, mean, rstd = res
    gf = g.astype(jnp.float32)
    xhat = (x.astype(jnp.float32) - mean) * rstd
    dscale = jnp.sum(gf * xhat, axis=tuple(range(g.ndim - 1)))
    dbias = jnp.sum(gf, axis=tuple(range(g.ndim - 1)))
    gy = gf * scale.astype(jnp.float32)
    dx = rstd * (gy - jnp.mean(gy, axis=-1, keepdims=True)
                 - xhat * jnp.mean(gy * xhat, axis=-1, keepdims=True))
    return (dscale.astype(scale.dtype), dbias.astype(scale.dtype),
            dx.astype(x.dtype))


_ln_core.defvjp(_ln_fwd, _ln_bwd)


def layer_norm_apply(params, x, eps: float = 1e-5,
                     policy: Policy = DEFAULT_POLICY):
    return _ln_core(eps, policy.compute_dtype, params["scale"],
                    params["bias"], x)


# --- RMSNorm -----------------------------------------------------------------
# x / sqrt(mean(x^2) + eps) * scale: no mean subtracted, no bias. In the
# zero-centred form (``qwen3_next``) the parameter is what the
# multiplier departs from 1 by, x / sqrt(mean(x^2) + eps) * (1 + w),
# initialised 0 and pulled to 0 by weight decay: a tree that holds
# ``bias`` (that departure) and no ``scale``.
# Statistics in fp32 as above. No custom VJP: its callers recompute a
# whole layer on the backward pass (``remat``), so autodiff's fp32
# residuals live for one layer at a time; a trace has not asked for
# more.


def rms_norm_init(dim: int, dtype=jnp.float32, zero_centered: bool = False):
    if zero_centered:
        return {"bias": jnp.zeros((dim,), dtype)}
    return {"scale": jnp.ones((dim,), dtype)}


def rms_norm_apply(params, x, eps: float = 1e-6,
                   policy: Policy = DEFAULT_POLICY):
    xf = x.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                         + eps)
    scale = params["scale"].astype(jnp.float32) if "scale" in params \
        else 1.0 + params["bias"].astype(jnp.float32)
    return (xf * rstd * scale).astype(policy.compute_dtype)
