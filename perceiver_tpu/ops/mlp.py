"""Transformer MLP block.

Reference semantics (``perceiver/model.py:20-26``): LayerNorm →
Linear(C→H) → GELU → Linear(H→C) where H == C — the reference uses **no
4× expansion**; hidden width equals channel width. ``widening_factor``
keeps that default while allowing larger configs.

GELU is the exact (erf) variant the reference's ``nn.GELU()`` uses,
wrapped in a custom VJP: XLA evaluates ``erf`` on bf16 inputs by
upcasting to fp32, and autodiff then saves that fp32 upcast as a
residual — stacked per layer through the encoder's scans, it was one
of the fp32 activation copies the round-5 trace flagged. The custom
rule saves only the bf16 input and recomputes the erf/pdf pair in the
backward pass (one fused elementwise pass).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perceiver_tpu.obs.trace import device_scope
from perceiver_tpu.ops.linear import linear_init, linear_apply
from perceiver_tpu.ops.norm import layer_norm_init, layer_norm_apply
from perceiver_tpu.ops.policy import Policy, DEFAULT_POLICY
from perceiver_tpu.ops.remat import dear

_INV_SQRT2 = 1.0 / math.sqrt(2.0)
_INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)


@jax.custom_vjp
def gelu_exact(x):
    """x · Φ(x) with Φ the exact normal CDF (erf), fp32 internally."""
    xf = x.astype(jnp.float32)
    return (0.5 * xf * (1.0 + jax.lax.erf(xf * _INV_SQRT2))).astype(x.dtype)


def _gelu_fwd(x):
    return gelu_exact(x), x


def _gelu_bwd(x, g):
    xf = x.astype(jnp.float32)
    cdf = 0.5 * (1.0 + jax.lax.erf(xf * _INV_SQRT2))
    pdf = _INV_SQRT_2PI * jnp.exp(-0.5 * xf * xf)
    return ((cdf + xf * pdf) * g.astype(jnp.float32)).astype(x.dtype),


gelu_exact.defvjp(_gelu_fwd, _gelu_bwd)


def mlp_init(key, dim: int, widening_factor: int = 1, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    hidden = dim * widening_factor
    return {
        "norm": layer_norm_init(dim, dtype),
        "fc1": linear_init(k1, dim, hidden, dtype),
        "fc2": linear_init(k2, hidden, dim, dtype),
    }


@device_scope("mlp")
def mlp_apply(params, x, policy: Policy = DEFAULT_POLICY):
    h = layer_norm_apply(params["norm"], x, policy=policy)
    h = dear(linear_apply(params["fc1"], h, policy=policy), "mlp_hidden")
    h = gelu_exact(h)
    return linear_apply(params["fc2"], h, policy=policy)


# --- gated (SwiGLU) MLP ------------------------------------------------------
# (silu(x Wg) * (x Wu)) Wd, no biases, no norm of its own: the decoder
# layer that uses it norms before and after (models/looped_lm.py); with
# a grouped ``product`` over rows sorted by expert, the gated routed
# experts of ``ops/moe.py`` (``params`` then holds the experts stacked).


def gated_mlp_init(key, dim: int, hidden: int, dtype=jnp.float32):
    kg, ku, kd = jax.random.split(key, 3)
    return {
        "gate": linear_init(kg, dim, hidden, dtype, bias=False),
        "up": linear_init(ku, dim, hidden, dtype, bias=False),
        "down": linear_init(kd, hidden, dim, dtype, bias=False),
    }


@device_scope("mlp")
def gated_mlp_apply(params, x, policy: Policy = DEFAULT_POLICY, *,
                    product=linear_apply, name: str = "mlp_hidden"):
    """``product`` and ``name`` as ``relu2_mlp_apply``'s: a grouped
    product over rows sorted by expert makes these the routed experts of
    ``ops/moe.py``."""
    gate, up = (product(params[n], x, policy=policy)
                if name is None else
                dear(product(params[n], x, policy=policy), name)
                for n in ("gate", "up"))
    return product(params["down"], jax.nn.silu(gate) * up, policy=policy)


# --- relu-squared MLP --------------------------------------------------------
# relu(x Wu)^2 Wd, no gate, no biases, no norm of its own (the layer
# that uses it norms before): a shared expert and, with a grouped
# ``product`` over rows sorted by expert, the routed experts of
# ``ops/moe.py`` (``params`` then holds the experts stacked).


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def relu2_mlp_init(key, dim: int, hidden: int, dtype=jnp.float32):
    ku, kd = jax.random.split(key)
    return {
        "up": linear_init(ku, dim, hidden, dtype, bias=False),
        "down": linear_init(kd, hidden, dim, dtype, bias=False),
    }


@device_scope("mlp")
def relu2_mlp_apply(params, x, policy: Policy = DEFAULT_POLICY, *,
                    product=linear_apply, name: str = "mlp_hidden"):
    """``product(params[...], x, policy=policy)`` multiplies by one of
    the two matrices; ``name`` is the hidden layer's ``dear`` name,
    None where the caller recomputes it in any case."""
    up = product(params["up"], x, policy=policy)
    if name is not None:
        up = dear(up, name)
    return product(params["down"], relu2(up), policy=policy)
