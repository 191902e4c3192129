"""Dense layer as pure init/apply functions."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perceiver_tpu.ops.initializers import torch_linear_uniform
from perceiver_tpu.ops.policy import Policy, DEFAULT_POLICY


def linear_init(key, in_dim: int, out_dim: int, dtype=jnp.float32,
                bias: bool = True):
    """Parameters for y = x @ w + b, torch nn.Linear-style init;
    ``bias=False`` leaves ``b`` out of the tree."""
    wk, bk = jax.random.split(key)
    params = {"w": torch_linear_uniform(wk, (in_dim, out_dim), in_dim, dtype)}
    if bias:
        params["b"] = torch_linear_uniform(bk, (out_dim,), in_dim, dtype)
    return params


def linear_apply(params, x, policy: Policy = DEFAULT_POLICY):
    """``x @ w + b``; a tree without ``b`` is a projection without
    bias."""
    w = policy.cast_param(params["w"])
    if "b" not in params:
        return policy.cast_compute(x) @ w
    b = policy.cast_param(params["b"])
    return policy.cast_compute(x) @ w + b
