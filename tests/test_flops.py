"""FLOPs/MFU instrumentation (SURVEY §5 profiling rebuild item)."""

import jax
import jax.numpy as jnp

from perceiver_tpu.utils.flops import (
    device_peak_flops,
    lowered_step_flops,
    mfu,
)


def test_lowered_step_flops_counts_matmul():
    @jax.jit
    def f(a, b):
        return a @ b

    a = jnp.ones((64, 128))
    b = jnp.ones((128, 32))
    flops = lowered_step_flops(f, a, b)
    # 2·M·K·N, allow cost-model slack
    assert flops is None or flops >= 2 * 64 * 128 * 32 * 0.5


def test_device_peak_flops_cpu_is_none():
    # tests run on the forced-CPU backend
    assert device_peak_flops() is None


def test_device_peak_flops_unknown_tpu_kind_is_an_error():
    import types

    import pytest

    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert device_peak_flops(v5e) == 197e12
    odd = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        device_peak_flops(odd)


def test_mfu_math_and_guards():
    assert mfu(1e12, 10, 1.0, 1, 197e12) == (1e13 / 197e12)
    assert mfu(None, 10, 1.0, 1, 197e12) is None
    assert mfu(1e12, 10, 1.0, 1, None) is None
    assert mfu(1e12, 10, 0.0, 1, 197e12) is None
