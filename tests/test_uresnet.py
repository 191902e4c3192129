"""UResNet: shapes, BatchNorm state threading, gradient flow.

Mirrors SURVEY.md §4 plan (a)/(b): unit coverage the reference never
had for ``uresnet.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

from perceiver_tpu.models.uresnet import UResNet
from perceiver_tpu.ops.conv import (
    batch_norm_apply,
    batch_norm_init,
    conv_apply,
    conv_init,
    conv_transpose_apply,
)
from perceiver_tpu.ops.policy import Policy

FP32 = Policy.fp32()


def test_conv_shapes():
    key = jax.random.key(0)
    p = conv_init(key, 3, 8, kernel=3)
    x = jnp.ones((2, 16, 16, 3))
    assert conv_apply(p, x, policy=FP32).shape == (2, 16, 16, 8)
    assert conv_apply(p, x, stride=2, policy=FP32).shape == (2, 8, 8, 8)


def test_conv_transpose_doubles():
    key = jax.random.key(0)
    p = {"w": jax.random.normal(key, (3, 3, 8, 4))}
    x = jnp.ones((2, 8, 8, 8))
    assert conv_transpose_apply(p, x, policy=FP32).shape == (2, 16, 16, 4)


def test_batch_norm_train_vs_eval():
    params, state = batch_norm_init(4)
    x = jax.random.normal(jax.random.key(1), (8, 4, 4, 4)) * 3.0 + 1.0
    y, new_state = batch_norm_apply(params, state, x, train=True,
                                    policy=FP32)
    # train mode normalizes with batch stats
    np.testing.assert_allclose(np.mean(y, axis=(0, 1, 2)), 0.0, atol=1e-5)
    np.testing.assert_allclose(np.std(y, axis=(0, 1, 2)), 1.0, atol=1e-3)
    # running stats moved toward the batch stats
    assert not np.allclose(new_state["mean"], state["mean"])
    # eval mode uses running stats, state unchanged
    y2, s2 = batch_norm_apply(params, new_state, x, train=False,
                              policy=FP32)
    assert s2 is new_state
    assert not np.allclose(np.asarray(y), np.asarray(y2))


@pytest.fixture(scope="module")
def tiny_uresnet():
    model = UResNet(num_classes=3, input_channels=1, inplanes=4,
                    head_kernels=4)
    variables = jit_once(model.init)(jax.random.key(0))
    return model, variables


def test_uresnet_output_shape(tiny_uresnet):
    model, variables = tiny_uresnet
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 1))
    logits, _ = jit_once(lambda v, x: model.apply(
        v, x, train=False, policy=FP32))(variables, x)
    assert logits.shape == (2, 32, 32, 3)
    assert np.isfinite(np.asarray(logits)).all()


def test_uresnet_train_updates_bn_state(tiny_uresnet):
    model, (params, state) = tiny_uresnet
    x = jax.random.normal(jax.random.key(2), (2, 32, 32, 1)) * 2.0
    logits, new_state = jit_once(lambda v, x: model.apply(
        v, x, train=True, policy=FP32))((params, state), x)
    before = state["stem1"]["bn"]["mean"]
    after = new_state["stem1"]["bn"]["mean"]
    assert not np.allclose(np.asarray(before), np.asarray(after))
    jax.tree.map(lambda a: None, new_state)  # same treedef as state
    assert (jax.tree.structure(new_state) == jax.tree.structure(state))


def test_uresnet_gradients_flow(tiny_uresnet):
    model, (params, state) = tiny_uresnet
    # 32×32 batch 2 keeps the deepest stage's BN over >1 element —
    # normalizing a single element zeroes its gradient by construction
    x = jax.random.normal(jax.random.key(3), (2, 32, 32, 1))
    labels = jnp.zeros((2, 32, 32), jnp.int32)

    @jit_once
    def loss_fn(p):
        logits, _ = model.apply((p, state), x, train=True, policy=FP32)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, labels[..., None], -1).mean()

    grads = jax.grad(loss_fn)(params)
    norms = [float(jnp.linalg.norm(g))
             for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(n) for n in norms)
    # every learned tensor receives gradient (BN biases included)
    assert all(n > 0 for n in norms)
