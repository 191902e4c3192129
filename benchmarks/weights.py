"""The benchmark's own weights: every leaf of a parameter tree drawn
from the seed on the device, in one jitted call.

The program's ``init`` is not used for values: the reference may take
nothing the program made, so both get what this file makes. Only the
tree's layout (names and shapes, ``jax.eval_shape`` of the program's
``init``) comes from the program. The scales follow the published
initialisation closely enough for activations of realistic size:
norm scales 1 with small biases, linear weights N(0, 1/fan_in), token
embedding U(+-0.1), position table U(+-0.5), latents and output
queries N(0, 0.02).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """``n`` uint32 words from any whole-number seed (the driver's are
    above 2**31, which a 32-bit key seed does not hold)."""
    return np.random.SeedSequence(int(seed)).generate_state(n)


def key_from_seed(seed: int):
    return jax.random.wrap_key_data(
        jnp.asarray(seed_words(seed), jnp.uint32))


def seed31(seed: int) -> int:
    """A 31-bit seed for program options that take a small int."""
    return int(seed_words(seed, 3)[2] >> 1)


def _leaf(key, name: str, shape, dtype):
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name in ("bias", "b"):
        return 0.02 * jax.random.normal(key, shape, dtype)
    if name == "w":
        return jax.random.normal(key, shape, dtype) / np.sqrt(shape[-2])
    if name == "embed":
        return jax.random.uniform(key, shape, dtype, -0.1, 0.1)
    if name == "pos":
        return jax.random.uniform(key, shape, dtype, -0.5, 0.5)
    if name in ("latent", "query"):
        return 0.02 * jax.random.normal(key, shape, dtype)
    raise ValueError(f"no rule for a parameter leaf named {name!r}")


def make_weights(shapes, seed: int):
    """A tree like ``shapes`` (of ``jax.ShapeDtypeStruct``) filled from
    ``seed``. Leaf i takes ``fold_in(key, i)`` in flattening order, so
    a leaf's values do not depend on the others' shapes."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [str(getattr(path[-1], "key", path[-1])) for path, _ in leaves]

    @jax.jit
    def build(key):
        return jax.tree.unflatten(treedef, [
            _leaf(jax.random.fold_in(key, i), name, s.shape, s.dtype)
            for i, (name, (_, s)) in enumerate(zip(names, leaves))])

    return build(key_from_seed(seed))
