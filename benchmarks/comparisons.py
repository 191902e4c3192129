"""The arithmetic of the comparisons that decide ``correct``: norms by
leaf, the worst leaf's gap, the widest logit gap of served tokens. The
limits are data (``benchmarks/limits/<cell>.json``); how each was set
is in PERF.md."""

from __future__ import annotations

from typing import Callable, Sequence

import numpy as np


def leaf_norms(tree) -> np.ndarray:
    """Euclidean norm of every leaf, in flattening order (on the
    device, one small array back)."""
    import jax
    import jax.numpy as jnp

    return np.asarray(jax.jit(lambda t: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
         for x in jax.tree.leaves(t)]))(tree))


def leaf_norms_of_difference(tree, make_other: Callable[[], object]
                             ) -> np.ndarray:
    """Norm by leaf of ``tree - make_other()``; the other tree lives
    only for the call."""
    import jax
    import jax.numpy as jnp

    return leaf_norms(jax.tree.map(jnp.subtract, tree, make_other()))


def leaf_gaps(program: Sequence[float],
              reference: Sequence[float]) -> np.ndarray:
    """By leaf, the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some leaves' gradients are all but zero)."""
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    if program.shape != reference.shape:
        raise ValueError(f"{program.shape} leaves against "
                         f"{reference.shape}")
    scale = np.maximum(reference, np.median(reference))
    return np.abs(program - reference) / scale


def worst_leaf_gap(program: Sequence[float],
                   reference: Sequence[float]) -> float:
    """The largest of ``leaf_gaps``: one leaf gone wrong shows here."""
    return float(np.max(leaf_gaps(program, reference)))


def rms_leaf_gap(program: Sequence[float],
                 reference: Sequence[float]) -> float:
    """The root mean square of ``leaf_gaps``. A lower precision moves
    every leaf a little, so it shows here, where one noisy leaf (a
    gradient that is a sum of cancelling terms) weighs little: steadier
    from seed to seed than the worst leaf."""
    return float(np.sqrt(np.mean(np.square(leaf_gaps(program,
                                                     reference)))))


def live_leaves(grad_norms: Sequence[float],
                floor: float = 1e-3) -> np.ndarray:
    """Which leaves have a first gradient worth the name: a norm of at
    least ``floor`` of the median leaf's."""
    grad_norms = np.asarray(grad_norms, np.float64)
    return grad_norms >= floor * np.median(grad_norms)


def widest_logit_gap(logits: np.ndarray, tokens: Sequence[int]) -> float:
    """How far, at worst, a served token's reference logit lies below
    the reference's best at its position. ``logits`` is (positions,
    vocabulary), ``tokens`` the token served at each position."""
    logits = np.asarray(logits, np.float64)
    served = logits[np.arange(len(tokens)), np.asarray(tokens)]
    return float(np.max(logits.max(axis=1) - served))
