"""Completion fence for timed regions.

JAX returns from a dispatch before the device finishes, so a wall
clock read without a sync measures the enqueue. ``fence`` ends a timed
region with a HOST FETCH of one element that data-depends on the
computation: a device→host transfer cannot complete before the
producing computation does, on any backend, and it accepts a whole
pytree (``block_until_ready`` on a metrics dict with host-side leaves
would need the caller to pick the device leaf). The fence scalar is
tiny so the fetch measures compute, not transfer bandwidth.
"""

from __future__ import annotations

import numpy as np


def fence(x) -> float:
    """Block until ``x`` is REALLY computed; returns one element as float.

    ``x`` may be a jax array of any shape or a pytree (the first
    jax.Array leaf is used — a host-side scalar leaf would device_get
    instantly and silently turn the fence into a no-op, the exact
    unfenced-timing bug this module exists to fix). A scalar is fetched
    directly; for larger arrays a one-element slice is dispatched on
    device first so only bytes for a single element cross the wire.
    """
    import jax

    leaves = jax.tree_util.tree_leaves(x)
    if not leaves:
        return 0.0
    leaf = next((l for l in leaves if isinstance(l, jax.Array)), None)
    if leaf is None:
        raise TypeError(
            "fence() needs at least one jax.Array leaf to synchronize "
            f"on; got only host-side leaves ({type(leaves[0]).__name__})")
    if getattr(leaf, "ndim", 0):
        leaf = leaf.ravel()[0]
    return float(np.asarray(jax.device_get(leaf)))
