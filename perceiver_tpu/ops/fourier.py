"""Fourier position encodings and rotary tables, precomputed host-side.

Behavioral parity with the reference image adapter
(``perceiver/adapter.py:53-97``):

- positions: per spatial dim, ``linspace(-1, 1, size)``; meshgrid →
  ``(*spatial, ndim)``.
- frequencies: per dim ``linspace(1.0, max_freq / 2, num_bands)`` where
  ``max_freq`` defaults to that dim's size (``adapter.py:79-82``).
- encodings: ``[positions] + [sin(π f p) per dim] + [cos(π f p) per dim]``
  concatenated on the channel axis (``adapter.py:88-94``) — note the
  ordering: all sins (dim-major) then all cosines.
- channel count: ``ndim * (2 * num_bands + 1)`` (``adapter.py:96-97``).

Computed in fp64 NumPy at model-build time and embedded as an XLA
constant — it never changes, so it costs zero step-time and no HBM
traffic beyond the initial transfer.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import numpy as np


def num_fourier_channels(spatial_shape: Sequence[int], num_bands: int,
                         include_positions: bool = True) -> int:
    return len(spatial_shape) * (2 * num_bands + int(include_positions))


def fourier_position_encodings(
        spatial_shape: Sequence[int],
        num_bands: int,
        max_frequencies: Optional[Tuple[float, ...]] = None,
        include_positions: bool = True,
        dtype=np.float32) -> np.ndarray:
    """Return encodings of shape (prod(spatial_shape), num_channels).

    Memoized: the 262k-position segmentation grid takes non-trivial
    host time to build, and eager (non-jit) callers hit this per
    forward pass.
    """
    return _fourier_cached(tuple(spatial_shape), num_bands,
                           None if max_frequencies is None
                           else tuple(max_frequencies),
                           include_positions, np.dtype(dtype).name)


@functools.lru_cache(maxsize=32)
def _fourier_cached(spatial_shape, num_bands, max_frequencies,
                    include_positions, dtype_name):
    dtype = np.dtype(dtype_name)
    coords = [np.linspace(-1.0, 1.0, s, dtype=np.float64)
              for s in spatial_shape]
    # meshgrid with matrix indexing → (*spatial, ndim), matching torch's
    # default meshgrid indexing ('ij') used by the reference.
    pos = np.stack(np.meshgrid(*coords, indexing="ij"), axis=-1)

    if max_frequencies is None:
        max_frequencies = spatial_shape

    parts = []
    if include_positions:
        parts.append(pos)
    grids = []
    for i, max_freq in enumerate(max_frequencies):
        freqs = np.linspace(1.0, max_freq / 2.0, num_bands, dtype=np.float64)
        grids.append(pos[..., i:i + 1] * freqs)
    parts.extend(np.sin(math.pi * g) for g in grids)
    parts.extend(np.cos(math.pi * g) for g in grids)

    enc = np.concatenate(parts, axis=-1).astype(dtype)
    return enc.reshape(-1, enc.shape[-1])


# --- rotary positions --------------------------------------------------------
# The same idea turned on its side: position i rotates the pair of
# channels (j, j + D/2) of every head by the angle i * theta^(-2j/D).
# The tables are computed in fp64 NumPy at model-build time like the
# encodings above; applying them is one fused elementwise pass.


@functools.lru_cache(maxsize=8)
def rope_tables(seq_len: int, head_dim: int, theta: float
                ) -> Tuple[np.ndarray, np.ndarray]:
    """``(cos, sin)``, each (seq_len, head_dim) float32, over the whole
    head dim: column j and column j + D/2 carry the same angle."""
    if head_dim % 2:
        raise ValueError(f"rotary positions pair channels: head_dim "
                         f"{head_dim} is odd")
    inv_freq = float(theta) ** (
        -np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    angles = np.arange(seq_len, dtype=np.float64)[:, None] * inv_freq
    angles = np.concatenate([angles, angles], axis=-1)
    return (np.cos(angles).astype(np.float32),
            np.sin(angles).astype(np.float32))


def rope_apply(x, cos, sin, num_heads: int):
    """Rotate (B, L, H·D) heads-side-by-side channels by the tables
    (L, R): ``x * cos + rotate_half(x) * sin`` with ``rotate_half`` of
    a head ``[-x2, x1]``. Tables narrower than a head (``R < D``, a
    partial rotary factor) rotate its first ``R`` channels, pairs
    ``(j, j + R/2)``, and leave the rest as they are. fp32 inside,
    ``x``'s dtype out."""
    import jax.numpy as jnp

    b, l, e = x.shape
    xh = x.reshape(b, l, num_heads, e // num_heads).astype(jnp.float32)
    turned, rest = xh, None
    if cos.shape[-1] < xh.shape[-1]:
        turned, rest = jnp.split(xh, [cos.shape[-1]], axis=-1)
    x1, x2 = jnp.split(turned, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    out = turned * cos[None, :l, None, :] + rotated * sin[None, :l, None, :]
    if rest is not None:
        out = jnp.concatenate([out, rest], axis=-1)
    return out.reshape(b, l, e).astype(x.dtype)
