"""Shared tiling helpers for the Pallas TPU kernels: host-side, numpy
only (what a kernel traces lives beside it)."""

from __future__ import annotations

import numpy as np


def round_up(n: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``n``."""
    return ((n + m - 1) // m) * m


# --- which tiles of a block-diffusion mask run ------------------------------
# (``ops/pallas_attention.py``'s third mask; the rules are at its head)

SKIPPED, MASKED, PLAIN = 0, 1, 2


def diffusion_tiles(half: int, block: int, block_q: int, block_k: int,
                    nq: int, nk: int) -> np.ndarray:
    """(nq, nk) int32: ``SKIPPED`` (no query of the tile sees a key of
    it), ``PLAIN`` (every query sees every key) or ``MASKED``, from the
    block-diffusion rules over each tile's ranges of queries and keys.
    Indices from ``2 * half`` on (padding) count as clean positions
    past the row's end, as the kernels' own mask
    (``ops/pallas_attention._diffusion_mask``) takes them."""

    def halves(lo, hi):
        """The tile's range split at ``half``: (noised, clean), each
        (first block, last block) or None; clean blocks are numbered
        from the clean half's start."""
        noised = (lo // block, (min(hi, half) - 1) // block) \
            if lo < half else None
        clean = ((max(lo, half) - half) // block, (hi - 1 - half) // block) \
            if hi > half else None
        return noised, clean

    kinds = np.zeros((nq, nk), np.int32)
    for iq in range(nq):
        q_noised, q_clean = halves(iq * block_q, (iq + 1) * block_q)
        for ik in range(nk):
            k_noised, k_clean = halves(ik * block_k, (ik + 1) * block_k)
            some, every = False, True
            if q_noised and k_noised:    # the same block
                some |= q_noised[0] <= k_noised[1] \
                    and k_noised[0] <= q_noised[1]
                every &= q_noised[0] == q_noised[1] == k_noised[0] \
                    == k_noised[1]
            if q_noised and k_clean:     # the clean blocks before
                some |= k_clean[0] < q_noised[1]
                every &= k_clean[1] < q_noised[0]
            if q_clean and k_noised:     # nothing
                every = False
            if q_clean and k_clean:      # the clean blocks up to its own
                some |= k_clean[0] <= q_clean[1]
                every &= k_clean[1] <= q_clean[0]
            kinds[iq, ik] = (PLAIN if every else MASKED) if some else SKIPPED
    return kinds


def held_tiles(kinds: np.ndarray) -> np.ndarray:
    """For each row of ``kinds``, the tile to hold at each step of the
    sweep along it: the step's own where it runs, else the last one
    that ran (the first that will, before any has): the pipeline then
    fetches nothing while tiles are skipped."""
    held = np.zeros_like(kinds)
    for row, out in zip(kinds, held):
        runs = np.flatnonzero(row)
        last = runs[0] if runs.size else 0
        for i, kind in enumerate(row):
            last = i if kind else last
            out[i] = last
    return held
