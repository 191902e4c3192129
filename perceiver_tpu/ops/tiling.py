"""Shared tiling helpers for the Pallas TPU kernels: host-side, numpy
only (what a kernel traces lives beside it)."""

from __future__ import annotations

import numpy as np


def round_up(n: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``n``."""
    return ((n + m - 1) // m) * m


# --- which tiles of a masked attention call run ------------------------------
# (``ops/pallas_attention.py``'s causal and block-diffusion masks; the
# rules are at its head)

SKIPPED, MASKED, PLAIN = 0, 1, 2


def causal_tiles(block_q: int, block_k: int, nq: int, nk: int) -> np.ndarray:
    """(nq, nk) int32 kinds under the causal triangle (query ``i`` sees
    keys ``0..i``): ``SKIPPED`` above the diagonal, ``MASKED`` where it
    crosses the tile, ``PLAIN`` below."""
    first_q = np.arange(nq)[:, None] * block_q
    first_k = np.arange(nk)[None, :] * block_k
    some = first_k <= first_q + block_q - 1
    every = first_k + block_k - 1 <= first_q
    return np.where(every, PLAIN, np.where(some, MASKED, SKIPPED)).astype(
        np.int32)


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


def mask_tiles(diffusion, block_q: int, block_k: int, nq: int,
               nk: int) -> np.ndarray:
    """The tiles' kinds under a masked call's mask: the block-diffusion
    mask of ``diffusion = (half, block)``, or the causal triangle where
    that is None."""
    if diffusion:
        return diffusion_tiles(*diffusion, block_q, block_k, nq, nk)
    return causal_tiles(block_q, block_k, nq, nk)


def sub_tile_lists(diffusion, block_q: int, block_k: int, nq: int, nk: int,
                   sub_q: int, sub_k: int):
    """One level down: the same rules over the ``sub_q x sub_k``
    sub-tiles of each *masked* tile. ``(spans, entries)``: ``spans``
    (nq, nk, 3) int32, for a masked tile where its list starts in
    ``entries`` and how many masked and how many plain sub-tiles it
    holds (zeros for the other kinds of tile); ``entries`` (n, 2)
    int32, a sub-tile that holds a visible pair each: its first query
    and first key, counted from the tile's own. A tile's list holds its
    masked sub-tiles, then its plain ones (wholly visible), each kind
    query rows first, keys ascending in a row. Tiles with the same
    pattern (every diagonal tile of one quadrant) share one list."""
    rows, cols = block_q // sub_q, block_k // sub_k
    coarse = mask_tiles(diffusion, block_q, block_k, nq, nk)
    fine = mask_tiles(diffusion, sub_q, sub_k, nq * rows, nk * cols)
    spans = np.zeros((nq, nk, 3), np.int32)
    entries, lists = [], {}
    for iq, ik in zip(*np.nonzero(coarse == MASKED)):
        pattern = fine[iq * rows:(iq + 1) * rows, ik * cols:(ik + 1) * cols]
        key = pattern.tobytes()
        if key not in lists:
            found = [np.argwhere(pattern == kind) * (sub_q, sub_k)
                     for kind in (MASKED, PLAIN)]
            lists[key] = (len(entries), *map(len, found))
            entries.extend(np.concatenate(found))
        spans[iq, ik] = lists[key]
    return spans, np.asarray(entries, np.int32).reshape(-1, 2)


def tile_counts(diffusion, block_q: int, block_k: int, nq: int, nk: int,
                sub_q: int, sub_k: int) -> str:
    """What a masked call runs, for a log line: ``plain 12 masked 12,
    sub-tiles 32/48`` — its tiles by kind and, of the masked tiles'
    sub-tiles, those that hold a visible pair."""
    coarse = mask_tiles(diffusion, block_q, block_k, nq, nk)
    spans, _ = sub_tile_lists(diffusion, block_q, block_k, nq, nk, sub_q,
                              sub_k)
    masked = int((coarse == MASKED).sum())
    return (f"plain {int((coarse == PLAIN).sum())} masked {masked}, "
            f"sub-tiles {int(spans[..., 1:].sum())}/"
            f"{masked * (block_q // sub_q) * (block_k // sub_k)}")
