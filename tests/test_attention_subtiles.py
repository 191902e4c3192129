"""The sub-tile level of the fused kernels' masks
(``ops/pallas_attention.py``, ``ops/tiling.py``): in the backward a
masked tile runs the sub-tiles that hold a visible (query, key) pair,
listed on the host and walked by two rolled loops in the kernel. The
lists against the masks written out pair by pair, the looped kernels
(interpreted) against the materialised core, and the size of what a
call traces."""

import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import out_and_grads

import perceiver_tpu.ops.attention as attn
import perceiver_tpu.ops.pallas_attention as pa
from perceiver_tpu.ops import tiling


def normal(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(seed), shape, dtype)


def rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12))


def visible(n: int, diffusion):
    """(n, n) bool, pair by pair from the rules at the head of
    ``ops/pallas_attention.py``: the causal triangle, or the
    block-diffusion mask of ``(half, block)`` with every index from
    ``2 * half`` on a clean position past the row's end."""
    j, l = np.arange(n)[:, None], np.arange(n)[None, :]   # noqa: E741
    if diffusion is None:
        return l <= j
    half, block = diffusion
    j_block = np.where(j < half, j, j - half) // block
    l_block = np.where(l < half, l, l - half) // block
    return np.where(
        j < half,
        np.where(l < half, j_block == l_block, l_block < j_block),
        (l >= half) & (l_block <= j_block))


# (positions, mask, block_q, block_k, sub_q, sub_k)
LISTS = {
    "bd_b4_whole_tiles": (1024, (512, 4), 256, 256, 64, 64),
    "bd_b32_whole_tiles": (1024, (512, 32), 256, 256, 64, 128),
    "bd_b4_halves_meet_in_a_tile": (768, (320, 4), 256, 256, 64, 64),
    "bd_b32_padded": (576, (288, 32), 256, 128, 128, 64),
    "bd_sdar_train": (8192, (4096, 4), 1024, 1024, 256, 256),
    "bd_sdar_train_128": (8192, (4096, 4), 1024, 1024, 128, 128),
    "causal": (1024, None, 256, 256, 64, 64),
    "causal_ouro_train": (4096, None, 1024, 1024, 256, 256),
    "causal_wide_keys": (768, None, 128, 256, 64, 128),
    "causal_wide_queries_padded": (1000, None, 512, 256, 128, 128),
    "causal_one_tile": (512, None, 512, 512, 128, 128),
}


@pytest.mark.parametrize("case", LISTS)
def test_the_lists_hold_every_visible_pair_and_no_empty_sub_tile(case):
    """``sub_tile_lists`` against the mask itself: a masked tile's list
    covers every visible pair of the tile, lists no sub-tile without
    one, holds first those a mask must be built for and then exactly
    those that are wholly visible, each kind query rows first, keys
    ascending; the other kinds of tile have no list."""
    n, diffusion, block_q, block_k, sub_q, sub_k = LISTS[case]
    nq, nk = -(-n // block_q), -(-n // block_k)
    sees = visible(max(nq * block_q, nk * block_k), diffusion)
    kinds = tiling.mask_tiles(diffusion, block_q, block_k, nq, nk)
    spans, entries = tiling.sub_tile_lists(diffusion, block_q, block_k, nq,
                                           nk, sub_q, sub_k)
    assert spans.shape == (nq, nk, 3) and entries.shape[1] == 2
    run = 0
    for iq, ik in itertools.product(range(nq), range(nk)):
        tile = sees[iq * block_q:(iq + 1) * block_q,
                    ik * block_k:(ik + 1) * block_k]
        want = tiling.SKIPPED if not tile.any() else \
            tiling.PLAIN if tile.all() else tiling.MASKED
        assert kinds[iq, ik] == want, (iq, ik)
        first, masked, plain = spans[iq, ik]
        if want != tiling.MASKED:
            assert masked == plain == 0
            continue
        run += masked + plain
        assert 0 < masked + plain <= (block_q // sub_q) * (block_k // sub_k)
        covered = np.zeros_like(tile)
        for start, count, whole in ((first, masked, False),
                                    (first + masked, plain, True)):
            listed = [tuple(e) for e in entries[start:start + count]]
            assert sorted(set(listed)) == listed       # rows first, no twice
            for row, col in listed:
                assert row % sub_q == 0 and col % sub_k == 0
                part = tile[row:row + sub_q, col:col + sub_k]
                assert part.any() and part.all() == whole, (iq, ik, row, col)
                assert not covered[row, col]
                covered[row:row + sub_q, col:col + sub_k] = True
        assert not (tile & ~covered).any(), (iq, ik)
    # the log line's words count the same tiles and sub-tiles
    masked = int((kinds == tiling.MASKED).sum())
    assert tiling.tile_counts(diffusion, block_q, block_k, nq, nk, sub_q,
                              sub_k) == (
        f"plain {int((kinds == tiling.PLAIN).sum())} masked {masked}, "
        f"sub-tiles {run}/"
        f"{masked * (block_q // sub_q) * (block_k // sub_k)}")


def test_tiles_with_one_pattern_share_one_list_and_the_cells_counts():
    """Every diagonal tile of a quadrant points at the same list (the
    tables grow with the patterns, not with the tiles); the counts of
    the two masked cells' calls as their ``[step_load]`` line words
    them."""
    spans, entries = tiling.sub_tile_lists((4096, 4), 1024, 1024, 8, 8,
                                           256, 256)
    diagonal = {tuple(spans[i, i]) for i in range(4)}
    below = {tuple(spans[i, i]) for i in range(4, 8)} \
        | {tuple(spans[i, i + 4]) for i in range(4)}
    assert diagonal == {(0, 4, 0)} and below == {(4, 4, 6)}
    assert len(entries) == 4 + 10       # two patterns: 4 of 16, 10 of 16
    spans, entries = tiling.sub_tile_lists(None, 1024, 1024, 4, 4, 256, 256)
    assert {tuple(spans[i, i]) for i in range(4)} == {(0, 4, 6)}
    assert len(entries) == 10
    assert pa.masked_call_tiles(8192, (4096, 4)) == \
        "block_diffusion tiles plain 12 masked 12, sub-tiles 32/48"
    assert pa.masked_call_tiles(4096) == \
        "causal tiles plain 6 masked 4, sub-tiles 12/16"


def test_the_step_load_line_counts_the_masked_call_sites_tiles(monkeypatch):
    """``attention call sites: fused=2; causal tiles plain 6 masked 4,
    sub-tiles 12/16 x2``: the fused call sites with a mask, by what
    they run; a call with no mask, or on the materialised core, adds
    nothing."""
    params = attn.mha_init(jax.random.key(0), 128, 1, bias=False)
    x = jax.ShapeDtypeStruct((1, 4096, 128), jnp.float32)

    def traced(**kw):
        with attn.attention_paths() as paths, \
                attn.masked_attention_tiles() as tiles:
            for _ in range(2):
                jax.eval_shape(lambda x: attn.mha_apply(
                    params, x, x, x, num_heads=1, **kw), x)
        return attn.format_attention_paths(paths, tiles)

    assert traced(causal=True) == "materialized[backend]=2"
    monkeypatch.setattr(attn, "_backend", lambda: "tpu")
    assert traced(causal=True) == \
        "fused=2; causal tiles plain 6 masked 4, sub-tiles 12/16 x2"
    assert traced(block_diffusion=(2048, 4)) == \
        "fused=2; block_diffusion tiles plain 2 masked 6, sub-tiles 16/24 x2"
    assert traced() == "fused=2"


# --- the looped kernels against the materialised core ------------------------


def reference(q, k, v, heads, diffusion):
    b, s, e = q.shape
    bias = jnp.where(jnp.asarray(visible(s, diffusion)), 0.0,
                     attn.NEG_INF)[None, None]
    split = [x.reshape(b, s, heads, e // heads) for x in (q, k, v)]
    out = attn._sdpa_core(1.0 / math.sqrt(e // heads), 0.0, jnp.float32,
                          *split, bias, None)
    return out.reshape(b, s, e)


@pytest.fixture
def sub_tiles_of_128(monkeypatch):
    """Sub-tiles of 128 (512 as shipped): tiles of 256 and 512 then
    hold 2 x 2 to 4 x 4 of them at sizes the interpreter runs."""
    monkeypatch.setattr(pa, "_SUB_TILE", 128)


# (positions, mask, heads, head dim, block_q, block_k)
KERNELS = {
    "bd_b4_whole_tiles": (1024, (512, 4), 1, 128, 512, 512),
    "bd_b4_two_heads_a_block": (1024, (512, 4), 2, 64, 512, 512),
    "bd_b32_halves_meet_in_a_tile_padded": (640, (320, 32), 1, 128, 256,
                                            256),
    "bd_one_tile": (512, (256, 4), 2, 64, 512, 512),
    "causal": (1024, None, 1, 128, 512, 512),
    "causal_two_heads_a_block_padded": (900, None, 2, 64, 256, 512),
    "causal_wide_queries": (768, None, 1, 128, 512, 256),
    "causal_one_tile": (512, None, 2, 64, 512, 512),
}


@pytest.mark.parametrize("case", KERNELS)
def test_looped_kernels_match_the_materialised_core(case, sub_tiles_of_128):
    """Forward and gradients of the kernels whose masked tiles walk
    their lists (interpreted), at the tolerance of the masked kernels'
    own tests (``tests/test_looped_lm.py``)."""
    seq, diffusion, heads, dim, block_q, block_k = KERNELS[case]
    lists = tiling.sub_tile_lists(
        diffusion, block_q, block_k, -(-seq // block_q), -(-seq // block_k),
        128, 128)[0][..., 1:].sum(-1)
    assert lists.max() > 1      # the case does walk a list
    q, k, v, g = (normal(30 + i, (2, seq, heads * dim)) for i in range(4))
    kw = dict(num_heads=heads, block_q=block_q, block_k=block_k,
              **(dict(causal=True) if diffusion is None
                 else dict(block_diffusion=diffusion)))
    got_out, got = out_and_grads(
        lambda *a: pa.flash_attention_channels(*a, **kw), g, q, k, v)
    want_out, want = out_and_grads(
        lambda *a: reference(*a, heads, diffusion), g, q, k, v)
    assert rel(got_out, want_out) < 1e-5
    for a, b in zip(got, want):
        assert rel(a, b) < 1e-5


# --- what a call traces ------------------------------------------------------


def kernels(fn, *args):
    """``{call name: (equations, loops, prefetched tables)}`` of the
    Pallas kernels ``fn`` traces: equations counted through every
    branch and loop body, the tables as the kernel's scalar operands."""

    def walk(jaxpr):
        eqns = loops = 0
        for eqn in jaxpr.eqns:
            eqns += 1
            loops += eqn.primitive.name in ("while", "scan")
            for sub in jax.core.jaxprs_in_params(eqn.params):
                e, l = walk(sub)     # noqa: E741
                eqns, loops = eqns + e, loops + l
        return eqns, loops

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    return {eqn.params["name"]: (
        *walk(eqn.params["jaxpr"]),
        eqn.params["grid_mapping"].num_index_operands)
        for eqn in calls(jax.make_jaxpr(fn)(*args).jaxpr)}


def masked_kernels(sub_tile, monkeypatch, **kw):
    monkeypatch.setattr(pa, "_SUB_TILE", sub_tile)
    q = jnp.zeros((1, 2048, 256), jnp.bfloat16)
    return kernels(jax.grad(lambda q, k, v: pa.flash_attention_channels(
        q, k, v, num_heads=2, interpret=False, **kw).astype(
            jnp.float32).sum(), (0, 1, 2)), q, q, q)


@pytest.mark.parametrize("kw,tables", [
    (dict(block_diffusion=(1024, 4)), 2), (dict(causal=True), 0)],
    ids=["block_diffusion", "causal"])
def test_a_masked_kernel_does_not_grow_with_its_sub_tiles(kw, tables,
                                                          monkeypatch):
    """Tiles of 1024 in sub-tiles of 512, 256 and 128: up to sixteen
    times the sub-tiles, longer tables, the same traced kernels. The
    backward holds one loop whose body is a masked sub-tile and one
    whose body is a plain one, and two tables more than the forward,
    which runs a masked tile whole and holds no loop."""
    traced = [masked_kernels(sub, monkeypatch, **kw)
              for sub in (512, 256, 128)]
    assert traced[0] == traced[1] == traced[2] and len(traced[0]) == 2
    (_, fwd_loops, fwd_tables), (_, bwd_loops, bwd_tables) = (
        next(v for name, v in traced[0].items() if name.endswith(which))
        for which in ("_fwd", "_bwd"))
    assert (fwd_loops, fwd_tables) == (0, tables)
    assert (bwd_loops, bwd_tables) == (2, tables + 2)
    # the lists did grow
    diffusion = kw.get("block_diffusion")
    sizes = [len(tiling.sub_tile_lists(diffusion, 1024, 1024, 2, 2, sub,
                                       sub)[1]) for sub in (512, 256, 128)]
    assert sizes[0] < sizes[1] < sizes[2]


@pytest.mark.parametrize("bias,want", [
    (False, {"flash_attention_fwd": 53, "flash_attention_bwd": 64}),
    (True, {"flash_attention_fwd": 55, "flash_attention_bwd": 77})],
    ids=["no_bias", "key_bias"])
def test_a_call_with_no_mask_traces_the_kernel_it_always_did(bias, want):
    """The sub-tile level is the masks': a call without one holds no
    loop, takes no prefetched table, and counts the equations it
    counted before the level existed (read from the parent tree of
    PR 41: keys streamed by 1024, one head a block)."""
    q = jnp.zeros((1, 4096, 256), jnp.bfloat16)
    b = jnp.zeros((1, 4096), jnp.float32) if bias else None
    got = kernels(jax.grad(lambda q, k, v: pa.flash_attention_channels(
        q, k, v, num_heads=2, bias=b, interpret=False).astype(
            jnp.float32).sum(), (0, 1, 2)), q, q, q)
    assert got == {name: (eqns, 0, 0) for name, eqns in want.items()}
