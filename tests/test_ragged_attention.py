"""Ragged (padding-free) attention kernels vs. pure-jax references.

Both kernels run in Pallas interpreter mode on the CPU test backend —
the identical kernel bodies that compile on TPU (see
ops/ragged_attention.py and docs/SERVING.md "Ragged serving").
The properties pinned here:

- the encoder kernel matches masked-softmax attention over each
  request's own token span, for aligned and unaligned offsets;
- zero-length rows return exactly zero (not NaN from an empty
  softmax);
- ``max_len`` only bounds the kv-block walk — numerics are unchanged
  as long as every request fits;
- the decoder kernel matches the block-diagonal latent mask and never
  leaks attention across requests;
- both survive jit and bf16 inputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jit_once

from perceiver_tpu.ops.ragged_attention import (
    ragged_cross_attention,
    ragged_cross_attention_reference,
    ragged_decode_attention,
    ragged_decode_attention_reference,
)


def _pack(lengths):
    lengths = np.asarray(lengths, np.int32)
    offsets = np.zeros_like(lengths)
    offsets[1:] = np.cumsum(lengths)[:-1]
    return jnp.asarray(offsets), jnp.asarray(lengths)


def _cross_inputs(key, lengths, h=2, nq=4, d=8, t=None):
    r = len(lengths)
    t = int(np.sum(lengths)) if t is None else t
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (r, h, nq, d))
    k = jax.random.normal(kk, (h, t, d))
    v = jax.random.normal(kv, (h, t, d))
    offs, lens = _pack(lengths)
    return q, k, v, offs, lens


class TestRaggedCross:
    def test_matches_reference(self):
        q, k, v, offs, lens = _cross_inputs(jax.random.key(0),
                                            [40, 7, 81])
        out = ragged_cross_attention(q, k, v, offs, lens, block_k=128)
        ref = ragged_cross_attention_reference(q, k, v, offs, lens)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_unaligned_offsets_cross_block_edges(self):
        # spans straddle block_k boundaries at both ends
        q, k, v, offs, lens = _cross_inputs(jax.random.key(1),
                                            [100, 200, 60, 31],
                                            t=400)
        out = ragged_cross_attention(q, k, v, offs, lens, block_k=128)
        ref = ragged_cross_attention_reference(q, k, v, offs, lens)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_zero_length_rows_are_zero(self):
        # empty spans park at the packed tail (the engine's padding
        # convention) and must come back exactly zero, not NaN
        q, k, v, _, _ = _cross_inputs(jax.random.key(2), [30, 0, 12, 0],
                                      t=64)
        offs = jnp.asarray([0, 42, 30, 42], jnp.int32)
        lens = jnp.asarray([30, 0, 12, 0], jnp.int32)
        out = ragged_cross_attention(q, k, v, offs, lens, block_k=128)
        ref = ragged_cross_attention_reference(q, k, v, offs, lens)
        assert np.all(np.isfinite(np.asarray(out)))
        np.testing.assert_array_equal(np.asarray(out[1]), 0.0)
        np.testing.assert_array_equal(np.asarray(out[3]), 0.0)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_single_row_spans_whole_buffer(self):
        q, k, v, offs, lens = _cross_inputs(jax.random.key(3), [96])
        out = ragged_cross_attention(q, k, v, offs, lens, block_k=32)
        ref = ragged_cross_attention_reference(q, k, v, offs, lens)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_max_len_bound_preserves_numerics(self):
        # max_len trims the kv-block walk (the bytes win) but must not
        # change the result while every request fits under it
        q, k, v, offs, lens = _cross_inputs(jax.random.key(4),
                                            [64, 17, 33], t=256)
        full = ragged_cross_attention(q, k, v, offs, lens, block_k=64)
        bounded = ragged_cross_attention(q, k, v, offs, lens,
                                         block_k=64, max_len=64)
        np.testing.assert_allclose(bounded, full, atol=1e-6, rtol=1e-6)

    def test_under_jit(self):
        q, k, v, offs, lens = _cross_inputs(jax.random.key(5),
                                            [20, 44, 64])
        fn = jit_once(lambda *a: ragged_cross_attention(*a, block_k=64))
        out = fn(q, k, v, offs, lens)
        ref = ragged_cross_attention_reference(q, k, v, offs, lens)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_bf16(self):
        q, k, v, offs, lens = _cross_inputs(jax.random.key(6),
                                            [40, 24, 64])
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        out = ragged_cross_attention(qb, kb, vb, offs, lens, block_k=64)
        ref = ragged_cross_attention_reference(q, k, v, offs, lens)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(out.astype(jnp.float32), ref,
                                   atol=2e-2, rtol=2e-2)

    def test_no_cross_request_leakage(self):
        # perturbing request 1's tokens must leave request 0's output
        # bit-identical — raggedness is isolation, not approximation
        q, k, v, offs, lens = _cross_inputs(jax.random.key(7), [32, 32])
        out_a = ragged_cross_attention(q, k, v, offs, lens, block_k=32)
        k2 = k.at[:, 32:, :].add(100.0)
        v2 = v.at[:, 32:, :].add(-7.0)
        out_b = ragged_cross_attention(q, k2, v2, offs, lens, block_k=32)
        np.testing.assert_array_equal(np.asarray(out_a[0]),
                                      np.asarray(out_b[0]))
        assert not np.allclose(np.asarray(out_a[1]),
                               np.asarray(out_b[1]))


class TestRaggedDecode:
    def _inputs(self, key, lengths, n=4, h=2, d=8):
        r = len(lengths)
        t = int(np.sum(lengths))
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (h, t, d))
        k = jax.random.normal(kk, (h, r * n, d))
        v = jax.random.normal(kv, (h, r * n, d))
        rows = jnp.asarray(np.repeat(np.arange(r), lengths), jnp.int32)
        return q, k, v, rows, n

    def test_matches_reference(self):
        q, k, v, rows, n = self._inputs(jax.random.key(10), [13, 40, 7])
        out = ragged_decode_attention(q, k, v, rows, latents_per_row=n,
                                      block_q=32)
        ref = ragged_decode_attention_reference(q, k, v, rows,
                                                latents_per_row=n)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_under_jit(self):
        q, k, v, rows, n = self._inputs(jax.random.key(11), [25, 39])
        fn = jit_once(lambda *a: ragged_decode_attention(
            *a, latents_per_row=n, block_q=16))
        out = fn(q, k, v, rows)
        ref = ragged_decode_attention_reference(q, k, v, rows,
                                                latents_per_row=n)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_no_cross_request_leakage(self):
        q, k, v, rows, n = self._inputs(jax.random.key(12), [16, 16])
        out_a = ragged_decode_attention(q, k, v, rows, latents_per_row=n)
        # blow up request 1's latents; request 0's tokens can't see them
        k2 = k.at[:, n:, :].add(50.0)
        v2 = v.at[:, n:, :].add(9.0)
        out_b = ragged_decode_attention(q, k2, v2, rows,
                                        latents_per_row=n)
        np.testing.assert_array_equal(np.asarray(out_a[:, :16]),
                                      np.asarray(out_b[:, :16]))
        assert not np.allclose(np.asarray(out_a[:, 16:]),
                               np.asarray(out_b[:, 16:]))

    def test_bf16(self):
        q, k, v, rows, n = self._inputs(jax.random.key(13), [30, 18, 16])
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        out = ragged_decode_attention(qb, kb, vb, rows,
                                      latents_per_row=n)
        ref = ragged_decode_attention_reference(q, k, v, rows,
                                                latents_per_row=n)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(out.astype(jnp.float32), ref,
                                   atol=2e-2, rtol=2e-2)

    @pytest.mark.parametrize("lengths", [[1], [5, 1, 1, 9]])
    def test_tiny_rows(self, lengths):
        q, k, v, rows, n = self._inputs(jax.random.key(14), lengths)
        out = ragged_decode_attention(q, k, v, rows, latents_per_row=n,
                                      block_q=16)
        ref = ragged_decode_attention_reference(q, k, v, rows,
                                                latents_per_row=n)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
