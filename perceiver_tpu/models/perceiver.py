"""Perceiver IO models as pure init/apply dataclasses.

Parity targets (reference ``perceiver/model.py``):

- ``PerceiverEncoder`` (``model.py:119-189``): input adapter → learned
  latent array (trunc-N(0,0.02) clamped ±2) broadcast over batch →
  ``layer_1`` (unshared) then ``layer_n`` applied ``num_layers - 1``
  times with **shared weights**. Each perceiver layer is a
  cross-attention layer (latent ← input, with key-padding mask) followed
  by a block of self-attention layers (no mask). Returns
  ``(x_latent, pad_mask)`` — the tuple contract the decoder consumes.
- ``PerceiverDecoder`` (``model.py:192-237``): learned output query
  array of shape ``output_adapter.output_shape``, one cross-attention
  layer (query ← latent, no mask — matching ``model.py:236``), then the
  output adapter. Supports query chunking for huge output arrays (the
  262k-query segmentation config) — exact, since output queries only
  interact with the latent kv, never with each other.
- ``PerceiverIO`` (``model.py:321-325``): encoder ∘ decoder.
- ``PerceiverMLM`` (``model.py:296-318``): masking → encoder → decoder →
  logits sliced to the input length. The reference version crashes
  (encoder tuple fed to the decoder as a single arg, SURVEY.md §2.6.1);
  here the plumbing is explicit and correct.

TPU-first design notes:

- The weight-shared ``layer_n`` recurrence and the per-block
  self-attention stack both run under ``lax.scan`` — each layer body is
  traced and compiled once regardless of depth, and the stacked
  parameter pytrees give XLA one big fused HBM layout per block.
- All residual/attention dropout uses explicitly threaded PRNG keys
  (scan carries a per-iteration key), so training steps stay pure and
  reproducible under ``jit`` and ``shard_map``.
- Latent and output-query broadcasts are ``jnp.broadcast_to`` views —
  no materialized per-batch copies in HBM.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from perceiver_tpu.models.masking import TextMasking
from perceiver_tpu.obs.trace import device_scope
from perceiver_tpu.ops.attention import (
    ATTENTION_IMPLS,
    DECODER_ATTENTION_IMPLS,
    cross_attention_init,
    cross_attention_apply,
    cross_attention_kv,
    data_shards,
    self_attention_init,
    self_attention_apply,
    untallied,
)
from perceiver_tpu.ops.dropout import dropout
from perceiver_tpu.ops.initializers import trunc_normal_clamped
from perceiver_tpu.ops.mlp import mlp_init, mlp_apply
from perceiver_tpu.ops.policy import Policy, DEFAULT_POLICY
from perceiver_tpu.ops.remat import (
    REMAT_NAMES,
    choose_keeps,
    layer_shapes,
    named_bytes,
)


def _rng_or_dummy(rng, deterministic: bool = True):
    """Dummy key for deterministic paths (scan still needs a key array).

    Raises when randomness is actually required but no rng was given —
    a silent constant key would reuse the same dropout/masking pattern
    every step and quietly degrade training.
    """
    if rng is None and not deterministic:
        raise ValueError(
            "deterministic=False requires an explicit `rng` key")
    return rng if rng is not None else jax.random.key(0)


# --- layer composers (reference model.py:29-44) ------------------------------


def cross_attention_layer_init(key, num_q_channels, num_kv_channels,
                               num_heads, widening_factor=1):
    ka, km = jax.random.split(key)
    return {
        "attn": cross_attention_init(ka, num_q_channels, num_kv_channels,
                                     num_heads),
        "mlp": mlp_init(km, num_q_channels, widening_factor),
    }


def cross_attention_layer_apply(params, x_q, x_kv, *, num_heads,
                                key_padding_mask=None, attn_mask=None,
                                dropout_rate=0.0, rng=None,
                                deterministic=True,
                                policy: Policy = DEFAULT_POLICY,
                                impl=None, kv_chunk_size=1024, spmd=None,
                                kv_heads=None):
    """Residual(CrossAttention) then Residual(mlp) (model.py:29-33).

    ``kv_heads`` carries the pre-normed, pre-projected kv from
    ``cross_attention_kv`` — the encoder hoists it out of the layer
    scan because the kv tokens (and the shared layer weights) are
    loop-invariant there."""
    k_attn, k_r1, k_r2 = jax.random.split(_rng_or_dummy(rng, deterministic), 3)
    y = cross_attention_apply(
        params["attn"], x_q, x_kv, num_heads=num_heads,
        key_padding_mask=key_padding_mask, attn_mask=attn_mask,
        dropout_rate=dropout_rate, rng=k_attn, deterministic=deterministic,
        policy=policy, impl=impl, kv_chunk_size=kv_chunk_size, spmd=spmd,
        kv_heads=kv_heads)
    x = x_q + dropout(y, dropout_rate, rng=k_r1, deterministic=deterministic)
    y = mlp_apply(params["mlp"], x, policy=policy)
    return x + dropout(y, dropout_rate, rng=k_r2, deterministic=deterministic)


def self_attention_layer_init(key, num_channels, num_heads,
                              widening_factor=1):
    ka, km = jax.random.split(key)
    return {
        "attn": self_attention_init(ka, num_channels, num_heads),
        "mlp": mlp_init(km, num_channels, widening_factor),
    }


def self_attention_layer_apply(params, x, *, num_heads,
                               key_padding_mask=None, attn_mask=None,
                               dropout_rate=0.0, rng=None, deterministic=True,
                               policy: Policy = DEFAULT_POLICY,
                               impl: Optional[str] = None):
    k_attn, k_r1, k_r2 = jax.random.split(_rng_or_dummy(rng, deterministic), 3)
    y = self_attention_apply(
        params["attn"], x, num_heads=num_heads,
        key_padding_mask=key_padding_mask, attn_mask=attn_mask,
        dropout_rate=dropout_rate, rng=k_attn, deterministic=deterministic,
        policy=policy, impl=impl)
    x = x + dropout(y, dropout_rate, rng=k_r1, deterministic=deterministic)
    y = mlp_apply(params["mlp"], x, policy=policy)
    return x + dropout(y, dropout_rate, rng=k_r2, deterministic=deterministic)


def self_attention_block_init(key, num_layers, num_channels, num_heads,
                              widening_factor=1):
    """Stacked parameters for ``num_layers`` self-attention layers.

    Leaves carry a leading ``num_layers`` axis so the block applies
    under a single ``lax.scan`` (one compiled layer body).
    """
    keys = jax.random.split(key, num_layers)
    return jax.vmap(
        lambda k: self_attention_layer_init(k, num_channels, num_heads,
                                            widening_factor))(keys)


def self_attention_block_apply(stacked, x, *, num_heads, dropout_rate=0.0,
                               rng=None, deterministic=True,
                               policy: Policy = DEFAULT_POLICY,
                               impl: Optional[str] = None, layer=None):
    """``layer``: the scan's body in place of the plain layer, a
    ``(layer_params, x, key) -> x`` (the encoder hands in its
    checkpointed one under ``remat``)."""
    num_layers = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    keys = jax.random.split(_rng_or_dummy(rng, deterministic), num_layers)
    if layer is None:
        def layer(layer_params, x, k):
            return self_attention_layer_apply(
                layer_params, x, num_heads=num_heads,
                dropout_rate=dropout_rate, rng=k,
                deterministic=deterministic, policy=policy, impl=impl)

    def body(carry, layer_in):
        layer_params, k = layer_in
        return layer(layer_params, carry, k), None

    x, _ = jax.lax.scan(body, x, (stacked, keys))
    return x


# --- encoder -----------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PerceiverEncoder:
    """Generic Perceiver IO encoder (reference model.py:119-189)."""

    input_adapter: object
    latent_shape: Tuple[int, int]  # (N latents, C latent channels)
    num_layers: int
    num_cross_attention_heads: int = 4
    num_self_attention_heads: int = 4
    num_self_attention_layers_per_block: int = 2
    dropout: float = 0.0
    widening_factor: int = 1
    # Attention core. None picks per call site from what it observes
    # (ops/attention.pick_attention_core): the fused Pallas kernels on
    # a TPU where the shapes tile well, else the materialized einsum
    # core — cross-attention and the latent self-attention stack
    # alike. "einsum" / "flash" force one core for every attention of
    # the encoder; "chunked" (lax.scan online softmax) and the
    # shard_map impls apply to the latent ← input cross-attention, the
    # long-kv op, and leave the latent stack to pick.
    attention_impl: Optional[str] = None
    kv_chunk_size: int = 1024
    # For the shard_map sequence-parallel attention impls ("seqpar",
    # "ring", "ulysses"): (mesh, seq_axis, batch_axis) describing how
    # the input token axis is laid out across devices. None for the
    # single-device / pure-GSPMD paths.
    spmd: Optional[tuple] = None
    # Do not hold a layer's activations: every attention layer (the
    # cross-attention, each self-attention of the block) recomputes on
    # the backward pass what is cheap to recompute — norms, GELU,
    # casts, residual sums — from its input and from the dear values
    # that cross the boundary by name: the kernels' outputs, the
    # projections, the MLP's hidden layer (ops/remat.py; as many of
    # them as fit the device's memory, reckoned from the shapes). The
    # lever that fits the seq-2048 / 12-block configs (BASELINE.md
    # configs[4]) without computing the encoder twice.
    remat: bool = False

    def __post_init__(self):
        # fail at model build, not deep inside a jit trace
        if self.attention_impl not in ATTENTION_IMPLS:
            raise ValueError(
                f"unknown attention_impl {self.attention_impl!r}; "
                f"expected one of {ATTENTION_IMPLS}")

    def _layer_init(self, key):
        kc, ks = jax.random.split(key)
        return {
            "cross": cross_attention_layer_init(
                kc, self.latent_shape[1],
                self.input_adapter.num_input_channels,
                self.num_cross_attention_heads, self.widening_factor),
            "selfs": self_attention_block_init(
                ks, self.num_self_attention_layers_per_block,
                self.latent_shape[1], self.num_self_attention_heads,
                self.widening_factor),
        }

    def init(self, key):
        k_adapter, k_latent, k1, kn = jax.random.split(key, 4)
        params = {
            "input_adapter": self.input_adapter.init(k_adapter),
            "latent": trunc_normal_clamped(k_latent, self.latent_shape),
            "layer_1": self._layer_init(k1),
        }
        if self.num_layers > 1:
            params["layer_n"] = self._layer_init(kn)
        return params

    def _remat_policy(self, cross_layer, self_layer, layer_params, kv_heads,
                      latent, key):
        """The save list of this encoder's checkpointed layers: each
        layer differentiated once for its shapes alone says what its
        names would hold; ``choose_keeps`` takes the bytes of all layer
        applications on one device (rows split over a mesh's ``data``
        axis) against what the device has left."""
        def named_by(layer, *args):
            with untallied():
                return named_bytes(lambda *a: layer(*a, key), *args)

        cross = named_by(cross_layer, layer_params["cross"], kv_heads,
                         latent)
        selfs = named_by(self_layer, layer_shapes(layer_params["selfs"]),
                         latent)
        n_cross = self.num_layers
        n_self = n_cross * self.num_self_attention_layers_per_block
        shards = data_shards(latent)
        held = {name: (n_cross * cross[name] + n_self * selfs[name]) // shards
                for name in REMAT_NAMES}
        layer_in = ((n_cross + n_self) * latent.size
                    * latent.dtype.itemsize // shards)
        return jax.checkpoint_policies.save_only_these_names(
            *choose_keeps(held, layer_in))

    def apply(self, params, x, pad_mask=None, attn_mask=None, *, rng=None,
              deterministic: bool = True, policy: Policy = DEFAULT_POLICY):
        """Returns ``(x_latent, pad_mask)`` (reference model.py:189)."""
        b = x.shape[0]
        with device_scope("input_adapter"):
            x = self.input_adapter.apply(params["input_adapter"], x,
                                         policy=policy)
        with device_scope("enc_cross_attn"):
            latent = jnp.broadcast_to(
                policy.cast_param(params["latent"])[None],
                (b, *self.latent_shape))

        k1, kn = jax.random.split(_rng_or_dummy(rng, deterministic))

        def layer_kv(layer_params):
            # hoisted loop-invariant kv: the cross-attention norms and
            # projects the SAME input tokens with the SAME (shared)
            # weights in every scan iteration — compute once per
            # distinct parameter set, close over it in the scan body
            with device_scope("enc_cross_attn"):
                return cross_attention_kv(
                    layer_params["cross"]["attn"], x, policy=policy)

        def cross_layer(cross_params, kv_heads, latent, k):
            with device_scope("enc_cross_attn"):
                return cross_attention_layer_apply(
                    cross_params, latent, None,
                    num_heads=self.num_cross_attention_heads,
                    key_padding_mask=pad_mask, attn_mask=attn_mask,
                    dropout_rate=self.dropout, rng=k,
                    deterministic=deterministic, policy=policy,
                    impl=self.attention_impl,
                    kv_chunk_size=self.kv_chunk_size, spmd=self.spmd,
                    kv_heads=kv_heads)

        latent_impl = (self.attention_impl
                       if self.attention_impl in ("einsum", "flash")
                       else None)

        def self_layer(layer_params, latent, k):
            return self_attention_layer_apply(
                layer_params, latent,
                num_heads=self.num_self_attention_heads,
                dropout_rate=self.dropout, rng=k,
                deterministic=deterministic, policy=policy,
                impl=latent_impl)

        kv_1 = layer_kv(params["layer_1"])
        if self.remat:
            # One boundary a layer, not one around a whole block: a
            # layer's recomputed values are used where they are made,
            # and the save list says what crosses the boundary beside
            # the layer's input. One checkpointed function for layer_1
            # and layer_n alike: its body is traced once.
            keep = self._remat_policy(cross_layer, self_layer,
                                      params["layer_1"], kv_1, latent, k1)
            cross_layer = jax.checkpoint(cross_layer, policy=keep)
            self_layer = jax.checkpoint(self_layer, policy=keep,
                                        prevent_cse=False)  # scanned only

        def one_layer(layer_params, kv_heads, latent, k):
            k_cross, k_selfs = jax.random.split(_rng_or_dummy(k))
            latent = cross_layer(layer_params["cross"], kv_heads, latent,
                                 k_cross)
            with device_scope("latent_self_attn"):
                return self_attention_block_apply(
                    layer_params["selfs"], latent,
                    num_heads=self.num_self_attention_heads,
                    rng=k_selfs, layer=self_layer)

        latent = one_layer(params["layer_1"], kv_1, latent, k1)
        if self.num_layers > 1:
            # Weight-shared recurrence (model.py:186-187) over
            # per-iteration keys.
            keys = jax.random.split(kn, self.num_layers - 1)
            layer_n = params["layer_n"]
            kv_n = layer_kv(layer_n)

            def body(carry, k):
                # explicit compute-dtype carry: the latent rides the
                # scan in bf16 under the default policy (fp32 master
                # values live only in params/optimizer state)
                return one_layer(layer_n, kv_n,
                                 policy.cast_compute(carry), k), None

            if self.remat:
                # Unrolled: what a block's scan saves for the backward
                # pass (a stack a name) would be copied into this
                # scan's stacks on the way in and out again on the way
                # back, a pass over every saved byte each (PERF.md,
                # PR 29: 19 ms of lm_train's 252 ms step). The blocks'
                # own scans stay: unrolled too they run faster still,
                # in a program four times the size (PERF.md, PR 29).
                for k in keys:
                    latent, _ = body(latent, k)
            else:
                # one compiled body, scanned num_layers-1 times
                latent, _ = jax.lax.scan(body, latent, keys)
        return latent, pad_mask


# --- decoder -----------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PerceiverDecoder:
    """Generic Perceiver IO decoder (reference model.py:192-237)."""

    output_adapter: object
    latent_shape: Tuple[int, int]
    num_cross_attention_heads: int = 4
    dropout: float = 0.0
    widening_factor: int = 1
    # Chunk the K output queries through cross-attention + mlp in slices
    # of this size (None = no chunking). Exact: queries never attend to
    # each other. Needed for the 262k-query segmentation config where
    # the full (B, K, N) attention-weight tensor would blow HBM.
    query_chunk_size: Optional[int] = None
    # Attention kernel for the output-query ← latent cross-attention
    # (see PerceiverEncoder.attention_impl). "flash" blocks over the
    # query axis in-kernel, an alternative to query_chunk_size for the
    # 262k-query config.
    attention_impl: Optional[str] = None
    kv_chunk_size: int = 1024

    def __post_init__(self):
        if self.attention_impl not in DECODER_ATTENTION_IMPLS:
            raise ValueError(
                f"unknown decoder attention_impl "
                f"{self.attention_impl!r}; expected one of "
                f"{DECODER_ATTENTION_IMPLS} (the SPMD impls shard the "
                "encoder token axis and do not apply to output queries)")

    def init(self, key):
        k_out, k_query, k_cross = jax.random.split(key, 3)
        return {
            "output_adapter": self.output_adapter.init(k_out),
            "query": trunc_normal_clamped(k_query,
                                          self.output_adapter.output_shape),
            "cross": cross_attention_layer_init(
                k_cross, self.output_adapter.output_shape[-1],
                self.latent_shape[1], self.num_cross_attention_heads,
                self.widening_factor),
        }

    def apply(self, params, x, pad_mask=None, *, rng=None,
              deterministic: bool = True, policy: Policy = DEFAULT_POLICY,
              return_hidden: bool = False, query_positions=None):
        """``pad_mask`` is accepted for the encoder-tuple contract but —
        matching the reference (model.py:229,236) — not applied in the
        decoder cross-attention (the latent kv has no padding).

        ``return_hidden=True`` skips the output adapter and returns the
        pre-projection ``(B, K, C)`` query states — the hook for fused
        projection+loss kernels (``perceiver_tpu.ops.fused_ce``).

        ``query_positions`` (B, Q) int32 decodes ONLY those rows of the
        learned query array (per example). Output queries never attend
        to each other, so the selected rows are computed exactly as in
        the full decode — the masked-position-only MLM loss path uses
        this to shrink every decoder-side tensor from seq_len to the
        ~mask_p·seq_len positions the loss actually reads. Requires
        ``return_hidden=True`` (the output adapter's position-wise
        ``output_shape`` contract assumes the full query array)."""
        del pad_mask
        b, *d = x.shape
        if tuple(d) != tuple(self.latent_shape):
            raise ValueError(
                f"Latent shape {tuple(d)} different from required shape "
                f"{tuple(self.latent_shape)}")

        if query_positions is not None and not return_hidden:
            raise ValueError("query_positions requires return_hidden=True")
        with device_scope("dec_cross_attn"):
            if query_positions is not None:
                query = jnp.take(policy.cast_param(params["query"]),
                                 query_positions, axis=0)
            else:
                query = jnp.broadcast_to(
                    policy.cast_param(params["query"])[None],
                    (b, *self.output_adapter.output_shape))

        def run(q, k):
            with device_scope("dec_cross_attn"):
                return cross_attention_layer_apply(
                    params["cross"], q, x,
                    num_heads=self.num_cross_attention_heads,
                    dropout_rate=self.dropout, rng=k,
                    deterministic=deterministic, policy=policy,
                    impl=self.attention_impl,
                    kv_chunk_size=self.kv_chunk_size)

        num_q = query.shape[1]
        cs = self.query_chunk_size
        if cs is not None and num_q > cs:
            if num_q % cs != 0:
                raise ValueError(
                    f"query_chunk_size {cs} must divide num queries {num_q}")
            n_chunks = num_q // cs
            chunks = query.reshape(b, n_chunks, cs, -1).swapaxes(0, 1)
            keys = jax.random.split(_rng_or_dummy(rng, deterministic), n_chunks)
            out = jax.lax.map(lambda qk: run(qk[0], qk[1]), (chunks, keys))
            out = out.swapaxes(0, 1).reshape(b, num_q, -1)
        else:
            out = run(query, _rng_or_dummy(rng, deterministic))
        if return_hidden:
            return out
        with device_scope("output_adapter"):
            return self.output_adapter.apply(params["output_adapter"], out,
                                             policy=policy)


# --- composed models ---------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PerceiverIO:
    """Encoder ∘ decoder (reference model.py:321-325)."""

    encoder: PerceiverEncoder
    decoder: PerceiverDecoder

    def init(self, key):
        ke, kd = jax.random.split(key)
        return {"encoder": self.encoder.init(ke),
                "decoder": self.decoder.init(kd)}

    def apply(self, params, x, pad_mask=None, *, rng=None,
              deterministic: bool = True, policy: Policy = DEFAULT_POLICY):
        ke, kd = jax.random.split(_rng_or_dummy(rng, deterministic))
        latent, pad_mask = self.encoder.apply(
            params["encoder"], x, pad_mask, rng=ke,
            deterministic=deterministic, policy=policy)
        return self.decoder.apply(
            params["decoder"], latent, pad_mask, rng=kd,
            deterministic=deterministic, policy=policy)


def _pack_masked_positions(labels, capacity: int):
    """Left-pack each example's masked positions into (B, capacity).

    labels: (B, L) with ``IGNORE_INDEX`` at unmasked positions (the
    ``TextMasking`` contract). Returns ``(positions, labels_q,
    dropped)``: positions (B, capacity) int32 into the L axis (slot j
    holds the j-th masked position of that row; unused slots point at
    position 0 with labels_q == IGNORE so downstream weights vanish),
    labels_q (B, capacity) the labels at those positions, and dropped
    — the scalar count of masked positions past ``capacity`` (loss
    bias when nonzero; callers surface it exactly like the packed-CE
    overflow). The per-row scatter is the batched twin of
    ``ops.fused_ce.pack_positions``."""
    from perceiver_tpu.models.masking import IGNORE_INDEX

    b, l = labels.shape
    sel = labels != IGNORE_INDEX
    slot = jnp.cumsum(sel.astype(jnp.int32), axis=1) - 1
    count = slot[:, -1] + 1
    dropped = jnp.maximum(count - capacity, 0).sum()
    # unmasked and overflow positions land on a dump slot sliced off
    slot = jnp.where(sel & (slot < capacity), slot, capacity)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    pos = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None, :], (b, l))
    positions = jnp.zeros((b, capacity + 1), jnp.int32)
    positions = positions.at[rows, slot].set(pos)[:, :capacity]
    labels_q = jnp.full((b, capacity + 1), IGNORE_INDEX, labels.dtype)
    labels_q = labels_q.at[rows, slot].set(labels)[:, :capacity]
    return positions, labels_q, dropped


@dataclasses.dataclass(frozen=True)
class PerceiverMLM:
    """Masked-language model (reference model.py:296-318, plumbing fixed)."""

    encoder: PerceiverEncoder
    decoder: PerceiverDecoder
    masking: TextMasking

    def init(self, key):
        ke, kd = jax.random.split(key)
        return {"encoder": self.encoder.init(ke),
                "decoder": self.decoder.init(kd)}

    def apply(self, params, x_input, pad_mask=None, *, masking: bool = True,
              rng=None, deterministic: bool = True,
              policy: Policy = DEFAULT_POLICY, return_hidden: bool = False,
              query_capacity: Optional[int] = None):
        """Returns ``(logits, labels)``; ``labels`` is None when
        ``masking=False`` (inference path, reference utils.py:30).

        ``return_hidden=True`` returns pre-vocab-projection decoder
        states ``(B, l, C)`` instead of logits (fused-loss hook; the
        vocab projection then happens inside the loss, see
        ``perceiver_tpu.ops.fused_ce``).

        ``query_capacity`` (static int Q, requires masking and
        return_hidden) switches to the masked-position-only decode:
        each example's ≤Q masked positions are packed left into a
        (B, Q) position buffer and ONLY those decoder queries are
        computed — exact, because output queries never attend to each
        other, and the loss reads nothing else. Returns
        ``(hidden (B,Q,C), labels (B,Q) IGNORE-padded, dropped)`` where
        ``dropped`` counts masked positions past Q (loss bias when
        nonzero — surface it like the packed-CE overflow). Every
        decoder-side tensor shrinks seq_len → Q ≈ mask_p·seq_len, the
        single largest HBM cut on the flagship MLM step."""
        l = x_input.shape[1]
        if masking and rng is None:
            # a silent constant key would mask the same positions in
            # every batch — val_loss would be computed on one fixed,
            # position-correlated 15% subset
            raise ValueError("masking=True requires an explicit `rng` key")
        if query_capacity is not None and not (masking and return_hidden):
            raise ValueError(
                "query_capacity requires masking=True and "
                "return_hidden=True (it selects masked positions and "
                "bypasses the output adapter)")
        k_mask, k_enc, k_dec = jax.random.split(
            _rng_or_dummy(rng, deterministic), 3)

        if masking:
            with device_scope("input_adapter"):
                x_masked, labels = self.masking.apply(k_mask, x_input,
                                                      pad_mask)
        else:
            x_masked, labels = x_input, None

        latent, _ = self.encoder.apply(
            params["encoder"], x_masked, pad_mask, rng=k_enc,
            deterministic=deterministic, policy=policy)
        if query_capacity is not None:
            with device_scope("dec_cross_attn"):
                positions, labels_q, dropped = _pack_masked_positions(
                    labels, query_capacity)
            hidden = self.decoder.apply(
                params["decoder"], latent, rng=k_dec,
                deterministic=deterministic, policy=policy,
                return_hidden=True, query_positions=positions)
            return hidden, labels_q, dropped
        out = self.decoder.apply(
            params["decoder"], latent, rng=k_dec,
            deterministic=deterministic, policy=policy,
            return_hidden=return_hidden)[:, :l, :]
        return out, labels
