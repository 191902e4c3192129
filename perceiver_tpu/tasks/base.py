"""Shared task hparams and loss helpers.

``TaskConfig`` carries the exact hparam surface of the reference's
``LitModel`` (``lightning.py:29-42``): num_latents=64,
num_latent_channels=64, 3 encoder layers, 4/4 cross/self heads, 6
self-attention layers per block, 4 decoder heads, dropout 0.0.

Losses are weighted by the batch's ``valid`` row mask (the input
pipeline pads final partial batches to keep shapes static; see
``perceiver_tpu.data.core``), so metrics remain exact.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from perceiver_tpu.models.masking import IGNORE_INDEX
from perceiver_tpu.obs.trace import device_scope


@dataclasses.dataclass(frozen=True)
class TaskConfig:
    num_latents: int = 64
    num_latent_channels: int = 64
    num_encoder_layers: int = 3
    num_encoder_cross_attention_heads: int = 4
    num_encoder_self_attention_heads: int = 4
    num_encoder_self_attention_layers_per_block: int = 6
    num_decoder_cross_attention_heads: int = 4
    dropout: float = 0.0
    # do not hold the encoder layers' activations: the backward pass
    # recomputes what is cheap (norms, GELU, casts) and keeps by name
    # what is dear (kernel outputs, projections, the MLP's hidden
    # layer), as much as the device's memory takes (memory ↔ time for
    # the large configs; see PerceiverEncoder.remat, ops/remat.py)
    remat: bool = False
    # encoder cross-attention kernel (PerceiverEncoder.attention_impl):
    # None/"einsum", "chunked", "flash", or — given a mesh with a "seq"
    # axis — the shard_map sequence-parallel impls "seqpar"/"ring"/
    # "ulysses"
    attention_impl: Optional[str] = None
    kv_chunk_size: int = 1024
    # Attention kernel for the decoder's output-query ← latent
    # cross-attention (PerceiverDecoder.attention_impl). None keeps the
    # einsum path; "chunked"/"flash" stream the latent kv without
    # materializing the (B, M, N) weight tensor. The SPMD impls shard
    # the encoder token axis and do not apply to output queries.
    decoder_attention_impl: Optional[str] = None
    # import a trained reference (PyTorch / PyTorch-Lightning)
    # checkpoint as this task's full model — the migration path for
    # reference users (reference README.md:72-74; utils/torch_import)
    torch_ckpt: Optional[str] = None

    def restore_pretrained(self, params):
        """``torch_ckpt`` → whole-model import of a trained reference
        checkpoint (key contract: utils/torch_import). Subclasses with
        richer transfer flags override and fall back to this."""
        if self.torch_ckpt is None:
            return params
        from perceiver_tpu.utils.torch_import import restore_from_torch
        return restore_from_torch(self.torch_ckpt, template=params)

    def __post_init__(self):
        from perceiver_tpu.ops.attention import (
            ATTENTION_IMPLS,
            DECODER_ATTENTION_IMPLS,
        )
        # fail at config time, not deep inside a jit trace — first the
        # domain checks, then the cross-field feature guards
        if self.attention_impl not in ATTENTION_IMPLS:
            raise ValueError(
                f"unknown attention_impl {self.attention_impl!r}; "
                f"expected one of {ATTENTION_IMPLS}")
        if self.decoder_attention_impl not in DECODER_ATTENTION_IMPLS:
            raise ValueError(
                f"decoder_attention_impl="
                f"{self.decoder_attention_impl!r} — the decoder "
                "cross-attention supports None, 'einsum', 'chunked', or "
                "'flash' (the SPMD impls shard the encoder token axis "
                "and do not apply to output queries)")
        # attention-weight dropout is only implemented for the einsum
        # and chunked kernels (chunked streams it — see
        # ops/chunked_attention.py). The other impls DEGRADE to chunked
        # at trace time with a one-time warning (ops/attention.py
        # mha_apply), so dropout>0 configs train under every impl
        # instead of failing — warn here too, where the config is
        # built, so the degrade is visible before the first trace.
        if self.dropout > 0.0:
            from perceiver_tpu.ops.attention import _warn_dropout_degrade
            if self.attention_impl in ("flash", "seqpar", "ring",
                                       "ulysses"):
                _warn_dropout_degrade(self.attention_impl)
            if self.decoder_attention_impl == "flash":
                _warn_dropout_degrade(self.decoder_attention_impl)

    @property
    def latent_shape(self) -> Tuple[int, int]:
        return (self.num_latents, self.num_latent_channels)

    # input fields whose second axis is the token/sequence axis; token
    # tasks set this so those arrays ride a 'seq' mesh axis when one
    # exists (class attribute, not a dataclass field)
    seq_partition_fields = ()

    def batch_partition(self, name: str, ndim: int, mesh) -> tuple:
        """Mesh axes to shard an input field's post-batch dims over
        (the batch axis itself is always sharded over 'data')."""
        if (mesh is not None and "seq" in mesh.axis_names
                and name in self.seq_partition_fields and ndim >= 2):
            return ("seq",)
        return ()

    def encoder_spmd(self, mesh) -> Optional[tuple]:
        """(mesh, seq_axis, batch_axis) for the shard_map attention
        impls, or None for single-device / pure-GSPMD kernels."""
        if self.attention_impl not in ("seqpar", "ring", "ulysses"):
            return None
        if mesh is None or "seq" not in mesh.axis_names:
            raise ValueError(
                f"attention_impl={self.attention_impl!r} needs a mesh "
                "with a 'seq' axis (make_mesh(..., seq_parallel=N)); "
                f"got {None if mesh is None else mesh.axis_names}")
        return (mesh, "seq", "data" if "data" in mesh.axis_names else None)


def masked_mean(values, mask):
    """Mean of ``values`` where ``mask`` (same leading shape) is set."""
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    return (values.astype(jnp.float32) * mask).sum() / denom


@device_scope("loss")
def cross_entropy(logits, labels, valid=None,
                  ignore_index: Optional[int] = None):
    """CE in fp32 with optional row mask and label ignore value."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    safe_labels = jnp.clip(labels, 0)
    nll = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]

    mask = jnp.ones(labels.shape, jnp.float32)
    if ignore_index is not None:
        mask = mask * (labels != ignore_index)
    if valid is not None:
        mask = mask * valid.reshape(valid.shape + (1,) * (labels.ndim - 1))
    return masked_mean(nll, mask)


@device_scope("loss")
def accuracy(logits, labels, valid=None):
    pred = jnp.argmax(logits, axis=-1)
    correct = (pred == labels)
    mask = valid if valid is not None else jnp.ones(labels.shape, bool)
    return masked_mean(correct, mask)


IGNORE = IGNORE_INDEX
