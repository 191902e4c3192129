"""Masked-language-model task (reference ``LitMaskedLanguageModel``,
``lightning.py:174-256``): TextInputAdapter/TextOutputAdapter around
PerceiverMLM, CE over (B, M, V) logits vs −100-ignored labels.

The reference's version cannot construct its model — it calls
``TextMasking(vocab_size)`` without the required token-id args
(``lightning.py:213``, SURVEY.md §2.6.2). Here the masking config is
explicit, defaulting to the framework tokenizer's special-token layout.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

from perceiver_tpu.adapters import TextInputAdapter, TextOutputAdapter
from perceiver_tpu.models import (
    PerceiverDecoder,
    PerceiverEncoder,
    PerceiverMLM,
    TextMasking,
)
from perceiver_tpu.ops.policy import Policy, DEFAULT_POLICY
from perceiver_tpu.tasks.base import IGNORE, TaskConfig, cross_entropy
from perceiver_tpu.tokenizer import (
    MASK_TOKEN_ID,
    SPECIAL_TOKENS,
    UNK_TOKEN_ID,
)


def create_encoder(cfg: TaskConfig, vocab_size: int,
                   max_seq_len: int, mesh=None) -> PerceiverEncoder:
    """Shared MLM/text-classifier encoder builder (lightning.py:186-200)."""
    input_adapter = TextInputAdapter(
        vocab_size=vocab_size, max_seq_len=max_seq_len,
        num_input_channels=cfg.num_latent_channels)
    return PerceiverEncoder(
        input_adapter=input_adapter,
        latent_shape=cfg.latent_shape,
        num_layers=cfg.num_encoder_layers,
        num_cross_attention_heads=cfg.num_encoder_cross_attention_heads,
        num_self_attention_heads=cfg.num_encoder_self_attention_heads,
        num_self_attention_layers_per_block=(
            cfg.num_encoder_self_attention_layers_per_block),
        dropout=cfg.dropout,
        attention_impl=cfg.attention_impl,
        kv_chunk_size=cfg.kv_chunk_size,
        spmd=cfg.encoder_spmd(mesh),
        remat=cfg.remat)


@dataclasses.dataclass(frozen=True)
class MaskedLanguageModelTask(TaskConfig):
    vocab_size: int = 10003
    max_seq_len: int = 512
    masked_samples: Optional[List[str]] = None
    num_predictions: int = 3
    mask_p: float = 0.15
    # Loss implementation — all numerically equivalent (fp32 softmax):
    #   "dense":  CE over materialized (B, M, V) logits (reference
    #             lightning.py:223-226 semantics, literally).
    #   "fused":  chunked projection+CE, never materializing the full
    #             logits (ops/fused_ce.py) — O(chunk·V) peak memory.
    #   "packed": fused CE over only the ~mask_p selected positions,
    #             scatter-packed to a static capacity — identical loss
    #             and gradients (zero-weight rows contribute zero), and
    #             the dominant vocab projection shrinks ~1/mask_p×.
    #   "pallas": packed positions fed to the fully fused Pallas TPU
    #             kernel (ops/pallas_ce.py) — logits tiles never leave
    #             VMEM (interpreter mode off-TPU).
    loss_impl: str = "packed"
    ce_chunk_size: int = 8192
    # packed-buffer capacity as a fraction of B·M. None derives
    # mask_p plus an additive ~6σ Binomial tail margin (computed at
    # loss time from the actual B·M): the selected count is
    # stochastically dominated by Binomial(B·M, mask_p), so overflow —
    # which silently drops rows — stays negligible at small
    # batch·seq products too, while the buffer (and its vocab-matmul
    # cost) tracks the true ~mask_p fraction
    packed_capacity: Optional[float] = None

    def __post_init__(self):
        super().__post_init__()
        if self.loss_impl not in ("dense", "fused", "packed", "pallas"):
            raise ValueError(
                f"unknown loss_impl {self.loss_impl!r}; expected "
                "'dense', 'fused', 'packed', or 'pallas'")

    def build(self, mesh=None) -> PerceiverMLM:
        encoder = create_encoder(self, self.vocab_size, self.max_seq_len,
                                 mesh=mesh)
        output_adapter = TextOutputAdapter(
            vocab_size=self.vocab_size, max_seq_len=self.max_seq_len,
            num_output_channels=self.num_latent_channels)
        decoder = PerceiverDecoder(
            output_adapter=output_adapter,
            latent_shape=self.latent_shape,
            num_cross_attention_heads=self.num_decoder_cross_attention_heads,
            dropout=self.dropout,
            attention_impl=self.decoder_attention_impl,
            kv_chunk_size=self.kv_chunk_size)
        masking = TextMasking(
            vocab_size=self.vocab_size, unk_token_id=UNK_TOKEN_ID,
            mask_token_id=MASK_TOKEN_ID,
            num_special_tokens=len(SPECIAL_TOKENS), mask_p=self.mask_p)
        return PerceiverMLM(encoder, decoder, masking)

    # token arrays ride the 'seq' mesh axis when one exists — GSPMD
    # (or the shard_map attention impls via encoder_spmd) partitions
    # the encoder cross-attention over the kv axis
    seq_partition_fields = ("input_ids", "pad_mask")

    def _masked_sample_predictions(self, trainer, state):
        """Top-k fills for the configured masked samples, or None when
        there are no samples or the datamodule has no tokenizer."""
        if not self.masked_samples:
            return None
        dm = trainer.datamodule
        if getattr(dm, "collator", None) is None:
            return None
        from perceiver_tpu.utils.predict import predict_masked_samples
        samples = [s.replace("<MASK>", "[MASK]")
                   for s in self.masked_samples]
        predictions = predict_masked_samples(
            samples, dm.collator.encode, dm.tokenizer, trainer.model,
            state.params, num_predictions=self.num_predictions,
            policy=trainer.policy)
        return list(zip(samples, predictions))

    def on_validation_epoch_end(self, trainer, state):
        """Log top-k predictions for the configured masked samples to
        the TB text plugin (reference ``lightning.py:241-256``)."""
        pairs = self._masked_sample_predictions(trainer, state)
        if pairs is None:
            return
        text = "\n\n".join("  \n".join([s] + ps) for s, ps in pairs)
        trainer.writer.add_text("sample predictions", text,
                                trainer.global_step)

    def predict(self, trainer, state):
        """CLI ``predict`` subcommand — the reference's only inference
        entry (masked-sample top-k fills, ``utils.py:22-43`` / SURVEY
        §3.5) as a standalone verb: encode ``--model.masked_samples``,
        run with ``masking=False``, return k fills per sample."""
        pairs = self._masked_sample_predictions(trainer, state)
        if pairs is None:
            raise SystemExit(
                "predict needs --model.masked_samples and a datamodule "
                "with a tokenizer (run fit or point --data at one)")
        # list-of-pairs, not a dict: duplicate / normalization-colliding
        # samples must each keep their predictions, in request order
        return [{"sample": s, "predictions": ps} for s, ps in pairs]

    def loss_and_metrics(self, model, params, batch, *, rng=None,
                         deterministic: bool = True,
                         policy: Policy = DEFAULT_POLICY):
        if self.loss_impl == "dense":
            logits, labels = model.apply(
                params, batch["input_ids"], batch["pad_mask"], rng=rng,
                deterministic=deterministic, policy=policy)
            loss = cross_entropy(logits, labels, batch.get("valid"),
                                 ignore_index=IGNORE)
            return loss, {"loss": loss}

        import jax.numpy as jnp

        from perceiver_tpu.ops.fused_ce import (
            fused_linear_cross_entropy,
            pack_positions,
        )

        packed = self.loss_impl in ("packed", "pallas")
        l_full = batch["input_ids"].shape[1]
        dropped = None
        if packed:
            # masked-position-only decode: the loss reads nothing but
            # the ~mask_p·L masked positions, and Perceiver output
            # queries never attend to each other, so decoding ONLY
            # those rows is exact — every decoder-side tensor shrinks
            # seq_len → Q (the flagship step's largest HBM cut).
            # Q = per-row mean + ~6σ Binomial(L, mask_p) tail, the same
            # margin the global packed buffer below uses.
            p = self.mask_p
            sigma_row = (l_full * p * (1.0 - p)) ** 0.5
            q_cap = min(l_full, int(l_full * p + 6.0 * sigma_row) + 8)
            hidden, labels, dropped = model.apply(
                params, batch["input_ids"], batch["pad_mask"], rng=rng,
                deterministic=deterministic, policy=policy,
                return_hidden=True, query_capacity=q_cap)
        else:
            hidden, labels = model.apply(
                params, batch["input_ids"], batch["pad_mask"], rng=rng,
                deterministic=deterministic, policy=policy,
                return_hidden=True)
        b, l, c = hidden.shape
        weight = (labels != IGNORE).astype(jnp.float32)
        valid = batch.get("valid")
        if valid is not None:
            weight = weight * valid.astype(jnp.float32)[:, None]
        hidden = hidden.reshape(b * l, c)
        labels = labels.reshape(b * l)
        weight = weight.reshape(b * l)
        metrics = {}
        if packed:
            # capacity tracks the FULL B·L position count (the masked
            # total is Binomial(B·L, mask_p) no matter how the decoder
            # rows were pre-packed per example)
            n = b * l_full
            if self.packed_capacity is not None:
                cap = int(n * min(self.packed_capacity, 1.0))
            else:
                # mean + ~6σ Binomial(n, mask_p) tail: the σ term is
                # what keeps overflow negligible when n is small
                p = self.mask_p
                sigma = (n * p * (1.0 - p)) ** 0.5
                cap = int(n * p + 6.0 * sigma) + 8
            cap = min(max(cap, 1), b * l)
            hidden, labels, weight, overflow = pack_positions(
                hidden, labels, weight, cap)
            # per-example pre-pack drops count exactly like global
            # capacity overflow: contributing rows lost from the loss
            overflow = overflow + dropped
            # overflow = contributing rows silently dropped by the
            # static capacity: it biases the loss, so it is reported
            # as the train_ce_overflow scalar. No in-graph warning: a
            # host callback would make the step a different program
            # per backend and uncacheable (cache/exec_cache.py).
            metrics["ce_overflow"] = overflow
        adapter_params = params["decoder"]["output_adapter"]["linear"]
        if self.loss_impl == "pallas":
            from perceiver_tpu.ops.pallas_ce import (
                pallas_linear_cross_entropy,
            )
            loss = pallas_linear_cross_entropy(
                adapter_params, hidden, labels, weight, policy=policy)
        else:
            loss = fused_linear_cross_entropy(
                adapter_params, hidden, labels, weight,
                chunk_size=min(self.ce_chunk_size, hidden.shape[0]),
                policy=policy)
        return loss, {"loss": loss, **metrics}
