"""Block-diffusion language-model task (BD3-LM, arXiv:2503.09573, as
SDAR, arXiv:2510.06303, adapts an autoregressive mixture-of-experts
model to it) on a stack of Qwen3-MoE layers (``models/hybrid_lm.py``,
pattern ``*E`` a published layer): a row ``x`` of ``L`` tokens in
blocks of ``block_length`` is noised inside the step from the step's
key, and the model runs the noised copy beside the clean one::

    for each row and block b:  t_b ~ U(t_min, 1)
    m_i ~ Bernoulli(t_block(i));  xt_i = MASK if m_i else x_i
    h = stack([xt ; x])            2 L positions, the block-diffusion mask
                                   (ops.attention.block_diffusion_mask),
                                   position j mod L for index j
    loss = (1 / (rows L)) sum_rows sum_{i < L, m_i} (1 / t_block(i))
           * (-log softmax(rms_f(h_i) Wh)[x_i])

the token at its own position (no shift), the weight ``1 / t`` of the
linear schedule of masked diffusion, each position of a block masked
independently. The head is read through ``ops.fused_ce.fused_linear_nll``
at the noised half alone: the clean half's final state feeds no loss.

The fields are the published ``config.json``'s under its own names
(``sdar_moe``, a Qwen3-MoE layer); the defaults are SDAR-30B-A3B-Chat's.
``block_length``, ``t_min`` and ``mask_token_id`` are the method's.
``held_experts`` and ``first_expert`` say which of the ``num_experts``
this chip holds (None: all), and a batch may name each expert layer's
share itself (``first_experts``, (rows, layers) int32, every row
alike), as ``HybridLMTask``'s does. ``vocab_size`` may be a slice of
the published vocabulary: ids, ``mask_token_id``, logits and loss are
over it. There is no auxiliary balancing loss. A row's padding
(``pad_mask``, right padding) carries no loss and is not masked out of
the attention: only the positions of the block the text ends in see it.

Every step's metrics carry ``bd_masked_positions`` (the positions the
loss read) and ``bd_weight_sum`` (the sum of their ``1 / t`` weights:
``rows L`` in expectation, so it says how heavy a step's tail was)
beside ``moe_assignments``, ``moe_load_max_over_mean`` (as
``HybridLMTask``'s) and ``moe_full_buffer_layers`` (the expert layers
of the step whose assignments did not fit the usual buffer,
``ops.moe.usual_rows``, and took the ``T x top_k`` one).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from perceiver_tpu.models.hybrid_lm import HybridLM
from perceiver_tpu.obs.trace import device_scope
from perceiver_tpu.ops.fused_ce import fused_linear_nll
from perceiver_tpu.ops.moe import usual_rows
from perceiver_tpu.ops.policy import DEFAULT_POLICY, Policy
from perceiver_tpu.tokenizer import MASK_TOKEN_ID


@device_scope("bd_noise")
def block_noise(rng, ids, block_length: int, t_min: float):
    """``(masked (B, L) bool, t (B, L) float32)``: a masking rate
    ``t ~ U(t_min, 1)`` a row and block, spread over the block's
    positions, and each position masked with it, independently."""
    rows, seq = ids.shape
    k_t, k_m = jax.random.split(rng)
    t = jnp.repeat(jax.random.uniform(
        k_t, (rows, seq // block_length), jnp.float32, t_min, 1.0),
        block_length, axis=1)
    return jax.random.uniform(k_m, (rows, seq), jnp.float32) < t, t


@dataclasses.dataclass(frozen=True)
class BlockDiffusionLMTask:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 4096
    # positions a block; a row's length is a multiple of it
    block_length: int = 4
    # the least masking rate a block draws
    t_min: float = 1e-3
    mask_token_id: int = MASK_TOKEN_ID
    # the experts this chip holds, from first_expert on; None: all
    held_experts: Optional[int] = None
    first_expert: int = 0
    # recompute every layer on the backward pass
    remat: bool = False
    # positions a chunk of the head projection + CE
    ce_chunk_size: int = 2048

    def build(self, mesh=None) -> HybridLM:
        del mesh   # one device or pure GSPMD: nothing to wire
        return HybridLM(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            pattern="*E" * self.num_hidden_layers,
            max_seq_len=self.max_seq_len,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            head_dim=self.head_dim, rope_theta=self.rope_theta,
            qk_norm=True, n_routed_experts=self.num_experts,
            num_experts_per_tok=self.num_experts_per_tok,
            moe_intermediate_size=self.moe_intermediate_size,
            router_scoring="softmax", norm_topk_prob=self.norm_topk_prob,
            gated_experts=True, held_experts=self.held_experts,
            first_expert=self.first_expert, norm_eps=self.rms_norm_eps,
            remat=self.remat)

    def batch_partition(self, name: str, ndim: int, mesh) -> tuple:
        """Rows over 'data' only: the kernels see whole rows."""
        return ()

    def loss_and_metrics(self, model: HybridLM, params, batch, *, rng=None,
                         deterministic: bool = True,
                         policy: Policy = DEFAULT_POLICY):
        del deterministic   # no dropout; the noise is the objective's
        ids = batch["input_ids"]
        rows, seq = ids.shape
        if seq % self.block_length:
            raise ValueError(f"rows of {seq} tokens do not divide into "
                             f"blocks of {self.block_length}")
        masked, t = block_noise(
            jax.random.key(0) if rng is None else rng, ids,
            self.block_length, self.t_min)
        with device_scope("bd_noise"):
            noised = jnp.where(masked, jnp.asarray(self.mask_token_id,
                                                   ids.dtype), ids)
            live = jnp.ones(ids.shape, bool)
            if "pad_mask" in batch:
                live = live & ~batch["pad_mask"]
            if "valid" in batch:
                live = live & batch["valid"].astype(bool)[:, None]
            read = masked & live
            weights = jnp.where(read, 1.0 / t, 0.0)
        firsts = batch.get("first_experts")
        h, loads = model.hidden_states(
            params, jnp.concatenate([noised, ids], axis=1), policy=policy,
            first_experts=None if firsts is None else firsts[0],
            block_diffusion=(seq, self.block_length))
        nll = fused_linear_nll(
            params["head"], h[:, :seq].reshape(-1, h.shape[-1]),
            ids.reshape(-1), chunk_size=self.ce_chunk_size,
            policy=policy).reshape(ids.shape)
        loss = (nll * weights).sum() / jnp.maximum(live.sum(), 1)
        usual = usual_rows(rows * 2 * seq, self.num_experts_per_tok,
                           model.num_held_experts, self.num_experts)
        loads_f = loads.astype(jnp.float32)
        metrics = {
            "loss": loss,
            "bd_masked_positions": read.sum().astype(jnp.float32),
            "bd_weight_sum": weights.sum(),
            "moe_assignments": loads_f.sum(),
            "moe_load_max_over_mean": (
                loads_f.max(-1) / jnp.maximum(loads_f.mean(-1), 1.0)).max(),
            "moe_full_buffer_layers": (
                loads.sum(-1) > usual).sum().astype(jnp.float32),
        }
        return loss, metrics
