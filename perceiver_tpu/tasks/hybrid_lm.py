"""Causal language-model task for a hybrid state-space /
mixture-of-experts stack (``models/hybrid_lm.py``): mean next-token
cross-entropy over the positions that have a label, one pass, the
labels and the fused head reading of ``tasks/causal_lm.py``.

The fields are the published ``config.json``'s under its own names
(``nemotron_h``); the defaults are NVIDIA-Nemotron-3-Nano-30B-A3B's.
A stack of another family's layers is the same task with its own
pattern and its own keys: ``qwen3_next`` (Qwen3-Next) is ``LELELE*E`` a
published period of four, a gated delta-rule mixer ``L`` (``linear_*``)
three layers of four, a softmax attention with an output gate, partial
rotary positions and q/k norms the fourth, zero-centred norms, a softmax
router with the top-k renormalised, gated experts and a gated shared
expert under a sigmoid gate (``scripts/configs/gated_delta_lm_1chip.yaml``).
``kimi_linear`` (Kimi Linear) is ``KD`` then ``KEKEAE`` a period: Kimi
Delta Attention ``K`` (``kda_*``), latent attention without positions
``A`` (``kv_lora_rank``, ``qk_*_head_dim``, ``v_head_dim``), a leading
dense gated MLP ``D`` (``intermediate_size``), a sigmoid router whose
renormalised top-k is scaled, gated experts and a shared expert gated
with three matrices alone (``shared_expert_kind`` ``glu``;
``scripts/configs/kimi_linear_lm_1chip.yaml``).
``glm4_moe_lite`` (GLM-4.7-Flash) is ``AE`` a published layer: latent
attention with a query latent (``q_lora_rank``) and rotary positions on
its ``qk_rope_head_dim`` shared channels (``rope_theta``), the same
experts at a scaling of 1.8, and after the stack a **multi-token
prediction module** (``num_nextn_predict_layers`` 1; DeepSeek-V3,
arXiv:2412.19437, section 2.2): position ``i`` of the module reads the
stack's state there beside the embedding of id ``i + 1`` and predicts id
``i + 2`` through the stack's own head, a second ``fused_linear_nll`` on
``params["head"]``; ``loss = main_loss + mtp_loss_weight x mtp_loss``,
each a mean over its own labelled positions (a row's last position has
no next id, its last two none after that), and no gradient is stopped
(``scripts/configs/glm_moe_lite_lm_1chip.yaml``).
``held_experts`` and ``first_expert`` say which of the
``n_routed_experts`` this chip holds (None: all): the router keeps its
width and its experts a token, and what the absent experts would have
added is left out of every expert layer's result. A batch may name
each expert layer's share itself (``first_experts``, (rows, expert
layers) int32, every row alike: the first expert held in each expert
layer), in ``first_expert``'s place: which share a chip plays is then a
value of the step, not of its program. ``vocab_size`` may be a slice of
the published vocabulary: ids, logits and loss are over it.

The balancing buffer ``e_score_correction_bias`` is held at 0, outside
the parameter tree, and there is no auxiliary loss. Every step's
metrics carry ``moe_assignments`` (the (token, held expert) pairs the
expert layers computed) and ``moe_load_max_over_mean`` (the fullest
held expert's load over the mean of the held, the largest over the
expert layers: the imbalance the no-drop rule is there for); under a
softmax router, whose untrained loads are far from even, also
``moe_full_buffer_layers`` (the expert layers of the step whose
assignments did not fit the usual buffer, ``ops.moe.usual_rows``, and
took the ``T x top_k`` one); with a prediction module also
``main_loss``, ``mtp_loss`` and ``mtp_positions`` (the positions the
module's loss read), and ``first_experts`` and the loads count the
module's expert layer last.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from perceiver_tpu.models.hybrid_lm import HybridLM, prediction_modules
from perceiver_tpu.obs.trace import device_scope
from perceiver_tpu.ops.fused_ce import fused_linear_nll
from perceiver_tpu.ops.moe import usual_rows
from perceiver_tpu.ops.policy import DEFAULT_POLICY, Policy
from perceiver_tpu.tasks.causal_lm import next_token_targets


@dataclasses.dataclass(frozen=True)
class HybridLMTask:
    vocab_size: int = 131072
    hidden_size: int = 2688
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    # L, a gated delta-rule mixer (qwen3_next's keys; 0: no such layer)
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    delta_chunk_size: int = 64
    # K, Kimi Delta Attention (kimi_linear's linear_attn_config: num_heads,
    # head_dim, short_conv_kernel_size; 0: no such layer)
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel_size: int = 4
    # A, latent attention over num_attention_heads heads (kimi_linear's
    # and glm4_moe_lite's keys; 0: no such layer); q_lora_rank 0: no
    # query latent; with rope_theta the rope channels turn
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    q_lora_rank: int = 0
    # D, a dense gated MLP's width (0: no such layer)
    intermediate_size: int = 0
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # rotary positions at this base over the first partial_rotary_factor
    # of a * head's channels and over A's qk_rope_head_dim; None: no
    # position embedding
    rope_theta: Optional[float] = None
    partial_rotary_factor: float = 1.0
    qk_norm: bool = False
    attn_output_gate: bool = False
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    router_scoring: str = "sigmoid"
    norm_topk_prob: bool = True
    gated_experts: bool = False
    # relu2, gated (three matrices under a sigmoid gate of a column) or
    # glu (the three matrices alone)
    shared_expert_kind: str = "relu2"
    norm_eps: float = 1e-5
    # every RMSNorm as x / rms(x) * (1 + w)
    zero_centered_norms: bool = False
    max_seq_len: int = 4096
    # the experts this chip holds, from first_expert on; None: all
    held_experts: Optional[int] = None
    first_expert: int = 0
    # recompute every layer on the backward pass
    remat: bool = False
    # positions a chunk of the head projection + CE
    ce_chunk_size: int = 2048
    # multi-token prediction modules after the stack (0: none; depth 1
    # is what there is) and the weight of their loss beside the
    # next-token loss (DeepSeek-V3's 0.3; read with a module only)
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3

    def build(self, mesh=None) -> HybridLM:
        del mesh   # one device or pure GSPMD: nothing to wire
        fields = {f.name for f in dataclasses.fields(HybridLM)}
        return HybridLM(
            pattern=self.hybrid_override_pattern,
            **{k: v for k, v in dataclasses.asdict(self).items()
               if k in fields})

    def batch_partition(self, name: str, ndim: int, mesh) -> tuple:
        """Rows over 'data' only: the scan and the causal kernels see
        whole rows."""
        return ()

    def loss_and_metrics(self, model: HybridLM, params, batch, *, rng=None,
                         deterministic: bool = True,
                         policy: Policy = DEFAULT_POLICY):
        del rng, deterministic   # no dropout, no masking to draw
        labels, mask = next_token_targets(batch)
        firsts = batch.get("first_experts")
        firsts = None if firsts is None else firsts[0]

        def mean_nll(state, labels, mask):
            # the count before the reading: the order the step's text
            # has had since before there was a second reading
            count = jnp.maximum(mask.sum(), 1.0)
            nll = fused_linear_nll(
                params["head"], state.reshape(-1, state.shape[-1]),
                labels.reshape(-1), chunk_size=self.ce_chunk_size,
                policy=policy).reshape(mask.shape)
            return (nll * mask).sum() / count

        if not model.num_nextn_predict_layers:
            h, loads = model.hidden_states(
                params, batch["input_ids"], policy=policy,
                first_experts=firsts)
            loss = mean_nll(h, labels, mask)
            metrics = {"loss": loss}
        else:
            # position i of the module reads the stack's state there and
            # the embedding of id i+1 and predicts id i+2: the labels one
            # further on, where both ids are there
            h, z, loads = model.prediction_states(
                params, batch["input_ids"], labels, policy=policy,
                first_experts=firsts)
            ahead = jnp.pad(labels[:, 1:], ((0, 0), (0, 1)))
            ahead_mask = mask * jnp.pad(mask[:, 1:], ((0, 0), (0, 1)))
            main = mean_nll(h, labels, mask)
            with device_scope("loss"), device_scope("mtp_loss"):
                ahead_loss = mean_nll(z, ahead, ahead_mask)
            prediction_modules.add(
                f"depth {model.num_nextn_predict_layers}, loss weight "
                f"{self.mtp_loss_weight:g}, the stack's head and embedding")
            loss = main + self.mtp_loss_weight * ahead_loss
            metrics = {"loss": loss, "main_loss": main,
                       "mtp_loss": ahead_loss,
                       "mtp_positions": ahead_mask.sum()}
        if loads.shape[0]:
            loads = loads.astype(jnp.float32)
            metrics["moe_assignments"] = loads.sum()
            metrics["moe_load_max_over_mean"] = (
                loads.max(-1) / jnp.maximum(loads.mean(-1), 1.0)).max()
            if self.router_scoring == "softmax":
                usual = usual_rows(mask.size, self.num_experts_per_tok,
                                   model.num_held_experts,
                                   self.n_routed_experts)
                metrics["moe_full_buffer_layers"] = (
                    loads.sum(-1) > usual).sum().astype(jnp.float32)
        return loss, metrics
