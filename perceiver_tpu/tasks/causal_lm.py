"""Causal language-model task for a looped LM (``models/looped_lm.py``):
next-token prediction through every pass of the weight-shared stack,
mixed by the exit gate's distribution.

The objective is the family's first training stage (arXiv:2510.25741):
the expected loss under the exit distribution, entropy-regularised
against a uniform prior::

    nll_t,i = CE(logits_t,i , ids_{i+1})
    loss = (1/N) sum_i [ sum_t p_t,i nll_t,i  -  beta * H(p_.,i) ]

over the ``N`` positions that have a label (a row's last position and
positions whose next token is padding have none). The four head
projections go through ``ops.fused_ce.fused_linear_nll`` one chunk of
positions at a time; the exit probabilities are weights that take a
gradient.

This is not a ``TaskConfig``: the fields are the model's own, and the
hooks are the ones the trainer calls (``build``, ``loss_and_metrics``,
``batch_partition``; ``restore_pretrained`` and
``on_validation_epoch_end`` have nothing to do here and are left out,
which the trainer allows).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from perceiver_tpu.models.looped_lm import (
    CAUSAL_ATTENTION_IMPLS,
    LoopedLM,
    exit_distribution,
)
from perceiver_tpu.obs.trace import device_scope
from perceiver_tpu.ops.fused_ce import fused_linear_nll
from perceiver_tpu.ops.policy import DEFAULT_POLICY, Policy


def next_token_targets(batch):
    """``(labels (B, S), mask (B, S) float32)`` of a causal-LM batch:
    position i is labelled with id i+1; a row's last position, a
    padding position and one whose next token is padding have no label
    (``pad_mask``: right padding), nor has a row that ``valid`` marks
    as filler."""
    ids = batch["input_ids"]
    b, s = ids.shape
    labels = jnp.concatenate([ids[:, 1:], jnp.zeros((b, 1), ids.dtype)],
                             axis=1)
    labelled = jnp.broadcast_to(jnp.arange(s) < s - 1, (b, s))
    if "pad_mask" in batch:   # right padding: no label from a pad
        pad = batch["pad_mask"]
        labelled = labelled & ~jnp.concatenate(
            [pad[:, 1:], jnp.ones((b, 1), bool)], axis=1) & ~pad
    if "valid" in batch:
        labelled = labelled & batch["valid"].astype(bool)[:, None]
    return labels, labelled.astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class CausalLMTask:
    vocab_size: int = 49152
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_seq_len: int = 4096
    # passes of the weight-shared stack
    total_ut_steps: int = 4
    # weight of the exit distribution's entropy in the loss
    exit_entropy_beta: float = 0.1
    # recompute every layer application on the backward pass
    remat: bool = False
    # positions a chunk of the head projection + CE (a chunk's fp32
    # logits are chunk x vocab_size x 4 bytes, twice in the backward)
    ce_chunk_size: int = 2048
    # None picks the attention core per call site (ops/attention.py)
    attention_impl: Optional[str] = None

    def __post_init__(self):
        # fail at config time, not deep inside a jit trace
        if self.attention_impl not in CAUSAL_ATTENTION_IMPLS:
            raise ValueError(
                f"unknown attention_impl {self.attention_impl!r}; a causal "
                f"stack takes one of {CAUSAL_ATTENTION_IMPLS}")

    def build(self, mesh=None) -> LoopedLM:
        del mesh   # one device or pure GSPMD: nothing to wire
        return LoopedLM(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_layers=self.num_hidden_layers,
            num_heads=self.num_attention_heads, head_dim=self.head_dim,
            intermediate_size=self.intermediate_size,
            max_seq_len=self.max_seq_len,
            total_ut_steps=self.total_ut_steps,
            rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
            remat=self.remat, attention_impl=self.attention_impl)

    def batch_partition(self, name: str, ndim: int, mesh) -> tuple:
        """Rows over 'data' only: the causal kernels see whole rows."""
        return ()

    def loss_and_metrics(self, model: LoopedLM, params, batch, *, rng=None,
                         deterministic: bool = True,
                         policy: Policy = DEFAULT_POLICY):
        del rng, deterministic   # no dropout, no masking to draw
        ids = batch["input_ids"]
        b, s = ids.shape
        t = model.total_ut_steps
        labels, mask = next_token_targets(batch)                # (B, S)

        states = model.hidden_states(params, ids, policy=policy)
        p, log_p = exit_distribution(model.gate_logits(params, states))
        with device_scope("exit_loss"):
            count = jnp.maximum(mask.sum(), 1.0)
            nll = fused_linear_nll(
                params["head"], states.reshape(t * b * s, -1),
                jnp.tile(labels.reshape(-1), t),
                chunk_size=self.ce_chunk_size, policy=policy
            ).reshape(t, b, s)
            expected = (p * nll).sum(0)                         # (B, S)
            entropy = -(p * log_p).sum(0)
            loss = ((expected - self.exit_entropy_beta * entropy)
                    * mask).sum() / count
            # a gate that collapses onto one pass is this family's
            # known failure: each pass's own loss, where the gate
            # exits on average and how spread it is, every step
            metrics = {"loss": loss,
                       "exit_pass_mean": ((p * jnp.arange(
                           1, t + 1, dtype=jnp.float32)[:, None, None]
                       ).sum(0) * mask).sum() / count,
                       "exit_entropy": (entropy * mask).sum() / count}
            for i in range(t):
                metrics[f"nll_pass{i + 1}"] = (nll[i] * mask).sum() / count
        return loss, metrics
