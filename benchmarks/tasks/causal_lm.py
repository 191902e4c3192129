"""Causal language model with a looped, weight-shared decoder stack
(``CausalLMTask``): full rows of Zipf-distributed ids over the whole
vocabulary, each position labelled with the next id; nothing is masked
and nothing is drawn inside the step."""

import numpy as np

from benchmarks import flops, traffic
from benchmarks.reference import looped_lm as ref
from benchmarks.reference.perceiver_io import IGNORE
from benchmarks.tasks import program_kwargs

loss_sum = ref.loss_sum


def program_task(cfg: dict):
    from perceiver_tpu.tasks import CausalLMTask as cls

    return cls, program_kwargs(cls, cfg)


def make_batch(rng, rows: int, cfg: dict) -> dict:
    return {"input_ids": traffic.zipf_ids(
        rng, cfg["vocab_size"],
        cfg.get("num_special_tokens", traffic.N_SPECIAL_DEFAULT),
        (rows, cfg["max_seq_len"])),
        "valid": np.ones(rows, bool)}


def tokens_per_row(cfg: dict) -> int:
    """Input positions, as ``train_tokens_per_s`` counts them."""
    return int(cfg["max_seq_len"])


def forward_parts(cfg: dict) -> dict:
    """Forward matrix-product operations for one row, by part, by the
    rules at the head of ``benchmarks/flops.py``: a product 2 m n k;
    the causal scores S (S + 1) / 2 pairs a head
    (``flash_attention_cost``); every pass applies every layer and
    reads the head and the gate once."""
    s, c = int(cfg["max_seq_len"]), int(cfg["hidden_size"])
    width, vocab = int(cfg["intermediate_size"]), int(cfg["vocab_size"])
    passes = int(cfg["total_ut_steps"])
    applications = passes * int(cfg["num_hidden_layers"])
    heads_width = int(cfg["num_attention_heads"]) * int(cfg["head_dim"])
    return {
        # q, k, v, out and the gated MLP's three projections
        "layer_products": applications * s * 2.0 * (
            3 * c * heads_width + heads_width * c + 3 * c * width),
        "causal_attention": applications * flops.flash_attention_cost(
            1, s, s, heads_width, backward=False, causal=True)[0],
        "head": passes * s * 2.0 * c * vocab,
        "exit_gate": passes * s * 2.0 * c,
    }


def train_step_flops(cfg: dict, rows: int) -> float:
    """Forward plus backward of one step of ``rows`` rows: a product
    costs twice itself again in the backward pass; the embedding takes
    its gradient, so the first layer's input does too. Recomputation is
    not counted."""
    return rows * 3.0 * sum(forward_parts(cfg).values())


def reference_batches(pool, cfg: dict, trainer_seed: int, steps: int):
    """The first ``steps`` batches as the reference takes them: the ids
    and the next ids as labels, no label at a row's last position.
    Nothing depends on the trainer's seed."""
    import jax.numpy as jnp

    del cfg, trainer_seed
    out = []
    for b in pool[:steps]:
        ids = np.asarray(b["input_ids"])
        labels = np.concatenate(
            [ids[:, 1:], np.full((ids.shape[0], 1), IGNORE, ids.dtype)],
            axis=1)
        out.append({"input_ids": jnp.asarray(ids),
                    "labels": jnp.asarray(labels)})
    return out
