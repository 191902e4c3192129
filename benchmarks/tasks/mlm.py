"""Masked-language model over token rows (``MaskedLanguageModelTask``):
full-length rows of Zipf-distributed ids, masked by the program inside
its step; the decode runner serves the same model as a token stream."""

import numpy as np

from benchmarks import traffic
from benchmarks.reference import perceiver_io as ref
from benchmarks.tasks import program_kwargs

loss_sum = ref.mlm_loss_sum


def program_task(cfg: dict):
    from perceiver_tpu.tasks import MaskedLanguageModelTask as cls

    return cls, program_kwargs(cls, cfg)


def make_batch(rng, rows: int, cfg: dict) -> dict:
    ids = traffic.zipf_ids(
        rng, cfg["vocab_size"],
        cfg.get("num_special_tokens", traffic.N_SPECIAL_DEFAULT),
        (rows, cfg["max_seq_len"]))
    return {"input_ids": ids,
            "pad_mask": np.zeros(ids.shape, bool),
            "label": np.zeros(rows, np.int32),
            "valid": np.ones(rows, bool)}


def tokens_per_row(cfg: dict) -> int:
    return int(cfg["max_seq_len"])


def flop_shape(cfg: dict) -> dict:
    """Tokens embedded at the latent width; the loss reads the masked
    share of the positions, each over the vocabulary; the embedding
    takes a gradient through the key/value projection."""
    return {"positions": int(cfg["max_seq_len"]),
            "channels": int(cfg["num_latent_channels"]),
            "queries": cfg["mask_p"] * cfg["max_seq_len"],
            "classes": int(cfg["vocab_size"]),
            "input_grad": True}


def reference_batches(pool, cfg: dict, trainer_seed: int, steps: int):
    """The first ``steps`` batches with each step's masking applied,
    re-derived from the trainer's seed."""
    import jax.numpy as jnp

    out = []
    for key, b in zip(ref.trainer_step_keys(trainer_seed, steps), pool):
        ids, pad = jnp.asarray(b["input_ids"]), jnp.asarray(b["pad_mask"])
        masked, labels = ref.mlm_mask(key, ids, pad, cfg)
        out.append({"masked_ids": masked, "pad_mask": pad,
                    "labels": labels})
    return out
