#!/usr/bin/env python
"""Causal language-model pretraining CLI for the hybrid state-space /
mixture-of-experts LM (``perceiver_tpu/tasks/hybrid_lm.py``): Mamba-2,
routed-expert and grouped-query attention layers in the order
``--model.hybrid_override_pattern`` gives, next-token loss.

Example (a small model on the IMDB text, or its synthetic fallback):

    python scripts/hybrid_lm.py fit --config scripts/configs/hybrid_lm_1chip.yaml
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from perceiver_tpu.data import IMDBDataModule  # noqa: E402
from perceiver_tpu.tasks import HybridLMTask  # noqa: E402
from perceiver_tpu.utils.config import CLI, Link  # noqa: E402

TRAINER_YAML = os.path.join(os.path.dirname(__file__), "trainer.yaml")


def main(args=None, run=True):
    return CLI(
        HybridLMTask,
        datamodules={"IMDBDataModule": IMDBDataModule},
        default_datamodule="IMDBDataModule",
        default_config_files=[TRAINER_YAML],
        defaults={"experiment": "hybrid_lm"},
        links=[
            # the model's vocabulary and row length are the data's
            Link("data.vocab_size", "model.vocab_size",
                 apply_on="instantiate"),
            Link("data.max_seq_len", "model.max_seq_len",
                 apply_on="instantiate"),
        ],
        description=__doc__,
        run=run,
        args=args,
    )


if __name__ == "__main__":
    main()
