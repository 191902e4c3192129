#!/usr/bin/env python
"""Block-diffusion language-model training CLI
(``perceiver_tpu/tasks/block_diffusion_lm.py``): a stack of Qwen3-MoE
layers (grouped-query attention with rotary positions and q/k norms,
softmax-routed gated experts) trained as SDAR adapts an autoregressive
model to block diffusion: every row runs as its noised copy beside its
clean copy under a block-structured attention mask, and the loss reads
the masked positions, each weighed by its block's ``1 / t``.

Example (a small model on the IMDB text, or its synthetic fallback):

    python scripts/block_diffusion_lm.py fit --config scripts/configs/block_diffusion_lm_1chip.yaml
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from perceiver_tpu.data import IMDBDataModule  # noqa: E402
from perceiver_tpu.tasks import BlockDiffusionLMTask  # noqa: E402
from perceiver_tpu.utils.config import CLI, Link  # noqa: E402

TRAINER_YAML = os.path.join(os.path.dirname(__file__), "trainer.yaml")


def main(args=None, run=True):
    return CLI(
        BlockDiffusionLMTask,
        datamodules={"IMDBDataModule": IMDBDataModule},
        default_datamodule="IMDBDataModule",
        default_config_files=[TRAINER_YAML],
        defaults={"experiment": "block_diffusion_lm"},
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
