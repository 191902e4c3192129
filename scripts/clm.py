#!/usr/bin/env python
"""Causal language-model pretraining CLI for the looped LM
(``perceiver_tpu/tasks/causal_lm.py``): one weight-shared decoder stack
run ``--model.total_ut_steps`` times, next-token loss mixed by the exit
gate.

Example (a small model on the IMDB text, or its synthetic fallback):

    python scripts/clm.py fit --config scripts/configs/looped_lm_1chip.yaml
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from perceiver_tpu.data import IMDBDataModule  # noqa: E402
from perceiver_tpu.tasks import CausalLMTask  # noqa: E402
from perceiver_tpu.utils.config import CLI, Link  # noqa: E402

TRAINER_YAML = os.path.join(os.path.dirname(__file__), "trainer.yaml")


def main(args=None, run=True):
    return CLI(
        CausalLMTask,
        datamodules={"IMDBDataModule": IMDBDataModule},
        default_datamodule="IMDBDataModule",
        default_config_files=[TRAINER_YAML],
        defaults={"experiment": "clm"},
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
