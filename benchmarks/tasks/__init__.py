"""One file per kind of task, found by the ``task`` key of a
configuration (``benchmarks/tasks/<task>.py``), as runners and layer
metrics are found by name. A task file gives everything that differs
between kinds of model and nothing else:

* ``program_task(cfg)``       the program's task class and its arguments
* ``make_batch(rng, rows, cfg)``  one seeded training batch
* ``tokens_per_row(cfg)``     input positions a training row holds
* ``flop_shape(cfg)``         what ``flops.forward_parts`` counts from
* ``reference_batches(pool, cfg, trainer_seed, steps)``  the first
  batches as the plain reference takes them
* ``loss_sum(params, batch, cfg, prec)``  the reference's loss: (sum
  over the rows' terms, their number)

so a configuration of a new kind arrives as its task file beside its
configuration file, with no edit to a file that is there.
"""

import dataclasses


def program_kwargs(cls, cfg: dict) -> dict:
    """The keys of the flat configuration ``cfg`` that the program's
    task class ``cls`` takes; the rest (token ids, the benchmark's own)
    are left out."""
    fields = {f.name for f in dataclasses.fields(cls)}
    return {k: v for k, v in cfg.items() if k in fields}
