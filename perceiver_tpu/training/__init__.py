"""Training engine: optimizers, train state, checkpointing, trainer."""

from perceiver_tpu.obs.process import import_span

# the heavy imports first and by name, so that each has a span of its
# own (proc/import) inside the package's
with import_span("perceiver_tpu.training"):
    with import_span("optax"):
        import optax  # noqa: F401
    with import_span("orbax.checkpoint"):
        import orbax.checkpoint  # noqa: F401
    from perceiver_tpu.training.state import TrainState  # noqa: F401
    from perceiver_tpu.training.optim import create_optimizer  # noqa: F401
    from perceiver_tpu.training.trainer import (  # noqa: F401
        Trainer,
        TrainerConfig,
    )
