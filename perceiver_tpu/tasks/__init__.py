"""Task wrappers binding models + losses + metrics (reference L2 layer,
``perceiver/lightning.py``) — pure-JAX, no framework dependency."""

from perceiver_tpu.obs.process import import_span

with import_span("perceiver_tpu.tasks"):
    from perceiver_tpu.tasks.image import ImageClassifierTask  # noqa: F401
    from perceiver_tpu.tasks.text import TextClassifierTask  # noqa: F401
    from perceiver_tpu.tasks.mlm import MaskedLanguageModelTask  # noqa: F401
    from perceiver_tpu.tasks.segmentation import SegmentationTask  # noqa: F401
    from perceiver_tpu.tasks.causal_lm import CausalLMTask  # noqa: F401
    from perceiver_tpu.tasks.hybrid_lm import HybridLMTask  # noqa: F401
    from perceiver_tpu.tasks.block_diffusion_lm import (  # noqa: F401
        BlockDiffusionLMTask,
    )
