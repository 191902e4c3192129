"""Model library: Perceiver encoder/decoder/IO/MLM, text masking, and
the looped and the hybrid causal language models."""

from perceiver_tpu.models.perceiver import (  # noqa: F401
    PerceiverEncoder,
    PerceiverDecoder,
    PerceiverIO,
    PerceiverMLM,
)
from perceiver_tpu.models.masking import TextMasking  # noqa: F401
from perceiver_tpu.models.uresnet import UResNet  # noqa: F401
from perceiver_tpu.models.looped_lm import LoopedLM  # noqa: F401
from perceiver_tpu.models.hybrid_lm import HybridLM  # noqa: F401
