"""perceiver_tpu — a TPU-native Perceiver / Perceiver IO framework.

Built from scratch on JAX/XLA: pure-function modules over parameter
pytrees, einsum attention lowered onto the MXU, pjit/GSPMD meshes for
distribution, and Pallas kernels for the attention hot loop.

Provides the full capability surface of the reference PyTorch
implementation (``felixyu7/perceiver-io-1``, see SURVEY.md): generic
``PerceiverEncoder``/``PerceiverDecoder``/``PerceiverIO`` models with
pluggable input/output adapters, BERT-style masked language modeling,
transfer learning with encoder freezing, image classification, and a
large-scale semantic-segmentation configuration.
"""

__version__ = "0.1.0"

# first, so that the timeline starts where the process did and the
# heavy imports below have spans (obs/process.py; standard library only)
from perceiver_tpu.obs import process as _process  # noqa: E402

_process.begin()
with _process.import_span("jax"):
    import jax  # noqa: F401
with _process.import_span("perceiver_tpu.models"):
    from perceiver_tpu.models.perceiver import (  # noqa: F401
        PerceiverEncoder,
        PerceiverDecoder,
        PerceiverIO,
        PerceiverMLM,
    )
