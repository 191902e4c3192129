"""Host-side data modules (NumPy pipelines feeding device batches)."""

from perceiver_tpu.obs.process import import_span

with import_span("perceiver_tpu.data"):
    from perceiver_tpu.data.core import ArrayDataset, BatchIterator  # noqa: F401
    from perceiver_tpu.data.images import SyntheticImageDataModule  # noqa: F401
    from perceiver_tpu.data.mnist import MNISTDataModule  # noqa: F401
    from perceiver_tpu.data.imdb import IMDBDataModule, Collator  # noqa: F401
    from perceiver_tpu.data.lartpc import load_lartpc, synthetic_events  # noqa: F401
