"""Persistent compile cache: serialized AOT executables on disk.

Every process used to pay the full XLA compile bill from scratch —
``ServingEngine.warmup`` compiled one executable per (batch, seq)
bucket at every startup, the trainer's first dispatch ate a
multi-second compile before any training happened, and
``scripts/check.py`` re-lowered every canonical target on every run.
TPU serving/training stacks instead treat compiled executables as
cacheable artifacts keyed by program + topology (PAPERS: pjit/TPUv4
scaling; Gemma-on-TPU serving); ``jax.experimental.
serialize_executable`` makes that implementable without forking XLA.

``ExecutableCache`` is the store: content-addressed files under one
directory, shareable between concurrent processes (single-writer
atomic rename), size-capped with LRU eviction, and failure-soft —
corruption, version skew, or a missing entry always degrades to a
real compile, never a crash. See docs/SERVING.md "Warm starts".
"""

from perceiver_tpu.cache.compiles import (  # noqa: F401
    compile_events,
    enable_compile_cache,
    register_compile_listener,
    unregister_compile_listener,
)
from perceiver_tpu.cache.exec_cache import (  # noqa: F401
    CacheStats,
    ExecutableCache,
    aot_compile,
    compile_lowered,
    default_cache,
    has_host_callbacks,
    source_tree_digest,
    topology_fingerprint,
)
