"""JAX's own compile machinery, as every entry point uses it: where
the persistent compilation cache lives, and a counter of compiles.

The cache directory is part of the cache's key, so it must not move
between runs: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
itself and nothing is set here; otherwise the cache is
``<checkout>/.jax_cache`` — never a temporary name, pid or time.

A program's metadata is part of its key here, which is not JAX's
default. By default the key leaves out operation names and source
lines, so a program that differs from an earlier one in metadata alone
is served the earlier executable *with the earlier metadata*: a step
compiled before the layer scopes existed then shows no scope in any
profile, and every metric read off the trace's name stacks reads
nothing (seen on the chip, PERF.md Findings, PR 25). The price is a
compile whenever a source line on the step's path moves.
"""

from __future__ import annotations

import contextlib
import os
from typing import Callable, Iterator, List

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Fires once per program handed to the backend compiler, whether the
# persistent cache then serves it or XLA compiles it; a dispatch of an
# executable already in memory fires nothing.
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. Call before the first device use."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # keep every program: a cold start is many sub-second compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def register_compile_listener(on_compile: Callable[[float], None]):
    """Call ``on_compile(seconds)`` for every backend compile from now
    on; returns the handle ``unregister_compile_listener`` takes."""
    import jax

    def listener(name, secs, **kwargs):
        if name == _BACKEND_COMPILE:
            on_compile(secs)

    jax.monitoring.register_event_duration_secs_listener(listener)
    return listener


def unregister_compile_listener(listener) -> None:
    import jax

    jax.monitoring.unregister_event_duration_listener(listener)


@contextlib.contextmanager
def compile_events() -> Iterator[List[float]]:
    """Collect the seconds of every backend compile inside the block:
    ``len`` is the number of programs compiled (or fetched from the
    persistent cache), ``sum`` the time that took."""
    seconds: List[float] = []
    listener = register_compile_listener(seconds.append)
    try:
        yield seconds
    finally:
        unregister_compile_listener(listener)
