"""The process's own spans: what ran before and beside the steps.

``PROCESS_PHASES`` of :mod:`perceiver_tpu.obs.trace`, written into the
same ring as the trainer's phases so that one timeline runs from the
process's start to its last step:

* ``proc/boot``: the kernel's start time of the process to the first
  line of ``perceiver_tpu/__init__.py``: the interpreter, and whatever
  the caller imported and did before it touched the program (attr
  ``loaded``: the heavy modules that were in ``sys.modules`` by then).
* ``proc/import`` (attr ``module``): one heavy import, by
  :class:`import_span` at the site where the program makes it first:
  ``jax`` and the models in ``perceiver_tpu/__init__.py``, ``optax``
  and ``orbax.checkpoint`` in ``training/__init__.py``, and the
  program's own subpackages in their ``__init__.py``.  Where one pulls
  another they nest; a reader counts self time.  An import under a
  millisecond (the module was loaded already) is not written.  A
  finder on ``sys.meta_path`` that wrapped the loaders did the same
  with no edit of an import site, and made ``orbax.checkpoint``'s
  import three times as long on the chip's host (PERF.md, PR 39): it
  went.
* ``proc/gc`` (attrs ``generation``, ``collected``): one collection of
  Python's collector that took over a millisecond, written by
  :class:`GcSpans` at the collection's end.

``proc/backend_init`` is written where the program makes the first
look for devices (``training.trainer.apply_accelerator``).

Standard library only, like the rest of the tracing.
"""

from __future__ import annotations

import gc
import os
import sys
import time
from typing import Optional

from perceiver_tpu.obs import trace

__all__ = ["HEAVY_MODULES", "GcSpans", "begin", "import_span",
           "process_start"]

#: The heavy third-party modules ``proc/boot`` says were loaded already
#: when the program's first line ran (the caller's imports).
HEAVY_MODULES = ("jax", "numpy", "optax", "orbax.checkpoint")
_FLOOR_S = 1e-3   # shorter imports and collections are not written


def process_start() -> Optional[float]:
    """The kernel's start time of this process on ``time.monotonic``,
    or None where ``/proc`` does not say: field 22 of
    ``/proc/self/stat`` is ticks after the system's boot, and
    ``CLOCK_BOOTTIME`` reads that clock now."""
    try:
        with open("/proc/self/stat") as f:
            # the command (field 2) may hold spaces and brackets
            fields = f.read().rsplit(")", 1)[1].split()
        age = time.clock_gettime(time.CLOCK_BOOTTIME) \
            - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    if not 0.0 <= age < 86400.0:
        return None   # a /proc that is not this kernel's
    return time.monotonic() - age


class import_span:
    """``with import_span("orbax.checkpoint"): import orbax.checkpoint``
    at the site where the program makes a heavy import first: a
    ``proc/import`` span around the block, not written where the module
    was loaded already (under a millisecond).  A ``with`` block and no
    finder on ``sys.meta_path``: nothing is installed, no frame lies
    between the import and its importer."""

    def __init__(self, module: str) -> None:
        self._span = trace.span("proc/import", module=module)

    def __enter__(self) -> None:
        self._span.__enter__()

    def __exit__(self, *exc) -> None:
        if trace._now() - getattr(self._span, "start", 0.0) < _FLOOR_S:
            self._span.cancel()
        self._span.__exit__(*exc)


_begun = False


def begin() -> None:
    """Called from the first line of ``perceiver_tpu/__init__.py``:
    write ``proc/boot``, once a process."""
    global _begun
    if _begun or not trace.enabled():
        return
    _begun = True
    now = trace._now()
    started = process_start()
    if started is not None and started < now:
        trace.timeline().record(
            "proc/boot", start=started, end=now,
            loaded=",".join(m for m in HEAVY_MODULES if m in sys.modules))


class GcSpans:
    """A callback for ``gc.callbacks``: ``seconds`` sums every
    collection's wall time while installed, and a collection over a
    millisecond is written as ``proc/gc``.  The collector holds the
    interpreter lock, so a collection on any thread stalls them all."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self._start: Optional[float] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = trace._now()
        elif self._start is not None:
            start, end = self._start, trace._now()
            self._start = None
            self.seconds += end - start
            if end - start > _FLOOR_S:
                trace.timeline().record(
                    "proc/gc", start=start, end=end,
                    generation=info.get("generation"),
                    collected=info.get("collected"))

    def install(self) -> "GcSpans":
        gc.callbacks.append(self)
        return self

    def uninstall(self) -> None:
        try:
            gc.callbacks.remove(self)
        except ValueError:
            pass
