"""Background-thread batch prefetching with a supervised producer.

The reference keeps its accelerator fed with torch ``DataLoader``
worker processes (``data/imdb.py:112-126`` sets ``num_workers=3``,
``data/mnist.py:15``). The JAX equivalent needs no worker *processes* —
batch assembly is NumPy slicing over preloaded arrays (C under the
hood) and the jitted step dispatches asynchronously — but the host
loop must not assemble batch N+1 *after* blocking on step N. A single
daemon thread with a small bounded queue decouples the two: the device
runs the current step while the host builds the next batches.

Failure contract (docs/RESILIENCE.md): a production input pipeline's
worker dying must not kill a multi-day run. When the producer raises
(or, with ``stall_timeout_s`` set, goes silent), the supervisor
restarts it with exponential backoff — re-iterating the inner loader
and discarding the batches already delivered, so the stream resumes
at the exact position with no duplicates and no gaps (the inner
loader's iteration order is deterministic per epoch). Restarts are
bounded by the ``max_restarts`` poison-pill budget; once spent, the
original exception is re-raised at the consumer's ``next()`` exactly
like in-line iteration — persistent failures stay loud. The default
budget is 0 (the historical die-on-first-error behavior); the trainer
passes its configured budget. Inner iterables that cannot be
re-iterated (bare generators) are never restarted.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Dict, Iterator, Optional

import numpy as np

from perceiver_tpu.resilience import faults

_SENTINEL = object()


class LoaderStalled(RuntimeError):
    """The producer delivered nothing for ``stall_timeout_s`` seconds."""


class PrefetchIterator:
    """Wrap a batch iterable so iteration overlaps with consumption.

    ``depth`` bounds host memory: at most ``depth`` assembled batches
    exist beyond the one being consumed. ``max_restarts`` /
    ``backoff_s`` / ``stall_timeout_s`` configure the producer
    supervisor (see module docstring). Proxies ``len``, ``set_epoch``
    and ``set_sharding`` so it can stand in for a ``BatchIterator``
    (``perceiver_tpu.data.core``) anywhere, including epoch-seeded
    shuffling and per-process multi-host sharding.
    """

    def __init__(self, inner, depth: int = 2, max_restarts: int = 0,
                 backoff_s: float = 0.05,
                 stall_timeout_s: Optional[float] = None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        if max_restarts < 0 or backoff_s < 0:
            raise ValueError("max_restarts and backoff_s must be >= 0")
        if stall_timeout_s is not None and stall_timeout_s <= 0:
            raise ValueError("stall_timeout_s must be positive or None")
        self.inner = inner
        self.depth = depth
        self.max_restarts = max_restarts
        self.backoff_s = backoff_s
        self.stall_timeout_s = stall_timeout_s
        # a bare iterator/generator consumes itself: re-iterating it
        # would silently drop the rest of the epoch, so never restart
        self._restartable = not hasattr(inner, "__next__")
        self.restarts = 0  # total producer restarts (observability)
        self._queue: Optional["queue.Queue"] = None  # the live epoch's

    def queue_depth(self) -> int:
        """Batches ready now (0: a pull would wait for the producer;
        also 0 between epochs)."""
        q = self._queue
        return q.qsize() if q is not None else 0

    def __len__(self) -> int:
        return len(self.inner)

    def set_epoch(self, epoch: int):
        if hasattr(self.inner, "set_epoch"):
            self.inner.set_epoch(epoch)

    def set_sharding(self, num_shards: int, shard_index: int,
                     pad_remainder: bool = False):
        """Proxy per-process sharding so a prefetched loader composes
        with multi-host runs (``distributed/bootstrap.py``): the
        producer then iterates only this process's disjoint shard, and
        a supervised restart re-derives the same strided slice — the
        no-dups/no-gaps restart guarantee holds per shard, hence
        globally."""
        if not hasattr(self.inner, "set_sharding"):
            raise ValueError(
                f"inner loader {type(self.inner).__name__} is not "
                f"process-shardable (no set_sharding)")
        self.inner.set_sharding(num_shards, shard_index, pad_remainder)

    # -- producer ---------------------------------------------------------

    def _produce(self, q: "queue.Queue", stop: threading.Event,
                 skip: int) -> None:
        """Iterate the inner loader, discarding the first ``skip``
        batches (restart reposition), and feed the bounded queue.
        Ends with a ``(_SENTINEL, exc_or_None)`` marker."""

        def put(item) -> bool:
            """False once the consumer has gone away."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        try:
            for i, batch in enumerate(self.inner):
                if i < skip:
                    continue
                # chaos seams fire once per *delivered* batch, so a
                # restart replays the same deterministic schedule
                faults.maybe_stall("loader.stall")
                faults.maybe_raise("loader.exception")
                if not put(batch):
                    return  # consumer exited early: stop, don't
                    # run the rest of the epoch dry
        except BaseException as e:  # handed to the supervisor
            put((_SENTINEL, e))
            return
        put((_SENTINEL, None))

    # -- consumer / supervisor -------------------------------------------

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        delivered = 0
        restarts_left = self.max_restarts
        backoff = self.backoff_s
        while True:
            q: "queue.Queue" = queue.Queue(maxsize=self.depth)
            self._queue = q
            stop = threading.Event()
            t = threading.Thread(target=self._produce,
                                 args=(q, stop, delivered), daemon=True)
            t.start()
            failure: Optional[BaseException] = None
            finished = False
            last_progress = time.monotonic()
            try:
                while True:
                    try:
                        item = q.get(timeout=0.2)
                    except queue.Empty:
                        if self.stall_timeout_s is not None \
                                and time.monotonic() - last_progress \
                                > self.stall_timeout_s:
                            failure = LoaderStalled(
                                f"loader produced nothing for "
                                f"{self.stall_timeout_s}s")
                            break
                        continue
                    last_progress = time.monotonic()
                    if isinstance(item, tuple) and len(item) == 2 \
                            and item[0] is _SENTINEL:
                        failure = item[1]
                        finished = failure is None
                        break
                    yield item
                    delivered += 1
            finally:
                # covers early consumer exit (break / preemption /
                # GeneratorExit) too: halt the producer after at most
                # its in-flight batch
                stop.set()
                self._queue = None
                t.join(timeout=0.2 if failure is not None else 5.0)
            if finished:
                return
            if not self._restartable or restarts_left <= 0 \
                    or isinstance(failure, (KeyboardInterrupt,
                                            SystemExit)):
                raise failure
            restarts_left -= 1
            self.restarts += 1
            if backoff > 0:
                time.sleep(backoff)
                backoff = min(backoff * 2, 2.0)
