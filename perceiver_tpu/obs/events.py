"""Structured JSONL event log with size-based rotation.

Absorbs the signals that used to live in ad-hoc prints and private
counters — guard skips/rewinds, breaker transitions, fleet ejection /
readmission / rollout steps, exec-cache hits, health transitions,
replica deaths, checkpoint seals, per-step training telemetry — each
as a *typed* event validated against one shared schema.

An event is one JSON object per line::

    {"ts": 1754379123.4, "type": "breaker_transition", "pid": 1234,
     "bucket": "b4s16", "old": "closed", "new": "open"}

``ts`` (wall clock), ``type`` and ``pid`` form the envelope; the
per-type required fields are in :data:`SCHEMA`.  Extra fields are
allowed (forward compatibility), missing required fields are not.

Every process gets a global default log (in-memory ring only unless a
path is configured).  Fleet replica subprocesses inherit the
``PERCEIVER_EVENT_LOG`` env var — a *directory* — and write
``events-<pid>.jsonl`` files there so one chaos run yields one
greppable directory of typed events.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

__all__ = [
    "SCHEMA",
    "EventLog",
    "validate_event",
    "default_log",
    "set_default_log",
    "emit",
]

#: event type -> required fields (beyond the ts/type/pid envelope).
SCHEMA: Dict[str, Tuple[str, ...]] = {
    # resilience
    "guard_skip": ("step",),
    "guard_rewind": ("step",),
    "breaker_transition": ("bucket", "old", "new"),
    "health_transition": ("old", "new"),
    # serving engine
    "exec_cache": ("bucket", "hit"),
    # fleet
    "fleet_ejection": ("replica",),
    "fleet_readmission": ("replica",),
    "replica_death": ("replica", "restarts"),
    "replica_respawn": ("replica",),
    "rollout_step": ("replica", "stage", "version"),
    # training
    "checkpoint_seal": ("path",),
    "preempt_checkpoint": ("step",),
    "train_step": ("step", "loss"),
    "profile_capture": ("dir",),
    # a step over its neighbours' median pace, taken apart by its
    # leaves (training/pace.py; "phase" names the largest excess)
    "slow_step": ("step", "interval_s", "median_s"),
    # distributed (multi-host groups; docs/RESILIENCE.md "Multi-host")
    "host_join": ("group", "rank"),
    "host_leave": ("group", "rank"),
    "group_reform": ("group", "generation"),
    "rendezvous_timeout": ("coordinator",),
    # two-phase cutover on process-group replicas (docs/SERVING.md)
    "cutover_stage": ("replica", "version"),
    "cutover_ack": ("replica", "version"),
    "cutover_rollback": ("replica", "version"),
    # autoregressive decode streams (serving/decode.py): one open /
    # close pair per stream; "tokens" = generated count at close.
    # stream_admitted fires when the unified scheduler grants a slot
    # + pages; prefill_complete when the last prompt chunk lands
    # ("chunks" = chunked-prefill steps the prompt took). Every
    # stream event carries the owning tenant (docs/OBSERVABILITY.md
    # "Tenant labels") so isolation is provable from the event log.
    "stream_open": ("stream", "tenant"),
    "stream_admitted": ("stream", "pages", "tenant"),
    "prefill_complete": ("stream", "prompt_tokens", "chunks", "tenant"),
    "stream_close": ("stream", "tokens", "tenant"),
    # multi-tenancy (serving/tenancy.py): one event per shed decision
    # attributing WHERE a tenant's excess load was dropped ("reason"
    # from serving/errors.SHED_REASONS)
    "tenant_shed": ("tenant", "reason"),
    # prefix caching (serving/prefix_cache.py): hit/miss at admission
    # lookup, publish when prefill hands full prompt-only pages back
    # to the index, evict when LRU reclaim frees index-only pages.
    "prefix_cache_hit": ("stream", "tokens", "pages"),
    "prefix_cache_miss": ("stream",),
    "prefix_cache_publish": ("stream", "pages"),
    "prefix_cache_evict": ("pages",),
    # speculative decoding (serving/speculative.py): one verify event
    # per speculative row per step ("drafted"/"accepted" token
    # counts); spec_fallback when a stream's acceptance EMA collapses
    # and the engine drops it back to plain decode for good.
    "spec_verify": ("stream", "drafted", "accepted"),
    "spec_fallback": ("stream", "acceptance"),
}


def validate_event(event: dict) -> None:
    """Raise ``ValueError`` if ``event`` doesn't satisfy the schema."""
    if not isinstance(event, dict):
        raise ValueError(f"event must be a dict, got {type(event).__name__}")
    etype = event.get("type")
    if etype not in SCHEMA:
        raise ValueError(f"unknown event type {etype!r}; "
                         f"expected one of {sorted(SCHEMA)}")
    for field in ("ts", "pid"):
        if field not in event:
            raise ValueError(f"event missing envelope field {field!r}")
    missing = [f for f in SCHEMA[etype] if f not in event]
    if missing:
        raise ValueError(f"event type {etype!r} missing required "
                         f"field(s) {missing}")


class EventLog:
    """In-memory ring of typed events, optionally mirrored to a JSONL
    file with size-based rotation (``path`` -> ``path.1`` -> ...)."""

    # the ring is appended from every instrumented thread; the JSONL
    # mirror (_write/_rotate) also runs under _lock so rotation never
    # interleaves with an append — obs/ is off the dispatch hot path,
    # which is why file IO under this lock is acceptable here
    _GUARDED = {"_ring": "_lock"}

    def __init__(self, path: Optional[str] = None, *,
                 max_bytes: int = 1 << 20, max_backups: int = 3,
                 ring: int = 1024) -> None:
        self.path = path
        self.max_bytes = int(max_bytes)
        self.max_backups = int(max_backups)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=int(ring))
        if path:
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)

    def emit(self, etype: str, **fields) -> dict:
        """Validate, ring-buffer, and (if configured) append to disk.

        Disk errors never propagate into the instrumented hot path —
        the in-memory ring is the source of truth for tests.
        """
        event = {"ts": time.time(), "type": etype, "pid": os.getpid()}
        event.update(fields)
        validate_event(event)
        with self._lock:
            self._ring.append(event)
            if self.path:
                try:
                    self._write(event)
                except OSError:  # disk full / rotated away — keep serving
                    pass
        return event

    def _write(self, event: dict) -> None:
        line = json.dumps(event, sort_keys=True) + "\n"
        try:
            size = os.path.getsize(self.path)
        except OSError:
            size = 0
        if size + len(line) > self.max_bytes and size > 0:
            self._rotate()
        with open(self.path, "a", encoding="utf-8") as f:
            f.write(line)

    def _rotate(self) -> None:
        for i in range(self.max_backups - 1, 0, -1):
            src = f"{self.path}.{i}"
            if os.path.exists(src):
                os.replace(src, f"{self.path}.{i + 1}")
        if os.path.exists(self.path):
            os.replace(self.path, f"{self.path}.1")
        # anything past max_backups falls off
        stale = f"{self.path}.{self.max_backups + 1}"
        if os.path.exists(stale):
            os.remove(stale)

    def events(self, etype: Optional[str] = None) -> List[dict]:
        with self._lock:
            evs = list(self._ring)
        if etype is not None:
            evs = [e for e in evs if e.get("type") == etype]
        return evs

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


#: env var naming a DIRECTORY: subprocesses (fleet replicas) mirror
#: their default log to ``<dir>/events-<pid>.jsonl``.
ENV_VAR = "PERCEIVER_EVENT_LOG"

_default_lock = threading.Lock()
_default: Optional[EventLog] = None

# module-global lock discipline (gated by check.py --race): the lazy
# default-log singleton is read/written only under _default_lock
_GUARDED_GLOBALS = {"_default": "_default_lock"}


def default_log() -> EventLog:
    """The process-global event log (lazy; honors ``ENV_VAR``)."""
    global _default
    with _default_lock:
        if _default is None:
            directory = os.environ.get(ENV_VAR)
            path = (os.path.join(directory, f"events-{os.getpid()}.jsonl")
                    if directory else None)
            _default = EventLog(path)
        return _default


def set_default_log(log: Optional[EventLog]) -> Optional[EventLog]:
    global _default
    with _default_lock:
        prev = _default
        _default = log
        return prev


def emit(etype: str, **fields) -> dict:
    """Module-level convenience: emit to the process default log."""
    return default_log().emit(etype, **fields)
