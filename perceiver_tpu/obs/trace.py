"""Request tracing: dependency-free trace contexts and span buffers.

One request, one ``trace_id``, many *spans* — each span is a typed
phase (``queue_wait``, ``batch_form``, ``pad_or_pack``, ``dispatch``,
``device``, ``rpc_hop``, ``retry``, ``route``) with a monotonic-clock
start/end measured in the process that did the work.  The context is
created where the request enters the system (``api.submit`` /
``Fleet.submit``), rides the fleet RPC envelope as a tiny wire dict
(``{"trace_id"}``), and the replica ships its locally
collected spans back in the dispatch reply so the router can absorb
them into one trace.  A retried request therefore yields a SINGLE
trace with the failed hop, the ``retry`` span, and the sibling's
server-side spans all visible.

Everything here is host-side Python: no device work and no compile, so
tracing can never change an XLA cache key.  The one thing taken from
JAX is ``jax.profiler.TraceAnnotation``, imported lazily and only in a
process that has loaded ``jax`` already.  When tracing is disabled
(``set_enabled(False)``), ``start_trace`` returns ``None``, ``span`` is
a no-op, and the hot-path cost of an instrumented call site collapses
to one global read.

Process-level spans (:func:`span`, :class:`Timeline`) are the second
half: what the trainer (and later the decode engine) was doing, step by
step, with no request to hang it on.  A span lands in a bounded ring on
``time.monotonic`` and, while it is open, is a ``TraceAnnotation`` too,
so in any profiler capture it lies on the host plane of the same
``.xplane.pb`` as the device's operations: one timeline, the device's
clock.  ``region`` goes through ``span``, so the wrap-style request
phases reach the profiler as well; phases recorded retrospectively
(``TraceContext.record(start=, end=)``: ``queue_wait``, ``batch_form``)
were over before anybody knew, and stay host-only.

*Annotations are leaves only.*  A reduction that gives each idle gap of
the device to the host event covering most of it would give every gap
to an enclosing ``train/step`` event.  So the spans of
``ENCLOSING_SPANS`` and ``TILED_PHASES`` go to the ring alone (as
parents, carrying ``step``) and only leaf phases are emitted as
annotations, each with ``step_num``; they tile their parent without
nesting.  An enclosing span keeps its leaves' seconds as they close
(``children``), so the trainer reads a step's phases from the step's
own span.

*The timeline has no holes.*  ``PROCESS_PHASES`` are what a process
did before and beside its steps: ``proc/boot`` and ``proc/import``
(:mod:`perceiver_tpu.obs.process`), ``proc/backend_init``, ``proc/gc``.
They go to the ring alone too; those that were over before anybody
could open them are written after the fact (:meth:`Timeline.record`).

Clock caveat: span ``start``/``end`` are ``time.monotonic`` values and
are only comparable *within* one process.  Cross-process ordering uses
the spans' ``wall`` field (coarse ``time.time``), durations are always
trustworthy.

Profiler captures themselves (the other kind of "trace") are taken by
``TrainerConfig.profiler``, SIGUSR1 (:mod:`perceiver_tpu.obs.telemetry`)
or the ``/profile`` endpoint of :mod:`perceiver_tpu.obs.server`, and
reduced by ``benchmarks/trace_reduce.py`` and
``benchmarks/scope_times.py``; docs/OBSERVABILITY.md, "Step timeline and
device scopes".
"""

from __future__ import annotations

import contextlib
import itertools
import os
import sys
import threading
import time
from collections import OrderedDict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "PHASES",
    "TRAIN_PHASES",
    "PROCESS_PHASES",
    "ENCLOSING_SPANS",
    "TILED_PHASES",
    "DEVICE_SCOPES",
    "Timeline",
    "span",
    "device_scope",
    "timeline",
    "set_timeline",
    "TraceContext",
    "TraceBuffer",
    "SpanCollector",
    "start_trace",
    "from_wire",
    "attach",
    "attached",
    "region",
    "enabled",
    "set_enabled",
    "default_buffer",
    "set_default_buffer",
]

#: The typed phase vocabulary.  ``record``/``region`` reject anything
#: else so dashboards and tests can rely on a closed set.
PHASES = (
    "submit",       # client-side: request accepted into the system
    "queue_wait",   # batcher: enqueue -> popped into a batch
    "batch_form",   # batcher: popped -> batch handed to the runner
    "pad_or_pack",  # engine: host-side bucket padding / packing
    "dispatch",     # engine: executable launch (async, host cost only)
    "device",       # api: materialize (the deliberate device sync)
    "route",        # router: replica selection
    "rpc_hop",      # router: one RPC attempt against one replica
    "retry",        # router: backoff + re-pick after a failed hop
    "decode_step",  # decode engine: one stepped-executable iteration
    "prefill_chunk",  # decode engine: one chunked-prefill slice of a prompt
    "token_emit",   # decode engine: one generated token handed out
    "prefix_lookup",  # decode engine: prefix-cache probe at admission
    "draft",        # decode engine: draft-model proposal calls for one row
    "verify",       # decode engine: target verification of drafted tokens
)

#: The trainer's phases (``training/trainer.py``), a closed set like
#: ``PHASES``.  Per dispatch: ``train/step`` holds ``input_wait``,
#: ``shard``, ``dispatch``, ``guard_sync`` (armed guard only), ``fence``
#: and ``log`` with its three leaves (logged steps), and ``step_load``
#: on the first.  Its attrs at the close: ``interval_s`` and ``cpu_s``
#: (``training/pace.py``).
TRAIN_PHASES = (
    "train/step",         # one dispatch of the loop; ring only
    "train/input_wait",   # the pull from the (prefetching) loader
    "train/shard",        # np.stack of a multi-step group + device_put
    "train/dispatch",     # the call of the jitted step (async: host cost)
    "train/guard_sync",   # armed guard: per-step losses to the host
    "train/fence",        # the host waiting for the device's metrics
    "train/log",          # a logged step's three writes; ring only
    "train/log_console",  # the heartbeat line on standard error
    "train/log_scalars",  # the summary writer's scalars, lr_fn's call
    "train/log_telemetry",  # the telemetry line and its counters
    "train/construct",    # Trainer(...): task.build, the log directory
    "train/data_setup",   # the data module's prepare_data / setup
    "train/io_setup",     # summary writer, telemetry, checkpoint hooks
    "train/epoch_end",    # the task's on_validation_epoch_end hook
    "train/build_state",  # model.init + restore + optimizer; ring only
    "train/model_init",   # eager model.init
    "train/restore",      # task.restore_pretrained / checkpoint restore
    "train/step_load",    # lower + compile or cache read of the step
    "train/eval",         # a validation pass
    "train/checkpoint",   # a checkpoint save handed to the hook
    "train/anchor",       # the guard's last-good anchor save
)

#: What a process did before and beside its steps; ring only, nesting
#: allowed (an import pulls another; a collection runs inside a leaf).
PROCESS_PHASES = (
    "proc/boot",          # process start -> perceiver_tpu's first line
    "proc/import",        # one heavy import (attr ``module``)
    "proc/backend_init",  # the accelerator runtime's start (attr ``platform``)
    "proc/gc",            # one collection of Python's collector over 1 ms
)

#: Spans that hold other spans: recorded in the ring, never emitted as
#: profiler annotations (module docstring, "leaves only").
ENCLOSING_SPANS = ("train/step", "train/build_state")

#: Phases of a step that are themselves tiled by leaves: ring only and
#: with ``children`` like an enclosing span, and still one of the step's
#: phases for a reader that sums them by their parent
#: (``benchmarks/scope_times.HOST_PHASES`` holds ``train/log``).
TILED_PHASES = ("train/log",)

#: ``jax.named_scope`` names on the train step's device operations, at
#: the layer boundaries.  Layers (outer): the two adapters and the three
#: attention stacks.  Inside a layer: ``attn_core`` (scores, softmax,
#: probabilities x values, in the forward AND the custom backward),
#: ``attn_proj`` (q/k/v/out projections), ``mlp``.  ``loss`` and
#: ``optimizer`` stand beside the layers.  The pass is not a scope: it
#: is JAX's own ``jvp(``, ``transpose(`` and ``checkpoint`` /
#: ``rematted_computation`` in the same name stack.
DEVICE_SCOPES = (
    "input_adapter", "enc_cross_attn", "latent_self_attn",
    "dec_cross_attn", "output_adapter",
    "attn_core", "attn_proj", "mlp", "loss", "optimizer",
    # a weight-shared decoder stack run several times (models/looped_lm)
    "loop_stack", "decoder_layer", "exit_gate", "exit_loss",
    # a stack of state-space, expert and attention layers
    # (models/hybrid_lm): a whole Mamba-2 mixer and, inside it, the
    # chunked scan alone; a whole expert layer and, inside it, what is
    # not a matrix product (router, top-k, sort, gather, combine) and
    # the grouped products over the held experts
    "hybrid_stack", "ssm_mixer", "ssm_scan", "moe", "moe_route",
    "moe_experts",
    # a gated delta-rule (linear-attention) mixer (ops/delta_rule): the
    # whole mixer and, inside it, the chunked rule alone (the solve
    # inside a chunk, the chunk products, the carried state); the
    # output gate of a gated softmax attention (the query's other half,
    # its sigmoid and the product: under attn_proj)
    "delta_mixer", "delta_rule", "attn_gate",
    # a Kimi Delta Attention mixer (ops/delta_rule: the delta rule with
    # a decay that is a vector a key channel): the whole mixer and,
    # inside it, the rule alone (the decays, the solve inside a chunk,
    # the chunk products, the carried state); a latent-attention (MLA)
    # layer (models/hybrid_lm), whole, with attn_proj and attn_core
    # inside it
    "kda_mixer", "kda_rule", "mla_mixer",
    # a multi-token prediction module after the stack (models/hybrid_lm:
    # its two norms, its projection, its latent-attention and expert
    # layer with their own scopes inside, its last norm) and, inside
    # loss, its reading of the stack's head (tasks/hybrid_lm)
    "mtp", "mtp_loss",
    # a block-diffusion step's own noising of its rows
    # (tasks/block_diffusion_lm): the masking rates, the masks, the
    # noised copy and the loss weights
    "bd_noise",
)

_SPAN_NAMES = frozenset(PHASES + TRAIN_PHASES + PROCESS_PHASES)
_ENCLOSING = frozenset(ENCLOSING_SPANS + TILED_PHASES)
_RING_ONLY = _ENCLOSING | frozenset(PROCESS_PHASES)

_enabled = True


def enabled() -> bool:
    return _enabled


def set_enabled(flag: bool) -> None:
    """Process-wide tracing switch (used by the overhead gate tests):
    off, ``start_trace`` gives ``None`` and ``span`` records nothing."""
    global _enabled
    _enabled = bool(flag)


def _new_id() -> str:
    return os.urandom(8).hex()


class SpanCollector:
    """A plain list sink for spans (replica side, per request).

    Replicas don't keep traces — they collect the spans a request
    produced locally and return them in the dispatch reply.
    """

    def __init__(self) -> None:
        self.spans: List[dict] = []

    def add(self, trace_id: str, span: dict) -> None:
        self.spans.append(span)


class TraceBuffer:
    """Bounded in-memory ring of traces (LRU-evicting, thread-safe).
    ``dropped_spans`` counts the spans a full trace refused; the
    endpoint's ``/metrics`` exports it (``obs_spans_dropped_total``)."""

    # spans arrive from every serving thread; the LRU OrderedDict and
    # the overflow counter move together under one lock
    _GUARDED = {"_traces": "_lock", "dropped_spans": "_lock"}

    def __init__(self, max_traces: int = 256,
                 max_spans_per_trace: int = 128) -> None:
        self.max_traces = int(max_traces)
        self.max_spans_per_trace = int(max_spans_per_trace)
        self._lock = threading.Lock()
        self._traces: "OrderedDict[str, List[dict]]" = OrderedDict()
        self.dropped_spans = 0

    def add(self, trace_id: str, span: dict) -> None:
        with self._lock:
            spans = self._traces.get(trace_id)
            if spans is None:
                spans = []
                self._traces[trace_id] = spans
                while len(self._traces) > self.max_traces:
                    self._traces.popitem(last=False)
            else:
                self._traces.move_to_end(trace_id)
            if len(spans) < self.max_spans_per_trace:
                spans.append(span)
            else:
                self.dropped_spans += 1

    def absorb(self, trace_id: str, spans: Iterable[dict],
               **extra_attrs) -> None:
        """Merge remotely collected spans into a trace, optionally
        tagging each with extra attrs (e.g. ``replica="r0"``)."""
        for span in spans:
            if extra_attrs:
                span = dict(span)
                attrs = dict(span.get("attrs") or {})
                attrs.update(extra_attrs)
                span["attrs"] = attrs
            self.add(trace_id, span)

    def get(self, trace_id: str) -> Optional[List[dict]]:
        with self._lock:
            spans = self._traces.get(trace_id)
            return list(spans) if spans is not None else None

    def trace_ids(self) -> List[str]:
        with self._lock:
            return list(self._traces)

    def __len__(self) -> int:
        with self._lock:
            return len(self._traces)


_default_buffer = TraceBuffer()


def default_buffer() -> TraceBuffer:
    return _default_buffer


def set_default_buffer(buffer: TraceBuffer) -> TraceBuffer:
    global _default_buffer
    prev = _default_buffer
    _default_buffer = buffer
    return prev


class TraceContext:
    """One request's trace handle.

    Spans are recorded *retrospectively*: the caller measures with
    whatever clocks it already has (``enqueued_at``, ``taken_at``) and
    calls :meth:`record` with explicit bounds, or uses the
    :func:`region` context manager for the simple wrap case.
    """

    __slots__ = ("trace_id", "origin", "_sink")

    def __init__(self, trace_id: Optional[str] = None,
                 sink=None, origin: str = "") -> None:
        self.trace_id = trace_id or _new_id()
        self.origin = origin
        self._sink = sink if sink is not None else _default_buffer

    def record(self, phase: str, *, start: Optional[float] = None,
               end: Optional[float] = None,
               duration_s: Optional[float] = None, **attrs) -> dict:
        if phase not in PHASES:
            raise ValueError(
                f"unknown trace phase {phase!r}; expected one of {PHASES}")
        if end is None:
            end = time.monotonic()
        if start is None:
            start = end - duration_s if duration_s is not None else end
        span = {
            "trace_id": self.trace_id,
            "phase": phase,
            "start": start,
            "end": end,
            "duration_s": round(end - start, 9),
            "wall": time.time(),
            "pid": os.getpid(),
        }
        if self.origin:
            span["origin"] = self.origin
        if attrs:
            span["attrs"] = attrs
        self._sink.add(self.trace_id, span)
        return span

    def wire(self) -> Dict[str, str]:
        """The cross-process envelope: small, picklable, stable."""
        return {"trace_id": self.trace_id}

    def absorb(self, spans: Iterable[dict], **extra_attrs) -> None:
        """Merge spans collected in another process into this trace
        (re-keyed to this ``trace_id``, optionally tagged — the router
        tags replica-side spans with the replica id)."""
        for span in spans:
            span = dict(span)
            span["trace_id"] = self.trace_id
            if extra_attrs:
                attrs = dict(span.get("attrs") or {})
                attrs.update(extra_attrs)
                span["attrs"] = attrs
            self._sink.add(self.trace_id, span)


def start_trace(origin: str = "",
                sink=None) -> Optional[TraceContext]:
    """Create a trace for a new request, or ``None`` when disabled.

    Call sites hold the possibly-``None`` context and guard with
    ``if ctx is not None`` — the disabled path does no allocation.
    """
    if not _enabled:
        return None
    return TraceContext(sink=sink, origin=origin)


def from_wire(wire: Optional[dict], sink=None,
              origin: str = "") -> Optional[TraceContext]:
    """Rehydrate a context from the RPC envelope dict (replica side)."""
    if not _enabled or not wire or "trace_id" not in wire:
        return None
    return TraceContext(trace_id=str(wire["trace_id"]),
                        sink=sink, origin=origin)


# --- thread-local attachment ------------------------------------------------
# The engine runs one *batch* containing many requests; spans recorded
# inside the batcher's runner call must land in every member trace.
# ``attach`` binds the member contexts to the current thread, ``region``
# records one measured span into each.  Unattached regions are no-ops.

_tls = threading.local()


def attached() -> Tuple[TraceContext, ...]:
    return getattr(_tls, "ctxs", ())


@contextlib.contextmanager
def attach(ctxs: Sequence[Optional[TraceContext]]):
    prev = getattr(_tls, "ctxs", ())
    _tls.ctxs = tuple(c for c in ctxs if c is not None)
    try:
        yield
    finally:
        _tls.ctxs = prev


@contextlib.contextmanager
def region(phase: str, **attrs):
    """Record ``phase`` over the wrapped block into every attached
    trace, as a :func:`span` (so it is in the timeline and, under a
    capture, in the profile).  Cost when nothing is attached: one
    getattr + tuple check."""
    ctxs = getattr(_tls, "ctxs", ())
    if not ctxs:
        yield
        return
    if phase not in PHASES:
        raise ValueError(
            f"unknown trace phase {phase!r}; expected one of {PHASES}")
    # a live request context means tracing was on when it began: the
    # span is taken whatever the switch says now
    sp = _Span(phase, dict(attrs))
    try:
        with sp:
            yield
    finally:
        for c in ctxs:
            c.record(phase, start=sp.start, end=sp.end, **attrs)


# --- process-level spans ----------------------------------------------------
# What a process was doing when no request says: the trainer's step
# phases.  One ring per process, written at span close.


class Timeline:
    """Bounded, preallocated ring of closed spans (thread-safe).

    A span is ``{"id", "parent", "name", "start", "end", "duration_s",
    "step", "thread", "attrs"}``: ``start``/``end`` on
    ``time.monotonic``, ``parent`` the id of the span open around it on
    the same thread (or None), ``step`` its own or its parent's.  When
    the ring is full the oldest span is overwritten and ``dropped``
    counts it.  The default holds a run: a 40 s window of 10 ms steps
    with eight leaves each is 36 k spans, and the benchmark's readers
    of set-up refuse a ring that dropped any."""

    _GUARDED = {"_slots": "_lock", "_next": "_lock", "dropped": "_lock"}

    def __init__(self, capacity: int = 65536) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._slots: List[Optional[tuple]] = [None] * self.capacity
        self._next = 0          # spans ever written
        self.dropped = 0

    def _add(self, record: tuple) -> None:
        with self._lock:
            i = self._next % self.capacity
            if self._slots[i] is not None:
                self.dropped += 1
            self._slots[i] = record
            self._next += 1

    def record(self, name: str, *, start: float, end: float,
               step: Optional[int] = None, **attrs) -> Optional[int]:
        """Write a span that was over before anybody could open it (the
        process's boot, a collection the collector reports at its end):
        ``start``/``end`` on the spans' clock.  Ring only, no
        annotation, so inside an open leaf it is no second event of the
        profile.  Its parent is the span open on this thread now, whose
        step it takes where it has none.  Returns its id, or None with
        tracing off."""
        if not _enabled:
            return None
        if name not in _SPAN_NAMES:
            raise ValueError(f"unknown span name {name!r}")
        stack = getattr(_tls, "spans", None)
        outer = stack[-1] if stack else None
        if step is None and outer is not None:
            step = outer.step
        sid = next(_span_ids)
        self._add((sid, outer.id if outer is not None else None, name,
                   start, end, step, threading.get_ident(), attrs))
        return sid

    def spans(self, name: Optional[str] = None, *,
              since: Optional[float] = None,
              until: Optional[float] = None) -> List[dict]:
        """The retained spans, oldest first; optionally those called
        ``name`` and those that started in ``[since, until]``."""
        with self._lock:
            n, cap = self._next, self.capacity
            records = [self._slots[i % cap]
                       for i in range(max(0, n - cap), n)]
        out = []
        for sid, parent, nm, start, end, step, thread, attrs in records:
            if name is not None and nm != name:
                continue
            if (since is not None and start < since) or \
                    (until is not None and start > until):
                continue
            out.append({"id": sid, "parent": parent, "name": nm,
                        "start": start, "end": end,
                        "duration_s": end - start, "step": step,
                        "thread": thread, "attrs": attrs})
        return out

    def __len__(self) -> int:
        with self._lock:
            return min(self._next, self.capacity)


_timeline = Timeline()
_span_ids = itertools.count(1)
_now = time.monotonic   # the spans' clock, one name for tests to pin


def timeline() -> Timeline:
    return _timeline


def set_timeline(tl: Timeline) -> Timeline:
    global _timeline
    prev = _timeline
    _timeline = tl
    return prev


def _annotation(name: str, attrs: dict):
    """``jax.profiler.TraceAnnotation`` if this process has ``jax``
    loaded, else None: tracing itself never imports it."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    return jax.profiler.TraceAnnotation(name, **attrs)


class _Span:
    """One open span; the context manager :func:`span` returns.  After
    the block ``seconds`` is its length; ``cancel()`` inside the block
    keeps it out of the ring (a pull that found the epoch over)."""

    __slots__ = ("name", "attrs", "id", "parent", "step", "start", "end",
                 "children", "_annotation", "_cancelled")

    def __init__(self, name: str, attrs: dict) -> None:
        self.name, self.attrs = name, attrs
        self.start = self.end = 0.0
        # an enclosing span's closed leaves, (name, seconds) in order of
        # closing; an enclosing span inside it hands its own up
        self.children = [] if name in _ENCLOSING else None
        self._annotation = None
        self._cancelled = False

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def cancel(self) -> None:
        self._cancelled = True

    def __enter__(self) -> "_Span":
        stack = getattr(_tls, "spans", None)
        if stack is None:
            stack = _tls.spans = []
        outer = stack[-1] if stack else None
        self.id = next(_span_ids)
        self.parent = outer.id if outer is not None else None
        self.step = self.attrs.pop("step", None)
        if self.step is None and outer is not None:
            self.step = outer.step
        stack.append(self)
        if self.name not in _RING_ONLY:
            notes = self.attrs if self.step is None \
                else {"step_num": self.step, **self.attrs}
            self._annotation = _annotation(self.name, notes)
            if self._annotation is not None:
                self._annotation.__enter__()
        self.start = _now()
        return self

    def __exit__(self, *exc) -> None:
        self.end = _now()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        stack = _tls.spans
        stack.pop()
        if stack and stack[-1].children is not None:
            if self.children is None:
                stack[-1].children.append((self.name, self.end - self.start))
            else:
                stack[-1].children.extend(self.children)
        if not self._cancelled:
            _timeline._add((self.id, self.parent, self.name, self.start,
                            self.end, self.step,
                            threading.get_ident(), self.attrs))


class _NoSpan:
    """``span`` with tracing off: nothing recorded, nothing timed."""

    __slots__ = ()
    seconds = 0.0
    children = None

    def cancel(self) -> None:
        pass

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NOOP = _NoSpan()


def span(name: str, **attrs):
    """Context manager: one process-level span called ``name`` (of
    ``PHASES``, ``TRAIN_PHASES`` or ``PROCESS_PHASES``) around the
    block, into the :func:`timeline` ring and, unless it is ring only,
    the profiler.  ``step=`` gives the span its step id; a span without
    one takes its parent's.  Other keywords are the span's ``attrs`` (and
    the annotation's).  With no capture running the cost is two clock
    reads, a ring write and an inactive ``TraceMe``."""
    if not _enabled:
        return _NOOP
    if name not in _SPAN_NAMES:
        raise ValueError(
            f"unknown span name {name!r}; expected one of "
            f"{PHASES + TRAIN_PHASES + PROCESS_PHASES}")
    return _Span(name, attrs)


class device_scope(contextlib.ContextDecorator):
    """``jax.named_scope(name)`` for a name of ``DEVICE_SCOPES``, as a
    context manager or a function decorator: the layer a device
    operation belongs to, in its name stack.  Metadata only: the
    compiled program does not change.  ``jax.named_scope`` is looked up
    at entry, so a test can lower the same step without the scopes."""

    def __init__(self, name: str) -> None:
        if name not in DEVICE_SCOPES:
            raise ValueError(
                f"unknown device scope {name!r}; expected one of "
                f"{DEVICE_SCOPES}")
        self.name = name
        self._scope = None

    def _recreate_cm(self) -> "device_scope":
        return device_scope(self.name)   # one per call: re-entrant

    def __enter__(self) -> None:
        import jax

        self._scope = jax.named_scope(self.name)
        self._scope.__enter__()

    def __exit__(self, *exc):
        return self._scope.__exit__(*exc)
