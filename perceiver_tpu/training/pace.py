"""The steps' pace: every step compared with its neighbours, a slow
one taken apart by the leaves of its own span.

:class:`StepPace` is fed each ``train/step`` span at the bottom of its
block.  It gives the span its two closing attrs:

* ``interval_s``: the seconds from the previous step's close to this
  one's, None on an epoch's first.  Close to close and not start to
  start: that interval is filled by the step's *own* leaves (and the
  few lines of the loop between two steps), so a long one can be taken
  apart by them; start to start it would be filled by the step before.
* ``cpu_s``: ``time.thread_time()`` over the span: a thread that
  waited (a blocked write, a descheduled host) and one that worked read
  differently.

**The slow-step rule** (constants, no option): a step whose interval
exceeds the median of the previous ``WINDOW`` (at least
``MIN_HISTORY``) by more than ``REL`` and by more than ``ABS_S`` is a
slow step: one ``slow_step`` event, one line on standard error and, for
a step that is not slow by design, the two stall counters of the
telemetry.  The line holds each leaf's seconds beside that leaf's own
median and names the largest excess: ``train/fence`` says the device
was late, a ``train/log_*`` leaf that a write blocked, ``proc/gc`` a
collection (named where it is half the excess or more), ``(no leaf)``
with little ``cpu_s`` that the host took the thread away.  A step that
holds one of ``BY_DESIGN`` is slow by design and says so.

It also sums the leaves' seconds since the last logged line, for the
telemetry's ``input_wait_s`` / ``host_s`` / ``fence_s``: one source for
the phases' seconds, the spans.
"""

from __future__ import annotations

import statistics
import sys
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

from perceiver_tpu.obs import events as events_mod
from perceiver_tpu.obs import trace

WINDOW = 32         # intervals the median is taken over
MIN_HISTORY = 8     # fewer say too little to judge by
REL = 0.05          # slow: over the median by more than this share ...
ABS_S = 0.020       # ... and by more than this many seconds
BY_DESIGN = ("train/eval", "train/checkpoint", "train/anchor",
             "train/step_load")
NO_LEAF = "(no leaf)"
GC = "proc/gc"

# the telemetry line's three sums, by leaf
_BUCKET = {
    "train/input_wait": "input_wait", "train/fence": "fence",
    "train/shard": "host", "train/dispatch": "host",
    "train/guard_sync": "host", "train/log_console": "host",
    "train/log_scalars": "host", "train/log_telemetry": "host",
}


def _sum_by_name(children: List[Tuple[str, float]]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, seconds in children:
        out[name] = out.get(name, 0.0) + seconds
    return out


class StepPace:
    """One per ``fit()``.  ``gc`` is the installed
    :class:`~perceiver_tpu.obs.process.GcSpans` (its ``seconds``), or
    None; ``telemetry`` the trainer's sink, or None."""

    def __init__(self, gc=None, telemetry=None) -> None:
        self._gc = gc
        self.telemetry = telemetry
        self._intervals: Deque[float] = deque(maxlen=WINDOW)
        self._leaves: Dict[str, Deque[float]] = {}
        self._steps = 0               # optimizer steps a dispatch
        self._last_close: Optional[float] = None
        self._gc_at_close = 0.0
        self._cpu_at_open = 0.0
        self._since_line = dict.fromkeys(("input_wait", "host", "fence"),
                                         0.0)
        self._folded = 0              # children of the open step summed

    def epoch_start(self) -> None:
        """What lies between two epochs is no step's interval."""
        self._last_close = None

    def step_open(self) -> None:
        self._cpu_at_open = time.thread_time()
        self._folded = 0    # a step a rewind broke off was never closed

    # --- the telemetry line's phases ---------------------------------------

    def _fold(self, step_span) -> None:
        kids = step_span.children
        for name, seconds in kids[self._folded:]:
            bucket = _BUCKET.get(name)
            if bucket is not None:
                self._since_line[bucket] += seconds
        self._folded = len(kids)

    def phases_since_line(self, step_span) -> Dict[str, float]:
        """``{"input_wait_s", "host_s", "fence_s"}``: the leaves'
        seconds since the last call, the open step's closed leaves
        among them (the last line's own logging too); empty with
        tracing off."""
        if step_span.children is None:
            return {}
        self._fold(step_span)
        out = {f"{k}_s": v for k, v in self._since_line.items()}
        self._since_line = dict.fromkeys(self._since_line, 0.0)
        return out

    # --- the pace -----------------------------------------------------------

    def step_close(self, step_span, *, steps: int = 1,
                   queue_depth: Optional[int] = None) -> None:
        """At the bottom of the step's block: the two attrs, the rule."""
        if step_span.children is None:      # tracing off
            return
        now = trace._now()
        self._fold(step_span)
        cpu_s = time.thread_time() - self._cpu_at_open
        gc_total = self._gc.seconds if self._gc is not None else 0.0
        gc_s, self._gc_at_close = gc_total - self._gc_at_close, gc_total
        last, self._last_close = self._last_close, now
        interval = None if last is None else now - last
        step_span.attrs["interval_s"] = interval
        step_span.attrs["cpu_s"] = cpu_s
        if steps != self._steps:
            # a trailing group runs step by step: another pace
            self._steps = steps
            self._intervals.clear()
            self._leaves.clear()
        leaves = _sum_by_name(step_span.children)
        if interval is not None:
            if len(self._intervals) >= MIN_HISTORY:
                median = statistics.median(self._intervals)
                if interval - median > max(ABS_S, REL * median):
                    self._slow(step_span, interval, median, leaves,
                               gc_s=gc_s, cpu_s=cpu_s,
                               queue_depth=queue_depth)
            self._intervals.append(interval)
            leaves[NO_LEAF] = interval - sum(leaves.values())
        for name, seconds in leaves.items():
            history = self._leaves.get(name)
            if history is None:
                history = self._leaves[name] = deque(maxlen=WINDOW)
            history.append(seconds)

    def _slow(self, step_span, interval: float, median: float,
              leaves: Dict[str, float], *, gc_s: float, cpu_s: float,
              queue_depth: Optional[int]) -> None:
        excess = interval - median
        no_leaf = interval - sum(leaves.values())
        usual = {name: statistics.median(h)
                 for name, h in self._leaves.items() if h}
        over = {name: seconds - usual.get(name, 0.0)
                for name, seconds in {**leaves, NO_LEAF: no_leaf}.items()}
        phase = max(over, key=over.get)
        if gc_s >= 0.5 * excess:
            phase = GC      # inside whichever leaf was open
        by_design = [n for n in BY_DESIGN if n in leaves]
        step = step_span.step
        fields = dict(
            interval_s=round(interval, 6), median_s=round(median, 6),
            excess_s=round(excess, 6), phase=phase,
            leaves={n: round(s, 6) for n, s in leaves.items()},
            usual={n: round(usual[n], 6) for n in leaves if n in usual},
            gc_s=round(gc_s, 6), cpu_s=round(cpu_s, 6),
            no_leaf_s=round(no_leaf, 6), queue_depth=queue_depth,
            by_design=by_design or None)
        events_mod.emit("slow_step", step=step, **fields)
        if self.telemetry is not None:
            self.telemetry.slow_step(step, stalled_s=None if by_design
                                     else excess, **fields)
        parts = " ".join(
            f"{n.removeprefix('train/')} {s:.4f} ({usual.get(n, 0.0):.4f})"
            for n, s in leaves.items())
        print(f"[slow_step] step {step}: interval {interval:.4f} s, median "
              f"{median:.4f} s of {len(self._intervals)} "
              f"(+{excess:.4f} s); largest excess {phase} "
              f"+{over.get(phase, gc_s):.4f} s; leaves, s (their medians): "
              f"{parts}; {GC} {gc_s:.4f} s; cpu {cpu_s:.4f} s; "
              f"queue_depth {queue_depth}; under no leaf {no_leaf:.4f} s"
              + (f"; slow by design: {','.join(by_design)}"
                 if by_design else ""),
              file=sys.stderr, flush=True)
