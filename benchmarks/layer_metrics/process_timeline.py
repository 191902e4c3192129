"""The program's timeline from the process's start to the window's end:
what ``setup.import_s``, ``setup.first_steps_s``, ``setup.uncovered_s``,
``train.stall_pct`` and ``train.gc_pct`` read.

The program's ring (``perceiver_tpu.obs.trace.timeline()``) holds, on
``time.monotonic``: ``proc/boot`` from the kernel's start time of the
process, one ``proc/import`` a heavy import (they nest: self seconds are
counted), ``proc/backend_init``, the trainer's spans around and inside
its steps, and ``proc/gc`` for every collection over a millisecond. The
window's opening (``run.outcome.t_open``, on ``perf_counter``) is put on
that clock through the pair the tracer noted at its start
(``run.tracer.t0`` / ``mono0``).

**Before the window** the five ``setup.*`` metrics add up to the run's
set-up seconds, process start to the window's opening:
``setup.import_s + setup.state_build_s + setup.step_load_s +
setup.first_steps_s + setup.uncovered_s`` (and the few spans that are
none of these, printed as ``other``). ``setup.uncovered_s`` is what lies
under no span of the program: the counter that keeps the timeline
closed.

**Inside the window** the readers take the whole window, not the
profiler's first seconds: every ``train/step`` span carries
``interval_s``, its pace against the step before.

Every function gives None, and raises nothing, where the program has
no such spans (the parent of the PR that added them) or the ring has
overwritten what it needs (with a line that says so).
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Tuple

from benchmarks import scope_times

IMPORT_SPANS = ("proc/boot", "proc/import", "proc/backend_init")
SKIP = 4   # the window's first steps, as train.step_ms leaves them out


def _program_timeline():
    """The ring of a program that writes the process's spans, or None."""
    try:
        from perceiver_tpu.obs import trace
    except ImportError:
        return None
    if not hasattr(trace, "PROCESS_PHASES"):
        return None
    return trace.timeline()


def window_opening(run) -> Optional[float]:
    """The window's opening on the spans' clock."""
    tracer = run.tracer
    if not tracer.enabled or getattr(tracer, "t0", None) is None \
            or tracer.mono0 is None:
        return None
    return run.outcome.t_open - tracer.t0 + tracer.mono0


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


class SetUp:
    """The program's spans from the process's start to the window's
    opening, on the threads that booted and ran the steps, each clipped
    to that stretch."""

    def __init__(self, start: float, opened: float, spans: List[dict],
                 ring: str = ""):
        self.start, self.opened, self.ring = start, opened, ring
        threads = {s["thread"] for s in spans
                   if s["name"] in ("proc/boot", "train/step")}
        self.spans = [
            dict(s, end=min(s["end"], opened),
                 duration_s=min(s["end"], opened) - s["start"])
            for s in spans
            if s["thread"] in threads and start <= s["start"] < opened]

    @property
    def seconds(self) -> float:
        return self.opened - self.start

    def named(self, *names: str) -> List[dict]:
        return [s for s in self.spans if s["name"] in names]

    def import_self_seconds(self) -> Dict[str, float]:
        """Self seconds of the boot, the imports and the runtime's
        start, by module (``(boot)``, ``(backend_init)``)."""
        spans = self.named(*IMPORT_SPANS)
        inside: Dict[int, float] = {}
        ids = {s["id"] for s in spans}
        for s in spans:
            if s["parent"] in ids:
                inside[s["parent"]] = inside.get(s["parent"], 0.0) \
                    + s["duration_s"]
        out: Dict[str, float] = {}
        for s in spans:
            label = s["attrs"].get("module") \
                or "(" + s["name"].split("/")[1] + ")"
            out[label] = out.get(label, 0.0) + s["duration_s"] \
                - inside.get(s["id"], 0.0)
        return out

    def first_steps_seconds(self) -> Optional[float]:
        """The steps before the window less the step's load inside
        them, plus the epochs' ends (where the benchmark's probe waits
        for the device and reads its norms)."""
        ends = self.named("train/epoch_end")
        steps = self.named("train/step")
        if not ends or not steps:
            return None
        ids = {s["id"] for s in steps}
        loads = sum(s["duration_s"] for s in self.named("train/step_load")
                    if s["parent"] in ids)
        return sum(s["duration_s"] for s in steps + ends) - loads

    def holes(self) -> List[Tuple[float, float]]:
        """The stretches under no span, in order."""
        covered = _union([(s["start"], s["end"]) for s in self.spans])
        edges = [self.start] + [t for ab in covered for t in ab] \
            + [self.opened]
        return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def set_up(run, what: str) -> Optional[SetUp]:
    tl = _program_timeline()
    opened = window_opening(run)
    if tl is None or opened is None:
        return None
    if not scope_times._kept_since(tl, None, what):
        return None
    spans = tl.spans()
    boots = [s for s in spans if s["name"] == "proc/boot"]
    if not boots:
        return None
    return SetUp(boots[0]["start"], opened, spans,
                 ring=f"the ring holds {len(spans)} spans, dropped "
                      f"{tl.dropped}")


def import_seconds(run) -> Optional[float]:
    su = set_up(run, "setup.import_s")
    if su is None:
        return None
    by_module = su.import_self_seconds()
    total = sum(by_module.values())
    boot = su.named("proc/boot")[0]
    print(f"[bench] setup.import_s {total:.3f} s, self seconds by module: "
          + ", ".join(f"{k} {v:.3f}" for k, v in sorted(
              by_module.items(), key=lambda kv: -kv[1]) if v >= 0.05)
          + f"; loaded before the program's first line: "
            f"{boot['attrs'].get('loaded') or 'none of the heavy modules'}",
          flush=True)
    return total


def first_steps_seconds(run) -> Optional[float]:
    su = set_up(run, "setup.first_steps_s")
    return None if su is None else su.first_steps_seconds()


def _label(span: dict) -> str:
    module = span["attrs"].get("module")
    return span["name"] + (f"[{module}]" if module else "")


def uncovered_seconds(run) -> Optional[float]:
    su = set_up(run, "setup.uncovered_s")
    if su is None:
        return None
    holes = su.holes()
    uncovered = sum(b - a for a, b in holes)
    parts = {
        "import": sum(su.import_self_seconds().values()),
        "state_build": sum(s["duration_s"]
                           for s in su.named("train/build_state")),
        "step_load": sum(s["duration_s"]
                         for s in su.named("train/step_load")),
        "first_steps": su.first_steps_seconds() or 0.0,
    }
    other = su.seconds - uncovered - sum(parts.values())
    print(f"[bench] setup.uncovered_s {uncovered:.3f} s of "
          f"{su.seconds:.3f} s from the process's start to the window's "
          "opening; " + " + ".join(f"{k} {v:.3f}" for k, v in parts.items())
          + f" + uncovered {uncovered:.3f} = "
          f"{sum(parts.values()) + uncovered:.3f} s (other spans "
          f"{other:.3f} s); {su.ring}", flush=True)
    # the longest holes, each between the spans that end and begin it
    ordered = sorted(su.spans, key=lambda s: (s["start"], -s["end"]))
    said = []
    for a, b in sorted((ab for ab in holes if ab[1] - ab[0] >= 1e-3),
                       key=lambda ab: ab[0] - ab[1])[:6]:
        before = [s for s in ordered if s["end"] <= a + 1e-9]
        after = [s for s in ordered if s["start"] >= b - 1e-9]
        said.append(
            f"{b - a:.3f} s at t+{a - su.start:.1f} after "
            f"{_label(max(before, key=lambda s: s['end'])) if before else 'the start'}"
            f" before {_label(after[0]) if after else 'the window'}")
    print("[bench] setup.uncovered_s longest holes: " + "; ".join(said),
          flush=True)
    # the benchmark's own spans that overlap the holes: said, not taken off
    tracer = run.tracer
    own = []
    for name, t0, t1, _ in getattr(run.spans, "items", []):
        m0, m1 = (t - tracer.t0 + tracer.mono0 for t in (t0, t1))
        overlap = sum(max(0.0, min(b, m1) - max(a, m0)) for a, b in holes)
        if overlap > 0:
            own.append(f"{name} {m1 - m0:.3f} s ({overlap:.3f} uncovered)")
    if own:
        print("[bench] setup.uncovered_s beside the benchmark's own spans: "
              + ", ".join(own), flush=True)
    return uncovered


# --- inside the window -------------------------------------------------------


def window_spans(run, what: str) -> Optional[Tuple[float, float, List[dict]]]:
    """(opening, length, the ring's spans that lie in the whole window)."""
    tl = _program_timeline()
    opened = window_opening(run)
    elapsed = run.outcome.data.get("elapsed_s")
    if tl is None or opened is None or not elapsed:
        return None
    if not scope_times._kept_since(tl, opened, what):
        return None
    spans = [s for s in tl.spans(since=opened)
             if s["end"] <= opened + elapsed + 1e-6]
    return opened, elapsed, spans


def _slow_step_events(steps: List[dict]) -> List[dict]:
    """The program's own ``slow_step`` events for these steps."""
    try:
        from perceiver_tpu.obs import events
    except ImportError:
        return []
    numbers = {s["step"] for s in steps}
    return [e for e in events.default_log().events("slow_step")
            if e.get("step") in numbers]


def stall_pct(run) -> Optional[float]:
    found = window_spans(run, "train.stall_pct")
    if found is None:
        return None
    _, _, spans = found
    steps = [s for s in spans if s["name"] == "train/step"]
    intervals = [s["attrs"].get("interval_s") for s in steps[SKIP:]]
    intervals = [x for x in intervals if x is not None]
    if len(intervals) < 3:
        return None
    mean, median = statistics.fmean(intervals), statistics.median(intervals)
    slow = _slow_step_events(steps)
    print(f"[bench] train.stall_pct over {len(intervals)} steps of the "
          f"whole window: interval mean {1e3 * mean:.3f} ms, median "
          f"{1e3 * median:.3f} ms, longest {1e3 * max(intervals):.3f} ms; "
          f"slow steps by the program's rule: "
          + ("; ".join(
              f"step {e['step']} {1e3 * e['interval_s']:.1f} ms "
              f"(median {1e3 * e['median_s']:.1f}) {e.get('phase')}"
              + (f" by design {e['by_design']}" if e.get("by_design") else "")
              for e in slow) or "none"), flush=True)
    return 100.0 * (mean - median) / mean


def gc_pct(run) -> Optional[float]:
    found = window_spans(run, "train.gc_pct")
    if found is None:
        return None
    _, elapsed, spans = found
    collections = [s for s in spans if s["name"] == "proc/gc"]
    seconds = sum(s["duration_s"] for s in collections)
    print(f"[bench] train.gc_pct: {len(collections)} collections over 1 ms "
          f"in the window, {seconds:.4f} s"
          + (f", the longest {1e3 * max(s['duration_s'] for s in collections):.1f}"
             f" ms (generation "
             f"{max(collections, key=lambda s: s['duration_s'])['attrs'].get('generation')})"
             if collections else ""), flush=True)
    return 100.0 * seconds / elapsed
