"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time, per-operation self time, and
the longest idle gaps with what the host was doing in them.

What a TPU trace holds (looked at by hand, PR 24): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed
HLO instruction, named by the instruction's text (``%fusion.12 = ...``),
control flow included: a ``%while`` event spans the events of its body.
``XLA Modules`` has one event per program run. Host threads are lines
of the plane ``/host:CPU``; JAX's own annotations (``PjitFunction(..)``,
``np.asarray(jax.Array)``) and the benchmark's (``bench/...``,
``jax.profiler.TraceAnnotation``) are events there, on the same clock.

Busy time is the union of the ``XLA Ops`` intervals, so a loop counts
as busy throughout. An operation's self time is its duration less its
children's, so that a loop's time is not counted twice.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# the longest gaps are attributed one by one, the rest lumped
ATTRIBUTED_GAPS = 400
# host events that say nothing about what the host was doing
_HOST_NOISE = ("MemoryAllocation", "MemoryDeallocation",
               "Wait for donation holds", "Wait for usage holds")


_LAYOUT = re.compile(r"\{[^{}]*\}")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def duration_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Reduction:
    devices: int
    window_ns: float
    busy_ns: float                      # mean over the devices
    op_self_ns: Dict[str, float]        # summed over the devices
    op_calls: Dict[str, int]
    gaps: List[Tuple[str, float]]       # (what the host did, ns), longest first
    events: Dict[str, List[Event]]      # device plane -> its op events

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith((".textproto", ".txt")):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def short_name(hlo_text: str, width: int = 96) -> str:
    """``%fusion.3 = bf16[8,128]{...} fusion(...)`` -> ``fusion.3
    bf16[8,128] fusion``: the instruction, its result's shape, its
    opcode; a Pallas kernel keeps its call target."""
    head, sep, rest = _LAYOUT.sub("", hlo_text).partition(" = ")
    if not sep:
        return hlo_text[:width]
    if rest.startswith("("):
        shape, _, tail = rest.partition(") ")
        shape += ")"
    else:
        shape, _, tail = rest.partition(" ")
    opcode = tail.split("(")[0]
    name = f"{head.lstrip('%')} {shape} {opcode}".strip()
    if "tpu_custom_call" in rest:
        name += " tpu_custom_call"
    return name[:width]


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def union_ns(events: Iterable[Event]) -> Tuple[float, List[Tuple[float, float]]]:
    """(total covered ns, the gaps between covered stretches)."""
    covered, gaps, end = 0.0, [], None
    for e in sorted(events, key=lambda e: e.start_ns):
        if end is None:
            covered, end = e.duration_ns, e.end_ns
        elif e.start_ns > end:
            gaps.append((end, e.start_ns))
            covered, end = covered + e.duration_ns, e.end_ns
        elif e.end_ns > end:
            covered, end = covered + e.end_ns - end, e.end_ns
    return covered, gaps


def self_times(events: Sequence[Event]) -> Tuple[Dict[str, float], Dict[str, int]]:
    """Self time and calls per event name: an event that lies inside
    another is the other's child, and its time is taken off it."""
    total: Dict[str, float] = {}
    calls: Dict[str, int] = {}
    stack: List[Event] = []
    for e in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        while stack and stack[-1].end_ns <= e.start_ns:
            stack.pop()
        if stack:
            parent = stack[-1]
            total[parent.name] = total.get(parent.name, 0.0) - e.duration_ns
        total[e.name] = total.get(e.name, 0.0) + e.duration_ns
        calls[e.name] = calls.get(e.name, 0) + 1
        stack.append(e)
    return total, calls


def _host_events(profile) -> List[Event]:
    out = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            out.extend(e for e in _events(line)
                       if e.duration_ns > 0 and e.name not in _HOST_NOISE)
    return out


def attribute(gap: Tuple[float, float], host: Sequence[Event],
              window_ns: float) -> str:
    """The host event that covers most of ``gap``; events that span
    most of the whole window (the benchmark's own window span, a thread
    blocked for the run) say nothing and are passed over, the
    benchmark's spans win ties."""
    lo, hi = gap
    best, best_ns = "no host event", 0.0
    for e in host:
        if e.duration_ns > 0.5 * window_ns:
            continue
        overlap = min(hi, e.end_ns) - max(lo, e.start_ns)
        if e.name.startswith("bench/"):
            overlap *= 1.0001
        if overlap > best_ns:
            best, best_ns = e.name, overlap
    return best


def reduce(profile, window_ns: Optional[float] = None,
           top_gaps: int = 10) -> Reduction:
    """Reduce a loaded trace. ``window_ns`` is the traced window's
    length on the host clock; without it, the span of the device
    events is taken."""
    per_device: Dict[str, List[Event]] = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines = {line.name: line for line in plane.lines}
        line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if line is not None:
            per_device[plane.name] = _events(line)
    per_device = {k: v for k, v in per_device.items() if v}
    if not per_device:
        raise ValueError("the trace holds no device operation")
    if window_ns is None:
        window_ns = max(
            max(e.end_ns for e in ev) - min(e.start_ns for e in ev)
            for ev in per_device.values())
    host = _host_events(profile)
    busy, op_self, op_calls, gaps = 0.0, {}, {}, []
    for events in per_device.values():
        covered, dev_gaps = union_ns(events)
        busy += covered
        totals, calls = self_times(events)
        for k, v in totals.items():
            op_self[k] = op_self.get(k, 0.0) + v
        for k, v in calls.items():
            op_calls[k] = op_calls.get(k, 0) + v
        gaps.extend(dev_gaps)
    gaps.sort(key=lambda g: g[0] - g[1])
    by_cause: Dict[str, float] = {}
    # the few hundred longest gaps carry the idle time; the stalls of
    # nanoseconds between one operation and the next are lumped
    for gap in gaps[:ATTRIBUTED_GAPS]:
        cause = attribute(gap, host, window_ns)
        by_cause[cause] = by_cause.get(cause, 0.0) + gap[1] - gap[0]
    rest = sum(hi - lo for lo, hi in gaps[ATTRIBUTED_GAPS:])
    if rest:
        by_cause["between operations (short stalls)"] = rest
    ranked = sorted(by_cause.items(), key=lambda kv: -kv[1])[:top_gaps]
    return Reduction(devices=len(per_device), window_ns=window_ns,
                     busy_ns=busy / len(per_device), op_self_ns=op_self,
                     op_calls=op_calls, gaps=ranked, events=per_device)


def breakdown(red: Reduction, top: int = 10) -> dict:
    """The contract's ``breakdown``: the operations that took most
    device time (self time, seconds, per device) and the idle time by
    what the host was doing."""
    ops = sorted(red.op_self_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[short_name(k), v / 1e9 / red.devices]
                       for k, v in ops],
        "idle_gaps": [[k[:96], v / 1e9 / red.devices]
                      for k, v in red.gaps],
    }


def kernel_seconds(red: Reduction, match: Sequence[str]) -> Tuple[float, int]:
    """(summed duration, calls) of the device events whose name holds
    every string of ``match``."""
    total, calls = 0.0, 0
    for events in red.events.values():
        for e in events:
            if all(m in e.name for m in match):
                total += e.duration_ns
                calls += 1
    return total / 1e9, calls
