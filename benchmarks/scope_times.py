"""Device time by layer scope and host time by trainer phase: what the
``model.*``, ``train.*`` and ``setup.*`` per-layer metrics read.

**Device scopes.** The program puts ``jax.named_scope`` names of a closed
vocabulary (``perceiver_tpu.obs.trace.DEVICE_SCOPES``) on the train step's
operations. XLA carries an operation's name stack into the trace as the
stat ``tf_op`` of the event's *metadata* (``XEventMetadata.stats``), e.g.
``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/
rematted_computation/latent_self_attn/attn_core/dot_general:`` (looked at
by hand, PR 25); a fusion carries its root instruction's.
``jax.profiler.ProfileData`` shows an event's own stats only, not its
metadata's, so this file reads the ``.xplane.pb`` protobuf itself: a
decoder of the wire format for the six messages of ``xplane.proto``,
nothing but the standard library (a ``.textproto`` fixture goes through
``ProfileData.text_proto_to_serialized_xspace`` first).

An operation's *self* time (its duration less its children's: a
``while`` does not count its body) goes to one class, by the scopes in
its name stack: ``optimizer``; else ``attn_core``; else ``loss`` (with
``output_adapter``); else ``dense`` (any other scope of the vocabulary:
projections, MLPs, norms, residuals, the input adapter); else
``unscoped``. The classes partition the busy time. The pass is JAX's own
marker in the same stack: ``rematted_computation`` (what ``remat``
recomputes), else ``transpose(`` (backward), else forward and the rest.

**Trainer phases.** The program's ``obs.trace`` timeline holds the
trainer's spans on ``time.monotonic``, the clock the benchmark's
``Tracer`` notes at the traced window's two ends.

Every function gives ``None`` where there is nothing to read: no trace,
no device plane (a rehearsal on the CPU), a program without the scopes
or the timeline (the parent of the PR that added them).
"""

from __future__ import annotations

import dataclasses
import functools
import re
import statistics
from typing import Dict, Iterator, List, Optional, Tuple

from benchmarks import trace_reduce

CLASSES = ("attn_core", "dense", "loss", "optimizer", "unscoped")
# the vocabulary, by class; tests hold it against the program's tuple
SCOPE_CLASS = {
    "optimizer": "optimizer",
    "attn_core": "attn_core",
    "loss": "loss", "output_adapter": "loss",
    "attn_proj": "dense", "mlp": "dense", "input_adapter": "dense",
    "enc_cross_attn": "dense", "latent_self_attn": "dense",
    "dec_cross_attn": "dense",
}
_PRIORITY = ("optimizer", "attn_core", "loss", "dense")
LAYERS = ("input_adapter", "enc_cross_attn", "latent_self_attn",
          "dec_cross_attn", "output_adapter", "loss", "optimizer")
REMAT_MARK = "rematted_computation"
BACKWARD_MARK = "transpose("
# the host's own work in a step; train/fence is the host waiting
HOST_PHASES = ("train/input_wait", "train/shard", "train/dispatch",
               "train/guard_sync", "train/log")

_NAME = re.compile(r"[A-Za-z_][A-Za-z0-9_.]*")


# --- the protobuf wire format, as far as xplane.proto needs it ---------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, i: int,
            end: int) -> Iterator[Tuple[int, int, int, int]]:
    """(field number, wire type, value or start, end) of each field of
    the message in ``buf[i:end]``; for a length-delimited field the
    payload is ``buf[value:end]``."""
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, wire, value, i
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, wire, i, i + size
            i += size
        elif wire == 1:
            yield field, wire, i, i + 8
            i += 8
        elif wire == 5:
            yield field, wire, i, i + 4
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _map_entry(buf: bytes, i: int, end: int) -> Tuple[int, int, int]:
    key = start = stop = 0
    for field, _, a, b in _fields(buf, i, end):
        if field == 1:
            key = a
        elif field == 2:
            start, stop = a, b
    return key, start, stop


@dataclasses.dataclass
class DeviceOps:
    """One device plane's ``XLA Ops`` line."""

    plane: str
    # (start ps, duration ps, metadata id) per event
    events: List[Tuple[int, int, int]]
    names: Dict[int, str]      # metadata id -> the instruction's text
    op_names: Dict[int, str]   # metadata id -> tf_op (the name stack)


def _plane(buf: bytes, i: int, end: int) -> Optional[DeviceOps]:
    name, lines, metadata, stat_names = "", [], [], {}
    for field, _, a, b in _fields(buf, i, end):
        if field == 2:
            name = buf[a:b].decode()
        elif field == 3:
            lines.append((a, b))
        elif field == 4:
            metadata.append((a, b))
        elif field == 5:
            key, s, e = _map_entry(buf, a, b)
            for f, _, x, y in _fields(buf, s, e):
                if f == 2:
                    stat_names[key] = buf[x:y].decode()
    if not name.startswith(trace_reduce.DEVICE_PLANE):
        return None
    tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
    names, op_names = {}, {}
    for a, b in metadata:
        key, s, e = _map_entry(buf, a, b)
        for f, _, x, y in _fields(buf, s, e):
            if f == 2:
                names[key] = buf[x:y].decode()
            elif f == 5:        # XStat
                stat_id, text = 0, None
                for g, _, p, q in _fields(buf, x, y):
                    if g == 1:
                        stat_id = p
                    elif g == 5:
                        text = buf[p:q].decode()
                    elif g == 7:    # a reference to a stat's name
                        text = stat_names.get(p, "")
                if stat_id in tf_op and text is not None:
                    op_names[key] = text
    events = []
    for a, b in lines:
        line_name, stamp_ns, raw = "", 0, []
        for f, _, x, y in _fields(buf, a, b):
            if f == 2:
                line_name = buf[x:y].decode()
            elif f == 3:
                stamp_ns = x
            elif f == 4:
                raw.append((x, y))
        if line_name != trace_reduce.OPS_LINE:
            continue
        for x, y in raw:
            meta = offset = duration = 0
            for g, _, p, _q in _fields(buf, x, y):
                if g == 1:
                    meta = p
                elif g == 2:
                    offset = p
                elif g == 3:
                    duration = p
            events.append((stamp_ns * 1000 + offset, duration, meta))
    return DeviceOps(name, events, names, op_names) if events else None


def load_device_ops(path: str) -> List[DeviceOps]:
    """The device planes of a trace file with their operations' name
    stacks; empty where the trace holds no device operation."""
    if path.endswith((".textproto", ".txt")):
        from jax.profiler import ProfileData

        with open(path) as f:
            buf = ProfileData.text_proto_to_serialized_xspace(f.read())
    else:
        with open(path, "rb") as f:
            buf = f.read()
    planes = []
    for field, _, a, b in _fields(buf, 0, len(buf)):
        if field == 1:
            plane = _plane(buf, a, b)
            if plane is not None:
                planes.append(plane)
    return planes


# --- device time by scope ----------------------------------------------------


def scopes_of(op_name: str) -> List[str]:
    """The vocabulary's scopes in a name stack, outermost first. A
    segment counts when it is a scope's name, bare or inside JAX's
    transform marks (``transpose(jvp(mlp))``), never a ``jit(...)``."""
    found = []
    for segment in op_name.rstrip(":").split("/"):
        *marks, name = _NAME.findall(segment) or [""]
        if name in SCOPE_CLASS and "jit" not in marks \
                and "pjit" not in marks:
            found.append(name)
    return found


def classify(op_name: str) -> str:
    found = {SCOPE_CLASS[s] for s in scopes_of(op_name)}
    for cls in _PRIORITY:
        if cls in found:
            return cls
    return "unscoped"


def pass_of(op_name: str) -> str:
    if REMAT_MARK in op_name:
        return "remat"
    if BACKWARD_MARK in op_name:
        return "backward"
    return "forward"


def layer_of(op_name: str) -> str:
    found = [s for s in scopes_of(op_name) if s in LAYERS]
    return found[0] if found else "none"


@dataclasses.dataclass
class ScopeTimes:
    devices: int
    busy_s: float                       # per device
    by_class: Dict[str, float]          # seconds per device; sums to busy_s
    by_pass: Dict[str, float]
    by_layer_pass: Dict[Tuple[str, str], float]
    top_unscoped: List[Tuple[str, float]]
    scoped: bool                        # some operation carries a scope

    def share(self, cls: str) -> float:
        return 100.0 * self.by_class.get(cls, 0.0) / self.busy_s


def self_ps_by_metadata(events) -> Dict[int, float]:
    """Self time in ps per metadata id (``trace_reduce.self_times``: an
    event's duration less that of the events lying directly inside)."""
    totals, _ = trace_reduce.self_times(
        [trace_reduce.Event(str(meta), start, start + duration)
         for start, duration, meta in events])
    return {int(meta): ps for meta, ps in totals.items()}


def reduce_scopes(planes: List[DeviceOps]) -> Optional[ScopeTimes]:
    if not planes:
        return None
    by_class = dict.fromkeys(CLASSES, 0.0)
    by_pass: Dict[str, float] = {}
    by_layer_pass: Dict[Tuple[str, str], float] = {}
    unscoped: Dict[str, float] = {}
    scoped = False
    for plane in planes:
        for meta, ps in self_ps_by_metadata(plane.events).items():
            op_name = plane.op_names.get(meta, "")
            seconds = ps / 1e12 / len(planes)
            cls = classify(op_name)
            scoped = scoped or cls != "unscoped"
            by_class[cls] += seconds
            which = pass_of(op_name)
            by_pass[which] = by_pass.get(which, 0.0) + seconds
            key = (layer_of(op_name), which)
            by_layer_pass[key] = by_layer_pass.get(key, 0.0) + seconds
            if cls == "unscoped":
                what = trace_reduce.short_name(plane.names.get(meta, "?"), 48)
                label = f"{what} <{op_name[-72:]}>"
                unscoped[label] = unscoped.get(label, 0.0) + seconds
    busy = sum(by_class.values())
    if busy <= 0:
        return None
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:5]
    return ScopeTimes(len(planes), busy, by_class, by_pass, by_layer_pass,
                      top, scoped)


@functools.lru_cache(maxsize=2)
def _scope_times_of(path: str) -> Optional[ScopeTimes]:
    times = reduce_scopes(load_device_ops(path))
    if times is not None:
        _say(times)
    return times


def _say(times: ScopeTimes) -> None:
    def pct(x):
        return f"{100.0 * x / times.busy_s:.2f}%"

    print(f"[bench] device busy {times.busy_s:.4f} s a chip in the traced "
          "window; by class: " + ", ".join(
              f"{k} {pct(v)}" for k, v in times.by_class.items())
          + "; by pass: " + ", ".join(
              f"{k} {pct(v)}" for k, v in sorted(times.by_pass.items())),
          flush=True)
    rows = sorted(times.by_layer_pass.items(), key=lambda kv: -kv[1])
    print("[bench] by layer and pass: " + ", ".join(
        f"{layer}/{which} {pct(v)}" for (layer, which), v in rows
        if v > 0.0005 * times.busy_s), flush=True)
    if times.top_unscoped:
        print("[bench] longest unscoped: " + "; ".join(
            f"{k} {v:.4f} s" for k, v in times.top_unscoped), flush=True)


def scope_times(run) -> Optional[ScopeTimes]:
    """The traced window's device time by scope, or None: no trace, no
    device plane, or a program whose operations carry no scope."""
    if run.trace is None or not run.tracer.enabled:
        return None
    try:
        path = trace_reduce.find_xplane(run.tracer.directory)
    except FileNotFoundError:
        return None
    times = _scope_times_of(path)
    if times is None or not times.scoped:
        return None
    return times


def class_share(run, cls: str) -> Optional[float]:
    times = scope_times(run)
    return None if times is None else times.share(cls)


def remat_share(run) -> Optional[float]:
    times = scope_times(run)
    if times is None:
        return None
    return 100.0 * times.by_pass.get("remat", 0.0) / times.busy_s


# --- the trainer's phases ----------------------------------------------------


def _timeline():
    """The program's span ring, or None where it has none."""
    try:
        from perceiver_tpu.obs import trace
    except ImportError:
        return None
    get = getattr(trace, "timeline", None)
    return get() if get is not None else None


def _kept_since(tl, since: Optional[float], what: str) -> bool:
    """False, with a line saying so, if the ring has overwritten spans
    that ``what`` needs (those from ``since`` on; None: all)."""
    if not tl.dropped:
        return True
    spans = tl.spans()
    if since is not None and spans and spans[0]["start"] <= since:
        return True
    print(f"[bench] {what}: not reported, the program's span ring "
          f"dropped {tl.dropped} spans it needs", flush=True)
    return False


def window_spans(run, what: str) -> Optional[List[dict]]:
    """The timeline's spans that began inside the traced window."""
    tl = _timeline()
    tracer = run.tracer
    if tl is None or not tracer.enabled or tracer.mono0 is None \
            or tracer.mono1 is None:
        return None
    if not _kept_since(tl, tracer.mono0, what):
        return None
    return tl.spans(since=tracer.mono0, until=tracer.mono1)


def input_wait_share(run) -> Optional[float]:
    spans = window_spans(run, "train.input_wait_pct")
    if not spans or not any(s["name"] == "train/step" for s in spans):
        return None
    waited = sum(s["duration_s"] for s in spans
                 if s["name"] == "train/input_wait")
    return 100.0 * waited / (run.tracer.mono1 - run.tracer.mono0)


def host_ms_per_step(run) -> Optional[float]:
    spans = window_spans(run, "train.host_ms_per_step")
    if not spans:
        return None
    steps = {s["id"]: 0.0 for s in spans if s["name"] == "train/step"}
    for s in spans:
        if s["name"] in HOST_PHASES and s["parent"] in steps:
            steps[s["parent"]] += s["duration_s"]
    if not steps:
        return None
    by_phase: Dict[str, float] = {}
    for s in spans:
        if s["parent"] in steps:
            by_phase[s["name"]] = by_phase.get(s["name"], 0.0) \
                + s["duration_s"]
    print(f"[bench] trainer phases over {len(steps)} steps of the traced "
          "window, ms a step: " + ", ".join(
              f"{k} {1e3 * v / len(steps):.3f}"
              for k, v in sorted(by_phase.items())), flush=True)
    return 1e3 * statistics.median(steps.values())


def setup_seconds(run, name: str, what: str) -> Optional[float]:
    """Seconds of the spans called ``name`` that ended before the traced
    window opened."""
    tl = _timeline()
    if tl is None or not run.tracer.enabled or run.tracer.mono0 is None:
        return None
    if not _kept_since(tl, None, what):
        return None
    spans = [s for s in tl.spans(name) if s["end"] <= run.tracer.mono0]
    if not spans:
        return None
    return sum(s["duration_s"] for s in spans)
