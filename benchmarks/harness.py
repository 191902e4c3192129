"""The benchmark's harness: finds a cell's files by name, looks for the
chip, runs the cell's runner, reads the per-layer metrics and prints
the result line.

Everything that belongs to one cell, configuration, traffic mix or
per-layer metric is a file of its own, found by the name
``BENCHMARK.json`` gives:

* ``BENCHMARK.json``                      the cell: configuration, mix, chips
* ``benchmarks/configs/<config>.json``    sizes, deployment, what was cut
* ``benchmarks/traffic/<traffic>.json``   the mix's parameters and its runner
* ``benchmarks/limits/<cell>.json``       the limit of each number compared
* ``benchmarks/pending/<cell>.json``      a proven cell's entries, not listed yet
* ``benchmarks/runners/<runner>.py``      ``run(ctx) -> Outcome``
* ``benchmarks/tasks/<task>.py``          what a kind of task needs
* ``benchmarks/layer_metrics/<name>.py``  ``read(run) -> number or None``

so a later cell, configuration, kind of task or metric is added as
files and an entry in ``BENCHMARK.json``, with no edit here.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# fixed, inside the checkout: the directory is part of the cache's key
CACHE_DIR = os.path.join(ROOT, ".bench_cache")


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class BenchmarkError(Exception):
    """The cell cannot be run as asked (no chip, a missing file)."""


# --- files by name -----------------------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchmarkError(f"no BENCHMARK.json in {root}")
    return load_json(path)


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchmarkError(
        f"BENCHMARK.json has no {what} named {name!r} "
        f"(it has {[e['name'] for e in entries]})")


def load_module(path: str, name: str):
    if not os.path.exists(path):
        raise BenchmarkError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module  # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


def load_task(name: str, root: str = ROOT):
    """The task file of a configuration's ``task`` key."""
    return load_module(os.path.join(root, "benchmarks", "tasks",
                                    f"{name}.py"), f"bench_task_{name}")


def flat_config(config: dict, rehearse: bool) -> dict:
    """The configuration as the runners, the reference and the FLOP
    functions read it: task, model sizes and token ids in one dict.
    A rehearsal swaps in the file's toy widths."""
    model = dict(config["model"])
    if rehearse:
        model.update(config["rehearsal"]["model"])
    return {"task": config["task"], **model, **config.get("tokens", {})}


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its files read."""

    name: str
    chips: int
    config: dict          # the configuration file
    mix: dict             # the traffic file
    limits: Dict[str, float]
    manifest: dict
    root: str

    def metrics(self, group: str) -> List[dict]:
        """The manifest's metrics of ``group`` that this cell reports:
        those that name it, or name no cell and move (or are) an
        end-to-end metric this cell reports."""
        mine = {m["name"] for m in self.manifest["end_to_end"]
                if self.name in m.get("workloads", [self.name])}
        out = []
        for m in self.manifest[group]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif group == "end_to_end" or m["moves"] in mine:
                out.append(m)
        return out


def with_pending(manifest: dict, name: str, root: str) -> dict:
    """``manifest`` with the entries of ``benchmarks/pending/<name>.json``
    added: a cell whose files are here and proven but which
    ``BENCHMARK.json`` does not list yet (PERF.md, Open questions). The
    file holds its ``workload`` entry and the ``end_to_end`` and
    ``per_layer`` entries it needs, as they would be added."""
    path = os.path.join(root, "benchmarks", "pending", f"{name}.json")
    if any(w["name"] == name for w in manifest["workloads"]) \
            or not os.path.exists(path):
        return manifest
    pending = load_json(path)
    manifest = json.loads(json.dumps(manifest))
    manifest["workloads"].append(pending["workload"])
    for group in ("end_to_end", "per_layer"):
        have = {m["name"] for m in manifest[group]}
        manifest[group] += [m for m in pending.get(group, [])
                            if m["name"] not in have]
    return manifest


def load_cell(name: str, root: str = ROOT) -> Cell:
    manifest = with_pending(load_manifest(root), name, root)
    entry = _by_name(manifest["workloads"], name, "workload")
    config_entry = _by_name(manifest["configs"], entry["config"], "config")
    bench = os.path.join(root, "benchmarks")
    mix = load_json(os.path.join(bench, "traffic",
                                 f"{entry['traffic']}.json"))
    return Cell(name=name, chips=int(entry["chips"]),
                config=load_json(os.path.join(root, config_entry["file"])),
                mix=mix,
                limits=load_json(os.path.join(bench, "limits",
                                              f"{name}.json")),
                manifest=manifest, root=root)


# --- what a runner gets and gives --------------------------------------------


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(math.isfinite(self.value) and self.value <= self.limit)


@dataclasses.dataclass
class Outcome:
    """What a runner hands back."""

    t_open: float                  # perf_counter at the window's opening
    metrics: Dict[str, float]      # end-to-end values, by name
    attempted: int
    failed: int
    checks: List[Check]
    data: Dict[str, Any]           # for the per-layer readers
    memory_peak_bytes: Optional[int]


class Spans:
    """The benchmark's own host spans, kept in memory; in a traced run
    each is also a ``TraceAnnotation``, so that it lies on the device
    trace's clock."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.items: List[tuple] = []
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        t0 = time.perf_counter()
        if self.annotate:
            import jax

            cm = jax.profiler.TraceAnnotation(f"bench/{name}")
        else:
            cm = contextlib.nullcontext()
        try:
            with cm:
                yield
        finally:
            with self._lock:
                self.items.append((name, t0, time.perf_counter(), attrs))


class Tracer:
    """The profiler around the first seconds of a traced run's window:
    a trace of the whole window is large, slows the host and says no
    more."""

    def __init__(self, enabled: bool, directory: str, seconds: float):
        self.enabled, self.directory, self.seconds = \
            enabled, directory, seconds
        self.t0 = self.t1 = None
        self.mono0 = self.mono1 = None
        self._timer: Optional[threading.Timer] = None
        self._lock = threading.Lock()

    def start(self) -> None:
        if not self.enabled:
            return
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # host TraceMe events only
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.t0, self.mono0 = time.perf_counter(), time.monotonic()
        self._timer = threading.Timer(self.seconds, self.stop)
        self._timer.daemon = True
        self._timer.start()

    def stop(self) -> None:
        with self._lock:
            if not self.enabled or self.t0 is None or self.t1 is not None:
                return
            import jax

            self.t1, self.mono1 = time.perf_counter(), time.monotonic()
            jax.profiler.stop_trace()

    def finish(self):
        """Stop if still running; the reduced trace, or None."""
        if not self.enabled or self.t0 is None:
            return None
        if self._timer is not None:
            self._timer.cancel()
        self.stop()
        from benchmarks import trace_reduce

        profile = trace_reduce.load(
            trace_reduce.find_xplane(self.directory))
        return trace_reduce.reduce(profile,
                                   window_ns=(self.t1 - self.t0) * 1e9)


@dataclasses.dataclass
class Context:
    cell: Cell
    cfg: dict             # flat_config
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    task: Any             # the module benchmarks/tasks/<task>.py
    workdir: str          # temporary, removed at exit
    spans: Spans
    tracer: Tracer
    t_start: float        # perf_counter at process start
    # benchmarks/control.py only: also compute the comparisons' numbers
    # for the reference in this lower precision, in the program's place
    control: Optional[str] = None

    @property
    def mix(self) -> dict:
        if self.rehearse:
            return {**self.cell.mix, **self.cell.mix.get("rehearsal", {})}
        return self.cell.mix

    @property
    def deployment(self) -> dict:
        dep = self.cell.config["deployment"][self.mix["runner"]]
        if self.rehearse:
            dep = {**dep, **self.cell.config["rehearsal"].get(
                self.mix["runner"], {})}
        return dep

    def mark(self, what: str) -> None:
        """Say how far into set-up the run is: where ``setup_s`` goes."""
        say(f"t+{time.perf_counter() - self.t_start:.1f} s: {what}")

    def check(self, name: str, value: float) -> Check:
        if name not in self.cell.limits:
            raise BenchmarkError(
                f"benchmarks/limits/{self.cell.name}.json has no limit "
                f"for {name!r}")
        limits = self.cell.limits
        if self.rehearse:
            # toy widths on the CPU round otherwise than the chip at
            # the real ones: the tests' limits are read there too
            limits = {**limits, **limits.get("rehearsal", {})}
        return Check(name, float(value), float(limits[name]))


@dataclasses.dataclass
class Run:
    """What a per-layer reader is handed."""

    cell: Cell
    cfg: dict
    task: Any             # the module benchmarks/tasks/<task>.py
    mix: dict
    seconds: float
    outcome: Outcome
    trace: Any            # trace_reduce.Reduction or None
    tracer: Tracer
    peak: dict            # the device's published peaks
    spans: Spans


# --- the chip ----------------------------------------------------------------


def enable_caches() -> str:
    """JAX's persistent compilation cache: where the variable says, else
    the program's fixed directory in the checkout; every program kept,
    so that a cell's second run compiles nothing."""
    import jax

    from perceiver_tpu.cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def find_devices(chips: int, rehearse: bool) -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        raise BenchmarkError(
            f"JAX found platform {platform!r}, not a TPU: the benchmark "
            "measures on the chip only")
    if len(devices) < chips:
        raise BenchmarkError(
            f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes on the fullest chip. On the TPU the allocator's
    ``peak_bytes_in_use`` leaves out the loaded programs' temporaries,
    which the runtime holds as ``peak_bytes_reserved`` (PERF.md,
    Findings, PR 24): the peak is their sum."""
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            in_use = int(stats["peak_bytes_in_use"])
            reserved = int(stats.get("peak_bytes_reserved", 0))
            say(f"memory on {d}: peak in use {in_use}, peak reserved "
                f"{reserved}")
            peaks.append(in_use + reserved)
    return max(peaks) if peaks else None


# --- one run -----------------------------------------------------------------


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             rehearse: bool, t_start: float,
             runner: Optional[Callable[[Context], Outcome]] = None,
             device: Optional[dict] = None,
             control: Optional[str] = None) -> dict:
    """Drive one run of ``cell`` and return the result line's object.
    ``runner`` and ``device`` are for the tests, which skip the look
    for a chip and break the timed path underneath."""
    from benchmarks import flops

    if device is None:
        enable_caches()
        device = find_devices(cell.chips, rehearse)
    peak = None if rehearse else flops.peaks(device["kind"])
    os.makedirs(CACHE_DIR, exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="bench-")
    try:
        cfg = flat_config(cell.config, rehearse)
        ctx = Context(
            cell=cell, cfg=cfg, seed=seed,
            seconds=seconds, trace=trace, rehearse=rehearse,
            task=load_task(cfg["task"], cell.root),
            workdir=workdir, spans=Spans(annotate=trace),
            tracer=Tracer(trace, os.path.join(workdir, "trace"),
                          float(cell.mix.get("trace_seconds", 5.0))),
            t_start=t_start, control=control)
        if runner is None:
            runner = load_module(
                os.path.join(cell.root, "benchmarks", "runners",
                             f"{ctx.mix['runner']}.py"),
                f"bench_runner_{ctx.mix['runner']}").run
        outcome = runner(ctx)
        try:
            reduction = ctx.tracer.finish()
        except ValueError:
            if not rehearse:  # off the chip a trace holds no device plane
                raise
            reduction = None
        return _result(ctx, outcome, reduction, device, peak)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _result(ctx: Context, outcome: Outcome, reduction, device: dict,
            peak: Optional[dict]) -> dict:
    cell = ctx.cell
    for c in outcome.checks:
        say(f"check {c.name} = {c.value:.6g} (limit {c.limit:.6g}) "
            f"{'ok' if c.ok else 'FAILED'}")
    correct = bool(outcome.checks) and all(c.ok for c in outcome.checks)
    say(f"attempted {outcome.attempted}, failed {outcome.failed}")
    metrics: Dict[str, dict] = {}
    if not ctx.trace:
        values = dict(outcome.metrics)
        values["setup_s"] = outcome.t_open - ctx.t_start
        for m in cell.metrics("end_to_end"):
            if m["name"] not in values:
                raise BenchmarkError(
                    f"the runner gave no value for {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        run = Run(cell=cell, cfg=ctx.cfg, task=ctx.task, mix=ctx.mix, seconds=ctx.seconds,
                  outcome=outcome, trace=reduction, tracer=ctx.tracer,
                  peak=peak or {}, spans=ctx.spans)
        for m in cell.metrics("per_layer"):
            reader = load_module(
                os.path.join(cell.root, "benchmarks", "layer_metrics",
                             f"{m['name']}.py"),
                "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    device = dict(device)
    device["memory_peak_bytes"] = outcome.memory_peak_bytes
    result = {"correct": correct, "attempted": outcome.attempted,
              "failed": outcome.failed, "metrics": metrics,
              "device": device}
    if ctx.control:
        result["checks"] = {c.name: c.value for c in outcome.checks}
        result["control_checks"] = {
            c.name: c.value
            for c in outcome.data["control_checks"] or []} or None
        result["raw"] = outcome.data.get("raw")
    if reduction is not None:
        from benchmarks import trace_reduce

        device["busy_s"] = reduction.busy_ns / 1e9
        device["window_s"] = reduction.window_ns / 1e9
        result["breakdown"] = trace_reduce.breakdown(reduction)
    if ctx.rehearse or device["platform"] != "tpu":
        # a rehearsal's numbers are not device numbers: the comparisons
        # ran and are shown, nothing else is reported
        result.update(correct=False, metrics={}, rehearsal=True,
                      rehearsal_checks_ok=correct,
                      rehearsal_metrics=sorted(metrics))
        result.pop("breakdown", None)
        device.pop("busy_s", None)
        device.pop("window_s", None)
    return result


def main(argv: Optional[List[str]], t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths on whatever backend JAX has; the "
                         "line never says correct and carries no metric")
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), rehearse=args.rehearse,
                          t_start=t_start)
    except BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0
