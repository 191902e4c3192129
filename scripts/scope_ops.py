#!/usr/bin/env python3
"""Device self time of every operation under one layer scope, by pass,
for a traced run of a benchmark cell::

    python3 scripts/scope_ops.py moe_route --workload sdar_train \
        --seed 7 --seconds 40 --trace 1

(``kda_mixer-kda_rule`` for a scope: the mixer's operations outside its
rule.)

The benchmark removes a run's trace with its work directory; this runs
the same run (``benchmarks/harness.py``, nothing of it changed) and,
before the directory goes, reads the trace's device planes once more:
the operations whose name stack holds the scope, over the **whole steps**
of the trace (as ``hybrid_costs.whole_steps`` counts them: from the
optimizer's marker to its last start), grouped by pass (forward / remat
/ backward), the JAX primitive at the end of the name stack and the HLO
instruction's opcode and result shape. It prints the table in ms a step
and writes it as JSON under ``chiprun_out/``. The run's own result line
is printed as it always is. On the chip only: a rehearsal's trace holds
no device plane.
"""

import time

_T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_NUMBERED = re.compile(r"^[a-z_\-]+(\.[0-9]+)+ ")


def table(planes, scope: str):
    """``(whole steps, rows)``; a row is ``(pass, primitive, instruction,
    calls a step, ms a step)``, dearest first. ``scope`` may be
    ``outer-inner``: the operations under ``outer`` and not under
    ``inner`` (``kda_mixer-kda_rule``: a mixer outside its rule)."""
    from benchmarks import scope_times, trace_reduce

    scope, _, less = scope.partition("-")
    steps, rows = 0, {}
    for plane in planes:
        # a step's marker, as ``hybrid_costs.whole_steps`` finds it: the
        # optimizer's longest operation runs once a step
        marker = {}
        for _, duration, meta in plane.events:
            if "optimizer" in scope_times.names_of(
                    plane.op_names.get(meta, "")):
                marker[meta] = marker.get(meta, 0) + duration
        longest = max(marker, key=marker.get, default=None)
        starts = sorted(s for s, _, m in plane.events if m == longest)
        if len(starts) < 2:
            return 0, []
        steps += len(starts) - 1
        inside = [e for e in plane.events if starts[0] <= e[0] < starts[-1]]
        calls = {}
        for _, _, meta in inside:
            calls[meta] = calls.get(meta, 0) + 1
        for meta, ps in scope_times.self_ps_by_metadata(inside).items():
            stack = plane.op_names.get(meta, "")
            names = scope_times.names_of(stack)
            if scope not in names or less in names:
                continue
            what = trace_reduce.short_name(plane.names.get(meta, "?"), 120)
            what = _NUMBERED.sub("", what)   # fusion.12 f32[8] fusion
            segments = stack.rstrip(":").split("/")
            # a Pallas kernel by its own name, not ``pallas_call``
            primitive = segments[-2] if segments[-2:-1] and \
                segments[-1] == "pallas_call" else segments[-1]
            key = (scope_times.pass_of(stack), primitive, what)
            n, s = rows.get(key, (0, 0.0))
            rows[key] = (n + calls[meta], s + ps / 1e9)
    return steps / len(planes), sorted(
        ((*key, c / steps, ms / steps) for key, (c, ms) in rows.items()),
        key=lambda r: -r[-1])


def main() -> int:
    scope, argv = sys.argv[1], sys.argv[2:]
    from benchmarks import harness, scope_times, trace_reduce

    finish = harness.Tracer.finish

    def finish_and_read(self):
        reduction = finish(self)
        if reduction is not None:
            planes = scope_times.load_device_ops(
                trace_reduce.find_xplane(self.directory))
            steps, rows = table(planes, scope)
            total = sum(r[-1] for r in rows)
            print(f"[scope_ops] {scope}: {total:.3f} ms a step over "
                  f"{steps:g} whole steps", flush=True)
            by_pass = {}
            for which, *_, ms in rows:
                by_pass[which] = by_pass.get(which, 0.0) + ms
            print("[scope_ops] by pass: " + ", ".join(
                f"{k} {v:.3f}" for k, v in sorted(by_pass.items())),
                flush=True)
            for which, prim, what, calls, ms in rows:
                if ms >= 0.02:
                    print(f"[scope_ops] {ms:8.3f} ms x{calls:5.1f} "
                          f"{which:8s} {prim:24s} {what}", flush=True)
            out = os.path.join(ROOT, "chiprun_out")
            os.makedirs(out, exist_ok=True)
            tag = "_".join(a for a in argv if not a.startswith("--"))
            with open(os.path.join(out, f"scope_ops_{scope}_{tag}.json"),
                      "w") as f:
                json.dump({"scope": scope, "argv": argv, "steps": steps,
                           "rows": rows}, f)
        return reduction

    harness.Tracer.finish = finish_and_read
    return harness.main(argv, _T_START)


if __name__ == "__main__":
    sys.exit(main())
