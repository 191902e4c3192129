#!/usr/bin/env python3
"""Two probes on the chip that PERF.md's sizing rests on; neither is
part of a benchmark run.

``python3 benchmarks/tools/probe.py memory``
    What ``device.memory_stats()`` counts on this chip: a buffer of
    known size, then a jitted program whose temporaries the compiler
    reports (``memory_analysis().temp_size_in_bytes``), printing
    ``bytes_in_use`` / ``peak_bytes_in_use`` / ``bytes_reserved`` /
    ``peak_bytes_reserved`` after each. It shows whether the peak in use
    holds a program's temporaries and whether the reserved bytes overlap
    it: the basis of ``harness.memory_peak_bytes``.

``python3 benchmarks/tools/probe.py cell --workload lm_decode --seed 5
--seconds 20 [--deployment max_streams=32 num_pages=4097] [--mix
clients=32 n_sizes=64]``
    One run of a cell with some keys of its deployment or mix
    overridden in memory (no file changes), for sizing a deployment: it
    prints the result line with both memory peaks apart. One process
    per variant: the peaks are the process's. PERF.md's sizing study of
    the decode cell (Findings, PR 24) is four such calls, seed 41:
    ``--seconds 20 --deployment num_pages=1025``; ``... num_pages=4097``;
    ``--seconds 25 --deployment max_streams=32 num_pages=4097 --mix
    clients=32``; ``--seconds 30 --deployment max_streams=48
    num_pages=6145 --mix clients=48 n_sizes=96``.
"""

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
        "peak_bytes_reserved", "bytes_limit")


def stats(what: str) -> dict:
    import jax

    s = jax.local_devices()[0].memory_stats() or {}
    row = {k: int(s[k]) for k in KEYS if k in s}
    print(f"{what:44s} " + " ".join(
        f"{k}={v / 2**20:9.1f}MiB" for k, v in row.items()), flush=True)
    return row


def memory() -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks import harness

    harness.find_devices(1, rehearse=False)
    base = stats("start")
    n = 16384
    x = jax.block_until_ready(jnp.full((n, n), 1e-3, jnp.float32))  # 1 GiB
    one = stats("after a 1 GiB buffer")

    def f(a):
        # two 1 GiB temporaries that must be written out: each is a
        # product that feeds a later product (and b two of them)
        b = a @ a
        c = b @ b
        return c @ b

    compiled = jax.jit(f).lower(x).compile()
    analysis = compiled.memory_analysis()
    temp = int(analysis.temp_size_in_bytes)
    print(f"compiler: arguments {analysis.argument_size_in_bytes}, output "
          f"{analysis.output_size_in_bytes}, temporaries {temp}")
    loaded = stats("program loaded, not run")
    y = jax.block_until_ready(compiled(x))
    ran = stats(f"program run (compiler: temp {temp / 2**20:.1f}MiB)")
    del x, y
    freed = stats("buffer deleted")
    del compiled
    stats("program deleted")
    print(json.dumps({
        "buffer_bytes": n * n * 4, "compiler_temp_bytes": temp,
        "in_use_rise_by_buffer": one["bytes_in_use"] - base["bytes_in_use"],
        "peak_in_use_rise_by_run":
            ran["peak_bytes_in_use"] - one["peak_bytes_in_use"],
        "reserved_rise_by_load":
            loaded.get("bytes_reserved", 0) - one.get("bytes_reserved", 0),
        "peak_reserved_rise_by_run":
            ran.get("peak_bytes_reserved", 0)
            - one.get("peak_bytes_reserved", 0),
        "in_use_after_delete": freed["bytes_in_use"]}))
    return 0


def _value(text: str):
    try:
        return json.loads(text)
    except ValueError:
        return text


def cell(args) -> int:
    from benchmarks import harness

    c = harness.load_cell(args.workload)
    runner = c.mix["runner"]
    for pair in args.deployment:
        k, v = pair.split("=", 1)
        c.config["deployment"][runner][k] = _value(v)
    for pair in args.mix:
        k, v = pair.split("=", 1)
        c.mix[k] = _value(v)
    try:
        result = harness.run_cell(c, seed=args.seed, seconds=args.seconds,
                                  trace=bool(args.trace), rehearse=False,
                                  t_start=_T_START)
    except harness.BenchmarkError as e:
        print(f"probe: {e}", file=sys.stderr)
        return 2
    stats("at exit")
    result["overrides"] = {"deployment": args.deployment, "mix": args.mix}
    print(json.dumps(result), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    sub.add_parser("memory")
    p = sub.add_parser("cell")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--deployment", nargs="*", default=[])
    p.add_argument("--mix", nargs="*", default=[])
    args = ap.parse_args()
    return memory() if args.what == "memory" else cell(args)


if __name__ == "__main__":
    sys.exit(main())
