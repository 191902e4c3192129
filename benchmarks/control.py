#!/usr/bin/env python3
"""Read the two numbers every limit is set from, on the chip, at the
cell's own size: what sound runs of the program give, and what the
control gives: the reference computed in the nearest precision below the
configuration's (fp8 operands for bfloat16) and put in the program's
place. The benchmark's own runs never run the control.

``python3 benchmarks/control.py --workload <cell> --seeds 1 2 3
--seconds 8``: one short run per seed in one process; the table of both
readings is printed and written to ``chiprun_out/control_<cell>.json``,
what they were made from to ``control_<cell>_raw.json``.
"""

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from benchmarks import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--precision", default="fp8")
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="run the control on the first N seeds only; "
                         "the rest read the program alone")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    with_control = len(args.seeds) if args.control_seeds is None \
        else args.control_seeds
    rows, raws = [], []
    for i, seed in enumerate(args.seeds):
        result = harness.run_cell(
            cell, seed=seed, seconds=args.seconds, trace=False,
            rehearse=args.rehearse, t_start=time.perf_counter(),
            control=args.precision if i < with_control else "none")
        rows.append({"seed": seed, "program": result["checks"],
                     "control": result["control_checks"]})
        raws.append({"seed": seed, **(result.get("raw") or {})})
        print(json.dumps(rows[-1]), flush=True)
    controls = [r["control"] for r in rows if r["control"]]
    print(f"{'number':24s} {'program max':>14s} {'control min':>14s} "
          f"{'limit':>10s}")
    for n in sorted(controls[0]) if controls else []:
        print(f"{n:24s} {max(r['program'][n] for r in rows):14.6g} "
              f"{min(c[n] for c in controls):14.6g} "
              f"{cell.limits[n]:10.4g}")
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    stem = f"control_{cell.name}" + ("_rehearsal" if args.rehearse else "")
    with open(os.path.join(out, f"{stem}.json"), "w") as f:
        json.dump({"precision": args.precision, "rows": rows}, f, indent=1)
    # what the numbers were made from (norms by leaf, losses), so that
    # another statistic can be tried on the same readings off the chip
    with open(os.path.join(out, f"{stem}_raw.json"), "w") as f:
        json.dump(raws, f, default=lambda a: [float(x) for x in a])
    return 0


if __name__ == "__main__":
    sys.exit(main())
