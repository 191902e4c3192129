"""Spread of every metric over the two sets that ``sets.sh`` ran:
``python3 benchmarks/tools/spread.py <cell>`` reads
``chiprun_out/sets/<cell>_set{1,2}_<seed>.log``. A spread is the distance
between the first and third quartile (``statistics.quantiles(n=4)``) over
the median; a bound is about five times the widest."""
import glob
import json
import os
import re
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
cell = sys.argv[1]
sets = {}
checks = {}
for path in sorted(glob.glob(os.path.join(
        ROOT, "chiprun_out", "sets", f"{cell}_set*_*.log"))):
    s = int(re.search(r"_set(\d)_", path).group(1))
    lines = open(path).read().strip().splitlines()
    try:
        r = json.loads(lines[-1])
    except Exception as e:
        print("BAD", path, lines[-1][:200]); continue
    if not r["correct"]: print("NOT CORRECT", path)
    for k, v in r["metrics"].items():
        sets.setdefault(k, {}).setdefault(s, []).append(v["value"])
    for ln in lines:
        m = re.match(r"\[bench\] check (\S+) = (\S+) ", ln)
        if m: checks.setdefault(m.group(1), []).append(float(m.group(2)))
    sets.setdefault("memory_peak_bytes", {}).setdefault(s, []).append(r["device"]["memory_peak_bytes"])
for k, by in sets.items():
    out = []
    for s, vals in sorted(by.items()):
        med = statistics.median(vals)
        q = statistics.quantiles(vals, n=4)
        out.append((s, len(vals), med, (q[2] - q[0]) / med))
    widest = max(o[3] for o in out)
    meds = [o[2] for o in out]
    drift = abs(meds[-1] - meds[0]) / meds[0] if len(meds) > 1 else 0
    print(f"{k:24s}", " ".join(f"set{s}: n={n} median={m:.6g} spread={sp*100:.3f}%" for s, n, m, sp in out), f"| widest {widest*100:.3f}% -> x5 = {widest*500:.2f}% | median drift {drift*100:.3f}%")
    print("    values", {s: [round(v, 4) for v in vals] for s, vals in by.items()})
for k, v in checks.items():
    print(f"check {k:20s} n={len(v)} max={max(v):.6g} min={min(v):.6g}")
