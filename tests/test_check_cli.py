"""``scripts/check.py --all`` as the literal subprocess: the merge gate
on this tree. A file of its own: it is the longest case of the suite,
and under ``--dist loadfile`` a file is one worker's
(``tests/test_graphcheck.py`` has the passes' own cases)."""

import os
import re
import subprocess
import sys

from conftest import RUNS_ONCE

from perceiver_tpu.analysis import CANONICAL_TARGETS


def test_check_cli_all_exits_zero():
    """``scripts/check.py --all`` — the literal merge gate, as the
    literal subprocess CI runs — exits 0 on this tree. Tier-1 (not
    slow-marked): graphcheck + hbm_budget only gate merges if the
    fast suite actually runs them. Also pins the check roster: the
    sharded targets must be in the default sweep and the three
    shardcheck passes must have actually run (a gate that silently
    stops running is worse than none)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "check.py"),
         "--all"],
        # the child lowers and compiles and runs nothing: it is given the
        # two LLVM-level options for its whole process (conftest.RUNS_ONCE
        # says why the tests' own processes are not)
        env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=" ".join(
            [os.environ["XLA_FLAGS"]] + [
                f"--{name}={str(value).lower()}"
                for name, value in RUNS_ONCE.items()])),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"\n{r.stdout}\n{r.stderr}"
    m = re.search(r"from (\d+) check\(s\): (.*)", r.stdout)
    assert m, r.stdout
    n_checks, roster = int(m.group(1)), m.group(2)
    assert n_checks >= 23, r.stdout
    for shard_pass in ("collective_budget", "replication_check",
                       "per_shard_hbm_budget", "unsharded-pjit",
                       "guarded-attrs", "lock-order",
                       "callback-under-lock", "blocking-under-lock",
                       "kv-alias"):
        assert shard_pass in roster, r.stdout
    m = re.search(r"lowering (\d+) canonical target", r.stderr)
    assert m and int(m.group(1)) == len(CANONICAL_TARGETS), r.stderr
