"""The command line on the CPU at toy width: the last line never carries
a device metric or ``correct: true`` off the TPU, and without
``--rehearse`` there is no line at all. The decode runner goes through
the command here; the train runner's rehearsal line is read in
``test_benchmark_control.py``, which drives the same ``run_cell``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run(args, cwd=ROOT, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("cell,seconds", [("lm_decode", "6")])
def test_rehearsal_line(cell, seconds):
    p = run(["--workload", cell, "--seed", "3000000011", "--seconds",
             seconds, "--trace", "0", "--rehearse"])
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is False and result["metrics"] == {}
    assert result["rehearsal"] is True
    assert result["rehearsal_checks_ok"] is True, "\n".join(lines[-15:])
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["memory_peak_bytes"] is None
    assert result["attempted"] > 0 and result["failed"] == 0
    # every number compared is printed beside its limit
    checks = [ln for ln in lines if ln.startswith("[bench] check ")]
    assert len(checks) >= 3 and all("(limit " in ln for ln in checks)
    for key in ("BENCH_RUN",):
        assert key not in p.stdout


def test_off_the_chip_without_rehearse_there_is_no_result():
    p = run(["--workload", "lm_train", "--seed", "1", "--seconds", "1",
             "--trace", "0"], env_extra={"BENCH_RUN": "x"})
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "not a TPU" in p.stderr


def test_unknown_cell_is_refused():
    p = run(["--workload", "no_such_cell", "--seed", "1", "--seconds",
             "1", "--trace", "0", "--rehearse"])
    assert p.returncode != 0 and "{" not in p.stdout


def test_alone_in_a_directory_the_benchmark_fails(tmp_path):
    """With only BENCHMARK.json and the files under ``paths`` there is
    no program to measure: no result, a non-zero code."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "lm_train",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=root, env={**env, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
