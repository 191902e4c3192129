"""A cell, a configuration, a kind of task, a traffic mix and a layer
metric dropped into a copy of the benchmark as new files (and entries of
BENCHMARK.json) are found and run, with no edit to a file that was
there: the harness's acceptance test."""

import hashlib
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            if f.endswith(".pyc"):
                continue
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_config_task_mix_and_metric_as_files(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(root)
    bench = os.path.join(root, "benchmarks")

    config = harness.load_json(os.path.join(
        bench, "configs", "perceiver_img.json"))
    config["name"] = "perceiver_img_wide"
    # a kind of task the benchmark does not have: its own file
    config["task"] = "img_clf_counted"
    with open(os.path.join(bench, "tasks", "img_clf.py")) as f:
        task_source = f.read()
    with open(os.path.join(bench, "tasks", "img_clf_counted.py"), "w") as f:
        f.write(task_source + """

ROWS_MADE = []
_make_batch = make_batch


def make_batch(rng, rows, cfg):
    ROWS_MADE.append(rows)
    return _make_batch(rng, rows, cfg)


def tokens_per_row(cfg):
    return 7
""")
    config["rehearsal"]["model"].update(image_shape=[6, 10, 3],
                                        num_latents=8)
    with open(os.path.join(bench, "configs",
                           "perceiver_img_wide.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", "img_rows2.json"), "w") as f:
        json.dump({"runner": "train", "batch_rows": 2, "pool_batches": 5,
                   "warmup_steps": 1, "reference_block_rows": 1}, f)
    shutil.copy(os.path.join(bench, "limits", "img_train.json"),
                os.path.join(bench, "limits", "img_wide.json"))
    with open(os.path.join(bench, "layer_metrics",
                           "train.window_steps.py"), "w") as f:
        f.write("def read(run):\n"
                "    return run.outcome.data.get('steps')\n")

    manifest = harness.load_manifest(ROOT)
    manifest["configs"].append({
        "name": "perceiver_img_wide", "source": "a test",
        "file": "benchmarks/configs/perceiver_img_wide.json",
        "reduced": [], "why": "a test"})
    manifest["workloads"].append({
        "name": "img_wide", "config": "perceiver_img_wide",
        "traffic": "img_rows2", "chips": 1, "why": "a test"})
    for m in manifest["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("img_wide")
    manifest["per_layer"].append({
        "name": "train.window_steps", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "trainer",
        "moves": "train_tokens_per_s", "workloads": ["img_wide"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    cell = harness.load_cell("img_wide", root=root)
    assert cell.mix["batch_rows"] == 2
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"train.window_steps", "train.step_ms", "train.mfu_pct",
            "device.idle_pct.train"} == names
    result = harness.run_cell(cell, seed=4_000_000_007, seconds=1.0,
                              trace=True, rehearse=True, t_start=0.0,
                              device=dict(CPU))
    after = digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert result["rehearsal"] and result["rehearsal_checks_ok"]
    assert result["correct"] is False and result["metrics"] == {}
    assert "train.window_steps" in result["rehearsal_metrics"]
    assert "train.step_ms" in result["rehearsal_metrics"]
    assert "busy_s" not in result["device"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    task = sys.modules["bench_task_img_clf_counted"]
    assert task.ROWS_MADE == [2] * 5 and task.tokens_per_row({}) == 7
