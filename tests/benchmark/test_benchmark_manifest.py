"""BENCHMARK.json against the contract's limits and against the files the
harness finds by name."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in MANIFEST["workloads"]]
END_TO_END = {m["name"]: m for m in MANIFEST["end_to_end"]}
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def reporting(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


def test_run_seconds_fits_a_full_check_of_24_cells():
    s = MANIFEST["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("path", MANIFEST["paths"])
def test_paths_exist(path):
    assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", path)
    assert os.path.isdir(os.path.join(ROOT, path))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_names_units_sources(metric):
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric["name"] in END_TO_END:
        assert set(metric) <= allowed | {"bound"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert set(metric) <= allowed | {"layer", "moves"}
        assert 1 <= len(metric["layer"]) <= 200
    assert set(reporting(metric)) <= set(CELLS)


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_moves_names_a_metric_of_the_same_cells(metric):
    target = END_TO_END[metric["moves"]]
    cells = metric.get("workloads")
    if cells is None:
        return  # reported wherever the target is
    assert set(cells) <= set(reporting(target))


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_every_layer_metric_has_its_reader(metric):
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "layer_metrics", metric["name"] + ".py"))


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_entries(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert config["file"].startswith(tuple(
        p + "/" for p in MANIFEST["paths"]))
    assert len(config["reduced"]) <= 16
    assert all(NAME.match(k) for k in config["reduced"])
    for text in (config["source"], config["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    with open(os.path.join(ROOT, config["file"])) as f:
        data = json.load(f)
    # every cut the manifest lists is explained in the file, and no cut
    # is a width
    assert set(config["reduced"]) == set(data["reduced"])
    for key in config["reduced"]:
        assert not key.endswith(("_dim", "_rank", "channels", "latents"))
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell_entries_and_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in MANIFEST["configs"]}
    bench = os.path.join(ROOT, "benchmarks")
    with open(os.path.join(bench, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert os.path.exists(os.path.join(bench, "runners",
                                       mix["runner"] + ".py"))
    assert os.path.exists(os.path.join(bench, "limits",
                                       cell["name"] + ".json"))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_metric_and_a_layer_metric(cell):
    mine = [m["name"] for m in MANIFEST["end_to_end"]
            if cell in reporting(m)]
    assert "setup_s" in mine and len(mine) >= 2
    layer = [m for m in MANIFEST["per_layer"]
             if cell in m.get("workloads", []) or
             ("workloads" not in m and m["moves"] in mine)]
    assert layer


def test_pairs_and_chip_share():
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(pairs) // 4)


def test_harness_picks_each_cells_metrics():
    import sys
    sys.path.insert(0, ROOT)
    from benchmarks import harness

    for name in CELLS:
        cell = harness.load_cell(name)
        e2e = {m["name"] for m in cell.metrics("end_to_end")}
        assert e2e == {m["name"] for m in MANIFEST["end_to_end"]
                       if name in reporting(m)}
        for m in cell.metrics("per_layer"):
            assert m["moves"] in e2e


PENDING = sorted(f[:-5] for f in os.listdir(
    os.path.join(ROOT, "benchmarks", "pending")) if f.endswith(".json"))


@pytest.mark.parametrize("name", PENDING)
def test_a_pending_cell_is_whole_but_for_its_manifest_entries(name):
    """A proven cell that BENCHMARK.json does not list (PERF.md, Open
    questions): its entries are well formed, every file they name is
    there, and the harness runs it from them."""
    import sys
    sys.path.insert(0, ROOT)
    from benchmarks import harness

    assert name not in CELLS
    with open(os.path.join(ROOT, "benchmarks", "pending",
                           name + ".json")) as f:
        pending = json.load(f)
    assert pending["workload"]["name"] == name and pending["why_pending"]
    test_cell_entries_and_files(pending["workload"])
    for m in pending["end_to_end"]:
        assert m["bound"] is None  # set by the PR that lists the cell
        assert set(m) == {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
        assert m["workloads"] == [name]
    cell = harness.load_cell(name)
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert e2e == {m["name"] for m in pending["end_to_end"]} | {"setup_s"}
    layer = cell.metrics("per_layer")
    assert {m["name"] for m in layer} == {
        m["name"] for m in pending["per_layer"]}
    for m in layer:
        assert m["moves"] in e2e and NAME.match(m["name"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
