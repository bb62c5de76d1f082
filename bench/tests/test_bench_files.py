"""BENCHMARK.json and every file it names load by name, and hold to the
benchmark's format.  Adding a configuration, a cell or a per-layer
metric is a new file plus an entry; these tests load each one the way a
run does."""
import json
import pathlib
import re
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_lines():
    entries = (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
               + SPEC["per_layer"])
    names = [e["name"] for e in entries]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        g = [e["name"] for e in SPEC[group]]
        assert len(g) == len(set(g)), group
    assert all(NAME.match(n) for n in names)
    for e in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
    for e in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_metrics_are_wired():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load_by_name(cell):
    files = harness.cell_files(SPEC, cell)
    config, traffic = files["config"], files["traffic"]
    entry = {c["name"]: c for c in SPEC["configs"]}[files["cell"]["config"]]
    assert config["name"] == entry["name"]
    assert config["reduced"] == entry["reduced"]
    assert set(config["reduced"]) <= set(config.get("published", {}))
    gr = config["graph"]
    assert gr["directed_edges"] == 2 * gr["n_edges"] + gr["n_nodes"]
    assert traffic["mode"] in ("full", "mesh")
    if traffic["mode"] == "mesh":
        # one partition a chip: every edge kept, full-graph training
        assert traffic["n_parts"] == files["cell"]["chips"]
        assert traffic["method"] in ("bfs", "random")
    else:
        assert "n_parts" not in traffic and "method" not in traffic
    assert set(files["limits"]) and all(v > 0 for v in
                                        files["limits"].values())
    assert {m["name"] for m in harness.metrics_of(SPEC, cell,
                                                   "end_to_end")} >= {
        "setup_s", "epoch_s"}
    assert harness.metrics_of(SPEC, cell, "per_layer")


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_metric_readers_load_by_name(metric):
    assert callable(harness.load_reader(metric))


def test_every_config_is_used_and_files_are_distinct():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    assert all(f.startswith("bench/") for f in files)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_metric_without_workloads_goes_to_every_cell_of_its_arrow():
    """A per-layer metric that lists no cells is reported in every cell
    that reports the end-to-end metric it moves."""
    spec = json.loads(json.dumps(SPEC))
    spec["per_layer"].append({"name": "later_metric", "moves": "epoch_s"})
    for cell in CELLS:
        names = {m["name"] for m in harness.metrics_of(spec, cell,
                                                       "per_layer")}
        assert "later_metric" in names
