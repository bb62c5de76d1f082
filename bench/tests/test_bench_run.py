"""The command end to end on the CPU: it refuses a machine without a TPU;
and, with the look for a chip skipped and the graph made small, a run
comes out correct, while its control and each planted fault of the
timed path come out not correct under the cells' limits."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
#: Small enough for a test run; the widths stay the configuration's.
NODES = 1500


def _small(config):
    config = json.loads(json.dumps(config))
    gr = config["graph"]
    gr["n_edges"] = gr["n_edges"] * NODES // gr["n_nodes"]
    gr["n_nodes"] = NODES
    gr["directed_edges"] = 2 * gr["n_edges"] + NODES
    return config


def _run(cell, fault=None, seed=2**31 + 11):
    """A short run of ``cell`` at a test's size, with ``fault`` (a key
    of :data:`FAULTS`) planted; a cell of several chips runs in a child
    with that many virtual CPU devices."""
    chips = harness.cell_files(SPEC, cell)["cell"]["chips"]
    if chips > 1:
        return _run_in_child(cell, fault, seed, chips)
    return _run_here(cell, fault, seed)


def _run_here(cell, fault, seed):
    """:func:`_run` in this process, on its devices."""
    with pytest.MonkeyPatch.context() as monkeypatch:
        if fault is not None:
            FAULTS[fault](monkeypatch)
        return harness.run(cell, seed, 0.2, False, t0=time.perf_counter(),
                           require_tpu=False, config_override=_small,
                           log=lambda msg: None)


#: The child of :func:`_run_in_child`: argv is the tests' directory,
#: ``src``, the cell, the fault ("" for none) and the seed.
CHILD = r"""
import json, sys
sys.path[:0] = sys.argv[1:3]
import test_bench_run
print(json.dumps(test_bench_run._run_here(sys.argv[3], sys.argv[4] or None,
                                          int(sys.argv[5]))))
"""


def _run_in_child(cell, fault, seed, devices):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    out = subprocess.run(
        [sys.executable, "-c", CHILD, str(BENCH / "tests"),
         str(ROOT / "src"), cell, fault or "", str(seed)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _command(cwd, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_machine_without_a_tpu(tmp_path):
    out = _command(ROOT, tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_refuses_without_the_program(tmp_path):
    (tmp_path / "co").mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "co")
    shutil.copytree(BENCH, tmp_path / "co" / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(tmp_path / "co", tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_sound_run_is_correct():
    r = _run("sage-arxiv.fp32")
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"epoch_s", "step_hbm_gb", "setup_s"}


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    """The plain reference in bfloat16, in the program's place."""
    import graphgen
    import verdict

    files = harness.cell_files(SPEC, cell)
    files["config"] = _small(files["config"])
    graph = graphgen.graph_from_config(files["config"], 12345)
    params0 = harness._host(graphgen.weights_from_config(files["config"],
                                                         12345))
    ref = harness.reference_run(files, graph, params0)
    ctl = harness.reference_run(files, graph, params0, control=True)
    ok, checks = verdict.judge(
        verdict.readings(ctl, ref, params0,
                         files["traffic"]["rp_ratio"] or 0),
        files["limits"])
    assert not ok, checks


def _state_unchanged(monkeypatch):
    from repro.engine import compile as engine_compile

    monkeypatch.setattr(engine_compile, "adamw_update",
                        lambda grads, state, params, opt: (params, state))


def _planted(name):
    def plant(monkeypatch):
        import calibrate

        for mod, attr, value in list(calibrate.plants(name)):
            monkeypatch.setattr(mod, attr, value)
    return plant


FAULTS = {"state_unchanged": _state_unchanged,
          "half_batch": _planted("half_batch"),
          "dequant_scale": _planted("dequant_scale"),
          "halo_dropped": _planted("halo_dropped")}
#: (cell, fault) for every fault a cell's file says its limits catch.
CAUGHT = [(cell, fault) for cell in CELLS
          for fault in harness.cell_files(SPEC, cell)["faults_caught"]]


def test_every_cell_catches_a_frozen_state():
    assert {c for c, f in CAUGHT if f == "state_unchanged"} == set(CELLS)


def test_every_cell_catches_half_a_batch():
    assert {c for c, f in CAUGHT if f == "half_batch"} == set(CELLS)


@pytest.mark.parametrize("cell", [c for c in CELLS if harness.cell_files(
    SPEC, c)["traffic"]["rp_ratio"]])
def test_sound_compressed_run_is_correct(cell):
    """RP noise at a test's size stays inside the cell's limits, and the
    cell catches a dequantize off by a constant factor."""
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert (cell, "dequant_scale") in CAUGHT


@pytest.mark.parametrize("cell,fault", CAUGHT,
                         ids=[f"{c}-{f}" for c, f in CAUGHT])
def test_planted_fault_is_not_correct(cell, fault):
    r = _run(cell, fault)
    assert not r["correct"], r["checks"]


def test_a_child_run_plants_its_fault():
    """The route a cell of several chips takes: the run, and its planted
    fault, in a child process with four virtual devices."""
    cell = "sage-arxiv.fp32"
    assert _run_in_child(cell, None, 2**31 + 11, 4)["correct"]
    assert not _run_in_child(cell, "half_batch", 2**31 + 11, 4)["correct"]
