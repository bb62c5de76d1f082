"""The harness's mesh mode across chips, on four virtual CPU devices in a
child process: it drives the plan's own epoch step with the round step
compiled once, its forward agrees with the full-graph reference, each
planted fault reaches the mesh's path, and it refuses a machine with fewer devices than
the cell's chips.  Also the readers that only a mesh gives numbers."""
import json
import os
import pathlib
import subprocess
import sys
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

#: Run in a child with four devices: the papers100M configuration at a
#: test's size over four partitions, sound, with each fault planted, and
#: through ``harness.run``.
CHILD = r"""
import json, sys
import numpy as np
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import calibrate, harness, verdict
from test_bench_run import _small

bench = sys.argv[1]
files = {"cell": {"name": "mesh4", "chips": 4},
         "config": _small(json.load(open(bench + "/configs/sage-papers100m.json"))),
         "traffic": json.load(open(bench + "/traffic/mesh4.json"))}
out = {}
b = harness.build(files, 2**31 + 11)
out["devices"] = b.comp.m
mem = b.exe.memory_analysis()
out["step_bytes"] = mem.temp_size_in_bytes
chips = harness.node_chips(b)
got = harness.first_steps(b)
out["round_compiles"] = b.comp._round_step._cache_size()
ref = harness.reference_run(files, b.graph, b.params0, chips=chips)
out["chip_norms"] = ref["chip_grad_norms"]
out["sound"] = verdict.readings(got, ref, b.params0, 8)
out["halo_edges"] = b.comp.result_extras()["halo_edges"]
weights = lambda g: [float(np.linalg.norm(layer["w"])) for layer in g]
for fault in ("halo_dropped", "half_batch", "dequant_scale"):
    with calibrate.planted(fault):
        bad = harness.first_steps(harness.build(files, 2**31 + 11))
    out[fault] = verdict.readings(bad, ref, b.params0, 8)
    out[fault]["weight_grad_ratio"] = [
        x / y for x, y in zip(weights(bad["grad"]), weights(got["grad"]))]
spec = harness.load_spec()
spec["workloads"].append(dict(files["cell"], config="sage-papers100m"))
spec["configs"].append({"name": "sage-papers100m",
                        "file": "bench/configs/sage-papers100m.json"})
harness.load_spec = lambda: spec
harness.cell_files = lambda s, c: dict(files, limits={"loss1_gap": 1e-5},
                                       faults_caught=[])
r = harness.run("mesh4", 2**31 + 11, 0.2, False, t0=0.0, require_tpu=False,
                log=lambda msg: None)
out["run"] = {k: r[k] for k in ("correct", "attempted", "failed")}
out["run"]["metrics"] = sorted(r["metrics"])
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh4():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", CHILD, str(BENCH), str(ROOT / "src")],
        cwd=BENCH / "tests", env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_mesh_spans_four_devices(mesh4):
    assert mesh4["devices"] == 4 and mesh4["halo_edges"] > 0
    assert mesh4["step_bytes"] > 0
    assert all(len(layer) == 4 for layer in mesh4["chip_norms"])


def test_round_step_compiles_once(mesh4):
    """The compiled program whose memory the run reports is the one the
    epoch step calls."""
    assert mesh4["round_compiles"] == 1


def test_mesh_forward_matches_the_reference(mesh4):
    assert mesh4["sound"]["loss1_gap"] < 1e-5


def test_a_run_drives_the_mesh(mesh4):
    """``harness.run`` end to end on four devices: the window, the
    metrics and the check."""
    assert mesh4["run"]["correct"] and mesh4["run"]["failed"] == 0
    assert mesh4["run"]["attempted"] >= 1
    assert mesh4["run"]["metrics"] == ["epoch_s", "setup_s", "step_hbm_gb"]


def test_dropped_halo_is_caught(mesh4):
    assert mesh4["halo_dropped"]["loss1_gap"] > 1e-3


def test_faults_plant_in_the_mesh(mesh4):
    """Half a batch left out moves the first loss; a dequantize doubled
    doubles the weight gradients (the bias gradients need no decompress)
    and leaves the forward alone."""
    assert mesh4["half_batch"]["loss1_gap"] > 1e-3
    assert mesh4["dequant_scale"]["loss1_gap"] == mesh4["sound"]["loss1_gap"]
    assert mesh4["dequant_scale"]["weight_grad_ratio"] == pytest.approx(
        [2.0] * 3, rel=1e-3)


def test_refuses_fewer_devices_than_chips():
    import jax

    with pytest.raises(harness.NoChip, match="chips"):
        harness.check_devices(jax.device_count() + 1, require_tpu=False)


def test_mesh_holds_one_partition_a_chip():
    files = {"cell": {"chips": 4}, "config": {}, "traffic": {
        "mode": "mesh", "n_parts": 8, "method": "bfs"}}
    with pytest.raises(ValueError, match="one partition a chip"):
        harness.program_objects(files, None)


def test_each_chip_draws_its_own_projection():
    """Each chip's stochastic-rounding seed differs, and the compressor
    seeds its random projection from it: the projections are drawn per
    chip."""
    import jax.numpy as jnp
    from repro.core.compressor import CompressionConfig, compress
    from repro.engine import seeds

    srs = seeds.sr_seed(seeds.batch_ordinals(jnp.asarray(5), 4, 0, 4, 0, 4))
    assert len({int(s) for s in srs}) == 4
    cfg = CompressionConfig(bits=2, group_size=256, rp_ratio=8)
    x = jnp.ones((16, 256), jnp.float32)
    assert len({int(compress(x, cfg, s).rp_seed) for s in srs}) == 4


def _reader(name):
    return harness.load_reader(name)


def test_collective_reader_counts_collectives_by_opcode():
    hlo = "\n".join([
        "HloModule jit_round_step",
        "%fused_a2a (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        "  ROOT %all-to-all.1 = f32[8]{0} all-to-all(%p), dimensions={0}",
        "}",
        "ENTRY %main (x: f32[8]) -> f32[8] {",
        "  %x = f32[8]{0} parameter(0)",
        "  %all-reduce-start.2 = f32[8]{0} all-reduce-start(%x)",
        "  %all-reduce-done.2 = f32[8]{0} all-reduce-done("
        "%all-reduce-start.2)",
        "  %fusion.3 = f32[8]{0} fusion(%all-reduce-done.2), kind=kCustom,"
        " calls=%fused_a2a",
        "  ROOT %add.4 = f32[8]{0} add(%fusion.3, %x)",
        "}",
    ])
    window = types.SimpleNamespace(op_s={
        "all-reduce-start.2": 0.001, "all-reduce-done.2": 0.003,
        "fusion.3": 0.004, "add.4": 0.5})
    ctx = types.SimpleNamespace(hlo_text=hlo, window=window, epochs=2,
                                cell={"chips": 4})
    assert _reader("collective_ms")(ctx) == pytest.approx(1.0)
    ctx.window = types.SimpleNamespace(op_s={"add.4": 0.5})
    assert _reader("collective_ms")(ctx) is None


def test_halo_reader_is_per_chip():
    ctx = types.SimpleNamespace(cell={"chips": 4},
                                extras={"halo_bytes_per_epoch": 8e9})
    assert _reader("halo_mb")(ctx) == 2000.0
    ctx.extras = {}
    assert _reader("halo_mb")(ctx) is None


def test_scope_milliseconds_are_per_chip():
    import scopes

    ctx = types.SimpleNamespace(epochs=2, cell={"chips": 4},
                                _scope_split={"relu_mask": 0.008})
    assert scopes.ms_per_epoch(ctx, "relu_mask") == pytest.approx(1.0)
    ctx.cell = {"chips": 1}
    assert scopes.ms_per_epoch(ctx, "relu_mask") == pytest.approx(4.0)
