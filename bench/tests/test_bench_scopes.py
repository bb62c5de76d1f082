"""The program's layer scopes and the benchmark's reading of them, on the
CPU: ``scope_of`` under JAX's transform prefixes, ``bench/scopes.py``'s
inheritance rule on a hand-made HLO module, the scopes a small compiled
step carries, and the readers on the recorded chip traces (three epochs
of ``sage-arxiv.int2-rp8`` on a TPU v5e: the step before it opened any
scope, and the scoped step)."""
import gzip
import json
import pathlib
import re
import sys
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import peaks  # noqa: E402
import scopes  # noqa: E402
import tracecut  # noqa: E402
from repro.obs.scopes import SCOPES, scope_of  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "sage-arxiv.int2-rp8"
NEW = ("agg_fwd_ms", "agg_bwd_ms", "compress_ms", "mask_ms")
OLD = ("device_idle", "step_mfu", "agg_ms", "quant_roofline", "stash_mb")


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/jvp(agg_fwd)/gather", "agg_fwd"),
    ("jit(step)/transpose(jvp(agg_bwd))/scatter-add", "agg_bwd"),
    ("jit(step)/jvp(relu_mask)/shift_left", "relu_mask"),
    ("jit(step)/transpose(jvp(relu_mask))/mul", "relu_mask"),
    ("jit(step)/jvp(dense)/act_compress/quant_pack/pallas_call",
     "act_compress"),
    ("jit(step)/transpose(jvp(dense))/act_decompress/dot_general",
     "act_decompress"),
    ("jit(step)/jvp(dense)/dot_general", "dense"),
    ("jit(act_decompress)/mul", "act_decompress"),
    ("jit(step)/jvp(jit(take_along_axis))/gather", None),
    ("jit(step)/jvp()/scatter-add", None),
    ("jit(step)/agg_fwd_extra/add", None),
    ("scatter-add", None),
    ("", None),
    (None, None),
])
def test_scope_of(op_name, scope):
    assert scope_of(op_name) == scope


HLO = """HloModule jit_step, entry_computation_layout={()}

%fused_mixed (p0: f32[8,4], p1: f32[8,4]) -> f32[8,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = f32[8,4]{1,0} parameter(1)
  %b.1 = f32[8,4]{1,0} broadcast(%p1), dimensions={}, metadata={op_name="jit(step)/jvp(agg_fwd)/mul"}
  ROOT %m.1 = f32[8,4]{1,0} multiply(%p0, %b.1), metadata={op_name="jit(step)/transpose(jvp(agg_bwd))/mul" stack_frame_id=3}
}

%fused_tuple (p0.2: f32[8,4]) -> (f32[8,4], f32[8,4]) {
  %p0.2 = f32[8,4]{1,0} parameter(0)
  %n.1 = f32[8,4]{1,0} negate(%p0.2), metadata={op_name="jit(step)/jvp(dense)/neg"}
  ROOT %t.1 = (f32[8,4]{1,0}, f32[8,4]{1,0}) tuple(%n.1, %n.1)
}

%body (s: (u32[], u32[64])) -> (u32[], u32[64]) {
  %s = (u32[], u32[64]{0}) parameter(0)
  %i = u32[] get-tuple-element(%s), index=0
  %v = u32[64]{0} get-tuple-element(%s), index=1
  %dus.1 = u32[64]{0} dynamic-update-slice(%v, %v, %i)
  %own.1 = u32[] add(%i, %i), metadata={op_name="jit(step)/jvp(agg_fwd)/add"}
  ROOT %tuple.b = (u32[], u32[64]{0}) tuple(%own.1, %dus.1)
}

%cond (s.1: (u32[], u32[64])) -> pred[] {
  %s.1 = (u32[], u32[64]{0}) parameter(0)
  %i.1 = u32[] get-tuple-element(%s.1), index=0
  ROOT %lt.1 = pred[] compare(%i.1, %i.1), direction=LT
}

ENTRY %main (x: f32[8,4], y: f32[8,4], w: u32[64]) -> f32[8,4] {
  %x = f32[8,4]{1,0} parameter(0)
  %y = f32[8,4]{1,0} parameter(1)
  %w = u32[64]{0} parameter(2)
  %c.1 = u32[] constant(0)
  %init.1 = (u32[], u32[64]{0}) tuple(%c.1, %w)
  %while.1 = (u32[], u32[64]{0}) while(%init.1), condition=%cond, body=%body
  %gte.1 = u32[64]{0} get-tuple-element(%while.1), index=1, metadata={op_name="jit(step)/jvp(relu_mask)/convert_element_type"}
  %fusion.1 = f32[8,4]{1,0} fusion(%x, %y), kind=kLoop, calls=%fused_mixed, metadata={op_name="jit(step)/transpose(jvp(agg_bwd))/mul"}
  %fusion.2 = (f32[8,4]{1,0}, f32[8,4]{1,0}) fusion(%x), kind=kLoop, calls=%fused_tuple, metadata={op_name="jit(step)/jvp(dense)/neg"}
  %relay.1 = f32[8,4]{1,0} copy(%fusion.1)
  %add.9 = f32[8,4]{1,0} add(%relay.1, %x), metadata={op_name="jit(step)/transpose(jvp(agg_bwd))/add"}
  %two.1 = f32[8,4]{1,0} copy(%x)
  %u.1 = f32[8,4]{1,0} add(%two.1, %x), metadata={op_name="jit(step)/jvp(agg_fwd)/add"}
  %u.2 = f32[8,4]{1,0} add(%two.1, %y), metadata={op_name="jit(step)/jvp(relu_mask)/add"}
  %plain.1 = f32[8,4]{1,0} multiply(%x, %y), metadata={op_name="jit(step)/mul"}
  ROOT %out.1 = (f32[8,4]{1,0}, f32[8,4]{1,0}) tuple(%add.9, %u.1, %u.2, %plain.1, %gte.1, %fusion.2)
}
"""


def test_inheritance_rule():
    sc = scopes.Scopes(HLO)
    want = {
        # a while with no op_name takes its get-tuple-element user's
        "while.1": "relu_mask",
        # its body's and condition's ops without one take the while's
        "dus.1": "relu_mask", "tuple.b": "relu_mask", "lt.1": "relu_mask",
        # a body op with an op_name keeps its own
        "own.1": "agg_fwd",
        # the while's operand tuple: from its single user, the while
        "init.1": "relu_mask",
        # a fusion: its root's op_name, not its fused broadcast's
        "fusion.1": "agg_bwd",
        # a fusion whose root (a tuple) has none: the fusion's own
        "fusion.2": "dense",
        # no op_name, one user: the user's; two users: unscoped
        "relay.1": "agg_bwd", "two.1": None,
        # an op_name in no scope: unscoped
        "plain.1": None,
    }
    assert {n: sc.scope(n) for n in want} == want
    assert sc.mixed("fusion.1") and not sc.mixed("fusion.2")
    assert not sc.mixed("while.1")


def _window(op_s, busy_s):
    return types.SimpleNamespace(op_s=op_s, busy_s=busy_s)


def test_split_and_readers_on_hand_made_window(capsys):
    op_s = {"while.1": 1.0, "dus.1": 0.5, "fusion.1": 2.0, "plain.1": 0.25,
            "fusion.2": 0.5, "not-in-hlo.3": 0.125}
    ctx = types.SimpleNamespace(window=_window(op_s, 4.375), epochs=2,
                                hlo_text=HLO)
    got = scopes.split(ctx)
    assert got == {"agg_fwd": 0.0, "agg_bwd": 2.0, "act_compress": 0.0,
                   "act_decompress": 0.0, "relu_mask": 1.5, "dense": 0.5,
                   None: 0.375, "mixed": 2.0}
    log = capsys.readouterr().err
    assert "unscoped_share=8.571%" in log
    assert "mixed_fusion_share=45.714%" in log
    read = {m: harness.load_reader(m)(ctx) for m in NEW}
    assert read == {"agg_fwd_ms": None, "agg_bwd_ms": 1000.0,
                    "compress_ms": None, "mask_ms": 750.0}


@pytest.fixture(scope="module")
def parent_fixture():
    rec = json.load(gzip.open(DATA / f"trace_{CELL}.json.gz", "rt"))
    hlo = gzip.open(DATA / f"hlo_{CELL}.txt.gz", "rt").read()
    return rec, hlo


@pytest.fixture(scope="module")
def scoped_fixture():
    rec = json.load(gzip.open(DATA / f"trace_{CELL}.scoped.json.gz", "rt"))
    hlo = gzip.open(DATA / f"hlo_{CELL}.scoped.txt.gz", "rt").read()
    return rec, hlo


def _ctx(rec, hlo, **extra):
    import counts
    import graphgen

    lo, hi = rec["window"]
    files = harness.cell_files(SPEC, CELL)
    win = tracecut.reduce_window(rec, lo, hi, tracecut.module_name(hlo))
    return types.SimpleNamespace(
        cell=files["cell"], window=win, epochs=rec["epochs"],
        wall_s=win.window_s, peaks=peaks.chip_peaks("TPU v5 lite"),
        ops=tracecut.hlo_ops(hlo, files["config"]["graph"]["directed_edges"]),
        model_flops=counts.model_flops(
            files["config"]["graph"]["n_nodes"],
            graphgen.layer_dims(files["config"])), **extra)


def test_program_without_scopes_reports_none(parent_fixture):
    ctx = _ctx(*parent_fixture, hlo_text=parent_fixture[1])
    assert scopes.split(ctx) is None
    assert {m: harness.load_reader(m)(ctx) for m in NEW} == dict.fromkeys(NEW)


def _tiny_graph_objects():
    """A small Graph and the cell's model, for ``stash_mb``'s ledger."""
    from repro.graph import GNNConfig, cora_like

    g = cora_like(scale=0.05, seed=0)
    return g, GNNConfig(arch="sage", hidden=(16,), n_classes=g.num_classes)


def test_old_readers_read_the_same_with_hlo_text(parent_fixture):
    rec, hlo = parent_fixture
    g, cfg = _tiny_graph_objects()
    bare = _ctx(rec, hlo, graph=g, gnn_cfg=cfg)
    given = _ctx(rec, hlo, graph=g, gnn_cfg=cfg, hlo_text=hlo)
    a = {m: harness.load_reader(m)(bare) for m in OLD}
    b = {m: harness.load_reader(m)(given) for m in OLD}
    assert a == b
    assert all(v is not None for v in a.values())


def _through_harness(rec, hlo, monkeypatch, tmp_path):
    """The per-layer metrics as ``harness._per_layer`` reads them from a
    recorded trace (the profiler's file replaced by the record)."""
    trace = tmp_path / "plugins" / "profile" / "run"
    trace.mkdir(parents=True)
    (trace / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(tracecut, "load_xplane", lambda path: rec)
    spec = dict(SPEC, per_layer=[m for m in SPEC["per_layer"]
                                 if m["name"] != "stash_mb"])
    files = harness.cell_files(SPEC, CELL)
    dev = types.SimpleNamespace(device_kind="TPU v5 lite")
    lo, hi = rec["window"]
    out = harness._per_layer(spec, files, CELL, str(tmp_path), hlo,
                             rec["epochs"], (hi - lo) * 1e-9, dev, None,
                             None, {}, {}, lambda msg: None, "[test]")
    return {k: v["value"] for k, v in out["metrics"].items()}


def test_harness_reads_old_metrics_alone_without_scopes(
        parent_fixture, monkeypatch, tmp_path):
    rec, hlo = parent_fixture
    got = _through_harness(rec, hlo, monkeypatch, tmp_path)
    ctx = _ctx(rec, hlo)
    assert got == {m: harness.load_reader(m)(ctx) for m in OLD
                   if m != "stash_mb"}


def test_harness_reads_the_scopes(scoped_fixture, monkeypatch, tmp_path):
    """Through the harness as it stands, which gives the readers the
    compiled step's ``hlo_text``."""
    rec, hlo = scoped_fixture
    got = _through_harness(rec, hlo, monkeypatch, tmp_path)
    ctx = _ctx(rec, hlo, hlo_text=hlo)
    assert set(got) == set(OLD) - {"stash_mb"} | set(NEW)
    assert got == {m: harness.load_reader(m)(ctx) for m in got}


def test_scoped_trace_splits_by_layer(scoped_fixture, capsys):
    rec, hlo = scoped_fixture
    ctx = _ctx(rec, hlo, hlo_text=hlo)
    got = scopes.split(ctx)
    busy = ctx.window.busy_s
    assert sum(v for k, v in got.items() if k != "mixed") == pytest.approx(
        sum(ctx.window.op_s.values()), rel=1e-12)
    assert got[None] < 0.02 * busy
    agg = harness.load_reader("agg_ms")(ctx)
    fwd = harness.load_reader("agg_fwd_ms")(ctx)
    bwd = harness.load_reader("agg_bwd_ms")(ctx)
    assert fwd + bwd == pytest.approx(agg, rel=0.05)
    assert fwd > bwd > 0
    assert harness.load_reader("compress_ms")(ctx) > 0
    assert harness.load_reader("mask_ms")(ctx) > 0
    assert "unscoped_share=" in capsys.readouterr().err


def _kernels(hlo):
    ops = tracecut.hlo_ops(hlo, 2_501_829)
    return sorted((o.kernel, o.bytes) for o in ops.values()
                  if o.kind == "kernel")


def test_scoped_step_has_the_same_kernels(parent_fixture, scoped_fixture):
    """Every Pallas call falls to the same ``KERNEL_KINDS`` kind, with the
    same bytes, under its ``name=`` as under its function's name."""
    old, new = _kernels(parent_fixture[1]), _kernels(scoped_fixture[1])
    assert old == new
    assert {k for k, _ in new} == {"quant", "dequant"}


_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")


def _ops(hlo: str) -> list[str]:
    """The module's instructions with ``metadata={...}`` and the Mosaic
    kernel bodies (which embed source lines) taken out, and each
    instruction named by its place in the module."""
    lines, names = [], {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        names[m.group(1)] = f"i{len(names)}"
        lines.append(m.group(2))
    ref = re.compile(r"%([\w.\-]+)")
    out = []
    for text in lines:
        text = re.sub(r', metadata=\{[^}]*\}', "", text)
        text = re.sub(r'"body":"[^"]*"', '"body":""', text)
        out.append(ref.sub(lambda m: "%" + names.get(m.group(1), m.group(1)),
                           text))
    return out


def test_scoped_step_is_the_same_program(parent_fixture, scoped_fixture):
    """The scopes and kernel names change metadata only: op for op the
    compiled step is the one recorded before them."""
    old, new = _ops(parent_fixture[1]), _ops(scoped_fixture[1])
    assert len(old) == len(new)
    assert old == new


def _small(config):
    config = json.loads(json.dumps(config))
    gr = config["graph"]
    gr["n_edges"] = gr["n_edges"] * 600 // gr["n_nodes"]
    gr["n_nodes"] = 600
    gr["directed_edges"] = 2 * gr["n_edges"] + 600
    return config


@pytest.mark.parametrize("cell,want", [
    ("sage-arxiv.int2-rp8", set(SCOPES)),
    ("sage-arxiv.fp32", set(SCOPES) - {"act_compress", "act_decompress"}),
])
def test_compiled_step_carries_the_scopes(cell, want):
    files = harness.cell_files(SPEC, cell)
    files["config"] = _small(files["config"])
    text = harness.build(files, 7).exe.as_text()
    found = {scope_of(i.op_name) for i in scopes.parse(text).values()}
    assert found - {None} == want
