"""The trace reduction on hand-made intervals, a hand-made HLO module and
a short trace recorded on a TPU v5e (three epochs of
``sage-arxiv.int2-rp8``).  Nothing here loads a TPU library."""
import base64
import gzip
import json
import pathlib
import subprocess
import sys
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import peaks  # noqa: E402
import tracecut  # noqa: E402


def test_import_loads_no_jax():
    code = ("import sys; sys.path.insert(0, %r); import tracecut, counts, "
            "peaks, harness; print('jax' in sys.modules)" % str(BENCH))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True)
    assert out.stdout.strip() == "False"


def test_union_and_gaps():
    iv = [(10, 20), (15, 30), (40, 50), (45, 46), (70, 90)]
    assert tracecut.union_ns(iv, 0, 100) == 10 + 10 + 10 + 20
    assert tracecut.union_ns(iv, 18, 80) == 12 + 10 + 10
    assert tracecut.idle_gaps(iv, 0, 100) == [(0, 10), (30, 40), (50, 70),
                                              (90, 100)]


def test_self_time_of_nested_ops():
    # a loop (0-100) holding two body ops, then a lone op
    evs = [["while", 0, 100], ["a", 10, 20], ["b", 50, 30], ["c", 120, 5]]
    assert tracecut.self_ns(evs) == [50, 20, 30, 5]


def _body(name: str) -> str:
    return base64.b64encode(b"\x00MLIR" + name.encode() + b"\x01").decode()


HLO = f"""HloModule jit_step, entry_computation_layout={{()}}

%fused_computation.1 (p0: f32[8,4], p1: s32[64]) -> f32[8,4] {{
  %p0 = f32[8,4]{{1,0}} parameter(0)
  ROOT %scatter.1 = f32[8,4]{{1,0}} scatter(%p0, %p1, %p0), to_apply=%add
}}

ENTRY %main.1 (x: f32[8,4], e: s32[64]) -> f32[8,4] {{
  %x = f32[8,4]{{1,0}} parameter(0)
  %e = s32[64]{{0}} parameter(1)
  %fusion.1 = f32[8,4]{{1,0}} fusion(%x, %e), kind=kCustom, calls=%fused_computation.1
  %sort.1 = (s32[64]{{0}}, s32[64]{{0}}) sort(%e, %e), dimensions={{0}}, to_apply=%lt
  %dot.1 = f32[8,8]{{1,0}} dot(%x, %x), lhs_contracting_dims={{1}}
  %jvp__.3 = (u32[2,4]{{1,0}}, f32[2,1]{{1,0}}, f32[2,1]{{1,0}}) custom-call(%s, %x), custom_call_target="tpu_custom_call", operand_layout_constraints={{u32[1,1]{{1,0}}, f32[2,16]{{1,0}}}}, backend_config={{"custom_call_config":{{"body":"{_body('_quant_pack_kernel')}"}}}}
  %t.5 = f32[2,16]{{1,0}} custom-call(%a, %b, %c), custom_call_target="tpu_custom_call", operand_layout_constraints={{u32[2,4]{{1,0}}, f32[2,1]{{1,0}}, f32[2,1]{{1,0}}}}, backend_config={{"custom_call_config":{{"body":"{_body('_dequant_unpack_kernel')}"}}}}
  ROOT %add.1 = f32[8,4]{{1,0}} add(%fusion.1, %x)
}}
"""


def test_hlo_classification():
    ops = tracecut.hlo_ops(HLO, n_edges=64)
    assert tracecut.module_name(HLO) == "jit_step"
    assert ops["fusion.1"].kind == "agg"        # holds a scatter
    assert ops["sort.1"].kind == "agg"          # one row per edge
    assert ops["dot.1"].kind == "matmul"
    assert ops["add.1"].kind == "other"
    q, d = ops["jvp__.3"], ops["t.5"]
    assert (q.kernel, d.kernel) == ("quant", "dequant")
    # 2 blocks of 16 f32 in; 2 x 4 words and 2 x (zero, range) out
    assert q.bytes == d.bytes == 2 * 16 * 4 + 2 * 4 * 4 + 2 * 2 * 4


@pytest.fixture(scope="module")
def recorded():
    rec = json.load(gzip.open(DATA / "trace_sage-arxiv.int2-rp8.json.gz",
                              "rt"))
    hlo = gzip.open(DATA / "hlo_sage-arxiv.int2-rp8.txt.gz", "rt").read()
    return rec, hlo


def _sweep_busy(evs, lo, hi):
    """Busy time by a sweep over the event boundaries (independent of
    ``union_ns``)."""
    points = sorted([(max(s, lo), 1) for _, s, d, _ in evs if s + d > lo]
                    + [(min(s + d, hi), -1) for _, s, d, _ in evs
                       if s + d > lo])
    busy, depth, last = 0, 0, None
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_trace(recorded):
    rec, hlo = recorded
    lo, hi = rec["window"]
    evs = rec["device"]["/device:TPU:0"]
    win = tracecut.reduce_window(rec, lo, hi, tracecut.module_name(hlo))
    ops = tracecut.hlo_ops(hlo, 2_501_829)
    assert all(n in ops for n in win.op_s)
    assert win.busy_s == pytest.approx(_sweep_busy(evs, lo, hi) * 1e-9,
                                       abs=1e-9)
    assert win.busy_s == pytest.approx(1.09903176, abs=1e-8)
    assert win.window_s == pytest.approx(1.101115499, abs=1e-9)
    # own times add up to the busy time: no op is counted twice
    assert sum(win.op_s.values()) == pytest.approx(win.busy_s, rel=1e-9)
    # the six compression kernels run once an epoch each, never nested:
    # their sum is the plain sum of their events' durations
    kern = {n for n, o in ops.items() if o.kernel in ("quant", "dequant")}
    assert len(kern) == 6
    direct = sum(d for n, s, d, _ in evs if n in kern) * 1e-9
    least, busy = win.kernel_totals(ops, ("quant", "dequant"),
                                    peaks.chip_peaks("TPU v5 lite"))
    assert busy == pytest.approx(direct, rel=1e-12)
    assert busy == pytest.approx(0.021089381, abs=1e-9)
    # 3 epochs x (23,200,128 * 2 + 46,400,256 * 4) bytes at 819 GB/s
    assert least == pytest.approx(3 * 232_001_280 / 819e9, rel=1e-12)

    ctx = types.SimpleNamespace(window=win, ops=ops, epochs=rec["epochs"],
                                peaks=peaks.chip_peaks("TPU v5 lite"))
    idle = harness.load_reader("device_idle")(ctx)
    assert idle == pytest.approx(100 * (1 - 1.09903176 / 1.101115499),
                                 rel=1e-6)
    agg = harness.load_reader("agg_ms")(ctx)
    assert agg == pytest.approx(1e3 * 0.974970476 / 3, rel=1e-6)
    roof = harness.load_reader("quant_roofline")(ctx)
    assert roof == pytest.approx(100 * least / busy) and 0 < roof < 100
    # a reader that finds none of its kernels returns nothing, not 0
    bare = types.SimpleNamespace(**vars(ctx))
    bare.ops = {n: o for n, o in ops.items() if o.kernel is None}
    assert harness.load_reader("quant_roofline")(bare) is None
    gaps = win.gaps
    assert gaps[0][1] in ("bench.wait", "bench.dispatch")
    assert gaps == sorted(gaps, key=lambda g: -g[0])
