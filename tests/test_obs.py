"""repro.obs: obs-on training is bit-identical to obs-off across every
engine, spans nest well-formed, export to a valid Chrome trace and land
on a ``jax.profiler`` trace's host plane,
the quant-health channel's measured SR variance agrees with its own
conditional expectation and with the Eq. 10 prediction, and the pager's
windowed overlap stat is live."""
import dataclasses
import glob
import json
import pathlib
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import quant as quantmod
from repro.core import random_projection as rpmod
from repro.core.act_compress import CompressionConfig
from repro.core.autoprec import LayerStats, expected_layer_variance
from repro.engine import run
from repro.engine.plan import (ExecutionPlan, KernelPolicy, ObsPolicy,
                               PrecisionPolicy, SamplingPolicy)
from repro.engine.seeds import layer_seed
from repro.graph import GNNConfig, cora_like
from repro.graph.models import graph_tuple, init_gnn_params
from repro.obs.metrics import (NULL_COUNTER, NULL_HISTOGRAM, Counter, Gauge,
                               Histogram, MetricsRegistry, get_metrics)
from repro.obs.quantstats import (QuantHealthMonitor, health_rows,
                                  layer_health, measure_quant_health,
                                  measured_sensitivity)
from repro.obs.session import NULL_SESSION, ObsSession
from repro.obs.trace import Tracer, set_tracer, stopwatch


@pytest.fixture(scope="module")
def g():
    return cora_like(scale=0.2, seed=0)


COMP = CompressionConfig(bits=2, group_size=64, rp_ratio=8)

#: The full-surface policy the bit-identity matrix runs under.
OBS = ObsPolicy(enabled=True, trace=True, metrics=True, quant_stats=True,
                quant_stats_every=2)


def _cfg(g, comp=COMP, hidden=(32,)):
    return GNNConfig(arch="sage", hidden=hidden, n_classes=g.num_classes,
                     compression=comp)


def _tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _plans(impl):
    kp = KernelPolicy(impl=impl)
    return {
        "full": ExecutionPlan(kernel=kp),
        "partition": ExecutionPlan(
            sampling=SamplingPolicy(kind="partition", n_parts=2), kernel=kp),
        "mesh": ExecutionPlan(
            sampling=SamplingPolicy(kind="mesh", n_parts=2, shuffle=False),
            kernel=kp),
    }


# ----------------------------------------------------------- bit-identity
@pytest.mark.parametrize("impl", ["jnp", "interp"])
@pytest.mark.parametrize("kind", ["full", "partition", "mesh"])
def test_obs_on_is_bit_identical(g, kind, impl):
    """The HARD gate: the full obs surface (spans + metrics + the quant
    probe on a 2-epoch cadence) must not move a single bit of the
    training trajectory — obs lives outside the training jaxpr."""
    cfg = _cfg(g)
    plan_off = _plans(impl)[kind]
    plan_on = dataclasses.replace(plan_off, obs=OBS)
    r_off = run(g, cfg, plan_off, n_epochs=3, seed=0)
    r_on = run(g, cfg, plan_on, n_epochs=3, seed=0)
    _tree_equal(r_off["params"], r_on["params"])
    assert r_off["test_acc"] == r_on["test_acc"]
    assert "obs" not in r_off
    obs = r_on["obs"]
    assert obs.enabled
    # the probe ran on its cadence and produced measured-vs-Eq.10 rows
    rows = obs.quant_rows()
    assert rows and rows[0]["epoch"] == 2
    assert all(r["predicted_var"] > 0 and r["measured_var"] > 0
               for r in rows)


# ------------------------------------------------------------------ spans
def test_span_tree_well_formed(g):
    cfg = _cfg(g)
    plan = dataclasses.replace(_plans("jnp")["full"], obs=OBS)
    r = run(g, cfg, plan, n_epochs=3, seed=0)
    spans = r["obs"].tracer.spans
    names = [s.name for s in spans]
    assert names.count("epoch") == 3
    assert "plan/compile" in names and "train/epochs" in names
    assert names.count("obs/quant_probe") == 2  # epochs 0 and 2
    for s in spans:
        assert s.dur >= 0.0
        if s.parent == -1:
            assert s.depth == 0
            continue
        p = spans[s.parent]
        assert s.depth == p.depth + 1
        # child interval nested in the parent's
        assert s.t0 >= p.t0
        assert s.t0 + s.dur <= p.t0 + p.dur + 1e-6
    # every epoch span hangs off the train/epochs stopwatch span
    root = names.index("train/epochs")
    assert all(spans[i].parent == root
               for i, n in enumerate(names) if n == "epoch")


def test_mesh_round_spans_and_halo_counter(g):
    plan = dataclasses.replace(_plans("jnp")["mesh"], obs=OBS)
    r = run(g, _cfg(g), plan, n_epochs=2, seed=0)
    obs = r["obs"]
    names = [s.name for s in obs.tracer.spans]
    rounds = r["updates_per_epoch"]
    assert names.count("mesh/round") == 2 * rounds
    assert names.count("pager/fetch") == 2 * rounds
    snap = obs.registry.snapshot()
    assert snap["pager/fetches"] == 2 * rounds
    assert "halo/bytes" in snap
    # single-device mesh over 2 partitions => 2 sequential rounds with a
    # real halo; the counter prices rounds * per-round bytes
    assert snap["halo/bytes"] == r["halo_bytes_per_epoch"] * 2
    ov = snap["pager/overlap_frac"]
    assert ov["count"] == 2 * rounds
    assert 0.0 <= ov["window_mean"] <= 1.0


def test_trace_exports_are_schema_valid(g, tmp_path):
    plan = dataclasses.replace(_plans("jnp")["full"], obs=OBS)
    r = run(g, _cfg(g), plan, n_epochs=2, seed=0)
    paths = r["obs"].export(tmp_path / "trace")
    # the Chrome trace_event file is the one export: what Perfetto loads
    assert paths == {"chrome": str(tmp_path / "trace.trace.json")}
    assert sorted(p.name for p in tmp_path.iterdir()) == ["trace.trace.json"]
    chrome = json.loads((tmp_path / "trace.trace.json").read_text())
    assert set(chrome) == {"traceEvents", "displayTimeUnit"}
    spans = r["obs"].tracer.spans
    assert [ev["name"] for ev in chrome["traceEvents"]] == [
        s.name for s in spans]
    for ev in chrome["traceEvents"]:
        assert ev["ph"] == "X" and ev["cat"] == "repro"
        assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
        assert ev["ts"] >= 0.0 and ev["dur"] >= 0.0


def _profiled_run(g, tmp, obs):
    """Three tiny epochs under ``jax.profiler.trace``; the host plane's
    events as ``[name, start_ns, dur_ns]``, read the way the benchmark
    reads a chip's trace."""
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                           / "bench"))
    import tracecut

    plan = ExecutionPlan(obs=obs)
    with jax.profiler.trace(str(tmp)):
        run(g, _cfg(g), plan, n_epochs=3, seed=0)
    (path,) = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")
    return tracecut.load_xplane(path)["host"]


def test_spans_land_on_the_profilers_host_plane(g, tmp_path):
    names = ("plan/compile", "epoch", "epoch/data", "epoch/dispatch")
    host = _profiled_run(g, tmp_path / "on", ObsPolicy(enabled=True))
    # (start, end) in ns, an enclosing span before the spans it holds
    evs = sorted(((s, s + d), n) for n, s, d in host if n in names)
    evs.sort(key=lambda e: (e[0][0], -e[0][1]))
    assert [n for _, n in evs] == ["plan/compile"] + [
        "epoch", "epoch/data", "epoch/dispatch"] * 3
    spans = [iv for iv, _ in evs]
    # compile ends before the first epoch; epochs follow one another;
    # each epoch holds its data step, then its dispatch step
    assert spans[0][1] <= spans[1][0]
    for k in range(3):
        ep, data, disp = spans[1 + 3 * k: 4 + 3 * k]
        assert ep[0] <= data[0] <= data[1] <= disp[0] <= disp[1] <= ep[1]
        if k:
            assert spans[3 * k - 2][1] <= ep[0]
    off = _profiled_run(g, tmp_path / "off", ObsPolicy())
    assert not [n for n, _, _ in off if n in names]


def test_stopwatch_measures_without_tracer():
    assert set_tracer(None) is None or True  # ensure no active tracer
    with stopwatch() as sw:
        sum(range(1000))
    assert sw.elapsed_s > 0.0
    # named stopwatch lands a span when a tracer is active
    t = Tracer()
    prev = set_tracer(t)
    try:
        with stopwatch("work", k=1) as sw:
            sum(range(1000))
    finally:
        set_tracer(prev)
    assert [s.name for s in t.spans] == ["work"]
    assert t.spans[0].args == {"k": 1}
    assert abs(t.spans[0].dur - sw.elapsed_s) < 0.05


# ---------------------------------------------------------------- metrics
def test_metrics_primitives():
    c, ga, h = Counter(), Gauge(), Histogram(window=4)
    c.inc(), c.inc(5)
    assert c.value == 6
    ga.set(3.0), ga.max(2.0), ga.max(7.0)
    assert ga.value == 7.0
    for v in (1.0, 2.0, 3.0, 4.0, 5.0, 6.0):
        h.observe(v)
    assert h.count == 6 and h.mean == 3.5
    assert h.window_size == 4
    assert h.window_mean == 4.5       # last four: 3,4,5,6
    assert h.window_min == 3.0 and h.window_max == 6.0
    assert h.vmin == 1.0 and h.vmax == 6.0


def test_disabled_registry_hands_out_nulls():
    reg = MetricsRegistry(enabled=False)
    assert reg.counter("x") is NULL_COUNTER
    assert reg.histogram("y") is NULL_HISTOGRAM
    reg.counter("x").inc()
    assert reg.snapshot() == {}
    # the module default is disabled: unconditional producer calls are
    # no-ops until a session activates its registry
    get_metrics().counter("anything").inc()


def test_session_activation_restores_previous_actives():
    sess = ObsSession(ObsPolicy(enabled=True))
    before = get_metrics()
    with sess.activate():
        assert get_metrics() is sess.registry
    assert get_metrics() is before
    assert NULL_SESSION.registry is None and NULL_SESSION.tracer is None


# ----------------------------------------------------------- quant health
def _replay_pipeline(x, comp, seed=0, li=0):
    ls = layer_seed(jnp.uint32(seed), li)
    xs = rpmod.rp(x, ls ^ jnp.uint32(0xA5A5A5A5),
                  x.shape[1] // comp.rp_ratio)
    blocks, n_valid = quantmod.group_reshape(xs, comp.group_size)
    lv = comp.levels() or quantmod.uniform_levels(comp.bits)
    codes, zero, rng = quantmod.quantize_grouped(blocks, comp.bits, ls, lv)
    return blocks, int(n_valid), codes, zero, rng, lv


def test_measured_variance_is_the_conditional_expectation():
    """The probe's sq_err is a single SR draw; over ~4k elements it must
    concentrate on the analytic conditional expectation
    Σ frac·(1−frac)·(rng/B)² of the very same blocks."""
    comp = CompressionConfig(bits=2, group_size=64, rp_ratio=8)
    x = jax.random.normal(jax.random.PRNGKey(3), (512, 64), jnp.float32)
    stats = jax.jit(
        lambda x: layer_health(x, comp, jnp.uint32(0), 0))(x)
    measured = float(stats[2])
    blocks, n_valid, codes, zero, rng, lv = _replay_pipeline(x, comp)
    assert n_valid == blocks.size  # no padded tail in this geometry
    B = 2 ** comp.bits - 1
    t = jnp.clip((blocks - zero) / rng, 0.0, 1.0) * B
    frac = t - jnp.floor(t)
    expected = float(jnp.sum(frac * (1 - frac) * (rng / B) ** 2))
    assert expected > 0.0
    np.testing.assert_allclose(measured, expected, rtol=0.1)
    # saturation rate: endpoint codes of the same draw, exactly
    sat = float(jnp.mean(((codes == 0) | (codes == B)).astype(jnp.float32)))
    np.testing.assert_allclose(float(stats[5]), sat, rtol=1e-6)


def test_measured_variance_agrees_with_eq10_on_synthetic_gaussian(g):
    """Gaussian activations through RP are the regime the CN_[1/D] model
    (Eq. 10) was derived for: measured and predicted variance must agree
    to well within 2x (the allocator only needs the *relative* per-layer
    scale, but the runtime monitor's ratio column should sit near 1)."""
    cfg = _cfg(g)
    params = init_gnn_params(jax.random.PRNGKey(0), cfg, g.n_feats)
    measured = measure_quant_health(params, graph_tuple(g), cfg, seed=0)
    rows = health_rows(measured, cfg.layer_compression())
    assert len(rows) == cfg.n_layers
    for r in rows:
        assert 0.4 < r["ratio"] < 2.5, r
    # sensitivities: measured_var / bit-scaling curve, None where
    # uncompressed
    sens = measured_sensitivity(measured, cfg.layer_compression())
    assert all(s is not None and s > 0 for s in sens)


def test_quant_monitor_history_and_epoch_tags(g):
    cfg = _cfg(g)
    params = init_gnn_params(jax.random.PRNGKey(0), cfg, g.n_feats)
    mon = QuantHealthMonitor(cfg)
    gt = graph_tuple(g)
    mon.probe(params, gt, 0)
    mon.probe(params, gt, 5)
    rows = mon.rows()
    assert rows and all(r["epoch"] == 5 for r in rows)
    hist = mon.history()
    assert [e for e, _ in hist] == [0, 5]
    # same params, same seed -> the probe replays bit-identically
    assert hist[0][1][0]["measured_var"] == hist[1][1][0]["measured_var"]


# -------------------------------------------------------- obs calibration
def test_autoprec_obs_calibration_allocates(g):
    cfg = GNNConfig(arch="sage", hidden=(32,), n_classes=g.num_classes,
                    compression=COMP)
    base = ExecutionPlan(
        precision=PrecisionPolicy(kind="autoprec", bit_budget=2.0,
                                  calibration="obs"),
        obs=ObsPolicy(enabled=True, quant_stats=True))
    r = run(g, cfg, base, n_epochs=2, seed=0)
    assert len(r["bits_per_layer"]) == cfg.n_layers
    assert all(b in (1, 2, 4, 8) for b in r["bits_per_layer"])


def test_obs_calibration_requires_telemetry_channel(g):
    plan = ExecutionPlan(
        precision=PrecisionPolicy(kind="autoprec", bit_budget=2.0,
                                  calibration="obs"))
    with pytest.raises(ValueError, match="quant_stats"):
        run(g, _cfg(g), plan, n_epochs=1, seed=0)


def test_policy_validation():
    with pytest.raises(ValueError, match="obs.quant_stats"):
        ObsPolicy(quant_stats=True)            # needs enabled=True
    with pytest.raises(ValueError, match="quant_stats_every"):
        ObsPolicy(enabled=True, quant_stats_every=0)
    with pytest.raises(ValueError, match="precision.calibration"):
        PrecisionPolicy(kind="autoprec", bit_budget=2.0,
                        calibration="bogus")
    with pytest.raises(ValueError, match="calibration"):
        PrecisionPolicy(kind="fixed", calibration="obs")
    p = dataclasses.replace(ExecutionPlan(), obs=ObsPolicy(enabled=True))
    assert "obs=trace+metrics" in p.describe()
    assert "obs" not in ExecutionPlan().describe()


# ------------------------------------------------------------------ pager
def test_pager_windowed_overlap(g):
    from repro.offload.pager import FeaturePager
    from repro.parallel.halo import graph_mesh

    mesh = graph_mesh(1)
    feats = np.random.default_rng(0).normal(
        size=(2, 1, 8, 4)).astype(np.float32)
    reg = MetricsRegistry()
    pg = FeaturePager(feats, mesh, metrics=reg, window=3)
    for r in (0, 1, 0, 1, 0, 1):
        pg.fetch(r)
        pg.prefetch((r + 1) % 2)
    st = pg.stats()
    assert st["fetches"] == 6
    assert st["overlap_window_size"] == 3      # bounded, not lifetime
    assert 0.0 <= st["overlap_frac_window"] <= 1.0
    assert st["overlap_frac_window_min"] <= st["overlap_frac_window"]
    assert reg.counter("pager/fetches").value == 6
    assert reg.gauge("pager/round_bytes").value == feats.nbytes // 2
    # without a registry the pager makes a private one: stats still live
    pg2 = FeaturePager(feats, mesh)
    pg2.fetch(0)
    assert pg2.stats()["overlap_window_size"] == 1
