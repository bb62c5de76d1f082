"""repro.staticcheck: crafted violations each yield exactly their finding,
and the real repo comes up clean."""
import dataclasses

import jax.numpy as jnp
import pytest

from repro.engine.plan import (ExecutionPlan, KernelPolicy, PrecisionPolicy,
                               SamplingPolicy, StashPolicy)
from repro.offload.gnn import plan_gnn_stashes
from repro.staticcheck import jaxpr_audit, kernel_contracts, plan_verify
from repro.staticcheck import seed_lint
from repro.staticcheck.findings import Finding, new_findings
from repro.staticcheck.matrix import audit_matrix, gnn_cfg, _FIXED


def _by_key():
    return {c.key: c for c in audit_matrix()}


# ---------------------------------------------------------------- policies


@pytest.mark.parametrize("field,make", [
    ("sampling.kind", lambda: SamplingPolicy(kind="bogus")),
    ("sampling.n_parts", lambda: SamplingPolicy(kind="partition",
                                                n_parts=0)),
    ("sampling.grad_accum", lambda: SamplingPolicy(kind="mesh", n_parts=4,
                                                   grad_accum=2)),
    ("precision.kind", lambda: PrecisionPolicy(kind="bogus")),
    ("precision.bit_budget", lambda: PrecisionPolicy(kind="autoprec")),
    ("stash.kind", lambda: StashPolicy(kind="bogus")),
    ("stash.placement", lambda: StashPolicy(kind="arena",
                                            placement="bogus")),
    ("kernel.impl", lambda: KernelPolicy(impl="bogus")),
    ("kernel.fused", lambda: KernelPolicy(fused="bogus")),
])
def test_plan_validation_names_offending_field(field, make):
    """Every policy validation error names the offending field and value
    (satellite 1); plan_verify surfaces the same message verbatim."""
    with pytest.raises(ValueError, match=field.replace(".", r"\.")) as ei:
        make()
    assert "bogus" in str(ei.value) or "=" in str(ei.value)


def test_verify_legacy_kwargs_reuses_field_messages():
    got = plan_verify.verify_legacy_kwargs(offload="bogus")
    assert len(got) == 1 and got[0].rule == "policy-field"
    assert "stash.placement" in got[0].message


# ------------------------------------------------------------ plan-verify


def _tensor_splan():
    return plan_gnn_stashes(gnn_cfg(_FIXED), 32, 256)


def test_arena_overlap_is_exactly_detected():
    splan = _tensor_splan()
    lp = splan.layers[0]
    # slide rp_seed inside the packed span: bounds/geometry stay valid
    bad = dataclasses.replace(lp, rp_seed=dataclasses.replace(
        lp.rp_seed, offset=lp.packed.offset))
    mutated = dataclasses.replace(splan,
                                  layers=(bad,) + splan.layers[1:])
    got = plan_verify.verify_stash_plan(mutated)
    assert [f.rule for f in got] == ["arena-overlap"]
    assert "u32 arena" in got[0].message


def test_ragged_mask_floor_is_exactly_detected():
    splan = _tensor_splan()
    lp = next(l for l in splan.layers if l.mask is not None)
    # the historical bug class: floor-divide drops the partial word of a
    # ragged tail (mask_elems not a multiple of 32)
    ragged = lp.mask_elems + 5
    floor_words = ragged // 32
    bad = dataclasses.replace(
        lp, mask_elems=ragged,
        mask=dataclasses.replace(lp.mask, size=floor_words))
    mutated = dataclasses.replace(
        splan, layers=tuple(bad if l is lp else l for l in splan.layers))
    got = plan_verify.verify_stash_plan(mutated)
    assert [f.rule for f in got] == ["mask-alignment"]
    assert "ragged tail" in got[0].message


def test_real_matrix_verifies_clean():
    for case in audit_matrix():
        assert plan_verify.verify_plan(case.plan, case.cfg, case.in_dim,
                                       case.n_nodes, where=case.key) == []


def test_kv_matrix_verifies_clean():
    assert plan_verify.verify_kv_matrix() == []


def _kv_layout(**kw):
    from repro.serving.kvcache import KVCacheConfig, plan_kv_layout

    return plan_kv_layout(KVCacheConfig(**kw), n_layers=2, n_kv_heads=4,
                          d_head=16)


def test_kv_page_overlap_and_bounds_exactly_detected():
    lay = _kv_layout(bits=4, n_pages=8)
    w = lay.words_per_page
    # page 1 starts one word inside page 0's span
    got = plan_verify.verify_kv_layout(
        lay, segments=[(0, 0, 0, w), (0, 1, w - 1, w)])
    assert [f.rule for f in got] == ["kv-page-overlap"]
    # last page pushed one word past the pool end
    got = plan_verify.verify_kv_layout(
        lay, segments=[(1, 7, lay.total_words - w + 1, w)])
    assert [f.rule for f in got] == ["kv-page-bounds"]
    # a segment sized off-geometry
    got = plan_verify.verify_kv_layout(lay, segments=[(0, 0, 0, w - 2)])
    assert [f.rule for f in got] == ["kv-page-geometry"]


def test_kv_word_alignment_exactly_detected():
    import dataclasses as dc

    # bypass plan_kv_layout validation: group of 6 at bits=8 leaves a
    # ragged 2-value tail in the last packed word of every block
    lay = dc.replace(_kv_layout(bits=8), group_size=6)
    rules = {f.rule for f in plan_verify.verify_kv_layout(lay)}
    assert "kv-page-alignment" in rules


def test_mesh_cross_policy_rules():
    plan = ExecutionPlan(
        sampling=SamplingPolicy(kind="mesh", n_parts=4),
        stash=StashPolicy(kind="arena", placement="device"),
        kernel=KernelPolicy(fused="on"))
    rules = {f.rule for f in plan_verify.verify_combination(plan)}
    assert rules == {"mesh-stash", "mesh-fused"}


# -------------------------------------------------------- kernel-contracts


def test_oversized_autotune_tile_is_exactly_detected():
    got = kernel_contracts.check_entry(
        "fwd/4096x1024x4096/b2/g64/TPU v5 lite", [2048, 2048])
    assert [f.rule for f in got] == ["vmem-budget"]
    assert "VMEM" in got[0].message
    # a single backward tile over the budget is no longer exempt
    got = kernel_contracts.check_entry(
        "bwd/169343x512x256/b2/g128/TPU v5 lite", [169343, 128])
    assert [f.rule for f in got] == ["vmem-budget"]


def test_malformed_cache_key_is_detected():
    got = kernel_contracts.check_entry("fwd/banana", [128, 128])
    assert [f.rule for f in got] == ["tile-key"]


def test_real_autotune_cache_is_contract_clean():
    assert kernel_contracts.run() == []


# --------------------------------------------------------------- seed-lint


def test_seed_constant_reuse_is_exactly_detected():
    got = seed_lint.lint_source(
        "def stash_seed(li):\n    return (li + 1) * 7919\n",
        "repro/somewhere/mod.py")
    assert [f.rule for f in got] == ["seed-constant"]
    assert "7919" in got[0].message


def test_seed_constants_allowed_in_scheme_home():
    src = "SR_SEED_PRIME = 7919\n"
    assert seed_lint.lint_source(src, "repro/engine/seeds.py") == []
    assert len(seed_lint.lint_source(src, "repro/other.py")) == 1


def test_jit_host_nondeterminism_detected():
    src = ("import time\nimport jax\n\n"
           "@jax.jit\ndef step(x):\n    t = time.time()\n    return x + t\n")
    got = seed_lint.lint_source(src, "repro/mod.py")
    assert [f.rule for f in got] == ["jit-host-nondeterminism"]


def test_sr_seed_reuse_detected():
    src = ("def f(x, y):\n"
           "    a = sr_seed(3)\n"
           "    b = sr_seed(3)\n"
           "    return a, b\n")
    got = seed_lint.lint_source(src, "repro/mod.py")
    assert [f.rule for f in got] == ["sr-seed-reuse"]


def test_host_callback_outside_obs_tap_detected():
    """A raw jax.debug.callback inside jitted code is a finding unless it
    lives in the sanctioned homes (the obs tap or the offload store)."""
    src = ("import jax\n\n"
           "@jax.jit\ndef step(x):\n"
           "    jax.debug.callback(print, x)\n    return x\n")
    got = seed_lint.lint_source(src, "repro/graph/train.py")
    assert [f.rule for f in got] == ["host-callback-tap"]
    # same source is sanctioned in the obs telemetry module and the
    # offload callback host store
    assert seed_lint.lint_source(src, "repro/obs/quantstats.py") == []
    assert seed_lint.lint_source(src, "repro/offload/engine.py") == []


def test_host_callback_variants_detected():
    src = ("import jax\n\n"
           "def inner(x):\n"
           "    return jax.pure_callback(abs, x, x)\n\n"
           "out = jax.jit(inner)\n")
    got = seed_lint.lint_source(src, "repro/core/quant.py")
    assert [f.rule for f in got] == ["host-callback-tap"]


def test_obs_tap_on_dataflow_path_detected():
    """tap() must never appear on the residual/stash dataflow path — a
    tap there puts the telemetry callback inside the training jaxpr and
    forfeits obs-on/obs-off bit-identity."""
    src = ("from repro.obs.quantstats import tap\n\n"
           "def f_fwd(x):\n    tap(print, x)\n    return x\n")
    got = seed_lint.lint_source(src, "repro/engine/forward.py")
    assert [f.rule for f in got] == ["obs-tap-dataflow"]
    for fname in ("repro/offload/engine.py", "repro/offload/arena.py"):
        assert ["obs-tap-dataflow"] == [
            f.rule for f in seed_lint.lint_source(src, fname)]
    # outside the dataflow path (and outside jit) a tap is fine
    assert seed_lint.lint_source(src, "repro/engine/runner.py") == []


def test_obs_calibration_needs_telemetry_channel():
    from repro.obs import ObsPolicy

    plan = ExecutionPlan(
        precision=PrecisionPolicy(kind="autoprec", bit_budget=2.0,
                                  calibration="obs"))
    got = plan_verify.verify_combination(plan)
    assert [f.rule for f in got] == ["obs-calibration"]
    ok = dataclasses.replace(
        plan, obs=ObsPolicy(enabled=True, quant_stats=True))
    assert plan_verify.verify_combination(ok) == []


def test_repo_seed_discipline_is_clean():
    assert seed_lint.run() == []


# -------------------------------------------------------------- jaxpr-audit


def _audit(key):
    return jaxpr_audit.audit_case(_by_key()[key])


@pytest.mark.parametrize("key", [
    "full/fixed/tensor/fused-off",
    "batched/fixed/device/fused-off",
    "mesh/fixed/tensor/fused-off",
])
def test_ledger_matches_memory_report(key):
    """Acceptance: the jaxpr byte ledger equals activation_memory_report
    within 1% on the full/batched/mesh matrix (it is exact here)."""
    r = _audit(key)
    assert r.findings == []
    assert r.ledger_bytes == r.report_bytes


def test_callback_plan_ships_exactly_planned_bytes():
    r = _audit("full/fixed/host/fused-off")
    assert r.findings == []
    assert r.ledger_bytes == r.report_bytes


def test_residual_leak_is_exactly_detected():
    from repro.engine.forward import _build

    case = _by_key()["full/fixed/tensor/fused-off"]
    splan = plan_gnn_stashes(case.cfg, case.in_dim, case.live_nodes)
    fwd = _build(case.cfg, splan, case.plan.stash,
                 case.plan.kernel.fused).fwd

    def leaky(*a):
        h, res = fwd(*a)
        # a raw f32 activation escaping the quantizer
        return h, (res, jnp.zeros((257,), jnp.float32))

    got, _ = jaxpr_audit.audit_forward(
        leaky, jaxpr_audit._example_args(case.cfg, case.in_dim,
                                         case.live_nodes),
        splan, "tensor", where="crafted")
    assert [f.rule for f in got] == ["residual-leak"]
    assert "escaped the quantizer" in got[0].message


def test_missing_stash_is_detected():
    from repro.engine.forward import _build

    case = _by_key()["full/fixed/tensor/fused-off"]
    splan = plan_gnn_stashes(case.cfg, case.in_dim, case.live_nodes)
    fwd = _build(case.cfg, splan, case.plan.stash,
                 case.plan.kernel.fused).fwd

    def dropping(*a):
        h, _ = fwd(*a)
        return h, ()

    got, ledger = jaxpr_audit.audit_forward(
        dropping, jaxpr_audit._example_args(case.cfg, case.in_dim,
                                            case.live_nodes),
        splan, "tensor", where="crafted")
    assert got and all(f.rule == "missing-stash" for f in got)
    assert ledger == 0


# ---------------------------------------------------------------- dead-code


def test_dead_code_crafted(tmp_path):
    from repro.staticcheck import deadcode

    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(
        "def used():\n    return 1\n\n\ndef unused():\n    return 2\n")
    (pkg / "other.py").write_text(
        "from repro.mod import used\n\n\ndef caller():\n"
        "    return used()\n")
    (tmp_path / "tests").mkdir()
    (tmp_path / "tests" / "test_x.py").write_text(
        "from repro.other import caller\ncaller()\n")
    got = deadcode.sweep(tmp_path)
    assert [(f.rule, "unused" in f.message) for f in got] == \
        [("unused-symbol", True)]
    assert "repro.mod.unused" in got[0].message


def test_reexport_is_transparent(tmp_path):
    from repro.staticcheck import deadcode

    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    # shim kept importable only by its package __init__: still dead
    (pkg / "__init__.py").write_text("from repro.mod import shim\n")
    (pkg / "mod.py").write_text("def shim():\n    return 0\n")
    got = deadcode.sweep(tmp_path)
    assert [f.rule for f in got] == ["unused-symbol"]


# ------------------------------------------------------------ CLI/baseline


def test_fingerprint_ignores_message_rewording():
    a = Finding("p", "r", "w", "old text")
    b = Finding("p", "r", "w", "new text")
    assert a.fingerprint() == b.fingerprint()
    assert new_findings([b], {a.fingerprint()}) == []
    assert new_findings([b], set()) == [b]


def test_cli_gates_on_new_findings(tmp_path):
    from repro.staticcheck.cli import main

    baseline = tmp_path / "baseline.json"
    assert main(["--passes", "kernel-contracts",
                 "--baseline", str(baseline)]) == 0
    assert main(["--passes", "bogus-pass",
                 "--baseline", str(baseline)]) == 2
