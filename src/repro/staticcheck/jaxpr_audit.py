"""jaxpr residual-leak audit: prove, per plan, that every saved-for-backward
byte is planned.

The engine's whole-network ``custom_vjp``
(:func:`repro.engine.forward._build`) keeps its forward rule reachable
after ``defvjp`` (``f.fwd``), so the exact residual set a compiled step
saves to HBM can be read off statically: trace ``f.fwd`` to a closed
jaxpr over :class:`jax.ShapeDtypeStruct` arguments and walk the output
vars after the primal.  Each residual leaf is classified:

* **pass-through** — the outvar is an invar (params, edge lists,
  aggregation weights, the node mask): no new HBM, and the donation
  contract holds (donated buffers reappear only as pass-throughs the
  backward consumes within the step);
* **planned** — its aval matches one entry of the
  :class:`~repro.offload.arena.StashPlan`-derived expectation multiset
  (per-tensor fields, the pooled arena pair, or the callback store's
  ticket+key under the host mechanisms);
* **leak** — an unmatched float residual reaching HBM (rule
  ``residual-leak``): an activation escaping the quantizer, the exact
  failure mode EXACT/GACT-style compressed training must exclude.
  Unmatched non-scalar integer residuals are ``unplanned-residual``.

Host-offloaded plans route bytes through ``jax.pure_callback`` instead of
residuals; the audit sums every callback's array operands (the
``host_put`` payload) as the ledger.  The per-plan byte ledger is then
cross-checked against ``activation_memory_report`` — the model the
benchmarks and the paper's Table-1 columns read — and any divergence
beyond 1% is a ``ledger-mismatch`` finding.

Mesh plans are audited at per-device geometry through the same unified
forward: :func:`repro.engine.forward.mesh_stash_plan` *is*
``plan_gnn_stashes`` at the partition's padded node count (halo rows
stash nothing), and the mesh per-op stack is gated bit-identical to the
engine forward, so the per-device residual set coincides.
"""
from __future__ import annotations

import dataclasses
from types import SimpleNamespace

import jax
import jax.extend.core as jax_core
import jax.numpy as jnp

from repro.offload.arena import StashPlan
from repro.staticcheck.findings import Finding
from repro.staticcheck.matrix import AuditCase, audit_matrix

PASS = "jaxpr-audit"

#: Relative tolerance of the ledger ↔ memory-report cross-check.  The two
#: models agree byte-for-byte by construction; 1% is headroom, not slack.
LEDGER_RTOL = 0.01

_EDGES = 512  # residual geometry is edge-count independent


@dataclasses.dataclass
class AuditResult:
    key: str
    findings: list[Finding]
    ledger_bytes: int
    report_bytes: int

    def to_json(self) -> dict:
        return {"key": self.key, "ledger_bytes": self.ledger_bytes,
                "report_bytes": self.report_bytes,
                "findings": [f.to_json() for f in self.findings]}

    @classmethod
    def from_json(cls, d: dict) -> "AuditResult":
        return cls(key=d["key"],
                   findings=[Finding(f["pass"], f["rule"], f["where"],
                                     f["message"])
                             for f in d["findings"]],
                   ledger_bytes=d["ledger_bytes"],
                   report_bytes=d["report_bytes"])


def expected_residuals(splan: StashPlan,
                       mechanism: str) -> list[tuple[str, tuple, str]]:
    """(dtype, shape, label) multiset the plan says the residual holds."""
    if mechanism == "device":
        return [("uint32", (splan.u32_words,), "u32-arena"),
                ("float32", (splan.f32_elems,), "f32-arena")]
    if mechanism == "callback":
        # bytes live in the host store; the residual is the chained ticket
        # (the forward key rides along as a pass-through of the seed invar)
        return [("uint32", (), "ticket")]
    # "tensor" (and "memkind", whose residual is the same fields as
    # host-kind arrays — unreachable on CPU hosts)
    exp = []
    for lp in splan.layers:
        tag = f"layer{lp.index}"
        if lp.cfg is not None:
            exp += [("uint32", (lp.n_blocks, lp.words_per_block),
                     f"{tag}/packed"),
                    ("float32", (lp.n_blocks,), f"{tag}/zero"),
                    ("float32", (lp.n_blocks,), f"{tag}/rng"),
                    ("uint32", (), f"{tag}/rp_seed")]
        else:
            exp.append(("float32", tuple(lp.shape), f"{tag}/raw"))
        if lp.mask is not None:
            exp.append(("uint32", (1, lp.mask.size), f"{tag}/mask"))
    return exp


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from _iter_eqns(inner)
                elif hasattr(sub, "eqns"):
                    yield from _iter_eqns(sub)


def _nbytes(aval) -> int:
    return int(aval.size) * jnp.dtype(aval.dtype).itemsize


def audit_forward(fwd, example_args, splan: StashPlan, mechanism: str,
                  where: str) -> tuple[list[Finding], int]:
    """Audit one forward rule; returns (findings, ledger_bytes)."""
    closed, out_shape = jax.make_jaxpr(fwd, return_shape=True)(*example_args)
    jx = closed.jaxpr
    n_primal = len(jax.tree.leaves(out_shape[0]))
    passthrough = set(jx.invars) | set(jx.constvars)

    findings: list[Finding] = []
    expected = expected_residuals(splan, mechanism)
    remaining = list(expected)
    ledger = 0
    for leaf in jx.outvars[n_primal:]:
        if isinstance(leaf, jax_core.Literal) or leaf in passthrough:
            continue  # pass-through residual: no new HBM
        aval = leaf.aval
        sig = (str(jnp.dtype(aval.dtype)), tuple(aval.shape))
        hit = next((e for e in remaining if (e[0], e[1]) == sig), None)
        if hit is not None:
            remaining.remove(hit)
            if mechanism != "callback":  # the ticket is bookkeeping, not
                ledger += _nbytes(aval)  # saved activation bytes
            continue
        if jnp.issubdtype(aval.dtype, jnp.floating):
            ledger += _nbytes(aval)
            findings.append(Finding(
                PASS, "residual-leak", where,
                f"{sig[0]}{list(sig[1])} residual ({_nbytes(aval)} bytes) "
                "reaches HBM but is not accounted for in the StashPlan — "
                "an activation escaped the quantizer"))
        elif int(aval.size) > 1:
            ledger += _nbytes(aval)
            findings.append(Finding(
                PASS, "unplanned-residual", where,
                f"{sig[0]}{list(sig[1])} residual ({_nbytes(aval)} bytes) "
                "is not in the StashPlan"))
        # unmatched integer scalars (stray seeds) are byte-negligible
    for dtype, shape, label in remaining:
        findings.append(Finding(
            PASS, "missing-stash", f"{where}/{label}",
            f"planned {dtype}{list(shape)} stash never appears in the "
            "residual — the backward would read unwritten state"))
    if mechanism == "callback":
        # planned bytes crossed to the host store through pure_callback;
        # each host_put's operands after (key, ticket) are the payload
        for eqn in _iter_eqns(jx):
            if eqn.primitive.name == "pure_callback":
                ledger += sum(_nbytes(v.aval) for v in eqn.invars[2:]
                              if not isinstance(v, jax_core.Literal))
    return findings, ledger


def _example_args(cfg, in_dim: int, n_nodes: int):
    from repro.graph.adjacency import EdgeList
    from repro.graph.models import _dims

    f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32
    sds = jax.ShapeDtypeStruct
    mult = 2 if cfg.arch == "sage" else 1
    dims = _dims(cfg, in_dim)
    params = [{"w": sds((d_in * mult, d_out), f32), "b": sds((d_out,), f32)}
              for d_in, d_out in zip(dims[:-1], dims[1:])]
    # the forward is the same whatever the aggregation operand; an edge
    # list is the one that needs only shapes
    src, dst = sds((_EDGES,), i32), sds((_EDGES,), i32)
    return (params, sds((n_nodes, in_dim), f32),
            EdgeList(src, dst, sds((_EDGES,), f32), n_nodes),
            EdgeList(src, dst, sds((_EDGES,), f32), n_nodes),
            sds((), u32), sds((n_nodes,), f32))


def _report_bytes(case: AuditCase) -> int:
    from repro.graph.train import activation_memory_report

    g = SimpleNamespace(n_feats=case.in_dim, n_nodes=case.n_nodes)
    rep = activation_memory_report(g, case.cfg, plan=case.plan)
    sp = case.plan.sampling
    if sp.kind == "full":
        return rep.get("compressed_bytes", rep["fp32_bytes"])
    sub = rep["mesh" if sp.kind == "mesh" else "batched"]
    return sub["peak_saved_bytes"]


def audit_case(case: AuditCase) -> AuditResult:
    from repro.engine.forward import TENSOR_STASH, _build
    from repro.offload.engine import resolve_stash
    from repro.offload.gnn import plan_gnn_stashes

    # the mesh forward stashes per-device local rows only: audit the
    # unified forward at per-partition geometry (see module docstring)
    stash = (TENSOR_STASH if case.plan.sampling.kind == "mesh"
             else case.plan.stash)
    live = case.live_nodes
    splan = plan_gnn_stashes(case.cfg, case.in_dim, live)
    mechanism = resolve_stash(stash.kind, stash.placement)
    fwd = _build(case.cfg, splan, stash, case.plan.kernel.fused).fwd
    findings, ledger = audit_forward(
        fwd, _example_args(case.cfg, case.in_dim, live), splan, mechanism,
        where=case.key)
    report = _report_bytes(case)
    if report and abs(ledger - report) > LEDGER_RTOL * report:
        findings.append(Finding(
            PASS, "ledger-mismatch", case.key,
            f"jaxpr residual ledger ({ledger} bytes) diverges from "
            f"activation_memory_report ({report} bytes) by more than "
            f"{LEDGER_RTOL:.0%}"))
    return AuditResult(key=case.key, findings=findings,
                       ledger_bytes=ledger, report_bytes=report)


def run(cases: list[AuditCase] | None = None) -> list[AuditResult]:
    return [audit_case(c) for c in (audit_matrix() if cases is None
                                    else cases)]
