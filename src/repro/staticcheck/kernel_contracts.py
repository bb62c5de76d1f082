"""Declarative pre/post-conditions for the compression kernels, evaluated
against the tiles the autotuner picks.

Each :class:`Contract` states one invariant the Pallas kernels assume and
checks it over one tile choice
(``{kind}/{m}x{d}x{n}/b{bits}/g{group}/{device_kind}`` → ``(t0, t1)``),
for every fused layer of the plan matrix and the full-width arxiv layers:

* ``fused_matmul`` forward — the row tile owns whole quantization blocks
  (``tm % row_tile_step(d, G) == 0``, the same legality
  :func:`repro.kernels.autotune.fwd_candidates` enumerates), tiles stay
  inside the (step-padded) operand, and the tile working set fits the
  per-core VMEM budget;
* ``fused_matmul`` backward — the row tile owns whole blocks and fits
  the VMEM budget, the single ``tile_rows == m`` tile included (the
  kernel wrapper pads a ragged last tile, so it need not divide ``m``);
* ``quant_blockwise`` — the base kernel preconditions (bits divides 32,
  the pack width divides the group, VM level tables fit the unrolled
  compare/select chain), via the one predicate the dispatch layer routes
  on (:func:`repro.core.backend.quant_kernel_unsupported`);
* ``rp_matmul`` — the projection ratio divides the stash width
  (``compress`` asserts this at trace time; off-grid tile shapes are an
  allowed jnp fallback, not a violation).

A pick violating a contract means the autotuner chose tiles a kernel
launch would miscompute or OOM on — exactly the class of bug that only
surfaces on real TPU hardware otherwise.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable

from repro.core import backend
from repro.kernels.autotune import (VMEM_BUDGET, bwd_vmem_bytes,
                                    fwd_vmem_bytes, get_tiles)
from repro.kernels.fused_matmul import row_tile_step
from repro.kernels.peaks import TARGET_KIND
from repro.staticcheck.findings import Finding

PASS = "kernel-contracts"

_KEY_RE = re.compile(
    r"^(?P<kind>fwd|bwd)/(?P<m>\d+)x(?P<d>\d+)x(?P<n>\d+)"
    r"/b(?P<bits>\d+)/g(?P<group>\d+)/(?P<device>[\w -]+)$")


@dataclasses.dataclass(frozen=True)
class Entry:
    """One parsed tile choice."""

    key: str
    kind: str
    m: int
    d: int
    n: int
    bits: int
    group_size: int
    device: str
    t0: int  # tm (fwd) | tile_rows (bwd)
    t1: int  # tn


@dataclasses.dataclass(frozen=True)
class Contract:
    rule: str
    description: str
    applies: str                       # "fwd" | "bwd" | "any"
    check: Callable[[Entry], str | None]  # violation message, or None


def _fwd_vmem(e: Entry) -> int:
    return fwd_vmem_bytes(e.t0, e.d, e.t1, e.bits, e.group_size)


def _bwd_vmem(e: Entry) -> int:
    return bwd_vmem_bytes(e.t0, e.d, e.t1, e.bits, e.group_size)


def _tile_positive(e: Entry) -> str | None:
    if e.t0 < 1 or e.t1 < 1:
        return f"non-positive tile ({e.t0}, {e.t1})"
    return None


def _quant_precondition(e: Entry) -> str | None:
    return backend.quant_kernel_unsupported(e.bits, e.group_size, None)


def _fwd_block_alignment(e: Entry) -> str | None:
    step = row_tile_step(e.d, e.group_size)
    if e.t0 % step:
        return (f"row tile tm={e.t0} does not own whole quantization "
                f"blocks: need a multiple of step={step} "
                f"(G={e.group_size}, D={e.d})")
    return None


def _fwd_index_bounds(e: Entry) -> str | None:
    step = row_tile_step(e.d, e.group_size)
    m_pad = -(-e.m // step) * step
    if e.t0 > m_pad:
        return (f"row tile tm={e.t0} exceeds the step-padded operand "
                f"height {m_pad} (m={e.m}, step={step})")
    if e.t1 > e.n:
        return f"column tile tn={e.t1} exceeds the output width n={e.n}"
    return None


def _fwd_vmem_budget(e: Entry) -> str | None:
    vmem = _fwd_vmem(e)
    if vmem > VMEM_BUDGET:
        return (f"tile ({e.t0}, {e.t1}) needs {vmem} bytes of VMEM "
                f"({e.t0}x{e.d} operand + {e.d}x{e.t1} weights + "
                f"{e.t0}x{e.t1} output + packed epilogue) over the "
                f"{VMEM_BUDGET}-byte per-core budget")
    return None


def _bwd_block_alignment(e: Entry) -> str | None:
    step = row_tile_step(e.d, e.group_size)
    if e.t0 % step:
        return (f"row tile tile_rows={e.t0} does not own whole "
                f"quantization blocks: need a multiple of step={step}")
    return None


def _bwd_index_bounds(e: Entry) -> str | None:
    if e.t0 > e.m:
        return f"tile_rows={e.t0} exceeds the operand height m={e.m}"
    if e.t1 > e.n:
        return f"column tile tn={e.t1} exceeds the output width n={e.n}"
    return None


def _bwd_vmem_budget(e: Entry) -> str | None:
    vmem = _bwd_vmem(e)
    if vmem > VMEM_BUDGET:
        return (f"row tile ({e.t0}, {e.t1}) needs {vmem} bytes of "
                f"VMEM over the {VMEM_BUDGET}-byte per-core budget")
    return None


CONTRACTS: tuple[Contract, ...] = (
    Contract("tile-bounds", "tiles are positive", "any", _tile_positive),
    Contract("quant-precondition",
             "base quant kernel can run (bits | 32, pack width | G)",
             "any", _quant_precondition),
    Contract("tile-block-alignment",
             "fwd row tile owns whole quantization blocks",
             "fwd", _fwd_block_alignment),
    Contract("tile-bounds", "fwd tiles stay inside the padded operand",
             "fwd", _fwd_index_bounds),
    Contract("vmem-budget", "fwd tile working set fits VMEM",
             "fwd", _fwd_vmem_budget),
    Contract("tile-block-alignment",
             "bwd row tile owns whole quantization blocks",
             "bwd", _bwd_block_alignment),
    Contract("tile-bounds", "bwd tiles stay inside the operand",
             "bwd", _bwd_index_bounds),
    Contract("vmem-budget", "bwd row tile fits VMEM",
             "bwd", _bwd_vmem_budget),
)


def parse_entry(key: str, tiles) -> Entry | None:
    m = _KEY_RE.match(key)
    if m is None or not (isinstance(tiles, (list, tuple))
                         and len(tiles) == 2):
        return None
    return Entry(key=key, kind=m["kind"], m=int(m["m"]), d=int(m["d"]),
                 n=int(m["n"]), bits=int(m["bits"]),
                 group_size=int(m["group"]), device=m["device"],
                 t0=int(tiles[0]), t1=int(tiles[1]))


def check_entry(key: str, tiles) -> list[Finding]:
    e = parse_entry(key, tiles)
    if e is None:
        return [Finding(PASS, "tile-key", key,
                        f"unparseable tile choice (tiles={tiles!r}); "
                        "expected kind/MxDxN/bBITS/gGROUP/device_kind -> "
                        "[t0, t1]")]
    out = []
    for c in CONTRACTS:
        if c.applies not in ("any", e.kind):
            continue
        msg = c.check(e)
        if msg is not None:
            out.append(Finding(PASS, c.rule, key, msg))
    return out


def tile_key(kind: str, m: int, d: int, n: int, bits: int,
             group_size: int, device_kind: str = TARGET_KIND) -> str:
    return f"{kind}/{m}x{d}x{n}/b{bits}/g{group_size}/{device_kind}"


def check_tile_choices(shapes) -> list[Finding]:
    """Every contract over the autotuner's fwd and bwd picks for each
    ``(m, d, n, bits, group_size)`` fused matmul shape."""
    out = []
    for shape in sorted(set(shapes)):
        for kind in ("fwd", "bwd"):
            out.extend(check_entry(tile_key(kind, *shape),
                                   get_tiles(kind, *shape, TARGET_KIND)))
    return out


def check_compression_config(cfg, stash_width: int,
                             where: str) -> list[Finding]:
    """quant_blockwise / rp_matmul preconditions for one layer config."""
    out = []
    reason = backend.quant_kernel_unsupported(cfg.bits, cfg.group_size,
                                              cfg.levels())
    if reason is not None:
        out.append(Finding(PASS, "quant-precondition", where, reason))
    if cfg.rp_ratio > 1 and stash_width % cfg.rp_ratio:
        out.append(Finding(
            PASS, "rp-precondition", where,
            f"rp_matmul projects the last dim {stash_width} by "
            f"rp_ratio={cfg.rp_ratio}, which does not divide it"))
    return out


def check_matrix_configs() -> list[Finding]:
    """Every (layer config × stash width) the plan matrix would launch."""
    from repro.graph.models import _dims
    from repro.staticcheck.matrix import audit_matrix

    out, seen = [], set()
    for case in audit_matrix():
        dims = _dims(case.cfg, case.in_dim)
        for li, (d_in, comp) in enumerate(
                zip(dims[:-1], case.cfg.layer_compression())):
            if comp is None:
                continue
            lin_in = d_in * (2 if case.cfg.arch == "sage" else 1)
            sig = (comp, lin_in)
            if sig in seen:
                continue
            seen.add(sig)
            out.extend(check_compression_config(
                comp, lin_in, f"{case.key}/layer{li}"))
    return out


def matrix_fused_shapes() -> list[tuple[int, int, int, int, int]]:
    """``(m, d, n, bits, group_size)`` of every fused matmul the plan
    matrix's ``fused='on'`` cells launch."""
    from repro.graph.models import _dims
    from repro.staticcheck.matrix import audit_matrix

    out = []
    for case in audit_matrix():
        if case.plan.kernel.fused != "on":
            continue
        dims = _dims(case.cfg, case.in_dim)
        for d_in, d_out, comp in zip(dims[:-1], dims[1:],
                                     case.cfg.layer_compression()):
            lin_in = d_in * (2 if case.cfg.arch == "sage" else 1)
            if comp is not None and backend.supports_fused(
                    (case.live_nodes, lin_in), comp.bits, comp.group_size):
                out.append((case.live_nodes, lin_in, d_out, comp.bits,
                            comp.group_size))
    return out


#: The full-width fused layers of GraphSAGE on arxiv (169,343 nodes, 128
#: features, hidden (256, 256), 40 classes) at INT2, G=128 — the shapes
#: the chip smoke trains.
ARXIV_FUSED_SHAPES = tuple((169343, d, n, 2, 128)
                           for d, n in ((256, 256), (512, 256), (512, 40)))


def run() -> list[Finding]:
    return (check_tile_choices([*matrix_fused_shapes(),
                                *ARXIV_FUSED_SHAPES])
            + check_matrix_configs())
