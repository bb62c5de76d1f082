"""Tile sizes for the fused matmul-quant kernels, computed in code.

A **roofline model** ranks every legal ``(tm, tn)`` candidate of the
fused forward by predicted time — max of the compute term and the HBM
term, where larger ``tm`` cuts repeated ``w`` reads and larger ``tn``
cuts repeated ``x`` reads — subject to a VMEM budget.  The rates come
from the ``device_kind``-keyed peak table (:mod:`repro.kernels.peaks`);
an unlisted kind raises rather than ranking against another chip.

The fused backward takes the largest row tile whose working set fits the
same budget: ``tile_rows == m`` (one tile, the bit-exact order) while it
fits, else the largest fixed row tile, with ragged rows zero-padded by
the kernel wrapper.  Fewer row tiles mean fewer ``(D, TN)`` partials
written and tree-reduced, so on the roofline the largest fitting tile
is always the fastest.

:func:`get_tiles` is the trace-time read path the dispatch layer uses.
Nothing is read from disk, so a fresh checkout picks the same tiles as
the machine that wrote it.

Legality: a row tile must own whole quantization blocks —
``(tm * d) % group_size == 0`` — which is the same invariant
:func:`repro.core.backend.supports_fused` enforces for the shape overall.
"""
from __future__ import annotations

from repro.kernels.fused_matmul import row_tile_step
from repro.kernels.peaks import TARGET_KIND, chip_peaks

#: Mosaic's default scoped-VMEM limit per kernel on v5e (bytes).  The
#: working-set models below must stay under it or the compile fails.
VMEM_BUDGET = 16 << 20

#: Row tiles the fused backward tries, largest first, after ``m`` itself.
BWD_ROW_TILES = (4096, 2048, 1024, 512, 256, 128)

#: VMEM bytes per tile element for the quantize/dequantize temporaries
#: (normalized values, noise, codes, the block regroup and the unpacked
#: code planes).  Fit to described-v5e compiles at 169,343 rows: every
#: tile this model admits compiled, and every tile Mosaic refused
#: (bits 2 and 8, G 128 and 256, D 128 to 512) is over the budget.
_TEMP_BYTES_PER_ELEM = 48


def _lanes(n: int) -> int:
    """``n`` rounded up to whole 128-lane vector rows."""
    return -(-n // 128) * 128


def _block_rows_bytes(n_blocks: int, bits: int, group_size: int) -> int:
    """Packed words plus the (zero, range) columns of ``n_blocks`` blocks:
    each is a lane-sparse row padded to whole 128-lane rows in VMEM."""
    return 4 * n_blocks * (_lanes(group_size * bits // 32) + 2 * 128)


def tile_device_kind() -> str:
    """The ``device_kind`` tiles are ranked for: the attached TPU, or the
    deployment target off-TPU (interpret mode then runs the chip's tiles)."""
    import jax

    dev = jax.devices()[0]
    return dev.device_kind if dev.platform == "tpu" else TARGET_KIND


def fwd_vmem_bytes(tm: int, d: int, tn: int, bits: int,
                   group_size: int) -> int:
    """Fused-forward working set: double-buffered operand, weight and
    output tiles and stash rows, plus the epilogue's temporaries."""
    io = (4 * (tm * d + d * _lanes(tn) + tm * _lanes(tn))
          + _block_rows_bytes(tm * d // group_size, bits, group_size))
    return 2 * io + _TEMP_BYTES_PER_ELEM * tm * d


def bwd_vmem_bytes(tile_rows: int, d: int, tn: int, bits: int,
                   group_size: int) -> int:
    """Fused-backward working set: double-buffered stash rows, gradient
    and partial tiles, plus the prologue's temporaries."""
    io = (4 * (tile_rows * _lanes(tn) + d * _lanes(tn))
          + _block_rows_bytes(tile_rows * d // group_size, bits, group_size))
    return 2 * io + _TEMP_BYTES_PER_ELEM * tile_rows * d


def fwd_candidates(m: int, d: int, n: int, group_size: int, bits: int):
    """Legal (tm, tn) pairs for the fused forward, VMEM-feasible."""
    step = row_tile_step(d, group_size)
    out = []
    for base in (8, 16, 32, 64, 128, 256, 512):
        tm = max(step, step * (base // step)) if step <= base else step
        tm = min(tm, ((m + step - 1) // step) * step)
        for tn in (128, 256, 512):
            tn = min(tn, n)
            if (fwd_vmem_bytes(tm, d, tn, bits, group_size) <= VMEM_BUDGET
                    and (tm, tn) not in out):
                out.append((tm, tn))
    return out or [(step, min(128, n))]


def fwd_roofline_us(m: int, d: int, n: int, tm: int, tn: int,
                    bits: int = 2, device_kind: str = TARGET_KIND) -> float:
    """Predicted fused-forward time (µs) for one (tm, tn) choice."""
    peaks = chip_peaks(device_kind)
    gi = -(-m // tm)
    gj = -(-n // tn)
    flops = 2.0 * m * d * n
    # x read once per N tile, w once per M tile, y written once, packed out
    bytes_moved = (4.0 * m * d * gj + 4.0 * d * n * gi + 4.0 * m * n
                   + m * d * bits / 8 + 8.0 * m * d / 64)
    return max(flops / peaks.flops, bytes_moved / peaks.hbm_bytes_per_s) * 1e6


def bwd_candidates(m: int, d: int, n: int, group_size: int, bits: int):
    """Legal (tile_rows, tn) pairs for the fused backward, largest row
    tile first; every one fits the VMEM budget.

    ``tile_rows = m`` (single tile, the bit-exact order) leads whenever
    it fits; fixed row tiles follow, and the kernel wrapper pads ragged
    ``m`` up to a whole number of them.
    """
    step = row_tile_step(d, group_size)
    tn = min(128, n)
    out = []
    for tile_rows in (m, *BWD_ROW_TILES):
        if tile_rows > m or tile_rows % step:
            continue
        if (bwd_vmem_bytes(tile_rows, d, tn, bits, group_size)
                <= VMEM_BUDGET
                and (tile_rows, tn) not in out):
            out.append((tile_rows, tn))
    return out or [(step, tn)]


def get_tiles(kind: str, m: int, d: int, n: int, bits: int,
              group_size: int, device_kind: str):
    """Tiles for one fused call on ``device_kind``.

    kind "fwd" → roofline-best (tm, tn); kind "bwd" → (tile_rows, tn)
    with the largest row tile that fits VMEM.  Raises ``ValueError`` for
    a ``device_kind`` missing from the peak table.
    """
    chip_peaks(device_kind)
    if kind == "bwd":
        return bwd_candidates(m, d, n, group_size, bits)[0]
    cands = fwd_candidates(m, d, n, group_size, bits)
    return min(cands, key=lambda c: fwd_roofline_us(
        m, d, n, *c, bits=bits, device_kind=device_kind))
