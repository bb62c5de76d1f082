"""Public jit'd entry points for the compression kernels.

``impl`` selects the path:
  * "pallas"  — real TPU lowering (the deployment path)
  * "interp"  — Pallas interpret mode (CPU correctness validation)
  * "jnp"     — the pure-jnp reference (fast on CPU; same bits)
  * "auto"    — pallas on TPU, jnp elsewhere

All paths return bit-identical packed words / codes — the SR noise is a
counter hash and the pack layout is shared (see quant_blockwise.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import fused_matmul as fk
from repro.kernels import ref as refmod
from repro.kernels import quant_blockwise as qk
from repro.kernels import rp_matmul as rk


@functools.lru_cache(maxsize=1)
def _platform() -> str:
    # memoized: the platform cannot change within a process, and this
    # sits on every trace of every dispatched primitive
    return jax.default_backend()


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _platform() == "tpu" else "jnp"
    return impl


def static_levels(levels):
    """Coerce a VM level table to a static hashable tuple of floats.

    The kernels unroll the table into compare/select chains at trace time —
    it must be a compile-time constant; a traced array here would silently
    bake in garbage or fail deep inside Pallas, so reject it with a usable
    error instead.  This is the single definition (``core.backend``
    re-exports it as ``normalize_levels``) so the jnp and Pallas paths
    cannot drift.
    """
    if levels is None:
        return None
    if isinstance(levels, jax.core.Tracer):
        raise TypeError(
            "VM level tables must be static (tuple of floats), not traced "
            "arrays — pass CompressionConfig.levels() through unchanged.")
    if isinstance(levels, (tuple, list)):
        return tuple(float(l) for l in levels)
    import numpy as np

    return tuple(float(l) for l in np.asarray(levels).reshape(-1))


def quantize_packed(x2d, bits: int, seed, levels=None, *, impl: str = "auto",
                    rows_per_tile: int = 8):
    """(n_blocks, G) -> (packed u32, zero (n,), rng (n,))."""
    impl = _resolve(impl)
    levels = static_levels(levels)
    if impl == "jnp":
        return refmod.quantize_packed(x2d, bits, seed, levels)
    packed, zero, rng = qk.quant_pack_call(
        x2d, bits, seed, levels, rows_per_tile=rows_per_tile,
        interpret=(impl == "interp"))
    return packed, zero[:, 0], rng[:, 0]


def dequantize_packed(packed, zero, rng, bits: int, group_size: int,
                      levels=None, *, impl: str = "auto",
                      rows_per_tile: int = 8):
    """(packed, zero (n,), rng (n,)) -> (n_blocks, G) f32."""
    impl = _resolve(impl)
    levels = static_levels(levels)
    if impl == "jnp":
        return refmod.dequantize_packed(packed, zero, rng, bits, group_size, levels)
    return qk.dequant_unpack_call(packed, zero[:, None], rng[:, None], bits,
                                  group_size, levels,
                                  rows_per_tile=rows_per_tile,
                                  interpret=(impl == "interp"))


# ------------------------------------------------------- fused matmul+quant
def _fused_tiles(kind: str, m: int, d: int, n: int, bits: int,
                 group_size: int, tm, tn):
    """Resolve tile sizes: explicit args win, else the autotuner's pick
    for this device (lazy import keeps ops light for non-fused callers)."""
    from repro.kernels import autotune

    auto_tm, auto_tn = autotune.get_tiles(kind, m, d, n, bits, group_size,
                                          autotune.tile_device_kind())
    return (tm if tm is not None else auto_tm,
            tn if tn is not None else auto_tn)


def matmul_quantize_packed(x2d, w, bits: int, seed, levels=None, *,
                           impl: str = "auto", group_size: int,
                           tm: int | None = None, tn: int | None = None):
    """Fused forward: ``y = x @ w`` with ``x`` quantized+packed in the
    epilogue.  Returns ``(y (M, N), packed u32, zero (nb,), rng (nb,))``
    — the stash triplet bit-identical to ``quantize_packed`` on the same
    ``x`` reshaped to whole blocks.

    Caller guarantees eligibility (``core.backend.supports_fused``):
    ``x.size % group_size == 0`` and blocks never straddle rows unless
    rows evenly divide into blocks (``d % G == 0`` or ``G % d == 0``).
    """
    impl = _resolve(impl)
    levels = static_levels(levels)
    m, d = x2d.shape
    n = w.shape[1]
    assert (m * d) % group_size == 0, (x2d.shape, group_size)
    if impl == "jnp":
        # reference composition — bit-identical by definition (this IS the
        # unfused path in one call)
        y = x2d.astype(jnp.float32) @ w.astype(jnp.float32)
        packed, zero, rng = refmod.quantize_packed(
            x2d.astype(jnp.float32).reshape(-1, group_size), bits, seed,
            levels)
        return y, packed, zero, rng
    tm, tn = _fused_tiles("fwd", m, d, n, bits, group_size, tm, tn)
    y, packed, zero, rng = fk.matmul_quant_call(
        x2d, w, bits, seed, levels, group_size=group_size, tm=tm, tn=tn,
        interpret=(impl == "interp"))
    return y, packed, zero[:, 0], rng[:, 0]


def dequant_matmul_packed(packed, zero, rng, g2d, bits: int,
                          group_size: int, d: int, levels=None, *,
                          impl: str = "auto", tile_rows: int | None = None,
                          tn: int | None = None):
    """Fused backward: ``dw = dequant(packed)ᵀ @ g`` for an (M, d) stash.

    The kernel unpacks+dequantizes the stashed tile as the prologue of
    the backward matmul.  Where the autotuner runs a single row tile (the
    whole operand fits VMEM) the result is bit-identical (up to the sign
    of exact zeros) to the unfused ``dequantize_packed`` → reshape →
    ``x̂ᵀ @ g``; larger operands are row-tiled and tree-reduced.
    """
    impl = _resolve(impl)
    levels = static_levels(levels)
    m, n = g2d.shape
    assert packed.shape[0] * group_size == m * d, (packed.shape, m, d)
    if impl == "jnp":
        x_hat = refmod.dequantize_packed(packed, zero, rng, bits,
                                         group_size, levels)
        return x_hat.reshape(m, d).T @ g2d.astype(jnp.float32)
    tile_rows, tn = _fused_tiles("bwd", m, d, n, bits, group_size,
                                 tile_rows, tn)
    return fk.dequant_matmul_call(packed, zero[:, None], rng[:, None], g2d,
                                  bits, group_size, d, levels,
                                  tile_rows=tile_rows, tn=tn,
                                  interpret=(impl == "interp"))


def rp_project(x2d, seed, d_out: int, *, impl: str = "auto",
               tm: int = 128, tn: int = 128, tk: int = 128):
    impl = _resolve(impl)
    if impl == "jnp":
        return refmod.rp_project(x2d, seed, d_out)
    assert d_out % tn == 0 and x2d.shape[1] % tk == 0, \
        "rp_project pallas path needs D, d_out multiples of the tile"
    return rk.rp_project_call(x2d, seed, d_out, tm=tm, tn=tn, tk=tk,
                              interpret=(impl == "interp"))


def irp_project(x2d, seed, d_in: int, *, impl: str = "auto",
                tm: int = 128, tn: int = 128, tk: int = 128):
    impl = _resolve(impl)
    if impl == "jnp":
        return refmod.irp_project(x2d, seed, d_in)
    assert d_in % tn == 0 and x2d.shape[1] % tk == 0, \
        "irp_project pallas path needs r, D multiples of the tile"
    return rk.irp_project_call(x2d, seed, d_in, tm=tm, tn=tn, tk=tk,
                               interpret=(impl == "interp"))
