"""Kernel-backend dispatch for the whole compression stack.

This module owns the *execution strategy* of every compress/decompress
primitive.  Callers (``core.compressor``, and through it the GNN models,
the transformer ``compressed_block`` path and the benchmarks) never pick a
kernel themselves — they name an ``impl`` and this layer routes:

  * ``"jnp"``     — the pure-jnp reference path (``repro.kernels.ref``)
  * ``"interp"``  — Pallas interpret mode (CPU validation of the kernels)
  * ``"pallas"``  — real Pallas lowering (the TPU deployment path)
  * ``"auto"``    — pallas on TPU, jnp elsewhere; unsupported shapes fall
                    back to jnp instead of erroring

All impls produce **bit-identical packed words** for quantize+pack (the SR
noise is a counter hash and the strided pack layout is shared; see
``tests/test_backend.py`` for the parity gate).  Random projection is a
float matmul, so impls agree to float tolerance, not bit-exactly — RP
routing is therefore best-effort: shapes that don't meet the Pallas tile
constraints use the jnp matmul (:func:`rp_kernel_unsupported` says why).

Static-argument discipline: VM level tables are normalized to *hashable
tuples of python floats* before they reach ``pallas_call`` (the kernels
unroll them into compare/select chains).  Passing a traced array as a
level table is an error by construction.

``use_impl`` installs a trace-time override (operator switch) that takes
precedence over per-config ``impl`` fields.  It affects *tracing* — an
already-compiled jit executable is not retraced when the override changes.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from repro.core import quant as quantmod
from repro.kernels import ops

VALID_IMPLS = ("auto", "jnp", "interp", "pallas")
VALID_FUSED = ("auto", "on", "off")

_OVERRIDE: list[str] = []  # stack managed by use_impl()


@functools.lru_cache(maxsize=1)
def _platform() -> str:
    """Memoized ``jax.default_backend()`` — ``auto`` resolution sits on
    every compress/decompress trace, and the platform cannot change
    within a process, so probe the backend exactly once."""
    return jax.default_backend()


def _check_impl(impl: str) -> str:
    if impl not in VALID_IMPLS:
        raise ValueError(f"impl={impl!r} not in {VALID_IMPLS}")
    return impl


@contextlib.contextmanager
def use_impl(impl: str | None):
    """Trace-time backend override; ``None`` is a no-op (plumbing-friendly)."""
    if impl is None:
        yield
        return
    _OVERRIDE.append(_check_impl(impl))
    try:
        yield
    finally:
        _OVERRIDE.pop()


def current_override() -> str | None:
    return _OVERRIDE[-1] if _OVERRIDE else None


def resolve_impl(impl: str = "auto") -> str:
    """Concrete impl after applying the ``use_impl`` override and ``auto``."""
    impl = _check_impl(_OVERRIDE[-1] if _OVERRIDE else impl)
    if impl == "auto":
        return "pallas" if _platform() == "tpu" else "jnp"
    return impl


def available_impl(impl: str) -> str:
    """Downgrade a *recorded* concrete impl to one runnable on this host.

    ``CompressedTensor.impl`` may say "pallas" in a checkpoint written on
    TPU; all impls are bit-identical, so restoring on a CPU host should
    quietly re-route through ``auto`` rather than fail to lower.
    """
    if impl == "pallas" and _platform() != "tpu":
        return "auto"
    return impl


# ------------------------------------------------------------- level tables
def normalize_levels(levels):
    """Coerce a VM level table to a static hashable tuple of floats.

    Single definition lives next to the kernels (the consumer that makes
    the static-tuple requirement real) — this delegates at *call* time
    rather than aliasing at import time, because this module sits inside
    the core<->kernels import cycle: entering the cycle from the
    ``repro.kernels`` side reaches here while ``ops`` is still
    half-initialized, and an eager ``ops.static_levels`` lookup crashes.
    """
    return ops.static_levels(levels)


# ----------------------------------------------------------------- routing
def quant_kernel_unsupported(bits: int, group_size: int,
                             levels) -> str | None:
    """Why the fused quant kernel can't run this config (None = it can)."""
    if 32 % bits:
        return f"bits={bits} does not divide 32"
    vpw = 32 // bits
    if group_size % vpw:
        return (f"group_size={group_size} is not a multiple of the "
                f"{vpw} codes-per-word pack width")
    if levels is not None and len(levels) > 16:
        return (f"VM table has {len(levels)} levels; the unrolled kernel "
                "chain supports at most 16 (bits <= 4)")
    return None


def route_quant(impl: str, bits: int, group_size: int, levels=None) -> str:
    """Concrete impl for quantize/dequantize.

    ``auto`` falls back to jnp when the kernel can't run the config; an
    *explicitly* requested kernel impl raises instead — the parity contract
    must never be silently narrowed.
    """
    requested = _check_impl(_OVERRIDE[-1] if _OVERRIDE else impl)
    concrete = resolve_impl(requested)
    if concrete == "jnp":
        return "jnp"
    reason = quant_kernel_unsupported(bits, group_size,
                                      normalize_levels(levels))
    if reason is None:
        return concrete
    if requested == "auto":
        return "jnp"
    raise ValueError(f"impl={requested!r} cannot run this config: {reason}")


# ----------------------------------------------------------- fused routing
def fused_unsupported(shape, bits: int, group_size: int,
                      levels=None) -> str | None:
    """Why the fused matmul+quant kernels can't run this stash (None =
    they can).  THE eligibility check — dispatch, the engine forward,
    the benchmarks, and the tests all call this one predicate (or its
    boolean face :func:`supports_fused`); it may not be re-derived
    anywhere else.

    Eligibility means the quantization blocks of the stashed operand
    coincide with whole kernel row tiles:

    * the base quant-kernel constraints hold (bits divides 32, pack-width
      divides the group, VM table fits the unrolled chain);
    * the operand is a 2-D (M, D) matrix (that is what the matmul sees);
    * blocks align to rows — ``D % G == 0`` (whole blocks per row) or
      ``G % D == 0`` (whole rows per block) — and the element count is
      whole blocks (``M*D % G == 0``), since the fused pad appends zero
      *rows* and cannot reproduce the reference replicate-padded ragged
      tail inside a real block.
    """
    reason = quant_kernel_unsupported(bits, group_size,
                                      normalize_levels(levels))
    if reason is not None:
        return reason
    if len(shape) != 2:
        return f"fused matmul needs a 2-D operand, got shape {shape}"
    m, d = int(shape[0]), int(shape[1])
    if d % group_size and group_size % d:
        return (f"blocks (G={group_size}) straddle rows of width {d}: "
                "need D % G == 0 or G % D == 0")
    if (m * d) % group_size:
        return (f"{m}x{d} is not whole blocks of {group_size} (the ragged "
                "tail needs the reference replicate-padding)")
    return None


def supports_fused(shape, bits: int, group_size: int, levels=None) -> bool:
    """Boolean face of :func:`fused_unsupported`."""
    return fused_unsupported(shape, bits, group_size, levels) is None


def fused_decline_reason(fused: str, impl: str, shape, bits: int,
                         group_size: int, levels=None,
                         rp_ratio: int = 0) -> str | None:
    """Why :func:`route_fused` keeps the unfused two-pass spelling for
    this layer (None = it fuses)."""
    if fused == "off":
        return "fused='off'"
    reason = fused_unsupported(shape, bits, group_size, levels)
    if reason is None and rp_ratio > 1:
        reason = (f"rp_ratio={rp_ratio} projects before quantization; the "
                  "fused epilogue quantizes the matmul operand itself")
    if reason is None and fused == "auto":
        concrete = resolve_impl(impl)
        if concrete != "pallas":
            reason = (f"fused='auto' fuses on the Pallas backend only; impl "
                      f"resolves to {concrete!r}")
    return reason


def route_fused(fused: str, impl: str, shape, bits: int, group_size: int,
                levels=None, rp_ratio: int = 0) -> str | None:
    """Concrete impl the fused matmul-quant pair should run on, or None
    for the unfused per-layer fallback.

    ``fused="off"`` never fuses.  ``fused="auto"`` fuses only where it
    wins: eligible shapes on a real kernel backend (resolved "pallas");
    the jnp/interp reference paths keep the unfused spelling.
    ``fused="on"`` forces the fused pair on whatever ``impl`` resolves
    to (the jnp resolution runs the fused *composition* — same bits,
    useful for parity tests) and raises on ineligible configs instead of
    silently narrowing the contract.
    """
    if fused not in VALID_FUSED:
        raise ValueError(f"fused={fused!r} not in {VALID_FUSED}")
    reason = fused_decline_reason(fused, impl, shape, bits, group_size,
                                  levels, rp_ratio)
    if reason is None:
        return resolve_impl(impl)
    if fused == "on":
        raise ValueError(f"fused='on' cannot run this config: {reason}")
    return None


def rp_kernel_unsupported(d_in: int, d_out: int, *, tn: int = 128,
                          tk: int = 128) -> str | None:
    if d_out % tn or d_in % tk:
        return (f"rp dims ({d_in}->{d_out}) not multiples of the "
                f"({tk},{tn}) tile")
    return None


def route_rp(impl: str, d_in: int, d_out: int, *, tn: int = 128,
             tk: int = 128) -> str:
    """Concrete impl for RP/IRP — best-effort (jnp fallback, never raises).

    RP across impls agrees to float tolerance only (matmul accumulation
    order), so forcing a kernel here buys no bit-parity; shapes off the
    tile grid take the reference matmul, and
    :func:`rp_kernel_unsupported` says why.
    """
    concrete = resolve_impl(impl)
    if concrete == "jnp":
        return "jnp"
    if rp_kernel_unsupported(d_in, d_out, tn=tn, tk=tk):
        return "jnp"
    return concrete


# ------------------------------------------------------------ block helpers
def to_blocks(x: jnp.ndarray, group_size: int) -> tuple[jnp.ndarray, int]:
    """Flatten + regroup into (n_blocks, G) with replicate tail padding.

    The *within-block* tail is padded by replicating the last element
    (cannot widen the final block's [min, max] envelope — zeros would).
    Whole-row padding to the kernel tile (the kernel wrappers'
    ``pad_to_multiple``) happens below this layer and only ever appends
    fake blocks that are sliced off, so real block stats are never
    contaminated.
    """
    return quantmod.group_reshape(x, group_size)


def from_blocks(blocks: jnp.ndarray, shape: tuple[int, ...],
                dtype=jnp.float32) -> jnp.ndarray:
    """Drop tail padding and restore the original shape."""
    n = 1
    for s in shape:
        n *= s
    return blocks.reshape(-1)[:n].reshape(shape).astype(dtype)


# -------------------------------------------------------------- primitives
def quantize_blocks(blocks, bits: int, seed, levels=None, *,
                    impl: str = "auto", rows_per_tile: int = 8):
    """(n_blocks, G) f32 -> (packed u32, zero (n,), rng (n,))."""
    concrete = route_quant(impl, bits, blocks.shape[-1], levels)
    return ops.quantize_packed(blocks, bits, seed, normalize_levels(levels),
                               impl=concrete, rows_per_tile=rows_per_tile)


def dequantize_blocks(packed, zero, rng, bits: int, group_size: int,
                      levels=None, *, impl: str = "auto",
                      rows_per_tile: int = 8):
    """(packed, zero (n,), rng (n,)) -> (n_blocks, G) f32."""
    concrete = route_quant(impl, bits, group_size, levels)
    return ops.dequantize_packed(packed, zero, rng, bits, group_size,
                                 normalize_levels(levels), impl=concrete,
                                 rows_per_tile=rows_per_tile)


def matmul_quantize(x2d, w, bits: int, seed, levels=None, *,
                    impl: str, group_size: int, tm: int | None = None,
                    tn: int | None = None):
    """Fused ``y = x @ w`` + quantize/pack ``x`` in the epilogue.

    ``impl`` must already be a *routed concrete* impl (the return value
    of :func:`route_fused`); this layer only normalizes the level table
    and forwards tile choices to the autotuned kernel entry.
    """
    return ops.matmul_quantize_packed(x2d, w, bits, seed,
                                      normalize_levels(levels), impl=impl,
                                      group_size=group_size, tm=tm, tn=tn)


def dequant_matmul(packed, zero, rng, g2d, bits: int, group_size: int,
                   d: int, levels=None, *, impl: str,
                   tile_rows: int | None = None, tn: int | None = None):
    """Fused ``dw = dequant(packed)ᵀ @ g`` (backward-prologue dequant)."""
    return ops.dequant_matmul_packed(packed, zero, rng, g2d, bits,
                                     group_size, d,
                                     normalize_levels(levels), impl=impl,
                                     tile_rows=tile_rows, tn=tn)


def rp(x, seed, d_out: int, *, impl: str = "auto"):
    """Project the last dim D -> d_out (any leading rank)."""
    d_in = x.shape[-1]
    concrete = route_rp(impl, d_in, d_out)
    lead = x.shape[:-1]
    out = ops.rp_project(x.reshape(-1, d_in), seed, d_out, impl=concrete)
    return out.reshape(*lead, d_out)


def irp(x, seed, d_in: int, *, impl: str = "auto"):
    """Recover the last dim r -> d_in (any leading rank)."""
    r = x.shape[-1]
    concrete = route_rp(impl, d_in, r)  # kernel reads (d_in x r) transposed
    lead = x.shape[:-1]
    out = ops.irp_project(x.reshape(-1, r), seed, d_in, impl=concrete)
    return out.reshape(*lead, d_in)
