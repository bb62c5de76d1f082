"""Fused block-wise quantize(+SR+pack) / dequantize(+unpack) Pallas kernels.

TPU adaptation of the paper's CUDA quantizer (DESIGN.md §4):

* one VMEM round-trip per direction — stats, normalize, stochastic round,
  and bit-pack all happen on the (ROWS, G) tile in registers/VMEM, vs. the
  four HBM-materializing steps of the reference path;
* blocks ARE the tile rows: ``G`` is the lane dimension, so per-block
  min/max are lane reductions and the strided packing is a shift/or over
  full-lane slices (word ``j`` holds codes ``[j, j+W, ...]``, matching
  ``repro.core.pack``);
* SR noise comes from the murmur3 counter hash on the *global* element
  index, so codes are bit-identical to ``repro.kernels.ref`` for any grid.

VM levels (paper §3.2) arrive as a static tuple and are unrolled into
compare/select chains (≤16 levels, i.e. bits ≤ 4; uniform levels use the
closed-form floor path for any width).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.prng import uniform_from_counter
from repro.core.quant import EPS as _EPS  # single shared clamp constant


def _sr_codes(h, u, bits: int, levels):
    """Stochastic-round normalized h in [0,B] to level indices (uint32).

    Float <-> integer conversions go through int32: Mosaic has no direct
    float32 <-> uint32 cast, and codes (<= 255) are exact in int32.
    """
    if levels is None:
        lo = jnp.floor(h)
        p_up = h - lo
        return (lo.astype(jnp.int32).astype(jnp.uint32)
                + (u < p_up).astype(jnp.uint32))
    # non-uniform (VM) levels: unrolled bin search over a static table
    idx = jnp.zeros(h.shape, jnp.uint32)
    for lv in levels[1:-1]:
        idx = idx + (h >= jnp.float32(lv)).astype(jnp.uint32)
    lo = jnp.full(h.shape, jnp.float32(levels[0]))
    hi = jnp.full(h.shape, jnp.float32(levels[-1]))
    for i, lv in enumerate(levels[:-1]):
        sel = idx == jnp.uint32(i)
        lo = jnp.where(sel, jnp.float32(levels[i]), lo)
        hi = jnp.where(sel, jnp.float32(levels[i + 1]), hi)
    p_up = (h - lo) / jnp.maximum(hi - lo, _EPS)
    return idx + (u < p_up).astype(jnp.uint32)


def _levels_value(codes, bits: int, levels):
    """Map level indices back to level values (f32)."""
    if levels is None:
        return codes.astype(jnp.int32).astype(jnp.float32)
    out = jnp.zeros(codes.shape, jnp.float32)
    for i, lv in enumerate(levels):
        out = jnp.where(codes == jnp.uint32(i), jnp.float32(lv), out)
    return out


def _quant_pack_kernel(seed_ref, x_ref, packed_ref, zero_ref, rng_ref,
                       *, bits: int, group_size: int, rows: int, levels):
    x = x_ref[...].astype(jnp.float32)                      # (rows, G)
    B = jnp.float32(2**bits - 1)
    zero = jnp.min(x, axis=1, keepdims=True)
    rng = jnp.max(x, axis=1, keepdims=True) - zero
    h = jnp.clip((x - zero) / jnp.maximum(rng, _EPS) * B, 0.0, B)

    row0 = (pl.program_id(0) * rows).astype(jnp.uint32)
    rid = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 0) + row0
    cid = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 1)
    u = uniform_from_counter(seed_ref[0, 0], rid * jnp.uint32(group_size) + cid)

    codes = _sr_codes(h, u, bits, levels)
    vpw = 32 // bits
    w = group_size // vpw
    packed = jnp.zeros((x.shape[0], w), jnp.uint32)
    for k in range(vpw):
        packed = packed | (codes[:, k * w:(k + 1) * w] << jnp.uint32(k * bits))
    packed_ref[...] = packed
    zero_ref[...] = zero
    rng_ref[...] = rng


def _dequant_unpack_kernel(packed_ref, zero_ref, rng_ref, out_ref,
                           *, bits: int, group_size: int, levels):
    words = packed_ref[...]                                  # (rows, W)
    vpw = 32 // bits
    mask = jnp.uint32(2**bits - 1)
    parts = [(words >> jnp.uint32(k * bits)) & mask for k in range(vpw)]
    codes = jnp.concatenate(parts, axis=1)                   # (rows, G)
    vals = _levels_value(codes, bits, levels)
    B = jnp.float32(2**bits - 1)
    out_ref[...] = vals * (rng_ref[...] / B) + zero_ref[...]


def pad_to_multiple(x, multiple: int, axis: int = 0):
    """Zero-pad ``x`` along ``axis`` up to a multiple of ``multiple``.

    The kernel wrappers below (and in ``fused_matmul``/``rp_matmul``) pad
    ragged operands to whole tiles themselves, so any row count reaches
    the kernel: real graphs are rarely a multiple of 8, 128 or 512 rows.
    Padded rows are fake blocks or zero rows whose outputs are sliced off.
    """
    pad = (-x.shape[axis]) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def quant_pack_call(x2d, bits: int, seed, levels=None, *,
                    rows_per_tile: int = 8, interpret: bool = False):
    """x2d (n_blocks, G) -> (packed (n, W), zero (n, 1), rng (n, 1)).

    Any ``n_blocks``: the tail tile is zero-padded with fake blocks whose
    outputs are dropped, so real block stats are never touched.
    """
    n, g = x2d.shape
    vpw = 32 // bits
    assert g % vpw == 0, f"group_size {g} must be a multiple of {vpw}"
    xp = pad_to_multiple(x2d, rows_per_tile)
    n_pad = xp.shape[0]
    w = g // vpw
    seed_arr = jnp.asarray(seed, jnp.uint32).reshape(1, 1)
    kern = functools.partial(_quant_pack_kernel, bits=bits, group_size=g,
                             rows=rows_per_tile, levels=levels)
    grid = (n_pad // rows_per_tile,)
    packed, zero, rng = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((rows_per_tile, g), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows_per_tile, w), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_tile, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, w), jnp.uint32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(seed_arr, xp)
    return packed[:n], zero[:n], rng[:n]


def dequant_unpack_call(packed, zero, rng, bits: int, group_size: int,
                        levels=None, *, rows_per_tile: int = 8,
                        interpret: bool = False):
    """(packed, zero(n,1), rng(n,1)) -> x_hat (n_blocks, G) f32; any n."""
    n, w = packed.shape
    assert w * (32 // bits) == group_size
    packed, zero, rng = (pad_to_multiple(a, rows_per_tile)
                         for a in (packed, zero, rng))
    n_pad = packed.shape[0]
    kern = functools.partial(_dequant_unpack_kernel, bits=bits,
                             group_size=group_size, levels=levels)
    grid = (n_pad // rows_per_tile,)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows_per_tile, w), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_tile, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows_per_tile, group_size), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, group_size), jnp.float32),
        interpret=interpret,
    )(packed, zero, rng)
    return out[:n]
