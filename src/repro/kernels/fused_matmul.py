"""Fused quantize-in-epilogue matmul / dequantize-in-prologue matmul.

The two kernels that put the Pallas backend on the training hot path
(ROADMAP item 1; ActNN/GACT compress activations *as they are produced*):

* :func:`matmul_quant_call` — ``y = x @ w`` whose **epilogue** computes
  per-block (zero, range) stats over the ``x`` tile, stochastically
  rounds, and bit-packs the codes while the tile is still in VMEM.  The
  unfused path reads ``x`` from HBM twice (matmul, then the separate
  compress pass) and writes the f32 normalized intermediate back out;
  fused, ``x`` is read once and only the packed words leave the chip.
* :func:`dequant_matmul_call` — ``dw = x̂ᵀ @ g`` whose **prologue**
  unpacks + dequantizes the stashed codes tile straight into the matmul
  operand, removing the HBM materialization of the f32 reconstruction
  between the unfused dequantize and the backward matmul.

Bit-parity contract
-------------------
SR codes are bit-identical to the unfused ``ref`` path by construction:
per-block stats are the same lane reductions, SR noise is the same
murmur3 counter hash on the *global* element index (the fused grid offsets
block ids by ``i * blocks_per_row_tile``), and the strided pack layout is
shared with :mod:`repro.kernels.quant_blockwise` (whose ``_sr_codes`` /
``_levels_value`` helpers are reused verbatim).  The forward matmul tile
``(TM, D) @ (D, TN)`` keeps the full contraction in one dot, so ``y`` is
the same per-element reduction as the unfused ``x @ w``.  The backward
contraction over rows is exact when run as a single row tile
(``tile_rows == M``, which the autotuner picks whenever it fits VMEM —
every shape where bit-parity is gated).  Tiling rows splits the
accumulation into per-tile partials combined by a **fixed-order pairwise
tree** (:func:`_tree_sum`): bit-stable run-to-run and across
backends/grid schedules (the order is a pure function of the tile
count), agreeing with the single-tile order to float tolerance.

Eligibility (quantization blocks must coincide with whole row tiles) is
owned by :func:`repro.core.backend.supports_fused`; these kernels assert
the same invariants.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.prng import uniform_from_counter
from repro.core.quant import EPS as _EPS
from repro.kernels.quant_blockwise import (_levels_value, _sr_codes,
                                          pad_to_multiple)


def _quant_epilogue(x, seed, row0_blocks, bits: int, group_size: int,
                    levels):
    """Quantize+pack a (rows, D) tile whose flat layout is whole blocks.

    Returns (packed (nb, W), zero (nb, 1), rng (nb, 1)) with nb =
    rows * D // group_size — exactly the rows this tile owns of the
    global packed array.
    """
    rows, d = x.shape
    nb = rows * d // group_size
    xb = x.reshape(nb, group_size)
    B = jnp.float32(2**bits - 1)
    zero = jnp.min(xb, axis=1, keepdims=True)
    rng = jnp.max(xb, axis=1, keepdims=True) - zero
    h = jnp.clip((xb - zero) / jnp.maximum(rng, _EPS) * B, 0.0, B)
    rid = jax.lax.broadcasted_iota(jnp.uint32, xb.shape, 0) + row0_blocks
    cid = jax.lax.broadcasted_iota(jnp.uint32, xb.shape, 1)
    u = uniform_from_counter(seed, rid * jnp.uint32(group_size) + cid)
    codes = _sr_codes(h, u, bits, levels)
    vpw = 32 // bits
    w = group_size // vpw
    packed = jnp.zeros((nb, w), jnp.uint32)
    for k in range(vpw):
        packed = packed | (codes[:, k * w:(k + 1) * w] << jnp.uint32(k * bits))
    return packed, zero, rng


def _matmul_quant_kernel(seed_ref, x_ref, w_ref, y_ref, packed_ref,
                         zero_ref, rng_ref, *, bits: int, group_size: int,
                         blocks_per_tile: int, levels):
    x = x_ref[...].astype(jnp.float32)                       # (TM, D)
    y_ref[...] = jnp.dot(x, w_ref[...].astype(jnp.float32),
                         preferred_element_type=jnp.float32)

    # the stash outputs depend only on the row tile i: emit once, on the
    # first N-tile visit (the blocks stay resident across j).  program_id
    # must be read outside the pl.when body — inside the cond jaxpr it is
    # not rewritten by interpret mode.
    row0 = (pl.program_id(0) * blocks_per_tile).astype(jnp.uint32)

    @pl.when(pl.program_id(1) == 0)
    def _epilogue():
        packed, zero, rng = _quant_epilogue(
            x, seed_ref[0, 0], row0, bits, group_size, levels)
        packed_ref[...] = packed
        zero_ref[...] = zero
        rng_ref[...] = rng


def row_tile_step(d: int, group_size: int) -> int:
    """Smallest row-tile increment keeping whole blocks per tile."""
    return group_size // math.gcd(group_size, d)


def _whole_block_rows(tile: int, d: int, group_size: int) -> int:
    """``tile`` rounded down to whole quantization blocks (at least one
    step), the legality every fused row tile needs."""
    step = row_tile_step(d, group_size)
    return max(step, (tile // step) * step)


def _build_matmul_quant(m, d, n, bits, group_size, levels, tm, tn,
                        interpret):
    assert m % tm == 0 and n % tn == 0, (m, n, tm, tn)
    assert (tm * d) % group_size == 0, (tm, d, group_size)
    vpw = 32 // bits
    assert group_size % vpw == 0, (group_size, vpw)
    bpt = tm * d // group_size          # packed rows owned by one row tile
    nb = m * d // group_size
    wpb = group_size // vpw
    kern = functools.partial(_matmul_quant_kernel, bits=bits,
                             group_size=group_size, blocks_per_tile=bpt,
                             levels=levels)
    return pl.pallas_call(
        kern,
        grid=(m // tm, n // tn),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((tm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, tn), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
            pl.BlockSpec((bpt, wpb), lambda i, j: (i, 0)),
            pl.BlockSpec((bpt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bpt, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.float32),
            jax.ShapeDtypeStruct((nb, wpb), jnp.uint32),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
        name="matmul_quant",
    )


def matmul_quant_call(x2d, w, bits: int, seed, levels=None, *,
                      group_size: int, tm: int = 128, tn: int = 128,
                      interpret: bool = False):
    """Fused forward: ``y = x @ w`` + quantize/pack ``x`` in the epilogue.

    Returns ``(y (M, N) f32, packed (M*D/G, G*bits/32) u32,
    zero (M*D/G, 1) f32, rng (M*D/G, 1) f32)`` — the stash triplet is
    bit-identical to ``quant_pack_call`` / the jnp reference on the same
    ``x``.  Any ``M`` and ``N``: ``tm`` is rounded to whole blocks, and
    ragged rows/columns are zero-padded to whole tiles (the padded rows
    form fake blocks that are sliced off, never mixed into real ones).
    """
    m, d = x2d.shape
    n = w.shape[1]
    assert (m * d) % group_size == 0, (x2d.shape, group_size)
    tm = _whole_block_rows(tm, d, group_size)
    xp = pad_to_multiple(x2d, tm)
    wp = pad_to_multiple(w, tn, axis=1)
    seed_arr = jnp.asarray(seed, jnp.uint32).reshape(1, 1)
    call = _build_matmul_quant(xp.shape[0], d, wp.shape[1], bits,
                               group_size, levels, tm, tn, interpret)
    y, packed, zero, rng = call(seed_arr, xp, wp)
    nb = m * d // group_size
    return y[:m, :n], packed[:nb], zero[:nb], rng[:nb]


def _dequant_matmul_kernel(packed_ref, zero_ref, rng_ref, g_ref, dw_ref,
                           *, bits: int, group_size: int, rows: int,
                           d: int, levels):
    words = packed_ref[...]                                  # (nb, W)
    vpw = 32 // bits
    mask = jnp.uint32(2**bits - 1)
    parts = [(words >> jnp.uint32(kk * bits)) & mask for kk in range(vpw)]
    codes = jnp.concatenate(parts, axis=1)                   # (nb, G)
    vals = _levels_value(codes, bits, levels)
    B = jnp.float32(2**bits - 1)
    x_hat = (vals * (rng_ref[...] / B) + zero_ref[...]).reshape(rows, d)
    g = g_ref[...].astype(jnp.float32)                       # (rows, TN)
    # each row tile writes its own partial — no cross-iteration += whose
    # summation order the grid schedule would own.  The fixed-order tree
    # reduction over the K partials happens outside the kernel.
    dw_ref[...] = jnp.dot(x_hat.T, g,
                          preferred_element_type=jnp.float32)[None]


def _tree_sum(parts):
    """Fixed-order pairwise reduction over the leading axis.

    Deterministic by construction: level l adds partial ``2i`` to partial
    ``2i+1`` (odd tails ride along unadded), independent of grid schedule
    or backend — the accumulation order is a pure function of K.
    """
    k = parts.shape[0]
    while k > 1:
        half = k // 2
        paired = parts[: 2 * half]
        parts = jnp.concatenate(
            [paired[0::2] + paired[1::2], parts[2 * half:]], axis=0)
        k = parts.shape[0]
    return parts[0]


def dequant_matmul_call(packed, zero, rng, g2d, bits: int, group_size: int,
                        d: int, levels=None, *, tile_rows: int | None = None,
                        tn: int = 128, interpret: bool = False):
    """Fused backward: ``dw = dequant(packed)ᵀ @ g`` (D, N).

    ``packed`` (M*D/G, W) + (zero, rng) (M*D/G, 1) are the stash of an
    (M, D) activation; ``g2d`` is (M, N).  ``tile_rows`` tiles the row
    contraction — ``None`` runs it as ONE tile, whose single dot keeps
    the per-element reduction identical to the unfused ``x̂ᵀ @ g`` (the
    bit-parity configuration, for shapes whose whole operand fits VMEM).
    Smaller tiles (the autotuner's pick once one tile exceeds VMEM) emit
    one ``(D, TN)`` partial per row tile and combine them with
    :func:`_tree_sum`: bit-stable run-to-run and across grid schedules,
    float-tolerance vs the single-tile order.  Any ``M``: ragged rows are
    padded with zero codes of zero range (which decode to exact zeros)
    and zero gradient rows, so they add nothing to ``dw``.
    """
    m, n = g2d.shape
    assert packed.shape[0] * group_size == m * d, (packed.shape, m, d)
    tile_rows = _whole_block_rows(m if tile_rows is None else tile_rows,
                                  d, group_size)
    gp = pad_to_multiple(pad_to_multiple(g2d, tile_rows), tn, axis=1)
    bpt = tile_rows * d // group_size
    packed, zero, rng = (pad_to_multiple(a, bpt)
                         for a in (packed, zero, rng))
    k_tiles = gp.shape[0] // tile_rows
    n_pad = gp.shape[1]
    kern = functools.partial(_dequant_matmul_kernel, bits=bits,
                             group_size=group_size, rows=tile_rows, d=d,
                             levels=levels)
    wpb = group_size // (32 // bits)
    parts = pl.pallas_call(
        kern,
        grid=(n_pad // tn, k_tiles),
        in_specs=[
            pl.BlockSpec((bpt, wpb), lambda j, k: (k, 0)),
            pl.BlockSpec((bpt, 1), lambda j, k: (k, 0)),
            pl.BlockSpec((bpt, 1), lambda j, k: (k, 0)),
            pl.BlockSpec((tile_rows, tn), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((1, d, tn), lambda j, k: (k, 0, j)),
        out_shape=jax.ShapeDtypeStruct((k_tiles, d, n_pad), jnp.float32),
        interpret=interpret,
        name="dequant_matmul",
    )(packed, zero, rng, gp)
    return _tree_sum(parts)[:, :n]
