"""Seeded Rademacher random-projection matmul (beyond-paper optimization).

``y = x @ R(seed)`` and ``y = x @ R(seed)ᵀ`` where R is *never materialized
in HBM*: each (TK, TN) tile of R is regenerated inside the kernel from the
murmur3 counter hash (bit-identical to ``repro.core.random_projection.
rp_matrix``), scaled 1/√r, and fed straight to the MXU.  Removes the D×R
fp32 parameter from memory and its HBM reads on every projection — on the
roofline this converts RP from memory-bound to compute-bound.

Grid is (M/TM, N/TN, D/TK) with K innermost; the f32 output tile accumulates
across K steps (init at k == 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.prng import rademacher_from_counter
from repro.kernels.quant_blockwise import pad_to_multiple


def _rp_kernel(seed_ref, x_ref, o_ref, *, tk: int, tn: int,
               r_dim: int, transpose: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)                        # (TM, TK)
    r0 = (k * tk)
    c0 = (pl.program_id(1) * tn)
    rid = jax.lax.broadcasted_iota(jnp.uint32, (tk, tn), 0) + jnp.uint32(r0)
    cid = jax.lax.broadcasted_iota(jnp.uint32, (tk, tn), 1) + jnp.uint32(c0)
    if transpose:
        # tile of Rᵀ: element (p, d) = R[d, p] = sign(hash(d * r_dim + p))
        counter = cid * jnp.uint32(r_dim) + rid
    else:
        # tile of R: element (d, p) = sign(hash(d * r_dim + p))
        counter = rid * jnp.uint32(r_dim) + cid
    signs = rademacher_from_counter(seed_ref[0, 0], counter)
    r = signs * jnp.float32(1.0 / (r_dim ** 0.5))
    o_ref[...] += jnp.dot(x, r, preferred_element_type=jnp.float32)


def _call(x2d, seed, n_out: int, r_dim: int, transpose: bool,
          tm: int, tn: int, tk: int, interpret: bool):
    m, d = x2d.shape
    assert d % tk == 0 and n_out % tn == 0, (d, n_out)
    # ragged rows are zero rows of the padded operand, sliced off below
    x2d = pad_to_multiple(x2d, tm)
    seed_arr = jnp.asarray(seed, jnp.uint32).reshape(1, 1)
    kern = functools.partial(_rp_kernel, tk=tk, tn=tn, r_dim=r_dim,
                             transpose=transpose)
    return pl.pallas_call(
        kern,
        grid=(x2d.shape[0] // tm, n_out // tn, d // tk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x2d.shape[0], n_out), jnp.float32),
        interpret=interpret,
        name="irp_kernel" if transpose else "rp_kernel",
    )(seed_arr, x2d)[:m]


def rp_project_call(x2d, seed, d_out: int, *, tm=128, tn=128, tk=128,
                    interpret: bool = False):
    """x (M, D) @ R(seed) (D, d_out);  R normalized by 1/√d_out."""
    return _call(x2d, seed, d_out, d_out, False, tm, tn, tk, interpret)


def irp_project_call(x2d, seed, d_in: int, *, tm=128, tn=128, tk=128,
                     interpret: bool = False):
    """x (M, r) @ R(seed)ᵀ (r, d_in);  same R as the forward projection."""
    r_dim = x2d.shape[1]
    return _call(x2d, seed, d_in, r_dim, True, tm, tn, tk, interpret)
