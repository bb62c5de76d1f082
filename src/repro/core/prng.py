"""Counter-based deterministic RNG used by stochastic rounding.

Both the pure-jnp reference path and the Pallas kernels draw their rounding
noise from this hash, so codes are bit-identical across paths (tests assert
exact equality, not allclose).  The hash is the murmur3 finalizer — cheap,
vectorizes to VPU ops on TPU, and runs unchanged in ``interpret=True``.
"""
from __future__ import annotations

import jax.numpy as jnp

import numpy as np

# numpy-scalar constants: inlined as literals at trace time so Pallas kernels
# don't capture closure arrays (python ints > int32 max would overflow).
_M1 = np.uint32(0x85EB_CA6B)
_M2 = np.uint32(0xC2B2_AE35)
_GOLDEN = np.uint32(0x9E37_79B9)
_RADEMACHER_SALT = np.uint32(0x517C_C1B7)

#: Knuth's 32-bit multiplicative-hash constant (⌊2³²/φ⌋, odd).  The single
#: home for every derived-stream multiply outside the murmur3 mix above:
#: autoprec probe seeds and the LM per-step activation seed
#: (:mod:`repro.engine.seeds`) and the offload callback-store tickets
#: (:mod:`repro.offload.engine`) all hash through this constant.  It lives
#: here — not in ``engine.seeds`` — because ``repro.offload`` must not
#: import the engine package (``engine.plan`` imports ``offload.engine``).
KNUTH_MULT = np.uint32(2654435761)


def hash_u32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 fmix32 over a uint32 array."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = (x * _M1).astype(jnp.uint32)
    x = x ^ (x >> 13)
    x = (x * _M2).astype(jnp.uint32)
    x = x ^ (x >> 16)
    return x


def uniform_from_counter(seed, counter: jnp.ndarray) -> jnp.ndarray:
    """U[0,1) floats from (seed, uint32 counter array).

    24 mantissa bits — exactly representable in float32.  ``seed`` is
    normally a scalar (the kernel path); an array seed broadcastable
    against ``counter`` selects a distinct stream per element (the
    wraparound-safe 64-bit counter path in :mod:`repro.core.quant`) and
    is bit-identical to the scalar path wherever the values coincide.
    """
    seed = jnp.asarray(seed, jnp.uint32)
    hs = hash_u32(seed.reshape(1) if seed.ndim == 0 else seed)
    mixed = hash_u32((counter.astype(jnp.uint32) * _GOLDEN).astype(jnp.uint32)
                     + hs)
    # via int32: Mosaic has no uint32 -> float32 cast, and a 24-bit draw
    # is exact in both
    return ((mixed >> 8).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


def rademacher_from_counter(seed, counter: jnp.ndarray) -> jnp.ndarray:
    """±1 float32 signs from (scalar seed, uint32 counter array).

    Selected in float32: Mosaic cannot multiply int8 vectors, so integer
    sign arithmetic would not lower inside the RP kernel.
    """
    seed = jnp.asarray(seed, jnp.uint32)
    mixed = hash_u32((counter.astype(jnp.uint32) * _GOLDEN).astype(jnp.uint32)
                     + hash_u32(seed.reshape(1) + jnp.uint32(_RADEMACHER_SALT)))
    return jnp.where((mixed & 1) == 0, jnp.float32(1.0), jnp.float32(-1.0))
