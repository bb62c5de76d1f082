"""User-facing compression API: config + compress/decompress.

``CompressedTensor`` is the stored form of an activation map: densely packed
codes + per-block (zero, range) + the RP seed if random projection was used.
It is a registered pytree so it can sit in ``custom_vjp`` residuals, scan
carries, and checkpoints.

Execution strategy is owned by :mod:`repro.core.backend` — this module is a
thin orchestrator: RP → fused quantize+pack → store on the way in, and
unpack+dequantize → IRP on the way back.  ``CompressionConfig.impl`` (or a
``backend.use_impl`` override) flips the whole stack between the pure-jnp
reference and the fused Pallas kernels; every impl writes bit-identical
packed words, and the tensor records the concrete backend it was written
with so decompress round-trips under ``custom_vjp`` residuals, scan
carries, and checkpoints even if the override has since been lifted.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import backend
from repro.core.variance import optimize_levels
from repro.obs import scopes


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """How to compress an activation map.

    bits        quantization precision (2 = the paper's INT2 extreme setting)
    group_size  elements per quantization block (paper §3.1).  The paper
                parameterizes this as G/R; we take the absolute element count.
    rp_ratio    D/R random-projection ratio (paper uses 8); 0 disables RP.
    vm          use variance-minimized non-uniform levels (paper §3.2).
    vm_dim      D parameter of CN_[1/D] for level optimization; defaults to
                the *post-RP* quantization block size, i.e.
                ``group_size // rp_ratio`` when RP is on (paper App. C uses
                the projected row dim).  ``None`` means "use the default";
                explicit values < 2 are rejected.
    impl        kernel backend: "auto" | "jnp" | "interp" | "pallas"
                (see :mod:`repro.core.backend`).  One flag flips an entire
                training job between reference and fused kernels.
    """

    bits: int = 2
    group_size: int = 256
    rp_ratio: int = 0
    vm: bool = False
    vm_dim: int | None = None
    impl: str = "auto"

    def cn_dim(self) -> int:
        """The D parameter of the CN_[1/D] activation model.

        An explicit ``vm_dim`` always wins (``None`` is the only sentinel —
        0 is rejected, not silently replaced).  The default follows paper
        App. C: the dimension the clip model sees is the *post-RP* one, so
        with ``rp_ratio > 1`` the block size is divided down by the
        projection ratio.  Clamped to 2 (CN needs Φ⁻¹(1/D) finite).
        """
        if self.vm_dim is not None:
            if self.vm_dim < 2:
                raise ValueError(
                    f"vm_dim must be >= 2 (CN_[1/D] needs 1/D < 1/2), got "
                    f"{self.vm_dim}")
            return int(self.vm_dim)
        d = (self.group_size // self.rp_ratio if self.rp_ratio > 1
             else self.group_size)
        return max(int(d), 2)

    def levels(self) -> tuple[float, ...] | None:
        if not self.vm:
            return None
        return optimize_levels(self.cn_dim(), self.bits)

    def with_impl(self, impl: str) -> "CompressionConfig":
        """Same compression scheme on a different kernel backend."""
        return dataclasses.replace(self, impl=impl)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CompressedTensor:
    packed: jnp.ndarray        # (n_blocks, words_per_block) uint32
    zero: jnp.ndarray          # (n_blocks,) f32
    rng: jnp.ndarray           # (n_blocks,) f32
    rp_seed: jnp.ndarray       # () uint32 (unused if cfg.rp_ratio == 0)
    # --- static ---
    shape: tuple[int, ...]     # original (pre-RP) shape
    dtype: object
    cfg: CompressionConfig
    impl: str = "auto"         # concrete backend the codes were written with

    def tree_flatten(self):
        return (self.packed, self.zero, self.rng, self.rp_seed), (
            self.shape, str(jnp.dtype(self.dtype)), self.cfg, self.impl)

    @classmethod
    def tree_unflatten(cls, aux, children):
        shape, dtype, cfg, impl = aux
        return cls(*children, shape=shape, dtype=jnp.dtype(dtype), cfg=cfg,
                   impl=impl)

    @property
    def nbytes(self) -> int:
        """Exact stored bytes: every child array at its actual itemsize
        (including the ``rp_seed`` scalar), so the ledger in
        ``analysis.saved_bytes_per_layer`` and the arena planner agree
        with the live residuals to the byte."""
        return int(sum(f.size * jnp.dtype(f.dtype).itemsize
                       for f in (self.packed, self.zero, self.rng,
                                 self.rp_seed)))

    @property
    def uncompressed_nbytes(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return int(n * jnp.dtype(self.dtype).itemsize)


def _proj_shape(shape: tuple[int, ...], rp_ratio: int) -> tuple[int, ...]:
    if rp_ratio <= 1:
        return shape
    d = shape[-1]
    assert d % rp_ratio == 0, f"last dim {d} not divisible by rp_ratio {rp_ratio}"
    return (*shape[:-1], d // rp_ratio)


def compress(x: jnp.ndarray, cfg: CompressionConfig, seed,
             impl: str | None = None) -> CompressedTensor:
    """Forward-pass compression: (optional RP) → fused block SR quant+pack.

    ``impl`` overrides ``cfg.impl`` for this call; a ``backend.use_impl``
    context overrides both.
    """
    seed = jnp.asarray(seed, jnp.uint32)
    orig_shape, orig_dtype = tuple(x.shape), x.dtype
    rp_seed = seed ^ jnp.uint32(0xA5A5_A5A5)
    requested = impl if impl is not None else cfg.impl
    levels = cfg.levels()
    impl_q = backend.route_quant(requested, cfg.bits, cfg.group_size, levels)
    with jax.named_scope(scopes.ACT_COMPRESS):
        if cfg.rp_ratio > 1:
            x = backend.rp(x.astype(jnp.float32), rp_seed,
                           x.shape[-1] // cfg.rp_ratio, impl=requested)
        blocks, _ = backend.to_blocks(x.astype(jnp.float32), cfg.group_size)
        packed, zero, rng = backend.quantize_blocks(
            blocks, cfg.bits, seed, levels, impl=impl_q)
    return CompressedTensor(packed, zero, rng, rp_seed,
                            shape=orig_shape, dtype=orig_dtype, cfg=cfg,
                            impl=impl_q)


def compress_matmul(x: jnp.ndarray, w: jnp.ndarray, cfg: CompressionConfig,
                    seed, impl: str | None = None, fused: str = "auto"
                    ) -> tuple[jnp.ndarray, CompressedTensor]:
    """Forward matmul with the operand compressed in the epilogue.

    Returns ``(y, ct)`` with ``y = x @ w`` (f32) and ``ct`` the stash of
    ``x`` — bit-identical packed words to :func:`compress` on the same
    backend.  Routing is :func:`repro.core.backend.route_fused`: when it
    declines (``fused="off"``, ineligible shape, or ``auto`` off the real
    kernel path) this falls back to the unfused two-pass spelling, so the
    call is always safe as a per-layer drop-in.
    """
    seed = jnp.asarray(seed, jnp.uint32)
    requested = impl if impl is not None else cfg.impl
    levels = cfg.levels()
    concrete = backend.route_fused(fused, requested, tuple(x.shape),
                                   cfg.bits, cfg.group_size, levels,
                                   cfg.rp_ratio)
    if concrete is None:
        ct = compress(x, cfg, seed, impl=impl)
        return x.astype(jnp.float32) @ w.astype(jnp.float32), ct
    # the fused kernel is the layer's matmul and its compression in one:
    # all of it is the compression route's
    with jax.named_scope(scopes.ACT_COMPRESS):
        y, packed, zero, rng = backend.matmul_quantize(
            x.astype(jnp.float32), w.astype(jnp.float32), cfg.bits, seed,
            levels, impl=concrete, group_size=cfg.group_size)
    ct = CompressedTensor(packed, zero, rng,
                          seed ^ jnp.uint32(0xA5A5_A5A5),
                          shape=tuple(x.shape), dtype=x.dtype, cfg=cfg,
                          impl=concrete)
    return y, ct


def decompress_matmul(ct: CompressedTensor, g2d: jnp.ndarray,
                      impl: str | None = None,
                      fused: str = "auto") -> jnp.ndarray:
    """Backward matmul ``dw = x̂ᵀ @ g`` with dequantization fused into the
    prologue (no HBM materialization of the f32 reconstruction).

    ``g2d`` is the (M, N) output gradient of the layer whose (M, D) input
    ``ct`` stashes.  Same routing/fallback story as
    :func:`compress_matmul`; on the fallback path this is exactly
    ``decompress(ct).Tᵀ``-style two-pass math, so results are
    bit-identical per impl either way (single row tile).
    """
    cfg = ct.cfg
    requested = impl if impl is not None else backend.available_impl(ct.impl)
    levels = cfg.levels()
    concrete = backend.route_fused(fused, requested, ct.shape, cfg.bits,
                                   cfg.group_size, levels, cfg.rp_ratio)
    d = ct.shape[-1]
    if concrete is None:
        x_hat = decompress(ct, impl=impl)
        return (x_hat.reshape(-1, d).astype(jnp.float32).T
                @ g2d.astype(jnp.float32))
    with jax.named_scope(scopes.ACT_DECOMPRESS):
        return backend.dequant_matmul(ct.packed, ct.zero, ct.rng,
                                      g2d.astype(jnp.float32), cfg.bits,
                                      cfg.group_size, d, levels,
                                      impl=concrete)


def decompress(ct: CompressedTensor, impl: str | None = None) -> jnp.ndarray:
    """Backward-pass recovery: unpack+dequant → (optional IRP).

    Defaults to the concrete backend the tensor was compressed with
    (``ct.impl``), downgraded to one runnable on this host — all impls are
    bit-identical, so a pallas-written checkpoint restores fine on CPU.  A
    ``backend.use_impl`` context still takes precedence.
    """
    cfg = ct.cfg
    requested = impl if impl is not None else backend.available_impl(ct.impl)
    proj_shape = _proj_shape(ct.shape, cfg.rp_ratio)
    levels = cfg.levels()
    with jax.named_scope(scopes.ACT_DECOMPRESS):
        blocks = backend.dequantize_blocks(
            ct.packed, ct.zero, ct.rng, cfg.bits, cfg.group_size, levels,
            impl=requested)
        x = backend.from_blocks(blocks, proj_shape)
        if cfg.rp_ratio > 1:
            x = backend.irp(x, ct.rp_seed, ct.shape[-1], impl=requested)
        return x.astype(ct.dtype)
