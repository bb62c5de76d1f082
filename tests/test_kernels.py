"""Pallas kernels vs pure-jnp oracle: bit-exact codes, allclose dequant,
shape/dtype/bits sweep (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

VM2 = (0.0, 1.05, 1.95, 3.0)


@pytest.mark.parametrize("n,g", [(8, 32), (16, 64), (24, 128), (8, 256),
                                 (3, 64), (1, 32)])
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_quant_kernel_matches_ref(n, g, bits):
    x = jax.random.normal(jax.random.PRNGKey(n * g + bits), (n, g),
                          jnp.float32) * 2.3 + 0.7
    pk, zk, rk = ops.quantize_packed(x, bits, 42, None, impl="interp")
    pr, zr, rr = ref.quantize_packed(x, bits, 42, None)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_allclose(np.asarray(zk), np.asarray(zr), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(rk), np.asarray(rr), rtol=1e-6)
    dk = ops.dequantize_packed(pk, zk, rk, bits, g, None, impl="interp")
    dr = ref.dequantize_packed(pr, zr, rr, bits, g, None)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr), atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_kernel_vm_levels(dtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), (16, 64)) * 3).astype(dtype)
    x32 = x.astype(jnp.float32)
    pk, zk, rk = ops.quantize_packed(x32, 2, 7, VM2, impl="interp")
    pr, zr, rr = ref.quantize_packed(x32, 2, 7, VM2)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    dk = ops.dequantize_packed(pk, zk, rk, 2, 64, VM2, impl="interp")
    dr = ref.dequantize_packed(pr, zr, rr, 2, 64, VM2)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr), atol=1e-5)


def test_quant_kernel_seed_sensitivity():
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    p1, _, _ = ops.quantize_packed(x, 2, 1, None, impl="interp")
    p2, _, _ = ops.quantize_packed(x, 2, 2, None, impl="interp")
    assert not np.array_equal(np.asarray(p1), np.asarray(p2))


@pytest.mark.parametrize("m,d,r", [(64, 256, 128), (100, 512, 128),
                                   (128, 128, 256)])
def test_rp_kernel_matches_ref(m, d, r):
    x = jax.random.normal(jax.random.PRNGKey(m + d), (m, d), jnp.float32)
    yk = ops.rp_project(x, 7, r, impl="interp")
    yr = ref.rp_project(x, 7, r)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                               rtol=2e-4, atol=2e-4)
    xik = ops.irp_project(yk, 7, d, impl="interp")
    xir = ref.irp_project(yr, 7, d)
    np.testing.assert_allclose(np.asarray(xik), np.asarray(xir),
                               rtol=2e-4, atol=2e-4)


def test_rp_kernel_projection_is_unbiased_reconstruction():
    x = jax.random.normal(jax.random.PRNGKey(5), (32, 256), jnp.float32)
    acc = jnp.zeros_like(x)
    n = 64
    for s in range(n):
        y = ops.rp_project(x, s, 128, impl="interp")
        acc = acc + ops.irp_project(y, s, 256, impl="interp")
    # single-seed rel err ≈ √(D/R − 1) ≈ 1.4; mean of n shrinks as 1/√n
    rel = float(jnp.linalg.norm(acc / n - x) / jnp.linalg.norm(x))
    assert rel < 2.8 / np.sqrt(n), rel


def test_jnp_impl_equals_interp_impl_end_to_end():
    """The 'auto' CPU path (jnp) and the kernel path produce identical bits."""
    x = jax.random.normal(jax.random.PRNGKey(9), (16, 128))
    for bits in (2, 4):
        pa, za, ra = ops.quantize_packed(x, bits, 3, None, impl="jnp")
        pb, zb, rb = ops.quantize_packed(x, bits, 3, None, impl="interp")
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))


# ------------------------------------------------- fused backward M-split
def _np_tree(parts):
    """The fixed-order pairwise reduction the kernel contract names."""
    while parts.shape[0] > 1:
        half = parts.shape[0] // 2
        paired = parts[: 2 * half]
        parts = np.concatenate([paired[0::2] + paired[1::2],
                                parts[2 * half:]], axis=0)
    return parts[0]


@pytest.mark.parametrize("tile_rows,m", [(128, 384), (128, 256), (64, 320),
                                         (128, 300)])
def test_fused_bwd_tiled_is_fixed_order_tree(tile_rows, m):
    """Row-tiled fused backward == the fixed-order pairwise tree over
    per-tile ``x̂ᵀ@g`` partials, exactly — including odd tile counts and
    a ragged last tile, which the kernel pads with zero rows — and is
    bit-stable across repeated runs."""
    d, n, bits, g = 32, 128, 2, 32
    x = jax.random.normal(jax.random.PRNGKey(0), (m, d), jnp.float32)
    gy = jax.random.normal(jax.random.PRNGKey(2), (m, n), jnp.float32)
    p, z, r = ops.quantize_packed(x.reshape(-1, g), bits, 7, None,
                                  impl="jnp")
    x_hat = np.asarray(ops.dequantize_packed(
        p, z, r, bits, g, None, impl="interp")).reshape(m, d)
    k_tiles = -(-m // tile_rows)
    pad = ((0, k_tiles * tile_rows - m), (0, 0))
    x_pad, gy_pad = np.pad(x_hat, pad), np.pad(np.asarray(gy), pad)
    parts = np.stack([
        np.asarray(jnp.dot(jnp.asarray(x_pad[k * tile_rows:
                                             (k + 1) * tile_rows]).T,
                           gy_pad[k * tile_rows:(k + 1) * tile_rows],
                           preferred_element_type=jnp.float32))
        for k in range(k_tiles)])
    dw = ops.dequant_matmul_packed(p, z, r, gy, bits, g, d, None,
                                   impl="interp", tile_rows=tile_rows)
    np.testing.assert_array_equal(np.asarray(dw), _np_tree(parts))
    dw2 = ops.dequant_matmul_packed(p, z, r, gy, bits, g, d, None,
                                    impl="interp", tile_rows=tile_rows)
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(dw2))
    # and the split accumulation stays float-close to the single-tile
    # (bit-parity) order
    dw_single = ops.dequant_matmul_packed(p, z, r, gy, bits, g, d, None,
                                          impl="interp")
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_single),
                               rtol=1e-4, atol=1e-4)


def test_tree_sum_orders():
    from repro.kernels.fused_matmul import _tree_sum

    for k in (1, 2, 3, 4, 5, 8):
        parts = jax.random.normal(jax.random.PRNGKey(k), (k, 8, 16),
                                  jnp.float32)
        np.testing.assert_array_equal(np.asarray(_tree_sum(parts)),
                                      _np_tree(np.asarray(parts)))


def test_peak_table_rejects_unknown_device_kind():
    """Tiles are ranked against one chip's published rates; a kind missing
    from the table is an error, never a silent default."""
    from repro.kernels import autotune, peaks

    assert peaks.chip_peaks("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(ValueError, match="TPU v99"):
        peaks.chip_peaks("TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        autotune.get_tiles("bwd", 1024, 256, 256, 2, 128, "TPU v99")


@pytest.mark.parametrize("d,n,g", [(256, 256, 128), (512, 256, 128),
                                   (512, 40, 128), (512, 256, 256)])
def test_fused_tiles_fit_vmem_at_arxiv_rows(d, n, g):
    """At 169,343 rows one backward tile would need far more VMEM than a
    core has: the pick is a fixed row tile under the budget, and the
    forward pick fits too."""
    from repro.kernels import autotune

    m = 169343
    tr, tn = autotune.get_tiles("bwd", m, d, n, 2, g, "TPU v5 lite")
    assert tr < m and tr % 8 == 0
    assert (autotune.bwd_vmem_bytes(tr, d, tn, 2, g)
            <= autotune.VMEM_BUDGET)
    tm, tn = autotune.get_tiles("fwd", m, d, n, 2, g, "TPU v5 lite")
    assert (autotune.fwd_vmem_bytes(tm, d, tn, 2, g)
            <= autotune.VMEM_BUDGET)
