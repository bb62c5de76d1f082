"""The main-path Pallas kernels compile for a TPU v5e at arxiv width.

Each test lowers one kernel for a described (not attached) v5e chip and
compiles it with the TPU compiler, which refuses what interpret mode
accepts: casts Mosaic has no lowering for, tiles over the VMEM limit,
unaligned slices.  Nothing runs, so these say nothing about values or
times; the chip smoke test (``chip_smoke.py``) does.  About two seconds
each.
"""
import os
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels import fused_matmul as fk
from repro.kernels import quant_blockwise as qk
from repro.kernels import rp_matmul as rk

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                       / "bench"))
import tracecut  # noqa: E402

#: ogbn-arxiv node count: ragged against every tile (not a multiple of 8).
ROWS = 169_343
G = 256


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e:2x2 topology (built here, never at
    import: only the worker that runs these tests loads the TPU library)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", before)


def _compiled_text(chip, fn, *shapes) -> str:
    one = SingleDeviceSharding(chip)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_kinds(text: str) -> set:
    """The benchmark's kinds (``tracecut.KERNEL_KINDS``) of the Pallas
    calls in a compiled module, which it finds by each call's ``name=``."""
    return {op.kernel for op in tracecut.hlo_ops(text).values()
            if op.kind == "kernel"}


@pytest.mark.parametrize("bits", [2, 8])
def test_quant_pack_compiles(chip, bits):
    text = _compiled_text(
        chip, lambda x, s: qk.quant_pack_call(x, bits, s),
        ((ROWS, G), jnp.float32), ((), jnp.uint32))
    assert "tpu_custom_call" in text
    assert _kernel_kinds(text) == {"quant"}


@pytest.mark.parametrize("bits", [2, 8])
def test_dequant_unpack_compiles(chip, bits):
    w = G * bits // 32
    text = _compiled_text(
        chip, lambda p, z, r: qk.dequant_unpack_call(p, z, r, bits, G),
        ((ROWS, w), jnp.uint32), ((ROWS, 1), jnp.float32),
        ((ROWS, 1), jnp.float32))
    assert "tpu_custom_call" in text
    assert _kernel_kinds(text) == {"dequant"}


def test_matmul_quant_compiles(chip):
    d, n, g = 128, 256, 128
    tm, tn = autotune.get_tiles("fwd", ROWS, d, n, 2, g, chip.device_kind)
    text = _compiled_text(
        chip, lambda x, w, s: fk.matmul_quant_call(x, w, 2, s, group_size=g,
                                                   tm=tm, tn=tn),
        ((ROWS, d), jnp.float32), ((d, n), jnp.float32), ((), jnp.uint32))
    assert "tpu_custom_call" in text
    assert _kernel_kinds(text) == {"fused_fwd"}


@pytest.mark.parametrize("d,n", [(256, 256), (512, 40)])
def test_dequant_matmul_compiles_with_picked_tiles(chip, d, n):
    g = 128
    tile_rows, tn = autotune.get_tiles("bwd", ROWS, d, n, 2, g,
                                       chip.device_kind)
    assert tile_rows < ROWS  # one tile of all rows would exceed VMEM
    nb, w = ROWS * d // g, g * 2 // 32
    text = _compiled_text(
        chip, lambda p, z, r, gy: fk.dequant_matmul_call(
            p, z, r, gy, 2, g, d, tile_rows=tile_rows, tn=tn),
        ((nb, w), jnp.uint32), ((nb, 1), jnp.float32),
        ((nb, 1), jnp.float32), ((ROWS, n), jnp.float32))
    assert "tpu_custom_call" in text
    assert _kernel_kinds(text) == {"fused_bwd"}


def test_rp_project_compiles(chip):
    text = _compiled_text(
        chip, lambda x, s: rk.rp_project_call(x, s, 128),
        ((ROWS, 256), jnp.float32), ((), jnp.uint32))
    assert "tpu_custom_call" in text
    assert _kernel_kinds(text) == {"rp"}


def test_irp_project_compiles(chip):
    text = _compiled_text(
        chip, lambda x, s: rk.irp_project_call(x, s, 256),
        ((ROWS, 128), jnp.float32), ((), jnp.uint32))
    assert "tpu_custom_call" in text
    assert _kernel_kinds(text) == {"rp"}
    assert "/irp_kernel/" in text
