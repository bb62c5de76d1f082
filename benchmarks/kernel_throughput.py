"""Kernel micro-bench: quant/dequant/RP wall time plus the bytes-moved model
that determines TPU-side speedup.

Two tiers:

* raw kernel calls (legacy rows, kept for trend continuity);
* the *dispatched* public compressor API (``compress``/``decompress``)
  swept over ``impl in {"jnp", "interp"}`` — this is the path training
  actually runs, so the perf trajectory tracks the dispatch layer, not
  hand-wired kernel calls.  Results land in ``BENCH_compressor.json``.

On CPU the Pallas path runs in interpret mode and is correctness-priced
only; the jnp rows are the meaningful CPU numbers.

The impl sweep covers {jnp, interp, auto} everywhere and adds real
"pallas" rows on TPU hosts only (off-TPU they are skipped with a note
row instead of crashing — ``auto`` already records what this host's
training would dispatch to).  The ``fused/*`` rows time the
quantize-in-epilogue matmul pair against the two-pass spelling it
replaces (XLA/kernel matmul + dispatched quant), recording the
machine-portable ``speedup`` ratio the CI regression gate tracks.
"""
from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp

from repro.core import CompressionConfig, compress, decompress
from repro.core import backend
from repro.kernels import ops
from repro.obs.trace import stopwatch

JSON_PATH = pathlib.Path(__file__).resolve().parent.parent / "BENCH_compressor.json"

#: impls every host sweeps; "pallas" joins on TPU (guarded in the sweeps)
SWEEP_IMPLS = ("jnp", "interp", "auto")


def _sweep_impls():
    if jax.default_backend() == "tpu":
        return SWEEP_IMPLS + ("pallas",)
    return SWEEP_IMPLS


def _time(f, *args, n=5):
    jax.block_until_ready(f(*args))  # compile + warm
    with stopwatch("bench/kernel", repeats=n) as sw:
        for _ in range(n):
            out = f(*args)
            jax.block_until_ready(out)
    return sw.elapsed_s / n * 1e6


def _raw_kernel_rows():
    out = []
    for (nb, g) in ((4096, 256), (16384, 256), (4096, 1024)):
        x = jax.random.normal(jax.random.PRNGKey(0), (nb, g), jnp.float32)
        qf = jax.jit(lambda x: ops.quantize_packed(x, 2, 7, impl="jnp"))
        us = _time(qf, x)
        in_bytes = x.size * 4
        out_bytes = x.size // 16 * 4 + nb * 8
        out.append((f"kernel/quant2_pack/{nb}x{g}", us,
                    f"in_MB={in_bytes / 1e6:.1f};out_MB={out_bytes / 1e6:.2f};"
                    f"compress={in_bytes / out_bytes:.1f}x"))
        packed, zero, rng = qf(x)
        df = jax.jit(lambda p, z, r: ops.dequantize_packed(
            p, z, r, 2, g, impl="jnp"))
        us = _time(df, packed, zero, rng)
        out.append((f"kernel/dequant2_unpack/{nb}x{g}", us, ""))
    x = jax.random.normal(jax.random.PRNGKey(1), (8192, 1024), jnp.float32)
    rp = jax.jit(lambda x: ops.rp_project(x, 3, 128, impl="jnp"))
    us = _time(rp, x)
    # seeded RP saves materializing + reading R: D x r fp32 per call
    saved = 1024 * 128 * 4
    out.append((f"kernel/rp_project/8192x1024->128", us,
                f"R_bytes_never_materialized={saved / 1e6:.2f}MB"))
    return out


def _dispatched_compressor_rows(impls=None):
    """Sweep the public compressor API across backends."""
    impls = _sweep_impls() if impls is None else impls
    rows, records = [], []
    cases = [
        ("int2_g256", CompressionConfig(bits=2, group_size=256), (4096, 256)),
        ("int2_g256_vm", CompressionConfig(bits=2, group_size=256, vm=True),
         (4096, 256)),
        ("int2_g256_rp8", CompressionConfig(bits=2, group_size=256,
                                            rp_ratio=8), (2048, 1024)),
    ]
    for tag, cfg, shape in cases:
        x = jax.random.normal(jax.random.PRNGKey(7), shape, jnp.float32)
        for impl in impls:
            cf = jax.jit(lambda x, c=cfg, i=impl: compress(x, c, 7, impl=i))
            us_c = _time(cf, x, n=3)
            ct = cf(x)
            df = jax.jit(decompress)
            us_d = _time(df, ct, n=3)
            derived = (f"impl={impl};stored_MB={ct.nbytes / 1e6:.3f};"
                       f"ratio={ct.uncompressed_nbytes / ct.nbytes:.1f}x")
            rows.append((f"compressor/{tag}/compress[{impl}]", us_c, derived))
            rows.append((f"compressor/{tag}/decompress[{impl}]", us_d, ""))
            records.append({
                "case": tag, "impl": impl, "shape": list(shape),
                "bits": cfg.bits, "group_size": cfg.group_size,
                "rp_ratio": cfg.rp_ratio, "vm": cfg.vm,
                "compress_us": us_c, "decompress_us": us_d,
                "stored_bytes": ct.nbytes,
                "uncompressed_bytes": ct.uncompressed_nbytes,
            })
    return rows, records


def fused_cases():
    """(tag, m, d, n, bits, group_size, levels) shapes the fused rows
    sweep, at the tiles the autotuner picks for training."""
    return [
        ("b2_g256", 4096, 256, 256, 2, 256, None),
        ("b4_g128", 2048, 256, 256, 4, 128, None),
        ("b2_g64_vm", 1024, 256, 256, 2, 64,
         CompressionConfig(bits=2, group_size=64, vm=True).levels()),
    ]


def _fused_matmul_rows(impls=None):
    """Fused quantize-in-epilogue matmul pair vs the two-pass spelling.

    For each (shape, bits, G, impl): the forward row times
    ``matmul_quantize_packed`` against separate ``x @ w`` + dispatched
    ``quantize_packed`` (the exact pair it replaces in the engine), and
    the backward row times ``dequant_matmul_packed`` against dispatched
    ``dequantize_packed`` + ``x̂ᵀ @ g``.  The recorded ``speedup``
    (unfused/fused) is machine-portable — the CI regression gate tracks
    it rather than raw wall time.
    """
    impls = _sweep_impls() if impls is None else impls
    rows, records = [], []
    for tag, m, d, n, bits, g, levels in fused_cases():
        x = jax.random.normal(jax.random.PRNGKey(0), (m, d), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (d, n), jnp.float32)
        gy = jax.random.normal(jax.random.PRNGKey(2), (m, n), jnp.float32)
        assert backend.supports_fused((m, d), bits, g, levels), (tag,)
        for impl in impls:
            ff = jax.jit(lambda x, w, i=impl: ops.matmul_quantize_packed(
                x, w, bits, 7, levels, impl=i, group_size=g))
            us_f = _time(ff, x, w, n=3)
            uf = jax.jit(lambda x, w, i=impl: (
                x @ w,
                ops.quantize_packed(x.reshape(-1, g), bits, 7, levels,
                                    impl=i)))
            us_u = _time(uf, x, w, n=3)
            y, packed, zero, rng = ff(x, w)
            fb = jax.jit(lambda p, z, r, gy, i=impl: ops.dequant_matmul_packed(
                p, z, r, gy, bits, g, d, levels, impl=i))
            us_fb = _time(fb, packed, zero, rng, gy, n=3)
            ub = jax.jit(lambda p, z, r, gy, i=impl: ops.dequantize_packed(
                p, z, r, bits, g, levels, impl=i).reshape(m, d).T @ gy)
            us_ub = _time(ub, packed, zero, rng, gy, n=3)
            rows.append((f"fused/{tag}/fwd[{impl}]", us_f,
                         f"unfused_us={us_u:.1f};speedup={us_u / us_f:.2f}x"))
            rows.append((f"fused/{tag}/bwd[{impl}]", us_fb,
                         f"unfused_us={us_ub:.1f};"
                         f"speedup={us_ub / us_fb:.2f}x"))
            records.append({
                "case": f"fused_{tag}", "impl": impl,
                "shape": [m, d, n], "bits": bits, "group_size": g,
                "vm": levels is not None,
                "fused_fwd_us": us_f, "unfused_fwd_us": us_u,
                "fwd_speedup": us_u / us_f,
                "fused_bwd_us": us_fb, "unfused_bwd_us": us_ub,
                "bwd_speedup": us_ub / us_fb,
            })
    if jax.default_backend() != "tpu":
        rows.append(("fused/pallas", 0.0,
                     "skipped=real-pallas rows need a TPU host"))
    return rows, records


def main(json_path: pathlib.Path | str | None = JSON_PATH):
    rows = _raw_kernel_rows()
    dispatched, records = _dispatched_compressor_rows()
    rows += dispatched
    fused_rows, fused_records = _fused_matmul_rows()
    rows += fused_rows
    records += fused_records
    if json_path:
        payload = {"backend": jax.default_backend(), "records": records}
        pathlib.Path(json_path).write_text(json.dumps(payload, indent=2))
    return rows


if __name__ == "__main__":
    for name, us, derived in main():
        print(f"{name},{us:.1f},{derived}")
    print(f"# wrote {JSON_PATH}")
