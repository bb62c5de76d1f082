"""Benchmark driver: one harness per paper table/figure + system benches.
Prints ``name,us_per_call,derived`` CSV; the kernel suite additionally
sweeps the dispatched compressor API over ``impl in {jnp, interp}`` and
drops ``BENCH_compressor.json`` next to the repo root, and the gnn_batched
suite drops ``BENCH_gnn_batched.json`` (mini-batch vs full-graph engine).

Set ``REPRO_TRACE_OUT=<base>`` to trace the whole sweep: one obs span per
suite (plus every ``stopwatch``-timed region inside the harnesses),
exported to ``<base>.trace.json`` (Perfetto)."""
from __future__ import annotations

import os
import sys
import traceback


def main() -> None:
    from benchmarks import (autoprec, fig3_variance_surface,
                            fig5_vm_dimensionality, gnn_batched, gnn_dist,
                            kernel_throughput, lm_act_compression, offload,
                            roofline, serve, table1_gnn, table2_distribution)

    suites = [
        ("fig3", fig3_variance_surface.main),
        ("fig5", fig5_vm_dimensionality.main),
        ("kernel", kernel_throughput.main),  # also writes BENCH_compressor.json
        ("table2", table2_distribution.main),
        ("lm_act", lm_act_compression.main),
        ("table1", table1_gnn.main),
        ("gnn_batched", gnn_batched.main),  # writes BENCH_gnn_batched.json
        ("gnn_dist", gnn_dist.main),  # writes BENCH_gnn_dist.json
        ("autoprec", autoprec.main),  # writes BENCH_autoprec.json
        ("offload", offload.main),  # writes BENCH_offload.json
        ("serve", serve.main),  # writes BENCH_serve.json
        ("roofline", roofline.main),
    ]
    trace_out = os.environ.get("REPRO_TRACE_OUT")
    tracer = prev = None
    if trace_out:
        from repro.obs.trace import Tracer, set_tracer

        tracer = Tracer()
        prev = set_tracer(tracer)

    print("name,us_per_call,derived")
    failures = 0
    try:
        for tag, fn in suites:
            try:
                if tracer is not None:
                    with tracer.span(f"suite/{tag}"):
                        rows = fn()
                else:
                    rows = fn()
                for name, us, derived in rows:
                    print(f"{name},{us:.1f},{derived}", flush=True)
            except Exception:
                failures += 1
                print(f"{tag}/ERROR,0,{traceback.format_exc(limit=2)!r}",
                      flush=True)
    finally:
        if tracer is not None:
            from repro.obs.trace import set_tracer

            set_tracer(prev)
            base = trace_out.removesuffix(".json").removesuffix(".trace")
            tracer.export_chrome(base + ".trace.json")
            print(f"# obs trace: {base}.trace.json", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
