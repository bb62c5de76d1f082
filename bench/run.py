#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip(s) of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's graph and weights from ``--seed``, compiles and warms
the program's full-graph epoch step, drives it for ``--seconds``, checks
what it computed against the plain reference, and prints one JSON object
as the last line of standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics from a profiler trace of the window),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared with its limit, which also end standard error.

Exits non-zero with no result where JAX finds no TPU, or fewer chips
than the cell asks for.  JAX's persistent compilation cache lives in
``.jax_cache`` in the checkout unless ``JAX_COMPILATION_CACHE_DIR`` says
otherwise.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(HERE), str(HERE.parent / "src")]
    import jax

    import harness
    from repro.runtime.compile_cache import enable_compile_cache

    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    try:
        jax.devices()
    except RuntimeError as e:  # JAX found no backend at all
        log(f"no accelerator: {e}")
        return 2
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {cache}")
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t0=T0, log=log)
    except harness.NoChip as e:
        log(str(e))
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
