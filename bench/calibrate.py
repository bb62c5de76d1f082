#!/usr/bin/env python3
"""Readings for setting a cell's correctness limits, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 12 \\
        --control-seeds 3 --fault-seeds 3 [--faults half_batch,...] \\
        [--out FILE]

For each seed it prints one JSON line of :func:`verdict.details` and
:func:`verdict.readings` for

* ``program``: the program's compiled step, exactly as a run drives it;
* ``control``: the plain reference in bfloat16 put in the program's place
  (the nearest precision below the configuration's float32);
* ``fault:<name>``: the program with a fault of :data:`FAULTS` planted;

each against the reference at the configuration's precision from the
same graph and weights.  A step that returns its state unchanged reads 1
on ``grad_gap`` and ``update_gap`` by construction and needs no run.
Runs on the chip(s) of this machine; no window is timed.
"""
import argparse
import contextlib
import importlib
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent


def half_batch(masked_nll):
    """``masked_nll`` with the training rows of the second half of the
    nodes left out."""
    import jax.numpy as jnp

    def loss(logits, labels, mask):
        n = mask.shape[0]
        return masked_nll(logits, labels,
                          mask * (jnp.arange(n) < n // 2).astype(mask.dtype))
    return loss


def dequant_scale(decompress):
    """``decompress`` with its output doubled: a dequantize off by a
    constant factor."""
    def wrapped(ct, impl=None):
        return 2 * decompress(ct, impl=impl)
    return wrapped


#: Planted faults: name -> (module, attribute, wrapper of the original).
FAULTS = {"half_batch": ("repro.engine.compile", "masked_nll", half_batch),
          "dequant_scale": ("repro.core.compressor", "decompress",
                            dequant_scale)}


@contextlib.contextmanager
def planted(name: str):
    """The program with fault ``name`` planted for the block."""
    module, attr, wrap = FAULTS[name]
    mod = importlib.import_module(module)
    orig = getattr(mod, attr)
    setattr(mod, attr, wrap(orig))
    try:
        yield
    finally:
        setattr(mod, attr, orig)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--faults", default="half_batch")
    ap.add_argument("--first-seed", type=int, default=1_000_003)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(HERE.parent / "src")]
    import jax

    import harness
    import verdict
    from repro.runtime.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    files = harness.cell_files(harness.load_spec(), args.workload)
    out = open(args.out, "a") if args.out else None

    rp = files["traffic"]["rp_ratio"] or 0

    def emit(kind, seed, got, ref, params0, **extra):
        line = json.dumps({"cell": args.workload, "kind": kind,
                           "seed": seed,
                           **verdict.readings(got, ref, params0, rp),
                           **verdict.details(got, ref, params0),
                           "losses": got["losses"], **extra})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def drive(seed):
        """The program's first steps and the reference's, state freed."""
        b = harness.build(files, seed)
        prog = harness.first_steps(b)
        del b.exe, b.params, b.state, b.data, b.comp
        return b, prog, harness.reference_run(files, b.graph, b.params0)

    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        b, prog, ref = drive(seed)
        emit("program", seed, prog, ref, b.params0,
             ref_losses=ref["losses"], seconds=time.perf_counter() - t)
        if i < args.control_seeds:
            ctl = harness.reference_run(files, b.graph, b.params0,
                                        control=True)
            emit("control", seed, ctl, ref, b.params0)
    for fault in filter(None, args.faults.split(",")):
        with planted(fault):
            for seed in seeds[:args.fault_seeds]:
                b, prog, ref = drive(seed)
                emit(f"fault:{fault}", seed, prog, ref, b.params0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
