#!/usr/bin/env python3
"""Readings for setting a cell's correctness limits, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 12 \\
        --control-seeds 3 --fault-seeds 3 [--faults half_batch,...] \\
        [--first-seed N] [--out FILE]

For each seed it prints one JSON line of :func:`verdict.details` and
:func:`verdict.readings` for

* ``program``: the program's compiled step, exactly as a run drives it;
* ``control``: the plain reference in bfloat16 put in the program's place
  (the nearest precision below the configuration's float32);
* ``fault:<name>``: the program with a fault of :data:`FAULTS` planted,
  on the first ``--fault-seeds`` seeds;

each against the reference at the configuration's precision from the
same graph and weights.  A seed's graph and reference serve its
control and its faults.  A step that returns its state unchanged reads 1
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


def half_partition(build_halo_program):
    """The mesh's layout with the training rows of the second half of
    each partition's rows left out: its loss, ``psum(nll * mask) /
    psum(mask)``, is then the mean over the rest."""
    def build(*args, **kwargs):
        prog = build_halo_program(*args, **kwargs)
        prog.train_mask[..., prog.n_pad // 2:] = 0
        return prog
    return build


def halo_dropped(halo_exchange):
    """The halo exchange returning its input with a zero strip: every
    edge from another chip reads zero."""
    import jax.numpy as jnp

    def exchange(h, send_idx, axis="graph"):
        m, width = send_idx.shape
        if width == 0 or axis is None or m == 1:
            return halo_exchange(h, send_idx, axis)
        return jnp.concatenate([h, jnp.zeros((m * width, h.shape[1]),
                                             h.dtype)])
    return exchange


def dequant_scale(decompress):
    """``decompress`` with its output doubled: a dequantize off by a
    constant factor."""
    def wrapped(ct, impl=None):
        return 2 * decompress(ct, impl=impl)
    return wrapped


#: Planted faults: name -> the (module, attribute, wrapper of the
#: original) it plants, one for each path of the program that the
#: fault's code takes (the full graph's, the mesh's).
FAULTS = {"half_batch": (("repro.engine.compile", "masked_nll", half_batch),
                         ("repro.engine.compile", "build_halo_program",
                          half_partition)),
          "dequant_scale": (("repro.core.compressor", "decompress",
                             dequant_scale),
                            ("repro.core.act_compress", "decompress",
                             dequant_scale)),
          "halo_dropped": (("repro.parallel.halo", "halo_exchange",
                            halo_dropped),)}


def plants(name: str):
    """``(module, attribute, faulty value)`` of each plant of fault
    ``name``."""
    for module, attr, wrap in FAULTS[name]:
        mod = importlib.import_module(module)
        yield mod, attr, wrap(getattr(mod, attr))


@contextlib.contextmanager
def planted(name: str):
    """The program with fault ``name`` planted for the block."""
    undo = []
    for mod, attr, value in plants(name):
        undo.append((mod, attr, getattr(mod, attr)))
        setattr(mod, attr, value)
    try:
        yield
    finally:
        for mod, attr, orig in reversed(undo):
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

    def program(seed):
        """The program's first three steps, its state freed."""
        b = harness.build(files, seed)
        chips = harness.node_chips(b)
        got = harness.first_steps(b)
        del b.exe, b.step, b.params, b.state, b.data, b.comp
        return b, chips, got

    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    faults = list(filter(None, args.faults.split(",")))
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        b, chips, got = program(seed)
        ref = harness.reference_run(files, b.graph, b.params0, chips=chips)
        emit("program", seed, got, ref, b.params0,
             ref_losses=ref["losses"], seconds=time.perf_counter() - t)
        if i < args.control_seeds:
            ctl = harness.reference_run(files, b.graph, b.params0,
                                        control=True)
            emit("control", seed, ctl, ref, b.params0)
        for fault in faults if i < args.fault_seeds else ():
            with planted(fault):
                _, _, got = program(seed)
            emit(f"fault:{fault}", seed, got, ref, b.params0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
