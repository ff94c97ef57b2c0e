#!/usr/bin/env python3
"""The readings the check's limits are set from, in one process: the
program's compared numbers over many seeds (one call each at the cell's own
size), and the control's, the plain reference put in the program's place
one precision below the configuration's.

    python3 bench/readings.py --workload hpl.n32768.1chip \
        --seeds 1,2,3 --control-seeds 4,5,6 [--reference-seeds 7]

Prints one JSON line per reading: kind (program, control, reference),
seed, and each compared number with its limit. The benchmark's own runs
never run the control.
"""
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None):
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--reference-seeds", default="")
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)

    from bench.harness import devices_for, prng_key, resolve
    from repro.compile_cache import use_compile_cache
    cell = resolve(args.workload, tiny=args.tiny)
    use_compile_cache()
    import jax
    drv = cell.driver
    mesh = drv.mesh(cell.size, devices_for(cell, args.tiny))
    plan = ([("program", s) for s in _seeds(args.seeds)] +
            [("control", s) for s in _seeds(args.control_seeds)] +
            [("reference", s) for s in _seeds(args.reference_seeds)])
    for kind, seed in plan:
        t0 = time.perf_counter()
        st = drv.setup(cell, mesh, prng_key(seed), seed)
        if kind == "program":
            checks = drv.check(st, jax.block_until_ready(drv.call(st)))
        else:
            out = getattr(drv, kind)(st)
            checks = drv.control_check(st, out)
        print(json.dumps({"workload": cell.name, "kind": kind, "seed": seed,
                          "seconds": time.perf_counter() - t0,
                          "checks": {k: {"value": v, "limit": lim}
                                     for k, (v, lim) in checks.items()}}),
              flush=True)
        del st


if __name__ == "__main__":
    main()
