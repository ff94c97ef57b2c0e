"""Paper Table 7 analogue — per-benchmark "resource usage" on the production
mesh: compiled FLOPs / HBM bytes / collective bytes per device and the
three roofline terms, for the paper's communication benchmarks (b_eff,
PTRANS, HPL) lowered at production scale, plus the LM cells read from the
dry-run results.

The paper reports logic/BRAM/DSP/frequency per bitstream; the TPU analogue
of "resources a design consumes" is exactly what the compiled artifact
reports: bytes per device (fits/doesn't fit), FLOPs, and wire traffic.

This module needs the 512-device placeholder runtime; when invoked under a
smaller device count it re-execs itself in a fresh interpreter, pinned to
the CPU backend so it never contends with the parent for an accelerator.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import RESULTS_DIR, fmt_bytes, save_result, table

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")


def _lower_hpcc():
    """Runs inside the 512-device interpreter: lower + analyse the paper's
    three communication benchmarks at production scale."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import roofline as rl
    from repro.comm.engine import CollectiveEngine
    from repro.comm.types import CommunicationType as CT
    from repro.core import beff as beff_mod
    from repro.core import hpl as hpl_mod
    from repro.core import ptrans as ptrans_mod
    from repro.launch.mesh import make_mesh

    out = {}

    # --- b_eff: ring over one pod (256 chips), 1 MiB messages ----------------
    mesh = make_mesh((256,), ("x",))
    for ct in (CT.ICI_DIRECT, CT.HOST_STAGED):
        step = beff_mod.make_step(mesh, CollectiveEngine.for_mesh(mesh, ct),
                                  rounds=4)
        L = 1 << 20
        spec = jax.ShapeDtypeStruct((256, L), np.uint8)
        with mesh:
            lowered = step.lower((spec, spec))
            compiled = lowered.compile()
        r = rl.from_compiled(compiled, chips=256,
                             model_flops=0.0)
        out[f"b_eff/{ct.value}"] = _terms(r)

    # --- PTRANS: 16x16 grid, n=32768 (paper's matrix), block 512 -------------
    mesh = make_mesh((16, 16), ("rows", "cols"))
    n, b = 32768, 512
    m = (n // b // 16) * b
    for ct in (CT.ICI_DIRECT, CT.HOST_STAGED):
        step = ptrans_mod.make_step(mesh, 16,
                                    CollectiveEngine.for_mesh(mesh, ct))
        spec = jax.ShapeDtypeStruct((256, m, m), np.float32)
        with mesh:
            compiled = step.lower(spec, spec).compile()
        r = rl.from_compiled(compiled, chips=256,
                             model_flops=float(n) * n)  # n^2 adds
        out[f"ptrans/{ct.value}"] = _terms(r)

    # --- HPL: 16x16 torus, n=24576 (paper's multi-FPGA size), block 256 ------
    n, b = 24576, 256
    for ct, sched in ((CT.ICI_DIRECT, "chain"), (CT.ICI_DIRECT, "native"),
                      (CT.HOST_STAGED, "staged")):
        fact = hpl_mod.make_factorize(mesh, pg=16, nb=n // b, b=b, comm=ct,
                                      schedule=sched)
        m = (n // b // 16) * b
        spec = jax.ShapeDtypeStruct((256, m, m), np.float32)
        with mesh:
            compiled = fact.lower(spec).compile()
        r = rl.from_compiled(compiled, chips=256,
                             model_flops=2.0 * n ** 3 / 3.0)
        out[f"hpl/{ct.value}/{sched}"] = _terms(r)

    print(json.dumps(out))


def _terms(r):
    return {
        "flops_per_device": r.flops,
        "hbm_bytes_per_device": r.hbm_bytes,
        "collective_wire_bytes": r.coll_wire_bytes,
        "compute_s": r.compute_s, "memory_s": r.memory_s,
        "collective_s": r.collective_s, "dominant": r.dominant,
        "useful_ratio": r.useful_ratio, "per_op": r.details["per_op_bytes"],
    }


def main(quick: bool = False, schedule=None):
    # compiled-footprint analysis; no measured collectives, ``schedule``
    # accepted for driver uniformity
    print("== resource table (paper Table 7 analogue): production-mesh "
          "compiled footprints ==")
    # HPCC benchmarks, lowered in a fresh 512-device interpreter
    cache = os.path.join(RESULTS_DIR, "resource_table_hpcc.json")
    if os.path.exists(cache):
        with open(cache) as f:
            hpcc = json.load(f)
    else:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=512",
                   PYTHONPATH=os.pathsep.join(
                       [os.path.join(os.path.dirname(__file__), "..", "src"),
                        os.path.join(os.path.dirname(__file__), ".."),
                        os.environ.get("PYTHONPATH", "")]))
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.resource_table", "--hpcc-lower"],
            capture_output=True, text=True, env=env,
            cwd=os.path.join(os.path.dirname(__file__), ".."), timeout=3600)
        if proc.returncode:
            raise RuntimeError("HPCC lowering failed (exit "
                               f"{proc.returncode}):\n{proc.stderr[-2000:]}")
        hpcc = json.loads(proc.stdout.strip().splitlines()[-1])
        os.makedirs(RESULTS_DIR, exist_ok=True)
        with open(cache, "w") as f:
            json.dump(hpcc, f, indent=1)

    rows = []
    for name, t in hpcc.items():
        rows.append([name, f"{t['flops_per_device']:.3g}",
                     fmt_bytes(t["hbm_bytes_per_device"]),
                     fmt_bytes(t["collective_wire_bytes"]),
                     f"{t['compute_s']:.3g}", f"{t['memory_s']:.3g}",
                     f"{t['collective_s']:.3g}", t["dominant"]])
    print(table(rows, ["benchmark", "FLOPs/dev", "HBM/dev", "wire/dev",
                       "compute_s", "memory_s", "coll_s", "dominant"]))

    # LM cells from the dry-run sweep
    if os.path.isdir(DRYRUN_DIR):
        rows = []
        for fn in sorted(os.listdir(DRYRUN_DIR)):
            with open(os.path.join(DRYRUN_DIR, fn)) as f:
                rec = json.load(f)
            if rec.get("status") != "ok":
                continue
            rows.append([rec["arch"], rec["shape"], rec["mesh"],
                         f"{rec['flops_per_device']:.3g}",
                         fmt_bytes(rec["hbm_bytes_per_device"]),
                         fmt_bytes(rec["collective_wire_bytes"]),
                         rec["dominant"], f"{rec['useful_ratio']:.1%}"])
        if rows:
            print("\n-- LM cells (from results/dryrun) --")
            print(table(rows, ["arch", "shape", "mesh", "FLOPs/dev",
                               "HBM/dev", "wire/dev", "dominant", "useful"]))
    save_result("resource_table", {"hpcc": hpcc})
    return hpcc


if __name__ == "__main__":
    if "--hpcc-lower" in sys.argv:
        _lower_hpcc()
    else:
        main()
