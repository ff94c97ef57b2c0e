"""Paper Figs. 5/7 analogue — communication/compute overlap benchmarks.

Three overlap structures, all engine-driven:

1. **HPL lookahead vs eager** per registered bcast schedule and per
   pipeline depth d: the depth-d factorization issues iterations
   k+1..k+d's panel broadcasts before iteration k's bulk trailing GEMM
   (the paper's headline LINPACK optimization), so XLA can hide the
   chain/ring2d hops behind the update. Output is bit-identical to eager
   mode by construction at every depth.

2. **Chunked vs monolithic PTRANS** per chunk count S: the strip-wise
   ``engine.pipelined`` grid transpose overlaps strip i's transpose-add
   with strip i+1's wire hops. The autotuned (cost-model) S is its own
   row; when it resolves to 1 the monolithic measurement is reused, so the
   recorded pipelined-vs-monolithic ratio is <= 1.0 whenever the model
   declines to chunk (the CI no-regression gate).

3. **Bucketed vs monolithic gradient reduction** per registered allreduce
   schedule: ``CollectiveEngine.allreduce_tree`` packs a synthetic gradient
   pytree into buckets; independent buckets give the backward-overlap
   structure, a single monolithic bucket is the baseline, leaf-wise is the
   pathological many-small-collectives end.
"""
from __future__ import annotations

from functools import partial

from benchmarks.common import ensure_devices, fmt_bytes, save_result, table

ensure_devices()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402
from jax import shard_map  # noqa: E402

from repro.comm.engine import CollectiveEngine, schedules_for  # noqa: E402
from repro.comm.overlap import tree_bytes  # noqa: E402
from repro.comm.types import CommunicationType as CT  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.core.hpcc import timeit  # noqa: E402
from repro.core.hpl import run_hpl  # noqa: E402
from repro.launch.mesh import make_torus_mesh  # noqa: E402


def _hpl_lookahead(quick: bool, schedules, record):
    n = 256 if quick else 512
    b = 64
    g = 2
    depths = (1, 2) if quick else (1, 2, 3)
    if not schedules:
        return
    if len(jax.devices()) < g * g:
        print("-- skipping HPL lookahead (needs 4 devices) --")
        return
    mesh = make_torus_mesh(g)
    print(f"== HPL lookahead vs eager (paper Figs. 5/7), n={n}, "
          f"{g}x{g} torus, depths {depths} ==")
    rows = []
    for schedule in schedules:
        perf = {}
        for lookahead in (False,) + depths:
            res = run_hpl(mesh, CT.ICI_DIRECT, n=n, b=b, schedule=schedule,
                          reps=1, lookahead=lookahead)
            mode = "eager" if not lookahead else f"d{int(lookahead)}"
            perf[mode] = res.metric
            record[f"hpl/{schedule}/{mode}"] = {
                "n": n, "gflops": res.metric, "err": res.error,
                "schedule": res.details["schedule"],
                "lookahead_depth": res.details["lookahead_depth"],
                "time": res.times["best"]}
        rows.append([schedule, f"{perf['eager']:.3f}"]
                    + [f"{perf[f'd{d}']:.3f}" for d in depths]
                    + [f"{perf[f'd{d}'] / perf['eager']:.2f}x"
                       for d in depths])
    print(table(rows, ["bcast schedule", "eager GFLOP/s"]
                + [f"d={d} GFLOP/s" for d in depths]
                + [f"d={d} ratio" for d in depths]))
    print()


def _ptrans_pipeline(quick: bool, record):
    """Chunked vs monolithic PTRANS (the in-flight strip pipeline). The
    autotuned chunk count is its own row; when it resolves to S=1 the
    monolithic timing is reused so the recorded ratio is exactly 1.0 —
    the model chose not to chunk, and chunking cannot regress."""
    g = 2
    if len(jax.devices()) < g * g:
        print("-- skipping PTRANS pipeline (needs 4 devices) --")
        return
    from repro.core.ptrans import CALLSITE, run_ptrans
    n = 256 if quick else 512
    b = 64
    mesh = make_torus_mesh(g)
    local_bytes = (n // g) * (n // g) * 4
    eng = CollectiveEngine.for_mesh(mesh)
    s_auto = eng.pipeline_chunks("grid_transpose", nbytes=local_bytes,
                                 axis=("rows", "cols"), callsite=CALLSITE)
    print(f"== chunked vs monolithic PTRANS, n={n}, {g}x{g} torus "
          f"(local payload {fmt_bytes(local_bytes)}, autotuned S={s_auto}) ==")
    reps = 2 if quick else 3
    times = {}
    rows = []
    for s in (1, 2, 4):
        res = run_ptrans(mesh, CT.ICI_DIRECT, n=n, b=b, reps=reps,
                         nchunks=s, validate=(s == 1))
        times[s] = res.times["best"]
        record[f"ptrans_pipe/S{s}"] = {
            "n": n, "nchunks": s, "time": times[s], "gflops": res.metric,
            "schedule": res.details["schedule"]}
        rows.append([f"S={s}", f"{times[s] * 1e3:.2f}ms",
                     f"{times[1] / times[s]:.2f}x"])
    t_auto = times[s_auto] if s_auto in times else run_ptrans(
        mesh, CT.ICI_DIRECT, n=n, b=b, reps=reps, nchunks=s_auto,
        validate=False).times["best"]
    ratio = t_auto / times[1]
    record["ptrans_pipe/auto"] = {
        "n": n, "nchunks": s_auto, "time": t_auto,
        "ratio_vs_monolithic": ratio}
    rows.append([f"auto (S={s_auto})", f"{t_auto * 1e3:.2f}ms",
                 f"{1 / ratio:.2f}x"])
    print(table(rows, ["chunks", "time", "speedup vs mono"]))
    print()


def _grad_tree(quick: bool):
    """Synthetic gradient pytree shaped like a small LM backward pass:
    a few large matmul grads plus a tail of small bias/norm grads."""
    scale = 1 if quick else 4
    rng = np.random.default_rng(0)
    tree = {}
    for i in range(4 * scale):
        tree[f"layer{i}/w"] = rng.integers(
            -8, 8, (128, 256)).astype(np.float32)
        tree[f"layer{i}/b"] = rng.integers(-8, 8, (256,)).astype(np.float32)
        tree[f"layer{i}/ln"] = rng.integers(-8, 8, (128,)).astype(np.float32)
    return jax.tree.map(jnp.asarray, tree)


def _bucketed_reduction(quick: bool, schedules, record):
    if not schedules:
        return
    ndev = len(jax.devices())
    mesh = make_mesh((ndev,), ("x",))
    tree = _grad_tree(quick)
    total = tree_bytes(tree)
    nleaves = len(jax.tree.leaves(tree))
    # monolithic = one bucket; bucketed = a few buckets; model = the
    # topology-derived size (pipeline depth x per-hop latency-bw product);
    # leafwise = the pathological many-small-collectives end
    model_bytes = CollectiveEngine.for_mesh(mesh).bucket_bytes_for("x")
    bucket_modes = {"monolithic": 1 << 40, "bucketed": max(total // 4, 1),
                    "model": model_bytes, "leafwise": 1}
    print(f"== bucketed vs monolithic gradient reduction "
          f"({nleaves} leaves, {fmt_bytes(total)}, ring of {ndev}, "
          f"model bucket {fmt_bytes(model_bytes)}) ==")
    rows = []
    for schedule in schedules:
        eng = CollectiveEngine.for_mesh(mesh, schedule=schedule)
        times = {}
        for mode, bucket_bytes in bucket_modes.items():
            # resolved name at this mode's bucket payload (allreduce_tree
            # resolves per bucket, so the mode's effective payload — one
            # bucket, capped by the whole tree — is what auto actually sees)
            resolved = eng.schedule_for(
                "allreduce", nbytes=min(bucket_bytes, total), axis="x")
            fn = jax.jit(shard_map(
                partial(eng.allreduce_tree, axis="x",
                        bucket_bytes=bucket_bytes),
                mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False))
            _, t = timeit(fn, tree, reps=2 if quick else 3)
            times[mode] = t
            record[f"reduce/{schedule}/{mode}"] = {
                "bytes": total, "leaves": nleaves, "time": t,
                "bucket_bytes": bucket_bytes, "schedule": resolved,
                "gbps": total / t / 1e9}
        rows.append([schedule] + [f"{times[m] * 1e3:.2f}ms"
                                  for m in bucket_modes]
                    + [f"{times['monolithic'] / times['bucketed']:.2f}x"])
    print(table(rows, ["allreduce schedule"] + list(bucket_modes)
                + ["mono/bucketed"]))
    print()


def main(quick: bool = False, schedule=None):
    record = {}
    bcasts = [s for s in schedules_for("bcast") if s != "staged"]
    reduces = [s for s in schedules_for("allreduce") if s != "staged"]
    if schedule == "auto":
        # cost-model resolution per callsite — its own sweep column
        bcasts, reduces = ["auto"], ["auto"]
    elif schedule is not None:  # sweep mode: restrict to the swept schedule;
        # a schedule with no counterpart for an op skips that half rather
        # than duplicating another schedule's measurement in the sweep
        bcasts = [s for s in bcasts if s == schedule]
        reduces = [s for s in reduces if s == schedule]
    _hpl_lookahead(quick, bcasts, record)
    if schedule in (None, "auto"):
        # the strip pipeline resolves its own schedule per callsite
        _ptrans_pipeline(quick, record)
    _bucketed_reduction(quick, reduces, record)
    save_result("overlap_bench", record)
    return record


if __name__ == "__main__":
    main()
