"""STREAM — memory bandwidth benchmark (paper legacy suite, §3.4).

Embarrassingly parallel across devices (the paper's multi-FPGA extension
only coordinates measurement); per-device compute is the Pallas triad/add/
scale/copy kernels. Metric: aggregated GB/s, normalized per HBM stack in the
benchmark report (the paper normalizes per memory bank).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.comm.types import CommunicationType
from repro.core.hpcc import BenchResult, register, timeit
from repro.kernels import ops


@register("stream")
def run_stream(mesh, comm=CommunicationType.ICI_DIRECT, *,
               elems_per_device: int = 1 << 20, reps: int = 3) -> BenchResult:
    n_dev = mesh.devices.size
    n = elems_per_device * n_dev
    spec = NamedSharding(mesh, P("x"))
    key = jax.random.PRNGKey(0)
    a = jax.device_put(jax.random.normal(key, (n,), jnp.float32), spec)
    b = jax.device_put(jax.random.normal(key, (n,), jnp.float32), spec)
    alpha = 3.0

    smap = lambda fn, n_in: jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(P("x"),) * n_in, out_specs=P("x"),
        check_vma=False))

    copy = smap(ops.stream_copy, 1)
    scale = smap(lambda x: ops.stream_scale(x, alpha), 1)
    add = smap(ops.stream_add, 2)
    triad = smap(lambda x, y: ops.stream_triad(x, y, alpha), 2)

    times = {}
    bw = {}
    _, times["copy"] = timeit(copy, a, reps=reps)
    _, times["scale"] = timeit(scale, a, reps=reps)
    _, times["add"] = timeit(add, a, b, reps=reps)
    out, times["triad"] = timeit(triad, a, b, reps=reps)
    bytes_per = {"copy": 2, "scale": 2, "add": 3, "triad": 3}
    for k, t in times.items():
        bw[k] = bytes_per[k] * 4.0 * n / t

    err = float(jnp.max(jnp.abs(out - (a + alpha * b))))
    return BenchResult(
        name="stream", metric_name="triad_B/s", metric=bw["triad"], error=err,
        times=times, details={"bandwidth": bw, "devices": n_dev,
                              "elems_per_device": elems_per_device})
