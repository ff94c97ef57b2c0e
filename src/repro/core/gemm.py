"""GEMM — C = alpha A B + beta C per device (paper legacy suite).

Embarrassingly parallel; per-device compute is the Pallas blocked matmul.
The paper normalizes to one kernel replication at 100 MHz with an 8x8x8
register tile (102.4 GFLOP/s theoretical); the TPU report normalizes to one
MXU at the roofline constants instead (benchmarks/legacy_suite.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.comm.types import CommunicationType
from repro.core.hpcc import BenchResult, register, timeit
from repro.kernels.ops import matmul


@register("gemm")
def run_gemm(mesh, comm=CommunicationType.ICI_DIRECT, *, m: int = 512,
             reps: int = 3) -> BenchResult:
    n_dev = mesh.devices.size
    key = jax.random.PRNGKey(0)
    spec = NamedSharding(mesh, P("x", None, None))
    a = jax.device_put(
        jax.random.normal(key, (n_dev, m, m), jnp.float32) / np.sqrt(m), spec)
    b = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (n_dev, m, m), jnp.float32)
        / np.sqrt(m), spec)

    fn = jax.jit(shard_map(
        lambda x, y: matmul(x[0], y[0], bm=128, bn=128, bk=128)[None],
        mesh=mesh, in_specs=(P("x", None, None),) * 2,
        out_specs=P("x", None, None), check_vma=False))
    out, t = timeit(fn, a, b, reps=reps)

    # reference on the device at full float32 precision; the error is
    # relative to the largest entry (entries shrink like 1/sqrt(m))
    ref = jnp.dot(a[0], b[0], precision=lax.Precision.HIGHEST)
    err = float(jnp.max(jnp.abs(out[0] - ref)) / jnp.max(jnp.abs(ref)))

    flops = 2.0 * m ** 3 * n_dev
    return BenchResult(
        name="gemm", metric_name="GFLOP/s", metric=flops / t / 1e9, error=err,
        times={"best": t}, details={"m": m, "devices": n_dev})
