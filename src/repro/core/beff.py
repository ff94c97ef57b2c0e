"""b_eff — effective bandwidth benchmark (paper §2.1).

Ring topology over all devices; message sizes 2^0 .. 2^max_log bytes are
exchanged with both ring neighbors simultaneously; the derived metric is
Eq. 1's effective bandwidth. The neighbor exchange routes through the
:class:`~repro.comm.engine.CollectiveEngine`:

* ``direct`` schedule under ICI_DIRECT — ``ppermute`` neighbor streams (the
  IEC/CSN implementation, paper Fig. 2: message chunks streamed to the
  neighbor, receive buffer forwarded to the send side for the next round via
  the carried state).
* ``staged`` (forced by HOST_STAGED) — every message transits the staging
  domain (PCIe+MPI path).

Verification follows the paper: the message is filled with byte value
``log2(size) mod 256`` and checked after the timed run.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.comm.engine import CollectiveEngine
from repro.comm.types import CommunicationType
from repro.core import models
from repro.core.hpcc import BenchResult, register, timeit


def _exchange_step(bufs, axis: str, engine: CollectiveEngine, rounds: int):
    """``rounds`` back-to-back bidirectional ring exchanges; the received
    buffers become the next round's send buffers (paper's internal-channel
    forwarding)."""
    def body(carry, _):
        fwd, bwd = carry
        recv_l, recv_r = engine.ring_exchange(fwd, bwd, axis)
        return (recv_l, recv_r), ()

    (fwd, bwd), _ = jax.lax.scan(body, bufs, None, length=rounds)
    return fwd, bwd


def make_step(mesh, engine: CollectiveEngine, rounds: int = 1):
    spec = P("x", None)
    fn = shard_map(
        partial(_exchange_step, axis="x", engine=engine, rounds=rounds),
        mesh=mesh, in_specs=((spec, spec),), out_specs=(spec, spec))
    return jax.jit(fn)


@register("b_eff")
def run_beff(mesh, comm=CommunicationType.ICI_DIRECT, *, max_log: int = 20,
             reps: int = 3, rounds: int = 4,
             schedule: str = "auto") -> BenchResult:
    """Measured b_eff over the devices of ``mesh`` (axis 'x')."""
    n = mesh.devices.size
    engine = CollectiveEngine.for_mesh(mesh, comm, schedule)
    bw: Dict[int, float] = {}
    times: Dict[str, float] = {}
    error = 0.0
    step = make_step(mesh, engine, rounds)
    for lg in range(max_log + 1):
        L = 2 ** lg
        fill = np.uint8(lg % 256)
        host = np.full((n, L), fill, np.uint8)
        fwd = jax.device_put(jnp.asarray(host), jax.NamedSharding(mesh, P("x", None)))
        bwd = jax.device_put(jnp.asarray(host), jax.NamedSharding(mesh, P("x", None)))
        (ofwd, obwd), t = timeit(step, (fwd, bwd), reps=reps)
        # bytes on the wire per round: every rank sends L fwd + L bwd
        total = 2.0 * L * n * rounds
        bw[L] = total / t
        times[f"L={L}"] = t
        ok = bool(jnp.all(ofwd == fill) & jnp.all(obwd == fill))
        error += 0.0 if ok else 1.0
    beff = models.effective_bandwidth(bw)
    # resolved provenance at the largest message (the bandwidth-defining
    # regime), never the literal "auto"
    resolved = engine.schedule_for("ring_exchange", nbytes=2 ** max_log,
                                   axis="x")
    return BenchResult(
        name="b_eff", metric_name="effective_bandwidth_B/s", metric=beff,
        error=error, times=times,
        details={"bandwidth_by_size": bw, "devices": n,
                 "comm": engine.comm.value,
                 "schedule": resolved,
                 "schedule_requested": engine.schedule,
                 "rounds": rounds})
