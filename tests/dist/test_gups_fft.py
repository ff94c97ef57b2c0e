"""Distributed GUPS + pencil FFT equivalence on the 8-device mesh.

The engine-routed RandomAccess must restore exactly under the inverse
update sequence, and agree with a numpy oracle that applies *every*
generated update, for every registered ``all_to_all_tiles`` schedule and
chunk count. The pencil FFT localizes full signals before transforming, so
its output is **bitwise** ``jnp.fft.fft`` per schedule x chunking.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm.callsites import FFT_TRANSPOSE, RA_UPDATES
from repro.comm.engine import CollectiveEngine, schedules_for
from repro.launch.mesh import make_mesh
from repro.core import fft as FFT
from repro.core import randomaccess as RA

NDEV = 8
pytestmark = pytest.mark.skipif(
    len(jax.devices()) < NDEV, reason=f"needs {NDEV} devices")

A2A_SCHEDULES = sorted(schedules_for("all_to_all_tiles"))
NCHUNKS = [1, 2, "auto"]

TABLE_LOG = 12
UPR = 64  # updates per rng stream


@pytest.fixture(scope="module")
def ring():
    return make_mesh((NDEV,), ("x",))


def _ra_fixtures(ring):
    table, seeds = RA._make_table_and_seeds(ring, table_log=TABLE_LOG,
                                            rngs_per_device=2)
    return table, seeds


def _np_apply_all_updates(table: np.ndarray, seeds: np.ndarray,
                          sign: int) -> np.ndarray:
    """Oracle: every generated update applied to its global address with
    int32 wraparound — what the routed path must compute."""
    out = table.astype(np.int64)
    mask = (1 << TABLE_LOG) - 1
    for s in seeds.reshape(-1):
        x = int(s) & 0xFFFFFFFF
        for _ in range(UPR):
            x = ((x << 1) & 0xFFFFFFFF) ^ (int(RA.POLY) if x >> 31 else 0)
            upd = np.int64(np.int32(np.uint32(x))) * sign
            out[x & mask] += upd
    # int32 wraparound semantics
    return out.astype(np.int64).astype(np.int32)


@pytest.mark.parametrize("nchunks", NCHUNKS)
@pytest.mark.parametrize("schedule", A2A_SCHEDULES)
def test_routed_gups_restores_exactly(ring, schedule, nchunks):
    res = RA.run_randomaccess_dist(ring, table_log=TABLE_LOG,
                                   rngs_per_device=2, updates_per_rng=UPR,
                                   reps=1, schedule=schedule,
                                   nchunks=nchunks)
    assert res.error == 0.0, (schedule, nchunks, res.error)
    assert res.details["schedule"] == schedule
    assert res.details["schedule"] != "auto"


@pytest.mark.parametrize("schedule", A2A_SCHEDULES)
def test_routed_gups_matches_global_oracle(ring, schedule):
    table, seeds = _ra_fixtures(ring)
    step = RA.make_routed_step(
        ring, CollectiveEngine.for_mesh(ring, schedule=schedule),
        updates_per_rng=UPR, table_log=TABLE_LOG, sign=+1)
    got = np.asarray(step(table, seeds))
    want = _np_apply_all_updates(np.asarray(table), np.asarray(seeds), +1)
    np.testing.assert_array_equal(got, want)


def test_routed_gups_schedules_agree_bitwise(ring):
    table, seeds = _ra_fixtures(ring)
    outs = {}
    for schedule in A2A_SCHEDULES:
        step = RA.make_routed_step(
            ring, CollectiveEngine.for_mesh(ring, schedule=schedule),
            updates_per_rng=UPR, table_log=TABLE_LOG, sign=+1)
        outs[schedule] = np.asarray(step(table, seeds))
    base = outs[A2A_SCHEDULES[0]]
    for schedule, out in outs.items():
        np.testing.assert_array_equal(out, base, err_msg=schedule)


def _fft_input(batch, n):
    rng = np.random.default_rng(11)
    return (rng.standard_normal((batch, n)).astype(np.float32)
            + 1j * rng.standard_normal((batch, n)).astype(np.float32)
            ).astype(np.complex64)


@pytest.mark.parametrize("nchunks", NCHUNKS)
@pytest.mark.parametrize("schedule", A2A_SCHEDULES)
def test_pencil_fft_bitwise_vs_jnp(ring, schedule, nchunks):
    batch, n = 2 * NDEV, 1 << 9
    x = _fft_input(batch, n)
    # the bitwise reference is jnp.fft.fft at the SAME (batch/P, n) block
    # shape each rank transforms — XLA's CPU FFT is shape-deterministic but
    # not row-independent across batch sizes, so the monolithic full-batch
    # transform differs in final bits (~1e-7 relative) while the
    # per-block transform, which is literally what the pencil path runs
    # after localizing full signals, must agree exactly
    blk = batch // NDEV
    ref = jax.jit(lambda a: jnp.fft.fft(a, axis=-1))
    want = np.concatenate([np.asarray(ref(x[j * blk:(j + 1) * blk]))
                           for j in range(NDEV)])

    engine = CollectiveEngine.for_mesh(ring, schedule=schedule)
    if nchunks == "auto":
        nchunks = engine.pipeline_chunks(
            "all_to_all_tiles", nbytes=batch * (n // NDEV) * 8, axis="x",
            callsite=FFT.CALLSITE)
    step = FFT.make_dist_step(ring, engine, nchunks=max(int(nchunks), 1))
    x_sh = jax.device_put(jnp.asarray(x), NamedSharding(ring, P(None, "x")))
    got = np.asarray(step(x_sh))
    np.testing.assert_array_equal(got, want, err_msg=f"{schedule}")
    # and the monolithic transform agrees to float32 FFT accuracy
    full = np.asarray(ref(jnp.asarray(x)))
    assert np.max(np.abs(got - full)) / np.max(np.abs(full)) < 1e-5


def test_pencil_fft_schedules_agree_bitwise(ring):
    batch, n = 2 * NDEV, 1 << 9
    x = _fft_input(batch, n)
    x_sh = jax.device_put(jnp.asarray(x), NamedSharding(ring, P(None, "x")))
    outs = {}
    for schedule in A2A_SCHEDULES:
        engine = CollectiveEngine.for_mesh(ring, schedule=schedule)
        for nchunks in (1, 2):
            step = FFT.make_dist_step(ring, engine, nchunks=nchunks)
            outs[(schedule, nchunks)] = np.asarray(step(x_sh))
    keys = sorted(outs)
    base = outs[keys[0]]
    for key in keys[1:]:
        np.testing.assert_array_equal(outs[key], base, err_msg=str(key))


def test_callsites_resolve_to_registered_schedules(ring):
    engine = CollectiveEngine.for_mesh(ring, schedule="auto")
    for callsite, nbytes in ((RA_UPDATES, 1 << 16), (FFT_TRANSPOSE, 1 << 16)):
        name = engine.schedule_for("all_to_all_tiles", nbytes=nbytes,
                                   axis="x", callsite=callsite)
        assert name != "auto" and name in schedules_for("all_to_all_tiles")
