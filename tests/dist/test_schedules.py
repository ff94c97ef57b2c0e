"""Schedule-equivalence suite on the simulated 8-device mesh.

Every registered schedule of the collective engine must produce *identical*
results for the same op — inputs are small integers in float32, so every
summation order is exact and equality is bitwise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.comm.engine import CollectiveEngine
from repro.comm.types import CommunicationType as CT
from repro.launch.mesh import make_mesh

NDEV = 8
pytestmark = pytest.mark.skipif(
    len(jax.devices()) < NDEV, reason=f"needs {NDEV} devices")


@pytest.fixture(scope="module")
def ring():
    return make_mesh((NDEV,), ("x",))


@pytest.fixture(scope="module")
def torus():
    return make_mesh((2, 2), ("rows", "cols"))


def _ints(shape, seed=0):
    return np.random.default_rng(seed).integers(-8, 8, shape).astype(np.float32)


def _run_ring(mesh, body):
    spec = P("x", None, None)
    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                           check_vma=False))
    return lambda x: np.asarray(fn(jnp.asarray(x)))


# ---------------------------------------------------------------------------
# bcast
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule", ["chain", "native", "staged", "ring2d"])
@pytest.mark.parametrize("src", [0, 3, 7])
def test_bcast_schedules_identical(ring, schedule, src):
    x = _ints((NDEV, 4, 128))
    eng = CollectiveEngine.for_mesh(ring, schedule=schedule)
    out = _run_ring(ring, lambda v: eng.bcast(v[0], "x", jnp.int32(src))[None])(x)
    np.testing.assert_array_equal(out, np.broadcast_to(x[src], out.shape))


def test_bcast_ragged_payload(ring):
    """ring2d pads internally: payload size not divisible by n."""
    x = _ints((NDEV, 3, 5), seed=9)
    for schedule in ("chain", "ring2d", "staged"):
        eng = CollectiveEngine.for_mesh(ring, schedule=schedule)
        out = _run_ring(ring, lambda v: eng.bcast(v[0], "x", 5)[None])(x)
        np.testing.assert_array_equal(out, np.broadcast_to(x[5], out.shape))


# ---------------------------------------------------------------------------
# allreduce
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule", ["native", "chain", "staged", "rs_ag",
                                      "ring2d"])
def test_allreduce_schedules_identical(ring, schedule):
    x = _ints((NDEV, 6, 128), seed=1)
    eng = CollectiveEngine.for_mesh(ring, schedule=schedule)
    out = _run_ring(ring, lambda v: eng.allreduce(v[0], "x")[None])(x)
    np.testing.assert_array_equal(out, np.broadcast_to(x.sum(0), out.shape))


def test_allreduce_ring2d_torus_axes(torus):
    """ring2d over ('rows','cols'): one ring pass per torus dimension."""
    x = _ints((4, 2, 64), seed=2)
    eng = CollectiveEngine.for_mesh(torus, schedule="ring2d")
    spec = P(("rows", "cols"), None, None)
    fn = jax.jit(shard_map(
        lambda v: eng.allreduce(v[0], ("rows", "cols"))[None],
        mesh=torus, in_specs=(spec,), out_specs=spec, check_vma=False))
    out = np.asarray(fn(jnp.asarray(x)))
    np.testing.assert_array_equal(out, np.broadcast_to(x.sum(0), out.shape))


def test_allreduce_scalar_payload(ring):
    x = _ints((NDEV, 1, 1), seed=3)
    for schedule in ("chain", "rs_ag", "staged", "native"):
        eng = CollectiveEngine.for_mesh(ring, schedule=schedule)
        out = _run_ring(ring, lambda v: eng.allreduce(v[0], "x")[None])(x)
        np.testing.assert_array_equal(out, np.broadcast_to(x.sum(0), out.shape))


# ---------------------------------------------------------------------------
# all_to_all_tiles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule", ["native", "chain", "staged"])
def test_all_to_all_schedules_identical(ring, schedule):
    x = _ints((NDEV, NDEV * 2, 16), seed=4)
    eng = CollectiveEngine.for_mesh(ring, schedule=schedule)
    out = _run_ring(ring, lambda v: eng.all_to_all_tiles(
        v[0], "x", split_axis=0, concat_axis=0)[None])(x)
    # reference: rank j gets split j of every source rank, ordered by source
    want = np.stack([
        np.concatenate([x[i, j * 2:(j + 1) * 2] for i in range(NDEV)], 0)
        for j in range(NDEV)])
    np.testing.assert_array_equal(out.reshape(want.shape), want)


# ---------------------------------------------------------------------------
# ring_exchange / grid_transpose
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("comm,schedule", [(CT.ICI_DIRECT, "direct"),
                                           (CT.ICI_DIRECT, "staged"),
                                           (CT.HOST_STAGED, "auto")])
def test_ring_exchange_schedules_identical(ring, comm, schedule):
    f, b = _ints((NDEV, 1, 32), seed=5), _ints((NDEV, 1, 32), seed=6)
    eng = CollectiveEngine.for_mesh(ring, comm, schedule)
    spec = P("x", None, None)
    fn = jax.jit(shard_map(
        lambda vf, vb: tuple(o[None] for o in
                             eng.ring_exchange(vf[0], vb[0], "x")),
        mesh=ring, in_specs=(spec, spec), out_specs=(spec, spec),
        check_vma=False))
    rl, rr = fn(jnp.asarray(f), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(rl), np.roll(f, 1, 0))
    np.testing.assert_array_equal(np.asarray(rr), np.roll(b, -1, 0))


@pytest.mark.parametrize("schedule", ["direct", "staged", "ring2d"])
def test_grid_transpose_schedules_identical(torus, schedule):
    x = _ints((4, 8, 8), seed=7)
    eng = CollectiveEngine.for_mesh(torus, schedule=schedule)
    spec = P(("rows", "cols"), None, None)
    fn = jax.jit(shard_map(
        lambda v: eng.grid_transpose(v[0], ("rows", "cols"), 2)[None],
        mesh=torus, in_specs=(spec,), out_specs=spec, check_vma=False))
    out = np.asarray(fn(jnp.asarray(x)))
    want = x.reshape(2, 2, 8, 8).transpose(1, 0, 2, 3).reshape(4, 8, 8)
    np.testing.assert_array_equal(out, want)


# ---------------------------------------------------------------------------
# end-to-end: benchmarks and MoE dispatch through the engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule", ["chain", "native", "ring2d"])
def test_hpl_torus_schedules_converge(torus, schedule):
    from repro.core.hpl import run_hpl
    res = run_hpl(torus, CT.ICI_DIRECT, n=128, b=32, schedule=schedule,
                  reps=1)
    assert res.error < 1.0, (schedule, res.error)
    assert res.details["schedule"] == schedule


def test_ptrans_schedules_agree(torus):
    from repro.core.ptrans import run_ptrans
    for comm, schedule in ((CT.ICI_DIRECT, "auto"), (CT.ICI_DIRECT, "ring2d"),
                           (CT.HOST_STAGED, "auto")):
        res = run_ptrans(torus, comm, n=128, b=32, reps=1, schedule=schedule)
        assert res.error < 1e-5, (comm, schedule, res.error)
        if schedule != "auto":
            assert res.details["schedule"] == schedule


def test_moe_exchange_dispatch_roundtrip(ring):
    from repro.models.moe import exchange_combine, exchange_dispatch
    B_loc, E, C, D = 2, NDEV * 2, 3, 8  # E divisible by ranks
    buf = _ints((NDEV, B_loc, E, C, D), seed=8)
    for schedule in ("native", "chain", "staged"):
        eng = CollectiveEngine.for_mesh(ring, schedule=schedule)
        spec = P("x", None, None, None, None)
        fn = jax.jit(shard_map(
            lambda v: exchange_combine(
                exchange_dispatch(v[0], "x", eng), "x", eng)[None],
            mesh=ring, in_specs=(spec,), out_specs=spec, check_vma=False))
        out = np.asarray(fn(jnp.asarray(buf)))
        np.testing.assert_array_equal(out, buf)


def test_dp_train_step_explicit_engine_schedules(ring):
    """The explicit DP step runs through engine.allreduce for every named
    reduction schedule and produces identical losses (exact for one step
    with identical inputs and bit-equal reductions is not guaranteed for
    float grads, so assert finite + close)."""
    from repro.configs import RunConfig, get_config, reduced
    from repro.models.model import build_model
    from repro.train.step import (init_train_state,
                                  make_dp_train_step_explicit)
    cfg = reduced(get_config("llama3.2-3b"), layers=1, d_model=32)
    model = build_model(cfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (NDEV, 16)), jnp.int32)}
    losses = {}
    for kind in ("native", "chain", "rs_ag"):
        run = RunConfig(learning_rate=1e-3, warmup_steps=1)
        state = init_train_state(model, jax.random.key(0))
        step = make_dp_train_step_explicit(model, run, ring,
                                           schedule_kind=kind)
        _, metrics = step(state, batch)
        losses[kind] = float(metrics["loss"])
        assert np.isfinite(losses[kind]), kind
    base = losses["native"]
    for kind, val in losses.items():
        np.testing.assert_allclose(val, base, rtol=1e-5, err_msg=kind)
