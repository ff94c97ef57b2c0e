"""Degraded- and dead-link resilience end-to-end on the 8-device mesh
(ISSUES 8 + 9).

Acceptance:
* degrade a link mid-run -> the RetuneController detects the drift ->
  a narrow retune re-prices and ``invalidate_resolutions`` swaps the
  resolved schedule **on the same engine object** (no rebuild) -> the
  bcast keeps returning bit-identical results through both flips;
* an ``InjectedFailure`` crash under ``step_mode="explicit_tp"`` resumes
  from the last checkpoint and lands on the uninterrupted run's loss;
* sever a ring hop -> the health mask reroutes bcast and allreduce onto
  the rooted chain, bit-identical to the healthy ring, for every break
  position;
* lose a rank mid-run -> ``train_loop_elastic`` resumes on the largest
  divisible survivor mesh from the resharded checkpoint, bitwise equal
  to a control run restored from the same snapshot.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.comm.autotune import CostModel, _seg_time, route_links, segments
from repro.comm.callsites import HPL_PANEL
from repro.comm.engine import CollectiveEngine, schedules_for
from repro.comm.faults import FaultInjector, FaultSchedule
from repro.comm.retune import RetuneController, Watched
from repro.comm.types import TPU_V5E
from repro.launch.mesh import make_mesh
from repro.configs import RunConfig
from repro.configs.qwen3_moe_235b_a22b import tiny
from repro.data import DataConfig
from repro.train.loop import InjectedFailure, TrainLoopConfig, train_loop

NDEV = 8
pytestmark = pytest.mark.skipif(
    len(jax.devices()) < NDEV, reason=f"needs {NDEV} devices")

NBYTES = 16384
FAULT_AT, HEAL_AT, STEPS = 8, 20, 30


@pytest.fixture(scope="module")
def ring():
    return make_mesh((NDEV,), ("x",))


def _modeled_step(inj, axes, bcast_schedule):
    """Analytic step time on the injector's current link numbers: the
    watched panel bcast at its *current* resolution plus a fixed rs_ag
    allreduce canary that always rides the ring — the canary is what lets
    the controller see the heal after the bcast has retuned onto the
    link-avoiding staged route."""
    hw = inj.hardware_view()
    total = 0.0
    for op, schedule in (("bcast", bcast_schedule), ("allreduce", "rs_ag")):
        total += sum(_seg_time(s, hw)
                     for s in segments(op, schedule, NBYTES, axes, hw))
    return total


def test_degrade_retune_heal_bit_identical(ring):
    eng = CollectiveEngine.for_mesh(
        ring, cost_model=CostModel(hw=TPU_V5E, table=None))
    axes = eng.topology.axes
    inj = FaultInjector(hw=TPU_V5E)
    fault = FaultSchedule.degrade_window(inj, FAULT_AT, HEAL_AT, axis="x",
                                         beta_scale=64.0)
    ctrl = RetuneController(eng, [Watched(HPL_PANEL, "bcast", NBYTES, "x")],
                            drift_factor=1.75, recent=2, min_baseline=3,
                            cooldown=2, hw_probe=inj.hardware_view)

    x = np.arange(NDEV * (NBYTES // 4), dtype=np.int32).reshape(NDEV, -1)

    def run_bcast():
        # rebuilt per phase from the SAME engine: the swap must land
        # through re-tracing alone, never through a new engine
        fn = jax.jit(shard_map(
            lambda v: eng.bcast(v[0], "x", 0, callsite=HPL_PANEL)[None],
            mesh=ring, in_specs=(P("x", None),), out_specs=P("x", None),
            check_vma=False))
        return np.asarray(fn(jnp.asarray(x)))

    outputs, resolved = {}, {}
    for step in range(STEPS):
        fault.apply(step)
        now = ctrl.resolutions()[HPL_PANEL]
        ctrl.observe(step, _modeled_step(inj, axes, now))
        if step == FAULT_AT - 1:
            resolved["before"], outputs["before"] = now, run_bcast()
        elif step == HEAL_AT - 1:
            resolved["during"], outputs["during"] = now, run_bcast()
        elif step == STEPS - 1:
            resolved["after"], outputs["after"] = now, run_bcast()

    # the resolution provably flipped away and back, on one engine object
    assert ctrl.engine is eng
    assert resolved["during"] != resolved["before"]
    assert resolved["after"] == resolved["before"]
    assert {resolved["before"], resolved["during"]} <= \
        set(schedules_for("bcast"))

    flips = [e for e in ctrl.events if e.changed]
    assert len(flips) >= 2
    assert flips[0].changed == {
        HPL_PANEL: (resolved["before"], resolved["during"])}
    # detection is prompt on both edges (two-sided drift)
    assert 0 <= flips[0].step - FAULT_AT <= 6
    assert 0 <= flips[1].step - HEAL_AT <= 6

    # exact routes: every phase is bit-identical and correct
    want = np.broadcast_to(x[0], x.shape)
    for phase, out in outputs.items():
        np.testing.assert_array_equal(out, want, err_msg=phase)


def test_injected_failure_resume_explicit_tp(ring, tmp_path):
    cfg = tiny(NDEV, layers=2)
    data = DataConfig(cfg.vocab_size, NDEV, 16)

    def _run(ckdir, **kw):
        run = RunConfig(checkpoint_dir=str(ckdir), checkpoint_every=2,
                        learning_rate=1e-3, warmup_steps=1)
        return train_loop(cfg, run, data,
                          TrainLoopConfig(steps=5, step_mode="explicit_tp",
                                          **kw),
                          mesh=ring)

    with pytest.raises(InjectedFailure):
        _run(tmp_path / "ck", fail_at_step=4)
    resumed = _run(tmp_path / "ck")
    assert resumed["step"][0] == 2  # restarted from the step-2 checkpoint

    clean = _run(tmp_path / "fresh")
    assert clean["step"] == list(range(5))
    np.testing.assert_allclose(resumed["loss"][-1], clean["loss"][-1],
                               rtol=1e-6)


@pytest.mark.parametrize("hop", [0, 3, NDEV - 1])
def test_rerouted_ring_bit_identical_to_healthy(ring, hop):
    """With hop severed, both collectives re-resolve onto the rooted chain
    and return exactly the healthy ring's bytes — for breaks at the
    wraparound, mid-ring, and the default cut position."""
    eng = CollectiveEngine.for_mesh(
        ring, cost_model=CostModel(hw=TPU_V5E, table=None))
    inj = FaultInjector(hw=TPU_V5E)
    x = np.arange(NDEV * (NBYTES // 4), dtype=np.int32).reshape(NDEV, -1)

    def run():
        fn = jax.jit(shard_map(
            lambda v: (eng.bcast(v[0], "x", 2)[None],
                       eng.allreduce(v, "x")),
            mesh=ring, in_specs=(P("x", None),),
            out_specs=(P("x", None), P("x", None)), check_vma=False))
        b, a = fn(jnp.asarray(x))
        return np.asarray(b), np.asarray(a)

    healthy = run()
    inj.down_link("x", hop)
    eng.invalidate_resolutions(health=inj.down_links())
    for op in ("bcast", "allreduce"):
        resolved = eng.schedule_for(op, nbytes=NBYTES, axis="x")
        assert resolved == "chain_rooted", (op, hop, resolved)
        route = route_links(op, resolved, eng.topology.axes,
                            health=inj.down_links())
        assert route is not None and ("x", hop) not in route
    rerouted = run()
    np.testing.assert_array_equal(rerouted[0], healthy[0],
                                  err_msg=f"bcast hop={hop}")
    np.testing.assert_array_equal(rerouted[1], healthy[1],
                                  err_msg=f"allreduce hop={hop}")
    np.testing.assert_array_equal(healthy[0], np.broadcast_to(x[2], x.shape))
    np.testing.assert_array_equal(healthy[1],
                                  np.broadcast_to(x.sum(axis=0), x.shape))


def test_rank_loss_elastic_resume_bitwise(ring, tmp_path):
    """Rank 7 dies at step 3: the loop resumes on the 4-survivor mesh from
    the resharded checkpoint, bitwise equal to a control restored from the
    identical snapshot on the identical mesh."""
    from repro.train.loop import train_loop_elastic

    cfg = tiny(NDEV, layers=2)
    data = DataConfig(cfg.vocab_size, NDEV, 16)

    def _lcfg(**kw):
        return TrainLoopConfig(steps=5, step_mode="explicit_tp", **kw)

    def _rcfg(ckdir):
        return RunConfig(checkpoint_dir=str(ckdir), checkpoint_every=2,
                         learning_rate=1e-3, warmup_steps=1)

    inj = FaultInjector(hw=TPU_V5E)
    fault = FaultSchedule.rank_loss(inj, 3, rank=NDEV - 1)
    hist, rec = train_loop_elastic(
        cfg, _rcfg(tmp_path / "ck"), data, _lcfg(fault_schedule=fault),
        mesh=ring, snapshot_dir=str(tmp_path / "snap"))

    assert rec is not None
    assert rec["lost_ranks"] == [NDEV - 1] and rec["fail_step"] == 3
    assert rec["new_size"] == 4 and rec["old_size"] == NDEV
    assert rec["resume_step"] <= rec["fail_step"]
    assert hist["step"][-1] == 4  # the resumed run finished all 5 steps

    devices = list(np.asarray(ring.devices).flat)
    ctrl_mesh = make_mesh((4,), ("x",),
                          devices=np.array(devices[:4]))
    ctrl = train_loop(cfg, _rcfg(tmp_path / "snap"), data, _lcfg(),
                      mesh=ctrl_mesh)
    i = hist["step"].index(rec["resume_step"])
    assert hist["loss"][i:] == ctrl["loss"]  # bitwise, not approx
