"""Fault-tolerant training loop.

One loop covers the quickstart, the 100M end-to-end example, and the
fault-tolerance tests:

* auto-resume: on start, if the checkpoint dir holds a valid step, restore
  it (elastically — the current mesh may differ from the saving mesh);
* periodic atomic checkpoints (+ a forced one when the straggler policy is
  'checkpoint' and a step blows its deadline);
* crash injection for tests: ``fail_at_step`` raises mid-run *after* the
  optimizer update but *before* that step's checkpoint, proving restart
  loses at most ``checkpoint_every`` steps;
* deterministic data: batches are a pure function of (seed, step), so a
  resumed run consumes exactly the batches the crashed run would have;
* elastic rank-loss recovery: when the fault schedule declares a device
  lost (``FaultInjector.fail_rank``), the loop raises
  :class:`~repro.comm.faults.RankLostError` and
  :func:`train_loop_elastic` rebuilds the mesh on the largest survivor
  count dividing the global batch, restores the latest checkpoint
  *resharded* onto it (``checkpoint.restore(..., reshard_to=mesh)``),
  and resumes — losing at most ``checkpoint_every`` steps of progress
  and zero data (batches are step-indexed).
"""
from __future__ import annotations

import logging
import shutil
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro import sharding as sh
from repro.comm.faults import RankLostError
from repro.configs.base import ModelConfig, RunConfig
from repro.data.pipeline import DataConfig, SyntheticLMDataset
from repro.models.model import Model, build_model
from repro.train.step import (TrainState, init_train_state, make_train_step,
                              make_whole_model_train_step_explicit,
                              shard_state, state_specs)
from repro.train.straggler import StepTimer, StragglerMonitor

log = logging.getLogger("repro.train")


@dataclass
class TrainLoopConfig:
    steps: int = 100
    log_every: int = 10
    fail_at_step: Optional[int] = None  # crash injection (tests)
    zero1: bool = True
    # "gspmd" (production jit path) | "explicit_tp" | "explicit_sp": the
    # explicit modes run the whole forward+backward inside one shard_map
    # with engine-routed collectives (make_whole_model_train_step_explicit)
    step_mode: str = "gspmd"
    # straggler reaction (repro.train.straggler.POLICIES): 'warn' |
    # 'checkpoint' (force an early save) | 'retune' (hand the flag to the
    # RetuneController below)
    straggler_policy: str = "checkpoint"
    # scripted degraded-link timeline (repro.comm.faults.FaultSchedule):
    # applied at each step's start, its host delays land inside the timed
    # region so the StragglerMonitor sees them
    fault_schedule: Optional[object] = None
    # adaptive retuning (repro.comm.retune.RetuneController): observes every
    # step duration; on a retune event under an explicit step_mode the
    # jitted step is rebuilt so the next trace picks up the swapped
    # schedules (the engine itself is never rebuilt)
    retune: Optional[object] = None


class InjectedFailure(RuntimeError):
    pass


def train_loop(model_cfg: ModelConfig, run_cfg: RunConfig, data_cfg: DataConfig,
               loop_cfg: TrainLoopConfig, *, mesh=None,
               key=None) -> Dict[str, List[float]]:
    """Returns metric history. Resumes from run_cfg.checkpoint_dir if set."""
    model = build_model(model_cfg)
    dataset = SyntheticLMDataset(data_cfg)
    key = key if key is not None else jax.random.key(run_cfg.seed)

    state = init_train_state(model, key)
    start_step = 0

    manager = None
    if run_cfg.checkpoint_dir:
        manager = ckpt.CheckpointManager(
            run_cfg.checkpoint_dir, every=run_cfg.checkpoint_every,
            keep=run_cfg.keep_checkpoints)
        if manager.has_checkpoint:
            if mesh is not None and loop_cfg.step_mode != "gspmd":
                # explicit whole-model layout: derive the shardings from
                # whole_model_param_specs on the *current* mesh — the
                # elastic path when it differs from the saving mesh
                start_step, trees, extra = manager.restore_latest(
                    {"state": state}, reshard_to=mesh)
            else:
                shardings = None
                if mesh is not None:
                    rules = sh.rules_for(mesh)
                    specs = state_specs(state, rules, mesh,
                                        zero1=loop_cfg.zero1)
                    shardings = {"state": jax.tree.map(
                        lambda s: jax.NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(
                            x, jax.sharding.PartitionSpec))}
                start_step, trees, extra = manager.restore_latest(
                    {"state": state}, shardings)
            state = trees["state"]
            log.info("resumed from checkpoint step %d", start_step)

    explicit = loop_cfg.step_mode != "gspmd"
    if explicit:
        # whole-model explicit path: the step's own shard_map in_specs place
        # the state (experts sharded, rest replicated) — no GSPMD shard_state
        if loop_cfg.step_mode not in ("explicit_tp", "explicit_sp"):
            raise ValueError(f"unknown step_mode {loop_cfg.step_mode!r}; "
                             "use 'gspmd', 'explicit_tp', or 'explicit_sp'")
        if mesh is None:
            raise ValueError("explicit step_mode requires a mesh")
        step_fn = make_whole_model_train_step_explicit(
            model, run_cfg, mesh, attn_mode=loop_cfg.step_mode[len("explicit_"):],
            total_steps=loop_cfg.steps)
    else:
        if mesh is not None and start_step == 0:
            state = shard_state(state, mesh, zero1=loop_cfg.zero1)
        step_mesh = mesh or jax.sharding.Mesh(np.array(jax.devices()[:1]),
                                              ("x",))
        if mesh is None:
            # give step 0 the placement the step returns, or the second call
            # sees new input shardings and compiles the step again
            state = jax.device_put(state, jax.NamedSharding(
                step_mesh, jax.sharding.PartitionSpec()))
        step_fn = make_train_step(model, run_cfg, step_mesh,
                                  total_steps=loop_cfg.steps)

    monitor = StragglerMonitor(deadline_factor=run_cfg.step_deadline_factor,
                               policy=loop_cfg.straggler_policy)
    retuner = loop_cfg.retune
    schedule = loop_cfg.fault_schedule
    history: Dict[str, List[float]] = {"loss": [], "step_time": [], "step": []}

    for step in range(start_step, loop_cfg.steps):
        if schedule is not None:
            schedule.apply(step)
            lost = schedule.injector.lost_ranks
            if lost:
                # the mesh as built no longer exists: surface the loss with
                # the partial history attached so train_loop_elastic can
                # rebuild on the survivors and resume from the checkpoint
                err = RankLostError(lost, step)
                err.history = history
                raise err
        batch_np = dataset.batch(step)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        if mesh is not None and not explicit:
            rules = sh.rules_for(mesh)
            bspec = sh.batch_specs(batch, rules, mesh)
            batch = {k: jax.device_put(v, jax.NamedSharding(mesh, bspec[k]))
                     for k, v in batch.items()}

        with StepTimer() as t:
            if schedule is not None:
                # inside the timed region: the monitor and the retune
                # controller both see the injected degradation
                schedule.injector.sleep("train.step")
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
        straggled = monitor.record(step, t.duration)

        if retuner is not None:
            if straggled and monitor.policy == "retune":
                event = retuner.on_straggler(step)
            else:
                event = retuner.observe(step, t.duration)
            if event is not None and explicit:
                # resolutions swapped — rebuild the (cheap) jitted step so
                # the next trace picks up the new schedules
                step_fn = make_whole_model_train_step_explicit(
                    model, run_cfg, mesh,
                    attn_mode=loop_cfg.step_mode[len("explicit_"):],
                    total_steps=loop_cfg.steps)

        history["loss"].append(loss)
        history["step_time"].append(t.duration)
        history["step"].append(step)
        if step % loop_cfg.log_every == 0:
            log.info("step %d loss %.4f (%.3fs)", step, loss, t.duration)

        next_step = step + 1
        if loop_cfg.fail_at_step is not None and next_step == loop_cfg.fail_at_step:
            raise InjectedFailure(f"injected failure before step {next_step}")

        if manager is not None:
            if straggled and monitor.policy == "checkpoint":
                manager.save(next_step, {"state": state},
                             extra={"loss": loss, "forced": True}, force=True)
            else:
                manager.maybe_save(next_step, {"state": state},
                                   extra={"loss": loss})

    if manager is not None:
        manager.save(loop_cfg.steps, {"state": state}, extra={"final": True},
                     force=True)
    history["straggler"] = monitor.summary()  # type: ignore[assignment]
    if retuner is not None:
        history["retune_events"] = retuner.events  # type: ignore[assignment]
    return history


def largest_divisible(survivors: int, global_batch: int) -> int:
    """The largest rank count <= ``survivors`` dividing ``global_batch`` —
    the biggest mesh the fixed batch reshards onto evenly."""
    if survivors < 1:
        raise ValueError(f"no survivors ({survivors})")
    for n in range(survivors, 1, -1):
        if global_batch % n == 0:
            return n
    return 1


def train_loop_elastic(model_cfg: ModelConfig, run_cfg: RunConfig,
                       data_cfg: DataConfig, loop_cfg: TrainLoopConfig, *,
                       mesh, key=None, snapshot_dir: Optional[str] = None
                       ) -> Tuple[Dict[str, List[float]], Optional[Dict]]:
    """:func:`train_loop` that survives a scripted rank loss.

    Runs the loop on ``mesh``; when the fault schedule fires ``fail_rank``
    and :class:`~repro.comm.faults.RankLostError` surfaces, it

    1. rebuilds the mesh on the **largest survivor count dividing the
       global batch** (:func:`largest_divisible` — the batch layout, not
       the hardware, caps elasticity),
    2. optionally snapshots the checkpoint directory to ``snapshot_dir``
       *before* resuming (so a control rerun can restore the exact
       checkpoint the recovery used),
    3. clears the injector's lost ranks (the one-shot schedule will not
       re-fire) and re-enters :func:`train_loop` on the survivor mesh —
       auto-resume restores the latest checkpoint resharded onto it via
       ``checkpoint.restore(..., reshard_to=mesh)``.

    Returns ``(history, recovery)``: the merged metric history (pre-loss
    steps + resumed steps) and a recovery record (``None`` when no rank
    was lost) with the lost ranks, fail/resume steps, survivor mesh size,
    and recovery wall-clock seconds.
    """
    try:
        return train_loop(model_cfg, run_cfg, data_cfg, loop_cfg,
                          mesh=mesh, key=key), None
    except RankLostError as e:
        t0 = time.perf_counter()
        if not run_cfg.checkpoint_dir:
            raise RuntimeError(
                "elastic recovery needs run_cfg.checkpoint_dir") from e
        devices = list(np.asarray(mesh.devices).flat)
        survivors = [d for i, d in enumerate(devices) if i not in e.ranks]
        if not survivors:
            raise RuntimeError("every rank lost; nothing to resume on") from e
        n = largest_divisible(len(survivors), data_cfg.global_batch)
        from repro.launch.mesh import make_mesh
        new_mesh = make_mesh((n,), tuple(mesh.axis_names),
                             devices=np.array(survivors[:n]))
        log.warning("rank(s) %s lost at step %d; resuming on %d survivors",
                    e.ranks, e.step, n)
        if snapshot_dir is not None:
            shutil.copytree(run_cfg.checkpoint_dir, snapshot_dir,
                            dirs_exist_ok=True)
        schedule = loop_cfg.fault_schedule
        if schedule is not None:
            schedule.injector.restore_ranks()
        resumed = train_loop(model_cfg, run_cfg, data_cfg, loop_cfg,
                             mesh=new_mesh, key=key)
        recovery = {
            "lost_ranks": list(e.ranks),
            "fail_step": e.step,
            "resume_step": int(resumed["step"][0]) if resumed["step"]
            else e.step,
            "old_size": len(devices),
            "new_size": n,
            "recovery_s": time.perf_counter() - t0,
        }
        partial = getattr(e, "history", None) or {}
        merged: Dict[str, List[float]] = dict(resumed)
        for k in ("loss", "step_time", "step"):
            pre = list(partial.get(k, ()))
            keep = [v for s, v in zip(partial.get("step", ()), pre)
                    if s < recovery["resume_step"]] if k != "step" else \
                   [s for s in partial.get("step", ())
                    if s < recovery["resume_step"]]
            merged[k] = keep + list(resumed.get(k, ()))
        return merged, recovery
