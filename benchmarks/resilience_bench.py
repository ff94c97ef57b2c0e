"""Beyond-paper — degraded-link resilience benchmark.

The paper's circuit-switched network can silently fall back to slower
routing, and its barrier discipline means one slow link paces the whole
machine. This module measures the full adaptive loop on the simulated
mesh, four sections:

* **train retune** (GATED, fully deterministic) — a scripted
  :class:`~repro.comm.faults.FaultSchedule` degrades one ring link
  (``beta_scale`` bandwidth collapse) mid-run; the
  :class:`~repro.comm.retune.RetuneController` watches modeled step
  timings, detects the drift, re-prices the engine on the injector's
  degraded :class:`HardwareModel`, and
  ``CollectiveEngine.invalidate_resolutions`` swaps the ``hpl.panel``
  bcast schedule mid-run without rebuilding the engine. After the heal
  event the same two-sided detector flips it back. Recorded: detection
  latency (steps), retune latency (seconds), the per-phase resolved
  schedule, and the bit-identity of the actual jitted bcast outputs
  across all three phases. SystemExit(1) unless the schedule provably
  flips away and back AND the outputs stay bit-identical.
* **measured retune** (informational) — the narrow
  :func:`~repro.comm.autotune.autotune_mesh` ladder for the hot callsite
  with the injector active vs clean: measured winners on the simulated
  CPU mesh are noisy, so this section records but never gates.
* **train degradation** (GATED on detection) — a real
  :func:`~repro.train.loop.train_loop` run with an injected host-delay
  window: the StragglerMonitor must flag inside the window, and the
  'checkpoint' policy must have forced an off-cadence save.
* **serve degradation** (GATED, deterministic) — the continuous-batching
  engine on a page pool too small for its workload, ``preempt=True``,
  with a host-delay window on ``serve.step``: tokens/sec before/during/
  after the fault, preemption/flip counts, and token-exact equality
  against a never-preempting large-pool run (zero lost tokens).
"""
from __future__ import annotations

import shutil
import tempfile
import time

from benchmarks.common import ensure_devices, save_result, table

ensure_devices()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import shard_map  # noqa: E402
import numpy as np  # noqa: E402

from repro.comm.autotune import (CostModel, _seg_time,  # noqa: E402
                                 autotune_mesh, segments)
from repro.comm.callsites import HPL_PANEL  # noqa: E402
from repro.comm.engine import CollectiveEngine, schedules_for  # noqa: E402
from repro.comm.faults import FaultInjector, FaultSchedule, injected  # noqa: E402
from repro.comm.retune import RetuneController, Watched  # noqa: E402
from repro.comm.topology import MeshTopology  # noqa: E402
from repro.comm.types import TPU_V5E  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

P = jax.sharding.PartitionSpec

NBYTES = 16384          # the watched hpl.panel payload (per shard)
BETA_SCALE = 64.0       # bandwidth collapse on the degraded link
FAULT_AT, HEAL_AT = 8, 20
STEPS = 30


def _modeled_step(inj: FaultInjector, axes, bcast_schedule: str) -> float:
    """Deterministic stand-in for one step's comm wall-time under the
    injector's current link state: the watched bcast at its *current*
    resolution plus a fixed-schedule gradient allreduce that always rides
    the ring — so a healed link shows up even while the bcast has been
    retuned onto a link-avoiding schedule."""
    hw = inj.hardware_view()
    t = 0.0
    for op, schedule in (("bcast", bcast_schedule), ("allreduce", "rs_ag")):
        t += sum(_seg_time(s, hw)
                 for s in segments(op, schedule, NBYTES, axes, hw))
    return t


def _train_retune_section(quick: bool):
    """Detect -> narrow retune -> invalidate -> schedule flip, bit-exact."""
    ndev = len(jax.devices())
    if ndev < 2:
        return {"skipped": f"needs >= 2 devices, have {ndev}"}

    mesh = make_mesh((ndev,), ("x",))
    topo = MeshTopology.from_mesh(mesh)
    axes = (topo.axis("x"),)
    inj = FaultInjector(hw=TPU_V5E)
    fault = FaultSchedule.degrade_window(
        inj, FAULT_AT, HEAL_AT, axis="x", hop=0, beta_scale=BETA_SCALE)
    # explicit analytic-only cost model: isolated from any measured
    # tuning.json the CI autotune step produced for the CPU mesh
    engine = CollectiveEngine.for_mesh(mesh,
                                       cost_model=CostModel(hw=TPU_V5E))
    ctrl = RetuneController(
        engine, [Watched(HPL_PANEL, "bcast", NBYTES, "x")],
        drift_factor=1.75, recent=2, min_baseline=3, cooldown=2,
        hw_probe=inj.hardware_view)

    x = np.arange(ndev * (NBYTES // 4), dtype=np.int32).reshape(ndev, -1)

    def _run_bcast():
        # rebuilt per phase: the jitted program re-resolves at trace time,
        # from the SAME engine object — only the cost model was mutated
        fn = jax.jit(shard_map(
            lambda v: engine.bcast(v[0], "x", 0, callsite=HPL_PANEL)[None],
            mesh=mesh, in_specs=(P("x", None),), out_specs=P("x", None),
            check_vma=False))
        return np.asarray(fn(jnp.asarray(x)))

    trace = []
    outputs = {}
    for step in range(STEPS):
        fault.apply(step)
        resolved = ctrl.resolutions()[HPL_PANEL]
        dur = _modeled_step(inj, axes, resolved)
        event = ctrl.observe(step, dur)
        trace.append({"step": step, "resolved": resolved,
                      "modeled_s": dur, "retuned": event is not None})
        phase = ("before" if step < FAULT_AT
                 else "during" if step < HEAL_AT else "after")
        if phase not in outputs:
            outputs[phase] = _run_bcast()

    by_phase = {ph: sorted({t["resolved"] for t in trace
                            if lo <= t["step"] < hi})
                for ph, lo, hi in (("before", 0, FAULT_AT),
                                   ("during", FAULT_AT, HEAL_AT),
                                   ("after", HEAL_AT, STEPS))}
    events = [{"step": e.step, "trigger": e.trigger,
               "detect_steps": e.detect_steps, "duration_s": e.duration_s,
               "changed": e.changed} for e in ctrl.events]
    flips = [e for e in ctrl.events if e.changed]
    bit_identical = all(
        np.array_equal(outputs["before"], outputs[ph]) for ph in outputs)
    ref = np.broadcast_to(x[0], outputs["before"].shape)
    return {
        "devices": ndev, "nbytes": NBYTES, "beta_scale": BETA_SCALE,
        "fault_at": FAULT_AT, "heal_at": HEAL_AT, "steps": STEPS,
        "resolved_before": trace[FAULT_AT - 1]["resolved"],
        "resolved_during": trace[HEAL_AT - 1]["resolved"],
        "resolved_after": trace[STEPS - 1]["resolved"],
        "by_phase": by_phase, "events": events,
        "flip_events": len(flips),
        "detect_degrade_steps": (flips[0].step - FAULT_AT) if flips else None,
        "detect_heal_steps": (flips[1].step - HEAL_AT) if len(flips) > 1
        else None,
        "retune_s": max((e.duration_s for e in ctrl.events), default=0.0),
        "time": max((e.duration_s for e in ctrl.events), default=0.0),
        "bit_identical": bit_identical,
        "bcast_correct": bool(np.array_equal(outputs["before"], ref)),
        "schedule": trace[HEAL_AT - 1]["resolved"],
    }


def _gate_train_retune(sec) -> None:
    if "skipped" in sec:
        return
    bad = []
    if sec["resolved_during"] == sec["resolved_before"]:
        bad.append("schedule never flipped away under the degraded link")
    if sec["resolved_after"] != sec["resolved_before"]:
        bad.append("schedule never flipped back after the heal")
    if sec["flip_events"] < 2:
        bad.append(f"expected >= 2 flip events, saw {sec['flip_events']}")
    if not sec["bit_identical"]:
        bad.append("bcast outputs diverged across schedule flips")
    if not sec["bcast_correct"]:
        bad.append("bcast output wrong vs the broadcast reference")
    for k in ("detect_degrade_steps", "detect_heal_steps"):
        if sec[k] is None or not 0 <= sec[k] <= 6:
            bad.append(f"{k}={sec[k]} outside [0, 6]")
    for name in (sec["resolved_before"], sec["resolved_during"]):
        if name not in schedules_for("bcast"):
            bad.append(f"unregistered resolution {name!r}")
    if bad:
        print("TRAIN-RETUNE GATE FAILED:", bad)
        raise SystemExit(1)


def _measured_retune_section(quick: bool):
    """Informational: the narrow measured ladder with the injector active.
    CPU-mesh microbenchmarks are noisy — recorded, never gated."""
    ndev = len(jax.devices())
    if ndev < 2:
        return {"skipped": f"needs >= 2 devices, have {ndev}"}
    inj = FaultInjector(hw=TPU_V5E, delay_scale=1e4)
    inj.degrade_link("x", 0, beta_scale=BETA_SCALE)
    sizes = (NBYTES,) if quick else (NBYTES // 4, NBYTES, NBYTES * 4)
    t0 = time.perf_counter()
    clean, _ = autotune_mesh(ops=("bcast@hpl.panel",), sizes=sizes,
                             reps=1, quick=True)
    with injected(inj):
        degraded, _ = autotune_mesh(ops=("bcast@hpl.panel",), sizes=sizes,
                                    reps=1, quick=True)
    return {
        "devices": ndev, "sizes": list(sizes),
        "clean_winners": clean.entries.get("bcast@hpl.panel", {}),
        "degraded_winners": degraded.entries.get("bcast@hpl.panel", {}),
        "wall_s": time.perf_counter() - t0,
    }


def _train_degradation_section(quick: bool):
    """A real train_loop run through a host-delay window: the monitor must
    flag inside the window and force an off-cadence checkpoint."""
    from repro.checkpoint.manager import all_steps, restore
    from repro.configs import get_config, reduced
    from repro.configs.base import RunConfig
    from repro.data.pipeline import DataConfig
    from repro.train.loop import TrainLoopConfig, train_loop

    steps, lo, hi = 16, 10, 13
    delay_s = 0.25
    cfg = reduced(get_config("llama3.2-3b"), layers=2, d_model=32)
    ckdir = tempfile.mkdtemp(prefix="resilience_ck_")
    try:
        run = RunConfig(checkpoint_dir=ckdir, checkpoint_every=100,
                        learning_rate=1e-2, warmup_steps=2,
                        step_deadline_factor=2.0)
        data = DataConfig(vocab_size=cfg.vocab_size, global_batch=4,
                          seq_len=32)
        inj = FaultInjector(hw=TPU_V5E)
        fault = FaultSchedule.degrade_window(
            inj, lo, hi, axis="x", host_delay_s=delay_s,
            callsite="train.step")
        hist = train_loop(cfg, run, data, TrainLoopConfig(
            steps=steps, straggler_policy="checkpoint",
            fault_schedule=fault))
        flagged = hist["straggler"].get("flagged", [])
        forced = []
        for s in all_steps(ckdir):
            _, _, extra = restore(ckdir, {}, step=s)
            if extra.get("forced"):
                forced.append(s)
        times = hist["step_time"]
        return {
            "steps": steps, "fault_window": [lo, hi], "delay_s": delay_s,
            "flagged": flagged,
            "detected": any(lo <= f < hi for f in flagged),
            "forced_checkpoints": forced,
            "median_before_s": float(np.median(times[1:lo])),
            "median_during_s": float(np.median(times[lo:hi])),
            "median_after_s": float(np.median(times[hi:])),
            "time": float(np.median(times[lo:hi])),
        }
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)


def _gate_train_degradation(sec) -> None:
    if "skipped" in sec:
        return
    bad = []
    if not sec["detected"]:
        bad.append(f"no straggler flag inside the fault window "
                   f"{sec['fault_window']} (flagged={sec['flagged']})")
    if not sec["forced_checkpoints"]:
        bad.append("the 'checkpoint' policy forced no off-cadence save")
    if bad:
        print("TRAIN-DEGRADATION GATE FAILED:", bad)
        raise SystemExit(1)


def _tok_per_s(stats, lo, hi):
    window = [s for s in stats[lo:hi] if s["decode_tokens"]]
    toks = sum(s["decode_tokens"] for s in window)
    secs = sum(s["decode_s"] for s in window)
    return toks / secs if secs > 0 else 0.0


def _serve_degradation_section(quick: bool):
    """Preempting small-pool engine under a host-delay window vs a large
    pool that never degrades: token-exact, with tok/s phases recorded."""
    from repro.configs import get_config, reduced
    from repro.models.kvcache import PagedCacheConfig
    from repro.models.model import build_model
    from repro.serve import ServeEngine

    cfg = reduced(get_config("llama3.2-3b"), layers=2, d_model=32)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(11)
    n_req, max_new = 3, 8
    prompts = [rng.integers(0, cfg.vocab_size, size=(4,)).astype(np.int32)
               for _ in range(n_req)]

    big = ServeEngine(model, params, PagedCacheConfig(
        page_size=4, num_pages=16, max_slots=4, max_seq=16))
    for p in prompts:
        big.submit(p, max_new)
    ref = big.run()

    lo, hi = 4, 8
    inj = FaultInjector(hw=TPU_V5E)
    fault = FaultSchedule.degrade_window(
        inj, lo, hi, axis="x", host_delay_s=0.02, callsite="serve.step")
    small = ServeEngine(model, params, PagedCacheConfig(
        page_size=4, num_pages=4, max_slots=2, max_seq=16),
        preempt=True, fault_schedule=fault)
    for p in prompts:
        small.submit(p, max_new)
    out, stats = small.run(collect_stats=True)

    lost = sum(int(ref[r].shape[0] - out[r].shape[0]) for r in ref)
    return {
        "requests": n_req, "max_new": max_new,
        "small_pool_pages": 4, "big_pool_pages": 16,
        "fault_window": [lo, hi], "steps": len(stats),
        "preempted": small.scheduler.preempted_total,
        "timeouts": sum(s["timeouts"] for s in stats),
        "rejected": sum(s["rejected"] for s in stats),
        "tok_per_s_before": _tok_per_s(stats, 1, lo),
        "tok_per_s_during": _tok_per_s(stats, lo, hi),
        "tok_per_s_after": _tok_per_s(stats, hi, len(stats)),
        "tokens_lost": lost,
        "token_identical": all(np.array_equal(ref[r], out[r]) for r in ref),
        "time": _tok_per_s(stats, lo, hi) and
        1.0 / max(_tok_per_s(stats, lo, hi), 1e-9),
    }


def _gate_serve_degradation(sec) -> None:
    if "skipped" in sec:
        return
    bad = []
    if not sec["token_identical"] or sec["tokens_lost"]:
        bad.append(f"preemption lost tokens (lost={sec['tokens_lost']})")
    if sec["preempted"] < 1:
        bad.append("pool pressure never triggered a preemption")
    if bad:
        print("SERVE-DEGRADATION GATE FAILED:", bad)
        raise SystemExit(1)


def main(quick: bool = False, schedule=None):
    if schedule not in (None, "auto"):
        print(f"[resilience: --schedule {schedule} ignored — this module "
              "measures the adaptive auto path]")
    record = {}

    tr = _train_retune_section(quick)
    record["train_retune"] = tr
    if "skipped" in tr:
        print(f"-- train retune: {tr['skipped']} --")
    else:
        print("-- adaptive retune under a scripted degraded link "
              f"(beta/{BETA_SCALE:.0f} on one ring hop) --")
        print(table(
            [[ph, "/".join(tr["by_phase"][ph])]
             for ph in ("before", "during", "after")],
            ["phase", "hpl.panel resolution(s)"]))
        print(f"   detect: degrade +{tr['detect_degrade_steps']} steps, "
              f"heal +{tr['detect_heal_steps']} steps; "
              f"retune {tr['retune_s'] * 1e3:.1f}ms; "
              f"bit-identical={tr['bit_identical']}")
    _gate_train_retune(tr)

    mr = _measured_retune_section(quick)
    record["measured_retune"] = mr
    if "skipped" in mr:
        print(f"\n-- measured retune: {mr['skipped']} --")
    else:
        print("\n-- narrow measured ladder, injector active "
              "(informational — CPU timing noise) --")
        print(f"   clean:    {mr['clean_winners']}")
        print(f"   degraded: {mr['degraded_winners']}")

    td = _train_degradation_section(quick)
    record["train_degradation"] = td
    print("\n-- train loop through a host-delay window "
          f"({td['delay_s']*1e3:.0f}ms over steps {td['fault_window']}) --")
    print(table([[td["flagged"], td["forced_checkpoints"],
                  f"{td['median_before_s']*1e3:.1f}ms",
                  f"{td['median_during_s']*1e3:.1f}ms",
                  f"{td['median_after_s']*1e3:.1f}ms"]],
                ["flagged", "forced ckpt", "median before", "during",
                 "after"]))
    _gate_train_degradation(td)

    sd = _serve_degradation_section(quick)
    record["serve_degradation"] = sd
    print("\n-- serve under page exhaustion + host-delay window --")
    print(table([[sd["preempted"], sd["tokens_lost"],
                  f"{sd['tok_per_s_before']:.1f}",
                  f"{sd['tok_per_s_during']:.1f}",
                  f"{sd['tok_per_s_after']:.1f}",
                  sd["token_identical"]]],
                ["preempted", "lost", "tok/s before", "during", "after",
                 "token-exact"]))
    _gate_serve_degradation(sd)

    save_result("resilience_bench", record)
    return record


if __name__ == "__main__":
    main()
