"""Beyond-paper — hard-failure survival benchmark.

PR 8's resilience bench covered *degraded* links (slow but alive); this
module covers links and ranks that are **gone**. Three gated sections:

* **link-down reroute** (GATED, fully deterministic) — one ring hop is
  marked hard-down (:meth:`~repro.comm.faults.FaultInjector.down_link`)
  and the health mask lands on the live engine through
  ``CollectiveEngine.invalidate_resolutions(health=...)``. The cost model
  prices every route crossing the cut at infinity, so bcast and allreduce
  re-resolve onto the rooted-chain schedule that detours away from the
  break. Recorded: the per-phase resolutions, the recovery latency (down
  event -> first successful rerouted collective), a
  :func:`~repro.comm.autotune.route_links` proof that the chosen route
  excludes the cut, and bit-identity of the outputs across all three
  phases. SystemExit(1) unless the schedule provably flips away and back
  AND the rerouted outputs are bit-identical to the healthy ones.
* **rank-loss elastic resume** (GATED) — a real ``explicit_tp``
  :func:`~repro.train.loop.train_loop_elastic` run loses a device
  mid-run (:meth:`FaultInjector.fail_rank`): the loop raises
  ``RankLostError``, rebuilds the mesh on the largest survivor count
  dividing the global batch, restores the latest checkpoint *resharded*
  onto it, and resumes. A control run restores the identical snapshot on
  an identical survivor mesh; the gate requires the resumed losses to
  match the control **bitwise**.
* **serve rank loss** (GATED) — the continuous-batching engine drains
  every request whose KV pages died with a lost rank (pages stripe
  ``p % nranks``): drained requests re-queue with ``tokens_so_far``
  intact and re-prefill onto surviving pages. Gate: every in-flight
  request completes token-identical to a fault-free run — zero lost
  tokens — with at least one drain observed, and tok/s recorded
  before/during/after the loss.
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

from repro.comm.autotune import CostModel, route_links  # noqa: E402
from repro.comm.engine import CollectiveEngine, schedules_for  # noqa: E402
from repro.comm.faults import FaultInjector, FaultSchedule  # noqa: E402
from repro.comm.topology import MeshTopology  # noqa: E402
from repro.comm.types import TPU_V5E  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

P = jax.sharding.PartitionSpec

NBYTES = 16384          # per-shard payload for the rerouted collectives
DOWN_HOP = 3            # the severed ring hop (wire between ranks 3 and 4)


def _link_down_section(quick: bool):
    """Sever one ring hop; the engine must re-resolve both ops onto a
    route that provably avoids it, bit-identically, then flip back."""
    ndev = len(jax.devices())
    if ndev < 2:
        return {"skipped": f"needs >= 2 devices, have {ndev}"}

    mesh = make_mesh((ndev,), ("x",))
    topo = MeshTopology.from_mesh(mesh)
    axes = (topo.axis("x"),)
    inj = FaultInjector(hw=TPU_V5E)
    # explicit analytic cost model: isolated from any measured tuning.json
    engine = CollectiveEngine.for_mesh(mesh,
                                       cost_model=CostModel(hw=TPU_V5E))

    n_ints = NBYTES // 4
    x = np.arange(ndev * n_ints, dtype=np.int32).reshape(ndev, -1)

    def _run():
        # rebuilt per phase from the SAME engine object: the reroute must
        # land through re-tracing alone, never through a new engine
        fn = jax.jit(shard_map(
            lambda v: (engine.bcast(v[0], "x", 0)[None],
                       engine.allreduce(v, "x")),
            mesh=mesh, in_specs=(P("x", None),),
            out_specs=(P("x", None), P("x", None)), check_vma=False))
        b, a = fn(jnp.asarray(x))
        return np.asarray(b), np.asarray(a)

    def _resolved():
        return {op: engine.schedule_for(op, nbytes=NBYTES, axis="x")
                for op in ("bcast", "allreduce")}

    res_before = _resolved()
    out_before = _run()

    t0 = time.perf_counter()
    inj.down_link("x", DOWN_HOP)
    down = inj.down_links()
    engine.invalidate_resolutions(health=down)
    res_during = _resolved()
    out_during = _run()           # first rerouted collective, jit included
    recovery_s = time.perf_counter() - t0

    # proof: the chosen route's link set exists and avoids the cut
    routes = {op: route_links(op, res_during[op], axes, health=down)
              for op in ("bcast", "allreduce")}
    excluded = all(r is not None and not (r & down)
                   for r in routes.values())

    inj.heal("x", DOWN_HOP)
    engine.invalidate_resolutions(health=inj.down_links())
    res_after = _resolved()
    out_after = _run()

    bit_identical = all(
        np.array_equal(out_before[i], out_during[i])
        and np.array_equal(out_before[i], out_after[i]) for i in (0, 1))
    ref_b = np.broadcast_to(x[0], x.shape)
    ref_a = np.broadcast_to(x.sum(axis=0), x.shape)
    return {
        "devices": ndev, "nbytes": NBYTES, "down_hop": DOWN_HOP,
        "resolved_before": res_before, "resolved_during": res_during,
        "resolved_after": res_after,
        "route_during": {op: sorted(map(list, r)) if r is not None else None
                         for op, r in routes.items()},
        "route_excludes_cut": excluded,
        "recovery_s": recovery_s,
        "bit_identical": bit_identical,
        "bcast_correct": bool(np.array_equal(out_before[0], ref_b)),
        "allreduce_correct": bool(np.array_equal(out_before[1], ref_a)),
        "time": recovery_s,
        "schedule": res_during["bcast"],
    }


def _gate_link_down(sec) -> None:
    if "skipped" in sec:
        return
    bad = []
    for op in ("bcast", "allreduce"):
        if sec["resolved_during"][op] == sec["resolved_before"][op]:
            bad.append(f"{op} never rerouted off the severed link")
        if sec["resolved_after"][op] != sec["resolved_before"][op]:
            bad.append(f"{op} never flipped back after the repair")
        if sec["resolved_during"][op] not in schedules_for(op):
            bad.append(f"unregistered {op} resolution "
                       f"{sec['resolved_during'][op]!r}")
    if not sec["route_excludes_cut"]:
        bad.append("a resolved route traverses the down link")
    if not sec["bit_identical"]:
        bad.append("outputs diverged across the reroute")
    if not (sec["bcast_correct"] and sec["allreduce_correct"]):
        bad.append("collective output wrong vs the reference")
    if bad:
        print("LINK-DOWN GATE FAILED:", bad)
        raise SystemExit(1)


def _rank_loss_section(quick: bool):
    """Lose a device mid-train; elastic resume must land bitwise on a
    control run restored from the identical checkpoint snapshot."""
    from repro.configs import RunConfig
    from repro.configs.qwen3_moe_235b_a22b import tiny
    from repro.data import DataConfig
    from repro.train.loop import (TrainLoopConfig, train_loop,
                                  train_loop_elastic)

    ndev = len(jax.devices())
    if ndev < 4:
        return {"skipped": f"needs >= 4 devices, have {ndev}"}
    steps, fail_at, lost_rank = (6, 4, ndev - 1) if quick \
        else (10, 6, ndev - 1)
    cfg = tiny(ndev, layers=2)
    data = DataConfig(cfg.vocab_size, ndev, 16)
    mesh = make_mesh((ndev,), ("x",))
    ck = tempfile.mkdtemp(prefix="failover_ck_")
    snap = tempfile.mkdtemp(prefix="failover_snap_")
    try:
        run = RunConfig(checkpoint_dir=ck, checkpoint_every=2,
                        learning_rate=1e-3, warmup_steps=1)
        inj = FaultInjector(hw=TPU_V5E)
        fault = FaultSchedule.rank_loss(inj, fail_at, rank=lost_rank)
        hist, rec = train_loop_elastic(
            cfg, run, data,
            TrainLoopConfig(steps=steps, step_mode="explicit_tp",
                            fault_schedule=fault),
            mesh=mesh, snapshot_dir=snap)

        # control: a fresh loop restoring the snapshot the recovery used,
        # on an identically-chosen survivor mesh
        devices = list(np.asarray(mesh.devices).flat)
        survivors = [d for i, d in enumerate(devices) if i != lost_rank]
        ctrl_mesh = make_mesh((rec["new_size"],), ("x",),
                              devices=np.array(survivors[:rec["new_size"]]))
        ctrl_run = RunConfig(checkpoint_dir=snap, checkpoint_every=2,
                             learning_rate=1e-3, warmup_steps=1)
        ctrl = train_loop(cfg, ctrl_run, data,
                          TrainLoopConfig(steps=steps,
                                          step_mode="explicit_tp"),
                          mesh=ctrl_mesh)
        i = hist["step"].index(rec["resume_step"])
        resumed_losses = hist["loss"][i:]
        return {
            "devices": ndev, "steps": steps, "fail_at": fail_at,
            "lost_rank": lost_rank,
            "recovery": rec,
            "completed": hist["step"][-1] == steps - 1 if hist["step"]
            else False,
            "resumed_losses": resumed_losses,
            "control_losses": list(ctrl["loss"]),
            "loss_bitwise": resumed_losses == list(ctrl["loss"]),
            "recovery_s": rec["recovery_s"],
            "time": rec["recovery_s"],
        }
    finally:
        shutil.rmtree(ck, ignore_errors=True)
        shutil.rmtree(snap, ignore_errors=True)


def _gate_rank_loss(sec) -> None:
    if "skipped" in sec:
        return
    bad = []
    rec = sec["recovery"]
    if rec is None:
        bad.append("rank loss never triggered elastic recovery")
    else:
        if rec["new_size"] >= rec["old_size"]:
            bad.append(f"survivor mesh did not shrink ({rec['old_size']} -> "
                       f"{rec['new_size']})")
        if rec["resume_step"] > rec["fail_step"]:
            bad.append(f"resume step {rec['resume_step']} past the failure "
                       f"at {rec['fail_step']}")
    if not sec["completed"]:
        bad.append("the resumed run never reached the final step")
    if not sec["loss_bitwise"]:
        bad.append("resumed losses diverge from the from-checkpoint control")
    if bad:
        print("RANK-LOSS GATE FAILED:", bad)
        raise SystemExit(1)


def _tok_per_s(stats, lo, hi):
    window = [s for s in stats[lo:hi] if s["decode_tokens"]]
    toks = sum(s["decode_tokens"] for s in window)
    secs = sum(s["decode_s"] for s in window)
    return toks / secs if secs > 0 else 0.0


def _serve_rank_loss_section(quick: bool):
    """Kill a rank mid-serve; every in-flight request must still finish
    with the token stream a fault-free run produces."""
    from repro.configs import get_config, reduced
    from repro.models.kvcache import PagedCacheConfig
    from repro.models.model import build_model
    from repro.serve import ServeEngine

    ndev = len(jax.devices())
    if ndev < 2:
        return {"skipped": f"needs >= 2 devices, have {ndev}"}
    cfg = reduced(get_config("llama3.2-3b"), layers=2, d_model=32)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(11)
    n_req, max_new = 3, 8
    prompts = [rng.integers(0, cfg.vocab_size, size=(4,)).astype(np.int32)
               for _ in range(n_req)]
    mesh = make_mesh((ndev,), ("x",))
    pcfg = PagedCacheConfig(page_size=4, num_pages=16, max_slots=4,
                            max_seq=16)

    ref_eng = ServeEngine(model, params, pcfg, mesh=mesh)
    for p in prompts:
        ref_eng.submit(p, max_new)
    ref = ref_eng.run()

    fail_at, lost_rank = 3, 3
    inj = FaultInjector(hw=TPU_V5E)
    fault = FaultSchedule.rank_loss(inj, fail_at, rank=lost_rank)
    eng = ServeEngine(model, params, pcfg, mesh=mesh, preempt=True,
                      fault_schedule=fault)
    for p in prompts:
        eng.submit(p, max_new)
    out, stats = eng.run(collect_stats=True)

    drained = sum(s["drained"] for s in stats)
    lost = sum(int(ref[r].shape[0] - out[r].shape[0]) for r in ref)
    return {
        "devices": ndev, "requests": n_req, "max_new": max_new,
        "fail_at": fail_at, "lost_rank": lost_rank,
        "steps": len(stats), "drained": drained,
        "tok_per_s_before": _tok_per_s(stats, 1, fail_at),
        "tok_per_s_during": _tok_per_s(stats, fail_at, fail_at + 2),
        "tok_per_s_after": _tok_per_s(stats, fail_at + 2, len(stats)),
        "tokens_lost": lost,
        "token_identical": all(np.array_equal(ref[r], out[r]) for r in ref),
    }


def _gate_serve_rank_loss(sec) -> None:
    if "skipped" in sec:
        return
    bad = []
    if not sec["token_identical"] or sec["tokens_lost"]:
        bad.append(f"rank loss lost tokens (lost={sec['tokens_lost']})")
    if sec["drained"] < 1:
        bad.append("the lost rank's pages never drained a request")
    if bad:
        print("SERVE-RANK-LOSS GATE FAILED:", bad)
        raise SystemExit(1)


def main(quick: bool = False, schedule=None):
    if schedule not in (None, "auto"):
        print(f"[failover: --schedule {schedule} ignored — this module "
              "measures the health-masked auto path]")
    record = {}

    ld = _link_down_section(quick)
    record["link_down"] = ld
    if "skipped" in ld:
        print(f"-- link-down reroute: {ld['skipped']} --")
    else:
        print(f"-- reroute around a severed ring hop "
              f"(hop {DOWN_HOP} hard-down) --")
        print(table(
            [[op, ld["resolved_before"][op], ld["resolved_during"][op],
              ld["resolved_after"][op]] for op in ("bcast", "allreduce")],
            ["op", "healthy", "severed", "repaired"]))
        print(f"   reroute latency {ld['recovery_s'] * 1e3:.1f}ms "
              f"(jit included); route excludes cut="
              f"{ld['route_excludes_cut']}; "
              f"bit-identical={ld['bit_identical']}")
    _gate_link_down(ld)

    rl = _rank_loss_section(quick)
    record["rank_loss"] = rl
    if "skipped" in rl:
        print(f"\n-- rank-loss elastic resume: {rl['skipped']} --")
    else:
        rec = rl["recovery"]
        print("\n-- elastic resume after losing rank "
              f"{rl['lost_rank']} at step {rl['fail_at']} --")
        print(table([[rec["old_size"], rec["new_size"], rec["fail_step"],
                      rec["resume_step"], f"{rec['recovery_s']:.2f}s",
                      rl["loss_bitwise"]]],
                    ["mesh", "survivors", "fail step", "resume step",
                     "recovery", "loss bitwise"]))
    _gate_rank_loss(rl)

    sl = _serve_rank_loss_section(quick)
    record["serve_rank_loss"] = sl
    if "skipped" in sl:
        print(f"\n-- serve rank loss: {sl['skipped']} --")
    else:
        print("\n-- serve through a rank loss (KV pages drained) --")
        print(table([[sl["drained"], sl["tokens_lost"],
                      f"{sl['tok_per_s_before']:.1f}",
                      f"{sl['tok_per_s_during']:.1f}",
                      f"{sl['tok_per_s_after']:.1f}",
                      sl["token_identical"]]],
                    ["drained", "lost", "tok/s before", "during", "after",
                     "token-exact"]))
    _gate_serve_rank_loss(sl)

    save_result("failover_bench", record)
    return record


if __name__ == "__main__":
    main()
