"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the fault-tolerant loop (repro.train.loop) on the local devices with a
reduced or full config. On a real cluster each host runs this same entry
point under ``jax.distributed.initialize`` (the mesh helper and data pipeline
are already multi-host safe: batches are pure functions of (seed, step,
shard) and checkpoint writes are per-shard).
"""
from __future__ import annotations

import argparse
import logging

import jax

from repro.compile_cache import use_compile_cache
from repro.configs import RunConfig, get_config, list_archs, reduced
from repro.data import DataConfig
from repro.launch.mesh import make_local_mesh
from repro.train.loop import (TrainLoopConfig, train_loop,
                              train_loop_elastic)


def parse_fault_args(fault_schedule, fail_rank):
    """Build the FaultSchedule a launcher's fault flags describe.

    ``fault_schedule`` is the :meth:`FaultSchedule.parse` spec string
    (``action@start[-end]:k=v,...`` separated by ``;``); ``fail_rank`` is
    the ``RANK@STEP`` shorthand appended to it as a rank-loss event.
    Returns None when neither flag is set.
    """
    if not fault_schedule and not fail_rank:
        return None
    from repro.comm.faults import FaultInjector, FaultSchedule
    spec = fault_schedule or ""
    if fail_rank:
        try:
            rank, at = fail_rank.split("@")
            part = f"fail_rank@{int(at)}:rank={int(rank)}"
        except ValueError:
            raise SystemExit(f"--fail-rank wants RANK@STEP, got "
                             f"{fail_rank!r}") from None
        spec = f"{spec};{part}" if spec else part
    return FaultSchedule.parse(FaultInjector(), spec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="train the reduced same-family config (CPU-sized)")
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none", choices=["none", "full", "dots"])
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--comm", default="ici_direct",
                    choices=["ici_direct", "host_staged"])
    ap.add_argument("--mesh", action="store_true",
                    help="use a (data, model) mesh over local devices")
    ap.add_argument("--fault-schedule", default=None, metavar="SPEC",
                    help="scripted fault timeline "
                         "(repro.comm.faults.FaultSchedule.parse), e.g. "
                         "'degrade@5-20:axis=x,hop=1,beta_scale=64' or "
                         "'down@5-20:axis=x,hop=3;fail_rank@12:rank=3'")
    ap.add_argument("--fail-rank", default=None, metavar="RANK@STEP",
                    help="shorthand: lose device RANK at step STEP and "
                         "resume elastically on the survivors (needs "
                         "--mesh and --checkpoint-dir)")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    run = RunConfig(comm_type=args.comm, microbatches=args.microbatches,
                    remat=args.remat, learning_rate=args.lr,
                    warmup_steps=max(args.steps // 10, 1),
                    checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every)
    data = DataConfig(vocab_size=cfg.vocab_size, global_batch=args.batch,
                      seq_len=args.seq)
    fault = parse_fault_args(args.fault_schedule, args.fail_rank)
    elastic = fault is not None and any(e.action == "fail_rank"
                                        for e in fault.events)
    # elastic recovery rebuilds a 1-D mesh on the survivors, so a rank-loss
    # timeline runs on the ring layout rather than the 2-D (data, model) one
    mesh = (make_local_mesh(("x",)) if elastic else make_local_mesh()) \
        if args.mesh else None

    lcfg = TrainLoopConfig(steps=args.steps, fault_schedule=fault)
    if elastic:
        if mesh is None or not args.checkpoint_dir:
            raise SystemExit("a fail_rank timeline needs --mesh and "
                             "--checkpoint-dir (elastic resume restores "
                             "the resharded checkpoint)")
        hist, recovery = train_loop_elastic(cfg, run, data, lcfg, mesh=mesh)
        if recovery is not None:
            print(f"recovered from rank loss {recovery['lost_ranks']} at "
                  f"step {recovery['fail_step']}: resumed on "
                  f"{recovery['new_size']}/{recovery['old_size']} devices "
                  f"from step {recovery['resume_step']} in "
                  f"{recovery['recovery_s']:.2f}s")
    else:
        hist = train_loop(cfg, run, data, lcfg, mesh=mesh)
    print(f"final loss {hist['loss'][-1]:.4f} "
          f"(first {hist['loss'][0]:.4f}); "
          f"median step {sorted(hist['step_time'])[len(hist['step_time'])//2]:.3f}s")
    print("straggler summary:", hist["straggler"])


if __name__ == "__main__":
    main()
