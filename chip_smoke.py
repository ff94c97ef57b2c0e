#!/usr/bin/env python3
"""Chip smoke run: the HPCC suite and one full-width training path, once,
on a TPU, each phase checked by its own correctness criterion.

    python3 chip_smoke.py              # one chip, every phase at full size
    python3 chip_smoke.py --chips 4    # 2x2 HPL + PTRANS, b_eff on a ring of
                                       # 4, and the same HPL on one chip
    JAX_PLATFORMS=cpu python3 chip_smoke.py --tiny [--chips 4]
                                       # rehearsal: toy sizes, Pallas in
                                       # interpret mode, never prints "ok"

Without ``--tiny`` any platform other than a TPU listed in the peaks table
(``repro.comm.types.PEAKS``) fails before a phase runs. Every phase prints
one ``smoke`` line (size, bytes, wall time, metric, error): these are smoke
numbers taken once, not benchmark results. A failed check or any error
exits nonzero. Only a full-size TPU run prints the last line,
``{"ok": true, "device": {...}}``, whose ``count`` is the chips used.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# one chip: (hpl n, hpl b, ptrans n, stream elems, gemm m, ra table log,
# ra updates log, fft log size, lm batch, lm seq); --tiny shrinks all of it
FULL = dict(hpl_n=16384, hpl_b=256, ptrans_n=16384, stream=1 << 27,
            gemm_m=8192, ra_log=28, ra_updates_log=24, fft_log=16,
            lm_batch=8, lm_seq=2048, lm_steps=3, beff_log=20)
TINY = dict(hpl_n=256, hpl_b=32, ptrans_n=256, stream=1 << 12, gemm_m=256,
            ra_log=12, ra_updates_log=10, fft_log=8, lm_batch=2, lm_seq=64,
            lm_steps=2, beff_log=4)

# |loss(default precision) - loss(highest)| bound for the first train step.
# The model runs bf16 activations; the two runs differ only in how float32
# matmul operands are rounded (one bf16 pass vs full float32): 5.7e-6 on a
# TPU v5e at batch 8 x 2048. Breaking the SSM's skip path or projections
# moves the loss by ~4 nats, dropping the state recurrence by ~8e-4 (CPU,
# batch 2 x 512, random init). The chunked SSD scan is also checked on its
# own against the sequential recurrence (SSD_TOL).
LM_LOSS_TOL = 1e-4
# max |chunked SSD - sequential recurrence| / max |recurrence|, both at
# float32 "highest" precision: reassociation across chunks only
SSD_TOL = 1e-3


class CheckFailed(RuntimeError):
    pass


def check(cond: bool, what: str):
    if not cond:
        raise CheckFailed(what)


def line(phase: str, **kv):
    parts = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"smoke {phase}: {parts}", flush=True)


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "n/a"))


def run_hpcc_one_chip(sz, dev):
    import jax
    import jax.numpy as jnp

    from repro.core.hpcc import get_benchmark
    from repro.core.hpl import generate_system, make_factorize
    from repro.launch.mesh import make_mesh

    torus = make_mesh((1, 1), ("rows", "cols"), devices=[dev])
    ring = make_mesh((1,), ("x",), devices=[dev])

    # HPL: residual through the registry, then lookahead vs eager bitwise
    n, b = sz["hpl_n"], sz["hpl_b"]
    t0 = time.perf_counter()
    r = get_benchmark("hpl")(torus, n=n, b=b, reps=1)
    line("hpl", n=n, b=b, data_bytes=n * n * 4, peak_device_bytes=peak_bytes(dev),
         wall_s=round(time.perf_counter() - t0, 3),
         gemm_precision="HIGHEST(fp32)", lu_trsm="vpu-fp32",
         metric=f"{r.metric:.6g}{r.metric_name}", residual=f"{r.error:.6g}")
    check(r.error < 1.0, f"hpl normalized residual {r.error} >= 1")

    a, _, _ = generate_system(n)
    a_sh = jax.device_put(a[None], dev)
    t0 = time.perf_counter()
    eager = make_factorize(torus, pg=1, nb=n // b, b=b)(a_sh)
    look = make_factorize(torus, pg=1, nb=n // b, b=b, lookahead=1)(a_sh)
    ndiff = int(jnp.sum(eager != look))
    line("hpl_lookahead", n=n, b=b, depth=1,
         wall_s=round(time.perf_counter() - t0, 3), differing_elements=ndiff)
    check(ndiff == 0, f"lookahead LU differs from eager in {ndiff} elements")
    del eager, look, a_sh

    n = sz["ptrans_n"]
    t0 = time.perf_counter()
    r = get_benchmark("ptrans")(torus, n=n, b=256, reps=2)
    line("ptrans", n=n, data_bytes=3 * n * n * 4,
         peak_device_bytes=peak_bytes(dev),
         wall_s=round(time.perf_counter() - t0, 3),
         metric=f"{r.metric:.6g}{r.metric_name}", max_abs_err=r.error)
    check(r.error == 0.0, f"ptrans C != B + A^T (max err {r.error})")

    elems = sz["stream"]
    t0 = time.perf_counter()
    r = get_benchmark("stream")(ring, elems_per_device=elems, reps=2)
    line("stream", elems=elems, data_bytes=3 * elems * 4,
         peak_device_bytes=peak_bytes(dev),
         wall_s=round(time.perf_counter() - t0, 3),
         metric=f"{r.metric:.6g}{r.metric_name}", max_abs_err=r.error)
    # a, b ~ N(0, 1): |a + 3b| < 40 over 2^27 draws, so the kernel and XLA
    # differ by at most two float32 roundings of it (< 5e-6)
    check(r.error <= 1e-5, f"stream triad error {r.error} > 1e-5")

    m = sz["gemm_m"]
    t0 = time.perf_counter()
    r = get_benchmark("gemm")(ring, m=m, reps=2)
    line("gemm", m=m, data_bytes=3 * m * m * 4,
         peak_device_bytes=peak_bytes(dev),
         wall_s=round(time.perf_counter() - t0, 3),
         metric=f"{r.metric:.6g}{r.metric_name}", rel_err=f"{r.error:.3g}")
    # float32 products at HIGHEST differ from the reference by reassociation
    # (~1e-6 relative); a single bf16 pass would give ~1e-3
    check(r.error < 1e-4, f"gemm relative error {r.error} >= 1e-4")

    log, ulog = sz["ra_log"], sz["ra_updates_log"]
    rngs = 1 << (ulog - 10)
    t0 = time.perf_counter()
    r = get_benchmark("randomaccess")(ring, table_log=log, rngs_per_device=rngs,
                                      updates_per_rng=1 << 10, reps=1)
    line("randomaccess", table_words=1 << log, data_bytes=4 << log,
         updates=1 << ulog, peak_device_bytes=peak_bytes(dev),
         wall_s=round(time.perf_counter() - t0, 3),
         metric=f"{r.metric:.6g}{r.metric_name}", mismatched_fraction=r.error)
    check(r.error == 0.0, f"randomaccess restore mismatch {r.error}")

    log = sz["fft_log"]
    t0 = time.perf_counter()
    r = get_benchmark("fft")(ring, log_size=log, batch_per_device=64, reps=2)
    line("fft", log_size=log, batch=64, data_bytes=2 * 64 * 8 << log,
         peak_device_bytes=peak_bytes(dev),
         wall_s=round(time.perf_counter() - t0, 3),
         metric=f"{r.metric:.6g}{r.metric_name}", rel_err=f"{r.error:.3g}")
    # float32 FFT error grows like eps * log2(n) (~2e-6 here); a wrong
    # transform is off by O(1)
    check(r.error < 1e-3, f"fft relative error {r.error} >= 1e-3")


def check_ssd_scan(cfg):
    """The model's chunked SSD (intra-chunk matmuls + inter-chunk scan) on
    the device against the step-by-step recurrence, at the config's head
    dims over four chunks."""
    import jax
    import jax.numpy as jnp

    from repro.models.ssm import ssd_chunked, ssd_reference, ssm_dims

    _, H, P, G, N = ssm_dims(cfg)
    Q = cfg.ssm_chunk
    L = 4 * Q
    k = jax.random.split(jax.random.key(1), 4)
    x = jax.random.normal(k[0], (1, L, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k[1], (1, L, H)) - 4.0)
    A = -jnp.arange(1, H + 1, dtype=jnp.float32)
    B = jax.random.normal(k[2], (1, L, G, N), jnp.float32)
    C = jax.random.normal(k[3], (1, L, G, N), jnp.float32)
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        y, h = jax.jit(ssd_chunked, static_argnums=5)(x, dt, A, B, C, Q)
        y_ref, h_ref = ssd_reference(x, dt, A, B, C)
    err = float(jnp.max(jnp.abs(y - y_ref)) / jnp.max(jnp.abs(y_ref)))
    herr = float(jnp.max(jnp.abs(h - h_ref)) / jnp.max(jnp.abs(h_ref)))
    line("ssd_scan", heads=H, head_dim=P, state=N, chunk=Q, length=L,
         wall_s=round(time.perf_counter() - t0, 3), y_rel_err=f"{err:.3g}",
         state_rel_err=f"{herr:.3g}")
    check(err < SSD_TOL and herr < SSD_TOL,
          f"chunked SSD vs recurrence: y {err}, state {herr} >= {SSD_TOL}")


def run_lm(sz, tiny: bool):
    import jax
    import numpy as np

    from repro.configs import RunConfig, get_config, reduced
    from repro.data import DataConfig, SyntheticLMDataset
    from repro.models.model import build_model, next_token_loss
    from repro.train.loop import TrainLoopConfig, train_loop

    cfg = get_config("mamba2-130m")
    if tiny:
        cfg = reduced(cfg)
    check_ssd_scan(cfg)
    steps = sz["lm_steps"]
    # what `python -m repro.launch.train --arch mamba2-130m --full
    # --remat full --batch 8 --seq 2048` builds
    run = RunConfig(remat="full", learning_rate=1e-3,
                    warmup_steps=max(steps // 10, 1))
    data = DataConfig(vocab_size=cfg.vocab_size, global_batch=sz["lm_batch"],
                      seq_len=sz["lm_seq"])

    model = build_model(cfg)
    params = model.init(jax.random.key(run.seed))  # train_loop's init
    batch = {k: jax.numpy.asarray(v)
             for k, v in SyntheticLMDataset(data).batch(0).items()}

    def loss_fn(p, bt):
        logits, _, _ = model.apply(p, bt, remat=run.remat)
        return next_token_loss(logits, bt["tokens"])

    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(loss_fn)(params, batch))
    del params

    t0 = time.perf_counter()
    hist = train_loop(cfg, run, data, TrainLoopConfig(steps=steps))
    wall = time.perf_counter() - t0
    losses = hist["loss"]
    line("train", arch=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model,
         batch=sz["lm_batch"], seq=sz["lm_seq"], steps=steps,
         wall_s=round(wall, 3),
         step_s=",".join(f"{t:.4f}" for t in hist["step_time"]),
         loss=",".join(f"{v:.6f}" for v in losses),
         loss0_highest=f"{ref:.6f}", loss0_diff=f"{abs(losses[0] - ref):.3g}")
    check(all(np.isfinite(losses)), f"non-finite loss {losses}")
    check(abs(losses[0] - ref) <= LM_LOSS_TOL,
          f"step-0 loss {losses[0]} vs highest-precision {ref}: "
          f"> {LM_LOSS_TOL}")


def run_four_chips(sz, devices):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.hpcc import get_benchmark
    from repro.core.hpl import (generate_system, make_factorize,
                                normalized_residual, solve_from_lu)
    from repro.core.ptrans import distribute_cyclic, undistribute_cyclic
    from repro.launch.mesh import make_mesh

    torus = make_mesh((2, 2), ("rows", "cols"), devices=devices)
    ring = make_mesh((4,), ("x",), devices=devices)
    for mesh in (torus, ring):
        check(len(set(mesh.devices.flat)) == 4,
              f"mesh {mesh.shape} does not span four devices")

    n, b = sz["hpl_n"], sz["hpl_b"]
    t0 = time.perf_counter()
    r = get_benchmark("hpl")(torus, n=n, b=b, reps=1)
    line("hpl_2x2", n=n, b=b, data_bytes=n * n * 4,
         wall_s=round(time.perf_counter() - t0, 3),
         schedule=r.details["schedule"],
         metric=f"{r.metric:.6g}{r.metric_name}", residual=f"{r.error:.6g}")
    check(r.error < 1.0, f"2x2 hpl normalized residual {r.error} >= 1")

    # the same system factored on the torus and on one of its chips
    a, _, rhs = generate_system(n)
    spec = NamedSharding(torus, P(("rows", "cols"), None, None))
    a4 = jax.device_put(distribute_cyclic(a, 2, b), spec)
    lu4 = undistribute_cyclic(
        np.asarray(make_factorize(torus, pg=2, nb=n // b, b=b)(a4)), 2, b)
    one = make_mesh((1, 1), ("rows", "cols"), devices=devices[:1])
    a1 = jax.device_put(a[None], devices[0])
    lu1 = np.asarray(make_factorize(one, pg=1, nb=n // b, b=b)(a1))[0]
    diff = float(np.max(np.abs(lu4 - lu1)))
    scale = float(np.max(np.abs(lu1)))
    res1 = normalized_residual(a, solve_from_lu(lu1, rhs), rhs)
    line("hpl_2x2_vs_1", n=n, b=b, max_abs_diff=f"{diff:.3g}",
         bitwise=bool(diff == 0.0), residual_1chip=f"{res1:.6g}")
    check(res1 < 1.0, f"1-chip hpl normalized residual {res1} >= 1")
    # the GEMM tiles and panel solves see the same operands on both grids;
    # only a broken exchange or layout moves an entry past float32 noise
    check(diff <= 1e-5 * scale, f"2x2 LU differs from 1-chip LU by {diff}")

    n = sz["ptrans_n"]
    t0 = time.perf_counter()
    r = get_benchmark("ptrans")(torus, n=n, b=min(256, n // 2), reps=2)
    line("ptrans_2x2", n=n, data_bytes=3 * n * n * 4,
         wall_s=round(time.perf_counter() - t0, 3),
         schedule=r.details["schedule"], nchunks=r.details["nchunks"],
         metric=f"{r.metric:.6g}{r.metric_name}", max_abs_err=r.error)
    check(r.error == 0.0, f"2x2 ptrans C != B + A^T (max err {r.error})")

    t0 = time.perf_counter()
    r = get_benchmark("b_eff")(ring, max_log=sz["beff_log"], reps=2)
    line("b_eff_ring4", max_message_bytes=1 << sz["beff_log"],
         wall_s=round(time.perf_counter() - t0, 3),
         schedule=r.details["schedule"],
         metric=f"{r.metric:.6g}{r.metric_name}", failed_sizes=r.error)
    check(r.error == 0.0, f"b_eff payload check failed at {r.error} sizes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes, any platform, no ok line (rehearsal)")
    args = ap.parse_args(argv)
    sz = TINY if args.tiny else FULL
    if args.tiny and args.chips > 1:
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={args.chips}")

    from repro.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()

    import jax

    from repro.comm.types import PEAKS

    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} device_kind={d0.device_kind!r} "
          f"count={len(devices)} compile_cache={cache_dir}", flush=True)
    if not args.tiny:
        check(d0.platform == "tpu", f"platform {d0.platform!r} is not a TPU "
              "(use --tiny to rehearse elsewhere)")
        check(d0.device_kind in PEAKS,
              f"no peaks for device kind {d0.device_kind!r}")
    check(len(devices) >= args.chips,
          f"{args.chips} chips asked for, {len(devices)} present")
    used = devices[:args.chips]

    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(sz, used)
    else:
        run_hpcc_one_chip(sz, used[0])
        run_lm(sz, args.tiny)
    print(f"smoke done: chips={args.chips} tiny={args.tiny} "
          f"wall_s={time.perf_counter() - t0:.3f}", flush=True)
    if not args.tiny:
        print(json.dumps({"ok": True, "device": {
            "platform": d0.platform, "kind": d0.device_kind,
            "count": len(used)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
