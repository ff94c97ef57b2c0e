"""Beyond-paper — continuous-batching serving benchmark (tiny qwen3-moe on
the simulated multi-device mesh).

Three sections:

* **decode equivalence** — the engine-routed explicit tensor-parallel
  decode step (``make_decode_step_explicit``, per-token collectives tagged
  ``decode.qkv`` / ``decode.out`` / ``decode.moe``) against the GSPMD
  paged decode from identical pages: logits AND cache parity per step,
  plus per-token step timings for both programs;
* **batch sweep** — the :class:`repro.serve.ServeEngine` loop at several
  slot counts: tokens/sec and p50/p99 per-token decode latency vs batch
  size, with the prefill-token budget set low enough that the scheduler
  interleaves prefill with in-flight decode (the mixed-step count is
  recorded);
* **mode comparison** — the same workload through the GSPMD and explicit
  decode programs, tokens/sec side by side.

Every section records the per-callsite resolved schedule at the actual
decode-regime payload sizes — never the literal ``"auto"`` — and the
module fails with SystemExit(1) if any resolution names an unregistered
schedule (the same gate as ``--autotune``)."""
from __future__ import annotations

import time

from benchmarks.common import ensure_devices, save_result, table

ensure_devices()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.comm.callsites import DECODE_MOE, DECODE_OUT, DECODE_QKV  # noqa: E402
from repro.comm.engine import CollectiveEngine, schedules_for  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.configs.qwen3_moe_235b_a22b import tiny  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.kvcache import (PagedCacheConfig, PageAllocator,  # noqa: E402
                                  commit_prefill)
from repro.models.model import build_model  # noqa: E402
from repro.serve import ServeEngine  # noqa: E402
from repro.train.serve import (make_decode_step_explicit,  # noqa: E402
                               make_paged_decode_step, make_prefill_step)

ARCH = "qwen3-moe-235b-a22b"
PAGE = 4


def _resolved_decode(engine, cfg, slots, ndev):
    """Per-callsite resolutions at the decode-regime payloads the explicit
    step actually exchanges (single-token tiles, small batch)."""
    B_loc = max(slots // ndev, 1)
    qkv_bytes = B_loc * 1 * cfg.num_heads * cfg.head_dim * 4
    C = MOE._capacity(cfg, 1)
    moe_bytes = B_loc * cfg.num_experts * C * cfg.d_model * 4

    def a2a(nbytes, cs):
        return engine.schedule_for("all_to_all_tiles", nbytes=nbytes,
                                   axis="x", callsite=cs)

    return ({DECODE_QKV: a2a(qkv_bytes, DECODE_QKV),
             DECODE_OUT: a2a(qkv_bytes, DECODE_OUT),
             DECODE_MOE: a2a(moe_bytes, DECODE_MOE)},
            {"qkv_bytes": qkv_bytes, "moe_bytes": moe_bytes})


def _gate_resolved(section) -> None:
    """SystemExit(1) if any decode-path resolution is unregistered or still
    the literal "auto" — the same gate as ``--autotune``."""
    resolved = (section or {}).get("resolved")
    if not resolved:
        return
    registered = schedules_for("all_to_all_tiles")
    bad = [(cs, name) for cs, name in resolved.items()
           if name == "auto" or name not in registered]
    if bad:
        print("UNREGISTERED decode-path resolutions:", bad)
        raise SystemExit(1)


def _prefill_pages(model, pcfg, params, prompts, max_new):
    """Dense prefill each prompt into a fresh page pool; returns the pool,
    the allocator, and the first sampled token per slot."""
    B, S0 = prompts.shape
    prefill = make_prefill_step(model, None)
    alloc = PageAllocator(pcfg)
    pages = T.init_paged_cache(model.cfg, pcfg, jnp.float32)
    first = np.zeros((B, 1), np.int32)
    for b in range(B):
        slot = alloc.allocate(S0 + max_new)
        c1 = model.init_cache(1, S0, jnp.float32)
        lg, c1 = prefill(params, {"tokens": prompts[b:b + 1]}, c1)
        pages["layers"] = commit_prefill(
            pages["layers"], c1["layers"],
            jnp.asarray(alloc.block_table[slot]), S0,
            page_size=pcfg.page_size)
        alloc.commit(slot, S0)
        first[slot, 0] = int(jnp.argmax(lg[0, -1]))
    return pages, alloc, first


def _equivalence_section(quick: bool, schedule):
    """Explicit-vs-GSPMD paged decode from identical pages."""
    ndev = len(jax.devices())
    if ndev < 2:
        return {"skipped": f"explicit decode needs >= 2 devices, have {ndev}"}

    requested = schedule or "auto"
    cfg = tiny(ndev)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    mesh = make_mesh((ndev,), ("x",))
    engine = CollectiveEngine.for_mesh(mesh, schedule=requested)

    B, S0 = ndev, 5
    # >= 3: the explicit step compiles twice (unsharded pages on the first
    # call, engine-sharded thereafter) before reaching steady state
    steps = 3 if quick else 4
    pcfg = PagedCacheConfig(page_size=PAGE, max_slots=B, max_seq=S0 + steps,
                            num_pages=B * (-(-(S0 + steps) // PAGE)))
    prompts = jax.random.randint(jax.random.key(1), (B, S0), 0,
                                 cfg.vocab_size).astype(jnp.int32)
    pages_g, alloc, first = _prefill_pages(model, pcfg, params, prompts, steps)
    pages_e = jax.tree.map(lambda a: a.copy(), pages_g)

    pd_g = make_paged_decode_step(model, None)
    pd_e = make_decode_step_explicit(model, mesh, engine=engine,
                                     schedule=schedule)
    tok = first.copy()
    logits_err = cache_err = 0.0
    t_g = []
    t_e = []
    for _ in range(steps):
        bt, ln = alloc.device_tables()
        t0 = time.perf_counter()
        lg, pages_g = jax.block_until_ready(
            pd_g(params, jnp.asarray(tok), pages_g, bt, ln))
        t_g.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        le, pages_e = jax.block_until_ready(
            pd_e(params, jnp.asarray(tok), pages_e, bt, ln))
        t_e.append(time.perf_counter() - t0)
        logits_err = max(logits_err, float(jnp.max(jnp.abs(lg - le))))
        cache_err = max(cache_err,
                        max(float(jnp.max(jnp.abs(x - y))) for x, y in
                            zip(jax.tree.leaves(pages_g),
                                jax.tree.leaves(pages_e))))
        for s in range(B):
            alloc.append(s)
        tok = np.asarray(jnp.argmax(lg[:, -1], -1), np.int32)[:, None]
    resolved, payloads = _resolved_decode(engine, cfg, B, ndev)
    return {
        "arch": ARCH, "devices": ndev, "slots": B, "steps": steps,
        "schedule": resolved[DECODE_QKV], "schedule_requested": requested,
        # steady-state per-token step time: the first call carries compile
        "t_gspmd_s": min(t_g), "t_explicit_s": min(t_e), "time": min(t_e),
        "max_logits_err": logits_err, "max_cache_err": cache_err,
        "resolved": resolved, **payloads,
    }


def _serve_workload(rng, cfg, n_requests, pmax):
    return [rng.integers(0, cfg.vocab_size,
                         size=(int(rng.integers(max(pmax // 2, 1),
                                                pmax + 1)),)).astype(np.int32)
            for _ in range(n_requests)]


def _run_engine(model, params, pcfg, prompts, max_new, **kw):
    eng = ServeEngine(model, params, pcfg, **kw)
    t0 = time.perf_counter()
    out, stats = eng.run(prompts, max_new_tokens=max_new, collect_stats=True)
    wall = time.perf_counter() - t0
    dec = [(s["decode_s"], s["decode_tokens"])
           for s in stats if s["decode_tokens"]]
    decode_tokens = sum(n for _, n in dec)
    # the first decode batch carries jit compile and the second a reshard
    # recompile (explicit mode): report the first separately and compute
    # throughput/percentiles over the steady-state samples
    steady = dec[2:] or dec[1:] or dec
    lat = sorted(t for t, _ in steady)
    new_tokens = sum(out[r].shape[0] - p.shape[0]
                     for r, p in enumerate(prompts))
    return {
        "requests": len(prompts), "new_tokens": new_tokens,
        "steps": len(stats), "wall_s": wall,
        "mixed_steps": sum(1 for s in stats
                           if s["prefills"] and s["decode_tokens"]),
        "decode_tokens": decode_tokens,
        "tok_per_s": sum(n for _, n in steady) / max(sum(lat), 1e-9),
        "first_decode_s": dec[0][0] if dec else 0.0,
        "p50_token_s": lat[len(lat) // 2],
        "p99_token_s": lat[min(int(len(lat) * 0.99), len(lat) - 1)],
    }


def _batch_sweep_section(quick: bool, schedule):
    """ServeEngine throughput/latency vs slot count (explicit decode)."""
    ndev = len(jax.devices())
    if ndev < 2:
        return {"skipped": f"explicit serve needs >= 2 devices, have {ndev}"}

    requested = schedule or "auto"
    cfg = tiny(ndev)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    mesh = make_mesh((ndev,), ("x",))
    engine = CollectiveEngine.for_mesh(mesh, schedule=requested)

    pmax, max_new = 8, (4 if quick else 8)
    max_seq = pmax + max_new
    slot_counts = (ndev,) if quick else (ndev, 2 * ndev)
    rng = np.random.default_rng(0)
    sweep = {}
    for slots in slot_counts:
        pcfg = PagedCacheConfig(
            page_size=PAGE, max_slots=slots, max_seq=max_seq,
            num_pages=slots * (-(-max_seq // PAGE)))
        prompts = _serve_workload(rng, cfg, 2 * slots, pmax)
        row = _run_engine(model, params, pcfg, prompts, max_new,
                          mode="explicit", mesh=mesh, engine=engine,
                          schedule=schedule,
                          prefill_token_budget=2 * pmax)
        sweep[slots] = row

    # mode comparison at the smallest batch: GSPMD vs explicit, same work
    slots = slot_counts[0]
    pcfg = PagedCacheConfig(
        page_size=PAGE, max_slots=slots, max_seq=max_seq,
        num_pages=slots * (-(-max_seq // PAGE)))
    gspmd = _run_engine(model, params, pcfg,
                        _serve_workload(np.random.default_rng(0), cfg,
                                        2 * slots, pmax),
                        max_new, mode="gspmd", prefill_token_budget=2 * pmax)

    resolved, payloads = _resolved_decode(engine, cfg, slot_counts[0], ndev)
    return {
        "arch": ARCH, "devices": ndev, "max_new": max_new,
        "schedule": resolved[DECODE_QKV], "schedule_requested": requested,
        "time": sweep[slot_counts[0]]["p50_token_s"],
        "sweep": {str(k): v for k, v in sweep.items()},
        "gspmd": gspmd, "resolved": resolved, **payloads,
    }


def main(quick: bool = False, schedule=None):
    record = {}

    eq = _equivalence_section(quick, schedule)
    record["decode_equivalence"] = eq
    if "skipped" in eq:
        print(f"-- decode equivalence: {eq['skipped']} --")
    else:
        print("-- explicit-vs-GSPMD paged decode (engine-routed) --")
        print(table(
            [[eq["arch"], eq["slots"], f"{eq['t_gspmd_s']*1e3:.1f}ms",
              f"{eq['t_explicit_s']*1e3:.1f}ms",
              f"{eq['max_logits_err']:.2e}", f"{eq['max_cache_err']:.2e}"]],
            ["arch", "slots", "gspmd/tok", "explicit/tok", "max|dlogits|",
             "max|dcache|"]))
        print("   resolved: " + " ".join(
            f"{cs}={name}" for cs, name in sorted(eq["resolved"].items())))
    _gate_resolved(eq)

    sweep = _batch_sweep_section(quick, schedule)
    record["batch_sweep"] = sweep
    if "skipped" in sweep:
        print(f"\n-- batch sweep: {sweep['skipped']} --")
    else:
        print("\n-- continuous batching: tokens/sec + per-token latency "
              "vs batch size (explicit decode) --")
        rows = [[slots, r["requests"], r["mixed_steps"],
                 f"{r['tok_per_s']:.1f}", f"{r['p50_token_s']*1e3:.2f}ms",
                 f"{r['p99_token_s']*1e3:.2f}ms"]
                for slots, r in sweep["sweep"].items()]
        g = sweep["gspmd"]
        rows.append([f"{list(sweep['sweep'])[0]} (gspmd)", g["requests"],
                     g["mixed_steps"], f"{g['tok_per_s']:.1f}",
                     f"{g['p50_token_s']*1e3:.2f}ms",
                     f"{g['p99_token_s']*1e3:.2f}ms"])
        print(table(rows, ["slots", "reqs", "mixed", "tok/s", "p50/tok",
                           "p99/tok"]))
        print("   resolved: " + " ".join(
            f"{cs}={name}" for cs, name in sorted(sweep["resolved"].items())))
    _gate_resolved(sweep)

    save_result("serve_bench", record)
    return record


if __name__ == "__main__":
    main()
