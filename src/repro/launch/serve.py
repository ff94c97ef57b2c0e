"""Serving launcher: continuous-batching generation over the paged cache.

``python -m repro.launch.serve --arch llama3-8b --requests 8``

Serves the reduced config on local devices through
:class:`repro.serve.ServeEngine`: requests with mixed prompt lengths are
queued, admitted under a per-step prefill-token budget, prefilled into the
paged KV cache, and decoded as one continuously-batched stream with slots
recycled on EOS / max-new. ``--mode explicit`` routes the per-token
collectives through the engine (``decode.*`` callsites) on an explicit
``shard_map`` decode; ``--legacy`` keeps the old whole-batch
``generate`` loop (and is the fallback for model families the paged cache
does not cover).
"""
from __future__ import annotations

import argparse
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs import get_config, list_archs, reduced
from repro.models.model import build_model
from repro.train.serve import generate


def _legacy(model, params, cfg, args):
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                       (args.requests, args.prompt_len)),
                          jnp.int32)
    extras = {}
    if cfg.family == "vlm":
        extras["patch_embeds"] = jnp.asarray(
            rng.standard_normal((args.requests, cfg.num_patches,
                                 cfg.vision_dim)), jnp.float32)
    if cfg.is_encoder_decoder:
        extras["frames"] = jnp.asarray(
            rng.standard_normal((args.requests, cfg.audio_ctx, cfg.d_model)),
            jnp.float32)

    t0 = time.perf_counter()
    out = generate(model, params, prompts, max_new_tokens=args.max_new,
                   temperature=args.temperature, extras=extras)
    out = jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    new_tokens = args.requests * args.max_new
    print(f"arch={args.arch} batch={args.requests} prompt={args.prompt_len} "
          f"new={args.max_new} [legacy generate]")
    print(f"generated {new_tokens} tokens in {dt:.2f}s "
          f"({new_tokens / dt:.1f} tok/s incl. compile)")
    print("first sequence:", np.asarray(out[0])[:args.prompt_len + 8])


def _paged(model, params, cfg, args):
    from repro.launch.mesh import make_mesh
    from repro.launch.train import parse_fault_args
    from repro.models.kvcache import PagedCacheConfig
    from repro.serve import ServeEngine

    fault = parse_fault_args(args.fault_schedule, args.fail_rank)

    max_seq = args.prompt_len + args.max_new
    slots = max(min(args.requests, len(jax.devices()) * 2), 1)
    mesh = None
    if args.mode == "explicit":
        # The head/expert exchange needs the axis size to divide every
        # exchanged dimension, so shrink the mesh to the largest divisor
        # the reduced config supports.
        n = math.gcd(len(jax.devices()), cfg.num_heads)
        n = math.gcd(n, cfg.num_kv_heads)
        if getattr(cfg, "num_experts", 0):
            n = math.gcd(n, cfg.num_experts)
        mesh = make_mesh((n,), ("x",))
        slots = max(slots // n, 1) * n
    pcfg = PagedCacheConfig(
        page_size=args.page_size,
        num_pages=slots * (-(-max_seq // args.page_size)) * 2,
        max_slots=slots, max_seq=max_seq)
    eng = ServeEngine(model, params, pcfg, mode=args.mode, mesh=mesh,
                      schedule=args.schedule,
                      prefill_token_budget=args.prefill_budget,
                      eos_id=args.eos_id, temperature=args.temperature,
                      preempt=args.preempt,
                      admission_retries=args.admission_retries,
                      fault_schedule=fault)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=(int(rng.integers(args.prompt_len // 2,
                                                   args.prompt_len + 1)),)
                            ).astype(np.int32)
               for _ in range(args.requests)]
    for p in prompts:
        eng.submit(p, args.max_new, deadline_s=args.deadline_s)
    t0 = time.perf_counter()
    out, stats = eng.run(collect_stats=True)
    dt = time.perf_counter() - t0
    new_tokens = sum(out[r].shape[0] - p.shape[0]
                     for r, p in enumerate(prompts))
    decode_steps = [s["decode_s"] for s in stats if s["decode_tokens"]]
    print(f"arch={args.arch} mode={args.mode} requests={args.requests} "
          f"slots={pcfg.max_slots} pages={pcfg.num_pages}x{pcfg.page_size}")
    print(f"generated {new_tokens} tokens in {dt:.2f}s "
          f"({new_tokens / dt:.1f} tok/s incl. compile) over "
          f"{len(stats)} steps ({len(decode_steps)} decode batches)")
    if decode_steps:
        lat = np.sort(decode_steps)
        print(f"decode-step latency p50={lat[len(lat) // 2] * 1e3:.2f}ms "
              f"p99={lat[min(int(len(lat) * 0.99), len(lat) - 1)] * 1e3:.2f}ms")
    degraded = {k: sum(s.get(k, 0) for s in stats)
                for k in ("preempted", "timeouts", "rejected", "drained")}
    if any(degraded.values()):
        print("degradation: " + " ".join(f"{k}={v}"
                                         for k, v in degraded.items()))
    print("first sequence:", out[0][:prompts[0].shape[0] + 8])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--mode", choices=("gspmd", "explicit"), default="gspmd")
    ap.add_argument("--schedule", default=None,
                    help="override the decode collectives' schedule "
                         "(explicit mode)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-budget", type=int, default=512)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--preempt", action="store_true",
                    help="evict the youngest active request (tokens kept, "
                         "re-prefilled) when the head cannot get pages")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock deadline; expired requests "
                         "finish with reason 'timeout'")
    ap.add_argument("--admission-retries", type=int, default=256,
                    help="failed admission attempts before the queue head "
                         "is rejected")
    ap.add_argument("--fault-schedule", default=None, metavar="SPEC",
                    help="scripted fault timeline applied per serve step "
                         "(repro.comm.faults.FaultSchedule.parse), e.g. "
                         "'delay@5-20:seconds=0.05,callsite=serve.step'")
    ap.add_argument("--fail-rank", default=None, metavar="RANK@STEP",
                    help="shorthand: lose device RANK at serve step STEP — "
                         "requests with KV pages on it drain and re-prefill "
                         "on surviving pages")
    ap.add_argument("--legacy", action="store_true",
                    help="whole-batch generate loop instead of the "
                         "continuous-batching engine")
    args = ap.parse_args()
    use_compile_cache()

    cfg = reduced(get_config(args.arch))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    paged_ok = (not cfg.is_encoder_decoder
                and all(k == "attn" for k in cfg.layer_kinds()))
    if args.legacy or not paged_ok:
        _legacy(model, params, cfg, args)
    else:
        _paged(model, params, cfg, args)


if __name__ == "__main__":
    main()
