"""Beyond-paper — LM train/serve step timings (reduced configs, measured on
CPU for regression) + the production-mesh roofline summary per assigned
architecture (read from the dry-run results), plus two explicit-vs-GSPMD
comparisons on the simulated multi-device mesh:

* the qwen3-moe expert *layer* once through GSPMD ``apply_moe`` and once
  through the engine-routed ``apply_moe_explicit``;
* the *whole model* (tiny qwen3-moe) trained one step through
  ``make_whole_model_train_step_explicit`` in both attention modes (``tp``
  head-parallel, ``sp`` ring) against the GSPMD ``make_train_step`` on the
  same mesh — loss / grad-norm / updated-param parity recorded.

Both record every per-callsite resolved schedule (``moe.dispatch`` /
``moe.combine`` / ``tp.qkv`` / ``sp.kv`` / ``dp.grads`` / ...) — never the
literal ``"auto"``. The module fails with SystemExit(1) if any resolution
names an unregistered schedule (the same gate ``--autotune`` applies)."""
from __future__ import annotations

import json
import os
import time

from benchmarks.common import ensure_devices, save_result, table

ensure_devices()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import RunConfig, get_config, list_archs, reduced  # noqa: E402
from repro.configs.qwen3_moe_235b_a22b import tiny  # noqa: E402
from repro.data import DataConfig, SyntheticLMDataset  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.train.serve import make_decode_step, make_prefill_step  # noqa: E402
from repro.train.step import init_train_state, make_train_step  # noqa: E402

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")

MOE_ARCH = "qwen3-moe-235b-a22b"


def _moe_explicit_section(quick: bool, schedule):
    """Explicit-vs-GSPMD MoE through the collective engine.

    Runs the reduced qwen3-moe expert layer twice on the live mesh — the
    GSPMD ``apply_moe`` with a batch-sharded input, and the engine-routed
    ``apply_moe_explicit`` (dispatch/combine as tagged ``all_to_all_tiles``,
    pipelined ``nchunks="auto"``) — plus one explicit-DP train step so the
    ``dp.grads`` bucket reduction resolves against real payload sizes.
    Returns the result record with every per-callsite resolved schedule.
    """
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.comm.engine import CollectiveEngine
    from repro.launch.mesh import make_mesh
    from repro.core.hpcc import timeit
    from repro.models import moe as MOE
    from repro.train.step import GRADS_CALLSITE, make_dp_train_step_explicit

    ndev = len(jax.devices())
    if ndev < 2:
        return {"skipped": f"explicit MoE needs >= 2 devices, have {ndev}"}

    requested = schedule or "auto"
    # one expert (shard) per device; capacity generous enough to drop nothing
    cfg = tiny(ndev, layers=1)
    mesh = make_mesh((ndev,), ("x",))
    engine = CollectiveEngine.for_mesh(mesh, schedule=requested)

    B, S, D = ndev, (16 if quick else 32), cfg.d_model
    p = MOE.init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (B, S, D), jnp.float32)

    # GSPMD path: one jit over the batch-sharded input, XLA schedules the
    # expert resharding itself
    xs = jax.device_put(x, NamedSharding(mesh, P("x", None, None)))
    gspmd = jax.jit(lambda p, x: MOE.apply_moe(p, cfg, x))
    out_g, t_gspmd = timeit(gspmd, p, xs, reps=2)

    # explicit path: engine-routed exchanges, pipelined capacity strips
    explicit = MOE.make_apply_moe_explicit(cfg, mesh, engine=engine,
                                           nchunks="auto")
    out_e, t_explicit = timeit(explicit, p, x, reps=2)
    err = float(np.max(np.abs(np.asarray(out_e, np.float32)
                              - np.asarray(out_g, np.float32))))

    # one explicit-DP step on the same config: the dp.grads bucket payload
    data = SyntheticLMDataset(DataConfig(cfg.vocab_size, B, S))
    batch = {"tokens": jnp.asarray(data.batch(0)["tokens"])}
    model = build_model(cfg)
    state = init_train_state(model, jax.random.key(2))
    step = make_dp_train_step_explicit(
        model, RunConfig(learning_rate=1e-3, warmup_steps=1), mesh,
        schedule_kind=requested)
    grad_bytes = 4 * sum(v.size for v in jax.tree.leaves(state.params))
    # the step donates its state: compile with the fresh state, time the
    # second step on the returned one
    state2, _ = jax.block_until_ready(step(state, batch))
    t0 = time.perf_counter()
    _, metrics = jax.block_until_ready(step(state2, batch))
    t_dp = time.perf_counter() - t0

    # per-callsite provenance at the actual payload sizes, matching the
    # hpl/ptrans convention: resolved names recorded, never "auto"
    C = MOE._capacity(cfg, S)
    exchange_bytes = (B // ndev) * cfg.num_experts * C * D * 4
    bucket_bytes = engine.bucket_bytes_for("x")
    # resolve dp.grads at the payloads the bucketed reduction actually runs
    # (greedy leaf packing can leave a small trailing bucket in a different
    # cost band than min(bucket, total) would suggest)
    from repro.comm.overlap import pack_buckets
    leaves = jax.tree.leaves(state.params)
    bucket_payloads = sorted({
        sum(leaves[i].size * 4 for i in b if leaves[i].size)
        for b in pack_buckets(leaves, bucket_bytes)} - {0})
    per_bucket = [engine.schedule_for("allreduce", nbytes=nb, axis="x",
                                      callsite=GRADS_CALLSITE)
                  for nb in bucket_payloads]
    resolved = {
        "moe.dispatch": engine.schedule_for(
            "all_to_all_tiles", nbytes=exchange_bytes, axis="x",
            callsite=MOE.DISPATCH_CALLSITE),
        "moe.combine": engine.schedule_for(
            "all_to_all_tiles", nbytes=exchange_bytes, axis="x",
            callsite=MOE.COMBINE_CALLSITE),
        # headline name: the largest bucket dominates the wire time; the
        # full per-bucket map below captures band-crossing resolutions
        "dp.grads": per_bucket[-1],
    }
    nchunks = engine.pipeline_chunks("all_to_all_tiles",
                                     nbytes=exchange_bytes, axis="x",
                                     callsite=MOE.DISPATCH_CALLSITE)
    return {
        "arch": MOE_ARCH, "devices": ndev,
        "time": t_explicit, "t_explicit_s": t_explicit,
        "t_gspmd_s": t_gspmd, "t_dp_step_s": t_dp,
        "dp_loss": float(metrics["loss"]),
        "max_abs_err_vs_gspmd": err,
        "schedule": resolved["moe.dispatch"],
        "schedule_requested": requested,
        "resolved": resolved, "nchunks": nchunks,
        "dp_grads_bucket_payloads": bucket_payloads,
        "dp_grads_resolved_per_bucket": per_bucket,
        "exchange_bytes": exchange_bytes, "bucket_bytes": bucket_bytes,
        "grad_bytes": grad_bytes,
    }


def _whole_model_section(quick: bool, schedule):
    """Whole-model explicit-vs-GSPMD: tiny qwen3-moe, one train step.

    The explicit step (:func:`make_whole_model_train_step_explicit`) runs
    the full forward+backward inside one ``shard_map`` — attention
    activations exchanged under ``tp.*`` / ``sp.*`` tags, MoE dispatch/
    combine under ``moe.*``, gradient buckets under ``dp.grads`` — and is
    compared against the GSPMD :func:`make_train_step` on the same mesh
    from identical init: loss, grad norm, and every updated parameter.
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.comm.callsites import (MOE_COMBINE, MOE_DISPATCH, SP_KV,
                                      SP_OUT, SP_QKV, TP_OUT, TP_QKV)
    from repro.comm.engine import CollectiveEngine
    from repro.comm.overlap import pack_buckets
    from repro.launch.mesh import make_mesh
    from repro.models import moe as MOE
    from repro.models.parallel import ATTN_MODES
    from repro.train.step import (GRADS_CALLSITE,
                                  make_whole_model_train_step_explicit,
                                  whole_model_param_specs)

    ndev = len(jax.devices())
    if ndev < 2:
        return {"skipped":
                f"whole-model explicit needs >= 2 devices, have {ndev}"}

    requested = schedule or "auto"
    cfg = tiny(ndev, layers=1)
    mesh = make_mesh((ndev,), ("x",))
    engine = CollectiveEngine.for_mesh(mesh, schedule=requested)
    run_cfg = RunConfig(learning_rate=1e-3, warmup_steps=1)

    B, S = ndev, (16 if quick else 32)
    model = build_model(cfg)
    data = SyntheticLMDataset(DataConfig(cfg.vocab_size, B, S))
    batch = {"tokens": jnp.asarray(data.batch(0)["tokens"])}

    # GSPMD reference on the same ring mesh (pure DP, params replicated)
    state0 = init_train_state(model, jax.random.key(0))
    ref_step = make_train_step(model, run_cfg, mesh, donate=False)
    ref_state, ref_metrics = jax.block_until_ready(ref_step(state0, batch))
    ref_leaves = [np.asarray(v, np.float32)
                  for v in jax.tree.leaves(ref_state.params)]

    modes = {}
    for mode in ATTN_MODES:
        step = make_whole_model_train_step_explicit(
            model, run_cfg, mesh, attn_mode=mode, schedule_kind=requested,
            nchunks="auto")
        st = init_train_state(model, jax.random.key(0))
        new_state, metrics = jax.block_until_ready(step(st, batch))
        # parity against the GSPMD step from identical init (host copies
        # first: the timing step below donates new_state's buffers)
        param_err = max(
            float(np.max(np.abs(np.asarray(a, np.float32) - b)))
            if a.size else 0.0
            for a, b in zip(jax.tree.leaves(new_state.params), ref_leaves))
        loss_err = abs(float(metrics["loss"]) - float(ref_metrics["loss"]))
        gnorm_err = abs(float(metrics["grad_norm"])
                        - float(ref_metrics["grad_norm"]))
        t0 = time.perf_counter()
        jax.block_until_ready(step(new_state, batch))
        t_step = time.perf_counter() - t0
        modes[mode] = {"t_step_s": t_step, "loss": float(metrics["loss"]),
                       "loss_err_vs_gspmd": loss_err,
                       "grad_norm_err_vs_gspmd": gnorm_err,
                       "max_abs_param_err_vs_gspmd": param_err}

    # per-callsite provenance at the actual per-rank payloads — resolved
    # names recorded, never "auto"
    H, KV, hd, D = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                    cfg.d_model)
    C = MOE._capacity(cfg, S)
    attn_bytes = (B // ndev) * S * H * hd * 4   # q/k/v a2a payload
    kv_ring_bytes = B * (S // ndev) * KV * 2 * hd * 4  # concat [k|v] block
    moe_bytes = (B // ndev) * cfg.num_experts * C * D * 4

    def a2a(nbytes, cs):
        return engine.schedule_for("all_to_all_tiles", nbytes=nbytes,
                                   axis="x", callsite=cs)

    resolved = {
        TP_QKV: a2a(attn_bytes, TP_QKV),
        TP_OUT: a2a(attn_bytes, TP_OUT),
        SP_QKV: a2a(attn_bytes, SP_QKV),
        SP_OUT: a2a(attn_bytes, SP_OUT),
        SP_KV: engine.schedule_for("ring_exchange", nbytes=kv_ring_bytes,
                                   axis="x", callsite=SP_KV),
        MOE_DISPATCH: a2a(moe_bytes, MOE_DISPATCH),
        MOE_COMBINE: a2a(moe_bytes, MOE_COMBINE),
    }
    # dp.grads reduces the REPLICATED leaves only (expert shards are
    # complete per-rank and never ride the wire)
    specs = whole_model_param_specs(state0.params)
    s_leaves = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    rep_leaves = [v for v, s in zip(jax.tree.leaves(state0.params), s_leaves)
                  if s == P()]
    bucket_bytes = engine.bucket_bytes_for("x")
    bucket_payloads = sorted({
        sum(rep_leaves[i].size * 4 for i in b if rep_leaves[i].size)
        for b in pack_buckets(rep_leaves, bucket_bytes)} - {0})
    per_bucket = [engine.schedule_for("allreduce", nbytes=nb, axis="x",
                                      callsite=GRADS_CALLSITE)
                  for nb in bucket_payloads]
    resolved[GRADS_CALLSITE] = per_bucket[-1]
    nchunks = engine.pipeline_chunks("all_to_all_tiles", nbytes=moe_bytes,
                                     axis="x", callsite=MOE_DISPATCH)
    return {
        "arch": MOE_ARCH, "devices": ndev,
        "schedule_requested": requested,
        "modes": modes, "resolved": resolved, "nchunks": nchunks,
        "dp_grads_bucket_payloads": bucket_payloads,
        "dp_grads_resolved_per_bucket": per_bucket,
        "attn_exchange_bytes": attn_bytes,
        "kv_ring_bytes": kv_ring_bytes,
        "moe_exchange_bytes": moe_bytes,
    }


# callsite tag -> engine op, for the resolution gate below
_GATE_OPS = {
    "moe.dispatch": "all_to_all_tiles", "moe.combine": "all_to_all_tiles",
    "tp.qkv": "all_to_all_tiles", "tp.out": "all_to_all_tiles",
    "sp.qkv": "all_to_all_tiles", "sp.out": "all_to_all_tiles",
    "sp.kv": "ring_exchange",
    "dp.grads": "allreduce",
}


def _gate_resolved(section) -> None:
    """SystemExit(1) if any explicit-path resolution is unregistered or
    still the literal "auto" — the same gate as ``--autotune``."""
    from repro.comm.engine import schedules_for

    resolved = (section or {}).get("resolved")
    if not resolved:
        return
    checks = list(resolved.items()) + [
        ("dp.grads", n) for n in section.get("dp_grads_resolved_per_bucket", ())]
    bad = [(cs, name) for cs, name in checks
           if name == "auto" or name not in schedules_for(_GATE_OPS[cs])]
    if bad:
        print("UNREGISTERED explicit-path resolutions:", bad)
        raise SystemExit(1)


def main(quick: bool = False, schedule=None):
    # GSPMD-scheduled train/decode steps (XLA picks the collectives);
    # ``schedule`` applies to the explicit-MoE section below
    archs = (["llama3-8b", "mamba2-130m", "qwen3-moe-235b-a22b"]
             if quick else list_archs())
    if schedule not in (None, "auto"):
        # a fixed schedule only affects the explicit-MoE section: skip the
        # schedule-invariant GSPMD arch timings (--sweep-schedules invokes
        # this module once per registered all_to_all_tiles schedule)
        archs = []
    B, S = 4, 64

    if archs:
        print("== LM step bench (reduced configs, CPU wall-time) ==")
    rows = []
    record = {}
    for arch in archs:
        cfg = reduced(get_config(arch))
        model = build_model(cfg)
        data = SyntheticLMDataset(DataConfig(cfg.vocab_size, B, S))
        batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        if cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros((B, cfg.num_patches,
                                               cfg.vision_dim), jnp.float32)
        if cfg.is_encoder_decoder:
            batch["frames"] = jnp.zeros((B, cfg.audio_ctx, cfg.d_model),
                                        jnp.float32)

        state = init_train_state(model, jax.random.key(0))
        step = make_train_step(
            model, RunConfig(learning_rate=1e-3, warmup_steps=1),
            jax.sharding.Mesh(jax.devices()[:1], ("x",)), donate=False)
        state, _ = step(state, batch)  # compile
        t0 = time.perf_counter()
        state, metrics = jax.block_until_ready(step(state, batch))
        t_train = time.perf_counter() - t0

        cache = model.init_cache(B, S + 8, jnp.float32)
        prefill = make_prefill_step(model)
        decode = make_decode_step(model)
        logits, cache = prefill(state.params, batch, cache)
        dec_extras = {k: v for k, v in batch.items()
                      if k not in ("tokens", "frames")}
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        _, cache = decode(state.params, tok, cache, dec_extras)  # compile
        t0 = time.perf_counter()
        _, cache = jax.block_until_ready(
            decode(state.params, tok, cache, dec_extras))
        t_decode = time.perf_counter() - t0

        rows.append([arch, f"{t_train*1e3:.1f}ms", f"{t_decode*1e3:.2f}ms",
                     f"{float(metrics['loss']):.3f}"])
        record[arch] = {"train_step_s": t_train, "decode_step_s": t_decode}
    if rows:
        print(table(rows, ["arch", "train_step", "decode_step", "loss"]))

    # explicit-vs-GSPMD MoE through the engine (simulated multi-device mesh)
    moe = _moe_explicit_section(quick, schedule)
    record["moe_explicit"] = moe
    if "skipped" in moe:
        print(f"\n-- explicit MoE: {moe['skipped']} --")
    else:
        print("\n-- explicit-vs-GSPMD MoE (engine-routed exchanges) --")
        print(table(
            [[moe["arch"], f"{moe['t_gspmd_s']*1e3:.1f}ms",
              f"{moe['t_explicit_s']*1e3:.1f}ms",
              f"{moe['t_dp_step_s']*1e3:.1f}ms",
              moe["resolved"]["moe.dispatch"],
              moe["resolved"]["moe.combine"],
              moe["resolved"]["dp.grads"], str(moe["nchunks"]),
              f"{moe['max_abs_err_vs_gspmd']:.2e}"]],
            ["arch", "gspmd", "explicit", "dp_step", "dispatch", "combine",
             "dp.grads", "S", "max|err|"]))
    _gate_resolved(moe)

    # whole-model explicit-vs-GSPMD training step (both attention modes)
    whole = _whole_model_section(quick, schedule)
    record["whole_model"] = whole
    if "skipped" in whole:
        print(f"\n-- whole-model explicit: {whole['skipped']} --")
    else:
        print("\n-- whole-model explicit-vs-GSPMD train step --")
        print(table(
            [[mode, f"{m['t_step_s']*1e3:.1f}ms",
              f"{m['loss']:.4f}", f"{m['loss_err_vs_gspmd']:.2e}",
              f"{m['grad_norm_err_vs_gspmd']:.2e}",
              f"{m['max_abs_param_err_vs_gspmd']:.2e}"]
             for mode, m in whole["modes"].items()],
            ["mode", "step", "loss", "|dloss|", "|dgnorm|", "max|dparam|"]))
        print("   resolved: " + " ".join(
            f"{cs}={name}" for cs, name in sorted(whole["resolved"].items())))
    _gate_resolved(whole)

    # production roofline per arch (train_4k, single pod) from the dry-run
    if os.path.isdir(DRYRUN_DIR):
        rows = []
        for arch in archs:
            tag = f"{arch}__train_4k__single.json"
            path = os.path.join(DRYRUN_DIR, tag)
            if not os.path.exists(path):
                continue
            with open(path) as f:
                rec = json.load(f)
            if rec.get("status") != "ok":
                continue
            bound = max(rec["compute_s"], rec["memory_s"], rec["collective_s"])
            rows.append([arch, f"{rec['compute_s']:.3g}",
                         f"{rec['memory_s']:.3g}",
                         f"{rec['collective_s']:.3g}", rec["dominant"],
                         f"{rec['useful_ratio']:.1%}"])
        if rows:
            print("\n-- production mesh (train_4k, 256 chips) roofline --")
            print(table(rows, ["arch", "compute_s", "memory_s", "coll_s",
                               "dominant", "useful"]))
    save_result("lm_step_bench", record)
    return record


if __name__ == "__main__":
    main()
