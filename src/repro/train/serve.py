"""Serving steps: prefill + decode with a sharded KV/SSM cache.

``serve_step`` for the dry-run lowers one decode token against a cache of
``seq_len`` (the assigned decode_*/long_* cells). ``generate`` is a small
batched greedy/temperature sampler driving the two jitted steps — the
"batched requests" server of deliverable (b).

The paged steps back the continuous-batching server (:mod:`repro.serve`):
``make_paged_decode_step`` is the GSPMD reference, and
``make_decode_step_explicit`` runs the same token forward inside ONE
``shard_map`` with every wire hop an explicit engine call — head-parallel
attention under ``decode.qkv``/``decode.out`` and MoE dispatch/combine
under ``decode.moe`` (:mod:`repro.comm.callsites`). Per-token payloads are
tiny, so these callsites resolve in the latency band of the cost model,
separately from the training-sized ``tp.*``/``moe.*`` entries.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro import sharding as sh
from repro.comm.callsites import DECODE_MOE
from repro.comm.engine import CollectiveEngine
from repro.models.model import Model


def make_prefill_step(model: Model, mesh: Optional[Mesh] = None) -> Callable:
    """(params, batch, cache) -> (logits, cache). Writes positions [0, S)."""
    shard = sh.make_shard_fn(mesh, sh.rules_for(mesh)) if mesh is not None \
        else (lambda x, _: x)

    def prefill(params, batch, cache):
        logits, cache, _ = model.apply(params, batch, cache=cache, shard=shard)
        return logits, cache

    return jax.jit(prefill, donate_argnums=(2,))


def make_decode_step(model: Model, mesh: Optional[Mesh] = None,
                     *, seq_shard: bool = False) -> Callable:
    """(params, tokens(B,1), cache, extras) -> (logits(B,1,V), cache)."""
    shard = sh.make_shard_fn(mesh, sh.rules_for(mesh, seq_shard=seq_shard)) \
        if mesh is not None else (lambda x, _: x)

    def decode(params, tokens, cache, extras):
        batch = {"tokens": tokens, **extras}
        logits, cache, _ = model.apply(params, batch, cache=cache, shard=shard)
        return logits, cache

    return jax.jit(decode, donate_argnums=(2,))


# ---------------------------------------------------------------------------
# paged decode: GSPMD reference + explicit engine-routed tensor-parallel
# ---------------------------------------------------------------------------


def make_paged_decode_step(model: Model, mesh: Optional[Mesh] = None
                           ) -> Callable:
    """GSPMD paged decode: ``(params, tokens(B,1), pages, block_table,
    lengths) -> (logits(B,1,V), pages)``.

    ``pages`` is :func:`repro.models.transformer.init_paged_cache` output;
    ``block_table`` (B, pmax) / ``lengths`` (B,) come from the host
    :class:`~repro.models.kvcache.PageAllocator`. Row b attends to its
    pages' positions ``<= lengths[b]`` (the new token is written at
    ``lengths[b]``); rows with a sentinel block-table row are inactive —
    their logits are garbage and their cache writes drop.
    """
    shard = sh.make_shard_fn(mesh, sh.rules_for(mesh)) if mesh is not None \
        else (lambda x, _: x)

    def decode(params, tokens, pages, block_table, lengths):
        cache = {"pos": lengths, "layers": pages["layers"]}
        page_table = {"block_table": block_table, "lengths": lengths}
        logits, new_cache, _ = model.apply(
            params, {"tokens": tokens}, cache=cache, shard=shard,
            page_table=page_table)
        return logits, {"layers": new_cache["layers"]}

    return jax.jit(decode, donate_argnums=(2,))


def make_decode_step_explicit(model: Model, mesh: Mesh, *, axis: str = "x",
                              engine: Optional[CollectiveEngine] = None,
                              schedule: Optional[str] = None,
                              nchunks=1) -> Callable:
    """Engine-routed paged decode: one token's forward inside ONE
    ``shard_map`` over ``axis``, signature-identical to
    :func:`make_paged_decode_step`.

    The residual stream stays batch-sharded; per layer the paged decode
    hook (:func:`repro.models.parallel.make_paged_decode_attention`)
    exchanges q and the token's k/v head-parallel (``@decode.qkv``), runs
    :func:`~repro.models.layers.decode_attention` against the rank-local
    page pool (KV heads sharded over ``axis``), and restores the layout
    (``@decode.out``); MoE layers dispatch/combine under ``@decode.moe``
    with experts sharded in the param tree. Requires batch (slot count),
    heads, kv heads — and experts, when present — divisible by the axis
    size. Matches the GSPMD step's logits and cache for every registered
    a2a schedule (tests/dist/test_serve.py).
    """
    from repro.models import moe as MOE
    from repro.models.parallel import make_paged_decode_attention
    from repro.train.step import whole_model_param_specs

    cfg = model.cfg
    engine = engine or CollectiveEngine.for_mesh(mesh, schedule="auto")
    attn_impl = make_paged_decode_attention(cfg, mesh, axis=axis,
                                            engine=engine, schedule=schedule)
    moe_impl = None
    if cfg.has_moe:
        moe_impl = MOE.make_moe_impl(cfg, mesh, axis=axis, engine=engine,
                                     schedule=schedule, nchunks=nchunks,
                                     dispatch_callsite=DECODE_MOE,
                                     combine_callsite=DECODE_MOE)

    def body(params, tokens, pages_layers, block_table, lengths, pos_loc):
        cache = {"pos": pos_loc, "layers": pages_layers}
        page_table = {"block_table": block_table, "lengths": lengths}
        logits, new_cache, _ = model.apply(
            params, {"tokens": tokens}, cache=cache, page_table=page_table,
            attn_impl=attn_impl, moe_impl=moe_impl)
        return logits, new_cache["layers"]

    def wrapped(params, tokens, pages, block_table, lengths):
        pspec = whole_model_param_specs(params, axis)
        # page pools shard the KV-head dim: (n_super, P, ps, KV, hd)
        pages_spec = jax.tree.map(
            lambda _: P(None, None, None, axis, None), pages["layers"])
        fn = shard_map(
            body, mesh=mesh,
            in_specs=(pspec, P(axis, None), pages_spec, P(), P(), P(axis)),
            out_specs=(P(axis, None, None), pages_spec),
            check_vma=False)
        logits, layers = fn(params, tokens, pages["layers"], block_table,
                            lengths, lengths)
        return logits, {"layers": layers}

    return jax.jit(wrapped, donate_argnums=(2,))


def generate(model: Model, params, prompts: jnp.ndarray, *,
             max_new_tokens: int = 32, max_seq: Optional[int] = None,
             temperature: float = 0.0, key=None, mesh: Optional[Mesh] = None,
             extras: Optional[Dict] = None,
             eos_id: Optional[int] = None) -> jnp.ndarray:
    """Batched generation. prompts: (B, S0) int32 -> (B, S0 + new)."""
    B, S0 = prompts.shape
    max_seq = max_seq or (S0 + max_new_tokens)
    extras = extras or {}
    dtype = jnp.dtype(model.cfg.dtype)
    cache = model.init_cache(B, max_seq, dtype if dtype != jnp.int32 else jnp.float32)

    prefill = make_prefill_step(model, mesh)
    decode = make_decode_step(model, mesh)

    logits, cache = prefill(params, {"tokens": prompts, **extras}, cache)
    last = logits[:, -1]

    decode_extras = {k: v for k, v in extras.items() if k != "frames"}

    def sample(logits_1, k):
        if temperature <= 0.0:
            return jnp.argmax(logits_1, axis=-1).astype(jnp.int32)
        return jax.random.categorical(k, logits_1 / temperature).astype(jnp.int32)

    key = key if key is not None else jax.random.key(0)
    out = [prompts]
    tok = sample(last, key)[:, None]
    done = jnp.zeros((B,), bool)
    for i in range(max_new_tokens):
        out.append(tok)
        if eos_id is not None:
            done = done | (tok[:, 0] == eos_id)
            if bool(done.all()):
                break  # every request hit EOS — stop decoding early
        if i == max_new_tokens - 1:
            break
        key, sub = jax.random.split(key)
        logits, cache = decode(params, tok, cache, decode_extras)
        tok = sample(logits[:, -1], sub)[:, None]
        if eos_id is not None:
            # finished rows are masked to EOS: their sampled continuations
            # never leak into the output
            tok = jnp.where(done[:, None], eos_id, tok)
    res = jnp.concatenate(out, axis=1)
    full = S0 + max_new_tokens
    if res.shape[1] < full:  # early EOS stop: pad to the fixed output shape
        pad = jnp.full((B, full - res.shape[1]), eos_id, res.dtype)
        res = jnp.concatenate([res, pad], axis=1)
    return res
