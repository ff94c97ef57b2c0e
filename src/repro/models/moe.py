"""Mixture-of-Experts layer with capacity-based scatter dispatch.

Dispatch is *scatter-based* (GShard-style capacity, MegaBlocks-style index
routing): tokens are routed into an (experts, capacity, d_model) buffer with
positions computed by a cumulative count — NO dense one-hot dispatch einsum.
This keeps compiled HLO FLOPs proportional to *active* compute (top-k), which
matters for the MODEL_FLOPS/HLO_FLOPs roofline ratio (EXPERIMENTS.md).

Two execution paths share the routing/scatter internals:

* :func:`apply_moe` — the GSPMD path: one un-mapped program; sharding intent
  under pjit (see repro/sharding.py):
    tokens  (B, S, D)   : B -> ('pod','data')
    experts (E, D, F)   : E -> 'model'  (expert parallelism)
    dispatch buffer (B, E, C, D): B -> data, E -> model  (GSPMD inserts the
    expert all-to-all-equivalent resharding).
* :func:`apply_moe_explicit` / :func:`make_apply_moe_explicit` — the
  engine-routed path: the whole layer runs inside ``shard_map`` over one
  mesh axis with experts sharded across ranks, and the dispatch/combine
  exchanges are *explicit* ``CollectiveEngine.all_to_all_tiles`` calls under
  the ``moe.dispatch`` / ``moe.combine`` callsite tags (``native``,
  paper-style ``chain``, ``staged``, or ``"auto"`` through the cost model),
  optionally software-pipelined into capacity-axis strips via
  ``engine.pipelined`` so the combine weighting of strip i overlaps strip
  i+1's wire hops.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.comm.callsites import MOE_COMBINE, MOE_DISPATCH
from repro.comm.engine import CollectiveEngine
from repro.configs.base import ModelConfig

# tuning-table callsite tags for the two expert exchanges (from the central
# repro.comm.callsites registry): they are issued back-to-back around the
# expert FFN, so measured winners may differ from an isolated all-to-all's
# (the paired pattern autotune_mesh measures)
DISPATCH_CALLSITE = MOE_DISPATCH
COMBINE_CALLSITE = MOE_COMBINE


# ---------------------------------------------------------------------------
# explicit expert-parallel dispatch (shard_map path)
# ---------------------------------------------------------------------------


def _monolithic(nchunks) -> bool:
    return isinstance(nchunks, int) and nchunks <= 1


def exchange_dispatch(buf: jnp.ndarray, axis: str, engine: CollectiveEngine,
                      *, schedule: Optional[str] = None, nchunks=1,
                      consume=None, callsite: str = DISPATCH_CALLSITE
                      ) -> jnp.ndarray:
    """Route a locally-built dispatch buffer to its expert owners.

    Inside ``shard_map`` over ``axis`` each rank holds tokens for *all*
    experts, ``buf`` = (B_loc, E, C, D). The exchange splits the expert dim
    across ranks and concatenates the batch shards, returning
    (B, E_loc, C, D): rank e now holds every rank's tokens for its experts —
    the MoE all-to-all, under whichever schedule the engine selects for the
    ``moe.dispatch`` callsite. ``nchunks`` > 1 (or ``"auto"``) pipelines the
    exchange into capacity-axis strips through ``engine.pipelined``;
    ``consume(strip, start)`` runs per landed strip.
    """
    if consume is None and _monolithic(nchunks):
        return engine.all_to_all_tiles(buf, axis, split_axis=1,
                                       concat_axis=0, schedule=schedule,
                                       callsite=callsite)
    return engine.pipelined("all_to_all_tiles", buf, axis, nchunks=nchunks,
                            split_axis=2, tile_split_axis=1,
                            tile_concat_axis=0, consume=consume,
                            schedule=schedule, callsite=callsite)


def exchange_combine(buf: jnp.ndarray, axis: str, engine: CollectiveEngine,
                     *, schedule: Optional[str] = None, nchunks=1,
                     consume=None, callsite: str = COMBINE_CALLSITE
                     ) -> jnp.ndarray:
    """Inverse of :func:`exchange_dispatch`: return expert outputs
    (B, E_loc, C, D) to the token-owning ranks as (B_loc, E, C, D), tagged
    ``moe.combine``. Same pipelining knobs as dispatch — the combine
    weighting is the natural ``consume`` hook."""
    if consume is None and _monolithic(nchunks):
        return engine.all_to_all_tiles(buf, axis, split_axis=0,
                                       concat_axis=1, schedule=schedule,
                                       callsite=callsite)
    return engine.pipelined("all_to_all_tiles", buf, axis, nchunks=nchunks,
                            split_axis=2, tile_split_axis=0,
                            tile_concat_axis=1, consume=consume,
                            schedule=schedule, callsite=callsite)


def init_moe(key, cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    std = 0.02
    p = {
        "router": jax.random.normal(k1, (d, e), jnp.float32) * std,
        "w_gate": jax.random.normal(k2, (e, d, f), jnp.float32) * std,
        "w_in": jax.random.normal(k3, (e, d, f), jnp.float32) * std,
        "w_out": jax.random.normal(k4, (e, f, d), jnp.float32)
        * (std / math.sqrt(2 * cfg.num_layers)),
    }
    if cfg.shared_expert:
        ks = jax.random.split(k5, 3)
        p["shared"] = {
            "w_gate": jax.random.normal(ks[0], (d, f), jnp.float32) * std,
            "w_in": jax.random.normal(ks[1], (d, f), jnp.float32) * std,
            "w_out": jax.random.normal(ks[2], (f, d), jnp.float32)
            * (std / math.sqrt(2 * cfg.num_layers)),
        }
    return p


def _capacity(cfg: ModelConfig, seq: int) -> int:
    c = int(math.ceil(seq * cfg.num_experts_per_tok * cfg.capacity_factor
                      / cfg.num_experts))
    return max(c, 1)


def route(p: dict, cfg: ModelConfig, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Router: returns (probs (B,S,k), ids (B,S,k)); probs renormalized over top-k."""
    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(x.dtype))
    logits = logits.astype(jnp.float32)
    top_logits, ids = jax.lax.top_k(logits, cfg.num_experts_per_tok)
    probs = jax.nn.softmax(top_logits, axis=-1)
    return probs, ids


# ---------------------------------------------------------------------------
# shared routing/scatter internals (GSPMD + explicit paths)
# ---------------------------------------------------------------------------


def _dispatch_indices(ids: jnp.ndarray, E: int, C: int):
    """Capacity bookkeeping: per-row exclusive cumulative counts give each
    (token, expert) slot its position within the expert's capacity buffer.

    Returns ``(e_idx, c_idx, keep, onehot)`` with e_idx/c_idx (B, S*K) flat
    scatter indices (dropped slots clamped to the scratch position C) and
    onehot (B, S*K, E) int32 for the load-balance metrics.
    """
    B, S, K = ids.shape
    flat_ids = ids.reshape(B, S * K)  # (B, T)
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)  # adds only
    pos_in_expert = jnp.cumsum(onehot, axis=1) - onehot  # exclusive cumsum
    pos = jnp.take_along_axis(
        pos_in_expert, flat_ids[..., None], axis=-1)[..., 0]  # (B, T)
    keep = pos < C  # capacity drop mask
    c_idx = jnp.where(keep, pos, C)
    return flat_ids, c_idx, keep, onehot


def _scatter_dispatch(tok: jnp.ndarray, e_idx, c_idx, E: int, C: int):
    """Scatter (B, S*K, D) token copies into the (B, E, C, D) dispatch
    buffer. vmapped over the batch row: a 3-dim advanced-index scatter hides
    batch-locality from GSPMD (it all-gathers the dp dim, measured §Perf
    iteration A1c); per-row scatters keep batch a clean mapped dim."""
    D = tok.shape[-1]

    def _dispatch_row(tok_row, e_row, c_row):
        # clamp dropped slots to a scratch position (C) then slice off
        return jnp.zeros((E, C + 1, D), tok.dtype).at[e_row, c_row].set(
            tok_row, mode="drop")

    return jax.vmap(_dispatch_row)(tok, e_idx, c_idx)[:, :, :C]


def _expert_ffn(p: dict, buf: jnp.ndarray, dtype) -> jnp.ndarray:
    """SwiGLU expert FFN on an expert-layout buffer (B, E[_loc], C, D)."""
    g = jnp.einsum("becd,edf->becf", buf, p["w_gate"].astype(dtype))
    h = jnp.einsum("becd,edf->becf", buf, p["w_in"].astype(dtype))
    return jnp.einsum("becf,efd->becd", jax.nn.silu(g) * h,
                      p["w_out"].astype(dtype))


def _combine_weights(probs, keep, e_idx, c_idx, E: int, C: int):
    """Top-k router probs scattered into expert layout: (B, E, C) f32."""
    B = e_idx.shape[0]
    w = probs.reshape(B, -1) * keep  # (B, T) f32

    def _weights_row(w_row, e_row, c_row):
        return jnp.zeros((E, C + 1), jnp.float32).at[e_row, c_row].set(
            w_row, mode="drop")

    return jax.vmap(_weights_row)(w, e_idx, c_idx)[:, :, :C]


def _combine_scatter(y_w, e_idx, c_idx, S: int, K: int, E: int, C: int):
    """SCATTER-ADD weighted expert outputs (B, E, C, D) f32 back to tokens.

    A fancy-index gather from the E-sharded buffer lowers to an all-reduce
    of the (B, S*K, D) output — K x more wire than needed. Scatter-add sums
    the K expert contributions shard-locally before the cross-device
    reduction, so the payload is (B, S, D/tp) once (§Perf iteration A1)."""
    D = y_w.shape[-1]
    s_idx = jnp.arange(S * K) // K  # slot -> destination token

    def _tokens_row(e_row, c_row):
        return jnp.full((E, C + 1), S, jnp.int32).at[e_row, c_row].set(
            s_idx, mode="drop")

    def _combine_row(yw_row, tok_row):
        return jnp.zeros((S, D), jnp.float32).at[tok_row].add(
            yw_row, mode="drop")

    tok_buf = jax.vmap(_tokens_row)(e_idx, c_idx)
    return jax.vmap(_combine_row)(y_w.reshape(-1, E * C, D),
                                  tok_buf[:, :, :C].reshape(-1, E * C))


def _shared_expert(sp: dict, x: jnp.ndarray, dtype) -> jnp.ndarray:
    sg = jnp.einsum("bsd,df->bsf", x, sp["w_gate"].astype(dtype))
    sh = jnp.einsum("bsd,df->bsf", x, sp["w_in"].astype(dtype))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(sg) * sh,
                      sp["w_out"].astype(dtype))


# ---------------------------------------------------------------------------
# GSPMD path
# ---------------------------------------------------------------------------


def apply_moe(p: dict, cfg: ModelConfig, x: jnp.ndarray,
              aux: Optional[dict] = None, shard=None) -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D). Per-batch-row dispatch groups.

    ``shard`` (optional activation-constraint callback) pins the dispatch
    buffer to P(dp, tp, None, None) — expert-parallel over the model axis —
    and the gathered-back tokens to P(dp, None, None). Without the
    constraints GSPMD lowers the scatter/gather through full-tensor fp32
    all-reduces (measured 16 GB wire per MoE layer on qwen3-moe prefill,
    §Perf iteration A1).
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    C = _capacity(cfg, S)
    dtype = x.dtype
    shard = shard or (lambda v, _name: v)

    probs, ids = route(p, cfg, x)  # (B,S,K)
    e_idx, c_idx, keep, onehot = _dispatch_indices(ids, E, C)

    tok = jnp.repeat(x, K, axis=1).reshape(B, S * K, D)  # each token K times
    tok = shard(tok, "moe_tokens")  # keep D sharded entering the all-to-all
    buf = shard(_scatter_dispatch(tok.astype(dtype), e_idx, c_idx, E, C),
                "moe_buf")  # (B, E, C, D), E over 'model'

    # --- expert FFN (SwiGLU), experts sharded over 'model'
    y = shard(_expert_ffn(p, buf, dtype), "moe_buf")

    # --- combine: weight in expert layout, then scatter-add back to tokens
    w_buf = _combine_weights(probs, keep, e_idx, c_idx, E, C)
    y_w = y.astype(jnp.float32) * w_buf[..., None]  # (B, E, C, D) f32
    out = _combine_scatter(y_w, e_idx, c_idx, S, K, E, C)
    out = shard(out, "moe_tokens").astype(dtype)

    if cfg.shared_expert:
        out = out + _shared_expert(p["shared"], x, dtype)
    if aux is not None:
        # load-balance metrics (Switch aux loss terms), fp32
        onehot_f = onehot.astype(jnp.float32)
        frac_tokens = onehot_f.mean(axis=(0, 1))  # (E,)
        aux["moe_frac_tokens"] = frac_tokens
        aux["moe_dropped"] = 1.0 - keep.astype(jnp.float32).mean()
    return out


# ---------------------------------------------------------------------------
# explicit engine-routed path (shard_map over one mesh axis)
# ---------------------------------------------------------------------------


def moe_param_specs(p: dict, axis: str, *, scanned: bool = False) -> dict:
    """PartitionSpecs for an :func:`init_moe` pytree under the explicit
    path: experts sharded over ``axis``, router/shared replicated.
    ``scanned`` shifts the expert specs one dim right for block params that
    carry a leading layer-scan (super-block) dim, (n_super, E, ...)."""
    e_spec = P(None, axis) if scanned else P(axis)
    specs = {"router": P(),
             "w_gate": e_spec, "w_in": e_spec, "w_out": e_spec}
    if "shared" in p:
        specs["shared"] = {k: P() for k in p["shared"]}
    return specs


def _explicit_body(p: dict, cfg: ModelConfig, x: jnp.ndarray, *, axis: str,
                   engine: CollectiveEngine, schedule: Optional[str] = None,
                   nchunks=1, dispatch_callsite: str = DISPATCH_CALLSITE,
                   combine_callsite: str = COMBINE_CALLSITE) -> jnp.ndarray:
    """The per-rank MoE layer (runs inside an enclosing ``shard_map``).

    ``x`` is the local batch shard (B_loc, S, D); ``p`` holds the local
    expert shard (E_loc experts) with the router/shared weights replicated.
    Routing uses global expert ids, so the dispatch/combine exchanges and
    the capacity bookkeeping match :func:`apply_moe` exactly.
    """
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    B_loc, S, D = x.shape
    C = _capacity(cfg, S)
    dtype = x.dtype
    probs, ids = route(p, cfg, x)  # router replicated: global expert ids
    e_idx, c_idx, keep, _ = _dispatch_indices(ids, E, C)
    tok = jnp.repeat(x, K, axis=1).reshape(B_loc, S * K, D)
    buf = _scatter_dispatch(tok.astype(dtype), e_idx, c_idx, E, C)
    buf = exchange_dispatch(buf, axis, engine, schedule=schedule,
                            nchunks=nchunks,
                            callsite=dispatch_callsite)  # (B, E_loc, C, D)
    y = _expert_ffn(p, buf, dtype)  # local experts only
    w_buf = _combine_weights(probs, keep, e_idx, c_idx, E, C)

    def weigh(strip, start):
        # the per-strip combine compute: weight the landed capacity
        # strip while the next strip is still on the wire
        wsl = lax.dynamic_slice_in_dim(w_buf, start, strip.shape[2], 2)
        return strip.astype(jnp.float32) * wsl[..., None]

    y_w = exchange_combine(y, axis, engine, schedule=schedule,
                           nchunks=nchunks, consume=weigh,
                           callsite=combine_callsite)
    out = _combine_scatter(y_w, e_idx, c_idx, S, K, E, C).astype(dtype)
    if cfg.shared_expert:
        out = out + _shared_expert(p["shared"], x, dtype)
    return out


def make_moe_impl(cfg: ModelConfig, mesh, *, axis: str = "x",
                  engine: Optional[CollectiveEngine] = None,
                  schedule: Optional[str] = None, nchunks=1,
                  dispatch_callsite: str = DISPATCH_CALLSITE,
                  combine_callsite: str = COMBINE_CALLSITE):
    """``moe_impl(p, x)`` hook for the explicit whole-model path.

    Unlike :func:`make_apply_moe_explicit` (which wraps one layer in its own
    ``shard_map``), the returned hook is the bare per-rank body — the
    transformer passes it via ``moe_impl=`` so the whole forward+backward
    stays inside a single enclosing ``shard_map``. Expert shards ride the
    param tree (specs from :func:`moe_param_specs` with ``scanned=True``).
    """
    n = mesh.shape[axis]
    if cfg.num_experts % n:
        raise ValueError(
            f"num_experts={cfg.num_experts} must be divisible by the "
            f"{axis!r} axis size {n} for the explicit expert-parallel "
            f"exchange")
    engine = engine or CollectiveEngine.for_mesh(mesh, schedule="auto")

    def moe_impl(p, x):
        return _explicit_body(p, cfg, x, axis=axis, engine=engine,
                              schedule=schedule, nchunks=nchunks,
                              dispatch_callsite=dispatch_callsite,
                              combine_callsite=combine_callsite)

    return moe_impl


def make_apply_moe_explicit(cfg: ModelConfig, mesh, *, axis: str = "x",
                            engine: Optional[CollectiveEngine] = None,
                            schedule: Optional[str] = None, nchunks=1):
    """jit'd ``(params, x) -> (B, S, D)`` expert-parallel MoE layer whose
    exchanges route through the collective engine.

    The whole layer runs inside ``shard_map`` over ``axis``: tokens are
    batch-sharded (B divisible by the axis size), experts sharded across
    ranks (E divisible too — ``E == axis size`` is the single-expert-per-
    rank edge). Each rank routes and scatters its own token rows into a
    (B_loc, E, C, D) buffer, :func:`exchange_dispatch` moves every rank's
    tokens to their expert owners (``all_to_all_tiles @ moe.dispatch``),
    the local experts run, and :func:`exchange_combine` returns the outputs
    (``@ moe.combine``) with the combine *weighting* applied per landed
    capacity strip — so with ``nchunks`` > 1 (or ``"auto"``, resolved by the
    fill-cost model) strip i's weighting overlaps strip i+1's wire hops.

    Routing, capacity drops, and the combine scatter-add order are shared
    with :func:`apply_moe`, so the output matches the GSPMD path (and
    :func:`reference_moe` when nothing is dropped) for every registered
    ``all_to_all_tiles`` schedule and every chunk count.
    """
    n = mesh.shape[axis]
    E = cfg.num_experts
    if E % n:
        raise ValueError(
            f"num_experts={E} must be divisible by the {axis!r} axis size "
            f"{n} for the explicit expert-parallel exchange")
    engine = engine or CollectiveEngine.for_mesh(mesh, schedule="auto")

    def body(p, x):
        return _explicit_body(p, cfg, x, axis=axis, engine=engine,
                              schedule=schedule, nchunks=nchunks)

    def wrapped(p, x):
        fn = shard_map(body, mesh=mesh,
                       in_specs=(moe_param_specs(p, axis), P(axis)),
                       out_specs=P(axis), check_vma=False)
        return fn(p, x)

    return jax.jit(wrapped)


def apply_moe_explicit(p: dict, cfg: ModelConfig, x: jnp.ndarray, mesh, *,
                       axis: str = "x",
                       engine: Optional[CollectiveEngine] = None,
                       schedule: Optional[str] = None, nchunks=1) -> jnp.ndarray:
    """Convenience wrapper: build :func:`make_apply_moe_explicit` and apply
    it once. For repeated timed calls hold the factory's jitted function."""
    return make_apply_moe_explicit(cfg, mesh, axis=axis, engine=engine,
                                   schedule=schedule, nchunks=nchunks)(p, x)


def reference_moe(p: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Oracle: dense loop over experts, no capacity drop. For tests with
    capacity_factor large enough that apply_moe drops nothing."""
    B, S, D = x.shape
    probs, ids = route(p, cfg, x)
    out = jnp.zeros((B, S, D), jnp.float32)
    for e in range(cfg.num_experts):
        w_e = ((ids == e).astype(jnp.float32) * probs).sum(axis=-1)  # (B,S)
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"][e].astype(x.dtype))
        h = jnp.einsum("bsd,df->bsf", x, p["w_in"][e].astype(x.dtype))
        y = jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * h, p["w_out"][e].astype(x.dtype))
        out = out + y.astype(jnp.float32) * w_e[..., None]
    out = out.astype(x.dtype)
    if cfg.shared_expert:
        out = out + _shared_expert(p["shared"], x, x.dtype)
    return out
