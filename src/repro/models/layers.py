"""Core neural-net layers: norms, rotary embeddings, attention, MLP.

Pure-functional: ``init_*`` build param pytrees, ``apply``-style functions
consume them. Attention is implemented blockwise (online softmax over KV
blocks) so activation memory stays O(S * block) instead of O(S^2); the Pallas
flash kernel in ``repro.kernels.attention`` is the TPU-optimized counterpart
and is validated against this implementation.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int) -> jnp.ndarray:
    return jnp.ones((d,), jnp.float32)


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Positions
# ---------------------------------------------------------------------------


def rope_table(positions: jnp.ndarray, head_dim: int, theta: float) -> tuple:
    """(sin, cos) tables for given integer positions; shape (..., head_dim/2)."""
    half = head_dim // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x: jnp.ndarray, sin: jnp.ndarray, cos: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, H, hd); sin/cos: (S, hd/2) or (B, S, hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if sin.ndim == 2:  # (S, half) -> broadcast over batch and heads
        sin_b = sin[None, :, None, :]
        cos_b = cos[None, :, None, :]
    else:  # (B, S, half)
        sin_b = sin[:, :, None, :]
        cos_b = cos[:, :, None, :]
    dtype = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out1 = x1f * cos_b - x2f * sin_b
    out2 = x2f * cos_b + x1f * sin_b
    return jnp.concatenate([out1, out2], axis=-1).astype(dtype)


def sinusoidal_positions(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    """Transformer sinusoidal embedding for integer positions -> (..., d_model)."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Attention (blockwise online softmax; GQA; causal or full)
# ---------------------------------------------------------------------------


def _gqa_scores_einsum(q, k):
    # q: (B, Sq, KV, G, hd), k: (B, Skv, KV, hd) -> (B, KV, G, Sq, Skv)
    return jnp.einsum("bqkgh,bskh->bkgqs", q, k, preferred_element_type=jnp.float32)


def _gqa_out_einsum(p, v):
    # p: (B, KV, G, Sq, Skv), v: (B, Skv, KV, hd) -> (B, Sq, KV, G, hd)
    return jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def attention(
    q: jnp.ndarray,  # (B, Sq, H, hd)
    k: jnp.ndarray,  # (B, Skv, KV, hd)
    v: jnp.ndarray,  # (B, Skv, KV, hd)
    *,
    causal: bool,
    q_offset=0,  # scalar or traced scalar: absolute position of q[0]
    kv_block: int = 1024,
    dense_threshold: int = 2048,
) -> jnp.ndarray:
    """Memory-efficient multi-head attention with GQA head grouping.

    For short KV (<= dense_threshold) or single-query decode the dense path is
    used (one einsum pair); otherwise KV is processed in blocks with an online
    softmax carried through ``lax.scan`` and per-block rematerialization.
    """
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = (q * scale).reshape(B, Sq, KV, G, hd)

    q_pos = q_offset + jnp.arange(Sq)

    if Sq == 1 or Skv <= dense_threshold:
        s = _gqa_scores_einsum(qg, k)  # (B, KV, G, Sq, Skv) fp32
        if causal:
            kv_pos = jnp.arange(Skv)
            mask = kv_pos[None, :] <= q_pos[:, None]  # (Sq, Skv)
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = _gqa_out_einsum(p, v)
        return o.reshape(B, Sq, H, hd).astype(q.dtype)

    # ---- blockwise path -----------------------------------------------------
    nblk = -(-Skv // kv_block)
    pad = nblk * kv_block - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblk, kv_block, KV, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, kv_block, KV, hd).transpose(1, 0, 2, 3, 4)

    def block(carry, xs):
        acc, m, l = carry
        kblk, vblk, bstart = xs  # (B, kv_block, KV, hd), scalar

        s = _gqa_scores_einsum(qg, kblk)  # (B, KV, G, Sq, kv_block) fp32
        kv_pos = bstart + jnp.arange(kv_block)
        valid = kv_pos[None, :] < Skv  # mask zero padding
        if causal:
            valid = valid & (kv_pos[None, :] <= q_pos[:, None])
        else:
            valid = jnp.broadcast_to(valid, (Sq, kv_block))
        s = jnp.where(valid[None, None, None], s, -jnp.inf)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard all-masked rows (m_new == -inf): scale factors become 0/exp(-inf)=0
        m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        alpha = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(valid[None, None, None], p, 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_blk = _gqa_out_einsum(p, vblk)  # (B, Sq, KV, G, hd) fp32
        acc_new = acc * alpha.transpose(0, 3, 1, 2)[..., None] + o_blk
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, Sq, KV, G, hd), jnp.float32)
    m0 = jnp.full((B, KV, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    starts = jnp.arange(nblk) * kv_block
    (acc, m, l), _ = jax.lax.scan(
        jax.checkpoint(block), (acc0, m0, l0), (kb, vb, starts))
    l = jnp.maximum(l, 1e-20)
    out = acc / l.transpose(0, 3, 1, 2)[..., None]
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,  # (B, 1, H, hd)
    k: jnp.ndarray,  # (B, Smax, KV, hd) gathered pages, new token written
    v: jnp.ndarray,  # (B, Smax, KV, hd)
    *,
    lengths: jnp.ndarray,  # (B,) int32: kv position of the newest token
) -> jnp.ndarray:
    """Single-token attention over gathered pages with per-row valid lengths.

    Positions ``> lengths[b]`` are masked out (``lengths[b]`` itself is the
    just-written token, so it participates). The mask fill is finite (no
    ``-inf``) so fully-masked rows — inactive serving slots — produce
    garbage instead of NaN; the server discards those rows.
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = (q * scale).reshape(B, Sq, KV, G, hd)
    s = _gqa_scores_einsum(qg, k)  # (B, KV, G, Sq, Smax) f32
    kv_pos = jnp.arange(k.shape[1])
    mask = kv_pos[None, :] <= lengths[:, None]  # (B, Smax)
    s = jnp.where(mask[:, None, None, None, :], s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s, axis=-1)
    o = _gqa_out_einsum(p, v)
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash-attention path: Pallas kernel under shard_map (prefill/forward-only)
# ---------------------------------------------------------------------------


def _flash_sharded(q, k, v, *, shard, causal: bool):
    """Run the Pallas flash kernel per device via shard_map: heads over the
    tensor-parallel axis, batch over dp; KV heads follow when they divide.
    The kernel keeps the score tile in VMEM, which removes the O(S^2) score
    materialization that dominates every prefill cell's HBM term (§Perf
    iteration A2). Returns None when this sharding is not applicable."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.kernels import ops as kops

    mesh = getattr(shard, "mesh", None)
    rules = getattr(shard, "rules", None)
    if mesh is None or rules is None:
        return None
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    dp, tp = rules.dp_spec, rules.tp
    dp_n = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_n *= mesh.shape[a]
    tp_n = mesh.shape[tp] if tp else 1
    if B % dp_n or (tp_n > 1 and H % tp_n) or Sq < 128:
        return None
    kv_ax = tp if (tp_n > 1 and KV % tp_n == 0) else None

    qspec = P(dp, None, tp if tp_n > 1 else None, None)
    kvspec = P(dp, None, kv_ax, None)
    H_loc = H // tp_n
    G_glob = H // KV

    def body(q_, k_, v_):
        if kv_ax is None and tp_n > 1:
            # KV heads replicated per shard: select the contiguous block of
            # kv heads this shard's q heads map to (GQA groups consecutive
            # q heads), so the kernel's local h//G mapping stays correct.
            s = jax.lax.axis_index(tp)
            n_kv = max(H_loc // G_glob, 1)
            start = (s * H_loc) // G_glob
            k_ = jax.lax.dynamic_slice_in_dim(k_, start, n_kv, axis=2)
            v_ = jax.lax.dynamic_slice_in_dim(v_, start, n_kv, axis=2)
        return kops.flash_attention(q_, k_, v_, causal=causal,
                                    bq=min(512, q_.shape[1]),
                                    bk=min(512, k_.shape[1]))

    fn = shard_map(body, mesh=mesh, in_specs=(qspec, kvspec, kvspec),
                   out_specs=qspec, check_vma=False)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Attention block (params + apply): self-attention with optional cache
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, *, kv_in_dim: Optional[int] = None,
                   layers_for_scale: Optional[int] = None) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kv_in = kv_in_dim or d
    nl = layers_for_scale or cfg.num_layers
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = 0.02
    p = {
        "wq": jax.random.normal(k1, (d, h, hd), jnp.float32) * std,
        "wk": jax.random.normal(k2, (kv_in, kv, hd), jnp.float32) * std,
        "wv": jax.random.normal(k3, (kv_in, kv, hd), jnp.float32) * std,
        "wo": jax.random.normal(k4, (h, hd, d), jnp.float32) * (std / math.sqrt(2 * nl)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), jnp.float32)
        p["bk"] = jnp.zeros((kv, hd), jnp.float32)
        p["bv"] = jnp.zeros((kv, hd), jnp.float32)
    if cfg.use_qk_norm:
        p["q_norm"] = init_rmsnorm(hd)
        p["k_norm"] = init_rmsnorm(hd)
    return p


def apply_attention(
    p: dict,
    cfg: ModelConfig,
    x: jnp.ndarray,  # (B, Sq, d_model)
    *,
    kv_x: Optional[jnp.ndarray] = None,  # cross-attn source (B, Skv, kv_in)
    cache: Optional[dict] = None,  # {'k','v'} (B, Smax, KV, hd) + pos,
    # or a paged pool {'k_pages','v_pages'} (num_pages, ps, KV, hd)
    pos=None,  # decode position scalar, or (B,) lengths for the paged path
    causal: bool = True,
    use_rope: bool = True,
    shard=None,  # activation-constraint callback (enables the flash path)
    attn_impl=None,  # explicit-path hook: (q, k, v, *, causal, q_offset) -> o
    page_table=None,  # paged decode: {'block_table': (B, pmax), 'lengths': (B,)}
):
    """Returns (out, new_cache). ``cache`` is updated at ``pos`` in decode.

    ``attn_impl`` (if given) replaces the core attention call — projections,
    biases, qk-norm, and rope still run here, then the hook receives the
    post-rope q/k/v. The explicit whole-model path passes the engine-routed
    exchanges from :mod:`repro.models.parallel`; the flash path is bypassed
    so the hook owns the entire score/softmax computation.

    When ``cache`` is a paged pool (``k_pages``/``v_pages``), ``page_table``
    maps serving slots to pages and the new-cache return carries only the
    token-sized ``k_upd``/``v_upd`` — the layer scan scatters them into the
    pool. A hook with a truthy ``paged`` attribute takes over the whole
    exchange + gather + attention (:func:`repro.models.parallel.
    make_paged_decode_attention`).
    """
    dtype = x.dtype
    src = kv_x if kv_x is not None else x

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    if cfg.use_qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)

    q_offset = 0
    if use_rope and cfg.rope_theta > 0 and kv_x is None:
        if pos is None:
            positions = jnp.arange(x.shape[1])
        elif getattr(pos, "ndim", 0) == 1:  # paged decode: per-row lengths
            positions = pos[:, None] + jnp.arange(x.shape[1])
            q_offset = pos
        else:
            positions = pos + jnp.arange(x.shape[1])
            q_offset = pos
        sin, cos = rope_table(positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    elif pos is not None:
        q_offset = pos

    if cache is not None and "k_pages" in cache:
        # paged decode: S == 1; the token update is NOT written here — the
        # layer scan scatters k_upd/v_upd into the page pool (one scatter
        # per buffer, same O(new tokens) HBM story as the dense merge)
        if kv_x is not None:
            raise ValueError("cross-attention KV is not cached here")
        if page_table is None:
            raise ValueError("paged cache requires page_table=")
        kp, vp = cache["k_pages"], cache["v_pages"]
        k_upd, v_upd = k.astype(kp.dtype), v.astype(vp.dtype)
        bt, lengths = page_table["block_table"], page_table["lengths"]
        if attn_impl is not None and getattr(attn_impl, "paged", False):
            o, k_upd, v_upd = attn_impl(q, k_upd, v_upd, pages_k=kp,
                                        pages_v=vp, block_table=bt,
                                        lengths=lengths)
        else:
            from repro.models.kvcache import gather_pages  # lazy: no cycle
            gk = gather_pages(kp, bt)
            gv = gather_pages(vp, bt)
            b_idx = jnp.arange(q.shape[0])
            gk = gk.at[b_idx, lengths].set(k_upd[:, 0], mode="drop")
            gv = gv.at[b_idx, lengths].set(v_upd[:, 0], mode="drop")
            o = decode_attention(q, gk.astype(dtype), gv.astype(dtype),
                                 lengths=lengths)
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dtype))
        return out, {"k_upd": k_upd, "v_upd": v_upd}

    new_cache = None
    if cache is not None:
        if kv_x is not None:
            raise ValueError("cross-attention KV is not cached here")
        ck, cv = cache["k"], cache["v"]
        if pos is None:  # prefill: write the whole prefix
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, 0, 0, 0))
            new_cache = {"k": ck, "v": cv}
        else:  # decode: write one (or few) positions
            k_upd, v_upd = k.astype(ck.dtype), v.astype(cv.dtype)
            ck = jax.lax.dynamic_update_slice(ck, k_upd, (0, pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v_upd, (0, pos, 0, 0))
            k, v = ck.astype(dtype), cv.astype(dtype)
            # return only the written token slice — the layer scan writes it
            # into the stacked cache with a token-sized dynamic-update-slice
            # instead of re-writing the whole layer cache (measured as the
            # dominant decode HBM term, §Perf iteration B2)
            new_cache = {"k_upd": k_upd, "v_upd": v_upd}

    o = None
    if attn_impl is not None:
        o = attn_impl(q, k, v, causal=causal and kv_x is None,
                      q_offset=q_offset)
    elif (shard is not None and kv_x is None and causal and cache is not None
            and pos is None):
        # prefill: forward-only — VMEM-tiled Pallas flash kernel per shard
        o = _flash_sharded(q, k, v, shard=shard, causal=True)
    if o is None:
        o = attention(q, k, v, causal=causal and kv_x is None,
                      q_offset=q_offset)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dtype))
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------


def init_mlp(key, d: int, d_ff: int, num_layers: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    std = 0.02
    return {
        "w_gate": jax.random.normal(k1, (d, d_ff), jnp.float32) * std,
        "w_in": jax.random.normal(k2, (d, d_ff), jnp.float32) * std,
        "w_out": jax.random.normal(k3, (d_ff, d), jnp.float32) * (std / math.sqrt(2 * num_layers)),
    }


def apply_mlp(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    dtype = x.dtype
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dtype))
    h = jnp.einsum("bsd,df->bsf", x, p["w_in"].astype(dtype))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * h, p["w_out"].astype(dtype))
