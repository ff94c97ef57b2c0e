"""Mamba-2 SSD (state-space duality) block — chunked matmul form + decode.

The chunked dual form (Dao & Gu, arXiv:2405.21060 §6) computes the selective
state-space recurrence as block-diagonal "attention-like" matmuls within
chunks plus a low-rank inter-chunk state recurrence — this is the MXU-friendly
TPU adaptation (systolic matmuls instead of a sequential scan over L).

Decode is the O(1)-memory recurrent step: h' = exp(dt*A) h + dt * (B ⊗ x),
y = C·h' + D*x — which is why the SSM/hybrid archs are the only ones that run
the 500k-token long-context decode cell (DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import rmsnorm, init_rmsnorm


def ssm_dims(cfg: ModelConfig) -> Tuple[int, int, int, int, int]:
    """(d_inner, nheads, head_dim P, ngroups G, state N)."""
    d_in = cfg.ssm_expand * cfg.d_model
    P = cfg.ssm_head_dim
    H = d_in // P
    return d_in, H, P, cfg.ssm_ngroups, cfg.ssm_state


def init_ssm(key, cfg: ModelConfig) -> dict:
    """Projections are stored *segmented* (x, z, BC, dt) rather than as
    mamba's packed in_proj so each segment can be tensor-sharded cleanly:
    d_inner and heads shard over 'model'; the (small, grouped) B/C and the
    conv over them stay replicated (repro/sharding.py)."""
    d = cfg.d_model
    d_in, H, P, G, N = ssm_dims(cfg)
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    std = 0.02
    # dt bias init so softplus(dt_bias) spans [1e-3, 1e-1] (mamba2 default)
    u = jax.random.uniform(k4, (H,), jnp.float32)
    dt0 = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))  # inverse softplus
    return {
        "in_x": jax.random.normal(k1, (d, d_in), jnp.float32) * std,
        "in_z": jax.random.normal(k5, (d, d_in), jnp.float32) * std,
        "in_bc": jax.random.normal(k6, (d, 2 * G * N), jnp.float32) * std,
        "in_dt": jax.random.normal(k7, (d, H), jnp.float32) * std,
        "conv_x": jax.random.normal(k2, (cfg.ssm_conv, d_in), jnp.float32) * std,
        "conv_x_b": jnp.zeros((d_in,), jnp.float32),
        "conv_bc": jax.random.normal(k2, (cfg.ssm_conv, 2 * G * N), jnp.float32) * std,
        "conv_bc_b": jnp.zeros((2 * G * N,), jnp.float32),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": dt_bias,
        "norm": init_rmsnorm(d_in),
        "out_proj": jax.random.normal(k3, (d_in, d), jnp.float32)
        * (std / math.sqrt(2 * cfg.num_layers)),
    }


# ---------------------------------------------------------------------------
# chunked SSD core
# ---------------------------------------------------------------------------


def ssd_chunked(x, dt, A, B, C, chunk: int, h0=None):
    """Chunked SSD.

    x: (b, L, H, P)  dt: (b, L, H)  A: (H,) (negative)
    B, C: (b, L, G, N);  heads h use group h // (H//G).
    Returns (y (b,L,H,P), h_final (b,H,P,N)).
    """
    b, L, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    Q = chunk
    pad = (-L) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Lp = L + pad
    nc = Lp // Q

    xc = x.reshape(b, nc, Q, H, P)
    dtc = dt.reshape(b, nc, Q, H).astype(jnp.float32)
    Bc = B.reshape(b, nc, Q, G, N)
    Cc = C.reshape(b, nc, Q, G, N)

    dA = dtc * A  # (b,nc,Q,H), negative
    cs = jnp.cumsum(dA, axis=2)  # inclusive cumsum within chunk

    # ---- intra-chunk (block-diagonal "attention") --------------------------
    # scores_g[b,c,g,q,k] = C_q . B_k  (per group)
    scores = jnp.einsum("bcqgn,bckgn->bcgqk", Cc, Bc,
                        preferred_element_type=jnp.float32)
    # decay L[b,c,h,q,k] = exp(cs_q - cs_k) for q >= k
    diff = cs[:, :, :, None, :] - cs[:, :, None, :, :]  # (b,nc,Q,Q,H) q,k
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    # mask before the exp: above the diagonal diff > 0 grows with the chunk
    # and overflows, and exp's gradient there (inf) times where's zero
    # cotangent is NaN
    decay = jnp.exp(jnp.where(mask[None, None, :, :, None], diff, -jnp.inf))
    # fold group->head: M[b,c,h,q,k]
    scores_h = jnp.repeat(scores, rep, axis=2) if rep > 1 else scores
    # scores_h: (b,nc,G*rep=H,q,k); decay: (b,nc,q,k,H) -> align
    M = scores_h * jnp.moveaxis(decay, -1, 2) * jnp.moveaxis(
        dtc, -1, 2)[:, :, :, None, :]  # dt_k
    y = jnp.einsum("bchqk,bckhp->bcqhp", M, xc.astype(jnp.float32),
                   preferred_element_type=jnp.float32)

    # ---- chunk states -------------------------------------------------------
    # S_c[b,h,p,n] = sum_k exp(cs_last - cs_k) dt_k x_k B_k
    seg = jnp.exp(cs[:, :, -1:, :] - cs) * dtc  # (b,nc,Q,H)
    Bh = jnp.repeat(Bc, rep, axis=3) if rep > 1 else Bc  # (b,nc,Q,H,N)
    states = jnp.einsum("bcqh,bcqhp,bcqhn->bchpn",
                        seg, xc.astype(jnp.float32), Bh.astype(jnp.float32),
                        preferred_element_type=jnp.float32)

    # ---- inter-chunk recurrence ---------------------------------------------
    chunk_decay = jnp.exp(cs[:, :, -1, :])  # (b,nc,H): exp(sum dA over chunk)
    if h0 is None:
        h0 = jnp.zeros((b, H, P, N), jnp.float32)

    def step(h, inp):
        s_c, g_c = inp  # (b,H,P,N), (b,H)
        prev = h
        h = g_c[:, :, None, None] * h + s_c
        return h, prev

    h_final, prev_states = jax.lax.scan(
        step, h0.astype(jnp.float32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)  # (b,nc,H,P,N)

    # ---- inter-chunk contribution --------------------------------------------
    Ch = jnp.repeat(Cc, rep, axis=3) if rep > 1 else Cc  # (b,nc,Q,H,N)
    q_decay = jnp.exp(cs)  # (b,nc,Q,H)
    y_off = jnp.einsum("bcqhn,bchpn->bcqhp", Ch.astype(jnp.float32), prev_states,
                       preferred_element_type=jnp.float32)
    y = y + y_off * q_decay[..., None]

    y = y.reshape(b, Lp, H, P)[:, :L]
    return y.astype(x.dtype), h_final


def ssd_reference(x, dt, A, B, C, h0=None):
    """Oracle: sequential recurrence over L (slow; tests only)."""
    b, L, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=2) if rep > 1 else B
    Ch = jnp.repeat(C, rep, axis=2) if rep > 1 else C
    h = jnp.zeros((b, H, P, N), jnp.float32) if h0 is None else h0.astype(jnp.float32)
    ys = []
    for t in range(L):
        dt_t = dt[:, t].astype(jnp.float32)  # (b,H)
        g = jnp.exp(dt_t * A)  # (b,H)
        upd = jnp.einsum("bh,bhp,bhn->bhpn", dt_t, x[:, t].astype(jnp.float32),
                         Bh[:, t].astype(jnp.float32))
        h = g[:, :, None, None] * h + upd
        y = jnp.einsum("bhn,bhpn->bhp", Ch[:, t].astype(jnp.float32), h)
        ys.append(y)
    return jnp.stack(ys, axis=1).astype(x.dtype), h


# ---------------------------------------------------------------------------
# full block
# ---------------------------------------------------------------------------


def _causal_conv(xBC, w, b, conv_cache=None):
    """Depthwise causal conv. xBC: (B, L, ch); w: (K, ch)."""
    K = w.shape[0]
    if conv_cache is None:
        pad = jnp.zeros((xBC.shape[0], K - 1, xBC.shape[2]), xBC.dtype)
    else:
        pad = conv_cache.astype(xBC.dtype)
    xp = jnp.concatenate([pad, xBC], axis=1)  # (B, L+K-1, ch)
    out = jnp.zeros_like(xBC, dtype=jnp.float32)
    for k in range(K):
        out = out + xp[:, k:k + xBC.shape[1]].astype(jnp.float32) * w[k].astype(jnp.float32)
    out = out + b.astype(jnp.float32)
    new_cache = xp[:, xp.shape[1] - (K - 1):]
    return out.astype(xBC.dtype), new_cache


def apply_ssm(p: dict, cfg: ModelConfig, x: jnp.ndarray, *,
              cache: Optional[dict] = None, pos=None):
    """Mamba-2 block. x: (B, S, D) -> (B, S, D); returns (y, new_cache).

    cache = {'conv': (B, K-1, ch), 'state': (B, H, P, N)}; decode when
    ``pos is not None`` and S == 1 (recurrent step).
    """
    Bsz, S, D = x.shape
    d_in, H, P, G, N = ssm_dims(cfg)
    dtype = x.dtype

    z = jnp.einsum("bsd,de->bse", x, p["in_z"].astype(dtype))
    xin = jnp.einsum("bsd,de->bse", x, p["in_x"].astype(dtype))
    bc = jnp.einsum("bsd,de->bse", x, p["in_bc"].astype(dtype))
    dt_raw = jnp.einsum("bsd,dh->bsh", x, p["in_dt"].astype(dtype))

    decode = pos is not None and S == 1
    xin, new_conv_x = _causal_conv(
        xin, p["conv_x"], p["conv_x_b"],
        conv_cache=cache.get("conv_x") if (cache and decode) else None)
    bc, new_conv_bc = _causal_conv(
        bc, p["conv_bc"], p["conv_bc_b"],
        conv_cache=cache.get("conv_bc") if (cache and decode) else None)
    xin = jax.nn.silu(xin)
    bc = jax.nn.silu(bc)
    Bm, Cm = jnp.split(bc, [G * N], axis=-1)

    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # (H,)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)

    xh = xin.reshape(Bsz, S, H, P)
    Bh = Bm.reshape(Bsz, S, G, N)
    Ch = Cm.reshape(Bsz, S, G, N)

    if decode:
        h = cache["state"].astype(jnp.float32)  # (B,H,P,N)
        dt1 = dt[:, 0]  # (B,H)
        g = jnp.exp(dt1 * A)
        rep = H // G
        B1 = jnp.repeat(Bh[:, 0], rep, axis=1) if rep > 1 else Bh[:, 0]  # (B,H,N)
        C1 = jnp.repeat(Ch[:, 0], rep, axis=1) if rep > 1 else Ch[:, 0]
        upd = jnp.einsum("bh,bhp,bhn->bhpn", dt1, xh[:, 0].astype(jnp.float32),
                         B1.astype(jnp.float32))
        h = g[:, :, None, None] * h + upd
        y = jnp.einsum("bhn,bhpn->bhp", C1.astype(jnp.float32), h)[:, None]  # (B,1,H,P)
        new_state = h
    else:
        h0 = cache["state"] if cache else None
        y, new_state = ssd_chunked(xh, dt, A, Bh, Ch, cfg.ssm_chunk, h0=h0)

    y = y + xh.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(Bsz, S, d_in).astype(dtype)

    # gated RMSNorm (mamba2): norm(y * silu(z))
    y = rmsnorm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(dtype))

    new_cache = None
    if cache is not None:
        new_cache = {"conv_x": new_conv_x.astype(cache["conv_x"].dtype),
                     "conv_bc": new_conv_bc.astype(cache["conv_bc"].dtype),
                     "state": new_state.astype(cache["state"].dtype)}
    return out, new_cache
