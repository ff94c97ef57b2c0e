"""Whole-model explicit-vs-GSPMD equivalence on the 8-device mesh.

The tentpole guarantee of the explicit path: one full qwen3-moe train step
through ``make_whole_model_train_step_explicit`` — forward+backward inside
a single ``shard_map``, attention exchanged under ``tp.*``/``sp.*`` tags,
MoE under ``moe.*``, gradient buckets under ``dp.grads`` — must match the
GSPMD ``make_train_step`` on the same mesh from identical init: the loss,
the clipped global grad norm, and every updated parameter, for every
registered schedule kind and chunk count tested. The two programs share
all the math; the exchanges and the hand-written reduction/clip only
reassociate float sums, so tolerances are f32-roundoff-sized.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.configs import RunConfig
from repro.configs.qwen3_moe_235b_a22b import tiny
from repro.data import DataConfig, SyntheticLMDataset
from repro.models.model import build_model
from repro.models.parallel import ATTN_MODES, make_attn_impl
from repro.train.loop import TrainLoopConfig, train_loop
from repro.train.step import (init_train_state, make_train_step,
                              make_whole_model_train_step_explicit)

NDEV = 8
pytestmark = pytest.mark.skipif(
    len(jax.devices()) < NDEV, reason=f"needs {NDEV} devices")

B, S = NDEV, 16


@pytest.fixture(scope="module")
def ring():
    return make_mesh((NDEV,), ("x",))


def _cfg(layers=2):
    # layers=2 with moe_every=1 gives n_super=2: the super-block scan (and
    # its scanned expert-param specs) is exercised, not just one layer
    return tiny(NDEV, layers=layers)


def _setup(cfg, seed=0):
    model = build_model(cfg)
    data = SyntheticLMDataset(DataConfig(cfg.vocab_size, B, S))
    batch = {"tokens": jnp.asarray(data.batch(0)["tokens"])}
    state = init_train_state(model, jax.random.key(seed))
    return model, batch, state


def _run_cfg():
    return RunConfig(learning_rate=1e-3, warmup_steps=1)


@pytest.fixture(scope="module")
def gspmd_ref(ring):
    """One GSPMD reference step (pure DP on the ring: params replicated)."""
    cfg = _cfg()
    model, batch, state = _setup(cfg)
    step = make_train_step(model, _run_cfg(), ring, donate=False)
    ref_state, ref_metrics = jax.block_until_ready(step(state, batch))
    params = [np.asarray(v, np.float32)
              for v in jax.tree.leaves(ref_state.params)]
    return {"params": params,
            "loss": float(ref_metrics["loss"]),
            "grad_norm": float(ref_metrics["grad_norm"])}


# ---------------------------------------------------------------------------
# explicit whole-model step == GSPMD, per mode x schedule x chunk count
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ATTN_MODES)
@pytest.mark.parametrize("schedule_kind", ["native", "chain"])
@pytest.mark.parametrize("nchunks", [1, "auto"])
def test_whole_model_matches_gspmd(ring, gspmd_ref, mode, schedule_kind,
                                   nchunks):
    cfg = _cfg()
    model, batch, state = _setup(cfg)
    step = make_whole_model_train_step_explicit(
        model, _run_cfg(), ring, attn_mode=mode,
        schedule_kind=schedule_kind, nchunks=nchunks)
    new_state, metrics = jax.block_until_ready(step(state, batch))

    tag = f"{mode}/{schedule_kind}/nchunks={nchunks}"
    np.testing.assert_allclose(float(metrics["loss"]), gspmd_ref["loss"],
                               atol=1e-5, rtol=0, err_msg=tag)
    np.testing.assert_allclose(float(metrics["grad_norm"]),
                               gspmd_ref["grad_norm"], rtol=1e-4,
                               err_msg=tag)
    for got, want in zip(jax.tree.leaves(new_state.params),
                         gspmd_ref["params"]):
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   atol=2e-5, rtol=1e-4, err_msg=tag)


def test_modes_agree_with_each_other(ring):
    """tp and sp compute the same updated params (independent of GSPMD)."""
    cfg = _cfg()
    results = {}
    for mode in ATTN_MODES:
        model, batch, state = _setup(cfg)
        step = make_whole_model_train_step_explicit(
            model, _run_cfg(), ring, attn_mode=mode)
        new_state, metrics = jax.block_until_ready(step(state, batch))
        results[mode] = (float(metrics["loss"]),
                         [np.asarray(v, np.float32)
                          for v in jax.tree.leaves(new_state.params)])
    l_tp, p_tp = results["tp"]
    l_sp, p_sp = results["sp"]
    np.testing.assert_allclose(l_tp, l_sp, atol=1e-5, rtol=0)
    for a, b in zip(p_tp, p_sp):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# validation errors
# ---------------------------------------------------------------------------


def test_indivisible_heads_raise(ring):
    import dataclasses
    cfg = dataclasses.replace(_cfg(), num_heads=4, num_kv_heads=4,
                              head_dim=16)  # 4 heads over 8 ranks
    with pytest.raises(ValueError, match="divisible"):
        make_attn_impl("tp", cfg, ring)


def test_unknown_mode_raises(ring):
    with pytest.raises(ValueError, match="unknown attention mode"):
        make_attn_impl("pp", _cfg(), ring)


def test_grad_compression_rejected(ring):
    model, _, _ = _setup(_cfg())
    run_cfg = RunConfig(learning_rate=1e-3, warmup_steps=1,
                        grad_compression="int8_ef")
    with pytest.raises(ValueError, match="grad_compression"):
        make_whole_model_train_step_explicit(model, run_cfg, ring)


# ---------------------------------------------------------------------------
# explicit train_loop smoke
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("step_mode", ["explicit_tp", "explicit_sp"])
def test_train_loop_explicit_smoke(ring, step_mode):
    cfg = _cfg(layers=1)
    hist = train_loop(
        cfg, _run_cfg(), DataConfig(cfg.vocab_size, B, S),
        TrainLoopConfig(steps=3, log_every=1, step_mode=step_mode),
        mesh=ring)
    assert len(hist["loss"]) == 3
    assert all(np.isfinite(v) for v in hist["loss"])
