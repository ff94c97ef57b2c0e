"""Explicit expert-parallel MoE equivalence suite on the 8-device mesh.

The engine-routed ``apply_moe_explicit`` path must agree with the dense
``reference_moe`` oracle and with the GSPMD ``apply_moe`` for every
registered ``all_to_all_tiles`` schedule and every pipeline chunk count —
the exchanges are pure data movement and the routing/scatter internals are
shared, so on CPU the agreement is exact (asserted with a tight tolerance
to stay robust to compiler reassociation).
"""
from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm.engine import CollectiveEngine, schedules_for
from repro.launch.mesh import make_mesh
from repro.configs import get_config, reduced
from repro.models import moe as MOE

NDEV = 8
pytestmark = pytest.mark.skipif(
    len(jax.devices()) < NDEV, reason=f"needs {NDEV} devices")

A2A_SCHEDULES = sorted(schedules_for("all_to_all_tiles"))


@pytest.fixture(scope="module")
def ring():
    return make_mesh((NDEV,), ("x",))


def _cfg(**over):
    cfg = reduced(get_config("qwen3-moe-235b-a22b"))
    base = dict(num_experts=2 * NDEV, num_experts_per_tok=2,
                capacity_factor=8.0)
    base.update(over)
    return replace(cfg, **base)


def _inputs(cfg, seed=0, B=NDEV, S=16):
    p = MOE.init_moe(jax.random.key(seed), cfg)
    x = jax.random.normal(jax.random.key(seed + 1), (B, S, cfg.d_model),
                          jnp.float32)
    return p, x


def _gspmd(cfg, p, x, mesh):
    """The GSPMD path run on the mesh: batch-sharded input, XLA inserts the
    expert resharding itself."""
    xs = jax.device_put(x, NamedSharding(mesh, P("x", None, None)))
    return np.asarray(jax.jit(lambda p, x: MOE.apply_moe(p, cfg, x))(p, xs))


# ---------------------------------------------------------------------------
# explicit == reference == GSPMD, per schedule x chunk count
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule", A2A_SCHEDULES)
@pytest.mark.parametrize("nchunks", [1, 2, "auto"])
def test_explicit_matches_reference_and_gspmd(ring, schedule, nchunks):
    cfg = _cfg()
    p, x = _inputs(cfg)
    out = np.asarray(MOE.apply_moe_explicit(p, cfg, x, ring,
                                            schedule=schedule,
                                            nchunks=nchunks))
    ref = np.asarray(MOE.reference_moe(p, cfg, x))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-4,
                               err_msg=f"{schedule}/nchunks={nchunks}")
    np.testing.assert_allclose(out, _gspmd(cfg, p, x, ring),
                               atol=1e-6, rtol=1e-6,
                               err_msg=f"{schedule}/nchunks={nchunks}")


def test_explicit_schedules_agree_with_each_other(ring):
    """Every (schedule, nchunks) variant lands on the same numbers: the
    exchange route never changes the data, only the wire path."""
    cfg = _cfg()
    p, x = _inputs(cfg, seed=4)
    base = np.asarray(MOE.apply_moe_explicit(p, cfg, x, ring,
                                             schedule="native", nchunks=1))
    for schedule in A2A_SCHEDULES:
        for nchunks in (2, 3, "auto"):
            out = np.asarray(MOE.apply_moe_explicit(
                p, cfg, x, ring, schedule=schedule, nchunks=nchunks))
            np.testing.assert_allclose(
                out, base, atol=1e-6, rtol=1e-6,
                err_msg=f"{schedule}/nchunks={nchunks}")


def test_explicit_auto_engine_resolves_registered(ring):
    """schedule="auto" end-to-end: the engine's per-callsite resolutions are
    registered names (never the literal "auto") and the output still
    matches the oracle."""
    cfg = _cfg()
    p, x = _inputs(cfg, seed=2)
    engine = CollectiveEngine.for_mesh(ring, schedule="auto")
    out = np.asarray(MOE.apply_moe_explicit(p, cfg, x, ring, engine=engine,
                                            nchunks="auto"))
    np.testing.assert_allclose(out, np.asarray(MOE.reference_moe(p, cfg, x)),
                               atol=1e-5, rtol=1e-4)
    nbytes = x.shape[0] // NDEV * cfg.num_experts * 16 * cfg.d_model * 4
    for callsite in (MOE.DISPATCH_CALLSITE, MOE.COMBINE_CALLSITE):
        name = engine.schedule_for("all_to_all_tiles", nbytes=nbytes,
                                   axis="x", callsite=callsite)
        assert name != "auto" and name in schedules_for("all_to_all_tiles")


# ---------------------------------------------------------------------------
# edge cases: capacity overflow, single expert per rank
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule", A2A_SCHEDULES)
def test_capacity_overflow_drops_match_gspmd(ring, schedule):
    """With capacity_factor << 1 tokens are dropped; the explicit path must
    drop exactly the same slots as the GSPMD path (shared per-row cumsum
    bookkeeping), so outputs agree even though the oracle does not."""
    cfg = _cfg(capacity_factor=0.5)
    p, x = _inputs(cfg, seed=6)
    aux = {}
    want = np.asarray(MOE.apply_moe(p, cfg, x, aux=aux))
    assert float(aux["moe_dropped"]) > 0.0  # the edge case is exercised
    out = np.asarray(MOE.apply_moe_explicit(p, cfg, x, ring,
                                            schedule=schedule, nchunks=2))
    np.testing.assert_allclose(out, want, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("nchunks", [1, "auto"])
def test_single_expert_per_rank_top1(ring, nchunks):
    """E == ranks (one expert per rank, E_loc = 1) with top-1 routing: the
    degenerate exchange shapes still round-trip."""
    cfg = _cfg(num_experts=NDEV, num_experts_per_tok=1, capacity_factor=16.0)
    p, x = _inputs(cfg, seed=8)
    out = np.asarray(MOE.apply_moe_explicit(p, cfg, x, ring,
                                            nchunks=nchunks))
    np.testing.assert_allclose(out, np.asarray(MOE.reference_moe(p, cfg, x)),
                               atol=1e-5, rtol=1e-4)


def test_experts_must_divide_over_axis(ring):
    cfg = _cfg(num_experts=NDEV - 2)
    p, x = _inputs(cfg)
    with pytest.raises(ValueError, match="divisible"):
        MOE.apply_moe_explicit(p, cfg, x, ring)


# ---------------------------------------------------------------------------
# pipelined exchange bit-identity (integer payloads -> exact)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule", A2A_SCHEDULES)
def test_pipelined_exchange_bit_identical_to_monolithic(ring, schedule):
    """exchange_dispatch/combine pipelined into capacity strips move exactly
    the same bytes as the monolithic exchange, for every schedule."""
    from jax import shard_map
    rng = np.random.default_rng(9)
    buf = rng.integers(-8, 8, (NDEV, 2, 2 * NDEV, 5, 4)).astype(np.float32)
    eng = CollectiveEngine.for_mesh(ring, schedule=schedule)
    spec = P("x", None, None, None, None)

    def run(nchunks):
        def body(v):
            d = MOE.exchange_dispatch(v[0], "x", eng, nchunks=nchunks)
            return MOE.exchange_combine(d, "x", eng, nchunks=nchunks)[None]
        fn = jax.jit(shard_map(body, mesh=ring, in_specs=(spec,),
                               out_specs=spec, check_vma=False))
        return np.asarray(fn(jnp.asarray(buf)))

    mono = run(1)
    np.testing.assert_array_equal(mono, buf)  # dispatch∘combine == identity
    for nchunks in (2, 3, 64, "auto"):  # 64 > C clamps to one slot per strip
        np.testing.assert_array_equal(run(nchunks), mono,
                                      err_msg=str(nchunks))
