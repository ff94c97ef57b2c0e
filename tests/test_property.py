"""Hypothesis property tests on system invariants."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed (optional [test] extra)")
from hypothesis import example, given, settings, strategies as st  # noqa: E402

from repro.comm import compression
from repro.comm.autotune import CostModel, route_links
from repro.comm.engine import CollectiveEngine, schedules_for
from repro.comm.topology import AxisTopology
from repro.comm.types import TPU_V5E
from repro.launch.mesh import make_mesh
from repro.core import models
from repro.core.ptrans import distribute_cyclic, undistribute_cyclic
from repro.data import DataConfig, SyntheticLMDataset
from repro.kernels.gemm import fit_block
from repro.models.model import next_token_loss
from repro.roofline import _wire_factor, shape_bytes

SETTINGS = settings(max_examples=25, deadline=None)
# collective property tests jit-compile per drawn shape: keep the example
# count small and the shape pools discrete so the compile cache saturates
A2A_SETTINGS = settings(max_examples=10, deadline=None)


# --- PQ block-cyclic distribution is a bijection ---------------------------


@SETTINGS
@given(pg=st.sampled_from([1, 2, 4]),
       lb=st.integers(1, 3),
       b=st.sampled_from([2, 4, 8]),
       seed=st.integers(0, 2**31 - 1))
def test_distribute_undistribute_roundtrip(pg, lb, b, seed):
    n = pg * lb * b
    rng = np.random.default_rng(seed)
    mat = rng.standard_normal((n, n)).astype(np.float32)
    shards = distribute_cyclic(mat, pg, b)
    assert shards.shape == (pg * pg, lb * b, lb * b)
    back = undistribute_cyclic(shards, pg, b)
    np.testing.assert_array_equal(back, mat)


@SETTINGS
@given(pg=st.sampled_from([2, 4]), b=st.sampled_from([2, 4]),
       seed=st.integers(0, 2**31 - 1))
def test_distribution_preserves_multiset(pg, b, seed):
    n = pg * 2 * b
    rng = np.random.default_rng(seed)
    mat = rng.standard_normal((n, n)).astype(np.float32)
    shards = distribute_cyclic(mat, pg, b)
    np.testing.assert_allclose(np.sort(shards.ravel()), np.sort(mat.ravel()))


# --- int8 error-feedback quantization ---------------------------------------


@SETTINGS
@given(size=st.integers(1, 2000), scale=st.floats(1e-3, 1e3),
       seed=st.integers(0, 2**31 - 1))
@example(size=446, scale=48.0, seed=237)  # |x| ~ 150: past any fixed slack
def test_quantize_error_bound(size, scale, seed):
    """|x - deq(q(x))| <= max|block| / 127 / 2 per element (half-step),
    plus float32 rounding of the scale, the quotient and the product — a
    few ulps of the block maximum, so the slack scales with it."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(size).astype(np.float32) * scale)
    q, s = compression.quantize(x)
    back = compression.dequantize(q, s, x.shape, x.size)
    blocks = np.asarray(jnp.pad(x, (0, (-x.size) % compression.BLOCK))
                        ).reshape(-1, compression.BLOCK)
    amax = np.abs(blocks).max(axis=1)
    bound = amax / 127.0 * 0.5 + 4 * np.finfo(np.float32).eps * amax
    err = np.abs(np.asarray(back) - np.asarray(x)).reshape(-1)
    per_block_err = np.pad(err, (0, (-err.size) % compression.BLOCK)
                           ).reshape(-1, compression.BLOCK)
    assert (per_block_err.max(axis=1) <= bound).all()


# --- fit_block always returns a divisor -------------------------------------


@SETTINGS
@given(size=st.integers(1, 4096), pref=st.integers(1, 512))
def test_fit_block_divides(size, pref):
    b = fit_block(size, pref)
    assert 1 <= b <= max(pref, 1)
    assert size % b == 0


# --- loss properties ---------------------------------------------------------


@SETTINGS
@given(seed=st.integers(0, 2**31 - 1))
def test_loss_nonnegative_and_uniform_bound(seed):
    """CE >= 0; for logits ~ 0 the loss is ~= log(V)."""
    rng = np.random.default_rng(seed)
    B, S, V = 2, 8, 64
    tokens = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    zero_logits = jnp.zeros((B, S, V))
    loss = float(next_token_loss(zero_logits, tokens, z_loss=0.0))
    np.testing.assert_allclose(loss, np.log(V), rtol=1e-5)
    logits = jnp.asarray(rng.standard_normal((B, S, V)), jnp.float32)
    assert float(next_token_loss(logits, tokens, z_loss=0.0)) > 0


@SETTINGS
@given(seed=st.integers(0, 2**31 - 1))
def test_loss_perfect_prediction_goes_small(seed):
    rng = np.random.default_rng(seed)
    B, S, V = 2, 8, 64
    tokens = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    logits = 100.0 * jax.nn.one_hot(tokens[:, 1:], V)
    logits = jnp.pad(logits, ((0, 0), (0, 1), (0, 0)))  # align: pos t -> t+1
    logits = jnp.roll(logits, 1, axis=1) * 0 + jnp.concatenate(
        [100.0 * jax.nn.one_hot(tokens[:, 1:], V),
         jnp.zeros((B, 1, V))], axis=1)
    assert float(next_token_loss(logits, tokens, z_loss=0.0)) < 1e-3


# --- paper model functions ----------------------------------------------------


@SETTINGS
@given(bws=st.lists(st.floats(1e3, 1e12), min_size=1, max_size=21))
def test_effective_bandwidth_is_mean(bws):
    d = {2 ** i: bw for i, bw in enumerate(bws)}
    assert models.effective_bandwidth(d) == sum(bws) / len(bws)
    assert min(bws) - 1e-6 <= models.effective_bandwidth(d) <= max(bws) + 1e-6


@SETTINGS
@given(L=st.integers(1, 1 << 20))
def test_beff_models_monotone_bounded(L):
    """Bandwidth grows with message size and never exceeds the link peak."""
    csn = models.beff_csn_model_520n(L)
    assert csn <= 2 * 64 * 156.25e6 + 1e-6  # 2 channels x 32 B x f
    ici = models.beff_ici_model(L)
    assert ici <= 2 * 50e9
    if L >= 2:
        assert models.beff_ici_model(L) >= models.beff_ici_model(L // 2) - 1e-6


def test_beff_csn_model_matches_paper_eq4():
    """Paper Eq. 4 at L=64B: b = 2*64 / (6.4ns + 520ns)."""
    got = models.beff_csn_model_520n(64)
    want = 2 * 64 / (6.4e-9 + 520e-9)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_hpl_flops_rule():
    assert models.hpl_flops(1000) == 2e9 / 3


@SETTINGS
@given(n=st.integers(2, 64))
def test_wire_factor_bounds(n):
    for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all"):
        f = _wire_factor(op, n)
        assert 0 < f < 2
    assert _wire_factor("all-reduce", n) == 2 * (n - 1) / n


# --- data pipeline: shard independence of worker count -----------------------


@SETTINGS
@given(step=st.integers(0, 1000), seed=st.integers(0, 100))
def test_data_pure_function_of_step_shard(step, seed):
    cfg = DataConfig(vocab_size=128, global_batch=4, seq_len=16, seed=seed)
    a = SyntheticLMDataset(cfg).batch(step, 1, 2)["tokens"]
    b = SyntheticLMDataset(cfg).batch(step, 1, 2)["tokens"]
    np.testing.assert_array_equal(a, b)


# --- all_to_all_tiles: schedule equivalence + pipelined == monolithic --------
#
# Runs over a ring of however many devices this process sees (1 locally; the
# CI tier-1 job sets the 8-device XLA flag, so the schedules exchange for
# real there). The 8-device-only MoE layer equivalence lives in
# tests/dist/test_moe.py; these randomized-shape/dtype properties cover
# every all_to_all_tiles callsite shape the engine can see.

_NDEV = len(jax.devices())
_A2A_MESH = make_mesh((_NDEV,), ("x",))
_A2A_DTYPES = ["float32", "int32", "bfloat16", "float16"]


def _a2a_run(schedule, x, split_axis, concat_axis):
    eng = CollectiveEngine.for_mesh(_A2A_MESH, schedule=schedule)

    def body(v):
        return eng.all_to_all_tiles(v[0], "x", split_axis=split_axis,
                                    concat_axis=concat_axis)[None]

    spec = P("x", *([None] * (x.ndim - 1)))
    fn = jax.jit(shard_map(body, mesh=_A2A_MESH, in_specs=(spec,),
                           out_specs=spec, check_vma=False))
    return np.asarray(fn(x).astype(jnp.float32))


def _a2a_reference(g, split_axis, concat_axis):
    """Rank j receives split j of every source rank, ordered by source."""
    n = g.shape[0]
    return np.stack([
        np.concatenate([np.split(g[i], n, axis=split_axis)[j]
                        for i in range(n)], axis=concat_axis)
        for j in range(n)])


@A2A_SETTINGS
@given(tiles=st.sampled_from([1, 2]), rows=st.sampled_from([0, 1, 3]),
       d=st.sampled_from([1, 4]), dtype=st.sampled_from(_A2A_DTYPES),
       concat=st.sampled_from([0, 1]), seed=st.integers(0, 2**31 - 1))
def test_a2a_schedule_equivalence_randomized(tiles, rows, d, dtype, concat,
                                             seed):
    """Every registered all_to_all_tiles schedule moves identical bytes for
    random shapes (including 0-row payloads) and dtypes — small-integer
    values, so every dtype carries them exactly."""
    rng = np.random.default_rng(seed)
    g = rng.integers(-8, 8, (_NDEV, _NDEV * tiles, rows, d))
    x = jnp.asarray(g).astype(dtype)
    want = _a2a_reference(np.asarray(g, np.float32), 0, concat)
    for schedule in sorted(schedules_for("all_to_all_tiles")):
        got = _a2a_run(schedule, x, split_axis=0, concat_axis=concat)
        np.testing.assert_array_equal(got.reshape(want.shape), want,
                                      err_msg=f"{schedule}/{dtype}")


@A2A_SETTINGS
@given(nchunks=st.sampled_from([1, 2, 3, 7, 64, "auto"]),
       rows=st.sampled_from([0, 1, 5, 8]),
       dtype=st.sampled_from(_A2A_DTYPES),
       schedule=st.sampled_from(sorted(schedules_for("all_to_all_tiles"))),
       seed=st.integers(0, 2**31 - 1))
def test_pipelined_a2a_matches_monolithic_randomized(nchunks, rows, dtype,
                                                     schedule, seed):
    """engine.pipelined('all_to_all_tiles', ...) is bit-identical to the
    monolithic exchange for every chunk count (non-divisible strip counts,
    nchunks > rows clamped to one row per strip, 0-row strip axes) — chunk
    boundaries only partition the payload along an axis the exchange leaves
    alone."""
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.integers(-8, 8, (_NDEV, _NDEV * 2, rows, 3))
                    ).astype(dtype)
    eng = CollectiveEngine.for_mesh(_A2A_MESH, schedule=schedule)
    spec = P("x", None, None, None)

    def run(pipelined):
        def body(v):
            loc = v[0]
            if pipelined:
                out = eng.pipelined("all_to_all_tiles", loc, "x",
                                    nchunks=nchunks, split_axis=1,
                                    tile_split_axis=0, tile_concat_axis=0)
            else:
                out = eng.all_to_all_tiles(loc, "x", split_axis=0,
                                           concat_axis=0)
            return out[None]
        fn = jax.jit(shard_map(body, mesh=_A2A_MESH, in_specs=(spec,),
                               out_specs=spec, check_vma=False))
        return np.asarray(fn(g).astype(jnp.float32))

    np.testing.assert_array_equal(run(True), run(False),
                                  err_msg=f"{schedule}/{dtype}/{nchunks}")


# (pipelined-a2a argument validation lives in
# tests/test_engine.py::test_pipelined_rejects_unsupported_ops)


# --- link-health masks: no resolution crosses a down link --------------------
#
# For every (op, topology kind, break position): with one link marked
# hard-down, the cost model's winner must have a *known* route whose link
# set excludes the cut and a finite cost — i.e. schedule resolution
# provably never routes through a dead wire, whichever hop died.


def _break_cases():
    cases = []
    for n in (4, 8):
        cases.append(("ring", (AxisTopology("x", n, "ring"),)))
        cases.append(("torus", (AxisTopology("rows", n, "ring"),
                                AxisTopology("cols", n, "ring"))))
    return cases


_BREAK_CASES = _break_cases()


@SETTINGS
@given(case=st.sampled_from(range(len(_BREAK_CASES))),
       op=st.sampled_from(["bcast", "allreduce"]),
       hop_seed=st.integers(0, 63),
       nbytes=st.sampled_from([256, 16384, 1 << 20]))
def test_no_resolution_crosses_a_down_link(case, op, hop_seed, nbytes):
    import math
    kind, axes = _BREAK_CASES[case]
    ax = axes[hop_seed % len(axes)]          # which axis breaks
    hop = (hop_seed // len(axes)) % ax.size  # where on it
    health = frozenset({(ax.name, hop)})
    model = CostModel(hw=TPU_V5E, table=None, health=health)
    winner = model.choose(op, nbytes, axes)
    assert winner in schedules_for(op), (kind, winner)
    route = route_links(op, winner, axes, health=health)
    assert route is not None, \
        f"{kind}: winner {winner!r} has no priceable route"
    assert not (route & health), \
        f"{kind}: {op}/{winner} routes through down link {(ax.name, hop)}"
    assert math.isfinite(model.cost(op, winner, nbytes, axes)), \
        f"{kind}: winner {winner!r} priced infinite yet chosen"


@SETTINGS
@given(hop=st.integers(0, 7), nbytes=st.sampled_from([256, 16384]))
def test_down_link_never_prices_crossing_schedule_finite(hop, nbytes):
    """The converse: any schedule whose route intersects the cut (or is
    unknown under a health mask) must be priced infinite."""
    import math
    axes = (AxisTopology("x", 8, "ring"),)
    health = frozenset({("x", hop)})
    model = CostModel(hw=TPU_V5E, table=None, health=health)
    for op in ("bcast", "allreduce"):
        for name in schedules_for(op):
            route = route_links(op, name, axes, health=health)
            cost = model.cost(op, name, nbytes, axes)
            if route is None or route & health:
                assert not math.isfinite(cost), (op, name, hop)


@SETTINGS
@given(hop=st.integers(0, 1), op=st.sampled_from(["bcast", "allreduce"]),
       nbytes=st.sampled_from([256, 16384]))
def test_size2_ring_down_wire_excludes_both_hop_ids(hop, op, nbytes):
    """A size-2 ring has ONE physical wire; hops 0 and 1 are two names for
    it. Downing either hop id must price every ICI schedule that touches
    the axis infinite (the rooted chain cannot route around the only wire),
    so resolution falls through to the link-free ``staged``."""
    import math
    axes = (AxisTopology("x", 2, "ring"),)
    assert axes[0].links() == (("x", 0),)       # dedupe: one link reported
    assert axes[0].canonical_hop(hop) == 0
    health = frozenset({("x", hop)})
    model = CostModel(hw=TPU_V5E, table=None, health=health)
    for name in schedules_for(op):
        route = route_links(op, name, axes, health=health)
        if route:  # any route touching the axis touches the one wire
            assert route == frozenset({("x", 0)}), (name, route)
            assert not math.isfinite(model.cost(op, name, nbytes, axes)), \
                f"{op}/{name} priced finite across the downed size-2 wire"
    assert model.choose(op, nbytes, axes) == "staged"


# --- HLO shape parser --------------------------------------------------------


@SETTINGS
@given(dims=st.lists(st.integers(1, 64), min_size=0, max_size=4),
       dt=st.sampled_from(["f32", "bf16", "s8", "u32", "f64"]))
def test_shape_bytes_product(dims, dt):
    sizes = {"f32": 4, "bf16": 2, "s8": 1, "u32": 4, "f64": 8}
    text = f"{dt}[{','.join(map(str, dims))}]"
    want = sizes[dt] * int(np.prod(dims)) if dims else sizes[dt]
    assert shape_bytes(text) == want
