"""Autotuning equivalence suite on the simulated 8-device mesh.

Acceptance properties (ISSUE 3):
* ``schedule="auto"`` is *bit-equivalent* to every fixed exact schedule for
  bcast, allreduce, and grid_transpose — the cost model only ever changes
  which wire route runs, never the numbers (inputs are small integers in
  float32, so every summation order is exact);
* the measured mode microbenchmarks the live mesh and its tuning table
  round-trips through save -> load -> identical picks;
* the explicit DP train step runs end-to-end with ``schedule_kind="auto"``
  and the derived bucket size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.comm.autotune import CostModel, TuningTable, autotune_mesh
from repro.comm.engine import CollectiveEngine, schedules_for
from repro.launch.mesh import make_mesh

NDEV = 8
pytestmark = pytest.mark.skipif(
    len(jax.devices()) < NDEV, reason=f"needs {NDEV} devices")

ALLREDUCE_EXACT = sorted(s for s in schedules_for("allreduce")
                         if s != "int8_ef")


@pytest.fixture(scope="module")
def ring():
    return make_mesh((NDEV,), ("x",))


@pytest.fixture(scope="module")
def torus():
    return make_mesh((2, 2), ("rows", "cols"))


def _ints(shape, seed=0):
    return np.random.default_rng(seed).integers(-8, 8, shape).astype(np.float32)


def _auto_engine(mesh):
    # analytic model: the committed tuning table must not decide which
    # fixed schedule auto agrees with — any exact pick must be bit-equal
    return CollectiveEngine.for_mesh(mesh, schedule="auto",
                                     cost_model=CostModel(table=None))


# ---------------------------------------------------------------------------
# auto == every fixed exact schedule, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("elems", [128, 1 << 16])  # latency + bandwidth regimes
def test_auto_allreduce_bit_equal_to_fixed(ring, elems):
    x = _ints((NDEV, elems), seed=1)
    spec = P("x", None)

    def run(eng):
        fn = jax.jit(shard_map(lambda v: eng.allreduce(v[0], "x")[None],
                               mesh=ring, in_specs=(spec,), out_specs=spec,
                               check_vma=False))
        return np.asarray(fn(jnp.asarray(x)))

    auto = run(_auto_engine(ring))
    np.testing.assert_array_equal(
        auto, np.broadcast_to(x.sum(0), auto.shape))
    for schedule in ALLREDUCE_EXACT:
        fixed = run(CollectiveEngine.for_mesh(ring, schedule=schedule))
        np.testing.assert_array_equal(auto, fixed, err_msg=schedule)


@pytest.mark.parametrize("elems", [96, 1 << 16])
def test_auto_bcast_bit_equal_to_fixed(ring, elems):
    x = _ints((NDEV, elems), seed=2)
    spec = P("x", None)

    def run(eng):
        fn = jax.jit(shard_map(lambda v: eng.bcast(v[0], "x", 3)[None],
                               mesh=ring, in_specs=(spec,), out_specs=spec,
                               check_vma=False))
        return np.asarray(fn(jnp.asarray(x)))

    auto = run(_auto_engine(ring))
    np.testing.assert_array_equal(auto, np.broadcast_to(x[3], auto.shape))
    for schedule in sorted(schedules_for("bcast")):
        fixed = run(CollectiveEngine.for_mesh(ring, schedule=schedule))
        np.testing.assert_array_equal(auto, fixed, err_msg=schedule)


def test_int8_ef_in_ring_error_feedback_bound(ring):
    """Lossy-bound pin for the residual-carrying int8_ef wire: with the
    per-hop requantization residual travelling alongside the payload, the
    end-to-end error is O(hops/127^2) of the input magnitude — ~1/127 of
    the residual-free wire's O(hops/127) bound — and the cost model prices
    the doubled int8 payload accordingly (INT8_WIRE_RATIO ~ 0.5)."""
    from repro.comm.autotune import INT8_WIRE_RATIO
    assert 0.5 <= INT8_WIRE_RATIO < 0.52  # 2 x (1/4 + 1/256) of f32 bytes
    rng = np.random.default_rng(12)
    x = rng.uniform(-50.0, 50.0, (NDEV, 2048)).astype(np.float32)
    eng = CollectiveEngine.for_mesh(ring, schedule="int8_ef")
    spec = P("x", None)
    fn = jax.jit(shard_map(lambda v: eng.allreduce(v[0], "x")[None],
                           mesh=ring, in_specs=(spec,), out_specs=spec,
                           check_vma=False))
    out = np.asarray(fn(jnp.asarray(x)))
    err = np.max(np.abs(out - x.sum(0, dtype=np.float64)))
    assert err <= 2.0 / 127.0 ** 2 * NDEV * np.max(np.abs(x)), err


def test_auto_pipelined_grid_transpose_bit_equal(torus):
    """engine.pipelined with nchunks="auto" (cost-model chunk count, per-
    callsite tag) == the monolithic exchange, bitwise."""
    x = _ints((4, 16, 16), seed=7)
    spec = P(("rows", "cols"), None, None)
    eng = _auto_engine(torus)

    def run(pipelined):
        def body(v):
            if pipelined:
                return eng.pipelined("grid_transpose", v[0],
                                     ("rows", "cols"), pg=2, nchunks="auto",
                                     callsite="ptrans.exchange")[None]
            return eng.grid_transpose(v[0], ("rows", "cols"), 2)[None]
        fn = jax.jit(shard_map(body, mesh=torus, in_specs=(spec,),
                               out_specs=spec, check_vma=False))
        return np.asarray(fn(jnp.asarray(x)))

    np.testing.assert_array_equal(run(True), run(False))


def test_auto_grid_transpose_bit_equal_to_fixed(torus):
    x = _ints((4, 16, 16), seed=3)
    spec = P(("rows", "cols"), None, None)

    def run(eng):
        fn = jax.jit(shard_map(
            lambda v: eng.grid_transpose(v[0], ("rows", "cols"), 2)[None],
            mesh=torus, in_specs=(spec,), out_specs=spec, check_vma=False))
        return np.asarray(fn(jnp.asarray(x)))

    auto = run(_auto_engine(torus))
    want = x.reshape(2, 2, 16, 16).transpose(1, 0, 2, 3).reshape(4, 16, 16)
    np.testing.assert_array_equal(auto, want)
    for schedule in sorted(schedules_for("grid_transpose")):
        fixed = run(CollectiveEngine.for_mesh(torus, schedule=schedule))
        np.testing.assert_array_equal(auto, fixed, err_msg=schedule)


def test_auto_allreduce_tree_with_derived_bucket(ring):
    """bucket_bytes=None: the engine derives the size from the topology and
    the reduction still matches leaf-wise sums exactly."""
    rng = np.random.default_rng(5)
    tree = {"w": rng.integers(-8, 8, (NDEV, 7, 33)).astype(np.float32),
            "b": rng.integers(-8, 8, (NDEV, 5)).astype(np.float32)}
    eng = _auto_engine(ring)
    assert eng.bucket_bytes_for("x") == 4 << 20  # v5e ring-of-8 derivation

    def body(t):
        loc = jax.tree.map(lambda v: v[0], t)
        out = eng.allreduce_tree(loc, "x")  # derived bucket size
        return jax.tree.map(lambda v: v[None], out)

    fn = jax.jit(shard_map(body, mesh=ring, in_specs=(P("x"),),
                           out_specs=P("x"), check_vma=False))
    out = fn(jax.tree.map(jnp.asarray, tree))
    for k, x in tree.items():
        np.testing.assert_array_equal(
            np.asarray(out[k]), np.broadcast_to(x.sum(0), out[k].shape),
            err_msg=k)


# ---------------------------------------------------------------------------
# measured mode on the live mesh
# ---------------------------------------------------------------------------


def test_measured_callsite_entry_round_trip(tmp_path):
    """The paired-bcast callsite pattern measures under its tagged key and
    a model with that table resolves the matching callsite through it,
    while untagged lookups fall back to the analytic ranking."""
    from repro.comm.topology import AxisTopology
    table, record = autotune_mesh(ops=("bcast@hpl.panel",),
                                  sizes=(1024,), reps=1, verbose=False)
    sig = "torus_row[2]"
    assert sig in table.entries.get("bcast@hpl.panel", {})
    rows = table.entries["bcast@hpl.panel"][sig]
    for _, name in rows:
        assert name in schedules_for("bcast")
    assert record
    # the HPL pattern is row/column-symmetric: the winner must also land
    # under the column-axis signature so the l_panel bcast matches it
    assert table.entries["bcast@hpl.panel"].get("torus_col[2]") == rows

    loaded = TuningTable.load(table.save(tmp_path / "tuning.json"))
    axes = (AxisTopology("rows", 2, "torus_row"),)
    m = CostModel(table=loaded)
    assert m.choose("bcast", 1024, axes, callsite="hpl.panel") == rows[0][1]
    col_axes = (AxisTopology("cols", 2, "torus_col"),)
    assert m.choose("bcast", 1024, col_axes, callsite="hpl.panel") \
        == rows[0][1]
    # no callsite -> no tagged entry consulted -> analytic pick
    assert m.choose("bcast", 1024, axes) \
        == CostModel(table=None).choose("bcast", 1024, axes)


def test_measured_moe_callsite_entry_round_trip(tmp_path):
    """The paired MoE dispatch+combine pattern measures on the ring under
    its tagged key — and, because the pattern is direction-symmetric, the
    winner also lands under the @moe.combine alias. A model with that table
    resolves both callsites through it; untagged lookups fall back to the
    analytic ranking."""
    from repro.comm.topology import AxisTopology
    table, record = autotune_mesh(ops=("all_to_all_tiles@moe.dispatch",),
                                  sizes=(1024,), reps=1, verbose=False)
    sig = f"ring[{NDEV}]"
    assert sig in table.entries.get("all_to_all_tiles@moe.dispatch", {})
    rows = table.entries["all_to_all_tiles@moe.dispatch"][sig]
    for _, name in rows:
        assert name in schedules_for("all_to_all_tiles")
    assert record
    # the combine alias carries the same measured bands
    assert table.entries["all_to_all_tiles@moe.combine"][sig] == rows

    loaded = TuningTable.load(table.save(tmp_path / "tuning.json"))
    axes = (AxisTopology("x", NDEV, "ring"),)
    m = CostModel(table=loaded)
    for cs in ("moe.dispatch", "moe.combine"):
        assert m.choose("all_to_all_tiles", 1024, axes,
                        callsite=cs) == rows[0][1]
    # no callsite -> no tagged entry consulted -> analytic pick
    assert m.choose("all_to_all_tiles", 1024, axes) \
        == CostModel(table=None).choose("all_to_all_tiles", 1024, axes)


def test_measured_decode_callsite_entry_round_trip(tmp_path):
    """The serving burst pattern measures under @decode.qkv on its own
    decode-sized ladder (not the training ladder), and the winner lands
    under the @decode.out / @decode.moe aliases; a model with that table
    resolves all three callsites through it."""
    from repro.comm.autotune import DECODE_SIZES_QUICK
    from repro.comm.topology import AxisTopology
    table, record = autotune_mesh(ops=("all_to_all_tiles@decode.qkv",),
                                  quick=True, verbose=False)
    sig = f"ring[{NDEV}]"
    assert sig in table.entries.get("all_to_all_tiles@decode.qkv", {})
    rows = table.entries["all_to_all_tiles@decode.qkv"][sig]
    for _, name in rows:
        assert name in schedules_for("all_to_all_tiles")
    # measured at the decode ladder sizes, not the default training sizes
    assert {int(k.rsplit("/", 1)[1]) for k in record} \
        == set(DECODE_SIZES_QUICK)
    for alias in ("all_to_all_tiles@decode.out",
                  "all_to_all_tiles@decode.moe"):
        assert table.entries[alias][sig] == rows

    loaded = TuningTable.load(table.save(tmp_path / "tuning.json"))
    axes = (AxisTopology("x", NDEV, "ring"),)
    m = CostModel(table=loaded)
    want = m.choose("all_to_all_tiles", 1024, axes, callsite="decode.qkv")
    assert want in schedules_for("all_to_all_tiles")
    for cs in ("decode.out", "decode.moe"):
        assert m.choose("all_to_all_tiles", 1024, axes, callsite=cs) == want
    # no callsite -> no tagged entry consulted -> analytic pick
    assert m.choose("all_to_all_tiles", 1024, axes) \
        == CostModel(table=None).choose("all_to_all_tiles", 1024, axes)


def test_dp_grads_callsite_threads_through_allreduce_tree(ring):
    """allreduce_tree(callsite="dp.grads") consults the tagged table entry
    for its buckets — forcing a distinguishable schedule via the tag changes
    nothing numerically (exact integer payloads) but resolves through it."""
    from repro.comm.autotune import axis_signature
    from repro.comm.topology import AxisTopology, MeshTopology
    axes = (AxisTopology("x", NDEV, "ring"),)
    t = TuningTable()
    t.set("allreduce@dp.grads", axis_signature(axes), [(None, "chain")])
    eng = CollectiveEngine(schedule="auto",
                           topology=MeshTopology.from_mesh(ring),
                           cost_model=CostModel(table=t))
    assert eng.schedule_for("allreduce", nbytes=1 << 20, axis="x",
                            callsite="dp.grads") == "chain"
    assert eng.schedule_for("allreduce", nbytes=1 << 20, axis="x") \
        == CostModel(table=None).choose("allreduce", 1 << 20, axes)

    tree = {"w": np.arange(NDEV * 6, dtype=np.float32).reshape(NDEV, 6),
            "b": np.ones((NDEV, 3), np.float32)}

    def body(tr):
        loc = jax.tree.map(lambda v: v[0], tr)
        out = eng.allreduce_tree(loc, "x", callsite="dp.grads")
        return jax.tree.map(lambda v: v[None], out)

    fn = jax.jit(shard_map(body, mesh=ring, in_specs=(P("x"),),
                           out_specs=P("x"), check_vma=False))
    out = fn(jax.tree.map(jnp.asarray, tree))
    for k, x in tree.items():
        np.testing.assert_array_equal(
            np.asarray(out[k]), np.broadcast_to(x.sum(0), out[k].shape),
            err_msg=k)


def test_measured_autotune_round_trip(tmp_path):
    table, record = autotune_mesh(ops=("allreduce",), sizes=(1024, 1 << 16),
                                  reps=1, verbose=False)
    sig = "ring[8]"
    assert sig in table.entries.get("allreduce", {})
    for _, name in table.entries["allreduce"][sig]:
        assert name in schedules_for("allreduce")
    assert record  # raw timings captured for the bench artifact

    loaded = TuningTable.load(table.save(tmp_path / "tuning.json"))
    m_live, m_disk = CostModel(table=table), CostModel(table=loaded)
    from repro.comm.topology import AxisTopology
    axes = (AxisTopology("x", NDEV, "ring"),)
    for size in (512, 1024, 1 << 16, 1 << 24):
        assert m_live.choose("allreduce", size, axes) \
            == m_disk.choose("allreduce", size, axes)


# ---------------------------------------------------------------------------
# end-to-end: explicit DP train step under auto
# ---------------------------------------------------------------------------


def test_dp_train_step_auto_schedule(ring):
    """schedule_kind="auto" + derived bucket size runs end-to-end and lands
    on the same loss as the fixed native reduction."""
    from repro.configs import RunConfig, get_config, reduced
    from repro.models.model import build_model
    from repro.train.step import init_train_state, make_dp_train_step_explicit
    cfg = reduced(get_config("llama3.2-3b"), layers=1, d_model=32)
    model = build_model(cfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (NDEV, 16)), jnp.int32)}
    losses = {}
    for kind in ("auto", "native"):
        run = RunConfig(learning_rate=1e-3, warmup_steps=1)
        state = init_train_state(model, jax.random.key(0))
        step = make_dp_train_step_explicit(model, run, ring,
                                           schedule_kind=kind)
        _, metrics = step(state, batch)
        losses[kind] = float(metrics["loss"])
        assert np.isfinite(losses[kind]), kind
    np.testing.assert_allclose(losses["auto"], losses["native"], rtol=1e-5)
