"""Overlap-subsystem equivalence suite on the simulated 8-device mesh.

Acceptance properties (ISSUE 2 + ISSUE 4):
* depth-d lookahead HPL (d in {1, 2, 3}) is *bit-identical* to eager HPL
  under every registered bcast schedule, including the nb == pg edge (the
  overlap restructuring must not change a single ulp);
* chunked (pipelined) grid_transpose is bit-identical to the monolithic
  exchange under every registered schedule, including nchunks > strips;
* ``CollectiveEngine.allreduce_tree`` matches leaf-wise ``lax.psum`` for
  every allreduce schedule and odd bucket boundaries (inputs are small
  integers in f32/int32 so every summation order is exact; the ``int8_ef``
  schedule gets inputs its block quantizer represents exactly).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.comm.engine import CollectiveEngine, schedules_for
from repro.launch.mesh import make_mesh

NDEV = 8
pytestmark = pytest.mark.skipif(
    len(jax.devices()) < NDEV, reason=f"needs {NDEV} devices")

BCAST_SCHEDULES = sorted(schedules_for("bcast"))
ALLREDUCE_EXACT = sorted(s for s in schedules_for("allreduce")
                         if s != "int8_ef")


@pytest.fixture(scope="module")
def ring():
    return make_mesh((NDEV,), ("x",))


@pytest.fixture(scope="module")
def torus():
    return make_mesh((2, 2), ("rows", "cols"))


# ---------------------------------------------------------------------------
# lookahead HPL == eager HPL, bitwise
# ---------------------------------------------------------------------------


def _int_system(n, seed=3):
    rng = np.random.default_rng(seed)
    a = rng.integers(-8, 8, (n, n)).astype(np.float32)
    a[np.arange(n), np.arange(n)] += n  # diagonally dominant (HPL-AI rule)
    return a


@pytest.mark.parametrize("schedule", BCAST_SCHEDULES)
def test_hpl_lookahead_bit_identical(torus, schedule):
    """Depth-d lookahead (d in {1, 2, 3}) == eager, bitwise, per schedule."""
    from repro.core.hpl import make_factorize
    from repro.core.ptrans import distribute_cyclic
    n, b, pg = 128, 32, 2
    a = _int_system(n)
    spec = NamedSharding(torus, P(("rows", "cols"), None, None))
    a_sh = jax.device_put(distribute_cyclic(a, pg, b), spec)
    eager = np.asarray(
        make_factorize(torus, pg=pg, nb=n // b, b=b, schedule=schedule)(a_sh))
    for depth in (1, 2, 3):
        look = make_factorize(torus, pg=pg, nb=n // b, b=b,
                              schedule=schedule, lookahead=depth)
        np.testing.assert_array_equal(np.asarray(look(a_sh)), eager,
                                      strict=True,
                                      err_msg=f"{schedule}/d={depth}")


@pytest.mark.parametrize("depth", [True, 2, 3])
def test_hpl_lookahead_single_block_column(torus, depth):
    """nb == pg edge: the lookahead carry wraps with only one local block
    (depth > nb clamps to nb panel sets in flight)."""
    from repro.core.hpl import make_factorize
    from repro.core.ptrans import distribute_cyclic
    n, b, pg = 64, 32, 2
    a = _int_system(n, seed=11)
    spec = NamedSharding(torus, P(("rows", "cols"), None, None))
    a_sh = jax.device_put(distribute_cyclic(a, pg, b), spec)
    eager = make_factorize(torus, pg=pg, nb=n // b, b=b, schedule="chain")
    look = make_factorize(torus, pg=pg, nb=n // b, b=b, schedule="chain",
                          lookahead=depth)
    np.testing.assert_array_equal(np.asarray(look(a_sh)),
                                  np.asarray(eager(a_sh)))


def test_run_hpl_lookahead_converges(torus):
    from repro.comm.types import CommunicationType as CT
    from repro.core.hpl import run_hpl
    res = run_hpl(torus, CT.ICI_DIRECT, n=128, b=32, schedule="ring2d",
                  reps=1, lookahead=True)
    assert res.error < 1.0
    assert res.details["lookahead"] is True
    assert res.details["lookahead_depth"] == 1
    # both bcast payloads carry resolved provenance, never the literal auto
    assert res.details["schedule_block"] == "ring2d"
    assert res.details["schedule_panel"] == "ring2d"


def test_run_hpl_auto_depth_and_schedule(torus):
    """schedule="auto" + lookahead="auto": the cost model resolves both the
    per-callsite bcast schedules and the pipeline depth, and the run still
    converges."""
    from repro.comm.engine import schedules_for
    from repro.comm.types import CommunicationType as CT
    from repro.core.hpl import run_hpl
    res = run_hpl(torus, CT.ICI_DIRECT, n=128, b=32, schedule="auto",
                  reps=1, lookahead="auto")
    assert res.error < 1.0
    assert 1 <= res.details["lookahead_depth"] <= 3
    for key in ("schedule", "schedule_block", "schedule_panel"):
        assert res.details[key] in schedules_for("bcast"), key


# ---------------------------------------------------------------------------
# chunked (pipelined) grid_transpose == monolithic, bitwise
# ---------------------------------------------------------------------------


GRID_SCHEDULES = sorted(schedules_for("grid_transpose"))


@pytest.mark.parametrize("schedule", GRID_SCHEDULES)
def test_pipelined_grid_transpose_bit_identical(torus, schedule):
    """Strip-chunked exchange == monolithic for every chunk count,
    including nchunks > strips (clamped to one row per strip)."""
    from jax import lax
    x = np.random.default_rng(9).integers(-8, 8, (4, 16, 16)) \
        .astype(np.float32)
    spec = P(("rows", "cols"), None, None)
    eng = CollectiveEngine.for_mesh(torus, schedule=schedule)

    def run(nchunks):
        def body(v):
            if nchunks is None:
                return eng.grid_transpose(v[0], ("rows", "cols"), 2)[None]
            return eng.pipelined("grid_transpose", v[0], ("rows", "cols"),
                                 pg=2, nchunks=nchunks)[None]
        fn = jax.jit(shard_map(body, mesh=torus, in_specs=(spec,),
                               out_specs=spec, check_vma=False))
        return np.asarray(fn(jnp.asarray(x)))

    mono = run(None)
    for nchunks in (1, 2, 4, 7, 64):  # 64 > the 16 strips available
        np.testing.assert_array_equal(run(nchunks), mono,
                                      err_msg=f"{schedule}/S={nchunks}")

    # with a consume hook the pipeline reproduces the strip-wise PTRANS
    bm = np.random.default_rng(10).integers(-8, 8, (4, 16, 16)) \
        .astype(np.float32)

    def body_pipe(va, vb):
        b_loc = vb[0]

        def consume(strip, start):
            return strip.T + lax.slice_in_dim(b_loc, start,
                                              start + strip.shape[0], axis=1)
        out = eng.pipelined("grid_transpose", va[0], ("rows", "cols"), pg=2,
                            nchunks=4, concat_axis=1, consume=consume)
        return out[None]

    fn = jax.jit(shard_map(body_pipe, mesh=torus, in_specs=(spec, spec),
                           out_specs=spec, check_vma=False))
    got = np.asarray(fn(jnp.asarray(x), jnp.asarray(bm)))
    want = np.stack([bm[i] + mono[i].T for i in range(4)])
    np.testing.assert_array_equal(got, want)


def test_run_ptrans_pipelined_matches_monolithic(torus):
    """run_ptrans with any chunk count (incl. auto) produces the exact
    transpose and records the resolved (schedule, nchunks)."""
    from repro.comm.engine import schedules_for as _sf
    from repro.core.ptrans import run_ptrans
    for nchunks in (1, 2, "auto"):
        res = run_ptrans(torus, n=128, b=32, reps=1, nchunks=nchunks)
        assert res.error == 0.0, nchunks
        assert res.details["schedule"] in _sf("grid_transpose")
        assert res.details["nchunks"] >= 1
        assert res.details["nchunks_requested"] == nchunks


# ---------------------------------------------------------------------------
# allreduce_tree == leaf-wise psum
# ---------------------------------------------------------------------------


def _grad_tree(seed=0):
    """Odd-shaped pytree: mixed dtypes, a 0-byte leaf, a scalar-ish leaf,
    and one giant leaf dwarfing the bucket size."""
    rng = np.random.default_rng(seed)
    return {
        "w": rng.integers(-8, 8, (NDEV, 7, 33)).astype(np.float32),
        "giant": rng.integers(-8, 8, (NDEV, 4096)).astype(np.float32),
        "bias": rng.integers(-8, 8, (NDEV, 5)).astype(np.float32),
        "ints": rng.integers(-8, 8, (NDEV, 11)).astype(np.int32),
        "empty": np.zeros((NDEV, 0), np.float32),
        "one": rng.integers(-8, 8, (NDEV, 1)).astype(np.float32),
    }


def _reduce_tree(mesh, eng, tree, bucket_bytes):
    def body(t):
        loc = jax.tree.map(lambda v: v[0], t)
        out = eng.allreduce_tree(loc, "x", bucket_bytes=bucket_bytes)
        return jax.tree.map(lambda v: v[None], out)

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("x"),),
                           out_specs=P("x"), check_vma=False))
    return fn(jax.tree.map(jnp.asarray, tree))


@pytest.mark.parametrize("schedule", ALLREDUCE_EXACT)
@pytest.mark.parametrize("bucket_bytes", [1, 64, 1 << 30])
def test_allreduce_tree_matches_leafwise_psum(ring, schedule, bucket_bytes):
    tree = _grad_tree()
    eng = CollectiveEngine.for_mesh(ring, schedule=schedule)
    out = _reduce_tree(ring, eng, tree, bucket_bytes)
    for key, x in tree.items():
        want = np.broadcast_to(x.sum(0, dtype=x.dtype), out[key].shape)
        np.testing.assert_array_equal(np.asarray(out[key]), want,
                                      err_msg=f"{schedule}/{bucket_bytes}/"
                                              f"{key}")


def test_allreduce_tree_int8_ef_exact_on_representable_inputs(ring):
    # int8_ef quantizes per ring chunk on every hop (the wire payload is
    # int8 + per-block scales hop by hop, never a whole fp32 bucket), so
    # "representable" means every hop's chunk must round-trip the block
    # quantizer exactly. Identical integer rows with a 127-max in every
    # 256-elem block of every 512-elem ring chunk give that: the partial
    # sum after k hops is k*v with block max k*127, so the scale is exactly
    # k and round((k*v)/k) == v on every requantization.
    rng = np.random.default_rng(1)
    row = rng.integers(-100, 100, (NDEV * 512,)).astype(np.float32)
    row[::256] = 127
    x = np.broadcast_to(row, (NDEV, NDEV * 512)).copy()
    tree = {"g": x}
    eng = CollectiveEngine.for_mesh(ring, schedule="int8_ef")
    out = _reduce_tree(ring, eng, tree, 1 << 30)
    np.testing.assert_array_equal(
        np.asarray(out["g"]), np.broadcast_to(x.sum(0), out["g"].shape))


def test_allreduce_int8_ef_close_on_general_inputs(ring):
    # per-hop requantization of partial sums is lossy in general, but the
    # residual chunk carried alongside the payload means each hop leaks only
    # the residual's own requantization — O(1/127^2) of the chunk magnitude
    # per hop, vs O(1/127) for the residual-free wire (ROADMAP in-ring
    # error-feedback item). Assert the tightened bound.
    rng = np.random.default_rng(6)
    x = rng.integers(-100, 100, (NDEV, 4096)).astype(np.float32)
    eng = CollectiveEngine.for_mesh(ring, schedule="int8_ef")
    out = _reduce_tree(ring, eng, {"g": x}, 1 << 30)
    want = np.broadcast_to(x.sum(0), out["g"].shape)
    err = np.max(np.abs(np.asarray(out["g"]) - want))
    assert err <= 2.0 / 127.0 ** 2 * NDEV * np.max(np.abs(x)), err


def test_bucketed_psum_tree_legacy_wrapper(ring):
    """The deprecated shim must warn exactly once at trace time and reduce
    to the same values as the engine op it forwards to."""
    import pytest

    from repro.comm.overlap import bucketed_psum_tree
    tree = _grad_tree(seed=2)
    eng = CollectiveEngine.for_mesh(ring, schedule="native")

    def run(reduce_fn):
        def body(t):
            loc = jax.tree.map(lambda v: v[0], t)
            out = reduce_fn(loc)
            return jax.tree.map(lambda v: v[None], out)

        fn = jax.jit(shard_map(body, mesh=ring, in_specs=(P("x"),),
                               out_specs=P("x"), check_vma=False))
        return fn(jax.tree.map(jnp.asarray, tree))

    want = run(lambda loc: eng.allreduce_tree(loc, "x", bucket_bytes=256))
    with pytest.warns(DeprecationWarning, match="allreduce_tree") as rec:
        out = run(lambda loc: bucketed_psum_tree(loc, "x", bucket_bytes=256))
    assert sum(issubclass(w.category, DeprecationWarning)
               and "bucketed_psum_tree" in str(w.message) for w in rec) == 1
    for key, x in tree.items():
        np.testing.assert_array_equal(
            np.asarray(out[key]),
            np.broadcast_to(x.sum(0, dtype=x.dtype), out[key].shape))
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(want[key]))


def test_compressed_psum_engine_routing(ring):
    """Error-feedback compression composed with the rs_ag ring reduces to
    the same values as its lax.psum transport on exactly-representable
    inputs, and carries identical error state."""
    from repro.comm.compression import compressed_psum
    rng = np.random.default_rng(4)
    x = rng.integers(-100, 100, (NDEV, 512)).astype(np.float32)
    x[:, 0] = 127
    x[:, 256] = 127
    eng = CollectiveEngine.for_mesh(ring, schedule="rs_ag")

    def body(v, use_engine):
        err = jnp.zeros_like(v[0])
        red, ne = compressed_psum(v[0], "x", err,
                                  engine=eng if use_engine else None)
        return red[None], ne[None]

    spec = P("x", None)
    outs = {}
    for use_engine in (False, True):
        fn = jax.jit(shard_map(lambda v, u=use_engine: body(v, u), mesh=ring,
                               in_specs=(spec,), out_specs=(spec, spec),
                               check_vma=False))
        outs[use_engine] = [np.asarray(o) for o in fn(jnp.asarray(x))]
    np.testing.assert_array_equal(outs[True][0], outs[False][0])
    np.testing.assert_array_equal(outs[True][1], outs[False][1])
    np.testing.assert_array_equal(outs[True][0],
                                  np.broadcast_to(x.sum(0), (NDEV, 512)))


def test_dp_train_step_explicit_compressed_engine(ring):
    """The int8_ef error-feedback DP step runs end-to-end through the engine
    transport and produces a finite loss."""
    from repro.configs import RunConfig, get_config, reduced
    from repro.models.model import build_model
    from repro.train.step import init_train_state, make_dp_train_step_explicit
    cfg = reduced(get_config("llama3.2-3b"), layers=1, d_model=32)
    model = build_model(cfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (NDEV, 16)), jnp.int32)}
    run = RunConfig(learning_rate=1e-3, warmup_steps=1,
                    grad_compression="int8_ef")
    state = init_train_state(model, jax.random.key(0), compression_on=True)
    step = make_dp_train_step_explicit(model, run, ring,
                                       schedule_kind="rs_ag")
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    err_norm = sum(float(jnp.sum(jnp.abs(e)))
                   for e in jax.tree.leaves(new_state.error))
    assert np.isfinite(err_norm)
