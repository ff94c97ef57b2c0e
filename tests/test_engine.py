"""Collective-engine registry and resolution tests (single device).

Multi-device schedule *equivalence* runs in tests/dist/test_schedules.py on
the simulated 8-device mesh (launched by tests/test_dist_wrapper.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import pytest

from repro.comm.engine import (OPS, CollectiveEngine, UnknownScheduleError,
                               known_schedules, register_schedule,
                               schedules_for)
from repro.comm.topology import AxisTopology, MeshTopology
from repro.comm.types import CommunicationType as CT
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P


def test_registry_has_core_schedules():
    assert {"chain", "native", "staged", "ring2d"} <= set(schedules_for("bcast"))
    assert {"chain", "native", "staged", "rs_ag", "ring2d", "int8_ef"} <= set(
        schedules_for("allreduce"))
    assert {"chain", "native", "staged"} <= set(
        schedules_for("all_to_all_tiles"))
    assert {"direct", "staged"} <= set(schedules_for("ring_exchange"))
    assert {"direct", "staged", "ring2d"} <= set(
        schedules_for("grid_transpose"))
    assert "auto" in known_schedules()


def test_unknown_schedule_rejected_with_clear_error():
    with pytest.raises(UnknownScheduleError) as exc:
        CollectiveEngine(schedule="fastest")
    msg = str(exc.value)
    assert "fastest" in msg and "chain" in msg  # names the options


def test_unknown_per_call_override_rejected():
    eng = CollectiveEngine()
    with pytest.raises(UnknownScheduleError) as exc:
        eng.schedule_for("bcast", "direct")  # registered, but not for bcast
    assert "bcast" in str(exc.value)


def test_unknown_op_rejected():
    with pytest.raises(ValueError):
        CollectiveEngine().schedule_for("gather")
    with pytest.raises(ValueError):
        register_schedule("gather", "x")


def test_host_staged_forces_staged_everywhere():
    eng = CollectiveEngine(comm=CT.HOST_STAGED, schedule="chain")
    assert all(eng.schedule_for(op) == "staged" for op in OPS)


def test_auto_defaults_and_partial_name_fallback():
    eng = CollectiveEngine()  # auto
    assert eng.schedule_for("bcast") == "chain"
    assert eng.schedule_for("allreduce") == "native"
    assert eng.schedule_for("all_to_all_tiles") == "native"
    # 'rs_ag' exists only for allreduce: other ops fall back to their default
    eng = CollectiveEngine(schedule="rs_ag")
    assert eng.schedule_for("allreduce") == "rs_ag"
    assert eng.schedule_for("bcast") == "chain"
    assert eng.schedule_for("ring_exchange") == "direct"


def test_custom_schedule_registration():
    @register_schedule("allreduce", "double_native")
    def _ar(engine, x, axis):
        from jax import lax
        return lax.psum(x, axis) * 0 + lax.psum(x, axis)

    assert "double_native" in schedules_for("allreduce")
    eng = CollectiveEngine(schedule="double_native")
    assert eng.schedule_for("allreduce") == "double_native"


def test_topology_metadata_and_validation():
    mesh = make_mesh((1, 1), ("rows", "cols"))
    topo = MeshTopology.from_mesh(mesh)
    assert topo.axis("rows").kind == "torus_row"
    assert topo.axis("cols").kind == "torus_col"
    assert topo.size(("rows", "cols")) == 1
    assert isinstance(topo.axis("rows"), AxisTopology)
    with pytest.raises(KeyError):
        topo.axis("nonexistent")
    eng = CollectiveEngine.for_mesh(mesh)
    with pytest.raises(KeyError):
        eng.bcast(jnp.zeros(4), "bogus_axis", 0)
    desc = eng.describe()
    assert desc["topology"] == {"rows": "torus_row[1]", "cols": "torus_col[1]"}
    assert desc["resolved"]["bcast"] == "chain"


@pytest.mark.parametrize("schedule", ["chain", "native", "staged", "ring2d",
                                      "rs_ag"])
def test_single_rank_ops_are_identity(schedule):
    """Every schedule degenerates to identity on a 1-rank axis."""
    mesh = make_mesh((1,), ("x",))
    eng = CollectiveEngine.for_mesh(mesh, schedule=schedule)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 4, 128)),
                    jnp.float32)

    def body(v):
        out = eng.allreduce(v[0], "x")
        out = eng.bcast(out, "x", 0)
        out = eng.all_to_all_tiles(out, "x", split_axis=0, concat_axis=0)
        return out[None]

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("x", None, None),),
                           out_specs=P("x", None, None), check_vma=False))
    np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(x))


def test_pipelined_rejects_unsupported_ops():
    eng = CollectiveEngine()
    with pytest.raises(ValueError) as exc:
        eng.pipelined("ring_exchange", jnp.zeros((4, 4)), "x", nchunks=2)
    assert "single-payload" in str(exc.value)
    with pytest.raises(ValueError):
        eng.pipelined("nonsense", jnp.zeros((4, 4)), "x", nchunks=2)
    # missing per-op operands fail fast with a named error, not a KeyError
    with pytest.raises(ValueError, match="src"):
        eng.pipelined("bcast", jnp.zeros((4, 4)), "x", nchunks=2)
    with pytest.raises(ValueError, match="pg"):
        eng.pipelined("grid_transpose", jnp.zeros((4, 4)),
                      ("rows", "cols"), nchunks=2)
    with pytest.raises(ValueError, match="tile_split_axis"):
        eng.pipelined("all_to_all_tiles", jnp.zeros((4, 4, 4)), "x",
                      nchunks=2, split_axis=2)
    with pytest.raises(ValueError, match="tile_concat_axis"):
        eng.pipelined("all_to_all_tiles", jnp.zeros((4, 4, 4)), "x",
                      nchunks=2, split_axis=2, tile_split_axis=0)
    # strips along a tile axis would change the tile boundaries the
    # exchange moves — rejected before any slicing happens
    for bad in (0, 1):
        with pytest.raises(ValueError, match="tile axis"):
            eng.pipelined("all_to_all_tiles", jnp.zeros((4, 4, 4)), "x",
                          nchunks=2, split_axis=bad, tile_split_axis=0,
                          tile_concat_axis=1)


@pytest.mark.parametrize("nchunks", [1, 2, 3, 64, "auto"])
def test_pipelined_a2a_single_rank_identity(nchunks):
    """The pipelined all_to_all_tiles on a 1-rank axis reproduces the input
    exactly for every chunk count (strips along the capacity-style axis,
    tile axes untouched)."""
    mesh = make_mesh((1,), ("x",))
    eng = CollectiveEngine.for_mesh(mesh)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 2, 6, 4)),
                    jnp.float32)

    def body(v):
        return eng.pipelined("all_to_all_tiles", v[0], "x", nchunks=nchunks,
                             split_axis=2, tile_split_axis=1,
                             tile_concat_axis=0)[None]

    fn = jax.jit(shard_map(body, mesh=mesh,
                           in_specs=(P("x", None, None, None),),
                           out_specs=P("x", None, None, None),
                           check_vma=False))
    np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(x))


@pytest.mark.parametrize("nchunks", [1, 2, 3, 64, "auto"])
def test_pipelined_single_rank_identity(nchunks):
    """Chunked ops on a 1-rank axis reproduce the input exactly for every
    chunk count (including nchunks > rows, clamped to one row per strip)."""
    mesh = make_mesh((1,), ("x",))
    eng = CollectiveEngine.for_mesh(mesh)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 12, 8)),
                    jnp.float32)

    def body(v):
        out = eng.pipelined("allreduce", v[0], "x", nchunks=nchunks)
        out = eng.pipelined("bcast", out, "x", src=0, nchunks=nchunks,
                            split_axis=1)
        return out[None]

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("x", None, None),),
                           out_specs=P("x", None, None), check_vma=False))
    np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(x))


def test_pipelined_consume_and_concat_axis():
    """consume runs per strip with its static start offset; outputs
    concatenate along concat_axis."""
    mesh = make_mesh((1,), ("x",))
    eng = CollectiveEngine.for_mesh(mesh)
    x = jnp.arange(24, dtype=jnp.float32).reshape(6, 4)
    starts = []

    def body(v):
        def consume(strip, start):
            starts.append(start)
            return strip.T  # (4, rows) -> concat along axis 1
        return eng.pipelined("bcast", v[0], "x", src=0, nchunks=3,
                             concat_axis=1, consume=consume)[None]

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("x", None, None),),
                           out_specs=P("x", None, None), check_vma=False))
    out = np.asarray(fn(x[None]))[0]
    assert starts == [0, 2, 4]  # three equal strips of the 6 rows
    np.testing.assert_array_equal(out, np.asarray(x).T)


def test_fused_ring_step_matches_plain_add():
    from repro.kernels.ring import fused_chunk_add
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((4, 256)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((4, 256)), jnp.float32)
    np.testing.assert_array_equal(np.asarray(fused_chunk_add(a, b)),
                                  np.asarray(a + b))
    # ragged chunk falls back to the jnp add, same semantics
    a2, b2 = a.reshape(-1)[:100], b.reshape(-1)[:100]
    np.testing.assert_array_equal(np.asarray(fused_chunk_add(a2, b2)),
                                  np.asarray(a2 + b2))


def test_engine_ops_run_under_their_callsite_scope():
    """Every engine op lowers under ``comm.<op>:<callsite>`` (``untagged``
    without a tag), one scope per op, nested calls under their own."""
    import re
    from repro.comm.callsites import DP_GRADS
    mesh = make_mesh((1,), ("x",))
    # staged emits ops on a single rank, where the others are identities
    eng = CollectiveEngine.for_mesh(mesh, CT.HOST_STAGED)

    def body(v):
        a = eng.allreduce(v, "x", callsite=DP_GRADS)
        b = eng.bcast(a, "x", 0)
        return eng.pipelined("allreduce", b, "x", nchunks=2,
                             callsite=DP_GRADS, consume=lambda s, i: s * 2)

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("x"),
                          out_specs=P("x"), check_vma=False))
    text = f.lower(jnp.ones((8, 128))).as_text(debug_info=True)
    found = set(re.findall(r"comm\.[a-z_]+:[a-z_.]+(?:/comm\.[a-z_]+:"
                           r"[a-z_.]+)?", text))
    assert {"comm.allreduce:dp.grads", "comm.bcast:untagged",
            "comm.pipelined:dp.grads/comm.allreduce:dp.grads"} <= found
