"""Single-device tests for the overlap subsystem: bucket packing, the
engine's bucketed allreduce_tree, and schedule registration. Multi-device
equivalence runs in tests/dist/test_overlap.py."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.comm.engine import CollectiveEngine, schedules_for
from repro.comm.overlap import bucketed_psum_tree, pack_buckets, tree_bytes
from repro.launch.mesh import make_mesh


def _leaves(*sizes):
    return [jnp.zeros((s,), jnp.float32) for s in sizes]


def test_pack_buckets_greedy_boundaries():
    # 3 x 40B leaves, 100B cap: [0, 1] fills 80B, 2 overflows into a new one
    assert pack_buckets(_leaves(10, 10, 10), 100) == [[0, 1], [2]]
    # a leaf larger than the cap gets its own bucket and closes the previous
    assert pack_buckets(_leaves(10, 100, 10), 100) == [[0], [1], [2]]
    # cap of one byte: every leaf alone
    assert pack_buckets(_leaves(2, 2, 2), 1) == [[0], [1], [2]]
    # everything fits
    assert pack_buckets(_leaves(2, 2, 2), 1 << 30) == [[0, 1, 2]]


def test_pack_buckets_zero_byte_leaves():
    # 0-byte leaves never force a bucket boundary
    assert pack_buckets(_leaves(0, 10, 0, 10), 100) == [[0, 1, 2, 3]]
    assert pack_buckets([], 100) == []


def test_tree_bytes():
    assert tree_bytes({"a": jnp.zeros((3,), jnp.float32),
                       "b": jnp.zeros((2,), jnp.int8)}) == 14


def test_overlap_schedules_registered():
    assert "int8_ef" in schedules_for("allreduce")
    assert "ring2d" in schedules_for("grid_transpose")


def test_allreduce_tree_single_device_identity():
    """On a 1-rank axis every schedule must return the tree unchanged."""
    mesh = make_mesh((1,), ("x",))
    tree = {"w": jnp.asarray(np.arange(12, dtype=np.float32).reshape(3, 4)),
            "b": jnp.asarray(np.arange(3, dtype=np.float32)),
            "empty": jnp.zeros((0,), jnp.float32)}
    for schedule in ("native", "chain", "rs_ag", "ring2d"):
        eng = CollectiveEngine.for_mesh(mesh, schedule=schedule)
        fn = jax.jit(shard_map(
            lambda t, e=eng: e.allreduce_tree(t, "x", bucket_bytes=16),
            mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False))
        out = fn(tree)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(out[k]),
                                          np.asarray(tree[k]), err_msg=k)


def test_allreduce_tree_validates_axis():
    mesh = make_mesh((1,), ("x",))
    eng = CollectiveEngine.for_mesh(mesh)
    with pytest.raises(KeyError):
        eng.allreduce_tree({"a": jnp.zeros(3)}, "bogus")


def test_bucketed_psum_tree_single_device():
    mesh = make_mesh((1,), ("x",))
    tree = {"a": jnp.ones((5,), jnp.float32), "b": jnp.ones((2, 2))}
    with pytest.warns(DeprecationWarning, match="allreduce_tree"):
        fn = jax.jit(shard_map(lambda t: bucketed_psum_tree(t, "x", 8),
                               mesh=mesh, in_specs=(P(),), out_specs=P(),
                               check_vma=False))
        out = fn(tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(tree[k]))


def test_bucketed_psum_tree_is_deprecated_shim():
    """Single code path: the legacy wrapper must warn and forward to the
    engine op rather than carry its own reduction."""
    mesh = make_mesh((1,), ("x",))
    tree = {"a": jnp.ones((3,), jnp.float32)}
    with pytest.warns(DeprecationWarning):
        fn = jax.jit(shard_map(lambda t: bucketed_psum_tree(t, "x"),
                               mesh=mesh, in_specs=(P(),), out_specs=P(),
                               check_vma=False))
        out = fn(tree)
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.asarray(tree["a"]))


def test_hpl_lookahead_single_cell_mesh():
    """lookahead on the trivial 1x1 torus matches eager bitwise."""
    from repro.core.hpl import generate_system, make_factorize
    mesh = make_mesh((1, 1), ("rows", "cols"))
    n, b = 64, 32
    a, _, _ = generate_system(n)
    a_sh = jnp.asarray(a)[None]
    eager = make_factorize(mesh, pg=1, nb=n // b, b=b)(a_sh)
    look = make_factorize(mesh, pg=1, nb=n // b, b=b, lookahead=True)(a_sh)
    np.testing.assert_array_equal(np.asarray(look), np.asarray(eager))
