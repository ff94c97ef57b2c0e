"""Compile every Pallas kernel of the main path for a described TPU v5e at
real tile shapes, without a chip: the TPU compiler refuses unaligned blocks,
unsupported primitives and VMEM overuse that interpret mode accepts.

The topology is described inside a module-scoped fixture (never at import):
only one process may load the TPU library at a time, and every test worker
imports this file. Where it cannot be described, the fixture skips.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import attention, gemm, lu, ring, stream, transpose

F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without that chip; keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


KERNELS = {
    "lu_factor_block_b128": (lambda a: lu.lu_factor_block(a),
                             [((128, 128), F32)]),
    "lu_factor_block_b256": (lambda a: lu.lu_factor_block(a),
                             [((256, 256), F32)]),
    "trsm_lower_left_b128": (lambda l, x: lu.trsm_lower_left(l, x),
                             [((128, 128), F32), ((128, 8192), F32)]),
    "trsm_lower_left_b256": (lambda l, x: lu.trsm_lower_left(l, x),
                             [((256, 256), F32), ((256, 8192), F32)]),
    "trsm_upper_right_b128": (lambda l, x: lu.trsm_upper_right(l, x),
                              [((128, 128), F32), ((8192, 128), F32)]),
    "trsm_upper_right_b256": (lambda l, x: lu.trsm_upper_right(l, x),
                              [((256, 256), F32), ((8192, 256), F32)]),
    "gemm_update_hpl": (lambda c, a, b: gemm.gemm_update(c, a, b, bm=256,
                                                         bn=256),
                        [((8192, 8192), F32), ((8192, 256), F32),
                         ((256, 8192), F32)]),
    "matmul_f32": (lambda a, b: gemm.matmul(a, b, bm=128, bn=128, bk=128),
                   [((8192, 8192), F32), ((8192, 8192), F32)]),
    "matmul_bf16": (lambda a, b: gemm.matmul(a, b),
                    [((4096, 4096), BF16), ((4096, 4096), BF16)]),
    "transpose_add": (lambda a, b: transpose.transpose_add(a, b),
                      [((4096, 4096), F32), ((4096, 4096), F32)]),
    "stream_triad": (lambda a, b: stream.stream_triad(a, b, 3.0),
                     [((1 << 26,), F32), ((1 << 26,), F32)]),
    "ring_add_step": (lambda a, b: ring.ring_add_step(a, b),
                      [((8192, 128), F32), ((8192, 128), F32)]),
    "flash_attention": (lambda q, k, v: attention.flash_attention(q, k, v),
                        [((1, 2048, 8, 128), BF16)] * 3),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    compiled = _compile(one_chip, fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text(), name


def test_ragged_ring_chunk_compiles_padded(one_chip, monkeypatch):
    """The engine's per-hop add pads a chunk that is not whole (8, 128)
    tiles, so the compiled kernel runs for every chunk shape."""
    monkeypatch.setattr(ring, "interpret_mode", lambda: False)
    compiled = _compile(one_chip, ring.fused_chunk_add,
                        ((1000,), F32), ((1000,), F32))
    assert "tpu_custom_call" in compiled.as_text()


def test_hpl_factorization_compiles_for_v5e(topo, monkeypatch):
    """The whole 1x1 HPL factorization (LU, both TRSMs and the (b, b, b)
    tiled GEMM inside the iteration loop) at b = 256, lookahead depth 1."""
    from repro.core.hpl import make_factorize
    from repro.kernels import ops
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("rows", "cols"))
    n, b = 2048, 256
    spec = jax.ShapeDtypeStruct(
        (1, n, n), F32,
        sharding=NamedSharding(mesh, P(("rows", "cols"), None, None)))
    fact = make_factorize(mesh, pg=1, nb=n // b, b=b, lookahead=1)
    assert "tpu_custom_call" in fact.lower(spec).compile().as_text()


def _ops_and_phases(text):
    """HLO instruction names and the registered scopes their op_names
    carry, in the compiled module text."""
    import re
    from repro.scopes import registered
    names = registered()
    ops, phases = set(), set()
    for m in re.finditer(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?metadata=\{'
                         r'op_name="([^"]*)"', text, re.M):
        ops.add(m.group(1).split(".")[0])
        phases |= {p for p in m.group(2).split("/") if p in names}
    return ops, phases


@pytest.mark.parametrize("pg", [1, 2])
def test_kernel_names_and_scopes_in_the_compiled_programs(topo, monkeypatch,
                                                          pg):
    """The Pallas kernels keep the instruction names the trace shows
    (``gemm_update``, ``lu_factor_block``, ``trsm_*``, ``transpose_add``),
    and every HPL and PTRANS phase and the engine's callsite scopes reach
    the compiled programs' op_name metadata, on one chip and on 2x2."""
    from repro.comm.engine import CollectiveEngine
    from repro.core.hpl import make_factorize
    from repro.core.ptrans import make_step
    from repro.kernels import ops
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    mesh = Mesh(np.array(topo.devices[:pg * pg]).reshape(pg, pg),
                ("rows", "cols"))
    n, b = 1024 * pg, 256
    spec = jax.ShapeDtypeStruct(
        (pg * pg, n // pg, n // pg), F32,
        sharding=NamedSharding(mesh, P(("rows", "cols"), None, None)))
    hpl = make_factorize(mesh, pg=pg, nb=n // b, b=b, lookahead=1)
    names, phases = _ops_and_phases(hpl.lower(spec).compile().as_text())
    assert {"gemm_update", "lu_factor_block", "trsm_lower_left",
            "trsm_upper_right"} <= names
    assert {"hpl.factor", "hpl.trsm", "hpl.update", "hpl.strip",
            "hpl.writeback"} <= phases
    if pg > 1:
        assert {"comm.bcast:hpl.block", "comm.bcast:hpl.panel"} <= phases
    step = make_step(mesh, pg, CollectiveEngine.for_mesh(mesh))
    names, phases = _ops_and_phases(step.lower(spec, spec).compile()
                                    .as_text())
    assert "transpose_add" in names and "collective-permute-start" in names
    assert {"ptrans.transpose_add",
            "comm.grid_transpose:ptrans.exchange"} <= phases
