"""Single-device blocked LU against scipy-grade references + HPL metrics."""
from __future__ import annotations

import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hpl import (generate_system, normalized_residual,
                            solve_from_lu)
from repro.core.hpl_blocked import lu_blocked


@pytest.mark.parametrize("n,b", [(64, 32), (128, 32), (128, 64), (192, 64)])
def test_lu_blocked_reconstructs(n, b):
    a, _, _ = generate_system(n)
    lu = np.asarray(lu_blocked(jnp.asarray(a), b))
    l = np.tril(lu, -1) + np.eye(n, dtype=np.float32)
    u = np.triu(lu)
    np.testing.assert_allclose(l @ u, a, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("n,b", [(128, 32), (256, 64)])
def test_hpl_end_to_end_residual(n, b):
    a, x_true, b_vec = generate_system(n)
    lu = np.asarray(lu_blocked(jnp.asarray(a), b))
    x = solve_from_lu(lu, b_vec)
    np.testing.assert_allclose(x, x_true, atol=1e-3)
    assert normalized_residual(a, x, b_vec) < 1.0


def test_lookahead_depth_normalization():
    from repro.core.hpl import lookahead_depth
    assert lookahead_depth(False) == 0
    assert lookahead_depth(None) == 0
    assert lookahead_depth(True) == 1
    assert lookahead_depth(3) == 3
    with pytest.raises(ValueError):
        lookahead_depth(-1)


def test_block_size_invariance():
    """The factorization must not depend on the block size."""
    n = 128
    a, _, _ = generate_system(n)
    lu32 = np.asarray(lu_blocked(jnp.asarray(a), 32))
    lu64 = np.asarray(lu_blocked(jnp.asarray(a), 64))
    np.testing.assert_allclose(lu32, lu64, rtol=1e-4, atol=1e-4)


def _bench_hpl():
    """The benchmark's HPL entry (``bench/drivers/hpl.py``), imported as
    ``bench`` is from the root of the checkout."""
    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.harness import load_module
    return load_module("drivers", "hpl")


@pytest.mark.parametrize("n,b,pg", [(32768, 256, 1), (32768, 256, 2),
                                    (1024, 64, 2), (512, 64, 1)])
def test_launched_update_is_the_required_update(n, b, pg):
    """The trailing-tile GEMM launches exactly the update HPL requires on
    every device of the torus (a full-grid launch does about 3x), and the
    panel solves launch the tiles past k, about half of a full grid."""
    from repro.core.hpl import launched_tiles
    bench_hpl = _bench_hpl()
    nb, lb = n // b, n // b // pg
    for r in range(pg):
        for c in range(pg):
            update, trsm = launched_tiles(nb, pg, r, c)
            assert 2 * b ** 3 * update == bench_hpl.required_update_flops(
                n, b, pg, r, c)
            assert update < nb * lb * lb / 2.9
            # a full grid: nb iterations x 2 solves x lb tiles
            assert 0.4 * 2 * nb * lb < trsm <= nb * lb
    if pg == 1:
        # one chip: iteration k launches (nb-1-k)^2 GEMM tiles and 2(nb-1-k)
        # TRSM tiles
        assert launched_tiles(nb, 1, 0, 0) == (
            sum((nb - 1 - k) ** 2 for k in range(nb)),
            sum(2 * (nb - 1 - k) for k in range(nb)))


def test_first_unfactored_on_one_chip_and_a_torus():
    """On one chip the trailing tiles start at k + 1; on a 2x2 torus they
    start where the device's global block indices pass k."""
    from repro.core.hpl import first_unfactored
    lb = 4
    one = np.arange(lb)
    assert [tuple(map(int, first_unfactored(k, one, one)))
            for k in range(lb)] == [(1, 1), (2, 2), (3, 3), (4, 4)]
    # 2x2, nb = 8: device (0, 1) holds global rows 0, 2, 4, 6, cols 1, 3, 5, 7
    rows, cols = one * 2, one * 2 + 1
    assert [tuple(map(int, first_unfactored(k, rows, cols)))
            for k in range(8)] == [(1, 0), (1, 1), (2, 1), (2, 2), (3, 2),
                                   (3, 3), (4, 3), (4, 4)]
