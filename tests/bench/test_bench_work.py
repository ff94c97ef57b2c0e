"""The work and bytes the algorithms require, which every roofline and
``hpl_mfu`` count, and the block-cyclic layout the checks undo."""
import numpy as np
import pytest

from bench.harness import load_module
from bench.layout import distribute, undistribute

hpl = load_module("drivers", "hpl")
ptrans = load_module("drivers", "ptrans")


@pytest.mark.parametrize("n,b,pg", [(1024, 256, 1), (2048, 256, 2),
                                    (16384, 256, 1), (32768, 256, 2),
                                    (768, 128, 3)])
def test_hpl_required_update_sums_to_closed_form(n, b, pg):
    per_dev = sum(hpl.required_update_flops(n, b, pg, r, c)
                  for r in range(pg) for c in range(pg))
    assert per_dev == hpl.required_update_flops_total(n, b)
    # the closed form is sum_k 2 b (N - (k+1) b)^2
    nb = n // b
    assert per_dev == sum(2.0 * b * (n - (k + 1) * b) ** 2 for k in range(nb))


@pytest.mark.parametrize("n,pg", [(16384, 1), (32768, 2)])
def test_hpl_required_update_is_a_third_of_the_masked_update(n, pg):
    b = 256
    required = hpl.required_update_flops_total(n, b)
    masked = pg * pg * hpl.masked_update_flops(n, b, pg)
    assert masked == 2.0 * n ** 3
    assert 0.32 < required / masked < 1 / 3
    # and the required update is HPL's nominal work less lower-order terms
    assert 0.97 < required / hpl.nominal_flops(n) <= 1.0


def test_hpl_required_update_one_chip_by_hand():
    # N = 3 blocks of b = 2 on one chip: k=0 leaves 4x4, k=1 2x2, k=2 none
    assert hpl.required_update_flops(6, 2, 1, 0, 0) == 2 * 2 * (16 + 4)


@pytest.mark.parametrize("n,pg", [(16384, 1), (32768, 2), (1024, 2)])
def test_ptrans_required_bytes(n, pg):
    m = n // pg
    assert ptrans.required_bytes(n, pg) == 3 * m * m * 4
    if pg == 1:
        assert ptrans.required_bytes(n, pg) == 3 * n ** 2 * 4


def test_nominal_work():
    assert hpl.nominal_flops(16384) == 2.0 * 16384 ** 3 / 3
    assert ptrans.nominal_flops(16384) == 16384.0 ** 2


@pytest.mark.parametrize("n,b,pg", [(64, 8, 1), (64, 8, 2), (96, 16, 3)])
def test_layout_matches_the_programs_block_cyclic_layout(n, b, pg):
    from repro.core.ptrans import distribute_cyclic, undistribute_cyclic
    x = np.arange(n * n, dtype=np.float32).reshape(n, n)
    shards = distribute(x, pg, b)
    np.testing.assert_array_equal(shards, distribute_cyclic(x, pg, b))
    np.testing.assert_array_equal(undistribute(shards, pg, b), x)
    np.testing.assert_array_equal(undistribute_cyclic(shards, pg, b), x)


@pytest.mark.parametrize("slab_bytes", [1, 1 << 12, 1 << 30])
@pytest.mark.parametrize("n,b,pg", [(64, 8, 1), (64, 8, 2), (96, 16, 3)])
def test_gather_copies_a_device_stack_into_its_global_matrix(
        n, b, pg, slab_bytes, monkeypatch):
    """One block row of slabs, several, and the whole stack in one."""
    import jax.numpy as jnp
    from bench import layout
    monkeypatch.setattr(layout, "SLAB_BYTES", slab_bytes)
    x = np.arange(n * n, dtype=np.float32).reshape(n, n)
    got = layout.gather(jnp.asarray(distribute(x, pg, b)), pg, b)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, x)


def test_slab_rows_are_whole_blocks_that_divide_the_local_rows():
    from bench.layout import SLAB_BYTES, slab_rows
    assert slab_rows(32768, 256, 32768 * 4) == SLAB_BYTES // (32768 * 4)
    assert slab_rows(16384, 256, 4 * 16384 * 4) == 256
    assert slab_rows(1280, 128, 1 << 20) == 128
    for m, b, row in [(32768, 256, 4), (1280, 128, 4 * 40), (96, 16, 4)]:
        r = slab_rows(m, b, row)
        assert r % b == 0 and m % r == 0
