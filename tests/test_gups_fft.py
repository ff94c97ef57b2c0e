"""Unit tests for the legacy GUPS/FFT kernels and the engine-routed
building blocks (the 8-device per-schedule equivalence suite lives in
tests/dist/test_gups_fft.py). Everything here runs at any device count."""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.core import fft as FFT
from repro.core import randomaccess as RA

NDEV = len(jax.devices())


@pytest.fixture(scope="module")
def ring():
    return make_mesh((NDEV,), ("x",))


# ---------------------------------------------------------------------------
# xorshift generator vs an independent reference
# ---------------------------------------------------------------------------


def _np_xorshift_stream(seed: int, count: int) -> np.ndarray:
    """Pure-python HPCC-style LCG: x <- (x << 1) ^ (msb(x) ? 0x7 : 0)."""
    x = int(seed) & 0xFFFFFFFF
    out = np.empty(count, np.uint32)
    for i in range(count):
        x = ((x << 1) & 0xFFFFFFFF) ^ (int(RA.POLY) if x >> 31 else 0)
        out[i] = x
    return out


@pytest.mark.parametrize("seed", [1, 12345, 0x7FFFFFFF, 0xDEADBEEF])
def test_xorshift_stream_matches_reference(seed):
    got = np.asarray(RA._gen_updates(jnp.uint32(seed), 64))
    want = _np_xorshift_stream(seed, 64)
    np.testing.assert_array_equal(got, want)


def test_xorshift_step_feedback_taps():
    # msb set -> the polynomial is XORed in; msb clear -> plain shift
    assert int(RA._xorshift_step(jnp.uint32(0x80000000))) == int(RA.POLY)
    assert int(RA._xorshift_step(jnp.uint32(1))) == 2


# ---------------------------------------------------------------------------
# legacy drop-local path
# ---------------------------------------------------------------------------


def test_randomaccess_inverse_restore_exact(ring):
    res = RA.run_randomaccess(ring, table_log=12, rngs_per_device=2,
                              updates_per_rng=128, reps=1)
    assert res.error == 0.0


def test_randomaccess_rejects_indivisible_table():
    # must raise (not assert — an `-O` run strips asserts) before any
    # device work: 2**20 is not divisible by 3
    fake = SimpleNamespace(devices=np.zeros(3))
    with pytest.raises(ValueError, match="not divisible"):
        RA.run_randomaccess(fake)


def test_fft_dist_rejects_indivisible_signal():
    fake = SimpleNamespace(devices=np.zeros(3))
    with pytest.raises(ValueError, match="not divisible"):
        FFT.run_fft_dist(fake, log_size=10)


# ---------------------------------------------------------------------------
# update bucketing (the routed path's local half)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sign", [+1, -1])
def test_bucket_updates_matches_numpy_oracle(sign):
    table_log, n_dev = 10, 4
    local_size = (1 << table_log) // n_dev
    rng = np.random.default_rng(7)
    vals = rng.integers(0, 1 << 32, 256, dtype=np.uint32)

    buf = np.asarray(RA._bucket_updates(
        jnp.asarray(vals), table_log=table_log, local_size=local_size,
        n_dev=n_dev, sign=sign))
    assert buf.shape == (n_dev, len(vals), 2)
    assert buf.dtype == np.int32

    addr = (vals & np.uint32((1 << table_log) - 1)).astype(np.int64)
    want_dest = addr // local_size
    # every update lands in exactly its owner's bucket, value preserved
    # (scatter-applying each bucket == applying every update once)
    applied = np.zeros(1 << table_log, np.int64)
    for d in range(n_dev):
        loc, upd = buf[d, :, 0], buf[d, :, 1]
        live = loc < local_size  # sentinel local_size marks unused slots
        assert np.count_nonzero(live) == np.count_nonzero(want_dest == d)
        np.add.at(applied, d * local_size + loc[live], upd[live])
        assert np.all(upd[~live] == 0)
    want = np.zeros(1 << table_log, np.int64)
    np.add.at(want, addr, vals.astype(np.int32).astype(np.int64) * sign)
    np.testing.assert_array_equal(applied, want)


def test_routed_randomaccess_restore_exact(ring):
    res = RA.run_randomaccess_dist(ring, table_log=12, rngs_per_device=2,
                                   updates_per_rng=128, reps=1,
                                   schedule="native", nchunks=1)
    assert res.error == 0.0
    assert res.details["schedule"] == "native"


# ---------------------------------------------------------------------------
# FFT: full-output validation
# ---------------------------------------------------------------------------


def test_fft_error_covers_full_output(ring):
    res = FFT.run_fft(ring, log_size=8, batch_per_device=4, reps=1)
    assert res.error < 1e-5


def test_fft_dist_matches_reference(ring):
    res = FFT.run_fft_dist(ring, log_size=8, batch_per_device=4, reps=1,
                           schedule="native", nchunks=1)
    assert res.error < 1e-5
    assert res.details["schedule"] == "native"
