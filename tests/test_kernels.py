"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against the
pure-jnp oracle (ref.py). On the CPU the kernels run in Pallas interpret
mode (kernels.ops.interpret_mode); tests/test_tpu_compile.py compiles them
for a TPU."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.gemm import fit_block

ATOL = {jnp.float32: 2e-4, jnp.bfloat16: 8e-2}


def _rand(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (64, 64, 64, 64, 64, 64),
    (128, 64, 192, 64, 64, 32),
    (256, 128, 128, 128, 128, 128),
    (96, 48, 80, 32, 16, 16),
])
def test_matmul_sweep(rng, dtype, m, k, n, bm, bn, bk):
    a = _rand(rng, (m, k), dtype)
    b = _rand(rng, (k, n), dtype)
    out = ops.matmul(a, b, bm=bm, bn=bn, bk=bk)
    want = ref.matmul(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype] * k ** 0.5, rtol=1e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("alpha", [-1.0, 0.5])
def test_gemm_update(rng, dtype, alpha):
    m, k, n = 128, 96, 64
    c = _rand(rng, (m, n), dtype)
    a = _rand(rng, (m, k), dtype)
    b = _rand(rng, (k, n), dtype)
    out = ops.gemm_update(c.copy(), a, b, alpha=alpha, bm=64, bn=32, bk=32)
    want = ref.gemm_update(c, a, b, alpha=alpha)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype] * k ** 0.5, rtol=1e-2)


@pytest.mark.parametrize("n,block", [(64, 64), (128, 64), (256, 128), (192, 64)])
def test_transpose_add(rng, n, block):
    a = _rand(rng, (n, n), jnp.float32)
    b = _rand(rng, (n, n), jnp.float32)
    out = ops.transpose_add(a, b, block=block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.transpose_add(a, b)),
                               atol=1e-6)


@pytest.mark.parametrize("n", [16, 64, 128])
def test_lu_factor_block(rng, n):
    a = rng.standard_normal((n, n)).astype(np.float32)
    a[np.arange(n), np.arange(n)] += n  # diagonally dominant (HPL-AI rule)
    a = jnp.asarray(a)
    lu = ops.lu_factor_block(a)
    np.testing.assert_allclose(np.asarray(lu), np.asarray(ref.lu_factor_block(a)),
                               rtol=1e-5, atol=1e-5)
    # L @ U must reconstruct A
    l, u = ref.unpack_lu(np.asarray(lu))
    np.testing.assert_allclose(np.asarray(l) @ np.asarray(u), np.asarray(a),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("b_cols", [64, 192])
def test_trsm_lower_left(rng, b_cols):
    n = 64
    a = rng.standard_normal((n, n)).astype(np.float32)
    a[np.arange(n), np.arange(n)] += n
    lu = ops.lu_factor_block(jnp.asarray(a))
    rhs = _rand(rng, (n, b_cols), jnp.float32)
    out = ops.trsm_lower_left(lu, rhs, bn=64)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.trsm_lower_left(lu, rhs)),
                               rtol=1e-4, atol=1e-4)
    # residual: L @ X == B
    l, _ = ref.unpack_lu(np.asarray(lu))
    np.testing.assert_allclose(l @ np.asarray(out), np.asarray(rhs),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("b_rows", [64, 192])
def test_trsm_upper_right(rng, b_rows):
    n = 64
    a = rng.standard_normal((n, n)).astype(np.float32)
    a[np.arange(n), np.arange(n)] += n
    lu = ops.lu_factor_block(jnp.asarray(a))
    rhs = _rand(rng, (b_rows, n), jnp.float32)
    out = ops.trsm_upper_right(lu, rhs, bm=64)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.trsm_upper_right(lu, rhs)),
                               rtol=1e-4, atol=1e-4)
    _, u = ref.unpack_lu(np.asarray(lu))
    np.testing.assert_allclose(np.asarray(out) @ u, np.asarray(rhs),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("B,H,KV,S,hd,bq,bk", [
    (2, 4, 4, 128, 32, 64, 64),     # MHA
    (1, 8, 2, 256, 64, 128, 64),    # GQA 4:1
    (2, 8, 1, 96, 32, 32, 32),      # MQA
])
def test_flash_attention_sweep(rng, dtype, causal, B, H, KV, S, hd, bq, bk):
    q = _rand(rng, (B, S, H, hd), dtype)
    k = _rand(rng, (B, S, KV, hd), dtype)
    v = _rand(rng, (B, S, KV, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    want = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype], rtol=2e-2)


def test_flash_attention_q_offset(rng):
    """Decode-style offset: last-row attention equals full attention row."""
    B, S, H, hd = 1, 128, 4, 32
    q = _rand(rng, (B, S, H, hd), jnp.float32)
    k = _rand(rng, (B, S, H, hd), jnp.float32)
    v = _rand(rng, (B, S, H, hd), jnp.float32)
    full = ops.flash_attention(q, k, v, causal=True, bq=32, bk=32)
    tail = ops.flash_attention(q[:, -32:], k, v, causal=True, q_offset=S - 32,
                               bq=32, bk=32)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(full[:, -32:]),
                               atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("n", [1 << 10, 3 << 10])
def test_stream_kernels(rng, n):
    a = _rand(rng, (n,), jnp.float32)
    b = _rand(rng, (n,), jnp.float32)
    np.testing.assert_allclose(np.asarray(ops.stream_copy(a)),
                               np.asarray(ref.stream_copy(a)))
    np.testing.assert_allclose(np.asarray(ops.stream_scale(a, 3.0)),
                               np.asarray(ref.stream_scale(a, 3.0)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ops.stream_add(a, b)),
                               np.asarray(ref.stream_add(a, b)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ops.stream_triad(a, b, 3.0)),
                               np.asarray(ref.stream_triad(a, b, 3.0)), atol=1e-5)


def test_fit_block():
    assert fit_block(256, 256) == 256
    assert fit_block(96, 64) == 48
    assert fit_block(100, 64) == 50
    for size in (64, 96, 100, 257):
        for pref in (16, 64, 256):
            b = fit_block(size, pref)
            assert size % b == 0 and b <= max(pref, 1)
