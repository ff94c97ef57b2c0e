"""jit'd public wrappers for the Pallas kernels.

One rule decides how every kernel runs (:func:`interpret_mode`): compiled
by Mosaic on a TPU backend, and in Pallas interpret mode on any other —
there the kernel bodies execute as the jnp semantics of the same BlockSpec
pipeline, which is how the CPU test suite validates them. Code that must
compile a kernel for a TPU it does not run on (tests/test_tpu_compile.py)
calls the kernel modules directly with ``interpret=False``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import attention as _attn
from repro.kernels import gemm as _gemm
from repro.kernels import lu as _lu
from repro.kernels import stream as _stream
from repro.kernels import transpose as _transpose


def interpret_mode() -> bool:
    """True unless the default backend is a TPU."""
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype"))
def matmul(a, b, *, bm=256, bn=256, bk=256, out_dtype=None):
    return _gemm.matmul(a, b, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
                        interpret=interpret_mode())


@partial(jax.jit, static_argnames=("alpha", "bm", "bn", "bk"),
         donate_argnums=(0,))
def gemm_update(c, a, b, *, alpha=-1.0, bm=256, bn=256, bk=256):
    return _gemm.gemm_update(c, a, b, alpha=alpha, bm=bm, bn=bn, bk=bk,
                             interpret=interpret_mode())


@partial(jax.jit, static_argnames=("block",))
def transpose_add(a, b, *, block=256):
    return _transpose.transpose_add(a, b, block=block,
                                    interpret=interpret_mode())


@jax.jit
def lu_factor_block(a):
    return _lu.lu_factor_block(a, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("bn",))
def trsm_lower_left(lu, b, *, bn=256):
    return _lu.trsm_lower_left(lu, b, bn=bn, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("bm",))
def trsm_upper_right(lu, b, *, bm=256):
    return _lu.trsm_upper_right(lu, b, bm=bm, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("causal", "q_offset", "bq", "bk"))
def flash_attention(q, k, v, *, causal=True, q_offset=0, bq=512, bk=512):
    return _attn.flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                                 bq=bq, bk=bk, interpret=interpret_mode())


@jax.jit
def stream_copy(a):
    return _stream.stream_copy(a, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("alpha",))
def stream_scale(c, alpha):
    return _stream.stream_scale(c, alpha, interpret=interpret_mode())


@jax.jit
def stream_add(a, b):
    return _stream.stream_add(a, b, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("alpha",))
def stream_triad(b, c, alpha):
    return _stream.stream_triad(b, c, alpha, interpret=interpret_mode())
