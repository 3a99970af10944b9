"""Jit'd public wrappers for the Pallas kernels.

Kernel dispatch policy: on a TPU backend the Pallas path is taken and the
kernels always compile for the chip. On the CPU, ``JAX_PALLAS_INTERPRET=1``
(or ``REPRO_FORCE_PALLAS_INTERPRET=1``) runs them in Pallas interpret mode,
which is how the tests exercise them; otherwise callers use the jnp / XLA
implementations. Interpret mode is never taken on a TPU, whatever the
environment says.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import countmin as _cms
from repro.kernels import ef_codec as _ef
from repro.kernels import flash_attention as _fa
from repro.kernels import mamba_scan as _ms
from repro.kernels import preprocess as _pp
from repro.kernels import rwkv6_wkv as _wkv


def _interpret() -> bool:
    # JAX_PALLAS_INTERPRET is the conventional spelling the CI oracle job
    # uses; REPRO_FORCE_PALLAS_INTERPRET kept for back-compat. Neither
    # applies on a TPU: there the kernels run compiled.
    if jax.default_backend() == "tpu":
        return False
    return (os.environ.get("REPRO_FORCE_PALLAS_INTERPRET", "0") == "1"
            or os.environ.get("JAX_PALLAS_INTERPRET", "0") == "1")


def pallas_available() -> bool:
    return jax.default_backend() == "tpu" or _interpret()


def gmm_tiling(k: int, n: int) -> tuple:
    """Row, contraction and output tiles of :func:`grouped_matmul`: the
    contraction whole up to 2,048 (one accumulation, as the XLA path
    does), output tiles of at most 1,408 columns. At DeepSeek-V2-Lite's
    expert shapes these were the fastest of those that fit the v5e's
    VMEM (measured on the chip, ``PERF.md``)."""
    if n <= 1408:
        return 256, min(k, 2048), n
    return 512, min(k, 2048), 1024


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` (m, k) rows in consecutive groups, group ``g`` holding
    ``group_sizes[g]`` rows, each times ``rhs[g]`` (k, n): the grouped
    product of a sorted expert layer. Rows past the last group are left
    undefined. On a TPU (or in interpret mode) the megablox Pallas kernel,
    which visits only the row tiles the groups cover; otherwise
    ``jax.lax.ragged_dot``."""
    if not pallas_available():
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m, k = lhs.shape
    tiling = gmm_tiling(k, rhs.shape[2])
    lhs = jnp.pad(lhs, ((0, -m % tiling[0]), (0, 0)))
    return gmm(lhs, rhs, group_sizes, lhs.dtype, tiling, None, None, False,
               _interpret())[:m]


def flash_supported(q, k, v, causal, q_offset, kv_len) -> bool:
    """Kernel handles plain causal/full attention without offsets/lengths
    (the cached-decode path uses the XLA implementation)."""
    if not pallas_available():
        return False
    if kv_len is not None:
        return False
    if isinstance(q_offset, jax.Array) or q_offset:
        return False
    return q.shape[-1] == k.shape[-1]


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 256,
                    bk: int = 256):
    return _fa.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_wkv(r, k, v, lw, u, h0, *, chunk: int = 32):
    return _wkv.rwkv6_wkv(r, k, v, lw, u, h0, chunk=chunk,
                          interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk", "bd"))
def mamba_scan(dt, x, Bm, Cm, A, h0, *, chunk: int = 128, bd: int = 256):
    return _ms.mamba_scan_bd(dt, x, Bm, Cm, A, h0, chunk=chunk, bd=bd,
                             interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("depth", "width", "block"))
def countmin_update(ids, *, depth: int, width: int, seeds, block: int = 512):
    return _cms.countmin_update(ids, depth, width, seeds, block=block,
                                interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block",))
def countmin_update_query(ids, table, seeds, *, block: int = 512):
    return _cms.countmin_update_query(ids, table, seeds, block=block,
                                      interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("impute", "block"))
def fused_normalize(x, n0, mean0, m20, *, impute: bool = True,
                    block: int = 256):
    return _pp.fused_normalize(x, n0, mean0, m20, impute=impute,
                               block=block, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("dim", "seed", "block"))
def hash_features(ids, vals, *, dim: int, seed: int = 17, block: int = 256):
    return _pp.fused_hash_features(ids, vals, dim, seed=seed, block=block,
                                   interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block",))
def ef_int8_roundtrip(residual, x, *, block: int = 65536):
    return _ef.ef_int8_roundtrip(residual, x, block=block,
                                 interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("k", "block"))
def ef_topk_int8_roundtrip(residual, x, *, k: int, block: int = 65536):
    return _ef.ef_topk_int8_roundtrip(residual, x, k, block=block,
                                      interpret=_interpret())
