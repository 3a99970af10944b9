"""Fused streaming-preprocess Pallas kernels (S2CE Transformations hot path).

The per-batch edge preprocessing path in ``streams/preprocess.py`` is
three separate host-dispatched jnp programs (impute, Welford update,
normalize), each materializing an (n, d) intermediate in HBM. These two
kernels fuse that path:

* :func:`fused_normalize` — impute (NaN -> prior running mean) + Welford
  merge of the batch statistics + normalize, in ONE ``pallas_call`` over
  the batch. A two-phase grid visits the row blocks twice: phase 0
  accumulates the batch's raw moments (sum, sum-of-squares) in VMEM
  scratch and merges them into the carried running state; phase 1
  re-reads each block and writes the normalized rows with the merged
  statistics. The imputed/centered intermediates never touch HBM.

* :func:`fused_hash_features` — signed feature hashing
  ``(ids, vals) -> dense (n, dim)``. TPU has no scatter-add, so each
  feature column scatters the VPU way: compare the hashed slots against
  a broadcasted column iota and accumulate ``val * sign`` where they
  match (the same one-hot trick as the count-min kernel).

Both are differential-tested against the jnp twins in ``kernels/ref.py``
(``tests/test_kernel_oracles.py``). Hashing is bitwise-identical (pure
int32 ops); normalization is tolerance-equal, not bitwise, because the
kernel accumulates raw moments while the jnp path subtracts the two-pass
batch mean first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HASH_P = 2_147_483_647
_HASH_C = 0x9E37


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _normalize_kernel(n0_ref, x_ref, mean0_ref, m20_ref,
                      y_ref, mean1_ref, m21_ref,
                      s1_scr, s2_scr, mean_scr, rstd_scr, *,
                      blocks: int, block: int, n: int, impute: bool):
    phase = pl.program_id(0)
    bi = pl.program_id(1)
    mean0 = mean0_ref[...]                                # (1, d)
    x = x_ref[...]                                        # (block, d)
    if impute:
        x = jnp.where(jnp.isnan(x), mean0, x)
    valid = (bi * block
             + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)) < n
    xm = jnp.where(valid, x, 0.0)

    @pl.when(phase == 0)
    def _accumulate():
        @pl.when(bi == 0)
        def _init():
            s1_scr[...] = jnp.zeros_like(s1_scr)
            s2_scr[...] = jnp.zeros_like(s2_scr)

        s1_scr[...] += jnp.sum(xm, axis=0, keepdims=True)
        s2_scr[...] += jnp.sum(xm * xm, axis=0, keepdims=True)

        @pl.when(bi == blocks - 1)
        def _merge():
            # Welford batch merge from raw moments: the batch m2 is
            # sum(x^2) - nb*mean_b^2 (algebraically equal to the jnp
            # twin's centered sum; tolerance-equal in fp32).
            n0 = n0_ref[0, 0]
            nb = jnp.float32(n)
            mean_b = s1_scr[...] / nb
            m2_b = jnp.maximum(s2_scr[...] - nb * mean_b * mean_b, 0.0)
            n1 = n0 + nb
            delta = mean_b - mean0
            mean1 = mean0 + delta * (nb / jnp.maximum(n1, 1.0))
            m21 = (m20_ref[...] + m2_b
                   + delta * delta * n0 * nb / jnp.maximum(n1, 1.0))
            var = m21 / jnp.maximum(n1 - 1.0, 1.0)
            mean_scr[...] = mean1
            rstd_scr[...] = jax.lax.rsqrt(var + 1e-6)
            mean1_ref[...] = mean1
            m21_ref[...] = m21

    @pl.when(phase == 1)
    def _normalize():
        y_ref[...] = (x - mean_scr[...]) * rstd_scr[...]


def fused_normalize(x: jax.Array, n0: jax.Array, mean0: jax.Array,
                    m20: jax.Array, *, impute: bool = True,
                    block: int = 256, interpret: bool = False):
    """Fused impute + Welford-update + normalize over one batch.

    x: (n, d) fp32 (may contain NaN when ``impute``); n0: scalar count,
    mean0/m20: (d,) running stats. Returns ``(y, n1, mean1, m21)`` —
    the normalized batch and the updated running state, matching
    ``ref.fused_normalize_ref`` (= impute_with_mean + norm_update_apply).
    """
    n, d = x.shape
    block = min(_round_up(block, 8), _round_up(n, 8))
    npad = _round_up(n, block)
    if npad != n:
        x = jnp.pad(x, ((0, npad - n), (0, 0)))
    blocks = npad // block
    n0 = jnp.asarray(n0, jnp.float32)
    kernel = functools.partial(_normalize_kernel, blocks=blocks, block=block,
                               n=n, impute=impute)
    row = pl.BlockSpec((1, d), lambda p, b: (0, 0))
    y, mean1, m21 = pl.pallas_call(
        kernel,
        grid=(2, blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block, d), lambda p, b: (b, 0)),
            row, row,
        ],
        # phase 0 parks on y's first block, so nothing unwritten is
        # flushed before phase 1 fills each block
        out_specs=[pl.BlockSpec((block, d), lambda p, b: (b * p, 0)),
                   row, row],
        out_shape=[
            jax.ShapeDtypeStruct((npad, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32) for _ in range(4)],
        interpret=interpret,
    )(n0.reshape(1, 1),
      x.astype(jnp.float32),
      jnp.asarray(mean0, jnp.float32)[None, :],
      jnp.asarray(m20, jnp.float32)[None, :])
    return y[:n], n0 + n, mean1[0], m21[0]


def _hash_kernel(ids_ref, vals_ref, out_ref, *, dim: int, f: int, a: int):
    ids = ids_ref[...]                                    # (block, f) int32
    vals = vals_ref[...].astype(jnp.float32)              # (block, f)
    h = (ids * jnp.int32(a) + jnp.int32(_HASH_C)) % _HASH_P
    slot = h % dim                                        # (block, f)
    sign = jnp.where((h // dim) % 2 == 0, 1.0, -1.0)
    block = ids.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, dim), 1)
    acc = jnp.zeros((block, dim), jnp.float32)
    for j in range(f):                                    # f is small/static
        acc = acc + jnp.where(cols == slot[:, j][:, None],
                              (vals[:, j] * sign[:, j])[:, None], 0.0)
    out_ref[...] = acc


def fused_hash_features(ids: jax.Array, vals: jax.Array, dim: int, *,
                        seed: int = 17, block: int = 256,
                        interpret: bool = False) -> jax.Array:
    """Signed feature hashing: ids/vals (n, f) -> dense (n, dim) fp32.

    Bitwise-identical to ``ref.hash_features_ref`` — the hash is pure
    int32 arithmetic and the per-row accumulation order is the feature
    order in both.
    """
    n, f = ids.shape
    block = min(block, max(n, 8))
    npad = -(-n // block) * block
    if npad != n:
        ids = jnp.pad(ids, ((0, npad - n), (0, 0)))
        vals = jnp.pad(vals, ((0, npad - n), (0, 0)))
    blocks = npad // block
    kernel = functools.partial(_hash_kernel, dim=dim, f=f, a=2 * seed + 1)
    out = pl.pallas_call(
        kernel,
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((block, f), lambda b: (b, 0)),
            pl.BlockSpec((block, f), lambda b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((block, dim), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((npad, dim), jnp.float32),
        interpret=interpret,
    )(ids.astype(jnp.int32), vals)
    return out[:n]
