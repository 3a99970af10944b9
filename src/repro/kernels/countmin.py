"""Count-Min sketch update and query as Pallas TPU kernels (S2CE ingest
hot path).

TPU has no atomic scatter-add, so per-depth histogram accumulation is done
the MXU way: hash each item id to a column, build a transposed one-hot
``(wtile, block)`` tile (sketch columns on sublanes, ids on lanes) and
matmul a ones-vector against it, i.e. a column-count reduction per id
block, accumulated in the resident output tile across the id grid. The
gather for the query runs the same one-hot the other way: the table row
(split into bf16-exact bytes) times the one-hot picks each id's cell.

Layout rules the chip's compiler enforces: ids ride lanes as ``(1, n)``,
the table is tiled ``(depth, wtile)`` with the full depth per block, and
the per-depth hash constants live in SMEM. Both kernels are exact in
int32: one-hot products are 0/1 and each output sums one nonzero term.

Hashing: universal (a*x + b) mod p mod width, with per-depth odd constants
(same family as the jnp oracle in ref.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_P = 2_147_483_647  # Mersenne prime 2^31-1
_WTILE = 512        # sketch columns per grid step


def hash_ids(ids: jax.Array, a: jax.Array, b: jax.Array, width: int):
    """Universal hash; seeds must be < 2^15 so products stay exact in the
    int32 domain (jax x64 is disabled in production configs)."""
    h = (ids.astype(jnp.int32) * a.astype(jnp.int32) + b.astype(jnp.int32))
    return ((h % _P) % width).astype(jnp.int32)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _onehot_t(ids_ref, seeds_ref, d: int, bi, wi, *, block: int,
              wtile: int, width: int, n: int):
    """(wtile, block) bf16 one-hot of depth ``d``'s hashes against the
    sketch columns of tile ``wi``; padded ids hit no column."""
    h = hash_ids(ids_ref[...], seeds_ref[d, 0], seeds_ref[d, 1], width)
    pos = bi * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    h = jnp.where(pos < n, h, -1)                          # (1, block)
    cols = wi * wtile + jax.lax.broadcasted_iota(jnp.int32, (wtile, block), 0)
    return jnp.where(cols == h, 1.0, 0.0).astype(jnp.bfloat16)


def _update_kernel(seeds_ref, ids_ref, table_ref, out_ref, *, depth: int,
                   block: int, wtile: int, width: int, n: int):
    wi = pl.program_id(0)
    bi = pl.program_id(1)

    @pl.when(bi == 0)
    def _init():
        out_ref[...] = table_ref[...]

    ones = jnp.ones((8, block), jnp.bfloat16)
    for d in range(depth):
        onehot = _onehot_t(ids_ref, seeds_ref, d, bi, wi, block=block,
                           wtile=wtile, width=width, n=n)
        counts = jax.lax.dot_general(ones, onehot, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        out_ref[d:d + 1, :] += counts[0:1].astype(jnp.int32)


def _query_kernel(seeds_ref, ids_ref, table_ref, est_ref, *, depth: int,
                  block: int, wtile: int, width: int, n: int):
    bi = pl.program_id(0)
    wi = pl.program_id(1)

    @pl.when(wi == 0)
    def _init():
        est_ref[...] = jnp.zeros_like(est_ref)

    # byte k of each cell on sublane k: every digit is exact in bf16, and
    # each one-hot column selects one cell, so the f32 sums are exact
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, wtile), 0)
    shift = jnp.minimum(sub, 3) * 8
    for d in range(depth):
        row = jnp.broadcast_to(table_ref[d:d + 1, :], (8, wtile))
        digits = jnp.where(sub < 4, (row >> shift) & 255, 0)
        onehot = _onehot_t(ids_ref, seeds_ref, d, bi, wi, block=block,
                           wtile=wtile, width=width, n=n)
        picked = jax.lax.dot_general(
            digits.astype(jnp.float32).astype(jnp.bfloat16), onehot,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)
        est_ref[d:d + 1, :] += (picked[0:1] + (picked[1:2] << 8)
                                + (picked[2:3] << 16) + (picked[3:4] << 24))


def _layout(n: int, width: int, block: int):
    block = min(_round_up(block, 128), _round_up(max(n, 1), 128))
    wtile = min(_WTILE, _round_up(width, 128))
    return block, _round_up(max(n, 1), block), wtile, _round_up(width, wtile)


def _call(kernel, grid, ids_spec, table_spec, out_spec, out_shape, seeds,
          ids, table, interpret):
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), ids_spec,
                  table_spec],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(seeds.astype(jnp.int32), ids, table)


def _update(ids, table, seeds, *, block: int, interpret: bool):
    """``table + increment`` for non-negative int32 tables."""
    depth, width = table.shape
    n = ids.shape[0]
    block, npad, wtile, wpad = _layout(n, width, block)
    ids = jnp.pad(ids.astype(jnp.int32), (0, npad - n))[None, :]
    table = jnp.pad(table.astype(jnp.int32), ((0, 0), (0, wpad - width)))
    kernel = functools.partial(_update_kernel, depth=depth, block=block,
                               wtile=wtile, width=width, n=n)
    out = _call(kernel, (wpad // wtile, npad // block),
                pl.BlockSpec((1, block), lambda w, b: (0, b)),
                pl.BlockSpec((depth, wtile), lambda w, b: (0, w)),
                pl.BlockSpec((depth, wtile), lambda w, b: (0, w)),
                jax.ShapeDtypeStruct((depth, wpad), jnp.int32),
                seeds, ids, table, interpret)
    return out[:, :width]


def countmin_update(ids: jax.Array, depth: int, width: int,
                    seeds: jax.Array, *, block: int = 512,
                    interpret: bool = False) -> jax.Array:
    """ids: (n,) int32 -> sketch increment (depth, width) int32.
    seeds: (depth, 2) int32 hash constants."""
    return _update(ids, jnp.zeros((depth, width), jnp.int32), seeds,
                   block=block, interpret=interpret)


def countmin_update_query(ids: jax.Array, table: jax.Array,
                          seeds: jax.Array, *, block: int = 512,
                          interpret: bool = False):
    """Batched add-then-query: fold ``ids`` into ``table`` and estimate
    each id's count against the UPDATED sketch.

    ids: (n,) int32; table: (depth, width) non-negative int32; seeds:
    (depth, 2). Returns ``(new_table (depth, width) int32, est (n,)
    int32)``, the same result as ``countmin_update`` + a per-depth gather
    + min, with no (n, width) one-hot materialized in HBM."""
    depth, width = table.shape
    n = ids.shape[0]
    new_table = _update(ids, table, seeds, block=block, interpret=interpret)
    block, npad, wtile, wpad = _layout(n, width, block)
    idsp = jnp.pad(ids.astype(jnp.int32), (0, npad - n))[None, :]
    tablep = jnp.pad(new_table, ((0, 0), (0, wpad - width)))
    kernel = functools.partial(_query_kernel, depth=depth, block=block,
                               wtile=wtile, width=width, n=n)
    est = _call(kernel, (npad // block, wpad // wtile),
                pl.BlockSpec((1, block), lambda b, w: (0, b)),
                pl.BlockSpec((depth, wtile), lambda b, w: (0, w)),
                pl.BlockSpec((depth, block), lambda b, w: (0, b)),
                jax.ShapeDtypeStruct((depth, npad), jnp.int32),
                seeds, idsp, tablep, interpret)
    return new_table, jnp.min(est[:, :n], axis=0)
