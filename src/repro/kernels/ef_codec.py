"""Fused error-feedback codec round-trip Pallas kernels (uplink hot path).

``dist/compression.py``'s wire round-trips are chains of small jnp
programs — fold residual, global amax, quantize, dequantize, subtract —
each materializing a tensor-sized intermediate in HBM. On the uplink
path the orchestrator runs one round-trip per crossing batch tensor per
step, so the traffic is all memory-bound. These kernels fuse each
round-trip into one ``pallas_call`` over the flattened tensor:

* :func:`ef_int8_roundtrip` — int8 error-feedback round-trip
  ``(residual, x) -> (decoded, residual')``. Two-phase grid: phase 0
  reduces the global amax of ``x + residual`` into VMEM scratch (max is
  an exact reduction, so the scale matches ``ef_roundtrip`` exactly);
  phase 1 quantizes, dequantizes, and emits the fresh residual per
  block. Outputs agree with ``dist.compression.ef_roundtrip`` to <=1 ulp
  (the scale division may fuse differently across the two programs);
  the EF identity ``decoded + residual' == x + residual`` is exact.

* :func:`ef_topk_int8_roundtrip` — the composed sparsify-then-quantize
  round-trip with ONE shared residual. Top-k selection is expressed as a
  magnitude threshold (the k-th largest ``|x + residual|``, found outside
  the kernel by an exact bisection over its bits — selection is the one
  genuinely global, sort-shaped step); the kernel then fuses mask +
  survivor amax + quantize-dequantize + residual in one pass. For
  tie-free inputs this
  is bitwise the same selection as exact top-k, and the error-feedback
  telescoping identity ``decoded + residual' == x + residual`` holds for
  ANY selection, ties included.

Twins: ``ref.ef_int8_roundtrip_ref`` / ``ref.ef_topk_int8_roundtrip_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_QMAX = 127.0
_LANES = 128


def _tiled(t: jax.Array, block: int):
    """Flatten + zero-pad to a lane-dense ``(rows, 128)`` slab, and pick
    the row block: about ``block`` elements, a multiple of 8 rows."""
    flat = jnp.ravel(t).astype(jnp.float32)
    size = flat.shape[0]
    need = -(-size // (8 * _LANES)) * 8
    rows = min(max(8, -(-block // (8 * _LANES)) * 8), need)
    npad = -(-need // rows) * rows * _LANES
    if npad != size:
        flat = jnp.pad(flat, (0, npad - size))
    return flat.reshape(npad // _LANES, _LANES), rows, size


def _kth_largest(a: jax.Array, k: int) -> jax.Array:
    """Exact k-th largest of a non-negative fp32 vector, by bisection on
    the bit pattern (non-negative floats order like their int32 bits):
    31 counting passes, where a sort-based top-k compiles for half a
    minute on the chip."""
    bits = jax.lax.bitcast_convert_type(a, jnp.int32)

    def step(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(jnp.sum(bits >= cand) >= k, cand, t)

    t = jax.lax.fori_loop(0, 31, step, jnp.int32(0))
    return jax.lax.bitcast_convert_type(t, jnp.float32)


def _amax(v: jax.Array) -> jax.Array:
    """Max over a 2-D tile, kept (1, 1)."""
    return jnp.max(jnp.max(v, axis=0, keepdims=True), axis=1, keepdims=True)


def _roundtrip(kernel, residual, x, block, interpret, *extra):
    """Run a two-phase round-trip kernel over row blocks of the flattened
    payload: phase 0 reduces the amax into a (1, 1) VMEM scratch, phase 1
    writes ``(decoded, residual')``. Phase 0 parks on the outputs' first
    block, so nothing unwritten is flushed. ``extra`` are (1, 1) inputs."""
    xb, rows, size = _tiled(x, block)
    rb, _, _ = _tiled(residual, block)
    blocks = xb.shape[0] // rows
    tile = pl.BlockSpec((rows, _LANES), lambda p, b: (b, 0))
    out = pl.BlockSpec((rows, _LANES), lambda p, b: (b * p, 0))
    scalar = pl.BlockSpec((1, 1), lambda p, b: (0, 0))
    dec, rout = pl.pallas_call(
        functools.partial(kernel, blocks=blocks),
        grid=(2, blocks),
        in_specs=[tile, tile] + [scalar] * len(extra),
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct(xb.shape, jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32)],
        interpret=interpret,
    )(xb, rb, *(e.reshape(1, 1) for e in extra))
    shape = jnp.shape(x)
    return (dec.reshape(-1)[:size].reshape(shape).astype(x.dtype),
            rout.reshape(-1)[:size].reshape(shape))


def _int8_kernel(x_ref, r_ref, dec_ref, rout_ref, amax_scr, *, blocks: int):
    phase = pl.program_id(0)
    bi = pl.program_id(1)
    xc = x_ref[...] + r_ref[...]                          # (rows, 128)

    @pl.when(phase == 0)
    def _reduce():
        @pl.when(bi == 0)
        def _init():
            amax_scr[...] = jnp.zeros_like(amax_scr)

        amax_scr[...] = jnp.maximum(amax_scr[...], _amax(jnp.abs(xc)))

    @pl.when(phase == 1)
    def _emit():
        scale = jnp.maximum(amax_scr[...], 1e-30) / _QMAX
        q = jnp.clip(jnp.round(xc / scale), -_QMAX, _QMAX)
        dec = q * scale
        dec_ref[...] = dec
        rout_ref[...] = xc - dec


def ef_int8_roundtrip(residual: jax.Array, x: jax.Array, *,
                      block: int = 65536, interpret: bool = False):
    """Fused int8 EF wire round-trip: ``(decoded, new_residual)``.

    Agrees with ``dist.compression.ef_roundtrip`` to <=1 ulp; the
    internal EF identity is exact."""
    return _roundtrip(_int8_kernel, residual, x, block, interpret)


def _topk_int8_kernel(x_ref, r_ref, t_ref, dec_ref, rout_ref, amax_scr, *,
                      blocks: int):
    phase = pl.program_id(0)
    bi = pl.program_id(1)
    xc = x_ref[...] + r_ref[...]                          # (rows, 128)
    kept = jnp.abs(xc) >= t_ref[...]

    @pl.when(phase == 0)
    def _reduce():
        @pl.when(bi == 0)
        def _init():
            amax_scr[...] = jnp.zeros_like(amax_scr)

        amax_scr[...] = jnp.maximum(
            amax_scr[...], _amax(jnp.where(kept, jnp.abs(xc), 0.0)))

    @pl.when(phase == 1)
    def _emit():
        scale = jnp.maximum(amax_scr[...], 1e-30) / _QMAX
        q = jnp.clip(jnp.round(jnp.where(kept, xc, 0.0) / scale),
                     -_QMAX, _QMAX)
        dec = jnp.where(kept, q * scale, 0.0)
        dec_ref[...] = dec
        rout_ref[...] = xc - dec


def ef_topk_int8_roundtrip(residual: jax.Array, x: jax.Array, k: int, *,
                           block: int = 65536, interpret: bool = False):
    """Fused top-k + int8 EF wire round-trip with one shared residual.

    Keeps the coordinates of ``x + residual`` whose magnitude reaches the
    k-th largest, int8-quantizes the survivors against their own amax,
    and carries dropped mass AND quantization error forward:
    ``(decoded, new_residual)``."""
    xc = jnp.ravel(x).astype(jnp.float32) + jnp.ravel(residual)
    k = max(1, min(int(k), xc.shape[0]))
    # the selection threshold: the one global, sort-shaped step
    t = _kth_largest(jnp.abs(xc), k)
    return _roundtrip(_topk_int8_kernel, residual, x, block, interpret, t)
