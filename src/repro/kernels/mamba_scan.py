"""Mamba selective scan as a chunked Pallas TPU kernel.

Grid: (B, d_inner blocks, chunks) with chunks innermost-sequential; the
running state h, held as (N, bd), persists in VMEM scratch. Within a chunk
the recurrence is swept step by step entirely in VMEM:

    h_t = a_t h_{t-1} + b_t,  a_t = exp(dt_t * A)

Per-chunk working set at Lc=128, bd=256, N=16: dt/x tiles (Lc, bd) f32
plus B/C tiles (N, Lc) ~= 0.3 MB — the (B,S,dI,N) tensor of the naive
parallel scan never exists.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(dt_ref, x_ref, bt_ref, ct_ref, at_ref, h0_ref, y_ref,
                  hout_ref, h_scr, *, chunks: int, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    dt = dt_ref[0].astype(jnp.float32)        # (Lc, bd)
    dx = dt * x_ref[0].astype(jnp.float32)    # (Lc, bd)
    bt = bt_ref[0].astype(jnp.float32)        # (N, Lc)
    ct = ct_ref[0].astype(jnp.float32)        # (N, Lc)
    at = at_ref[...].astype(jnp.float32)      # (N, bd)
    h = h_scr[...]                            # (N, bd)
    # a short sequential sweep over the chunk, unrolled with static slices
    # (VMEM-resident; the chip's kernel compiler indexes values statically)
    for t in range(chunk):
        h = jnp.exp(at * dt[t:t + 1]) * h + bt[:, t:t + 1] * dx[t:t + 1]
        y_ref[0, t:t + 1, :] = jnp.sum(h * ct[:, t:t + 1], axis=0,
                                       keepdims=True).astype(y_ref.dtype)
    h_scr[...] = h

    @pl.when(ci == chunks - 1)
    def _final():
        hout_ref[0] = h


def mamba_scan_bd(dt, x, Bm, Cm, A, h0, *, chunk: int = 128, bd: int = 256,
                  interpret: bool = False):
    """dt,x: (B, S, dI); Bm,Cm: (B, S, N); A: (dI, N); h0: (B, dI, N) fp32.
    Returns (y (B,S,dI) fp32, h_last (B,dI,N) fp32).

    Inside the kernel the state is held transposed, ``(N, bd)``, so the
    inner dim rides lanes; B and C come in as ``(N, S)``."""
    B, S, dI = dt.shape
    N = Bm.shape[-1]
    bd = min(bd, dI)
    assert dI % bd == 0, (dI, bd)
    chunk = min(chunk, S)
    Sp = -(-S // chunk) * chunk
    if Sp != S:
        dt = jnp.pad(dt, ((0, 0), (0, Sp - S), (0, 0)))
        x = jnp.pad(x, ((0, 0), (0, Sp - S), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, Sp - S), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, Sp - S), (0, 0)))
    chunks = Sp // chunk
    kernel = functools.partial(_mamba_kernel, chunks=chunks, chunk=chunk)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(B, dI // bd, chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, N, chunk), lambda b, d, c: (b, 0, c)),
            pl.BlockSpec((1, N, chunk), lambda b, d, c: (b, 0, c)),
            pl.BlockSpec((N, bd), lambda b, d, c: (0, d)),
            pl.BlockSpec((1, N, bd), lambda b, d, c: (b, 0, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, N, bd), lambda b, d, c: (b, 0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, dI), jnp.float32),
            jax.ShapeDtypeStruct((B, N, dI), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, bd), jnp.float32)],
        interpret=interpret,
    )(dt, x, Bm.transpose(0, 2, 1), Cm.transpose(0, 2, 1), A.T,
      h0.transpose(0, 2, 1))
    return y[:, :S, :], h_last.transpose(0, 2, 1)
