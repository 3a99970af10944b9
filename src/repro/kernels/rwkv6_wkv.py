"""RWKV6 WKV recurrence as a chunked Pallas TPU kernel.

Grid: (B*H, chunks) with chunks innermost-sequential; the per-head state
S (hs x hs) persists in VMEM scratch across chunk steps. Within a chunk the
pairwise decay exponent L_excl[t]-L[s] <= 0 keeps everything overflow-free
(same math as the jnp path in models/rwkv.py — the two are asserted
allclose in tests). The intra-chunk term is a (Lc, Lc, hs) pairwise tensor:
VPU-heavy but VMEM-resident; an all-MXU log-space variant is future work
(EXPERIMENTS.md §Perf).

VMEM per grid point at Lc=32, hs=64: r/k/v/lw tiles 4x(32,64)f32 + pair
(32,32,64)f32 + state (64,64)f32 ~= 0.3 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, h0_ref, o_ref, hout_ref,
                h_scr, *, chunks: int, chunk: int, hs: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    r = r_ref[0].astype(jnp.float32)          # (Lc, hs)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)        # log-decay <= 0
    u = u_ref[0].astype(jnp.float32)          # (1, hs)
    h = h_scr[...]                            # (hs, hs)

    # inclusive cumsum over the chunk as a lower-triangular matmul (the
    # chip's kernel compiler has no cumsum)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = jnp.where(s_idx <= t_idx, 1.0, 0.0)
    L = jax.lax.dot_general(tril, lw, (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    L_excl = L - lw
    # inter-chunk: (r_t * exp(L_excl_t)) @ S
    q_in = r * jnp.exp(L_excl)
    o = jax.lax.dot_general(q_in, h, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # intra-chunk pairwise-stable
    dpair = jnp.exp(jnp.minimum(L_excl[:, None, :] - L[None, :, :], 0.0))
    scores = jnp.sum(r[:, None, :] * dpair * k[None, :, :], axis=-1)
    scores = jnp.where(s_idx < t_idx, scores, 0.0)
    o = o + jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # diagonal bonus
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)
    o = o + diag * v
    o_ref[0] = o.astype(o_ref.dtype)

    # state update
    L_end = L[chunk - 1:chunk]                # (1, hs)
    # the same total decay as a column, to scale the rows of S
    L_end_col = jax.lax.dot_general(
        lw, jnp.ones((chunk, 1), jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)   # (hs, 1)
    kdec = k * jnp.exp(L_end - L)
    h_new = jnp.exp(L_end_col) * h + jax.lax.dot_general(
        kdec, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    h_scr[...] = h_new

    @pl.when(ci == chunks - 1)
    def _final():
        hout_ref[0] = h_new


def rwkv6_wkv_bh(r, k, v, lw, u, h0, *, chunk: int = 32,
                 interpret: bool = False):
    """r,k,v,lw: (BH, S, hs); u: (BH, hs); h0: (BH, hs, hs) fp32.
    Returns (o (BH, S, hs), h_last (BH, hs, hs))."""
    BH, S, hs = r.shape
    chunk = min(chunk, S)
    Sp = -(-S // chunk) * chunk
    if Sp != S:  # pad with zero k/v (contributes nothing), decay 0
        pad = ((0, 0), (0, Sp - S), (0, 0))
        r, k, v = (jnp.pad(t, pad) for t in (r, k, v))
        lw = jnp.pad(lw, pad)
    chunks = Sp // chunk
    kernel = functools.partial(_wkv_kernel, chunks=chunks, chunk=chunk, hs=hs)
    o, h_last = pl.pallas_call(
        kernel,
        grid=(BH, chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, hs), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hs), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hs), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hs), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, hs), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, hs, hs), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hs), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, hs, hs), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sp, hs), r.dtype),
            jax.ShapeDtypeStruct((BH, hs, hs), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)],
        interpret=interpret,
    )(r, k, v, lw, u[:, None, :], h0)
    return o[:, :S, :], h_last


def rwkv6_wkv(r, k, v, lw, u, h0, *, chunk: int = 32,
              interpret: bool = False):
    """Model-layout wrapper. r,k,v,lw: (B,S,H,hs); u: (H,hs);
    h0: (B,H,hs,hs). Returns (o (B,S,H,hs), h_last)."""
    B, S, H, hs = r.shape
    def fold(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, S, hs)
    uf = jnp.broadcast_to(u[None], (B, H, hs)).reshape(B * H, hs)
    o, h_last = rwkv6_wkv_bh(fold(r), fold(k), fold(v), fold(lw), uf,
                             h0.reshape(B * H, hs, hs), chunk=chunk,
                             interpret=interpret)
    return (o.reshape(B, H, S, hs).transpose(0, 2, 1, 3),
            h_last.reshape(B, H, hs, hs))
