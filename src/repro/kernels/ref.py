"""Pure-jnp oracles for every Pallas kernel (the ground truth the kernels
are swept against in tests/test_kernels.py)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def attention_ref(q, k, v, *, causal: bool = True):
    """q: (B,S,H,D); k,v: (B,T,KV,D). Materialized-softmax oracle."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def rwkv6_wkv_ref(r, k, v, lw, u, h0):
    """Naive per-timestep recurrence. r,k,v,lw: (B,S,H,hs); u: (H,hs);
    h0: (B,H,hs,hs). Returns (o, h_last) in fp32."""
    B, S, H, hs = r.shape
    rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
    w = jnp.exp(lw.astype(jnp.float32))          # decay in (0,1]

    def step(h, t):
        rt, kt, vt, wt = rf[:, t], kf[:, t], vf[:, t], w[:, t]
        kv = jnp.einsum("bhi,bhj->bhij", kt, vt)
        o_t = jnp.einsum("bhi,bhij->bhj", rt, h + u[None, :, :, None] * kv)
        h = wt[..., None] * h + kv
        return h, o_t

    h, outs = jax.lax.scan(step, h0.astype(jnp.float32), jnp.arange(S))
    return jnp.moveaxis(outs, 0, 1), h            # (B,S,H,hs), (B,H,hs,hs)


def mamba_scan_ref(dt, x, Bm, Cm, A, h0):
    """Naive per-timestep selective scan. dt,x: (B,S,dI); Bm,Cm: (B,S,N);
    A: (dI,N); h0: (B,dI,N). Returns (y (B,S,dI), h_last)."""
    dtf = dt.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    Bf, Cf = Bm.astype(jnp.float32), Cm.astype(jnp.float32)
    Af = A.astype(jnp.float32)

    def step(h, t):
        a = jnp.exp(dtf[:, t][:, :, None] * Af[None])
        b = (dtf[:, t] * xf[:, t])[:, :, None] * Bf[:, t][:, None, :]
        h = a * h + b
        y = jnp.einsum("bdn,bn->bd", h, Cf[:, t])
        return h, y

    h, ys = jax.lax.scan(step, h0.astype(jnp.float32), jnp.arange(dt.shape[1]))
    return jnp.moveaxis(ys, 0, 1), h


def countmin_ref(ids, depth, width, seeds):
    """Scatter-add oracle for the Count-Min sketch increment."""
    P = 2_147_483_647
    out = jnp.zeros((depth, width), jnp.int32)
    for d in range(depth):
        h = ((ids.astype(jnp.int32) * int(seeds[d, 0])
              + int(seeds[d, 1])) % P) % width
        out = out.at[d].add(
            jnp.zeros((width,), jnp.int32).at[h].add(1))
    return out


def countmin_update_query_ref(ids, table, seeds):
    """Scatter-add + gather oracle for the fused add-then-query kernel:
    fold the batch into the sketch, then estimate each id against the
    UPDATED table (min over depths)."""
    P = 2_147_483_647
    depth, width = table.shape
    new_table = table + countmin_ref(ids, depth, width, seeds)
    ests = []
    for d in range(depth):
        h = ((ids.astype(jnp.int32) * int(seeds[d, 0])
              + int(seeds[d, 1])) % P) % width
        ests.append(new_table[d, h])
    return new_table, jnp.min(jnp.stack(ests), axis=0)


def fused_normalize_ref(x, n0, mean0, m20, *, impute=True):
    """Impute (NaN -> prior mean) + Welford merge + normalize — the
    composition ``impute_with_mean`` then ``norm_update_apply`` from
    streams/preprocess.py, restated here as a standalone oracle."""
    x = jnp.asarray(x, jnp.float32)
    mean0 = jnp.asarray(mean0, jnp.float32)
    m20 = jnp.asarray(m20, jnp.float32)
    n0 = jnp.asarray(n0, jnp.float32)
    if impute:
        x = jnp.where(jnp.isnan(x), mean0[None, :], x)
    nb = x.shape[0]
    mean_b = jnp.mean(x, axis=0)
    m2_b = jnp.sum(jnp.square(x - mean_b), axis=0)
    n1 = n0 + nb
    delta = mean_b - mean0
    mean1 = mean0 + delta * (nb / jnp.maximum(n1, 1.0))
    m21 = m20 + m2_b + jnp.square(delta) * n0 * nb / jnp.maximum(n1, 1.0)
    var = m21 / jnp.maximum(n1 - 1.0, 1.0)
    y = (x - mean1) * jax.lax.rsqrt(var + 1e-6)
    return y, n1, mean1, m21


def hash_features_ref(ids, vals, dim, seed=17):
    """Signed feature hashing oracle (scatter-add form): ids/vals (n, f)
    -> dense (n, dim). Same int32 hash as streams/preprocess.

    One scatter per feature column, so colliding features add in feature
    order on every backend (a single scatter leaves the order of
    duplicate-index updates to the backend)."""
    a = 2 * seed + 1
    h = (ids.astype(jnp.int32) * a + 0x9E37) % 2_147_483_647
    slot = h % dim
    sign = jnp.where((h // dim) % 2 == 0, 1.0, -1.0)
    upd = vals.astype(jnp.float32) * sign
    n, f = ids.shape
    rows = jnp.arange(n)
    out = jnp.zeros((n, dim), jnp.float32)
    for j in range(f):
        out = out.at[rows, slot[:, j]].add(upd[:, j])
    return out


def ef_int8_roundtrip_ref(residual, x):
    """Int8 error-feedback wire round-trip oracle: fold the carried
    residual, symmetric per-tensor int8 quantize-dequantize, carry the
    fresh error. Mirrors dist.compression.ef_roundtrip."""
    xc = x.astype(jnp.float32) + residual
    amax = jnp.max(jnp.abs(xc))
    scale = jnp.maximum(amax, jnp.asarray(1e-30, jnp.float32)) / 127.0
    q = jnp.clip(jnp.round(xc / scale), -127.0, 127.0)
    dec = q * scale
    return dec.astype(x.dtype), xc - dec


def ef_topk_int8_roundtrip_ref(residual, x, k):
    """Composed top-k + int8 EF round-trip oracle with one shared
    residual. Selection is by magnitude threshold (the k-th largest
    ``|x + residual|``) — for tie-free inputs identical to exact top-k,
    and the EF telescoping identity holds for any selection."""
    xc = jnp.ravel(x).astype(jnp.float32) + jnp.ravel(residual)
    k = max(1, min(int(k), xc.shape[0]))
    t = jax.lax.top_k(jnp.abs(xc), k)[0][-1]
    kept = jnp.abs(xc) >= t
    amax = jnp.max(jnp.where(kept, jnp.abs(xc), 0.0))
    scale = jnp.maximum(amax, jnp.asarray(1e-30, jnp.float32)) / 127.0
    q = jnp.clip(jnp.round(jnp.where(kept, xc, 0.0) / scale), -127.0, 127.0)
    dec = jnp.where(kept, q * scale, 0.0)
    shape = jnp.shape(x)
    return (dec.reshape(shape).astype(x.dtype),
            (xc - dec).reshape(shape))
