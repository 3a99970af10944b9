"""Attention: GQA self-attention, MLA (DeepSeek latent), cross-attention.

Three execution paths, all numerically equivalent (tested against each other):

* ``dense``   — materialized scores; smoke tests / short sequences.
* ``chunked`` — lax.scan over KV blocks with online softmax; O(S * chunk)
                memory; the portable path used by dry-runs (compiles on any
                backend, XLA-fusable on TPU).
* ``pallas``  — the flash-attention kernel in :mod:`repro.kernels`
                (TPU target; validated in interpret mode).

GQA under tensor parallelism: when the `heads` logical axis maps to a mesh
axis wider than n_kv_heads, KV heads are repeated to `tp` virtual KV heads
(standard Megatron-GQA duplication) so both q and kv shard evenly.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist import axis_size, shard
from repro.models.layers import apply_rope, yarn_mscale
from repro.models.params import Spec

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def attn_specs(cfg: ArchConfig, cross: bool = False):
    d, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    sp = {
        "wq": Spec((d, H, Dh), ("embed", "heads", "head_dim")),
        "wk": Spec((d, KV, Dh), ("embed", "kv_heads", "head_dim")),
        "wv": Spec((d, KV, Dh), ("embed", "kv_heads", "head_dim")),
        "wo": Spec((H, Dh, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        sp["bq"] = Spec((H, Dh), ("heads", "head_dim"), "zeros")
        sp["bk"] = Spec((KV, Dh), ("kv_heads", "head_dim"), "zeros")
        sp["bv"] = Spec((KV, Dh), ("kv_heads", "head_dim"), "zeros")
    return sp


def mla_specs(cfg: ArchConfig):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qdim = m.nope_head_dim + m.rope_head_dim
    sp = {
        "w_dkv": Spec((d, m.kv_lora_rank), ("embed", "lora")),
        "w_kr": Spec((d, m.rope_head_dim), ("embed", "head_dim")),
        "kv_norm": Spec((m.kv_lora_rank,), ("lora",), "ones"),
        "w_uk": Spec((m.kv_lora_rank, H, m.nope_head_dim), ("lora", "heads", "head_dim")),
        "w_uv": Spec((m.kv_lora_rank, H, m.v_head_dim), ("lora", "heads", "head_dim")),
        "wo": Spec((H, m.v_head_dim, d), ("heads", "head_dim", "embed")),
    }
    if m.q_lora_rank:
        sp["w_dq"] = Spec((d, m.q_lora_rank), ("embed", "lora"))
        sp["q_norm"] = Spec((m.q_lora_rank,), ("lora",), "ones")
        sp["w_uq"] = Spec((m.q_lora_rank, H, qdim), ("lora", "heads", "head_dim"))
    else:
        sp["wq"] = Spec((d, H, qdim), ("embed", "heads", "head_dim"))
    return sp


# ---------------------------------------------------------------------------
# KV repeat for TP (Megatron-GQA duplication)
# ---------------------------------------------------------------------------

def kv_repeat_factor(cfg: ArchConfig) -> int:
    tp = axis_size("heads")
    if tp <= cfg.n_kv_heads:
        return 1
    rep = tp // cfg.n_kv_heads
    if (cfg.n_kv_heads * rep) > cfg.n_heads or cfg.n_heads % (cfg.n_kv_heads * rep):
        return 1  # cannot repeat evenly; fall back to plain GQA grouping
    return rep


def _expand_kv(k: jax.Array, rep: int) -> jax.Array:
    if rep == 1:
        return k
    return jnp.repeat(k, rep, axis=2)


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------

def _group(q: jax.Array, n_kv: int):
    B, S, H, Dh = q.shape
    return q.reshape(B, S, n_kv, H // n_kv, Dh)


def dense_attention(q, k, v, *, causal: bool, q_offset=0,
                    kv_len: Optional[jax.Array] = None,
                    scale: Optional[float] = None) -> jax.Array:
    """Materialized-scores attention. q:(B,S,H,Dh) k,v:(B,T,KV,Dh).
    ``scale`` defaults to Dh ** -0.5."""
    B, S, H, Dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    qg = _group(q, KV)
    scale = 1.0 / math.sqrt(Dh) if scale is None else scale
    s = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = _make_mask(S, T, causal, q_offset, kv_len, B)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, v.shape[-1]).astype(q.dtype)


def _make_mask(S, T, causal, q_offset, kv_len, B):
    """(B, S, T) bool validity mask."""
    qpos = jnp.arange(S)[:, None] + q_offset            # (S,1) (+ (B,1,1) if array)
    kpos = jnp.arange(T)[None, :]
    if isinstance(q_offset, jax.Array) and q_offset.ndim > 0:
        qpos = jnp.arange(S)[None, :, None] + q_offset.reshape(-1, 1, 1)
        kpos = kpos[None]
    m = jnp.ones((S, T), bool) if not causal else (kpos <= qpos)
    if m.ndim == 2:
        m = jnp.broadcast_to(m[None], (B, S, T))
    if kv_len is not None:
        kl = jnp.asarray(kv_len).reshape(-1, 1, 1)
        m = m & (jnp.arange(T)[None, None, :] < kl)
    return m


def chunked_attention(q, k, v, *, causal: bool, chunk: int = 512, q_offset=0,
                      kv_len: Optional[jax.Array] = None,
                      scale: Optional[float] = None) -> jax.Array:
    """Online-softmax attention scanning KV blocks; O(S*chunk) memory."""
    B, S, H, Dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // KV
    chunk = min(chunk, T)
    nblk = -(-T // chunk)
    Tp = nblk * chunk
    if Tp != T:
        pad = [(0, 0), (0, Tp - T), (0, 0), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    qg = _group(q, KV).astype(jnp.float32)
    scale = 1.0 / math.sqrt(Dh) if scale is None else scale
    ks = jnp.moveaxis(k.reshape(B, nblk, chunk, KV, k.shape[-1]), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, nblk, chunk, KV, Dv), 1, 0)

    qoff = jnp.asarray(q_offset)
    if qoff.ndim == 0:
        qpos_b = jnp.broadcast_to(jnp.arange(S)[None] + qoff, (B, S))
    else:
        qpos_b = jnp.arange(S)[None] + qoff.reshape(-1, 1)      # (B,S)
    kl = None if kv_len is None else jnp.asarray(kv_len).reshape(-1)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, blk = xs
        kpos = blk * chunk + jnp.arange(chunk)          # (chunk,)
        s = jnp.einsum("bskgd,bckd->bkgsc", qg, kb.astype(jnp.float32)) * scale
        valid = kpos[None, None, :] < T                  # padding
        if causal:
            valid = valid & (kpos[None, None, :] <= qpos_b[:, :, None])
        if kl is not None:
            valid = valid & (kpos[None, None, :] < kl[:, None, None])
        s = jnp.where(valid[:, None, None], s, NEG_INF)  # (B,KV,G,S,chunk)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgsc,bckd->bkgsd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    init = (jnp.full((B, KV, G, S), NEG_INF, jnp.float32),
            jnp.zeros((B, KV, G, S), jnp.float32),
            jnp.zeros((B, KV, G, S, Dv), jnp.float32))
    # nested remat: keep per-block fp32 score residuals out of the backward
    # save-list (flash-attention-style recompute)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), init,
                                  (ks, vs, jnp.arange(nblk)))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    o = jnp.moveaxis(o, 3, 1)                            # (B,S,KV,G,Dv)
    return o.reshape(B, S, H, Dv).astype(q.dtype)


def attention(q, k, v, *, causal: bool, impl: str = "dense", chunk: int = 512,
              q_offset=0, kv_len=None, scale=None) -> jax.Array:
    if impl == "pallas":
        from repro.kernels import ops as kops
        if scale is None and kops.flash_supported(q, k, v, causal, q_offset,
                                                  kv_len):
            return kops.flash_attention(q, k, v, causal=causal)
        impl = "chunked"
    if impl == "chunked" and k.shape[1] > chunk:
        return chunked_attention(q, k, v, causal=causal, chunk=chunk,
                                 q_offset=q_offset, kv_len=kv_len,
                                 scale=scale)
    return dense_attention(q, k, v, causal=causal, q_offset=q_offset,
                           kv_len=kv_len, scale=scale)


# ---------------------------------------------------------------------------
# Self-attention block (GQA)
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jax.Array          # (B, T, KV, Dh)
    v: jax.Array
    length: jax.Array     # () int32 — filled prefix


def _project(p, cfg, x, name):
    w = p["w" + name]
    y = jnp.einsum("bsd,dhe->bshe", x, w.astype(x.dtype))
    if cfg.qkv_bias and ("b" + name) in p:
        y = y + p["b" + name].astype(x.dtype)
    return y


def self_attention(p, cfg: ArchConfig, x: jax.Array, *, positions,
                   cache: Optional[KVCache] = None, causal: bool = True,
                   impl: str = "chunked"):
    """x: (B,S,D). Returns (out, new_cache)."""
    q = _project(p, cfg, x, "q")
    k = _project(p, cfg, x, "k")
    v = _project(p, cfg, x, "v")
    if cfg.pos_embed == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", None, "heads", None)

    new_cache = None
    kv_len = None
    if isinstance(positions, jax.Array):
        q_offset = positions[:, 0] if positions.ndim == 2 else positions[0]
    else:
        q_offset = positions
    if cache is not None:
        k_all = jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype), cache.length, axis=1)
        v_all = jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype), cache.length, axis=1)
        new_cache = KVCache(k_all, v_all, cache.length + k.shape[1])
        k, v = k_all.astype(x.dtype), v_all.astype(x.dtype)
        kv_len = cache.length + q.shape[1]
        q_offset = cache.length
    rep = kv_repeat_factor(cfg)
    k = shard(_expand_kv(k, rep), "batch", "kv_seq", "heads" if rep > 1 else "kv_heads", None)
    v = shard(_expand_kv(v, rep), "batch", "kv_seq", "heads" if rep > 1 else "kv_heads", None)

    o = attention(q, k, v, causal=causal, impl=impl, chunk=cfg.attn_chunk,
                  q_offset=q_offset, kv_len=kv_len)
    o = shard(o, "batch", None, "heads", None)
    out = jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(x.dtype))
    return out, new_cache


def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> KVCache:
    KV, Dh = cfg.n_kv_heads, cfg.d_head
    return KVCache(
        k=jnp.zeros((batch, max_len, KV, Dh), dtype),
        v=jnp.zeros((batch, max_len, KV, Dh), dtype),
        length=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------

class MLACache(NamedTuple):
    c_kv: jax.Array       # (B, T, r)  compressed latent
    k_rope: jax.Array     # (B, T, dr) shared rope key
    length: jax.Array


def mla_softmax_scale(cfg: ArchConfig) -> float:
    """(nope + rope head dims) ** -0.5, times YaRN's ``mscale_all_dim``
    factor squared where the rope is scaled (DeepSeek-V2's attention)."""
    m = cfg.mla
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    y = m.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def mla_attention(p, cfg: ArchConfig, x: jax.Array, *, positions,
                  cache: Optional[MLACache] = None, impl: str = "chunked"):
    """Latent attention. A decode step (one new position against a cache)
    scores in the latent space; every other call expands the latent into
    per-head keys and values."""
    decode = cache is not None and x.shape[1] == 1
    with jax.named_scope("s2ce.mla.decode_latent" if decode
                         else "s2ce.mla.prefill"):
        return _mla(p, cfg, x, positions=positions, cache=cache, impl=impl,
                    decode=decode)


def _mla(p, cfg: ArchConfig, x, *, positions, cache, impl, decode):
    m = cfg.mla
    B, S, d = x.shape
    H = cfg.n_heads
    dn, dr = m.nope_head_dim, m.rope_head_dim

    if m.q_lora_rank:
        cq = x @ p["w_dq"]
        cq = cq * jax.lax.rsqrt(jnp.mean(jnp.square(cq.astype(jnp.float32)),
                                         -1, keepdims=True) + cfg.norm_eps).astype(x.dtype)
        q = jnp.einsum("bsr,rhe->bshe", cq, p["w_uq"].astype(x.dtype))
    else:
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(x.dtype))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, m.rope_scaling)

    c = x @ p["w_dkv"]                                   # (B,S,r)
    cf = c.astype(jnp.float32)
    c = (cf * jax.lax.rsqrt(jnp.mean(jnp.square(cf), -1, keepdims=True)
                            + cfg.norm_eps) * p["kv_norm"].astype(jnp.float32)
         ).astype(x.dtype)
    kr = apply_rope((x @ p["w_kr"])[:, :, None, :], positions, cfg.rope_theta,
                    m.rope_scaling)
    kr = kr[:, :, 0, :]                                  # (B,S,dr)
    scale = mla_softmax_scale(cfg)

    q_offset = 0
    kv_len = None
    if cache is not None:
        c_all = jax.lax.dynamic_update_slice_in_dim(cache.c_kv, c.astype(cache.c_kv.dtype), cache.length, axis=1)
        kr_all = jax.lax.dynamic_update_slice_in_dim(cache.k_rope, kr.astype(cache.k_rope.dtype), cache.length, axis=1)
        new_cache = MLACache(c_all, kr_all, cache.length + S)
        c, kr = c_all.astype(x.dtype), kr_all.astype(x.dtype)
        kv_len = cache.length + S
        q_offset = cache.length
    else:
        new_cache = None

    if decode:
        # the query absorbs W_uk, the context leaves through W_uv: scores
        # and the weighted sum run against the latent cache itself, and no
        # per-head key or value is formed from it
        f32 = jnp.float32
        q_lat = jnp.einsum("bshe,rhe->bshr", q_nope.astype(f32),
                           p["w_uk"].astype(f32))
        s = (jnp.einsum("bshr,btr->bhst", q_lat, c.astype(f32))
             + jnp.einsum("bshe,bte->bhst", q_rope.astype(f32),
                          kr.astype(f32))) * scale
        valid = jnp.arange(c.shape[1]) < kv_len
        s = jnp.where(valid, s, NEG_INF)
        ctx = jnp.einsum("bhst,btr->bshr", jax.nn.softmax(s, axis=-1),
                         c.astype(f32))
        o = jnp.einsum("bshr,rhe->bshe", ctx.astype(x.dtype),
                       p["w_uv"].astype(x.dtype))
        return jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(x.dtype)), \
            new_cache

    k_nope = jnp.einsum("btr,rhe->bthe", c, p["w_uk"].astype(x.dtype))
    vv = jnp.einsum("btr,rhe->bthe", c, p["w_uv"].astype(x.dtype))
    T = k_nope.shape[1]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, :, None, :], (B, T, H, dr))], -1)
    qq = jnp.concatenate([q_nope, q_rope], -1)
    qq = shard(qq, "batch", None, "heads", None)
    k = shard(k, "batch", "kv_seq", "heads", None)
    vv = shard(vv, "batch", "kv_seq", "heads", None)

    o = attention(qq, k, vv, causal=True, impl=impl, chunk=cfg.attn_chunk,
                  q_offset=q_offset, kv_len=kv_len, scale=scale)
    out = jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(x.dtype))
    return out, new_cache


def init_mla_cache(cfg: ArchConfig, batch: int, max_len: int, dtype) -> MLACache:
    m = cfg.mla
    return MLACache(
        c_kv=jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        k_rope=jnp.zeros((batch, max_len, m.rope_head_dim), dtype),
        length=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Cross-attention (enc-dec / VLM)
# ---------------------------------------------------------------------------

class CrossCache(NamedTuple):
    k: jax.Array          # (B, T_src, KV, Dh) — precomputed from memory
    v: jax.Array


def cross_attention(p, cfg: ArchConfig, x: jax.Array,
                    memory: Optional[jax.Array] = None,
                    cache: Optional[CrossCache] = None,
                    impl: str = "chunked"):
    """K/V from `memory` (encoder output / image embeds) or from `cache`."""
    q = _project(p, cfg, x, "q")
    q = shard(q, "batch", None, "heads", None)
    if cache is None:
        assert memory is not None
        k = _project(p, cfg, memory, "k")
        v = _project(p, cfg, memory, "v")
        new_cache = CrossCache(k, v)
    else:
        k, v = cache.k.astype(x.dtype), cache.v.astype(x.dtype)
        new_cache = cache
    rep = kv_repeat_factor(cfg)
    k = shard(_expand_kv(k, rep), "batch", None, "heads" if rep > 1 else "kv_heads", None)
    v = shard(_expand_kv(v, rep), "batch", None, "heads" if rep > 1 else "kv_heads", None)
    o = attention(q, k, v, causal=False, impl=impl, chunk=cfg.attn_chunk)
    out = jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(x.dtype))
    return out, new_cache
