"""Core layers: norms, rotary/sinusoidal positions, MLPs, embeddings.

All functions are pure; parameters are plain dicts materialized from Spec
trees (:mod:`repro.models.params`). Activation sharding annotations use
logical axes via :func:`repro.dist.shard`.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.dist import shard
from repro.models.params import Spec


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16, "int8": jnp.int8}[name]


@jax.custom_vjp
def cot_cast(x):
    """Identity whose BACKWARD casts the cotangent to the primal dtype.
    Without it, one fp32 contribution (e.g. a norm VJP) promotes the whole
    residual-stream cotangent chain to fp32 — 2x bytes on every backward
    collective and 2x bwd matmul width (EXPERIMENTS.md §Perf)."""
    return x


def _cot_cast_fwd(x):
    return x, jnp.zeros((0,), x.dtype)   # dtype token (residuals must be arrays)


def _cot_cast_bwd(token, ct):
    return (ct.astype(token.dtype),)


cot_cast.defvjp(_cot_cast_fwd, _cot_cast_bwd)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_specs(cfg: ArchConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    if cfg.norm_type == "layernorm":
        return {"scale": Spec((d,), ("embed",), "ones"),
                "bias": Spec((d,), ("embed",), "zeros")}
    return {"scale": Spec((d,), ("embed",), "ones")}


def apply_norm(p, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """Reductions in fp32; the normalized product drops to x.dtype BEFORE the
    scale multiply, so no fp32 tensor feeds downstream collectives (XLA-CPU
    does not sink converts below all-gathers; see EXPERIMENTS.md §Perf)."""
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = ((xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)).astype(x.dtype)
        y = y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = (xf * jax.lax.rsqrt(ms + cfg.norm_eps)).astype(x.dtype)
        y = y * p["scale"].astype(x.dtype)
    return y


def groupnorm_heads(scale, bias, x: jax.Array, n_heads: int, eps: float) -> jax.Array:
    """GroupNorm with one group per head over (..., H, hs) flattened input."""
    *lead, d = x.shape
    hs = d // n_heads
    xf = x.astype(jnp.float32).reshape(*lead, n_heads, hs)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y.reshape(*lead, d) * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Positional encodings
# ---------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, d_head, 2, dtype=np.float32) / d_head))


def yarn_mscale(factor: float, mscale: float) -> float:
    """``yarn_get_mscale`` of DeepSeek-V2's modeling code."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(d_head: int, theta: float, y) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies: the plain
    ones below the correction range, divided by ``y.factor`` above it, and
    a linear ramp between, at every position."""
    idx = np.arange(0, d_head, 2, dtype=np.float32) / d_head
    extra = 1.0 / (theta ** idx)
    inter = 1.0 / (y.factor * theta ** idx)

    def dim_of(rotations):
        return (d_head * math.log(y.original_max_position
                                  / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(y.beta_fast)), 0)
    high = min(math.ceil(dim_of(y.beta_slow)), d_head - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d_head // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1).astype(np.float32)
    return inter * ramp + extra * (1 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x: (B, S, H, Dh); positions: (B, S) or (S,). ``scaling``: a
    :class:`~repro.configs.base.YaRNConfig`, or None for plain rope."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta) if scaling is None
                        else yarn_freqs(d, theta, scaling))  # (d/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,d/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sincos_pos_embed(seq: int, d: int, offset: int = 0) -> jax.Array:
    pos = jnp.arange(offset, offset + seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-np.log(10000.0) / d))
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# ---------------------------------------------------------------------------
# MLP (dense feed-forward)
# ---------------------------------------------------------------------------

def mlp_specs(cfg: ArchConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp_act.endswith("_glu"):
        return {
            "w_gate": Spec((d, f), ("embed", "ff")),
            "w_up": Spec((d, f), ("embed", "ff")),
            "w_down": Spec((f, d), ("ff", "embed")),
        }
    return {
        "w_up": Spec((d, f), ("embed", "ff")),
        "b_up": Spec((f,), ("ff",), "zeros"),
        "w_down": Spec((f, d), ("ff", "embed")),
        "b_down": Spec((d,), ("embed",), "zeros"),
    }


def _act(name: str, x: jax.Array) -> jax.Array:
    if name.startswith("silu"):
        return jax.nn.silu(x)
    if name.startswith("gelu"):
        return jax.nn.gelu(x)
    if name == "relu":
        return jax.nn.relu(x)
    if name == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(name)


def apply_mlp(p, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """x: (B, S, D) -> (B, S, D)."""
    if cfg.mlp_act.endswith("_glu"):
        h = _act(cfg.mlp_act, x @ p["w_gate"]) * (x @ p["w_up"])
        h = shard(h, "batch", None, "ff")
        return h @ p["w_down"]
    h = _act(cfg.mlp_act, x @ p["w_up"] + p["b_up"].astype(x.dtype))
    h = shard(h, "batch", None, "ff")
    return h @ p["w_down"] + p["b_down"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Embeddings / LM head
# ---------------------------------------------------------------------------

def embed_specs(cfg: ArchConfig):
    V, d = cfg.padded_vocab, cfg.d_model
    sp = {"tok": Spec((V, d), ("vocab", "embed"), scale=1.0)}
    if not cfg.tie_embeddings:
        sp["head"] = Spec((d, V), ("embed", "vocab"))
    return sp


def embed_tokens(p, cfg: ArchConfig, tokens: jax.Array) -> jax.Array:
    x = p["tok"].astype(dtype_of(cfg.compute_dtype))[tokens]
    return shard(x, "batch", None, "embed")


def lm_logits(p, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """Final-norm'ed hidden -> (B, S, padded_vocab) fp32 logits (pads masked)."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, p["tok"].astype(x.dtype))
    else:
        logits = x @ p["head"]
    logits = logits.astype(jnp.float32)
    if cfg.logits_softcap:
        c = cfg.logits_softcap
        logits = c * jnp.tanh(logits / c)
    if cfg.padded_vocab != cfg.vocab_size:
        pad = cfg.padded_vocab - cfg.vocab_size
        mask = jnp.concatenate([jnp.zeros((cfg.vocab_size,), jnp.float32),
                                jnp.full((pad,), -1e30, jnp.float32)])
        logits = logits + mask
    return shard(logits, "batch", None, "vocab")
