"""Plain reference of a Qwen2 decoder (arXiv:2407.10671; the keys of the
model's ``config.json``), and the seeded weights both the served model and
this reference are made from.

Forward pass: token embedding; per layer, RMSNorm, grouped-query attention
with biased q/k/v projections, rotary position embedding (rotate-half,
``rope_theta``) and a causal softmax, a residual add, RMSNorm, a SiLU-gated
MLP and a residual add; a final RMSNorm and logits against the tied
embedding. It runs in float32 with matrix products at the highest
precision, over whole sequences with no cache, one layer at a time under a
scan. It imports nothing of the program.

The served model sees each prompt left-padded with token 0 to the longest
prompt of its wave, with no padding mask: the padded row is the prompt it
serves, and so it is the prompt given here.

``control="fp8"`` is the same pass with every weight and every matrix
product's activation rounded to float8 (e4m3, one scale per tensor): the
step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"L": int(cfg["num_hidden_layers"]), "d": d, "h": h,
            "kv": int(cfg["num_key_value_heads"]),
            "dh": int(cfg.get("head_dim") or d // h),
            "f": int(cfg["intermediate_size"]),
            "V": int(cfg["vocab_size"]),
            "Vp": -(-int(cfg["vocab_size"]) // 256) * 256}


def shapes(cfg: dict) -> Dict[str, tuple]:
    n = dims(cfg)
    L, d, h, kv, dh, f = n["L"], n["d"], n["h"], n["kv"], n["dh"], n["f"]
    return {"embed": (n["Vp"], d), "final_norm": (d,),
            "attn_norm": (L, d), "wq": (L, d, h, dh), "bq": (L, h, dh),
            "wk": (L, d, kv, dh), "bk": (L, kv, dh), "wv": (L, d, kv, dh),
            "bv": (L, kv, dh), "wo": (L, h, dh, d), "mlp_norm": (L, d),
            "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)}


def weights(cfg: dict, seed: int, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Seeded weights in one jitted call on the device: norm scales are
    one, every other leaf is normal with the config's ``initializer_range``
    as its std (leaf ``i`` of :func:`shapes`, in its order, from
    ``fold_in(PRNGKey(seed), i)``)."""
    sh = shapes(cfg)
    std = float(cfg["initializer_range"])

    @jax.jit
    def make(key):
        out = {}
        for i, (name, s) in enumerate(sh.items()):
            if name.endswith("norm"):
                out[name] = jnp.ones(s, dtype)
            else:
                out[name] = (jax.random.normal(jax.random.fold_in(key, i), s,
                                               jnp.float32) * std).astype(dtype)
        return out

    return make(jax.random.PRNGKey(seed))


def _fp8(x):
    """Round to float8 e4m3 against the tensor's own peak, back in f32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh))
    ang = pos[:, :, None].astype(jnp.float32) * jnp.asarray(freqs)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _logits_at(w, tokens, at, cfg_items, control):
    """Logits at positions ``at`` (B, P) of rows ``tokens`` (B, T)."""
    cfg = dict(cfg_items)
    n = dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    q8 = _fp8 if control == "fp8" else (lambda a: a)
    f32 = lambda a: q8(a.astype(jnp.float32))
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    causal = jnp.tril(jnp.ones((T, T), bool))
    g = n["h"] // n["kv"]

    def layer(x, p):
        h = q8(_rms(x, p["attn_norm"].astype(jnp.float32), eps))
        q = mm("btd,dhe->bthe", h, f32(p["wq"])) + p["bq"].astype(jnp.float32)
        k = mm("btd,dhe->bthe", h, f32(p["wk"])) + p["bk"].astype(jnp.float32)
        v = mm("btd,dhe->bthe", h, f32(p["wv"])) + p["bv"].astype(jnp.float32)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        q = q.reshape(B, T, n["kv"], g, n["dh"])
        s = mm("btkgd,bskd->bkgts", q8(q), q8(k)) / math.sqrt(n["dh"])
        s = jnp.where(causal, s, -jnp.inf)
        a = mm("bkgts,bskd->btkgd", q8(jax.nn.softmax(s, -1)), q8(v))
        o = mm("bthe,hed->btd", q8(a.reshape(B, T, n["h"], n["dh"])),
               f32(p["wo"]))
        x = x + o
        h = q8(_rms(x, p["mlp_norm"].astype(jnp.float32), eps))
        u = jax.nn.silu(mm("btd,df->btf", h, f32(p["w_gate"]))) \
            * mm("btd,df->btf", h, f32(p["w_up"]))
        return x + mm("btf,fd->btd", q8(u), f32(p["w_down"])), None

    stack = {k: v for k, v in w.items() if k not in ("embed", "final_norm")}
    x = w["embed"].astype(jnp.float32)[tokens]
    x, _ = jax.lax.scan(layer, x, stack)
    x = jnp.take_along_axis(x, at[:, :, None], axis=1)
    x = q8(_rms(x, w["final_norm"].astype(jnp.float32), eps))
    return mm("bpd,vd->bpv", x, f32(w["embed"]))[:, :, :n["V"]]


def served_gaps(cfg: dict, seed: int, rows: Sequence[dict],
                control: Optional[str] = None, batch: int = 4) -> dict:
    """For each row ``{"prompt": padded prompt ids, "served": served ids}``,
    one pass over the prompt and the served tokens gives, at each served
    position, the gap between the best logit and the served token's logit
    (``"served"``), and under ``control`` the gap of the token the control
    puts first there (``"control"``)."""
    w = weights(cfg, seed)
    T = max(len(r["prompt"]) + len(r["served"]) for r in rows)
    P = max(len(r["served"]) for r in rows)
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    out = {"served": [], "control": [] if control else None}
    for i in range(0, len(rows), batch):
        real = list(rows[i:i + batch])
        chunk = real + [real[0]] * (batch - len(real))
        toks = np.zeros((batch, T), np.int32)
        at = np.zeros((batch, P), np.int32)
        for j, r in enumerate(chunk):
            seq = np.concatenate([r["prompt"], r["served"][:-1]])
            toks[j, :len(seq)] = seq
            at[j] = np.minimum(len(r["prompt"]) - 1 + np.arange(P),
                               len(seq) - 1)
        args = (w, jnp.asarray(toks), jnp.asarray(at), items)
        ref = np.asarray(_logits_at(*args, None))
        pick = (np.asarray(_logits_at(*args, control)).argmax(-1)
                if control else None)
        for j, r in enumerate(real):
            L = len(r["served"])
            row = ref[j, :L]
            best = row.max(-1)
            out["served"].append(best - row[np.arange(L), r["served"]])
            if control:
                out["control"].append(best - row[np.arange(L), pick[j, :L]])
    return out
