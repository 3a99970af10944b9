"""Plain reference of DeepSeek-V2-Lite (arXiv:2405.04434; the keys of the
model's ``config.json``) cut to one chip's share of its expert layers, and
the seeded weights both the served model and this reference are made from.

Forward pass: token embedding; per layer, RMSNorm, multi-head latent
attention, a residual add, RMSNorm, the layer's MLP and a residual add; a
final RMSNorm and logits against the untied head. Latent attention
(``q_lora_rank`` null): q = h Wq split into a no-rope and a rope part; the
latent c = RMSNorm(h W_dkv) (``rms_norm_eps``); keys are c W_uk beside one
shared rope key h W_kr, values c W_uv; rope with YaRN scaling
(``DeepseekV2YarnRotaryEmbedding``: inverse frequencies ramped between the
plain ones and those divided by ``factor`` over the correction range that
``beta_fast`` and ``beta_slow`` give, cos and sin times mscale /
mscale_all_dim) on the rope parts; softmax scale (nope + rope dims) ** -0.5
times ``yarn_get_mscale(factor, mscale_all_dim)`` squared; causal softmax;
the heads through Wo. The first ``first_k_dense_replace`` layers have a
SiLU-gated MLP; the rest route: softmax over the router's
``router_experts`` outputs, the greedy top ``num_experts_per_tok``, gates
renormalised only under ``norm_topk_prob``, times ``routed_scaling_factor``.
This chip holds experts 0 .. ``n_routed_experts`` - 1 of them: each is
computed densely for every token and weighted by the gate the token gives
it (zero where it is not among the token's top-k); what the experts held
elsewhere would add is left out, as in the program. The shared experts are
one SiLU-gated MLP of ``n_shared_experts`` x ``moe_intermediate_size``.

Rope is computed rotate-half; with random weights that is the published
interleaved rope up to a fixed permutation of the rope weight columns.
It runs in float32 with matrix products at the highest precision, over
whole sequences with no cache, one layer at a time under a scan. It
imports nothing of the program.

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
    n_dense = int(cfg["first_k_dense_replace"])
    return {"L": int(cfg["num_hidden_layers"]), "dense": n_dense,
            "d": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]),
            "r": int(cfg["kv_lora_rank"]), "dn": int(cfg["qk_nope_head_dim"]),
            "dr": int(cfg["qk_rope_head_dim"]), "dv": int(cfg["v_head_dim"]),
            "F": int(cfg["intermediate_size"]),
            "f": int(cfg["moe_intermediate_size"]),
            "fs": int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"]),
            "E": int(cfg["router_experts"]), "n": int(cfg["n_routed_experts"]),
            "K": int(cfg["num_experts_per_tok"]), "V": int(cfg["vocab_size"]),
            "Vp": -(-int(cfg["vocab_size"]) // 256) * 256}


def _attn_shapes(n: dict, lead: int) -> Dict[str, tuple]:
    d, h, r = n["d"], n["h"], n["r"]
    return {"attn_norm": (lead, d), "wq": (lead, d, h, n["dn"] + n["dr"]),
            "w_dkv": (lead, d, r), "w_kr": (lead, d, n["dr"]),
            "kv_norm": (lead, r), "w_uk": (lead, r, h, n["dn"]),
            "w_uv": (lead, r, h, n["dv"]), "wo": (lead, h, n["dv"], d),
            "mlp_norm": (lead, d)}


def shapes(cfg: dict) -> Dict[str, Dict[str, tuple]]:
    """Leaves by group: the embedding and head, the leading dense layers
    and the routed layers, each group's layers stacked."""
    n = dims(cfg)
    d, nd, nm = n["d"], n["dense"], n["L"] - n["dense"]
    return {
        "top": {"embed": (n["Vp"], d), "head": (d, n["Vp"]),
                "final_norm": (d,)},
        "dense": {**_attn_shapes(n, nd), "w_gate": (nd, d, n["F"]),
                  "w_up": (nd, d, n["F"]), "w_down": (nd, n["F"], d)},
        "moe": {**_attn_shapes(n, nm), "router": (nm, d, n["E"]),
                "e_gate": (nm, n["n"], d, n["f"]),
                "e_up": (nm, n["n"], d, n["f"]),
                "e_down": (nm, n["n"], n["f"], d),
                "s_gate": (nm, d, n["fs"]), "s_up": (nm, d, n["fs"]),
                "s_down": (nm, n["fs"], d)},
    }


def weights(cfg: dict, seed: int) -> Dict[str, dict]:
    """Seeded weights in the config's ``torch_dtype``, in one jitted call
    on the device: norm scales are one, every other leaf is normal with
    the config's ``initializer_range`` as its std (leaf ``i`` of
    :func:`shapes`, groups and leaves in their order, from
    ``fold_in(PRNGKey(seed), i)``)."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    sh = shapes(cfg)
    std = float(cfg["initializer_range"])

    @jax.jit
    def make(key):
        out, i = {}, 0
        for group, leaves in sh.items():
            out[group] = {}
            for name, s in leaves.items():
                if name.endswith("norm"):
                    out[group][name] = jnp.ones(s, dtype)
                else:
                    out[group][name] = (jax.random.normal(
                        jax.random.fold_in(key, i), s, jnp.float32)
                        * std).astype(dtype)
                i += 1
        return out

    return make(jax.random.PRNGKey(seed))


def _fp8(x):
    """Round to float8 e4m3 against the tensor's own peak, back in f32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding.inv_freq`` for the rope head dim."""
    dim, base = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    factor = float(rs["factor"])
    pos = np.arange(0, dim, 2, dtype=np.float32) / dim
    freq_extra = 1.0 / (base ** pos)
    freq_inter = 1.0 / (factor * base ** pos)

    def correction_dim(rot):
        return (dim * math.log(float(rs["original_max_position_embeddings"])
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rs["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (freq_inter * (1 - mask) + freq_extra * mask).astype(np.float32)


def rope_mscale(cfg: dict) -> float:
    """What the YaRN cos and sin tables are multiplied by."""
    rs = cfg["rope_scaling"]
    return (yarn_get_mscale(float(rs["factor"]), float(rs["mscale"]))
            / yarn_get_mscale(float(rs["factor"]),
                              float(rs["mscale_all_dim"])))


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    scale = (int(cfg["qk_nope_head_dim"])
             + int(cfg["qk_rope_head_dim"])) ** -0.5
    if rs.get("mscale_all_dim"):
        scale *= yarn_get_mscale(float(rs["factor"]),
                                 float(rs["mscale_all_dim"])) ** 2
    return scale


def _rope(x, pos, inv_freq, mscale):
    """x: (B, T, ..., dr) rotate-half; pos: (B, T)."""
    ang = pos[:, :, None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    while cos.ndim < x.ndim:
        cos, sin = cos[:, :, None], sin[:, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


MM = functools.partial(jnp.einsum, precision=HIGHEST)


def _rounding(control):
    """(activation rounding, weight to float32 with that rounding)."""
    q8 = _fp8 if control == "fp8" else (lambda a: a)
    return q8, lambda a: q8(a.astype(jnp.float32))


def moe_mlp(cfg: dict, p: dict, h, control: Optional[str] = None):
    """A routed layer's MLP on its normed input ``h`` (B, T, d): the held
    experts, each weighted by the gate each token gives it, plus the
    shared experts."""
    n = dims(cfg)
    q8, f32 = _rounding(control)
    silu = jax.nn.silu
    probs = jax.nn.softmax(MM("btd,de->bte", h, f32(p["router"])), -1)
    top, ids = jax.lax.top_k(probs, n["K"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * float(cfg["routed_scaling_factor"])
    # the gate each token gives each held expert (0 .. n-1)
    gate = jnp.sum(jnp.where(ids[..., None] == jnp.arange(n["n"]),
                             top[..., None], 0.0), axis=-2)       # (B,T,n)
    u = silu(MM("btd,ndf->btnf", h, f32(p["e_gate"]))) \
        * MM("btd,ndf->btnf", h, f32(p["e_up"]))
    y = MM("btnf,nfd->btnd", q8(u), f32(p["e_down"]))
    us = silu(MM("btd,df->btf", h, f32(p["s_gate"]))) \
        * MM("btd,df->btf", h, f32(p["s_up"]))
    return jnp.sum(y * gate[..., None], axis=2) \
        + MM("btf,fd->btd", q8(us), f32(p["s_down"]))


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _logits_at(w, tokens, at, cfg_items, control):
    """Logits at positions ``at`` (B, P) of rows ``tokens`` (B, T)."""
    cfg = dict(cfg_items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    n = dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    inv_freq, msc, scale = yarn_inv_freq(cfg), rope_mscale(cfg), \
        softmax_scale(cfg)
    q8, f32 = _rounding(control)
    mm, silu = MM, jax.nn.silu
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    causal = jnp.tril(jnp.ones((T, T), bool))

    def attention(x, p):
        h = q8(_rms(x, p["attn_norm"].astype(jnp.float32), eps))
        q = mm("btd,dhe->bthe", h, f32(p["wq"]))
        q_nope, q_pe = q[..., :n["dn"]], q[..., n["dn"]:]
        c = q8(_rms(mm("btd,dr->btr", h, f32(p["w_dkv"])),
                    p["kv_norm"].astype(jnp.float32), eps))
        k_pe = mm("btd,de->bte", h, f32(p["w_kr"]))
        q_pe, k_pe = _rope(q_pe, pos, inv_freq, msc), \
            _rope(k_pe, pos, inv_freq, msc)
        k_nope = mm("btr,rhe->bthe", c, f32(p["w_uk"]))
        v = mm("btr,rhe->bthe", c, f32(p["w_uv"]))
        s = (mm("bshe,bthe->bhst", q8(q_nope), q8(k_nope))
             + mm("bshe,bte->bhst", q8(q_pe), q8(k_pe))) * scale
        s = jnp.where(causal, s, -jnp.inf)
        a = mm("bhst,bthe->bshe", q8(jax.nn.softmax(s, -1)), q8(v))
        x = x + mm("bshe,hed->bsd", q8(a), f32(p["wo"]))
        return x, q8(_rms(x, p["mlp_norm"].astype(jnp.float32), eps))

    def dense_layer(x, p):
        x, h = attention(x, p)
        u = silu(mm("btd,df->btf", h, f32(p["w_gate"]))) \
            * mm("btd,df->btf", h, f32(p["w_up"]))
        return x + mm("btf,fd->btd", q8(u), f32(p["w_down"])), None

    def moe_layer(x, p):
        x, h = attention(x, p)
        return x + moe_mlp(cfg, p, h, control), None

    x = w["top"]["embed"].astype(jnp.float32)[tokens]
    x, _ = jax.lax.scan(dense_layer, x, w["dense"])
    x, _ = jax.lax.scan(moe_layer, x, w["moe"])
    x = jnp.take_along_axis(x, at[:, :, None], axis=1)
    x = q8(_rms(x, w["top"]["final_norm"].astype(jnp.float32), eps))
    return mm("bpd,dv->bpv", x, f32(w["top"]["head"]))[:, :, :n["V"]]


def _items(cfg: dict) -> tuple:
    """The config's scalars and its rope scaling, hashable."""
    out = [(k, v) for k, v in cfg.items()
           if isinstance(v, (int, float, str, bool))]
    out.append(("rope_scaling", tuple(sorted(
        (k, v) for k, v in cfg["rope_scaling"].items()
        if isinstance(v, (int, float, str, bool))))))
    return tuple(sorted(out))


def logits(cfg: dict, w, tokens, at, control: Optional[str] = None):
    """Logits (B, P, vocab) at positions ``at`` of rows ``tokens``."""
    return _logits_at(w, jnp.asarray(tokens), jnp.asarray(at), _items(cfg),
                      control)


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
        ref = np.asarray(logits(cfg, w, toks, at))
        pick = (np.asarray(logits(cfg, w, toks, at, control)).argmax(-1)
                if control else None)
        for j, r in enumerate(real):
            L = len(r["served"])
            row = ref[j, :L]
            best = row.max(-1)
            out["served"].append(best - row[np.arange(L), r["served"]])
            if control:
                out["control"].append(best - row[np.arange(L), pick[j, :L]])
    return out
