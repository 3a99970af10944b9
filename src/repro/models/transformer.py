"""Unified LM assembly for every assigned architecture family.

A config compiles to a *layer plan*: a short unscanned prefix plus a
periodic pattern of per-layer "slots" scanned over stacked parameters
(keeps HLO size independent of depth — essential for 88–100-layer dry-run
compiles). Slot mixers: attn | mla | cross | attn_cross | mamba | rwkv;
slot MLPs: dense | moe | rwkv_cm | none.

Families:
  dense/moe      -> decoder-only stack
  rwkv/ssm       -> recurrent mixers, O(1) decode state
  hybrid (jamba) -> periodic (7 mamba + 1 attn), alternating MoE
  vlm            -> gated cross-attention layer every N (image stub memory)
  encdec         -> bidirectional encoder stack + decoder with cross-attn
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist import current_mesh, shard
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    apply_mlp, apply_norm, cot_cast, dtype_of, embed_specs, embed_tokens,
    lm_logits, mlp_specs, norm_specs, sincos_pos_embed,
)
from repro.models.params import Spec


# ---------------------------------------------------------------------------
# Layer plans
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Slot:
    mixer: str            # attn|mla|cross|attn_cross|mamba|rwkv
    mlp: str              # dense|moe|rwkv_cm|none
    causal: bool = True
    gated: bool = False   # vlm-style gated cross layer


def _slot_list(cfg: ArchConfig, n_layers: int, decoder: bool = True):
    moe_mask = cfg.moe_layer_mask(n_layers)
    attn_mask = cfg.attn_layer_mask() if cfg.family == "hybrid" else None
    cross_mask = cfg.cross_layer_mask() if cfg.family == "vlm" else None
    slots = []
    for i in range(n_layers):
        mlp = "moe" if (moe_mask[i] and cfg.moe.num_experts) else "dense"
        if cfg.family == "rwkv":
            slots.append(Slot("rwkv", "rwkv_cm"))
        elif cfg.family == "ssm":
            slots.append(Slot("mamba", mlp))
        elif cfg.family == "hybrid":
            slots.append(Slot("attn" if attn_mask[i] else "mamba", mlp))
        elif cfg.family == "vlm":
            slots.append(Slot("cross", mlp, gated=True) if cross_mask[i]
                         else Slot("attn", mlp))
        elif cfg.family == "encdec" and decoder:
            slots.append(Slot("attn_cross", mlp))
        elif cfg.family == "encdec":
            slots.append(Slot("attn", mlp, causal=False))
        else:
            slots.append(Slot("mla" if cfg.mla is not None else "attn", mlp))
    return slots


def layer_plan(cfg: ArchConfig, n_layers: int, decoder: bool = True):
    """-> (prefix_slots, repeat, pattern_slots)."""
    slots = _slot_list(cfg, n_layers, decoder)
    for prefix in range(0, min(4, n_layers)):
        rest = slots[prefix:]
        if not rest:
            continue
        for period in range(1, min(len(rest), 16) + 1):
            if len(rest) % period:
                continue
            if all(rest[i] == rest[i % period] for i in range(len(rest))):
                if len(rest) // period == 1 and period > 1:
                    continue  # prefer true repetition over one fat block
                return tuple(slots[:prefix]), len(rest) // period, tuple(rest[:period])
    return tuple(slots), 0, ()


# ---------------------------------------------------------------------------
# Per-slot specs
# ---------------------------------------------------------------------------

def _mixer_specs(cfg: ArchConfig, slot: Slot):
    if slot.mixer in ("attn", "cross"):
        return attn.attn_specs(cfg)
    if slot.mixer == "mla":
        return attn.mla_specs(cfg)
    if slot.mixer == "attn_cross":
        return {"self": attn.attn_specs(cfg), "cross": attn.attn_specs(cfg)}
    if slot.mixer == "mamba":
        return ssm_mod.mamba_specs(cfg)
    if slot.mixer == "rwkv":
        return rwkv_mod.rwkv_time_mix_specs(cfg)
    raise ValueError(slot.mixer)


def _mlp_specs(cfg: ArchConfig, slot: Slot):
    if slot.mlp == "dense":
        return mlp_specs(cfg)
    if slot.mlp == "moe":
        return moe_mod.moe_specs(cfg)
    if slot.mlp == "rwkv_cm":
        return rwkv_mod.rwkv_channel_mix_specs(cfg)
    return {}


def slot_specs(cfg: ArchConfig, slot: Slot):
    sp = {"norm1": norm_specs(cfg), "mixer": _mixer_specs(cfg, slot)}
    if slot.mixer == "attn_cross":
        sp["norm_cross"] = norm_specs(cfg)
    if slot.mlp != "none":
        sp["norm2"] = norm_specs(cfg)
        sp["mlp"] = _mlp_specs(cfg, slot)
    if slot.gated:
        sp["gate_attn"] = Spec((), (), "zeros")
        sp["gate_mlp"] = Spec((), (), "zeros")
    return sp


def _stack_specs(tree, n: int):
    return jax.tree.map(
        lambda s: Spec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale, s.const),
        tree, is_leaf=lambda x: isinstance(x, Spec))


def model_specs(cfg: ArchConfig):
    sp: dict = {"embed": embed_specs(cfg), "final_norm": norm_specs(cfg)}
    if cfg.family == "encdec":
        pre_e, rep_e, pat_e = layer_plan(cfg, cfg.enc_layers, decoder=False)
        pre_d, rep_d, pat_d = layer_plan(cfg, cfg.dec_layers, decoder=True)
        sp["enc"] = {
            "prefix": [slot_specs(cfg, s) for s in pre_e],
            "stack": _stack_specs([slot_specs(cfg, s) for s in pat_e], rep_e),
            "final_norm": norm_specs(cfg),
        }
        sp["dec"] = {
            "prefix": [slot_specs(cfg, s) for s in pre_d],
            "stack": _stack_specs([slot_specs(cfg, s) for s in pat_d], rep_d),
        }
    else:
        pre, rep, pat = layer_plan(cfg, cfg.n_layers)
        sp["prefix"] = [slot_specs(cfg, s) for s in pre]
        sp["stack"] = _stack_specs([slot_specs(cfg, s) for s in pat], rep)
    if cfg.frontend != "none":
        sp["frontend_proj"] = Spec((cfg.frontend_dim, cfg.d_model),
                                   ("embed", None))
    return sp


# ---------------------------------------------------------------------------
# Slot application
# ---------------------------------------------------------------------------

def apply_slot(p, cfg: ArchConfig, slot: Slot, x, *, positions, memory,
               cache, impl: str):
    """Returns (x, update, aux). With a cache, ``update`` maps each of the
    slot's cache entries to what replaces it, except that a
    position-indexed cache (``"kv"``) gives only its new positions, which
    the caller writes in place (``_write_update``)."""
    aux = jnp.zeros((), jnp.float32)
    # norm on the seq-sharded residual (fp32 internals stay 1/16-seq),
    # then gather the bf16 norm output for the TP matmuls
    h = apply_norm(p["norm1"], cfg, x)
    h = shard(h, "batch", None, "embed")
    new_cache = cache

    if slot.mixer == "attn":
        o, kv = attn.self_attention(
            p["mixer"], cfg, h, positions=positions,
            cache=cache.get("kv") if cache else None,
            causal=slot.causal, impl=impl)
        new_cache = {"kv": kv} if cache else None
    elif slot.mixer == "mla":
        o, kv = attn.mla_attention(
            p["mixer"], cfg, h, positions=positions,
            cache=cache.get("kv") if cache else None, impl=impl)
        new_cache = {"kv": kv} if cache else None
    elif slot.mixer == "cross":
        o, cc = attn.cross_attention(
            p["mixer"], cfg, h, memory=memory,
            cache=cache.get("cross") if cache and cache.get("cross") is not None else None,
            impl=impl)
        new_cache = {"cross": cc} if cache else None
    elif slot.mixer == "attn_cross":
        o, kv = attn.self_attention(
            p["mixer"]["self"], cfg, h, positions=positions,
            cache=cache.get("kv") if cache else None,
            causal=slot.causal, impl=impl)
        o = shard(o, "batch", "seq_sp", "embed")   # reduce-scatter form
        x = x + o
        h2 = apply_norm(p["norm_cross"], cfg, x)
        h2 = shard(h2, "batch", None, "embed")
        o, cc = attn.cross_attention(
            p["mixer"]["cross"], cfg, h2, memory=memory,
            cache=cache.get("cross") if cache and cache.get("cross") is not None else None,
            impl=impl)
        new_cache = {"kv": kv, "cross": cc} if cache else None
    elif slot.mixer == "mamba":
        st = cache.get("mamba") if cache else None
        if st is not None and x.shape[1] == 1:
            o, st = ssm_mod.mamba_decode_step(p["mixer"], cfg, h, st)
        else:
            o, st = ssm_mod.mamba_mixer(p["mixer"], cfg, h, st)
        new_cache = {"mamba": st} if cache else None
    elif slot.mixer == "rwkv":
        st = cache.get("rwkv") if cache else None
        o, tm_shift, wkv = rwkv_mod.rwkv_time_mix(p["mixer"], cfg, h, st, impl=impl)
        cm_prev = st.cm_shift if st is not None else None
    else:
        raise ValueError(slot.mixer)

    if slot.gated:
        o = o * jnp.tanh(p["gate_attn"].astype(o.dtype))
    if slot.mixer == "rwkv":
        o = shard(o, "batch", "seq_sp", "embed")
        x = x + o
        h = apply_norm(p["norm2"], cfg, x)
        h = shard(h, "batch", None, "embed")
        st_in = st if st is not None else None
        o2, cm_shift = rwkv_mod.rwkv_channel_mix(
            p["mlp"], cfg, h,
            rwkv_mod.RWKVState(tm_shift, cm_prev, wkv) if st_in is not None else None)
        x = x + o2
        if cache:
            new_cache = {"rwkv": rwkv_mod.RWKVState(tm_shift, cm_shift, wkv)}
        x = shard(cot_cast(x), "batch", "seq_sp", "embed")
        return x, new_cache, aux

    o = shard(o, "batch", "seq_sp", "embed")       # reduce-scatter form
    x = x + o
    if slot.mlp != "none":
        h = apply_norm(p["norm2"], cfg, x)
        h = shard(h, "batch", None, "embed")
        if slot.mlp == "moe":
            o2, a = moe_mod.apply_moe(p["mlp"], cfg, h)
            aux = aux + a
        else:
            o2 = apply_mlp(p["mlp"], cfg, h)
        if slot.gated:
            o2 = o2 * jnp.tanh(p["gate_mlp"].astype(o2.dtype))
        o2 = shard(o2, "batch", "seq_sp", "embed")
        x = x + o2
    x = shard(cot_cast(x), "batch", "seq_sp", "embed")
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Stack runner (scan over stacked params / caches)
# ---------------------------------------------------------------------------

def _remat_wrap(fn, cfg: ArchConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots
    else:
        pol = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=pol)


def _constrain_layer_params(lp, axes):
    """Pin each sliced per-layer param to its sharded layout inside the scan
    body. Without this the SPMD partitioner may reshard (all-gather) the
    ENTIRE stacked parameter tree at the while-loop boundary — 100s of GB
    for frontier-scale stacks (observed on jamba-398B, see EXPERIMENTS.md)."""
    if axes is None:
        return lp
    from repro.dist import shard_param
    return jax.tree.map(
        lambda x, ax: shard_param(x, ax[1:]) if hasattr(x, "ndim") and
        x.ndim + 1 == len(ax) else x, lp, axes)


@jax.custom_vjp
def _diff_barrier(tree):
    """optimization_barrier with a differentiation rule (jax<=0.4.37 has
    none): barrier the primals forward and the cotangents backward, so the
    gather-serialization effect holds in both passes."""
    return jax.lax.optimization_barrier(tree)


def _diff_barrier_fwd(tree):
    return _diff_barrier(tree), None


def _diff_barrier_bwd(_, ct):
    return (jax.lax.optimization_barrier(ct),)


_diff_barrier.defvjp(_diff_barrier_fwd, _diff_barrier_bwd)

# names the barrier in the lowered program, present exactly where it
# engages (listed in ``repro.core.spans``)
PARAM_BARRIER = "s2ce.scan.param_barrier"


def _gathers_params() -> bool:
    """Whether the active mesh can all-gather a layer's weights: a mesh of
    more than one device."""
    mesh = current_mesh()
    return mesh is not None and mesh.size > 1


def _layer_params(lp, axes):
    """One layer's parameter slice as the scan body reads it. Where a mesh
    of more than one device shards the stack, a barrier plus per-leaf pins
    keep the slice's FSDP all-gather inside the loop, so XLA cannot hoist
    a gather of the whole stack out of the scan. Otherwise the slice is
    returned untouched and the layer reads its weights in place from the
    stacked operand: behind the barrier XLA copies every layer's slice
    out of the stack on every pass."""
    if not _gathers_params():
        return lp
    with jax.named_scope(PARAM_BARRIER):
        lp = _diff_barrier(lp)
    return _constrain_layer_params(lp, axes)


# names the write of each layer's new positions into the carried cache
# stack, present exactly where the scan carries a position-indexed cache
# (listed in ``repro.core.spans``)
CACHE_WRITE = "s2ce.scan.cache_write"


def _positional(cache) -> bool:
    """A cache that grows by writing new positions at its length."""
    return isinstance(cache, (attn.KVCache, attn.MLACache))


def _write_update(cache, update):
    """A slot's cache after ``apply_slot``: new positions appended to each
    position-indexed cache, every other state replaced."""
    return {k: attn.append(cache[k], u) if _positional(cache.get(k)) else u
            for k, u in update.items()}


def _write_layer(stack, new, i):
    """A stacked position-indexed cache with layer ``i``'s new positions
    written at that layer's length: one dynamic_update_slice a field, so
    no layer's whole slice is written back."""
    at = stack.length[i]
    fields = [jax.lax.dynamic_update_slice(
        a, u.astype(a.dtype)[None], (i, 0, at) + (0,) * (a.ndim - 3))
        for a, u in zip(stack, new)]
    length = jax.lax.dynamic_update_index_in_dim(
        stack.length, at + new[0].shape[1], i, 0)
    return type(stack)(*fields, length)


def run_stack(params, cfg: ArchConfig, pattern, x, *, positions, memory,
              caches, impl, stack_axes=None):
    """params: stacked slot-param list; caches: stacked cache trees or None.

    Position-indexed caches (KV, latent) ride the scan's carry: layer ``i``
    reads its slice of the stack and writes only its new positions back,
    in place. Every other state (recurrent states, cross caches) is
    replaced whole, so it enters as ``xs`` and leaves as ``ys``."""

    def body(x, layer_params, layer_caches):
        aux = jnp.zeros((), jnp.float32)
        new_caches = []
        for i, slot in enumerate(pattern):
            if i and _gathers_params():
                # serialize weight-gathers across unrolled slots: slot i+1's
                # FSDP all-gather must wait for slot i's output, otherwise
                # every slot's full weights are live simultaneously
                with jax.named_scope(PARAM_BARRIER):
                    x, layer_params = _diff_barrier((x, layer_params))
            c = layer_caches[i] if layer_caches is not None else None
            x, nc, a = apply_slot(layer_params[i], cfg, slot, x,
                                  positions=positions, memory=memory,
                                  cache=c, impl=impl)
            aux = aux + a
            new_caches.append(nc)
        return x, new_caches, aux

    body = _remat_wrap(body, cfg)

    if caches is None:
        def scan_body(x, lp):
            x, _, aux = body(x, _layer_params(lp, stack_axes), None)
            return x, aux
        x, auxs = jax.lax.scan(scan_body, x, params)
        return x, None, jnp.sum(auxs)

    from repro.models.model_zoo import constrain_caches
    stacks = [{k: c for k, c in slot.items() if _positional(c)}
              for slot in caches]
    states = [{k: c for k, c in slot.items() if not _positional(c)}
              for slot in caches]
    n = jax.tree.leaves(params)[0].shape[0]

    def scan_body(carry, xs):
        x, stacks = carry
        lp, lc, i = xs
        layer = [dict(st, **jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), sk))
            for sk, st in zip(stacks, lc)]
        x, upd, aux = body(x, _layer_params(lp, stack_axes), layer)
        with jax.named_scope(CACHE_WRITE):
            stacks = constrain_caches(
                [{k: _write_layer(c, u[k], i) for k, c in sk.items()}
                 for sk, u in zip(stacks, upd)])
        rest = [{k: v for k, v in u.items() if k not in sk}
                for sk, u in zip(stacks, upd)]
        return (x, stacks), (rest, aux)

    (x, stacks), (rest, auxs) = jax.lax.scan(
        scan_body, (x, stacks), (params, states, jnp.arange(n)))
    return x, [dict(r, **sk) for r, sk in zip(rest, stacks)], jnp.sum(auxs)


def run_prefix(params, cfg: ArchConfig, slots, x, *, positions, memory,
               caches, impl):
    aux = jnp.zeros((), jnp.float32)
    new_caches = []
    for i, slot in enumerate(slots):
        c = caches[i] if caches is not None else None
        x, upd, a = apply_slot(params[i], cfg, slot, x, positions=positions,
                               memory=memory, cache=c, impl=impl)
        new_caches.append(_write_update(c, upd) if c is not None else None)
        aux = aux + a
    return x, (new_caches if caches is not None else None), aux


# ---------------------------------------------------------------------------
# Frontend stubs
# ---------------------------------------------------------------------------

def stack_axes_for(cfg: ArchConfig, which: str = "stack"):
    """Logical-axes tree for the scanned layer stack (sharding pins)."""
    from repro.models import params as pmod
    sp = model_specs(cfg)
    node = sp
    for k in which.split("/"):
        node = node[k]
    return pmod.axes_of(node)


def frontend_memory(params, cfg: ArchConfig, batch: dict):
    """Project stubbed modality embeddings into d_model memory tokens."""
    if cfg.frontend == "none":
        return None
    key = "frames" if cfg.frontend == "audio_frames" else "patches"
    emb = batch[key]
    mem = emb.astype(dtype_of(cfg.compute_dtype)) @ params["frontend_proj"].astype(
        dtype_of(cfg.compute_dtype))
    return shard(mem, "batch", None, "embed")


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _positions(B, S, offset=0):
    return jnp.arange(S)[None, :] + jnp.asarray(offset).reshape(-1, 1)


def forward_lm(params, cfg: ArchConfig, batch: dict, *, impl: str = "chunked"):
    """Training/eval forward. Returns (logits fp32, aux_loss)."""
    if cfg.family == "encdec":
        return _forward_encdec(params, cfg, batch, impl=impl)
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_tokens(params["embed"], cfg, tokens)
    if cfg.pos_embed == "sincos":
        x = x + sincos_pos_embed(S, cfg.d_model).astype(x.dtype)[None]
    memory = frontend_memory(params, cfg, batch)
    pre, rep, pat = layer_plan(cfg, cfg.n_layers)
    positions = _positions(B, S)
    x, _, aux1 = run_prefix(params["prefix"], cfg, pre, x,
                            positions=positions, memory=memory, caches=None,
                            impl=impl)
    aux2 = jnp.zeros((), jnp.float32)
    if rep:
        x, _, aux2 = run_stack(params["stack"], cfg, pat, x,
                               positions=positions, memory=memory,
                               caches=None, impl=impl,
                               stack_axes=stack_axes_for(cfg))
    x = apply_norm(params["final_norm"], cfg, x)
    return lm_logits(params["embed"], cfg, x), aux1 + aux2


def _forward_encdec(params, cfg: ArchConfig, batch: dict, *, impl="chunked"):
    mem_in = frontend_memory(params, cfg, batch)        # (B,Se,D)
    Se = mem_in.shape[1]
    enc_x = mem_in + sincos_pos_embed(Se, cfg.d_model).astype(mem_in.dtype)[None]
    pre, rep, pat = layer_plan(cfg, cfg.enc_layers, decoder=False)
    pos_e = _positions(enc_x.shape[0], Se)
    enc_x, _, _ = run_prefix(params["enc"]["prefix"], cfg, pre, enc_x,
                             positions=pos_e, memory=None, caches=None, impl=impl)
    if rep:
        enc_x, _, _ = run_stack(params["enc"]["stack"], cfg, pat, enc_x,
                                positions=pos_e, memory=None, caches=None,
                                impl=impl,
                                stack_axes=stack_axes_for(cfg, "enc/stack"))
    memory = apply_norm(params["enc"]["final_norm"], cfg, enc_x)

    tgt = batch["tokens"]
    B, Sd = tgt.shape
    x = embed_tokens(params["embed"], cfg, tgt)
    if cfg.pos_embed == "sincos":
        x = x + sincos_pos_embed(Sd, cfg.d_model).astype(x.dtype)[None]
    pre, rep, pat = layer_plan(cfg, cfg.dec_layers, decoder=True)
    pos_d = _positions(B, Sd)
    x, _, aux1 = run_prefix(params["dec"]["prefix"], cfg, pre, x,
                            positions=pos_d, memory=memory, caches=None,
                            impl=impl)
    aux2 = jnp.zeros((), jnp.float32)
    if rep:
        x, _, aux2 = run_stack(params["dec"]["stack"], cfg, pat, x,
                               positions=pos_d, memory=memory, caches=None,
                               impl=impl,
                               stack_axes=stack_axes_for(cfg, "dec/stack"))
    x = apply_norm(params["final_norm"], cfg, x)
    return lm_logits(params["embed"], cfg, x), aux1 + aux2


def lm_loss(params, cfg: ArchConfig, batch: dict, *, impl: str = "chunked"):
    """Next-token cross-entropy (+ MoE aux). Returns (loss, metrics)."""
    logits, aux = forward_lm(params, cfg, batch, impl=impl)
    tokens = batch["tokens"]
    labels = tokens[:, 1:]
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    mask = mask[:, 1:].astype(jnp.float32) if mask is not None else jnp.ones_like(nll)
    ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return ce + aux, {"ce": ce, "aux": aux}
