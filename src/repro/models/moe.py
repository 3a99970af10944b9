"""Mixture-of-Experts: a dropless expert layer over the experts it holds.

The router keeps all ``num_experts`` outputs (softmax, greedy top-k, gates
renormalised only where ``norm_topk_prob`` says so, times
``routed_scaling_factor``). The layer holds the contiguous range
``MoEConfig.held`` of the routed experts (all of them by default; under
expert parallelism a chip holds its share) and computes, for every
assignment routed to a held expert, that expert's gated output: the
assignments are sorted by expert and run through one grouped product
(``kernels.ops.grouped_matmul``) per projection, or, for a few tokens,
every held expert runs densely with the unrouted pairs' gates zero.
Nothing is dropped and there is no capacity. What experts held elsewhere
would add is not computed here. Shared experts run densely, once.

Returns the load-balancing auxiliary loss alongside the output.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.ops import grouped_matmul
from repro.models.layers import _act
from repro.models.params import Spec


def moe_specs(cfg: ArchConfig):
    e = cfg.moe
    d, f = cfg.d_model, e.d_ff_expert
    lo, hi = e.held
    glu = cfg.mlp_act.endswith("_glu")
    sp = {
        "router": Spec((d, e.num_experts), ("embed", "experts"), scale=0.02),
        "w_up": Spec((hi - lo, d, f), ("experts", "embed", "ff")),
        "w_down": Spec((hi - lo, f, d), ("experts", "ff", "embed")),
    }
    if glu:
        sp["w_gate"] = Spec((hi - lo, d, f), ("experts", "embed", "ff"))
    if e.num_shared:
        fs = e.d_ff_shared or e.num_shared * f
        sp["shared"] = {
            "w_up": Spec((d, fs), ("embed", "ff")),
            "w_down": Spec((fs, d), ("ff", "embed")),
        }
        if glu:
            sp["shared"]["w_gate"] = Spec((d, fs), ("embed", "ff"))
    return sp


def route(router, cfg: ArchConfig, xf: jax.Array, rng=None):
    """xf: (t, D) -> (probs (t, E) fp32, gates (t, K) fp32, ids (t, K))."""
    e = cfg.moe
    logits = (xf @ router.astype(xf.dtype)).astype(jnp.float32)
    if e.router_jitter and rng is not None:
        logits = logits + e.router_jitter * jax.random.normal(rng, logits.shape)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, e.top_k)
    if e.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return probs, gates * e.routed_scaling_factor, ids


# at most this many (token, held expert) pairs are computed densely: about
# a row tile of the grouped product, which would visit a tile for each
# group the tokens reach
DENSE_PAIRS = 256


def held_experts(p, cfg: ArchConfig, xf: jax.Array, gates, ids) -> jax.Array:
    """Sum over each token's assignments to the held experts of gate times
    expert output. xf: (t, D); gates, ids: (t, K) -> (t, D). A few tokens
    (a decode step) go through every held expert densely, at a cost that
    does not depend on the routing; more go through the grouped product."""
    e = cfg.moe
    lo, hi = e.held
    n = hi - lo
    t, D = xf.shape
    K = ids.shape[-1]
    dt = xf.dtype
    local = ids - lo                                               # (t, K)
    held = (local >= 0) & (local < n)
    if t * n <= DENSE_PAIRS:
        gate = jnp.sum(jnp.where(local[..., None] == jnp.arange(n),
                                 gates[..., None], 0.0), axis=1)   # (t, n)
        up = jnp.einsum("td,edf->tef", xf, p["w_up"].astype(dt))
        if "w_gate" in p:
            h = _act(cfg.mlp_act, jnp.einsum(
                "td,edf->tef", xf, p["w_gate"].astype(dt))) * up
        else:
            h = _act(cfg.mlp_act, up)
        y = jnp.einsum("tef,efd->ted", h, p["w_down"].astype(dt))
        return jnp.einsum("ted,te->td", y, gate.astype(dt))
    # held assignments first, grouped by expert; the rest sort last and
    # fall outside every group
    key = jnp.where(held, local, n).reshape(-1)                    # (t*K,)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=n + 1)[:n]
    in_group = held.reshape(-1)[order]
    # rows outside every group are left undefined by the grouped product:
    # zero them going in and select them out coming back, so that nothing
    # they hold reaches the sum or a gradient
    xs = jnp.where(in_group[:, None], xf[order // K], 0)           # (t*K, D)
    up = grouped_matmul(xs, p["w_up"].astype(dt), sizes)
    if "w_gate" in p:
        h = _act(cfg.mlp_act, grouped_matmul(
            xs, p["w_gate"].astype(dt), sizes)) * up
    else:
        h = _act(cfg.mlp_act, up)
    ys = grouped_matmul(h, p["w_down"].astype(dt), sizes)          # (t*K, D)
    g = gates.reshape(-1)[order].astype(dt)
    ys = jnp.where(in_group[:, None], ys * g[:, None], 0)
    # back to assignment order (gathers only), then sum each token's K
    return ys[jnp.argsort(order)].reshape(t, K, D).sum(axis=1)


def apply_moe(p, cfg: ArchConfig, x: jax.Array,
              rng=None) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,D) -> (out (B,S,D), aux_loss scalar)."""
    e = cfg.moe
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    with jax.named_scope("s2ce.moe.route"):
        probs, gates, ids = route(p["router"], cfg, xf, rng)
        # load-balance aux (Switch): E * sum_e f_e * P_e
        fe = jnp.mean(jax.nn.one_hot(ids[:, 0], e.num_experts,
                                     dtype=jnp.float32), axis=0)
        aux = e.aux_loss_coef * e.num_experts * jnp.sum(
            fe * jnp.mean(probs, axis=0))
    with jax.named_scope("s2ce.moe.experts"):
        y = held_experts(p, cfg, xf, gates, ids)
        if e.num_shared:
            sp = p["shared"]
            if "w_gate" in sp:
                hs = _act(cfg.mlp_act, xf @ sp["w_gate"].astype(xf.dtype)) * (
                    xf @ sp["w_up"].astype(xf.dtype))
            else:
                hs = _act(cfg.mlp_act, xf @ sp["w_up"].astype(xf.dtype))
            y = y + hs @ sp["w_down"].astype(xf.dtype)
    return y.reshape(B, S, D), aux
