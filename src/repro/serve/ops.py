"""Serving as a placement-priced operator graph: ``prefill -> decode``
over the pipeline substrate, so the prefill->decode crossing is a real
:class:`~repro.core.costmodel.Link` hop and the KV cache is the state
the placement DP prices against ``mem_cap``.

Both ops are *host ops* (``Op.jit=False``) built around one
:class:`~repro.serve.engine.ServeEngine`: they call the engine's own
jitted ``_prefill``/``_decode`` executables, so the graph path is
bitwise-identical to ``ServeEngine._serve_wave`` (same executables, same
rng threading, same donated decode buffers) — the differential contract
``tests`` pin down. The KV cache crosses between them as the ``"kv"``
batch channel (a cache pytree, not a flat array): under a cloud-prefill/
edge-decode placement the orchestrator's wire round-trip compresses
exactly that channel with the KV codec ladder (``kv_int8`` /
``kv_latent``), which is what makes KV compression SLA-governed uplink
state.

``decode`` declares ``OperatorCost.downlink_ok``: its flow parent may
legitimately sit in the cloud and ship the cache *down* — the relaxed
closure relation (``OpGraph.closure_parent_indices``) admits the
``{decode}`` frontier and the evaluator prices the crossing instead of
marking it backhaul.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costmodel import OperatorCost
from repro.core.pipeline import Op, OpGraph
from repro.core.spans import span
from repro.launch.roofline import dl_operator_cost
from repro.models import model_zoo as zoo
from repro.serve.engine import ServeEngine
from repro.serve.sampling import sample


def _shape_tree_bytes(tree) -> float:
    return float(sum(
        int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(tree)))


def param_bytes(cfg) -> float:
    """Resident bytes of the model weights (no materialization)."""
    return _shape_tree_bytes(zoo.param_shapes(cfg))


def kv_cache_bytes(cfg, batch: int, max_len: int, src_len: int = 0) -> float:
    """Resident bytes of a full KV-cache pytree at ``(batch, max_len)``
    — the decode op's placement-priced state, computed from shapes only
    (``jax.eval_shape``), never allocated here."""
    shapes = jax.eval_shape(
        lambda: zoo.init_caches(cfg, batch, max_len, src_len))
    return _shape_tree_bytes(shapes)


def _model_extra_keys(cfg) -> Tuple[str, ...]:
    if cfg.family == "vlm":
        return ("patches",)
    if cfg.family == "encdec":
        return ("frames",)
    return ()


def prefill_op(engine: ServeEngine, *, prompt_len: int,
               cost: Optional[OperatorCost] = None) -> Op:
    """The prefill stage as a host op: run the engine's jitted prefill,
    sample the first token (identical rng threading to
    ``ServeEngine._serve_wave``), and emit the KV cache on the ``"kv"``
    channel — the state the downlink ships."""
    cfg = engine.cfg
    extras = _model_extra_keys(cfg)

    def fn(state, batch):
        model_in = {"tokens": batch["tokens"],
                    **{k: batch[k] for k in extras}}
        rows, length = batch["tokens"].shape
        with span("serve.prefill", rows=rows, len=length):
            logits, caches = engine._prefill(engine.params, model_in)
            rng, sub = jax.random.split(batch["rng"])
            tok = sample(logits[:, 0, :cfg.vocab_size], sub,
                         engine.sampling)
        return state, {"kv": caches, "tok": tok, "rng": rng}

    if cost is None:
        B = engine.batch_size
        kvb = kv_cache_bytes(cfg, B, engine.max_len)
        cost = dl_operator_cost(
            "prefill", cfg, phase="prefill", batch=B, seq_len=prompt_len,
            param_bytes=param_bytes(cfg),
            # the KV cache is what this op emits downstream, per event
            out_bytes_per_event=kvb / B,
            state_bytes=param_bytes(cfg))
    return Op("prefill", fn, cost, jit=False,
              reads=("tokens", "rng") + extras,
              writes=("kv", "tok", "rng"))


def decode_op(engine: ServeEngine, *, max_new_tokens: int,
              cost: Optional[OperatorCost] = None) -> Op:
    """The decode loop as a host op: consume the ``"kv"`` channel and the
    first sampled token, loop the engine's donated-buffer jitted decode
    step ``max_new_tokens - 1`` times, and emit every request's token
    row as ``"out_tokens"`` (B, max_new_tokens).

    Declares ``downlink_ok`` (the KV cache may arrive over the
    cloud->edge downlink) and deletes its inputs: the decode executable
    donates the cache buffers, so the stale references must not survive
    in the channel env."""
    cfg = engine.cfg
    steps = max_new_tokens - 1

    def fn(state, batch):
        caches, tok, rng = batch["kv"], batch["tok"], batch["rng"]
        toks = [tok]
        for i in range(steps):
            with span("serve.decode_step", i=i):
                tok, caches, rng = engine._decode(
                    engine.params, caches, tok[:, None], rng)
            toks.append(tok)
        with span("serve.gather"):
            out = jnp.stack(toks, axis=1).astype(jnp.int32)
        return state, {"out_tokens": out, "rng": rng}

    if cost is None:
        B = engine.batch_size
        pb = param_bytes(cfg)
        kvb = kv_cache_bytes(cfg, B, engine.max_len)
        cost = dl_operator_cost(
            "decode", cfg, phase="decode", batch=B, seq_len=0,
            new_tokens=max_new_tokens, param_bytes=pb,
            out_bytes_per_event=4.0 * max_new_tokens,
            # the decode-resident state the DP prices against mem_cap:
            # the weights AND the live KV cache
            state_bytes=pb + kvb, downlink_ok=True)
    return Op("decode", fn, cost, jit=False,
              reads=("kv", "tok", "rng"),
              writes=("out_tokens", "rng"), deletes=("kv", "tok"))


def serving_graph(engine: ServeEngine, *, prompt_len: int,
                  max_new_tokens: int) -> OpGraph:
    """The split serving graph ``prefill -> decode`` (one flow edge —
    the KV-cache hop placement prices per link). Frontiers are ``{}``,
    ``{prefill, decode}``, ``{prefill}`` and — via decode's
    ``downlink_ok`` — ``{decode}``: the cloud-prefill/edge-decode split."""
    return OpGraph([
        prefill_op(engine, prompt_len=prompt_len),
        decode_op(engine, max_new_tokens=max_new_tokens),
    ])


def serve_wave_batch(engine: ServeEngine, prompts, *, seed: int = 0):
    """The channel env for one wave of ``prompts`` (list of int 1-D
    arrays): left-padded tokens exactly as ``ServeEngine._serve_wave``
    builds them, family extras, and the wave rng."""
    cfg = engine.cfg
    B = len(prompts)
    S = max(len(p) for p in prompts)
    toks = np.zeros((B, S), np.int32)
    for i, p in enumerate(prompts):
        toks[i, S - len(p):] = np.asarray(p, np.int32)
    batch = {"tokens": jnp.asarray(toks),
             "rng": jax.random.PRNGKey(seed)}
    if cfg.family == "vlm":
        batch["patches"] = jnp.zeros(
            (B, cfg.frontend_len, cfg.frontend_dim), jnp.float32)
    if cfg.family == "encdec":
        batch["frames"] = jnp.zeros((B, S, cfg.frontend_dim), jnp.float32)
    return batch
