"""The yardstick for shares of a peak: the table of peaks, and the
operations and bytes each measured kernel or program needs, computed from
shapes alone. Later changes to the program cannot move these."""

from __future__ import annotations

import pathlib
from typing import Dict

from bench.cells import load_json

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    table = load_json(PEAKS_FILE)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return {k: float(v) for k, v in table[device_kind].items()}


# -- a dense decoder-only transformer (config keys as in HF config.json) -----

def padded_vocab(cfg: dict, multiple: int = 256) -> int:
    v = int(cfg["vocab_size"])
    return -(-v // multiple) * multiple


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim")
               or cfg["hidden_size"] // cfg["num_attention_heads"])


def layer_matmul_params(cfg: dict) -> int:
    """Weights one decoder layer multiplies by (attention projections and
    the gated MLP); biases and norm scales left out."""
    d, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    hq = int(cfg["num_attention_heads"]) * head_dim(cfg)
    hkv = int(cfg["num_key_value_heads"]) * head_dim(cfg)
    return d * hq + 2 * d * hkv + hq * d + 3 * d * f


def head_params(cfg: dict) -> int:
    """Weights of the output head (the tied embedding when tied)."""
    return padded_vocab(cfg) * int(cfg["hidden_size"])


def matmul_params(cfg: dict) -> int:
    """All weights a token's forward pass multiplies by."""
    return int(cfg["num_hidden_layers"]) * layer_matmul_params(cfg) \
        + head_params(cfg)


def serve_flops(cfg: dict, prompt_tokens: int, output_tokens: int) -> float:
    """Model operations to serve one request: every prompt token and every
    output token but the last passes through the layers; every output
    token takes one evaluation of the head (prefill evaluates the head at
    the last prompt position only). Attention's score and value products
    are not counted."""
    layers = int(cfg["num_hidden_layers"]) * layer_matmul_params(cfg)
    passes = prompt_tokens + max(output_tokens - 1, 0)
    return 2.0 * layers * passes + 2.0 * head_params(cfg) * output_tokens


def weight_bytes(cfg: dict, itemsize: int = 2) -> float:
    """Bytes of every weight a decode step reads, norms and biases too."""
    d = int(cfg["hidden_size"])
    hq = int(cfg["num_attention_heads"]) * head_dim(cfg)
    hkv = int(cfg["num_key_value_heads"]) * head_dim(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * d + hq + 2 * hkv
    return itemsize * (int(cfg["num_hidden_layers"]) * per_layer
                       + head_params(cfg) + d)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> float:
    """Cache bytes one position holds over all layers (keys and values)."""
    return itemsize * 2 * int(cfg["num_hidden_layers"]) \
        * int(cfg["num_key_value_heads"]) * head_dim(cfg)


def decode_step_bytes(cfg: dict, batch: int, cache_len: int) -> float:
    """Bytes one decode step needs: every weight once, and the live cache
    (``cache_len`` positions of each of ``batch`` rows)."""
    return weight_bytes(cfg) + batch * cache_len * kv_bytes_per_token(cfg)
