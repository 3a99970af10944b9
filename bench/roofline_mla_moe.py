"""The yardstick for a decoder with multi-head latent attention and a
held share of routed experts (DeepSeek-V2 config keys, with
``n_routed_experts`` the experts held here and ``router_experts`` the
router's width): the weights and operations a token needs and the bytes a
decode step reads, from shapes alone. Later changes to the program cannot
move these."""

from __future__ import annotations

from bench.roofline import padded_vocab


def _n(cfg: dict, key: str) -> int:
    return int(cfg[key])


def attn_params(cfg: dict) -> int:
    """Latent attention's projections: q (no q LoRA), the latent and the
    rope key, the latent's expansion into keys and values, the output."""
    d, h, r = (_n(cfg, k) for k in ("hidden_size", "num_attention_heads",
                                    "kv_lora_rank"))
    dn, dr, dv = (_n(cfg, k) for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                       "v_head_dim"))
    return d * h * (dn + dr) + d * r + d * dr + r * h * (dn + dv) + h * dv * d


def expert_params(cfg: dict) -> int:
    """One routed expert (SiLU-gated)."""
    return 3 * _n(cfg, "hidden_size") * _n(cfg, "moe_intermediate_size")


def always_params(cfg: dict, layer: int) -> int:
    """Weights layer ``layer`` multiplies every token by: attention and
    the dense MLP, or attention, the router and the shared experts."""
    d = _n(cfg, "hidden_size")
    if layer < _n(cfg, "first_k_dense_replace"):
        return attn_params(cfg) + 3 * d * _n(cfg, "intermediate_size")
    shared = _n(cfg, "n_shared_experts") * _n(cfg, "moe_intermediate_size")
    return attn_params(cfg) + d * _n(cfg, "router_experts") + 3 * d * shared


def moe_layers(cfg: dict) -> int:
    return _n(cfg, "num_hidden_layers") - _n(cfg, "first_k_dense_replace")


def head_params(cfg: dict) -> int:
    return padded_vocab(cfg) * _n(cfg, "hidden_size")


def expert_evaluations(cfg: dict) -> float:
    """Held experts a token is evaluated by, on average: its top-k land
    on the held share at the share's size (6 x 8 / 64 = 0.75)."""
    return _n(cfg, "num_experts_per_tok") * _n(cfg, "n_routed_experts") \
        / _n(cfg, "router_experts")


def token_flops(cfg: dict) -> float:
    """Operations of one token's pass through the layers (weights times
    two; attention's score and value products not counted)."""
    layers = sum(always_params(cfg, i)
                 for i in range(_n(cfg, "num_hidden_layers")))
    experts = moe_layers(cfg) * expert_evaluations(cfg) * expert_params(cfg)
    return 2.0 * (layers + experts)


def serve_flops(cfg: dict, prompt_tokens: int, output_tokens: int) -> float:
    """Operations to serve one request: every prompt token and every output
    token but the last passes through the layers; every output token takes
    one evaluation of the head."""
    passes = prompt_tokens + max(output_tokens - 1, 0)
    return token_flops(cfg) * passes + 2.0 * head_params(cfg) * output_tokens


def touched(cfg: dict, rows: int) -> float:
    """Chance that at least one of ``rows`` tokens routes to a given
    expert: 1 - (1 - k / E) ** rows (0.793 for 16 rows, top-6 of 64)."""
    return 1.0 - (1.0 - _n(cfg, "num_experts_per_tok")
                  / _n(cfg, "router_experts")) ** rows


def weight_bytes(cfg: dict, expert_share: float = 1.0,
                 itemsize: int = 2) -> float:
    """Bytes of the weights held, norms, embedding and head too, with each
    held expert counted at ``expert_share`` (1: all of them)."""
    d, r = _n(cfg, "hidden_size"), _n(cfg, "kv_lora_rank")
    L = _n(cfg, "num_hidden_layers")
    layers = sum(always_params(cfg, i) + 2 * d + r for i in range(L))
    experts = moe_layers(cfg) * _n(cfg, "n_routed_experts") \
        * expert_params(cfg) * expert_share
    return itemsize * (layers + experts + 2 * head_params(cfg) + d)


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> float:
    """Latent cache one position holds over all layers: the latent and the
    rope key."""
    return itemsize * _n(cfg, "num_hidden_layers") \
        * (_n(cfg, "kv_lora_rank") + _n(cfg, "qk_rope_head_dim"))


def decode_step_bytes(cfg: dict, batch: int, cache_len: int) -> float:
    """Bytes one decode step of ``batch`` rows needs: every weight once,
    each held expert at the chance that a row routes to it, of the
    embedding only the ``batch`` rows it looks up, and the live latent
    cache (``cache_len`` positions of each row)."""
    unread = 2 * (padded_vocab(cfg) - batch) * _n(cfg, "hidden_size")
    return weight_bytes(cfg, touched(cfg, batch)) - unread \
        + batch * cache_len * cache_bytes_per_token(cfg)
