"""DeepSeek-V2-style latent attention and a held share of routed experts
against the plain reference (``bench/configs/deepseek_v2_lite_ref.py``),
on the CPU at a small size with seeded weights, in float32.

Tolerances are relative to the largest reference value compared. Float32
against the reference's float32 at the highest precision differs by
reassociation alone, about 1e-6 of the values here; 1e-4 leaves room for
that and is some hundred times tighter than bfloat16's rounding (2**-8 of
each operand), which the first test shows fails it.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells

TOL = 1e-4

DSV2 = cells.load_json(cells.BENCH_DIR / "configs"
                       / "deepseek_v2_lite_serve.json")
REF = cells.load_module(cells.BENCH_DIR / "configs" / "deepseek_v2_lite_ref.py")
DRV = cells.load_module(cells.BENCH_DIR / "drivers" / "serve_mla_moe.py")


def small(**kw):
    """1 dense and 2 routed layers; 8 router outputs, 4 held, top-2."""
    base = dict(DSV2, hidden_size=64, intermediate_size=128,
                num_hidden_layers=3, num_attention_heads=4,
                num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=16, v_head_dim=16, moe_intermediate_size=32,
                router_experts=8, n_routed_experts=4, num_experts_per_tok=2,
                n_shared_experts=2, vocab_size=512, initializer_range=0.1,
                torch_dtype="float32")
    base.update(kw)
    return base


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def served_logits(cfg, w, tokens, prompt_len, dtype=None):
    """The program's logits at positions prompt_len-1 .. T-1: prefill of
    the prompt, then decode steps through the latent cache fed the row's
    own next tokens."""
    from repro.models import model_zoo as zoo
    arch = DRV.arch_config(cfg)
    params = DRV.program_params(w, arch)
    if dtype is not None:
        arch = arch.with_overrides(param_dtype=dtype, compute_dtype=dtype,
                                   kv_cache_dtype=dtype)
        params = jax.tree.map(lambda a: a.astype(jnp.dtype(dtype)), params)
    V = int(cfg["vocab_size"])
    logits, caches = zoo.prefill(params, arch,
                                 {"tokens": jnp.asarray(tokens[:, :prompt_len])},
                                 max_len=tokens.shape[1])
    out = [logits[:, 0, :V]]
    for i in range(prompt_len, tokens.shape[1]):
        logits, caches = zoo.decode_step(params, arch, caches,
                                         jnp.asarray(tokens[:, i:i + 1]))
        out.append(logits[:, 0, :V])
    return np.stack([np.asarray(o, np.float32) for o in out], 1)


def test_prefill_then_latent_decode_matches_the_reference_forward():
    cfg = small()
    w = REF.weights(cfg, 7)
    tokens = np.random.default_rng(0).integers(0, 512, (2, 14)).astype(
        np.int32)
    P = 6
    at = np.broadcast_to(np.arange(P - 1, 14), (2, 14 - P + 1))
    want = np.asarray(REF.logits(cfg, w, tokens, at))
    assert rel_err(served_logits(cfg, w, tokens, P), want) < TOL
    # the tolerance is tight enough that bfloat16 fails it
    assert rel_err(served_logits(cfg, w, tokens, P, "bfloat16"),
                   want) > 10 * TOL


def test_latent_decode_equals_the_expanded_form():
    from repro.models import attention, model_zoo as zoo
    cfg = small()
    arch = DRV.arch_config(cfg)
    params = DRV.program_params(REF.weights(cfg, 3), arch)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 512, (3, 9)),
                         jnp.int32)
    _, caches = zoo.prefill(params, arch, {"tokens": tokens}, max_len=16)
    cache = caches["prefix"][0]["kv"]
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 1, 64), jnp.float32)
    pos = jnp.full((3, 1), 9)
    p = params["prefix"][0]["mixer"]
    run = lambda decode: attention._mla(p, arch, x, positions=pos,
                                        cache=cache, impl="chunked",
                                        decode=decode)
    (lat, c_lat), (exp, c_exp) = run(True), run(False)
    assert rel_err(lat, exp) < TOL
    np.testing.assert_array_equal(c_lat.c_kv, c_exp.c_kv)
    assert int(c_lat.length) == 10


def test_programs_name_their_latent_attention_and_expert_work():
    """The named scopes that ``repro.core.spans`` lists reach the compiled
    programs' op metadata, where a trace viewer shows them."""
    from repro.models import model_zoo as zoo
    cfg = small()
    arch = DRV.arch_config(cfg)
    params = DRV.program_params(REF.weights(cfg, 3), arch)
    tokens = jnp.zeros((2, 5), jnp.int32)
    prefill = jax.jit(lambda p, t: zoo.prefill(p, arch, {"tokens": t},
                                               max_len=8))
    caches = prefill(params, tokens)[1]
    decode = jax.jit(lambda p, c, t: zoo.decode_step(p, arch, c, t))
    hlo = {"prefill": prefill.lower(params, tokens).compile().as_text(),
           "decode": decode.lower(params, caches, tokens[:, :1]).compile()
           .as_text()}
    for prog, scope in (("prefill", "mla.prefill"),
                        ("decode", "mla.decode_latent")):
        assert f"s2ce.{scope}/" in hlo[prog], scope
        for moe_scope in ("moe.route", "moe.experts"):
            assert f"s2ce.{moe_scope}/" in hlo[prog], moe_scope
    assert "s2ce.mla.prefill/" not in hlo["decode"]


def test_yarn_frequencies_and_softmax_scale_are_the_published_ones():
    from repro.configs.base import get_config
    from repro.models import attention, layers
    full = get_config("deepseek-v2-lite-16b")
    got = layers.yarn_freqs(64, 1e4, full.mla.rope_scaling)
    want = REF.yarn_inv_freq(DSV2)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = layers.rope_freqs(64, 1e4)
    # the ramp runs over frequency indices 10..23: below it the plain
    # frequencies, past it those divided by the factor, 40
    np.testing.assert_array_equal(got[:11], plain[:11])
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    assert np.all(got[11:23] < plain[11:23])
    scale = attention.mla_softmax_scale(full)
    assert scale == pytest.approx(REF.softmax_scale(DSV2), rel=1e-12)
    assert scale == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2, rel=1e-12)
    assert REF.rope_mscale(DSV2) == 1.0
    assert full.norm_eps == 1e-6


def _rope_before(x, positions, theta):
    """The rope the program had before YaRN, written out."""
    d = x.shape[-1]
    freqs = jnp.asarray(1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32)
                                          / d)))
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_plain_rope_is_bitwise_unchanged(dtype):
    from repro.models import layers
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, 128), dtype)
    pos = jnp.broadcast_to(jnp.arange(1600, 1640)[None], (2, 40))
    got = jax.jit(layers.apply_rope, static_argnums=2)(x, pos, 1e6)
    want = jax.jit(_rope_before, static_argnums=2)(x, pos, 1e6)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert str(jax.make_jaxpr(lambda a, b: layers.apply_rope(a, b, 1e6))(
        x, pos)) == str(jax.make_jaxpr(lambda a, b: _rope_before(a, b, 1e6))(
            x, pos))


# -- the expert layer -----------------------------------------------------------

def _layer(experts=16, top_k=6, seed=0, **moe):
    """A routed layer's weights (reference names) and the program's config
    holding all of its experts."""
    cfg = small(router_experts=experts, n_routed_experts=experts,
                num_experts_per_tok=top_k, **moe)
    w = REF.weights(dict(cfg, num_hidden_layers=2), seed)["moe"]
    p = jax.tree.map(lambda a: a[0], w)
    return cfg, DRV.arch_config(cfg), p


def _program_params(p, lo=0, hi=None):
    return {"router": p["router"], "w_gate": p["e_gate"][lo:hi],
            "w_up": p["e_up"][lo:hi], "w_down": p["e_down"][lo:hi],
            "shared": {"w_gate": p["s_gate"], "w_up": p["s_up"],
                       "w_down": p["s_down"]}}


def _held(arch, first, n):
    return arch.with_overrides(moe=dataclasses.replace(
        arch.moe, first_held=first, num_held=n))


# (batch, length): a decode-sized call goes through every held expert
# densely, a prefill-sized one through the grouped product
SIZES = pytest.mark.parametrize("shape", [(2, 7), (4, 80)],
                                ids=["dense", "grouped"])


@SIZES
def test_eight_shares_sum_to_the_uncut_layer(shape):
    """Eight chips of 2 experts each: their outputs, with the shared
    experts (which every chip computes alike) counted once, add up to the
    reference's layer holding all 16."""
    from repro.models import moe
    cfg, arch, p = _layer()
    h = jax.random.normal(jax.random.PRNGKey(4), shape + (64,), jnp.float32)
    parts = [moe.apply_moe(_program_params(p, 2 * j, 2 * j + 2),
                           _held(arch, 2 * j, 2), h)[0] for j in range(8)]
    shared = (jax.nn.silu(h @ p["s_gate"]) * (h @ p["s_up"])) @ p["s_down"]
    total = sum(parts) - 7 * shared
    want = REF.moe_mlp(cfg, p, h)
    assert rel_err(total, want) < TOL
    uncut, _ = moe.apply_moe(_program_params(p), arch, h)
    assert rel_err(uncut, want) < TOL


@SIZES
def test_every_token_routed_to_one_expert_loses_nothing(shape):
    """The router sends every token's first choice to expert 0; a capacity
    dispatch would drop most of them, this layer computes them all."""
    from repro.models import moe
    cfg, arch, p = _layer(experts=8, top_k=2, seed=1)
    p = dict(p, router=p["router"].at[:, 0].set(1.0))
    h = jax.random.normal(jax.random.PRNGKey(5), shape + (64,)) + 3.0
    _, _, ids = moe.route(p["router"], arch, h.reshape(-1, 64))
    assert bool(jnp.all(ids[:, 0] == 0))
    share = _held(arch, 0, 4)
    got, _ = moe.apply_moe(_program_params(p, 0, 4), share, h)
    held = dict(p, **{k: p[k][:4] for k in ("e_gate", "e_up", "e_down")})
    want = REF.moe_mlp(dict(cfg, n_routed_experts=4), held, h)
    assert rel_err(got, want) < TOL


def test_grouped_product_kernel_matches_in_interpret_mode(monkeypatch):
    """The Pallas grouped product a TPU takes, run in interpret mode: the
    held layer's output and its gradients match the XLA path's, the rows
    outside every group (unrouted assignments) contributing nothing."""
    from repro.models import moe
    cfg, arch, p = _layer(experts=8, top_k=2, seed=2)
    share, pp = _held(arch, 2, 4), _program_params(p, 2, 6)
    xf = jax.random.normal(jax.random.PRNGKey(7), (300, 64))
    _, gates, ids = moe.route(pp["router"], share, xf)

    w = {k: pp[k] for k in ("w_gate", "w_up", "w_down")}

    def loss(w, xf):
        return jnp.sum(moe.held_experts(w, share, xf, gates, ids) ** 2)

    want = jax.value_and_grad(loss, argnums=(0, 1))(w, xf)
    monkeypatch.setenv("JAX_PALLAS_INTERPRET", "1")
    got = jax.value_and_grad(loss, argnums=(0, 1))(w, xf)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert rel_err(a, b) < TOL


@pytest.mark.parametrize("norm,scale", [(False, 1.0), (True, 1.0),
                                        (False, 2.5)])
def test_gates_follow_norm_topk_prob_and_the_scaling_factor(norm, scale):
    from repro.models import moe
    _, arch, p = _layer(experts=8, top_k=3)
    arch = arch.with_overrides(moe=dataclasses.replace(
        arch.moe, norm_topk_prob=norm, routed_scaling_factor=scale))
    xf = jax.random.normal(jax.random.PRNGKey(6), (10, 64))
    probs, gates, ids = moe.route(p["router"], arch, xf)
    raw = jnp.take_along_axis(jax.nn.softmax(xf @ p["router"], -1), ids, -1)
    want = raw / raw.sum(-1, keepdims=True) if norm else raw
    np.testing.assert_allclose(gates, scale * want, rtol=1e-6)
    if not norm:
        assert float(jnp.max(gates.sum(-1))) < scale


def test_param_counts_count_the_held_share():
    from repro.configs.base import get_config
    from repro.models import model_zoo as zoo
    full = get_config("deepseek-v2-lite-16b")
    cut = _held(full, 0, 8)
    d, r, L = 2048, 512, 27
    router, norms = 26 * d * 64, L * (2 * d + r) + d
    assert cut.param_counts()["total"] + router + norms == \
        zoo.param_count(cut)
    assert 3.110e9 < zoo.param_count(cut) < 3.112e9     # 3.111 B held
