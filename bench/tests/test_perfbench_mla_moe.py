"""The latent attention and held-expert serving cell: ``correct`` on the
CPU at a test size (the sound program passes, the float8 control fails,
and each fault the cell can have fails once), the yardstick's numbers at
the published sizes, and ``serve_mla_moe.py``'s check of the parameter
layout."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths  # noqa: F401
from bench import cells, harness, roofline_mla_moe

CELL = "deepseek_v2_lite.serve_saturated"
DSV2 = cells.load_json(cells.BENCH_DIR / "configs"
                       / "deepseek_v2_lite_serve.json")


def small(cfg, **kw):
    """The configuration at a test size: 1 dense and 2 routed layers,
    8 router outputs of which 4 held, top-2, one shared expert."""
    return dict(cfg, hidden_size=128, intermediate_size=256,
                num_hidden_layers=3, num_attention_heads=4,
                num_key_value_heads=4, kv_lora_rank=64, qk_nope_head_dim=32,
                qk_rope_head_dim=16, v_head_dim=32, moe_intermediate_size=64,
                router_experts=8, n_routed_experts=4, num_experts_per_tok=2,
                n_shared_experts=1, vocab_size=4096, **kw)


def serve_cell():
    """The cell at a test size, short prompts and long outputs. Weights at
    std 0.1 sharpen routing and attention so that each fault below shows
    in the served tokens; the program runs in float32, since at that
    sharpness bfloat16's rounding alone reads up to 0.29 on some seeds
    (the limit is set for the published widths, where it reads far less:
    ``PERF.md``)."""
    cell = cells.load_cell(CELL)
    cfg = small(cell.config, initializer_range=0.1, torch_dtype="float32")
    cfg["serve"] = dict(cfg["serve"], batch_size=4, max_len=40,
                        prompt_len=16, max_new_tokens=24)
    cell.config = cfg
    cell.traffic = dict(cell.traffic, rate=40.0, classes=[
        {"prompt_len": p, "output_len": o, "weight": 1}
        for p, o in ((4, 24), (8, 8), (12, 24), (16, 8))])
    return cell


def run(cell, seed=5, seconds=1.0):
    out = harness.run_cell(cell.name, seed, seconds, False,
                           time.perf_counter(), require_accelerator=False,
                           cell=cell)
    return out["result"]


def test_sound_run_is_correct_through_the_orchestrator(monkeypatch):
    from repro.models import attention
    latent = []
    real = attention._mla

    def spy(*a, **kw):
        latent.append(kw["decode"])
        return real(*a, **kw)

    monkeypatch.setattr(attention, "_mla", spy)
    res = run(serve_cell())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert True in latent and False in latent     # decode and prefill paths


# -- faults planted under the timed path --------------------------------------

def _capacity_dropping(p, cfg, xf, gates, ids):
    """A capacity dispatch (factor 1): an expert's assignments past
    t * k / E are dropped."""
    from repro.models import moe
    t, K = ids.shape
    E = cfg.moe.num_experts
    oh = jax.nn.one_hot(ids.reshape(-1), E, dtype=jnp.int32)
    pos = jnp.sum(jnp.cumsum(oh, 0) * oh, -1) - 1
    keep = (pos < max(1, t * K // E)).reshape(t, K)
    return moe.__dict__["_bench_real"](p, cfg, xf, jnp.where(keep, gates, 0),
                                       ids)


def _renormalised(router, cfg, xf, rng=None):
    from repro.models import moe
    probs, gates, ids = moe.__dict__["_bench_real"](router, cfg, xf, rng)
    return probs, gates / gates.sum(-1, keepdims=True), ids


def _no_yarn_scale(cfg):
    m = cfg.mla
    return (m.nope_head_dim + m.rope_head_dim) ** -0.5


def _cache_lost(self, params, caches, tokens, rng):
    from repro.serve.engine import ServeEngine
    lost = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x)
        if "c_kv" in jax.tree_util.keystr(path) else x, caches)
    return ServeEngine.__dict__["_bench_real"](self, params, lost, tokens, rng)


@pytest.mark.parametrize("where,name,fault", [
    ("repro.models.moe", "held_experts", _capacity_dropping),
    ("repro.models.moe", "route", _renormalised),
    ("repro.models.attention", "mla_softmax_scale", _no_yarn_scale),
    ("repro.serve.engine:ServeEngine", "_decode_fn", _cache_lost),
], ids=["capacity_drops", "gates_renormalised", "yarn_scale_missing",
        "latent_cache_lost"])
def test_fault_is_not_correct(monkeypatch, where, name, fault):
    import importlib
    mod_name, _, cls = where.partition(":")
    target = importlib.import_module(mod_name)
    if cls:
        target = getattr(target, cls)
    monkeypatch.setattr(target, "_bench_real", getattr(target, name),
                        raising=False)
    monkeypatch.setattr(target, name, fault)
    res = run(serve_cell())
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("seed", [1, 2])
def test_control_is_not_correct(seed):
    """The float8 control, read at the positions of random prompts and
    continuations at the published widths (one dense and one routed layer,
    a 16,384-token vocabulary), has its first choice fall below the
    reference's best by more than the cell's limit."""
    cell = cells.load_cell(CELL)
    cfg = dict(cell.config, num_hidden_layers=2, vocab_size=16384)
    rng = np.random.default_rng(seed)
    rows = [{"prompt": rng.integers(0, 16384, 24).astype(np.int32),
             "served": rng.integers(0, 16384, 24).astype(np.int32)}
            for _ in range(2)]
    gaps = cell.reference().served_gaps(cfg, seed, rows, control="fp8",
                                        batch=2)
    widest = float(max(g.max() for g in gaps["control"]))
    assert not harness.judge({"served_gap": widest},
                             cell.driver().limits(cell))[0]


# -- the yardstick -------------------------------------------------------------

def test_yardstick_at_published_sizes():
    assert roofline_mla_moe.weight_bytes(DSV2) == 6_221_978_624   # 6.222e9
    assert roofline_mla_moe.cache_bytes_per_token(DSV2) == 31_104
    assert roofline_mla_moe.touched(DSV2, 16) == pytest.approx(
        1 - (58 / 64) ** 16)
    assert roofline_mla_moe.expert_evaluations(DSV2) == 0.75
    step = roofline_mla_moe.decode_step_bytes(DSV2, 16, 1000)
    assert step - roofline_mla_moe.decode_step_bytes(DSV2, 16, 0) == \
        16 * 1000 * 31_104


def test_yardstick_weights_agree_with_the_program_tree():
    drv = cells.load_module(cells.BENCH_DIR / "drivers" / "serve_mla_moe.py")
    from repro.models import model_zoo as zoo
    shapes = zoo.param_shapes(drv.arch_config(DSV2))
    total = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in jax.tree.leaves(shapes))
    assert roofline_mla_moe.weight_bytes(DSV2) == total


def test_yardstick_flops_count_the_held_share():
    from repro.configs.base import get_config
    drv = cells.load_module(cells.BENCH_DIR / "drivers" / "serve_mla_moe.py")
    counts = drv.arch_config(DSV2).param_counts()
    router = 26 * 2048 * 64        # the program's count leaves the router out
    head = roofline_mla_moe.head_params(DSV2)
    assert roofline_mla_moe.token_flops(DSV2) == 2 * (
        counts["active"] - 2 * head + router)
    full = get_config("deepseek-v2-lite-16b").param_counts()["total"]
    assert 15.6e9 < full + router < 15.8e9      # 15.71 B published


# -- serve_mla_moe.py's layout check --------------------------------------------

def test_layout_check_takes_the_seeded_weights():
    drv = cells.load_module(cells.BENCH_DIR / "drivers" / "serve_mla_moe.py")
    cfg = small(DSV2, initializer_range=0.02)
    arch = drv.arch_config(cfg)
    w = cells.load_module(cells.BENCH_DIR / "configs"
                          / "deepseek_v2_lite_ref.py").weights(cfg, 3)
    tree = drv.program_params(w, arch)
    assert tree["stack"][0]["mlp"]["w_up"].shape == (2, 4, 128, 64)
    assert tree["prefix"][0]["mlp"]["w_up"].shape == (128, 256)
    bad = dict(w, moe=dict(w["moe"], e_up=w["moe"]["e_up"][:, :3]))
    with pytest.raises(ValueError, match="layout"):
        drv.program_params(bad, arch)


def test_a_program_without_an_expert_share_fails_before_any_weights(
        monkeypatch):
    drv = cells.load_module(cells.BENCH_DIR / "drivers" / "serve_mla_moe.py")
    cell = serve_cell()
    made = []
    monkeypatch.setattr(drv, "holds_a_share", lambda: False)
    monkeypatch.setattr(cell, "reference", lambda: made.append(1))
    with pytest.raises(RuntimeError, match="share"):
        drv.setup(cell, None)
    assert not made
