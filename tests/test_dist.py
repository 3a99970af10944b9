"""Unit tests for the repro.dist subsystem: mesh context, logical->spec
mapping, int8 compression, async checkpointing, and elastic policy."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding

from repro.dist import (
    axis_size, mesh_active, pin_params, shard, shard_param, use_mesh,
)
from repro.dist import checkpoint as ckpt
from repro.dist import elastic
from repro.dist.api import logical_to_spec
from repro.dist.compression import (
    compressed_allreduce_mean, dequantize_int8, ef_init, ef_roundtrip,
    ef_topk_roundtrip, int8_roundtrip, quantize_int8, topk_densify,
    topk_roundtrip, topk_sparsify,
)
from repro.dist.sharding import build_rules


# ---------------------------------------------------------------------------
# shard / axis_size / use_mesh
# ---------------------------------------------------------------------------

def test_shard_is_noop_outside_mesh():
    x = jnp.ones((4, 8))
    assert not mesh_active()
    assert shard(x, "batch", "embed") is x
    assert shard_param(x, ("embed", "ff")) is x
    assert pin_params({"w": x}, {"w": ("embed", "ff")})["w"] is x


def test_axis_size_defaults_to_one():
    assert axis_size("heads") == 1          # no mesh at all
    with use_mesh({"data": 2, "model": 2},
                  {"param": {}, "act": {"heads": ("model",)}}):
        assert axis_size("heads") == 2      # mapped logical axis
        assert axis_size("data") == 2       # physical axis by name
        assert axis_size("no_such_axis") == 1


def test_use_mesh_degrades_to_single_device():
    with use_mesh() as mesh:                # no mesh given at all
        assert mesh.devices.size == 1
        assert mesh_active()
        x = shard(jnp.ones((4, 4)), "batch", None)
        assert x.shape == (4, 4)
    assert not mesh_active()


def test_shard_applies_constraint_in_jit():
    rules = build_rules(recipe="tp_fsdp")
    with use_mesh({"data": 2, "model": 4}, rules):
        y = jax.jit(lambda x: shard(x, "batch", None, "ff"))(
            jnp.ones((4, 3, 8)))
        spec = y.sharding.spec
        assert spec[0] == "data" and spec[2] == "model"
        # non-dividing dim (3 % 4 != 0) must stay replicated, not crash
        z = jax.jit(lambda x: shard(x, "batch", None, "ff"))(
            jnp.ones((4, 3, 6)))
        # jax may trim trailing Nones from the spec; just require that the
        # ff dim landed on no mesh axis
        assert "model" not in tuple(z.sharding.spec)


# ---------------------------------------------------------------------------
# logical_to_spec
# ---------------------------------------------------------------------------

class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape

    @property
    def axis_names(self):
        return tuple(self.shape)


def test_logical_to_spec_divisibility():
    mesh = _FakeMesh({"data": 2, "model": 4})
    rules = {"batch": ("data", "model")}
    # 8 divides by 2*4 -> both axes
    assert logical_to_spec(("batch",), rules, mesh, (8,))[0] == ("data", "model")
    # 6 divides by 2 only -> prefix
    assert logical_to_spec(("batch",), rules, mesh, (6,))[0] == "data"
    # 5 divides by nothing -> replicated
    assert logical_to_spec(("batch",), rules, mesh, (5,))[0] is None


def test_logical_to_spec_never_reuses_mesh_axes():
    mesh = _FakeMesh({"model": 4})
    rules = {"heads": ("model",), "ff": ("model",)}
    spec = logical_to_spec(("heads", "ff"), rules, mesh, (8, 8))
    assert spec[0] == "model" and spec[1] is None


def test_logical_to_spec_skips_absent_mesh_axes():
    mesh = _FakeMesh({"data": 2})
    spec = logical_to_spec(("layers", "batch"), {"batch": ("pod", "data")},
                           mesh, (3, 4))
    # "layers" has no rule -> replicated; "pod" is absent -> skipped,
    # the chain continues to "data" (multipod rules on a single-pod mesh)
    assert spec[0] is None and spec[1] == "data"


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

def test_int8_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(scale=5.0, size=(256,)).astype(np.float32))
    q, scale = quantize_int8(x)
    assert q.dtype == jnp.int8
    err = jnp.max(jnp.abs(dequantize_int8(q, scale) - x))
    assert float(err) <= float(scale) * 0.5 + 1e-6


def test_quantize_all_zeros_is_exact():
    q, scale = quantize_int8(jnp.zeros((16,)))
    np.testing.assert_array_equal(np.asarray(dequantize_int8(q, scale)),
                                  np.zeros((16,), np.float32))


def test_compressed_mean_host_side():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
    mean, err = compressed_allreduce_mean(x)   # leading dim = workers
    np.testing.assert_allclose(np.asarray(mean), np.asarray(x.mean(0)),
                               atol=2e-2)
    assert float(err) >= 0.0 and np.isfinite(float(err))


def test_topk_sparsify_keeps_largest_coordinates():
    x = jnp.asarray(np.array([[0.1, -5.0, 0.2], [3.0, -0.05, 0.4]],
                             np.float32))
    v, i = topk_sparsify(x, 2)
    dense = topk_densify(v, i, x.shape)
    # the two largest-|.| entries survive exactly; the rest are zeroed
    np.testing.assert_array_equal(
        np.asarray(dense), np.array([[0, -5.0, 0], [3.0, 0, 0]], np.float32))
    np.testing.assert_array_equal(np.asarray(topk_roundtrip(x, 2)),
                                  np.asarray(dense))
    # k clamps to the tensor size (full fidelity)
    np.testing.assert_array_equal(np.asarray(topk_roundtrip(x, 100)),
                                  np.asarray(x))


def test_topk_error_feedback_bounds_accumulated_error():
    """Residual carry keeps the error of a 50-step accumulated sparse
    uplink bounded (every coordinate is eventually transmitted); plain
    top-k drops the same small coordinates every step and drifts
    linearly. Mirrors the int8 `ef_roundtrip` bounded-error test."""
    rng = np.random.default_rng(0)
    d, k, steps = 128, 16, 50
    g = jnp.asarray(rng.normal(scale=1e-2, size=(d,)).astype(np.float32))
    plain = jnp.zeros_like(g)
    ef = jnp.zeros_like(g)
    residual = ef_init(g)
    for _ in range(steps):
        plain = plain + topk_roundtrip(g, k)
        dec, residual = ef_topk_roundtrip(residual, g, k)
        ef = ef + dec
    true = steps * g
    err_plain = float(jnp.max(jnp.abs(plain - true)))
    err_ef = float(jnp.max(jnp.abs(ef - true)))
    # exact telescoping identity: everything not yet sent is the residual
    np.testing.assert_allclose(np.asarray(ef + residual), np.asarray(true),
                               rtol=1e-4, atol=1e-5)
    # EF error stays bounded by one round-robin sweep of dropped mass...
    assert err_ef <= (d / k) * float(jnp.max(jnp.abs(g))) + 1e-6
    # ...while plain top-k accumulates the dropped coordinates linearly
    assert err_plain >= 0.5 * steps * float(jnp.sort(jnp.abs(g))[d - k - 1])
    assert err_ef < err_plain


def test_error_feedback_bounds_accumulated_error():
    """Residual carry keeps the error of a 50-step accumulated uplink
    bounded by ~one quantum; plain quantization drifts linearly."""
    rng = np.random.default_rng(0)
    # constant-ish gradient: round-to-nearest bias repeats every step
    g = jnp.asarray(rng.normal(scale=1e-2, size=(128,)).astype(np.float32))
    plain = jnp.zeros_like(g)
    ef = jnp.zeros_like(g)
    residual = ef_init(g)
    for _ in range(50):
        plain = plain + int8_roundtrip(g)
        dec, residual = ef_roundtrip(residual, g)
        ef = ef + dec
    true = 50.0 * g
    err_plain = float(jnp.max(jnp.abs(plain - true)))
    err_ef = float(jnp.max(jnp.abs(ef - true)))
    _, scale = quantize_int8(g)
    assert err_ef < err_plain, (err_ef, err_plain)
    # EF error never exceeds one carried quantum (scale of the last round)
    assert err_ef <= 2.0 * float(scale) + 1e-6
    # while plain accumulates a visible multiple of it
    assert err_plain > 5.0 * float(scale)


def test_rescale_cycle_preserves_values(tmp_path):
    """save -> rebuild_mesh -> reshard_tree returns the same values on a
    fresh mesh (the elastic grow/shrink runtime mechanism)."""
    tree = {"params": {"w": jnp.arange(32.0).reshape(8, 4)},
            "opt": {"m": jnp.ones((8, 4))}}
    axes = {"params": {"w": ("embed", "ff")},
            "opt": elastic.replicated_axes(tree["opt"])}
    rules = {"param": {"embed": "data", "ff": "model"}, "act": {}}
    out, mesh = elastic.rescale_cycle(tmp_path, 7, tree, axes, rules,
                                      new_workers=2)
    assert mesh.devices.size >= 1
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.asarray(tree["params"]["w"]))
    np.testing.assert_array_equal(np.asarray(out["opt"]["m"]),
                                  np.asarray(tree["opt"]["m"]))
    assert ckpt.latest_step(tmp_path) == 7


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_async_checkpointer_wait_ordering(tmp_path):
    """After wait(), every submitted step is on disk and the latest wins."""
    t = {"w": jnp.arange(16.0).reshape(4, 4)}
    ac = ckpt.AsyncCheckpointer(tmp_path)
    for s in (1, 2, 3, 4, 5):
        ac.save(s, jax.tree.map(lambda x: x + s, t))
    ac.wait()
    assert ckpt.latest_step(tmp_path) == 5
    restored, meta = ckpt.restore(tmp_path, t)
    assert meta["step"] == 5
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(t["w"] + 5))
    ac.close()
    with pytest.raises(RuntimeError):
        ac.save(6, t)


def test_checkpoint_keep_retention(tmp_path):
    for s in range(6):
        ckpt.save(tmp_path, s, {"w": jnp.ones((2,))}, keep=3)
    assert ckpt.latest_step(tmp_path) == 5
    steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.iterdir()
                   if p.name.startswith("step_"))
    assert steps == [3, 4, 5]


# ---------------------------------------------------------------------------
# train/launch wiring
# ---------------------------------------------------------------------------

def test_train_step_int8_grad_compression():
    from repro.configs import get_config
    from repro.train.optim import make_optimizer
    from repro.train.train_step import make_train_step

    cfg = get_config("qwen2-1.5b", smoke=True)
    opt = make_optimizer(cfg, "sgd", lr=lambda step: 0.1)  # no warmup
    params = {"w": jnp.ones((4,))}

    def loss_fn(p, b):
        return jnp.sum(jnp.square(p["w"] - b["x"])), {}

    ts = make_train_step(cfg, opt, loss_fn=loss_fn, microbatches=1,
                         grad_compression="int8")
    new_p, *_ = jax.jit(ts)(params, opt.init(params), jnp.asarray(0),
                            {"x": jnp.zeros((4,))})
    # grads survive the int8 wire well enough to descend
    assert float(jnp.max(new_p["w"])) < 1.0
    with pytest.raises(ValueError, match="grad_compression"):
        make_train_step(cfg, opt, grad_compression="zfp")


def test_mesh_context_activates_recipe_rules():
    from repro.configs import get_config
    from repro.launch.mesh import mesh_context

    cfg = get_config("qwen2-1.5b", smoke=True).with_overrides(recipe="tp_fsdp")
    with mesh_context(cfg, data=2, model=4):
        assert mesh_active()
        assert axis_size("heads") == 4
    assert not mesh_active()


# ---------------------------------------------------------------------------
# elastic
# ---------------------------------------------------------------------------

def test_factor_mesh_power_of_two_data():
    assert elastic.factor_mesh(6, prefer_model=2) == (2, 2)
    assert elastic.factor_mesh(8, prefer_model=2) == (4, 2)
    assert elastic.factor_mesh(8, prefer_model=1) == (8, 1)
    assert elastic.factor_mesh(1, prefer_model=4) == (1, 1)


def test_plan_reshard_checkpoint_cycle():
    assert not elastic.plan_reshard(2, 4).needs_checkpoint_cycle   # even grow
    assert not elastic.plan_reshard(4, 2).needs_checkpoint_cycle   # even shrink
    assert elastic.plan_reshard(4, 6).needs_checkpoint_cycle       # uneven
    assert elastic.plan_reshard(3, 3).action == "hold"


def test_elastic_controller_hysteresis():
    ctl = elastic.ElasticController(workers=2, patience=3, cooldown=5)
    # two overloaded steps then relief: patience not met -> hold
    assert ctl.observe(0, offered=10.0, achieved=1.0).action == "hold"
    assert ctl.observe(1, offered=10.0, achieved=1.0).action == "hold"
    assert ctl.observe(2, offered=1.0, achieved=1.0).action == "hold"
    # three sustained overloads -> grow 2 -> 4
    for s in (3, 4):
        assert ctl.observe(s, offered=10.0, achieved=1.0).action == "hold"
    plan = ctl.observe(5, offered=10.0, achieved=1.0)
    assert plan.action == "grow" and plan.workers == 4
    # cooldown gates the next action
    assert ctl.observe(6, offered=40.0, achieved=1.0).reason == "cooldown"


def test_make_elastic_mesh_survives_failures():
    from repro.launch.mesh import make_elastic_mesh

    mesh = make_elastic_mesh(prefer_model=2, failed=[jax.devices()[0]])
    # 8 devices - 1 failed = 7 -> model 2, data pow2_floor(3) = 2
    assert dict(mesh.shape) == {"data": 2, "model": 2}
    assert jax.devices()[0] not in set(mesh.devices.flat)


def test_reshard_tree_roundtrip():
    mesh = jax.make_mesh((2, 2), ("data", "model"), (AxisType.Auto,) * 2,
                         devices=jax.devices()[:4])
    tree = {"w": jnp.arange(32.0).reshape(8, 4), "b": jnp.ones((5,))}
    axes = {"w": ("embed", "ff"), "b": ("embed",)}   # 5 % 2 -> replicated
    rules = {"param": {"embed": ("data",), "ff": ("model",)}, "act": {}}
    out = elastic.reshard_tree(tree, axes, rules, mesh)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), tree, out)
    assert isinstance(out["w"].sharding, NamedSharding)
    assert out["w"].sharding.spec[0] == "data"
