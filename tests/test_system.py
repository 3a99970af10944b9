"""End-to-end behaviour tests for the S2CE system: the orchestrated
pipeline, multi-device distribution (subprocess with 8 host devices),
elastic recovery, and compressed gradient sync."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_end_to_end_stream_job():
    from repro.core.orchestrator import Orchestrator, StreamJob
    from repro.streams.generators import DriftSpec, HyperplaneStream
    job = StreamJob("sys", dim=8, drift_detector="ph")
    orch = Orchestrator(job)
    gen = HyperplaneStream(dim=8, seed=1,
                           drift=DriftSpec("gradual", at=0.5, width=0.2),
                           horizon=40 * 64.0)
    m = orch.run([gen.batch(i, 64) for i in range(40)])
    assert m.events == 40 * 64
    assert m.preq["accuracy"] > 0.6


def test_sharded_train_step_matches_single_device():
    """The same train step on a (2,4) mesh must produce (numerically) the
    same params as unsharded execution."""
    out = _run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.configs import get_config
        from repro.dist import use_mesh
        from repro.dist.sharding import build_rules
        from repro.models import model_zoo as zoo
        from repro.train.optim import make_optimizer
        from repro.train.train_step import make_train_step

        cfg = get_config("qwen2-1.5b", smoke=True).with_overrides(recipe="tp_fsdp")
        params = zoo.init_params(cfg, 0)
        opt = make_optimizer(cfg, "sgd", lr=1e-2)
        state = opt.init(params)
        batch = {"tokens": jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 32)),
            jnp.int32)}
        step_fn = make_train_step(cfg, opt, microbatches=1)
        p1, *_ = jax.jit(step_fn)(params, state, jnp.asarray(0), batch)

        mesh = jax.make_mesh((2, 4), ("data", "model"), (AxisType.Auto,) * 2)
        rules = build_rules(cfg)
        with use_mesh(mesh, rules):
            p2, *_ = jax.jit(step_fn)(params, state, jnp.asarray(0), batch)
        a = np.asarray(jax.tree.leaves(p1)[0], np.float32)
        b = np.asarray(jax.tree.leaves(p2)[0], np.float32)
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
        print("MATCH")
    """)
    assert "MATCH" in out


def test_elastic_recovery_after_failure():
    out = _run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.dist import elastic
        mesh = jax.make_mesh((4, 2), ("data", "model"), (AxisType.Auto,) * 2)
        new = elastic.rebuild_mesh(list(mesh.devices.flat), failed=[3, 5],
                                   prefer_model=2)
        assert new.devices.size == 4, new.devices.size
        tree = {"w": jnp.arange(32, dtype=jnp.float32).reshape(8, 4)}
        axes = {"w": ("embed", "ff")}
        rules = {"param": {"embed": "data", "ff": "model"}, "act": {}}
        out = elastic.reshard_tree(tree, axes, rules, new)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(tree["w"]))
        print("RECOVERED")
    """)
    assert "RECOVERED" in out


def test_compressed_allreduce_matches_mean():
    out = _run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.dist.compression import compressed_allreduce_mean
        mesh = jax.make_mesh((8,), ("data",), (AxisType.Auto,))
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(8, 64)).astype(np.float32))

        @partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                 out_specs=(P("data"), P("data")))
        def f(xs):
            m, err = compressed_allreduce_mean(xs[0], "data")
            return m[None], err[None]

        mean, err = f(x)
        want = x.mean(0)
        got = np.asarray(mean[0])
        np.testing.assert_allclose(got, np.asarray(want), atol=2e-2)
        assert np.isfinite(np.asarray(err)).all()
        print("COMPRESSED_OK", float(np.abs(got - np.asarray(want)).max()))
    """)
    assert "COMPRESSED_OK" in out


def test_elastic_train_rescales_through_checkpoint_cycle(tmp_path):
    """`--elastic` drives an ElasticController grow through the real
    save -> rebuild_mesh -> reshard_tree -> resume cycle mid-training."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen2-1.5b",
         "--smoke", "--steps", "6", "--batch", "2", "--seq", "16",
         "--data-mesh", "2", "--elastic", "--elastic-demand", "8",
         "--max-workers", "4", "--ckpt-every", "50",
         "--ckpt-dir", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "elastic grow -> 4 workers" in out.stdout, out.stdout
    assert "resumed from checkpoint cycle" in out.stdout
    assert "rescales=1" in out.stdout
    # the cycle left a published checkpoint behind
    from repro.dist import checkpoint as ckpt
    assert ckpt.latest_step(tmp_path) is not None


def test_elastic_without_demand_grows_on_queue_backlog(tmp_path):
    """`--elastic` WITHOUT `--elastic-demand` used to be a silent no-op
    (offered = achieved x workers -> utilization exactly 1.0, never
    crossing a threshold). Offered load now derives from the stream
    feeder's queue depth: the generator outpaces the smoke-config train
    step on CPU, the backlog builds, and the controller must emit a grow
    plan driven through the checkpoint rescale cycle."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen2-1.5b",
         "--smoke", "--steps", "8", "--batch", "2", "--seq", "16",
         "--data-mesh", "1", "--elastic", "--max-workers", "2",
         "--ckpt-every", "50", "--ckpt-dir", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "elastic grow -> 2 workers" in out.stdout, out.stdout
    assert "resumed from checkpoint cycle" in out.stdout
    from repro.dist import checkpoint as ckpt
    assert ckpt.latest_step(tmp_path) is not None


def test_dryrun_single_cell_small_mesh():
    """The dry-run machinery on a small in-test mesh: lower+compile a
    reduced arch over (2,4) and extract scan-aware roofline terms."""
    out = _run_with_devices("""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.dist import use_mesh
        from repro.dist.sharding import build_rules
        from repro.launch import hlo_analysis as ha
        from repro.models import model_zoo as zoo
        from repro.train.optim import make_optimizer
        from repro.train.train_step import make_train_step

        cfg = get_config("granite-moe-1b-a400m", smoke=True).with_overrides(
            recipe="ep_fsdp")
        shape = InputShape("tiny_train", 32, 8, "train")
        mesh = jax.make_mesh((2, 4), ("data", "model"), (AxisType.Auto,) * 2)
        rules = build_rules(cfg, shape=shape)
        opt = make_optimizer(cfg, "adamw")
        ts = make_train_step(cfg, opt, microbatches=1)
        params = zoo.init_params(cfg, 0)
        state = opt.init(params)
        batch = {"tokens": jnp.zeros((8, 32), jnp.int32)}
        with use_mesh(mesh, rules):
            compiled = jax.jit(ts).lower(params, state, jnp.asarray(0),
                                         batch).compile()
        t = ha.analyze(compiled.as_text())
        assert t["flops"] > 0
        assert compiled.memory_analysis().temp_size_in_bytes > 0
        print("CELL_OK flops=%.3e coll=%.3e" % (
            t["flops"], t["collective_bytes_total"]))
    """)
    assert "CELL_OK" in out
