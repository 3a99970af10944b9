"""Training launcher: builds the mesh, shards params/optimizer per the
arch's recipe, and runs the streaming train loop with async checkpointing
and drift-adaptive control.

On a CPU host it runs the reduced configs (``--smoke``), with forced host
devices standing in for a mesh. On a TPU host the same entrypoint runs the
published config (drop ``--smoke``); JAX finds the chips itself. The step
function is identical to the dry-run cells.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
      --steps 50 --batch 8 --seq 64

``--elastic`` activates the rate-driven :class:`ElasticController`; when
it emits a grow/shrink plan the loop drives it through the real
state-carrying cycle — ``checkpoint.save -> rebuild_mesh ->
reshard_tree -> resume`` (dist/elastic.rescale_cycle) — so a rescale
event goes through the same machinery as a failure recovery.
``--elastic-demand`` scales the offered rate relative to measured
per-worker throughput (a synthetic load curve for demos/tests).

Without ``--elastic-demand`` the offered load is derived from the
stream feeder's queue depth: batches are pulled through a
:class:`~repro.streams.feeder.StreamFeeder`, and a prefetch queue that
stays FULL for ``patience`` consecutive steps means the source outpaces
the pool, so controller utilization crosses the grow threshold.
(Previously measured-rate mode set offered = achieved x workers —
utilization exactly 1.0 forever, a silent no-op.) The backpressure
signal only grows the pool, toward the source's real rate or
``--max-workers``; shrinking needs the explicit demand curve.
"""

from __future__ import annotations

import argparse
import pathlib
import tempfile
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor", "lion", "sgd"])
    ap.add_argument("--recipe", default=None)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--elastic", action="store_true",
                    help="rate-driven worker scaling via checkpoint cycle")
    ap.add_argument("--max-workers", type=int, default=8,
                    help="elastic data-parallel worker cap")
    ap.add_argument("--elastic-demand", type=float, default=0.0,
                    help="offered rate = demand x per-worker throughput "
                         "(0 = use the measured rate)")
    args = ap.parse_args()

    # a >1 mesh on a CPU host needs forced host devices, and the flag must
    # land before jax initializes; harmless on real accelerator platforms.
    # An inherited flag with a too-small count is raised to n_req.
    import os
    import re
    n_req = args.data_mesh * args.model_mesh
    if args.elastic:
        n_req = max(n_req, args.max_workers * args.model_mesh)
    if n_req > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
        if m is None:
            flags = f"{flags} --xla_force_host_platform_device_count={n_req}"
        elif int(m.group(1)) < n_req:
            flags = flags.replace(
                m.group(0), f"--xla_force_host_platform_device_count={n_req}")
        os.environ["XLA_FLAGS"] = flags.strip()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.dist import checkpoint as ckpt
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import mesh_context
    from repro.models import model_zoo as zoo
    from repro.streams.generators import DriftSpec, TokenStream
    from repro.train.optim import make_optimizer
    from repro.train.train_step import make_train_step

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.recipe:
        cfg = cfg.with_overrides(recipe=args.recipe)
    if args.microbatches:
        cfg = cfg.with_overrides(microbatches=args.microbatches)

    n_dev = args.data_mesh * args.model_mesh
    print(f"arch={cfg.name} params={zoo.param_count(cfg)/1e6:.1f}M "
          f"recipe={cfg.recipe} mesh={n_dev} devices")

    gen = TokenStream(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      drift=DriftSpec("abrupt", at=0.5),
                      horizon=float(args.steps * args.batch * args.seq))
    opt = make_optimizer(cfg, args.optimizer, lr=args.lr,
                         total_steps=args.steps)

    ckpt_dir = pathlib.Path(args.ckpt_dir or tempfile.mkdtemp(prefix="s2ce_"))
    saver = ckpt.AsyncCheckpointer(ckpt_dir)
    params = zoo.init_params(cfg, 0)
    state = opt.init(params)
    step = jnp.asarray(0)
    start = 0
    if args.resume and ckpt.latest_step(ckpt_dir) is not None:
        tree, meta = ckpt.restore(ckpt_dir, {"params": params, "opt": state})
        params, state, start = tree["params"], tree["opt"], meta["step"]
        step = jnp.asarray(start)
        print(f"resumed from step {start}")

    import contextlib

    from repro.dist import elastic as el
    from repro.dist.sharding import build_rules

    controller = (el.ElasticController(
        workers=args.data_mesh, max_workers=args.max_workers,
        patience=2, cooldown=2) if args.elastic else None)
    workers = args.data_mesh

    # measured-rate elastic mode: pull batches through the stream feeder
    # so its queue depth gives a real offered-load signal (a backlog
    # means the source outpaces the pool -> utilization > 1 -> grow)
    feeder = None
    if controller is not None and args.elastic_demand <= 0:
        from repro.streams.feeder import StreamFeeder
        feeder = StreamFeeder(lambda shard, idx, n: gen.batch(idx, n),
                              n_shards=1, batch_per_shard=args.batch,
                              deadline_s=30.0, prefetch=4, start_idx=start)
        feeder.start()

    def make_batch(i):
        src = feeder.next() if feeder is not None else gen.batch(i, args.batch)
        batch = {"tokens": jnp.asarray(src.data["tokens"])}
        if cfg.family == "vlm":
            batch["patches"] = jnp.zeros(
                (args.batch, cfg.frontend_len, cfg.frontend_dim),
                jnp.float32)
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (args.batch, args.seq, cfg.frontend_dim), jnp.float32)
        return batch

    t0 = time.perf_counter()
    i = start
    while i < args.steps:
        # one mesh epoch: (re)trace the step under the current mesh; a
        # rescale below breaks out, round-trips state, and re-enters here
        n_dev = workers * args.model_mesh
        ctx = (mesh_context(cfg, workers, args.model_mesh)
               if n_dev > 1 else contextlib.nullcontext())
        step_fn = jax.jit(make_train_step(cfg, opt))
        plan = None
        with ctx:
            while i < args.steps:
                t_step = time.perf_counter()
                params, state, step, metrics = step_fn(
                    params, state, step, make_batch(i))
                if (i + 1) % args.ckpt_every == 0:
                    saver.save(int(step), {"params": params, "opt": state})
                if i % 10 == 0:
                    print(f"step {i:4d} loss={float(metrics['loss']):7.3f} "
                          f"gnorm={float(metrics['grad_norm']):6.2f} "
                          f"workers={workers}")
                if controller is not None:
                    jax.block_until_ready(metrics["loss"])
                    dt_step = max(time.perf_counter() - t_step, 1e-9)
                    achieved = args.batch * args.seq / dt_step / workers
                    if args.elastic_demand > 0:
                        offered = args.elastic_demand * achieved
                    elif feeder is not None:
                        # binary backpressure: a SUSTAINED-full prefetch
                        # queue (for `patience` consecutive steps) means
                        # the source outpaces the pool -> grow. This
                        # signal only ever grows (util is 1.0 when the
                        # queue has slack, never under the shrink
                        # threshold); shrinking needs a demand curve
                        # (--elastic-demand).
                        full = feeder.backlog >= feeder.prefetch
                        offered = achieved * workers * (2.0 if full else 1.0)
                    else:
                        offered = achieved * workers
                    plan = controller.observe(i, offered, achieved)
                i += 1
                if plan is not None and plan.changed:
                    break
                plan = None
        if plan is not None and plan.changed and i < args.steps:
            # the ROADMAP cycle: save -> rebuild_mesh -> reshard -> resume
            saver.wait()
            tree = {"params": params, "opt": state}
            axes = {"params": zoo.param_axes(cfg),
                    "opt": el.replicated_axes(state)}
            tree, mesh = el.rescale_cycle(
                ckpt_dir, int(step), tree, axes, build_rules(cfg),
                plan.workers, prefer_model=args.model_mesh,
                meta={"reason": plan.reason})
            params, state = tree["params"], tree["opt"]
            step = jnp.asarray(int(step))   # uncommit from the old mesh
            workers = plan.workers
            print(f"elastic {plan.action} -> {workers} workers at step "
                  f"{int(step)} ({plan.reason}); resumed from checkpoint "
                  f"cycle on a {tuple(mesh.devices.shape)} mesh")
    if feeder is not None:
        feeder.stop()
    saver.wait()
    dt = time.perf_counter() - t0
    toks = (args.steps - start) * args.batch * args.seq
    print(f"done: {toks/dt:.0f} tok/s; checkpoints at {ckpt_dir} "
          f"(latest {ckpt.latest_step(ckpt_dir)}, "
          f"rescales={controller.rescales if controller else 0})")


if __name__ == "__main__":
    main()
