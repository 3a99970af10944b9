"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state. The dry-run (and only the dry-run) forces 512 host platform devices;
``make_production_mesh`` then carves the single-pod (16,16)=256-chip mesh or
the multi-pod (2,16,16)=512-chip mesh out of the available devices.
"""

from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    from jax.sharding import AxisType
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(launch/dryrun.py does this automatically)")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(shape),
                         devices=devices[:n])


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests)."""
    import jax
    from jax.sharding import AxisType
    n = data * model
    return jax.make_mesh((data, model), ("data", "model"),
                         (AxisType.Auto,) * 2, devices=jax.devices()[:n])


def make_elastic_mesh(prefer_model: int = 1, failed=()):
    """Best-effort mesh over whatever devices currently survive.

    Used after an elastic grow/shrink or a worker failure: carves the
    largest power-of-two data axis (x ``prefer_model``) out of the
    non-failed local devices via dist/elastic.
    """
    import jax

    from repro.dist.elastic import rebuild_mesh
    return rebuild_mesh(jax.devices(), failed=failed,
                        prefer_model=prefer_model)


def mesh_context(cfg, data: int = 1, model: int = 1, *, shape=None):
    """``use_mesh`` context for a local (data, model) mesh with the
    arch's recipe rules — the one-liner launchers use to activate
    distribution (a (1,1) request still yields a working context)."""
    from repro.dist import use_mesh
    from repro.dist.sharding import build_rules
    return use_mesh(make_local_mesh(data, model),
                    build_rules(cfg, shape=shape))
