"""repro.dist — the distributed-execution subsystem.

S2CE's hybrid cloud/edge promise needs one substrate that the models,
the train step, the launchers, and the orchestrator all share. This
package is that substrate; everything else in the repo talks to it
through a handful of names:

  * :func:`use_mesh`     — context manager activating a (mesh, rules)
    pair. Accepts a ``jax.sharding.Mesh``, a ``{axis: size}`` dict, or
    ``None`` (degrades to a single-device mesh — CPU laptops work).
  * :func:`shard` / :func:`shard_param` — ``with_sharding_constraint``
    wrappers keyed by *logical* axis names; strict no-ops outside a
    mesh, and per-dim divisibility-guarded inside one.
  * :func:`pin_params`   — tree-level :func:`shard_param` (the train
    step pins stacked weights so GSPMD cannot hoist whole-stack
    all-gathers out of scan loops).
  * :func:`axis_size`    — resolved size of a logical axis (1 when
    unmapped / no mesh); drives KV-head TP duplication and MoE token
    grouping.
  * submodules: :mod:`api` (logical->PartitionSpec), :mod:`sharding`
    (recipe->rules), :mod:`checkpoint` (step-dir save/restore + async),
    :mod:`compression` (int8 edge-uplink gradient compression),
    :mod:`elastic` (worker add/remove resharding decisions).

Logical-axis naming conventions (used across ``models/transformer.py``,
``models/moe.py``, ``models/ssm.py``, ``models/rwkv.py``):

  ============== =====================================================
  name           meaning
  ============== =====================================================
  batch          global example dim (data parallel: pod x data)
  seq_sp         sequence dim in reduce-scattered residual form
  kv_seq         key/value sequence dim (never sharded today)
  embed          model/residual feature dim (params: FSDP over data)
  heads / kv_heads  attention head dims (TP over model)
  ff             MLP hidden dim (TP over model)
  dinner         SSM/RWKV inner feature dim (TP over model)
  vocab          softmax/vocab dim (TP over model)
  experts        expert weight dim (expert parallel over model)
  layers         scanned layer stack dim (always replicated)
  head_dim/lora  per-head / low-rank dims (always replicated)
  ============== =====================================================
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding

from repro.dist import checkpoint  # noqa: F401  (re-export submodule)
from repro.dist.api import logical_to_spec, spec_is_replicated

__all__ = [
    "use_mesh", "current_mesh", "current_rules", "mesh_active",
    "shard", "shard_param", "pin_params", "axis_size", "checkpoint",
]


@dataclass(frozen=True)
class _MeshContext:
    mesh: Mesh
    rules: dict


class _State(threading.local):
    def __init__(self):
        self.stack = []


_STATE = _State()


def _current() -> Optional[_MeshContext]:
    return _STATE.stack[-1] if _STATE.stack else None


def current_mesh() -> Optional[Mesh]:
    ctx = _current()
    return ctx.mesh if ctx else None


def current_rules() -> Optional[dict]:
    ctx = _current()
    return ctx.rules if ctx else None


def mesh_active() -> bool:
    return _current() is not None


def _coerce_mesh(mesh) -> Mesh:
    if mesh is None:
        mesh = {"data": 1, "model": 1}
    if isinstance(mesh, dict):
        names = tuple(mesh)
        shape = tuple(int(v) for v in mesh.values())
        n = 1
        for s in shape:
            n *= s
        devs = jax.devices()
        if len(devs) < n:
            raise ValueError(
                f"mesh {dict(zip(names, shape))} needs {n} devices, "
                f"have {len(devs)}")
        return jax.make_mesh(shape, names, (AxisType.Auto,) * len(shape),
                             devices=devs[:n])
    return mesh


@contextlib.contextmanager
def use_mesh(mesh=None, rules: Optional[dict] = None):
    """Activate (mesh, rules) for the enclosed block.

    ``mesh``: a Mesh, an ``{axis: size}`` dict (built over local devices),
    or None (single-device degenerate mesh). ``rules``: as produced by
    :func:`repro.dist.sharding.build_rules`; defaults to empty rules,
    i.e. everything replicated.
    """
    ctx = _MeshContext(_coerce_mesh(mesh),
                       rules if rules is not None else {"param": {}, "act": {}})
    _STATE.stack.append(ctx)
    try:
        yield ctx.mesh
    finally:
        _STATE.stack.pop()


def _constrain(x, logical_axes, table_key: str):
    ctx = _current()
    if ctx is None or not hasattr(x, "ndim"):
        return x
    if len(logical_axes) != x.ndim:
        return x
    rules = ctx.rules.get(table_key, {})
    if not rules:
        return x
    spec = logical_to_spec(logical_axes, rules, ctx.mesh, x.shape)
    if spec_is_replicated(spec):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def shard(x, *logical_axes):
    """Constrain an activation to its logical layout (no-op outside a
    mesh, or when a dim does not divide by its mesh axes)."""
    return _constrain(x, logical_axes, "act")


def shard_param(x, logical_axes):
    """Constrain a parameter (or grad) leaf to its param-rule layout."""
    return _constrain(x, tuple(logical_axes), "param")


def pin_params(tree, axes_tree):
    """Apply :func:`shard_param` across a tree; leaves whose rank does
    not match their axes entry (e.g. non-array aux state) pass through."""
    if _current() is None:
        return tree
    return jax.tree.map(
        lambda x, ax: shard_param(x, ax)
        if hasattr(x, "ndim") and x.ndim == len(ax) else x,
        tree, axes_tree)


def axis_size(name: str) -> int:
    """Resolved size of logical axis ``name`` under the active mesh.

    Returns 1 with no active mesh, for unmapped names, and for mesh
    axes absent from the current mesh. ``name`` may also be a physical
    mesh axis name.
    """
    ctx = _current()
    if ctx is None:
        return 1
    sizes = dict(ctx.mesh.shape)
    if name in sizes:
        return int(sizes[name])
    rule = ctx.rules.get("act", {}).get(name)
    if rule is None:
        rule = ctx.rules.get("param", {}).get(name)
    if rule is None:
        return 1
    if isinstance(rule, str):
        rule = (rule,)
    n = 1
    for ax in rule:
        n *= int(sizes.get(ax, 1))
    return n
