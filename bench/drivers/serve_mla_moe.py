"""Serving cells of a decoder with multi-head latent attention and a held
share of routed experts (DeepSeek-V2 config keys): the same
``StreamJob(pipeline=serving_graph(engine, ...))`` under ``Orchestrator``
as ``serve.py`` drives, whose window, wave, sample, check and calibration
it takes as they are. What is its own: the program's config for these keys
and the layout of the seeded weights as the program's parameter tree.

``n_routed_experts`` is the number of experts this chip holds, 0 ..
``n_routed_experts`` - 1 of the router's ``router_experts``. A program
whose expert layer cannot be told which experts it holds cannot run the
cell: set-up says so before it makes any weights.
"""

from __future__ import annotations

import dataclasses
import pathlib

import jax
import numpy as np

from bench import arrivals
from bench.cells import load_module

_serve = load_module(pathlib.Path(__file__).resolve().parent / "serve.py")
System = _serve.System
window = _serve.window
sample_rows = _serve.sample_rows
check = _serve.check
calibrate = _serve.calibrate
limits = _serve.limits
_one_wave = _serve._one_wave


def holds_a_share() -> bool:
    """The program's expert layer can be told which experts it holds."""
    from repro.configs.base import MoEConfig
    fields = {f.name for f in dataclasses.fields(MoEConfig)}
    return {"first_held", "num_held", "norm_topk_prob",
            "routed_scaling_factor"} <= fields


def arch_config(cfg: dict):
    """The program's config for ``cfg`` (keys of the model's config.json),
    run as the configuration states: its widths, depth, share of experts,
    gates, rope scaling, norm epsilon and precision."""
    from repro.configs.base import MLAConfig, MoEConfig, YaRNConfig, \
        get_config
    base = get_config(cfg["program_arch"])
    rs = cfg["rope_scaling"]
    h = int(cfg["num_attention_heads"])
    shared = int(cfg["n_shared_experts"])
    dtype = {"bfloat16": "bfloat16", "float32": "float32"}[
        cfg["torch_dtype"]]
    return base.with_overrides(
        n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]), n_heads=h, n_kv_heads=h,
        d_ff=int(cfg["intermediate_size"]), vocab_size=int(cfg["vocab_size"]),
        vocab_pad_multiple=256, qkv_bias=bool(cfg["attention_bias"]),
        mlp_act="silu_glu", norm_type="rmsnorm",
        norm_eps=float(cfg["rms_norm_eps"]), pos_embed="rope",
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        mla=MLAConfig(
            kv_lora_rank=int(cfg["kv_lora_rank"]),
            q_lora_rank=int(cfg["q_lora_rank"] or 0),
            rope_head_dim=int(cfg["qk_rope_head_dim"]),
            nope_head_dim=int(cfg["qk_nope_head_dim"]),
            v_head_dim=int(cfg["v_head_dim"]),
            rope_scaling=YaRNConfig(
                factor=float(rs["factor"]),
                original_max_position=int(
                    rs["original_max_position_embeddings"]),
                beta_fast=float(rs["beta_fast"]),
                beta_slow=float(rs["beta_slow"]),
                mscale=float(rs["mscale"]),
                mscale_all_dim=float(rs["mscale_all_dim"]))),
        moe=MoEConfig(
            num_experts=int(cfg["router_experts"]),
            top_k=int(cfg["num_experts_per_tok"]),
            d_ff_expert=int(cfg["moe_intermediate_size"]),
            num_shared=shared,
            d_ff_shared=shared * int(cfg["moe_intermediate_size"]),
            layer_period=int(cfg["moe_layer_freq"]),
            first_dense=int(cfg["first_k_dense_replace"]),
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            first_held=0, num_held=int(cfg["n_routed_experts"])),
        param_dtype=dtype, compute_dtype=dtype, kv_cache_dtype=dtype)


_MIXER = ("wq", "w_dkv", "w_kr", "kv_norm", "w_uk", "w_uv", "wo")


def _slot(g: dict, mlp: dict, i=None) -> dict:
    at = (lambda a: a[i]) if i is not None else (lambda a: a)
    return {"norm1": {"scale": at(g["attn_norm"])},
            "mixer": {k: at(g[k]) for k in _MIXER},
            "norm2": {"scale": at(g["mlp_norm"])},
            "mlp": jax.tree.map(at, mlp)}


def program_params(w: dict, arch):
    """Lay the seeded weights out as the program's parameter tree (the
    leading dense layers unstacked, the routed layers as one stack), and
    check the layout against the program's own shapes."""
    from repro.models import model_zoo as zoo
    dn, mo = w["dense"], w["moe"]
    tree = {
        "embed": {"tok": w["top"]["embed"], "head": w["top"]["head"]},
        "final_norm": {"scale": w["top"]["final_norm"]},
        "prefix": [_slot(dn, {k: dn[k] for k in ("w_gate", "w_up",
                                                 "w_down")}, i)
                   for i in range(dn["wq"].shape[0])],
        "stack": [_slot(mo, {
            "router": mo["router"], "w_gate": mo["e_gate"],
            "w_up": mo["e_up"], "w_down": mo["e_down"],
            "shared": {"w_gate": mo["s_gate"], "w_up": mo["s_up"],
                       "w_down": mo["s_down"]}})],
    }
    want = zoo.param_shapes(arch)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter layout changed: "
                         f"{jax.tree.structure(want)}")
    return tree


def setup(cell, run) -> System:
    if not holds_a_share():
        raise RuntimeError("the program's expert layer cannot hold a share "
                           "of the experts: it cannot run this cell")
    from repro.core.orchestrator import Orchestrator, StreamJob
    from repro.core.sla import SLA
    from repro.serve.engine import ServeEngine
    from repro.serve.ops import serve_wave_batch, serving_graph
    from repro.serve.sampling import SamplingParams
    cfg, tr = cell.config, cell.traffic
    sv = cfg["serve"]
    arch = arch_config(cfg)
    params = program_params(cell.reference().weights(cfg, run.seed), arch)
    engine = ServeEngine(arch, params, batch_size=sv["batch_size"],
                         max_len=sv["max_len"], impl=sv["impl"],
                         sampling=SamplingParams(greedy=True))
    graph = serving_graph(engine, prompt_len=sv["prompt_len"],
                          max_new_tokens=sv["max_new_tokens"])
    orch = Orchestrator(StreamJob(cell.name, sla=SLA(**cfg["sla"]),
                                  pipeline=graph, workers=1,
                                  max_workers=cfg["max_workers"]))
    sys_ = System(orch=orch, engine=engine, offered=float(tr["rate"]))
    orch.begin(sys_.offered, seed=run.seed)
    run_graph = orch.pipeline.run
    # compile each wave shape the traffic sends outside the orchestrator's
    # telemetry (a compile inside execute_batch reads as an SLA violation),
    # then run one wave of each through the step primitives
    lengths = arrivals.size_values(tr, "prompt_len")
    for s in lengths:
        rows = [np.ones(s, np.int32)] + [np.zeros(1, np.int32)] * (
            sv["batch_size"] - 1)
        _, out = run_graph(orch.states, serve_wave_batch(engine, rows),
                           orch.frontier)
        jax.block_until_ready(out)

    def run_and_keep(states, batch, frontier=(), uplink=None):
        states, out = run_graph(states, batch, frontier, uplink=uplink)
        sys_.outputs[sys_.step] = out["out_tokens"]
        return states, out

    orch.pipeline.run = run_and_keep
    for s in lengths:
        _one_wave(sys_, run, [np.ones(s, np.int32)])
    sys_.outputs.clear()
    return sys_
