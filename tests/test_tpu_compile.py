"""The main-path Pallas kernels compile for a TPU v5e chip at deployment
widths, and the serving decode step's layer loop reads its weights in
place.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) ``v5e:2x2`` topology, so the chip's compiler refuses here what
interpret-mode oracles cannot see (tile alignment, VMEM limits, ops Mosaic
cannot lower). Each compiled program must hold the Mosaic kernel
(``tpu_custom_call``). The topology is described inside a fixture, never
at import, and the persistent compilation cache is off around the compiles
(entries written for an absent chip cannot be read back).
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import countmin, ef_codec, flash_attention, mamba_scan
from repro.kernels import ops, preprocess, rwkv6_wkv

N = 65536             # events per batch
W = 65536             # count-min width
F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32



def _gmm(k, n):
    """The grouped product as ``ops.grouped_matmul`` calls it on a TPU."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    return lambda lhs, rhs, sizes: gmm(lhs, rhs, sizes, BF16,
                                       ops.gmm_tiling(k, n))


# name -> (kernel, argument shapes); shapes are the deployment widths the
# on-chip smoke run uses (qwen2-1.5b for attention; rwkv6-1.6b and a
# Jamba-style mamba block for the recurrent kernels; a DeepSeek-V2-Lite
# prefill wave's 16 x 1,500 x 6 assignments, padded to the row tile, over
# 8 held experts for the grouped product)
CASES = {
    "grouped_matmul_up": (
        _gmm(2048, 1408),
        [((144384, 2048), BF16), ((8, 2048, 1408), BF16), ((8,), I32)]),
    "grouped_matmul_down": (
        _gmm(1408, 2048),
        [((144384, 1408), BF16), ((8, 1408, 2048), BF16), ((8,), I32)]),
    "countmin_update": (
        lambda ids, seeds: countmin.countmin_update(ids, 4, W, seeds),
        [((N,), I32), ((4, 2), I32)]),
    "countmin_update_query": (
        countmin.countmin_update_query,
        [((N,), I32), ((4, W), I32), ((4, 2), I32)]),
    "fused_normalize": (
        preprocess.fused_normalize,
        [((N, 256), F32), ((), F32), ((256,), F32), ((256,), F32)]),
    "fused_hash_features": (
        lambda ids, vals: preprocess.fused_hash_features(ids, vals, 1024),
        [((N, 39), I32), ((N, 39), F32)]),
    "ef_int8_roundtrip": (
        ef_codec.ef_int8_roundtrip,
        [((N, 256), F32), ((N, 256), F32)]),
    "ef_topk_int8_roundtrip": (
        lambda r, x: ef_codec.ef_topk_int8_roundtrip(r, x, 1677722),
        [((N, 256), F32), ((N, 256), F32)]),
    "flash_attention": (
        flash_attention.flash_attention,
        [((1, 2048, 12, 128), BF16), ((1, 2048, 2, 128), BF16),
         ((1, 2048, 2, 128), BF16)]),
    "rwkv6_wkv": (
        rwkv6_wkv.rwkv6_wkv,
        [((1, 2048, 32, 64), BF16)] * 4
        + [((32, 64), F32), ((1, 32, 64, 64), F32)]),
    "mamba_scan": (
        mamba_scan.mamba_scan_bd,
        [((1, 2048, 4096), F32)] * 2 + [((1, 2048, 16), F32)] * 2
        + [((4096, 16), F32), ((1, 4096, 16), F32)]),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    kernel, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the decode step's layer loop --------------------------------------------

HLO_DTYPES = {"bfloat16": "bf16", "float32": "f32"}


def _computations(hlo: str) -> dict:
    """Instruction lines of each computation of a compiled program's text."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split()
            cur = comps.setdefault(
                head[1 if head[0] == "ENTRY" else 0].lstrip("%"), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _result_and_opcode(line: str):
    rest = line.split(" = ", 1)[1]
    if rest.startswith("("):                        # a tuple-shaped result
        depth = 0
        for end, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        return rest[:end + 1], rest[end + 2:].split("(", 1)[0]
    result, rest = rest.split(" ", 1)
    return result, rest.split("(", 1)[0]


FREE = {"parameter", "get-tuple-element", "tuple", "bitcast", "constant"}


def _weight_slices_in_loops(hlo: str, stack) -> list:
    """Instructions of a while loop's body whose result holds one layer's
    slice of a stacked weight matrix: weights copied out of the stack.
    Matrices are the stack's leaves of at least 64 KiB a layer; biases and
    norm scales (a few KiB at deployment widths) are left out. A slice that
    keeps the scan's unit layer axis does not match: the compiler stages
    such slices into on-chip memory (async slices of the small weights at
    these widths; at deployment widths latent decode's 2 MB absorbed
    projections and Qwen2's k/v projections), with or without the barrier."""
    per_layer = {(HLO_DTYPES[jnp.dtype(a.dtype).name], tuple(a.shape[1:]))
                 for a in jax.tree.leaves(stack)
                 if a.ndim >= 3 and a.size // a.shape[0]
                 * jnp.dtype(a.dtype).itemsize >= 64 * 1024}
    comps = _computations(hlo)
    found = []
    for body in set(re.findall(r"body=%?([\w.\-]+)", hlo)):
        for line in comps.get(body, []):
            if " = " not in line:
                continue
            result, opcode = _result_and_opcode(line)
            shapes = {(d, tuple(int(n) for n in dims.split(",") if n))
                      for d, dims in re.findall(r"(\w+)\[([\d,]*)\]", result)}
            if opcode not in FREE and shapes & per_layer:
                found.append(line.split(" = ", 1)[0].strip())
    return found


def _small(name: str, **widths):
    """A serving configuration's program at tile-aligned test widths."""
    from bench import cells
    cfg = dict(cells.load_json(cells.BENCH_DIR / "configs" / f"{name}.json"),
               **widths)
    driver = cells.load_module(cells.BENCH_DIR / "drivers"
                               / f"{cfg['driver']}.py")
    return driver.arch_config(cfg)


# latent attention with a held share of routed experts and shared experts
# over a stack of two routed layers; a dense GQA decoder with q/k/v biases
DECODERS = {
    "mla_moe": dict(
        name="deepseek_v2_lite_serve", hidden_size=256, intermediate_size=512,
        num_hidden_layers=3, num_attention_heads=2, num_key_value_heads=2,
        kv_lora_rank=128, moe_intermediate_size=256, router_experts=16,
        n_routed_experts=4, num_experts_per_tok=2, vocab_size=1024),
    "gqa": dict(
        name="qwen2_1_5b_serve", hidden_size=512, intermediate_size=1024,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        vocab_size=1024),
}


@pytest.mark.parametrize("model", sorted(DECODERS))
def test_decode_layer_loop_reads_weights_in_place(model, one_chip,
                                                  no_persistent_cache):
    """Compiled for one chip with no mesh, the decode step's layer loop
    copies no layer's weights out of the stack: its products read the
    stacked operand through a fused slice."""
    from repro.models import model_zoo as zoo
    arch = _small(**DECODERS[model])
    place = lambda t: jax.tree.map(                         # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    params = place(zoo.param_shapes(arch))
    caches = place(jax.eval_shape(lambda: zoo.init_caches(arch, 4, 128)))
    tokens = jax.ShapeDtypeStruct((4, 1), I32, sharding=one_chip)
    hlo = jax.jit(lambda p, c, t: zoo.decode_step(p, arch, c, t)).lower(
        params, caches, tokens).compile().as_text()
    assert "while" in hlo
    assert _weight_slices_in_loops(hlo, params["stack"]) == []
