"""The main-path Pallas kernels compile for a TPU v5e chip at deployment
widths.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) ``v5e:2x2`` topology, so the chip's compiler refuses here what
interpret-mode oracles cannot see (tile alignment, VMEM limits, ops Mosaic
cannot lower). Each compiled program must hold the Mosaic kernel
(``tpu_custom_call``). The topology is described inside a fixture, never
at import, and the persistent compilation cache is off around the compiles
(entries written for an absent chip cannot be read back).
"""

import os

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
