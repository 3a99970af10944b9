"""The yardstick: peaks by device kind, and the bytes and operations the
measured kernels and programs need."""

import pytest

import _paths  # noqa: F401
from bench import cells, roofline

QWEN = cells.load_json(cells.BENCH_DIR / "configs" / "qwen2_1_5b_serve.json")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_v5e_peaks_are_the_published_ones():
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_matmul_params_agree_with_the_program_count():
    from repro.configs.base import get_config
    cfg = get_config("qwen2-1.5b")
    assert roofline.matmul_params(QWEN) == cfg.param_counts()["active"]


def test_weight_bytes_agree_with_the_program_tree():
    import jax
    import numpy as np
    sys_drv = cells.load_module(cells.BENCH_DIR / "drivers" / "serve.py")
    from repro.models import model_zoo as zoo
    shapes = zoo.param_shapes(sys_drv.arch_config(QWEN))
    total = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in jax.tree.leaves(shapes))
    assert roofline.weight_bytes(QWEN) == total


def test_decode_step_bytes_adds_the_live_cache():
    base = roofline.decode_step_bytes(QWEN, 16, 0)
    kv = roofline.decode_step_bytes(QWEN, 16, 1152) - base
    assert kv == 16 * 1152 * 28 * 2 * 2 * 128 * 2      # 28,672 B a position


def test_serve_flops_counts_each_weight_twice_per_pass():
    layers = 28 * roofline.layer_matmul_params(QWEN)
    head = roofline.head_params(QWEN)
    assert roofline.serve_flops(QWEN, 100, 1) == 2 * layers * 100 + 2 * head
    assert roofline.serve_flops(QWEN, 100, 3) == \
        2 * layers * 102 + 2 * head * 3
