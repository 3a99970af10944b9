"""The stream cell's data: MOA's hyperplane generator by its documented
semantics, a function of the seed alone."""

import numpy as np
import pytest

import _paths  # noqa: F401
from bench import cells, datagen

MOA = {"numClasses": 2, "numAtts": 10, "numDriftAtts": 2, "magChange": 0.001,
       "noisePercentage": 5, "sigmaPercentage": 10}
STILL = dict(MOA, magChange=0.0, noisePercentage=0)


def _initial_weights(seed, atts=10):
    return datagen._rng(seed, 0).random(atts)


def test_attributes_are_uniform_on_the_unit_interval():
    (x, y), = datagen.hyperplane(7, 1, 20000, MOA)
    assert x.shape == (20000, 10) and x.dtype == np.float32
    assert y.dtype == np.int32 and set(np.unique(y)) == {0, 1}
    assert 0.0 <= x.min() and x.max() < 1.0
    assert abs(float(x.mean()) - 0.5) < 0.01


def test_class_is_the_side_of_half_the_weight_sum():
    (x, y), = datagen.hyperplane(3, 1, 5000, STILL)
    w = _initial_weights(3)
    want = (x.astype(np.float64) @ w >= 0.5 * w.sum()).astype(np.int32)
    assert np.array_equal(y, want)


def test_noise_flips_its_share_of_the_classes():
    (x, y), = datagen.hyperplane(3, 1, 100000, dict(STILL, noisePercentage=5))
    w = _initial_weights(3)
    clean = (x.astype(np.float64) @ w >= 0.5 * w.sum()).astype(np.int32)
    assert np.mean(y != clean) == pytest.approx(0.05, abs=0.004)


def test_the_drifting_weights_rotate_the_concept():
    seed, n = 11, 65536
    drift = datagen.hyperplane(seed, 4, n, dict(MOA, noisePercentage=0))
    still = datagen.hyperplane(seed, 4, n, STILL)
    for (xa, _), (xb, _) in zip(drift, still):
        assert np.array_equal(xa, xb)
    moved = [float(np.mean(a[1] != b[1])) for a, b in zip(drift, still)]
    assert moved[0] > 0.0 and moved[-1] > moved[0]


def test_later_batches_continue_the_same_stream():
    a = datagen.hyperplane(2**31 + 5, 3, 1000, MOA)
    b = datagen.hyperplane(2**31 + 5, 5, 1000, MOA)
    c = datagen.hyperplane(6, 3, 1000, MOA)
    for (xa, ya), (xb, yb) in zip(a, b):
        assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
    assert not np.array_equal(a[0][0], c[0][0])


def test_committed_configuration_is_moa_hyperplane():
    cfg = cells.load_json(cells.BENCH_DIR / "configs"
                          / "fanout_moa_hyperplane.json")
    for key, value in MOA.items():
        assert cfg["data"][key] == value
    assert cfg["graph"]["dim"] == cfg["data"]["numAtts"]
    assert cfg["sla"]["error_budget"] == 0.0
