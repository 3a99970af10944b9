"""Seeded data for the cells, made on the host as a source would make it.

``hyperplane`` is MOA's ``HyperplaneGenerator`` (Hulten, Spencer and
Domingos, KDD 2001), instance for instance by its documented semantics:
attributes uniform in [0, 1); the class is 1 where the weighted sum reaches
half the sum of the weights; ``noisePercentage`` of the classes flipped;
after each instance the first ``numDriftAtts`` weights move by
``magChange`` in their direction, and each direction reverses with
probability ``sigmaPercentage``. ``zipf_tokens`` draws prompt tokens from a
Zipf unigram law over the vocabulary. Both are functions of the seed alone.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng((int(seed) & (2**63 - 1),) + stream)


def hyperplane(seed: int, batches: int, n: int,
               moa: Dict[str, float]) -> List[Tuple[np.ndarray, np.ndarray]]:
    """The first ``batches`` batches of ``n`` instances of one stream:
    ``[(x float32 (n, numAtts), y int32 (n,)), ...]``. ``moa`` holds the
    generator's options under MOA's names."""
    atts, drifting = int(moa["numAtts"]), int(moa["numDriftAtts"])
    mag, noise = float(moa["magChange"]), float(moa["noisePercentage"]) / 100
    flip_p = float(moa["sigmaPercentage"]) / 100
    if int(moa.get("numClasses", 2)) != 2:
        raise ValueError("the hyperplane generator makes two classes")
    w = _rng(seed, 0).random(atts)
    sigma = np.where(np.arange(atts) < drifting, 1.0, 0.0)
    out = []
    for b in range(batches):
        rng = _rng(seed, 1, b)
        x = rng.random((n, atts), dtype=np.float32)
        # instance t is labelled under the weights before its own drift
        # step; the direction a step takes reverses after each step with
        # probability flip_p
        flips = rng.random((n, atts)) < flip_p
        parity = np.cumsum(flips, axis=0) % 2
        sign = np.where(np.vstack([np.zeros((1, atts), np.int64),
                                   parity[:-1]]) == 1, -1.0, 1.0)
        step = sigma * sign * mag
        moved = np.vstack([np.zeros((1, atts)), np.cumsum(step, 0)[:-1]])
        wt = w + moved                                     # (n, atts)
        xd = x.astype(np.float64)
        y = (np.sum(wt * xd, 1) >= 0.5 * np.sum(wt, 1)).astype(np.int32)
        y = np.where(rng.random(n) < noise, 1 - y, y).astype(np.int32)
        out.append((x, y))
        w = wt[-1] + step[-1]
        sigma = sigma * np.where(parity[-1] == 1, -1.0, 1.0)
    return out


def zipf_tokens(seed: int, index: int, length: int, vocab: int,
                a: float = 1.3) -> np.ndarray:
    """Request ``index``'s prompt of ``length`` token ids."""
    raw = _rng(seed, 2, index).zipf(a, size=length)
    return (raw % vocab).astype(np.int32)
