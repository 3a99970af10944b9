"""The one traffic generator: turns a traffic file's parameters and a seed
into an open-loop schedule of due times and sizes.

Two kinds of mix:

* ``stream`` — micro-batches of ``batch_events`` events. Events arrive as a
  Poisson process at ``rate`` events/s, so batch ``i`` is due when its last
  event arrives: the gaps between batches are Gamma(``batch_events``) draws.
* ``requests`` — requests arriving at ``rate`` requests/s, each one of the
  file's ``classes`` (a whole set of sizes: ``prompt_len``,
  ``output_len``), drawn by the classes' weights.

``open_backlog`` items (default none) are due as the window opens: a
cell above capacity then starts with the queue it would hold anyway, and
its first wave is as full as the rest.

Draws are stratified in blocks of ``block`` items: every block holds each
class in proportion to its weight, and exponential gaps at evenly
spaced quantiles, each shuffled by the seed. So every seed offers the same
work in a different order, and runs with different seeds compare.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List

import numpy as np


@dataclass(frozen=True)
class Item:
    """One unit of offered work: a stream batch or a request."""
    index: int
    due: float                   # seconds after the window opens
    sizes: Dict[str, int]


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng((int(seed) & (2**63 - 1), stream))


def _block_values(spec: dict, block: int) -> np.ndarray:
    values, weights = spec["values"], spec["weights"]
    if len(values) != len(weights) or not values:
        raise ValueError(f"values and weights differ in length: {spec}")
    total = sum(weights)
    if block % total:
        raise ValueError(f"block {block} does not hold whole multiples of "
                         f"the weights {weights}")
    reps = block // total
    return np.repeat(np.asarray(values, np.int64),
                     [w * reps for w in weights])


def quota_draws(spec: dict, n: int, block: int,
                rng: np.random.Generator) -> np.ndarray:
    """``n`` values whose every block of ``block`` holds each value in
    proportion to its weight, in an order drawn from ``rng``."""
    base = _block_values(spec, block)
    out = [rng.permutation(base) for _ in range(-(-n // block))]
    return np.concatenate(out)[:n] if out else np.zeros(0, np.int64)


def exp_gaps(n: int, rate: float, block: int,
             rng: np.random.Generator) -> np.ndarray:
    """Exponential gaps at the ``(i + 1/2) / block`` quantiles, shuffled."""
    q = (np.arange(block) + 0.5) / block
    base = -np.log1p(-q) / rate
    out = [rng.permutation(base) for _ in range(-(-n // block))]
    return np.concatenate(out)[:n] if out else np.zeros(0)


def size_values(traffic: dict, key: str) -> List[int]:
    """Every value the mix can give ``key``, in increasing order."""
    return sorted({int(c[key]) for c in traffic["classes"]})


def schedule(traffic: dict, seed: int, horizon_s: float) -> List[Item]:
    """Every item due in ``[0, horizon_s)``, in due order."""
    rate = float(traffic["rate"])
    n = int(math.ceil(horizon_s * rate * 1.5)) + 16
    if traffic["kind"] == "stream":
        ev = int(traffic["batch_events"])
        n = int(math.ceil(horizon_s * rate / ev * 1.5)) + 16
        gaps = _rng(seed, 1).gamma(ev, 1.0 / rate, size=n)
        due = np.cumsum(gaps)
        sizes = [{"events": ev} for _ in range(n)]
    elif traffic["kind"] == "requests":
        block = int(traffic["block"])
        due = np.cumsum(exp_gaps(n, rate, block, _rng(seed, 1)))
        classes = traffic["classes"]
        pick = quota_draws({"values": list(range(len(classes))),
                            "weights": [c["weight"] for c in classes]},
                           n, block, _rng(seed, 2))
        sizes = [{k: int(classes[c][k]) for k in traffic["size_keys"]}
                 for c in pick]
    else:
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
    due[:int(traffic.get("open_backlog", 0))] = 0.0
    return [Item(i, float(d), sizes[i]) for i, d in enumerate(due)
            if d < horizon_s]
