"""Plain reference of the S2CE Fig. 2 fan-out job (arXiv:2007.01260):

    normalize -> sketch
              -> anomaly ---------------> alert
              -> sample -> train -> drift -^

written from the job's stated semantics in straightforward ``jax.numpy``,
one batch at a time, with the uplink codec applied where the executed plan
crosses from the edge side to the cloud side. It imports nothing of the
program: the same operations on the same data give the same answers.

* normalize: Welford running mean and variance over the whole stream, then
  ``(x - mean) / sqrt(var + 1e-6)`` with the updated statistics.
* sketch: running count, mean, M2, min and max per feature.
* anomaly: 8 random projections (``normal(PRNGKey(0), (d, 8)) / sqrt(d)``)
  into 32 equal bins over [-4, 4]; counts start at one; the batch is
  counted, then scored as the mean over projections of ``-log`` of its
  bin's share.
* sample: Algorithm R over a 256-row reservoir (its own key chain from
  ``PRNGKey(0)``: per event, split the key, draw ``j`` uniform in
  ``[0, seen)``), then Bernoulli thinning at rate 0.5 with a key split
  from the batch key.
* train: prequential logistic regression: predict ``p`` on the batch, the
  error flag ``(p > 0.5) != y``, then one AdaGrad step (lr 0.5, l2 1e-4)
  on the thinned rows.
* drift: DDM (warn 2, drift 3, warm-up 30) over the error flags;
  ``drifted`` if any event reaches drift. A drift halves the learner's
  weights and bias and resets its AdaGrad state.
* alert: ``mean(score > 3) > 0.5 or drifted``.
* uplink ``topk_int8_ef``: each float channel crossing the uplink adds its
  carried residual, keeps the ``round(0.1 n)`` largest magnitudes,
  quantizes them to int8 against their own peak, and carries what was not
  sent.

Matrix products run at the backend's default precision, as the
configuration states. The reservoir is computed without the per-event
buffer rewrite: a scan draws each event's slot, and each slot then takes
the last event that landed in it, which is Algorithm R's result. That
scan runs on the host's CPU.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

OPS = ("normalize", "sketch", "anomaly", "sample", "train", "drift",
       "alert")
OUTPUT_KEYS = ("score", "p", "err", "drifted", "alert")


def init_states(g: dict, dt=jnp.float32) -> dict:
    d, k, m, bins = g["dim"], g["reservoir_k"], 8, 32
    proj = (jax.random.normal(jax.random.PRNGKey(0), (d, m))
            / jnp.sqrt(d)).astype(dt)
    z = lambda *s: jnp.zeros(s, dt)
    return {
        "normalize": (z(), z(d), z(d)),
        "sketch": (z(), z(d), z(d), jnp.full((d,), jnp.inf, dt),
                   jnp.full((d,), -jnp.inf, dt)),
        "anomaly": (proj, jnp.linspace(-4.0, 4.0, bins + 1).astype(dt),
                    jnp.ones((m, bins), dt), z()),
        "sample": (z(k, d), jnp.zeros((k,), jnp.int32),
                   jnp.zeros((), jnp.int32), jax.random.PRNGKey(0)),
        "train": ((z(d), z(), jnp.full((d,), 1e-8, dt), z()),
                  (z(), z(), z(), jnp.asarray(0.5, dt))),
        "drift": (z(), z(), jnp.asarray(1e9, dt), jnp.asarray(1e9, dt),
                  jnp.zeros((), jnp.int32)),
    }


# -- the ops -----------------------------------------------------------------

@jax.jit
def normalize(st, x):
    n0, mean0, m20 = st
    nb = x.shape[0]
    mb = jnp.mean(x, axis=0)
    m2b = jnp.sum(jnp.square(x - mb), axis=0)
    n = n0 + nb
    delta = mb - mean0
    mean = mean0 + delta * (nb / jnp.maximum(n, 1.0))
    m2 = m20 + m2b + jnp.square(delta) * n0 * nb / jnp.maximum(n, 1.0)
    var = m2 / jnp.maximum(n - 1.0, 1.0)
    return (n, mean, m2), (x - mean) * jax.lax.rsqrt(var + 1e-6)


@jax.jit
def sketch(st, x):
    n0, mean0, m20, lo, hi = st
    nb = x.shape[0]
    mb = x.mean(0)
    m2b = jnp.sum(jnp.square(x - mb), axis=0)
    n = n0 + nb
    delta = mb - mean0
    mean = mean0 + delta * nb / jnp.maximum(n, 1.0)
    m2 = m20 + m2b + jnp.square(delta) * n0 * nb / jnp.maximum(n, 1.0)
    return (n, mean, m2, jnp.minimum(lo, x.min(0)), jnp.maximum(hi, x.max(0)))


def _bins(proj, edges, x, nbins):
    z = x @ proj
    return jnp.clip(jnp.searchsorted(edges, z) - 1, 0, nbins - 1)


@jax.jit
def anomaly(st, x):
    proj, edges, counts, n = st
    nbins = counts.shape[1]
    idx = _bins(proj, edges, x, nbins)                       # (n, m)
    hist = jax.nn.one_hot(idx, nbins, dtype=jnp.float32).sum(0)
    counts = counts + hist.astype(counts.dtype)
    n = n + x.shape[0]
    share = jnp.take_along_axis(counts, idx.T, axis=1).T \
        / jnp.maximum(counts.sum(-1), 1.0)[None]
    return (proj, edges, counts, n), -jnp.log(share + 1e-9).mean(-1)


@functools.partial(jax.jit, static_argnames=("n",))
def _slots(key, seen0, n):
    """Algorithm R's draw for each of ``n`` events: (slot, taken)."""
    def step(carry, _):
        key, seen = carry
        key, sub = jax.random.split(key)
        seen = seen + 1
        j = jax.random.randint(sub, (), 0, seen)
        return (key, seen), (j, seen)
    (key, seen), (j, seen_i) = jax.lax.scan(step, (key, seen0), None,
                                           length=n)
    return key, seen, j, seen_i


@functools.partial(jax.jit, static_argnames=("rate",))
def _fill(st, x, y, rng, drawn, rate):
    buf, extra, _, _ = st
    key, seen, j, seen_i = drawn
    k = buf.shape[0]
    slot = jnp.where(seen_i <= k, seen_i - 1, j)
    taken = (seen_i <= k) | (j < k)
    event = jnp.arange(y.shape[0])
    last = jnp.full((k,), -1, jnp.int32).at[
        jnp.where(taken, jnp.clip(slot, 0, k - 1), k)].max(
        event, mode="drop")
    hit = last >= 0
    buf = jnp.where(hit[:, None], x[jnp.maximum(last, 0)], buf)
    extra = jnp.where(hit, y.astype(jnp.int32)[jnp.maximum(last, 0)], extra)
    rng, sub = jax.random.split(rng)
    mask = jax.random.bernoulli(sub, rate, (y.shape[0],))
    return (buf, extra, seen, key), mask, rng


def sample(st, x, y, rng, rate):
    """The key chain runs on the host's CPU: it is integer arithmetic,
    the same on every backend, and one event after another, which a CPU
    steps through faster than an accelerator."""
    cpu = jax.devices("cpu")[0]
    here = next(iter(x.devices()))
    drawn = _slots(jax.device_put(st[3], cpu), jax.device_put(st[2], cpu),
                   int(y.shape[0]))
    return _fill(st, x, y, rng, jax.device_put(drawn, here), rate=rate)


@jax.jit
def train(st, x, y, mask):
    (w, b, g2, n), (pn, correct, loss, ewma) = st
    dt = x.dtype
    p = jax.nn.sigmoid(x @ w + b)
    err = (jnp.where(p > 0.5, 1, 0) != y).astype(dt)
    nb = p.shape[0]
    acc = jnp.mean((p > 0.5).astype(jnp.int32) == y).astype(dt)
    ll = -jnp.mean(y * jnp.log(p + 1e-9) + (1 - y) * jnp.log(1 - p + 1e-9))
    decay = 0.995 ** nb
    preq = (pn + nb, correct + acc * nb, loss + ll * nb,
            decay * ewma + (1 - decay) * acc)
    keep = mask.astype(dt)
    xm, ym = x * keep[:, None], y * mask
    q = jax.nn.sigmoid(xm @ w + b)
    e = q - ym.astype(dt)
    gw = xm.T @ e / nb + 1e-4 * w
    gb = e.mean()
    g2 = g2 + jnp.square(gw)
    w = w - 0.5 * gw * jax.lax.rsqrt(g2)
    b = b - 0.5 * gb
    return ((w, b, g2, n + nb), preq), p, err


@jax.jit
def drift(st, err):
    def step(s, e):
        n0, p0, s_min0, p_min0, _ = s
        n = n0 + 1.0
        p = p0 + (e - p0) / n
        sd = jnp.sqrt(p * (1 - p) / jnp.maximum(n, 1.0))
        better = (n >= 30) & ((p + sd) < (p_min0 + s_min0))
        p_min = jnp.where(better, p, p_min0)
        s_min = jnp.where(better, sd, s_min0)
        level = jnp.where((p + sd) > (p_min + 3.0 * s_min), 2,
                          jnp.where((p + sd) > (p_min + 2.0 * s_min), 1, 0))
        level = jnp.where(n < 30, 0, level).astype(jnp.int32)
        reset = level == 2
        return (jnp.where(reset, 0.0, n).astype(n.dtype),
                jnp.where(reset, 0.0, p).astype(p.dtype),
                jnp.where(reset, 1e9, s_min).astype(s_min.dtype),
                jnp.where(reset, 1e9, p_min).astype(p_min.dtype),
                level), level
    st, levels = jax.lax.scan(step, st, err)
    return st, jnp.any(levels == 2)


@functools.partial(jax.jit, static_argnames=("threshold",))
def alert(score, drifted, threshold):
    hot = jnp.mean((score > threshold).astype(jnp.float32))
    return jnp.logical_or(hot > 0.5, drifted)


def on_drift(train_state):
    (w, b, g2, n), preq = train_state
    return (w * 0.5, b * 0.5, jnp.full_like(g2, 1e-8),
            jnp.zeros((), n.dtype)), preq


# -- the uplink codec ----------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k",))
def topk_int8_ef(residual, x, k):
    xc = x.astype(jnp.float32) + residual
    mag = jnp.abs(xc).ravel()
    t = jnp.sort(mag)[mag.shape[0] - k]
    kept = jnp.abs(xc) >= t
    amax = jnp.max(jnp.where(kept, jnp.abs(xc), 0.0))
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(jnp.where(kept, xc, 0.0) / scale), -127, 127)
    dec = jnp.where(kept, q * scale, 0.0)
    return dec.astype(x.dtype), xc - dec


def codec_k(size: int, k_frac: float = 0.1) -> int:
    return max(1, int(round(k_frac * size)))


class Uplink:
    """The wire between the sides: a per-channel carried residual."""

    def __init__(self, codec: str):
        if codec not in ("identity", "topk_int8_ef"):
            raise ValueError(f"reference has no codec {codec!r}")
        self.codec = codec
        self.residuals: Dict[str, jax.Array] = {}

    def __call__(self, env: dict) -> dict:
        if self.codec == "identity":
            return env
        out = dict(env)
        for key, v in env.items():
            if key == "rng" or not jnp.issubdtype(v.dtype, jnp.floating):
                continue
            r = self.residuals.get(key)
            if r is None or r.shape != v.shape:
                r = jnp.zeros(v.shape, jnp.float32)
            out[key], self.residuals[key] = topk_int8_ef(
                r, v, codec_k(int(np.prod(v.shape))))
        return out


# -- one batch under a plan ------------------------------------------------------

def _apply(name: str, states: dict, env: dict, g: dict) -> dict:
    env = dict(env)
    if name == "normalize":
        states[name], env["x"] = normalize(states[name], env["x"])
    elif name == "sketch":
        states[name] = sketch(states[name], env["x"])
    elif name == "anomaly":
        states[name], env["score"] = anomaly(states[name], env["x"])
    elif name == "sample":
        states[name], env["mask"], env["rng"] = sample(
            states[name], env["x"], env["y"], env["rng"],
            rate=float(g["sample_rate"]))
    elif name == "train":
        states[name], env["p"], env["err"] = train(
            states[name], env["x"], env["y"], env["mask"])
    elif name == "drift":
        states[name], env["drifted"] = drift(states[name], env["err"])
    elif name == "alert":
        env["alert"] = alert(env["score"], env["drifted"],
                             threshold=float(g["anomaly_threshold"]))
    return env


def replay(config: dict, batches: Iterable[Tuple[np.ndarray, np.ndarray]],
           steps: Sequence[int], plans: Sequence[Tuple[frozenset, str]],
           root_seed: int, keep: Iterable[int] = (),
           keep_decoded: Iterable[int] = (), dt=jnp.float32) -> dict:
    """Run the job over ``batches`` (host ``(x, y)`` in processing order) at
    the program's step numbers under each step's ``(edge ops, codec)``.
    Returns the outputs of the steps in ``keep``, the decoded uplink
    payloads of the steps in ``keep_decoded``, and the final states."""
    g = config["graph"]
    if g["kind"] != "fanout_stream_graph" or g["drift_detector"] != "ddm":
        raise ValueError(f"reference covers the DDM fan-out job, not {g}")
    keep, keep_decoded = set(keep), set(keep_decoded)
    states = init_states(g, dt)
    root = jax.random.PRNGKey(root_seed)
    wires: Dict[str, Uplink] = {}
    outputs: Dict[int, dict] = {}
    decoded: Dict[int, dict] = {}
    for (x, y), step, (edge, codec) in zip(batches, steps, plans):
        wire = wires.setdefault(codec, Uplink(codec))
        if len(wires) > 1:
            raise ValueError("the codec changed during the run")
        env = {"x": jnp.asarray(x).astype(dt), "y": jnp.asarray(y),
               "rng": jax.random.fold_in(root, step)}
        order = [n for n in OPS if n in edge] + \
                [n for n in OPS if n not in edge]
        crossed = False      # the source is on the edge side
        for name in order:
            if name not in edge and not crossed:
                env = wire(env)
                crossed = True
                if step in keep_decoded:
                    decoded[step] = {k: v for k, v in env.items()
                                     if k != "rng"}
            env = _apply(name, states, env, g)
        if bool(env["drifted"]):
            states["train"] = on_drift(states["train"])
        if step in keep:
            outputs[step] = {k: env[k] for k in OUTPUT_KEYS}
    return {"outputs": outputs, "decoded": decoded, "states": states}
