"""Streams substrate: generators, drift detectors, preprocessing, sampling,
sketches, fusion, feeder (incl. straggler rescue)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.streams import drift as dd
from repro.streams import preprocess as prep
from repro.streams import sampling as samp
from repro.streams import sketches as sk
from repro.streams.events import StreamBatch
from repro.streams.feeder import StreamFeeder
from repro.streams.fusion import DelayedLabelAligner, WindowJoin
from repro.streams.generators import (DriftSpec, FittedGaussianGenerator,
                                      HyperplaneStream, TokenStream)


# ---------------------------------------------------------------------------
# Generators
# ---------------------------------------------------------------------------

def test_generator_replayable():
    g = HyperplaneStream(dim=8, seed=3)
    a = g.batch(7, 64)
    b = g.batch(7, 64)
    np.testing.assert_array_equal(np.asarray(a.data["x"]),
                                  np.asarray(b.data["x"]))
    assert a.watermark == b.watermark


def test_generator_drift_changes_concept():
    g = HyperplaneStream(dim=8, seed=0,
                         drift=DriftSpec("abrupt", at=0.5), horizon=1000.0)
    early = g.batch(0, 100)
    late = g.batch(9, 100)
    # same x distribution, different labeling rule: a linear model fit on
    # early should do poorly late
    from repro.ml import online
    st = online.logreg_init(8)
    for _ in range(50):
        st = online.logreg_update(st, jnp.asarray(early.data["x"]),
                                  jnp.asarray(early.data["y"]))
    acc_early = float(((online.logreg_predict(st, jnp.asarray(early.data["x"]))
                        > .5).astype(np.int32) == early.data["y"]).mean())
    acc_late = float(((online.logreg_predict(st, jnp.asarray(late.data["x"]))
                       > .5).astype(np.int32) == late.data["y"]).mean())
    assert acc_early > 0.85
    assert acc_late < acc_early - 0.2


def test_token_stream_shapes_and_drift():
    g = TokenStream(vocab_size=128, seq_len=32,
                    drift=DriftSpec("abrupt", at=0.5), horizon=32 * 32 * 10)
    b0 = g.batch(0, 16)
    b9 = g.batch(9, 16)
    assert b0.data["tokens"].shape == (16, 32)
    assert b0.data["tokens"].max() < 128
    # drifted domain uses permuted vocab -> different unigram histogram
    h0 = np.bincount(b0.data["tokens"].ravel(), minlength=128)
    h9 = np.bincount(b9.data["tokens"].ravel(), minlength=128)
    assert np.abs(h0 - h9).sum() > 0


def test_fitted_generator_matches_moments():
    rng = np.random.default_rng(0)
    x = np.concatenate([rng.normal(-2, 1, (500, 4)),
                        rng.normal(3, 0.5, (500, 4))]).astype(np.float32)
    y = np.concatenate([np.zeros(500, np.int32), np.ones(500, np.int32)])
    gen = FittedGaussianGenerator.fit(x, y, seed=1)
    b = gen.batch(0, 4000)
    xs, ys = np.asarray(b.data["x"]), np.asarray(b.data["y"])
    for c, mu in [(0, -2.0), (1, 3.0)]:
        assert abs(xs[ys == c].mean() - mu) < 0.2
    # privacy: generator object stores only moments, never the data
    assert gen.means.shape == (2, 4)


# ---------------------------------------------------------------------------
# Drift detectors
# ---------------------------------------------------------------------------

def _error_stream(n0=800, n1=800, p0=0.1, p1=0.5, seed=0):
    rng = np.random.default_rng(seed)
    e = np.concatenate([(rng.random(n0) < p0), (rng.random(n1) < p1)])
    return jnp.asarray(e.astype(np.float32))


@pytest.mark.parametrize("name,init,step", [
    ("ddm", dd.ddm_init, dd.ddm_step),
    ("eddm", dd.eddm_init, dd.eddm_step),
    ("ph", dd.ph_init, dd.ph_step),
    ("adwin", dd.adwin_init, dd.adwin_step),
])
def test_detector_fires_after_shift_not_before(name, init, step):
    errs = _error_stream()
    _, levels = dd.run_detector(jax.jit(step), init(), errs)
    levels = np.asarray(levels)
    pre = levels[:700]
    post = levels[800:]
    assert (pre == dd.DRIFT).sum() == 0, f"{name}: false alarm before shift"
    assert (post == dd.DRIFT).sum() >= 1, f"{name}: missed drift"


def test_detector_stable_stream_low_false_positive():
    rng = np.random.default_rng(1)
    errs = jnp.asarray((rng.random(4000) < 0.15).astype(np.float32))
    for init, step in [(dd.ddm_init, dd.ddm_step), (dd.ph_init, dd.ph_step)]:
        _, levels = dd.run_detector(jax.jit(step), init(), errs)
        assert (np.asarray(levels) == dd.DRIFT).mean() < 0.01


# ---------------------------------------------------------------------------
# Preprocess / sampling / sketches
# ---------------------------------------------------------------------------

def test_norm_update_apply_standardizes():
    rng = np.random.default_rng(0)
    st = prep.norm_init(4)
    y = None
    for i in range(20):
        x = jnp.asarray(rng.normal(5.0, 3.0, (128, 4)).astype(np.float32))
        st, y = prep.norm_update_apply(st, x)
    assert abs(float(y.mean())) < 0.2
    assert abs(float(y.std()) - 1.0) < 0.2


def test_impute_uses_running_mean():
    st = prep.NormState(jnp.asarray(10.0), jnp.asarray([2.0, 3.0]),
                        jnp.ones((2,)))
    x = jnp.asarray([[np.nan, 1.0], [4.0, np.nan]], jnp.float32)
    y = prep.impute_with_mean(st, x)
    np.testing.assert_allclose(np.asarray(y), [[2.0, 1.0], [4.0, 3.0]])


def test_reservoir_uniformity():
    st = samp.reservoir_init(64, 1, seed=0)
    xs = jnp.arange(2048, dtype=jnp.float32)[:, None]
    ys = jnp.zeros(2048, jnp.int32)
    st = jax.jit(samp.reservoir_update)(st, xs, ys)
    vals = np.asarray(st.buf[:, 0])
    assert int(st.seen) == 2048
    assert len(np.unique(vals)) == 64
    # uniform over history: mean of sample ~ mean of stream
    assert abs(vals.mean() - 1023.5) < 200


def _algorithm_r(state, x, y):
    """The oracle: Algorithm R one event at a time, the whole buffer in the
    scan's carry."""
    k = state.buf.shape[0]

    def step(st, item):
        xi, yi = item
        rng, r1 = jax.random.split(st.rng)
        seen = st.seen + 1
        # position: if seen <= k -> seen-1 else random j in [0, seen)
        j = jax.random.randint(r1, (), 0, seen)
        idx = jnp.where(seen <= k, seen - 1, j)
        take = (seen <= k) | (j < k)
        idx = jnp.clip(idx, 0, k - 1)
        buf = jnp.where(take, st.buf.at[idx].set(xi), st.buf)
        extra = jnp.where(take, st.extra.at[idx].set(yi), st.extra)
        return samp.ReservoirState(buf, extra, seen, rng), None

    state, _ = jax.lax.scan(step, state, (x, y.astype(jnp.int32)))
    return state


def _assert_states_equal(a, b):
    for name, u, v in zip(samp.ReservoirState._fields, a, b):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v),
                                      err_msg=name)


@pytest.mark.parametrize("k,n,dim,batches", [
    (256, 16384, 10, 3),      # the benchmark's fan-out job
    (64, 100, 3, 3),          # seen crosses k inside the first batch
    (64, 20, 3, 5),           # batches smaller than k
    (4, 1, 2, 8),             # one event at a time
    (4, 4096, 3, 3),          # many events draw each slot
], ids=["cell", "crosses_k", "under_k", "one_event", "collisions"])
def test_reservoir_update_is_algorithm_r(k, n, dim, batches):
    rng = np.random.default_rng(k * 7919 + n)
    new = ref = samp.reservoir_init(k, dim, seed=5)
    update, oracle = jax.jit(samp.reservoir_update), jax.jit(_algorithm_r)
    for _ in range(batches):
        x = jnp.asarray(rng.normal(size=(n, dim)).astype(np.float32))
        y = jnp.asarray(rng.integers(0, 2, n).astype(np.int32))
        new, ref = update(new, x, y), oracle(ref, x, y)
        _assert_states_equal(new, ref)
    assert int(new.seen) == n * batches


def _loop_carries(jaxpr):
    """(primitive, carry avals) of every scan and while loop, nested ones
    included."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    out = []
    for eqn in jaxpr.eqns:
        p = eqn.params
        if eqn.primitive.name == "scan":
            c0 = p["num_consts"]
            out.append(("scan", p["jaxpr"].in_avals[c0:c0 + p["num_carry"]]))
        elif eqn.primitive.name == "while":
            out.append(("while",
                        p["body_jaxpr"].in_avals[p["body_nconsts"]:]))
        for v in p.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, ClosedJaxpr):
                    out += _loop_carries(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    out += _loop_carries(sub)
    return out


def test_reservoir_loop_carries_only_the_key():
    """The buffer stays out of the per-event loop: the one loop there is
    carries the PRNG key and nothing else."""
    st = samp.reservoir_init(256, 10)
    x = jnp.zeros((16384, 10), jnp.float32)
    y = jnp.zeros((16384,), jnp.int32)
    loops = _loop_carries(jax.make_jaxpr(samp.reservoir_update)(st, x, y)
                          .jaxpr)
    assert [(kind, [(a.shape, a.dtype) for a in carry])
            for kind, carry in loops] == [
        ("scan", [(st.rng.shape, st.rng.dtype)])]


def test_stratified_update_unchanged(monkeypatch):
    """Per-class reservoirs, fed one event at a time, end as they did with
    the per-event Algorithm R scan."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(40, 3)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 2, 40).astype(np.int32))
    sr = samp.stratified_init(2, 4, 3, seed=11)
    new = samp.stratified_update(sr, x, y, 2)
    monkeypatch.setattr(samp, "reservoir_update", _algorithm_r)
    ref = samp.stratified_update(sr, x, y, 2)
    _assert_states_equal(new.states, ref.states)
    np.testing.assert_array_equal(np.asarray(new.states.seen),
                                  [int((y == c).sum()) for c in range(2)])


def test_misra_gries_finds_heavy_hitter():
    rng = np.random.default_rng(0)
    ids = np.where(rng.random(2000) < 0.3, 7, rng.integers(100, 10_000, 2000))
    mg = sk.mg_init(16)
    mg = jax.jit(sk.mg_update)(mg, jnp.asarray(ids, jnp.int32))
    keys = np.asarray(mg.keys)
    counts = np.asarray(mg.counts)
    assert 7 in keys[counts > 0]
    top = keys[np.argmax(counts)]
    assert top == 7


def test_countmin_streaming_estimates():
    cm = sk.countmin_init(4, 512, seed=0)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 100, 5000), jnp.int32)
    cm = sk.countmin_add(cm, ids)
    true = np.bincount(np.asarray(ids), minlength=100)
    est = np.asarray(sk.countmin_query(cm, jnp.arange(100, dtype=jnp.int32)))
    assert (est >= true).all()
    assert (est - true).mean() < 40


def test_countmin_edge_cloud_path_parity(monkeypatch):
    """The sketch an edge node builds on the reference path and the one a
    cloud/TPU node builds through the Pallas kernel must be the SAME
    sketch — counts merge across tiers, so any divergence corrupts the
    global summary. (Kernel path runs in interpret mode here.)"""
    monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(42)
    ids = jnp.asarray(rng.integers(0, 3000, 901), jnp.int32)
    cm0 = sk.countmin_init(depth=3, width=257, seed=5)
    edge = sk.countmin_add(cm0, ids, use_kernel=False)
    cloud = sk.countmin_add(cm0, ids, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(edge.table),
                                  np.asarray(cloud.table))
    edge_cm, edge_est = sk.countmin_add_query(cm0, ids, use_kernel=False)
    cloud_cm, cloud_est = sk.countmin_add_query(cm0, ids, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(edge_cm.table),
                                  np.asarray(cloud_cm.table))
    np.testing.assert_array_equal(np.asarray(edge_est), np.asarray(cloud_est))


def test_countmin_dispatch_is_recorded_and_loud(monkeypatch):
    """Regression for the silent-fallback bug: a kernel request that
    cannot run must (a) warn, (b) fall back correctly, and (c) be
    visible in the dispatch counter — it used to vanish without trace."""
    monkeypatch.delenv("REPRO_FORCE_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("JAX_PALLAS_INTERPRET", raising=False)
    if jax.default_backend() == "tpu":
        pytest.skip("fallback path requires a no-Pallas backend")
    rng = np.random.default_rng(6)
    ids = jnp.asarray(rng.integers(0, 500, 123), jnp.int32)
    cm = sk.countmin_init(depth=2, width=64)
    sk.reset_dispatch_counts()
    sk.countmin_add(cm, ids)                       # auto -> reference on CPU
    with pytest.warns(RuntimeWarning, match="falling back"):
        fell_back = sk.countmin_add(cm, ids, use_kernel=True)
    np.testing.assert_array_equal(
        np.asarray(fell_back.table),
        np.asarray(sk.countmin_add(cm, ids, use_kernel=False).table))
    counts = sk.dispatch_counts()
    assert counts == {"pallas": 0, "reference": 3}
    # and the kernel path is counted as pallas when it actually runs
    monkeypatch.setenv("REPRO_FORCE_PALLAS_INTERPRET", "1")
    sk.reset_dispatch_counts()
    sk.countmin_add(cm, ids, use_kernel=True)
    assert sk.dispatch_counts() == {"pallas": 1, "reference": 0}
    sk.reset_dispatch_counts()


# ---------------------------------------------------------------------------
# Fusion
# ---------------------------------------------------------------------------

def test_window_join_matches_within_tolerance():
    right = StreamBatch(data={"x": np.arange(10, dtype=np.float32)[:, None]},
                        ts=np.arange(10, dtype=np.float64))
    left = StreamBatch(data={"x": np.zeros((3, 1), np.float32)},
                       ts=np.asarray([2.05, 5.4, 30.0]))
    j = WindowJoin(tolerance=0.5)
    j.push_right(right)
    joined, matched = j.join_left(left)
    assert matched.tolist() == [True, True, False]
    assert joined.data["joined"][0, 0] == 2.0
    assert joined.data["joined"][1, 0] == 5.0


def test_window_join_circular_buffer_reuses_storage():
    """Regression: push_right must write into the preallocated ring in
    place (head/tail indices, amortized O(1) eviction) instead of
    reallocating the whole buffer per push."""
    j = WindowJoin(tolerance=0.5, max_buffer=100)
    mk = lambda lo: StreamBatch(
        data={"x": np.full((40, 2), float(lo), np.float32)},
        ts=np.arange(lo, lo + 40, dtype=np.float64))
    j.push_right(mk(0))
    buf_t, buf_v = j._buf_t, j._buf_v
    assert len(buf_t) >= 2 * j.max_buffer     # preallocated capacity
    for lo in range(40, 40 * 5, 40):
        j.push_right(mk(lo))
        assert j._buf_t is buf_t and j._buf_v is buf_v, \
            "push reallocated the ring buffer"
    # eviction keeps only the newest max_buffer rows, oldest first
    assert len(j._rt) == 100
    np.testing.assert_array_equal(j._rt, np.arange(100, 200, dtype=np.float64))
    # wrap-around compaction keeps join results identical
    for lo in range(200, 1200, 40):
        j.push_right(mk(lo))
    assert j._buf_t is buf_t, "compaction must reuse the same storage"
    left = StreamBatch(data={"x": np.zeros((3, 1), np.float32)},
                       ts=np.asarray([1100.2, 1150.0, 10.0]))
    joined, matched = j.join_left(left)
    assert matched.tolist() == [True, True, False]
    assert joined.data["joined"][0, 0] == 1080.0   # batch holding ts=1100
    assert joined.data["joined"][1, 0] == 1120.0


def test_window_join_promotes_value_dtype_mid_stream():
    """A wider dtype arriving after the ring is allocated must widen the
    buffer (as the old concatenate path did), not silently truncate."""
    j = WindowJoin(tolerance=0.5, max_buffer=16)
    j.push_right(StreamBatch(
        data={"x": np.arange(4)[:, None]},          # int64 values
        ts=np.arange(4, dtype=np.float64)))
    j.push_right(StreamBatch(
        data={"x": np.full((4, 1), 7.5, np.float64)},
        ts=np.arange(4, 8, dtype=np.float64)))
    left = StreamBatch(data={"x": np.zeros((1, 1), np.float32)},
                       ts=np.asarray([5.0]))
    joined, matched = j.join_left(left)
    assert matched.all()
    assert joined.data["joined"][0, 0] == 7.5       # not truncated to 7


def test_window_join_oversized_push_keeps_newest():
    j = WindowJoin(tolerance=0.5, max_buffer=10)
    j.push_right(StreamBatch(
        data={"x": np.arange(25, dtype=np.float32)[:, None]},
        ts=np.arange(25, dtype=np.float64)))
    assert len(j._rt) == 10
    np.testing.assert_array_equal(j._rt, np.arange(15, 25, dtype=np.float64))


def test_delayed_label_aligner():
    al = DelayedLabelAligner()
    al.push_features(np.arange(5), np.arange(5, dtype=np.float64),
                     np.random.default_rng(0).normal(size=(5, 3)).astype(np.float32))
    assert al.backlog == 5
    out = al.push_labels(np.asarray([1, 3]), np.asarray([0, 1], np.int32))
    assert out is not None and out.n == 2
    assert al.backlog == 3
    assert out.data["y"].tolist() == [0, 1]


# ---------------------------------------------------------------------------
# Feeder + straggler rescue
# ---------------------------------------------------------------------------

def test_feeder_straggler_rescue_preserves_data():
    gen = HyperplaneStream(dim=4, seed=0)

    def make(shard, idx, n):
        g = HyperplaneStream(dim=4, seed=shard)
        return g.batch(idx, n)

    slow = StreamFeeder(make, n_shards=2, batch_per_shard=32,
                        deadline_s=0.05,
                        inject_straggle=lambda s, i: 0.3 if (s == 1 and i == 1) else 0.0)
    slow.start()
    batches = [slow.next() for _ in range(3)]
    slow.stop()
    assert slow.stats.straggler_rescues >= 1
    # rescued batch identical to what the straggler would have produced
    want = HyperplaneStream(dim=4, seed=1).batch(1, 32)
    got = batches[1]
    np.testing.assert_array_equal(
        np.asarray(got.data["x"][32:]), np.asarray(want.data["x"]))
