"""``correct`` on the CPU at sizes a test run holds: the sound program
passes; the control (the reference one precision step down, in the
program's place) fails; and a run with the timed path broken underneath
fails, once for each fault the cell can have. The harness's look for a
chip is skipped; the rest of a run is driven as on the chip."""

import time

import jax
import jax.numpy as jnp
import pytest

import _paths  # noqa: F401
from bench import cells, harness


@pytest.fixture
def kernels(monkeypatch):
    """The codec's fused kernel, in Pallas interpret mode: the path a TPU
    takes (its selection keeps every element tied at the threshold, as
    the reference does; the CPU's jnp path keeps exactly k)."""
    monkeypatch.setenv("JAX_PALLAS_INTERPRET", "1")


def stream_cell():
    cell = cells.load_cell("fanout_moa.saturated")
    cell.traffic = dict(cell.traffic, batch_events=512, ring_batches=3,
                        warmup_batches=1)
    return cell


def serve_cell(width=256, vocab=4096):
    """The serving cell at a test size: two layers, short prompts and long
    outputs, and weights at std 0.05, which sharpen attention enough that
    a decode step that loses the cache shows in the served tokens."""
    cell = cells.load_cell("qwen2_1_5b.serve_saturated")
    cfg = dict(cell.config, hidden_size=width, intermediate_size=2 * width,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=vocab,
               initializer_range=0.05)
    cfg["serve"] = dict(cfg["serve"], batch_size=4, max_len=40,
                        prompt_len=16, max_new_tokens=24)
    cell.config = cfg
    cell.traffic = dict(cell.traffic, rate=40.0, classes=[
        {"prompt_len": p, "output_len": o, "weight": 1}
        for p, o in ((4, 24), (8, 8), (12, 24), (16, 8))])
    return cell


def run(cell, seed=5, seconds=1.0):
    out = harness.run_cell(cell.name, seed, seconds, False,
                           time.perf_counter(), require_accelerator=False,
                           cell=cell)
    return out["result"]


def test_sound_stream_run_is_correct(kernels):
    res = run(stream_cell())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def test_sound_serve_run_is_correct_and_waves_are_padded(monkeypatch):
    from repro.serve import ops
    rows = []
    real = ops.serve_wave_batch

    def spy(engine, prompts, **kw):
        rows.append(len(prompts))
        return real(engine, prompts, **kw)

    drv = cells.load_module(cells.BENCH_DIR / "drivers" / "serve.py")
    monkeypatch.setattr(ops, "serve_wave_batch", spy)
    res = run(serve_cell())
    assert res["correct"], res["checks"]
    assert rows and set(rows) == {4}
    assert drv.SAMPLE >= 2


# -- faults planted under the stream cell's timed path ------------------------

def _moments_unchanged(m, x):
    return m


def _norm_on_half(state, x):
    from repro.streams import preprocess as prep
    half = x.shape[0] // 2
    st, _ = prep.NormState(*state), None
    n_b = half
    mean_b = jnp.mean(x[:half], axis=0)
    m2_b = jnp.sum(jnp.square(x[:half] - mean_b), axis=0)
    n = st.n + n_b
    delta = mean_b - st.mean
    mean = st.mean + delta * (n_b / jnp.maximum(n, 1.0))
    m2 = st.m2 + m2_b + jnp.square(delta) * st.n * n_b / jnp.maximum(n, 1.0)
    var = m2 / jnp.maximum(n - 1.0, 1.0)
    return prep.NormState(n, mean, m2), (x - mean) * jax.lax.rsqrt(var + 1e-6)


def _score_altered(state, x):
    from repro.ml import online
    return online.__dict__["_bench_real_score"](state, x).at[0].add(1.0)


@pytest.mark.parametrize("module,name,fault", [
    ("repro.streams.sketches", "moments_update", _moments_unchanged),
    ("repro.streams.preprocess", "norm_update_apply", _norm_on_half),
    ("repro.ml.online", "anomaly_score", _score_altered),
], ids=["state_unchanged", "half_batch", "answer_altered"])
def test_stream_fault_is_not_correct(kernels, monkeypatch, module, name,
                                     fault):
    import importlib
    mod = importlib.import_module(module)
    monkeypatch.setitem(mod.__dict__, "_bench_real_" + name.split("_")[-1],
                        getattr(mod, name))
    monkeypatch.setattr(mod, name, fault)
    res = run(stream_cell())
    assert not res["correct"], res["checks"]


def test_stream_control_is_not_correct(kernels):
    cell = stream_cell()
    drv = cell.driver()
    r = harness.Run(cell=cell, seed=9, seconds=1.0, trace=False,
                    t_process=time.perf_counter())
    r.compiles = None
    sys_ = drv.setup(cell, r)
    drv.window(sys_, r, None)
    got = drv.calibrate(sys_, r, control=True)
    assert harness.judge(got["program"], drv.limits(cell))[0]
    assert not harness.judge(got["control"], drv.limits(cell))[0]


# -- faults planted under the serving cell's timed path -----------------------

def _decode_token_altered(self, params, caches, tokens, rng):
    from repro.serve.engine import ServeEngine
    tok, caches, rng = ServeEngine.__dict__["_bench_real_decode"](
        self, params, caches, tokens, rng)
    return tok.at[0].set((tok[0] + 1) % self.cfg.vocab_size), caches, rng


def _decode_state_unchanged(self, params, caches, tokens, rng):
    from repro.serve.engine import ServeEngine
    tok, _, rng = ServeEngine.__dict__["_bench_real_decode"](
        self, params, caches, tokens, rng)
    return tok, caches, rng


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_is_not_correct(seed):
    """The float8 control, read at the positions of random prompts and
    continuations at the published widths (two layers, a 16,384-token
    vocabulary), has its first choice fall below the reference's best by
    more than the cell's limit."""
    import numpy as np
    cell = serve_cell()
    cfg = dict(cell.config, hidden_size=1536, intermediate_size=8960,
               num_attention_heads=12, num_key_value_heads=2,
               vocab_size=16384, initializer_range=0.02)
    rng = np.random.default_rng(seed)
    rows = [{"prompt": rng.integers(0, 16384, 32).astype(np.int32),
             "served": rng.integers(0, 16384, 32).astype(np.int32)}
            for _ in range(4)]
    gaps = cell.reference().served_gaps(cfg, seed, rows, control="fp8")
    drv = cell.driver()
    widest = float(max(g.max() for g in gaps["control"]))
    assert not harness.judge({"served_gap": widest}, drv.limits(cell))[0]


@pytest.mark.parametrize("fault", [_decode_token_altered,
                                   _decode_state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_serve_fault_is_not_correct(monkeypatch, fault):
    from repro.serve.engine import ServeEngine
    monkeypatch.setattr(ServeEngine, "_bench_real_decode",
                        ServeEngine._decode_fn, raising=False)
    monkeypatch.setattr(ServeEngine, "_decode_fn", fault)
    res = run(serve_cell())
    assert not res["correct"], res["checks"]


def test_serve_calibration_reads_the_gap_of_unpadded_prompts():
    """Beside the compared gap, calibration reads the served tokens against
    the reference given each request's own, unpadded prompt."""
    cell = serve_cell()
    drv = cell.driver()
    r = harness.Run(cell=cell, seed=4, seconds=1.0, trace=False,
                    t_process=time.perf_counter())
    r.compiles = None
    sys_ = drv.setup(cell, r)
    drv.window(sys_, r, None)
    got = drv.calibrate(sys_, r, control=False)
    assert got["padded_rows"] > 0
    assert got["served_gap_unpadded"] >= 0.0
    assert harness.judge(got["program"], drv.limits(cell))[0]
