"""The harness finds a cell's files by the names in BENCHMARK.json: a
configuration, a traffic mix or a metric dropped into its directory is
found without editing the harness. Without a TPU the command exits non-zero
and prints no result."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import _paths  # noqa: F401
from bench import cells

ROOT = cells.ROOT


def _copy_bench(tmp: pathlib.Path) -> pathlib.Path:
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    return tmp


def test_every_workload_resolves_its_files():
    bench = cells.load_json(ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.driver().setup and cell.reference()
        names = set(cell.metric_readers(False)) | set(
            cell.metric_readers(True))
        assert "setup_s" in names and len(cell.per_layer) >= 1
        assert (cell.bench_dir / "limits" / f"{w['name']}.json").is_file()


def test_new_files_are_found_by_name(tmp_path):
    root = _copy_bench(tmp_path)
    bdir = root / "bench"
    shutil.copy(bdir / "configs" / "fanout_moa_hyperplane.json",
                bdir / "configs" / "fanout_other.json")
    traffic = json.loads((bdir / "traffic" / "stream_backlog_b16384.json")
                         .read_text())
    (bdir / "traffic" / "stream_slow.json").write_text(
        json.dumps(dict(traffic, rate=1000.0)))
    (bdir / "metrics" / "queue_depth.py").write_text(
        "def read(run):\n    return 7.0\n")
    (bdir / "limits" / "fanout_other.slow.json").write_text(
        (bdir / "limits" / "fanout_moa.saturated.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="fanout_other",
                                 file="bench/configs/fanout_other.json"))
    bench["workloads"].append({"name": "fanout_other.slow",
                               "config": "fanout_other",
                               "traffic": "stream_slow", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "queue_depth", "unit": "items",
                               "better": "lower", "source": "host_clock",
                               "layer": "device", "moves": "setup_s",
                               "workloads": ["fanout_other.slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load_cell("fanout_other.slow", root)
    assert cell.traffic["rate"] == 1000.0
    readers = cell.metric_readers(True)
    assert set(readers) == {"queue_depth"}
    assert readers["queue_depth"].read(None) == 7.0
    assert cell.driver().__name__ != ""


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "fanout_moa.saturated", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and p.stdout.strip() == ""


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    root = _copy_bench(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fanout_moa.saturated",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=root, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_stream_deployment_plans_every_op_on_the_chip():
    """At the rate the stream cell's traffic tells the controller, the
    committed deployment's plan is all-cloud and feasible: the one chip
    runs the whole job."""
    from repro.core.placement import place_frontier
    drv = cells.load_module(cells.BENCH_DIR / "drivers" / "stream.py")
    cell = cells.load_cell("fanout_moa.saturated")
    orch = drv.build_job(cell)
    plan, frontier = place_frontier(orch.pipeline, orch.cluster,
                                    float(cell.traffic["rate"]))
    assert not frontier and plan.feasible
    assert orch.codec.name == "identity"
