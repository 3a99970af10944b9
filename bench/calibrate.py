"""Readings that the limits of ``correct`` are set from, for one cell, in
one process: for each seed a set-up and a short window at the cell's own
load, then the numbers the program's results give against the plain
reference and, for the control seeds, the numbers the control gives (the
reference computed one precision step below the configuration, put in the
program's place). With ``--rates`` it sweeps offered rates instead, and
reports each rate's end-to-end numbers.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds 10 [--rates r1,r2] [--out file]
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    # the TPU runtime's logs go inside the checkout, not to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_out" / "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

    import jax
    from bench import cells, harness
    from bench.spans import CompileCounter
    from repro.launch.compile_cache import enable_compile_cache
    cell = cells.load_cell(args.workload, ROOT)
    device = harness._device_info(jax, cell.chips, require=True)
    enable_compile_cache()
    driver = cell.driver()
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    out = open(args.out, "a") if args.out else None
    counter = CompileCounter()
    for rate in rates:
        if rate is not None:
            cell.traffic = dict(cell.traffic, rate=rate)
        for seed in args.seeds:
            run = harness.Run(cell=cell, seed=seed, seconds=args.seconds,
                              trace=False, t_process=time.perf_counter(),
                              device_kind=device["kind"], compiles=counter)
            sys_ = driver.setup(cell, run)
            driver.window(sys_, run, None)
            line = {"workload": cell.name, "seed": seed, "rate":
                    cell.traffic["rate"], "window_s": run.t1 - run.t0,
                    "setup_s": run.setup_s,
                    "compiles_in_window": counter.count(run.t0, run.t1),
                    **{m: r.read(run) for m, r in
                       cell.metric_readers(False).items()},
                    "counters": run.counters}
            if rate is None:
                t = time.perf_counter()
                line.update(driver.calibrate(
                    sys_, run, seed in args.control_seeds))
                line["reference_s"] = time.perf_counter() - t
            del sys_
            text = json.dumps(line, default=str)
            print(text, flush=True)
            if out:
                out.write(text + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
