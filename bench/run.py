"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints a counters line, then as its last line one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and, when
traced, ``breakdown``), ending with ``checks``. Exits non-zero, with no
result line, where JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime's logs go inside the checkout, not to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_out" / "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

    from bench import harness
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), T_PROCESS, root=ROOT)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(harness.dumps(out["earlier"]), flush=True)
    print(harness.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
