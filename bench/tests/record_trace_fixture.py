"""Records the small device trace that ``test_perfbench_devtrace.py``
reads: on a TPU, three matrix products under ``bench.execute`` spans with
sleeps under ``bench.wait_arrival`` spans between them, all inside a
``bench.trace_window`` span. Writes ``fixtures/v5e_small.xplane.pb`` and
``fixtures/v5e_small.json`` (what was run, and the host clock's spans).

    python bench/tests/record_trace_fixture.py   # on a machine with a TPU
"""

import glob
import json
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent


def main() -> int:
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    f = jax.jit(lambda a: (a @ a).sum())
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(f(a))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.trace_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("bench.execute"):
                jax.block_until_ready(f(a))
    jax.profiler.stop_trace()
    src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    (HERE / "fixtures").mkdir(exist_ok=True)
    shutil.copy(src, HERE / "fixtures" / "v5e_small.xplane.pb")
    (HERE / "fixtures" / "v5e_small.json").write_text(json.dumps({
        "device_kind": jax.devices()[0].device_kind,
        "program": "three (2048, 2048) bf16 matmuls, 20 ms sleeps between",
        "sleep_s": 0.02, "runs": 3}))
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main())
