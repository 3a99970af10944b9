"""The shipped example set is the public face of the API: every script
must run green, and none may emit a DeprecationWarning (it would land in
every new user's first session). Scripts run as real subprocesses with
warnings forced on, so a regression anywhere in the import graph — not
just in the example text — trips this."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
EXAMPLES = sorted((REPO / "examples").glob("*.py"))


def test_example_set_is_complete():
    names = {p.name for p in EXAMPLES}
    assert {"quickstart.py", "edge_cloud_pipeline.py", "edge_serving.py",
            "train_stream_lm.py"} <= names


@pytest.mark.slow
def test_examples_run_clean_of_deprecated_shims():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    procs = {
        p.name: subprocess.Popen(
            [sys.executable, "-W", "always::DeprecationWarning", str(p)],
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for p in EXAMPLES}
    failures = []
    for name, proc in procs.items():
        out, _ = proc.communicate(timeout=600)
        if proc.returncode != 0:
            failures.append(f"{name} exited {proc.returncode}:\n{out}")
        if "DeprecationWarning" in out:
            failures.append(f"{name} emitted a DeprecationWarning:\n{out}")
    assert not failures, "\n\n".join(failures)
