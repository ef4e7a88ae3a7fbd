"""The benchmark's own tests: ``python -m pytest kfbench/tests -q`` on
the CPU.  They never touch a chip."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

CELLS = ["gpt2m-train-1chip", "gpt2l-serve-chat", "gpt2m-train-4chip"]


def rehearse(workload, *extra, worker=None, seed=5, seconds=2, trace=0):
    """Run one rehearsal of a cell as the driver would a run; return
    (exit code, the last line of stdout parsed, all of stdout)."""
    code = ("import sys; sys.path.insert(0, %r); import kfbench.run as r; "
            "r.WORKER = %r or r.WORKER; sys.exit(r.main(sys.argv[1:]))"
            % (ROOT, worker))
    p = subprocess.run(
        [sys.executable, "-c", code, "--workload", workload, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace),
         "--rehearse", *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1]) if p.returncode == 0 and lines else None
    return p.returncode, last, p.stdout + p.stderr


@pytest.fixture(scope="session")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
