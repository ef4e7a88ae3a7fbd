"""The control of ``correct`` at a size a test run can hold: the
reference in float8 in the program's place comes out as not correct,
while the program's own numbers stay inside the same limits."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT
from kfbench.lib import files


def control(workload, seeds):
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "kfbench", "tools", "control.py"),
         "--workload", workload, "--rehearse", "--seconds", "3", "--seeds",
         *map(str, seeds)], cwd=ROOT, capture_output=True, text=True,
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    return [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]


def limits(loop):
    return files.load_json("rehearsal.json")["traffic"][loop]["check"]["limits"]


@pytest.mark.parametrize("workload,loop", [
    ("gpt2m-train-1chip", "train"), ("gpt2l-serve-chat", "serve")])
def test_float8_in_the_programs_place_is_not_correct(workload, loop):
    lim = limits(loop)
    for row in control(workload, (11, 12, 13)):
        over = [k for k, v in row["control"].items() if v > lim[k]]
        assert over, row  # it has to fail one of the cell's numbers
        for k, v in row.get("program", {}).items():
            if k in lim:
                assert v <= lim[k], row
