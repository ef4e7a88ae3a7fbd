"""Every open-loop traffic file of the benchmark keeps PR 43's contract
(``kfbench/README.md``, "What ``--seed`` draws and what the traffic file
draws"): the file states its ``schedule_seed``, its ``pairing_seed`` and
why its rate is what it is, and two runs with different ``--seed`` are
offered the same requests at the same instants with other prompt ids.
One case a file, so a file added later is held from the start."""

import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SERVING = sorted(
    os.path.basename(path)[:-len(".json")]
    for path in glob.glob(os.path.join(ROOT, "kfbench", "traffic", "*.json"))
    if json.load(open(path)).get("loop") == "serve")


def test_there_are_serving_files_and_every_serving_cell_names_one():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    named = {w["traffic"] for w in bench["workloads"]}
    assert len(SERVING) >= 6 and set(SERVING) <= named
    assert "mathreason-open" in SERVING


@pytest.mark.parametrize("name", SERVING)
def test_an_open_loop_file_fixes_its_schedule_whatever_the_seed(name):
    from kfbench.lib import files
    from kfbench.loops import serve

    tr = files.load_traffic(name)
    for key in ("schedule_seed", "pairing_seed"):
        assert isinstance(tr[key], int), key
    assert isinstance(tr["rate_reason"], str) and len(tr["rate_reason"]) > 40
    assert tr["rate_rps"] > 0 and tr["preroll_s"] > 0
    runs = [serve.offered({"config": {"vocab_size": 320}, "traffic": tr,
                           "seed": seed, "seconds": 40.0})
            for seed in (7, 2 ** 31 + 7)]
    first, second = ([(r.due, len(r.prompt), r.max_new, r.in_window)
                      for r in run] for run in runs)
    assert first == second and len(first) >= 2
    assert first == sorted(first, key=lambda r: r[0])
    assert all(a.prompt != b.prompt for a, b in zip(*runs))
    assert all(p + o <= tr["max_total"] for _, p, o, _ in first)
