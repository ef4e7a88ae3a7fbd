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

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
BIG = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits
#: per-layer readers that find something in every serving cell of an
#: expert model, so each such cell has to be on their lists
EXPERT_CELL_READERS = (
    "decode_slot_use", "decode_step_spread", "decode_read_ms",
    "first_token_ms_per_ktok", "engine_host_ms_per_step",
    "idle_ms_per_step.decode", "scope_ms_per_step.decode_dense",
    "decode_path_ms.moe_router", "decode_path_ms.moe_experts",
    "decode_path_ms.moe_shared", "moe_experts_touched",
    "moe_load_max_over_mean")


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


def one_schedule_whatever_the_seed(traffic_name, seconds=40.0):
    """An open-loop file's contract, held; returns its schedule.  Seeds
    1, 2 and one past 32 bits are offered the same requests at the same
    instants with other prompt ids; another ``schedule_seed`` gives
    another schedule of the same multiset, and so do the windows of
    ``tools/sweep_fresh.py``, which states one a window."""
    from kfbench.lib import files, traffic as gen
    from kfbench.loops import serve

    tr = files.load_traffic(traffic_name)
    assert tr["schedule_seed"] == 4343 and tr["pairing_seed"]
    schedule = gen.open_schedule(tr, seconds)
    assert schedule == sorted(schedule, key=lambda r: r[0])
    runs = [serve.offered({"config": {"vocab_size": 320}, "traffic": tr,
                           "seed": seed, "seconds": seconds})
            for seed in (1, 2, BIG)]
    for run in runs:
        assert [(r.due, len(r.prompt), r.max_new, r.in_window)
                for r in run] == schedule
    for a, b in ((0, 1), (0, 2), (1, 2)):
        assert all(x.prompt != y.prompt for x, y in zip(runs[a], runs[b]))

    def multiset(sch):
        return sorted((p, o, w) for _, p, o, w in sch)

    other = gen.open_schedule(dict(tr, schedule_seed=4344), seconds)
    assert multiset(other) == multiset(schedule) and other != schedule
    sweep = files.load_module("tools", "sweep_fresh")
    spec = {"traffic": dict(tr), "seed": 0, "seconds": seconds}
    windows = [gen.open_schedule(w["traffic"], seconds) for w in
               sweep.windows(spec, [100, 200], [tr["rate_rps"]] * 2)]
    assert all(multiset(w) == multiset(schedule) for w in windows)
    assert len({tuple(w) for w in windows + [schedule]}) == 5
    n = round(tr["rate_rps"] * seconds)
    assert sum(w for *_, w in schedule) == n
    for due, p, o, w in schedule:
        assert p + o <= tr["max_total"] and o >= 1
        assert (0 <= due < seconds) if w else (-tr["preroll_s"] <= due < 0)
    return tr, schedule


@pytest.fixture(scope="session")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
