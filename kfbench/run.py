#!/usr/bin/env python3
"""kfbench: one run of one cell of ``BENCHMARK.json``.

    python3 kfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This parent never touches JAX while the worker runs: it reads the cell,
its configuration and its traffic by name, starts the one worker that
holds the chip, times its start, and -- once the worker has ended --
makes every metric from the run's facts with that metric's own reader
(``metrics/<name>.py``) and prints the result line.  A cell that finds no
chip, or fewer than it asks for, exits non-zero with no result line.

``--rehearse`` is the mode of the benchmark's own tests: the same
control flow on the CPU at a tiny size (``rehearsal.json``), as many
virtual devices as the cell has chips, every number printed under a
``rehearsal.`` name so that none can be read as a device metric.
"""

from __future__ import annotations

import time

T_START = time.time()  # before anything heavy: set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from kfbench.lib import files, hostwatch, peaks  # noqa: E402

#: the worker's program (the benchmark's own tests put a broken one here)
WORKER = os.path.join(HERE, "worker.py")
#: a worker that has not ended by then is killed (the driver allows a
#: warm run 360 s and a cell's first, which compiles, 1200 s)
WORKER_LIMIT_S = 1150
#: the stretch of the window a --trace 1 run puts under the profiler
TRACE_SECONDS = 4.0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "run_seconds of BENCHMARK.json)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, numbers under rehearsal.* names")
    return ap.parse_args(argv)


def rehearsal_sizes(spec: dict) -> None:
    """Cut the configuration and the traffic to the tiny preset."""
    tiny = files.load_json("rehearsal.json")
    spec["config"].update(tiny["config"])
    over = tiny["traffic"].get(spec["traffic"]["loop"], {})
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(spec["traffic"].get(key), dict):
            spec["traffic"][key].update(value)
        else:
            spec["traffic"][key] = value


def build_spec(a) -> dict:
    bench = files.load_benchmark()
    cell = files.cell(bench, a.workload)
    with open(os.path.join(ROOT, cell["config_file"])) as f:
        config = json.load(f)
    spec = {
        "workload": a.workload, "seed": a.seed, "trace": a.trace,
        "seconds": float(a.seconds if a.seconds is not None
                         else bench["run_seconds"]),
        "trace_seconds": TRACE_SECONDS, "rehearse": a.rehearse,
        "cell": cell, "config": config,
        "traffic": files.load_traffic(cell["traffic"]),
        "out_dir": os.path.join(
            ROOT, ".kfbench_runs",
            f"{a.workload}.seed{a.seed}.trace{a.trace}"
            + (".rehearsal" if a.rehearse else "")),
    }
    if a.rehearse:
        rehearsal_sizes(spec)
    return spec


def start_worker(spec: dict):
    """Start the worker and return (process, launch facts).  The launch
    facts fill as its stderr is read: when ``peer-started`` came."""
    shutil.rmtree(spec["out_dir"], ignore_errors=True)
    os.makedirs(spec["out_dir"])
    spec_path = os.path.join(spec["out_dir"], "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    backend = "cpu" if spec["rehearse"] else "tpu"
    env = dict(os.environ)
    env.update(files.cache_env())
    argv = [sys.executable, WORKER, "--spec", spec_path]
    if spec["traffic"].get("entry") == "kfrun":
        argv = [sys.executable, "-m", "kungfu_tpu", "-np", "1",
                "-backend", backend, "-timeout", str(WORKER_LIMIT_S)] + argv
    else:
        env["JAX_PLATFORMS"] = backend
    if spec["rehearse"]:
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{spec['cell']['chips']}")
        for key in files.cache_env():  # the tests run with the cache off
            env.pop(key)
    launch = {"t_launch": time.time(), "t_peer_started": None}
    proc = subprocess.Popen(argv, cwd=ROOT, env=env, stdout=sys.stderr,
                            stderr=subprocess.PIPE, text=True,
                            errors="replace")

    def pump():
        for line in proc.stderr:
            if launch["t_peer_started"] is None and "peer-started" in line:
                launch["t_peer_started"] = time.time()
            sys.stderr.write(line)

    reader = threading.Thread(target=pump, daemon=True)
    reader.start()
    return proc, reader, launch


def make_metrics(spec: dict, facts: dict) -> dict:
    """Every metric of this run's kind, by its own reader.  A reader
    that finds nothing to read returns None and the metric is left out."""
    entries = spec["cell"]["per_layer" if spec["trace"] else "end_to_end"]
    out = {}
    for m in entries:
        value = files.load_module("metrics", m["name"]).read(facts, m)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    peaks.check_shares(out)
    return out


def main(argv=None) -> int:
    a = parse_args(argv)
    spec = build_spec(a)
    proc, reader, launch = start_worker(spec)
    try:
        rc = proc.wait(timeout=WORKER_LIMIT_S + 30)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        rc = 124
    reader.join(timeout=10)
    if rc != 0:
        print(f"kfbench: the worker of {a.workload} failed (exit code {rc})",
              file=sys.stderr)
        return rc or 1
    with open(os.path.join(spec["out_dir"], "run.json")) as f:
        facts = json.load(f)
    facts.update(spec=spec, launch=launch, t_start=T_START)
    if not spec["rehearse"]:
        facts["peaks"] = peaks.of(facts["device"]["kind"])
    if facts.get("trace"):
        from kfbench.lib import trace

        facts["trace"]["reduced"] = trace.reduce_dir(
            facts["trace"]["dir"],
            0 if spec["rehearse"] else facts["device"]["count"])

    check = facts["check"]
    correct = facts["failed"] == 0
    for name, n in check["numbers"].items():
        ok = n["value"] <= n["limit"]
        correct = correct and ok
        print(f"check {name}: {n['value']!r} (limit {n['limit']!r}) "
              f"{'ok' if ok else 'OUT'}")
    print("check seconds:", round(check["seconds"], 2))
    setup = facts["window_wall"] - T_START
    # spawn: this process's start to the worker's (through the launcher,
    # for a train cell); the rest are the worker's own, in order
    print("setup parts:", json.dumps(dict(
        {"spawn": facts["t_worker"] - T_START}, **facts["setup_parts"],
        total=setup)))
    host = facts.get("train", {}).get("host")
    if host:  # what the host was doing beside each block that ran long
        for slow in hostwatch.slow_blocks(facts["train"]["block_s"], host):
            print("slow block:", json.dumps(slow))
    metrics = make_metrics(spec, facts)
    result = {"correct": bool(correct), "attempted": facts["attempted"],
              "failed": facts["failed"], "metrics": metrics,
              "device": facts["device"]}
    if spec["trace"] and facts.get("trace"):
        red = facts["trace"]["reduced"]
        result["device"].update(busy_s=red["busy_s"],
                                window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"][:10],
                               "idle_gaps": red["idle_gaps"][:10]}
    if spec["rehearse"]:
        result["metrics"] = {"rehearsal." + k: v for k, v in metrics.items()}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
