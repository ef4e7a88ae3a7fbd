"""Where the benchmark keeps what belongs to one configuration, one
traffic mix, one loop, one metric: a file of its own, found by the name
``BENCHMARK.json`` gives.  Adding a cell adds files and one entry there;
nothing here is edited.

    configs/<config>.json      sizes as run, source, departures
    adapters/<family>.py       builds the program's model, makes weights
    reference/<family>.py      the plain float32 reference (no program)
    traffic/<traffic>.json     parameters one generator reads
    loops/<loop>.py            the timed loop a traffic file names
    metrics/<metric>.py        one reader per metric
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def cache_env() -> dict:
    """Where and how a worker keeps JAX's persistent compile cache: one
    fixed directory inside this checkout (the program takes the one it is
    given); all of it kept -- a size limit from the environment, as the
    chip machines set, evicts one of a train cell's two whole-step
    programs on every run; and every program in it, the sub-second ones
    too -- a serving engine compiles one small slice per page count, some
    forty a cell, which a warm run should read, not compile."""
    return {"JAX_COMPILATION_CACHE_DIR": os.path.join(ROOT, ".jax_cache"),
            "JAX_COMPILATION_CACHE_MAX_SIZE": "-1",
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
            "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"}


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return load_json("configs", name + ".json")


def load_traffic(name: str) -> dict:
    return load_json("traffic", name + ".json")


def load_module(kind: str, name: str):
    """The module ``<kind>/<name>.py``; a dotted metric name such as
    ``mfu.train`` falls back to its base (``metrics/mfu.py``), since the
    suffix only says which end-to-end metric that cell reports."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(BENCH_DIR, kind, stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"kfbench.{kind}.{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no {kind}/{name}.py under {BENCH_DIR}")


def load_adapter(family: str):
    return load_module("adapters", family)


def load_reference(family: str):
    return load_module("reference", family)


def cell(bench: dict, workload: str) -> dict:
    """One cell of ``BENCHMARK.json`` with its metrics' entries."""
    found = [w for w in bench["workloads"] if w["name"] == workload]
    if not found:
        raise SystemExit(f"kfbench: no workload {workload!r} in "
                         "BENCHMARK.json")
    c = dict(found[0])
    conf = [x for x in bench["configs"] if x["name"] == c["config"]][0]
    c["config_file"] = conf["file"]

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    c["end_to_end"] = [m for m in bench["end_to_end"] if mine(m)]
    e2e = {m["name"] for m in c["end_to_end"]}
    c["per_layer"] = [m for m in bench["per_layer"]
                      if mine(m) and m["moves"] in e2e]
    return c
