#!/usr/bin/env python3
"""An open-loop cell's knee, the highest rate at which the backlog does
not grow, from windows that share nothing: one process, one warm engine,
and every window driven with a seed of its own.  The traffic file's
``rate_rps`` is then written by hand at the stated share of it, with the
sweep's lines in its ``rate_reason``.

Window ``j`` of ``--seeds`` entry ``s`` takes seed ``s + j`` for both
things a run keeps apart: the schedule, which a run takes from the
file's ``schedule_seed`` and which is drawn anew here because a knee has
to hold over draws and not on one; and the prompts, so that they are new
to the prefix cache.  (``traffic.prompt_ids(vocab, seed, index, n)``
gives request ``index`` the same first ids at every rate under one seed:
from the second rate on its requests would hit the pages the rate before
committed, prefill only a suffix, and the engine would look faster than
it is; PERF.md, PR 27.)  The weights stay those of the first seed: they
do not move the timing.  Each line says how many prompt tokens came out
of the cache: it has to read 0.

    python3 kfbench/tools/sweep_fresh.py --workload cmdaplus-serve-mixedlen \
        --rates 1.5 2.0 --seeds 1100 1200 --seconds 40
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def windows(spec: dict, seeds, rates):
    """``spec`` set for each window in turn: window ``j`` of seed ``s``
    draws its schedule and its prompts from ``s + j``.  What is yielded
    is ``spec`` itself, the same object every time: use each window
    before asking for the next."""
    for seed in seeds:
        for j, rate in enumerate(rates):
            spec["seed"] = spec["traffic"]["schedule_seed"] = seed + j
            spec["traffic"]["rate_rps"] = rate
            yield spec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    from kfbench import run as harness_run
    from kfbench.lib import files, harness, stats

    os.environ.update(files.cache_env())
    harness.pin_platform({"rehearse": a.rehearse})
    spec = harness_run.build_spec(argparse.Namespace(
        workload=a.workload, seed=a.seeds[0], seconds=a.seconds, trace=0,
        rehearse=a.rehearse))
    loop = files.load_module("loops", spec["traffic"]["loop"])
    serving = loop.Serving(spec)
    shapes = set()
    for rate in a.rates:  # (the lengths follow the rate, not the draw)
        spec["traffic"]["rate_rps"] = rate
        shapes |= loop.traffic_shapes(spec)
    serving.warm(spec, shapes)
    for window in windows(spec, a.seeds, a.rates):
        seed, rate = window["seed"], window["traffic"]["rate_rps"]
        d = loop.drive(window, serving)
        t0, t_end = d["t0"], d["t_end"]
        inside = [s for s in d["steps"] if t0 < s[0] <= t_end]
        half = len(inside) // 2
        done = [r.stats for r in d["requests"] if r.stats is not None]
        ttft = [r.token_t[0] - r.due for r in d["counted"] if r.token_t]
        gaps = [b - x for r in d["requests"]
                for x, b in zip(r.token_t, r.token_t[1:]) if t0 < b <= t_end]
        bare = stats.percentile([s[1] for s in inside], 50)
        print(json.dumps({
            "seed": seed, "rate_rps": rate, "offered": len(d["counted"]),
            "failed": len(d["failed"]),
            # requests in the engine (waiting or active), averaged over
            # each half of the window: a backlog that grows shows here
            "backlog_first_half": sum(s[4] for s in inside[:half]) / max(half, 1),
            "backlog_second_half": sum(s[4] for s in inside[half:])
            / max(len(inside) - half, 1),
            "backlog_at_close": inside[-1][4] if inside else None,
            "backlog_max": max((s[4] for s in inside), default=None),
            "drain_s": d["steps"][-1][0] - t_end,
            "steps": len(inside),
            "busy_s": sum(s[1] for s in inside),
            # what the window's steps took beyond bare decode steps, a
            # request admitted in it: a prefill and a page commit
            "extra_s_per_admission": sum(s[1] - bare for s in inside)
            / max(sum(1 for s in inside if s[3]), 1),
            "prompt_tokens_from_cache": sum(s["reused_tokens"] for s in done),
            "prompt_tokens_computed": sum(s["computed_tokens"] for s in done),
            "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
            "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90),
            "itl_p50_ms": 1e3 * stats.percentile(gaps, 50),
            "itl_p95_ms": 1e3 * stats.percentile(gaps, 95)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
