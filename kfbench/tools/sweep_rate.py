#!/usr/bin/env python3
"""Find an open-loop cell's knee once: the highest rate at which the
backlog does not grow.  One process, one warm engine, one window per
rate; the traffic file's ``rate_rps`` is then written by hand at the
stated share of it (PERF.md section 4 records the sweep).

    python3 kfbench/tools/sweep_rate.py --workload gpt2l-serve-chat \
        --rates 1.0 1.5 2.0 2.5 3.0 --seconds 30 --seed 7
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    from kfbench import run as harness_run
    from kfbench.lib import files, harness, stats

    os.environ.update(files.cache_env())
    harness.pin_platform({"rehearse": a.rehearse})
    spec = harness_run.build_spec(argparse.Namespace(
        workload=a.workload, seed=a.seed, seconds=a.seconds, trace=0,
        rehearse=a.rehearse))
    loop = files.load_module("loops", spec["traffic"]["loop"])
    serving = loop.Serving(spec)
    shapes = set()
    for rate in a.rates:
        spec["traffic"]["rate_rps"] = rate
        shapes |= loop.traffic_shapes(spec)
    serving.warm(spec, shapes)
    for rate in a.rates:
        spec["traffic"]["rate_rps"] = rate
        d = loop.drive(spec, serving)
        t0, t_end = d["t0"], d["t_end"]
        inside = [s for s in d["steps"] if t0 < s[0] <= t_end]
        half = len(inside) // 2
        ttft = [r.token_t[0] - r.due for r in d["counted"] if r.token_t]
        gaps = [b - x for r in d["requests"]
                for x, b in zip(r.token_t, r.token_t[1:]) if t0 < b <= t_end]
        print(json.dumps({
            "rate_rps": rate, "offered": len(d["counted"]),
            "failed": len(d["failed"]),
            # requests in the engine (waiting or active), averaged over
            # each half of the window: a backlog that grows shows here
            "backlog_first_half": sum(s[4] for s in inside[:half]) / max(half, 1),
            "backlog_second_half": sum(s[4] for s in inside[half:])
            / max(len(inside) - half, 1),
            "backlog_at_close": inside[-1][4] if inside else None,
            "drain_s": d["steps"][-1][0] - t_end,
            "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
            "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90),
            "itl_p50_ms": 1e3 * stats.percentile(gaps, 50),
            "itl_p95_ms": 1e3 * stats.percentile(gaps, 95),
            "steps": len(inside)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
