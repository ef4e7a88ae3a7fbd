#!/usr/bin/env python3
"""Spreads of the candidate estimators of a train cell's rate, from the
block times a set of runs wrote (``run.json``), over the first 20, 30
and 40 seconds of each: the table of PERF.md section 2.

    python3 kfbench/tools/spread.py chiprun_out/study/*.run.json
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from kfbench.lib import stats  # noqa: E402


def rate_total(block_s, per_block: float) -> float:
    """All the work over all the time: the end-to-end metric."""
    return per_block * len(block_s) / sum(block_s)


def rate_median_block(block_s, per_block: float) -> float:
    """Work per block over the median block time: one slow stretch costs
    one reading, not the result (the per-layer ``block_rate_median``)."""
    return per_block / stats.median(block_s)


def rate_midmean(block_s, per_block: float) -> float:
    """Work per block over the mean of the middle half of block times."""
    v = sorted(block_s)
    q = len(v) // 4
    mid = v[q:len(v) - q] or v
    return per_block / (sum(mid) / len(mid))


ESTIMATORS = {"total/elapsed": rate_total,
              "median block": rate_median_block,
              "mean of middle half": rate_midmean}


def main() -> int:
    runs = []
    for path in sys.argv[1:]:
        with open(path) as f:
            runs.append(json.load(f)["train"])
    print(f"{len(runs)} runs")
    for seconds in (10, 20, 30, 40, 50):
        for name, est in ESTIMATORS.items():
            values = []
            for t in runs:
                cut, acc = [], 0.0
                for b in t["block_s"]:
                    if acc >= seconds:
                        break
                    cut.append(b)
                    acc += b
                per_block = t["tokens_per_step"] * t["block_steps"] / t["chips"]
                values.append(est(cut, per_block))
            if max(sum(t["block_s"]) for t in runs) < seconds - 1:
                continue
            print(f"first {seconds:2d} s  {name:20s} median "
                  f"{stats.median(values):10.2f}  spread "
                  f"{100 * stats.spread(values):.3f} %  "
                  f"min {min(values):.1f} max {max(values):.1f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
