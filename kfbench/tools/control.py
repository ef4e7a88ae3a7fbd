#!/usr/bin/env python3
"""The control of ``correct``: the reference put in the program's place
and computed in float8 (e4m3), the nearest precision below the bfloat16
the configurations state.  It has to come out as not correct; its
smallest numbers, beside the sound runs' largest, are what each limit in
a traffic file's ``check.limits`` was set from (PERF.md section 2).

On the chip, at the cell's own size, several seeds in one process:

    python3 kfbench/tools/control.py --workload gpt2m-train-4chip --seeds 1 2 3

A train cell needs no program and no window for this (one chip does, at
the cell's own number of rows); a serve cell serves a short window at
the cell's own load and judges, at each position of the sampled prompts
and served tokens, the token that float8 puts first.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    from kfbench import run as harness_run
    from kfbench.lib import files, harness

    os.environ.update(files.cache_env())
    harness.pin_platform({"rehearse": a.rehearse})
    for seed in a.seeds:
        spec = harness_run.build_spec(argparse.Namespace(
            workload=a.workload, seed=seed, seconds=a.seconds, trace=0,
            rehearse=a.rehearse))
        spec["control"] = True
        loop = files.load_module("loops", spec["traffic"]["loop"])
        ref = files.load_reference(spec["config"]["family"])
        if spec["traffic"]["loop"] == "train":
            feed = loop.make_feed(spec, spec["traffic"]["global_batch"])
            want = loop.reference_steps(spec, feed)
            ctl = loop.reference_steps(spec, feed, cast=ref.to_fp8)
            out = {"control": loop.gaps(ctl, want)}
        else:
            spec["cell"]["chips"] = 1  # one engine, one chip
            os.makedirs(spec["out_dir"], exist_ok=True)
            facts = loop.run(spec)
            out = {"program": {k: v["value"] for k, v in
                               facts["check"]["numbers"].items()},
                   "control": facts["check"]["control"],
                   "sampled_tokens": facts["check"]["sampled_tokens"],
                   "failed": facts["failed"]}
        print(json.dumps({"workload": a.workload, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
