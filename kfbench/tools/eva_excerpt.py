#!/usr/bin/env python3
"""Cut what the readers of ``evabyte-serve-docbytes``'s own metrics read
out of a traced run, small enough to keep beside the tests
(``tests/recorded_eva_docbytes.json``), as ``tools/kv_excerpt.py`` does
for the ``kv_*`` readers.

    python3 kfbench/tools/eva_excerpt.py <run dir under .kfbench_runs> <out.json>

Kept: the row attrs of EVERY ``kf:serve.decode_read`` span of the traced
stretch; of the first three decode runs, the operations whose scope path
holds ``eva_attn``, ``eva_pool``, ``kv_write`` or ``attn_core``, with
their paths (times from the first run's start); and, under
``whole_trace``, what the readers gave on the whole trace, which the
excerpt's readings are held against.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

COUNTED = {"eva_attn", "eva_pool", "kv_write", "attn_core"}
ATTRS = ("kv_rows_live", "kv_rows_read", "kv_rows_written", "kv_row_bytes",
         "summary_rows_live", "summary_rows_read", "summary_rows_written")
READERS = ("decode_path_ms.eva_attn", "decode_path_ms.eva_pool",
           "summary_rows_live_share", "eva_attn_roofline",
           "kv_rows_live_share")


def main(run_dir: str, out: str, n_runs: int = 3) -> int:
    from kfbench.lib import decode_paths, files, peaks, spans, trace

    with open(os.path.join(run_dir, "run.json")) as f:
        facts = json.load(f)
    with open(os.path.join(run_dir, "spec.json")) as f:
        facts["spec"] = json.load(f)
    facts["peaks"] = peaks.of(facts["device"]["kind"])
    facts["trace"]["reduced"] = trace.reduce_dir(
        facts["trace"]["dir"], facts["device"]["count"])
    whole = {name: files.load_module("metrics", name).read(
        facts, {"name": name}) for name in READERS}
    ops, runs = decode_paths.device_ops(trace.find_xplane(
        facts["trace"]["dir"]))
    lo, hi = runs[0][0], runs[n_runs - 1][1]
    kept = [(p, s - lo, e - lo) for p, s, e in ops
            if lo <= s and e <= hi and COUNTED & set(p.split("/"))]
    paths = sorted({p for p, _, _ in kept})
    at = {p: i for i, p in enumerate(paths)}
    with open(out, "w") as f:
        json.dump({
            "cell": facts["spec"]["workload"], "seed": facts["spec"]["seed"],
            "device": facts["device"]["kind"],
            "hbm_bytes_per_s": facts["peaks"]["hbm_bytes_per_s"],
            "runs": [[s - lo, e - lo] for s, e in runs[:n_runs]],
            "paths": paths,
            "ops": [[at[p], round(s, 9), round(e, 9)] for p, s, e in kept],
            "read_stats": [{k: s.stats[k] for k in ATTRS}
                           for s in spans.of(facts).named("serve.decode_read")
                           if all(k in s.stats for k in ATTRS)],
            "whole_trace": whole,
        }, f, separators=(",", ":"))
    print(json.dumps(whole), os.path.getsize(out), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
