#!/usr/bin/env python3
"""Cut what the readers of ``phi4flash-serve-mathreason``'s own metrics
read out of a traced run, small enough to keep beside the tests
(``tests/recorded_sambay_mathreason.json``), as ``tools/eva_excerpt.py``
does for its cell.

    python3 kfbench/tools/sambay_excerpt.py <run dir under .kfbench_runs> <out.json>

Kept: the row and state attrs of EVERY ``kf:serve.decode_read`` span of
the traced stretch; of the first three decode runs (the fewest a reader
takes), the operations whose scope path holds one of the scopes the
readers add up (``ssm_proj``,
``ssm_state``, ``gmu``, ``attn_window``, ``attn_full``, ``attn_cross``,
``kv_write``), with their paths (times from the first run's start); and,
under ``whole_trace``, what the readers gave on the whole trace, which
the excerpt's readings are held against.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

COUNTED = {"ssm_proj", "ssm_state", "gmu", "attn_window", "attn_full",
           "attn_cross", "kv_write"}
ATTRS = ("kv_rows_live", "kv_rows_read", "kv_rows_written", "kv_row_bytes",
         "kv_rows_live_full", "state_slots_live", "state_slots_read",
         "state_bytes_read")
READERS = ("decode_path_ms.ssm_proj", "decode_path_ms.ssm_state",
           "decode_path_ms.gmu", "decode_path_ms.attn_cross",
           "decode_path_ms.attn_window", "decode_path_ms.attn_full",
           "ssm_state_roofline", "sambay_attn_roofline",
           "kv_rows_live_share", "state_slots_live_share")


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
