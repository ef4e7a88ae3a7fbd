#!/usr/bin/env python3
"""Cut what the ``kv_*`` readers read out of a traced run, small enough
to keep beside the tests (``tests/recorded_kv_chat.json``).

    python3 kfbench/tools/kv_excerpt.py <run dir under .kfbench_runs> <out.json>

Kept: the ``kv_*`` attrs of EVERY ``kf:serve.decode_read`` span and the
device time of EVERY ``jit__decode_fn`` run of the traced stretch (a few
hundred numbers each); of the first three decode runs, the operations
whose scope path holds a scope that ``lib/kv.py`` counts, with their
paths (times from the first run's start); and, under ``whole_trace``,
what the three readers gave on the whole trace, which the excerpt's
readings are held against.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

COUNTED = {"attn_core", "attn_window", "attn_full", "kv_write"}
READERS = ("kv_rows_live_share", "kv_attn_roofline", "decode_step_spread")


def main(run_dir: str, out: str, n_runs: int = 3) -> int:
    from kfbench.lib import decode_paths, files, kv, peaks, spans, trace

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
            "ops": [[at[p], s, e] for p, s, e in kept],
            "run_seconds": trace.module_runs(trace.of(facts),
                                             decode_paths.DECODE),
            "read_stats": [{k: s.stats[k] for k in kv.ATTRS}
                           for s in spans.of(facts).named("serve.decode_read")
                           if all(k in s.stats for k in kv.ATTRS)],
            "whole_trace": dict(whole, attn_ms_per_run=kv.attn_ms_per_run(
                facts), least_seconds=kv.least_seconds(facts)),
        }, f, separators=(",", ":"))
    print(json.dumps(whole), os.path.getsize(out), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
