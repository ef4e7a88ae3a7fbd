#!/usr/bin/env python3
"""A decode step's device time by scope at fixed numbers of live slots:
does the routed experts' time follow the routing or the occupancy?  One
process, one engine with weights from ``--seed``; for each count of live
slots, that many requests are admitted, then ``--steps`` bare decode
steps run under the profiler and ``lib/decode_paths.py`` reads them.

    python3 kfbench/tools/decode_by_occupancy.py \
        --workload cmdaplus-serve-mixedlen --live 4 16 32 --seed 7

``--excerpt FILE`` also writes the first three decode runs of the last
trace as ``lib/decode_paths.py`` reads them (operations with their whole
scope path, the runs, the ``kf:serve.decode_read`` spans with their attrs;
times from the first run's start): a recorded excerpt small enough to
keep beside the tests (``tests/recorded_decode_paths.json``).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

SCOPES = ("moe_router", "moe_experts", "moe_shared", "attn_window",
          "attn_full", "kv_write")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--live", type=int, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--prompt", type=int, default=300)
    ap.add_argument("--excerpt", default=None)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    import jax

    from kfbench import run as harness_run
    from kfbench.lib import decode_paths, files, harness, traffic as gen

    os.environ.update(files.cache_env())
    harness.pin_platform({"rehearse": a.rehearse})
    spec = harness_run.build_spec(argparse.Namespace(
        workload=a.workload, seed=a.seed, seconds=1.0, trace=0,
        rehearse=a.rehearse))
    loop = files.load_module("loops", spec["traffic"]["loop"])
    serving = loop.Serving(spec)
    eng, vocab = serving.eng, spec["config"]["vocab_size"]
    new = a.steps * 3 + 8
    eng.submit("warm", gen.prompt_ids(vocab, a.seed, 0, a.prompt), 2)
    eng.drain()
    for n in a.live:
        for i in range(n):
            eng.submit(f"n{n}r{i}", gen.prompt_ids(
                vocab, a.seed, 1000 * n + i, a.prompt), new)
        while eng.pending_count:
            eng.step()
        for _ in range(4):
            eng.step()
        out = os.path.join(spec["out_dir"], f"occupancy{n}")
        shutil.rmtree(out, ignore_errors=True)
        jax.profiler.start_trace(out)
        for _ in range(a.steps):
            eng.step()
        live = eng.active_count
        jax.profiler.stop_trace()
        facts = {"trace": {"dir": out}}
        runs, by_path = decode_paths.decode_path_seconds(facts)
        line = {"seed": a.seed, "live": live, "decode_runs": runs,
                "decode_busy_ms": 1e3 * sum(by_path.values()) / max(runs, 1)}
        for s in SCOPES:
            line[s + "_ms"] = decode_paths.scope_ms_per_run(facts, s)
        print(json.dumps(line), flush=True)
        while eng.active_count:  # let them finish: slots free for the next
            eng.step()
    if a.excerpt:
        write_excerpt(facts, a.excerpt)
    return 0


def write_excerpt(facts: dict, out: str, n_runs: int = 3) -> None:
    from kfbench.lib import decode_paths, spans, trace

    ops, runs = decode_paths.device_ops(trace.find_xplane(
        facts["trace"]["dir"]))
    lo, hi = runs[0][0], runs[n_runs - 1][1]
    host = list(spans.of(facts).named("serve.decode_read"))
    first = host[0].start  # the host's clock and the device's differ
    with open(out, "w") as f:
        json.dump({
            "runs": [[s - lo, e - lo] for s, e in runs[:n_runs]],
            "ops": [[p, s - lo, e - lo] for p, s, e in ops
                    if lo <= s and e <= hi],
            "read_spans": [[s.start - first, s.dur, s.stats]
                             for s in host[:n_runs + 1]]}, f,
            separators=(",", ":"))


if __name__ == "__main__":
    sys.exit(main())
