#!/usr/bin/env python3
"""kf-xray gate: causal step-time attribution on a mesh with a planted
slow link, offline and online, and the per-phase budget.

The 3-rank mesh of ``examples/chaos_mesh.py`` trains with the 0<->1 link
throttled from the first step.  The flight recorder's dump is then
attributed twice — offline through the real ``kftrace`` dump and load
path, and online through a live :class:`ClusterAggregator` fed per-rank
snapshots — and the script asserts:

* the two verdicts are IDENTICAL (one implementation, monitor/xray.py);
* the verdict names rank 1 as the straggler and ``comm_exposed`` as the
  dominant phase, and the report says so;
* ``comm_exposed`` covers the planted wire and the straggler's excess is
  attributed;
* the MFU gauge reports an MFU exactly when a chip peak is known (none
  on the CPU: the model-FLOPs rate alone);
* the per-phase medians sit inside the ceilings of
  ``tests/xray_budget.json``.

Wired into ``make xray-gate`` and ``scripts/check.sh``.  It prints the
row it judged as one JSON line, then ``xray-gate OK``; any check false
exits non-zero.  See docs/xray.md.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from examples.chaos_mesh import (BATCH, RANKS, SEQ, WIRE_MS,  # noqa: E402
                                 ChaosMesh, link_delay_spec)

STEPS = 8


def run() -> dict:
    mesh = ChaosMesh(link_delay_spec())

    import numpy as np

    from kungfu_tpu.monitor import timeline, traceview
    from kungfu_tpu.monitor import xray as xraylib
    from kungfu_tpu.monitor.aggregator import (REPORT_KINDS,
                                               ClusterAggregator,
                                               make_snapshot)
    from kungfu_tpu.monitor.registry import REGISTRY
    from kungfu_tpu.ops import costmodel

    cfg = mesh.cfg
    flops_per_step = costmodel.train_step_flops(cfg, BATCH, SEQ)
    meter = costmodel.MFUMeter(step_flops=flops_per_step)  # peak None: CPU

    timeline.reset()
    walls = []
    try:
        for i in range(STEPS):
            timeline.set_step(i)
            t0 = time.perf_counter()
            mesh.step()
            wall = time.perf_counter() - t0
            walls.append(wall)
            meter.step(wall_s=wall)
        events = timeline.snapshot()
        # offline: through the REAL kftrace dump + load path
        fd, dump = tempfile.mkstemp(suffix=".jsonl", prefix="kf-xray-")
        os.close(fd)
        try:
            timeline.dump(dump)
            loaded = traceview.load_all([dump])
        finally:
            os.unlink(dump)
        offline = xraylib.verdict(loaded)
        report = xraylib.render_report(loaded)
        # online: the live aggregator fed per-rank snapshots (the
        # reporter's REPORT_KINDS filter applied, like production)
        gauges = {k: float(v) for k, v in REGISTRY.snapshot().items()
                  if isinstance(v, float)}
        agg = ClusterAggregator(stale_after=3600.0)
        for r in range(RANKS):
            agg.ingest(make_snapshot(
                rank=r, pid=os.getpid(), wall=time.time(), step=STEPS - 1,
                step_time_s=float(np.median(walls)),
                counters={}, gauges=gauges if r == 0 else {}, latency={},
                events=[e for e in events
                        if e["rank"] == r and e["kind"] in REPORT_KINDS],
                net={}, strategy="STAR"))
        online = (agg.cluster_view()["xray"] or {}).get("verdict")
    finally:
        mesh.close()

    rows = xraylib.step_attribution(loaded)
    med = {ph: float(np.median([r["phases"][ph] for r in rows]))
           for ph in xraylib.PHASES}
    med_wall = float(np.median([r["wall_s"] for r in rows]))
    with open(os.path.join(REPO, "tests", "xray_budget.json")) as f:
        budget = json.load(f)
    ceilings = budget["phase_ceilings_s_per_step"]
    budget_ok = (med_wall <= budget["step_wall_s_max"]
                 and all(med[ph] <= ceilings[ph] for ph in xraylib.PHASES))
    culprit = offline["culprit"] or {}
    checks = {
        "offline_online_verdict_identical":
            json.loads(json.dumps(offline)) == json.loads(
                json.dumps(online)),
        "culprit_is_planted_edge_rank1": culprit.get("slowest_rank") == 1,
        "dominant_phase_is_comm_exposed":
            offline["dominant"] == "comm_exposed",
        "comm_exposed_covers_planted_wire":
            med["comm_exposed"] >= WIRE_MS / 1e3,
        "straggler_excess_attributed":
            med["straggler_wait"] >= 0.3 * WIRE_MS / 1e3,
        # no peak -> no MFU row (model-FLOPs rate only); a detected TPU
        # peak (or KF_XRAY_PEAK_FLOPS) must yield a real MFU
        "mfu_follows_detected_peak": ((meter.mfu is not None)
                                      == (meter.peak_flops is not None)),
        "model_flops_rate_measured":
            gauges.get("kf_model_flops_s", 0.0) > 0,
        "report_names_culprit": "rank 1" in report,
        "budget_ok": budget_ok,
    }
    share = (med["comm_exposed"] + med["straggler_wait"]) / max(
        sum(med.values()), 1e-9)
    return {
        "metric": "xray_comm_share_attributed_to_planted_link",
        "value": round(share, 3),
        "unit": "fraction",
        "platform": "cpu-hostplane",
        "n_devices": RANKS,
        "model": (f"{RANKS} ranks, GPT d{cfg.d_model}xL{cfg.n_layers} "
                  f"fwd+bwd per step + 200 KiB allreduce, {WIRE_MS} ms "
                  f"chaos delay on rank 1's send+recv legs of the 0<->1 "
                  f"link"),
        "checks": checks,
        "rows": {
            "attribution": {
                "steps": STEPS,
                "median_step_wall_ms": round(med_wall * 1e3, 2),
                "phases_ms": {ph: round(v * 1e3, 2)
                              for ph, v in med.items()},
                "culprit": culprit,
                "straggler": offline["straggler"],
                "dominant": offline["dominant"],
            },
            "mfu_decomp": {
                "model": f"d{cfg.d_model} L{cfg.n_layers} B{BATCH} S{SEQ}",
                "flops_per_step": flops_per_step,
                "model_flops_s": round(gauges.get("kf_model_flops_s",
                                                  0.0), 1),
                "mfu": (round(meter.mfu, 5) if meter.mfu is not None
                        else None),
                "peak_flops": meter.peak_flops,
                "phase_seconds_per_step": {
                    ph: round(v, 5) for ph, v in med.items()},
            },
            "budget": {"ok": budget_ok, **budget},
        },
    }


def main() -> int:
    row = run()
    print(json.dumps(row))
    failed = sorted(k for k, ok in row["checks"].items() if not ok)
    if failed:
        print(f"xray-gate FAILED: {failed}", file=sys.stderr)
        return 1
    print("xray-gate OK: offline and online verdicts identical, the planted "
          "edge named, every phase inside its ceiling")
    return 0


if __name__ == "__main__":
    sys.exit(main())
