#!/usr/bin/env python3
"""kf-overlap demo: bucketed communication/computation overlap, measured.

A 3-rank in-process host-plane cluster runs the ZeRO-2 bucket loop twice
under chaos-injected wire latency (``KF_CHAOS_SPEC`` ``delay`` on every
send, set below): once as the serial reference (issue, wait, compute,
repeat) and once as the depth-k software pipeline
(:func:`kungfu_tpu.parallel.zero.host_bucket_pipeline` — bucket i+k's
reduce-scatter is issued on the engine's async window while bucket i's
optimizer math runs).  The script asserts:

* measured overlap > 0 — the pipelined step time beats the serial one,
  and the ``kf_overlap_efficiency`` histogram saw hidden wire time;
* final parameters are BITWISE identical between the two runs (the
  pipeline moves wall clock only);
* the ``kf_overlap_inflight`` gauge is back at 0 (no leaked handles).

Wired into ``make overlap-demo`` and ``scripts/check.sh``.  See
docs/overlap.md for the design.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WIRE_MS = 25

# before any kungfu_tpu import: chaos controllers and the engine read
# these at construction
os.environ["KF_NATIVE_ENGINE"] = "0"          # chaos rides the py path
os.environ.setdefault("KF_CONFIG_LOG_LEVEL", "WARNING")
os.environ["KF_CHAOS_SPEC"] = f"delay:ms={WIRE_MS},on=send"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--base-port", type=int, default=24960)
    ns = ap.parse_args()

    import threading

    import numpy as np

    from kungfu_tpu.comm.engine import CollectiveEngine
    from kungfu_tpu.comm.host import HostChannel
    from kungfu_tpu.monitor.registry import REGISTRY
    from kungfu_tpu.parallel.zero import (host_bucket_all_gather,
                                          host_bucket_pipeline,
                                          host_bucket_spans)
    from kungfu_tpu.plan import PeerID, PeerList, Strategy

    n, chunk, n_buckets = 3, 24_000, 4
    widths = [chunk // n_buckets] * n_buckets
    spans = host_bucket_spans(chunk, widths)
    total = n * chunk
    lr, mu = np.float32(0.125), np.float32(0.5)

    def run_world(fns, timeout=120.0):
        outs = [None] * len(fns)
        errs = []

        def wrap(i, f):
            try:
                outs[i] = f()
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=wrap, args=(i, f), daemon=True)
              for i, f in enumerate(fns)]
        for t in ts:
            t.start()
        deadline = time.monotonic() + timeout
        for t in ts:
            t.join(max(0.0, deadline - time.monotonic()))
        if errs:
            raise errs[0]
        if any(t.is_alive() for t in ts):
            raise TimeoutError("demo cluster hung")
        return outs

    def run_mode(pipelined, base_port, tag):
        peers = PeerList.of(*(PeerID("127.0.0.1", base_port + i)
                              for i in range(n)))
        chans = [HostChannel(p, bind_host="127.0.0.1") for p in peers]
        engines = [CollectiveEngine(c, peers, Strategy.STAR) for c in chans]
        try:
            def one(i):
                params = (np.arange(total, dtype=np.float32) % 64) / 64
                mom = np.zeros(chunk, np.float32)
                eng = engines[i]
                times = []
                for k in range(ns.steps):
                    t0 = time.perf_counter()
                    g = params * np.float32(0.5) + np.float32(2.0 ** -(k + 2))
                    own = params[i * chunk:(i + 1) * chunk].copy()

                    def compute(b, red):
                        off, w = spans[b]
                        m = mom[off:off + w] * mu + red
                        mom[off:off + w] = m
                        own[off:off + w] -= lr * m

                    host_bucket_pipeline(eng, g, widths, compute,
                                         pipelined=pipelined,
                                         name=f"{tag}r{k}")
                    params = host_bucket_all_gather(
                        eng, own, widths, pipelined=pipelined,
                        name=f"{tag}g{k}")
                    times.append(time.perf_counter() - t0)
                assert eng.inflight() == 0, "leaked handles"
                return times, params

            outs = run_world([lambda i=i: one(i) for i in range(n)])
            step_s = float(np.median(
                [max(outs[i][0][k] for i in range(n))
                 for k in range(1, ns.steps)]))
            return step_s, outs[0][1]
        finally:
            for c in chans:
                c.close()

    serial_s, final_serial = run_mode(False, ns.base_port, "s")
    pipe_s, final_pipe = run_mode(True, ns.base_port + 10, "p")

    assert final_serial.tobytes() == final_pipe.tobytes(), (
        "pipelined run diverged from serial — the geometry invariant broke")
    overlap_pct = (1.0 - pipe_s / serial_s) * 100.0
    assert overlap_pct > 0, (
        f"no measured overlap (serial {serial_s * 1e3:.1f} ms, "
        f"pipelined {pipe_s * 1e3:.1f} ms)")
    snap = REGISTRY.snapshot()
    eff = snap.get("kf_overlap_efficiency", {"count": 0})
    assert eff["count"] > 0, "efficiency histogram never observed"
    assert snap.get("kf_overlap_inflight", 0.0) == 0.0, "gauge not at 0"
    print(
        f"overlap-demo: overlap {overlap_pct:.0f}% measured "
        f"(serial {serial_s * 1e3:.1f} ms -> pipelined {pipe_s * 1e3:.1f} ms "
        f"under {WIRE_MS} ms injected wire latency; bitwise-identical "
        f"params; inflight gauge 0)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
