#!/usr/bin/env python3
"""kf-adapt demo: scripted interference A/B, asserting the swap fires.

A 3-rank in-process host-plane cluster starts on STAR while the chaos
layer (``KF_CHAOS_SPEC`` ``delay`` clauses, set below) throttles the
0<->1 link on both the data path and the latency probe.  The UCB bandit
(:class:`kungfu_tpu.monitor.adapt_device.HostBanditDriver`) reads its
measured windows, majority-votes, and performs the consensus-fenced
lockstep swap onto the measured-latency MST, after which the step time
recovers.  The script asserts:

* a swap fired, away from the degraded starting strategy;
* the flight recorder holds the ``swap`` event on EVERY rank with one
  agreed sequence number (the fence contract);
* post-swap steady-state step time beats the degraded phase.

Wired into ``make adapt-demo`` and ``scripts/check.sh``; see
docs/adaptation.md for the design.
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
os.environ["KF_CONFIG_ENABLE_TRACE"] = "1"    # record the swap events
os.environ.setdefault("KF_CONFIG_LOG_LEVEL", "WARNING")
os.environ["KF_CHAOS_SPEC"] = ";".join(
    f"delay:ms={WIRE_MS},rank={a},peer={b},on={on}"
    for a, b in ((0, 1), (1, 0)) for on in ("send", "ping")
)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--base-port", type=int, default=24700)
    ns = ap.parse_args()

    import threading

    import numpy as np

    from kungfu_tpu.monitor import timeline
    from kungfu_tpu.monitor.adapt_device import HostBanditDriver
    from kungfu_tpu.peer import Peer
    from kungfu_tpu.plan import Cluster, PeerList, parse_strategy
    from kungfu_tpu.utils.envs import Config

    workers = PeerList.parse(
        ",".join(f"127.0.0.1:{ns.base_port + i}" for i in range(3)))
    runners = PeerList.parse(f"127.0.0.1:{ns.base_port + 99}")
    cluster = Cluster(runners, workers)
    peers = [Peer(Config(self_id=w, cluster=cluster)) for w in workers]
    for p in peers:
        p.config.strategy = parse_strategy("STAR")
        p.start()
    drivers = [HostBanditDriver(p, check_every=2, min_pulls=1,
                                min_swap_collectives=1) for p in peers]
    data = np.ones(50_000, np.float32)
    times, swap_at = [], None

    def run_world(fns):
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
        for t in ts:
            t.join(60)
        if errs:
            raise errs[0]
        if any(t.is_alive() for t in ts):
            raise TimeoutError("demo cluster hung")
        return outs

    def one(p, d):
        t0 = time.perf_counter()
        out = p.engine().all_reduce(data, op="sum")
        dt = time.perf_counter() - t0
        assert float(out[0]) == 3.0, out[:4]
        return dt, d.step(dt)

    try:
        for i in range(ns.steps):
            outs = run_world([lambda p=p, d=d: one(p, d)
                              for p, d in zip(peers, drivers)])
            flags = {s for _, s in outs}
            assert len(flags) == 1, f"non-lockstep swap at step {i}: {flags}"
            times.append(max(dt for dt, _ in outs))
            if flags.pop() and swap_at is None:
                swap_at = i
        assert swap_at is not None, "the bandit never swapped"
        actives = {d.active for d in drivers}
        assert actives != {"STAR"}, "degraded strategy was not abandoned"
        swaps = [e for e in timeline.snapshot() if e["kind"] == "swap"]
        seqs = {}
        for e in swaps:
            seqs.setdefault(e["attrs"]["seq"], set()).add(e["rank"])
        assert any(len(ranks) == 3 for ranks in seqs.values()), (
            f"swap event not on every rank: {seqs}")
        degraded = float(np.median(times[:swap_at + 1]))
        steady = float(np.median(times[-5:]))
        assert steady < degraded, (degraded, steady)
        print(
            f"adapt-demo: swap fired at step {swap_at} "
            f"(arm={actives.pop()}, ranks={sorted(max(seqs.values(), key=len))}); "
            f"steady {steady * 1e3:.1f} ms vs degraded {degraded * 1e3:.1f} ms"
        )
        return 0
    finally:
        for p in peers:
            p.close()


if __name__ == "__main__":
    sys.exit(main())
