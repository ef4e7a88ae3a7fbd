#!/usr/bin/env python3
"""Allreduce bus-bandwidth harness.

Parity with reference ``kungfu/tensorflow/v1/benchmarks/__main__.py:112-120``
(prints ``RESULT: <x> +-<err> GiB/s``) over the fake model size lists
(ResNet-50 / VGG16 / BERT / SLP, ``model_sizes.py`` analog in
:mod:`kungfu_tpu.models.fake`).  Two backends:

* ``device`` — the TPU data plane: fused ``group_all_reduce`` (psum) over
  the XLA mesh (all local devices; ICI on real hardware, the reference's
  NCCL analog);
* ``host``  — the host graph-collective engine over localhost TCP
  (in-process multi-engine cluster), sweepable over the 8 strategies
  (the reference's Go CPU path analog).

Bus bandwidth uses the standard allreduce formula 2·(n−1)/n · bytes / t.

    python benchmarks/allreduce.py --backend device --model resnet50-imagenet
    python benchmarks/allreduce.py --backend host --np 4 --strategy RING
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import statistics
import threading
import time

import numpy as np

GIB = float(1 << 30)


def bus_bandwidth(nbytes: int, n: int, seconds: float) -> float:
    if n <= 1:
        return float("inf") if seconds == 0 else nbytes / seconds / GIB
    return 2 * (n - 1) / n * nbytes / seconds / GIB


def bench_device(model: str, iters: int, warmup: int):
    import jax

    from kungfu_tpu.comm.device import Communicator
    from kungfu_tpu.models.fake import fake_model_sizes

    comm = Communicator()
    n = comm.size
    sizes = fake_model_sizes(model)
    # stacked per-peer slices (single-controller Communicator contract:
    # leading axis = peer) — payload counted per peer, as the reference does
    grads = [
        np.broadcast_to(
            np.random.default_rng(i).standard_normal(s).astype(np.float32), (n, s)
        )
        for i, s in enumerate(sizes)
    ]
    nbytes = sum(s * 4 for s in sizes)
    out = comm.group_all_reduce(list(grads), op="sum")  # compile
    jax.block_until_ready(out)
    times = []
    for i in range(warmup + iters):
        t0 = time.perf_counter()
        out = comm.group_all_reduce(list(grads), op="sum")
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        if i >= warmup:
            times.append(dt)
    return nbytes, n, times


def bench_host(model: str, np_workers: int, strategy: str, iters: int, warmup: int):
    from kungfu_tpu.comm.engine import CollectiveEngine
    from kungfu_tpu.comm.host import HostChannel
    from kungfu_tpu.models.fake import fake_model_sizes
    from kungfu_tpu.plan import PeerID, PeerList, parse_strategy

    base = 21000
    peers = PeerList.of(*(PeerID("127.0.0.1", base + i) for i in range(np_workers)))
    chans = [HostChannel(p, bind_host="127.0.0.1") for p in peers]
    engines = [CollectiveEngine(c, peers, parse_strategy(strategy)) for c in chans]
    sizes = fake_model_sizes(model)
    nbytes = sum(s * 4 for s in sizes)
    bufs = [
        np.random.default_rng(0).standard_normal(sum(sizes)).astype(np.float32)
        for _ in range(np_workers)
    ]
    times = []
    try:
        for i in range(warmup + iters):
            t0 = time.perf_counter()

            def run(e):
                # per-engine private buffer, reduced in place (the NCCL
                # in-place convention the reference benchmark also uses)
                e.all_reduce(bufs[engines.index(e)], op="sum",
                             name=f"bench.{i}", inplace=True)

            ts = [threading.Thread(target=run, args=(e,)) for e in engines]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            dt = time.perf_counter() - t0
            if i >= warmup:
                times.append(dt)
    finally:
        for e in engines:
            e.close()
        for c in chans:
            c.close()
    return nbytes, np_workers, times


def main(argv=None) -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--backend", choices=["device", "host"], default="device")
    p.add_argument("--model", default="resnet50-imagenet")
    p.add_argument("--np", dest="np_workers", type=int, default=4,
                   help="host-backend worker count")
    p.add_argument("--strategy", default="AUTO",
                   help="AUTO measures what ships (single host -> RING)")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--quick", action="store_true")
    p.add_argument("--cpu-mesh", type=int, default=0, metavar="N",
                   help="force an N-device virtual CPU mesh (test/CI mode)")
    args = p.parse_args(argv)
    if args.quick:
        args.iters, args.warmup, args.model = 3, 1, "slp-mnist"
    if args.cpu_mesh:
        import jax

        # before any backend init; env vars are too late when jax is preloaded
        jax.config.update("jax_num_cpu_devices", int(args.cpu_mesh))
        jax.config.update("jax_platforms", "cpu")

    if args.backend == "device":
        nbytes, n, times = bench_device(args.model, args.iters, args.warmup)
    else:
        nbytes, n, times = bench_host(
            args.model, args.np_workers, args.strategy, args.iters, args.warmup
        )

    bws = [bus_bandwidth(nbytes, n, t) for t in times]
    mean = statistics.mean(bws)
    err = statistics.stdev(bws) if len(bws) > 1 else 0.0
    print(
        f"RESULT: {mean:.3f} +-{err:.3f} GiB/s "
        f"(model={args.model}, backend={args.backend}, np={n}, "
        f"payload={nbytes / GIB:.3f} GiB)"
    )
    result = {
        "metric": "allreduce_bus_bandwidth",
        "value": round(mean, 3),
        "unit": "GiB/s",
        "model": args.model,
        "backend": args.backend,
        "np": n,
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
