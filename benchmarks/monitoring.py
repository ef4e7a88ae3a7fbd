#!/usr/bin/env python3
"""Monitoring-overhead benchmark: GNS / gradient-variance cost.

Parity with reference ``benchmarks/monitoring/benchmark.py`` (GNS and
variance optimizers vs plain S-SGD on ResNet-50, 4 GPUs): measures step
time of ``synchronous_sgd`` vs ``monitor_gradient_noise_scale`` vs
``monitor_gradient_variance`` on the same model and reports the overhead
percentage.  On TPU the monitors are in-graph (fused by XLA), so the
expected overhead is near zero — that is the design claim this harness
checks.

    python benchmarks/monitoring.py --cpu-mesh 8 --quick
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import time


def main(argv=None) -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--quick", action="store_true")
    p.add_argument("--cpu-mesh", type=int, default=0, metavar="N")
    args = p.parse_args(argv)
    if args.quick:
        args.steps, args.warmup, args.batch_size = 5, 1, 2

    import jax

    if args.cpu_mesh:
        jax.config.update("jax_num_cpu_devices", int(args.cpu_mesh))
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks.system import build_model
    from kungfu_tpu.comm.device import Communicator
    from kungfu_tpu.optimizers import (
        monitor_gradient_noise_scale,
        monitor_gradient_variance,
        synchronous_sgd,
    )
    from kungfu_tpu.parallel.train import dp_train_step

    comm = Communicator()
    n = comm.size
    on_tpu = jax.devices()[0].platform == "tpu"
    params0, loss_fn, make_batch = build_model("transformer", quick=not on_tpu)
    inner = optax.sgd(1e-3)
    variants = {
        "sync-sgd": synchronous_sgd(inner, comm.axis),
        "gns": monitor_gradient_noise_scale(
            inner, comm.axis, local_batch_size=args.batch_size
        ),
        "variance": monitor_gradient_variance(inner, comm.axis),
    }

    rng = np.random.default_rng(0)
    global_batch = args.batch_size * n
    step_times = {}
    if on_tpu:
        # overhead is a RATIO: all three variants share one interleaved
        # chained-K group (bench.measure_group) so a burst of host noise
        # cannot land on one side of it.  Each variant's train state
        # rides its own slot of a shared carry.
        from bench import measure_group

        b = make_batch(rng, global_batch)
        carry0, named = {}, {}
        for name, tx in variants.items():
            # the chain traces the step: take the jitted program behind
            # the pulse wrapper
            step = dp_train_step(loss_fn, tx, comm)
            step = getattr(step, "base", step)
            carry0[name] = (params0, tx.init(params0))

            def f(c, name=name, step=step):
                p, o, _loss = step(c[name][0], c[name][1], b)
                return {**c, name: (p, o)}

            named[name] = f
        k_lo = max(1, args.steps // 4)
        k_hi = max(args.steps, k_lo + 1)
        t = measure_group(named, carry0, k_lo=k_lo, k_hi=k_hi)
        # the headline needs sync-sgd + gns; a lone unmeasurable
        # variance variant only costs its own secondary number
        if t["sync-sgd"] is None or t["gns"] is None:
            result = {"metric": "monitoring_overhead", "value": 0.0,
                      "unit": "% (gns vs sync-sgd)", "np": n,
                      "error": "unmeasurable"}
            print(json.dumps(result))
            return result
        step_times = t
    else:
        for name, tx in variants.items():
            step = dp_train_step(loss_fn, tx, comm)
            params, opt_state = params0, tx.init(params0)
            b = make_batch(rng, global_batch)
            params, opt_state, loss = step(params, opt_state, b)  # compile
            jax.block_until_ready(loss)
            times = []
            for i in range(args.warmup + args.steps):
                b = make_batch(rng, global_batch)
                t0 = time.perf_counter()
                params, opt_state, loss = step(params, opt_state, b)
                jax.block_until_ready(loss)
                if i >= args.warmup:
                    times.append(time.perf_counter() - t0)
            step_times[name] = sum(times) / len(times)

    base = step_times["sync-sgd"]
    result = {
        "metric": "monitoring_overhead",
        "value": round(100 * (step_times["gns"] - base) / base, 2),
        "unit": "% (gns vs sync-sgd)",
        "step_times_ms": {k: (None if v is None else round(v * 1e3, 2))
                          for k, v in step_times.items()},
        "np": n,
    }
    if step_times.get("variance") is not None:
        result["variance_overhead_pct"] = round(
            100 * (step_times["variance"] - base) / base, 2
        )
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
