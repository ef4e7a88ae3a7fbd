"""Decentralized gossip training under the launcher.

Parity with the reference's async-scalability usage
(``benchmark_kungfu.py --kf-optimizer=pair-avg`` under ``kungfu-run``):
N worker PROCESSES train a least-squares model with PairAveraging —
each step pulls one peer's fused model over the host p2p plane
(zero-copy registered receive), averages 0.5/0.5, applies local
gradients, republishes.  No collective anywhere: stragglers never block.

    python -m kungfu_tpu.runner.cli -np 2 -H 127.0.0.1:2 \
        python examples/gossip_train.py -- --steps 40

Prints one ``KFGOSSIP`` line per worker: final local loss, max weight
error vs the shared ground truth (small only if the replicas mixed),
pull count, and the average pull latency.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--optimizer", choices=["pair-avg", "async"],
                    default="pair-avg",
                    help="async = AsyncPairAveraging: background puller, "
                         "step averages with the last landed model")
    ns = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax

    import kungfu_tpu as kf
    from kungfu_tpu.optimizers.async_sgd import (
        AsyncPairAveragingOptimizer,
        PairAveragingOptimizer,
    )

    peer = kf.init()
    rank, size = kf.current_rank(), kf.cluster_size()

    # every worker sees a DIFFERENT slice of the same ground truth —
    # convergence to w_true proves the models actually mixed
    rng = np.random.RandomState(0)
    w_true = jnp.asarray(rng.randn(ns.dim, 1), np.float32)
    local = np.random.RandomState(1000 + rank)
    X = jnp.asarray(local.randn(128, ns.dim), jnp.float32)
    Y = X @ w_true

    def loss_fn(p):
        return jnp.mean((X @ p["w"] - Y) ** 2)

    grad = jax.jit(jax.grad(loss_fn))
    cls = (AsyncPairAveragingOptimizer if ns.optimizer == "async"
           else PairAveragingOptimizer)
    opt = cls(optax.sgd(ns.lr), peer, name="gt", selector="roundrobin")
    params = {"w": jnp.zeros((ns.dim, 1), jnp.float32)}
    state = opt.init(params)
    for _ in range(ns.steps):
        params, state = opt.step(params, grad(params), state)
    if ns.optimizer == "async":
        opt.close()
    # the faster worker must not close its peer while a slower one is
    # still pulling from its store
    peer.barrier()

    final = float(loss_fn(params))
    err = float(jnp.max(jnp.abs(params["w"] - w_true)))
    n_pulls = opt.pull_bytes // (4 * ns.dim)
    pull_ms = (opt.pull_seconds / n_pulls * 1e3) if n_pulls else 0.0
    print(
        f"KFGOSSIP rank={rank} size={size} final_loss={final:.5f} "
        f"w_err={err:.4f} pulls={n_pulls} pull_ms_avg={pull_ms:.2f}",
        flush=True,
    )
    kf.finalize()
    # convergence bar: local loss near zero AND weights near the shared
    # truth (impossible without mixing — each worker only sees its slice)
    return 0 if (final < 0.05 and err < 0.5) else 1


if __name__ == "__main__":
    sys.exit(main())
