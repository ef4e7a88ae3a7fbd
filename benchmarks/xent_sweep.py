#!/usr/bin/env python3
"""Block-size sweep for the fused-xent Pallas kernels on the local chip.

Same methodology as flash_sweep.py: all candidates compiled once, timed
via bench.py's measure_group (interleaved rounds, per-program running
min) so a burst of host noise can't land on one candidate.

    python benchmarks/xent_sweep.py [--bwd] [--rounds 8] [--n 8192] [--v 32768]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import measure_group  # noqa: E402


def crossover(args):
    """Kernel-vs-XLA sweep over (N, V) x {fwd, fwd+bwd} — the measured
    basis of ``token_nll``'s auto routing (ops/pallas/xent.py
    ``_route_fused``).  Prints one row per cell with both times and the
    winner; feed disagreements back into the baked thresholds.

        python benchmarks/xent_sweep.py --crossover
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kungfu_tpu.ops.pallas.xent import _route_fused, softmax_cross_entropy

    shapes = [(n, v)
              for n in (1024, 4096, 8192, 16384)
              for v in (8192, 32768, 65536)]
    rng = np.random.default_rng(0)
    for n, v in shapes:
        logits = jnp.asarray(rng.standard_normal((n, v)), jnp.bfloat16)
        targets = jnp.asarray(rng.integers(0, v, n), jnp.int32)
        for bwd in (False, True):
            if bwd:
                def k_step(lg):
                    dl = jax.grad(lambda x: softmax_cross_entropy(
                        x, targets).mean())(lg)
                    return (lg - 0.1 * dl).astype(lg.dtype)

                def x_step(lg):
                    def plain(x):
                        logp = jax.nn.log_softmax(x)
                        return -jnp.take_along_axis(
                            logp, targets[:, None], axis=-1).mean()
                    dl = jax.grad(plain)(lg)
                    return (lg - 0.1 * dl).astype(lg.dtype)
            else:
                def k_step(lg):
                    return lg + softmax_cross_entropy(
                        lg, targets).mean().astype(lg.dtype)

                def x_step(lg):
                    logp = jax.nn.log_softmax(lg)
                    nll = -jnp.take_along_axis(
                        logp, targets[:, None], axis=-1).mean()
                    return lg + nll.astype(lg.dtype)
            times = measure_group(
                {"pallas": k_step, "xla": x_step}, logits,
                rounds=args.rounds, on_error="skip", target_sep=0.3,
            )
            tp, tx = times.get("pallas"), times.get("xla")
            routed = _route_fused(n, v, 2, training=bwd)
            row = {"n": n, "v": v, "bwd": bwd,
                   "pallas_ms": None if tp is None else round(tp * 1e3, 3),
                   "xla_ms": None if tx is None else round(tx * 1e3, 3),
                   "auto_routes_to": "pallas" if routed else "xla"}
            if tp is not None and tx is not None:
                row["winner"] = "pallas" if tp < tx else "xla"
                row["route_correct"] = (row["winner"] == row["auto_routes_to"])
            elif tx is None and tp is not None:
                # XLA variant failed (usually OOM) — the kernel is the
                # only path that runs; routing there is trivially right
                row["winner"] = "pallas"
                row["route_correct"] = routed
            elif tp is None and tx is not None:
                # the KERNEL failed at a shape auto might route to — the
                # one disagreement that breaks production, flag loudly
                row["winner"] = "xla"
                row["route_correct"] = not routed
            print(json.dumps(row), flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--n", type=int, default=8192)
    p.add_argument("--v", type=int, default=32768)
    p.add_argument("--bwd", action="store_true")
    p.add_argument("--rounds", type=int, default=8)
    p.add_argument("--blocks", type=str, default="",
                   help="comma list of bn:bv pairs")
    p.add_argument("--crossover", action="store_true",
                   help="kernel-vs-XLA (N,V) x {fwd,fwd+bwd} routing sweep")
    args = p.parse_args()
    if args.crossover:
        return crossover(args)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kungfu_tpu.ops.pallas.xent import softmax_cross_entropy

    N, V = args.n, args.v
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((N, V)), jnp.bfloat16)
    targets = jnp.asarray(rng.integers(0, V, N), jnp.int32)
    # bytes one iteration must move: fwd reads the logits once; the bwd
    # chain re-reads them, writes dlogits, and the epilogue reads+writes
    # the logits again (all bf16)
    gb = N * V * 2 * (5 if args.bwd else 1) / 1e9

    if args.blocks:
        pairs = [tuple(int(x) for x in pair.split(":"))
                 for pair in args.blocks.split(",")]
    else:
        pairs = [(bn, bv)
                 for bn in (128, 256, 512, 1024)
                 for bv in (1024, 2048, 4096, 8192)]

    def make_step(bn, bv):
        if args.bwd:
            def step(lg):
                dl = jax.grad(
                    lambda x: softmax_cross_entropy(x, targets,
                                                    block_n=bn, block_v=bv).mean()
                )(lg)
                return (lg - 0.1 * dl).astype(lg.dtype)
        else:
            def step(lg):
                return lg + softmax_cross_entropy(
                    lg, targets, block_n=bn, block_v=bv
                ).mean().astype(lg.dtype)
        return step

    # target_sep=0.3: ranking tolerance, not record tolerance (see
    # flash_sweep.py) — keeps a many-pair sweep's runtime sane
    times = measure_group(
        {f"{bn}:{bv}": make_step(bn, bv) for bn, bv in pairs},
        logits, rounds=args.rounds, on_error="skip", target_sep=0.3,
    )
    for name, t in times.items():
        bn, bv = (int(x) for x in name.split(":"))
        row = {"block_n": bn, "block_v": bv, "n": N, "v": V, "bwd": args.bwd}
        if t is None:
            row["error"] = "unmeasured: compile failure or noise (see stderr)"
        else:
            row.update(ms=round(t * 1e3, 3), gb_s=round(gb / t, 1))
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
