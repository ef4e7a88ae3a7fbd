#!/usr/bin/env python3
"""Block-size sweep for the flash-attention Pallas kernels on the local chip.

The shipped defaults ((128, 128) until round 3) were never swept on real
TPU; VMEM is ~16 MB/core, so much larger tiles fit.  All candidates are
timed through bench.py's ``measure_group`` — one interleaved group with
per-program running mins, so a burst of host noise inflates single
rounds instead of single candidates.  The round-3 v5e result is monotonic in block_k: (128,128)
2.60 ms → (256,1024) 0.34 ms fwd, which set the shipped adaptive
defaults (`attention._default_blocks`).

    python benchmarks/flash_sweep.py [--seq-len 2048] [--bwd] [--rounds 8]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import measure_group  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--head-dim", type=int, default=128,
                   help="64 = the GPT-small shape; defaults were tuned at 128")
    p.add_argument("--bwd", action="store_true", help="sweep fwd+bwd instead of fwd")
    p.add_argument("--rounds", type=int, default=8)
    p.add_argument("--blocks", type=str, default="",
                   help="comma list of bq:bk pairs, e.g. 128:128,256:512")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kungfu_tpu.ops.pallas.attention import flash_attention

    B, H, S, D = 4, 8, args.seq_len, args.head_dim
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
    attn_flops = 2 * 2 * B * H * S * S * D / 2  # causal fwd
    flop_mult = 3.5 if args.bwd else 1.0

    if args.blocks:
        pairs = [tuple(int(x) for x in pair.split(":"))
                 for pair in args.blocks.split(",")]
    else:
        pairs = [(bq, bk)
                 for bq in (128, 256, 512)
                 for bk in (128, 256, 512, 1024)
                 if bq <= S and bk <= S]

    def make_step(bq, bk):
        if args.bwd:
            def step(q_):
                dq = jax.grad(
                    lambda qq: jnp.sum(
                        flash_attention(qq, k, v, causal=True, block_q=bq,
                                        block_k=bk).astype(jnp.float32) ** 2
                    )
                )(q_)
                return (q_ - 1e-3 * dq).astype(q_.dtype)
        else:
            def step(q_):
                return flash_attention(q_, k, v, causal=True,
                                       block_q=bq, block_k=bk)
        return step

    # target_sep=0.3: ~10% worst-case jitter error is plenty for RANKING
    # tile shapes (the spread between candidates is 7x); the full 1.0 s
    # default would multiply a many-pair sweep's runtime for nothing
    times = measure_group(
        {f"{bq}:{bk}": make_step(bq, bk) for bq, bk in pairs},
        q, rounds=args.rounds, on_error="skip", target_sep=0.3,
    )
    for name, t in times.items():
        bq, bk = (int(x) for x in name.split(":"))
        row = {"block_q": bq, "block_k": bk, "seq": S, "bwd": args.bwd}
        if t is None:
            row["error"] = "unmeasured: compile failure or noise (see stderr)"
        else:
            row.update(ms=round(t * 1e3, 3),
                       tflops=round(flop_mult * attn_flops / t / 1e12, 1))
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
