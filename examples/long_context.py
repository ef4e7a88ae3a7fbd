#!/usr/bin/env python3
"""Long-context training demo: sequence parallelism with ring attention.

The sequence axis is sharded over an ``sp`` mesh ring; each device holds
S/sp tokens and K/V blocks rotate via ``ppermute``
(:mod:`kungfu_tpu.parallel.ring`). On TPU each rotation's block runs
through the Pallas flash kernel (``block_impl=auto``), so per-device
attention memory is O(kernel block) — sequence length is limited by
activation storage, not by the S² score matrix.

Runs anywhere::

    python examples/long_context.py --sp 4 --seq-len 512 --cpu-devices 8
    python examples/long_context.py --sp 4 --seq-len 32768   # on a TPU slice

Trains a small causal LM on synthetic token data and checks the sharded
loss against the single-device reference at the start (exactness is the
point of ring attention: it is dense attention, distributed).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--sp", type=int, default=4, help="ring size (mesh sp axis)")
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--batch-size", type=int, default=2)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--cpu-devices", type=int, default=0,
                   help="force an N-device virtual CPU mesh (demo mode)")
    p.add_argument("--block-impl", default="auto",
                   choices=["auto", "flash", "einsum"])
    args = p.parse_args()

    import jax

    if args.cpu_devices:
        jax.config.update("jax_num_cpu_devices", args.cpu_devices)
        jax.config.update("jax_platforms", "cpu")

    import functools

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    from kungfu_tpu.models.transformer import Transformer, TransformerConfig
    from kungfu_tpu.parallel.ring import make_ring_attn

    devs = jax.devices()
    if len(devs) < args.sp:
        print(f"need {args.sp} devices, have {len(devs)} "
              f"(use --cpu-devices {args.sp})", file=sys.stderr)
        return 1
    if args.seq_len % args.sp:
        print("--seq-len must divide by --sp", file=sys.stderr)
        return 1

    cfg = TransformerConfig(
        vocab_size=1024, d_model=args.d_model, n_layers=args.n_layers,
        n_heads=max(2, args.d_model // 64), d_ff=args.d_model * 4,
        max_seq=args.seq_len, causal=True, pos="learned",
        dtype="float32" if devs[0].platform == "cpu" else "bfloat16",
    )
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))

    mesh = Mesh(np.array(devs[: args.sp]), ("sp",))
    attn = make_ring_attn(axis="sp", block_impl=args.block_impl)
    s_loc = args.seq_len // args.sp

    def sharded_loss(params, ids, targets):
        def inner(ids_shard, tgt_shard):
            pos = jax.lax.axis_index("sp") * s_loc + jnp.arange(s_loc)
            positions = jnp.broadcast_to(pos, ids_shard.shape)
            local = model.loss(
                params, (ids_shard, tgt_shard), attn_fn=attn,
                positions=positions,
            )
            # global mean NLL = mean of equal-size shard means
            return jax.lax.pmean(local, "sp")
        per_shard = shard_map(
            inner, mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp")),
            out_specs=P(),
        )(ids, targets)
        return per_shard

    rng = np.random.default_rng(0)
    ids = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch_size, args.seq_len)),
        jnp.int32,
    )
    targets = jnp.roll(ids, -1, axis=1)

    # exactness check: the sharded ring loss IS the dense loss.  The
    # dense reference materializes [B, H, S, S] scores, so gate it: at
    # the sequence lengths this demo exists for, the check itself would
    # exhaust HBM
    if args.seq_len < 4096:
        ref = float(model.loss(params, (ids, targets)))
        got = float(jax.jit(sharded_loss)(params, ids, targets))
        print(f"loss check: ring={got:.6f} dense={ref:.6f}")
        assert abs(got - ref) < max(1e-4, 2e-3 * abs(ref)), (got, ref)
    else:
        print(f"loss check skipped: dense reference needs the O(S^2) "
              f"scores (~{4 * args.batch_size * cfg.n_heads * args.seq_len**2 / 2**30:.0f} GiB at S={args.seq_len})")

    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, ids, targets):
        loss, grads = jax.value_and_grad(sharded_loss)(params, ids, targets)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    # untimed warmup step: compiles the fwd+bwd ring program so tok/s
    # reports steady state, not XLA compile time
    params, opt_state, loss = step(params, opt_state, ids, targets)
    first = last = float(loss)
    t0 = time.perf_counter()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, ids, targets)
        last = float(loss)
    dt = time.perf_counter() - t0
    tok_s = args.batch_size * args.seq_len * args.steps / dt
    print(f"trained {args.steps} steps: loss {first:.4f} -> {last:.4f} "
          f"({tok_s:,.0f} tok/s, sp={args.sp}, S={args.seq_len})")
    assert last < first, "loss did not decrease"
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
