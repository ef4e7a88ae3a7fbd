#!/usr/bin/env python3
"""ResNet-50 batch-norm variant sweep — the round-3 BN-tax hunt.

Round-3 diagnosis (docs/perf.md, a pre-PR-1 chip run): the
batch-stats BN path costs ~20% of the training step (2,517 img/s with
batch stats vs 3,138 with frozen stats).  This harness times the FULL
train step (fwd+bwd+SGD) under BN implementation variants, interleaved
via bench.measure_group so a burst of host noise can't land on one
variant:

* ``prod``      — the shipping ``nn.batchnorm_apply`` (f32 one-pass moments)
* ``eval_bn``   — frozen running stats (diagnostic ceiling, NOT a candidate:
                  changes training semantics)
* ``bf16_norm`` — identical f32 stats, but the normalize/scale/shift
                  elementwise chain computes in the activation dtype
                  (halves the HBM bytes of BN's elementwise part)
* ``ghost<G>``  — ghost BN: stats per G-sample group (semantic change;
                  regularization-equivalent at small G per the ghost-BN
                  literature, included because the VERDICT asked)

    python benchmarks/bn_sweep.py              # batch 64 @ 224, bf16 (chip)
    python benchmarks/bn_sweep.py --quick      # tiny CPU smoke
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import measure_group  # noqa: E402


def bn_variant(kind: str, ghost: int = 0):
    """A batchnorm_apply replacement implementing ``kind``."""
    import jax
    import jax.numpy as jnp

    from kungfu_tpu.models import nn

    prod = nn.batchnorm_apply

    if kind == "prod":
        return prod

    if kind == "eval_bn":
        def apply(p, stats, x, train, momentum=0.9, eps=1e-5, axis_name=None):
            return prod(p, stats, x, False, momentum, eps, axis_name)
        return apply

    if kind == "f32_norm":
        # the pre-round-5 production path: all-f32 elementwise chain
        # (prod now defaults to the activation dtype — this row keeps the
        # sweep's before/after comparison meaningful)
        def apply(p, stats, x, train, momentum=0.9, eps=1e-5, axis_name=None):
            return prod(p, stats, x, train, momentum, eps, axis_name,
                        compute_dtype=jnp.float32)
        return apply

    if kind == "bf16_norm":
        def apply(p, stats, x, train, momentum=0.9, eps=1e-5, axis_name=None):
            if not train:
                return prod(p, stats, x, train, momentum, eps, axis_name)
            xf = x.astype(jnp.float32)
            axes = tuple(range(x.ndim - 1))
            mean = jnp.mean(xf, axes)
            m2 = jnp.mean(jnp.square(xf), axes)
            if axis_name is not None:
                mean = jax.lax.pmean(mean, axis_name)
                m2 = jax.lax.pmean(m2, axis_name)
            var = m2 - jnp.square(mean)
            new_stats = {
                "mean": momentum * stats["mean"] + (1 - momentum) * mean,
                "var": momentum * stats["var"] + (1 - momentum) * var,
            }
            # the ONLY change vs prod: the elementwise chain runs in the
            # activation dtype (mean/inv folded to bf16 scalars per
            # channel), so BN's big reads/writes stay 2-byte
            inv = (jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)
            y = (x - mean.astype(x.dtype)) * inv + p["bias"].astype(x.dtype)
            return y, new_stats
        return apply

    if kind.startswith("ghost"):
        g = ghost or int(kind[len("ghost"):] or "16")

        def apply(p, stats, x, train, momentum=0.9, eps=1e-5, axis_name=None):
            if not train or x.shape[0] == g:
                # one group spanning the whole batch IS plain BN
                return prod(p, stats, x, train, momentum, eps, axis_name)
            if axis_name is not None:
                raise NotImplementedError(
                    "sync ghost-BN is out of the sweep's scope — a silent "
                    "no-collective variant would conflate ghost grouping "
                    "with dropping sync-BN")
            if x.shape[0] % g != 0:
                # raising (not falling back) keeps the sweep honest: a
                # 'ghost' row that actually measured prod is a lie —
                # measure_group reports the variant unmeasured instead
                raise ValueError(
                    f"ghost group {g} does not divide batch {x.shape[0]}")
            b = x.shape[0]
            xg = x.reshape((b // g, g) + x.shape[1:])
            xf = xg.astype(jnp.float32)
            axes = tuple(range(1, xf.ndim - 1))
            mean = jnp.mean(xf, axes, keepdims=True)      # [groups,1,..,C]
            m2 = jnp.mean(jnp.square(xf), axes, keepdims=True)
            var = m2 - jnp.square(mean)
            inv = jax.lax.rsqrt(var + eps) * p["scale"]
            y = ((xf - mean) * inv + p["bias"]).astype(x.dtype)
            # running stats from RAW moments (mean of per-group vars
            # would drop the between-group mean spread — the same
            # pitfall nn.batchnorm_apply's sync-BN comment documents)
            gm = jnp.mean(mean, axis=0).reshape(-1)
            gv = (jnp.mean(m2, axis=0).reshape(-1) - jnp.square(gm))
            new_stats = {
                "mean": momentum * stats["mean"] + (1 - momentum) * gm,
                "var": momentum * stats["var"] + (1 - momentum) * gv,
            }
            return y.reshape(x.shape), new_stats
        return apply

    raise ValueError(f"unknown BN variant {kind!r}")


def main(argv=None) -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=0)
    p.add_argument("--image-size", type=int, default=0)
    p.add_argument("--rounds", type=int, default=6)
    p.add_argument("--quick", action="store_true")
    p.add_argument("--cpu", action="store_true",
                   help="force the CPU backend, before backend init")
    p.add_argument("--variants", default="prod,eval_bn,f32_norm,ghost16")
    args = p.parse_args(argv)

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    on_tpu = jax.default_backend() == "tpu"
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kungfu_tpu.models import nn
    from kungfu_tpu.models.resnet import ResNet

    batch = args.batch_size or (64 if on_tpu else 4)
    img = args.image_size or (224 if on_tpu else 32)
    depth = 50  # the only CNN family depth with a stage table below 101
    if args.quick:
        batch, img = (8, 64) if on_tpu else (2, 32)

    model = ResNet(depth, num_classes=1000)
    params0, bn0 = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.standard_normal((batch, img, img, 3)),
                         jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 1000, (batch,)), jnp.int32)
    tx = optax.sgd(0.1, momentum=0.9)
    opt0 = tx.init(params0)

    prod_apply = nn.batchnorm_apply

    def make_step(kind):
        variant = bn_variant(kind)

        def step(carry):
            p, bn, opt, _ = carry
            nn.batchnorm_apply = variant  # trace-time swap
            try:
                def loss_fn(p_):
                    loss, new_bn = model.loss(p_, bn, (images, labels),
                                              train=True)
                    return loss, new_bn
                (loss, new_bn), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(p)
            finally:
                nn.batchnorm_apply = prod_apply
            ups, opt = tx.update(grads, opt, p)
            p = optax.apply_updates(p, ups)
            return p, new_bn, opt, loss.astype(jnp.float32)

        return step

    kinds = [k.strip() for k in args.variants.split(",") if k.strip()]
    carry = (params0, bn0, opt0, jnp.float32(0.0))
    times = measure_group({k: make_step(k) for k in kinds}, carry,
                          rounds=args.rounds if on_tpu else 1,
                          on_error="skip")
    base = times.get("prod")
    rows = {}
    for k, t in times.items():
        row = {"ms": None if t is None else round(t * 1e3, 3)}
        if t is not None:
            row["img_per_sec"] = round(batch / t, 1)
            if base:
                row["vs_prod"] = round(base / t, 3)
        rows[k] = row
    result = {
        "metric": "resnet_bn_variant_sweep",
        "value": rows.get("prod", {}).get("img_per_sec", 0) or 0,
        "unit": "images/sec",
        "batch": batch, "image": img, "depth": depth,
        "platform": jax.default_backend(),
        "variants": rows,
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
