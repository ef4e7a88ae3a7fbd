#!/usr/bin/env python3
"""Synthetic training-throughput benchmark (img/sec or tokens/sec).

Parity with reference ``benchmarks/system/benchmark_kungfu.py`` (Horovod-
style synthetic data, ``--kf-optimizer=sync-sgd --model=ResNet50
--batch-size=64``): drives the framework's real models + distributed
optimizers on synthetic batches over all local devices (data-parallel
mesh), reporting samples/sec.

    python benchmarks/system.py --model resnet50 --optimizer sync-sgd
    python benchmarks/system.py --model transformer --optimizer gns --quick
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax


def _image_classifier(model, quick: bool):
    """Shared harness for the ImageNet-shaped families (resnet50/vgg16)."""
    img = 64 if quick else 224

    def make_batch(rng, batch):
        x = rng.standard_normal((batch, img, img, 3)).astype(np.float32)
        y = rng.integers(0, 1000, size=(batch,))
        return jnp.asarray(x), jnp.asarray(y)

    # BN running stats ride in the tree with zero grads (train mode
    # uses batch stats); their EMA update is skipped — irrelevant to
    # a throughput measurement, keeps the loss a pure fn of (tree, batch)
    def loss_fn(tree, batch):
        x, y = batch
        logits, _ = model.apply(tree["params"], tree["bn"], x, train=True)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    params, bn = model.init(jax.random.PRNGKey(0))
    return {"params": params, "bn": bn}, loss_fn, make_batch


def build_model(name: str, quick: bool):
    if name == "resnet50":
        from kungfu_tpu.models.resnet import ResNet

        return _image_classifier(ResNet(depth=50, num_classes=1000), quick)

    if name == "vgg16":
        from kungfu_tpu.models.vgg import VGG

        return _image_classifier(VGG(depth=16, num_classes=1000), quick)

    if name in ("transformer", "bert"):
        from kungfu_tpu.models.transformer import Transformer, TransformerConfig

        if name == "bert":
            # BERT-base sized, bidirectional (BASELINE config 3: BERT-base
            # + SynchronousAveraging); synthetic next-token objective —
            # this is a throughput harness, like the reference's
            # gradient-buffer benches (v1/benchmarks/model_sizes.py)
            if quick:
                cfg = TransformerConfig(vocab_size=1000, d_model=128,
                                        n_layers=2, n_heads=4, d_ff=256,
                                        max_seq=128, causal=False,
                                        pos="learned")
            else:
                from kungfu_tpu.models.transformer import bert_base

                cfg = bert_base().cfg  # the preset, not copied numbers
        elif quick:
            cfg = TransformerConfig(vocab_size=1000, d_model=128, n_layers=2,
                                    n_heads=4, d_ff=256, max_seq=128)
        else:
            cfg = TransformerConfig(vocab_size=32128, d_model=768, n_layers=12,
                                    n_heads=12, d_ff=3072, max_seq=512)
        model = Transformer(cfg)

        def make_batch(rng, batch):
            ids = rng.integers(0, cfg.vocab_size, size=(batch, cfg.max_seq))
            return jnp.asarray(ids, jnp.int32), jnp.asarray(ids, jnp.int32)

        def loss_fn(params, batch):
            ids, tgt = batch
            logits = model.apply(params, ids)
            return optax.softmax_cross_entropy_with_integer_labels(logits, tgt).mean()

        params = model.init(jax.random.PRNGKey(0))
        return params, loss_fn, make_batch

    raise ValueError(f"unknown model {name!r}")


def inner_optimizer():
    """The shared inner update rule — every distributed optimizer wraps
    THIS, so cross-optimizer rows compare the same update math."""
    return optax.sgd(1e-3, momentum=0.9)


def build_optimizer(name: str, axis, batch: int):
    from kungfu_tpu.optimizers import (
        monitor_gradient_noise_scale,
        monitor_gradient_variance,
        synchronous_averaging,
        synchronous_sgd,
    )

    inner = inner_optimizer()
    if name == "sync-sgd":
        return synchronous_sgd(inner, axis), True
    if name == "sma":
        return synchronous_averaging(inner, axis, alpha=0.1), False
    if name == "gns":
        return monitor_gradient_noise_scale(inner, axis, local_batch_size=batch), True
    if name == "variance":
        return monitor_gradient_variance(inner, axis), True
    raise ValueError(f"unknown optimizer {name!r}")


def host_engine_main(args) -> dict:
    """Launcher-driven multi-process system bench (the reference's
    ``kungfu-run -np 4 python benchmark_kungfu.py`` harness shape,
    ``benchmarks/system/README.md:9-16``): N worker PROCESSES exchange a
    fused fake-model gradient buffer per step through the NATIVE host
    engine (the TCP/unix data plane) and apply an SGD update — the path
    a CPU cluster or a between-mesh-epoch phase trains on.  Run under
    the launcher; rank 0 prints the JSON row::

        python -m kungfu_tpu.runner.cli -q -np 4 -H 127.0.0.1:4 \\
            python benchmarks/system.py -- --backend host --model resnet50
    """
    import kungfu_tpu as kf
    from kungfu_tpu.models.fake import fake_model_sizes

    fakes = {"resnet50": "resnet50-imagenet", "vgg16": "vgg16-imagenet",
             "bert": "bert"}
    if args.model not in fakes:
        raise SystemExit(
            f"--backend host has no fake-size list for {args.model!r}; "
            f"one of {sorted(fakes)}"
        )
    fake_name = fakes[args.model]
    steps = 5 if args.quick else args.steps
    warmup = 1 if args.quick else args.warmup
    peer = kf.init()
    engine = peer.engine()
    if engine is None:
        raise SystemExit(
            "--backend host measures the multi-process host engine: run "
            "under the launcher, e.g.  python -m kungfu_tpu.runner.cli "
            "-np 2 -H 127.0.0.1:2 python benchmarks/system.py -- "
            "--backend host"
        )
    n = peer.size()
    total = sum(fake_model_sizes(fake_name))
    rng = np.random.default_rng(peer.rank())
    params = np.zeros(total, np.float32)
    grads = rng.standard_normal(total).astype(np.float32)
    lr = np.float32(1e-3)

    def step_once(i):
        # fresh salt per step: no two dispatches byte-identical, and the
        # reduced values stay rank-agreed (same salt everywhere)
        g = grads + np.float32(i)
        engine.all_reduce(g, op="mean", inplace=True, name=f"sysg{i}")
        # in-place on the closed-over buffer (a bare `params -=` would
        # rebind the name local to this function)
        params[:] -= lr * g

    for i in range(warmup):
        step_once(-1 - i)
    peer.barrier()  # start the timed window together
    t0 = time.perf_counter()
    for i in range(steps):
        step_once(i)
    dt = time.perf_counter() - t0
    result = {
        "metric": f"{args.model}_host_engine_steps_per_sec",
        "value": round(steps / dt, 3),
        "unit": "steps/sec",
        "np": n,
        "model_mib": round(total * 4 / (1 << 20), 1),
        "grad_exchange_gib_s": round(total * 4 * steps / dt / (1 << 30), 3),
        "cmd": ("python -m kungfu_tpu.runner.cli -q -np {n} -H 127.0.0.1:{n} "
                "python benchmarks/system.py -- --backend host --model {m}"
                "{extra}").format(
                    n=n, m=args.model,
                    extra=(" --quick" if args.quick else
                           f" --steps {steps} --warmup {warmup}")),
    }
    if peer.rank() == 0:
        print(json.dumps(result))
    kf.finalize()
    return result


def main(argv=None) -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50",
                   choices=["resnet50", "vgg16", "transformer", "bert"])
    p.add_argument("--optimizer", default="sync-sgd",
                   choices=["sync-sgd", "sma", "gns", "variance", "zero1"])
    p.add_argument("--backend", default="device", choices=["device", "host"],
                   help="device = local mesh (default); host = the native "
                        "host engine across kfrun worker processes")
    p.add_argument("--batch-size", type=int, default=0, help="per-device")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--quick", action="store_true")
    p.add_argument("--cpu-mesh", type=int, default=0, metavar="N",
                   help="force an N-device virtual CPU mesh (test/CI mode)")
    p.add_argument("--fuse-grads", action="store_true",
                   help="bucket the gradient pytree into one flat buffer "
                        "before the collective (sync-sgd only)")
    p.add_argument("--donate", action="store_true",
                   help="donate params/opt-state buffers to the step "
                        "(in-place update)")
    args = p.parse_args(argv)

    if args.backend == "host":
        return host_engine_main(args)

    if args.cpu_mesh:
        # before any backend init; env vars are too late when jax is preloaded
        jax.config.update("jax_num_cpu_devices", int(args.cpu_mesh))
        jax.config.update("jax_platforms", "cpu")

    from kungfu_tpu.comm.device import Communicator
    from kungfu_tpu.parallel.train import dp_train_step, stack_for_replicas

    comm = Communicator()
    n = comm.size
    on_tpu = jax.devices()[0].platform == "tpu"
    batch = args.batch_size or (64 if on_tpu else 4)
    if args.quick:
        args.steps, args.warmup, batch = 5, 1, 2

    params, loss_fn, make_batch = build_model(args.model, args.quick or not on_tpu)
    if args.optimizer == "zero1":
        # weight-update sharding: same wire bytes as sync-sgd, optimizer
        # state sharded 1/n per device (parallel.zero)
        from kungfu_tpu.parallel import zero1_train_step

        step, init_opt = zero1_train_step(loss_fn, inner_optimizer(), comm)
        opt_state = init_opt(params)
    else:
        if args.optimizer == "sync-sgd" and args.fuse_grads:
            from kungfu_tpu.optimizers import synchronous_sgd

            tx, replicated = synchronous_sgd(
                inner_optimizer(), comm.axis, fuse_grads=True), True
        else:
            tx, replicated = build_optimizer(args.optimizer, comm.axis, batch)
        step = dp_train_step(loss_fn, tx, comm, replicated_params=replicated,
                             donate=args.donate)
        opt_state = tx.init(params)
        if not replicated:
            params = stack_for_replicas(params, n)
            opt_state = stack_for_replicas(opt_state, n)

    rng = np.random.default_rng(0)
    global_batch = batch * n
    batch0 = make_batch(rng, global_batch)
    params, opt_state, loss = step(params, opt_state, batch0)  # compile
    jax.block_until_ready(loss)

    if on_tpu:
        # chain the step with a fixed batch (salted per dispatch) and
        # difference two K's, the window derived from --steps as
        # bench.py's payloads do (see bench.measure_group for what the
        # chained number is and is not).  The chain traces the step, so
        # it takes the jitted program behind the pulse wrapper.
        from bench import measure_chained

        base = getattr(step, "base", step)

        def step_c(c):
            p, o, _ = c
            return base(p, o, batch0)

        k_lo = max(1, args.steps // 4)
        k_hi = max(args.steps, k_lo + 1)
        try:
            dt = measure_chained(step_c, (params, opt_state, loss),
                                 k_lo=k_lo, k_hi=k_hi)
        except RuntimeError as e:
            # honor the one-JSON-line contract even when noise makes
            # the run unmeasurable
            result = {
                "metric": f"{args.model}_{args.optimizer}_throughput",
                "value": 0.0, "unit": "samples/sec", "np": n,
                "error": str(e),
            }
            print(json.dumps(result))
            return result
        sps = global_batch / dt
        # prove real training beyond the timing chain
        for _ in range(args.steps):
            params, opt_state, loss = step(params, opt_state,
                                           make_batch(rng, global_batch))
    else:
        times = []
        for i in range(args.warmup + args.steps):
            b = make_batch(rng, global_batch)
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, b)
            jax.block_until_ready(loss)
            dt = time.perf_counter() - t0
            if i >= args.warmup:
                times.append(dt)
        sps = global_batch * len(times) / sum(times)
    unit = "sequences/sec" if args.model in ("transformer", "bert") else "images/sec"
    result = {
        "metric": f"{args.model}_{args.optimizer}_throughput",
        "value": round(sps, 2),
        "unit": unit,
        "np": n,
        "global_batch": global_batch,
        "final_loss": float(loss),
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
