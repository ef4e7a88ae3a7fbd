#!/usr/bin/env python3
"""Headline benchmark — prints ONE JSON line.

Metric (per BASELINE.json): ResNet-50 training throughput in images/sec on
the available chip, via the framework's synchronous-SGD path (the analog of
reference ``benchmarks/system/benchmark_kungfu.py --kf-optimizer=sync-sgd
--model=ResNet50 --batch-size=64``).

``vs_baseline`` compares against the reference's per-worker target — NCCL
on 8x V100 ResNet-50 synchronous throughput, ~360 images/sec/GPU (the
per-worker rate behind reference README.md:201-213's 16xV100 scalability
plot; see BASELINE.md).

Process model: this parent never touches JAX — a chip belongs to one
process at a time — and runs the measurement payload in ONE child under a
hard timeout.  The device payloads (resnet, kernels, allreduce, lm, zero,
pallas) measure on a TPU or fail: without ``--cpu``/``--cpu-mesh`` a
machine with no chip makes the child, and then this script, exit non-zero
with no result line.  A failed or hung payload is a failure too; nothing
is retried, degraded or carried forward.  The remaining payloads are
host-plane drills that pin themselves to the CPU.

Modes::

    python bench.py                  # headline ResNet-50 images/sec JSON
    python bench.py --kernels        # pallas-vs-XLA flash-attn + xent micro-bench
    python bench.py --allreduce      # device + host allreduce GiB/s
    python bench.py --lm             # GPT-small training, kernels in anger
    python bench.py --cpu --quick    # local smoke
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

BASELINE_IMG_PER_SEC_PER_WORKER = 360.0
REPO = os.path.dirname(os.path.abspath(__file__))

PAYLOAD_TIMEOUT_S = 900.0  # first TPU compile can be slow


def run_payload(payload_args, timeout=PAYLOAD_TIMEOUT_S) -> dict:
    """Run ``bench.py <payload_args>`` in a child and return the JSON
    object on its last stdout line.  Anything else — a non-zero exit, a
    timeout, a last line that is not JSON — ends this process with the
    child's last lines on stderr and a non-zero code."""
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + payload_args,
            capture_output=True, text=True, timeout=timeout, cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        sys.exit(f"bench: payload timed out after {timeout:.0f}s")
    # forward the payload's measurement diagnostics (settle/re-span
    # forensics) — invisible failures here cost a round of debugging
    for ln in (r.stderr or "").splitlines():
        if "measure_group" in ln:
            print(ln, file=sys.stderr)
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.strip()]
    if r.returncode == 0 and lines:
        try:
            return json.loads(lines[-1])
        except ValueError:
            sys.exit(f"bench: payload printed non-JSON: {lines[-1][:200]}")
    tail = (r.stderr or r.stdout or "").strip().splitlines()[-6:]
    sys.exit(f"bench: payload failed (rc={r.returncode}): "
             + " | ".join(tail)[-600:])


#: payloads that measure on the device: without --cpu/--cpu-mesh they
#: need a TPU (the others are host-plane drills pinned to the CPU)
CHIP_PAYLOADS = ("resnet", "kernels", "allreduce", "lm", "zero", "pallas")


def require_chip() -> None:
    """The chip path's first touch of JAX: place the compile cache, then
    insist on a TPU.  JAX left to itself falls back to the host's CPU
    without a word, and a CPU number must never be printed under the
    name of a device metric."""
    import jax

    from kungfu_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench: no TPU — JAX came up on {dev.platform!r}.  A "
                 "measurement path that finds no chip fails; pass --cpu "
                 "for a local smoke run")


# --------------------------------------------------------------------------
# payloads (each runs in the child process)
# --------------------------------------------------------------------------

def _peak_tflops(dev):
    """bf16 peak TFLOP/s of ``dev`` for the MFU denominator, from the one
    table the repo keeps (``ops/costmodel.py``, longest ``device_kind``
    prefix wins); ``None`` for a kind the table does not hold."""
    from kungfu_tpu.ops.costmodel import chip_peak_flops

    peak = chip_peak_flops(dev)
    return peak / 1e12 if peak else None


def payload_resnet(args) -> dict:
    """ResNet-50 S-SGD training THROUGH the framework: the measured step is
    ``parallel.dp_train_step`` + ``optimizers.synchronous_sgd`` over a
    ``Communicator`` mesh (n=1 on a single chip — same collectives code
    path with a degenerate axis), the analog of the reference harness
    ``benchmarks/system/benchmark_kungfu.py --kf-optimizer=sync-sgd``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    batch = args.batch_size or (64 if on_tpu else 8)
    img = args.image_size or (224 if on_tpu else 64)
    steps, warmup = args.steps, args.warmup
    if args.quick:
        batch, img, steps = 8, 64, 5

    from kungfu_tpu.comm.device import Communicator
    from kungfu_tpu.models.resnet import ResNet
    from kungfu_tpu.optimizers import synchronous_sgd
    from kungfu_tpu.parallel.train import dp_train_step

    comm = Communicator(devices=[dev], local_size=1)
    model = ResNet(50, num_classes=1000)
    params, bn_state = model.init(jax.random.PRNGKey(0))
    tx = synchronous_sgd(optax.sgd(0.1, momentum=0.9), comm.axis)
    opt_state = tx.init(params)

    def loss_fn(params, bn_state, batch_):
        images, labels = batch_
        logits, new_state = model.apply(params, bn_state, images, train=True)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
        return nll, new_state

    train_step = dp_train_step(loss_fn, tx, comm, has_aux=True, donate=True)

    rng = np.random.default_rng(0)
    images = jnp.asarray(
        rng.standard_normal((batch, img, img, 3), dtype=np.float32), dtype=jnp.bfloat16
    )
    labels = jnp.asarray(rng.integers(0, 1000, size=(batch,)), dtype=jnp.int32)

    # AOT-compile once: the executable serves the FLOP count (MFU
    # numerator) AND the direct warmup/proof loops below (calling the
    # jitted train_step directly would compile the step a second time —
    # the chained timing program needs the traceable callable and
    # compiles its own fused loop either way).  has_aux steps are plain
    # jit objects (no pulse wrapper), so .lower is there.
    drive_step = train_step.lower(
        params, bn_state, opt_state, (images, labels)
    ).compile()
    ca = drive_step.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops_per_step = float(ca["flops"])

    for _ in range(warmup):
        params, bn_state, opt_state, loss = drive_step(
            params, bn_state, opt_state, (images, labels)
        )
    float(loss)  # materialize through the full warmup chain

    # timing: the same chained-K differencing as every other payload
    # (measure_chained) — one compiled program runs K data-dependent
    # training steps and returns a scalar, timed dispatch → host
    # materialization at two K values, differenced so the constant
    # per-dispatch cost cancels.
    carry0 = (params, bn_state, opt_state, jnp.float32(0.0))

    def step_c(c):
        p, b, o, _ = c
        return train_step(p, b, o, (images, labels))

    k_lo = max(1, steps // 4)
    k_hi = max(steps, k_lo + 1)  # --steps 1 must not difference K with itself
    # CPU smoke runs (seconds per step on one core) must not pay the
    # settle/re-span machinery built for dispatch jitter: rounds=1 skips both
    dt_step = measure_chained(step_c, carry0, k_lo=k_lo, k_hi=k_hi,
                              rounds=5 if on_tpu else 1)

    # prove real training: advance `steps` more real steps and report the
    # loss (random labels, so it decays toward memorization, not 0)
    for _ in range(steps):
        params, bn_state, opt_state, loss = drive_step(
            params, bn_state, opt_state, (images, labels)
        )
    final_loss = float(loss)

    img_per_sec = batch / dt_step
    achieved_tflops = flops_per_step / dt_step / 1e12
    peak = _peak_tflops(dev) if on_tpu else None
    return {
        "metric": "resnet50_sync_sgd_images_per_sec_per_chip",
        "value": round(img_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC_PER_WORKER, 4),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "batch": batch,
        "image": img,
        "final_loss": round(final_loss, 4),
        "achieved_tflops": round(achieved_tflops, 2),
        "mfu": round(achieved_tflops / peak, 4) if peak else None,
        "framework_path": "dp_train_step+synchronous_sgd over Communicator(n=1)",
        "timing": f"chained fori_loop K={k_lo}/{k_hi} differencing, interleaved min-of-rounds",
    }


def payload_lm(args) -> dict:
    """GPT-small LM training THROUGH the framework with the Pallas kernels
    in anger: flash attention + fused token-xent inside ``dp_train_step``
    + ``synchronous_sgd`` over a ``Communicator``, timed against the
    XLA-attention/XLA-xent variant of the *same* framework step in one
    interleaved group.  The reference has no LM-training baseline (it
    moves gradient buffers only, SURVEY §2.4), so ``vs_baseline`` is the
    kernel path's speedup over the XLA path — the micro-bench win
    certified inside a real training step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"

    from kungfu_tpu.comm.device import Communicator
    from kungfu_tpu.models.transformer import (
        Transformer, TransformerConfig, default_attention, gpt_small,
    )
    from kungfu_tpu.ops.pallas import make_flash_attn
    from kungfu_tpu.optimizers import synchronous_sgd
    from kungfu_tpu.parallel.train import dp_train_step

    if args.quick or not on_tpu:
        batch, seq = 2, 128
        model = Transformer(TransformerConfig(
            vocab_size=1024, d_model=128, n_layers=2, n_heads=4, d_ff=512,
            max_seq=seq,
        ))
    else:
        # batch 8 OOMs a 16 GB v5e: the XLA variant holds the [B, S, 32128]
        # f32 logits plus their log_softmax residual
        batch, seq = args.batch_size or 4, args.seq_len
        model = gpt_small(max_seq=seq)

    comm = Communicator(devices=[dev], local_size=1)
    params = model.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    V = model.cfg.vocab_size
    ids = jnp.asarray(rng.integers(0, V, (batch, seq)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, V, (batch, seq)), jnp.int32)

    from kungfu_tpu.ops.pallas.xent import softmax_cross_entropy

    def plain_nll(logits, targets_):
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(
            logp, targets_[..., None], axis=-1
        ).squeeze(-1).mean()

    # both variants pin their attention AND xent implementations
    # explicitly — routing through pick_attention/token_nll would let the
    # KF_TPU_ATTN/KF_TPU_XENT debug switches (or simply being off-TPU)
    # silently change what the "pallas" side runs while the JSON still
    # claimed the kernel path.  Off-TPU the kernels run in interpret mode
    # — slow, but the smoke then validates the path the label names.
    flash_attn = make_flash_attn()
    def loss_pallas(params, batch_):
        ids_, targets_ = batch_
        logits = model.apply(params, ids_, train=True, attn_fn=flash_attn)
        return jnp.mean(softmax_cross_entropy(logits, targets_))

    def loss_xla(params, batch_):
        ids_, targets_ = batch_
        logits = model.apply(params, ids_, train=True, attn_fn=default_attention)
        return plain_nll(logits, targets_)

    from kungfu_tpu.ops.pallas.lm_head import lm_head_nll

    def loss_fused_head(params, batch_):
        # round-5 contestant: flash attention + the fused LM-head kernel
        # pair — neither logits nor dlogits materialize in HBM (the
        # head matmul fwd AND bwd run inside the xent kernels)
        ids_, targets_ = batch_
        h = model.hidden(params, ids_, train=True, attn_fn=flash_attn)
        return jnp.mean(lm_head_nll(h, params["head"]["w"], targets_))

    tx = synchronous_sgd(optax.sgd(0.05, momentum=0.9), comm.axis)
    opt0 = tx.init(params)  # one momentum tree, shared by both variants

    def make_step(loss_fn):
        step = dp_train_step(loss_fn, tx, comm, donate=False)
        # the chained harness traces the step inside one compiled loop,
        # and the pulse wrapper syncs scalars to the host on its sample
        # steps: what is timed is the jitted step behind it (pulse off)
        base = getattr(step, "base", step)

        def step_c(c):
            p, o, _ = c
            return base(p, o, (ids, targets))

        return step, step_c

    step_p, step_c_p = make_step(loss_pallas)
    step_x, step_c_x = make_step(loss_xla)
    step_f, step_c_f = make_step(loss_fused_head)

    # FLOP count from the XLA variant (same math): flash/xent flops live
    # inside pallas_call custom calls, which XLA cost analysis counts as
    # ZERO — the pallas program would understate MFU by the whole
    # attention share.  `.base` is the jitted step behind the pulse
    # wrapper (the wrapper itself is a plain function with no .lower).
    ca = getattr(step_x, "base", step_x).lower(
        params, opt0, (ids, targets)).compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops_per_step = float(ca["flops"])

    # both variants share one carry (identical pytree structure, same tx)
    # and one interleaved timing group, so a burst of host noise can't
    # land on just one side of the ratio
    carry = (params, opt0, jnp.float32(0.0))
    t = measure_group(
        {"pallas": step_c_p, "xla": step_c_x, "fused_head": step_c_f},
        carry, k_lo=2, k_hi=8,
    )
    t_p, t_x, t_f = t["pallas"], t["xla"], t["fused_head"]
    if t_p is None or t_x is None:
        raise RuntimeError("lm payload: unmeasurable ("
                           "K-differencing never separated)")
    kernel_path = "flash+xent"
    headline_step = step_p
    if t_f is not None and t_f < t_p:
        # headline rides the best kernel variant; the JSON names which,
        # and the training-proof loop below runs the SAME variant
        t_p, kernel_path, headline_step = t_f, "flash+fused_head", step_f

    # prove real training on the kernel path the headline claims
    p_, o_, loss = params, opt0, None
    for _ in range(args.steps):
        p_, o_, loss = headline_step(p_, o_, (ids, targets))
    final_loss = float(loss) if loss is not None else None

    tokens_per_sec = batch * seq / t_p
    peak = _peak_tflops(dev) if on_tpu else None
    achieved = flops_per_step / t_p / 1e12
    return {
        "metric": "gpt_small_sync_sgd_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(t_x / t_p, 4),
        "vs_baseline_meaning": "speedup of the pallas-kernel step over the same framework step with XLA attention+xent (no reference LM baseline exists)",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "batch": batch,
        "seq_len": seq,
        "xla_variant_tokens_per_sec": round(batch * seq / t_x, 1),
        "kernel_path": kernel_path,
        "fused_head_tokens_per_sec": (round(batch * seq / t_f, 1)
                                      if t_f is not None else None),
        "final_loss": round(final_loss, 4) if final_loss is not None else None,
        "achieved_tflops": round(achieved, 2) if achieved else None,
        "mfu": round(achieved / peak, 4) if achieved and peak else None,
        "framework_path": "dp_train_step+synchronous_sgd over Communicator(n=1), flash attention + fused xent",
    }


def measure_group(named_steps, init_carry, k_lo=4, k_hi=12, rounds=5,
                  on_error="raise", settle_tol=0.05, max_rounds=40,
                  target_sep=1.0):
    """Per-iteration times by chained-K differencing, for a set of step
    functions sharing one carry.

    Compile ONE program per step that applies it K times with a data
    dependence and returns a scalar; time from dispatch to HOST
    materialization of the scalar; run at two K values and difference
    them so that whatever a dispatch costs once (launch, host sync)
    cancels:

        t_iter = (t(k_hi) - t(k_lo)) / (k_hi - k_lo)

    The harness dates from a backend on which ``block_until_ready`` was
    believed not to fence.  On an attached chip it does:
    ``chip_smoke.py``'s train leg times the same GPT-small steps both
    ways and prints the two numbers side by side (PERF.md, Findings,
    PR 21).  What the differencing still buys is the removal of the
    per-dispatch host cost from a sub-millisecond kernel's time; what it
    costs is a second whole-program compile per contestant and a traced
    step (no host callbacks: the pulse wrapper of ``dp_train_step`` must
    be bypassed through ``.base``).  Five tier-1 tests pin its logic;
    its replacement is the benchmark PR's (ROADMAP S1/D3).

    All contestants are timed in interleaved rounds with a per-program
    running min: a burst of host noise inflates one round for everyone
    equally instead of one contestant's entire measurement, so both
    absolute mins and ratios survive.

    The differencing only cancels jitter that is SMALL relative to the
    K-separation ``(k_hi-k_lo)·t_iter``.  At the default span of 8
    iterations a sub-ms kernel separates its two programs by <15 ms and
    the derived time can collapse in BOTH directions.  Two defenses,
    both on by default for real runs:

    * **Adaptive span** (``target_sep``): after a pilot at the base K,
      any contestant whose separation is below ``target_sep`` seconds of
      real compute is rebuilt with a span that provides it, and the
      re-measurement itself verifies the achieved separation (a
      garbage pilot estimate re-spans again, up to twice) — jitter of
      tens of ms then moves the derived per-iteration time by <5%.
    * **Settling** (``settle_tol``): keep interleaving extra rounds
      until every program's best observation is confirmed by a second
      one within tolerance AND the K-differencing is positive — the
      floor was seen twice, not once through a lucky gap — capped at
      ``max_rounds`` total per phase.

    ``rounds=1`` (CI smoke) skips both.

    Phases: a short unsettled pilot sizes the spans; re-span passes
    verify their own estimates; then ONE settled final phase re-measures
    every contestant interleaved, so both sides of any reported ratio
    share the same windows.

    Returns ``{name: seconds_per_iteration}``.  ``on_error="skip"`` maps
    contestants that fail to compile/warm to ``None`` (error on stderr)
    instead of raising — sweep harnesses probe tile shapes that may not
    lower.  A contestant whose K-differencing stays non-positive after
    all rounds also maps to ``None``: that is "unmeasurable", not a
    number.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    import numpy as np

    def prog(k, make_step):
        @jax.jit
        def run(carry, salt):
            # every timed call carries a fresh 4-byte scalar that perturbs
            # the inputs, so no two dispatches are byte-identical
            carry = jax.tree_util.tree_map(
                lambda a: a + salt.astype(a.dtype), carry
            )
            out = lax.fori_loop(0, k, lambda i, c: make_step(c), carry)
            return jnp.sum(
                jnp.concatenate(
                    [jnp.ravel(x).astype(jnp.float32)[:1]
                     for x in jax.tree_util.tree_leaves(out)]
                )
            )
        return run

    rng = np.random.default_rng(1234)

    def fresh_salt():
        return jnp.float32(rng.random() * 1e-3)

    progs, spans, failed = {}, {}, {}
    makers = {}
    for name, make_step in named_steps.items():
        lo, hi = prog(k_lo, make_step), prog(k_hi, make_step)
        try:
            float(lo(init_carry, fresh_salt()))  # compile + warm
            float(hi(init_carry, fresh_salt()))
        except Exception as e:  # noqa: BLE001 — sweep points may not lower
            if on_error != "skip":
                raise
            print(f"measure_group: {name}: {str(e).splitlines()[0][:200]}",
                  file=sys.stderr)
            failed[name] = None
            continue
        progs[name] = (lo, hi)
        spans[name] = k_hi - k_lo
        makers[name] = make_step

    def once(f):
        salt = fresh_salt()
        t0 = time.perf_counter()
        float(f(init_carry, salt))
        return time.perf_counter() - t0

    inf = float("inf")

    def settled(stats, name):
        # the floor is trustworthy once it has been seen twice (within
        # tolerance) and the two K-programs actually separate
        best, second = stats
        if best[name][1] <= best[name][0]:
            return False
        return all(
            second[name][idx] < inf
            and second[name][idx] - best[name][idx] <= settle_tol * best[name][idx]
            for idx in (0, 1)
        )

    walls = {}  # min observed hi-program wall per name (RTT-inclusive)

    def measure(names, phase, n_rounds, settle):
        best = {name: [inf, inf] for name in names}
        second = {name: [inf, inf] for name in names}
        stats = (best, second)

        def run_round():
            for name in names:
                lo, hi = progs[name]
                for idx, f in ((0, lo), (1, hi)):
                    t = once(f)
                    if t < best[name][idx]:
                        second[name][idx] = best[name][idx]
                        best[name][idx] = t
                    elif t < second[name][idx]:
                        second[name][idx] = t

        done = 0
        for _ in range(n_rounds):
            run_round()
            done += 1
        while (settle and done < max_rounds
               and not all(settled(stats, n) for n in names)):
            run_round()
            done += 1
        if settle and names and done > n_rounds:
            noisy = [n for n in names if not settled(stats, n)]
            print(f"measure_group[{phase}]: settled after {done} rounds"
                  + (f" (still noisy: {noisy})" if noisy else ""),
                  file=sys.stderr)
        walls.update({name: best[name][1] for name in names})
        return {
            name: (best[name][1] - best[name][0]) / spans[name]
            for name in names
        }

    names = list(progs)

    # adaptive span: rebuild any contestant whose two programs are
    # separated by less real compute than the dispatch jitter.
    # Iterate — the pilot estimate itself can be jitter-garbage (both
    # high AND low), so each pass re-checks the achieved separation with
    # the better estimate it just produced.  The span is bounded by the
    # OBSERVED dispatch wall (walls[name]/span is a per-iteration upper
    # bound including the RTT share), so a collapsed estimate can never
    # build a program whose single dispatch runs for minutes.
    # (rounds=1 smoke runs skip the pilot too — its estimates only feed
    # this block.)
    if rounds >= 2 and target_sep:
        # pilot: a few unsettled rounds, only to size the re-span — its
        # estimates are discarded once the final phase runs
        est = measure(names, "pilot", min(rounds, 3), settle=False)
        for attempt in (1, 2, 3):
            rekeyed = []
            for name in names:
                t_est = est[name]
                sep = spans[name] * t_est if t_est > 0 else 0.0
                if sep >= 0.8 * target_sep:
                    continue
                per_iter_ub = walls[name] / spans[name]
                wall_cap = max(spans[name],
                               int(4 * target_sep / max(per_iter_ub, 1e-9)))
                want = (int(target_sep / max(t_est, 1e-7)) + 1
                        if t_est > 0 else wall_cap)
                span = min(want, wall_cap, 8192)
                if span <= spans[name]:
                    if t_est > 0:
                        print(f"measure_group: {name} separation "
                              f"{sep:.3f}s stays below target "
                              f"{target_sep}s (span capped at "
                              f"{spans[name]})", file=sys.stderr)
                    continue
                try:
                    hi = prog(k_lo + span, makers[name])
                    float(hi(init_carry, fresh_salt()))  # compile + warm
                except Exception as e:  # noqa: BLE001
                    if on_error != "skip":
                        raise
                    print(f"measure_group: {name} re-span: "
                          f"{str(e).splitlines()[0][:200]}", file=sys.stderr)
                    continue
                progs[name] = (progs[name][0], hi)
                spans[name] = span
                rekeyed.append(name)
            if not rekeyed:
                break
            print(f"measure_group: re-span #{attempt} {rekeyed} to "
                  f">= {target_sep}s of chained compute", file=sys.stderr)
            # only the rebuilt contestants need their estimate refreshed
            # (these numbers are discarded before the final phase, so
            # interleaving is not at stake here)
            est.update(measure(rekeyed, f"respan{attempt}", min(rounds, 3),
                               settle=False))
        for name in names:
            t_est = est[name]
            if t_est and 0 < spans[name] * t_est < 0.8 * target_sep:
                print(f"measure_group: {name}: separation "
                      f"{spans[name] * t_est:.3f}s still below target "
                      f"{target_sep}s after re-span — treat its final "
                      "number as jitter-prone", file=sys.stderr)

    # final: every contestant re-measured in ONE interleaved settled
    # phase, so both sides of any ratio share the same windows
    final = measure(names, "final", rounds, settle=rounds >= 2)
    out = {}
    for name, t in final.items():
        # collapse floor: the differencing cancels constant overhead, so
        # a derived time well below the per-iteration wall bound is
        # normal — but 1000x below it means the two K-programs never
        # separated beyond jitter (observed: a ms-scale train step once
        # derived ~30 ns and printed as a 0.0 ms row).  Relative to the
        # contestant's OWN observed wall, so a genuinely-ns synthetic op
        # (tests) stays measurable while a collapsed ms-scale step does
        # not.
        floor = walls.get(name, 0.0) / max(spans.get(name, 1), 1) * 1e-3
        if (t <= 0 or t < floor) and rounds >= 2:
            # the two K-programs never separated: there is no
            # measurement here, and a collapsed value would print as an
            # impossible TFLOP/s or a 0.0 ms row — report honestly
            print(f"measure_group: {name}: differencing non-positive or "
                  f"collapsed below the jitter floor ({floor:.2e}s) "
                  "after all rounds; unmeasurable", file=sys.stderr)
            out[name] = None
        else:
            # rounds=1 smoke runs keep the clamp: a sub-µs op under
            # timer noise is not a measurement failure worth failing on
            out[name] = max(t, 1e-9)
    out.update(failed)
    return out


def measure_chained(make_step, init_carry, k_lo=4, k_hi=12, rounds=5):
    """Single-step convenience wrapper over :func:`measure_group`."""
    t = measure_group(
        {"step": make_step}, init_carry, k_lo=k_lo, k_hi=k_hi, rounds=rounds
    )["step"]
    if t is None:
        # fail rather than report a fabricated number
        raise RuntimeError("measure_chained: unmeasurable ("
                           "K-differencing never separated)")
    return t


def payload_kernels(args) -> dict:
    """Pallas kernels vs their XLA equivalents on this chip (VERDICT round
    1 weak #7: kernels were interpret-mode tested only)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    if args.quick:
        # CPU/interpret-mode smoke shapes; the real numbers come from TPU
        args.seq_len = min(args.seq_len, 256)

    results = {}
    rng = np.random.default_rng(0)

    # flash attention: pallas kernel vs naive XLA softmax(QK^T)V
    from kungfu_tpu.ops.pallas.attention import flash_attention

    B, H, S, D = 4, 8, args.seq_len, 128
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.bfloat16)

    def xla_attn(q, k, v):
        # causal-masked softmax(QK^T)V — the O(S^2)-HBM baseline XLA
        # produces without a fused kernel
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / (D ** 0.5)
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    # chain q -> attn(q,k,v) -> attn(...): output matches q's shape, values
    # stay bounded (convex combinations of v rows).  Pallas and the XLA
    # baseline are timed as ONE interleaved group so a burst of host
    # noise can't land on just one side of the speedup ratio.
    # causal fwd FLOPs: QK^T + PV over the lower triangle
    attn_flops = 2 * 2 * B * H * S * S * D / 2
    # the un-fused baseline materializes [B,H,S,S] f32 scores — past
    # S~4k that alone is O(10 GB) and the comparison stops being a
    # measurement of anything but HBM exhaustion
    long_context = S >= 4096

    # grad path (round 3: the Pallas dQ + dK/dV backward kernels): chain
    # q -> q - eps * dq, which forces a full fwd+bwd per iteration
    def grad_step(attn):
        def f(q_):
            dq = jax.grad(lambda qq: jnp.sum(attn(qq).astype(jnp.float32) ** 2))(q_)
            return (q_ - 1e-3 * dq).astype(q_.dtype)
        return f

    fwd_group = {"pallas": lambda q_: flash_attention(q_, k, v, causal=True)}
    bwd_group = {"pallas": grad_step(lambda qq: flash_attention(qq, k, v, causal=True))}
    if not long_context:
        fwd_group["xla"] = lambda q_: xla_attn(q_, k, v)
        bwd_group["xla"] = grad_step(lambda qq: xla_attn(qq, k, v))

    def ratio_row(t, shape, flops=None, xla_field="xla_ms"):
        """Build one kernels row; a ``None`` time (measure_group could not
        separate the K-programs) becomes an explicit error field instead
        of a fabricated number."""
        tp, tx = t.get("pallas"), t.get("xla")
        if tp is None:
            return {"error": "unmeasurable (K-differencing "
                             "never separated)", "shape": shape}
        row = {"pallas_ms": round(tp * 1e3, 3), "shape": shape}
        if flops is not None:
            row["pallas_achieved_tflops"] = round(flops / tp / 1e12, 1)
        if "xla" in t:
            if tx is None:
                row["xla_error"] = "unmeasurable"
            else:
                row[xla_field] = round(tx * 1e3, 3)
                row["speedup"] = round(tx / tp, 3)
        return row

    t_fwd = measure_group(fwd_group, q)
    results["flash_attention"] = ratio_row(
        t_fwd, [B, H, S, D], flops=attn_flops, xla_field="xla_naive_ms")

    t_bwd = measure_group(bwd_group, q)
    results["flash_attention_fwd_bwd"] = ratio_row(
        t_bwd, [B, H, S, D], flops=3.5 * attn_flops,
        xla_field="xla_naive_ms")

    # fused softmax-xent: pallas kernel vs XLA logsumexp path
    from kungfu_tpu.ops.pallas.xent import softmax_cross_entropy

    V, N = (2048, 512) if args.quick else (32768, 8192)
    logits = jnp.asarray(rng.standard_normal((N, V)), jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, V, N), jnp.int32)

    def xla_xent(logits, labels):
        lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(
            logits.astype(jnp.float32), labels[:, None], axis=-1
        )[:, 0]
        return (lse - gold).mean()

    # chain logits -> logits + xent(logits): xent is shift-invariant per
    # row (uniform scalar add), so every iteration does identical work
    t_x = measure_group({
        "pallas": lambda lg: lg + softmax_cross_entropy(lg, labels).mean().astype(lg.dtype),
        "xla": lambda lg: lg + xla_xent(lg, labels).astype(lg.dtype),
    }, logits)
    results["fused_xent"] = ratio_row(t_x, [N, V])

    # grad path (round 3: the Pallas dlogits kernel)
    def xent_grad_step(scalar_loss):
        def f(lg):
            dl = jax.grad(scalar_loss)(lg)
            return (lg - 0.1 * dl).astype(lg.dtype)
        return f

    t_xg = measure_group({
        "pallas": xent_grad_step(lambda x: softmax_cross_entropy(x, labels).mean()),
        "xla": xent_grad_step(lambda x: xla_xent(x, labels)),
    }, logits)
    results["fused_xent_fwd_bwd"] = ratio_row(t_xg, [N, V])

    # flash_attention carries no speedup in long-context runs (no XLA
    # baseline); speedup_covers says which kernels the headline value
    # spans.  All rows unmeasurable → raise: the run fails instead of
    # recording 0.
    covered = [
        name
        for name in ("flash_attention", "fused_xent")
        if "speedup" in results[name]
    ]
    if not covered:
        raise RuntimeError("kernels payload: no speedup row was "
                           "measurable; see stderr")
    return {
        "metric": "pallas_kernel_speedup_vs_xla",
        "value": round(min(results[n]["speedup"] for n in covered), 3),
        "speedup_covers": covered,
        "long_context_pallas_only": long_context,
        "unit": "x",
        "vs_baseline": 1.0,
        "platform": dev.platform,
        "kernels": results,
    }


def payload_allreduce(args) -> dict:
    """Device-plane allreduce bus bandwidth (the headline comm number)."""
    import jax

    if args.cpu_mesh:
        # a virtual N-device CPU mesh: the same shard_map/psum collective
        # code path the TPU runs, minus the ICI (scaling-shape artifact,
        # not a bandwidth claim).  Must precede any backend init.
        jax.config.update("jax_num_cpu_devices", int(args.cpu_mesh))
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    n = len(devs)
    if args.quick:
        args.mbytes = min(args.mbytes, 4)
    # per-RANK payload is args.mbytes (the busbw convention: each rank
    # allreduces a buffer of this size); the global sharded array is n
    # ranks' worth
    per_rank_bytes = args.mbytes << 20
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal(n * per_rank_bytes // 4),
        jnp.float32,
    )

    if n == 1:
        # single chip: no collective possible; measure an on-chip
        # read+write of the buffer as a floor.  NOT (y+y)*0.5 — the
        # algebraic simplifier folds that to the identity and the loop
        # would time nothing; a decay factor != 1 survives optimization.
        # At the default 64 MiB this runs ~100 us/iter — differencing
        # noise then dominates (a recorded 64 MiB run
        # exceeded HBM spec) — so the K window stretches to put ~3 ms of
        # real work in the differenced span
        decay = jnp.float32(1.0 - 2.0 ** -12)
        step = lambda y: y * decay
        k_window = {"k_lo": 8, "k_hi": 40}
    else:
        from jax.sharding import Mesh, PartitionSpec as P
        from jax.experimental.shard_map import shard_map

        from kungfu_tpu.ops.schedules import all_reduce_scheduled

        mesh = Mesh(np.array(devs), ("d",))
        inv_n = 1.0 / n

        def make_step(schedule):
            return shard_map(
                lambda y: all_reduce_scheduled(
                    y, "d", schedule=schedule) * inv_n,
                mesh=mesh, in_specs=P("d"), out_specs=P("d"),
            )

        step = make_step("psum")
        k_window = {}
    dt = measure_chained(step, x, **k_window)

    def busbw(t):
        # standard allreduce bus-bandwidth convention over per-rank size
        return (2 * (n - 1) / n if n > 1 else 1.0) * per_rank_bytes / t / (1 << 30)

    schedules = None
    if n > 1:
        # the selectable decompositions (kungfu_tpu.ops.schedules) timed
        # against the same psum in one interleaved group — the
        # device-plane analog of the reference's per-strategy throughput
        # table (session/strategy.go:17-56)
        t = measure_group(
            {s: make_step(s) for s in ("psum", "two_stage", "ring")}, x,
            rounds=3, target_sep=0.3,
        )
        schedules = {
            s: (None if ts is None else round(busbw(ts), 3))
            for s, ts in t.items()
        }
    bus = busbw(dt)
    out = {
        "metric": "allreduce_bus_bandwidth",
        "value": round(bus, 3),
        "unit": "GiB/s",
        "vs_baseline": 1.0,
        "platform": devs[0].platform,
        "n_devices": n,
        "mbytes": args.mbytes,
    }
    if schedules is not None:
        out["schedule_bus_gib_s"] = schedules
    return out


def payload_zero(args) -> dict:
    """ZeRO weight-update sharding rows + the bare shard_map/psum
    framework-tax baseline (ROADMAP #1's ``benchmark_horovod.py``
    analog): the SAME model and chained-K harness timed four ways —

    * ``bare``  — raw JAX: shard_map + per-leaf ``lax.psum`` + optax
      apply, zero framework code in the step;
    * ``zero1`` — all-reduce grads, sharded update (the framework's
      measured comm baseline);
    * ``zero2`` — bucketed reduce-scatter grads (the claim under test:
      gradient wire bytes <= ~55% of zero1's);
    * ``zero3`` — zero2 + parameters sharded 1/n between steps.

    Comm bytes are READ FROM THE TRACED PROGRAM
    (:func:`kungfu_tpu.ops.schedules.traced_collective_bytes`), not from
    the motivating formula, so a silent all-reduce would show up as 2x;
    the partitioner-inserted stage-1/2 param all-gather is reported
    analytically (``analytic_*``).  Per-rank optimizer memory is the
    worst-device footprint (:func:`opt_state_bytes_per_device`) — the
    number the ZeRO memory claim is about."""
    import jax

    if args.cpu_mesh:
        # must land before backend init (this payload runs in a fresh
        # child process, so the backend is still cold here)
        jax.config.update("jax_num_cpu_devices", int(args.cpu_mesh))

    if args.cpu_mesh or args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from kungfu_tpu.comm.device import Communicator
    from kungfu_tpu.ops.schedules import traced_collective_bytes
    from kungfu_tpu.parallel.zero import (opt_state_bytes,
                                          opt_state_bytes_per_device,
                                          zero_train_step)
    from jax import shard_map

    devs = jax.devices()
    n = len(devs)
    comm = Communicator(devices=devs, local_size=n)
    mesh, axis = comm.mesh, comm.axis
    ax_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    d = 256 if args.quick else 512
    rng = np.random.default_rng(0)
    params = {
        f"w{i}": jnp.asarray(
            rng.standard_normal((d, d)) / np.sqrt(d), jnp.float32)
        for i in range(3)
    }
    xb = jnp.asarray(rng.standard_normal((2 * n, d)), jnp.float32)
    yb = jnp.asarray(rng.standard_normal((2 * n, d)), jnp.float32)
    batch = (xb, yb)

    def loss_fn(p, b):
        x, y = b
        h = x
        for i in range(3):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    def inner():
        return optax.adam(1e-3)

    # -- bare shard_map + psum: the no-framework floor ---------------------
    tx = inner()
    o_bare = tx.init(params)

    def bare_body(p, o, b):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        g = jax.tree_util.tree_map(lambda a: lax.psum(a, axis) / n, g)
        updates, o = tx.update(g, o, p)
        p = optax.apply_updates(p, updates)
        return p, o, lax.pmean(loss, axis)

    bare_step = jax.jit(shard_map(
        bare_body, mesh=mesh,
        in_specs=(P(), P(), P(axis)), out_specs=(P(), P(), P()),
    ))

    # scalar-loss carry: iteration i perturbs the (closed-over) params
    # by 1e-8 x the previous loss, so the chain has a real data
    # dependence and no two iterations are CSE-identical
    contestants = {}

    contestants["bare"] = lambda c: bare_step(
        jax.tree_util.tree_map(lambda a: a + c * 1e-8, params),
        o_bare, batch)[2]

    zsteps, rows = {}, {}
    for stage in (1, 2, 3):
        z = zero_train_step(loss_fn, inner(), comm, stage=stage)
        o = z.init_opt(params)
        p0 = z.init_params(params)
        zsteps[stage] = (z, p0, o)
        contestants[f"zero{stage}"] = (
            lambda c, z=z, p0=p0, o=o: z.step(
                jax.tree_util.tree_map(lambda a: a + c * 1e-8, p0),
                o, batch)[2])

    t = measure_group(contestants, jnp.float32(0.0),
                      rounds=1 if args.quick else 3, target_sep=0.1)

    # -- comm bytes from the traced programs -------------------------------
    traced = {"bare": traced_collective_bytes(
        lambda p, o, b: bare_step(p, o, b), params, o_bare, batch,
        axis_sizes=ax_sizes)}
    for stage, (z, p0, o) in zsteps.items():
        traced[f"zero{stage}"] = traced_collective_bytes(
            lambda p_, o_, b_, z=z: z.step(p_, o_, b_), p0, o, batch,
            axis_sizes=ax_sizes)

    full_state = opt_state_bytes(o_bare)  # replicated: full on EVERY rank
    for name in ("bare", "zero1", "zero2", "zero3"):
        # sub-us "step times" are the rounds=1 smoke path's clamped
        # non-positive differencing (one lo/hi sample each on a loaded
        # 1-core box can time inverted) — that is no measurement of a
        # ms-scale train step; report None like the settled path does
        t_name = t.get(name)
        if t_name is not None and t_name < 1e-6:
            t_name = None
        row = {
            "step_ms": (None if t_name is None
                        else round(t_name * 1e3, 4)),
            "traced_comm_bytes_per_rank": {
                k: round(v, 1) for k, v in traced[name].items()},
        }
        if name == "bare":
            row["opt_state_bytes_per_rank"] = full_state
        else:
            stage = int(name[-1])
            z, p0, o = zsteps[stage]
            row["opt_state_bytes_per_rank"] = opt_state_bytes_per_device(o)
            row["analytic_comm_bytes_per_rank"] = {
                k: round(v, 1) for k, v in z.comm_bytes(params).items()}
        rows[name] = row

    grad_ratio = (sum(traced["zero2"].values())
                  / max(sum(traced["zero1"].values()), 1e-9))
    return {
        "metric": "zero2_traced_comm_bytes_vs_zero1",
        "value": round(grad_ratio, 4),
        "unit": "x",
        # the claim: stage 2 moves <= ~55% of the stage-1 gradient bytes
        "vs_baseline": round(0.55 / grad_ratio, 4) if grad_ratio else 0.0,
        "vs_baseline_meaning": "0.55 target over measured ratio (>1 = met)",
        "platform": devs[0].platform,
        "n_devices": n,
        "model": f"mlp3x{d} adam ({sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))} params)",
        "rows": rows,
        "framework_tax_zero1_vs_bare": (
            None if not t.get("bare") or not t.get("zero1")
            or t["bare"] < 1e-6 or t["zero1"] < 1e-6  # same smoke floor
            else round(t["zero1"] / t["bare"], 4)),
    }


def payload_multislice(args) -> dict:
    """Emulated 2-slice hierarchical all-reduce vs flat, with DCN
    wire-latency injection — the ``BENCH_extra.json`` gossip technique
    (a wrapper adds fixed one-way latency to every CROSS-SLICE send,
    intra-slice sends stay fast), so the row measures exactly what the
    hierarchy buys: cross-slice hops leave the critical path.

    Pure host-plane CPU (4 in-process HostChannels in threads, 2 slices
    x 2 ranks): it needs no chip.  ``flat`` is
    the chunked ring all-reduce over all 4 ranks — 2(n-1) synchronized
    steps, each gated by its slowest (cross-slice) link; ``hier`` is the
    two-stage shape the multislice communicator compiles (reduce to the
    slice leader over "ICI", one leader exchange over "DCN", broadcast
    back).  Both reduce to identical sums (asserted)."""
    import threading
    import time as _time

    import numpy as np

    from kungfu_tpu.comm.host import PyHostChannel
    from kungfu_tpu.plan import PeerID, PeerList

    n_slices, rps = 2, 2
    n = n_slices * rps
    wire_ms = 30.0  # injected one-way DCN latency per cross-slice send
    elems = 16384 if args.quick else 65536  # 64/256 KiB float32
    rounds = 3 if args.quick else 5
    base = 23400
    peers = PeerList.of(*(PeerID("127.0.0.1", base + i) for i in range(n)))
    chans = [PyHostChannel(p, token=0, bind_host="127.0.0.1")
             for p in peers]

    def slice_of(r):
        return r // rps

    cross_hops = [0] * n

    class LatChan:
        """The gossip wire proxy, channel-shaped: cross-slice sends pay
        the DCN latency before hitting the real loopback socket."""

        def __init__(self, chan, rank):
            self.chan, self.rank = chan, rank

        def send(self, dst, name, buf):
            if slice_of(dst) != slice_of(self.rank):
                cross_hops[self.rank] += 1
                _time.sleep(wire_ms / 1e3)
            self.chan.send(peers[dst], name, buf)

        def recv(self, src, name):
            return self.chan.recv(peers[src], name)

    wrapped = [LatChan(c, i) for i, c in enumerate(chans)]

    def flat_ring(rank, x, tag):
        """Chunked ring all-reduce over ALL ranks, slice-blind: every
        one of the 2(n-1) steps crosses the slice boundary somewhere,
        so every step pays the injected DCN latency."""
        ch = wrapped[rank]
        chunk = (x.size + n - 1) // n
        padded = np.zeros(chunk * n, np.float32)
        padded[:x.size] = x
        parts = padded.reshape(n, chunk).copy()
        nxt, prv = (rank + 1) % n, (rank - 1) % n
        for s in range(n - 1):
            si, ri = (rank - s) % n, (rank - s - 1) % n
            ch.send(nxt, f"{tag}.rs{s}", parts[si].tobytes())
            parts[ri] += np.frombuffer(
                ch.recv(prv, f"{tag}.rs{s}"), np.float32)
        for s in range(n - 1):
            si, ri = (rank + 1 - s) % n, (rank - s) % n
            ch.send(nxt, f"{tag}.ag{s}", parts[si].tobytes())
            parts[ri] = np.frombuffer(
                ch.recv(prv, f"{tag}.ag{s}"), np.float32)
        return parts.reshape(-1)[:x.size]

    def hier(rank, x, tag):
        """The two-stage multislice shape: ICI reduce to the slice
        leader, ONE DCN exchange among leaders, ICI broadcast back —
        cross-slice latency is paid once, not per ring step."""
        ch = wrapped[rank]
        leader = slice_of(rank) * rps
        if rank != leader:
            ch.send(leader, f"{tag}.up{rank}", x.tobytes())
            return np.frombuffer(
                ch.recv(leader, f"{tag}.dn{rank}"), np.float32).copy()
        acc = x.copy()
        for m in range(leader + 1, leader + rps):
            acc += np.frombuffer(ch.recv(m, f"{tag}.up{m}"), np.float32)
        others = [l for l in range(0, n, rps) if l != leader]
        for o in others:
            ch.send(o, f"{tag}.x{leader}", acc.tobytes())
        total = acc.copy()
        for o in others:
            total += np.frombuffer(ch.recv(o, f"{tag}.x{o}"), np.float32)
        for m in range(leader + 1, leader + rps):
            ch.send(m, f"{tag}.dn{m}", total.tobytes())
        return total

    data = [np.full(elems, float(r + 1), np.float32) for r in range(n)]
    want = sum(data)

    def run_world(fn, tag):
        outs = [None] * n

        def one(r):
            outs[r] = fn(r, data[r], tag)

        ts = [threading.Thread(target=one, args=(r,), daemon=True)
              for r in range(n)]
        t0 = _time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
        if any(t.is_alive() for t in ts):
            raise TimeoutError(f"{tag} hung")
        dt = _time.perf_counter() - t0
        for o in outs:
            assert np.array_equal(o, want), "allreduce result mismatch"
        return dt

    try:
        results = {}
        hops = {}
        for name, fn in (("flat", flat_ring), ("hier", hier)):
            run_world(fn, f"warm.{name}")  # warm sockets + caches
            for r in range(n):
                cross_hops[r] = 0
            best = min(run_world(fn, f"{name}.{i}") for i in range(rounds))
            results[name] = best
            hops[name] = max(cross_hops)  # critical-path cross sends/rank
            for r in range(n):
                cross_hops[r] = 0
    finally:
        for c in chans:
            c.close()

    speedup = results["flat"] / max(results["hier"], 1e-9)
    return {
        "metric": "multislice_hier_allreduce_speedup_vs_flat",
        "value": round(speedup, 4),
        "unit": "x",
        # the claim: the hierarchy strips cross-slice hops off the
        # critical path; under any real DCN latency that must beat flat
        "vs_baseline": round(speedup, 4),
        "vs_baseline_meaning": "flat ring time over hierarchical (>1 = hierarchy wins)",
        "platform": "cpu-hostplane",
        "n_devices": n,
        "model": (f"{n_slices} slices x {rps} ranks, {elems * 4 >> 10} KiB "
                  f"fp32, {wire_ms:.0f} ms injected DCN latency"),
        "rows": {
            name: {
                "allreduce_s": round(results[name], 4),
                "cross_slice_sends_per_round": hops[name] // rounds,
            } for name in results
        },
    }


def payload_adapt(args) -> dict:
    """kf-adapt A/B under chaos-injected interference (ISSUE 9 gate):
    a 3-rank in-process host-plane cluster with ``delay`` clauses (the
    PR-2 chaos layer) throttling the 0<->1 link on BOTH the data path
    and the latency probe (``on=ping``).  Every fixed strategy routes
    traffic over the degraded edge (all 3-peer topologies contain 0-1),
    so each fixed arm pays the injected latency every step; the bandit
    (:class:`kungfu_tpu.monitor.adapt_device.HostBanditDriver`) measures
    its windows, votes, and lockstep-swaps onto the measured-latency MST
    (0-2-1: the slow edge leaves the tree) — steady-state step time must
    beat the best fixed strategy, and the flight recorder must show the
    consensus-fenced ``swap`` event on every rank at one step.

    Pure host-plane CPU (the multislice-row technique): needs no
    chip."""
    import os
    import time as _time
    from collections import Counter

    import numpy as np

    os.environ["KF_NATIVE_ENGINE"] = "0"  # chaos hooks ride the py path
    os.environ["KF_CONFIG_ENABLE_TRACE"] = "1"  # swap events must record
    os.environ.setdefault("KF_CONFIG_LOG_LEVEL", "WARNING")
    wire_ms = 30
    os.environ["KF_CHAOS_SPEC"] = ";".join(
        f"delay:ms={wire_ms},rank={a},peer={b},on={on}"
        for a, b in ((0, 1), (1, 0)) for on in ("send", "ping")
    )

    from kungfu_tpu.monitor import timeline
    from kungfu_tpu.monitor.adapt_device import HostBanditDriver
    from kungfu_tpu.peer import Peer
    from kungfu_tpu.plan import Cluster, PeerList, parse_strategy
    from kungfu_tpu.utils.envs import Config

    elems = 25_000 if args.quick else 50_000  # 100/200 KiB fp32
    fixed_steps = 6 if args.quick else 10
    adapt_steps = 24 if args.quick else 40
    data = np.ones(elems, np.float32)
    fixed_arms = ("STAR", "RING", "BINARY_TREE_STAR")

    def make_peers(base_port, strategy):
        workers = PeerList.parse(
            ",".join(f"127.0.0.1:{base_port + i}" for i in range(3)))
        runners = PeerList.parse(f"127.0.0.1:{base_port + 99}")
        cluster = Cluster(runners, workers)
        ps = [Peer(Config(self_id=w, cluster=cluster)) for w in workers]
        for p in ps:
            p.config.strategy = parse_strategy(strategy)
            p.start()
        return ps

    def run_world(fns, timeout=120.0):
        import threading

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
        deadline = _time.monotonic() + timeout
        for t in ts:
            t.join(max(0.0, deadline - _time.monotonic()))
        if errs:
            raise errs[0]
        if any(t.is_alive() for t in ts):
            raise TimeoutError("adapt world hung")
        return outs

    def measure_step(p, driver=None):
        t0 = _time.perf_counter()
        out = p.engine().all_reduce(data, op="sum")
        dt = _time.perf_counter() - t0
        assert float(out[0]) == 3.0, out[:4]
        swapped = driver.step(dt) if driver is not None else False
        return dt, swapped

    def run_fixed(strategy, port):
        ps = make_peers(port, strategy)
        try:
            times = []
            for _ in range(fixed_steps):
                dts = run_world([
                    lambda p=p: measure_step(p)[0] for p in ps])
                times.append(max(dts))
            # drop warm-up (connection bring-up) steps before the median
            return float(np.median(times[2:]))
        finally:
            for p in ps:
                p.close()

    fixed = {s: run_fixed(s, 24500 + 10 * i)
             for i, s in enumerate(fixed_arms)}

    timeline.reset()
    ps = make_peers(24600, fixed_arms[0])
    drivers = [HostBanditDriver(p, check_every=2, min_pulls=1,
                                min_swap_collectives=1) for p in ps]
    times, swap_steps = [], []
    try:
        for i in range(adapt_steps):
            outs = run_world([
                lambda p=p, d=d: measure_step(p, d)
                for p, d in zip(ps, drivers)])
            flags = {s for _, s in outs}
            assert len(flags) == 1, f"non-lockstep swap at step {i}: {flags}"
            times.append(max(dt for dt, _ in outs))
            if flags.pop():
                swap_steps.append(i)
        active = {d.active for d in drivers}
        assert len(active) == 1, f"ranks diverged on the arm: {active}"
        swap_events = [e for e in timeline.snapshot() if e["kind"] == "swap"]
        by_seq = Counter((e["attrs"]["seq"], e["name"]) for e in swap_events)
        # the fence contract: every swap seq carries one event per rank
        lockstep = {f"seq{seq}:{arm}": n for (seq, arm), n in
                    sorted(by_seq.items())}
        assert all(n == 3 for n in by_seq.values()), lockstep
    finally:
        for p in ps:
            p.close()

    steady = float(np.median(times[-8:]))
    best_fixed = min(fixed.values())
    speedup = best_fixed / max(steady, 1e-9)
    return {
        "metric": "adapt_bandit_steady_step_time_speedup_vs_best_fixed",
        "value": round(speedup, 3),
        "unit": "x",
        "vs_baseline": round(speedup, 3),
        "vs_baseline_meaning": ("best fixed-strategy step time over the "
                                "bandit's steady state (>1 = adaptation "
                                "wins)"),
        "platform": "cpu-hostplane",
        "n_devices": 3,
        "model": (f"3 ranks, {elems * 4 >> 10} KiB fp32 allreduce/step, "
                  f"{wire_ms} ms chaos delay on the 0<->1 link "
                  "(send + ping)"),
        "rows": {
            **{f"fixed_{s}": {"step_ms": round(t * 1e3, 2)}
               for s, t in fixed.items()},
            "bandit": {
                "steady_step_ms": round(steady * 1e3, 2),
                "active_arm": next(iter(active)),
                "swaps_at_steps": swap_steps,
                "swap_events_per_rank": lockstep,
            },
        },
    }


def payload_overlap(args) -> dict:
    """kf-overlap A/B (ISSUE 10 gate): the bucketed ZeRO-2/3 loops over
    a 3-rank in-process host-plane cluster with 30 ms chaos-injected
    wire latency on every send — serial bucket loop (issue, wait,
    compute) vs the depth-k software pipeline
    (:func:`kungfu_tpu.parallel.zero.host_bucket_pipeline`: issue bucket
    i+k while bucket i's optimizer math runs, the engine's bounded
    async window running up to k collectives' wire time concurrently).
    Final parameters must be BITWISE identical between the serial and
    pipelined runs — the pipeline moves wall clock only.  A bare
    ``shard_map``+``psum`` device-plane row on the same model rides
    along as the no-framework reference (no injected latency there:
    XLA's CPU rings share memory, so the row contextualizes framework
    tax, not the overlap ratio).

    Pure host-plane CPU (the multislice/adapt-row technique): needs no
    chip."""
    import os
    import time as _time

    import numpy as np

    os.environ["KF_NATIVE_ENGINE"] = "0"  # chaos hooks ride the py path
    os.environ.setdefault("KF_CONFIG_LOG_LEVEL", "WARNING")
    wire_ms = 30
    os.environ["KF_CHAOS_SPEC"] = f"delay:ms={wire_ms},on=send"

    from kungfu_tpu.comm.engine import CollectiveEngine
    from kungfu_tpu.comm.host import HostChannel
    from kungfu_tpu.monitor.registry import REGISTRY
    from kungfu_tpu.parallel.zero import (host_bucket_all_gather,
                                          host_bucket_pipeline,
                                          host_bucket_spans)
    from kungfu_tpu.plan import PeerID, PeerList, Strategy

    n = 3
    chunk = 12_000 if args.quick else 60_000
    n_buckets = 4
    widths = [chunk // n_buckets] * n_buckets
    spans = host_bucket_spans(chunk, widths)
    total = n * chunk
    steps = 3 if args.quick else 5
    lr, mu = np.float32(0.125), np.float32(0.5)  # exact binary fractions

    def init_state(rank):
        params = (np.arange(total, dtype=np.float32) % 64) / 64
        mom = np.zeros(chunk, np.float32)
        return params, mom

    def grad_of(params, rank_unused, k):
        # deterministic pseudo-gradient in exact binary fractions: any
        # re-carve or ordering error breaks byte equality loudly
        return params * np.float32(0.5) + np.float32(2.0 ** -(k + 2))

    def zero2_step(engine, params, mom, k, pipelined, tag):
        g = grad_of(params, None, k)
        me = engine.rank
        own = params[me * chunk:(me + 1) * chunk].copy()

        def compute(b, red):
            off, w = spans[b]
            m = mom[off:off + w] * mu + red
            mom[off:off + w] = m
            own[off:off + w] -= lr * m
            return None

        host_bucket_pipeline(engine, g, widths, compute,
                             pipelined=pipelined, name=f"{tag}r{k}")
        full = host_bucket_all_gather(engine, own, widths,
                                      pipelined=pipelined, name=f"{tag}g{k}")
        return full, mom

    def zero3_step(engine, own, mom, k, pipelined, tag):
        # params live SHARDED between steps: bucketed all-gather first
        # (the in-step parameter prefetch), then the gradient
        # reduce-scatter pipeline updates the owned chunk
        full = host_bucket_all_gather(engine, own, widths,
                                      pipelined=pipelined, name=f"{tag}g{k}")
        g = grad_of(full, None, k)
        me = engine.rank
        new_own = own.copy()

        def compute(b, red):
            off, w = spans[b]
            m = mom[off:off + w] * mu + red
            mom[off:off + w] = m
            new_own[off:off + w] -= lr * m
            return None

        host_bucket_pipeline(engine, g, widths, compute,
                             pipelined=pipelined, name=f"{tag}r{k}")
        return new_own, mom

    def run_world(fns, timeout=240.0):
        import threading

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
        deadline = _time.monotonic() + timeout
        for t in ts:
            t.join(max(0.0, deadline - _time.monotonic()))
        if errs:
            raise errs[0]
        if any(t.is_alive() for t in ts):
            raise TimeoutError("overlap world hung")
        return outs

    def run_mode(stage, pipelined, base_port, tag):
        peers = PeerList.of(*(PeerID("127.0.0.1", base_port + i)
                              for i in range(n)))
        chans = [HostChannel(p, bind_host="127.0.0.1") for p in peers]
        engines = [CollectiveEngine(c, peers, Strategy.STAR) for c in chans]
        try:
            def one(i):
                params, mom = init_state(i)
                eng = engines[i]
                if stage == 3:
                    state = params[i * chunk:(i + 1) * chunk].copy()
                else:
                    state = params
                times = []
                for k in range(steps):
                    t0 = _time.perf_counter()
                    if stage == 3:
                        state, mom = zero3_step(eng, state, mom, k,
                                                pipelined, tag)
                    else:
                        state, mom = zero2_step(eng, state, mom, k,
                                                pipelined, tag)
                    times.append(_time.perf_counter() - t0)
                if stage == 3:
                    # gather once at the end for the bitwise check
                    state = host_bucket_all_gather(
                        eng, state, widths, pipelined=pipelined,
                        name=f"{tag}fin")
                assert eng.inflight() == 0, "leaked handles"
                return times, state

            outs = run_world([lambda i=i: one(i) for i in range(n)])
            step_s = float(np.median(
                [max(outs[i][0][k] for i in range(n))
                 for k in range(1, steps)]))
            finals = [o[1] for o in outs]
            for f in finals[1:]:
                assert f.tobytes() == finals[0].tobytes(), "ranks diverged"
            return step_s, finals[0]
        finally:
            for c in chans:
                c.close()

    rows = {}
    finals = {}
    port = 24900
    for stage in (2, 3):
        for pipelined in (False, True):
            key = f"{'pipelined' if pipelined else 'serial'}_zero{stage}"
            step_s, fin = run_mode(stage, pipelined, port,
                                   key.replace("_", "")[:6])
            rows[key] = {"step_ms": round(step_s * 1e3, 2)}
            finals[(stage, pipelined)] = fin
            port += 10
    bitwise = all(
        finals[(s, True)].tobytes() == finals[(s, False)].tobytes()
        for s in (2, 3))
    assert bitwise, "pipelined run diverged from serial (geometry bug)"

    ratio2 = rows["pipelined_zero2"]["step_ms"] / rows["serial_zero2"]["step_ms"]
    ratio3 = rows["pipelined_zero3"]["step_ms"] / rows["serial_zero3"]["step_ms"]
    speedup = 1.0 / max(ratio2, 1e-9)

    # bare shard_map + psum reference row on the same model (device
    # plane; no wire injection — see docstring)
    try:
        import jax

        jax.config.update("jax_num_cpu_devices", int(n))
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        mesh = Mesh(np.array(jax.devices()[:n]), ("d",))

        def bare_body(p):
            g = p * 0.5 + 0.01
            g = jax.lax.psum(g, "d") / n
            return p - 0.125 * g

        bare = jax.jit(shard_map(bare_body, mesh=mesh, in_specs=(P(),),
                                 out_specs=P()))
        x = jnp.asarray(init_state(0)[0])
        bare(x).block_until_ready()  # compile
        t0 = _time.perf_counter()
        for _ in range(20):
            x = bare(x)
        x.block_until_ready()
        rows["bare_shardmap_psum"] = {
            "step_ms": round((_time.perf_counter() - t0) / 20 * 1e3, 4),
            "note": ("device-plane reference, no injected wire latency "
                     "(XLA CPU rings are shared-memory) — framework-tax "
                     "context, not part of the overlap ratio"),
        }
    except Exception as e:  # noqa: BLE001 - reference row is best-effort
        rows["bare_shardmap_psum"] = {"error": str(e)[:200]}

    eff = REGISTRY.snapshot().get("kf_overlap_efficiency", {})
    return {
        "metric": "overlap_pipelined_zero2_speedup_vs_serial",
        "value": round(speedup, 3),
        "unit": "x",
        "vs_baseline": round(speedup, 3),
        "vs_baseline_meaning": ("serial bucket-loop step time over the "
                                "depth-k pipelined step time under 30 ms "
                                "injected wire latency (>=1.5 = gate)"),
        "platform": "cpu-hostplane",
        "n_devices": n,
        "model": (f"{total} fp32 params, {n_buckets} buckets x "
                  f"{widths[0] * 4 >> 10} KiB, momentum SGD, {wire_ms} ms "
                  "chaos delay on every send"),
        "rows": {
            **rows,
            "pipelined_vs_serial_zero2": round(ratio2, 3),
            "pipelined_vs_serial_zero3": round(ratio3, 3),
            "bitwise_identical_final_params": bitwise,
            "overlap_efficiency_p50": round(float(eff.get("p50", 0.0)), 3),
        },
    }


def payload_pallas(args) -> dict:
    """Pallas ICI ring collectives (ISSUE 12 / ROADMAP item 2 gate).

    Correctness half (every backend, chip-free on the virtual CPU
    mesh): the interpret-mode kernels — uni/bidirectional reduce-scatter
    and all-gather, padded-tail shapes included — pinned **bitwise**
    against the order-matched lax emulation, bitwise against the
    ``lax.psum_scatter``/``lax.all_gather`` references on order-exact
    data (allclose on arbitrary floats: the ring's reduction order is
    its own, documented), plus traced-bytes parity: the emulation's
    ppermute hops cost exactly what the reference primitives cost under
    the ring convention.

    Perf half: the four allreduce schedules (``psum``/``two_stage``/
    ``ring``/``pallas_ring``) timed in one interleaved ``measure_group``
    at ``--mbytes`` per rank — on a TPU these are the compiled-kernel
    device rows (the measured A/B the bandit arms on); on the CPU mesh
    the pallas_ring arm times the lax emulation (scaling shape, not a
    bandwidth claim)."""
    import jax

    if args.cpu_mesh:
        # must land before backend init (fresh child process)
        jax.config.update("jax_num_cpu_devices", int(args.cpu_mesh))

    if args.cpu_mesh or args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import functools

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from kungfu_tpu.ops.pallas.collectives import (ring_all_gather,
                                                   ring_reduce_scatter,
                                                   ring_wire_bytes)
    from kungfu_tpu.ops.schedules import (all_reduce_scheduled,
                                          traced_collective_bytes)
    from jax import shard_map

    devs = jax.devices()
    n = len(devs)
    if n < 2:
        raise RuntimeError(
            "pallas payload needs >= 2 devices (pass --cpu-mesh 8 off-TPU)")
    on_tpu = devs[0].platform == "tpu"
    mesh = Mesh(np.array(devs), ("d",))

    def world(fn, x):
        f = shard_map(fn, mesh=mesh, in_specs=(P("d"),), out_specs=P("d"))
        return np.asarray(jax.jit(f)(x))

    # -- correctness A/B (interpret kernels vs lax) ------------------------
    # 2180 f32 elements -> 24 padded rows: a ragged tail inside the tile
    # AND tall enough that the bidirectional band split really engages
    # (it falls back to uni below 16 rows — this pins both code paths)
    chunk = 2180
    rng = np.random.default_rng(0)
    checks = {}
    for bidi in (False, True):
        tag = "bidir" if bidi else "uni"
        x = rng.standard_normal((n, n * chunk)).astype(np.float32)
        xi = rng.integers(-1000, 1000, (n, n * chunk)).astype(np.float32)

        def rs(impl, interp, row):
            return ring_reduce_scatter(
                row[0], "d", bidirectional=bidi, impl=impl,
                interpret=interp)[None]

        def rs_ref(row):
            return jax.lax.psum_scatter(
                row[0], "d", scatter_dimension=0, tiled=True)[None]

        kern = world(functools.partial(rs, "pallas", True), jnp.asarray(x))
        emul = world(functools.partial(rs, "lax", None), jnp.asarray(x))
        ref = world(rs_ref, jnp.asarray(x))
        checks[f"rs_{tag}_kernel_vs_emulation_bitwise"] = (
            kern.tobytes() == emul.tobytes())
        checks[f"rs_{tag}_vs_psum_scatter_close"] = bool(
            np.allclose(kern, ref, rtol=1e-5, atol=1e-5))
        ki = world(functools.partial(rs, "pallas", True), jnp.asarray(xi))
        ri = world(rs_ref, jnp.asarray(xi))
        checks[f"rs_{tag}_exact_data_bitwise_vs_psum_scatter"] = (
            ki.tobytes() == ri.tobytes())

        s = rng.standard_normal((n, chunk)).astype(np.float32)

        def ag(impl, interp, sh):
            return ring_all_gather(
                sh[0], "d", bidirectional=bidi, impl=impl,
                interpret=interp)[None]

        def ag_ref(sh):
            return jax.lax.all_gather(sh[0], "d", axis=0, tiled=True)[None]

        kag = world(functools.partial(ag, "pallas", True), jnp.asarray(s))
        rag = world(ag_ref, jnp.asarray(s))
        checks[f"ag_{tag}_bitwise_vs_all_gather"] = (
            kag.tobytes() == rag.tobytes())

        if on_tpu:
            # the COMPILED kernels — the exact program the perf rows
            # time and the bandit would install — validated on chip:
            # a Mosaic-only bug (slot race, semaphore drift) that
            # interpret mode cannot manifest must fail the gate here,
            # not ship inside a promoted bandwidth row
            kc = world(functools.partial(rs, "pallas", False),
                       jnp.asarray(x))
            checks[f"rs_{tag}_compiled_close_vs_emulation"] = bool(
                np.allclose(kc, emul, rtol=1e-5, atol=1e-5))
            kci = world(functools.partial(rs, "pallas", False),
                        jnp.asarray(xi))
            checks[f"rs_{tag}_compiled_exact_bitwise"] = (
                kci.tobytes() == ri.tobytes())
            kcg = world(functools.partial(ag, "pallas", False),
                        jnp.asarray(s))
            checks[f"ag_{tag}_compiled_bitwise"] = (
                kcg.tobytes() == rag.tobytes())

    # -- traced-bytes parity ----------------------------------------------
    pchunk = 1024  # one exact [8, 128] f32 tile: no pad inflation
    rs_traced = traced_collective_bytes(
        shard_map(lambda row: ring_reduce_scatter(
            row[0], "d", impl="lax")[None],
            mesh=mesh, in_specs=(P("d"),), out_specs=P("d")),
        jnp.ones((n, n * pchunk), jnp.float32), axis_sizes={"d": n})
    want_rs = ring_wire_bytes(n * pchunk * 4, n, "reduce_scatter")
    parity = rs_traced.get("ppermute", 0.0) / want_rs
    checks["traced_bytes_parity"] = bool(abs(parity - 1.0) < 1e-6)
    gate_ok = all(checks.values())

    # -- the schedule A/B rows --------------------------------------------
    if args.quick:
        args.mbytes = min(args.mbytes, 4)
    per_rank_bytes = args.mbytes << 20
    xbig = jnp.asarray(
        rng.standard_normal(n * per_rank_bytes // 4), jnp.float32)
    inv_n = 1.0 / n

    def make_step(schedule):
        return shard_map(
            lambda y: all_reduce_scheduled(
                y, "d", schedule=schedule) * inv_n,
            mesh=mesh, in_specs=(P("d"),), out_specs=P("d"))

    t = measure_group(
        {s: make_step(s)
         for s in ("psum", "two_stage", "ring", "pallas_ring")},
        xbig, rounds=3, target_sep=0.3, on_error="skip",
    )

    def busbw(dt):
        return (2 * (n - 1) / n) * per_rank_bytes / dt / (1 << 30)

    rows = {s: (None if dt is None else round(busbw(dt), 3))
            for s, dt in t.items()}
    speedup = 0.0
    if t.get("psum") and t.get("pallas_ring"):
        speedup = round(t["psum"] / t["pallas_ring"], 3)

    return {
        "metric": "pallas_ring_bitwise_and_parity_gate",
        "value": 1.0 if gate_ok else 0.0,
        "unit": "pass",
        "vs_baseline": 1.0 if gate_ok else 0.0,
        "platform": devs[0].platform,
        "n_devices": n,
        "mbytes": args.mbytes,
        "checks": {k: bool(v) for k, v in checks.items()},
        "schedule_bus_gib_s": rows,
        "pallas_ring_speedup_vs_psum": speedup,
        "pallas_ring_impl": "compiled" if on_tpu else "lax-emulation",
        "note": ("device rows: compiled ring kernels over ICI" if on_tpu
                 else "CPU mesh: pallas_ring times the bitwise-identical "
                      "lax emulation (scaling shape, not a bandwidth "
                      "claim); kernel correctness ran in interpret mode"),
    }


def payload_serve(args) -> dict:
    """kf-serve SLO row (ISSUE 13 gate): a 7-peer in-process deployment
    — 6 continuous-batching serving workers over 3 emulated 2-rank
    slices + 1 router — takes a FIXED offered load (one request per
    50 ms, shared 16-token system prompt, 24 new tokens each) while the
    chaos layer kills one worker mid-decode (``die``) and later a whole
    slice (``die_slice``).  The router's progress-deadline ladder
    excludes the victims at slice grain and replays their in-flight
    requests from the committed decode positions on survivors.

    Measured: p50/p99 e2e latency per phase — before / during / after
    each kill, where "during" = requests whose lifetime overlaps the
    kill-to-recovery window — with the gate p99(after) <= 2 x p99(pre)
    and ZERO lost accepted requests; plus the prefix-reuse prefill
    delta (computed tokens vs the no-cache prefill cost) and the
    kf_kv_cache_bytes -> aggregator-snapshot -> serving-rollup flow.

    Decode cadence is pinned at 10 ms/step (ServeWorker.step_period_s):
    the toy transformer's sub-ms CPU steps would make every latency
    queue-free noise — the row measures latency STRUCTURE under
    failure, like every other chip-free CPU-mesh row measures
    protocol structure, not chip speed."""
    import os
    import time as _time

    import numpy as np

    os.environ["KF_NATIVE_ENGINE"] = "0"   # chaos rides the py path
    os.environ.setdefault("KF_CONFIG_LOG_LEVEL", "WARNING")
    os.environ["KF_TPU_HOST_TRANSPORT"] = "python"
    # worker rank 1 dies alone; slice 1 (worker ranks 2,3) dies whole.
    # step = the worker's decode iteration (10 ms cadence), so the kills
    # land ~2.5 s and ~6 s into the loaded run
    os.environ["KF_CHAOS_SPEC"] = (
        "die:rank=1,step=250,mode=raise;"
        "die_slice:slice=1,step=600,mode=raise,rps=2")

    import jax

    from kungfu_tpu.elastic.slices import SliceTopology
    from kungfu_tpu.models.transformer import Transformer, TransformerConfig
    from kungfu_tpu.monitor.aggregator import (ClusterAggregator,
                                               RankReporter, field)
    from kungfu_tpu.monitor.registry import REGISTRY
    from kungfu_tpu.peer import Peer
    from kungfu_tpu.plan import Cluster, PeerList
    from kungfu_tpu.serve.engine import InferenceEngine
    from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec
    from kungfu_tpu.serve.router import ServeRouter, ServeWorker
    from kungfu_tpu.utils.envs import Config

    quick = bool(args.quick)
    period_s = 0.05                      # offered load: 20 req/s
    step_period_s = 0.010                # pinned decode cadence
    new_tokens = 24
    load_seconds = 6.0 if quick else 12.0
    base_port = 24910

    cfg = TransformerConfig(vocab_size=96, d_model=32, n_layers=2,
                            n_heads=2, d_ff=64, max_seq=128,
                            dtype="float32")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    system_prompt = list(range(1, 17))   # 2 full 8-token pages shared

    workers = PeerList.parse(
        ",".join(f"127.0.0.1:{base_port + i}" for i in range(7)))
    runners = PeerList.parse(f"127.0.0.1:{base_port + 99}")
    cluster = Cluster(runners, workers)
    peers = [Peer(Config(self_id=w, cluster=cluster)) for w in workers]
    for p in peers:
        p.start()
    servers = []
    for p in peers[:6]:
        eng = InferenceEngine(
            model, params,
            pool=KVCachePool(PageSpec.for_model(cfg, page_tokens=8), 256),
            max_batch=4, max_seq=cfg.max_seq, rank=p.chaos_rank())
        eng.warmup(prompt_lens=(len(system_prompt) + 4,))
        servers.append(ServeWorker(p, eng, commit_every=4,
                                   step_period_s=step_period_s).start())
    router = ServeRouter(peers[6], worker_ranks=list(range(6)),
                         queue_depth=512, deadline_s=2.0, strike_limit=2,
                         topology=SliceTopology(3, 2))

    # recovery observer: samples the victim flags + the router's dead
    # set so kill/recovery walls come from the OBSERVED ladder, not from
    # guessed chaos timing
    marks = {}
    stop_poll = [False]

    def poll():
        while not stop_poll[0]:
            t = _time.perf_counter()
            if "k1" not in marks and servers[1].dead:
                marks["k1"] = t
            if "r1" not in marks and 1 in router.dead_workers:
                marks["r1"] = t
            if "k2" not in marks and (servers[2].dead or servers[3].dead):
                marks["k2"] = t
            if "r2" not in marks and {2, 3} <= set(router.dead_workers):
                marks["r2"] = t
            _time.sleep(0.005)

    import threading

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()

    handles = []
    t_start = _time.perf_counter()
    i = 0
    while _time.perf_counter() - t_start < load_seconds:
        handles.append(router.submit(system_prompt + [20 + (i % 70)],
                                     new_tokens))
        i += 1
        _time.sleep(period_s)
    outs = [h.wait(120) for h in handles]
    stop_poll[0] = True
    poller.join(1.0)

    lost = sum(1 for o in outs if len(o) != new_tokens)
    k1, r1 = marks.get("k1"), marks.get("r1")
    k2, r2 = marks.get("k2"), marks.get("r2")

    def overlaps(h, lo, hi):
        return lo is not None and hi is not None \
            and h.submitted_s <= hi and h.done_s >= lo

    phases = {"pre": [], "during_worker_kill": [], "between": [],
              "during_slice_kill": [], "after": []}
    for h in handles:
        e2e = h.done_s - h.submitted_s
        if overlaps(h, k1, r1):
            phases["during_worker_kill"].append(e2e)
        elif overlaps(h, k2, r2):
            phases["during_slice_kill"].append(e2e)
        elif k1 is not None and h.done_s < k1:
            phases["pre"].append(e2e)
        elif r2 is not None and h.submitted_s > r2:
            phases["after"].append(e2e)
        else:
            phases["between"].append(e2e)

    def pct(xs, q):
        return float(np.percentile(np.asarray(xs), q)) if xs else None

    rows = {
        name: {"n": len(xs),
               "p50_ms": round(pct(xs, 50) * 1e3, 2) if xs else None,
               "p99_ms": round(pct(xs, 99) * 1e3, 2) if xs else None}
        for name, xs in phases.items()
    }
    p99_pre = pct(phases["pre"], 99)
    p99_after = pct(phases["after"], 99)
    recovery_ratio = (p99_after / p99_pre
                      if p99_pre and p99_after else None)

    # prefix reuse: without the paged cache every admission prefills its
    # whole prompt; with it, only the un-cached suffix computes
    reused = REGISTRY.counter("kf_serve_prefill_tokens_total",
                              what="reused").value
    computed = REGISTRY.counter("kf_serve_prefill_tokens_total",
                                what="computed").value
    naive = sum(len(h.prompt) for h in handles) \
        + sum(len(h.committed) for h in handles)  # replays re-prefill too

    # observability flow: the kv gauge + serve counters must ride a real
    # snapshot into the aggregator's serving rollup (the kftop view)
    rep = RankReporter(rank=0, server_url="http://127.0.0.1:1",
                       slice_id=None)
    agg = ClusterAggregator(stale_after=60.0)
    agg.ingest(rep.snapshot_once())
    srv = field(agg.cluster_view(), "serving")
    kv_flow = bool(srv) and field(srv, "kv_bytes") >= 0 \
        and field(srv, "completed") > 0

    router.close()
    for s in servers:
        if not s.dead:
            s.stop()
    for p in peers:
        try:
            p.close()
        except Exception:  # noqa: BLE001 — victims already closed
            pass

    checks = {
        "zero_lost_accepted_requests": lost == 0,
        "worker_kill_observed": k1 is not None and r1 is not None,
        "slice_kill_observed": k2 is not None and r2 is not None,
        "slice_excluded_whole": {2, 3} <= set(router.dead_workers),
        "replays_happened": router.replayed >= 1,
        "recovery_within_2x": (recovery_ratio is not None
                               and recovery_ratio <= 2.0),
        "prefix_reuse_engaged": reused > 0 and computed < naive,
        "kv_gauge_flows_to_cluster_view": kv_flow,
    }
    return {
        "metric": "serve_slo_p99_recovery_ratio_post_vs_pre",
        "value": round(recovery_ratio, 3) if recovery_ratio else 0.0,
        "unit": "x",
        "vs_baseline": round(recovery_ratio, 3) if recovery_ratio else 0.0,
        "vs_baseline_meaning": ("post-kill p99 over pre-kill p99 at fixed "
                                "offered load (gate: <= 2.0)"),
        "n_devices": 6,
        "platform": "cpu-hostplane",
        "model": (f"6 serve workers (3x2-rank slices) + router, 20 req/s "
                  f"offered, {new_tokens} tokens/req, 10 ms decode "
                  "cadence, worker kill @ step 250 + slice kill @ 600"),
        "rows": {
            "phases": rows,
            "requests": {"accepted": len(handles), "lost": lost,
                         "completed": router.completed,
                         "replayed": router.replayed,
                         "dead_workers": router.dead_workers},
            "prefill_tokens": {"computed": int(computed),
                               "reused": int(reused),
                               "no_cache_cost": int(naive)},
        },
        "checks": checks,
        "note": ("chip-free CPU-mesh SLO row: the chaos `die` kill "
                 "excludes the victim's slice (training-ladder "
                 "semantics), the `die_slice` kill removes slice 1 "
                 "whole, and every in-flight request replays from its "
                 "last committed decode position — greedy decode makes "
                 "the replayed continuation deterministic"),
    }


def payload_pp(args) -> dict:
    """kf-pipeline A/B (ISSUE 15 gate): a 2-stage cross-DCN pipeline
    over a 2-rank in-process host-plane cluster — each rank emulating
    one SLICE, 30 ms chaos-injected wire latency on every send (every
    send IS a cross-slice activation/gradient hop at dp=1) — 1F1B with
    async-handle prefetch vs naive sequential microbatching.  Final
    params must be BITWISE identical between the schedules (the
    schedule moves wall clock only), and the bubble fraction comes from
    the kf-xray step decomposition (the ``pp_bubble`` phase over the
    recorded ``pp`` spans).

    Pure host-plane CPU (the multislice/adapt/overlap-row technique):
    needs no chip."""
    import os
    import threading
    import time as _time

    import numpy as np

    os.environ["KF_NATIVE_ENGINE"] = "0"  # chaos hooks ride the py path
    os.environ.setdefault("KF_CONFIG_LOG_LEVEL", "WARNING")
    os.environ["KF_CONFIG_ENABLE_TRACE"] = "1"  # xray bubble feedstock
    wire_ms = 30
    os.environ["KF_CHAOS_SPEC"] = f"delay:ms={wire_ms},on=send"

    import jax
    import optax

    from kungfu_tpu.comm.engine import CollectiveEngine
    from kungfu_tpu.comm.host import HostChannel
    from kungfu_tpu.models.transformer import TransformerConfig
    from kungfu_tpu.monitor import timeline, xray
    from kungfu_tpu.parallel import pp as ppmod
    from kungfu_tpu.parallel.train import ParallelPlan
    from kungfu_tpu.plan import PeerID, PeerList, Strategy

    cfg = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=4, n_heads=2, d_ff=64,
        max_seq=16, dtype="float32")
    n_micro = 4 if args.quick else 8
    steps = 2 if args.quick else 3
    plan_of = {
        "1f1b": ParallelPlan(pp=2, n_micro=n_micro, pp_schedule="1f1b"),
        "sequential": ParallelPlan(pp=2, n_micro=n_micro,
                                   pp_schedule="sequential"),
    }
    full = ppmod.init_stacked_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B = n_micro * 2
    ids = rng.integers(0, cfg.vocab_size, (B, 16)).astype(np.int32)
    tgt = rng.integers(0, cfg.vocab_size, (B, 16)).astype(np.int32)

    def run_arm(name, base_port):
        plan = plan_of[name]
        peers = PeerList.of(PeerID("127.0.0.1", base_port),
                            PeerID("127.0.0.1", base_port + 1))
        chans = [HostChannel(p, bind_host="127.0.0.1") for p in peers]
        engines = []
        try:
            engines = [CollectiveEngine(c, peers, Strategy.STAR)
                       for c in chans]
            pipes = [ppmod.HostPipeline(e, plan, cfg, full_params=full,
                                        inner=optax.sgd(0.125))
                     for e in engines]

            losses = []

            def world(k):
                outs = [None, None]
                errs = []

                def one(i):
                    try:
                        outs[i] = pipes[i].train_step(ids, tgt)
                    except BaseException as e:  # noqa: BLE001
                        errs.append(e)

                ts = [threading.Thread(target=one, args=(i,), daemon=True)
                      for i in range(2)]
                t0 = _time.perf_counter()
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(600)
                if errs or any(t.is_alive() for t in ts):
                    raise RuntimeError(f"{name} step {k} wedged: {errs}")
                return _time.perf_counter() - t0, outs[1]

            world(0)  # warmup: compiles + socket bring-up
            cursor, _ = timeline.events_tail(0)
            walls = []
            for k in range(steps):
                dt, loss = world(1 + k)
                walls.append(dt)
                losses.append(float(loss))
            cursor2, evs = timeline.events_tail(cursor)
            # kf-xray decomposition over the measured window: the
            # pp_bubble phase per rank / summed wall
            bubble = wall = 0.0
            for r in range(2):
                split = xray.rank_phase_split(
                    [e for e in evs if e.get("rank") == r])
                bubble += split["pp_bubble"]
                wall += split["wall_s"]
            return {
                "step_ms": round(1e3 * min(walls), 2),
                "mean_step_ms": round(1e3 * float(np.mean(walls)), 2),
                "losses": [round(l, 6) for l in losses],
                "bubble_fraction_xray": round(bubble / wall, 4)
                if wall else None,
                "final": [np.concatenate(
                    [np.asarray(l, np.float32).ravel()
                     for l in jax.tree_util.tree_leaves(p.params[0])])
                    for p in pipes],
            }
        finally:
            # engines own thread pools: the sequential arm's must not
            # survive into the 1f1b arm's timed window
            for e in engines:
                e.close()
            for c in chans:
                c.close()

    rows = {}
    finals = {}
    for i, name in enumerate(("sequential", "1f1b")):
        r = run_arm(name, 24500 + 10 * i)
        finals[name] = r.pop("final")
        rows[name] = r
    bitwise = all(
        np.array_equal(a, b)
        for a, b in zip(finals["sequential"], finals["1f1b"]))
    losses_equal = rows["sequential"]["losses"] == rows["1f1b"]["losses"]
    speedup = rows["sequential"]["step_ms"] / rows["1f1b"]["step_ms"]
    rows["bitwise_identical_final_params"] = bool(bitwise)
    rows["losses_equal"] = bool(losses_equal)
    rows["speedup_1f1b_vs_sequential"] = round(speedup, 3)
    return {
        "metric": "pp_1f1b_speedup_vs_naive_sequential",
        "value": round(speedup, 3),
        "unit": "x",
        # the ISSUE 15 gate: >= 1.5x under 30 ms injected DCN latency
        # with bitwise-identical finals
        "vs_baseline": round(speedup, 3),
        "gate_1p5x": bool(speedup >= 1.5 and bitwise and losses_equal),
        "platform": "cpu-hostplane",
        "n_devices": 2,
        "model": (f"transformer d{cfg.d_model} L{cfg.n_layers} "
                  f"vocab {cfg.vocab_size}, {n_micro} microbatches, "
                  f"2 stages (1 rank per emulated slice), "
                  f"{wire_ms} ms chaos delay on every send"),
        "rows": rows,
    }


def payload_xray(args) -> dict:
    """kf-xray gate (ISSUE 14): causal step-time attribution + the
    mfu_decomp row, chip-free on the CPU mesh.

    A 3-rank in-process host-plane cluster trains a small transformer
    (real jit fwd+bwd per rank = the ``compute`` phase, a timed batch
    fetch = ``input_stall``) and allreduces a gradient-sized buffer per
    step while chaos ``delay`` clauses throttle the 0<->1 link: 30 ms on
    BOTH send directions (every rank pays the wire → ``comm_exposed``
    dominates) plus 30 ms on rank 1's receive from rank 0 (an
    asymmetric straggler → the skew math must name rank 1 and the
    planted edge).  The flight recorder's dump is then attributed twice
    — offline through the real ``kftrace`` load path and online through
    a live :class:`ClusterAggregator` fed per-rank snapshots — and the
    two verdicts are asserted IDENTICAL (one implementation,
    monitor/xray.py).  The mfu_decomp row reports per-phase seconds and
    the analytic model-FLOPs rate (no MFU on CPU: there is no honest
    peak), and the checked-in ``tests/xray_budget.json`` ceilings gate
    the row in scripts/check.sh."""
    import os
    import tempfile
    import threading
    import time as _time

    import numpy as np

    os.environ["KF_NATIVE_ENGINE"] = "0"  # chaos hooks ride the py path
    os.environ["KF_CONFIG_ENABLE_TRACE"] = "1"
    os.environ.setdefault("KF_CONFIG_LOG_LEVEL", "WARNING")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    wire_ms = 30
    # the planted 0<->1 link: both SEND directions pay the wire (a
    # barrier collective stalls every rank on the slow link, so the
    # whole cluster's spans inflate — that is the comm_exposed share)
    # and rank 1's RECEIVE leg pays 2x (exit asymmetry: rank 1 leaves
    # the collective ~2x wire after everyone else — a deterministic
    # straggler margin no scheduling jitter can flip, so the verdict
    # must name rank 1 and the widest-skew edge)
    os.environ["KF_CHAOS_SPEC"] = (
        f"delay:ms={wire_ms},rank=0,peer=1,on=send;"
        f"delay:ms={wire_ms},rank=1,peer=0,on=send;"
        f"delay:ms={2 * wire_ms},rank=1,peer=0,on=recv"
    )

    import jax
    import jax.numpy as jnp

    from kungfu_tpu.models.transformer import Transformer, TransformerConfig
    from kungfu_tpu.monitor import timeline, traceview
    from kungfu_tpu.monitor import xray as xraylib
    from kungfu_tpu.monitor.aggregator import (REPORT_KINDS,
                                               ClusterAggregator,
                                               make_snapshot)
    from kungfu_tpu.monitor.registry import REGISTRY
    from kungfu_tpu.ops import costmodel
    from kungfu_tpu.peer import Peer
    from kungfu_tpu.plan import Cluster, PeerList, parse_strategy
    from kungfu_tpu.utils.envs import Config

    steps = 8 if args.quick else 16
    B, S = 2, 32
    cfg = TransformerConfig(vocab_size=512, d_model=128, n_layers=2,
                            n_heads=4, d_ff=512, max_seq=64)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    flops_per_step = costmodel.train_step_flops(cfg, B, S)
    grad_fn = jax.jit(jax.grad(lambda p, ids, tg: model.loss(p, (ids, tg))))
    # warm the compile outside the measured steps
    warm = jnp.zeros((B, S), jnp.int32)
    jax.block_until_ready(grad_fn(params, warm, warm))

    workers = PeerList.parse(",".join(f"127.0.0.1:{24700 + i}"
                                      for i in range(3)))
    runners = PeerList.parse("127.0.0.1:24799")
    cluster = Cluster(runners, workers)
    peers = [Peer(Config(self_id=w, cluster=cluster)) for w in workers]
    for p in peers:
        p.config.strategy = parse_strategy("STAR")
        p.start()

    grad_buf = np.ones(50_000, np.float32)  # ~200 KiB, the wire payload
    # one Generator per rank thread: numpy Generators are not
    # thread-safe, and the three rank threads draw concurrently
    rngs = [np.random.default_rng(r) for r in range(3)]
    meter = costmodel.MFUMeter(step_flops=flops_per_step)  # peak None: CPU

    def run_world(fns, timeout=120.0):
        outs, errs = [None] * len(fns), []

        def wrap(i, f):
            try:
                outs[i] = f()
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=wrap, args=(i, f), daemon=True)
              for i, f in enumerate(fns)]
        for t in ts:
            t.start()
        deadline = _time.monotonic() + timeout
        for t in ts:
            t.join(max(0.0, deadline - _time.monotonic()))
        if errs:
            raise errs[0]
        if any(t.is_alive() for t in ts):
            raise TimeoutError("xray world hung")
        return outs

    def rank_step(p, rank):
        with timeline.span("input", "batch.next", rank=rank):
            ids = rngs[rank].integers(0, cfg.vocab_size,
                                      (B, S)).astype(np.int32)
        g = grad_fn(params, jnp.asarray(ids), jnp.asarray(ids))
        jax.block_until_ready(g)
        out = p.engine().all_reduce(grad_buf, op="sum")
        assert float(out[0]) == 3.0

    timeline.reset()
    walls = []
    try:
        for i in range(steps):
            timeline.set_step(i)
            t0 = _time.perf_counter()
            run_world([lambda p=p, r=r: rank_step(p, r)
                       for r, p in enumerate(peers)])
            wall = _time.perf_counter() - t0
            walls.append(wall)
            meter.step(wall_s=wall)
        events = timeline.snapshot()
        # offline: through the REAL kftrace dump + load path
        fd, dump = tempfile.mkstemp(suffix=".jsonl", prefix="kf-xray-")
        os.close(fd)
        try:
            timeline.dump(dump)
            loaded = traceview.load_all([dump])
        finally:
            os.unlink(dump)
        offline = xraylib.verdict(loaded)
        report = xraylib.render_report(loaded)
        # online: the live aggregator fed per-rank snapshots (the
        # reporter's REPORT_KINDS filter applied, like production)
        gauges = {k: float(v) for k, v in REGISTRY.snapshot().items()
                  if isinstance(v, float)}
        agg = ClusterAggregator(stale_after=3600.0)
        for r in range(3):
            agg.ingest(make_snapshot(
                rank=r, pid=os.getpid(), wall=_time.time(), step=steps - 1,
                step_time_s=float(np.median(walls)),
                counters={}, gauges=gauges if r == 0 else {}, latency={},
                events=[e for e in events
                        if e["rank"] == r and e["kind"] in REPORT_KINDS],
                net={}, strategy="STAR"))
        view = agg.cluster_view()
        online = (view["xray"] or {}).get("verdict")
    finally:
        for p in peers:
            p.close()

    rows = xraylib.step_attribution(loaded)
    med = {ph: float(np.median([r["phases"][ph] for r in rows]))
           for ph in xraylib.PHASES}
    med_wall = float(np.median([r["wall_s"] for r in rows]))
    with open(os.path.join(REPO, "tests", "xray_budget.json")) as f:
        budget = json.load(f)
    ceilings = budget["phase_ceilings_s_per_step"]
    budget_ok = (med_wall <= budget["step_wall_s_max"]
                 and all(med[ph] <= ceilings[ph] for ph in xraylib.PHASES))
    culprit = offline["culprit"] or {}
    checks = {
        "offline_online_verdict_identical":
            json.loads(json.dumps(offline)) == json.loads(
                json.dumps(online)),
        "culprit_is_planted_edge_rank1": culprit.get("slowest_rank") == 1,
        "dominant_phase_is_comm_exposed":
            offline["dominant"] == "comm_exposed",
        "comm_exposed_covers_planted_wire":
            med["comm_exposed"] >= wire_ms / 1e3,
        "straggler_excess_attributed":
            med["straggler_wait"] >= 0.3 * wire_ms / 1e3,
        # CPU mesh: no peak -> no MFU row (model-FLOPs rate only); a
        # detected TPU peak (or KF_XRAY_PEAK_FLOPS) must yield a real MFU
        "mfu_follows_detected_peak": ((meter.mfu is not None)
                                      == (meter.peak_flops is not None)),
        "model_flops_rate_measured":
            gauges.get("kf_model_flops_s", 0.0) > 0,
        "report_names_culprit": "rank 1" in report,
        "budget_ok": budget_ok,
    }
    share = (med["comm_exposed"] + med["straggler_wait"]) / max(
        sum(med.values()), 1e-9)
    return {
        "metric": "xray_comm_share_attributed_to_planted_link",
        "value": round(share, 3),
        "unit": "fraction",
        "vs_baseline": 1.0 if all(checks.values()) else 0.0,
        "vs_baseline_meaning": ("1.0 = every xray check passed "
                                "(offline==online, culprit edge named, "
                                "budget within ceilings)"),
        "platform": "cpu-hostplane",
        "n_devices": 3,
        "model": (f"3 ranks, GPT d{cfg.d_model}xL{cfg.n_layers} fwd+bwd "
                  f"per step + 200 KiB allreduce, {wire_ms} ms chaos "
                  f"delay on rank 1's send+recv legs of the 0<->1 link"),
        "checks": checks,
        "rows": {
            "attribution": {
                "steps": steps,
                "median_step_wall_ms": round(med_wall * 1e3, 2),
                "phases_ms": {ph: round(v * 1e3, 2)
                              for ph, v in med.items()},
                "culprit": culprit,
                "straggler": offline["straggler"],
                "dominant": offline["dominant"],
            },
            "mfu_decomp": {
                "model": f"d{cfg.d_model} L{cfg.n_layers} B{B} S{S}",
                "flops_per_step": flops_per_step,
                "model_flops_s": round(gauges.get("kf_model_flops_s",
                                                  0.0), 1),
                # a detected chip peak (TPU, or KF_XRAY_PEAK_FLOPS) makes
                # this a real MFU row; the CPU mesh has no honest peak
                # and reports the model-FLOPs rate alone
                "mfu": (round(meter.mfu, 5) if meter.mfu is not None
                        else None),
                "peak_flops": meter.peak_flops,
                "peak_note": (None if meter.peak_flops is not None else
                              "CPU mesh: no honest chip peak — "
                              "model-FLOPs rate only; the TPU row is in "
                              "scripts/tpu_backlog.sh"),
                "phase_seconds_per_step": {
                    ph: round(v, 5) for ph, v in med.items()},
            },
            "budget": {"ok": budget_ok, **budget},
        },
    }


def payload_persist(args) -> dict:
    """kf-persist gate (ISSUE 17): async checkpoint overhead + measured
    Poisson-preemption goodput, chip-free on the host plane.

    Two rows over the same deterministic elementwise-SGD state (sharded
    the ZeroBoundary way, so the manifest plane under test is the real
    one):

    * **overhead** — a 4-rank step loop (real numpy compute per rank +
      ``commit_local``) timed twice: persistence off vs a
      :class:`~kungfu_tpu.elastic.persist.PersistPlane` persisting every
      5th step — still ~2 orders of magnitude denser than the 30 s
      default period (a CPU-only arm can't persist EVERY step without
      measuring GIL steal from the writer threads instead of the handle
      pattern; issue cost itself is ~0.1 ms).  The async handle pattern
      keeps the writes off the step path; the gate is overhead <= 5%.
    * **goodput** — preemptions at seeded Poisson arrivals kill the
      whole world mid-run; every relaunch cold-restarts from the newest
      complete manifest onto an ALTERNATING world size (4 -> 2 -> 4 ...)
      via the shape-agnostic ``restore_from_manifest``, and the final
      params must be bitwise identical to a straight fixed-world replay.
      goodput = useful steps / executed steps (lost work is the replayed
      tail past the last complete manifest).
    """
    import tempfile
    import time as _time

    import numpy as np

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("KF_CONFIG_LOG_LEVEL", "WARNING")

    from kungfu_tpu.elastic.persist import (PersistPlane,
                                            newest_complete_manifest,
                                            restore_from_manifest)
    from kungfu_tpu.elastic.reshard import ZeroBoundary

    TOTAL = 1 << 16            # 64k f32 = 256 KiB of sharded state
    LR = np.float32(0.125)

    def update_chunk(chunk, lo, t):
        # elementwise and offset-keyed: identical math under ANY
        # chunking, so a resharded restore replays bitwise
        idx = np.arange(lo, lo + chunk.shape[0], dtype=np.float32)
        target = np.float32(t) * np.float32(0.001) + idx * np.float32(1e-6)
        return chunk - LR * (chunk - target)

    def make_world(n, global_params):
        chunk = -(-TOTAL // n)
        padded = np.zeros(chunk * n, np.float32)
        padded[:TOTAL] = global_params
        bounds, chunks = [], []
        for r in range(n):
            bounds.append(ZeroBoundary())
            chunks.append(padded[r * chunk:(r + 1) * chunk].copy())
        return chunk, bounds, chunks

    def gather(chunks):
        return np.concatenate(chunks)[:TOTAL]

    # -- overhead: persist-every-step vs persistence off -----------------
    n = 4
    steps = 20 if args.quick else 40
    K_OV = 5  # overhead-arm persist cadence, in steps
    # compute sized so a step is a real training-step's worth of math
    # (~tens of ms): the <=5% gate is about the issue-path cost of the
    # async handle pattern, which only holds while the writer thread can
    # keep up — a step shorter than one shard write measures depth-2
    # backpressure, not overhead
    d = 512
    rng = np.random.default_rng(0)
    work = [rng.standard_normal((d, d)).astype(np.float32)
            for _ in range(n)]

    def run_arm(plane_root):
        planes = None
        if plane_root:
            planes = [PersistPlane(plane_root, r, period_s=0.0, depth=2,
                                   keep=2) for r in range(n)]
        chunk, bounds, chunks = make_world(n, np.zeros(TOTAL, np.float32))
        # warm the compute (BLAS thread spin-up) outside the window
        for r in range(n):
            work[r] = np.tanh(work[r] @ work[r]) * np.float32(0.99)
        t0 = _time.perf_counter()
        for t in range(steps):
            for r in range(n):
                # the "model math": a real matmul chain per rank
                for _ in range(4):
                    work[r] = np.tanh(work[r] @ work[r]) * np.float32(0.99)
                chunks[r] = update_chunk(chunks[r], r * chunk, t)
                bounds[r].commit_local(t, {"v0": chunks[r]}, TOTAL, n, r)
                if planes and t % K_OV == K_OV - 1:
                    planes[r].persist_async(t, bounds[r])
        dt = _time.perf_counter() - t0
        persisted = 0
        if planes:
            for p in planes:
                persisted += p.persist_fence()
                p.close()
        return dt / steps, persisted

    # interleaved rounds, median per arm: a 1-core host's scheduling
    # noise between two single-shot arms is larger than a 5% effect
    offs, ons = [], []
    persisted = 0
    with tempfile.TemporaryDirectory() as td:
        for i in range(3):
            dt, _ = run_arm(None)
            offs.append(dt)
            dt, pn = run_arm(os.path.join(td, f"m{i}"))
            ons.append(dt)
            persisted += pn
    step_off = float(np.median(offs))
    step_on = float(np.median(ons))
    overhead = step_on / step_off - 1.0

    # -- goodput: Poisson preemptions, alternating-world cold restarts ---
    S = 60 if args.quick else 120   # useful steps the job must complete
    K = 5                           # persist cadence (steps)
    prng = np.random.default_rng(7)
    preempt_at = []
    t = 0.0
    while t < S * 3:
        t += prng.exponential(S / 3.0)  # ~3 expected preemptions
        preempt_at.append(int(t))

    executed = 0
    preemptions = 0
    restore_worlds = []
    with tempfile.TemporaryDirectory() as td:
        root = os.path.join(td, "m")
        worlds = [4, 2]
        n_now = worlds[0]
        chunk, bounds, chunks = make_world(n_now, np.zeros(TOTAL, np.float32))
        resume = 0
        kill_iter = iter(preempt_at)
        next_kill = next(kill_iter)
        planes = [PersistPlane(root, r, period_s=0.0, depth=2, keep=2)
                  for r in range(n_now)]
        t = resume
        while t < S:
            if executed >= next_kill and t > resume:
                # whole-world preemption: abandon state, no fence (an
                # in-flight write may land torn — the manifest verifier
                # must shrug it off)
                preemptions += 1
                next_kill = next(kill_iter)
                for p in planes:
                    p.close()
                n_now = worlds[preemptions % len(worlds)]
                restore_worlds.append(n_now)
                mdir = newest_complete_manifest(root)
                chunk, bounds, chunks = make_world(
                    n_now, np.zeros(TOTAL, np.float32))
                resume = 0
                if mdir is not None:
                    merged = np.zeros(TOTAL, np.float32)
                    for r in range(n_now):
                        rs = restore_from_manifest(mdir, r, n_now)
                        nc = rs.chunk
                        lo = r * nc
                        merged[lo:min(lo + nc, TOTAL)] = (
                            rs.vec[0][:max(min(lo + nc, TOTAL) - lo, 0)])
                        resume = rs.step + 1
                    chunk, bounds, chunks = make_world(n_now, merged)
                t = resume
                planes = [PersistPlane(root, r, period_s=0.0, depth=2,
                                       keep=2) for r in range(n_now)]
                continue
            for r in range(n_now):
                chunks[r] = update_chunk(chunks[r], r * chunk, t)
                bounds[r].commit_local(t, {"v0": chunks[r]}, TOTAL,
                                       n_now, r)
            if t % K == K - 1:
                for r in range(n_now):
                    planes[r].persist_async(t, bounds[r])
            executed += 1
            t += 1
        for p in planes:
            p.persist_fence()
            p.close()
        final = gather(chunks)

    replay = np.zeros(TOTAL, np.float32)
    for t in range(S):
        replay = update_chunk(replay, 0, t)
    bitwise = bool(np.array_equal(final, replay))
    goodput = S / max(executed, 1)

    return {
        "metric": "persist_preemption_goodput_fraction",
        "value": round(goodput, 4),
        "unit": "fraction",
        "vs_baseline": round(goodput, 4),
        "vs_baseline_meaning": (
            "useful steps / executed steps under seeded Poisson whole-"
            "job preemptions with cold restarts from the newest complete "
            "manifest (1.0 = no lost work; the overhead row's gate is "
            "async issue-path overhead <= 5%)"),
        "platform": "cpu-hostplane",
        "n_devices": 4,
        "rows": {
            "overhead": {
                "step_ms_off": round(step_off * 1e3, 3),
                "step_ms_on": round(step_on * 1e3, 3),
                "overhead_frac": round(overhead, 4),
                "overhead_ok": bool(overhead <= 0.05),
                "persists": persisted,
                "cadence": f"every {K_OV} steps",
            },
            "goodput": {
                "useful_steps": S,
                "executed_steps": executed,
                "preemptions": preemptions,
                "persist_every_steps": K,
                "restore_worlds": restore_worlds,
                "goodput": round(goodput, 4),
                "bitwise_identical_final_params": bitwise,
            },
        },
    }


def payload_sentinel(args) -> dict:
    """kf-sentinel gate (ISSUE 19): online regression detection with a
    reproducible offline verdict, chip-free on the CPU mesh.

    A 3-rank in-process host-plane cluster trains the small transformer
    and allreduces a gradient-sized buffer per step, feeding per-rank
    snapshots to a live :class:`ClusterAggregator` with an attached
    :class:`Sentinel` (fake aggregator clock -> exactly one sentinel
    sample per step, deterministic cadence).  After a clean baseline
    phase, chaos ``delay`` clauses are armed MID-RUN on the 0<->1 link
    (30 ms each send direction + 60 ms on rank 1's receive leg), so
    step walls inflate and the planted straggler is rank 1.  The gate
    asserts the sentinel plane end to end: no alert fires during the
    clean phase, a ``regress:step_time_s`` changepoint alert fires
    online within K=2 detection windows of the onset, the incident
    flight record's kf-xray verdict names the planted rank/edge, and
    ``kfhist --verdict --upto <history_n>`` replayed over the durable
    history reproduces the incident's verdicts IDENTICALLY (one
    implementation, monitor/detect.py)."""
    import gc
    import os
    import shutil
    import tempfile
    import threading
    import time as _time

    import numpy as np

    os.environ["KF_NATIVE_ENGINE"] = "0"  # chaos hooks ride the py path
    os.environ["KF_CONFIG_ENABLE_TRACE"] = "1"
    os.environ.setdefault("KF_CONFIG_LOG_LEVEL", "WARNING")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp

    from kungfu_tpu import chaos
    from kungfu_tpu.models.transformer import Transformer, TransformerConfig
    from kungfu_tpu.monitor import kfhist, timeline
    from kungfu_tpu.monitor.aggregator import (REPORT_KINDS,
                                               ClusterAggregator,
                                               make_snapshot)
    from kungfu_tpu.monitor.registry import REGISTRY
    from kungfu_tpu.monitor.sentinel import Sentinel
    from kungfu_tpu.peer import Peer
    from kungfu_tpu.plan import Cluster, PeerList, parse_strategy
    from kungfu_tpu.utils.envs import Config

    window = 4
    k_windows = 2          # the detection-latency budget, in windows
    clean_steps = 12 if args.quick else 16
    chaos_steps = 8 if args.quick else 10
    wire_ms = 30
    # the planted fault, armed MID-RUN: the delay clauses stay inert
    # until note_step announces `clean_steps` (after_step gating), so
    # the baseline phase is clean and the 0<->1 link degrades from one
    # deterministic step boundary — rank 1's receive leg pays 2x wire
    # (the asymmetric straggler the incident's xray verdict must name)
    os.environ["KF_CHAOS_SPEC"] = (
        f"delay:ms={wire_ms},rank=0,peer=1,on=send,after_step={clean_steps};"
        f"delay:ms={wire_ms},rank=1,peer=0,on=send,after_step={clean_steps};"
        f"delay:ms={2 * wire_ms},rank=1,peer=0,on=recv,"
        f"after_step={clean_steps}")
    root = tempfile.mkdtemp(prefix="kf-sentinel-bench-")
    # the env knob family steers BOTH planes: Sentinel.from_env() (the
    # production attach path) and kfhist's offline replay defaults
    os.environ["KF_SENTINEL_DIR"] = root
    os.environ["KF_SENTINEL_PERIOD"] = "1"
    os.environ["KF_SENTINEL_WINDOW"] = str(window)

    B, S = 2, 32
    cfg = TransformerConfig(vocab_size=512, d_model=128, n_layers=2,
                            n_heads=4, d_ff=512, max_seq=64)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    grad_fn = jax.jit(jax.grad(lambda p, ids, tg: model.loss(p, (ids, tg))))
    warm = jnp.zeros((B, S), jnp.int32)
    jax.block_until_ready(grad_fn(params, warm, warm))

    workers = PeerList.parse(",".join(f"127.0.0.1:{24700 + i}"
                                      for i in range(3)))
    runners = PeerList.parse("127.0.0.1:24799")
    cluster = Cluster(runners, workers)
    peers = [Peer(Config(self_id=w, cluster=cluster)) for w in workers]
    for p in peers:
        p.config.strategy = parse_strategy("STAR")
        p.start()

    grad_buf = np.ones(50_000, np.float32)  # ~200 KiB, the wire payload
    rngs = [np.random.default_rng(r) for r in range(3)]

    def run_world(fns, timeout=120.0):
        outs, errs = [None] * len(fns), []

        def wrap(i, f):
            try:
                outs[i] = f()
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=wrap, args=(i, f), daemon=True)
              for i, f in enumerate(fns)]
        for t in ts:
            t.start()
        deadline = _time.monotonic() + timeout
        for t in ts:
            t.join(max(0.0, deadline - _time.monotonic()))
        if errs:
            raise errs[0]
        if any(t.is_alive() for t in ts):
            raise TimeoutError("sentinel world hung")
        return outs

    # paced steps: every step runs at least `pace_s` (an input-bound
    # training loop's fixed cadence).  The clean baseline is then flat
    # to scheduler jitter — the detector must judge the PLANTED fault,
    # not the host CPU's frequency-boost decay, which drifts raw 27 ms
    # compute walls by ~9% over the run and is a genuine (but
    # machine-local) median shift
    pace_s = 0.05

    def rank_step(p, rank):
        t0 = _time.perf_counter()
        with timeline.span("input", "batch.next", rank=rank):
            ids = rngs[rank].integers(0, cfg.vocab_size,
                                      (B, S)).astype(np.int32)
        g = grad_fn(params, jnp.asarray(ids), jnp.asarray(ids))
        jax.block_until_ready(g)
        out = p.engine().all_reduce(grad_buf, op="sum")
        assert float(out[0]) == 3.0
        pad = pace_s - (_time.perf_counter() - t0)
        if pad > 0:
            _time.sleep(pad)
        return _time.perf_counter() - t0

    clock = [1000.0]  # the aggregator's fake clock: 1 tick = 1 step
    agg = ClusterAggregator(stale_after=3600.0, time_fn=lambda: clock[0])
    sentinel = Sentinel.from_env()
    agg.attach_sentinel(sentinel)

    def ingest(rank, step, wall_s, events):
        # bounded event window per snapshot (last two steps), like the
        # production RankReporter — cumulative lists would grow the
        # per-sample xray cost quadratically over the run
        agg.ingest(make_snapshot(
            rank=rank, pid=os.getpid(), wall=clock[0], step=step,
            step_time_s=wall_s, counters={}, gauges={}, latency={},
            events=[e for e in events
                    if e["rank"] == rank and e["kind"] in REPORT_KINDS
                    and e.get("step", -1) >= step - 1],
            net={}, strategy="STAR"))

    # unsampled warm steps: the measured baseline must not include the
    # first-steps drift (cache/thermal settling would read as a shift)
    for _ in range(4):
        run_world([lambda p=p, r=r: rank_step(p, r)
                   for r, p in enumerate(peers)])
    timeline.reset()
    onset_records = None
    false_positive = False
    # GC pauses land inside the timed rank threads and read as step-time
    # jitter on the clean baseline; the detector must judge the planted
    # fault, not the host interpreter's collector
    gc.disable()
    try:
        for i in range(clean_steps + chaos_steps):
            if i == clean_steps:
                # the sentinel must be clean BEFORE the fault arms
                false_positive = bool(sentinel.alerts_view()["alerts"])
                onset_records = sentinel.alerts_view()["records"]
            for r in range(3):
                # the production step announcement: stamps the timeline
                # step AND drives each rank's after_step arming clock
                chaos.note_step(r, i)
            walls = run_world([lambda p=p, r=r: rank_step(p, r)
                               for r, p in enumerate(peers)])
            events = timeline.snapshot()
            for r in range(3):
                ingest(r, i, walls[r], events)
            # advance the fake clock past the sample period and flush:
            # the re-ingest of rank 0's (identical) snapshot triggers the
            # sentinel with all three rank rows fresh for step i
            clock[0] += 1.0
            ingest(0, i, walls[0], events)
    finally:
        gc.enable()
        for p in peers:
            p.close()
        os.environ.pop("KF_CHAOS_SPEC", None)

    av = sentinel.alerts_view()
    fired = [a for a in av["alerts"] if a["rule"] == "regress:step_time_s"]
    incident = {}
    if fired and fired[0].get("incident"):
        with open(fired[0]["incident"]) as f:
            incident = json.load(f)
    detection_latency = (incident.get("history_n", 10 ** 9)
                         - (onset_records or 0))
    # the offline replay: kfhist --verdict --upto <history_n> over the
    # durable history, window/threshold from the SAME env knobs
    offline = kfhist.verdict_from_dir(root, upto=incident.get("history_n"))
    counters = REGISTRY.snapshot()
    culprit = ((incident.get("xray") or {}).get("verdict") or {}
               ).get("culprit") or {}
    checks = {
        "no_false_positive_in_clean_phase": not false_positive,
        "changepoint_alert_fired_online": bool(fired),
        "alert_within_k_windows_of_onset":
            detection_latency <= k_windows * window,
        "incident_flight_record_written": bool(incident),
        "incident_names_planted_rank1_edge":
            culprit.get("slowest_rank") == 1,
        "offline_verdict_identical_to_incident":
            bool(incident) and json.loads(json.dumps(
                offline["verdicts"])) == incident.get("verdicts"),
        "offline_step_time_shifted_up":
            (offline["verdicts"].get("step_time_s") or {}).get("shifted")
            is True
            and offline["verdicts"]["step_time_s"]["direction"] == "up",
        "alert_counter_ticked": any(
            k.startswith("kf_alerts_total") and "regress:step_time_s" in k
            and v >= 1 for k, v in counters.items()),
        "evidence_bounded": len(incident.get("timeline_tail", [])) <= 256,
    }
    shutil.rmtree(root, ignore_errors=True)
    os.environ.pop("KF_SENTINEL_DIR", None)
    v = (incident.get("verdicts") or {}).get("step_time_s") or {}
    return {
        "metric": "sentinel_online_offline_verdict_gate",
        "value": round(float(v.get("score", 0.0)), 2),
        "unit": "mad-score",
        "vs_baseline": 1.0 if all(checks.values()) else 0.0,
        "vs_baseline_meaning": ("1.0 = every sentinel check passed "
                                "(clean baseline silent, online alert "
                                "within K windows, incident names the "
                                "planted edge, kfhist replay verdict "
                                "identical)"),
        "platform": "cpu-hostplane",
        "n_devices": 3,
        "model": (f"3 ranks, GPT d{cfg.d_model}xL{cfg.n_layers} fwd+bwd "
                  f"per step + 200 KiB allreduce; {wire_ms} ms chaos "
                  f"delay armed mid-run on the 0<->1 link after "
                  f"{clean_steps} clean steps"),
        "checks": checks,
        "rows": {
            "detection": {
                "clean_steps": clean_steps,
                "chaos_steps": chaos_steps,
                "window": window,
                "k_windows_budget": k_windows,
                "onset_records": onset_records,
                "alert_history_n": incident.get("history_n"),
                "detection_latency_samples": (
                    detection_latency if incident else None),
                "rule": fired[0]["rule"] if fired else None,
                "shift_score": round(float(v.get("score", 0.0)), 2),
                "base_median_s": v.get("base_median"),
                "recent_median_s": v.get("recent_median"),
            },
            "incident": {
                "culprit": culprit or None,
                "timeline_tail_events": len(
                    incident.get("timeline_tail", [])),
                "history_records": len(incident.get("history", [])),
                "active_alerts": (incident.get("config") or {}
                                  ).get("active_alerts"),
            },
        },
    }


def payload_pulse(args) -> dict:
    """kf-pulse gate (ISSUE 20), two rows in one payload:

    * **overhead** — the GNS/variance pulse plane threaded into
      ``zero_train_step`` (stage 2) must cost <= 2% amortized step time
      at ``KF_PULSE_EVERY=10`` on a virtual CPU mesh.  Off steps run
      the bare jit program untouched (asserted bitwise: the pulse
      arm's params equal the bare build's after identical steps from
      identical init) and sample steps add only two scalar reductions
      plus one host sync, so 1-in-10 sampling amortizes under the gate;
    * **attribution** — a 3-rank host-plane bandit drill under a
      chaos-planted 30 ms link: every consensus swap writes a durable
      decision record, the ledger joins it to the measured step-time
      effect, and a verdict must name the swap onto the final arm as
      ``improved`` — with :func:`~kungfu_tpu.monitor.ledger.
      replay_effects` recomputing every judged verdict offline from the
      durable streams byte-identically.

    Part A runs on the virtual CPU mesh (fresh child process, so
    the backend is still cold); part B is pure host-plane CPU — both
    chip-free."""
    import gc
    import json as _json
    import os
    import shutil
    import tempfile
    import time as _time

    import jax

    n_mesh = args.cpu_mesh or 4
    jax.config.update("jax_num_cpu_devices", int(n_mesh))
    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np
    import optax

    from kungfu_tpu.comm.device import Communicator
    from kungfu_tpu.monitor.registry import REGISTRY
    from kungfu_tpu.parallel.zero import zero_train_step

    devs = jax.devices()
    n = len(devs)
    comm = Communicator(devices=devs, local_size=n)

    # a pulse sample's extra cost is two scalar collectives + one
    # square-sum + one host sync — FIXED per sample, while the step's
    # own work scales with the batch.  On this virtual CPU mesh a
    # scalar collective costs ~0.5 ms of dispatch overhead (it is ~us
    # on real ICI), so the step must carry a realistic amount of
    # compute or the gate measures mesh artifacts, not the plane's tax:
    # at 8 rows/rank the "step" is mostly collective dispatch
    d = 256
    b_rank = 24 if args.quick else 32
    rng = np.random.default_rng(0)
    params = {
        f"w{i}": jnp.asarray(
            rng.standard_normal((d, d)) / np.sqrt(d), jnp.float32)
        for i in range(3)
    }
    batch = (jnp.asarray(
                 rng.standard_normal((b_rank * n, d)), jnp.float32),
             jnp.asarray(
                 rng.standard_normal((b_rank * n, d)), jnp.float32))

    def loss_fn(p, b):
        x, y = b
        h = x
        for i in range(3):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    every = 10
    arms = {}
    for name, every_env in (("bare", "0"), ("pulse", str(every))):
        os.environ["KF_PULSE_EVERY"] = every_env
        z = zero_train_step(loss_fn, optax.adam(1e-3), comm, stage=2)
        arms[name] = [z, z.init_params(params), z.init_opt(params)]
    (z_off, p_off, o_off), (z_on, p_on, o_on) = arms["bare"], arms["pulse"]
    assert z_off.pulse is None and z_on.pulse is not None

    # warm both arms THROUGH a pulse sample: compiles the bare program
    # (call 1) and the instrumented program (call `every`), and pins the
    # off-step bitwise contract along the way
    for _ in range(every + 2):
        p_off, o_off, _ = z_off.step(p_off, o_off, batch)
        p_on, o_on, _ = z_on.step(p_on, o_on, batch)
    jax.block_until_ready((p_off, p_on))
    params_match = all(
        np.array_equal(np.asarray(p_off[k]), np.asarray(p_on[k]))
        for k in p_off)
    assert z_on.pulse.samples >= 1, "pulse arm never sampled during warmup"
    gns_val = REGISTRY.snapshot().get("kf_gns")
    gns_ok = gns_val is not None and np.isfinite(float(gns_val))

    # amortized A/B: K calls per round (a multiple of `every`, so every
    # round pays the same pulse-sample count regardless of phase),
    # interleaved rounds, running min per arm — min-of-aggregates is
    # robust to scheduler bursts where a mean is not
    K = 30 if args.quick else 60
    rounds = 3 if args.quick else 5

    def time_round(z, p, o):
        t0 = _time.perf_counter()
        loss = None
        for _ in range(K):
            p, o, loss = z.step(p, o, batch)
        jax.block_until_ready(loss)
        return (_time.perf_counter() - t0) / K, p, o

    t_off = t_on = float("inf")
    gc.disable()
    try:
        for _ in range(rounds):
            dt, p_off, o_off = time_round(z_off, p_off, o_off)
            t_off = min(t_off, dt)
            dt, p_on, o_on = time_round(z_on, p_on, o_on)
            t_on = min(t_on, dt)
    finally:
        gc.enable()
    overhead = t_on / max(t_off, 1e-12)

    # ---- part B: decision ledger attribution drill -----------------------
    os.environ["KF_NATIVE_ENGINE"] = "0"  # chaos hooks ride the py path
    os.environ["KF_CONFIG_ENABLE_TRACE"] = "1"  # swap events must record
    os.environ.setdefault("KF_CONFIG_LOG_LEVEL", "WARNING")
    wire_ms = 30
    os.environ["KF_CHAOS_SPEC"] = ";".join(
        f"delay:ms={wire_ms},rank={a},peer={b},on={on}"
        for a, b in ((0, 1), (1, 0)) for on in ("send", "ping"))

    root = tempfile.mkdtemp(prefix="kf-pulse-ledger-")
    os.environ["KF_SENTINEL_DIR"] = root
    # window=2 (the floor): the bandit explores early and often, and a
    # swap must be judged from samples that fit between consecutive
    # votes — the 30 ms planted delay dwarfs a 2-sample MAD anyway
    os.environ["KF_SENTINEL_WINDOW"] = "2"

    from kungfu_tpu.monitor import history, ledger, timeline
    from kungfu_tpu.monitor.adapt_device import HostBanditDriver
    from kungfu_tpu.peer import Peer
    from kungfu_tpu.plan import Cluster, PeerList, parse_strategy
    from kungfu_tpu.utils.envs import Config

    ledger.reset()
    timeline.reset()
    led = ledger.ledger_for(root)  # window from env: 2
    cluster_ring = history.HistoryRing(root, "cluster")

    elems = 25_000 if args.quick else 50_000
    steps = 24 if args.quick else 36
    data = np.ones(elems, np.float32)

    workers = PeerList.parse(
        ",".join(f"127.0.0.1:{24650 + i}" for i in range(3)))
    runners = PeerList.parse("127.0.0.1:24749")
    ps = [Peer(Config(self_id=w, cluster=Cluster(runners, workers)))
          for w in workers]
    for peer in ps:
        peer.config.strategy = parse_strategy("STAR")
        peer.start()
    # the payload_adapt-proven config: votes every 2 steps give the
    # bandit enough pulls to land on the measured-latency MST within
    # the drill's step budget
    drivers = [HostBanditDriver(peer, check_every=2, min_pulls=1,
                                min_swap_collectives=1) for peer in ps]

    def run_world(fns, timeout=120.0):
        import threading

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
        deadline = _time.monotonic() + timeout
        for t in ts:
            t.join(max(0.0, deadline - _time.monotonic()))
        if errs:
            raise errs[0]
        if any(t.is_alive() for t in ts):
            raise TimeoutError("pulse ledger world hung")
        return outs

    def measure_step(peer, driver):
        t0 = _time.perf_counter()
        out = peer.engine().all_reduce(data, op="sum")
        dt = _time.perf_counter() - t0
        assert float(out[0]) == 3.0, out[:4]
        driver.step(dt)
        return dt

    times = []
    try:
        for _ in range(steps):
            dts = run_world([lambda p=p, drv=drv: measure_step(p, drv)
                             for p, drv in zip(ps, drivers)])
            dt = max(dts)
            times.append(dt)
            # the sentinel's role, inlined: ONE record per step lands in
            # the durable cluster stream AND feeds the online join, so
            # the offline replay sees exactly the samples the ledger saw
            rec = {"series": {"step_time_s": dt}}
            cluster_ring.append(rec)
            led.on_sample(rec)
        active = {drv.active for drv in drivers}
        assert len(active) == 1, f"ranks diverged on the arm: {active}"
        arm = next(iter(active))
    finally:
        for peer in ps:
            peer.close()

    view = led.view()
    improved = [row for row in view["decisions"]
                if ledger.lfield(row["effect"], "verdict") == "improved"]
    named = any(
        ledger.lfield(row["decision"], "actor") == "bandit-host"
        and ledger.lfield(row["decision"], "knob") == "strategy"
        and ledger.lfield(row["decision"], "new") == arm
        for row in improved)

    rep = ledger.replay_effects(root)
    judged = [r for r in rep["decisions"] if r["online"] is not None]
    replay_ok = bool(judged) and all(
        _json.dumps(r["online"], sort_keys=True)
        == _json.dumps(r["replayed"], sort_keys=True)
        for r in judged)
    decision_events = [e for e in timeline.snapshot()
                       if e["kind"] == "decision"]
    shutil.rmtree(root, ignore_errors=True)

    checks = {
        "pulse_overhead_within_2pct": bool(overhead <= 1.02),
        "pulse_off_steps_bitwise_identical": bool(params_match),
        "kf_gns_gauge_published": bool(gns_ok),
        "ledger_effect_names_winning_swap": bool(named),
        "ledger_replay_byte_identical": bool(replay_ok),
        "decision_timeline_counted": bool(decision_events),
    }
    return {
        "metric": "pulse_gns_overhead_and_ledger_attribution_gate",
        "value": round(overhead, 4),
        "unit": "x",
        "vs_baseline": 1.0 if all(checks.values()) else 0.0,
        "vs_baseline_meaning": ("1.0 = GNS pulse amortized step-time "
                                "overhead <= 2% AND the decision ledger "
                                "attributed the chaos fix to the winning "
                                "swap with byte-identical offline replay"),
        "platform": "cpu-hostplane",
        "n_devices": n,
        "model": (f"part A: mlp3x{d} zero2, {b_rank} rows/rank on a "
                  f"{n}-device virtual CPU "
                  f"mesh, KF_PULSE_EVERY={every}; part B: 3 ranks, "
                  f"{elems * 4 >> 10} KiB fp32 allreduce/step, "
                  f"{wire_ms} ms chaos delay on the 0<->1 link"),
        "rows": {
            "overhead": {
                "bare_step_ms": round(t_off * 1e3, 3),
                "pulse_step_ms": round(t_on * 1e3, 3),
                "amortized_ratio": round(overhead, 4),
                "gns": None if gns_val is None else round(float(gns_val), 4),
                "pulse_samples": int(z_on.pulse.samples),
            },
            "attribution": {
                "final_arm": arm,
                "decisions": view["summary"]["total"],
                "judged": view["summary"]["judged"],
                "by_verdict": view["summary"]["by_verdict"],
                "replayed_rows": len(judged),
                "steady_step_ms": round(
                    float(np.median(times[-6:])) * 1e3, 2),
            },
            "checks": checks,
        },
    }


PAYLOADS = {
    "resnet": payload_resnet,
    "kernels": payload_kernels,
    "allreduce": payload_allreduce,
    "lm": payload_lm,
    "zero": payload_zero,
    "multislice": payload_multislice,
    "adapt": payload_adapt,
    "overlap": payload_overlap,
    "pallas": payload_pallas,
    "serve": payload_serve,
    "xray": payload_xray,
    "pp": payload_pp,
    "persist": payload_persist,
    "sentinel": payload_sentinel,
    "pulse": payload_pulse,
}


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--image-size", type=int, default=None)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--mbytes", type=int, default=64)
    p.add_argument("--quick", action="store_true")
    p.add_argument("--cpu-mesh", dest="cpu_mesh", type=int, default=0,
                   help="allreduce mode: force an N-device virtual CPU "
                        "mesh so the multi-device psum path runs off-TPU")
    p.add_argument("--cpu", action="store_true",
                   help="force the CPU backend (local smoke runs; without "
                        "it a device payload needs a TPU and fails when "
                        "there is none)")
    p.add_argument("--kernels", action="store_true", help="pallas-vs-XLA micro-bench")
    p.add_argument("--allreduce", action="store_true", help="allreduce GiB/s")
    p.add_argument("--lm", action="store_true",
                   help="GPT-small training with the kernels in anger")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO stage rows + bare shard_map/psum baseline")
    p.add_argument("--multislice", action="store_true",
                   help="emulated 2-slice hierarchical vs flat all-reduce "
                        "with injected DCN wire latency (host-plane CPU; "
                        "chip-free)")
    p.add_argument("--adapt", action="store_true",
                   help="kf-adapt A/B: bandit strategy adaptation vs every "
                        "fixed strategy under chaos-injected link "
                        "interference (host-plane CPU; chip-free)")
    p.add_argument("--overlap", action="store_true",
                   help="kf-overlap A/B: serial vs depth-k pipelined "
                        "ZeRO-2/3 bucket loops under injected wire "
                        "latency, plus the bare shard_map+psum row "
                        "(host-plane CPU; chip-free)")
    p.add_argument("--serve", action="store_true",
                   help="kf-serve SLO row: p50/p99 e2e at fixed offered "
                        "load before/during/after a chaos worker kill "
                        "AND a slice kill, with replay-from-committed "
                        "recovery (host-plane CPU; chip-free)")
    p.add_argument("--xray", action="store_true",
                   help="kf-xray attribution + mfu_decomp row on the "
                        "3-rank chaos CPU mesh (chip-free)")
    p.add_argument("--pp", dest="pp", action="store_true",
                   help="kf-pipeline A/B: 1F1B vs naive sequential "
                        "microbatching over a 2-stage emulated 2-slice "
                        "pipeline under 30 ms injected DCN latency, "
                        "bubble fraction from the xray decomposition "
                        "(host-plane CPU; chip-free)")
    p.add_argument("--persist", action="store_true",
                   help="kf-persist: async checkpoint issue-path "
                        "overhead (<= 5% gate, persist-every-step) and "
                        "Poisson-preemption goodput with alternating-"
                        "world cold restarts from the durable manifest "
                        "plane, final params bitwise vs fixed-world "
                        "replay (host-plane CPU; chip-free)")
    p.add_argument("--sentinel", action="store_true",
                   help="kf-sentinel: online step-time changepoint alert "
                        "under a mid-run chaos delay, incident flight "
                        "record naming the planted edge, and the kfhist "
                        "offline replay reproducing the identical "
                        "verdict (host-plane CPU; chip-free)")
    p.add_argument("--pulse", action="store_true",
                   help="kf-pulse: GNS/variance pulse overhead gate "
                        "(<= 2% amortized at KF_PULSE_EVERY=10, off "
                        "steps bitwise-identical) plus the 3-rank "
                        "bandit-swap drill where the decision ledger's "
                        "effect verdict names the swap that fixed a "
                        "chaos-planted 30 ms link, replayed offline "
                        "byte-identically (host-plane CPU; "
                        "chip-free)")
    p.add_argument("--pallas", action="store_true",
                   help="Pallas ICI ring collectives: interpret-kernel "
                        "bitwise A/B vs the lax references + traced-"
                        "bytes parity (chip-free on a virtual CPU "
                        "mesh), compiled-kernel device rows on TPU")
    p.add_argument("--payload", choices=sorted(PAYLOADS), default=None,
                   help=argparse.SUPPRESS)  # internal: the child's entry
    p.add_argument("--timeout", type=float, default=PAYLOAD_TIMEOUT_S)
    args = p.parse_args()

    if args.payload:
        # the child: the only process of this script that touches JAX
        if (args.payload in CHIP_PAYLOADS
                and not (args.cpu or args.cpu_mesh)):
            require_chip()
        print(json.dumps(PAYLOADS[args.payload](args)))
        return

    which = ("kernels" if args.kernels else "allreduce" if args.allreduce
             else "lm" if args.lm else "zero" if args.zero
             else "multislice" if args.multislice
             else "adapt" if args.adapt
             else "overlap" if args.overlap
             else "serve" if args.serve
             else "xray" if args.xray
             else "pp" if args.pp
             else "persist" if args.persist
             else "sentinel" if args.sentinel
             else "pulse" if args.pulse
             else "pallas" if args.pallas else "resnet")
    fwd = ["--payload", which]
    for flag, val in [
        ("--batch-size", args.batch_size), ("--image-size", args.image_size),
        ("--steps", args.steps), ("--warmup", args.warmup),
        ("--seq-len", args.seq_len), ("--mbytes", args.mbytes),
    ]:
        if val is not None:
            fwd += [flag, str(val)]
    if args.cpu_mesh:
        fwd += ["--cpu-mesh", str(args.cpu_mesh)]
    if args.quick:
        fwd.append("--quick")
    if args.cpu:
        fwd.append("--cpu")
    print(json.dumps(run_payload(fwd, timeout=args.timeout)))


if __name__ == "__main__":
    main()
