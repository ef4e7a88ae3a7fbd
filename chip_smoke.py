#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the main paths still start on
the chip.

With no arguments it needs one TPU chip and drives, at the full width of
GPT-small (``models/transformer.py::gpt_small``: 12 layers, d_model 768,
12 heads, d_ff 3072, vocab 32128, bf16, sequence 2048), through the entry
points a user calls:

* **train** — a worker started by the launcher with the chip backend
  (``python -m kungfu_tpu -np 1 -backend tpu``) calls ``kf.init()``, takes
  its ``Communicator`` from the peer, builds ``synchronous_sgd`` over
  ``comm.axis`` and ``dp_train_step`` on the model's own ``loss``, and
  takes 12 steps at batch 4 — enough for the default ``KF_PULSE_EVERY``
  of 10 to compile and run the second whole-step program.  The loss is
  finite on every step and lower at the end than at the start.
* **serve** — one ``InferenceEngine`` over the same model, warmed for its
  prefill buckets, answers requests of different prompt lengths submitted
  together; each returns its full token budget, and the first tokens of
  the longest one agree with the greedy argmax of a plain ``model.apply``
  forward on the same tokens.

``--chips 4`` runs instead, in one process over four devices, S-SGD and
ZeRO-2 training against the same steps on a one-device ``Communicator``.

A chip belongs to one process at a time: this parent never imports JAX,
each leg is a child that exits before the next starts, and the device in
the last line is what a child saw.  A leg that fails makes the script exit
non-zero without a result line.  ``--cpu`` rehearses the same code on the
CPU backend (tiny sizes are the caller's to pass) and reports the CPU as
what it is.  Sizes are arguments and never change which code runs.

The last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: near-tie allowance when the engine's greedy token is compared with the
#: reference's: bf16 keeps 8 bits of mantissa, and two correct bf16
#: implementations of the same forward may rank two logits that are this
#: close (4 ulps of the row's largest logit) either way round
TIE_ULPS = 4 * 2.0 ** -8
#: allowed |loss(4 devices) - loss(1 device)| relative to the loss: the
#: two runs split the same batch differently, so bf16 activations and the
#: reduction order differ while the arithmetic is the same
LOSS_RTOL = 5e-3


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the four-device training phase and the "
                        "one-device run it is compared with")
    p.add_argument("--cpu", action="store_true",
                   help="rehearse on the CPU backend (virtual devices for "
                        "--chips 4); the last line then reports the CPU")
    p.add_argument("--seed", type=int, default=0)
    # sizes: the full-width values are the defaults
    p.add_argument("--layers", type=int, default=12)
    p.add_argument("--d-model", type=int, default=768)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--d-ff", type=int, default=3072)
    p.add_argument("--vocab", type=int, default=32128)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--steps", type=int, default=12)
    p.add_argument("--timed-steps", type=int, default=10,
                   help="steps timed by host clock after the training steps")
    p.add_argument("--mesh-steps", type=int, default=4,
                   help="steps of each run of the --chips 4 phase")
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--prompt-lens", default="24,100,700,1500")
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--check-tokens", type=int, default=4)
    p.add_argument("--leg-timeout", type=int, default=None,
                   help="seconds one leg may take (default 540; 1100 for "
                        "--chips 4)")
    # internal: run one leg in this process
    p.add_argument("--leg", choices=("train", "serve", "mesh"),
                   default=None, help=argparse.SUPPRESS)
    p.add_argument("--out", default=None, help=argparse.SUPPRESS)
    a = p.parse_args(argv)
    if a.leg_timeout is None:
        a.leg_timeout = 1100 if a.chips == 4 else 540
    return a


# ---------------------------------------------------------------------------
# parent: no JAX here
# ---------------------------------------------------------------------------

def leg_argv(a, leg: str, out: str):
    argv = [sys.executable, os.path.abspath(__file__), "--leg", leg,
            "--out", out]
    if a.cpu:
        argv.append("--cpu")
    for name, value in vars(a).items():  # every size, as this run has it
        if name not in ("leg", "out", "cpu"):
            argv += ["--" + name.replace("_", "-"), str(value)]
    return argv


def run_leg(a, leg: str, workdir: str) -> dict:
    """Run one leg to its end in a child and return the facts it wrote.
    Exits the script, non-zero, if the leg did not succeed."""
    out = os.path.join(workdir, f"{leg}.json")
    env = dict(os.environ)
    backend = "cpu" if a.cpu else "tpu"
    argv = leg_argv(a, leg, out)
    if leg == "train":
        # the normal entry point: the launcher starts the worker, pins its
        # platform, and kills it at the timeout
        argv = [sys.executable, "-m", "kungfu_tpu", "-np", "1",
                "-backend", backend, "-timeout", str(a.leg_timeout + 15),
                ] + argv
    else:
        env["JAX_PLATFORMS"] = backend
    if a.cpu:
        # the rehearsal's stand-in for the chips: as many virtual devices
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={a.chips}")
    t0 = time.monotonic()
    # the children's own output goes to stderr: stdout carries only this
    # script's fact lines and the result line
    r = subprocess.run(argv, cwd=HERE, env=env, stdout=sys.stderr,
                       timeout=a.leg_timeout + 45)
    if r.returncode != 0:
        sys.exit(f"chip_smoke: {leg} leg failed (exit code {r.returncode})")
    with open(out) as f:
        facts = json.load(f)
    facts["leg_seconds"] = round(time.monotonic() - t0, 1)
    print(f"{leg}: {json.dumps(facts)}", flush=True)
    return facts


def main(argv=None) -> int:
    a = parse_args(argv)
    if a.leg:
        signal.alarm(a.leg_timeout)  # no leg outlives its limit
        facts = {"train": leg_train, "serve": leg_serve,
                 "mesh": leg_mesh}[a.leg](a)
        with open(a.out, "w") as f:
            json.dump(facts, f)
        return 0
    legs = ("mesh",) if a.chips == 4 else ("train", "serve")
    with tempfile.TemporaryDirectory(prefix="chip_smoke.") as workdir:
        devices = [run_leg(a, leg, workdir)["device"] for leg in legs]
    if any(d != devices[0] for d in devices):
        sys.exit(f"chip_smoke: the legs saw different devices: {devices}")
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# legs: each runs in its own process, the only one that holds the chip
# ---------------------------------------------------------------------------

def build_model(a):
    """GPT-small as the repo defines it, with every size an argument."""
    from dataclasses import replace

    from kungfu_tpu.models.transformer import Transformer, gpt_small

    cfg = replace(gpt_small(vocab=a.vocab, max_seq=a.seq).cfg,
                  n_layers=a.layers, d_model=a.d_model, n_heads=a.heads,
                  d_ff=a.d_ff)
    return Transformer(cfg)


def start_jax(a):
    """A direct child's first touch of JAX (the train worker's is
    ``kf.init()``): pin the platform, place the compile cache on the chip
    path, and refuse a device that is not what was asked for."""
    import jax

    platform = "cpu" if a.cpu else "tpu"
    jax.config.update("jax_platforms", platform)
    if not a.cpu:
        from kungfu_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
    return check_device(a)


def check_device(a) -> dict:
    """The device facts of the result line, as JAX reports them — after
    refusing anything but the platform and the count that were asked for."""
    import jax

    devs = jax.devices()
    want = "cpu" if a.cpu else "tpu"
    if devs[0].platform != want or len(devs) != a.chips:
        raise RuntimeError(
            f"wanted {a.chips} {want} device(s), JAX has {devs}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def token_batch(a, batch: int):
    """(ids, targets) [batch, seq] from the seed: one fixed batch of
    uniform random tokens, which a few steps can only memorize — all the
    loss check needs."""
    import numpy as np

    toks = np.random.default_rng(a.seed).integers(
        0, a.vocab, (batch, a.seq + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def lowered_facts(lowered) -> dict:
    """What the lowered step holds: compiled kernels by name, and the
    collectives written into the program."""
    import re
    from collections import Counter

    txt = lowered.as_text()
    return {
        "tpu_custom_calls": txt.count("tpu_custom_call"),
        "kernels": dict(Counter(
            re.findall(r'kernel_name\s*=\s*"(\w+)"', txt))),
        "collectives": dict(Counter(re.findall(
            r"stablehlo\.(all_reduce|reduce_scatter|all_gather)\b", txt))),
    }


def memory_facts(dev) -> dict:
    stats = dev.memory_stats() or {}  # the CPU backend reports none
    return {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_in_use")}


def finite(x: float, what: str) -> float:
    import math

    if not math.isfinite(x):
        raise RuntimeError(f"{what} is not finite: {x}")
    return x


def leg_train(a) -> dict:
    import jax
    import numpy as np
    import optax

    import kungfu_tpu as kf
    from kungfu_tpu.optimizers import synchronous_sgd
    from kungfu_tpu.parallel.train import dp_train_step
    from kungfu_tpu.utils.compile_cache import CacheCounter

    peer = kf.init()  # platform pin, compile cache, host channel
    device = check_device(a)
    cache = CacheCounter()
    comm = peer.communicator()
    model = build_model(a)
    tx = synchronous_sgd(optax.adamw(3e-4), comm.axis)
    # the state is placed as the step returns it (replicated over the
    # mesh): left where jit put it, the second call would see new input
    # shardings and compile the whole step again
    rep = comm.replicated_sharding()
    params = jax.device_put(
        jax.jit(model.init)(jax.random.PRNGKey(a.seed)), rep)
    opt_state = jax.device_put(tx.init(params), rep)
    step = dp_train_step(model.loss, tx, comm)
    batch = tuple(map(jax.numpy.asarray, token_batch(a, a.batch)))

    losses, step_s = [], []
    for i in range(a.steps):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(finite(float(loss), f"loss at step {i}"))
        step_s.append(round(time.perf_counter() - t0, 3))
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall: {losses}")
    if step.pulse.samples < 1:
        raise RuntimeError(
            f"the pulse step never ran in {a.steps} steps "
            f"(KF_PULSE_EVERY={step.pulse.every})")

    # what the default routers put into the step (printed, not assumed)
    low = lowered_facts(step.base.lower(params, opt_state, batch))
    if device["platform"] == "tpu" and not low["kernels"].get("flash_fwd"):
        raise RuntimeError(f"no compiled flash kernel in the step: {low}")
    head = ("fused LM head (Pallas)" if "lm_head_fwd" in low["kernels"]
            else "Pallas xent" if "xent_fwd" in low["kernels"]
            else "XLA xent")

    # the same steps timed by host clock around block_until_ready: each
    # step waited for, then all dispatched before one wait.  Both time
    # the jitted step behind the pulse wrapper (pulse off): the wrapper
    # syncs scalars to the host.
    base = step.base
    host = []
    p, o = params, opt_state
    for _ in range(a.timed_steps):
        t0 = time.perf_counter()
        p, o, loss = base(p, o, batch)
        jax.block_until_ready((p, o, loss))
        host.append(time.perf_counter() - t0)
    # (few steps: nothing is donated, so every step in flight holds a
    # whole train state of its own on the device)
    n_async = min(4, a.timed_steps)
    t0 = time.perf_counter()
    for _ in range(n_async):
        p, o, loss = base(p, o, batch)
    jax.block_until_ready((p, o, loss))
    one_wait = (time.perf_counter() - t0) / n_async
    del p, o

    facts = {
        "device": device,
        "entry": "kfrun -> kf.init() -> Communicator -> synchronous_sgd "
                 "-> dp_train_step(Transformer.loss)",
        "host_transport": ("native" if "Native" in type(peer.channel).__name__
                           else "python"),
        "params": int(sum(np.prod(l.shape)
                          for l in jax.tree_util.tree_leaves(params))),
        "batch_x_seq": [a.batch, a.seq],
        "steps": a.steps,
        "loss_first": losses[0], "loss_last": losses[-1],
        "pulse": {"every": step.pulse.every, "samples": step.pulse.samples,
                  "gns": step.pulse.gns},
        "step_seconds": step_s,  # 1st and pulse steps include compiles
        "lowered_step": low,
        "loss_head": head,
        "timing_of_jitted_step_pulse_off": {
            "host_clock_block_until_ready_ms": {
                "median": round(float(np.median(host)) * 1e3, 3),
                "min": round(min(host) * 1e3, 3), "n": len(host)},
            f"host_clock_one_wait_after_{n_async}_steps_ms":
                round(one_wait * 1e3, 3),
        },
        "compile_seconds": round(cache.compile_seconds, 1),
        "compile_cache": cache.facts(),
        "memory": memory_facts(jax.devices()[0]),
    }
    kf.finalize()
    return facts


def leg_serve(a) -> dict:
    device = start_jax(a)

    import jax
    import numpy as np

    from kungfu_tpu.serve.engine import InferenceEngine
    from kungfu_tpu.utils.compile_cache import CacheCounter

    cache = CacheCounter()
    model = build_model(a)
    params = jax.jit(model.init)(jax.random.PRNGKey(a.seed))
    eng = InferenceEngine(model, params, max_batch=a.max_batch,
                          max_seq=a.seq)
    lens = [int(x) for x in a.prompt_lens.split(",")]
    t0 = time.perf_counter()
    eng.warmup(lens)
    warm_s = time.perf_counter() - t0

    rng = np.random.default_rng(a.seed)
    prompts = {f"r{i}": rng.integers(0, a.vocab, n).tolist()
               for i, n in enumerate(lens)}
    t0 = time.perf_counter()
    for rid, prompt in prompts.items():  # submitted together
        eng.submit(rid, prompt, a.max_new)
    done = {e["rid"]: e["tokens"] for e in eng.drain()
            if e["kind"] == "done"}
    serve_s = time.perf_counter() - t0
    for rid in prompts:
        if len(done.get(rid, ())) != a.max_new:
            raise RuntimeError(
                f"request {rid} returned {len(done.get(rid, ()))} of "
                f"{a.max_new} tokens")

    # reference: one plain forward over prompt + answer of the longest
    # request; the engine's first tokens must be its greedy argmax
    rid = max(prompts, key=lambda r: len(prompts[r]))
    prompt, answer = prompts[rid], done[rid]
    seq = np.asarray([prompt + answer[:-1]], np.int32)
    logits = np.asarray(model.apply(params, seq))[0]
    if not np.isfinite(logits).all():
        raise RuntimeError("reference logits are not finite")
    exact, margins = 0, []
    for i in range(min(a.check_tokens, a.max_new)):
        row = logits[len(prompt) - 1 + i]
        margin = float(row.max() - row[answer[i]])
        margins.append(round(margin, 5))
        exact += int(margin == 0.0)
        if margin > TIE_ULPS * float(np.abs(row).max()):
            raise RuntimeError(
                f"token {i} of {rid}: the engine chose {answer[i]}, "
                f"{margin:.4f} below the reference argmax "
                f"{int(row.argmax())} (margins so far {margins})")
    return {
        "device": device,
        "entry": "InferenceEngine.warmup/submit/drain",
        "max_batch_x_max_seq": [a.max_batch, a.seq],
        "prompt_lens": lens,
        "tokens_returned": {r: len(t) for r, t in done.items()},
        "checked_request": rid,
        "checked_tokens_equal_reference_argmax": exact,
        "checked_tokens": len(margins),
        "reference_margins": margins,
        "warmup_seconds": round(warm_s, 1),
        "serve_seconds": round(serve_s, 2),
        "compile_seconds": round(cache.compile_seconds, 1),
        "compile_cache": cache.facts(),
        "memory": memory_facts(jax.devices()[0]),
    }


def leg_mesh(a) -> dict:
    device = start_jax(a)

    import jax
    import optax

    from kungfu_tpu.comm.device import Communicator
    from kungfu_tpu.optimizers import synchronous_sgd
    from kungfu_tpu.parallel.train import dp_train_step
    from kungfu_tpu.utils.compile_cache import CacheCounter

    cache = CacheCounter()
    devs = jax.devices()
    model = build_model(a)
    params0 = jax.device_get(jax.jit(model.init)(jax.random.PRNGKey(a.seed)))
    batch = token_batch(a, a.batch)

    def run(name, comm, zero_stage):
        inner = optax.adamw(3e-4)
        # placed as the step returns it, so that no call after the first
        # compiles (see leg_train)
        rep = comm.replicated_sharding()
        params = jax.device_put(params0, rep)
        if zero_stage:
            # the ZeRO step owns the gradient collective: tx is the inner
            # transform
            step = dp_train_step(model.loss, inner, comm,
                                 zero_stage=zero_stage)
            opt_state = step.init_opt(params)
            jitted = step.jitted(params)
        else:
            tx = synchronous_sgd(inner, comm.axis)
            step = dp_train_step(model.loss, tx, comm)
            opt_state = jax.device_put(tx.init(params), rep)
            jitted = step.base
        losses = []
        step_s = []
        for i in range(a.mesh_steps):
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(finite(float(loss), f"{name} loss at step {i}"))
            step_s.append(round(time.perf_counter() - t0, 3))
        n = comm.size
        spans = {len(leaf.sharding.device_set) for leaf in
                 jax.tree_util.tree_leaves((params, opt_state))}
        if spans != {n}:
            raise RuntimeError(
                f"{name}: state leaves span {spans} devices, not {n}")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in comm.mesh.devices.flat]
        if device["platform"] == "tpu" and not all(in_use):
            raise RuntimeError(f"{name}: bytes in use per device {in_use}")
        low = lowered_facts(jitted.lower(params, opt_state, batch))
        return {"devices": n, "losses": losses, "step_seconds": step_s,
                "pulse": {"samples": step.pulse.samples,
                          "gns": step.pulse.gns},
                "state_spans_devices": n,
                "bytes_in_use_per_device": in_use, "lowered_step": low}

    ref = run("reference", Communicator(devices=devs[:1], local_size=1), None)
    comm = Communicator(devices=devs, local_size=len(devs))
    ssgd = run("s-sgd", comm, None)
    zero2 = run("zero-2", comm, 2)
    if not ssgd["lowered_step"]["collectives"].get("all_reduce"):
        raise RuntimeError(f"no all-reduce in the S-SGD step: {ssgd}")
    if not zero2["lowered_step"]["collectives"].get("reduce_scatter"):
        raise RuntimeError(f"no reduce-scatter in the ZeRO-2 step: {zero2}")
    worst = {}
    for name, got in (("s-sgd", ssgd), ("zero-2", zero2)):
        devs_rel = [abs(x - y) / abs(y)
                    for x, y in zip(got["losses"], ref["losses"])]
        worst[name] = round(max(devs_rel), 6)
        if worst[name] > LOSS_RTOL:
            raise RuntimeError(
                f"{name} losses {got['losses']} leave the one-device "
                f"reference {ref['losses']} by {worst[name]} "
                f"(allowed {LOSS_RTOL})")
    return {
        "device": device,
        "global_batch_x_seq": [a.batch, a.seq],
        "reference_1_device": ref, "s_sgd": ssgd, "zero_2": zero2,
        "worst_relative_loss_gap": worst, "allowed": LOSS_RTOL,
        "compile_seconds": round(cache.compile_seconds, 1),
        "compile_cache": cache.facts(),
    }


if __name__ == "__main__":
    sys.exit(main())
