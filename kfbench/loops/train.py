"""The training loop a user runs, timed in blocks.

    launcher -> kf.init() -> Communicator -> dp_train_step(
        Transformer.loss, synchronous_sgd(adamw)) ; monitoring at its default

One object -- the compiled step with its state -- is built in set-up,
driven from the seed through its first steps (which the comparison
reads: the last of them is a pulse step, so both whole-step programs are
compared), warmed through one more block, and handed to the window.
The window is a run of blocks of ``block_steps`` steps: a fresh packed
batch every step, no wait inside a block, the loss read once at its end
(which fences).  Every block's time goes to the run's file, with what
the host was doing beside it (``lib/hostwatch.py``); the metrics are
made from those by their readers.
"""

from __future__ import annotations

import time

from kfbench.lib import files, harness, hostwatch, traffic as gen

#: a leaf whose reference gradient norm is under this share of the median
#: leaf's has no gradient to speak of
DEAD_LEAF = 1e-3


def run(spec: dict) -> dict:
    parts = harness.Parts()
    cfg, tr = spec["config"], spec["traffic"]

    with parts("imports"):
        import jax
        import numpy as np
        import optax

        import kungfu_tpu as kf
        from kungfu_tpu.optimizers import synchronous_sgd
        from kungfu_tpu.parallel.train import dp_train_step
        from kungfu_tpu.utils.compile_cache import CacheCounter
    with parts("kf_init"):
        peer = kf.init()  # platform pin, compile cache, host channel
        device = harness.check_device(spec)
        cache = CacheCounter()
        comm = peer.communicator()
    adapter = files.load_adapter(cfg["family"])
    model = adapter.program_model(cfg)
    opt = tr["optimizer"]
    tx = synchronous_sgd(optax.adamw(
        opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"]), comm.axis)
    rows = tr["global_batch"]  # the traffic states it; each chip a share
    if rows % comm.size:
        raise SystemExit(f"kfbench: a batch of {rows} rows does not divide "
                         f"over {comm.size} chips")
    seed = spec["seed"]
    feed = make_feed(spec, rows)

    with parts("init_state"):
        # made on the device from the seed, placed as the step returns
        # it: left where jit puts it the second call would compile again
        rep = comm.replicated_sharding()
        make = jax.jit(lambda k: adapter.init_params(cfg, k),
                       out_shardings=rep)
        params = make(jax.random.PRNGKey(seed % (2 ** 31)))
        adapter.check_tree(cfg, params)
        opt_state = jax.jit(tx.init, out_shardings=rep)(params)
        norms = jax.jit(harness.leaf_norms)
        delta_norms = jax.jit(lambda a, b: harness.leaf_norms(
            jax.tree_util.tree_map(lambda x, y: x - y, a, b)))
        jax.block_until_ready(opt_state)
    step = dp_train_step(model.loss, tx, comm, donate=tr["donate"])
    period = step.pulse.every
    if tr["block_steps"] % period:
        raise SystemExit(f"kfbench: block_steps {tr['block_steps']} is not "
                         f"a multiple of the pulse period {period}")

    # -- the first steps, which the comparison reads ---------------------
    # The monitor samples every ``period``-th call through a second
    # whole-step program.  Its gate is moved on so that the last compared
    # step is such a call: the comparison then holds both programs, and
    # every later block of the window still ends in its one pulse step.
    check_steps = tr["check"]["steps"]
    for _ in range((period - check_steps) % period):
        step.pulse.should_sample()
    seen = {"losses": []}
    n_steps = 0
    with parts("first_step"):  # trace, lower, compile or cache read, run
        params, opt_state, loss = step(params, opt_state, feed(0))
        seen["losses"].append(float(loss))
        n_steps = 1
    adam = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")][0]
    # adam's first moment after one step is (1 - b1) times the gradient
    # the optimizer was handed
    seen["grad_norms"] = (np.asarray(norms(adam.mu)) / (1 - opt["b1"])).tolist()
    del adam
    with parts("warmup_steps"):
        while n_steps < check_steps - 1:
            params, opt_state, loss = step(params, opt_state, feed(n_steps))
            seen["losses"].append(float(loss))
            n_steps += 1
    with parts("first_pulse_step"):  # the second whole-step program
        params, opt_state, loss = step(params, opt_state, feed(n_steps))
        seen["losses"].append(float(loss))
        n_steps += 1
    if step.pulse.samples != 1:
        raise SystemExit("kfbench: the last compared step was not a pulse "
                         "step")
    with parts("warmup_steps"):
        # the seeded weights made again: the step was given the first
        # ones to overwrite
        seen["update_norms"] = np.asarray(delta_norms(
            params, make(jax.random.PRNGKey(seed % (2 ** 31))))).tolist()
        for _ in range(tr["warmup_blocks"] * tr["block_steps"]):
            params, opt_state, loss = step(params, opt_state, feed(n_steps))
            n_steps += 1
        float(loss)
    compile_s = cache.compile_seconds

    # -- the window ------------------------------------------------------
    tracer = harness.Tracer(spec)
    blocks, dispatch, losses = [], [], []
    watch = hostwatch.HostWatch()
    window_wall = time.time()
    t0 = t_block = time.perf_counter()
    while True:
        tracer.start_if_due(t_block, t0 + spec["seconds"])
        for _ in range(tr["block_steps"]):
            with harness.annotate("kfb:feed"):
                batch = feed(n_steps)
            td = time.perf_counter()
            with harness.annotate("kfb:step"):
                params, opt_state, loss = step(params, opt_state, batch)
            dispatch.append(time.perf_counter() - td)
            n_steps += 1
        with harness.annotate("kfb:read_loss"):
            losses.append(float(loss))
        now = time.perf_counter()
        blocks.append(now - t_block)
        watch.mark()
        t_block = now
        if now - t0 >= spec["seconds"]:
            break
    window_s = time.perf_counter() - t0
    host = watch.stop(t0)
    tracer.stop()
    peak = harness.memory_peak_bytes()
    compiled_in_window_s = cache.compile_seconds - compile_s
    pulse_samples = step.pulse.samples
    kf.finalize()

    # -- the comparison, after the program's state is freed --------------
    del params, opt_state, step, loss
    t_check = time.perf_counter()
    check = compare(spec, seen, feed)
    check["seconds"] = time.perf_counter() - t_check
    check["numbers"]["compiled_in_window_s"] = {
        "value": compiled_in_window_s, "limit": 0}
    bad_steps = sum(1 for l in losses if not np.isfinite(l))
    return {
        "device": dict(device, memory_peak_bytes=peak),
        "window_wall": window_wall, "window_s": window_s,
        "setup_parts": parts.seconds,
        "train": {"block_s": blocks, "block_steps": tr["block_steps"],
                  "tokens_per_step": rows * tr["seq_len"],
                  "chips": comm.size, "dispatch_s": dispatch,
                  "losses": losses, "pulse_period": period,
                  "pulse_samples": pulse_samples, "host": host},
        "counters": {"compile_s": compile_s,
                     "cache_hits": cache.hits, "cache_misses": cache.misses},
        "trace": tracer.facts(),
        "check": check,
        "attempted": len(blocks) * tr["block_steps"],
        "failed": bad_steps * tr["block_steps"],
    }


def make_feed(spec: dict, rows: int):
    """step -> that step's packed batch of ``rows`` rows, from the seed."""
    tr, vocab = spec["traffic"], spec["config"]["vocab_size"]
    return lambda step: gen.packed_batch(tr, vocab, spec["seed"], step, rows)


def reference_steps(spec: dict, feed, cast=None) -> dict:
    """The plain reference through the same first steps on the same
    rows: each step's loss, the per-leaf norms of the first gradient and
    of the parameters' change after the last step.  Rows are independent,
    so where the process has several chips each takes an equal share of
    the rows and the means are averaged: the same sum, sooner."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg, tr = spec["config"], spec["traffic"]
    adapter = files.load_adapter(cfg["family"])
    ref = files.load_reference(cfg["family"])
    opt = tr["optimizer"]
    rows = feed(0)[0].shape[0]
    devs = jax.local_devices()
    mesh = Mesh(devs if rows % len(devs) == 0 else devs[:1], ("rows",))
    everywhere = NamedSharding(mesh, P())
    make = jax.jit(lambda k: adapter.init_params(cfg, k),
                   out_shardings=everywhere)
    params = make(jax.random.PRNGKey(spec["seed"] % (2 ** 31)))

    def share(params, ids, targets):
        loss, grads = ref.batch_loss_and_grad(cfg, params, ids, targets, cast)
        return jax.lax.pmean((loss, grads), "rows")

    @jax.jit
    def one(params, state, ids, targets):
        loss, grads = jax.shard_map(
            share, mesh=mesh, in_specs=(P(), P("rows"), P("rows")),
            out_specs=(P(), P()), check_vma=False)(params, ids, targets)
        new, state = ref.adamw_update(opt, params, grads, state)
        return new, state, loss, harness.leaf_norms(grads)

    state = jax.jit(ref.adamw_init, out_shardings=everywhere)(params)
    out = {"losses": []}
    for i in range(tr["check"]["steps"]):
        params, state, loss, gnorms = one(params, state, *feed(i))
        out["losses"].append(float(loss))
        if i == 0:
            out["grad_norms"] = np.asarray(gnorms).tolist()
    first = make(jax.random.PRNGKey(spec["seed"] % (2 ** 31)))
    out["update_norms"] = np.asarray(jax.jit(
        lambda a, b: harness.leaf_norms(jax.tree_util.tree_map(
            lambda x, y: x - y, a, b)))(params, first)).tolist()
    return out


def gaps(seen: dict, want: dict) -> dict:
    """The numbers compared: each a gap between what the timed path's
    first steps gave and what the reference gives."""
    import numpy as np

    g = harness.leaf_gaps(seen["grad_norms"], want["grad_norms"])
    u = harness.leaf_gaps(seen["update_norms"], want["update_norms"])
    # under adam a leaf whose gradient is all but zero (a key bias: the
    # softmax ignores it) moves by its rounding noise over eps, in any
    # precision: its change says nothing, so the change is compared on
    # the leaves that have a gradient
    ref_g = np.asarray(want["grad_norms"])
    live = ref_g >= DEAD_LEAF * np.median(ref_g)
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(seen["losses"], want["losses"])),
        "grad_norm_gap": float(g.max()),
        "update_norm_gap": float(u[live].max()),
    }


def compare(spec: dict, seen: dict, feed) -> dict:
    want = reference_steps(spec, feed)
    limits = spec["traffic"]["check"]["limits"]
    numbers = {k: {"value": v, "limit": limits[k]}
               for k, v in gaps(seen, want).items()}
    return {"numbers": numbers, "reference_losses": want["losses"],
            "program_losses": seen["losses"]}
