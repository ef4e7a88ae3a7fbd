"""The serving loop: one ``InferenceEngine`` on one chip, under an open
loop of requests from independent users.

A generator thread submits each request when it is due, whether or not
earlier ones have finished.  Which request is due when is the traffic
file's (``lib/traffic.py``), the same in every run of the cell.  Traffic
starts ``preroll_s`` before the window so that the window opens on an
engine already at its steady number of active slots.  Requests are
counted if they were due inside the window and are timed from when they
were due.  Every token is stamped when the ``engine.step()`` that
produced it returns.  The facts of every request go to the run's file.
"""

from __future__ import annotations

import threading
import time

from kfbench.lib import files, harness, traffic as gen

#: once the window has closed, what is still in flight may take this long
DRAIN_LIMIT_S = 90.0


class Request:
    def __init__(self, index, prompt, max_new, due=None, in_window=False):
        self.index = index
        self.rid = f"r{index}"
        self.prompt = prompt
        self.max_new = max_new
        self.due = due            # perf_counter time it was due
        self.in_window = in_window
        self.submitted = None
        self.token_t = []
        self.stats = None

    def facts(self) -> dict:
        s = self.stats or {}
        return {"index": self.index, "prompt_len": len(self.prompt),
                "max_new": self.max_new, "due": self.due,
                "in_window": self.in_window, "submitted": self.submitted,
                "token_t": self.token_t, "done": self.stats is not None,
                "n_tokens": len(s.get("tokens", ())),
                "queue_s": s.get("queue_s"), "engine_ttft_s": s.get("ttft_s")}


class Serving:
    """The engine with the weights it serves, built once in set-up."""

    def __init__(self, spec: dict):
        self.parts = parts = harness.Parts()
        cfg, e = spec["config"], spec["traffic"]["engine"]
        with parts("imports"):
            import jax

            harness.pin_platform(spec)
            from kungfu_tpu.serve.engine import InferenceEngine
            from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec
            from kungfu_tpu.utils.compile_cache import CacheCounter
        with parts("backend"):
            self.device = harness.check_device(spec)
            self.cache = CacheCounter()
        adapter = files.load_adapter(cfg["family"])
        model = adapter.program_model(cfg)
        with parts("init_state"):
            self.params = jax.jit(lambda k: adapter.init_params(cfg, k))(
                jax.random.PRNGKey(spec["seed"] % (2 ** 31)))
            adapter.check_tree(cfg, self.params)
            self.eng = InferenceEngine(
                model, self.params, max_batch=e["max_batch"],
                max_seq=e["max_seq"],
                pool=KVCachePool(PageSpec.for_model(
                    model.cfg, page_tokens=e["page_tokens"]),
                    capacity_pages=e["kv_pages"]))
            jax.block_until_ready(self.params)
        self.buckets = []

    def warm(self, spec: dict, shapes) -> None:
        """Warm the shapes this traffic uses and no others, through the
        public path.  ``shapes`` are its (prompt, output) lengths.  The
        engine compiles one prefill program per power-of-two bucket of
        the prompt, and -- when a request completes and its pages are
        committed -- one slice of the slab per number of full pages
        (prompt + output - 1) // page_tokens.  One two-token request per
        page count, and one per bucket those do not reach."""
        eng, cfg = self.eng, spec["config"]
        e = spec["traffic"]["engine"]
        with self.parts("warmup"):
            self.buckets = sorted({eng._prefill_bucket(p) for p, _ in shapes})
            pages = sorted({(p + o - 1) // e["page_tokens"] for p, o in shapes})
            lens = [e["page_tokens"] * f - 1 for f in pages if f >= 1]
            reached = {eng._prefill_bucket(n) for n in lens}
            lens += [min(b, e["max_seq"] - 2) for b in self.buckets
                     if b not in reached]
            for i, n in enumerate(lens):
                eng.submit(f"warm{i}", gen.prompt_ids(
                    cfg["vocab_size"], spec["seed"], 10 ** 6 + i, n), 2)
            done = [ev for ev in eng.drain() if ev["kind"] == "done"]
            if len(done) != len(lens):
                raise SystemExit("kfbench: the warm-up requests did not "
                                 "finish")
        self.pages = pages


def offered(spec: dict) -> list:
    """The requests of one run, in the order they are due.  Which
    lengths, and when each is due, is the traffic file's and the same in
    every run; the ids of each prompt are ``--seed``'s."""
    vocab, seed = spec["config"]["vocab_size"], spec["seed"]
    return [Request(i, gen.prompt_ids(vocab, seed, i, p), o, due=due,
                    in_window=w)
            for i, (due, p, o, w) in enumerate(
                gen.open_schedule(spec["traffic"], spec["seconds"]))]


def drive(spec: dict, serving: Serving, tracer=None) -> dict:
    """Pre-roll, window and drain of one traffic mix on a warm engine."""
    tr, eng = spec["traffic"], serving.eng
    reqs = {r.rid: r for r in offered(spec)}
    tracer = tracer or harness.Tracer(dict(spec, trace=0))

    def all_answered():
        """Every request due in the window has its first token."""
        return all(r.token_t for r in reqs.values() if r.in_window)

    steps = []  # (t_return, seconds, tokens, prompt tokens prefilled, backlog)
    t_pre = time.perf_counter()
    wall_pre = time.time()
    t0 = t_pre + tr["preroll_s"]
    t_end = t0 + spec["seconds"]
    for r in reqs.values():
        r.due = t0 + r.due
    stop_submitting = threading.Event()

    def offer():
        for r in reqs.values():
            wait = r.due - time.perf_counter()
            if wait > 0 and stop_submitting.wait(wait):
                return
            r.submitted = time.perf_counter()
            eng.submit(r.rid, r.prompt, r.max_new)

    gen_thread = threading.Thread(target=offer, name="kfb-generator")
    gen_thread.start()
    deadline = None
    try:
        while True:
            now = time.perf_counter()
            if deadline is not None and now > deadline:
                break
            if not (eng.pending_count or eng.active_count):
                if gen_thread.is_alive():
                    eng.wait_for_work(0.02)
                    continue
                break
            tracer.start_if_due(now, t_end)
            ts = time.perf_counter()
            with harness.annotate("kfb:engine.step"):
                events = eng.step()
            t = time.perf_counter()
            n_tok = n_prefill = 0
            for ev in events:
                r = reqs[ev["rid"]]
                if ev["kind"] == "token":
                    r.token_t.append(t)
                    n_tok += 1
                    if ev["n"] == 1:
                        n_prefill += len(r.prompt)
                elif ev["kind"] == "done":
                    r.stats = ev
            steps.append((t, t - ts, n_tok, n_prefill,
                          eng.pending_count + eng.active_count))
            if deadline is None and t >= t_end:
                deadline = t + DRAIN_LIMIT_S
            if deadline is not None and all_answered():
                tracer.stop()  # (stalls this thread: only the drain waits)
    finally:
        stop_submitting.set()
        gen_thread.join(timeout=10)
    tracer.stop()
    counted = [r for r in reqs.values() if r.in_window]
    return {
        "t0": t0, "t_end": t_end, "steps": steps,
        "window_wall": wall_pre + (t0 - t_pre),
        "gen_late_s": [r.submitted - r.due for r in reqs.values()
                       if r.submitted is not None],
        "requests": list(reqs.values()), "counted": counted,
        "failed": [r for r in counted if r.stats is None
                   or len(r.stats["tokens"]) != r.max_new],
    }


def traffic_shapes(spec: dict):
    """Every (prompt, output) length this cell's traffic will send."""
    return {(p, o) for _, p, o, _ in gen.open_schedule(
        spec["traffic"], spec["seconds"])}


def run(spec: dict) -> dict:
    serving = Serving(spec)
    serving.warm(spec, traffic_shapes(spec))
    cache = serving.cache
    compile_s = cache.compile_seconds
    tracer = harness.Tracer(spec)
    d = drive(spec, serving, tracer)
    peak = harness.memory_peak_bytes()
    compiled_in_window_s = cache.compile_seconds - compile_s

    # -- the comparison, once the engine's slab is freed -------------------
    served = [r for r in d["counted"] if r.stats is not None]
    params, device, parts = serving.params, serving.device, serving.parts
    buckets, pages = serving.buckets, serving.pages
    del serving.eng, serving
    t_check = time.perf_counter()
    check = compare(spec, params, served)
    check["seconds"] = time.perf_counter() - t_check
    check["numbers"]["compiled_in_window_s"] = {
        "value": compiled_in_window_s, "limit": 0}
    return {
        "device": dict(device, memory_peak_bytes=peak),
        "window_wall": d["window_wall"], "window_s": d["t_end"] - d["t0"],
        "setup_parts": parts.seconds,
        "serve": {"t0": d["t0"], "t_end": d["t_end"],
                  "steps": d["steps"], "gen_late_s": d["gen_late_s"],
                  "requests": [r.facts() for r in d["requests"]],
                  "buckets": buckets, "pages": pages},
        "counters": {"compile_s": compile_s,
                     "cache_hits": cache.hits, "cache_misses": cache.misses},
        "trace": tracer.facts(),
        "check": check,
        "attempted": len(d["counted"]), "failed": len(d["failed"]),
    }


def sample_requests(spec: dict, served: list) -> list:
    """A sample of the served requests, drawn from the seed, with the
    longest in it."""
    k = spec["traffic"]["check"]["sample_requests"]
    if not served:
        return []
    longest = max(served, key=lambda r: len(r.prompt) + r.max_new)
    rest = [r for r in served if r is not longest]
    order = gen.rng(spec["seed"], 6).permutation(len(rest))
    return [longest] + [rest[int(i)] for i in order[:k - 1]]


def served_gaps(spec: dict, params, sample: list, cast=None):
    """For each sampled request, the reference's logits over its prompt
    and served tokens (one plain forward pass, padded to ``max_seq``;
    padding after a position cannot reach it under a causal mask), and
    per served token the gap by which its logit lies below the
    reference's best.  With ``cast`` (the control) the token judged at
    each position is the one the lower precision puts first."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = spec["config"]
    ref = files.load_reference(cfg["family"])
    width = spec["traffic"]["engine"]["max_seq"]

    @jax.jit
    def gaps_of(params, ids, nxt):
        lg = ref.logits(cfg, params, ids)
        if cast is not None:
            nxt = jnp.argmax(ref.logits(cfg, params, ids, cast), axis=-1)
        picked = jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
        return jnp.max(lg, axis=-1) - picked

    out = []
    for r in sample:
        toks = r.stats["tokens"]
        seq = (list(r.prompt) + toks)[:width + 1]
        ids = np.zeros(width, np.int32)
        nxt = np.zeros(width, np.int32)
        ids[:len(seq) - 1] = seq[:-1]
        nxt[:len(seq) - 1] = seq[1:]
        g = np.asarray(gaps_of(params, jnp.asarray(ids), jnp.asarray(nxt)))
        lo = len(r.prompt) - 1
        out.append(g[lo:lo + len(toks)].tolist())
    return out


def compare(spec: dict, params, served: list) -> dict:
    sample = sample_requests(spec, served)
    per_req = served_gaps(spec, params, sample)
    widest = max((g for gs in per_req for g in gs), default=float("inf"))
    limit = spec["traffic"]["check"]["limits"]["token_gap"]
    out = {"numbers": {"token_gap": {"value": widest, "limit": limit}},
           "sampled": [r.index for r in sample],
           "sampled_tokens": sum(len(g) for g in per_req)}
    if spec.get("control"):  # tools/control.py: the reference in float8
        ref = files.load_reference(spec["config"]["family"])
        ctl = served_gaps(spec, params, sample, cast=ref.to_fp8)
        out["control"] = {"token_gap": max(g for gs in ctl for g in gs)}
    return out
