#!/usr/bin/env python3
"""kf-serve demo: a request survives a chaos worker kill, zero losses.

A 4-peer in-process deployment — ranks 0..2 serving workers
(continuous-batching engines over a small transformer), rank 3 the
router — takes a steady request stream while the chaos layer kills
worker 1 at its 10th decode iteration (``die:step=10,mode=raise``, set
below).  The router's progress-deadline ladder detects the death,
excludes the worker, and replays its in-flight requests from their
last committed decode position on the survivors.  The script asserts:

* EVERY accepted request completes with its full token budget — zero
  lost accepted requests, including the ones in flight on the victim;
* at least one request was replayed (the kill landed mid-flight);
* the victim is on the router's dead list and the survivors are not;
* a replayed continuation equals the deterministic greedy reference;
* prefix reuse engaged (the shared system prompt prefilled once per
  worker, later requests reused its pages).

Wired into ``make serve-demo`` and ``scripts/check.sh``; a whole-slice
kill is ``tests/test_serve.py::TestRouterLive``.  See docs/serving.md.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# before any kungfu_tpu import: chaos controllers read these at creation
os.environ["KF_NATIVE_ENGINE"] = "0"
os.environ.setdefault("KF_CONFIG_LOG_LEVEL", "WARNING")
os.environ["KF_CHAOS_SPEC"] = "die:step=10,rank=1,mode=raise"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--base-port", type=int, default=24810)
    ns = ap.parse_args()

    import jax
    import numpy as np

    from kungfu_tpu.models.transformer import Transformer, TransformerConfig
    from kungfu_tpu.peer import Peer
    from kungfu_tpu.plan import Cluster, PeerList
    from kungfu_tpu.serve.engine import InferenceEngine
    from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec
    from kungfu_tpu.serve.router import ServeRouter, ServeWorker
    from kungfu_tpu.utils.envs import Config

    cfg = TransformerConfig(vocab_size=96, d_model=32, n_layers=2,
                            n_heads=2, d_ff=64, max_seq=128,
                            dtype="float32")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))

    workers = PeerList.parse(
        ",".join(f"127.0.0.1:{ns.base_port + i}" for i in range(4)))
    runners = PeerList.parse(f"127.0.0.1:{ns.base_port + 99}")
    cluster = Cluster(runners, workers)
    peers = [Peer(Config(self_id=w, cluster=cluster)) for w in workers]
    for p in peers:
        p.start()

    system_prompt = list(range(1, 17))  # shared prefix: 2 pages of 8
    servers = []
    for p in peers[:3]:
        eng = InferenceEngine(
            model, params,
            pool=KVCachePool(PageSpec.for_model(cfg, page_tokens=8), 256),
            max_batch=4, max_seq=cfg.max_seq, rank=p.chaos_rank())
        eng.warmup(prompt_lens=(len(system_prompt) + 4,))
        servers.append(ServeWorker(p, eng, commit_every=2).start())
    router = ServeRouter(peers[3], worker_ranks=[0, 1, 2],
                         queue_depth=64, deadline_s=2.0)

    try:
        handles = []
        for i in range(ns.requests):
            handles.append(
                router.submit(system_prompt + [20 + i], ns.tokens))
            time.sleep(0.02)  # a steady offered load, not one burst
        outs = [h.wait(120) for h in handles]
        assert all(len(o) == ns.tokens for o in outs), \
            f"lost tokens: {[len(o) for o in outs]}"
        assert router.completed == ns.requests
        assert router.dead_workers == [1], router.dead_workers
        assert router.replayed >= 1, "the kill landed between requests"
        assert servers[1].dead and not servers[0].dead

        # determinism: a replayed request equals the greedy reference
        replayed = next(h for h in handles if h.replays > 0)
        ref = list(replayed.prompt)
        for _ in range(ns.tokens):
            logits = model.apply(params, np.asarray([ref], np.int32))
            ref.append(int(np.argmax(np.asarray(logits)[0, -1])))
        assert replayed.tokens == ref[len(replayed.prompt):], \
            "replayed continuation diverged from the reference"

        # prefix reuse engaged on the shared system prompt
        from kungfu_tpu.monitor.registry import REGISTRY

        reused = REGISTRY.counter("kf_serve_prefill_tokens_total",
                                  what="reused").value
        assert reused > 0, "no prefix reuse measured"

        print(
            f"serve-demo: survived worker kill; "
            f"{router.completed}/{ns.requests} requests completed "
            f"(replayed {router.replayed}, dead {router.dead_workers}, "
            f"reused {reused} prefill tokens)"
        )
        return 0
    finally:
        router.close()
        for s in servers:
            if not s.dead:
                s.stop()
        for p in peers:
            try:
                p.close()
            except Exception:  # noqa: BLE001 — the victim is already down
                pass


if __name__ == "__main__":
    sys.exit(main())
