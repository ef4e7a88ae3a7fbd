"""kf-serve: the elastic inference plane.

Turns a :class:`~kungfu_tpu.peer.Peer` world into an inference
deployment over the substrate the training arc built — host transport
with registered receives and load-scaled responder pools, elastic
membership with slice-aware shrink, the aggregator/kftop observability
plane:

* :mod:`kungfu_tpu.serve.kvcache` — paged KV-cache block manager
  (fixed-size pages, free-list allocation, prefix-hash reuse, LRU
  eviction) whose per-rank footprint is the ``kf_kv_cache_bytes`` gauge
  next to ``kf_opt_state_bytes``;
* :mod:`kungfu_tpu.serve.engine` — continuous-batching decode loop
  (jit-compiled prefill/decode steps, decode-priority admission) over
  whatever ``model.serve_caches`` answers: :mod:`kungfu_tpu.serve.caches`
  for the dense transformer, :mod:`kungfu_tpu.serve.windowed` for a
  model that mixes window and full attention layers,
  :mod:`kungfu_tpu.serve.latent` for latent (MLA) attention,
  :mod:`kungfu_tpu.serve.recurrent` for layers that keep a state a
  slot, :mod:`kungfu_tpu.serve.pooled` for attention over pooled chunk
  rows;
* :mod:`kungfu_tpu.serve.router` — request router + admission policy
  (FCFS, bounded queue, typed overload rejection) speaking over the
  existing host channel / p2p handler machinery, with SLO-gated fault
  tolerance: a killed worker or killed slice is detected, excluded at
  the slice grain when a topology exists, and its in-flight requests
  replay from the last committed decode position on survivors;
* :mod:`kungfu_tpu.serve.slo` — TTFT / per-token / e2e latency
  histograms in the unified registry, flowing through aggregator
  snapshots to the kftop serving view.

Design + SLO methodology + failure semantics: docs/serving.md.
"""

from kungfu_tpu.serve.kvcache import (CacheExhausted, KVCachePool, PageSpec,
                                      chain_hashes)
from kungfu_tpu.serve.slo import SLOTargets, slo_snapshot

__all__ = [
    "CacheExhausted",
    "KVCachePool",
    "PageSpec",
    "chain_hashes",
    "SLOTargets",
    "slo_snapshot",
    "InferenceEngine",
    "ServeRouter",
    "ServeWorker",
    "RequestHandle",
]


def __getattr__(name):
    # engine/router import jax and the comm stack — lazy, so the pure
    # kvcache/slo units (and stdlib-only tooling) stay importable alone
    if name == "InferenceEngine":
        from kungfu_tpu.serve.engine import InferenceEngine

        return InferenceEngine
    if name in ("ServeRouter", "ServeWorker", "RequestHandle"):
        from kungfu_tpu.serve import router as _router

        return getattr(_router, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
