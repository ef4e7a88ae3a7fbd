"""Serving SLO surfaces: latency histograms, load gauges, targets.

One thin layer over the unified registry
(:mod:`kungfu_tpu.monitor.registry`) so every serving latency lands in
the SAME pipeline the training plane already built: local ``/metrics``
rendering, percentile summaries, and — because
:class:`~kungfu_tpu.monitor.aggregator.RankReporter` forwards registry
counters/gauges and histogram *deltas* in every snapshot — the
aggregator ``/cluster`` view and the kftop serving section, with no new
wire schema.

The three serving latencies (docs/serving.md):

* **TTFT** (``kf_serve_ttft_seconds``) — admission to first decoded
  token, measured at the worker (includes engine queue wait);
* **per-token** (``kf_serve_token_seconds``) — the time between two
  deliveries of decode tokens to the host, measured at the worker (the
  engine runs one step ahead: a delivery, not a step's own dispatch and
  read, is what a client feels);
* **e2e** (``kf_serve_e2e_seconds``) — submit to completion, measured
  at the router (includes routing, wire, queue, replay after a worker
  death — the number a user feels).

Request accounting rides the flight recorder's counted-kind machinery:
``timeline.event("request", "accept"|"reject"|"complete"|"replay"|
"lost")`` ticks ``kf_serve_requests_total{what=...}`` even with tracing
off, exactly like the chaos/shrink counters.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

from kungfu_tpu.monitor.registry import REGISTRY
from kungfu_tpu.utils import envs

TTFT_HIST = "kf_serve_ttft_seconds"
TOKEN_HIST = "kf_serve_token_seconds"
E2E_HIST = "kf_serve_e2e_seconds"
QUEUE_GAUGE = "kf_serve_queue_depth"
ACTIVE_GAUGE = "kf_serve_active_requests"
REQUESTS_COUNTER = "kf_serve_requests_total"
PREFILL_COUNTER = "kf_serve_prefill_tokens_total"

DEFAULT_TTFT_MS = 500.0
DEFAULT_E2E_MS = 5000.0


def observe_ttft(seconds: float) -> None:
    REGISTRY.histogram(TTFT_HIST).observe(seconds)


def observe_token(seconds: float) -> None:
    REGISTRY.histogram(TOKEN_HIST).observe(seconds)


def observe_e2e(seconds: float) -> None:
    REGISTRY.histogram(E2E_HIST).observe(seconds)


def note_queue_depth(n: int) -> None:
    REGISTRY.gauge(QUEUE_GAUGE).set(n)


def note_active(n: int) -> None:
    REGISTRY.gauge(ACTIVE_GAUGE).set(n)


def count_prefill(computed: int = 0, reused: int = 0) -> None:
    """Prefill work accounting: ``computed`` tokens ran the forward,
    ``reused`` came out of the paged cache's prefix chain — the counted
    basis of the prefix-reuse claim."""
    if computed:
        REGISTRY.counter(PREFILL_COUNTER, what="computed").inc(computed)
    if reused:
        REGISTRY.counter(PREFILL_COUNTER, what="reused").inc(reused)


@dataclass(frozen=True)
class SLOTargets:
    """Latency objectives; the policy layer's controllers steer against
    these (docs/serving.md SLO methodology)."""

    ttft_s: float = DEFAULT_TTFT_MS / 1e3
    e2e_s: float = DEFAULT_E2E_MS / 1e3

    @classmethod
    def from_env(cls) -> "SLOTargets":
        return cls(
            ttft_s=envs.parse_float_env(envs.SERVE_SLO_TTFT_MS,
                                        DEFAULT_TTFT_MS) / 1e3,
            e2e_s=envs.parse_float_env(envs.SERVE_SLO_E2E_MS,
                                       DEFAULT_E2E_MS) / 1e3,
        )


@dataclass(frozen=True)
class SLORules:
    """Declarative burn-rate rules the kf-sentinel evaluates online.

    Budgets are in MILLISECONDS because the sentinel judges the
    aggregator rollup series (``ttft_ms``/``e2e_ms``, already ms), not
    the local histograms.  The two-window test
    (:func:`kungfu_tpu.monitor.detect.slo_burn`) alerts only when BOTH
    the short window (fast burn, happening now) and the long window
    (sustained burn, not one blip) exceed their violation fractions —
    docs/sentinel.md has the rule table.

    monitor/sentinel.py reads the same env tokens from ``os.environ``
    directly (mirror constants — kfhist's stubbed context never imports
    this jax-adjacent package); tests pin both sides to these exact
    defaults so the contract cannot drift.
    """

    ttft_budget_ms: float = DEFAULT_TTFT_MS
    e2e_budget_ms: float = DEFAULT_E2E_MS
    short_window: int = 6
    long_window: int = 24
    short_frac: float = 0.5
    long_frac: float = 0.25

    @classmethod
    def from_env(cls) -> "SLORules":
        return cls(
            ttft_budget_ms=envs.parse_float_env(envs.SERVE_SLO_TTFT_MS,
                                                DEFAULT_TTFT_MS),
            e2e_budget_ms=envs.parse_float_env(envs.SERVE_SLO_E2E_MS,
                                               DEFAULT_E2E_MS),
            short_window=envs.parse_int_env(envs.SENTINEL_SLO_SHORT, 6),
            long_window=envs.parse_int_env(envs.SENTINEL_SLO_LONG, 24),
        )

    def budgets(self) -> Dict[str, float]:
        """Rollup-series name -> ms budget, the shape the sentinel's
        rule loop iterates."""
        return {"ttft_ms": self.ttft_budget_ms, "e2e_ms": self.e2e_budget_ms}


def slo_snapshot() -> Dict[str, Dict[str, float]]:
    """Current percentile summaries of the three serving histograms
    (local process view; the cross-rank view is kftop's)."""
    return {
        "ttft": REGISTRY.histogram(TTFT_HIST).summary(),
        "token": REGISTRY.histogram(TOKEN_HIST).summary(),
        "e2e": REGISTRY.histogram(E2E_HIST).summary(),
    }


def slo_verdict(targets: Optional[SLOTargets] = None,
                snapshot: Optional[Dict[str, Dict[str, float]]] = None
                ) -> Dict[str, bool]:
    """p99-vs-target booleans (empty histograms pass: no traffic is not
    a violation)."""
    targets = targets or SLOTargets.from_env()
    snap = snapshot if snapshot is not None else slo_snapshot()

    def ok(name: str, budget: float) -> bool:
        s = snap.get(name) or {}
        return s.get("count", 0) == 0 or s.get("p99", 0.0) <= budget

    return {"ttft_ok": ok("ttft", targets.ttft_s),
            "e2e_ok": ok("e2e", targets.e2e_s)}
