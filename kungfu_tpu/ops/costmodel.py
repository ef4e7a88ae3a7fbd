"""kf-xray cost model: analytic FLOPs/bytes for the flagship transformer.

MFU is a ratio of two numbers this repo previously had neither of: the
model FLOPs a step *must* execute (analytic, below — NOT a profiler
count, so recompute/fusion choices cannot inflate it) and the chip's
peak FLOP/s (detected from the TPU device kind, or pinned by the
``KF_XRAY_PEAK_FLOPS`` launch env).  On the CPU mesh there is no
meaningful peak, so :func:`chip_peak_flops` returns ``None`` and every
consumer reports the **model-FLOPs rate** row instead of an MFU: a
CPU-mesh row is a count, never a device metric.

Three model surfaces (docs/xray.md derives each):

* :func:`train_step_flops` — fwd+bwd(+head) for one training step, the
  standard 3x-forward accounting (backward re-does both matmul operands);
* :func:`serve_prefill_flops` / :func:`serve_decode_flops` — the serving
  plane's phases (prefill computes ``tokens`` positions attending into a
  growing context; decode computes one position over the full context);
* bytes: :func:`param_bytes` and :func:`kv_bytes_per_token` — the
  roofline denominators next to the ``kf_opt_state_bytes`` /
  ``kf_kv_cache_bytes`` gauges.

The live surface is :class:`MFUMeter`: one object per training loop (or
serving engine) that turns per-step wall clock + the analytic FLOPs into
the ``kf_mfu`` / ``kf_model_flops_s`` gauges and the per-phase
``kf_step_phase_seconds{phase=...}`` gauges, all riding the existing
snapshot → aggregator → ``/cluster`` → kftop flow.
"""

from __future__ import annotations

import os
import time
from typing import Dict, Optional

from kungfu_tpu.monitor import timeline
from kungfu_tpu.monitor.registry import REGISTRY

#: launch env pinning the per-chip peak FLOP/s (overrides detection;
#: registered in utils/envs.py like every KF_* knob)
PEAK_ENV = "KF_XRAY_PEAK_FLOPS"

#: per-chip bf16 peak FLOP/s by jax ``device_kind`` prefix (public
#: figures; one chip = what one jax device reports).  Longest prefix
#: wins so "TPU v5p" is not swallowed by "TPU v5".
CHIP_PEAK_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
    "TPU7x": 2307e12,
}


# -- parameter / bytes accounting ------------------------------------------
def transformer_param_count(cfg) -> int:
    """Exact parameter count of :class:`~kungfu_tpu.models.transformer.
    Transformer` under ``cfg`` — pinned against a real ``init()`` tree in
    tests so the analytic model cannot drift from the code."""
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    per_layer = (
        4 * (d * d + d)      # wq/wk/wv/wo (+bias)
        + (d * f + f) + (f * d + d)  # ffn_in/ffn_out (+bias)
        + 2 * 2 * d          # ln1/ln2 scale+bias
    )
    total = v * d + cfg.n_layers * per_layer + 2 * d  # embed + layers + ln_f
    if cfg.pos == "learned":
        total += cfg.max_seq * d
    total += d * v  # untied head, no bias
    return total


def matmul_param_count(cfg) -> int:
    """Parameters that participate in matmuls (the ``2 * P * tokens``
    denominator of the classic FLOPs estimate): everything except the
    embedding lookup table, positions, layernorms, and biases."""
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    return cfg.n_layers * (4 * d * d + 2 * d * f) + d * v


def param_bytes(cfg, dtype_bytes: int = 4) -> int:
    """Model parameter footprint (f32 master params by default)."""
    return transformer_param_count(cfg) * dtype_bytes


def kv_bytes_per_token(cfg, dtype_bytes: int = 2) -> int:
    """KV-cache bytes one token pins: K+V per layer in compute dtype —
    the per-token slope of the ``kf_kv_cache_bytes`` gauge."""
    return 2 * cfg.n_layers * cfg.n_heads * cfg.head_dim * dtype_bytes


# -- FLOPs model ------------------------------------------------------------
def forward_flops(cfg, batch: int, seq: int, lm_head: bool = True) -> int:
    """Forward-pass FLOPs for ``[batch, seq]`` tokens: the matmul term
    (``2 * P_matmul`` per token), the quadratic attention term
    (``4 * d * S`` per token per layer for QK^T + PV), and optionally
    the LM head."""
    d = cfg.d_model
    tokens = batch * seq
    matmul = 2 * tokens * cfg.n_layers * (4 * d * d + 2 * d * cfg.d_ff)
    attn = 4 * tokens * seq * d * cfg.n_layers
    head = 2 * tokens * d * cfg.vocab_size if lm_head else 0
    return matmul + attn + head


def train_step_flops(cfg, batch: int, seq: int) -> int:
    """Fwd + bwd for one step: the standard 3x-forward accounting (the
    backward pass re-computes both operands of every matmul)."""
    return 3 * forward_flops(cfg, batch, seq)


def serve_prefill_flops(cfg, tokens: int, start: int = 0) -> int:
    """Prefill of ``tokens`` new positions on top of ``start`` cached
    ones (prefix reuse skips the cached positions' FLOPs — the saving
    ``kf_serve_prefill_tokens_total`` counts in computed tokens): matmul +
    attention into the growing ``[0, start+tokens)`` context, plus ONE
    logits row (prefill emits only the last position's token)."""
    if tokens <= 0:
        return 0
    d = cfg.d_model
    matmul = 2 * tokens * cfg.n_layers * (4 * d * d + 2 * d * cfg.d_ff)
    # position start+i attends over start+i+1 keys; sum_i ~ t*(start + (t+1)/2)
    attended = tokens * start + tokens * (tokens + 1) // 2
    attn = 4 * d * cfg.n_layers * attended
    head = 2 * d * cfg.vocab_size
    return matmul + attn + head


def serve_decode_flops(cfg, context: int) -> int:
    """One decode position of one sequence attending over ``context``
    keys (its own included)."""
    d = cfg.d_model
    matmul = 2 * cfg.n_layers * (4 * d * d + 2 * d * cfg.d_ff)
    attn = 4 * d * cfg.n_layers * max(1, context)
    head = 2 * d * cfg.vocab_size
    return matmul + attn + head


# -- chip peak --------------------------------------------------------------
def chip_peak_flops(device=None) -> Optional[float]:
    """Per-chip peak FLOP/s: the ``KF_XRAY_PEAK_FLOPS`` env wins, else
    the detected TPU device kind's table entry; ``None`` on CPU/unknown
    backends (there is no honest peak to divide by — consumers report
    the model-FLOPs rate instead)."""
    pinned = os.environ.get(PEAK_ENV, "").strip()
    if pinned:
        try:
            v = float(pinned)
            return v if v > 0 else None
        except ValueError:
            pass
    try:
        if device is None:
            import jax

            devices = jax.devices()
            if not devices:
                return None
            device = devices[0]
        kind = str(getattr(device, "device_kind", "") or "")
    except Exception:  # noqa: BLE001 — detection must never break a loop
        return None
    best = None
    for prefix, peak in CHIP_PEAK_FLOPS.items():
        if kind.startswith(prefix) and (best is None or len(prefix) > best[0]):
            best = (len(prefix), peak)
    return best[1] if best else None


# -- live meter -------------------------------------------------------------
def record_phases(phases: Dict[str, float]) -> None:
    """Export a per-step phase split as the
    ``kf_step_phase_seconds{phase=...}`` gauges (the continuous
    decomposition kftop's XRAY section renders cluster-wide)."""
    for phase, seconds in phases.items():
        REGISTRY.gauge("kf_step_phase_seconds", phase=phase).set(
            float(seconds))


class MFUMeter:
    """Continuous MFU / model-FLOPs-rate accounting for one loop.

    ``step_flops`` may be a constant (training: one analytic number per
    step) or accumulated via :meth:`add_flops` (serving: prefill/decode
    FLOPs vary per iteration).  Each :meth:`step` turns the window into
    the ``kf_model_flops_s`` gauge, the ``kf_mfu`` gauge when a chip
    peak is known, and — when a phase split is supplied — the per-phase
    gauges plus an ``xray`` timeline mark so offline dumps carry the
    same sample the live plane exports."""

    def __init__(self, step_flops: int = 0,
                 peak_flops: Optional[float] = None,
                 detect_peak: bool = True,
                 ema_alpha: float = 0.2,
                 rank: Optional[int] = None):
        self.step_flops = int(step_flops)
        self.peak_flops = (peak_flops if peak_flops is not None
                           else (chip_peak_flops() if detect_peak else None))
        self._alpha = float(ema_alpha)
        self._pending_flops = 0
        self._last = None  # perf_counter of the previous step boundary
        self._rate_ema: Optional[float] = None
        self.rank = rank
        self.mfu: Optional[float] = None

    def add_flops(self, flops: int) -> None:
        """Accumulate FLOPs executed since the last :meth:`step` (the
        serving engine's per-prefill/per-decode contributions)."""
        self._pending_flops += int(flops)

    def step(self, wall_s: Optional[float] = None,
             phases: Optional[Dict[str, float]] = None) -> Optional[float]:
        """One step boundary.  ``wall_s`` pins the step duration; without
        it the meter uses the time since its previous call.  Returns the
        smoothed model-FLOPs rate (FLOP/s), ``None`` until measurable."""
        now = time.perf_counter()
        if wall_s is None:
            wall_s = (now - self._last) if self._last is not None else None
        self._last = now
        flops = self.step_flops + self._pending_flops
        self._pending_flops = 0
        if wall_s is None or wall_s <= 0 or flops <= 0:
            return self._rate_ema
        rate = flops / wall_s
        self._rate_ema = (rate if self._rate_ema is None
                          else (1 - self._alpha) * self._rate_ema
                          + self._alpha * rate)
        REGISTRY.gauge("kf_model_flops_s").set(self._rate_ema)
        if self.peak_flops:
            self.mfu = self._rate_ema / self.peak_flops
            REGISTRY.gauge("kf_mfu").set(self.mfu)
        if phases:
            record_phases(phases)
        if timeline.enabled():
            timeline.event(
                "xray", "mfu-sample", rank=self.rank,
                flops=flops, wall_s=round(wall_s, 6),
                flops_s=round(self._rate_ema, 3),
                mfu=(round(self.mfu, 5) if self.mfu is not None else None),
                **{f"phase_{k}": round(v, 6)
                   for k, v in (phases or {}).items()})
        return self._rate_ema
