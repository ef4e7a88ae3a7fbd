"""Flight recorder: bounded in-process ring of structured events, and the
program's spans on the profiler's clock.

The ring answers "*which rank* stalled *which collective* at *which
step*, and was a chaos fault or a shrink in flight at the time".  Every
event is ``(ts, rank, step, kind, name, dur, attrs)``:

* ``ts`` — wall-clock start time (``time.time()``, so cross-rank merges
  align without a clock-sync protocol; NTP-level skew is visible but the
  per-collective *skew analysis* in ``kftrace`` compares durations, which
  are immune to it);
* ``rank`` — the emitting rank (``None`` for rank-less subsystems like
  the detector; the module-level default set by :func:`set_rank` fills
  in when the call site passes nothing);
* ``step`` — the current training step (:func:`set_step`), ``-1`` before
  the first step;
* ``kind`` — one of :data:`EVENT_KINDS` (enforced by the ``trace-vocab``
  kflint rule: a typo'd kind would silently vanish from every ``kftrace``
  filter);
* ``dur`` — seconds for :func:`span` regions, ``0`` for one-shot
  :func:`event` marks.

Every :func:`span` is also a ``jax.profiler.TraceAnnotation`` named
``kf:<kind>.<name>`` with its scalar attrs as the event's stats, whether
or not the ring records: a profiler session around a few steps
(``jax.profiler.start_trace``) shows the program's spans beside the
device's operations, on one clock.  JAX is never imported from here —
the runner, the detector and the config server use this module without
it — so the annotation exists only where ``jax.profiler`` is already in
``sys.modules``.

Cost contract: the ring is gated by ``KF_CONFIG_ENABLE_TRACE``.
Disabled, :func:`span` returns the bare annotation (one small object;
with no profiler session its enter/exit is an atomic check: no clock,
no lock, no log line), or a shared no-op where JAX is not loaded, and
:func:`event` returns after one env check — except for the rare
*counted* kinds (retry/deadline/chaos/down/shrink), whose registry
counters tick regardless so ``/metrics`` stays truthful without paying
for the ring on the hot path.

Dump: one JSONL file per process (= per rank under the runner) written
by :func:`maybe_dump` (``Peer.close``) and an ``atexit`` hook when
``KF_CONFIG_TRACE_DUMP`` names a directory (or a ``*.jsonl`` file).
``scripts/kftrace`` merges N ranks' dumps into one Chrome-trace JSON and
prints the straggler report (:mod:`kungfu_tpu.monitor.traceview`).
"""

from __future__ import annotations

import atexit
import collections
import itertools
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from kungfu_tpu.monitor.registry import REGISTRY
from kungfu_tpu.utils.log import get_logger
from kungfu_tpu.utils.trace import trace_enabled

_log = get_logger("timeline")

#: JSONL dump location: a directory (one ``trace-*.jsonl`` per process)
#: or an exact ``*.jsonl`` path (single-process runs)
DUMP_ENV = "KF_CONFIG_TRACE_DUMP"
#: ring capacity override (events); default 65536
CAP_ENV = "KF_CONFIG_TIMELINE_CAP"

DEFAULT_CAP = 65536

#: the event vocabulary.  The ``trace-vocab`` kflint rule rejects any
#: ``span()``/``event()`` call site whose kind is not listed here — add
#: the kind FIRST, then the instrumentation.
EVENT_KINDS = frozenset({
    "collective",  # host-engine collective span (comm/engine.py)
    "device",      # device-plane collective span (comm/device.py)
    "send",        # host-channel frame egress mark, byte-counted
    "recv",        # host-channel frame ingress mark, byte-counted
    "retry",       # engine send retry after a transient wire fault
    "deadline",    # per-peer deadline exhausted -> PeerFailureError
    "signal",      # detector heartbeat intake (begin/end/epoch/...)
    "down",        # detector down verdict / local down report
    "shrink",      # shrink-to-survivors phase boundary
    "slice",       # slice-granular recovery phase (elastic/shrink.py:
                   # verdict / self-excluded / leader-consensus /
                   # propose / quorum-lost at the multislice grain)
    "chaos",       # fault injection fired (chaos/inject.py)
    "swap",        # consensus-fenced strategy/schedule swap (kf-adapt:
                   # monitor/adapt_device.py — host arm or device
                   # per-bucket schedule installed in lockstep)
    "overlap",     # async collective handle lifecycle (kf-overlap,
                   # comm/engine.py: "issue" / "complete" marks carrying
                   # tag, nbytes, and the in-flight queue depth).  A hot
                   # kind: recorded only when tracing is on — the
                   # always-on surfaces are the kf_overlap_inflight
                   # gauge and the kf_overlap_efficiency histogram.
                   # Since kf-xray, recorded marks also ride the monitor
                   # pushes (aggregator.REPORT_KINDS ⊇ xray.XRAY_KINDS:
                   # the online attribution needs the async-tag set)
    "serve",       # serving-plane engine/router lifecycle (kf-serve,
                   # serve/engine.py + serve/router.py: prefill/decode
                   # spans — hot, ring-only — plus the rare worker-dead/
                   # slice-dead/readmit marks of the serving fault
                   # ladder)
    "pp",          # pipeline-parallel lifecycle (kf-pipeline,
                   # parallel/pp.py): "fwd"/"bwd" stage-compute spans
                   # and the "bubble" span — the time a stage blocks on
                   # a cross-DCN activation/gradient hop — plus the
                   # rare "buddy-replicate"/"stage-recarve" marks of
                   # the elastic stage re-carve.  A hot kind, recorded
                   # only when tracing is on; recorded spans ride the
                   # monitor pushes (REPORT_KINDS) so kf-xray's online
                   # step decomposition attributes bubble time as its
                   # own phase (monitor/xray.py::PHASES pp_bubble)
    "input",       # input-pipeline wait span (kf-xray: the consumer-side
                   # block for the next batch — datasets/prefetch.py and
                   # any loader that wants its stall attributed.  A hot
                   # kind, one span per consumed batch, recorded only
                   # when tracing is on; recorded spans also ride the
                   # monitor pushes (REPORT_KINDS) so the online
                   # input_stall attribution sees them)
    "xray",        # kf-xray attribution mark (monitor/xray.py /
                   # ops/costmodel.py: the rank-local per-step phase
                   # split and MFU sample, so a dump carries the same
                   # decomposition the live gauges export)
    "request",     # serving request lifecycle mark (kf-serve router:
                   # "accept" / "reject" / "complete" / "replay" /
                   # "lost").  A counted kind: every mark ticks
                   # kf_serve_requests_total{what=<name>} even with
                   # tracing off, like the chaos/shrink counters
    "ckpt",        # durable persist plane (kf-persist,
                   # elastic/persist.py): "persist-issue" /
                   # "persist-done" marks around each async manifest
                   # write and the "restore" mark of a cold restart —
                   # rare boundary events, so always recordable; the
                   # always-on surfaces are the kf_ckpt_* gauges
    "alert",       # kf-sentinel rule firing (monitor/sentinel.py): a
                   # detector/burn-rate/watermark rule crossed its
                   # threshold and an incident flight record was cut.
                   # A counted kind labeled by RULE name: every firing
                   # ticks kf_alerts_total{rule=...} even with tracing
                   # off — an alert that /metrics cannot count did not
                   # happen
    "decision",    # adaptive-actor knob change (kf-ledger,
                   # monitor/ledger.py: a bandit swap, a batch-width
                   # move, an autoscale resize, a shrink — any actor
                   # writing a durable decision record).  A counted
                   # kind labeled by ACTOR name: every decision ticks
                   # kf_decisions_total{actor=...} even with tracing
                   # off — a knob change /metrics cannot count did not
                   # happen
    "pulse",       # gradient-signal sample mark (kf-pulse,
                   # monitor/pulse.py: the GNS/variance pair computed
                   # every KF_PULSE_EVERY steps).  A hot-ish kind,
                   # recorded only when tracing is on — the always-on
                   # surfaces are the kf_gns / kf_grad_variance /
                   # kf_grad_norm gauges
    "step",        # training-step mark
    "mark",        # generic one-shot annotation
})

#: kinds whose registry counters tick even with tracing off — rare
#: events that /metrics must count unconditionally.  Values are the
#: counter names; chaos/shrink additionally label by the event name
#: (a closed set: clause kinds / phase names).
_COUNTED_KINDS = {
    "retry": "kf_engine_retries_total",
    "deadline": "kf_peer_faults_total",
    "chaos": "kf_chaos_injections_total",
    "down": "kf_detector_down_total",
    "shrink": "kf_shrink_events_total",
    "slice": "kf_slice_events_total",
    "swap": "kf_strategy_swaps_total",
    "request": "kf_serve_requests_total",
    "alert": "kf_alerts_total",
    "decision": "kf_decisions_total",
}
_LABELED_KINDS = ("chaos", "shrink", "slice", "swap", "request", "alert",
                  "decision")
#: label KEY per labeled kind; default "what".  Alerts label by "rule"
#: so the counter reads kf_alerts_total{rule="regress:step_time_s"} —
#: the name SLO dashboards group by; decisions label by ACTOR the same
#: way (kf_decisions_total{actor="bandit-host"}).
_LABEL_KEYS = {"alert": "rule", "decision": "actor"}

_lock = threading.Lock()
_ring: collections.deque = collections.deque()
_cap: Optional[int] = None  # resolved lazily from CAP_ENV
_dropped = 0
_rank: Optional[int] = None
_step = -1

# -- causal context (kf-xray) ----------------------------------------------
# Every recorded span carries a ``(trace, span, parent)`` triple in its
# attrs: ``span`` is a process-unique id allocated at entry, ``trace``
# groups spans of one logical operation ACROSS ranks/processes, and
# ``parent`` is the enclosing span (same trace) when one exists.  Two
# propagation paths, chosen so the hot path ships no extra wire bytes:
#
# * **derived** — collective spans compute the SAME trace id on every
#   rank from values all ranks already agree on
#   (:func:`collective_trace_id` over (cluster_version, step, op, tag)),
#   so the cross-rank link costs zero wire bytes;
# * **explicit** — request/response flows (serve frames, p2p blob pulls)
#   carry a compact ``tc`` string in their existing JSON meta body; the
#   receiving side re-enters it via :func:`trace_ctx` so its spans and
#   events join the requester's trace.
#
# Ambient context is a per-thread stack: entering a span (or a
# :func:`trace_ctx`) pushes ``(trace, span_id)``; events and child spans
# recorded inside inherit it unless their call site passes explicit
# ``trace=``/``parent=`` attrs.
_span_seq = itertools.count(1)
_tls = threading.local()


def new_span_id() -> str:
    """Process-unique span id (``s<rank>.<n>``); deterministic given the
    event order, so replayed tests produce stable ids."""
    r = _rank if _rank is not None else "x"
    return f"s{r}.{next(_span_seq)}"


def collective_trace_id(version, step, op: str, tag: str) -> str:
    """Deterministic cross-rank trace id for one logical collective:
    every participating rank derives the identical id from values it
    already holds — the cluster version (mesh epoch), the current step,
    and the collective's op/tag — so the same collective links across
    ranks in a merged trace with NO extra wire bytes."""
    return f"c{version}.{step}.{op}.{tag}"


def _ctx_stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current_trace() -> Tuple[Optional[str], Optional[str]]:
    """``(trace_id, span_id)`` of the innermost ambient context on this
    thread, or ``(None, None)``."""
    st = _ctx_stack()
    return st[-1] if st else (None, None)


class trace_ctx:
    """Re-enter a received trace context: spans/events recorded inside
    join ``trace`` as children of ``parent`` (e.g. the serving worker
    handling a router frame whose meta carried ``tc``)."""

    __slots__ = ("trace", "parent")

    def __init__(self, trace: Optional[str], parent: Optional[str] = None):
        self.trace = trace
        self.parent = parent

    def __enter__(self):
        _ctx_stack().append((self.trace, self.parent))
        return self

    def __exit__(self, *exc):
        _ctx_stack().pop()
        return False


def parse_trace_context(tc) -> Tuple[Optional[str], Optional[str]]:
    """``(trace, parent)`` from the compact wire form ``"trace"`` or
    ``"trace@parent"`` (the ``tc`` meta field of serve/p2p frames);
    ``(None, None)`` on anything malformed — a bad peer must not break
    the receiver's recording."""
    if not isinstance(tc, str) or not tc:
        return None, None
    trace, sep, parent = tc.partition("@")
    if not trace:
        # "@x" and friends: an empty trace id would group unrelated
        # requests under one bogus "" trace — unlinked beats mislinked
        return None, None
    return trace, (parent or None) if sep else None


def format_trace_context(trace: Optional[str],
                         parent: Optional[str] = None) -> Optional[str]:
    """The compact wire form consumed by :func:`parse_trace_context`."""
    if not trace:
        return None
    return f"{trace}@{parent}" if parent else trace


def context_attrs(trace: Optional[str],
                  parent: Optional[str] = None) -> Dict[str, str]:
    """Span/event attrs for an explicitly-propagated context: empty when
    there is no (or an empty) trace, and never a literal ``None`` parent
    — the dump schema stays uniform with the ambient-merge paths, which
    omit absent keys entirely."""
    if not trace:
        return {}
    attrs = {"trace": trace}
    if parent is not None:
        attrs["parent"] = parent
    return attrs


def enabled() -> bool:
    """Whether the ring records (``KF_CONFIG_ENABLE_TRACE``)."""
    return trace_enabled()


def set_rank(rank: Optional[int]) -> None:
    """Default rank stamped on events whose call site passes none.
    (In-process multi-rank test clusters pass ``rank=`` explicitly at
    the rank-owning call sites; this default serves real one-rank-per-
    process workers and the dump filename.)"""
    global _rank
    _rank = rank


def set_step(step: int) -> None:
    """Current training step, stamped on subsequent events."""
    global _step
    _step = step


def current_step() -> int:
    """The step last stamped by :func:`set_step` (``-1`` before the
    first) — the live plane's reporter reads it for its snapshot."""
    return _step


def current_rank() -> Optional[int]:
    """The process-default rank installed by :func:`set_rank`."""
    return _rank


def _capacity() -> int:
    global _cap
    if _cap is None:
        try:
            _cap = max(1, int(os.environ.get(CAP_ENV, "") or DEFAULT_CAP))
        except ValueError:
            _cap = DEFAULT_CAP
    return _cap


def _append(ts: float, rank: Optional[int], kind: str, name: str,
            dur: float, attrs: Optional[Dict]) -> None:
    global _dropped
    ev = (ts, rank if rank is not None else _rank, _step, kind, name, dur,
          attrs or None)
    cap = _capacity()
    with _lock:
        if len(_ring) >= cap:
            # flight-recorder semantics: keep the newest, evict the
            # oldest, and count the loss so a truncated dump says so
            _ring.popleft()
            _dropped += 1
            REGISTRY.counter("kf_timeline_dropped_total").inc()
        _ring.append(ev)


def _count(kind: str, name: str) -> None:
    metric = _COUNTED_KINDS.get(kind)
    if metric is None:
        return
    if kind in _LABELED_KINDS:
        REGISTRY.counter(metric,
                         **{_LABEL_KEYS.get(kind, "what"): name}).inc()
    else:
        REGISTRY.counter(metric).inc()


def event(kind: str, name: str, rank: Optional[int] = None,
          force: bool = False, **attrs) -> None:
    """One-shot mark.  Counted kinds always tick their registry counter;
    the ring records only when tracing is enabled (or ``force``).  An
    ambient :func:`trace_ctx` (or enclosing span) stamps the mark's
    ``trace``/``parent`` unless the call site passed its own."""
    _count(kind, name)
    if not (force or trace_enabled()):
        return
    if "trace" not in attrs:
        tr, parent = current_trace()
        if tr is not None:
            attrs["trace"] = tr
            if parent is not None and "parent" not in attrs:
                attrs["parent"] = parent
    _append(time.time(), rank, kind, name, 0.0, attrs)


class _NoopSpan:
    """Shared disabled-path span where JAX is not loaded: no
    allocation, no timing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **attrs):
        pass


_NOOP_SPAN = _NoopSpan()

#: the prefix of every span's name on the profiler's clock
ANNOTATION_PREFIX = "kf:"
_SCALARS = (int, float, str, bool)


def _annotation(kind: str, name: str, attrs: Optional[Dict]):
    """The span as a ``jax.profiler.TraceAnnotation`` with its scalar
    attrs as the event's stats, or None in a process that has not
    imported JAX (which this module never does itself)."""
    prof = sys.modules.get("jax.profiler")
    if prof is None:
        return None
    if attrs:
        attrs = {k: v for k, v in attrs.items() if isinstance(v, _SCALARS)}
    return prof.TraceAnnotation(f"{ANNOTATION_PREFIX}{kind}.{name}",
                                **(attrs or {}))


class _Span:
    __slots__ = ("kind", "name", "rank", "attrs", "_t0", "_ts",
                 "span_id", "_trace", "_parent", "_note")

    def __init__(self, kind, name, rank, attrs):
        self.kind = kind
        self.name = name
        self.rank = rank
        self.attrs = attrs
        self._note = _annotation(kind, name, attrs)

    def set_metadata(self, **attrs):
        """Attrs known only once the region is under way (what an
        admission reused, what a commit fetched): the name and the
        meaning of ``TraceAnnotation.set_metadata``, which is what a
        call site holds when the ring is off."""
        self.attrs = dict(self.attrs or {}, **attrs)
        if self._note is not None:
            self._note.set_metadata(**attrs)

    def __enter__(self):
        # causal triple: explicit trace= attr wins; else inherit the
        # thread's ambient context.  The span then BECOMES the ambient
        # parent for everything recorded inside it.
        attrs = self.attrs
        trace = (attrs or {}).get("trace")
        parent = (attrs or {}).get("parent")
        if trace is None:
            trace, ambient_parent = current_trace()
            if parent is None:
                parent = ambient_parent
        self.span_id = new_span_id()
        self._trace, self._parent = trace, parent
        _ctx_stack().append((trace, self.span_id))
        if self._note is not None:
            self._note.__enter__()
        self._ts = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb):
        dt = time.perf_counter() - self._t0
        if self._note is not None:
            self._note.__exit__(et, ev, tb)
        _ctx_stack().pop()
        attrs = dict(self.attrs or {})
        if et is not None:
            attrs["error"] = et.__name__
        attrs["span"] = self.span_id
        if self._trace is not None:
            attrs["trace"] = self._trace
        if self._parent is not None:
            attrs["parent"] = self._parent
        _append(self._ts, self.rank, self.kind, self.name, dt, attrs)
        if self.kind in ("collective", "device"):
            op = (attrs or {}).get("op") if attrs else None
            REGISTRY.histogram(
                "kf_collective_latency_seconds",
                plane=self.kind, op=op or self.name,
            ).observe(dt)
        return False


def span(kind: str, name: str, rank: Optional[int] = None,
         force: bool = False, **attrs):
    """Timed region.  Always a profiler annotation ``kf:<kind>.<name>``
    (where JAX is loaded); with tracing on (or ``force``) it also
    records one event with ``dur`` set and, for collective/device kinds,
    feeds the per-op latency histogram."""
    if not (force or trace_enabled()):
        return _annotation(kind, name, attrs) or _NOOP_SPAN
    return _Span(kind, name, rank, attrs or None)


def dropped() -> int:
    with _lock:
        return _dropped


def snapshot() -> List[Dict]:
    """Current ring contents as dicts, oldest first."""
    with _lock:
        evs = list(_ring)
    return [
        {"ts": ts, "rank": r, "step": s, "kind": k, "name": n, "dur": d,
         "attrs": a or {}}
        for ts, r, s, k, n, d, a in evs
    ]


def events_tail(since: int, kinds: Optional[frozenset] = None
                ) -> Tuple[int, List[Dict]]:
    """``(cursor, events)``: every event appended after the ``since``
    cursor (0 = beginning of time), optionally kind-filtered, oldest
    first.  The cursor is the cumulative append count (evicted + live),
    so the cluster reporter's incremental read costs O(new events) per
    push and never re-sends or misses one — a timestamp filter would
    miss long spans, which are appended at exit carrying their *start*
    time.  Events evicted before the caller returned are simply gone
    (flight-recorder semantics; the drop counter says how many)."""
    with _lock:
        total = _dropped + len(_ring)
        start = max(0, since - _dropped)
        evs = list(_ring)[start:] if start < len(_ring) else []
    if kinds is not None:
        evs = [e for e in evs if e[3] in kinds]
    return total, [
        {"ts": ts, "rank": r, "step": s, "kind": k, "name": n, "dur": d,
         "attrs": a or {}}
        for ts, r, s, k, n, d, a in evs
    ]


def reset(cap: Optional[int] = None) -> None:
    """Clear the ring — tests and long-lived processes re-arming a
    capture.  ``cap`` pins a capacity; without it the next append
    re-resolves ``KF_CONFIG_TIMELINE_CAP``."""
    global _dropped, _cap, _step, _span_seq
    with _lock:
        _ring.clear()
        _dropped = 0
        _cap = max(1, cap) if cap is not None else None
        _step = -1
        _span_seq = itertools.count(1)  # stable span ids per capture


def dump_path_from_env() -> Optional[str]:
    """Resolve ``KF_CONFIG_TRACE_DUMP`` to this process's dump file, or
    None when dumping is not configured."""
    target = os.environ.get(DUMP_ENV, "").strip()
    if not target:
        return None
    if target.endswith(".jsonl"):
        return target
    r = _rank if _rank is not None else "x"
    return os.path.join(target, f"trace-r{r}-p{os.getpid()}.jsonl")


def dump(path: str) -> int:
    """Write the ring as JSONL (header line first); returns the event
    count written."""
    events = snapshot()
    header = {
        "kftrace": 1,
        "rank": _rank,
        "pid": os.getpid(),
        "dropped": dropped(),
        "wall": time.time(),
    }
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        f.write(json.dumps(header) + "\n")
        for ev in events:
            f.write(json.dumps(ev) + "\n")
    return len(events)


def maybe_dump() -> Optional[str]:
    """Dump to the env-configured path if set and the ring is non-empty;
    returns the path written (idempotent: later calls overwrite with a
    superset, so close + atexit double-firing is harmless)."""
    path = dump_path_from_env()
    if path is None:
        return None
    with _lock:
        if not _ring:
            return None
    try:
        n = dump(path)
    except OSError as e:
        _log.warning("cannot dump timeline to %s: %s", path, e)
        return None
    _log.info("%d event(s) dumped to %s", n, path)
    return path


atexit.register(maybe_dump)
