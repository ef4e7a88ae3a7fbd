"""The worker bootstrap env-var contract.

Parity with reference ``srcs/go/kungfu/env/envs.go:4-18`` and
``kungfu/config/config.go``: the launcher communicates everything a worker
needs through ``KF_*`` environment variables; unset envs fall back to
single-process mode (reference ``env/config.go:24-80``).

Bootstrap envs (written by the runner, read once at init):

==========================  ====================================================
``KF_SELF_SPEC``            this worker's ``host:port``
``KF_INIT_PEERS``           comma-separated worker list
``KF_INIT_RUNNERS``         comma-separated runner list
``KF_PARENT_ID``            runner that spawned us (``host:port``)
``KF_INIT_CLUSTER_VERSION`` integer mesh-epoch at spawn time
``KF_ALLREDUCE_STRATEGY``   host-engine strategy name (see plan.strategy)
``KF_DEVICE_STRATEGY``      device allreduce schedule (ops.schedules)
``KF_CONFIG_SERVER``        URL of the elastic config server
``KF_JOB_START_TIMESTAMP``  unix seconds the job started (event timeline)
``KF_PROC_START_TIMESTAMP`` unix seconds this process started
``KF_NUM_DEVICES``          virtual device count for CPU-backend clusters
``KF_COORDINATOR``          jax.distributed coordinator address
``KF_NUM_PROCESSES``        jax.distributed process count
``KF_PROCESS_ID``           jax.distributed process index
``KF_WORLD_PEERS``          full provisioned worker-slot list (max world).
                            When set, the jax.distributed world is booted
                            ONCE over ALL slots and elastic resize re-carves
                            the device mesh over the *active* subset — no
                            world re-init, surviving workers keep training
                            (reference live-resize semantics,
                            ``peer/peer.go:236-276``)
==========================  ====================================================

Tuning envs (read anywhere, any time):

=================================  ============================================
``KF_CONFIG_ENABLE_MONITORING``    "true"/"false"
``KF_CONFIG_MONITORING_PERIOD``    seconds, default 1
``KF_CONFIG_ENABLE_STALL_DETECTION`` "true"/"false"
``KF_CONFIG_LOG_LEVEL``            DEBUG/INFO/WARN/ERROR
``KF_CONFIG_STRATEGY_HASH_METHOD`` chunk→strategy hash: "simple"|"name"
``KF_CONFIG_WAIT_RUNNER_TIMEOUT``  s to wait for a runner before a resize
                                   notification is dropped, default 10
``KF_CONFIG_CHUNK_SIZE``           engine chunk bytes; default 1 MiB,
                                   or 256 KiB when all peers share one
                                   host (measured, engine.py).  Must be
                                   identical cluster-wide (set at the
                                   launcher; it propagates to workers)
``KF_CONFIG_ENGINE_THREADS``       native executor threads, default
                                   min(8, cores)
``KF_CONFIG_ENGINE_TIMEOUT``       per-collective timeout s, default 60
``KF_CONFIG_PEER_DEADLINE``        per-peer send/recv deadline s for one
                                   engine collective primitive; on
                                   exhaustion a typed PeerFailureError
                                   (suspect rank attached) replaces the
                                   hang/raw error — the entry point of
                                   shrink-to-survivors recovery.  Default
                                   = the engine timeout (comm/engine.py)
``KF_CONFIG_ENABLE_TRACE``         truthy: record flight-recorder
                                   timeline events (monitor/timeline.py;
                                   its spans are profiler annotations
                                   either way)
``KF_CONFIG_TRACE_DUMP``           timeline JSONL dump target: a
                                   directory (one trace-*.jsonl per
                                   process) or an exact *.jsonl path;
                                   written on Peer.close/exit and merged
                                   by scripts/kftrace
                                   (monitor/timeline.py)
``KF_CONFIG_TIMELINE_CAP``         flight-recorder ring capacity in
                                   events, default 65536; evictions are
                                   counted in kf_timeline_dropped_total
                                   (monitor/timeline.py)
``KF_CONFIG_ENABLE_CLUSTER_MONITOR`` truthy: each worker pushes live
                                   snapshots (step, counters, latency
                                   deltas, recent collective spans) to
                                   the cluster aggregator co-hosted with
                                   the config server; view with
                                   ``kftop`` (monitor/aggregator.py)
``KF_CONFIG_MONITOR_PUSH_PERIOD``  snapshot push interval seconds,
                                   default 1 (monitor/aggregator.py)
``KF_CONFIG_MONITOR_STALE_AFTER``  seconds without a snapshot before the
                                   aggregator flags a rank *stale*;
                                   default 3x the push period — well
                                   inside the failure detector's 10 s
                                   down verdict (monitor/aggregator.py)
``KF_CONFIG_P2P_RESPONDERS``       p2p blob responder pool size override;
                                   default scales with peer count via
                                   host_pool_size (store/p2p.py)
``KF_CONFIG_OVERLAP_DEPTH``        bound on in-flight async collective
                                   handles per engine (the kf-overlap
                                   window), default 2; issuing past it
                                   blocks until one completes.  Local
                                   backpressure only — tags and issue
                                   order are unchanged, so peers may
                                   legally run different depths
                                   (comm/engine.py; learnable via
                                   policy.bandit.OverlapDepthBandit)
``KF_CONFIG_HOST_POOL_MAX``        cap on the load-scaled host-plane
                                   responder/sender pools, default 16
                                   (wins over per-pool floors); current
                                   sizes exported as the
                                   kf_host_pool_size{pool=...} gauge
                                   (comm/host.py)
``KF_CONFIG_USE_AFFINITY``         truthy: partition host cores between
                                   colocated workers (utils/affinity.py)
``KF_CONFIG_WATCH_GRACE``          runner natural-end grace window s,
                                   default 10 (runner/watch.py)
``KF_XRAY_WINDOW_STEPS``           steps in the online kf-xray
                                   attribution window the aggregator
                                   serves under /cluster -> xray,
                                   default 32 (monitor/xray.py)
``KF_XRAY_PEAK_FLOPS``             per-chip peak FLOP/s pinned for the
                                   kf_mfu gauge, overriding TPU
                                   device-kind detection; unset on CPU
                                   meshes = no MFU, model-FLOPs rate
                                   only (ops/costmodel.py)
``KF_PP_STAGES``                   pipeline stages (the cross-DCN pp
                                   axis degree), default 1;
                                   ParallelPlan.from_env reads it so
                                   entrypoints stop hand-wiring the
                                   axis combination (parallel/train.py)
``KF_PP_MICROBATCHES``             pipeline microbatches per step, 0 =
                                   the stage count (the minimum that
                                   fills the pipe); parallel/train.py
``KF_PP_SCHEDULE``                 pipeline microbatch schedule: 1f1b
                                   (default) | interleaved |
                                   sequential (the naive baseline the
                                   bench gate measures against);
                                   parallel/train.py -> parallel/pp.py
=================================  ============================================

Transport / native-runtime envs:

=============================  ================================================
``KF_TPU_HOST_TRANSPORT``      host channel backend: "auto"|"native"|"python"
                               (comm/host.py)
``KF_TPU_USE_UNIXSOCK``        "0" disables the colocated-peer unix sockets;
                               default on (comm/host.py)
``KF_SOCK_DIR``                unix sockfile directory override; default
                               /tmp/kf-tpu-<uid> (comm/host.py AND
                               native/transport.cpp — keep in lockstep)
``KF_TPU_NO_NATIVE``           "1" skips the native .so entirely (numpy +
                               python-transport fallbacks, native/__init__.py)
``KF_NATIVE_ENGINE``           "0"/"false"/"no" disables the fully-native
                               collective executor; default on (comm/engine.py)
``KF_NATIVE_MARCH``            build the native .so with -march=<value>
                               (homogeneous clusters only; native/__init__.py)
``KF_NATIVE_SANITIZE``         "tsan"|"asan": load the sanitizer-instrumented
                               native build variant (libkfnative-<v>.so) for
                               race/memory debugging (native/__init__.py)
``KF_MONITOR_ADDR``            failure-detector endpoint workers report to
                               (monitor/signals.py; set by the runner)
=============================  ================================================

Multislice (TPU pod) envs — the ``MEGASCALE_*`` names are the TPU
runtime's contract, read by :mod:`kungfu_tpu.platforms.tpu_pod` and the
slice topology layer (:mod:`kungfu_tpu.elastic.slices`):

=================================  ============================================
``MEGASCALE_COORDINATOR_ADDRESS``  multislice DCN coordinator (slice 0 host 0)
``MEGASCALE_SLICE_ID``             this host's slice index; in the CPU-mesh
                                   emulation contract the launcher sets it
                                   per worker (= worker rank // ranks/slice)
``MEGASCALE_NUM_SLICES``           total slice count; >1 switches the peer to
                                   the hierarchical ICI-within / DCN-across
                                   communicator and slice-granular elasticity
``KF_SLICE_RANKS``                 worker ranks per slice, pinned by the
                                   launcher (``kfrun -num-slices``); without
                                   it the topology derives ranks/slice from
                                   the bootstrap worker count
=================================  ============================================

Serving envs (the kf-serve inference plane, :mod:`kungfu_tpu.serve`;
see docs/serving.md):

=============================  ================================================
``KF_SERVE_QUEUE_DEPTH``       router admission bound: accepted-but-unfinished
                               requests past it are rejected with the typed
                               ``ServeOverloadError`` instead of queueing
                               unboundedly; default 64 (serve/router.py)
``KF_SERVE_PAGE_TOKENS``       tokens per KV-cache page, default 16
                               (serve/kvcache.py)
``KF_SERVE_KV_PAGES``          KV-cache pool capacity in pages, default 512;
                               the per-rank footprint is the
                               ``kf_kv_cache_bytes`` gauge (serve/kvcache.py)
``KF_SERVE_MAX_BATCH``         decode batch width (continuous-batching slots)
                               per engine, default 8; the policy layer's
                               BatchWidthController moves the *admitted* width
                               under this cap (serve/engine.py)
``KF_SERVE_MAX_TOKENS``        per-request new-token cap, default 256
                               (serve/engine.py)
``KF_SERVE_COMMIT_EVERY``      decode positions between progress commits to
                               the router (the replay boundary after a worker
                               death), default 8 (serve/router.py)
``KF_SERVE_REQUEST_DEADLINE``  router per-request progress deadline seconds
                               (no progress/completion within it = a strike
                               against the worker; strikes escalate to the
                               dead-worker ladder), default 60
                               (serve/router.py)
``KF_SERVE_SLO_TTFT_MS``       time-to-first-token SLO target ms, default 500
                               (serve/slo.py)
``KF_SERVE_SLO_E2E_MS``        end-to-end request SLO target ms, default 5000
                               (serve/slo.py)
=============================  ================================================

Persistence envs (the durable state plane,
:mod:`kungfu_tpu.elastic.persist`; see docs/persistence.md):

=============================  ================================================
``KF_PERSIST_DIR``             manifest root for durable checkpoints; unset =
                               the persist plane is off (``kfrun
                               -persist-dir`` / ``-restore-from`` set it)
``KF_PERSIST_PERIOD``          seconds between issued persists, default 30.0;
                               0 = persist at every commit (demos/tests)
``KF_PERSIST_ASYNC_DEPTH``     max in-flight async persist handles before
                               issue blocks on the oldest, default 2
``KF_PERSIST_KEEP``            keep-last-k complete manifests retained by
                               rank-0 GC (min 1), default 3
``KF_PERSIST_RESTORE``         truthy = restore-armed start: the worker
                               agrees on and restores the newest complete
                               manifest before training (set by ``kfrun
                               -restore-from``)
=============================  ================================================

Sentinel envs (the kf-sentinel judging plane,
:mod:`kungfu_tpu.monitor.sentinel`; see docs/sentinel.md — the sentinel
and kfhist read these tokens from ``os.environ`` directly via mirror
constants, like timeline.py's CAP_ENV, so the stubbed kfhist/CI context
never imports this jax-adjacent module; :func:`sentinel_knobs` below
pins the defaults both sides must agree on):

=============================  ================================================
``KF_SENTINEL_DIR``            durable metrics-history root; unset = the
                               whole sentinel plane is off and aggregator
                               behavior is byte-identical (``kfrun
                               -sentinel`` sets it)
``KF_SENTINEL_KEEP_BYTES``     per-stream history ring byte budget,
                               default 8 MiB; oldest sealed segments are
                               GC'd past it (monitor/history.py)
``KF_SENTINEL_PERIOD``         seconds between sentinel samples, default
                               1.0; <= 0 samples on every aggregator
                               ingest (tests)
``KF_SENTINEL_WINDOW``         changepoint window in samples, default 8
                               (monitor/detect.py)
``KF_SENTINEL_THRESHOLD``      median-shift score (MAD multiples) before
                               a series alerts, default 4.0
``KF_SENTINEL_MFU_FLOOR``      MFU watermark: alert when the cluster MFU
                               mean sinks below it; default 0 = off
``KF_SENTINEL_STEP_CEILING_S`` step-time watermark seconds; default 0 =
                               off
``KF_SENTINEL_WARMUP_STEPS``   steps considered warmup, default 32; XLA
                               recompiles AFTER it raise the
                               recompile-steady alert
``KF_SENTINEL_INCIDENT_WINDOW`` history records embedded in an incident
                               flight record, default 64
``KF_SENTINEL_SLO_SHORT``      SLO burn-rate short window in samples,
                               default 6 (serve/slo.py SLORules)
``KF_SENTINEL_SLO_LONG``       SLO burn-rate long window in samples,
                               default 24 (serve/slo.py SLORules)
=============================  ================================================

Pulse envs (kf-pulse gradient-signal monitoring,
:mod:`kungfu_tpu.monitor.pulse`; see docs/pulse.md — the pulse module
reads these via mirror constants, same stdlib-only doctrine as the
sentinel; :func:`pulse_knobs` pins the shared defaults):

=============================  ================================================
``KF_PULSE_EVERY``             sample the gradient-noise-scale /
                               variance pair every N training steps,
                               default 10; <= 0 disables the pulse
                               plane (``PulseMonitor.from_env`` returns
                               None and the step is byte-identical)
``KF_PULSE_EMA``               EMA weight for smoothing the per-sample
                               GNS/variance estimates, default 0.2
=============================  ================================================

Fault-injection envs (the chaos layer, :mod:`kungfu_tpu.chaos`; see
docs/fault_tolerance.md for the full matrix):

=============================  ================================================
``KF_CHAOS_SPEC``              deterministic fault clauses
                               (``die``/``die_slice``/``reset``/``delay``/
                               ``drop_fanout``/``drop_request``/
                               ``config_down``; grammar in chaos/spec.py).
                               Unset = every injection hook is a zero-cost
                               no-op and behavior is byte-identical to an
                               injection-free build
``KF_CHAOS_SEED``              integer seed for the only randomized
                               perturbation (delay jitter), default 0
=============================  ================================================

Protocol-verifier envs (the kf-verify static SPMD checker,
:mod:`kungfu_tpu.analysis.protoverify`; see docs/lint.md):

=============================  ================================================
``KF_VERIFY_MAX_RANKS``        largest world size the geometry sweep
                               enumerates ParallelPlans for, default 16
``KF_VERIFY_GEOMETRY_CAP``     hard cap on geometries simulated per family
                               (0 = unlimited), default 0
``KF_VERIFY_TIMEOUT_S``        wall-clock budget for the whole geometry
                               sweep in seconds, default 60.0; on expiry
                               the sweep reports how many geometries it
                               covered instead of silently truncating
=============================  ================================================

Kernel / model / data selection envs:

=============================  ================================================
``KF_JAX_PLATFORM``            jax platform for workers ("cpu"|"tpu"|...);
                               runner sets "cpu" for local clusters (peer.py)
``KF_DATA_DIR``                dataset cache root, default ~/.cache/kungfu_tpu
                               (datasets/cache.py)
``KF_TPU_CKPT_BACKEND``        checkpoint backend: "auto"|"orbax"|"npz"
                               (checkpoint.py)
``KF_TPU_ATTN``                attention impl: "auto"|"flash"|"plain"
                               (models/transformer.py)
``KF_TPU_LM_HEAD``             lm-head impl: "auto"|"fused"|"plain"
                               (models/transformer.py)
``KF_TPU_XENT``                cross-entropy impl: "auto"|"fused"|"plain"|
                               "xla" (ops/pallas/xent.py)
``KF_TPU_BN_COMPUTE``          "f32" restores legacy f32 batch-norm compute
                               (models/nn.py)
``KF_PALLAS_BWD``              "pallas" forces the pallas backward kernels
                               even under interpret mode (ops/pallas)
``KF_PALLAS_COLLECTIVES``      ring-collective impl: "auto"|"pallas"|"lax"
                               (ops/pallas/collectives.py; launch-set,
                               read at import)
``KF_XENT_FWD_MIN_ELEMENTS``   min logits elements before the fused xent
                               forward engages (ops/pallas/xent.py)
``KF_XENT_XLA_BUDGET_MB``      logits-bytes budget under which plain XLA
                               xent is preferred (ops/pallas/xent.py)
=============================  ================================================

Not an env var (registered so the ``KF_*`` contract scan covers C++):
``KF_SIMD_CLONES`` is a compile-time macro in native/reduce.cpp selecting
per-ISA function cloning.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Optional

from kungfu_tpu.plan.cluster import Cluster
from kungfu_tpu.plan.peer import PeerID, parse_peer_id
from kungfu_tpu.plan.peerlist import PeerList
from kungfu_tpu.plan.strategy import Strategy, parse_strategy

#: launch-set knob objects (import-time env reads with an explicit
#: ``reload()`` — the recompile-hazard hoist pattern of ops/pallas):
#: every instance registers here so tooling that mutates the
#: environment (tests above all) can re-read ALL of them without
#: enumerating modules by hand
LAUNCH_KNOBS: list = []


def register_launch_knobs(knobs):
    """Track a reload()-able launch-knob object; returns it."""
    LAUNCH_KNOBS.append(knobs)
    return knobs


class LaunchKnobs:
    """Base for a set of launch-set env knobs: subclasses implement
    ``_read(self)`` — read ``os.environ``, validate loudly (ValueError
    on a typo beats silently mis-routing), assign attributes.  The env
    is read at CONSTRUCTION (import time) and on explicit
    :meth:`reload`, never at trace time — the recompile-hazard hoist —
    and every instance auto-registers for :func:`reload_launch_knobs`
    so tooling that mutates the environment can re-read all knobs
    without enumerating modules."""

    def __init__(self):
        self._read()
        register_launch_knobs(self)

    def reload(self):
        """Re-read the current environment; returns self."""
        self._read()
        return self

    def _read(self) -> None:
        raise NotImplementedError


def reload_launch_knobs() -> None:
    """Re-read every registered launch-set knob from the current
    environment (test teardowns; config tools)."""
    for k in LAUNCH_KNOBS:
        k.reload()


# bootstrap envs
SELF_SPEC = "KF_SELF_SPEC"
INIT_PEERS = "KF_INIT_PEERS"
INIT_RUNNERS = "KF_INIT_RUNNERS"
PARENT_ID = "KF_PARENT_ID"
INIT_CLUSTER_VERSION = "KF_INIT_CLUSTER_VERSION"
ALLREDUCE_STRATEGY = "KF_ALLREDUCE_STRATEGY"
DEVICE_STRATEGY = "KF_DEVICE_STRATEGY"
CONFIG_SERVER = "KF_CONFIG_SERVER"
JOB_START_TIMESTAMP = "KF_JOB_START_TIMESTAMP"
PROC_START_TIMESTAMP = "KF_PROC_START_TIMESTAMP"
NUM_DEVICES = "KF_NUM_DEVICES"
COORDINATOR = "KF_COORDINATOR"
NUM_PROCESSES = "KF_NUM_PROCESSES"
PROCESS_ID = "KF_PROCESS_ID"
WORLD_PEERS = "KF_WORLD_PEERS"

# tuning envs
ENABLE_MONITORING = "KF_CONFIG_ENABLE_MONITORING"
MONITORING_PERIOD = "KF_CONFIG_MONITORING_PERIOD"
ENABLE_STALL_DETECTION = "KF_CONFIG_ENABLE_STALL_DETECTION"
LOG_LEVEL = "KF_CONFIG_LOG_LEVEL"
STRATEGY_HASH_METHOD = "KF_CONFIG_STRATEGY_HASH_METHOD"
WAIT_RUNNER_TIMEOUT = "KF_CONFIG_WAIT_RUNNER_TIMEOUT"
CHUNK_SIZE = "KF_CONFIG_CHUNK_SIZE"
ENGINE_THREADS = "KF_CONFIG_ENGINE_THREADS"
ENGINE_TIMEOUT = "KF_CONFIG_ENGINE_TIMEOUT"
PEER_DEADLINE = "KF_CONFIG_PEER_DEADLINE"
HOST_POOL_MAX = "KF_CONFIG_HOST_POOL_MAX"
P2P_RESPONDERS = "KF_CONFIG_P2P_RESPONDERS"
OVERLAP_DEPTH = "KF_CONFIG_OVERLAP_DEPTH"

# observability envs (read by kungfu_tpu/monitor/timeline.py, which
# defines mirror constants next to its reader code; registered here so
# the env-contract scan anchors them like every other KF_* knob)
TRACE_DUMP = "KF_CONFIG_TRACE_DUMP"
TIMELINE_CAP = "KF_CONFIG_TIMELINE_CAP"

# live cluster-monitor envs (monitor/aggregator.py: per-rank snapshot
# pushes to the aggregator co-hosted with the config server)
ENABLE_CLUSTER_MONITOR = "KF_CONFIG_ENABLE_CLUSTER_MONITOR"
MONITOR_PUSH_PERIOD = "KF_CONFIG_MONITOR_PUSH_PERIOD"
MONITOR_STALE_AFTER = "KF_CONFIG_MONITOR_STALE_AFTER"

# kf-xray envs (monitor/xray.py + ops/costmodel.py define mirror
# constants next to their readers, like timeline.py's CAP_ENV; the
# env-contract scan anchors the tokens here)
XRAY_WINDOW_STEPS = "KF_XRAY_WINDOW_STEPS"
XRAY_PEAK_FLOPS = "KF_XRAY_PEAK_FLOPS"

# pipeline-parallel envs (kf-pipeline: read by ParallelPlan.from_env in
# parallel/train.py, consumed by parallel/pp.py)
PP_STAGES = "KF_PP_STAGES"
PP_MICROBATCHES = "KF_PP_MICROBATCHES"
PP_SCHEDULE = "KF_PP_SCHEDULE"

# multislice envs.  The MEGASCALE_* names are the TPU runtime's own
# contract (libtpu/GKE publish them on every pod host; the emulation
# contract sets them per worker process — platforms/tpu_pod.py);
# KF_SLICE_RANKS is this framework's addition: the launcher pins the
# ranks-per-slice so elastic membership changes cannot break the
# bootstrap-derived slice mapping.  Registered here so the env-contract
# scan anchors them instead of module-local constants drifting.
MEGASCALE_COORDINATOR = "MEGASCALE_COORDINATOR_ADDRESS"
MEGASCALE_SLICE_ID = "MEGASCALE_SLICE_ID"
MEGASCALE_NUM_SLICES = "MEGASCALE_NUM_SLICES"
SLICE_RANKS = "KF_SLICE_RANKS"

# serving envs (read by kungfu_tpu/serve via these constants; registered
# here so the env-contract scan anchors the kf-serve knobs to the same
# registry as every other KF_* token)
SERVE_QUEUE_DEPTH = "KF_SERVE_QUEUE_DEPTH"
SERVE_PAGE_TOKENS = "KF_SERVE_PAGE_TOKENS"
SERVE_KV_PAGES = "KF_SERVE_KV_PAGES"
SERVE_MAX_BATCH = "KF_SERVE_MAX_BATCH"
SERVE_MAX_TOKENS = "KF_SERVE_MAX_TOKENS"
SERVE_COMMIT_EVERY = "KF_SERVE_COMMIT_EVERY"
SERVE_REQUEST_DEADLINE = "KF_SERVE_REQUEST_DEADLINE"
SERVE_SLO_TTFT_MS = "KF_SERVE_SLO_TTFT_MS"
SERVE_SLO_E2E_MS = "KF_SERVE_SLO_E2E_MS"

# persistence envs (read by kungfu_tpu/elastic/persist.py via
# persist_knobs() at plane construction and by the runner's supervisor
# path; registered here so the env-contract scan anchors the kf-persist
# knobs to the same registry as every other KF_* token)
PERSIST_DIR = "KF_PERSIST_DIR"
PERSIST_PERIOD = "KF_PERSIST_PERIOD"
PERSIST_ASYNC_DEPTH = "KF_PERSIST_ASYNC_DEPTH"
PERSIST_KEEP = "KF_PERSIST_KEEP"
PERSIST_RESTORE = "KF_PERSIST_RESTORE"

# kf-sentinel envs (monitor/sentinel.py + monitor/history.py define
# mirror constants next to their readers and parse os.environ directly —
# the stubbed kfhist/kftop context cannot import this module; registered
# here so the env-contract scan anchors the tokens, and sentinel_knobs()
# below pins the defaults both sides must agree on)
SENTINEL_DIR = "KF_SENTINEL_DIR"
SENTINEL_KEEP_BYTES = "KF_SENTINEL_KEEP_BYTES"
SENTINEL_PERIOD = "KF_SENTINEL_PERIOD"
SENTINEL_WINDOW = "KF_SENTINEL_WINDOW"
SENTINEL_THRESHOLD = "KF_SENTINEL_THRESHOLD"
SENTINEL_MFU_FLOOR = "KF_SENTINEL_MFU_FLOOR"
SENTINEL_STEP_CEILING_S = "KF_SENTINEL_STEP_CEILING_S"
SENTINEL_WARMUP_STEPS = "KF_SENTINEL_WARMUP_STEPS"
SENTINEL_INCIDENT_WINDOW = "KF_SENTINEL_INCIDENT_WINDOW"
SENTINEL_SLO_SHORT = "KF_SENTINEL_SLO_SHORT"
SENTINEL_SLO_LONG = "KF_SENTINEL_SLO_LONG"

# kf-pulse envs (monitor/pulse.py defines mirror constants next to its
# reader, same doctrine as the sentinel tokens above; pulse_knobs()
# below pins the defaults both sides must agree on)
PULSE_EVERY = "KF_PULSE_EVERY"
PULSE_EMA = "KF_PULSE_EMA"

# fault-injection envs (read by kungfu_tpu/chaos/inject.py at controller
# creation; registered here so the env-contract scan anchors them to the
# same registry as every other KF_* knob)
CHAOS_SPEC = "KF_CHAOS_SPEC"
CHAOS_SEED = "KF_CHAOS_SEED"

# protocol-verifier envs (read by kungfu_tpu/analysis/protoverify.py via
# os.environ directly — the analysis package is stdlib-only and must not
# import this jax-adjacent module; registered here so the env-contract
# scan anchors the kf-verify knobs to the same registry, and
# verify_knobs() below pins the defaults both sides must agree on)
VERIFY_MAX_RANKS = "KF_VERIFY_MAX_RANKS"
VERIFY_GEOMETRY_CAP = "KF_VERIFY_GEOMETRY_CAP"
VERIFY_TIMEOUT_S = "KF_VERIFY_TIMEOUT_S"

ALL_BOOTSTRAP_ENVS = [
    SELF_SPEC, INIT_PEERS, INIT_RUNNERS, PARENT_ID, INIT_CLUSTER_VERSION,
    ALLREDUCE_STRATEGY, CONFIG_SERVER, JOB_START_TIMESTAMP,
    PROC_START_TIMESTAMP, NUM_DEVICES, COORDINATOR, NUM_PROCESSES, PROCESS_ID,
    WORLD_PEERS,
]


def parse_bool_env(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def parse_int_env(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def parse_float_env(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def verify_knobs() -> dict:
    """The kf-verify geometry-sweep knobs, parsed with their defaults.

    protoverify._knobs() reads the same tokens from ``os.environ``
    directly (it cannot import this module); tests pin that both sides
    use these exact defaults so the documented contract cannot drift.
    """
    return {
        "max_ranks": parse_int_env(VERIFY_MAX_RANKS, 16),
        "geometry_cap": parse_int_env(VERIFY_GEOMETRY_CAP, 0),
        "timeout_s": parse_float_env(VERIFY_TIMEOUT_S, 60.0),
    }


def persist_knobs() -> dict:
    """The kf-persist plane knobs, parsed with their defaults
    (elastic/persist.py constructs a :class:`~kungfu_tpu.elastic.
    persist.PersistPlane` from these; kfrun's ``-persist-dir`` /
    ``-restore-from`` flags export the dir + restore arm)."""
    return {
        "dir": os.environ.get(PERSIST_DIR, ""),
        "period_s": parse_float_env(PERSIST_PERIOD, 30.0),
        "depth": parse_int_env(PERSIST_ASYNC_DEPTH, 2),
        "keep": parse_int_env(PERSIST_KEEP, 3),
        "restore": parse_bool_env(PERSIST_RESTORE, False),
    }


def sentinel_knobs() -> dict:
    """The kf-sentinel plane knobs, parsed with their defaults.

    monitor/sentinel.py reads the same tokens from ``os.environ``
    directly (the stubbed kfhist context cannot import this module);
    tests pin that both sides use these exact defaults so the
    documented contract cannot drift.
    """
    return {
        "dir": os.environ.get(SENTINEL_DIR, ""),
        "keep_bytes": parse_int_env(SENTINEL_KEEP_BYTES, 8 << 20),
        "period_s": parse_float_env(SENTINEL_PERIOD, 1.0),
        "window": parse_int_env(SENTINEL_WINDOW, 8),
        "threshold": parse_float_env(SENTINEL_THRESHOLD, 4.0),
        "mfu_floor": parse_float_env(SENTINEL_MFU_FLOOR, 0.0),
        "step_ceiling_s": parse_float_env(SENTINEL_STEP_CEILING_S, 0.0),
        "warmup_steps": parse_int_env(SENTINEL_WARMUP_STEPS, 32),
        "incident_window": parse_int_env(SENTINEL_INCIDENT_WINDOW, 64),
        "slo_short": parse_int_env(SENTINEL_SLO_SHORT, 6),
        "slo_long": parse_int_env(SENTINEL_SLO_LONG, 24),
    }


def pulse_knobs() -> dict:
    """The kf-pulse plane knobs, parsed with their defaults.

    monitor/pulse.py reads the same tokens from ``os.environ`` directly
    (mirror constants, same doctrine as :func:`sentinel_knobs`); tests
    pin that both sides use these exact defaults so the documented
    contract cannot drift.
    """
    return {
        "every": parse_int_env(PULSE_EVERY, 10),
        "ema": parse_float_env(PULSE_EMA, 0.2),
    }


@dataclass
class Config:
    """Parsed bootstrap configuration for one worker process."""

    self_id: PeerID
    cluster: Cluster
    parent: Optional[PeerID] = None
    strategy: Strategy = Strategy.AUTO
    #: initial device-plane allreduce schedule ("" = psum default)
    device_strategy: str = ""
    init_version: int = 0
    config_server: str = ""
    single_process: bool = False
    coordinator: str = ""
    num_processes: int = 1
    process_id: int = 0
    #: full provisioned worker-slot list; None = fixed world (world == the
    #: initial worker list, resize beyond it needs relaunched processes)
    world_peers: Optional[PeerList] = None
    job_start: float = field(default_factory=time.time)
    proc_start: float = field(default_factory=time.time)

    @property
    def detached(self) -> bool:
        """True when self is not a member of the current worker list."""
        return self.cluster.workers.rank(self.self_id) is None

    @property
    def rank(self) -> int:
        r = self.cluster.workers.rank(self.self_id)
        if r is None:
            raise RuntimeError(
                f"peer {self.self_id} is not in the worker list {self.cluster.workers}"
            )
        return r

    @property
    def size(self) -> int:
        return self.cluster.size()


def parse_config_from_env(env=None) -> Config:
    """Parse the bootstrap contract; fall back to single-process mode when
    ``KF_SELF_SPEC`` is unset (reference ``env/config.go:24-80``)."""
    env = env if env is not None else os.environ
    self_spec = env.get(SELF_SPEC)
    if not self_spec:
        c = Cluster.single_process()
        return Config(self_id=c.workers[0], cluster=c, single_process=True,
                      device_strategy=env.get(DEVICE_STRATEGY, ""))
    self_id = parse_peer_id(self_spec)
    workers = PeerList.parse(env.get(INIT_PEERS, self_spec))
    runners_spec = env.get(INIT_RUNNERS, "")
    if runners_spec:
        runners = PeerList.parse(runners_spec)
    else:
        # no runner daemon (mp-spawn / test mode): synthesize one per host
        from kungfu_tpu.plan.hostspec import DEFAULT_RUNNER_PORT

        runners = PeerList(tuple(PeerID(h, DEFAULT_RUNNER_PORT) for h in workers.hosts()))
    cluster = Cluster(runners, workers)
    cluster.validate()
    parent = parse_peer_id(env[PARENT_ID]) if env.get(PARENT_ID) else None
    world_spec = env.get(WORLD_PEERS, "")
    world = PeerList.parse(world_spec) if world_spec else None
    if world is not None and world.rank(self_id) is None:
        raise ValueError(f"{WORLD_PEERS} set but {self_id} is not a slot in {world}")
    # with a provisioned world, the jax process identity is the WORLD slot
    # index (stable across resizes), not the elastic worker rank
    num_processes = int(env.get(NUM_PROCESSES, str(len(world)) if world else "1"))
    process_id = int(env.get(PROCESS_ID, str(world.rank(self_id)) if world else "0"))
    return Config(
        self_id=self_id,
        cluster=cluster,
        parent=parent,
        strategy=parse_strategy(env.get(ALLREDUCE_STRATEGY, "AUTO")),
        device_strategy=env.get(DEVICE_STRATEGY, ""),
        init_version=int(env.get(INIT_CLUSTER_VERSION, "0")),
        config_server=env.get(CONFIG_SERVER, ""),
        coordinator=env.get(COORDINATOR, ""),
        num_processes=num_processes,
        process_id=process_id,
        world_peers=world,
        job_start=float(env.get(JOB_START_TIMESTAMP, time.time())),
        proc_start=float(env.get(PROC_START_TIMESTAMP, time.time())),
    )


def single_machine_env(rank: int, size: int, host: str = "127.0.0.1") -> dict:
    """Env dict for mp-spawned single-machine workers
    (reference ``env/config.go:59`` SingleMachineEnv)."""
    from kungfu_tpu.plan.hostspec import DEFAULT_PORT_RANGE

    lo, _ = DEFAULT_PORT_RANGE
    peers = ",".join(f"{host}:{lo + i}" for i in range(size))
    return {
        SELF_SPEC: f"{host}:{lo + rank}",
        INIT_PEERS: peers,
        INIT_RUNNERS: f"{host}:38080",
        INIT_CLUSTER_VERSION: "0",
    }
