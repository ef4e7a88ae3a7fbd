"""Graph-driven collective engine over the host channel.

Direct capability parity with the reference's Go collective engine
(``srcs/go/kungfu/session/{session,allreduce,shard}.go``): collectives
executed by walking (reduce-graph, broadcast-graph) pairs generated from
the 8 named strategies, with buffers **chunked** and each chunk hashed onto
a strategy pair for multi-graph load balancing (``session.go:292-321``,
``shard.go:11-31``).

Role in the TPU build: the **multi-process data path when no shared XLA
mesh exists** — N worker processes (CPU backend tests, or gossip/elastic
phases between mesh epochs) allreduce gradients over TCP exactly like the
reference; the TPU hot path remains :mod:`kungfu_tpu.comm.device`.  This is
also where strategy adaptation is observable: each engine call records
per-strategy throughput (see :mod:`kungfu_tpu.monitor`).

The reduce inner loop runs in the native C++ module
(:mod:`kungfu_tpu.native`, the ``std_transform_2`` analog) with a numpy
fallback when the native build is unavailable.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from kungfu_tpu import native
from kungfu_tpu.chaos import controller_for as _chaos_controller_for
from kungfu_tpu.comm.faults import PeerFailureError
from kungfu_tpu.comm.host import CONNECT_TIMEOUT_S, ConnType, HostChannel
from kungfu_tpu.monitor import timeline
from kungfu_tpu.monitor.registry import REGISTRY
from kungfu_tpu.utils import envs
from kungfu_tpu.utils.retry import sleep_backoff
from kungfu_tpu.plan import (
    Strategy,
    auto_select,
    gen_binary_tree,
    gen_binary_tree_star,
    gen_circular_graph_pair,
    gen_multi_binary_tree_star,
    gen_multi_star,
    gen_star,
    gen_tree,
)
from kungfu_tpu.plan.topology import (
    gen_clique,
    gen_cross_binary_tree,
    gen_cross_ring_pairs,
)
from kungfu_tpu.plan.graph import Graph
from kungfu_tpu.plan.peerlist import PeerList
from kungfu_tpu.utils.log import get_logger

_log = get_logger("engine")

CHUNK_SIZE = 1 << 20  # 1 MiB, reference session.go:292-316


#: colocated peers (single-host cluster, unix-socket transport) pipeline
#: the socket→reduce stages best with smaller chunks: on a loopback
#: harness before PR 1, RING over 256 KiB chunks reached 1.03 GiB/s bus
#: bandwidth at np=4 where the 1 MiB reference default got 0.66 (a CPU
#: run, not repeated); cross-host traffic keeps the reference's 1 MiB.
CHUNK_SIZE_COLOCATED = 256 << 10


def engine_chunk_size(colocated: bool = False) -> int:
    """Chunk size for graph sharding (``KF_CONFIG_CHUNK_SIZE`` bytes).
    MUST be identical on every peer — chunk boundaries and tags derive
    from it, and a mismatch surfaces as collective timeouts.  The
    launcher propagates the launcher-shell env to all workers, so set it
    where the job is launched, not per worker (``colocated`` is derived
    from the shared peer list, so it is consistent by construction).
    Non-positive values fall back to the default (0 would
    divide-by-zero the chunk count)."""
    default = CHUNK_SIZE_COLOCATED if colocated else CHUNK_SIZE
    v = envs.parse_int_env(envs.CHUNK_SIZE, default)
    return v if v > 0 else default


def engine_threads() -> int:
    """Native executor worker threads (``KF_CONFIG_ENGINE_THREADS``).
    Default adapts to the machine: on a 1-core CI box thread thrash
    costs ~20% (measured), on real hosts chunk parallelism wins."""
    import os

    return envs.parse_int_env(
        envs.ENGINE_THREADS, min(8, max(1, os.cpu_count() or 1))
    )


def engine_timeout_s() -> float:
    """Native executor per-collective timeout (``KF_CONFIG_ENGINE_TIMEOUT``
    seconds) — round-2 VERDICT: a large slow-network collective must be
    tunable past the old hardcoded 60 s."""
    return envs.parse_float_env(envs.ENGINE_TIMEOUT, 60.0)


def overlap_depth_default() -> int:
    """Bound on in-flight async collective handles per engine
    (``KF_CONFIG_OVERLAP_DEPTH``, default 2).  Issuing past the window
    blocks the caller until a handle completes — the backpressure that
    keeps a depth-k software pipeline from ballooning into
    buffer-everything.  Purely local: the window changes *when* this
    process's collectives run, never their tags or issue order, so peers
    may legally run different depths (and the depth is a learnable knob,
    :class:`kungfu_tpu.policy.bandit.OverlapDepthBandit`).  Non-positive
    values fall back to the default, like every engine env reader
    (``engine_chunk_size``); depth 1 IS the serial window — set that to
    disable overlap."""
    v = envs.parse_int_env(envs.OVERLAP_DEPTH, 2)
    return v if v > 0 else 2


def peer_deadline_s() -> float:
    """Per-peer deadline for one collective primitive
    (``KF_CONFIG_PEER_DEADLINE`` seconds; default = the engine timeout).
    A send/recv that cannot complete toward one peer within this window
    raises :class:`PeerFailureError` carrying the suspect rank instead of
    hanging — the entry point of the shrink-to-survivors recovery path
    (see ``elastic/shrink.py``)."""
    return envs.parse_float_env(envs.PEER_DEADLINE, engine_timeout_s())


#: ceiling on the connect-ladder length handed to ``channel.send`` per
#: retry attempt; the actual ladder is derived from the remaining
#: per-peer deadline (see ``_send``), this just bounds the fast case
_SEND_CONNECT_RETRIES = 10

#: "caller did not choose a chaos identity" — distinct from an explicit
#: ``None`` (= a late joiner with no bootstrap rank, which must use the
#: rank-less controller like every other chaos hook does for it)
_CHAOS_RANK_UNSET = object()

REDUCE_OPS = native.REDUCE_OPS  # single source of op names

#: worker count of :meth:`CollectiveEngine.async_pool` — the hard
#: ceiling on concurrently RUNNING caller-level async ops per engine.
#: Callers that keep windows of handles in flight (pipeline prefetch,
#: bucket pipelines) must bound them below this number or queued sends
#: can starve behind blocked recvs; ``parallel/pp.py`` asserts its
#: window against this constant at plan-validation time, and the
#: kf-verify protocol checker (``analysis/protoverify.py``) re-derives
#: the bound statically.
ASYNC_POOL_WORKERS = 8

#: Static protocol metadata for every public wire op of
#: :class:`CollectiveEngine` — the declarative issue-site table the
#: kf-verify abstract interpreter (``analysis/commgraph.py``) extracts
#: comm sequences from.  MUST stay a pure literal dict: the analysis
#: layer reads it via ``ast.literal_eval`` without importing this
#: module (kflint runs in bare CI images with no numpy/jax).
#:
#: Per op: ``kind`` ("collective" = group rendezvous over every engine
#: peer; "p2p-send"/"p2p-recv" = point-to-point toward the rank in the
#: first positional arg), ``group`` (the membership axis a collective
#: rendezvouses over), ``tag`` (how the caller ``name`` becomes the
#: wire rendezvous tag; ``{name}`` is the caller's argument), and
#: ``blocking`` (False = returns a :class:`CollectiveHandle`; the
#: wait/fence discipline is checked by handle-discipline and the
#: kf-verify wait-for-graph pass).  ``analysis/protoverify.py``
#: cross-checks this table against the actual method defs both ways,
#: so drift (new wire op without metadata, metadata for a removed op)
#: is a lint finding, not silent rot.
COMM_OP_SPECS = {
    "all_reduce":          {"kind": "collective", "group": "world",
                            "tag": "{name}", "blocking": True,
                            "name_pos": 2, "peer_pos": None},
    "broadcast":           {"kind": "collective", "group": "world",
                            "tag": "{name}", "blocking": True,
                            "name_pos": 2, "peer_pos": None},
    "reduce":              {"kind": "collective", "group": "world",
                            "tag": "{name}.r", "blocking": True,
                            "name_pos": 3, "peer_pos": None},
    "gather":              {"kind": "collective", "group": "world",
                            "tag": "{name}.g", "blocking": True,
                            "name_pos": 2, "peer_pos": None},
    "all_gather":          {"kind": "collective", "group": "world",
                            "tag": "{name}.ag", "blocking": True,
                            "name_pos": 1, "peer_pos": None},
    "reduce_scatter":      {"kind": "collective", "group": "world",
                            "tag": "{name}.rs", "blocking": True,
                            "name_pos": 2, "peer_pos": None},
    "local_reduce":        {"kind": "collective", "group": "slice",
                            "tag": "{name}.lr", "blocking": True,
                            "name_pos": 2, "peer_pos": None},
    "local_broadcast":     {"kind": "collective", "group": "slice",
                            "tag": "{name}.lb", "blocking": True,
                            "name_pos": 1, "peer_pos": None},
    "cross_all_reduce":    {"kind": "collective", "group": "cross",
                            "tag": "{name}.x", "blocking": True,
                            "name_pos": 2, "peer_pos": None},
    "send_to":             {"kind": "p2p-send", "group": "pair",
                            "tag": "{name}", "blocking": True,
                            "name_pos": 2, "peer_pos": 0},
    "recv_from":           {"kind": "p2p-recv", "group": "pair",
                            "tag": "{name}", "blocking": True,
                            "name_pos": 1, "peer_pos": 0},
    "send_async":          {"kind": "p2p-send", "group": "pair",
                            "tag": "{name}", "blocking": False,
                            "name_pos": 2, "peer_pos": 0},
    "recv_async":          {"kind": "p2p-recv", "group": "pair",
                            "tag": "{name}", "blocking": False,
                            "name_pos": 1, "peer_pos": 0},
    "all_reduce_async":    {"kind": "collective", "group": "world",
                            "tag": "{name}", "blocking": False,
                            "name_pos": 2, "peer_pos": None},
    "reduce_scatter_async": {"kind": "collective", "group": "world",
                             "tag": "{name}.rs", "blocking": False,
                             "name_pos": 2, "peer_pos": None},
    "all_gather_async":    {"kind": "collective", "group": "world",
                            "tag": "{name}.ag", "blocking": False,
                            "name_pos": 1, "peer_pos": None},
}


def build_strategy_graphs(
    strategy: Strategy, peers: PeerList
) -> List[Tuple[Graph, Graph]]:
    """Generate the (reduce, broadcast) graph pairs for a strategy over the
    given peer list (reference ``session/strategy.go:90-174``)."""
    n = len(peers)
    host_ranks = list(peers.partition_by_host().values())
    if strategy == Strategy.AUTO:
        strategy = auto_select(len(host_ranks))
    if strategy == Strategy.STAR:
        return [gen_star(n)]
    if strategy == Strategy.MULTI_STAR:
        return gen_multi_star(n, host_ranks)
    if strategy == Strategy.RING:
        return [gen_circular_graph_pair(n, shift=s) for s in range(n)]
    if strategy == Strategy.CLIQUE:
        return gen_clique(n)
    if strategy == Strategy.TREE:
        return [gen_tree(n, host_ranks)]
    if strategy == Strategy.BINARY_TREE:
        return [gen_binary_tree(n)]
    if strategy == Strategy.BINARY_TREE_STAR:
        return [gen_binary_tree_star(n, host_ranks)]
    if strategy == Strategy.MULTI_BINARY_TREE_STAR:
        return gen_multi_binary_tree_star(n, host_ranks)
    raise ValueError(f"unhandled strategy {strategy}")


def build_cross_strategy_graphs(
    strategy: Strategy, peers: PeerList
) -> List[Tuple[Graph, Graph]]:
    """Cross-host-stage strategies for hierarchical allreduce (reference
    ``session/strategy.go:188-210`` genCrossStrategyList): RING runs ring
    rotations over the local masters; every other strategy runs one
    binary tree over them."""
    n = len(peers)
    masters = [ranks[0] for ranks in peers.partition_by_host().values() if ranks]
    if strategy == Strategy.RING:
        return gen_cross_ring_pairs(n, masters)
    return gen_cross_binary_tree(n, masters)


def name_based_hash(name: str) -> int:
    """Name-based chunk→strategy hash (reference ``shard.go:17-23``): all
    chunks of one tensor share a strategy keyed by its name, balancing
    load across *tensors* instead of across chunks."""
    return sum(ord(c) * ord(c) for c in name)


# -- async collective plane (kf-overlap) -----------------------------------
#: process-wide in-flight accounting behind the ``kf_overlap_inflight``
#: gauge: in-process multi-rank clusters (every chaos/overlap test) run
#: several engines in one registry, so the gauge is the SUM of their
#: windows — "returned to 0" then means no rank leaked a handle
_inflight_lock = threading.Lock()
_inflight_total = 0

#: observed-at-wait hidden-wire fraction buckets (a ratio in [0, 1],
#: not a latency — the default latency buckets would collapse it)
_EFFICIENCY_BUCKETS = (0.1, 0.25, 0.5, 0.75, 0.9, 1.0)


def _inflight_adjust(delta: int) -> int:
    global _inflight_total
    with _inflight_lock:
        _inflight_total += delta
        total = _inflight_total
        # set INSIDE the lock: Gauge is last-write-wins, and two
        # concurrent completions setting 1-then-0 out of order would
        # strand the gauge nonzero after a full drain — the exact value
        # the demos and chaos tests assert returns to 0
        REGISTRY.gauge("kf_overlap_inflight").set(total)
    return total


class CollectiveHandle:
    """A collective in flight: issued now, settled at :meth:`wait`.

    The completion contract mirrors the sync path exactly — whatever the
    collective would have raised inline (typed
    :class:`~kungfu_tpu.comm.faults.PeerFailureError` with the suspect
    rank attached, an injected chaos death, a protocol error) is raised
    at :meth:`wait` instead of hanging; the per-peer deadline machinery
    runs inside the collective, so a handle always settles in bounded
    time even when a peer silently dies mid-flight.

    Lifetime discipline (enforced by the ``handle-discipline`` kflint
    rule): every handle is waited on every control-flow path, never
    dropped, and never held across a membership change —
    :meth:`CollectiveEngine.drain_async` fences the window at
    resize/shrink boundaries."""

    __slots__ = ("tag", "op", "nbytes", "_event", "_result", "_error",
                 "_t_issue", "_t_complete", "_observed")

    def __init__(self, tag: str, op: str, nbytes: int):
        self.tag = tag
        self.op = op
        self.nbytes = nbytes
        self._event = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None
        self._t_issue = time.perf_counter()
        self._t_complete: Optional[float] = None
        self._observed = False

    # -- issuer side ------------------------------------------------------
    def _settle(self, result=None, error: Optional[BaseException] = None):
        self._t_complete = time.perf_counter()
        self._result = result
        self._error = error
        self._event.set()

    # -- owner side -------------------------------------------------------
    def done(self) -> bool:
        """True once the collective settled (successfully or not)."""
        return self._event.is_set()

    def error(self) -> Optional[BaseException]:
        """The settled failure, or None (not yet settled / succeeded)."""
        return self._error

    def wait(self, timeout: Optional[float] = None):
        """Block until the collective settles; return its result or
        re-raise its typed failure.  Observes the hidden-wire fraction
        into ``kf_overlap_efficiency`` on first call: 1.0 = the wire
        time was fully hidden under the caller's compute."""
        t_wait = time.perf_counter()
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"handle {self.tag!r} not complete after {timeout}s "
                "(the collective's own deadline machinery should settle "
                "it; is KF_CONFIG_PEER_DEADLINE larger than this wait?)")
        if self._error is not None:
            # no efficiency observation for a failed collective: a
            # doomed handle waited on late would record hidden≈1.0 —
            # "wire fully hidden" for a transfer that delivered nothing
            # — skewing the histogram toward 1.0 during fault storms,
            # exactly when operators read it
            raise self._error
        if not self._observed:
            self._observed = True
            wire = (self._t_complete or t_wait) - self._t_issue
            hidden = 1.0 if wire <= 0 else max(
                0.0, min(1.0, (t_wait - self._t_issue) / wire))
            REGISTRY.histogram(
                "kf_overlap_efficiency", buckets=_EFFICIENCY_BUCKETS
            ).observe(hidden)
        return self._result


class CollectiveEngine:
    """Executes graph collectives for one peer over its host channel."""

    def __init__(
        self,
        channel: HostChannel,
        peers: PeerList,
        strategy: Strategy = Strategy.AUTO,
        chaos_rank=_CHAOS_RANK_UNSET,
    ):
        self.channel = channel
        self.peers = peers
        self.rank = peers.rank(channel.self_id)
        if self.rank is None:
            raise ValueError(f"{channel.self_id} not in {peers}")
        self.strategy = strategy
        self._graphs = build_strategy_graphs(strategy, peers)
        self._cross_graphs = build_cross_strategy_graphs(strategy, peers)
        # derived from the shared peer list → identical on every peer
        self._colocated = len(peers.hosts()) <= 1
        # chunk→strategy hash mode (reference shard.go:25-31); read once at
        # engine construction, like the reference reads config at init
        import os

        self._hash_name_based = (
            os.environ.get(envs.STRATEGY_HASH_METHOD, "").strip().upper() == "NAME"
        )
        #: fault injection (None unless KF_CHAOS_SPEC is set — the hot
        #: path pays one attribute load + branch when disabled).
        #: ``chaos_rank`` is the process's STABLE identity (its bootstrap
        #: rank, Peer.chaos_rank()): a shrink promotes survivor ranks, and
        #: a rank-scoped fault clause must not re-target the promoted
        #: survivor of the very failure it injected.  An explicit ``None``
        #: (a late joiner with no bootstrap rank) selects the rank-less
        #: controller, matching every other chaos hook for that process;
        #: engines built directly (tests) default to the current rank.
        self._chaos = _chaos_controller_for(
            self.rank if chaos_rank is _CHAOS_RANK_UNSET else chaos_rank
        )
        #: identity stamped on timeline events: the STABLE bootstrap rank
        #: when the owner supplied one (a shrink renumbers self.rank on
        #: the rebuilt engine, and a merged kftrace timeline must keep
        #: one track per process — a renumbered survivor would otherwise
        #: alias a pre-shrink peer's track); engines built directly
        #: (tests, no resize in play) use the live rank
        self._timeline_rank = (
            chaos_rank
            if chaos_rank is not _CHAOS_RANK_UNSET and chaos_rank is not None
            else self.rank
        )
        #: resolved once — _send/_recv run per chunk per peer, and a
        #: per-call env parse on that path is measurable noise (engines
        #: are rebuilt each mesh epoch, so retuning still lands)
        self._peer_deadline = peer_deadline_s()
        #: resolved once for the same reason: _begin_collective runs on
        #: every public collective, and the registry lookup is a lock +
        #: dict hash it doesn't need to repay per call
        self._coll_counter = REGISTRY.counter("kf_engine_collectives_total")
        self._seq = 0
        self._lock = threading.Lock()
        self._stats_lock = threading.Lock()  # guards stats/_window swaps
        self._peers_csv = ",".join(str(p) for p in peers)
        self._graph_ser: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        from concurrent.futures import ThreadPoolExecutor

        from kungfu_tpu.comm.host import host_pool_size

        # sender/chunk pool scaled with peer count (floor 8 preserves
        # the measured chunk-pipelining win on small clusters; wider
        # worlds get up to KF_CONFIG_HOST_POOL_MAX concurrent chunks)
        self._pool = ThreadPoolExecutor(
            max_workers=host_pool_size(len(peers), floor=8, pool="engine"),
            thread_name_prefix="kf-engine",
        )
        self._async_pool: Optional[ThreadPoolExecutor] = None
        # per-strategy-pair accounting for adaptation: cumulative
        # (bytes, seconds), a recent window (reset on throughputs()), and
        # the best window rate ever observed (the reference compares recent
        # throughput against the recorded best, adaptiveStrategies.go)
        self.stats = [[0, 0.0] for _ in self._graphs]
        self._window = [[0, 0.0] for _ in self._graphs]
        self.best_throughputs = [0.0 for _ in self._graphs]
        # swap-eligibility epoch (kf-adapt): collectives executed since
        # the last strategy swap — the bandit driver refuses to judge an
        # arm that has not carried real traffic yet (mark_swap resets)
        self._colls_total = 0
        self._colls_at_swap = 0
        # kf-overlap: the bounded in-flight window for async handles.
        # A plain count + condition (not a Semaphore) so the depth can
        # be retuned live (set_overlap_depth) without rebuilding
        self._overlap_depth = overlap_depth_default()
        self._overlap_cond = threading.Condition()
        self._inflight_handles: set = set()
        #: ``fn(nbytes, depth, seconds)`` per completed async collective
        #: — the kf-adapt latency feed (None = disabled)
        self._latency_hook = None

    # -- public collectives ----------------------------------------------
    def all_reduce(
        self, x: np.ndarray, op: str = "sum", name: str = "", record: bool = True,
        inplace: bool = False,
    ) -> np.ndarray:
        """Chunked graph allreduce (reference ``allreduce.go:11`` +
        ``runStrategies``).  ``record=False`` keeps control-plane traffic
        (e.g. interference votes) out of the throughput window so the
        adaptation signal only sees data-plane transfers.

        ``inplace=True`` reduces directly in ``x``'s buffer and returns
        ``x`` — skips one full defensive copy, the NCCL in-place
        allreduce analog; the input values are clobbered.  The contract
        is honored for ANY writable ndarray (a non-contiguous view pays
        a staging copy but still receives the result); a read-only input
        raises instead of silently downgrading."""
        if op not in REDUCE_OPS and op != "mean":
            raise ValueError(f"op {op!r}")
        self._begin_collective(name or "all_reduce")
        eff_op = "sum" if op == "mean" else op
        if inplace and not x.flags["WRITEABLE"]:
            raise ValueError("inplace=True requires a writable array")
        orig = x
        x = np.ascontiguousarray(x)
        flat = x.reshape(-1)
        tag = name or f"ar{self._next_seq()}"
        with timeline.span(
            "collective", f"engine.all_reduce[{flat.nbytes}B]",
            rank=self._timeline_rank, op="all_reduce", tag=tag, nbytes=flat.nbytes,
            trace=self._trace_id("all_reduce", tag),
        ):
            out = self._run_over_graphs(
                flat, eff_op, tag, self._graphs, record=record, inplace=inplace
            )
        out = out.reshape(x.shape)
        if op == "mean":
            out = np.divide(out, len(self.peers), out=out if inplace else None)
        if inplace:
            # the Python fallback, a mean divide, or a non-contiguous
            # staging copy may have produced a fresh array — the inplace
            # contract says the CALLER's buffer holds the result either way
            if not np.shares_memory(out, orig):
                np.copyto(orig, out)
            return orig
        return out

    def _begin_collective(self, tag: str) -> None:
        """Entry hook of every public collective: ticks the unified
        collective counter (the live plane's per-push rate source) and
        advances the injector's ``coll`` counter — ``die:coll=N`` means
        the Nth engine collective of any kind, so an experiment against
        a loop that opens with a parameter broadcast still dies where
        the spec says."""
        self._coll_counter.inc()
        with self._stats_lock:
            self._colls_total += 1
        if self._chaos is not None:
            self._chaos.on_collective(tag)

    def _trace_id(self, op: str, tag: str) -> str:
        """kf-xray derived cross-rank trace id: every participant
        computes the identical id from the cluster version (the
        channel's epoch token), the current step, and the collective's
        op/tag — the same logical collective links across ranks in a
        merged trace with no extra wire bytes (docs/xray.md)."""
        return timeline.collective_trace_id(
            getattr(self.channel, "token", 0), timeline.current_step(),
            op, tag)

    def broadcast(self, x: np.ndarray, root: int = 0, name: str = "") -> np.ndarray:
        self._begin_collective(name or "broadcast")
        with self._lock:
            seq = self._seq
            self._seq += 1
        tag = name or f"bc{seq}"
        _, bcast_g = gen_star(len(self.peers), center=root)
        flat = np.ascontiguousarray(x).reshape(-1)
        with timeline.span(
            "collective", "engine.broadcast", rank=self._timeline_rank,
            op="broadcast", tag=tag, nbytes=flat.nbytes,
            trace=self._trace_id("broadcast", tag),
        ):
            out = self._run_bcast(flat.copy(), f"{tag}", bcast_g)
        return out.reshape(x.shape)

    def reduce(self, x: np.ndarray, root: int = 0, op: str = "sum", name: str = "") -> np.ndarray:
        """Reduce to ``root`` (reference ``session.go:157-161``): only the
        root returns the reduced value; other ranks get their input back."""
        self._begin_collective(name or "reduce")
        tag = (name or f"rd{self._next_seq()}") + ".r"
        flat = np.ascontiguousarray(x).reshape(-1)
        eff_op = "sum" if op == "mean" else op
        reduce_g, _ = gen_star(len(self.peers), center=root)
        me = self.rank
        acc = flat.copy()
        with timeline.span("collective", "engine.reduce", rank=self._timeline_rank,
                           op="reduce", tag=tag, nbytes=flat.nbytes,
                           trace=self._trace_id("reduce", tag)):
            for prev in reduce_g.prevs(me):
                data = np.frombuffer(self._recv(prev, tag), dtype=flat.dtype)
                acc = native.transform2(acc, data, eff_op)
            for nxt in reduce_g.nexts(me):
                self._send(nxt, tag, acc.tobytes())
        if me == root and op == "mean":
            acc = acc / len(self.peers)
        return acc.reshape(x.shape) if me == root else x

    def gather(self, x: np.ndarray, root: int = 0, name: str = "") -> Optional[np.ndarray]:
        """Root returns [n, ...] stacked in rank order; others None
        (reference gathers to rank 0, ``session.go:189-211``)."""
        self._begin_collective(name or "gather")
        tag = (name or f"ga{self._next_seq()}") + ".g"
        flat = np.ascontiguousarray(x).reshape(-1)
        with timeline.span("collective", "engine.gather", rank=self._timeline_rank,
                           op="gather", tag=tag, nbytes=flat.nbytes,
                           trace=self._trace_id("gather", tag)):
            if self.rank == root:
                parts = []
                for r in range(len(self.peers)):
                    if r == root:
                        parts.append(flat)
                    else:
                        parts.append(
                            np.frombuffer(self._recv(r, tag), dtype=flat.dtype)
                        )
                return np.stack(parts).reshape((len(self.peers),) + x.shape)
            self._send(root, tag, flat.tobytes())
            return None

    def all_gather(self, x: np.ndarray, name: str = "") -> np.ndarray:
        """Direct full-exchange (reference ``allgather.go:17-45``): every
        peer sends to every other; returns [n, ...] in rank order."""
        self._begin_collective(name or "all_gather")
        tag = (name or f"ag{self._next_seq()}") + ".ag"
        flat = np.ascontiguousarray(x).reshape(-1)
        me = self.rank
        with timeline.span("collective", "engine.all_gather", rank=self._timeline_rank,
                           op="all_gather", tag=tag, nbytes=flat.nbytes,
                           trace=self._trace_id("all_gather", tag)):
            for r in range(len(self.peers)):
                if r != me:
                    self._send(r, tag, flat.tobytes())
            parts = []
            for r in range(len(self.peers)):
                if r == me:
                    parts.append(flat)
                else:
                    parts.append(
                        np.frombuffer(self._recv(r, tag), dtype=flat.dtype)
                    )
        return np.stack(parts).reshape((len(self.peers),) + x.shape)

    def reduce_scatter(self, x: np.ndarray, op: str = "sum",
                       name: str = "") -> np.ndarray:
        """Reduce-scatter over the host plane: every rank contributes a
        full flat buffer and receives the 1/n chunk it owns (rank-major,
        zero-padded to ``n * chunk``) reduced across all ranks.  Direct
        exchange: each rank sends every OTHER rank that rank's chunk of
        its local buffer — per-rank wire volume ``(n-1)/n`` of the
        buffer, the bandwidth-optimal half of an allreduce, and the
        host-plane analog of the ZeRO-2 gradient collective
        (:meth:`kungfu_tpu.comm.device.Communicator.reduce_scatter`)."""
        if op not in REDUCE_OPS and op != "mean":
            raise ValueError(f"op {op!r}")
        self._begin_collective(name or "reduce_scatter")
        eff_op = "sum" if op == "mean" else op
        tag = (name or f"rs{self._next_seq()}") + ".rs"
        flat = np.ascontiguousarray(x).reshape(-1)
        n = len(self.peers)
        me = self.rank
        chunk = -(-flat.shape[0] // n) if flat.shape[0] else 0
        padded = np.zeros((chunk * n,), flat.dtype)
        padded[: flat.shape[0]] = flat
        with timeline.span(
            "collective", f"engine.reduce_scatter[{flat.nbytes}B]",
            rank=self._timeline_rank, op="reduce_scatter", tag=tag,
            nbytes=flat.nbytes, trace=self._trace_id("reduce_scatter", tag),
        ):
            for r in range(n):
                if r != me:
                    self._send(
                        r, f"{tag}.{r}",
                        padded[r * chunk:(r + 1) * chunk].tobytes())
            acc = padded[me * chunk:(me + 1) * chunk].copy()
            for r in range(n):
                if r == me:
                    continue
                data = np.frombuffer(
                    self._recv(r, f"{tag}.{me}"), dtype=flat.dtype)
                acc = native.transform2(acc, data, eff_op)
        if op == "mean":
            acc = acc / n
        return acc

    # -- point-to-point (kf-pipeline) --------------------------------------
    def send_to(self, rank: int, data, name: str) -> int:
        """Deadline-bounded point-to-point send to ``rank`` on the
        engine's wire (same retry/deadline/chaos machinery as the
        collective sends — a dead receiver raises typed
        :class:`PeerFailureError` naming the suspect instead of riding
        the channel's full connect ladder).  ``data`` is an ndarray or
        bytes; returns the payload size.  The pipeline-parallel
        activation hop (``parallel/pp.py``) — NOT a collective: it does
        not tick the collective counter and ``die:coll=N`` clauses do
        not count it (``delay``/``die`` send-scoped clauses still fire
        inside ``_send``)."""
        if isinstance(data, np.ndarray):
            payload = np.ascontiguousarray(data).tobytes()
        else:
            payload = bytes(data)
        with timeline.span(
            "collective", f"engine.send[{len(payload)}B]",
            rank=self._timeline_rank, op="send", tag=name,
            nbytes=len(payload),
            # op "p2p" on BOTH halves: sender and receiver must derive
            # the IDENTICAL trace id or the hop never forms a
            # cross-rank causal edge in a merged trace
            trace=self._trace_id("p2p", name),
        ):
            self._send(rank, name, payload)
        return len(payload)

    def recv_from(self, rank: int, name: str, dtype=None, shape=None):
        """Deadline-bounded point-to-point receive from ``rank``.
        Returns raw bytes, or an ndarray when ``dtype`` is given
        (reshaped to ``shape`` when that is too).  Timeouts surface as
        typed :class:`PeerFailureError` with the suspect rank — the
        same contract as every collective recv."""
        with timeline.span(
            "collective", "engine.recv", rank=self._timeline_rank,
            op="recv", tag=name, nbytes=0,
            trace=self._trace_id("p2p", name),
        ):
            data = self._recv(rank, name)
        if dtype is None:
            return data
        out = np.frombuffer(data, dtype=dtype)
        return out.reshape(shape) if shape is not None else out

    def send_async(self, rank: int, data, name: str) -> CollectiveHandle:
        """Issue a point-to-point send and return immediately with a
        :class:`CollectiveHandle` (kf-pipeline: the 1F1B activation
        hop rides the async plane so the wire hides under stage
        compute).  The tag is fixed HERE, at issue time on the calling
        thread — the ``handle-discipline`` lint polices the handle's
        lifetime exactly like the async collectives'."""
        nbytes = data.nbytes if hasattr(data, "nbytes") else len(data)
        return self._issue_async(
            "send", name, nbytes, lambda: self.send_to(rank, data, name))

    def recv_async(self, rank: int, name: str, dtype=None,
                   shape=None) -> CollectiveHandle:
        """Issue a point-to-point receive; the payload (and any typed
        failure) surfaces at ``handle.wait()``.  The 1F1B prefetch
        primitive: posting the recv one op early hides the DCN hop
        under the current microbatch's compute.

        Each in-flight async op occupies one async-pool slot until it
        settles; callers owning MANY handles (a pipeline schedule)
        must bound their outstanding set below the pool size — see
        ``parallel/pp.py``'s prefetch discipline."""
        return self._issue_async(
            "recv", name, 0,
            lambda: self.recv_from(rank, name, dtype=dtype, shape=shape))

    # -- async collectives (kf-overlap) ------------------------------------
    def all_reduce_async(self, x: np.ndarray, op: str = "sum",
                         name: str = "", record: bool = True
                         ) -> CollectiveHandle:
        """Issue a chunked graph allreduce and return immediately with a
        :class:`CollectiveHandle`; the result (and any typed failure)
        surfaces at ``handle.wait()``.  The wire protocol is identical
        to :meth:`all_reduce` — the tag is fixed HERE, in issue order on
        the calling thread, so peers mixing sync and async issue styles
        still rendezvous."""
        tag = name or f"ar{self._next_seq()}"
        nbytes = np.asarray(x).nbytes
        return self._issue_async(
            "all_reduce", tag, nbytes,
            lambda: self.all_reduce(x, op=op, name=tag, record=record))

    def reduce_scatter_async(self, x: np.ndarray, op: str = "sum",
                             name: str = "") -> CollectiveHandle:
        """Async :meth:`reduce_scatter` — the ZeRO-2/3 gradient-bucket
        pipeline primitive (``parallel/zero.py::host_bucket_pipeline``
        issues bucket i+1 here while bucket i's optimizer math runs)."""
        base = name or f"rs{self._next_seq()}"
        nbytes = np.asarray(x).nbytes
        return self._issue_async(
            "reduce_scatter", base, nbytes,
            lambda: self.reduce_scatter(x, op=op, name=base))

    def all_gather_async(self, x: np.ndarray, name: str = ""
                         ) -> CollectiveHandle:
        """Async :meth:`all_gather` — the ZeRO-3 parameter-bucket
        prefetch primitive."""
        base = name or f"ag{self._next_seq()}"
        nbytes = np.asarray(x).nbytes
        return self._issue_async(
            "all_gather", base, nbytes, lambda: self.all_gather(x, name=base))

    def _issue_async(self, op: str, tag: str, nbytes: int,
                     fn) -> CollectiveHandle:
        """Admit one collective into the bounded in-flight window and
        run it on the async pool.  Blocks while ``overlap_depth`` handles
        are already in flight (completion — success OR typed failure —
        releases a slot; a slot is never released by ``wait()``, so an
        unwaited handle cannot deadlock the window)."""
        pool = self.async_pool()
        with self._overlap_cond:
            while len(self._inflight_handles) >= self._overlap_depth:
                self._overlap_cond.wait()
            handle = CollectiveHandle(tag, op, nbytes)
            self._inflight_handles.add(handle)
            depth_now = len(self._inflight_handles)
        total = _inflight_adjust(+1)
        if timeline.enabled():
            timeline.event("overlap", "issue", rank=self._timeline_rank,
                           op=op, tag=tag, nbytes=nbytes,
                           inflight=depth_now, inflight_total=total)

        def run():
            err = None
            t0 = time.perf_counter()
            try:
                out = fn()
            except BaseException as e:  # noqa: BLE001 - settled at wait()
                err = e
                out = None
            dt = time.perf_counter() - t0
            # one critical section for the whole completion: gauge
            # decrement, window removal, settle, notify.  Ordering races
            # on either side otherwise — a drainer waking on the empty
            # set must find the handle already settled (the chaos tests
            # read hb.error() right after a drain), and a waiter woken
            # by _settle must find the gauge already decremented (the
            # demos assert it reads 0 the moment every wait returned).
            # Lock nesting is cond → _inflight_lock only, never the
            # reverse — no cycle.
            with self._overlap_cond:
                total_now = _inflight_adjust(-1)
                self._inflight_handles.discard(handle)
                left = len(self._inflight_handles)
                handle._settle(out, err)
                self._overlap_cond.notify_all()
            if timeline.enabled():
                timeline.event(
                    "overlap", "complete", rank=self._timeline_rank,
                    op=op, tag=tag, nbytes=nbytes, inflight=left,
                    inflight_total=total_now, dur=round(dt, 6),
                    error=type(err).__name__ if err is not None else None)
            hook = self._latency_hook
            if hook is not None and err is None:
                try:
                    hook(nbytes, self._overlap_depth, dt)
                except Exception:  # noqa: BLE001 - observability only
                    _log.exception("overlap latency hook failed")

        pool.submit(run)
        return handle

    @property
    def overlap_depth(self) -> int:
        """The in-flight window bound currently in force."""
        return self._overlap_depth

    def set_overlap_depth(self, depth: int) -> None:
        """Retune the in-flight window live.  Safe mid-flight: shrinking
        only delays FUTURE issues (already-issued handles finish), and
        growth wakes blocked issuers immediately.  Local backpressure
        only — never part of the wire protocol, so no fence is needed."""
        if depth < 1:
            raise ValueError(f"overlap depth must be >= 1, got {depth}")
        with self._overlap_cond:
            self._overlap_depth = int(depth)
            self._overlap_cond.notify_all()

    def inflight(self) -> int:
        """Issued-and-unsettled handle count on THIS engine."""
        with self._overlap_cond:
            return len(self._inflight_handles)

    def drain_async(self, timeout: Optional[float] = None) -> int:
        """Block until every in-flight handle settles; returns how many
        were drained.  THE membership fence: a handle may never cross a
        resize/shrink (its tags and peer set belong to the old epoch),
        so ``Peer._propose`` and the shrink ladder drain here first.
        Settling is deadline-bounded by construction (every send/recv
        inside a collective runs under the per-peer deadline), so a
        bare drain cannot hang on a dead peer — it observes the typed
        failure and moves on; the failure still belongs to the handle's
        owner and re-raises at that handle's ``wait()``."""
        deadline = (time.monotonic() + timeout) if timeout is not None else None
        with self._overlap_cond:
            drained = len(self._inflight_handles)
            while self._inflight_handles:
                remaining = None
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"{len(self._inflight_handles)} async handle(s) "
                            f"still in flight after {timeout}s drain")
                self._overlap_cond.wait(remaining)
        return drained

    def set_latency_hook(self, fn) -> None:
        """Install ``fn(nbytes, depth, seconds)`` to receive each
        completed async collective's measured wall time — the kf-adapt
        feed that makes the overlap depth a learnable arm
        (:class:`kungfu_tpu.policy.bandit.OverlapDepthBandit`).  Pass
        ``None`` to disable."""
        self._latency_hook = fn

    # -- hierarchical (host-partitioned) collectives ----------------------
    # Local = peers sharing this peer's host; the local root is the
    # lowest-global-rank peer on each host (reference local masters).
    def _local_ranks(self) -> List[int]:
        host = self.peers[self.rank].host
        return [r for r, p in enumerate(self.peers) if p.host == host]

    def _local_roots(self) -> List[int]:
        seen = {}
        for r, p in enumerate(self.peers):
            seen.setdefault(p.host, r)
        return sorted(seen.values())

    def _subset_reduce(self, flat, ranks: List[int], root: int, op: str, tag: str):
        """Star-reduce over a rank subset; result lands on ``root``."""
        me = self.rank
        acc = flat.copy()
        if me == root:
            for r in ranks:
                if r != root:
                    data = np.frombuffer(self._recv(r, tag), dtype=flat.dtype)
                    acc = native.transform2(acc, data, op)
        else:
            self._send(root, tag, flat.tobytes())
        return acc

    def _subset_bcast(self, flat, ranks: List[int], root: int, tag: str):
        me = self.rank
        if me == root:
            for r in ranks:
                if r != root:
                    self._send(r, tag, flat.tobytes())
            return flat
        return np.frombuffer(self._recv(root, tag), dtype=flat.dtype).copy()

    def local_reduce(self, x: np.ndarray, op: str = "sum", name: str = "") -> np.ndarray:
        """Reduce among same-host peers; result on the local root
        (reference ``LocalReduce``).  Non-roots get their input back."""
        self._begin_collective(name or "local_reduce")
        tag = (name or f"lr{self._next_seq()}") + ".lr"
        flat = np.ascontiguousarray(x).reshape(-1)
        ranks = self._local_ranks()
        root = min(ranks)
        with timeline.span("collective", "engine.local_reduce",
                           rank=self._timeline_rank, op="local_reduce", tag=tag,
                           nbytes=flat.nbytes,
                           trace=self._trace_id("local_reduce", tag)):
            acc = self._subset_reduce(
                flat, ranks, root, "sum" if op == "mean" else op, tag)
        if self.rank == root:
            if op == "mean":
                acc = acc / len(ranks)
            return acc.reshape(x.shape)
        return x

    def local_broadcast(self, x: np.ndarray, name: str = "") -> np.ndarray:
        """Broadcast from the local root to same-host peers."""
        self._begin_collective(name or "local_broadcast")
        tag = (name or f"lb{self._next_seq()}") + ".lb"
        flat = np.ascontiguousarray(x).reshape(-1)
        ranks = self._local_ranks()
        with timeline.span("collective", "engine.local_broadcast",
                           rank=self._timeline_rank, op="local_broadcast", tag=tag,
                           nbytes=flat.nbytes,
                           trace=self._trace_id("local_broadcast", tag)):
            out = self._subset_bcast(flat, ranks, min(ranks), tag)
        return out.reshape(x.shape)

    def cross_all_reduce(self, x: np.ndarray, op: str = "sum", name: str = "") -> np.ndarray:
        """Hierarchical allreduce (reference ``allreduce.go:38``
        CrossAllReduce + the ScheduledHierarchical pattern): local reduce
        to the host roots, allreduce among roots, local broadcast."""
        self._begin_collective(name or "cross_all_reduce")
        base = name or f"xa{self._next_seq()}"
        eff_op = "sum" if op == "mean" else op
        flat = np.ascontiguousarray(x).reshape(-1)
        local = self._local_ranks()
        local_root = min(local)
        roots = self._local_roots()
        with timeline.span(
            "collective", "engine.cross_all_reduce", rank=self._timeline_rank,
            op="cross_all_reduce", tag=base, nbytes=flat.nbytes,
            trace=self._trace_id("cross_all_reduce", base),
        ):
            acc = self._subset_reduce(
                flat, local, local_root, eff_op, base + ".lr")
            if self.rank == local_root and len(roots) > 1:
                # allreduce among the host roots via the cross-stage
                # strategy graphs (ring rotations or binary tree over the
                # masters, reference strategy.go:188-210), chunked like
                # the global path
                acc = self._run_over_graphs(
                    np.ascontiguousarray(acc), eff_op, base + ".x",
                    self._cross_graphs,
                )
            acc = self._subset_bcast(acc, local, local_root, base + ".lb")
        if op == "mean":
            acc = acc / len(self.peers)
        return acc.reshape(x.shape)

    def _run_over_graphs(
        self,
        flat: np.ndarray,
        op: str,
        tag: str,
        graphs: List[Tuple[Graph, Graph]],
        record: bool = False,
        inplace: bool = False,
    ) -> np.ndarray:
        """The runStrategies core (reference ``session.go:292-321``):
        chunk ``flat``, hash each chunk onto a graph pair, run the pairs
        concurrently.  ``record`` feeds the per-strategy throughput stats
        (only meaningful for the global strategy list, whose indices the
        stats arrays are keyed by).

        When the channel is native and the dtype/op have native kernels,
        the whole loop — chunk split, hash, recv/accumulate/send — runs in
        C++ (one ctypes crossing per collective, transport.cpp
        kf_engine_all_reduce); the Python pool below is the fallback and
        the reference implementation of the same wire protocol."""
        out = self._native_run(flat, op, tag, graphs, record, inplace=inplace)
        if out is not None:
            return out
        chunks = self._split(flat)
        outs: List[Optional[np.ndarray]] = [None] * len(chunks)
        errs: List[BaseException] = []

        def run_chunk(i: int, chunk: np.ndarray):
            gi = self._choose(i, tag, len(graphs))
            reduce_g, bcast_g = graphs[gi]
            t0 = time.perf_counter()
            try:
                outs[i] = self._run_graphs(chunk, op, f"{tag}.c{i}", reduce_g, bcast_g)
            except BaseException as e:  # noqa: BLE001
                errs.append(e)
                return
            if record:
                dt = time.perf_counter() - t0
                with self._stats_lock:
                    st = self.stats[gi]
                    st[0] += chunk.nbytes
                    st[1] += dt
                    w = self._window[gi]
                    w[0] += chunk.nbytes
                    w[1] += dt

        if len(chunks) == 1:
            run_chunk(0, chunks[0])
        else:
            futures = [
                self._pool.submit(run_chunk, i, c) for i, c in enumerate(chunks)
            ]
            for f in futures:
                f.result()
        if errs:
            raise errs[0]
        return np.concatenate(outs) if len(outs) > 1 else outs[0]

    def _next_seq(self) -> int:
        with self._lock:
            seq = self._seq
            self._seq += 1
        return seq

    # -- native executor delegation ---------------------------------------
    def _native_run(
        self, flat, op, tag, graphs, record, inplace: bool = False
    ) -> Optional[np.ndarray]:
        """Run the collective in the C++ executor when possible; None =
        caller should use the Python path."""
        import os

        if os.environ.get("KF_NATIVE_ENGINE", "1").lower() in ("0", "false", "no"):
            return None
        if self._chaos is not None:
            # fault injection lives in the Python send/recv wrappers; the
            # C++ executor would bypass every hook, so a chaos run pins
            # the reference Python path (and stays deterministic)
            return None
        t = getattr(self.channel, "_t", None)  # NativeHostChannel only
        if t is None or not hasattr(t, "engine_all_reduce"):
            return None
        code = native._DTYPE_CODES.get(flat.dtype)
        opc = native._OP_CODES.get(op)
        if code is None or opc is None:
            return None
        key = id(graphs)
        ser = self._graph_ser.get(key)
        if ser is None:
            ser = self._graph_ser[key] = self._serialize_graphs(graphs)
        data, offsets = ser
        # reduced in place; the defensive copy preserves the caller's
        # input unless it opted in to clobbering (NCCL in-place analog)
        buf = flat if inplace else np.ascontiguousarray(flat).copy()
        stats = np.zeros(len(graphs) * 2, np.float64)
        rc = t.engine_all_reduce(
            self._peers_csv, buf, flat.dtype.itemsize, code, opc,
            data, offsets, len(graphs), tag,
            1 if self._hash_name_based else 0,
            engine_chunk_size(self._colocated),
            # honor a tightened per-peer deadline on the native path too
            # (default: both are the engine timeout — no behavior change)
            min(engine_timeout_s(), peer_deadline_s()), engine_threads(), stats,
        )
        # the C++ executor reports collective-level failure without a
        # per-peer attribution — rank=None tells the recovery driver to
        # find the dead set by probing (elastic/shrink.find_dead_ranks)
        if rc == 1:
            timeline.event("deadline", tag, rank=self._timeline_rank,
                           phase="native-collective", cause="TimeoutError")
            raise PeerFailureError(
                None, op=tag, phase="native-collective",
                cause=TimeoutError(f"native collective {tag!r} timed out"),
            )
        if rc == 2:
            timeline.event("deadline", tag, rank=self._timeline_rank,
                           phase="native-collective", cause="ConnectionError")
            raise PeerFailureError(
                None, op=tag, phase="native-collective",
                cause=ConnectionError(
                    f"native collective {tag!r}: peer unreachable/closed"
                ),
            )
        if rc != 0:
            raise RuntimeError(f"native collective {tag!r} failed (rc={rc})")
        if record and graphs is self._graphs:
            with self._stats_lock:
                for gi in range(len(graphs)):
                    b, s = stats[2 * gi], stats[2 * gi + 1]
                    self.stats[gi][0] += int(b)
                    self.stats[gi][1] += s
                    self._window[gi][0] += int(b)
                    self._window[gi][1] += s
        if self.channel.monitor is not None:
            # egress accounting: every reduce/bcast next got chunk-sized
            # sends; approximate per-peer attribution is done natively for
            # ingress — skip fine-grained egress here (native sends bypass
            # the python wrapper)
            pass
        return buf

    def _serialize_graphs(self, graphs) -> Tuple[np.ndarray, np.ndarray]:
        """Me-centric adjacency serialization consumed by
        ``kf_engine_all_reduce`` (see transport.cpp for the layout)."""
        me = self.rank
        data: List[int] = []
        offsets = [0]
        for red, bc in graphs:
            for g in (red, bc):
                data.append(1 if g.is_self_loop(me) else 0)
                prevs = list(g.prevs(me))
                data.append(len(prevs))
                data.extend(prevs)
                nexts = list(g.nexts(me))
                data.append(len(nexts))
                data.extend(nexts)
            offsets.append(len(data))
        return np.asarray(data, np.int32), np.asarray(offsets, np.int32)

    # -- internals -------------------------------------------------------
    def _split(self, flat: np.ndarray) -> List[np.ndarray]:
        n_chunks = max(1, -(-flat.nbytes // engine_chunk_size(self._colocated)))
        return [np.ascontiguousarray(c) for c in np.array_split(flat, n_chunks)]

    def _choose(self, chunk_idx: int, name: str, n_graphs: Optional[int] = None) -> int:
        """Chunk→strategy hash (reference ``shard.go:11-31``): simple mode
        spreads chunks round-robin; NAME mode
        (``KF_CONFIG_STRATEGY_HASH_METHOD=NAME``) keys on the tensor name
        so whole tensors stick to one strategy."""
        n = n_graphs if n_graphs is not None else len(self._graphs)
        if self._hash_name_based:
            return name_based_hash(name) % n
        return chunk_idx % n

    def _send(self, rank: int, name: str, payload: bytes):
        """Send under a per-peer deadline: transient wire faults (a reset
        mid-chunk, a peer restarting its listener) are retried with
        jittered exponential backoff; deadline exhaustion raises
        :class:`PeerFailureError` naming the suspect instead of riding
        the channel's full 100 s connect ladder."""
        peer = self.peers[rank]
        deadline = time.monotonic() + self._peer_deadline
        attempt = 0
        while True:
            # size the channel's connect ladder by the remaining budget:
            # against a SYN-dropping dead host each rung can burn the
            # full CONNECT_TIMEOUT_S, so a fixed-length ladder would
            # blow through a tight deadline 10x over before this loop
            # ever saw the clock again (one rung of overshoot is the
            # floor — a single TCP connect cannot be subdivided)
            remaining = deadline - time.monotonic()
            retries = max(1, min(_SEND_CONNECT_RETRIES,
                                 int(remaining / CONNECT_TIMEOUT_S)))
            try:
                if self._chaos is not None:
                    self._chaos.on_send(
                        rank, name, payload, channel=self.channel, peer=peer
                    )
                self.channel.send(
                    peer, name, payload, ConnType.COLLECTIVE, retries=retries,
                )
                return
            except (ConnectionError, TimeoutError, OSError) as e:
                if time.monotonic() >= deadline:
                    timeline.event(
                        "deadline", name, rank=self._timeline_rank, peer=rank,
                        phase="send", cause=type(e).__name__,
                    )
                    raise PeerFailureError(
                        rank, peer, op=name, phase="send", cause=e
                    ) from e
                timeline.event(
                    "retry", name, rank=self._timeline_rank, peer=rank,
                    attempt=attempt, cause=type(e).__name__,
                )
                sleep_backoff(attempt, base=0.05, cap=1.0)
                attempt += 1

    def _recv(self, rank: int, name: str) -> bytes:
        peer = self.peers[rank]
        if self._chaos is not None:
            self._chaos.on_recv(rank, name)
        try:
            return self.channel.recv(
                peer, name, ConnType.COLLECTIVE, timeout=self._peer_deadline
            )
        except (TimeoutError, ConnectionError) as e:
            timeline.event("deadline", name, rank=self._timeline_rank, peer=rank,
                           phase="recv", cause=type(e).__name__)
            raise PeerFailureError(
                rank, peer, op=name, phase="recv", cause=e
            ) from e

    def _recv_into(self, rank: int, name: str, arr: np.ndarray) -> None:
        """Receive a same-shaped payload into ``arr`` via the registered
        zero-copy path (native: socket→buffer in the C++ stream thread).
        Graph collectives exchange deterministically-sized chunks, so a
        size mismatch is a protocol violation — diagnosed loudly, not
        papered over."""
        peer = self.peers[rank]
        if self._chaos is not None:
            self._chaos.on_recv(rank, name)
        try:
            filled = self.channel.recv_into(
                peer, name, arr, ConnType.COLLECTIVE, timeout=self._peer_deadline
            )
        except (TimeoutError, ConnectionError) as e:
            timeline.event("deadline", name, rank=self._timeline_rank, peer=rank,
                           phase="recv", cause=type(e).__name__)
            raise PeerFailureError(
                rank, peer, op=name, phase="recv", cause=e
            ) from e
        if filled:
            return
        data = self.channel.recv(peer, name, ConnType.COLLECTIVE)
        raise ValueError(
            f"collective {name!r} from rank {rank}: expected {arr.nbytes} "
            f"bytes, got {len(data)} — peers disagree on the chunk layout "
            "(mixed strategy/epoch?)"
        )

    def _run_graphs(
        self, chunk: np.ndarray, op: str, tag: str, reduce_g: Graph, bcast_g: Graph
    ) -> np.ndarray:
        """The reference hot loop (``session.go:222-290`` runGraphs):
        reduce stage — recv from graph prevs, accumulate, send to nexts;
        broadcast stage — recv final value, forward to nexts."""
        me = self.rank
        acc = chunk.copy() if reduce_g.is_self_loop(me) else None

        # reduce stage: wait for all prevs, accumulate (native C++ kernel,
        # numpy fallback — kungfu_tpu/native/reduce.cpp).  Receives land
        # directly in a registered scratch buffer (zero-copy on the native
        # transport: no per-message allocation or queue hop).
        scratch: Optional[np.ndarray] = None
        for prev in reduce_g.prevs(me):
            if scratch is None:
                scratch = np.empty_like(chunk)
            self._recv_into(prev, tag + ".r", scratch)
            if acc is None:
                acc = scratch
                scratch = None  # acc now owns it; next prev gets a fresh one
            else:
                acc = native.transform2(acc, scratch, op)
        if acc is None:
            acc = chunk.copy()
        for nxt in reduce_g.nexts(me):
            self._send(nxt, tag + ".r", acc.tobytes())

        # broadcast stage: roots already hold the result
        if not bcast_g.is_self_loop(me):
            prevs = bcast_g.prevs(me)
            if prevs:
                acc = np.empty_like(chunk)
                self._recv_into(prevs[0], tag + ".b", acc)
        for nxt in bcast_g.nexts(me):
            self._send(nxt, tag + ".b", acc.tobytes())
        return acc

    def _run_bcast(self, buf: np.ndarray, tag: str, bcast_g: Graph) -> np.ndarray:
        me = self.rank
        if not bcast_g.is_self_loop(me):
            prevs = bcast_g.prevs(me)
            if prevs:
                buf = np.frombuffer(self._recv(prevs[0], tag + ".b"), dtype=buf.dtype).copy()
        for nxt in bcast_g.nexts(me):
            self._send(nxt, tag + ".b", buf.tobytes())
        return buf

    def async_pool(self):
        """Per-engine executor for caller-level async collectives (torch
        binding et al.).  Per-engine — never shared across in-process
        engines — and FIFO with the caller's deterministic submission
        order, so equal-sized pools run identical op prefixes on every
        rank and cannot cross-starve.  Distinct from ``_pool`` (the chunk
        pool) so a blocked caller-level op cannot occupy a chunk slot."""
        with self._lock:
            if self._async_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._async_pool = ThreadPoolExecutor(
                    max_workers=ASYNC_POOL_WORKERS,
                    thread_name_prefix="kf-engine-async"
                )
            return self._async_pool

    def close(self) -> None:
        """Shut the worker pools down (engines are rebuilt per mesh
        epoch; leaking 8 threads per epoch would grow unboundedly)."""
        self._pool.shutdown(wait=False)
        if self._async_pool is not None:
            self._async_pool.shutdown(wait=False)

    # -- adaptation hooks ------------------------------------------------
    def throughputs(self) -> List[float]:
        """Per-strategy-pair achieved GiB/s over the window since the last
        call; also updates :attr:`best_throughputs`
        (reference ``strategy.go:17-56``)."""
        out = []
        with self._stats_lock:
            for i, (b, t) in enumerate(self._window):
                rate = (b / t / 2**30) if t > 0 else 0.0
                out.append(rate)
                if rate > self.best_throughputs[i]:
                    self.best_throughputs[i] = rate
                self._window[i][0] = 0
                self._window[i][1] = 0.0
        return out

    def total_throughputs(self) -> List[float]:
        """Lifetime per-strategy-pair GiB/s."""
        with self._stats_lock:
            return [(b / t / 2**30) if t > 0 else 0.0 for b, t in self.stats]

    def window_peek(self) -> List[Tuple[int, float]]:
        """Non-destructive view of the recent per-strategy-pair window:
        ``[(bytes, seconds), ...]`` accumulated since the last
        :meth:`throughputs` call.  The kf-adapt window export: unlike
        ``throughputs()`` it does NOT reset the window, so the bandit
        driver and the interference checker can read the same window
        without racing each other's resets."""
        with self._stats_lock:
            return [(int(b), float(t)) for b, t in self._window]

    def mark_swap(self) -> None:
        """Open a new swap-eligibility epoch: collectives before this
        point no longer count toward :meth:`swap_eligible` (called by
        the adaptation drivers right after a fenced strategy swap, so
        the next verdict is about the NEW arm only)."""
        with self._stats_lock:
            self._colls_at_swap = self._colls_total

    def collectives_since_swap(self) -> int:
        """Collectives executed in the current swap-eligibility epoch."""
        with self._stats_lock:
            return self._colls_total - self._colls_at_swap

    def swap_eligible(self, min_collectives: int = 2) -> bool:
        """Whether the active strategy has carried enough real traffic
        since the last swap to be judged — the hysteresis gate that
        stops a bandit (or any adaptation driver) from thrashing
        strategies faster than it can measure them."""
        return self.collectives_since_swap() >= max(0, int(min_collectives))

    def set_strategy(self, strategy: Strategy) -> None:
        """Swap the strategy set (reference ``SetGlobalStrategy`` +
        ``adaptation.go:8-28``; caller is responsible for the barrier +
        consensus fencing around the swap)."""
        # kf-overlap: a handle in flight walks the OLD graphs — swapping
        # them under it would tear the wire protocol mid-collective.
        # Free when the window is empty (the fenced-swap drivers barrier
        # before calling here, so it always is in practice).
        self.drain_async()
        self.strategy = strategy
        self._graphs = build_strategy_graphs(strategy, self.peers)
        self._cross_graphs = build_cross_strategy_graphs(strategy, self.peers)
        self._graph_ser.clear()
        with self._stats_lock:
            self.stats = [[0, 0.0] for _ in self._graphs]
            self._window = [[0, 0.0] for _ in self._graphs]
            self.best_throughputs = [0.0 for _ in self._graphs]
            # a swap opens a fresh eligibility epoch by definition
            self._colls_at_swap = self._colls_total
