"""Worker-side peer runtime: membership + mesh epochs + elasticity.

Parity with reference ``srcs/go/kungfu/peer/peer.go``: a ``Peer`` is created
from the env bootstrap contract, owns the host-side message endpoint and the
current :class:`~kungfu_tpu.comm.device.Communicator` (the analog of the
reference's per-membership ``Session``), and implements the membership
change protocol (consensus on the proposed cluster → notify runners →
bump version → rebuild communicator, or mark self detached).

Process model on TPU: one peer process per host, driving all local chips
(the launcher sets ``KF_COORDINATOR``/``KF_NUM_PROCESSES``/``KF_PROCESS_ID``
and we bring up ``jax.distributed``); or one process per simulated device in
CPU-backend test clusters; or a single process in single-controller mode.
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.request
from typing import Optional

from kungfu_tpu.comm.device import Communicator
from kungfu_tpu.comm.host import ConnType, HostChannel
from kungfu_tpu.monitor import timeline
from kungfu_tpu.plan.cluster import Cluster
from kungfu_tpu.utils import envs
from kungfu_tpu.utils.log import get_logger, log_event
from kungfu_tpu.utils.stall import stall_detector

_log = get_logger("peer")


class Peer:
    def __init__(self, config: Optional[envs.Config] = None):
        self.config = config or envs.parse_config_from_env()
        self.cluster: Cluster = self.config.cluster
        self.cluster_version: int = self.config.init_version
        self.detached: bool = False
        #: in the provisioned device world but not in the active worker
        #: list — alive, holding its jax.distributed slot, waiting to be
        #: re-included by a future resize (no reference analog: the
        #: reference kills/spawns processes, we re-carve the mesh)
        self.standby: bool = (
            self.config.world_peers is not None
            and self.cluster.workers.rank(self.config.self_id) is None
        )
        self._channel: Optional[HostChannel] = None
        self._comm: Optional[Communicator] = None
        self._comm_version = -1
        #: bootstrap slice topology (None = single slice, the byte-
        #: identical legacy path); the CURRENT topology is derived per
        #: membership via slice_topology() — whole-slice elasticity
        #: keeps ranks_per_slice invariant
        from kungfu_tpu.elastic.slices import bootstrap_topology

        try:
            self._slice_boot = bootstrap_topology(
                len(self.config.cluster.workers))
        except ValueError as e:
            # a pod host's inherited MEGASCALE_NUM_SLICES with a worker
            # world that does not tile it (e.g. -np 3 on a 2-slice pod's
            # env): before the multislice wiring this trained flat —
            # keep doing that, loudly, instead of crashing kf.init()
            _log.warning("incoherent multislice contract (%s) — "
                         "running single-slice (flat)", e)
            self._slice_boot = None
        #: carried across mesh epochs — the resize paths retire the old
        #: communicator object, not the user's strategy decision.
        #: Multislice default is two_stage: the hierarchical mesh's
        #: outer (DCN) stage then compiles as an explicit reduce-scatter
        #: + all-gather over slice representatives after the inner ICI
        #: psum (ops/schedules.all_reduce_scheduled), instead of one
        #: flat collective XLA must route across the slow axis blind.
        self._comm_strategy = self.config.device_strategy or (
            "two_stage" if self._slice_boot is not None else "psum")
        self._engine = None
        self._engine_version = -1
        self._lock = threading.RLock()
        self._started = False
        self._jax_initialized = False
        from kungfu_tpu.store.store import VersionedStore

        #: this peer's versioned model store (served to gossip peers)
        self.store = VersionedStore()
        #: control-plane blobs (reserved ``kf.`` names): own eviction
        #: window so gossip's per-step model versions cannot push out an
        #: epoch's strategy record before a joiner pulls it
        self._ctrl_store = VersionedStore(window=8)
        self.net_monitor = None
        self._metrics_server = None
        #: live-plane snapshot pusher (KF_CONFIG_ENABLE_CLUSTER_MONITOR)
        self._reporter = None

    # -- lifecycle -------------------------------------------------------
    def start(self) -> None:
        with self._lock:
            if self._started:
                return
            self._started = True
            platform = os.environ.get("KF_JAX_PLATFORM")
            if platform:
                import jax

                jax.config.update("jax_platforms", platform)
                if platform != "cpu":
                    # the chip path: compiles of whole-step programs are
                    # worth keeping across processes and runs
                    from kungfu_tpu.utils.compile_cache import \
                        enable_compile_cache

                    enable_compile_cache()
            monitor = None
            if envs.parse_bool_env(envs.ENABLE_MONITORING):
                from kungfu_tpu.monitor.metrics import (
                    METRICS_PORT_OFFSET,
                    MetricsServer,
                    NetMonitor,
                    monitoring_period_from_env,
                )

                monitor = NetMonitor(monitoring_period_from_env()).start()
                self.net_monitor = monitor
                try:
                    self._metrics_server = MetricsServer(
                        monitor, self.config.self_id.port + METRICS_PORT_OFFSET
                    ).start()
                    _log.info("/metrics on port %d", self._metrics_server.port)
                except OSError as e:
                    _log.warning("metrics server not started: %s", e)
            if not self.config.single_process:
                from kungfu_tpu.comm.host import bind_own_host_channel

                self._channel = bind_own_host_channel(
                    self.config.self_id, token=self.cluster_version,
                    monitor=monitor
                )
                from kungfu_tpu.store import install_p2p_handler

                self._p2p_stop = install_p2p_handler(
                    self._channel, self.store, self._ctrl_store,
                    n_peers=self.size())
            if self.config.coordinator and self.config.num_processes > 1:
                self._init_jax_distributed()
            if platform and platform != "cpu":
                # an accelerator was asked for: bring the backend up now
                # (after the distributed world, which must come first)
                # and refuse to train on anything else — a backend that
                # was initialized before start() ignores the config above
                got = jax.devices()[0].platform
                if got != platform:
                    raise RuntimeError(
                        f"the launcher asked for the {platform!r} backend "
                        f"but JAX came up on {got!r}")
            from kungfu_tpu.utils.affinity import bind_local_rank

            world = self.config.world_peers
            if world is not None:
                # world mode: pin by the STABLE world-slot position so the
                # binding survives resizes (and standby peers — which have
                # no active local rank — still get a valid share)
                lr = world.local_rank(self.config.self_id)
                bind_local_rank(
                    0 if lr is None else lr,
                    max(world.local_size(self.config.self_id), 1),
                )
            else:
                bind_local_rank(self.local_rank(), self.local_size())
            # every fresh process is about to cold-compile its step: tell
            # the failure detector (no-op without KF_MONITOR_ADDR).  This
            # also covers a joiner that reuses a rank id whose previous
            # incarnation left non-fresh detector state.
            from kungfu_tpu.monitor.signals import monitor_compile_grace

            monitor_compile_grace(self.rank())
            # flight-recorder identity: events (and the dump filename)
            # default to this worker's rank; in-process multi-peer test
            # clusters pass rank= explicitly at rank-owning call sites
            timeline.set_rank(None if self.detached or self.standby
                              else self.rank())
            # live cluster plane: push snapshots to the aggregator
            # co-hosted with the config server (kfrun -monitor).  The
            # reporter's identity is the STABLE bootstrap rank, matching
            # the flight recorder's per-process tracks — a shrink must
            # not make a promoted survivor alias a dead rank's row.
            if (envs.parse_bool_env(envs.ENABLE_CLUSTER_MONITOR)
                    and self.config.config_server):
                rank = self.chaos_rank()
                if rank is None and not (self.detached or self.standby):
                    rank = self.rank()
                if rank is not None:
                    from kungfu_tpu.monitor.aggregator import RankReporter
                    from kungfu_tpu.monitor.metrics import (
                        install_compile_metrics, publish_device_memory)

                    # XLA compiles become registry series the snapshot
                    # carries (kf_jit_compiles_total — the sentinel's
                    # recompile-steady feedstock)
                    install_compile_metrics()
                    # slice identity rides the same stable bootstrap
                    # frame as the rank: kftop's per-slice grouping
                    # must not re-home a row when a shrink renumbers
                    # the live topology
                    slice_id = (self._slice_boot.slice_of(rank)
                                if self._slice_boot is not None else None)
                    self._reporter = RankReporter(
                        rank, self.config.config_server,
                        strategy_fn=self._active_strategy,
                        net_totals_fn=(self._net_totals
                                       if monitor is not None else None),
                        slice_id=slice_id,
                        # HBM gauges refresh once per push (None-safe:
                        # CPU backends simply publish nothing)
                        pre_snapshot_fn=publish_device_memory,
                    ).start()
            log_event("peer-started")

    def _active_strategy(self) -> str:
        """The active strategy/arm set, stamped on live snapshots (the
        kftop ``strategy`` column): the host-engine strategy in force
        (set_strategy / adaptation swaps included; an installed explicit
        tree renders as ``tree``), plus the device communicator's
        per-bucket schedule table when the kf-adapt bandit has installed
        one — e.g. ``STAR dev[small=psum,large=ring]``."""
        engine = self._engine
        s = engine.strategy if engine is not None else self.config.strategy
        name = "tree" if (engine is not None and s is None) \
            else getattr(s, "name", str(s))
        comm = self._comm
        if comm is not None:
            buckets = comm.bucket_summary()
            if buckets:
                name = f"{name} dev[{buckets}]"
        return name

    def _net_totals(self) -> dict:
        mon = self.net_monitor
        if mon is None:
            return {}
        totals = mon.totals()
        return {
            "egress_bytes": sum(totals["egress"].values()),
            "ingress_bytes": sum(totals["ingress"].values()),
        }

    def _init_jax_distributed(self) -> None:
        """Bring up the jax.distributed world ONCE per process.

        The device world is fixed for a process's lifetime (jax.distributed
        cannot re-initialize in-process).  Two operating modes:

        * **Provisioned world** (``KF_WORLD_PEERS`` set): the world spans
          ALL provisioned slots — every slot's process boots here at job
          start, whether or not it is in the initial worker list.  Elastic
          resize then re-carves the Communicator mesh over the *active*
          workers' devices (``_carve_active_devices``); inactive in-world
          peers go ``standby`` instead of detaching.  This is the live
          resize the reference promises (``peer/peer.go:236-276`` +
          ``gpu/scheduler.cpp:43-72``): survivors keep training on the
          device plane, no process relaunch.

        * **Fixed world** (no ``KF_WORLD_PEERS``): world == the initial
          worker set; a resize beyond it only takes effect in relaunched
          workers and ``_propose`` warns on the survivors."""
        import jax

        platform = os.environ.get("KF_JAX_PLATFORM") or ""
        if platform == "cpu":
            # CPU-backend multi-process clusters (the fake-cluster test
            # trick, SURVEY §4) need an explicit cross-process collectives
            # impl; TPU uses ICI/DCN natively
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
            ndev = os.environ.get(envs.NUM_DEVICES)
            if ndev:
                jax.config.update("jax_num_cpu_devices", int(ndev))
        with stall_detector("jax.distributed.initialize"):
            jax.distributed.initialize(
                coordinator_address=self.config.coordinator,
                num_processes=self.config.num_processes,
                process_id=self.config.process_id,
            )
            # force backend bring-up NOW: global device discovery exchanges
            # every process's local topology through the coordinator — a
            # standby peer that never touched jax would otherwise stall
            # every active peer's first jax.devices() call forever
            n = len(jax.devices())
        self._jax_initialized = True
        self._jax_world_procs = self.config.num_processes
        _log.info(
            "jax.distributed world up: %d processes, %d devices",
            self.config.num_processes, n,
        )

    def _carve_active_devices(self):
        """Devices of the ACTIVE workers, in worker-rank order — the mesh
        epoch is a sub-mesh of the provisioned world (grow/shrink =
        re-carving, not re-initializing).  Returns (devices, local_size),
        or (None, None) to fall back to the full-world mesh."""
        world = self.config.world_peers
        if world is None:
            return None, None
        import jax

        by_proc = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, []).append(d)
        devs, per = [], None
        for w in self.cluster.workers:
            wr = world.rank(w)
            if wr is None or wr not in by_proc:
                _log.warning(
                    "worker %s is outside the provisioned device world "
                    "(%d slots) — cannot carve a device mesh for this "
                    "membership; falling back to the full-world mesh", w,
                    len(world),
                )
                return None, None
            ds = by_proc[wr]
            if per is None:
                per = len(ds)
            elif len(ds) != per:
                _log.warning(
                    "uneven device counts per world slot (%d vs %d) — "
                    "falling back to the full-world mesh", len(ds), per,
                )
                return None, None
            devs.extend(ds)
        # the mesh's local axis must span a HOST (the local_*/cross_*
        # hierarchy contract, see Communicator._infer_local_size), not a
        # process: a host may hold several world slots
        hosts = [w.host for w in self.cluster.workers]
        counts = {}
        seen = set()
        contiguous = True
        for i, h in enumerate(hosts):
            counts[h] = counts.get(h, 0) + 1
            if i > 0 and h != hosts[i - 1] and h in seen:
                contiguous = False  # host's workers split into >1 run
            seen.add(h)
        sizes = set(counts.values())
        if len(sizes) == 1 and contiguous:
            local_size = sizes.pop() * (per or 1)
        else:
            _log.warning(
                "active workers are unevenly or non-contiguously placed "
                "across hosts %s: mesh degrades to flat 1x%d — local_* "
                "collectives will span ALL devices and cross_* collectives "
                "become no-ops", counts, len(devs),
            )
            local_size = len(devs)
        return devs, local_size

    def close(self) -> None:
        # flush the flight recorder before tearing channels down (the
        # atexit hook also fires, but a long-lived driver that closes and
        # re-opens peers would otherwise only dump its last incarnation)
        timeline.maybe_dump()
        if self._reporter is not None:
            # final push BEFORE channels tear down: a clean shutdown
            # leaves fresh numbers on the aggregator, not a stale flag
            self._reporter.stop(final_push=True)
            self._reporter = None
        with self._lock:
            if self._channel is not None:
                self._notify_done()
                if getattr(self, "_p2p_stop", None) is not None:
                    self._p2p_stop()
                    self._p2p_stop = None
                self._channel.close()
                self._channel = None
            if self._metrics_server is not None:
                self._metrics_server.stop()
                self._metrics_server = None
            if self.net_monitor is not None:
                self.net_monitor.stop()
                self.net_monitor = None
            if self._engine is not None:
                self._engine.close()
            self._engine = None
            self._engine_version = -1
            self._retire_comm()  # keep the strategy across close/start
            self._comm_version = -1
            self._started = False

    # -- identity --------------------------------------------------------
    def rank(self) -> int:
        if self.detached or self.standby:
            return -1
        r = self.cluster.workers.rank(self.config.self_id)
        if r is None:
            raise RuntimeError(
                f"{self.config.self_id} not in worker list {self.cluster.workers}"
            )
        return r

    def size(self) -> int:
        return self.cluster.size()

    def local_rank(self) -> int:
        r = self.cluster.workers.local_rank(self.config.self_id)
        return 0 if r is None else r

    def local_size(self) -> int:
        return self.cluster.workers.local_size(self.config.self_id)

    @property
    def channel(self) -> Optional[HostChannel]:
        return self._channel

    # -- slice identity (multislice pods) ---------------------------------
    def slice_topology(self):
        """The CURRENT membership's :class:`~kungfu_tpu.elastic.slices.
        SliceTopology`, or ``None`` on a single-slice job.  Ranks-per-
        slice is the bootstrap invariant; the slice count follows the
        membership (slice-granular elasticity keeps it whole).  A
        membership that no longer tiles is the rank-granular tail — a
        job shrunk to its last slice keeps surviving RANK deaths
        (elastic/shrink.py falls back to the classic ladder there), and
        from then on slice semantics are over: ``None``."""
        if self._slice_boot is None:
            return None
        try:
            return self._slice_boot.for_size(self.size())
        except ValueError:
            return None

    def slice_id(self) -> Optional[int]:
        """This worker's slice in the CURRENT membership (``None`` on a
        single-slice job; raises for detached/standby peers, like
        :meth:`rank`)."""
        topo = self.slice_topology()
        return None if topo is None else topo.slice_of(self.rank())

    def chaos_rank(self) -> Optional[int]:
        """Stable fault-injection identity: this process's rank in its
        BOOTSTRAP worker list.  Elastic reshuffles change :meth:`rank`
        (a shrink promotes survivors), and a rank-scoped chaos
        clause must keep pointing at the same process for the whole
        experiment — the end-to-end repro of the alternative is a
        ``die`` clause re-firing on the promoted survivor of the very
        failure it injected."""
        return self.config.cluster.workers.rank(self.config.self_id)

    # -- communicator (mesh epoch) ---------------------------------------
    def _retire_comm(self) -> None:
        """Drop the current communicator ahead of a new mesh epoch,
        preserving the installed allreduce strategy (set_strategy /
        autotune) for the next epoch's build.  Callers hold the lock."""
        if self._comm is not None:
            self._comm_strategy = self._comm.strategy
        self._comm = None

    def _record_strategy(self, name: str) -> None:
        """``on_strategy_change`` hook: a ``set_strategy`` call lands on
        the Peer durably even if the communicator object it was made on
        is being retired by a concurrent resize."""
        self._comm_strategy = name

    _STRATEGY_BLOB = "kf.device-strategy"

    def _sync_device_strategy(self, version: int) -> None:
        """Cluster-consistent device schedule for a mesh epoch: rank 0's
        strategy IS the epoch's strategy — it publishes to its blob store
        keyed by the cluster version, everyone else adopts via a p2p pull
        (retried: rank 0 publishes when it builds its own communicator).

        This is mandatory, not cosmetic, on multi-controller meshes: a
        survivor compiling ring collectives while a joiner compiles psum
        is two DIFFERENT programs on one mesh — a deadlock, not a wrong
        value.  (The reference sidesteps this by rebuilding sessions from
        the static configured strategy on every membership change,
        i.e. runtime swaps do not survive resizes at all; here they
        survive whenever rank 0 survives.)  A joiner that becomes rank 0
        resets the epoch to its own default — consistency wins over
        persistence."""
        if self._channel is None or self.size() <= 1:
            return
        ver = str(version)
        if self.rank() == 0:
            # fixed-width payload: Store.save refuses same-name size
            # changes, and a close/start cycle may legitimately
            # re-publish a different (longer) strategy for this version
            self._ctrl_store.save(
                self._STRATEGY_BLOB,
                self._comm_strategy.ljust(32).encode(), version=ver
            )
            return
        deadline = time.monotonic() + 30.0
        attempt = 0
        while time.monotonic() < deadline:
            try:
                blob = self.request(0, self._STRATEGY_BLOB, version=ver,
                                    timeout=5.0)
            except (OSError, ConnectionError, TimeoutError):
                blob = None
            if blob:
                self._comm_strategy = blob.decode().strip()
                return
            from kungfu_tpu.utils.retry import sleep_backoff

            # every non-zero rank polls rank 0 at once after a resize;
            # jittered backoff keeps the pulls from re-synchronizing
            sleep_backoff(attempt, base=0.2, cap=1.0)
            attempt += 1
        _log.warning(
            "no device-strategy from rank 0 for v%d after 30s; keeping %r "
            "(mesh-wide schedule mismatch possible)",
            version, self._comm_strategy,
        )

    def communicator(self) -> Communicator:
        """The communicator for the current cluster version; rebuilt lazily
        after membership changes (analog of ``Peer.CurrentSession`` +
        ``updateTo``, peer.go:138-166)."""
        with self._lock:
            if self.standby:
                raise RuntimeError(
                    "standby peer is not in the active worker list; call "
                    "await_rejoin() before communicator()"
                )
            if self._comm is None or self._comm_version != self.cluster_version:
                devices = local_size = None
                if self._jax_initialized:
                    devices, local_size = self._carve_active_devices()
                if self._slice_boot is not None and devices is not None:
                    # multislice: the mesh epoch is hierarchical — outer
                    # axis = slice (DCN), inner = within-slice (ICI).
                    # slice_mesh_layout re-groups the carved devices by
                    # slice (the emulation contract groups by process)
                    # and validates the federation against the CURRENT
                    # topology: after a slice-shrink the surviving
                    # devices regroup into fewer slices — the DCN mesh
                    # re-carve (docs/multislice.md).  Without a booted
                    # jax.distributed world (devices=None: the host-
                    # plane emulation) this lone process's local devices
                    # cannot show the federation — the legacy local
                    # Communicator stands
                    from kungfu_tpu.platforms.tpu_pod import \
                        slice_mesh_layout

                    topo = self.slice_topology()
                    devices, local_size = slice_mesh_layout(
                        topo.num_slices, devices)
                # an installed schedule (set_strategy / autotune)
                # survives the mesh epoch swap — the resize rebuilds the
                # mesh, not the user's strategy decision — and the epoch
                # agrees on ONE schedule cluster-wide (rank 0's)
                self._retire_comm()
                self._sync_device_strategy(self.cluster_version)
                self._comm = Communicator(
                    cluster=self.cluster,
                    version=self.cluster_version,
                    devices=devices,
                    local_size=local_size,
                    strategy=self._comm_strategy,
                    on_strategy_change=self._record_strategy,
                )
                self._comm_version = self.cluster_version
                _log.info("new %r", self._comm)
            return self._comm

    def engine(self):
        """Graph-collective engine over the host channel for the current
        membership — the multi-process data path when no shared XLA mesh
        exists (CPU test clusters, between-mesh-epoch phases).  None in
        single-process mode."""
        with self._lock:
            if self._channel is None:
                return None
            if self._engine is None or self._engine_version != self.cluster_version:
                from kungfu_tpu.comm.engine import CollectiveEngine

                if self._engine is not None:
                    self._engine.close()
                self._engine = CollectiveEngine(
                    self._channel, self.cluster.workers, self.config.strategy,
                    chaos_rank=self.chaos_rank(),
                )
                self._engine_version = self.cluster_version
            return self._engine

    # -- sync ------------------------------------------------------------
    def barrier(self) -> None:
        """Host-level barrier across worker processes."""
        if self.size() <= 1 or self._channel is None:
            return
        with timeline.span("collective", "peer.barrier", op="barrier"), \
                stall_detector("barrier"):
            self._channel.barrier(
                self.cluster.workers, name=f"barrier.v{self.cluster_version}"
            )

    def consensus_bytes(self, data: bytes, name: str = "consensus") -> bool:
        if self.size() <= 1 or self._channel is None:
            return True
        return self._channel.consensus_bytes(
            data, self.cluster.workers, name=f"{name}.v{self.cluster_version}"
        )

    # -- elasticity (full protocol in kungfu_tpu.elastic) -----------------
    def propose_new_size(self, new_size: int) -> None:
        """Rank 0 PUTs the resized cluster to the config server
        (reference ``peer/legacy.go:18-39``)."""
        if not self.config.config_server:
            raise RuntimeError("propose_new_size requires KF_CONFIG_SERVER")
        if self.rank() != 0:
            return
        from kungfu_tpu.elastic.resize import slice_aligned_size

        # multislice: planned elasticity moves whole slices (a fractional
        # slice has no within-slice mesh to join) — no-op on single-slice
        new_size = slice_aligned_size(self, new_size)
        world = self.config.world_peers
        if world is not None and new_size > len(world):
            # a phantom worker (valid PeerID, no process) would wedge every
            # later host-plane collective waiting for it to come up; clamp
            # rather than raise — schedules drive this from per-step hooks
            # and an over-ask must not kill the training run
            _log.warning(
                "proposed size %d exceeds the provisioned device world "
                "(%d slots) — clamping to the world capacity",
                new_size, len(world),
            )
            new_size = len(world)
        new_cluster = self.cluster.resize(new_size)
        req = urllib.request.Request(
            self.config.config_server,
            data=new_cluster.to_json().encode(),
            method="PUT",
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            resp.read()

    def resize_cluster_from_url(self) -> bool:
        """Fetch the target cluster from the config server, reach consensus,
        and apply (reference ``peer.go:236-263``).  Returns True if
        membership changed."""
        if not self.config.config_server:
            raise RuntimeError("resize requires KF_CONFIG_SERVER")
        from kungfu_tpu.elastic.resize import fetch_cluster_with_consensus

        new_cluster, version = fetch_cluster_with_consensus(self)
        return self._propose(new_cluster, version)

    def resize_cluster(self, n: int) -> bool:
        """Direct resize (config-server-backed when available)."""
        if self.config.config_server:
            self.propose_new_size(n)
            return self.resize_cluster_from_url()
        new_cluster = self.cluster.resize(n)
        return self._propose(new_cluster, self.cluster_version + 1)

    def _propose(self, new_cluster: Cluster, version: int) -> bool:
        """Apply an agreed membership change (reference ``peer.go:177-225``):
        notify runners, bump version, detach if not in the new worker list."""
        # kf-overlap fence: an async collective handle may never cross a
        # membership change (its tags and peer set belong to the old
        # epoch; the post-resize engine rebuild would strand its recvs).
        # Settling is deadline-bounded, so this cannot hang on a dead
        # peer — a doomed handle completes with its typed failure, which
        # still re-raises at that handle's own wait().  Outside the lock:
        # the draining collectives' completion path must not need it.
        eng = self._engine
        if eng is not None:
            eng.drain_async()
        with self._lock:
            if new_cluster.workers == self.cluster.workers:
                return False
            with timeline.span("mark", "peer.propose", version=version), \
                    stall_detector("propose"):
                self._notify_runners(new_cluster, version)
                self.cluster = new_cluster
                self.cluster_version = version
                if self._channel is not None:
                    self._channel.set_token(version)
                    # pooled sockets to removed peers must not leak
                    self._channel.reset_connections()
                world = self.config.world_peers
                active = new_cluster.workers.rank(self.config.self_id) is not None
                in_world = (
                    world is not None
                    and world.rank(self.config.self_id) is not None
                )
                # in-world peers never detach: they go standby and can be
                # re-carved into a later mesh epoch without a relaunch
                self.detached = not active and not in_world
                self.standby = not active and in_world
                self._retire_comm()  # next communicator() builds the new epoch
                if self._jax_initialized and active and world is None:
                    new_procs = len(new_cluster.workers)
                    if new_procs != getattr(self, "_jax_world_procs", new_procs):
                        # fixed-world mode only (no KF_WORLD_PEERS): the
                        # device world is per-process-lifetime; collectives
                        # in this process keep spanning the ORIGINAL world.
                        # With a provisioned world this path is unreachable —
                        # communicator() re-carves the sub-mesh instead.
                        _log.warning(
                            "resize to %d worker processes but this "
                            "process's jax.distributed world has %d — "
                            "surviving processes keep their original device "
                            "world; the new world takes effect in "
                            "relaunched workers only (set KF_WORLD_PEERS "
                            "to provision a max world for live resize)",
                            new_procs, self._jax_world_procs,
                        )
            log_event(f"cluster-resized-v{version}-n{new_cluster.size()}")
        # control event for the live plane (best-effort, outside the
        # lock): rank 0 of the NEW membership announces the resize so
        # kftop's cluster-health line flips with the epoch
        if new_cluster.workers.rank(self.config.self_id) == 0:
            from kungfu_tpu.monitor.aggregator import post_control_if_enabled

            post_control_if_enabled(self, "resize", version=version,
                                    size=new_cluster.size())
        return True

    def _notify_done(self) -> None:
        """Tell every runner the job completed cleanly (rank 0, on close).
        Hosts the schedule shrank to zero workers have a runner idling for
        a possible re-grow — without this signal they could never exit
        (``watch_run``'s job_done condition)."""
        if self.config.parent is None or self.detached or self.standby:
            return
        if self.cluster.workers.rank(self.config.self_id) != 0:
            return  # rank() is None for non-members — also not rank 0
        for runner in self.cluster.runners:
            try:
                # best-effort: a runner whose host finished earlier is
                # already gone — don't ride the 500-retry connect loop
                self._channel.send(
                    runner, "done", b"", ConnType.CONTROL, retries=2
                )
            except (TimeoutError, ConnectionError, OSError) as e:
                _log.debug("cannot send done to runner %s: %s", runner, e)

    def _notify_runners(self, new_cluster: Cluster, version: int) -> None:
        """Send the new Stage to every runner so they can spawn/kill local
        workers (reference ``peer.go:195-209`` → ``runner/handler.go``).
        Skipped when no runner spawned us (mp-spawn / direct-driven test
        clusters have no runner daemon to notify).

        Rank 0 fans the stage out to EVERY runner; every OTHER worker
        also sends it to its own parent.  The parent copy closes a
        shutdown race: a worker the stage removes exits right after this
        call, and if rank 0's fan-out were the only copy, the runner
        could reap that exit first, read it as the job's natural end, and
        quit — orphaning the host for later re-grows.  The local send
        happens-before the local exit; duplicate versions are tolerated
        (``watch_run`` cross-checks and drops them)."""
        if self._channel is None or self.config.parent is None:
            return
        # rank in the OLD membership; standby/detached peers don't notify
        if self.cluster.workers.rank(self.config.self_id) is None:
            return
        stage = json.dumps(
            {"version": version, "cluster": json.loads(new_cluster.to_json())}
        ).encode()
        targets = (new_cluster.runners
                   if self.cluster.workers.rank(self.config.self_id) == 0
                   else [self.config.parent])
        wait_s = envs.parse_float_env(envs.WAIT_RUNNER_TIMEOUT, 10.0)
        for runner in targets:
            try:
                self._channel.wait(runner, timeout=wait_s)
                self._channel.send(runner, "update", stage, ConnType.CONTROL)
            except (TimeoutError, ConnectionError) as e:
                _log.warning("cannot notify runner %s: %s", runner, e)

    # -- standby / world (provisioned-world live elasticity) --------------
    def world_barrier(self, name: str = "world") -> None:
        """Host-plane barrier over ALL provisioned slots (active + standby).
        Used for job-wide phases (start/shutdown) that must include peers
        currently outside the worker list."""
        world = self.config.world_peers
        if world is None or len(world) <= 1 or self._channel is None:
            return
        with timeline.span("collective", "peer.world_barrier", op="barrier"), \
                stall_detector("world_barrier"):
            self._channel.barrier(world, name=f"wbarrier.{name}")

    def observe_stage(self):
        """Fetch the config server's current (cluster, version) without
        applying it — standby peers poll this to decide when to rejoin or
        shut down."""
        if not self.config.config_server:
            raise RuntimeError("observe_stage requires KF_CONFIG_SERVER")
        from kungfu_tpu.elastic.resize import fetch_cluster

        return fetch_cluster(self.config.config_server)

    def await_rejoin(self, timeout: float = 300.0, poll_period: float = 0.2) -> bool:
        """Standby peer blocks until the config server publishes a stage
        that includes it, then adopts that stage (version fence + fresh
        mesh epoch).  Returns True on rejoin; False if a newer stage
        excludes us and ``timeout`` elapses.

        The active set reached consensus on the stage before publishing
        (``fetch_cluster_with_consensus``); a joining standby peer takes
        the versioned config server as the source of truth — its first
        collective with the new membership synchronizes it with the
        survivors (device-plane collectives block until every participant
        arrives, the moral of the reference's post-update ``sess.Barrier()``,
        ``peer.go:144-166``)."""
        from kungfu_tpu.utils.retry import sleep_backoff

        deadline = time.time() + timeout
        failures = 0
        while time.time() < deadline:
            try:
                cluster, version = self.observe_stage()
            except (OSError, ValueError, KeyError) as e:
                _log.debug("stage fetch failed: %s", e)
                # a DOWN config server + every standby peer polling it =
                # a reconnect storm at recovery time; back off instead
                sleep_backoff(failures, base=poll_period, cap=2.0)
                failures += 1
                continue
            failures = 0
            if version > self.cluster_version:
                if cluster.workers.rank(self.config.self_id) is not None:
                    with self._lock:
                        self.cluster = cluster
                        self.cluster_version = version
                        if self._channel is not None:
                            self._channel.set_token(version)
                            self._channel.reset_connections()
                        self.standby = False
                        self.detached = False
                        self._retire_comm()
                    log_event(f"rejoined-v{version}-n{cluster.size()}")
                    return True
                # newer stage still excludes us: track the version so a
                # subsequent rejoin fences on the right token
                with self._lock:
                    self.cluster = cluster
                    self.cluster_version = version
                    if self._channel is not None:
                        self._channel.set_token(version)
            time.sleep(poll_period)
        return False

    # -- in-flight fault tolerance (elastic.shrink) ------------------------
    def recover_from_failure(self, failure: Optional[BaseException] = None,
                             snapshot=None, zero_boundary=None,
                             stage_boundary=None):
        """Survivor-side in-flight recovery after a collective raised
        :class:`~kungfu_tpu.comm.faults.PeerFailureError`: confirm the
        dead set by ping, run the exclusion consensus, apply the shrunk
        membership through the propose path, and return ``(shrunk,
        replay)`` — see :func:`kungfu_tpu.elastic.shrink.
        recover_from_peer_failure`.  Raises ``QuorumLostError`` (after
        signaling the failure detector) when the survivors are not a
        strict majority — the detector-driven relaunch is the last
        resort, no longer the only mechanism.

        ``zero_boundary`` (a :class:`kungfu_tpu.elastic.reshard.
        ZeroBoundary`) carries ZeRO-sharded optimizer state through the
        shrink: it is re-carved leaderlessly across the survivors (dead
        ranks' chunks served from ring-buddy mirrors) — see
        docs/zero.md.

        ``stage_boundary`` (a :class:`kungfu_tpu.parallel.pp.
        StageBoundary`) carries a pipeline stage through it the same
        way: the survivors re-balance layers over the remaining stages,
        a whole dead stage restored from its predecessor's ring-buddy
        mirror — recovery-ladder rung 10 (docs/pipeline.md)."""
        from kungfu_tpu.elastic.shrink import recover_from_peer_failure

        return recover_from_peer_failure(self, failure, snapshot,
                                         zero_boundary=zero_boundary,
                                         stage_boundary=stage_boundary)

    # -- monitoring / adaptation (reference peer.hpp GetPeerLatencies /
    # CheckInterference / GetEgressRates / SetTree) ----------------------
    def get_peer_latencies(self, samples: int = 1):
        from kungfu_tpu.monitor.adapt import get_peer_latencies

        return get_peer_latencies(self, samples)

    def get_egress_rates(self):
        if self.net_monitor is None:
            return [0.0] * self.size()
        return self.net_monitor.egress_rates(
            [str(w) for w in self.cluster.workers]
        )

    def check_interference(self) -> bool:
        from kungfu_tpu.monitor.adapt import check_interference, majority_vote_interference

        engine = self.engine()
        suspected = bool(engine and check_interference(engine))
        return majority_vote_interference(self, suspected)

    def set_tree(self, forest) -> None:
        """Install an explicit broadcast tree after cluster-wide agreement
        (reference SetTree: consensus on the tree digest, barrier, swap)."""
        from kungfu_tpu.monitor.adapt import set_tree
        from kungfu_tpu.plan.graph import Graph

        digest = Graph.from_forest_array(forest).digest_bytes()
        if not self.consensus_bytes(digest, name="set-tree"):
            raise RuntimeError("peers disagree on the proposed tree")
        self.barrier()
        engine = self.engine()
        if engine is not None:
            set_tree(engine, forest)

    # -- p2p blob store (gossip) -----------------------------------------
    def save(self, name: str, blob, version: Optional[str] = None,
             copy: bool = True) -> None:
        """Save into this peer's gossip store.  Names under ``kf.`` are
        reserved for the control plane (served from a separate store).
        ``copy=False`` hands over the caller's buffer (never mutate it
        after) — the gossip hot path publishes ~100 MiB fused models."""
        self.store.save(name, blob, version, copy=copy)

    def request(self, target_rank: int, name: str,
                version: Optional[str] = None,
                timeout: float = 60.0) -> Optional[bytes]:
        """Pull a named blob from a peer's versioned store
        (reference ``p2p.go:15-41``, ``handler/p2p.go:102-120``).
        ``kf.``-prefixed names are answered from the target's
        control-plane store."""
        from kungfu_tpu.store import remote_request

        target = self.cluster.workers[target_rank]
        return remote_request(self, target, name, version, timeout=timeout)

    def request_into(self, target_rank: int, name: str, buf,
                     version: Optional[str] = None,
                     timeout: float = 60.0,
                     send_retries: Optional[int] = None):
        """Pull a named blob INTO a preallocated buffer — zero-copy on
        the native backend (see :func:`remote_request_into`).
        ``send_retries`` bounds the request's connect ladder (miss-
        tolerant callers like gossip fail fast on a dead target)."""
        from kungfu_tpu.store import remote_request_into

        target = self.cluster.workers[target_rank]
        return remote_request_into(self, target, name, buf, version,
                                   timeout=timeout,
                                   send_retries=send_retries)
