"""Job → worker process construction.

Parity with reference ``srcs/go/kungfu/job/job.go:31-72``: build one Proc
per local worker with the full ``KF_*`` bootstrap env.  Device slotting:
where the reference assigned ``CUDA_VISIBLE_DEVICES`` per slot
(``cuda_visible_device.go``), the TPU build pins CPU-backend test workers
to their own virtual device world, and TPU workers get the standard
per-host TPU visibility (one worker process per host sees all local chips).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from kungfu_tpu.plan.cluster import Cluster
from kungfu_tpu.plan.peer import PeerID
from kungfu_tpu.plan.peerlist import PeerList
from kungfu_tpu.plan.strategy import Strategy
from kungfu_tpu.runner.proc import Proc
from kungfu_tpu.utils import envs

#: jax.distributed coordinator port = first worker's (job-unique) peer
#: port + this offset, so two jobs sharing a host never collide
COORDINATOR_PORT_OFFSET = 20000


@dataclass
class Job:
    prog: str
    args: List[str]
    strategy: Strategy = Strategy.AUTO
    device_strategy: str = ""  # initial device allreduce schedule
    config_server: str = ""
    log_dir: str = ""
    parent: Optional[PeerID] = None
    extra_envs: Dict[str, str] = field(default_factory=dict)
    backend: str = "cpu"  # worker jax platform: "cpu" test clusters | "tpu"
    #: full provisioned worker-slot list (device-world elastic mode): the
    #: jax.distributed world is booted once over ALL slots; resize re-carves
    #: the mesh over the active subset (see Peer._carve_active_devices)
    world: Optional[PeerList] = None
    #: multislice worker partitioning (``kfrun -num-slices``): > 1 stamps
    #: each worker's env with its slice identity (slice-major contiguous,
    #: ``MEGASCALE_SLICE_ID = rank // ranks_per_slice`` — the tpu_pod
    #: emulation contract) plus ``MEGASCALE_NUM_SLICES``/``KF_SLICE_RANKS``
    #: so the peers build the hierarchical communicator and slice-granular
    #: elasticity with no user code change
    slices: int = 0
    #: ranks per slice, pinned at the FIRST spawn (0 = derive then): a
    #: watch-mode respawn after a resize passes the CURRENT cluster, and
    #: re-deriving from its size would stamp joiners with a different
    #: slice geometry than the incumbents hold
    slice_rps: int = 0
    job_start: float = field(default_factory=time.time)

    def new_proc(self, worker: PeerID, cluster: Cluster, version: int = 0) -> Proc:
        rank = cluster.workers.rank(worker)
        env = {
            envs.SELF_SPEC: str(worker),
            envs.INIT_PEERS: str(cluster.workers),
            envs.INIT_RUNNERS: str(cluster.runners),
            envs.INIT_CLUSTER_VERSION: str(version),
            envs.ALLREDUCE_STRATEGY: str(self.strategy),
            **({envs.DEVICE_STRATEGY: self.device_strategy}
               if self.device_strategy else {}),
            envs.JOB_START_TIMESTAMP: f"{self.job_start:.3f}",
            envs.PROC_START_TIMESTAMP: f"{time.time():.3f}",
        }
        if self.parent is not None:
            env[envs.PARENT_ID] = str(self.parent)
        if self.slices and self.slices > 1:
            # slice identity rides the STABLE spawn rank (world-slot index
            # in device-world mode): elastic reshuffles re-rank workers
            # but never move a process between slices
            spawn_list = self.world if self.world is not None else cluster.workers
            base_rank = (self.world.rank(worker) if self.world is not None
                         else rank)
            if self.slice_rps <= 0:
                # first spawn pins the geometry; later calls (watch-mode
                # respawns over a RESIZED cluster) reuse it — the slice
                # count follows the membership, ranks-per-slice never
                # changes (the elastic layer's whole-slice invariant)
                if len(spawn_list) % self.slices:
                    raise ValueError(
                        f"{len(spawn_list)} worker slot(s) cannot "
                        f"partition into {self.slices} slices")
                self.slice_rps = len(spawn_list) // self.slices
            rps = self.slice_rps
            if base_rank is None or len(spawn_list) % rps:
                raise ValueError(
                    f"{len(spawn_list)} worker slot(s) do not tile "
                    f"{rps}-rank slices")
            env[envs.MEGASCALE_NUM_SLICES] = str(len(spawn_list) // rps)
            env[envs.MEGASCALE_SLICE_ID] = str(base_rank // rps)
            env[envs.SLICE_RANKS] = str(rps)
        if self.config_server:
            env[envs.CONFIG_SERVER] = self.config_server
        # the worker's platform is pinned, never left to JAX's choice:
        # a "tpu" worker that finds no TPU fails at kf.init() instead of
        # training on the host's CPU.  kf.init() applies KF_JAX_PLATFORM
        # through jax.config as well, which holds even where jax was
        # imported before the variable was set.
        env["JAX_PLATFORMS"] = self.backend
        env["KF_JAX_PLATFORM"] = self.backend
        if self.world is not None:
            # provisioned device world: EVERY slot (active or standby) joins
            # one jax.distributed world keyed by its stable world-slot index
            wr = self.world.rank(worker)
            if wr is None:
                raise ValueError(f"worker {worker} is not a provisioned world slot")
            first = self.world[0]
            coord_port = first.port + COORDINATOR_PORT_OFFSET
            if coord_port > 65535:
                coord_port = 20000 + (coord_port % 25536)
            env[envs.WORLD_PEERS] = str(self.world)
            env[envs.COORDINATOR] = f"{first.host}:{coord_port}"
            env[envs.NUM_PROCESSES] = str(len(self.world))
            env[envs.PROCESS_ID] = str(wr)
            if self.backend == "cpu":
                # extra_envs is merged last and may override this default
                env[envs.NUM_DEVICES] = "1"
        elif self.backend != "cpu":
            # TPU backend, one worker process per host driving all its
            # chips (the launcher refuses more: a chip belongs to one
            # process).  Across hosts the workers form one
            # jax.distributed world (device plane over ICI/DCN — the
            # NCCL-bootstrap analog): the coordinator is the first
            # worker's host, and peer.start() runs
            # jax.distributed.initialize from these envs.  (CPU workers
            # are each their own single-device world; their collectives
            # run on the host channel, CollectiveEngine.)
            n = len(cluster.workers)
            if n > 1 and rank is not None:
                first = cluster.workers[0]
                coord_port = first.port + COORDINATOR_PORT_OFFSET
                if coord_port > 65535:
                    # user-supplied port ranges above 45535 would derive an
                    # impossible port and fail at jax.distributed init —
                    # wrap back into the dynamic range instead
                    coord_port = 20000 + (coord_port % 25536)
                env[envs.COORDINATOR] = f"{first.host}:{coord_port}"
                env[envs.NUM_PROCESSES] = str(n)
                env[envs.PROCESS_ID] = str(rank)
        # make the kungfu_tpu package importable in workers regardless of cwd
        import os as _os

        import kungfu_tpu as _pkg

        pkg_root = _os.path.dirname(_os.path.dirname(_os.path.abspath(_pkg.__file__)))
        existing = _os.environ.get("PYTHONPATH", "")
        env["PYTHONPATH"] = pkg_root + (_os.pathsep + existing if existing else "")
        env.update(self.extra_envs)
        return Proc(
            name=f"worker-{rank}" if rank is not None else f"worker-{worker.port}",
            prog=self.prog,
            args=list(self.args),
            envs=env,
            log_dir=self.log_dir,
        )

    def create_procs(self, cluster: Cluster, self_host: str, version: int = 0) -> List[Proc]:
        """Procs for all workers on ``self_host``
        (reference ``job.go:74`` CreateProcs).  In device-world mode ALL
        provisioned slots are spawned — slots outside the initial worker
        list boot as standby peers."""
        spawn_list = self.world if self.world is not None else cluster.workers
        return [
            self.new_proc(w, cluster, version)
            for w in spawn_list
            if w.host == self_host
        ]
