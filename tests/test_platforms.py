"""TPU-pod platform adapter (the reference's modelarts-adapter slot,
``platforms/modelarts/modelarts.go`` — scheduler env → cluster inputs)."""

import pytest

from kungfu_tpu.platforms import parse_tpu_pod_env
from kungfu_tpu.platforms.tpu_pod import detected


class TestParse:
    def test_not_a_pod(self):
        assert parse_tpu_pod_env(env={}) is None
        assert not detected(env={})

    def test_four_host_pod(self):
        env = {
            "TPU_WORKER_HOSTNAMES": "t1k-0,t1k-1,t1k-2,t1k-3",
            "TPU_WORKER_ID": "2",
        }
        info = parse_tpu_pod_env(env=env)
        assert info.num_hosts == 4
        assert info.self_host == "t1k-2" and info.worker_id == 2
        assert info.num_slices == 1 and info.coordinator == ""
        assert [h.ip for h in info.hosts.hosts] == ["t1k-0", "t1k-1", "t1k-2", "t1k-3"]
        assert all(h.slots == 1 for h in info.hosts.hosts)

    def test_multislice(self):
        env = {
            "TPU_WORKER_HOSTNAMES": "a,b",
            "TPU_WORKER_ID": "0",
            "MEGASCALE_COORDINATOR_ADDRESS": "a:8476",
            "MEGASCALE_SLICE_ID": "1",
            "MEGASCALE_NUM_SLICES": "4",
        }
        info = parse_tpu_pod_env(env=env)
        assert info.coordinator == "a:8476"
        assert info.slice_id == 1 and info.num_slices == 4

    def test_single_host_id_optional(self):
        info = parse_tpu_pod_env(env={"TPU_WORKER_HOSTNAMES": "solo"})
        assert info.worker_id == 0 and info.self_host == "solo"

    def test_missing_id_multi_host_raises(self):
        with pytest.raises(ValueError, match="TPU_WORKER_ID"):
            parse_tpu_pod_env(env={"TPU_WORKER_HOSTNAMES": "a,b"})

    def test_out_of_range_id_raises(self):
        with pytest.raises(ValueError, match="outside"):
            parse_tpu_pod_env(
                env={"TPU_WORKER_HOSTNAMES": "a,b", "TPU_WORKER_ID": "2"}
            )


class TestCliWiring:
    def test_platform_fills_topology(self, monkeypatch):
        from kungfu_tpu.runner.cli import apply_platform, build_cluster, build_parser

        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1")
        monkeypatch.setenv("TPU_WORKER_ID", "1")
        ns = build_parser().parse_args(["-platform", "tpu-pod", "prog"])
        apply_platform(ns)
        assert ns.self_host == "h1" and ns.backend == "tpu" and ns.np == 2
        cluster = build_cluster(ns)
        assert cluster.size() == 2
        assert {w.host for w in cluster.workers} == {"h0", "h1"}

    def test_explicit_hosts_win_in_auto(self, monkeypatch):
        from kungfu_tpu.runner.cli import apply_platform, build_parser

        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1")
        monkeypatch.setenv("TPU_WORKER_ID", "0")
        ns = build_parser().parse_args(["-np", "2", "-H", "127.0.0.1:2", "prog"])
        apply_platform(ns)
        assert ns.hosts == "127.0.0.1:2" and ns.self_host == "127.0.0.1"

    def test_forced_platform_without_env_exits(self, monkeypatch):
        from kungfu_tpu.runner.cli import apply_platform, build_parser

        monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
        ns = build_parser().parse_args(["-platform", "tpu-pod", "prog"])
        with pytest.raises(SystemExit):
            apply_platform(ns)

    def test_platform_none_ignores_env(self, monkeypatch):
        from kungfu_tpu.runner.cli import apply_platform, build_parser

        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1")
        ns = build_parser().parse_args(["-platform", "none", "prog"])
        apply_platform(ns)
        assert ns.hosts == "" and ns.self_host == "127.0.0.1"

    def test_auto_oversize_np_keeps_localhost(self, monkeypatch):
        """An explicit -np the detected pod can't host (1 slot/host) opts
        out of detection — the CPU-backend test-cluster case on a TPU VM
        whose env still carries the pod contract."""
        from kungfu_tpu.runner.cli import apply_platform, build_cluster, build_parser

        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1")
        monkeypatch.setenv("TPU_WORKER_ID", "0")
        ns = build_parser().parse_args(["-np", "4", "prog"])
        apply_platform(ns)
        assert ns.hosts == "" and ns.backend is None
        assert build_cluster(ns).size() == 4  # localhost:4

    def test_forced_oversize_np_exits_cleanly(self, monkeypatch):
        from kungfu_tpu.runner.cli import apply_platform, build_parser

        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1")
        monkeypatch.setenv("TPU_WORKER_ID", "0")
        ns = build_parser().parse_args(["-platform", "tpu-pod", "-np", "4", "prog"])
        with pytest.raises(SystemExit, match="exceeds the detected TPU pod"):
            apply_platform(ns)

    def test_explicit_np1_survives_detection(self, monkeypatch):
        """-np 1 given explicitly must stay 1; only the argparse default
        (None) expands to one worker per pod host."""
        from kungfu_tpu.runner.cli import apply_platform, build_parser

        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1")
        monkeypatch.setenv("TPU_WORKER_ID", "0")
        ns = build_parser().parse_args(["-np", "1", "prog"])
        apply_platform(ns)
        assert ns.np == 1 and ns.backend == "tpu"  # pod applies, np kept

        ns = build_parser().parse_args(["prog"])
        apply_platform(ns)
        assert ns.np == 2  # default expands to the pod


class _Dev:
    """Stand-in device: slice_device_groups touches only these attrs."""

    def __init__(self, slice_index=None, process_index=0):
        if slice_index is not None:
            self.slice_index = slice_index
        self.process_index = process_index

    def __repr__(self):
        return (f"Dev(s={getattr(self, 'slice_index', None)},"
                f"p={self.process_index})")


class TestSliceLayoutEdgeCases:
    """slice_device_groups / slice_mesh_layout edge cases on synthetic
    device worlds (satellite: uneven slices, contract disagreement,
    by='process' emulation fallback, single-slice passthrough)."""

    def test_groups_by_slice_index_outer_sorted(self):
        from kungfu_tpu.platforms.tpu_pod import slice_device_groups

        devs = [_Dev(slice_index=s, process_index=p)
                for s, p in ((1, 3), (0, 1), (1, 2), (0, 0))]
        groups = slice_device_groups(devs)
        assert [len(g) for g in groups] == [2, 2]
        assert {d.slice_index for d in groups[0]} == {0}
        assert {d.slice_index for d in groups[1]} == {1}

    def test_by_process_emulation_fallback(self):
        """CPU devices report no usable slice_index; the emulation
        contract regroups by process (MEGASCALE_SLICE_ID = process id)
        when the declared slice count matches THAT grouping."""
        from kungfu_tpu.platforms.tpu_pod import slice_mesh_layout

        # constant slice_index 0 (what CPU backends report) but two
        # processes: the by-slice grouping shows ONE group, the
        # process grouping shows the declared two
        devs = [_Dev(slice_index=0, process_index=p) for p in (0, 0, 1, 1)]
        flat, per = slice_mesh_layout(num_slices=2, devices=devs)
        assert per == 2 and len(flat) == 4
        assert [d.process_index for d in flat] == [0, 0, 1, 1]

    def test_contract_disagreement_fails_loudly(self):
        from kungfu_tpu.platforms.tpu_pod import slice_mesh_layout

        devs = [_Dev(slice_index=0, process_index=0) for _ in range(4)]
        with pytest.raises(ValueError, match="slice group"):
            slice_mesh_layout(num_slices=3, devices=devs)

    def test_uneven_slices_fail_loudly(self):
        from kungfu_tpu.platforms.tpu_pod import slice_mesh_layout

        devs = ([_Dev(slice_index=0)] * 3) + ([_Dev(slice_index=1)] * 1)
        with pytest.raises(ValueError, match="uneven slice sizes"):
            slice_mesh_layout(num_slices=2, devices=devs)

    def test_single_slice_passthrough(self):
        """num_slices=1 (or the env unset): one group, devices
        untouched — the byte-identical legacy path."""
        from kungfu_tpu.platforms.tpu_pod import slice_mesh_layout

        devs = [_Dev(slice_index=0, process_index=0) for _ in range(4)]
        flat, per = slice_mesh_layout(num_slices=1, devices=devs)
        assert flat == devs and per == 4

    def test_env_contract_default(self, monkeypatch):
        from kungfu_tpu.platforms.tpu_pod import slice_mesh_layout

        monkeypatch.setenv("MEGASCALE_NUM_SLICES", "2")
        devs = [_Dev(slice_index=s) for s in (0, 0, 1, 1)]
        flat, per = slice_mesh_layout(devices=devs)  # env supplies 2
        assert per == 2
        monkeypatch.setenv("MEGASCALE_NUM_SLICES", "4")
        with pytest.raises(ValueError, match="slice group"):
            slice_mesh_layout(devices=devs)


class TestKfrunSlicePropagation:
    """kfrun propagates slice identity to workers instead of logging it:
    per-worker MEGASCALE_SLICE_ID / MEGASCALE_NUM_SLICES / KF_SLICE_RANKS
    (slice-major, the emulation contract)."""

    def _job_envs(self, argv):
        from kungfu_tpu.runner.cli import build_cluster, build_parser
        from kungfu_tpu.runner.job import Job
        from kungfu_tpu.plan import parse_strategy
        from kungfu_tpu.plan.peer import PeerID

        ns = build_parser().parse_args(argv)
        cluster = build_cluster(ns)
        job = Job(prog="prog", args=[], strategy=parse_strategy("AUTO"),
                  parent=PeerID(ns.self_host, 38080),
                  slices=max(ns.num_slices, 0))
        return [job.new_proc(w, cluster).envs for w in cluster.workers]

    def test_worker_envs_carry_slice_identity(self):
        envs_per_worker = self._job_envs(
            ["-np", "4", "-num-slices", "2", "prog"])
        assert [e["MEGASCALE_SLICE_ID"] for e in envs_per_worker] == \
            ["0", "0", "1", "1"]
        assert all(e["MEGASCALE_NUM_SLICES"] == "2" for e in envs_per_worker)
        assert all(e["KF_SLICE_RANKS"] == "2" for e in envs_per_worker)

    def test_no_slices_no_envs(self):
        envs_per_worker = self._job_envs(["-np", "2", "prog"])
        assert all("MEGASCALE_SLICE_ID" not in e for e in envs_per_worker)

    def test_respawn_after_resize_keeps_slice_geometry(self):
        """Ranks-per-slice is pinned at the FIRST spawn: a watch-mode
        respawn over a RESIZED cluster must stamp joiners with the same
        geometry the incumbents hold (slice count follows membership,
        rps never moves) — re-deriving rps from the grown size would
        split the world into divergent rank→slice maps."""
        from kungfu_tpu.plan import Cluster, PeerID, PeerList
        from kungfu_tpu.plan import parse_strategy
        from kungfu_tpu.plan.peer import PeerID as PID
        from kungfu_tpu.runner.job import Job

        def mk_cluster(n):
            return Cluster(
                PeerList.parse("127.0.0.1:38089"),
                PeerList.of(*(PeerID("127.0.0.1", 23800 + i)
                              for i in range(n))))

        job = Job(prog="prog", args=[], strategy=parse_strategy("AUTO"),
                  parent=PID("127.0.0.1", 38080), slices=2)
        c4 = mk_cluster(4)
        first = [job.new_proc(w, c4).envs for w in c4.workers]
        assert [e["MEGASCALE_SLICE_ID"] for e in first] == \
            ["0", "0", "1", "1"]
        c6 = mk_cluster(6)
        grown = [job.new_proc(w, c6).envs for w in c6.workers]
        # rps stays 2; the grown world is 3 slices of 2, not 2 of 3
        assert all(e["KF_SLICE_RANKS"] == "2" for e in grown)
        assert all(e["MEGASCALE_NUM_SLICES"] == "3" for e in grown)
        assert [e["MEGASCALE_SLICE_ID"] for e in grown] == \
            ["0", "0", "1", "1", "2", "2"]

    def test_non_tiling_np_exits(self):
        from kungfu_tpu.runner.cli import main

        with pytest.raises(SystemExit, match="does not tile"):
            main(["-np", "3", "-num-slices", "2", "prog"])

    def test_real_pod_rejects_num_slices(self, monkeypatch):
        """On a detected multislice pod, TPU_WORKER_HOSTNAMES is THIS
        slice's host list — `-num-slices` would carve one slice into
        synthetic slices and overwrite each host's true
        MEGASCALE_SLICE_ID, so it is a launch error; without the flag,
        identity passes through via the inherited env (no stamping)."""
        from kungfu_tpu.runner.cli import apply_platform, build_parser

        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1")
        monkeypatch.setenv("TPU_WORKER_ID", "0")
        monkeypatch.setenv("MEGASCALE_SLICE_ID", "1")
        monkeypatch.setenv("MEGASCALE_NUM_SLICES", "2")
        ns = build_parser().parse_args(
            ["-platform", "tpu-pod", "-num-slices", "2", "prog"])
        with pytest.raises(SystemExit, match="MEGASCALE_SLICE_ID"):
            apply_platform(ns)
        ns = build_parser().parse_args(["-platform", "tpu-pod", "prog"])
        apply_platform(ns)
        assert ns.num_slices == 0  # not auto-armed: env identity wins


class TestMultislice:
    def test_single_slice_groups_and_validation(self):
        import jax

        from kungfu_tpu.platforms.tpu_pod import (multislice_communicator,
                                                  slice_device_groups)

        groups = slice_device_groups()
        assert len(groups) == 1 and len(groups[0]) == len(jax.devices())
        comm = multislice_communicator(num_slices=1)
        assert comm.size == len(jax.devices())
        import numpy as np

        x = np.arange(1, comm.size + 1, dtype=np.float32)[:, None]
        out = np.asarray(comm.all_reduce(x))
        assert float(out[0, 0]) == comm.size * (comm.size + 1) / 2
        with pytest.raises(ValueError, match="slice group"):
            multislice_communicator(num_slices=2)

    @pytest.mark.slow
    def test_two_slice_emulation_cross_slice_reduce(self):
        """Two subprocess 'slices' (one jax process each, 2 CPU devices,
        MEGASCALE_* contract set): the hierarchical two_stage reduce over
        the (slice, within-slice) mesh must match the flat psum."""
        import os
        import socket
        import subprocess
        import sys
        import time

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

        child = (
            "import sys, os, numpy as np\n"
            f"sys.path.insert(0, {repo!r})\n"
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "jax.config.update('jax_num_cpu_devices', 2)\n"
            "jax.config.update('jax_cpu_collectives_implementation', 'gloo')\n"
            "rank, port = int(sys.argv[1]), int(sys.argv[2])\n"
            "jax.distributed.initialize(f'127.0.0.1:{port}', 2, rank)\n"
            "from kungfu_tpu.platforms.tpu_pod import multislice_communicator\n"
            "comm = multislice_communicator()  # MEGASCALE_NUM_SLICES env\n"
            "assert comm.size == 4 and comm.num_hosts == 2, comm\n"
            "x = np.full((comm.addressable_n, 1), float(rank + 1), np.float32)\n"
            "flat = np.asarray(comm.all_reduce(x))          # psum\n"
            "comm.set_strategy('two_stage')\n"
            "hier = np.asarray(comm.all_reduce(x))          # DCN-shaped\n"
            "assert float(flat[0, 0]) == 6.0, flat\n"
            "assert np.array_equal(flat, hier), (flat, hier)\n"
            "# the cross-slice stage alone reduces over the OUTER axis\n"
            "cross = np.asarray(comm.cross_all_reduce(x))\n"
            "assert float(cross[0, 0]) == 3.0, cross\n"
            "print(f'MULTISLICE_OK rank={rank}')\n"
        )
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["MEGASCALE_NUM_SLICES"] = "2"
        env["MEGASCALE_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", child, str(r), str(port)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
                env={**env, "MEGASCALE_SLICE_ID": str(r)},
            )
            for r in range(2)
        ]
        deadline = time.monotonic() + 180.0
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(
                    timeout=max(1.0, deadline - time.monotonic()))
                outs.append(out)
                assert p.returncode == 0, out
            assert all("MULTISLICE_OK" in o for o in outs), outs
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait()
