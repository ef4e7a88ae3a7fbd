"""Monitoring/adaptation tests: counters, /metrics, latencies, MST,
set_tree, interference (reference test_tensorflow_throughput_monitoring.py
/ test_set_tree.py analogs)."""

import time
import urllib.request

import numpy as np
import pytest

from kungfu_tpu.monitor.metrics import MetricsServer, NetMonitor
from kungfu_tpu.plan.mst import minimum_spanning_tree

from tests._util import run_all


class TestNetMonitor:
    def test_counters_and_rates(self):
        m = NetMonitor(period=0.1).start()
        try:
            for _ in range(10):
                m.egress("a:1", 1000)
                m.ingress("b:2", 500)
            time.sleep(0.3)
            totals = m.totals()
            assert totals["egress"]["a:1"] == 10000
            assert totals["ingress"]["b:2"] == 5000
            assert m.egress_rates(["a:1"])[0] >= 0
            assert m.egress_rates(["missing:9"]) == [0.0]
        finally:
            m.stop()

    def test_metrics_endpoint(self):
        m = NetMonitor(period=0.1).start()
        s = MetricsServer(m, port=28123).start()
        try:
            m.egress("peer:1", 2048)
            with urllib.request.urlopen("http://127.0.0.1:28123/metrics", timeout=5) as r:
                text = r.read().decode()
            assert 'kf_egress_bytes_total{peer="peer:1"} 2048' in text
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen("http://127.0.0.1:28123/nope", timeout=5)
        finally:
            s.stop()
            m.stop()


class TestHostNoiseScale:
    """ops/monitor.py::host_noise_scale — the host-plane (engine) GNS
    estimator: the n==1 no-signal contract, and agreement with the
    in-graph ``global_noise_scale`` on identical inputs."""

    def _engines(self, base_port, n):
        from kungfu_tpu.comm.engine import CollectiveEngine
        from kungfu_tpu.comm.host import HostChannel
        from kungfu_tpu.plan import PeerID, PeerList
        from kungfu_tpu.plan.strategy import Strategy

        peers = PeerList.of(*(PeerID("127.0.0.1", base_port + i)
                              for i in range(n)))
        chans = [HostChannel(p, bind_host="127.0.0.1") for p in peers]
        engines = [CollectiveEngine(c, peers, strategy=Strategy.STAR)
                   for c in chans]
        return chans, engines

    def test_single_worker_reports_no_signal(self):
        """b_small == b_big on one worker: the two-batch estimator is
        undefined; callers must get ``None`` ("no estimate"), not 0.0 —
        a zero would read as a measured noise scale of zero and the
        pulse plane would EMA it into the published gauge."""
        from kungfu_tpu.ops.monitor import host_noise_scale

        chans, engines = self._engines(23720, 1)
        try:
            g = np.random.RandomState(0).uniform(-1, 1, 32).astype(np.float32)
            assert host_noise_scale(engines[0], g, g, 16) is None
        finally:
            for c in chans:
                c.close()

    @pytest.mark.parametrize("n", [3, 5])
    def test_non_power_of_two_world_matches_in_graph(self, n):
        """The one-estimator property across ODD world sizes: the
        host-plane value over a real n-peer engine equals the in-graph
        ``global_noise_scale`` over an n-device mesh on the SAME
        per-peer gradients.  Non-power-of-two sizes exercise the
        b_big = n*b_small arithmetic where a pairwise-halving mental
        model would silently diverge."""
        import jax
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P

        import kungfu_tpu.ops.collective as kc
        from kungfu_tpu.ops.monitor import global_noise_scale, host_noise_scale
        from jax import shard_map

        b_small = 8.0
        rng = np.random.RandomState(100 + n)
        base = rng.uniform(1.0, 2.0, 48)
        grads = np.stack(
            [base + 0.1 * rng.uniform(-1, 1, 48) for _ in range(n)]
        ).astype(np.float32)

        chans, engines = self._engines(23740 + 10 * n, n)
        try:
            def one(i):
                avg = engines[i].all_reduce(grads[i], op="mean")
                return host_noise_scale(engines[i], grads[i], avg, b_small)

            host_vals = run_all([lambda i=i: one(i) for i in range(n)])
        finally:
            for c in chans:
                c.close()
        assert all(v is not None for v in host_vals)
        # symmetric: every rank publishes the same estimate
        for v in host_vals[1:]:
            assert host_vals[0] == pytest.approx(v, rel=1e-9)

        mesh = Mesh(np.array(jax.devices()[:n]), ("kf",))

        def gns_fn(g):
            avg = kc.all_reduce(g, "kf", op="mean")
            return global_noise_scale(g, avg, b_small, "kf")[None]

        got = shard_map(gns_fn, mesh=mesh, in_specs=P("kf"),
                        out_specs=P("kf"))(grads)
        in_graph = float(np.asarray(got)[0])
        assert host_vals[0] == pytest.approx(in_graph, rel=1e-3)

    def test_two_peer_engine_matches_in_graph_estimator(self):
        """The host-plane estimate over a real 2-peer CollectiveEngine
        equals the in-graph ``global_noise_scale`` over a 2-device mesh
        on the SAME per-peer gradients — the two planes implement one
        estimator, not two approximations of it."""
        import jax
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P

        import kungfu_tpu.ops.collective as kc
        from kungfu_tpu.ops.monitor import global_noise_scale, host_noise_scale
        from jax import shard_map

        b_small = 16.0
        rng = np.random.RandomState(7)
        # base + per-peer noise keeps |G|^2 well away from zero, so the
        # estimator is well-conditioned and float32-vs-float64 plane
        # differences stay in the mantissa, not the structure
        base = rng.uniform(1.0, 2.0, 64)
        grads = np.stack(
            [base + 0.1 * rng.uniform(-1, 1, 64) for _ in range(2)]
        ).astype(np.float32)

        chans, engines = self._engines(23730, 2)
        try:
            def one(i):
                avg = engines[i].all_reduce(grads[i], op="mean")
                return host_noise_scale(engines[i], grads[i], avg, b_small)

            host_vals = run_all([lambda i=i: one(i) for i in range(2)])
        finally:
            for c in chans:
                c.close()
        # symmetric by construction (the inner mean is a collective)
        assert host_vals[0] == pytest.approx(host_vals[1], rel=1e-9)

        mesh = Mesh(np.array(jax.devices()[:2]), ("kf",))

        def gns_fn(g):
            avg = kc.all_reduce(g, "kf", op="mean")
            return global_noise_scale(g, avg, b_small, "kf")[None]

        got = shard_map(gns_fn, mesh=mesh, in_specs=P("kf"),
                        out_specs=P("kf"))(grads)
        in_graph = float(np.asarray(got)[0])
        assert host_vals[0] == pytest.approx(in_graph, rel=1e-3)


class TestMST:
    def test_chain(self):
        # latencies force a chain 0-1-2
        w = np.array([[0, 1, 10], [1, 0, 1], [10, 1, 0]], float)
        f = minimum_spanning_tree(w)
        assert f[0] == 0 and f[1] == 0 and f[2] == 1

    def test_star(self):
        w = np.array([[0, 1, 1, 1], [1, 0, 9, 9], [1, 9, 0, 9], [1, 9, 9, 0]], float)
        assert minimum_spanning_tree(w) == [0, 0, 0, 0]

    def test_asymmetric_symmetrized(self):
        w = np.array([[0, 2], [4, 0]], float)
        assert minimum_spanning_tree(w) == [0, 0]

    def test_bad_shape(self):
        with pytest.raises(ValueError):
            minimum_spanning_tree(np.zeros((2, 3)))


class TestAdaptIntegration:
    @pytest.fixture
    def peers(self):
        from kungfu_tpu.peer import Peer
        from kungfu_tpu.plan import Cluster, PeerList
        from kungfu_tpu.utils.envs import Config

        workers = PeerList.parse("127.0.0.1:27301,127.0.0.1:27302,127.0.0.1:27303")
        runners = PeerList.parse("127.0.0.1:38087")
        cluster = Cluster(runners, workers)
        ps = [Peer(Config(self_id=w, cluster=cluster)) for w in workers]
        for p in ps:
            p.start()
        yield ps
        for p in ps:
            p.close()


    def test_latencies(self, peers):
        lats = peers[0].get_peer_latencies()
        assert len(lats) == 3
        assert lats[0] == 0.0  # self
        assert lats[1] > 0 and lats[2] > 0

    def test_latency_matrix_and_mst(self, peers):
        from kungfu_tpu.monitor.adapt import latency_matrix

        mats = run_all([lambda p=p: latency_matrix(p) for p in peers])
        for m in mats:
            assert m.shape == (3, 3)
        f = minimum_spanning_tree(mats[0])
        assert len(f) == 3 and f[0] == 0

    def test_set_tree_then_allreduce(self, peers):
        chain = [0, 0, 1]  # explicit chain topology

        def one(p, val):
            p.set_tree(chain)
            out = p.engine().all_reduce(np.full(4, val, np.float32))
            return out

        outs = run_all([lambda p=p, v=v: one(p, float(v)) for v, p in enumerate(peers)])
        for o in outs:
            np.testing.assert_allclose(o, np.full(4, 3.0))  # 0+1+2

    def test_interference_vote(self, peers):
        # no throughput data -> no interference
        outs = run_all([lambda p=p: p.check_interference() for p in peers])
        assert outs == [False, False, False]

    def test_adaptive_driver_swaps_on_interference(self, peers):
        """Close the adaptation loop (reference adaptiveStrategies.go:
        57-121): establish a best-throughput window, throttle the network,
        and assert every rank swaps strategy in lockstep — with collectives
        still correct afterwards."""
        import time as _time

        from kungfu_tpu.monitor.adaptive import AdaptiveStrategyDriver
        from kungfu_tpu.plan import Strategy

        for p in peers:
            p.config.strategy = Strategy.STAR
        drivers = [
            AdaptiveStrategyDriver(p, check_every=1, min_steps_between_swaps=1)
            for p in peers
        ]
        data = np.ones(64_000, np.float32)  # big enough for a stable rate

        def train_step(p, d):
            out = p.engine().all_reduce(data, op="sum")
            swapped = d.step()
            return out, swapped

        # healthy step: establishes the reference window; the first check
        # can never flag (window == freshly-recorded best)
        outs = run_all([lambda p=p, d=d: train_step(p, d) for p, d in zip(peers, drivers)])
        assert not any(s for _, s in outs)

        # pin the recorded best far above anything this machine can do —
        # real wall-clock rates flap under parallel test load, so the
        # drop-below-0.8x condition is forced deterministically while the
        # suspicion -> majority vote -> fenced swap loop stays fully real
        for p in peers:
            e = p.engine()
            e.best_throughputs = [1e9] * len(e.best_throughputs)
        originals = []
        for p in peers:
            ch = p.channel
            orig = ch.send
            originals.append((ch, orig))

            def slow_send(*a, _orig=orig, **kw):
                _time.sleep(0.005)
                return _orig(*a, **kw)

            ch.send = slow_send
        try:
            swapped_anywhere = False
            for _ in range(3):
                outs = run_all(
                    [lambda p=p, d=d: train_step(p, d) for p, d in zip(peers, drivers)],
                    timeout=120,
                )
                for o, _ in outs:
                    np.testing.assert_allclose(o, data * 3)
                flags = [s for _, s in outs]
                assert len(set(flags)) == 1  # lockstep: all or none
                if flags[0]:
                    swapped_anywhere = True
                    break
            assert swapped_anywhere, "no swap despite sustained throttling"
            assert all(d.swaps == 1 for d in drivers)
            strategies = {p.engine().strategy for p in peers}
            assert strategies == {Strategy.BINARY_TREE_STAR}
        finally:
            for ch, orig in originals:
                ch.send = orig
        # post-swap collectives remain correct at full speed
        outs = run_all(
            [lambda p=p: p.engine().all_reduce(np.full(5, 2.0, np.float32)) for p in peers]
        )
        for o in outs:
            np.testing.assert_allclose(o, np.full(5, 6.0))

    def test_egress_rates_with_monitoring(self):
        import os

        from kungfu_tpu.peer import Peer
        from kungfu_tpu.plan import Cluster, PeerList
        from kungfu_tpu.utils.envs import Config

        os.environ["KF_CONFIG_ENABLE_MONITORING"] = "true"
        try:
            workers = PeerList.parse("127.0.0.1:27311,127.0.0.1:27312")
            cluster = Cluster(PeerList.parse("127.0.0.1:38088"), workers)
            ps = [Peer(Config(self_id=w, cluster=cluster)) for w in workers]
            for p in ps:
                p.start()
            try:
                engines = [p.engine() for p in ps]
                data = np.ones(1000, np.float32)
                run_all([lambda e=e: e.all_reduce(data) for e in engines])
                # native-backend egress arrives via the counter poll thread
                deadline = time.time() + 5
                while time.time() < deadline:
                    totals = ps[0].net_monitor.totals()
                    if sum(totals["egress"].values()) > 0:
                        break
                    time.sleep(0.2)
                assert sum(totals["egress"].values()) > 0
                assert len(ps[0].get_egress_rates()) == 2
                # /metrics endpoint is live at port+10000
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{27311 + 10000}/metrics", timeout=5
                ) as r:
                    assert b"kf_egress_bytes_total" in r.read()
            finally:
                for p in ps:
                    p.close()
        finally:
            os.environ.pop("KF_CONFIG_ENABLE_MONITORING", None)
