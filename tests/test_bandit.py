"""kf-adapt: the UCB collective bandit (ISSUE 9).

Covers the satellite test checklist end to end:

* deterministic-seed arm convergence on synthetic latency streams
  (identical replicas make identical selection sequences);
* the size-bucketed schedule table: independent winners per bucket,
  installed into the device communicator's per-``nbytes`` dispatch;
* consensus-fenced swap identical on every rank (3-rank in-process
  cluster) with the ``swap`` timeline event on each rank at one seq;
* bandit state reset/re-explore across a LIVE resize (``elastic_step``'s
  ``bandit=`` wiring, 3 -> 2 through the real config-server protocol);
* a chaos-``delay`` run where the policy abandons the degraded strategy;
* the load-scaled host pool and the hardened autotune winner guard.
"""

from __future__ import annotations

import random
import threading
import time

import numpy as np
import pytest

from tests._util import run_all


class TestArmStats:
    def test_deterministic_convergence_on_synthetic_stream(self):
        """Two replicas fed the same seeded latency stream make the SAME
        selection sequence and both converge on the fastest arm — the
        property the cluster-wide lockstep swap rests on."""
        from kungfu_tpu.policy.bandit import ArmStats

        lat = {"a": 0.10, "b": 0.04, "c": 0.20}

        def run():
            t = ArmStats(("a", "b", "c"), min_pulls=2)
            rng = random.Random(7)
            seq = []
            for _ in range(60):
                arm = t.select()
                seq.append(arm)
                t.observe(arm, lat[arm] + rng.random() * 0.005)
            return seq

        s1, s2 = run(), run()
        assert s1 == s2, "identical streams must make identical decisions"
        assert set(s1[-10:]) == {"b"}, f"did not converge: {s1[-10:]}"
        # exploration visited every arm at least min_pulls times
        assert all(s1.count(a) >= 2 for a in ("a", "b", "c"))

    def test_unexplored_first_in_declaration_order(self):
        from kungfu_tpu.policy.bandit import ArmStats

        t = ArmStats(("x", "y", "z"), min_pulls=1)
        assert t.select() == "x"
        t.observe("x", 1.0)
        assert t.select() == "y"
        t.observe("y", 1.0)
        assert t.select() == "z"

    def test_reset_reexplores(self):
        from kungfu_tpu.policy.bandit import ArmStats

        t = ArmStats(("x", "y"))
        t.observe("x", 0.1)
        t.observe("y", 0.2)
        assert t.unexplored() is None
        t.reset()
        assert t.unexplored() == "x"
        assert t.mean("x") is None

    def test_rejects_uncredible_observations(self):
        """A 0-count, negative, or non-finite sample is the startup-probe
        failure mode (ROADMAP #4) — rejected loudly, never folded."""
        from kungfu_tpu.policy.bandit import ArmStats

        t = ArmStats(("x",))
        with pytest.raises(ValueError):
            t.observe("x", float("nan"))
        with pytest.raises(ValueError):
            t.observe("x", -1.0)
        with pytest.raises(ValueError):
            t.observe("x", 0.0)  # a 0 s mean would be unbeatable forever
        with pytest.raises(ValueError):
            t.observe("x", 0.1, count=0)
        with pytest.raises(KeyError):
            t.observe("nope", 0.1)

    def test_degraded_incumbent_is_abandoned(self):
        """Non-stationarity: once the converged winner's measurements
        degrade, UCB moves off it within a few windows."""
        from kungfu_tpu.policy.bandit import ArmStats

        t = ArmStats(("fast", "slow"), min_pulls=1)
        for _ in range(6):
            t.observe(t.select(), 0.01 if t.select() == "fast" else 0.05)
        # interference hits the incumbent
        for _ in range(20):
            arm = t.select()
            t.observe(arm, 0.5 if arm == "fast" else 0.05)
        assert t.select() == "slow"


class TestScheduleTable:
    def test_buckets_learn_independent_winners(self):
        from kungfu_tpu.policy.bandit import ScheduleTable

        st = ScheduleTable(("psum", "ring"), n_buckets=2, min_pulls=1)
        for _ in range(8):
            st.observe(0, "psum", 0.001)
            st.observe(0, "ring", 0.010)
            st.observe(1, "psum", 0.100)
            st.observe(1, "ring", 0.020)
        assert st.select(0) == "psum"
        assert st.select(1) == "ring"
        st.install(0, "psum")
        st.install(1, "ring")
        assert st.active == ["psum", "ring"]
        with pytest.raises(KeyError):
            st.install(0, "bogus")

    def test_size_bucket_edges(self):
        from kungfu_tpu.ops.schedules import (SIZE_BUCKET_EDGES,
                                              SIZE_BUCKETS, size_bucket)

        assert len(SIZE_BUCKETS) == len(SIZE_BUCKET_EDGES) + 1
        assert size_bucket(0) == 0
        assert size_bucket(SIZE_BUCKET_EDGES[0] - 1) == 0
        assert size_bucket(SIZE_BUCKET_EDGES[0]) == 1
        assert size_bucket(1 << 30) == len(SIZE_BUCKETS) - 1


class TestDeviceBucketDispatch:
    @pytest.fixture
    def comm(self):
        import jax

        from kungfu_tpu.comm.device import Communicator

        return Communicator(devices=jax.devices()[:4], local_size=4)

    def test_per_bucket_strategy_dispatch(self, comm):
        """Small and large payloads ride independently-installed
        schedules; values stay identical to psum."""
        small = np.arange(4, dtype=np.float32)[:, None]
        large = np.ones((4, 100_000), np.float32)
        comm.set_bucket_strategy(1, "ring")
        out_s = np.asarray(comm.all_reduce(small))
        out_l = np.asarray(comm.all_reduce(large))
        assert float(out_s[0, 0]) == 6.0
        assert np.all(out_l == 4.0)
        assert comm.strategy_for(small.nbytes // 4) == "psum"
        assert comm.strategy_for(large.nbytes) == "ring"
        # the compiled-program cache carries the per-bucket schedule
        scheds = {k[5] for k in comm._fns if k[0] == "ar"}
        assert {"psum", "ring"} <= scheds
        assert comm.bucket_summary() == "large=ring"
        comm.set_bucket_strategy(1, None)
        assert comm.bucket_summary() == ""
        assert comm.strategy_for(large.nbytes) == "psum"
        with pytest.raises(ValueError):
            comm.set_bucket_strategy(0, "bogus")
        with pytest.raises(ValueError):
            comm.set_bucket_strategy(99, "ring")

    def test_latency_hook_reports_executed_schedule(self, comm):
        obs = []
        comm.set_latency_hook(lambda n, s, dt: obs.append((n, s, dt)))
        comm.set_bucket_strategy(1, "two_stage")
        comm.all_reduce(np.arange(4, dtype=np.float32)[:, None])
        comm.all_reduce(np.ones((4, 100_000), np.float32))
        comm.set_latency_hook(None)
        assert [(n, s) for n, s, _ in obs] == [
            (16, "psum"), (1_600_000, "two_stage")]
        assert all(dt >= 0 for _, _, dt in obs)
        # hook removed: no further observations
        comm.all_reduce(np.arange(4, dtype=np.float32)[:, None])
        assert len(obs) == 2

    def test_autotune_rejects_uncredible_winner(self, comm, monkeypatch):
        """The satellite-1 guard: a 0.0 s / non-finite winning time keeps
        the incumbent instead of installing a coin-flip."""
        comm.set_strategy("two_stage")
        for bad in ([0.0, 0.0, 0.0],          # 0.0 s winner
                    [float("nan")] * 3,       # -> 1e9 sentinels
                    [1e9, 1e9, 1e9]):         # nothing really timed
            monkeypatch.setattr(
                type(comm), "_time_schedules",
                lambda self, x, trials, _bad=bad: list(_bad))
            assert comm.autotune_strategy(nbytes=1 << 10,
                                          trials=1) == "two_stage"
            assert comm.strategy == "two_stage"

    def test_device_driver_converges_and_installs(self, comm):
        """Single-controller device bandit: explores every (bucket, arm),
        then installs winners into the communicator's bucket table."""
        from kungfu_tpu.monitor.adapt_device import DeviceBanditDriver

        d = DeviceBanditDriver(comm, check_every=2, min_pulls=1)
        small = np.arange(4, dtype=np.float32)[:, None]
        large = np.ones((4, 50_000), np.float32)
        swaps = 0
        for _ in range(18):
            comm.all_reduce(small)
            comm.all_reduce(large)
            if d.step():
                swaps += 1
        assert swaps > 0, "exploration never installed a bucket override"
        summary = d.summary()
        assert set(summary) == {0, 1}
        # every arm of every bucket was measured at least once
        for b in summary.values():
            assert all(v["count"] > 0 for v in b["arms"].values()), summary
        # the communicator reflects the driver's installed table
        for b, active in enumerate(d.table.active):
            assert comm.strategy_for_bucket(b) == active
        comm.set_latency_hook(None)

    def test_device_driver_timeline_feed(self, comm, monkeypatch):
        """``feed="timeline"``: the per-schedule ring is fed from the
        flight recorder's device spans (which carry nbytes/sched)."""
        from kungfu_tpu.monitor import timeline
        from kungfu_tpu.monitor.adapt_device import DeviceBanditDriver

        monkeypatch.setenv("KF_CONFIG_ENABLE_TRACE", "1")
        timeline.reset()
        d = DeviceBanditDriver(comm, check_every=4, feed="timeline")
        assert comm._latency_hook is None  # timeline mode installs none
        comm.all_reduce(np.ones((4, 100_000), np.float32))
        comm.all_reduce(np.arange(4, dtype=np.float32)[:, None])
        assert d.feed_from_timeline() == 2
        pend = d._pending
        assert sum(c for c, _ in pend[1].values()) == 1  # large span
        assert sum(c for c, _ in pend[0].values()) == 1  # small span
        timeline.reset()


class TestEngineSwapEpochs:
    def test_window_peek_and_swap_eligibility(self):
        """window_peek is non-destructive (unlike throughputs) and the
        swap-eligibility epoch counts collectives since mark_swap."""
        from kungfu_tpu.comm.engine import CollectiveEngine
        from kungfu_tpu.comm.host import PyHostChannel
        from kungfu_tpu.plan import PeerID, PeerList, Strategy

        peers = PeerList.of(PeerID("127.0.0.1", 27531),
                            PeerID("127.0.0.1", 27532))
        chans = [PyHostChannel(p, bind_host="127.0.0.1") for p in peers]
        engines = [CollectiveEngine(c, peers, Strategy.STAR)
                   for c in chans]
        try:
            data = np.ones(1000, np.float32)
            run_all([lambda e=e: e.all_reduce(data) for e in engines])
            e = engines[0]
            w1 = e.window_peek()
            w2 = e.window_peek()
            assert w1 == w2 and sum(b for b, _ in w1) > 0
            assert e.throughputs()  # destructive reset
            assert sum(b for b, _ in e.window_peek()) == 0
            assert e.collectives_since_swap() >= 1
            assert e.swap_eligible(1)
            e.mark_swap()
            assert e.collectives_since_swap() == 0
            assert not e.swap_eligible(1)
            assert e.swap_eligible(0)
        finally:
            for e in engines:
                e.close()
            for c in chans:
                c.close()


def _make_peers(base_port, strategy="STAR", n=3, config_server=None):
    from kungfu_tpu.peer import Peer
    from kungfu_tpu.plan import Cluster, PeerList, parse_strategy
    from kungfu_tpu.utils.envs import Config

    workers = PeerList.parse(
        ",".join(f"127.0.0.1:{base_port + i}" for i in range(n)))
    runners = PeerList.parse(f"127.0.0.1:{base_port + 99}")
    cluster = Cluster(runners, workers)
    ps = [Peer(Config(self_id=w, cluster=cluster,
                      config_server=config_server)) for w in workers]
    for p in ps:
        p.config.strategy = parse_strategy(strategy)
        p.start()
    return ps


class TestFencedSwapLockstep:
    """3-rank in-process cluster: every rank must reach the same swap
    decision at the same step from DIVERGENT local measurements (the
    window exchange is an allreduce; the decision is pure)."""

    @pytest.fixture
    def peers(self, monkeypatch):
        monkeypatch.setenv("KF_NATIVE_ENGINE", "0")
        ps = _make_peers(27501)
        yield ps
        for p in ps:
            p.close()

    def test_lockstep_swap_and_event_on_every_rank(self, peers, monkeypatch):
        from kungfu_tpu.monitor import timeline
        from kungfu_tpu.monitor.adapt_device import HostBanditDriver
        from kungfu_tpu.monitor.registry import REGISTRY

        monkeypatch.setenv("KF_CONFIG_ENABLE_TRACE", "1")
        timeline.reset()
        drivers = [
            HostBanditDriver(p, arms=("STAR", "RING"), check_every=2,
                             min_pulls=1, min_swap_collectives=1)
            for p in peers
        ]
        swaps_before = REGISTRY.counter(
            "kf_strategy_swaps_total", what="RING").value

        def one(rank, p, d, step):
            # synthetic measured windows, rank-skewed so locals DISAGREE:
            # STAR reads ~100 ms, RING ~1 ms — only the allreduced mean
            # can make the ranks agree
            dt = (0.1 if d.active == "STAR" else 0.001) * (1 + 0.2 * rank)
            return d.step(dt)

        swap_steps = []
        for step in range(8):
            flags = run_all([
                lambda r=r, p=p, d=d: one(r, p, d, step)
                for r, (p, d) in enumerate(zip(peers, drivers))
            ])
            assert len(set(flags)) == 1, f"non-lockstep at step {step}"
            if flags[0]:
                swap_steps.append(step)
        assert swap_steps, "no swap fired"
        # every rank landed on the same arm, and the engines agree
        actives = {d.active for d in drivers}
        assert len(actives) == 1
        strategies = {getattr(p.engine().strategy, "name", None)
                      for p in peers}
        assert len(strategies) == 1
        # the fence contract: each swap seq has one event per rank
        swaps = [e for e in timeline.snapshot() if e["kind"] == "swap"]
        assert swaps, "swap events missing from the flight recorder"
        by_seq = {}
        for e in swaps:
            by_seq.setdefault(e["attrs"]["seq"], []).append(e["rank"])
        for seq, ranks in by_seq.items():
            assert sorted(ranks) == [0, 1, 2], (seq, ranks)
        # the counted kind ticks the registry even beyond the ring
        assert REGISTRY.counter("kf_strategy_swaps_total",
                                what="RING").value > swaps_before
        timeline.reset()


    def test_ledger_names_the_swap_that_fixed_the_step(self, peers,
                                                       monkeypatch,
                                                       tmp_path):
        """Every consensus swap writes a durable decision record; fed
        the step times that followed, the ledger's ``improved`` verdict
        names the swap onto the arm the ranks ended on, and the offline
        replay of the durable streams recomputes every judged verdict
        byte-identically.  Step times are synthetic: no clock."""
        import json

        from kungfu_tpu.monitor import history, ledger
        from kungfu_tpu.monitor.adapt_device import HostBanditDriver

        monkeypatch.setenv("KF_SENTINEL_DIR", str(tmp_path))
        # the floor: a swap is judged from samples that fit between
        # consecutive votes
        monkeypatch.setenv("KF_SENTINEL_WINDOW", "2")
        ledger.reset()
        led = ledger.ledger_for(str(tmp_path))
        ring = history.HistoryRing(str(tmp_path), "cluster")
        drivers = [
            HostBanditDriver(p, arms=("STAR", "RING"), check_every=2,
                             min_pulls=1, min_swap_collectives=1)
            for p in peers
        ]
        try:
            for _ in range(12):
                dt = 0.1 if drivers[0].active == "STAR" else 0.001
                # the sentinel's role: ONE record a step lands in the
                # durable cluster stream AND feeds the online join
                rec = {"series": {"step_time_s": dt}}
                ring.append(rec)
                led.on_sample(rec)
                run_all([lambda d=d: d.step(dt) for d in drivers])
            arms = {d.active for d in drivers}
            assert arms == {"RING"}, arms
            improved = [row for row in led.view()["decisions"]
                        if ledger.lfield(row["effect"], "verdict")
                        == "improved"]
            assert any(
                ledger.lfield(row["decision"], "actor") == "bandit-host"
                and ledger.lfield(row["decision"], "knob") == "strategy"
                and ledger.lfield(row["decision"], "new") == "RING"
                for row in improved), led.view()
            judged = [r for r in ledger.replay_effects(
                str(tmp_path))["decisions"] if r["online"] is not None]
            assert judged
            for r in judged:
                assert json.dumps(r["online"], sort_keys=True) \
                    == json.dumps(r["replayed"], sort_keys=True)
        finally:
            ledger.reset()


class TestCollectiveBanditPolicy:
    """The PolicyRunner wiring: the bandit rides the per-step policy
    callbacks, fed by the loop's measured collective seconds."""

    def test_runner_drives_lockstep_swaps(self, monkeypatch):
        from kungfu_tpu.policy import CollectiveBanditPolicy, PolicyRunner

        monkeypatch.setenv("KF_NATIVE_ENGINE", "0")
        peers = _make_peers(27541)
        try:
            policies = [CollectiveBanditPolicy(
                p, arms=("STAR", "RING"), check_every=2, min_pulls=1,
                min_swap_collectives=1) for p in peers]
            runners = [PolicyRunner([pol], peer=p, batch_size=4)
                       for pol, p in zip(policies, peers)]

            def one(pol, run):
                dt = 0.1 if pol.host.active == "STAR" else 0.001
                run.after_step(step_collective_s=dt)
                return pol.host.active, run.ctx.metrics.get("bandit_swaps")

            last = []
            for _ in range(6):
                last = run_all([lambda pol=pol, run=run: one(pol, run)
                                for pol, run in zip(policies, runners)])
                assert len({a for a, _ in last}) == 1  # lockstep arms
            assert {a for a, _ in last} == {"RING"}
            assert all(s and s >= 1.0 for _, s in last), last
        finally:
            for p in peers:
                p.close()


class TestResizeReexplore:
    """Bandit state across a LIVE resize (3 -> 2 over the real config
    server + consensus protocol, driven by ``elastic_step(bandit=...)``):
    the arm table resets and the new membership re-explores."""

    def test_live_shrink_resets_bandit(self, monkeypatch):
        from kungfu_tpu.elastic import ConfigServer
        from kungfu_tpu.elastic.hooks import ElasticState, elastic_step
        from kungfu_tpu.monitor.adapt_device import HostBanditDriver
        from kungfu_tpu.plan import Cluster, PeerList

        monkeypatch.setenv("KF_NATIVE_ENGINE", "0")
        workers = PeerList.parse(
            ",".join(f"127.0.0.1:{27511 + i}" for i in range(3)))
        runners = PeerList.parse("127.0.0.1:27610")
        server = ConfigServer(port=29141,
                              cluster=Cluster(runners, workers)).start()
        peers = _make_peers(27511,
                            config_server="http://127.0.0.1:29141/get")
        drivers = [HostBanditDriver(p, arms=("STAR", "RING"), check_every=2,
                                    min_pulls=1, min_swap_collectives=1)
                   for p in peers]
        params = {"w": np.arange(4.0, dtype=np.float32)}
        # 3 workers until step 3, then 2 (a live planned shrink)
        schedule = "3:3,2:100"
        try:
            def loop(p, d):
                state = ElasticState()
                out = dict(resets=0, stopped=False, size=p.size())
                for _ in range(6):
                    counts_before = sum(d.table.counts)
                    state, _, stop = elastic_step(
                        p, state, schedule, params, bandit=d)
                    if stop:
                        out["stopped"] = True
                        break
                    d.step(0.01)
                    if counts_before > 0 and sum(d.table.counts) == 0:
                        out["resets"] += 1
                out["size"] = p.size()
                out["version"] = d._seen_version
                return out

            outs = run_all(
                [lambda p=p, d=d: loop(p, d)
                 for p, d in zip(peers, drivers)], timeout=180)
            stopped = [o for o in outs if o["stopped"]]
            survived = [o for o in outs if not o["stopped"]]
            assert len(stopped) == 1 and len(survived) == 2, outs
            # the survivors crossed the resize: state was reset at least
            # once and the drivers track the new cluster version
            assert all(o["size"] == 2 for o in survived)
            assert all(o["resets"] >= 1 for o in survived), outs
            versions = {o["version"] for o in survived}
            assert len(versions) == 1 and versions != {0}
            # post-resize the table re-explores from scratch
            for d, o in zip(drivers, outs):
                if not o["stopped"]:
                    assert sum(d.table.counts) < 4  # only fresh windows
        finally:
            for p in peers:
                p.close()
            server.stop()


class TestChaosDelayAbandon:
    """The satellite chaos run: ``delay`` clauses degrade the 0<->1 link;
    the policy must abandon the degraded starting strategy."""

    def test_bandit_abandons_degraded_strategy(self, monkeypatch):
        from kungfu_tpu import chaos
        from kungfu_tpu.monitor.adapt_device import HostBanditDriver

        wire_ms = 15
        monkeypatch.setenv("KF_NATIVE_ENGINE", "0")
        monkeypatch.setenv("KF_CHAOS_SPEC", ";".join(
            f"delay:ms={wire_ms},rank={a},peer={b},on={on}"
            for a, b in ((0, 1), (1, 0)) for on in ("send", "ping")))
        chaos.reset()
        peers = _make_peers(27521)
        data = np.ones(20_000, np.float32)
        try:
            drivers = [HostBanditDriver(p, check_every=2, min_pulls=1,
                                        min_swap_collectives=1)
                       for p in peers]

            def one(p, d):
                t0 = time.perf_counter()
                out = p.engine().all_reduce(data, op="sum")
                dt = time.perf_counter() - t0
                assert float(out[0]) == 3.0
                return dt, d.step(dt)

            # run PAST the exploration phase (4 arms x check_every=2 x
            # observe+settle) so the tail medians measure the converged
            # arm, not a mid-exploration one — every non-mst arm pays
            # the link delay, so an early cut would compare noise
            times, swapped_at = [], None
            for i in range(24):
                outs = run_all([lambda p=p, d=d: one(p, d)
                                for p, d in zip(peers, drivers)])
                flags = {s for _, s in outs}
                assert len(flags) == 1, f"non-lockstep at {i}"
                times.append(max(t for t, _ in outs))
                if flags.pop() and swapped_at is None:
                    swapped_at = i
            assert swapped_at is not None, "policy never abandoned STAR"
            actives = {d.active for d in drivers}
            assert len(actives) == 1 and actives != {"STAR"}, actives
            # and the adaptation paid off: the converged tail beats the
            # degraded opening phase (only the MST tree dodges the
            # throttled 0<->1 edge, by ~10x — ample noise margin)
            degraded = float(np.median(times[:swapped_at + 1]))
            steady = float(np.median(times[-3:]))
            assert steady < degraded, (degraded, steady)
        finally:
            for p in peers:
                p.close()
            chaos.reset()

    def test_delay_on_ping_inflates_latency_probe(self, monkeypatch):
        """``on=ping`` reaches get_peer_latencies — the MST re-carve must
        see the same interference the data path pays."""
        from kungfu_tpu import chaos
        from kungfu_tpu.monitor.adapt import get_peer_latencies

        monkeypatch.setenv("KF_CHAOS_SPEC",
                           "delay:ms=60,rank=0,peer=1,on=ping")
        chaos.reset()
        peers = _make_peers(27526, n=2)
        try:
            row = get_peer_latencies(peers[0], samples=1)
            assert row[0] == 0.0
            assert row[1] >= 0.055, row
        finally:
            for p in peers:
                p.close()
            chaos.reset()


class TestHostPoolScaling:
    def test_scales_with_peer_count_capped_and_gauged(self, monkeypatch):
        from kungfu_tpu.comm.host import host_pool_size
        from kungfu_tpu.monitor.registry import REGISTRY

        assert host_pool_size(2) == 2
        assert host_pool_size(1) == 2          # floor
        assert host_pool_size(10) == 10
        assert host_pool_size(500) == 16       # default cap
        assert REGISTRY.gauge("kf_host_pool_size", pool="host").value == 16
        monkeypatch.setenv("KF_CONFIG_HOST_POOL_MAX", "4")
        assert host_pool_size(10) == 4
        # the operator's cap wins over any caller floor (a
        # thread-constrained host must be able to bound the engine pool)
        assert host_pool_size(10, floor=8, pool="engine") == 4
        assert REGISTRY.gauge("kf_host_pool_size", pool="engine").value == 4
        monkeypatch.setenv("KF_CONFIG_HOST_POOL_MAX", "0")
        assert host_pool_size(10) >= 1         # nonsense cap stays sane

    def test_p2p_responder_pool_scales_with_peers(self, monkeypatch):
        """install_p2p_handler sizes the responder pool from the peer
        count (env override still pins it)."""
        from kungfu_tpu.store.p2p import install_p2p_handler

        class FakeChan:
            def on_p2p_request(self, h):
                self.handler = h

        def n_responders():
            return sum(1 for t in threading.enumerate()
                       if t.is_alive()
                       and t.name.startswith("kf-p2p-responder"))

        monkeypatch.delenv("KF_CONFIG_P2P_RESPONDERS", raising=False)
        before = n_responders()
        stop = install_p2p_handler(FakeChan(), store={}, n_peers=6)
        try:
            assert n_responders() - before == 6
        finally:
            stop()
        monkeypatch.setenv("KF_CONFIG_P2P_RESPONDERS", "3")
        before = n_responders()
        stop = install_p2p_handler(FakeChan(), store={}, n_peers=12)
        try:
            assert n_responders() - before == 3
            # the gauge reflects the PINNED size too
            from kungfu_tpu.monitor.registry import REGISTRY

            assert REGISTRY.gauge("kf_host_pool_size",
                                  pool="p2p").value == 3
        finally:
            stop()


class TestPallasRingArm:
    """ISSUE 12: ``pallas_ring`` — the in-kernel-overlap ICI ring of
    ``ops/pallas/collectives.py`` — as a first-class device-bandit arm:
    default arm set, per-bucket install through the consensus-fenced
    lockstep swap, and the reset-on-resize contract."""

    def test_pallas_ring_in_default_arm_set(self):
        import jax

        from kungfu_tpu.comm.device import Communicator
        from kungfu_tpu.monitor.adapt_device import DeviceBanditDriver

        comm = Communicator(devices=jax.devices()[:4], local_size=4)
        d = DeviceBanditDriver(comm, check_every=2)
        assert "pallas_ring" in d.table.arms
        comm.set_latency_hook(None)

    def test_pallas_ring_installs_per_bucket(self):
        """Synthetic latencies make pallas_ring the measured winner of
        the LARGE bucket only: the driver installs it there via
        set_bucket_strategy and leaves the small bucket alone."""
        import jax

        from kungfu_tpu.comm.device import Communicator
        from kungfu_tpu.monitor.adapt_device import DeviceBanditDriver

        comm = Communicator(devices=jax.devices()[:4], local_size=4)
        d = DeviceBanditDriver(comm, check_every=1, min_pulls=1)
        lat = {"psum": 0.05, "two_stage": 0.04, "ring": 0.06,
               "pallas_ring": 0.001}
        small, large = 1 << 10, 1 << 20
        for _ in range(12):
            # both buckets measure every arm: pallas_ring wins the
            # large payloads, psum the latency-bound small ones
            for arm, t in lat.items():
                d._on_collective(large, arm, t)
                d._on_collective(small, arm,
                                 0.0001 if arm == "psum" else 0.01)
            d.step()
        assert comm.strategy_for_bucket(1) == "pallas_ring"
        assert d.table.active[1] == "pallas_ring"
        assert comm.strategy_for_bucket(0) == "psum"
        # the installed arm really routes: a large eager collective now
        # compiles the pallas_ring schedule (cache key carries it)
        x = np.random.default_rng(0).standard_normal((4, large // 4)) \
            .astype(np.float32)
        out = np.asarray(comm.all_reduce(x))
        np.testing.assert_allclose(
            out, np.broadcast_to(x.sum(0), x.shape), rtol=1e-4, atol=1e-4)
        assert any(k[-1] == "pallas_ring" for k in comm._fns
                   if k[0] == "ar"), list(comm._fns)
        comm.set_latency_hook(None)

    def test_fenced_lockstep_install_across_ranks(self, monkeypatch):
        """3-rank in-process cluster, each rank owning its own device
        communicator + driver: identical window exchanges must install
        pallas_ring on EVERY rank at the same seq, through the
        consensus_bytes digest + barrier fence."""
        import jax

        from kungfu_tpu.comm.device import Communicator
        from kungfu_tpu.monitor.adapt_device import DeviceBanditDriver

        monkeypatch.setenv("KF_NATIVE_ENGINE", "0")
        peers = _make_peers(27561)
        try:
            comms = [Communicator(devices=jax.devices()[:4], local_size=4)
                     for _ in peers]
            drivers = [DeviceBanditDriver(c, peer=p, check_every=2,
                                          min_pulls=1)
                       for c, p in zip(comms, peers)]

            def one(rank, d):
                # rank-skewed locals (only the allreduced window can
                # agree), pallas_ring clearly fastest on large payloads
                skew = 1 + 0.3 * rank
                for arm, t in (("psum", 0.05), ("two_stage", 0.04),
                               ("ring", 0.06), ("pallas_ring", 0.002)):
                    d._on_collective(1 << 20, arm, t * skew)
                return d.step()

            for step in range(10):
                flags = run_all([
                    lambda r=r, d=d: one(r, d)
                    for r, d in enumerate(drivers)
                ], timeout=120)
                assert len(set(flags)) == 1, f"non-lockstep at {step}"
            installed = {c.strategy_for_bucket(1) for c in comms}
            assert installed == {"pallas_ring"}, installed
            seqs = {d._seq for d in drivers}
            assert len(seqs) == 1
        finally:
            for p in peers:
                p.close()

    def test_reset_on_live_resize(self):
        """A mesh-epoch rebuild (the resize simulation the strategy
        tests use: retire the communicator, bump the version) rebinds
        the driver, zeroes every bucket table, and drops the installed
        pallas_ring override — a new membership is a new regime."""
        from kungfu_tpu.peer import Peer
        from kungfu_tpu.monitor.adapt_device import DeviceBanditDriver
        from kungfu_tpu.utils import envs as E

        peer = Peer(config=E.parse_config_from_env({}))
        comm0 = peer.communicator()
        d = DeviceBanditDriver(comm0, peer=peer, check_every=1,
                               min_pulls=1)
        for _ in range(6):
            for arm, t in (("psum", 0.05), ("two_stage", 0.04),
                           ("ring", 0.06), ("pallas_ring", 0.001)):
                d._on_collective(1 << 20, arm, t)
            d.step()
        assert comm0.strategy_for_bucket(1) == "pallas_ring"
        assert sum(d.table.tables[1].counts) > 0
        with peer._lock:
            peer._retire_comm()
        peer.cluster_version += 1
        d.step()  # detects the version move and rebinds
        comm1 = peer.communicator()
        assert d.comm is comm1 and comm1 is not comm0
        # re-explore from scratch on the new epoch: table zeroed, no
        # bucket override carried (deliberately NOT persisted — the
        # bandit must re-measure the new regime)
        assert sum(sum(t.counts) for t in d.table.tables) == 0
        assert comm1.bucket_strategies() == {}
        assert d.table.active[1] == comm1.strategy_for_bucket(1)
