"""Distributed optimizer semantics tests.

Simulated peers = leading stacked axis shard-mapped over the 8-device CPU
mesh (analog of the reference's np=4 localhost optimizer tests,
tests/python/integration/test_optimizers.py).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from kungfu_tpu.comm import Communicator
from kungfu_tpu.optimizers import (
    adaptive_sgd,
    monitor_gradient_noise_scale,
    monitor_gradient_variance,
    synchronous_averaging,
    synchronous_sgd,
)
from jax import shard_map

N = 8


@pytest.fixture(scope="module")
def comm():
    return Communicator()


def per_peer(comm, fn):
    """shard_map a per-peer function over stacked inputs."""
    return jax.jit(
        shard_map(
            fn,
            mesh=comm.mesh,
            in_specs=P(comm.axis),
            out_specs=P(comm.axis),
        )
    )


def stacked(shape, seed=0):
    return np.random.RandomState(seed).uniform(-1, 1, (N,) + shape).astype(np.float32)


class TestSyncSGD:
    def test_equals_mean_gradient_sgd(self, comm):
        lr = 0.1
        params0 = stacked((4,))
        grads = stacked((4,), seed=1)
        opt = synchronous_sgd(optax.sgd(lr), axis=comm.axis)

        def step(p, g):
            state = opt.init(p)
            updates, _ = opt.update(g, state, p)
            return optax.apply_updates(p, updates)

        out = np.asarray(per_peer(comm, step)(params0, grads))
        want = params0 - lr * np.broadcast_to(grads.mean(0), grads.shape)
        np.testing.assert_allclose(out, want, rtol=1e-5)

    def test_sum_mode(self, comm):
        params0 = stacked((3,))
        grads = stacked((3,), seed=2)
        opt = synchronous_sgd(optax.sgd(1.0), axis=comm.axis, average=False)

        def step(p, g):
            updates, _ = opt.update(g, opt.init(p), p)
            return optax.apply_updates(p, updates)

        out = np.asarray(per_peer(comm, step)(params0, grads))
        np.testing.assert_allclose(out, params0 - grads.sum(0), rtol=1e-5)

    def test_fused_buckets_match_per_leaf(self, comm):
        """fuse_grads=True (one flat-buffer collective) must be
        value-identical to the per-leaf path, mixed shapes and dtypes
        included, on every schedule."""
        lr = 0.1
        tree_p = {
            "w": stacked((4, 3)),
            "b": stacked((3,), seed=5),
        }
        tree_g = {
            "w": stacked((4, 3), seed=6),
            "b": stacked((3,), seed=7),
        }
        for sched in ("psum", "ring", "two_stage"):
            outs = {}
            for fused in (False, True):
                opt = synchronous_sgd(optax.sgd(lr), axis=comm.axis,
                                      schedule=sched, fuse_grads=fused)

                def step(p, g):
                    updates, _ = opt.update(g, opt.init(p), p)
                    return optax.apply_updates(p, updates)

                outs[fused] = per_peer(comm, step)(tree_p, tree_g)
            for a, b in zip(jax.tree_util.tree_leaves(outs[False]),
                            jax.tree_util.tree_leaves(outs[True])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-7,
                                           err_msg=sched)

    def test_replicas_stay_in_sync(self, comm):
        """After a sync step from identical params, replicas are identical."""
        p0 = np.broadcast_to(np.arange(4, dtype=np.float32), (N, 4)).copy()
        grads = stacked((4,), seed=3)
        opt = synchronous_sgd(optax.adam(1e-2), axis=comm.axis)

        def step(p, g):
            updates, _ = opt.update(g, opt.init(p), p)
            return optax.apply_updates(p, updates)

        out = np.asarray(per_peer(comm, step)(p0, grads))
        for i in range(1, N):
            np.testing.assert_allclose(out[i], out[0], rtol=1e-6)


class TestSMA:
    def test_ea_sgd_update(self, comm):
        lr, alpha = 0.1, 0.1
        params0 = stacked((4,))
        grads = stacked((4,), seed=1)
        opt = synchronous_averaging(optax.sgd(lr), axis=comm.axis, alpha=alpha)

        def step(p, g):
            updates, _ = opt.update(g, opt.init(p), p)
            return optax.apply_updates(p, updates)

        out = np.asarray(per_peer(comm, step)(params0, grads))
        avg = params0.mean(0)
        want = params0 - lr * grads + alpha * (avg - params0)
        np.testing.assert_allclose(out, want, rtol=1e-5)

    def test_contracts_toward_consensus(self, comm):
        """With zero gradients, repeated SMA shrinks replica disagreement."""
        opt = synchronous_averaging(optax.sgd(0.1), axis=comm.axis, alpha=0.5)
        p = stacked((4,))
        zeros = np.zeros_like(p)

        def step(p, g):
            updates, _ = opt.update(g, opt.init(p), p)
            return optax.apply_updates(p, updates)

        f = per_peer(comm, step)
        spread0 = p.std(0).mean()
        for _ in range(5):
            p = np.asarray(f(p, zeros))
        assert p.std(0).mean() < 0.05 * spread0


class TestAdaptiveSGD:
    def test_phase_switch(self, comm):
        lr, alpha, change = 0.1, 0.1, 2
        opt = adaptive_sgd(optax.sgd(lr), axis=comm.axis, change_step=change, alpha=alpha)
        params0 = stacked((4,))
        grads = stacked((4,), seed=1)

        def steps(p, g):
            state = opt.init(p)
            outs = []
            for _ in range(4):
                updates, state = opt.update(g, state, p)
                p = optax.apply_updates(p, updates)
                outs.append(p)
            return tuple(outs)

        outs = per_peer(comm, steps)(params0, grads)
        outs = [np.asarray(o) for o in outs]
        # step 0 (SMA phase): local grads + alpha pull
        avg0 = params0.mean(0)
        want0 = params0 - lr * grads + alpha * (avg0 - params0)
        np.testing.assert_allclose(outs[0], want0, rtol=1e-4)
        # after the switch step, replicas are re-synced and move together
        post = outs[2]
        for i in range(1, N):
            np.testing.assert_allclose(post[i], post[0], rtol=1e-4, atol=1e-6)
        # and stay together under sync updates
        final = outs[3]
        for i in range(1, N):
            np.testing.assert_allclose(final[i], final[0], rtol=1e-4, atol=1e-6)


class TestMonitors:
    def test_gns_state_updates(self, comm):
        opt = monitor_gradient_noise_scale(
            optax.sgd(0.1), axis=comm.axis, local_batch_size=32
        )
        params0 = stacked((6,))
        grads = stacked((6,), seed=1)

        def step(p, g):
            state = opt.init(p)
            updates, state = opt.update(g, state, p)
            return optax.apply_updates(p, updates), state.noise_scale[None]

        newp, gns = per_peer(comm, step)(params0, grads)
        gns = np.asarray(gns)
        assert np.all(np.isfinite(gns))
        # identical grads across peers -> zero noise -> GNS ~ 0
        same = np.broadcast_to(grads[0], grads.shape).copy()
        _, gns0 = per_peer(comm, step)(params0, same)
        assert abs(float(np.asarray(gns0)[0])) < 1e-3

    def test_variance_zero_for_identical_grads(self, comm):
        opt = monitor_gradient_variance(optax.sgd(0.1), axis=comm.axis)
        params0 = stacked((5,))
        same = np.broadcast_to(params0[0], params0.shape).copy()

        def step(p, g):
            updates, state = opt.update(g, opt.init(p), p)
            return optax.apply_updates(p, updates), state.variance[None]

        _, var_same = per_peer(comm, step)(params0, same)
        assert float(np.asarray(var_same)[0]) < 1e-6
        diff = stacked((5,), seed=9)
        _, var_diff = per_peer(comm, step)(params0, diff)
        assert float(np.asarray(var_diff)[0]) > 1e-3

    def test_variance_matches_numpy(self, comm):
        """Exactness vs the definition E_i |g_i - g_avg|^2 computed in
        numpy — transcription errors in cross-replica statistics are
        invisible to zero/nonzero smoke checks (the sync-BN variance bug
        shipped through exactly that gap)."""
        from kungfu_tpu.ops.monitor import group_all_reduce_with_variance

        grads = stacked((7,), seed=3)

        def f(g):
            avg, var = group_all_reduce_with_variance(g, comm.axis)
            return avg, var[None]

        avg, var = per_peer(comm, f)(grads)
        want_avg = grads.mean(axis=0)
        want_var = np.mean([np.sum((g - want_avg) ** 2) for g in grads])
        np.testing.assert_allclose(np.asarray(avg)[0], want_avg, rtol=1e-5)
        np.testing.assert_allclose(float(np.asarray(var)[0]), want_var, rtol=1e-4)

    def test_gns_matches_formula(self, comm):
        """Exactness vs the two-batch estimator (OpenAI GNS appendix):
        |G|^2 = (B|g_B|^2 - b|g_b|^2)/(B - b), S = (|g_b|^2 - |g_B|^2) /
        (1/b - 1/B), GNS = S/|G|^2, with |g_b|^2 peer-averaged."""
        from kungfu_tpu.ops.monitor import global_noise_scale

        b_small = 16
        grads = stacked((9,), seed=4)

        def gns_fn(g):
            import kungfu_tpu.ops.collective as kc
            avg = kc.all_reduce(g, comm.axis, op="mean")
            return global_noise_scale(g, avg, b_small, comm.axis)[None]

        got = float(np.asarray(per_peer(comm, gns_fn)(grads))[0])

        n = grads.shape[0]
        b_big = b_small * n
        avg = grads.mean(axis=0)
        g_small_sq = np.mean([np.sum(g * g) for g in grads])
        g_big_sq = np.sum(avg * avg)
        g2 = (b_big * g_big_sq - b_small * g_small_sq) / (b_big - b_small)
        s = (g_small_sq - g_big_sq) / (1.0 / b_small - 1.0 / b_big)
        want = s / abs(g2)
        np.testing.assert_allclose(got, want, rtol=1e-4)


class TestPairAveraging:
    def test_single_process_gossip_loop(self):
        """np=1 degenerate mode: behaves like plain SGD, publishes models."""
        from kungfu_tpu.optimizers import PairAveragingOptimizer
        from kungfu_tpu.peer import Peer
        from kungfu_tpu.store.store import reset_local_store

        reset_local_store()
        peer = Peer()  # single-process config
        peer.start()
        opt = PairAveragingOptimizer(optax.sgd(0.1), peer=peer)
        params = {"w": jnp.arange(4, dtype=jnp.float32)}
        state = opt.init(params)
        grads = {"w": jnp.ones(4, jnp.float32)}
        params, state = opt.step(params, grads, state)
        np.testing.assert_allclose(
            np.asarray(params["w"]), np.arange(4) - 0.1, rtol=1e-6
        )
        # model was published to the peer's store
        assert peer.store.get("model") is not None
        reset_local_store()

    def test_two_peer_gossip_averaging(self):
        """Two in-process peers with real TCP channels: pull + average."""
        from kungfu_tpu.optimizers import PairAveragingOptimizer
        from kungfu_tpu.plan import Cluster, PeerID, PeerList
        from kungfu_tpu.peer import Peer
        from kungfu_tpu.store.store import reset_local_store
        from kungfu_tpu.utils.envs import Config

        reset_local_store()
        workers = PeerList.parse("127.0.0.1:24001,127.0.0.1:24002")
        runners = PeerList.parse("127.0.0.1:38081")
        cluster = Cluster(runners, workers)
        peers = [
            Peer(Config(self_id=workers[i], cluster=cluster))
            for i in range(2)
        ]
        for p in peers:
            p.start()
        try:
            opts = [
                PairAveragingOptimizer(optax.sgd(0.0), peer=p, selector="roundrobin")
                for p in peers
            ]
            params = [
                {"w": jnp.zeros(4, jnp.float32)},
                {"w": jnp.ones(4, jnp.float32) * 2.0},
            ]
            import threading

            states = [None, None]

            def init_one(i):
                states[i] = opts[i].init(params[i])

            ts = [threading.Thread(target=init_one, args=(i,)) for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)
            grads = {"w": jnp.zeros(4, jnp.float32)}
            # peer 0 pulls peer 1's model (2.0) and averages -> 1.0
            params0, _ = opts[0].step(params[0], grads, states[0])
            np.testing.assert_allclose(np.asarray(params0["w"]), np.ones(4), rtol=1e-6)
        finally:
            for p in peers:
                p.close()
            reset_local_store()


class _FakePullPeer:
    """Drives _ModelPuller without a wire: request_into fills the buffer
    with an incrementing fill value, or misses when told to."""

    def __init__(self):
        self.pulls = 0
        self.miss = False
        self.delay = 0.0

    def request_into(self, target, name, buf, version=None, timeout=None,
                     send_retries=None):
        import time

        if self.delay:
            time.sleep(self.delay)
        if self.miss:
            return None
        self.pulls += 1
        buf[:] = float(self.pulls)
        return buf


class TestAsyncPairAveraging:
    def _puller(self, peer, **kw):
        from kungfu_tpu.optimizers.async_sgd import _ModelPuller

        kw.setdefault("min_interval", 0.0)
        return _ModelPuller(peer, "m", 32, lambda: 1, **kw)

    def test_puller_lands_and_reuses(self):
        import time

        peer = _FakePullPeer()
        p = self._puller(peer, min_interval=60.0)  # exactly one landing
        p.start()
        try:
            assert p.wait_landed(5.0)
            buf, seq = p.take()
            assert seq == 1
            np.testing.assert_allclose(buf, 1.0)
            # no new landing: take() reuses the same model + seq
            buf2, seq2 = p.take()
            assert seq2 == 1 and buf2 is buf
        finally:
            p.close()
        assert not p.is_alive()

    def test_puller_freshest_wins(self):
        import time

        peer = _FakePullPeer()
        p = self._puller(peer)
        p.start()
        try:
            assert p.wait_landed(5.0)
            deadline = time.monotonic() + 5.0
            while peer.pulls < 5 and time.monotonic() < deadline:
                time.sleep(0.01)
            buf, seq = p.take()
            assert seq >= 2  # skipped straight to the freshest landing
            later_buf, later_seq = p.take()
            assert later_seq >= seq
        finally:
            p.close()

    def test_puller_miss_path(self):
        peer = _FakePullPeer()
        peer.miss = True
        p = self._puller(peer)
        p.start()
        try:
            assert not p.wait_landed(0.3)
            assert p.take() is None
            assert p.misses > 0
        finally:
            p.close()
        assert not p.is_alive()

    def test_puller_teardown_with_slow_wire(self):
        """close() returns promptly even with a pull in flight."""
        import time

        peer = _FakePullPeer()
        peer.delay = 0.5
        p = self._puller(peer, pull_timeout=1.0)
        p.start()
        t0 = time.monotonic()
        p.close()
        assert time.monotonic() - t0 < 5.0
        assert not p.is_alive()

    def test_two_peer_async_gossip_averaging(self):
        """Real TCP channels: the background pull lands and the step
        averages with it off the critical path."""
        from kungfu_tpu.optimizers import AsyncPairAveragingOptimizer
        from kungfu_tpu.peer import Peer
        from kungfu_tpu.plan import Cluster, PeerList
        from kungfu_tpu.store.store import reset_local_store
        from kungfu_tpu.utils.envs import Config

        reset_local_store()
        workers = PeerList.parse("127.0.0.1:24011,127.0.0.1:24012")
        runners = PeerList.parse("127.0.0.1:38082")
        cluster = Cluster(runners, workers)
        peers = [Peer(Config(self_id=workers[i], cluster=cluster))
                 for i in range(2)]
        for p in peers:
            p.start()
        opts = []
        try:
            opts = [AsyncPairAveragingOptimizer(
                optax.sgd(0.0), peer=p, selector="roundrobin",
                pull_timeout=10.0) for p in peers]
            params = [
                {"w": jnp.zeros(4, jnp.float32)},
                {"w": jnp.ones(4, jnp.float32) * 2.0},
            ]
            import threading

            states = [None, None]

            def init_one(i):
                states[i] = opts[i].init(params[i])

            ts = [threading.Thread(target=init_one, args=(i,))
                  for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)
            grads = {"w": jnp.zeros(4, jnp.float32)}
            # first step blocks for the first landing (reference
            # semantics), so the average is deterministic: 0.5*(0+2)=1
            params0, _ = opts[0].step(params[0], grads, states[0])
            np.testing.assert_allclose(np.asarray(params0["w"]),
                                       np.ones(4), rtol=1e-6)
            assert opts[0].averaged_steps == 1
            assert opts[0].pull_bytes >= 16
        finally:
            for o in opts:
                o.close()
            for p in peers:
                p.close()
            reset_local_store()

    def test_staleness_bound_blocks_for_fresh_landing(self):
        """After max_staleness consumptions of one landing, the step
        waits (bounded) for a fresh one instead of diverging."""
        from kungfu_tpu.optimizers.async_sgd import AsyncPairAveragingOptimizer

        opt = AsyncPairAveragingOptimizer.__new__(AsyncPairAveragingOptimizer)
        # drive only the staleness logic with a hand-built puller
        peer = _FakePullPeer()
        from kungfu_tpu.optimizers.async_sgd import _ModelPuller

        p = _ModelPuller(peer, "m", 16, lambda: 1,
                         min_interval=30.0)  # one landing, then silence
        p.start()
        try:
            assert p.wait_landed(5.0)
            _, seq = p.take()
            # consume the same landing repeatedly; wait_landed on a silent
            # wire returns False after the bound, not hang
            import time

            t0 = time.monotonic()
            assert not p.wait_landed(0.3)
            assert time.monotonic() - t0 < 2.0
        finally:
            p.close()

    def test_async_step_latency_independent_of_wire(self):
        """The whole point: with a slow wire, async step wall time stays
        at compute scale (blocking would pay the wire every step)."""
        import time

        import optax

        class _FakeGossipPeer:
            """Just enough peer surface for the optimizer + puller."""

            def __init__(self, wire_s=0.3):
                self.wire_s = wire_s
                self.blobs = {}

            def rank(self):
                return 0

            def size(self):
                return 2

            def save(self, name, blob, version=None, copy=True):
                self.blobs[name] = np.asarray(blob).copy()

            def barrier(self):
                pass

            def request_into(self, target, name, buf, version=None,
                             timeout=None, send_retries=None):
                time.sleep(self.wire_s)
                buf[:] = 7.0
                return buf

        from kungfu_tpu.optimizers.async_sgd import (
            AsyncPairAveragingOptimizer,
        )

        peer = _FakeGossipPeer(wire_s=0.3)
        opt = AsyncPairAveragingOptimizer(optax.sgd(0.0), peer=peer,
                                          pull_timeout=5.0)
        params = {"w": jnp.zeros(1024, jnp.float32)}
        state = opt.init(params)
        grads = {"w": jnp.zeros(1024, jnp.float32)}
        # first step blocks for the first landing; time the next 5
        params, state = opt.step(params, grads, state)
        try:
            t0 = time.perf_counter()
            for _ in range(5):
                params, state = opt.step(params, grads, state)
            wall = time.perf_counter() - t0
            # blocking would cost >= 5 * 0.3s; async stays at compute
            # scale plus at most one staleness wait
            assert wall < 1.0, f"async steps paid the wire: {wall:.2f}s"
            assert opt.averaged_steps + opt.local_steps == 6
            # the averaged value actually came from the landed model:
            # step 1 averaged 0 with 7 -> 3.5
            assert float(np.asarray(params["w"])[0]) > 0.0
        finally:
            opt.close()

    def test_async_gossip_survives_peer_departure(self):
        """A peer closing mid-gossip must not kill the puller thread or
        the survivors' steps: pulls from the dead peer miss (timeout or
        connection error), the staleness bound keeps the step bounded,
        and averaging resumes between the survivors."""
        from kungfu_tpu.optimizers import AsyncPairAveragingOptimizer
        from kungfu_tpu.peer import Peer
        from kungfu_tpu.plan import Cluster, PeerList
        from kungfu_tpu.store.store import reset_local_store
        from kungfu_tpu.utils.envs import Config

        reset_local_store()
        workers = PeerList.parse(
            "127.0.0.1:24021,127.0.0.1:24022,127.0.0.1:24023")
        cluster = Cluster(PeerList.parse("127.0.0.1:38083"), workers)
        peers = [Peer(Config(self_id=workers[i], cluster=cluster))
                 for i in range(3)]
        for p in peers:
            p.start()
        opts = []
        try:
            opts = [AsyncPairAveragingOptimizer(
                optax.sgd(0.0), peer=p, selector="roundrobin",
                pull_timeout=2.0, max_staleness=2) for p in peers]
            params = [{"w": jnp.full(4, float(i), jnp.float32)}
                      for i in range(3)]
            import threading

            states = [None] * 3

            def init_one(i):
                states[i] = opts[i].init(params[i])

            ts = [threading.Thread(target=init_one, args=(i,))
                  for i in range(3)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)
            grads = {"w": jnp.zeros(4, jnp.float32)}
            for i in range(3):
                params[i], states[i] = opts[i].step(params[i], grads,
                                                    states[i])
            # peer 2 leaves without ceremony
            opts[2].close()
            peers[2].close()
            avg_before = [opts[i].averaged_steps for i in range(2)]
            # survivors keep stepping; round-robin targets include the
            # dead peer — those pulls miss, the thread must survive
            import time

            t0 = time.monotonic()
            for _ in range(4):
                for i in range(2):
                    params[i], states[i] = opts[i].step(params[i], grads,
                                                        states[i])
            assert time.monotonic() - t0 < 60.0
            for i in range(2):
                assert opts[i]._puller.is_alive()
                # averaging CONTINUED after the departure (fresh landings
                # from the live peer, or reuse of the last landing) —
                # the pre-departure steps alone must not satisfy this
                assert opts[i].averaged_steps > avg_before[i]
        finally:
            for o in opts[:2]:
                o.close()
            for p in peers[:2]:
                p.close()
            reset_local_store()

    def test_bf16_wire_gossip(self):
        """fuse_dtype=bfloat16 halves gossip wire bytes; the whole
        store/serve/registered-receive chain must survive an ml_dtypes
        dtype that does not export the buffer protocol (the model
        travels as raw uint8 views)."""
        import threading

        from kungfu_tpu.optimizers import AsyncPairAveragingOptimizer
        from kungfu_tpu.peer import Peer
        from kungfu_tpu.plan import Cluster, PeerList
        from kungfu_tpu.store.store import reset_local_store
        from kungfu_tpu.utils.envs import Config

        reset_local_store()
        workers = PeerList.parse("127.0.0.1:24031,127.0.0.1:24032")
        cluster = Cluster(PeerList.parse("127.0.0.1:38084"), workers)
        peers = [Peer(Config(self_id=workers[i], cluster=cluster))
                 for i in range(2)]
        for p in peers:
            p.start()
        opts = []
        try:
            opts = [AsyncPairAveragingOptimizer(
                optax.sgd(0.0), peer=p, selector="roundrobin",
                fuse_dtype=jnp.bfloat16) for p in peers]
            params = [{"w": jnp.zeros(64, jnp.float32)},
                      {"w": jnp.ones(64, jnp.float32) * 2.0}]
            states = [None, None]

            def init_one(i):
                states[i] = opts[i].init(params[i])

            ts = [threading.Thread(target=init_one, args=(i,))
                  for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)
            grads = {"w": jnp.zeros(64, jnp.float32)}
            p0, _ = opts[0].step(params[0], grads, states[0])
            np.testing.assert_allclose(
                np.asarray(p0["w"], np.float32), np.ones(64), rtol=1e-2)
            # 64 params x 2 bytes on the wire per landed model
            assert opts[0].pull_bytes % 128 == 0 and opts[0].pull_bytes > 0
        finally:
            for o in opts:
                o.close()
            for p in peers:
                p.close()
            reset_local_store()
