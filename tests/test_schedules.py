"""Device-plane allreduce schedules (SURVEY §7 step 9: strategy choice =
choice among compiled collective decompositions).

Every schedule must produce the SAME values as ``lax.psum``-family
reference collectives — on the 8-device virtual CPU mesh (conftest), for
ragged sizes that exercise the padding path, and for the int dtypes whose
pad identity differs from float.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from kungfu_tpu.ops.schedules import ALLREDUCE_SCHEDULES, all_reduce_scheduled

N_DEV = 8


def _mesh():
    return Mesh(np.asarray(jax.devices()[:N_DEV]), ("x",))


def _run(schedule, op, x):
    """x: [N_DEV, ...] stacked input; returns the allreduced stack."""
    mesh = _mesh()

    def body(s):
        return all_reduce_scheduled(s, "x", op=op, schedule=schedule)

    f = shard_map(body, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"))
    return jax.jit(f)(x)


def _reference(op, x):
    red = {
        "sum": np.sum, "mean": np.mean, "min": np.min, "max": np.max,
    }[op](np.asarray(x, np.float64 if x.dtype != np.int32 else np.int64),
          axis=0)
    return np.broadcast_to(red, x.shape)


class TestSchedules:
    @pytest.mark.parametrize("schedule", ["two_stage", "ring"])
    @pytest.mark.parametrize("op", ["sum", "mean", "min", "max"])
    @pytest.mark.parametrize("length", [1, 7, 64, 1000])
    def test_matches_reference(self, schedule, op, length):
        rng = np.random.RandomState(hash((schedule, op, length)) % 2**31)
        x = jnp.asarray(rng.randn(N_DEV, length), jnp.float32)
        out = _run(schedule, op, x)
        ref = _reference(op, np.asarray(x))
        np.testing.assert_allclose(np.asarray(out), ref,
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("schedule", ["two_stage", "ring"])
    @pytest.mark.parametrize("op", ["min", "max"])
    def test_int_dtypes_pad_identity(self, schedule, op):
        """A 0/inf pad would corrupt int min/max on the ragged tail."""
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randint(-1000, 1000, (N_DEV, 13)), jnp.int32)
        out = _run(schedule, op, x)
        ref = _reference(op, np.asarray(x))
        np.testing.assert_array_equal(np.asarray(out), ref)

    def test_psum_schedule_is_default_path(self):
        x = jnp.asarray(np.arange(N_DEV * 4, dtype=np.float32).reshape(N_DEV, 4))
        out = _run("psum", "sum", x)
        np.testing.assert_allclose(np.asarray(out), _reference("sum", np.asarray(x)))

    def test_pytree_input(self):
        rng = np.random.RandomState(0)
        tree = {
            "w": jnp.asarray(rng.randn(N_DEV, 5), jnp.float32),
            "b": jnp.asarray(rng.randn(N_DEV, 3), jnp.float32),
        }
        mesh = _mesh()

        def body(s):
            return all_reduce_scheduled(s, "x", op="sum", schedule="ring")

        f = shard_map(body, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"))
        out = jax.jit(f)(tree)
        for k in tree:
            np.testing.assert_allclose(
                np.asarray(out[k]), _reference("sum", np.asarray(tree[k])),
                rtol=1e-5, atol=1e-5)

    def test_errors(self):
        with pytest.raises(ValueError, match="unknown schedule"):
            all_reduce_scheduled(jnp.ones(4), "x", schedule="tree")
        with pytest.raises(ValueError, match="unsupported op"):
            all_reduce_scheduled(jnp.ones(4), "x", op="prod", schedule="ring")

    def test_tuple_axes_hierarchical(self):
        """(outer, inner) axis tuples: inner folds by psum, the schedule
        runs the outer (cross-host) stage; values match a plain psum."""
        mesh = Mesh(np.asarray(jax.devices()[:N_DEV]).reshape(2, 4),
                    ("h", "l"))
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(N_DEV, 21), jnp.float32)

        def body(s):
            return all_reduce_scheduled(s, ("h", "l"), op="mean",
                                        schedule="ring")

        f = shard_map(body, mesh=mesh, in_specs=(P(("h", "l")),),
                      out_specs=P(("h", "l")))
        out = jax.jit(f)(x)
        np.testing.assert_allclose(np.asarray(out),
                                   _reference("mean", np.asarray(x)),
                                   rtol=1e-5, atol=1e-5)


class TestScheduleFuzz:
    @pytest.mark.slow  # compile-heavy e2e; full tier + CI slow job
    def test_randomized_sweep_matches_psum(self):
        """Seeded randomized sweep (the engine-fuzz analog for the device
        plane): random shapes/dtypes/ops/schedules/mesh splits must all
        agree with the psum reference."""
        rng = np.random.RandomState(20260731)
        for trial in range(25):
            local = int(rng.choice([1, 2, 4, 8]))
            schedule = str(rng.choice(["two_stage", "ring"]))
            op = str(rng.choice(["sum", "mean", "min", "max"]))
            length = int(rng.randint(1, 300))
            dtype = rng.choice([np.float32, np.int32])
            if dtype is np.int32:
                x = rng.randint(-1000, 1000, (N_DEV, length)).astype(np.int32)
                if op == "mean":
                    op = "sum"  # int mean: ill-defined either way
            else:
                x = rng.randn(N_DEV, length).astype(np.float32)
            mesh = Mesh(np.asarray(jax.devices()[:N_DEV]).reshape(
                N_DEV // local, local), ("h", "l"))

            def body(s, op=op, schedule=schedule):
                return all_reduce_scheduled(s, ("h", "l"), op=op,
                                            schedule=schedule)

            f = shard_map(body, mesh=mesh, in_specs=(P(("h", "l")),),
                          out_specs=P(("h", "l")))
            got = np.asarray(jax.jit(f)(jnp.asarray(x)))
            ref = _reference(op, x)
            if x.dtype == np.int32:
                np.testing.assert_array_equal(
                    got, ref.astype(got.dtype),
                    err_msg=f"trial {trial}: {schedule}/{op}/{length}/"
                            f"{local}")
            else:
                np.testing.assert_allclose(
                    got, ref, rtol=1e-5, atol=1e-5,
                    err_msg=f"trial {trial}: {schedule}/{op}/{length}/"
                            f"{local}")


class TestCommunicatorStrategy:
    """Strategy selection on the eager Communicator (the reference's
    ``SetGlobalStrategy`` analog, ``session/adaptation.go:8-28``)."""

    def _comm(self, local_size):
        from kungfu_tpu.comm.device import Communicator

        return Communicator(devices=jax.devices()[:N_DEV],
                            local_size=local_size)

    @pytest.mark.parametrize("local_size", [1, 4, 8])
    @pytest.mark.parametrize("strategy", ALLREDUCE_SCHEDULES)
    def test_all_strategies_match_psum(self, local_size, strategy):
        """Flat (1xN, Nx1) and hierarchical (2x4) meshes; the
        hierarchical case applies the schedule to the cross-host stage."""
        comm = self._comm(local_size)
        comm.set_strategy(strategy)
        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(N_DEV, 33), jnp.float32)
        for op in ("sum", "mean", "max"):
            out = comm.all_reduce(x, op=op)
            ref = _reference(op, np.asarray(x))
            np.testing.assert_allclose(np.asarray(out), ref,
                                       rtol=1e-5, atol=1e-5)

    def test_swap_recompiles_and_caches(self):
        comm = self._comm(8)
        x = jnp.ones((N_DEV, 4), jnp.float32)
        comm.all_reduce(x)
        n0 = len(comm._fns)
        comm.set_strategy("ring")
        comm.all_reduce(x)  # new cache entry under the ring key
        assert len(comm._fns) == n0 + 1
        comm.set_strategy("psum")
        comm.all_reduce(x)  # back to the original compiled program
        assert len(comm._fns) == n0 + 1

    @pytest.mark.parametrize("strategy", ["two_stage", "ring"])
    def test_sub_axis_collectives_honor_axes(self, strategy):
        """local_/cross_all_reduce under a non-psum strategy must reduce
        over their OWN axis, not the whole mesh (regression: the
        scheduled body once ignored the requested axes and silently
        computed a global sum)."""
        comm = self._comm(4)  # 2 hosts x 4 local
        comm.set_strategy(strategy)
        x = jnp.asarray(np.arange(N_DEV * 2, dtype=np.float32).reshape(N_DEV, 2))
        xa = np.asarray(x)
        local = np.asarray(comm.local_all_reduce(x, op="mean"))
        # per-host means, replicated within each host's block of 4
        for h in range(2):
            blk = xa[4 * h:4 * h + 4]
            np.testing.assert_allclose(local[4 * h:4 * h + 4],
                                       np.broadcast_to(blk.mean(0), blk.shape),
                                       rtol=1e-6)
        cross = np.asarray(comm.cross_all_reduce(x, op="sum"))
        # peers with the same local rank sum across the 2 hosts
        for l in range(4):
            pair = xa[[l, 4 + l]]
            np.testing.assert_allclose(cross[[l, 4 + l]],
                                       np.broadcast_to(pair.sum(0), pair.shape),
                                       rtol=1e-6)
        # flat mesh: cross is a no-op under every strategy
        flat = self._comm(8)
        flat.set_strategy(strategy)
        np.testing.assert_allclose(np.asarray(flat.cross_all_reduce(x)), xa)

    @pytest.mark.parametrize("strategy", ["two_stage", "ring"])
    def test_bool_min_max(self, strategy):
        """bool consensus-style reduces must not be strategy-dependent
        (regression: _pad_identity crashed on bool via jnp.iinfo)."""
        comm = self._comm(8)
        comm.set_strategy(strategy)
        x = jnp.asarray(np.random.RandomState(0).rand(N_DEV, 5) > 0.5)
        got_max = np.asarray(comm.all_reduce(x, op="max"))
        got_min = np.asarray(comm.all_reduce(x, op="min"))
        xa = np.asarray(x)
        np.testing.assert_array_equal(
            got_max, np.broadcast_to(xa.max(0), xa.shape))
        np.testing.assert_array_equal(
            got_min, np.broadcast_to(xa.min(0), xa.shape))

    def test_env_contract_sets_initial_strategy(self):
        """KF_DEVICE_STRATEGY (the launcher's -device-strategy) seeds the
        peer's schedule — the reference's KUNGFU_ALLREDUCE_STRATEGY
        contract, device plane."""
        from kungfu_tpu.peer import Peer
        from kungfu_tpu.runner.job import Job
        from kungfu_tpu.plan import Cluster, HostList
        from kungfu_tpu.utils import envs as E

        peer = Peer(config=E.parse_config_from_env(
            {E.DEVICE_STRATEGY: "two_stage"}))
        assert peer.communicator().strategy == "two_stage"
        # and the launcher writes it into worker envs
        hl = HostList.parse("127.0.0.1:2")
        cluster = Cluster(hl.gen_runner_list(), hl.gen_peer_list(2))
        job = Job(prog="python3", args=["t.py"], device_strategy="ring")
        p = job.new_proc(cluster.workers[0], cluster)
        assert p.envs[E.DEVICE_STRATEGY] == "ring"

    def test_strategy_survives_mesh_epoch_rebuild(self):
        """A resize rebuilds the mesh, not the user's strategy decision:
        the next mesh epoch's Communicator inherits the installed
        schedule."""
        from kungfu_tpu.peer import Peer
        from kungfu_tpu.utils import envs as E

        peer = Peer(config=E.parse_config_from_env({}))
        comm0 = peer.communicator()
        comm0.set_strategy("ring")
        # what _propose/await_rejoin do on a genuine membership change:
        # retire the communicator object BEFORE the version moves (the
        # naive `_comm = None` here is how the strategy once got lost)
        with peer._lock:
            peer._retire_comm()
        peer.cluster_version += 1
        comm1 = peer.communicator()
        assert comm1 is not comm0
        assert comm1.strategy == "ring"

    def test_strategy_blob_survives_gossip_churn(self):
        """The epoch strategy record lives in the control store, not the
        gossip window: 3+ per-step model saves must not evict it, and a
        re-publish with a longer strategy name must not raise (fixed
        width)."""
        from kungfu_tpu.peer import Peer
        from kungfu_tpu.store.p2p import remote_request
        from kungfu_tpu.utils import envs as E

        peer = Peer(config=E.parse_config_from_env({}))
        peer._ctrl_store.save(Peer._STRATEGY_BLOB, "psum".ljust(32).encode(),
                              version="0")
        # gossip churn: per-step versions roll the gossip store's window
        for step in range(5):
            peer.save("model", b"x" * 8, version=str(step))
        got = remote_request(peer, peer.config.self_id, Peer._STRATEGY_BLOB,
                             version="0")
        assert got is not None and got.decode().strip() == "psum"
        # re-publish a longer name for the same version: fixed width
        peer._ctrl_store.save(Peer._STRATEGY_BLOB,
                              "two_stage".ljust(32).encode(), version="0")
        got = remote_request(peer, peer.config.self_id, Peer._STRATEGY_BLOB,
                             version="0")
        assert got.decode().strip() == "two_stage"

    def test_set_strategy_racing_a_resize_still_lands(self):
        """set_strategy made on a communicator the resize just retired
        must still reach the next epoch (the on_strategy_change hook
        records it on the Peer durably)."""
        from kungfu_tpu.peer import Peer
        from kungfu_tpu.utils import envs as E

        peer = Peer(config=E.parse_config_from_env({}))
        comm0 = peer.communicator()
        with peer._lock:
            peer._retire_comm()  # a concurrent resize got there first
        comm0.set_strategy("two_stage")  # user's call on the old object
        peer.cluster_version += 1
        assert peer.communicator().strategy == "two_stage"

    def test_unknown_strategy_rejected(self):
        comm = self._comm(8)
        with pytest.raises(ValueError, match="unknown strategy"):
            comm.set_strategy("BINARY_TREE_STAR")

    def test_autotune_picks_and_installs(self):
        """autotune_strategy returns a valid schedule, installs it, and
        results stay correct under the winner (the measured AUTO analog
        of reference strategy.go:90-99)."""
        comm = self._comm(8)
        winner = comm.autotune_strategy(nbytes=1 << 12, trials=1)
        assert winner in ALLREDUCE_SCHEDULES
        assert comm.strategy == winner
        x = jnp.asarray(np.random.RandomState(2).randn(N_DEV, 9), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(comm.all_reduce(x, op="mean")),
            _reference("mean", np.asarray(x)), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("schedule", ALLREDUCE_SCHEDULES)
    def test_schedule_reaches_the_training_step(self, schedule):
        """synchronous_sgd(schedule=...) compiles the decomposition into
        the hot path: one dp_train_step over a hierarchical mesh must
        produce identical params under every schedule."""
        import optax

        from kungfu_tpu.optimizers import synchronous_sgd
        from kungfu_tpu.parallel.train import dp_train_step

        comm = self._comm(4)  # 2 hosts x 4 local

        def loss_fn(params, batch):
            x, y = batch
            pred = x @ params["w"]
            return jnp.mean((pred - y) ** 2)

        rng = np.random.RandomState(0)
        params0 = {"w": jnp.asarray(rng.randn(3), jnp.float32)}
        batch = (jnp.asarray(rng.randn(16, 3), jnp.float32),
                 jnp.asarray(rng.randn(16), jnp.float32))
        def run(sched):
            tx = synchronous_sgd(optax.sgd(0.1), comm.axis, schedule=sched)
            step = dp_train_step(loss_fn, tx, comm)
            p1, _, loss = step(params0, tx.init(params0), batch)
            assert np.isfinite(float(loss))
            return np.asarray(p1["w"])

        # psum reference computed inline so the pin holds under any test
        # selection/ordering
        np.testing.assert_allclose(run(schedule), run("psum"),
                                   rtol=1e-5, atol=1e-6)

    def test_device_strategy_driver(self):
        """Step-time regression → collective re-autotune → caller told to
        re-jit; healthy windows track the baseline instead."""
        from kungfu_tpu.monitor import DeviceStrategyDriver

        comm = self._comm(8)
        drv = DeviceStrategyDriver(comm, check_every=4, regression=1.5,
                                   consecutive=2, autotune_nbytes=1 << 10)
        # healthy baseline windows
        for _ in range(8):
            assert not drv.observe(0.010)
        # a single bad window must NOT trigger (consecutive=2)
        for _ in range(4):
            assert not drv.observe(0.030)
        # second consecutive bad window triggers the re-tune
        fired = [drv.observe(0.030) for _ in range(4)]
        assert fired[:3] == [False, False, False] and fired[3]
        assert drv.swaps == 1
        assert comm.strategy in ALLREDUCE_SCHEDULES
        # the new schedule re-establishes its own baseline: the next
        # window only seeds, no instant re-trigger
        for _ in range(4):
            assert not drv.observe(0.030)
        for _ in range(4):
            assert not drv.observe(0.030)
        assert drv.swaps == 1

    def test_ctor_strategy(self):
        from kungfu_tpu.comm.device import Communicator

        comm = Communicator(devices=jax.devices()[:N_DEV], local_size=8,
                            strategy="two_stage")
        assert comm.strategy == "two_stage"
        x = jnp.ones((N_DEV, 4), jnp.float32)
        np.testing.assert_allclose(np.asarray(comm.all_reduce(x)),
                                   np.full((N_DEV, 4), 8.0))


class TestBucketedScatterGather:
    """reduce_scatter_flat / all_gather_flat: the ZeRO collective pair.
    Bucketing is pure program structure — results must be bit-identical
    across bucket layouts, and the pair must round-trip the mesh-major
    chunk geometry exactly."""

    def _mesh(self, n=8):
        return Mesh(np.array(jax.devices()[:n]), ("d",))

    def test_reduce_scatter_matches_psum_slice(self):
        from kungfu_tpu.ops.schedules import reduce_scatter_flat

        n, chunk = 8, 5
        mesh = self._mesh(n)
        rng = np.random.RandomState(0)
        x = rng.randn(n, n * chunk).astype(np.float32)  # per-device rows

        def body(row):
            return reduce_scatter_flat(row[0], ["d"], chunk)

        out = shard_map(body, mesh=mesh, in_specs=P("d"),
                        out_specs=P("d"))(x)
        want = x.sum(0)  # the reduced flat buffer
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)

    @pytest.mark.parametrize("widths", [None, [1] * 5, [2, 3], [4, 1]])
    def test_bucketing_is_bitwise_invariant(self, widths):
        from kungfu_tpu.ops.schedules import reduce_scatter_flat

        n, chunk = 8, 5
        mesh = self._mesh(n)
        rng = np.random.RandomState(1)
        x = rng.randn(n, n * chunk).astype(np.float32)

        def run(w):
            body = lambda row: reduce_scatter_flat(row[0], ["d"], chunk, w)
            return np.asarray(shard_map(
                body, mesh=mesh, in_specs=P("d"), out_specs=P("d"))(x))

        np.testing.assert_array_equal(run(widths), run(None))

    def test_gather_inverts_scatter(self):
        from kungfu_tpu.ops.schedules import (all_gather_flat,
                                              reduce_scatter_flat)

        n, chunk = 8, 3
        mesh = self._mesh(n)
        rng = np.random.RandomState(2)
        x = rng.randn(n, n * chunk).astype(np.float32)

        def body(row):
            shard = reduce_scatter_flat(row[0], ["d"], chunk, [2, 1])
            return all_gather_flat(shard, ["d"], [2, 1])[None]

        out = np.asarray(shard_map(body, mesh=mesh, in_specs=P("d"),
                                   out_specs=P("d"))(x))
        want = x.sum(0)
        for r in range(n):  # every device sees the full reduced buffer
            np.testing.assert_allclose(out[r], want, rtol=1e-5)

    def test_empty_axes_is_identity(self):
        from kungfu_tpu.ops.schedules import (all_gather_flat,
                                              reduce_scatter_flat)

        x = jnp.arange(6, dtype=jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(reduce_scatter_flat(x, [], 6)), np.asarray(x))
        np.testing.assert_array_equal(
            np.asarray(all_gather_flat(x, [])), np.asarray(x))

    # kf-overlap satellite: serial vs pipelined bucket sequencing is a
    # SCHEDULING property only — results pinned bitwise for all bucket
    # counts, including the 1-bucket and padded-tail degenerate cases
    # (chunk=5, widths [4,1]/[2,3] leave a tail narrower than the body;
    # chunk 5 over n=8 means the last devices' rows are pure padding in
    # the zero geometry — the shapes below exercise both).
    @pytest.mark.parametrize("widths", [None, [5], [2, 3], [4, 1], [1] * 5])
    def test_serial_pipelined_bitwise(self, widths):
        from kungfu_tpu.ops.schedules import reduce_scatter_flat

        n, chunk = 8, 5
        mesh = self._mesh(n)
        rng = np.random.RandomState(3)
        x = rng.randn(n, n * chunk).astype(np.float32)

        def run(serial):
            body = lambda row: reduce_scatter_flat(
                row[0], ["d"], chunk, widths, serial=serial)
            return np.asarray(shard_map(
                body, mesh=mesh, in_specs=P("d"), out_specs=P("d"))(x))

        a, b = run(False), run(True)
        assert a.tobytes() == b.tobytes()

    @pytest.mark.parametrize("widths", [None, [3], [2, 1], [1] * 3])
    def test_all_gather_prefetch_bitwise(self, widths):
        from kungfu_tpu.ops.schedules import all_gather_flat

        n, chunk = 8, 3
        mesh = self._mesh(n)
        rng = np.random.RandomState(4)
        shards = rng.randn(n * chunk).astype(np.float32)

        def run(prefetch):
            body = lambda s: all_gather_flat(
                s, ["d"], widths, prefetch=prefetch)[None]
            return np.asarray(shard_map(
                body, mesh=mesh, in_specs=P("d"), out_specs=P("d"))(shards))

        a, b = run(False), run(True)
        assert a.tobytes() == b.tobytes()

    def test_prefetch_gradient_path_bitwise(self):
        """The ZeRO-3 shape: grad through the prefetch fence (its custom
        vjp barriers the cotangents) must be bitwise the plain path's
        gradient — the fence is a value identity in both directions."""
        from kungfu_tpu.ops.schedules import all_gather_flat

        n, chunk = 4, 6
        mesh = self._mesh(n)
        rng = np.random.RandomState(5)
        shards = rng.randn(n * chunk).astype(np.float32)
        w = rng.randn(n * chunk).astype(np.float32)

        def grad_of(prefetch):
            def loss_body(s):
                full = all_gather_flat(s, ["d"], [2, 2, 2],
                                       prefetch=prefetch)
                return jnp.sum(full * w) * jnp.ones((1,))

            # the gathered value is replicated in fact but typed as
            # varying over d, which the replicated out_spec rejects
            f = shard_map(loss_body, mesh=mesh, in_specs=P("d"),
                          out_specs=P(None), check_vma=False)
            return np.asarray(jax.jit(jax.grad(
                lambda s: f(s)[0]))(jnp.asarray(shards)))

        a, b = grad_of(False), grad_of(True)
        assert a.tobytes() == b.tobytes()

    def test_gather_transpose_is_reduce_scatter(self):
        """grad(loss(all_gather_flat(shard))) must arrive already
        reduce-scattered — the ZeRO-3 gradient path costs no extra
        collective.  Witnessed structurally: the traced backward program
        contains a reduce_scatter, not a psum + slice."""
        from kungfu_tpu.ops.schedules import (all_gather_flat,
                                              traced_collective_bytes)

        n, chunk = 8, 4
        mesh = self._mesh(n)

        def body(shard):
            def loss(s):
                return jnp.sum(all_gather_flat(s, ["d"]) ** 2)

            return jax.grad(loss)(shard)

        fn = shard_map(body, mesh=mesh, in_specs=P("d"), out_specs=P("d"))
        x = jnp.ones((n * chunk,), jnp.float32)
        got = traced_collective_bytes(fn, x, axis_sizes={"d": n})
        assert "reduce_scatter" in got, got


class TestBucketWidths:
    def test_partitions_chunk(self):
        from kungfu_tpu.ops.schedules import bucket_widths

        for chunk, n, item, bb in [(100, 8, 4, 64), (5, 2, 4, 1 << 20),
                                   (7, 3, 2, 12), (1, 8, 4, 1)]:
            w = bucket_widths(chunk, n, item, bb)
            assert sum(w) == chunk and all(x > 0 for x in w)
            per = max(1, bb // (n * item))
            assert all(x <= per for x in w)

    def test_degenerate(self):
        from kungfu_tpu.ops.schedules import bucket_widths

        assert bucket_widths(0, 8, 4, 64) == []
        assert bucket_widths(10, 1, 4, 1 << 30) == [10]


class TestTracedCollectiveBytes:
    """The bench measurement primitive: wire bytes read from the traced
    program, ring convention."""

    def test_psum_cost_exact(self):
        from kungfu_tpu.ops.schedules import traced_collective_bytes

        n = 8
        mesh = Mesh(np.array(jax.devices()[:n]), ("d",))
        m = 16

        def body(row):
            return jax.lax.psum(row[0], "d")[None]

        fn = shard_map(body, mesh=mesh, in_specs=P("d"), out_specs=P("d"))
        x = jnp.ones((n, m), jnp.float32)
        got = traced_collective_bytes(fn, x, axis_sizes={"d": n})
        want = 2.0 * (n - 1) / n * m * 4
        assert got == {"psum": want}, (got, want)

    def test_single_axis_world_costs_nothing(self):
        from kungfu_tpu.ops.schedules import traced_collective_bytes

        mesh = Mesh(np.array(jax.devices()[:1]), ("d",))

        def body(row):
            return jax.lax.psum(row[0], "d")[None]

        fn = shard_map(body, mesh=mesh, in_specs=P("d"), out_specs=P("d"))
        got = traced_collective_bytes(
            fn, jnp.ones((1, 4), jnp.float32), axis_sizes={"d": 1})
        assert got == {}

    def test_non_collective_program_is_empty(self):
        from kungfu_tpu.ops.schedules import traced_collective_bytes

        got = traced_collective_bytes(
            lambda x: x * 2 + 1, jnp.ones((8,)), axis_sizes={"d": 8})
        assert got == {}
