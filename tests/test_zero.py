"""Weight-update sharding (ZeRO-1): exactness vs plain S-SGD, per-device
optimizer-state memory, padding, hierarchical meshes.

The technique (reduce-scatter grads → shard update → all-gather params)
is exactly equivalent to the replicated update for elementwise inner
transforms — these tests pin that equivalence against
``dp_train_step + synchronous_sgd`` on the 8-device virtual CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kungfu_tpu.comm.device import Communicator
from kungfu_tpu.parallel.train import dp_train_step
from kungfu_tpu.parallel.zero import opt_state_bytes, zero1_train_step
from kungfu_tpu.optimizers import synchronous_sgd

N_DEV = 8


def _params(sizes=((13, 7), (7,), (7, 5))):
    rng = np.random.RandomState(0)
    return {
        f"w{i}": jnp.asarray(rng.randn(*s), jnp.float32)
        for i, s in enumerate(sizes)
    }


def _loss_fn(params, batch):
    x, y = batch
    h = jnp.tanh(x @ params["w0"] + params["w1"])
    pred = h @ params["w2"]
    return jnp.mean((pred - y) ** 2)


def _batch(n=16):
    rng = np.random.RandomState(1)
    return (jnp.asarray(rng.randn(n, 13), jnp.float32),
            jnp.asarray(rng.randn(n, 5), jnp.float32))


def _reference_step(comm, inner, params, batch):
    tx = synchronous_sgd(inner, comm.axis)
    step = dp_train_step(_loss_fn, tx, comm)
    p1, _, loss = step(params, tx.init(params), batch)
    return p1, loss


class TestZero1:
    @pytest.mark.parametrize("local_size", [8, 4])
    @pytest.mark.parametrize("make_inner", [
        lambda: optax.sgd(0.1, momentum=0.9),
        lambda: optax.adam(1e-2),
        lambda: optax.adamw(1e-2, weight_decay=0.01),
    ], ids=["momentum", "adam", "adamw"])
    def test_matches_replicated_update(self, local_size, make_inner):
        comm = Communicator(devices=jax.devices()[:N_DEV],
                            local_size=local_size)
        params, batch = _params(), _batch()
        ref_p, ref_loss = _reference_step(comm, make_inner(), params, batch)

        step, init_opt = zero1_train_step(_loss_fn, make_inner(), comm)
        opt = init_opt(params)
        p1, opt1, loss = step(params, opt, batch)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(p1[k]), np.asarray(ref_p[k]),
                rtol=1e-5, atol=1e-6, err_msg=k)

    def test_opt_state_is_sharded(self):
        """Each device holds 1/n of the momentum (plus padding) — the
        entire point of the technique."""
        comm = Communicator(devices=jax.devices()[:N_DEV], local_size=8)
        params, batch = _params(), _batch()
        step, init_opt = zero1_train_step(
            _loss_fn, optax.sgd(0.1, momentum=0.9), comm)
        opt = init_opt(params)
        total = sum(int(np.prod(l.shape))
                    for l in jax.tree_util.tree_leaves(params))
        mom = [l for l in jax.tree_util.tree_leaves(opt)
               if hasattr(l, "shape") and l.ndim == 1]
        assert mom, opt
        chunk = -(-total // N_DEV)  # ceil
        for leaf in mom:
            assert leaf.shape[0] == chunk * N_DEV  # padded global
            shard_sizes = {
                int(np.prod(s.data.shape)) for s in leaf.addressable_shards
            }
            assert shard_sizes == {chunk}, shard_sizes
        # global optimizer footprint ~= one full momentum (split across
        # devices), NOT n replicated copies
        full_tx = optax.sgd(0.1, momentum=0.9)
        full_bytes = opt_state_bytes(full_tx.init(params))
        assert opt_state_bytes(opt) <= full_bytes + chunk * N_DEV * 4

    def test_multiple_steps_track_reference(self):
        comm = Communicator(devices=jax.devices()[:N_DEV], local_size=8)
        params, batch = _params(), _batch()
        inner = optax.sgd(0.05, momentum=0.9)
        tx = synchronous_sgd(inner, comm.axis)
        ref_step = dp_train_step(_loss_fn, tx, comm)
        ref_p, ref_o = params, tx.init(params)

        step, init_opt = zero1_train_step(
            _loss_fn, optax.sgd(0.05, momentum=0.9), comm)
        p, o = params, init_opt(params)
        for _ in range(3):
            ref_p, ref_o, _ = ref_step(ref_p, ref_o, batch)
            p, o, _ = step(p, o, batch)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(p[k]), np.asarray(ref_p[k]),
                rtol=1e-4, atol=1e-5, err_msg=k)

    def test_reshard_across_resize_tracks_reference(self):
        """An elastic resize mid-run (8 → 4 devices) with zero1_reshard
        must continue EXACTLY like the replicated optimizer seeing the
        same global batches: momentum state survives the re-chunking."""
        from kungfu_tpu.parallel.zero import zero1_reshard

        devs = jax.devices()
        c8 = Communicator(devices=devs[:8], local_size=8, version=0)
        c4 = Communicator(devices=devs[:4], local_size=4, version=1)
        params, batch = _params(), _batch(16)  # 16 divides 8 and 4
        inner = lambda: optax.adam(1e-2)  # noqa: E731 — two-moment state

        # reference: replicated S-SGD over the SAME global batches, mesh
        # change irrelevant to its math
        tx = synchronous_sgd(inner(), c8.axis)
        ref_step8 = dp_train_step(_loss_fn, tx, c8)
        tx4 = synchronous_sgd(inner(), c4.axis)
        ref_step4 = dp_train_step(_loss_fn, tx4, c4)
        ref_p, ref_o = params, tx.init(params)
        for _ in range(2):
            ref_p, ref_o, _ = ref_step8(ref_p, ref_o, batch)
        # carry the OPTIMIZER state across the mesh change (replicated
        # state has no geometry — only its placement moves epochs)
        from kungfu_tpu.initializer import resync_parameters

        ref_p = resync_parameters(ref_p, comm=c4)
        ref_o = jax.tree_util.tree_map(
            lambda a: jax.device_put(np.asarray(a), c4.replicated_sharding()),
            ref_o)
        for _ in range(2):
            ref_p, ref_o, _ = ref_step4(ref_p, ref_o, batch)

        step8, init8 = zero1_train_step(_loss_fn, inner(), c8)
        p, o = params, init8(params)
        for _ in range(2):
            p, o, _ = step8(p, o, batch)
        o = zero1_reshard(o, p, c4)
        p = resync_parameters(p, comm=c4)  # params re-place replicated
        step4, _ = zero1_train_step(_loss_fn, inner(), c4)
        for _ in range(2):
            p, o, _ = step4(p, o, batch)

        for k in params:
            np.testing.assert_allclose(
                np.asarray(p[k]), np.asarray(ref_p[k]),
                rtol=1e-4, atol=1e-5, err_msg=k)

    def test_snapshot_restore_roundtrip_across_resize(self):
        """snapshot → restore across 8→4 must agree exactly with
        zero1_reshard (the host-plane path for provisioned worlds, here
        exercised channel-less: every chunk is locally addressable)."""
        from kungfu_tpu.parallel.zero import (zero1_reshard, zero1_restore,
                                              zero1_snapshot)

        devs = jax.devices()
        c8 = Communicator(devices=devs[:8], local_size=8, version=0)
        c4 = Communicator(devices=devs[:4], local_size=4, version=1)
        params, batch = _params(), _batch()
        step8, init8 = zero1_train_step(_loss_fn, optax.adam(1e-2), c8)
        p, o = params, init8(params)
        for _ in range(2):
            p, o, _ = step8(p, o, batch)

        blob = zero1_snapshot(o)
        want = zero1_reshard(o, p, c4)
        _, init4 = zero1_train_step(_loss_fn, optax.adam(1e-2), c4)
        got = zero1_restore(blob, init4(p), p, new_comm=c4)
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_restore_detects_missing_chunks(self):
        """A snapshot missing a contributor's chunks must raise, not
        silently restore zeros into the momentum."""
        import io

        from kungfu_tpu.parallel.zero import zero1_restore, zero1_snapshot

        comm = Communicator(devices=jax.devices()[:8], local_size=8)
        params = _params()
        step, init_opt = zero1_train_step(
            _loss_fn, optax.sgd(0.1, momentum=0.9), comm)
        o = init_opt(params)
        blob = zero1_snapshot(o)
        with np.load(io.BytesIO(blob)) as z:
            kept = {k: z[k] for k in z.files if not k.endswith("_o0")}
        bio = io.BytesIO()
        np.savez(bio, **kept)
        with pytest.raises(ValueError, match="missing"):
            zero1_restore(bio.getvalue(), init_opt(params), params,
                          new_comm=comm)

    def test_reshard_multicontroller_routes_to_host_plane(self):
        """A multi-controller mesh routes reshard through the
        snapshot/restore host plane (one entry point); without the
        snapshot the contract violation is loud, not a silent
        mis-shard."""
        from kungfu_tpu.parallel.zero import zero1_reshard, zero1_snapshot

        comm = Communicator(devices=jax.devices()[:4], local_size=4)
        _, init_opt = zero1_train_step(_loss_fn, optax.sgd(0.1), comm)
        o = init_opt(_params())
        comm._multiproc = True  # simulate a provisioned-world mesh
        with pytest.raises(ValueError, match="snapshot"):
            zero1_reshard(o, _params(), comm)
        # with the pre-resize snapshot the fold works even on the
        # simulated multi-controller flag (all chunks addressable here)
        blob = zero1_snapshot(o)
        comm._multiproc = False  # placement back on the real local mesh
        got = zero1_reshard(o, _params(), comm, snapshot=blob)
        for a, b in zip(jax.tree_util.tree_leaves(o),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_odd_total_size_pads(self):
        """A parameter count not divisible by n exercises the pad path
        end to end (pad grads are zero, pad params stay zero)."""
        comm = Communicator(devices=jax.devices()[:N_DEV], local_size=8)
        params = {"w": jnp.asarray(np.random.RandomState(3).randn(3, 5),
                                   jnp.float32)}  # 15 elements, n=8

        def loss(p, b):
            x, y = b
            return jnp.mean((x @ p["w"] - y) ** 2)

        rng = np.random.RandomState(4)
        batch = (jnp.asarray(rng.randn(16, 3), jnp.float32),
                 jnp.asarray(rng.randn(16, 5), jnp.float32))
        tx = synchronous_sgd(optax.sgd(0.1), comm.axis)
        ref_p, _, _ = dp_train_step(loss, tx, comm)(
            params, tx.init(params), batch)

        step, init_opt = zero1_train_step(loss, optax.sgd(0.1), comm)
        p1, _, _ = step(params, init_opt(params), batch)
        np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(ref_p["w"]),
                                   rtol=1e-5, atol=1e-6)


class TestReshardSnapshotFold:
    def test_reshard_with_snapshot_matches_direct(self):
        """zero1_reshard(snapshot=...) — the folded host-plane path — is
        value-identical to the direct single-controller re-placement,
        with structure supplied by a FRESH init (the joiner contract)."""
        from kungfu_tpu.parallel.zero import (zero1_reshard, zero1_snapshot,
                                              zero1_train_step)

        devs = jax.devices()
        c8 = Communicator(devices=devs[:8], local_size=8, version=0)
        c4 = Communicator(devices=devs[:4], local_size=4, version=1)
        params, batch = _params(), _batch()
        step8, init8 = zero1_train_step(_loss_fn, optax.adam(1e-2), c8)
        p, o = params, init8(params)
        for _ in range(2):
            p, o, _ = step8(p, o, batch)

        want = zero1_reshard(o, p, c4)
        blob = zero1_snapshot(o)
        _, init4 = zero1_train_step(_loss_fn, optax.adam(1e-2), c4)
        got = zero1_reshard(init4(p), p, c4, snapshot=blob)
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the sharded placement really is 1/n on the new mesh
        vec = [l for l in jax.tree_util.tree_leaves(got)
               if getattr(l, "ndim", 0) == 1]
        assert vec and all(
            len(l.sharding.device_set) == 4 for l in vec)


# ==========================================================================
# ZeRO-2 / ZeRO-3 (zero_train_step) — bucketed reduce-scatter, sharded
# params, measured comm volume
# ==========================================================================


def _comm8(version=0):
    return Communicator(devices=jax.devices()[:8], local_size=8,
                        version=version)


class TestZeroStages:
    """Staged steps must reproduce the replicated update exactly — the
    stage only changes WHERE bytes move, never the math."""

    @pytest.mark.parametrize("stage", [2, 3])
    @pytest.mark.parametrize("make_inner", [
        lambda: optax.sgd(0.1, momentum=0.9),
        lambda: optax.adam(1e-2),
    ], ids=["momentum", "adam"])
    def test_matches_replicated_update(self, stage, make_inner):
        from kungfu_tpu.parallel.zero import zero_train_step

        comm = _comm8()
        params, batch = _params(), _batch()
        ref_p, ref_loss = _reference_step(comm, make_inner(), params, batch)
        z = zero_train_step(_loss_fn, make_inner(), comm, stage=stage)
        o = z.init_opt(params)
        p = z.init_params(params)
        p, o, loss = z.step(p, o, batch)
        full = z.gather_params(p)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(full[k]), np.asarray(ref_p[k]),
                rtol=1e-5, atol=1e-6, err_msg=k)

    def test_stage2_bitwise_vs_replicated_sgd(self):
        """With a stateless elementwise inner (plain SGD) the
        reduce-scatter path is BITWISE identical to the replicated
        all-reduce step on identical inputs — the psum and psum_scatter
        reductions see the same addends in the same combining order."""
        from kungfu_tpu.parallel.zero import zero_train_step

        comm = _comm8()
        params, batch = _params(), _batch()
        ref_p, _ = _reference_step(comm, optax.sgd(0.1), params, batch)
        step, init_opt = zero_train_step(_loss_fn, optax.sgd(0.1), comm,
                                         stage=2)
        p, o, _ = step(params, init_opt(params), batch)
        for k in params:
            np.testing.assert_array_equal(
                np.asarray(p[k]), np.asarray(ref_p[k]), err_msg=k)

    @pytest.mark.parametrize("stage", [2, 3])
    def test_bucketed_matches_unbucketed_bitwise(self, stage):
        """Folding the collective into many small buckets is pure
        program structure: the result must be bit-identical to the
        single-bucket step (the invariant that keeps the elastic state
        geometry stage- and bucket-agnostic)."""
        from kungfu_tpu.parallel.zero import zero_train_step

        comm = _comm8()
        params, batch = _params(), _batch()
        runs = []
        for bb in (4 << 20, 16):  # one bucket vs ~width-4 buckets
            z = zero_train_step(_loss_fn, optax.adam(1e-2), comm,
                                stage=stage, bucket_bytes=bb)
            o = z.init_opt(params)
            p = z.init_params(params)
            p, o, _ = z.step(p, o, batch)
            runs.append(z.gather_params(p))
        for k in params:
            np.testing.assert_array_equal(
                np.asarray(runs[0][k]), np.asarray(runs[1][k]), err_msg=k)

    def test_stage3_params_sharded_between_steps(self):
        """Stage 3's whole point: at rest each device holds 1/n of the
        flat parameter buffer; gather_params reassembles bitwise."""
        from kungfu_tpu.parallel.zero import zero_train_step

        comm = _comm8()
        params = _params()
        z = zero_train_step(_loss_fn, optax.adam(1e-2), comm, stage=3)
        z.init_opt(params)
        p_shard = z.init_params(params)
        total = sum(int(np.prod(l.shape))
                    for l in jax.tree_util.tree_leaves(params))
        chunk = -(-total // N_DEV)
        assert p_shard.shape == (chunk * N_DEV,)
        assert {int(np.prod(s.data.shape))
                for s in p_shard.addressable_shards} == {chunk}
        back = z.gather_params(p_shard)
        for k in params:
            np.testing.assert_array_equal(
                np.asarray(back[k]), np.asarray(params[k]), err_msg=k)

    def test_stage3_multiple_steps_track_reference(self):
        from kungfu_tpu.parallel.zero import zero_train_step

        comm = _comm8()
        params, batch = _params(), _batch()
        inner = lambda: optax.sgd(0.05, momentum=0.9)  # noqa: E731
        tx = synchronous_sgd(inner(), comm.axis)
        ref_step = dp_train_step(_loss_fn, tx, comm)
        ref_p, ref_o = params, tx.init(params)
        z = zero_train_step(_loss_fn, inner(), comm, stage=3)
        o = z.init_opt(params)
        p = z.init_params(params)
        for _ in range(3):
            ref_p, ref_o, _ = ref_step(ref_p, ref_o, batch)
            p, o, _ = z.step(p, o, batch)
        full = z.gather_params(p)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(full[k]), np.asarray(ref_p[k]),
                rtol=1e-4, atol=1e-5, err_msg=k)

    def test_unpacks_like_zero1(self):
        """step, init_opt = zero_train_step(...) keeps the ZeRO-1
        calling convention for stages 1/2."""
        from kungfu_tpu.parallel.zero import ZeroStep, zero_train_step

        comm = _comm8()
        params, batch = _params(), _batch()
        out = zero_train_step(_loss_fn, optax.sgd(0.1), comm, stage=2)
        assert isinstance(out, ZeroStep)
        step, init_opt = out
        p, o, loss = step(params, init_opt(params), batch)
        assert np.isfinite(float(loss))

    def test_invalid_stage_rejected(self):
        from kungfu_tpu.parallel.zero import zero_train_step

        with pytest.raises(ValueError, match="stage"):
            zero_train_step(_loss_fn, optax.sgd(0.1), _comm8(), stage=4)

    def test_stage3_step_before_init_params_raises(self):
        from kungfu_tpu.parallel.zero import zero_train_step

        z = zero_train_step(_loss_fn, optax.sgd(0.1), _comm8(), stage=3)
        params, batch = _params(), _batch()
        with pytest.raises(RuntimeError, match="init_params"):
            z.step(params, z.init_opt(params), batch)

    def test_one_rank_world_degenerate_shard(self):
        """n=1: chunk == total, no collective — every stage must still
        run (the regression the elastic re-shard generalization needs:
        a 1-rank world is a legal carve)."""
        from kungfu_tpu.parallel.zero import zero_train_step

        c1 = Communicator(devices=jax.devices()[:1], local_size=1)
        params, batch = _params(), _batch()
        want = None
        for stage in (1, 2, 3):
            z = zero_train_step(_loss_fn, optax.sgd(0.1), c1, stage=stage)
            o = z.init_opt(params)
            p = z.init_params(params)
            p, o, _ = z.step(p, o, batch)
            full = z.gather_params(p)
            if want is None:
                want = full
            else:
                for k in params:
                    np.testing.assert_array_equal(
                        np.asarray(full[k]), np.asarray(want[k]), err_msg=k)

    def test_dp_train_step_routes_zero_stage(self):
        from kungfu_tpu.parallel.zero import ZeroStep

        comm = _comm8()
        params, batch = _params(), _batch()
        out = dp_train_step(_loss_fn, optax.sgd(0.1), comm, zero_stage=2)
        assert isinstance(out, ZeroStep)
        step, init_opt = out
        p, o, loss = step(params, init_opt(params), batch)
        assert np.isfinite(float(loss))
        with pytest.raises(ValueError, match="zero_stage"):
            dp_train_step(_loss_fn, optax.sgd(0.1), comm, zero_stage=2,
                          has_aux=True)


class TestZeroCommVolume:
    """What each way of taking the step moves and holds, counted from the
    TRACED program (no clock, and not the formula that motivated it):
    ``bare`` is raw JAX — ``shard_map``, a ``psum`` a leaf, optax, no
    framework code in the step; ``zero1`` all-reduces the gradients and
    shards the update; ``zero2`` reduce-scatters them; ``zero3`` also
    keeps the parameters sharded between steps."""

    def _bare(self, comm, params):
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P

        tx = optax.adam(1e-2)
        axis, n = comm.axis, comm.size

        def body(p, o, b):
            loss, g = jax.value_and_grad(_loss_fn)(p, b)
            g = jax.tree_util.tree_map(lambda a: lax.psum(a, axis) / n, g)
            updates, o = tx.update(g, o, p)
            return optax.apply_updates(p, updates), o, lax.pmean(loss, axis)

        step = shard_map(body, mesh=comm.mesh,
                         in_specs=(P(), P(), P(axis)),
                         out_specs=(P(), P(), P()))
        return step, params, tx.init(params)

    _traced: dict = {}  # arm -> _arm's answer: the zero2 case reads three

    def _arm(self, arm):
        """``(collective bytes a rank by primitive, optimizer-state bytes
        a rank)`` of one arm, traced once a session."""
        from kungfu_tpu.ops.schedules import traced_collective_bytes
        from kungfu_tpu.parallel.zero import (opt_state_bytes_per_device,
                                              zero_train_step)

        if arm in self._traced:
            return self._traced[arm]
        comm, params, batch = _comm8(), _params(), _batch()
        if arm == "bare":
            step, p, o = self._bare(comm, params)
        else:
            z = zero_train_step(_loss_fn, optax.adam(1e-2), comm,
                                stage=int(arm[-1]))
            step, p, o = z.step, z.init_params(params), z.init_opt(params)
        ax = dict(zip(comm.mesh.axis_names, comm.mesh.devices.shape))
        traced = traced_collective_bytes(
            lambda p_, o_, b_: step(p_, o_, b_), p, o, batch, axis_sizes=ax)
        if arm == "bare":
            # what a device holds AFTER a step: the state the step returns
            # (the unplaced init says nothing about any device)
            _, o, _ = jax.jit(step)(p, o, batch)
        self._traced[arm] = traced, opt_state_bytes_per_device(o)
        return self._traced[arm]

    @pytest.mark.parametrize("arm", ["bare", "zero1", "zero2", "zero3"])
    def test_arm_moves_and_holds_what_it_claims(self, arm):
        n = _comm8().size
        traced, held = self._arm(arm)
        replicated = opt_state_bytes(optax.adam(1e-2).init(_params()))
        if arm in ("bare", "zero1"):
            # the gradient path is an all-reduce (the loss pmean rides
            # every arm: a few bytes)
            assert "psum" in traced and "reduce_scatter" not in traced, traced
        else:
            assert "reduce_scatter" in traced, traced
        if arm == "zero2":
            # the claim: at most ~55% of the all-reduce path's bytes
            # (ring convention), against the framework's and raw JAX's
            for other in ("zero1", "bare"):
                base, _ = self._arm(other)
                ratio = sum(traced.values()) / sum(base.values())
                assert ratio <= 0.55, (other, ratio, traced, base)
        if arm == "zero3":
            # the parameter all-gather and its reduce-scatter transpose
            # both live INSIDE the traced step
            assert "all_gather" in traced, traced
        if arm == "bare":
            assert held == replicated  # a device holds the full state
        else:
            # replicated state is about n times a rank's shard
            assert replicated > (n - 1) * held, (replicated, held)

    def test_analytic_table(self):
        from kungfu_tpu.parallel.zero import zero_comm_bytes

        b1 = zero_comm_bytes(1000, 8, 1)
        b2 = zero_comm_bytes(1000, 8, 2)
        b3 = zero_comm_bytes(1000, 8, 3)
        assert b1["grad_bytes"] == 2 * b2["grad_bytes"]
        assert b2 == b3  # stage 3 moves the same bytes, placed JIT
        assert b1["param_bytes"] == b2["param_bytes"]
        with pytest.raises(ValueError):
            zero_comm_bytes(1000, 0, 2)

    def test_zerostep_comm_bytes_accessor(self):
        from kungfu_tpu.parallel.zero import zero_train_step

        comm = _comm8()
        params = _params()
        z = zero_train_step(_loss_fn, optax.adam(1e-2), comm, stage=2)
        cb = z.comm_bytes(params)
        assert set(cb) >= {"grad_bytes", "param_bytes", "total_bytes"}
        assert cb["grad_bytes"] == cb["param_bytes"]  # both (n-1)/n * N


class TestReshardEdgeCases:
    """The zero1_reshard generalization prerequisites: worlds where the
    padded total shrinks below an old rank's shard offset, and 1-rank
    (degenerate) worlds on either side."""

    def _trained(self, comm, params, batch, steps=1):
        step, init_opt = zero1_train_step(_loss_fn, optax.adam(1e-2), comm)
        p, o = params, init_opt(params)
        for _ in range(steps):
            p, o, _ = step(p, o, batch)
        return p, o

    def test_padded_total_shrinks_below_old_shard(self):
        """total=15 over 8 ranks pads to 16 (rank 7 owns [14:16)); the
        5-rank world pads to 15 < 16 — the old top shard's padding must
        vanish, not shift values."""
        from kungfu_tpu.parallel.zero import (zero1_reshard, zero1_restore,
                                              zero1_snapshot)

        devs = jax.devices()
        c8 = Communicator(devices=devs[:8], local_size=8, version=0)
        c5 = Communicator(devices=devs[:5], local_size=5, version=1)
        params = {"w": jnp.asarray(np.random.RandomState(3).randn(3, 5),
                                   jnp.float32)}

        def loss(p, b):
            x, y = b
            return jnp.mean((x @ p["w"] - y) ** 2)

        rng = np.random.RandomState(4)
        batch = (jnp.asarray(rng.randn(16, 3), jnp.float32),
                 jnp.asarray(rng.randn(16, 5), jnp.float32))
        step8, init8 = zero1_train_step(loss, optax.adam(1e-2), c8)
        p, o = params, init8(params)
        p, o, _ = step8(p, o, batch)

        o5 = zero1_reshard(o, p, c5)
        for a, b in zip(jax.tree_util.tree_leaves(o),
                        jax.tree_util.tree_leaves(o5)):
            a, b = np.asarray(a), np.asarray(b)
            if a.ndim:
                assert b.shape == (15,)
                np.testing.assert_array_equal(a[:15], b)
            else:
                np.testing.assert_array_equal(a, b)
        # snapshot/restore agrees with the direct re-placement
        blob = zero1_snapshot(o)
        _, init5 = zero1_train_step(loss, optax.adam(1e-2), c5)
        got = zero1_restore(blob, init5(p), p, new_comm=c5)
        for a, b in zip(jax.tree_util.tree_leaves(o5),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_one_rank_world_roundtrip(self):
        """8 -> 1 -> 8: the 1-rank world is a legal degenerate carve
        (chunk == total, no padding); values round-trip bitwise."""
        from kungfu_tpu.parallel.zero import zero1_reshard

        devs = jax.devices()
        c8 = Communicator(devices=devs[:8], local_size=8, version=0)
        c1 = Communicator(devices=devs[:1], local_size=1, version=1)
        params, batch = _params(), _batch()
        p, o = self._trained(c8, params, batch)
        o1 = zero1_reshard(o, p, c1)
        total = sum(int(np.prod(l.shape))
                    for l in jax.tree_util.tree_leaves(params))
        for l in jax.tree_util.tree_leaves(o1):
            if getattr(l, "ndim", 0):
                assert l.shape == (total,)  # no padding at n=1
        c8b = Communicator(devices=devs[:8], local_size=8, version=2)
        o8 = zero1_reshard(o1, p, c8b)
        for a, b in zip(jax.tree_util.tree_leaves(o),
                        jax.tree_util.tree_leaves(o8)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_old_world_larger_than_param_count(self):
        """total=5 over 8 ranks: ranks 5..7 hold PURE padding — their
        chunks must neither break the snapshot tiling check nor leak
        padding into the 3-rank re-carve."""
        from kungfu_tpu.parallel.zero import (zero1_reshard, zero1_restore,
                                              zero1_snapshot)

        devs = jax.devices()
        c8 = Communicator(devices=devs[:8], local_size=8, version=0)
        c3 = Communicator(devices=devs[:3], local_size=3, version=1)
        params = {"w": jnp.asarray(np.random.RandomState(5).randn(5),
                                   jnp.float32)}
        _, init8 = zero1_train_step(
            lambda p, b: jnp.sum(p["w"] ** 2), optax.adam(1e-2), c8)
        o = init8(params)
        o3 = zero1_reshard(o, params, c3)
        blob = zero1_snapshot(o)
        _, init3 = zero1_train_step(
            lambda p, b: jnp.sum(p["w"] ** 2), optax.adam(1e-2), c3)
        got = zero1_restore(blob, init3(params), params, new_comm=c3)
        for a, b in zip(jax.tree_util.tree_leaves(o3),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestReshardPlan:
    @pytest.mark.parametrize("total,old_n,new_n", [
        (10, 4, 1), (10, 1, 4), (7, 3, 5), (100, 4, 2), (5, 8, 3),
        (16, 4, 4), (1, 1, 1), (3, 8, 8),
    ])
    def test_plan_partitions_exactly(self, total, old_n, new_n):
        """Segments tile [0, total) with no gap or overlap, and every
        segment lies inside BOTH its old and its new owner's chunk."""
        from kungfu_tpu.parallel.zero import reshard_plan

        plan = reshard_plan(total, old_n, new_n)
        oc, nc = -(-total // old_n), -(-total // new_n)
        cover = np.zeros(total, bool)
        for (o, r, s, ln) in plan:
            assert ln > 0
            assert not cover[s:s + ln].any(), "overlap"
            cover[s:s + ln] = True
            assert o * oc <= s and s + ln <= min((o + 1) * oc, total)
            assert r * nc <= s and s + ln <= min((r + 1) * nc, total)
        assert cover.all(), "gap"

    def test_identity_world_is_identity(self):
        from kungfu_tpu.parallel.zero import reshard_plan

        for (o, r, s, ln) in reshard_plan(64, 4, 4):
            assert o == r

    def test_invalid_world_sizes(self):
        from kungfu_tpu.parallel.zero import reshard_plan

        with pytest.raises(ValueError):
            reshard_plan(10, 0, 2)
        with pytest.raises(ValueError):
            reshard_plan(10, 2, 0)


class TestZeroReshardP2P:
    def test_single_controller_matches_zero1_reshard(self):
        """The leaderless segment-exchange re-carve (numpy replay of the
        wire plan) is bitwise identical to the direct re-placement."""
        from kungfu_tpu.parallel.zero import zero1_reshard, zero_reshard_p2p

        devs = jax.devices()
        c8 = Communicator(devices=devs[:8], local_size=8, version=0)
        c4 = Communicator(devices=devs[:4], local_size=4, version=1)
        params, batch = _params(), _batch()
        step8, init8 = zero1_train_step(_loss_fn, optax.adam(1e-2), c8)
        p, o = params, init8(params)
        for _ in range(2):
            p, o, _ = step8(p, o, batch)
        want = zero1_reshard(o, p, c4)
        got = zero_reshard_p2p(o, p, c4)  # old_n inferred from sharding
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_grow_matches_direct(self):
        from kungfu_tpu.parallel.zero import zero1_reshard, zero_reshard_p2p

        devs = jax.devices()
        c4 = Communicator(devices=devs[:4], local_size=4, version=0)
        c8 = Communicator(devices=devs[:8], local_size=8, version=1)
        params, batch = _params(), _batch()
        step4, init4 = zero1_train_step(_loss_fn, optax.adam(1e-2), c4)
        p, o = params, init4(params)
        p, o, _ = step4(p, o, batch)
        want = zero1_reshard(o, p, c8)
        got = zero_reshard_p2p(o, p, c8, old_n=4)
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestOptStateGauge:
    def test_record_opt_state_gauge(self):
        """init_opt publishes the per-rank footprint as the
        kf_opt_state_bytes gauge (the kftop / /metrics memory column)."""
        from kungfu_tpu.monitor.registry import REGISTRY
        from kungfu_tpu.parallel.zero import (opt_state_bytes_per_device,
                                              zero_train_step)

        comm = _comm8()
        params = _params()
        z = zero_train_step(_loss_fn, optax.adam(1e-2), comm, stage=2)
        o = z.init_opt(params)
        want = opt_state_bytes_per_device(o)
        assert want > 0
        assert REGISTRY.gauge("kf_opt_state_bytes").value == want
