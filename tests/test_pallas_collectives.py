"""Pallas ICI ring collectives — the interpreter-path bitwise suite.

The contract (docs/pallas_collectives.md), pinned form by form:

* every kernel (uni/bidirectional reduce-scatter and all-gather, the
  1-chunk and padded-tail degenerate shapes, non-divisible world sizes)
  is **bitwise-identical** on the CPU interpreter path to the
  order-matched lax emulation — same hop schedule, same fold-operand
  order, so the float bits cannot differ;
* against the ``lax.psum_scatter`` / ``lax.all_gather`` reference:
  all-gather is pure data movement and pins bitwise unconditionally;
  reduce-scatter pins bitwise on order-exact data (ints, integer-valued
  floats) and allclose on arbitrary floats (the ring's reduction order
  is documented, not XLA's);
* the custom-vjp pair: grad through the all-gather IS the ring
  reduce-scatter of the cotangent (and vice versa), impl-bitwise;
* the ``pallas_ring`` schedule plumbs through ``reduce_scatter_flat`` /
  ``all_gather_flat`` (bucketing bitwise-invariant, ZeRO geometry
  byte-identical), the eager ``Communicator`` per-bucket table, the
  ZeRO-2/3 step, ring attention's gathered-K/V path, and the sharded
  trainer's gradient sync.

This file is the ``make pallas-check`` gate (scripts/check.sh).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from kungfu_tpu.ops.pallas.collectives import (
    ring_all_gather,
    ring_all_reduce,
    ring_reduce_scatter,
    ring_wire_bytes,
)
from jax import shard_map

N_DEV = 8


def _world(n, fn, x, out_specs=None):
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("x",))
    f = shard_map(fn, mesh=mesh, in_specs=(P("x"),),
                  out_specs=out_specs if out_specs is not None else P("x"),
                  check_vma=False)
    return np.asarray(jax.jit(f)(x))


# world sizes: even rings, odd/non-divisible rings, the 2-ring edge
WORLDS = (2, 3, 5, 8)
# chunk shapes: a 2-band chunk where the bidirectional row split really
# engages (f32 needs >= 16 rows, i.e. chunk > 1024 — anything shorter
# falls back to unidirectional), a full single-tile chunk, a ragged
# (padded-tail) chunk, and the 1-chunk degenerate (smaller than one
# [8, 128] tile)
CHUNKS = (2048, 1024, 1000, 40)


def test_band_split_engages_in_this_suite():
    """Guard the guard: _band_rows must actually split at least one
    CHUNKS entry, or every ``bidi=True`` parametrization silently tests
    the unidirectional fallback twice (the exact gap a review caught:
    chunk 1024 is 8 f32 rows — below the 2-sublane-tile threshold)."""
    from kungfu_tpu.ops.pallas.collectives import _band_rows, _tile_rows

    assert _band_rows(8, np.float32) == 0        # uni fallback
    assert _band_rows(16, np.float32) == 8       # 8/8 split
    assert _band_rows(24, np.float32) == 16      # 16/8 split
    split = [c for c in CHUNKS
             if _band_rows(_tile_rows(c, np.float32), np.float32) > 0]
    assert split, "no CHUNKS entry engages the bidirectional band split"


class TestReduceScatterBitwise:
    @pytest.mark.parametrize("n", WORLDS)
    @pytest.mark.parametrize("chunk", CHUNKS)
    @pytest.mark.parametrize("bidi", [False, True])
    def test_kernel_bitwise_vs_emulation_and_close_vs_lax(
            self, n, chunk, bidi):
        rng = np.random.default_rng(n * 7919 + chunk + bidi)
        x = rng.standard_normal((n, n * chunk)).astype(np.float32)

        def rs(impl):
            body = lambda row: ring_reduce_scatter(
                row[0], "x", bidirectional=bidi, impl=impl)[None]
            return _world(n, body, jnp.asarray(x)).reshape(n, chunk)

        kern, emul = rs("pallas"), rs("lax")
        assert kern.tobytes() == emul.tobytes(), (
            f"kernel != emulation (n={n} chunk={chunk} bidi={bidi})")
        # the lax reference: psum_scatter of the same mesh-major buffer
        def ref_body(row):
            return jax.lax.psum_scatter(
                row[0], "x", scatter_dimension=0, tiled=True)[None]

        ref = _world(n, ref_body, jnp.asarray(x)).reshape(n, chunk)
        np.testing.assert_allclose(kern, ref, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("n", (3, 8))
    @pytest.mark.parametrize("bidi", [False, True])
    @pytest.mark.parametrize("dtype", [np.float32, np.int32])
    def test_kernel_bitwise_vs_psum_scatter_on_exact_data(
            self, n, bidi, dtype):
        """Order-exact data (int32, and integer-valued f32 whose sums
        are exactly representable): EVERY reduction order produces the
        same bits, so the kernel pins bitwise against the
        lax.psum_scatter reference itself."""
        chunk = 200
        rng = np.random.default_rng(11 + n)
        x = rng.integers(-1000, 1000, (n, n * chunk)).astype(dtype)

        def rs(row):
            return ring_reduce_scatter(
                row[0], "x", bidirectional=bidi, impl="pallas")[None]

        def ref(row):
            return jax.lax.psum_scatter(
                row[0], "x", scatter_dimension=0, tiled=True)[None]

        got = _world(n, rs, jnp.asarray(x))
        want = _world(n, ref, jnp.asarray(x))
        assert got.tobytes() == want.tobytes()

    @pytest.mark.parametrize("chunk,bidi", [
        (400, False),
        # bf16 sublane is 16 rows: the band split needs >= 32 rows,
        # i.e. chunk > 3968 — 4096 really exercises the bf16 bands
        (4096, True),
    ])
    def test_bf16_bitwise_vs_emulation(self, chunk, bidi):
        from kungfu_tpu.ops.pallas.collectives import (_band_rows,
                                                       _tile_rows)

        n = 4
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((n, n * chunk)), jnp.bfloat16)
        if bidi:
            assert _band_rows(_tile_rows(chunk, jnp.bfloat16),
                              jnp.bfloat16) > 0

        def rs(impl):
            body = lambda row: ring_reduce_scatter(
                row[0], "x", bidirectional=bidi, impl=impl)[None]
            return _world(n, body, x)

        assert rs("pallas").tobytes() == rs("lax").tobytes()

    def test_single_device_identity(self):
        x = jnp.arange(12, dtype=jnp.float32)
        got = _world(1, lambda row: ring_reduce_scatter(
            row[0], "x", impl="pallas")[None], x[None])
        np.testing.assert_array_equal(got[0], np.asarray(x))

    def test_rejects_non_divisible_buffer(self):
        with pytest.raises(ValueError, match="flat"):
            _world(2, lambda row: ring_reduce_scatter(
                row[0], "x", impl="lax")[None],
                jnp.ones((2, 7), jnp.float32))


class TestAllGatherBitwise:
    @pytest.mark.parametrize("n", WORLDS)
    @pytest.mark.parametrize("chunk", CHUNKS)
    @pytest.mark.parametrize("bidi", [False, True])
    def test_kernel_bitwise_vs_emulation_and_lax(self, n, chunk, bidi):
        """Gathering is pure movement: kernel == emulation == the
        lax.all_gather reference, all bitwise."""
        rng = np.random.default_rng(n * 131 + chunk + bidi)
        shards = rng.standard_normal((n, chunk)).astype(np.float32)

        def ag(impl):
            body = lambda s: ring_all_gather(
                s[0], "x", bidirectional=bidi, impl=impl)[None]
            return _world(n, body, jnp.asarray(shards))

        def ref(s):
            return jax.lax.all_gather(s[0], "x", axis=0, tiled=True)[None]

        kern, emul = ag("pallas"), ag("lax")
        want = _world(n, ref, jnp.asarray(shards))
        assert kern.tobytes() == emul.tobytes()
        assert kern.tobytes() == want.tobytes()

    def test_int32_and_single_device(self):
        n, chunk = 3, 70
        x = np.arange(n * chunk, dtype=np.int32).reshape(n, chunk)
        got = _world(n, lambda s: ring_all_gather(
            s[0], "x", impl="pallas")[None], jnp.asarray(x))
        assert got.reshape(n, n * chunk).tobytes() == np.tile(
            x.reshape(-1), (n, 1)).tobytes()
        y = jnp.arange(5, dtype=jnp.float32)
        got1 = _world(1, lambda s: ring_all_gather(
            s[0], "x", impl="pallas")[None], y[None])
        np.testing.assert_array_equal(got1[0], np.asarray(y))


class TestVjpPair:
    """The custom-vjp contract: gather's backward IS the ring
    reduce-scatter (ZeRO-3's transpose invariant), scatter's backward is
    the gather — and the kernel/emulation pair agrees bitwise on
    gradients too."""

    @pytest.mark.parametrize("bidi", [False, True])
    def test_gather_grad_is_reduce_scatter(self, bidi):
        n, chunk = 4, 300
        rng = np.random.default_rng(2)
        shards = rng.standard_normal((n, chunk)).astype(np.float32)
        w = rng.standard_normal((n * chunk,)).astype(np.float32)

        def grad_of(impl):
            def body(s):
                def loss(sh):
                    full = ring_all_gather(
                        sh, "x", bidirectional=bidi, impl=impl)
                    return jnp.sum(full * w) * jnp.ones((1,))

                return jax.grad(lambda sh: loss(sh)[0])(s[0])[None]

            return _world(n, body, jnp.asarray(shards))

        kern, emul = grad_of("pallas"), grad_of("lax")
        assert kern.tobytes() == emul.tobytes()
        # every device's cotangent is w → the shard grad is the
        # reduce-scatter of n identical copies: n * w[chunk r]
        np.testing.assert_allclose(
            kern.reshape(n, chunk), w.reshape(n, chunk) * n, rtol=1e-4)

    def test_scatter_grad_is_gather(self):
        n, chunk = 4, 128
        rng = np.random.default_rng(3)
        flat = rng.standard_normal((n, n * chunk)).astype(np.float32)

        def grad_of(impl):
            def body(s):
                def loss(f):
                    red = ring_reduce_scatter(f, "x", impl=impl)
                    return jnp.sum(red ** 2) * jnp.ones((1,))

                return jax.grad(lambda f: loss(f)[0])(s[0])[None]

            return _world(n, body, jnp.asarray(flat))

        kern, emul = grad_of("pallas"), grad_of("lax")
        assert kern.tobytes() == emul.tobytes()


class TestWireParity:
    """Traced-bytes parity: the emulation's explicit ppermute hops cost
    exactly what the lax reference primitives cost under the standard
    ring convention — the program the schedule claims is the program it
    moves."""

    def test_emulation_bytes_match_reference_costs(self):
        from kungfu_tpu.ops.schedules import traced_collective_bytes

        # chunk = one exact [8, 128] f32 tile: sub-tile chunks pad up to
        # tile granularity ON THE WIRE too (documented overhead; real
        # buckets are orders of magnitude above a tile)
        n, chunk = 8, 1024
        mesh = Mesh(np.asarray(jax.devices()[:n]), ("x",))

        def rs_emul(row):
            return ring_reduce_scatter(row[0], "x", impl="lax")[None]

        def ag_emul(s):
            return ring_all_gather(s[0], "x", impl="lax")[None]

        rs = traced_collective_bytes(
            shard_map(rs_emul, mesh=mesh, in_specs=(P("x"),),
                      out_specs=P("x")),
            jnp.ones((n, n * chunk), jnp.float32), axis_sizes={"x": n})
        ag = traced_collective_bytes(
            shard_map(ag_emul, mesh=mesh, in_specs=(P("x"),),
                      out_specs=P("x")),
            jnp.ones((n, chunk), jnp.float32), axis_sizes={"x": n})
        buf = n * chunk * 4
        assert rs == {"ppermute": pytest.approx(
            ring_wire_bytes(buf, n, "reduce_scatter"))}
        assert ag == {"ppermute": pytest.approx(
            ring_wire_bytes(chunk * 4, n, "all_gather"))}

    def test_analytic_matches_schedule_table(self):
        from kungfu_tpu.ops.schedules import _COLLECTIVE_COST

        for n in (2, 3, 8):
            s = 4096.0
            assert ring_wire_bytes(s, n, "reduce_scatter") == (
                _COLLECTIVE_COST["reduce_scatter"](s, n))
            assert ring_wire_bytes(s, n, "all_gather") == (
                _COLLECTIVE_COST["all_gather"](s, n))
            assert ring_wire_bytes(s, n, "all_reduce") == (
                _COLLECTIVE_COST["psum"](s, n))
        with pytest.raises(ValueError, match="unknown kind"):
            ring_wire_bytes(1, 2, "gossip")


class TestScheduleIntegration:
    """pallas_ring as a first-class member of the schedule layer."""

    def test_registered_in_allreduce_schedules(self):
        from kungfu_tpu.ops.schedules import (ALLREDUCE_SCHEDULES,
                                              FLAT_SCHEDULES)

        assert "pallas_ring" in ALLREDUCE_SCHEDULES
        assert FLAT_SCHEDULES == ("lax", "pallas_ring")

    @pytest.mark.parametrize("op", ["sum", "mean", "min", "max"])
    def test_all_reduce_scheduled_matches_psum(self, op):
        from kungfu_tpu.ops.schedules import all_reduce_scheduled

        rng = np.random.default_rng(4)
        x = rng.standard_normal((N_DEV, 37)).astype(np.float32)

        def body(s):
            return all_reduce_scheduled(s, "x", op=op,
                                        schedule="pallas_ring")

        got = _world(N_DEV, body, jnp.asarray(x))
        ref = {"sum": np.sum, "mean": np.mean, "min": np.min,
               "max": np.max}[op](x.astype(np.float64), axis=0)
        np.testing.assert_allclose(got, np.broadcast_to(ref, x.shape),
                                   rtol=1e-5, atol=1e-5)

    def test_hierarchical_tuple_axes(self):
        """(host, local) axis tuples: inner folds by psum, the ring
        kernels run the cross-host stage — same contract as ring/two_stage."""
        from kungfu_tpu.ops.schedules import all_reduce_scheduled

        mesh = Mesh(np.asarray(jax.devices()[:N_DEV]).reshape(2, 4),
                    ("h", "l"))
        rng = np.random.default_rng(5)
        x = rng.standard_normal((N_DEV, 21)).astype(np.float32)

        def body(s):
            return all_reduce_scheduled(s, ("h", "l"), op="mean",
                                        schedule="pallas_ring")

        f = shard_map(body, mesh=mesh, in_specs=(P(("h", "l")),),
                      out_specs=P(("h", "l")))
        got = np.asarray(jax.jit(f)(jnp.asarray(x)))
        np.testing.assert_allclose(
            got, np.broadcast_to(x.mean(0), x.shape), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("widths", [None, [5], [2, 3], [4, 1], [1] * 5])
    def test_flat_bucketing_bitwise_invariant(self, widths):
        """Bucketing is pure program structure under pallas_ring too:
        any bucket layout produces the same bits (the ZeRO invariant)."""
        from kungfu_tpu.ops.schedules import reduce_scatter_flat

        n, chunk = 8, 5
        rng = np.random.default_rng(6)
        x = rng.standard_normal((n, n * chunk)).astype(np.float32)

        def run(w):
            body = lambda row: reduce_scatter_flat(
                row[0], ["x"], chunk, w, schedule="pallas_ring")[None]
            return _world(n, body, jnp.asarray(x))

        assert run(widths).tobytes() == run(None).tobytes()

    def test_flat_gather_bitwise_vs_lax_and_roundtrip(self):
        from kungfu_tpu.ops.schedules import (all_gather_flat,
                                              reduce_scatter_flat)

        n, chunk = 8, 6
        rng = np.random.default_rng(7)
        x = rng.standard_normal((n, n * chunk)).astype(np.float32)

        def round_trip(schedule):
            def body(row):
                shard = reduce_scatter_flat(row[0], ["x"], chunk, [4, 2],
                                            schedule=schedule)
                return all_gather_flat(shard, ["x"], [4, 2],
                                       schedule=schedule)[None]

            return _world(n, body, jnp.asarray(x))

        got = round_trip("pallas_ring")
        np.testing.assert_allclose(
            got.reshape(n, n * chunk),
            np.broadcast_to(x.sum(0), (n, n * chunk)), rtol=1e-4)
        # gather alone is movement: bitwise across schedules
        shards = rng.standard_normal((n, chunk)).astype(np.float32)

        def gather(schedule):
            body = lambda s: all_gather_flat(
                s[0], ["x"], schedule=schedule)[None]
            return _world(n, body, jnp.asarray(shards))

        assert gather("pallas_ring").tobytes() == gather("lax").tobytes()

    def test_unknown_schedule_rejected(self):
        from kungfu_tpu.ops.schedules import (all_gather_flat,
                                              reduce_scatter_flat)

        with pytest.raises(ValueError, match="unknown flat schedule"):
            reduce_scatter_flat(jnp.ones(8), ["x"], 2, schedule="bogus")
        with pytest.raises(ValueError, match="unknown flat schedule"):
            all_gather_flat(jnp.ones(8), ["x"], schedule="bogus")


class TestCommunicatorIntegration:
    """The eager device plane: pallas_ring installed per payload bucket
    routes the stacked collectives through the ring schedules."""

    def _comm(self):
        from kungfu_tpu.comm.device import Communicator

        return Communicator(devices=jax.devices()[:4], local_size=4)

    def test_all_reduce_under_pallas_ring_strategy(self):
        comm = self._comm()
        comm.set_strategy("pallas_ring")
        rng = np.random.default_rng(8)
        x = rng.standard_normal((4, 33)).astype(np.float32)
        for op in ("sum", "mean", "max"):
            got = np.asarray(comm.all_reduce(jnp.asarray(x), op=op))
            ref = {"sum": np.sum, "mean": np.mean, "max": np.max}[op](
                x.astype(np.float64), axis=0)
            np.testing.assert_allclose(
                got, np.broadcast_to(ref, x.shape), rtol=1e-5, atol=1e-5)

    def test_bucketed_scatter_gather_roundtrip(self):
        from kungfu_tpu.ops.schedules import size_bucket

        comm = self._comm()
        rng = np.random.default_rng(9)
        x = rng.standard_normal((4, 1000)).astype(np.float32)
        bucket = size_bucket(x[0].nbytes)
        comm.set_bucket_strategy(bucket, "pallas_ring")
        red = comm.reduce_scatter(jnp.asarray(x))
        back = comm.all_gather_shard(red)
        full = np.asarray(back)[0]
        np.testing.assert_allclose(full, x.sum(0), rtol=1e-4, atol=1e-5)
        # the compiled program is cached under the schedule key: clearing
        # the override swaps back to a DIFFERENT cached program
        n_fns = len(comm._fns)
        comm.set_bucket_strategy(bucket, None)
        comm.reduce_scatter(jnp.asarray(x))
        assert len(comm._fns) == n_fns + 1


class TestZeroIntegration:
    """ZeRO-2/3 bucket loops riding schedule="pallas_ring": same losses
    and params as the lax schedule (allclose — the ring's documented
    reduction order), same shard geometry (bitwise)."""

    def _setup(self, stage, schedule):
        import optax

        from kungfu_tpu.comm.device import Communicator
        from kungfu_tpu.parallel.zero import zero_train_step

        comm = Communicator(devices=jax.devices()[:4], local_size=4)

        def loss_fn(params, batch):
            x, y = batch
            pred = x @ params["w"] + params["b"]
            return jnp.mean((pred - y) ** 2)

        rng = np.random.RandomState(0)
        params = {"w": jnp.asarray(rng.randn(5, 3), jnp.float32),
                  "b": jnp.asarray(rng.randn(3), jnp.float32)}
        batch = (jnp.asarray(rng.randn(8, 5), jnp.float32),
                 jnp.asarray(rng.randn(8, 3), jnp.float32))
        step = zero_train_step(loss_fn, optax.sgd(0.1), comm, stage=stage,
                               bucket_bytes=16, schedule=schedule)
        return step, params, batch

    @pytest.mark.parametrize("stage", [2, 3])
    def test_stage_matches_lax_schedule(self, stage):
        outs = {}
        for schedule in ("lax", "pallas_ring"):
            step, params, batch = self._setup(stage, schedule)
            if stage == 3:
                p = step.init_params(params)
            else:
                p = params
            opt = step.init_opt(params)
            for _ in range(2):
                p, opt, loss = step.step(p, opt, batch)
            if stage == 3:
                p = step.gather_params(p)
            outs[schedule] = (jax.tree_util.tree_map(np.asarray, p),
                              float(loss))
        (p_lax, l_lax), (p_pal, l_pal) = outs["lax"], outs["pallas_ring"]
        np.testing.assert_allclose(l_pal, l_lax, rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(p_pal),
                        jax.tree_util.tree_leaves(p_lax)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_bad_schedule_rejected(self):
        import optax

        from kungfu_tpu.comm.device import Communicator
        from kungfu_tpu.parallel.zero import zero_train_step

        comm = Communicator(devices=jax.devices()[:4], local_size=4)
        with pytest.raises(ValueError, match="unknown schedule"):
            zero_train_step(lambda p, b: 0.0, optax.sgd(0.1), comm,
                            schedule="bogus")


class TestRingAttentionIntegration:
    """ring_attention(kv_gather=...): one ring all-gather of K/V instead
    of n ppermute rounds — exact vs the rotation path."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("schedule", ["lax", "pallas_ring"])
    def test_gathered_matches_rotation(self, causal, schedule):
        from kungfu_tpu.parallel.ring import ring_attention

        n_sp, B, H, S, D = 4, 1, 2, 8, 16
        rng = np.random.default_rng(10)
        q, k, v = (jnp.asarray(
            rng.standard_normal((B, H, n_sp * S, D)), jnp.float32)
            for _ in range(3))
        mesh = Mesh(np.asarray(jax.devices()[:n_sp]), ("sp",))

        def run(kv_gather):
            def body(q_, k_, v_):
                return ring_attention(q_, k_, v_, causal=causal,
                                      axis="sp", block_impl="einsum",
                                      kv_gather=kv_gather)

            f = shard_map(body, mesh=mesh,
                          in_specs=(P(None, None, "sp", None),) * 3,
                          out_specs=P(None, None, "sp", None))
            return np.asarray(jax.jit(f)(q, k, v))

        np.testing.assert_allclose(run(schedule), run(None),
                                   rtol=2e-5, atol=2e-5)

    def test_gathered_path_differentiable(self):
        """dK/dV flow back through the gather's transpose (the ring
        reduce-scatter custom vjp) and match the rotation path."""
        from kungfu_tpu.parallel.ring import ring_attention

        n_sp, B, H, S, D = 2, 1, 1, 4, 8
        rng = np.random.default_rng(11)
        q, k, v = (jnp.asarray(
            rng.standard_normal((B, H, n_sp * S, D)), jnp.float32)
            for _ in range(3))
        mesh = Mesh(np.asarray(jax.devices()[:n_sp]), ("sp",))

        def grads(kv_gather):
            def body(q_, k_, v_):
                def loss(kk, vv):
                    out = ring_attention(q_, kk, vv, causal=True,
                                         axis="sp", block_impl="einsum",
                                         kv_gather=kv_gather)
                    return jnp.sum(out ** 2) * jnp.ones((1,))

                g = jax.grad(lambda kk, vv: loss(kk, vv)[0],
                             argnums=(0, 1))(k_, v_)
                return g

            f = shard_map(body, mesh=mesh,
                          in_specs=(P(None, None, "sp", None),) * 3,
                          out_specs=(P(None, None, "sp", None),) * 2)
            return [np.asarray(t) for t in jax.jit(f)(q, k, v)]

        for a, b in zip(grads("pallas_ring"), grads(None)):
            np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-5)

    def test_bad_kv_gather_rejected(self):
        from kungfu_tpu.parallel.ring import ring_attention

        with pytest.raises(ValueError, match="kv_gather"):
            ring_attention(jnp.ones((1, 1, 4, 8)), jnp.ones((1, 1, 4, 8)),
                           jnp.ones((1, 1, 4, 8)), kv_gather="bogus")


class TestLaunchKnob:
    def test_env_selects_default_impl(self, monkeypatch):
        from kungfu_tpu.ops.pallas import collectives as C

        monkeypatch.setenv("KF_PALLAS_COLLECTIVES", "lax")
        C.ENV.reload()
        assert C._use_pallas(None) is False
        monkeypatch.setenv("KF_PALLAS_COLLECTIVES", "pallas")
        C.ENV.reload()
        assert C._use_pallas(None) is True
        monkeypatch.setenv("KF_PALLAS_COLLECTIVES", "bogus")
        with pytest.raises(ValueError, match="KF_PALLAS_COLLECTIVES"):
            C.ENV.reload()
        monkeypatch.setenv("KF_PALLAS_COLLECTIVES", "auto")
        C.ENV.reload()
        assert C._use_pallas(None) == (jax.default_backend() == "tpu")

    def test_explicit_impl_overrides_env(self):
        from kungfu_tpu.ops.pallas import collectives as C

        assert C._use_pallas("pallas") is True
        assert C._use_pallas("lax") is False
        with pytest.raises(ValueError, match="impl"):
            C._use_pallas("bogus")


class TestShardedTrainerSchedule:
    """The sharded trainer (ring attention + fused LM head inside)
    accepts schedule="pallas_ring" for its gradient sync — the last
    consumer named by ROADMAP item 2."""

    def test_trainer_accepts_pallas_ring(self):
        from kungfu_tpu.models.transformer import TransformerConfig
        from kungfu_tpu.parallel.train import MeshPlan, ShardedTrainer

        cfg = TransformerConfig(vocab_size=64, d_model=16, n_layers=1,
                                n_heads=2, d_ff=32, max_seq=8,
                                dtype="float32")
        trainer = ShardedTrainer(cfg, MeshPlan(dp=2, pp=1, sp=1, tp=1),
                                 schedule="pallas_ring")
        assert trainer.schedule == "pallas_ring"
        with pytest.raises(ValueError, match="unknown schedule"):
            ShardedTrainer(cfg, MeshPlan(dp=2, pp=1, sp=1, tp=1),
                           schedule="bogus")


class TestCompiledOnTpu:
    """The COMPILED kernels (``interpret=False``), the program a step
    would run, against what the interpreter suite above pins.  A
    Mosaic-only fault (a slot race, semaphore drift) cannot show in
    interpret mode; it must fail here.  Skipped off the chip, and
    tests/conftest.py holds every ordinary run to the CPU; on a host
    with chips::

        python -m pytest --noconftest tests/test_pallas_collectives.py \\
            -k TestCompiledOnTpu
    """

    @pytest.fixture(autouse=True)
    def _needs_chips(self):
        if jax.default_backend() != "tpu" or jax.device_count() < 2:
            pytest.skip("the compiled ring kernels run over ICI: "
                        "needs >= 2 TPU chips")

    # 2180 f32 elements -> 24 padded rows: a ragged tail inside the tile
    # AND tall enough that the bidirectional band split engages
    CHUNK = 2180

    @pytest.mark.parametrize("bidi", [False, True])
    def test_reduce_scatter_close_vs_emulation_exact_bitwise(self, bidi):
        n = jax.device_count()
        rng = np.random.default_rng(int(bidi))
        x = rng.standard_normal((n, n * self.CHUNK)).astype(np.float32)
        xi = rng.integers(-1000, 1000,
                          (n, n * self.CHUNK)).astype(np.float32)

        def rs(impl, interpret):
            return lambda row: ring_reduce_scatter(
                row[0], "x", bidirectional=bidi, impl=impl,
                interpret=interpret)[None]

        def ref(row):
            return jax.lax.psum_scatter(
                row[0], "x", scatter_dimension=0, tiled=True)[None]

        np.testing.assert_allclose(
            _world(n, rs("pallas", False), jnp.asarray(x)),
            _world(n, rs("lax", None), jnp.asarray(x)),
            rtol=1e-5, atol=1e-5)
        # order-exact data: every reduction order gives the same bits
        assert (_world(n, rs("pallas", False), jnp.asarray(xi)).tobytes()
                == _world(n, ref, jnp.asarray(xi)).tobytes())

    @pytest.mark.parametrize("bidi", [False, True])
    def test_all_gather_bitwise_vs_lax(self, bidi):
        n = jax.device_count()
        rng = np.random.default_rng(2 + int(bidi))
        s = jnp.asarray(
            rng.standard_normal((n, self.CHUNK)).astype(np.float32))

        def ag(sh):
            return ring_all_gather(sh[0], "x", bidirectional=bidi,
                                   impl="pallas", interpret=False)[None]

        def ref(sh):
            return jax.lax.all_gather(sh[0], "x", axis=0, tiled=True)[None]

        assert _world(n, ag, s).tobytes() == _world(n, ref, s).tobytes()
