"""Pallas kernel tests (interpreter mode on the CPU test platform).

Numerical cross-check against the plain-XLA attention — the same
"pluggable impls compared against each other" strategy the reference
uses for its collectives (SURVEY §4).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.models.transformer import default_attention
from kungfu_tpu.ops.pallas import flash_attention, make_flash_attn


def _rand_qkv(b, h, s, d, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.normal(size=(b, h, s, d)), dtype) for _ in range(3)
    )


#: id -> (heads, S, D, causal, dtype, (block_q, block_k), VMEM budget or
#: None): one case for every branch ``tile_plan`` can take.  A small
#: budget sends a small S down the streamed branch (a hold of two tiles).
PLAN_CASES = {
    "causal": (4, 256, 32, True, jnp.float32, (None, None), None),
    "noncausal": (4, 256, 32, False, jnp.float32, (None, None), None),
    "cells_s1024_d64": (1, 1024, 64, True, jnp.float32, (None, None), None),
    "cells_s1024_d64_bf16": (1, 1024, 64, True, jnp.bfloat16, (None, None),
                             None),
    "s2048_d128_streamed_f32": (1, 2048, 128, True, jnp.float32,
                                (None, None), None),
    "s2048_d128_resident_bf16": (1, 2048, 128, True, jnp.bfloat16,
                                 (None, None), None),
    "s1152_off_tile": (1, 1152, 64, True, jnp.float32, (None, None), None),
    "s300_off_tile": (2, 300, 32, True, jnp.float32, (None, None), None),
    "s300_noncausal": (2, 300, 32, False, jnp.float32, (None, None), None),
    "s300_wide_k": (1, 300, 32, True, jnp.float32, (64, 128), None),
    "s300_wide_q": (1, 300, 32, False, jnp.float32, (128, 64), None),
    "streamed": (2, 256, 32, True, jnp.float32, (32, 32), 64 * 7424),
    "streamed_off_tile": (2, 250, 32, True, jnp.float32, (32, 64),
                          128 * 7424),
    "streamed_noncausal": (2, 250, 32, False, jnp.float32, (64, 32),
                           64 * 7424),
}
PLAN_PATHS = {"s2048_d128_streamed_f32": "streamed", "streamed": "streamed",
              "streamed_off_tile": "streamed",
              "streamed_noncausal": "streamed"}


def _plan_case(name, monkeypatch):
    """(q, k, v, causal, blocks, tolerance scale) of a case, with the
    VMEM budget it asks for in place and its branch checked."""
    from kungfu_tpu.ops.pallas import attention as A

    h, s, d, causal, dtype, blocks, budget = PLAN_CASES[name]
    if budget is not None:
        monkeypatch.setattr(A, "VMEM_BUDGET_BYTES", budget)
    plan = A.tile_plan(s, d, dtype, causal, *blocks)
    assert plan.path == PLAN_PATHS.get(name, "resident"), plan
    q, k, v = _rand_qkv(1, h, s, d, dtype, seed=s + d)
    return q, k, v, causal, blocks, (1 if dtype == jnp.float32 else 2e3)


def _f32(*xs):
    return tuple(x.astype(jnp.float32) for x in xs)


class TestFlashForward:
    @pytest.mark.parametrize("case", PLAN_CASES)
    def test_matches_xla_attention(self, monkeypatch, case):
        q, k, v, causal, (bq, bk), tol = _plan_case(case, monkeypatch)
        ref = default_attention(*_f32(q, k, v), causal)
        got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(ref),
                                   np.asarray(got.astype(jnp.float32)),
                                   atol=2e-5 * tol)

    def test_ragged_seq_len_padding(self):
        # S not a multiple of the block sizes exercises the tail mask
        q, k, v = _rand_qkv(1, 2, 200, 32)
        ref = default_attention(q, k, v, True)
        got = flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=2e-5)

    def test_small_blocks(self):
        q, k, v = _rand_qkv(1, 1, 128, 16)
        ref = default_attention(q, k, v, True)
        got = flash_attention(
            q, k, v, causal=True, block_q=32, block_k=64, interpret=True
        )
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=2e-5)

    def test_three_dim_input(self):
        q, k, v = _rand_qkv(1, 3, 128, 16)
        got3 = flash_attention(
            q.reshape(3, 128, 16), k.reshape(3, 128, 16), v.reshape(3, 128, 16),
            causal=True, interpret=True,
        )
        got4 = flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got3), np.asarray(got4).reshape(3, 128, 16), atol=1e-6
        )


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_xla_attention(self, causal):
        q, k, v = _rand_qkv(1, 2, 160, 32, seed=1)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(default_attention(q, k, v, causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, err_msg=f"d{name}"
            )


class TestFlashBackwardPallasKernels:
    """The round-3 Pallas backward kernels (dQ + dK/dV), forced on via
    KF_PALLAS_BWD=pallas and run in interpret mode, cross-checked against
    plain-XLA autodiff AND the blocked-jnp reference backward."""

    @pytest.mark.parametrize("case", PLAN_CASES)
    def test_kernel_grads_match_xla(self, monkeypatch, case):
        monkeypatch.setenv("KF_PALLAS_BWD", "pallas")
        q, k, v, causal, (bq, bk), tol = _plan_case(case, monkeypatch)
        w = jnp.asarray(np.random.default_rng(9).normal(size=q.shape),
                        jnp.float32)

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal, block_q=bq,
                                block_k=bk, interpret=True)
            return jnp.sum(o.astype(jnp.float32) * w)

        def loss_ref(q, k, v):
            return jnp.sum(default_attention(q, k, v, causal) * w)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(*_f32(q, k, v))
        for name, a, b in zip("qkv", gf, gr):
            np.testing.assert_allclose(
                np.asarray(a.astype(jnp.float32)), np.asarray(b),
                atol=2e-5 * tol, err_msg=f"d{name}"
            )

    @pytest.mark.parametrize("case", ["causal", "noncausal", "s300_off_tile",
                                      "streamed_off_tile",
                                      "streamed_noncausal"])
    @pytest.mark.parametrize("bwd", ["pallas", "blocked"])
    def test_with_lse_grads_match_xla(self, monkeypatch, case, bwd):
        """``flash_attention_with_lse`` under a loss that reads both
        outputs: the lse cotangent is not zero (ring attention's merge)."""
        from kungfu_tpu.ops.pallas import flash_attention_with_lse

        monkeypatch.setenv("KF_PALLAS_BWD", bwd)
        q, k, v, causal, (bq, bk), _ = _plan_case(case, monkeypatch)
        q, k, v = (t[0] for t in (q, k, v))            # [BH, S, D]
        rng = np.random.default_rng(11)
        w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
        u = jnp.asarray(rng.normal(size=q.shape[:2]), jnp.float32)

        def loss_flash(q, k, v):
            o, lse = flash_attention_with_lse(
                q, k, v, causal=causal, block_q=bq, block_k=bk,
                interpret=True)
            return jnp.sum(o * w) + jnp.sum(lse * u)

        def loss_ref(q, k, v):
            sc = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(q.shape[-1])
            if causal:
                n = q.shape[1]
                sc = jnp.where(jnp.tril(jnp.ones((n, n), bool)), sc, -1e30)
            lse = jax.scipy.special.logsumexp(sc, axis=-1)
            o = jnp.einsum("bqk,bkd->bqd", jnp.exp(sc - lse[..., None]), v)
            return jnp.sum(o * w) + jnp.sum(lse * u)

        np.testing.assert_allclose(loss_flash(q, k, v), loss_ref(q, k, v),
                                   rtol=1e-5)
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, err_msg=f"d{name}")

    def test_kernel_matches_blocked_jnp(self, monkeypatch):
        """Bit-level-ish agreement between the two backward impls on the
        same saved (out, lse) — isolates the kernels from fwd noise,
        including the ragged-tail padding path (S=200 vs 128-blocks)."""
        from kungfu_tpu.ops.pallas.attention import (
            _bwd_blocked, _bwd_pallas, _fwd_call, tile_plan,
        )

        rng = np.random.default_rng(7)
        bh, s, d = 2, 200, 32
        q, k, v, do = (
            jnp.asarray(rng.normal(size=(bh, s, d)), jnp.float32)
            for _ in range(4)
        )
        plan = tile_plan(s, d, q.dtype, True, 128, 128)
        out, lse = _fwd_call(q, k, v, True, plan, True)
        ref = _bwd_blocked(q, k, v, out, lse, do, True, 128)
        got = _bwd_pallas(q, k, v, out, lse, do, True, plan, True)
        for name, a, b in zip(("dq", "dk", "dv"), got, ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4, err_msg=name
            )

    def test_kernel_small_blocks_noncausal(self, monkeypatch):
        monkeypatch.setenv("KF_PALLAS_BWD", "pallas")
        q, k, v = _rand_qkv(1, 1, 96, 16, seed=5)

        def loss(q, k, v):
            return jnp.sum(
                flash_attention(
                    q, k, v, causal=False, block_q=32, block_k=32,
                    interpret=True,
                )
            )

        def loss_ref(q, k, v):
            return jnp.sum(default_attention(q, k, v, False))

        gf = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, err_msg=f"d{name}"
            )


class TestTransformerIntegration:
    def test_flash_as_attn_fn(self):
        from kungfu_tpu.models.transformer import Transformer, TransformerConfig

        # f32 activations: compares the attention math itself; in bf16 the
        # two impls' (equally valid) rounding diverges through the layers
        cfg = TransformerConfig(
            vocab_size=128, d_model=64, n_layers=2, n_heads=2, d_ff=128,
            max_seq=64, causal=True, dtype="float32",
        )
        model = Transformer(cfg)
        params = model.init(jax.random.PRNGKey(0))
        ids = jnp.asarray(
            np.random.default_rng(0).integers(0, 128, size=(2, 64)), jnp.int32
        )
        ref = jax.jit(model.apply)(params, ids)
        got = jax.jit(functools.partial(
            model.apply, attn_fn=make_flash_attn()))(params, ids)
        np.testing.assert_allclose(
            np.asarray(ref), np.asarray(got), atol=2e-3
        )


class TestFusedCrossEntropy:
    def _data(self, b=2, s=100, v=1000, seed=0):
        rng = np.random.default_rng(seed)
        logits = jnp.asarray(rng.normal(size=(b, s, v)) * 3, jnp.float32)
        targets = jnp.asarray(rng.integers(0, v, size=(b, s)), jnp.int32)
        return logits, targets

    def _ref(self, logits, targets):
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, targets[..., None], -1).squeeze(-1)

    def test_matches_log_softmax(self):
        from kungfu_tpu.ops.pallas import softmax_cross_entropy

        logits, targets = self._data()
        got = softmax_cross_entropy(logits, targets, interpret=True)
        np.testing.assert_allclose(
            np.asarray(self._ref(logits, targets)), np.asarray(got), atol=1e-4
        )

    def test_grads_match(self):
        from kungfu_tpu.ops.pallas import softmax_cross_entropy

        logits, targets = self._data(b=1, s=64, v=700, seed=1)
        gk = jax.grad(lambda x: jnp.mean(softmax_cross_entropy(x, targets, interpret=True)))(logits)
        gr = jax.grad(lambda x: jnp.mean(self._ref(x, targets)))(logits)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=1e-6)

    def test_unmasked_fast_path(self, monkeypatch):
        """block_v dividing the vocab takes the masked=False branch — the
        production LM-head shape (V=32768, block_v=2048) and the path the
        other tests' odd vocabs never reach.  KF_PALLAS_BWD=pallas forces
        the backward KERNEL (not the blocked-jnp fallback) so its
        masked=False branch is covered too."""
        from kungfu_tpu.ops.pallas import softmax_cross_entropy

        monkeypatch.setenv("KF_PALLAS_BWD", "pallas")
        logits, targets = self._data(b=1, s=64, v=512, seed=2)
        got = softmax_cross_entropy(
            logits, targets, block_n=32, block_v=256, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(self._ref(logits, targets)), np.asarray(got), atol=1e-4
        )
        gk = jax.grad(lambda x: jnp.mean(softmax_cross_entropy(
            x, targets, block_n=32, block_v=256, interpret=True)))(logits)
        gr = jax.grad(lambda x: jnp.mean(self._ref(x, targets)))(logits)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=1e-6)

    def test_bf16_logits(self):
        from kungfu_tpu.ops.pallas import softmax_cross_entropy

        logits, targets = self._data(v=512)
        got = softmax_cross_entropy(logits.astype(jnp.bfloat16), targets, interpret=True)
        ref = self._ref(logits.astype(jnp.bfloat16).astype(jnp.float32), targets)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=1e-3)

    def test_pallas_bwd_kernel_grads_match(self, monkeypatch):
        """The round-3 xent backward KERNEL (KF_PALLAS_BWD=pallas) matches
        XLA autodiff of the logsumexp formulation, incl. ragged vocab."""
        from kungfu_tpu.ops.pallas import softmax_cross_entropy

        monkeypatch.setenv("KF_PALLAS_BWD", "pallas")
        rng = np.random.default_rng(11)
        logits = jnp.asarray(rng.normal(size=(96, 700)), jnp.float32)
        targets = jnp.asarray(rng.integers(0, 700, 96), jnp.int32)

        def loss_fused(x):
            return softmax_cross_entropy(x, targets, interpret=True).mean()

        def loss_ref(x):
            lse = jax.scipy.special.logsumexp(x, axis=-1)
            gold = jnp.take_along_axis(x, targets[:, None], axis=-1)[:, 0]
            return (lse - gold).mean()

        gf = jax.grad(loss_fused)(logits)
        gr = jax.grad(loss_ref)(logits)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=2e-5)

    def test_model_loss_fused_matches(self, monkeypatch):
        from kungfu_tpu.models.transformer import Transformer, TransformerConfig

        cfg = TransformerConfig(
            vocab_size=256, d_model=64, n_layers=1, n_heads=2, d_ff=128,
            max_seq=32, causal=True, dtype="float32",
        )
        model = Transformer(cfg)
        params = model.init(jax.random.PRNGKey(0))
        data = np.random.default_rng(0).integers(0, 256, size=(2, 33))
        batch = (jnp.asarray(data[:, :-1], jnp.int32), jnp.asarray(data[:, 1:], jnp.int32))
        from kungfu_tpu.ops.pallas.xent import XENT_ENV

        monkeypatch.setenv("KF_TPU_XENT", "xla")
        XENT_ENV.reload()
        ref = model.loss(params, batch)
        monkeypatch.setenv("KF_TPU_XENT", "fused")
        XENT_ENV.reload()
        got = model.loss(params, batch)
        np.testing.assert_allclose(float(ref), float(got), atol=1e-5)


class TestXentRouting:
    """Per-shape kernel-vs-XLA auto routing (round-3 VERDICT item 3:
    auto sent EVERY TPU caller to the kernel, including training shapes
    where XLA's fused backward is ~2x faster)."""

    def test_training_routes_by_memory_budget(self):
        from kungfu_tpu.ops.pallas.xent import _route_fused

        # the settled micro-bench shape (N=8192, V=32768, bf16): XLA's
        # residual estimate is ~1.5 GiB < budget -> XLA wins the train
        # path (it measured 2.3 vs 4.7 ms)
        assert _route_fused(8192, 32768, 2, training=True) is False
        # the batch-8 LM shape that OOMs the XLA variant -> kernel
        assert _route_fused(16384, 50304, 2, training=True) is True

    def test_eval_routes_by_streaming_scale(self):
        from kungfu_tpu.ops.pallas.xent import _route_fused

        # fwd-only: kernel measured ~2x at HBM scale
        assert _route_fused(8192, 32768, 2, training=False) is True
        # tiny logits: pallas call overhead loses, route XLA
        assert _route_fused(128, 1024, 4, training=False) is False

    def test_env_budget_override(self, monkeypatch):
        """The knobs are launch-set (read at import — the
        recompile-hazard hoist): env mutations take effect through
        ``XENT_ENV.reload()``, never at trace time."""
        from kungfu_tpu.ops.pallas.xent import XENT_ENV, _route_fused

        monkeypatch.setenv("KF_XENT_XLA_BUDGET_MB", "1")
        XENT_ENV.reload()
        assert _route_fused(1024, 1024, 2, training=True) is True
        monkeypatch.setenv("KF_XENT_XLA_BUDGET_MB", "1048576")
        # without a reload the mutation is invisible — launch-set for real
        assert _route_fused(1024, 1024, 2, training=True) is True
        XENT_ENV.reload()
        assert _route_fused(16384, 50304, 2, training=True) is False

    def test_forced_modes_bypass_routing(self, monkeypatch):
        """KF_TPU_XENT=fused/plain still win over the shape router, and
        both compute the same value."""
        import kungfu_tpu.ops.pallas.xent as X

        logits = jnp.asarray(
            np.random.default_rng(0).standard_normal((4, 64)), jnp.float32)
        targets = jnp.asarray([1, 2, 3, 4], jnp.int32)
        monkeypatch.setenv("KF_TPU_XENT", "plain")
        X.XENT_ENV.reload()
        ref = float(X.token_nll(logits, targets))
        monkeypatch.setenv("KF_TPU_XENT", "fused")
        X.XENT_ENV.reload()
        got = float(X.token_nll(logits, targets))
        np.testing.assert_allclose(ref, got, atol=1e-5)


class TestTilePlan:
    """``tile_plan``: the flash kernels' schedule from (S, D, dtype,
    causal) alone -- tile sizes, what a grid step holds, and how much of
    what it computes the mask keeps."""

    @staticmethod
    def _plan(s, d=64, dtype=jnp.bfloat16, causal=True, bq=None, bk=None):
        from kungfu_tpu.ops.pallas.attention import tile_plan

        return tile_plan(s, d, dtype, causal, bq, bk)

    @pytest.mark.parametrize("s,d,path,tile,hold", [
        (1024, 64, "resident", 128, 1024),      # the train cells' call
        (2048, 64, "resident", 256, 2048),      # chip_smoke's train leg
        (2048, 128, "resident", 256, 2048),     # the old defaults' shape
        (8192, 128, "streamed", 512, 2048),
    ])
    def test_long_seq_holds_what_fits(self, s, d, path, tile, hold):
        p = self._plan(s, d)
        assert (p.path, p.block_q, p.block_k, p.hold) == (
            path, tile, tile, hold)

    @pytest.mark.parametrize("s,s_pad", [(128, 128), (100, 128), (300, 384)])
    def test_short_seq_never_pads_a_whole_tile(self, s, s_pad):
        p = self._plan(s)
        assert (p.block_q, p.block_k, p.s_pad) == (128, 128, s_pad)

    @pytest.mark.parametrize("s,d,dtype", [
        (1152, 64, jnp.bfloat16), (1536, 64, jnp.bfloat16),
        (8320, 128, jnp.bfloat16), (5000, 128, jnp.float32),
        (2049, 128, jnp.float32)])
    def test_padding_allowance_caps_waste(self, s, d, dtype):
        """Resident or streamed, S is padded by less than a tile or by an
        eighth, whichever is more (1152 stays 1152: the old 1024-wide K
        block would have made it 2048)."""
        p = self._plan(s, d, dtype)
        assert p.s_pad % p.hold == 0 and p.hold % p.block_q == 0
        assert p.s_pad - s <= max(s // 8, p.block_q - 1)

    @pytest.mark.parametrize("bq,bk,want", [(32, 64, (32, 64)),
                                            (None, 64, (256, 64)),
                                            (96, None, (96, 256))])
    def test_explicit_blocks_pass_through(self, bq, bk, want):
        p = self._plan(2048, bq=bq, bk=bk)
        assert (p.block_q, p.block_k) == want
        assert p.s_pad % p.block_q == 0 and p.s_pad % p.block_k == 0

    @pytest.mark.parametrize("s,d,most", [(1024, 64, 1.15), (2048, 64, 1.25),
                                          (2048, 128, 1.25),
                                          (8192, 128, 1.25)])
    def test_computed_share_stays_near_the_triangle(self, s, d, most):
        """Score elements computed over the causal triangle's: 2.0 at
        S 1024 and 1.5 at S 2048 with the (256, 1024) blocks this
        replaces; not causal, every tile is needed."""
        assert 1.0 <= self._plan(s, d).computed_share <= most
        assert self._plan(s, d, causal=False).computed_share == 1.0

    @pytest.mark.parametrize("s,bq,bk", [(1024, None, None),
                                         (2048, None, None),
                                         (8192, None, None), (2048, 32, 64),
                                         (1024, 1024, 1024)])
    def test_runs_stay_inside_their_budget(self, s, bq, bk):
        """Tiles of a row are joined up to ``RUN_ELEMENTS`` scores a
        product; a tile larger than that stands alone."""
        from kungfu_tpu.ops.pallas.attention import RUN_ELEMENTS

        p = self._plan(s, bq=bq, bk=bk)
        assert p.wide >= 1
        assert (p.wide * p.block_q * p.block_k <= RUN_ELEMENTS
                or p.wide == 1)
        assert (p.wide + 1) * p.block_q * p.block_k > RUN_ELEMENTS

    def test_budget_decides_the_path(self, monkeypatch):
        from kungfu_tpu.ops.pallas import attention as A

        assert self._plan(1024).path == "resident"
        monkeypatch.setattr(A, "VMEM_BUDGET_BYTES", 2 << 20)
        p = self._plan(1024)
        assert (p.path, p.hold, p.block_q) == ("streamed", 256, 128)
        assert p.computed_share == self._plan(1024, bq=128).computed_share


class TestFusedLMHead:
    """lm_head.py — the LM-head matmuls fused into the xent fwd+bwd:
    loss and BOTH gradients must match the plain logits path."""

    def _ref(self, h, w, t):
        logits = (h.astype(jnp.float32) @ w.astype(jnp.float32))
        lse = jax.scipy.special.logsumexp(logits, -1)
        tl = jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        return lse - tl

    @pytest.mark.parametrize("shape", [
        (16, 32, 256),    # aligned
        (20, 48, 300),    # ragged N, D, V (pad paths in every dim)
        (8, 128, 1000),   # ragged V only
    ])
    def test_loss_and_grads_match_reference(self, shape):
        from kungfu_tpu.ops.pallas.lm_head import lm_head_nll

        n, d, v = shape
        rng = np.random.default_rng(1)
        h = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((d, v)) * 0.1, jnp.float32)
        t = jnp.asarray(rng.integers(0, v, n), jnp.int32)

        l_ref = self._ref(h, w, t)
        l_k = lm_head_nll(h, w, t, block_n=8, block_v=128)
        np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_ref),
                                   rtol=2e-5, atol=1e-6)

        g_ref = jax.grad(lambda h, w: jnp.mean(self._ref(h, w, t)),
                         argnums=(0, 1))(h, w)
        g_k = jax.grad(
            lambda h, w: jnp.mean(lm_head_nll(h, w, t, block_n=8,
                                              block_v=128)),
            argnums=(0, 1))(h, w)
        for a, b in zip(g_k, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    def test_bf16_inputs(self):
        from kungfu_tpu.ops.pallas.lm_head import lm_head_nll

        rng = np.random.default_rng(2)
        n, d, v = 16, 64, 384
        h = jnp.asarray(rng.standard_normal((n, d)), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((d, v)) * 0.1, jnp.bfloat16)
        t = jnp.asarray(rng.integers(0, v, n), jnp.int32)
        l_ref = self._ref(h, w, t)
        loss, grads = jax.value_and_grad(
            lambda h, w: jnp.mean(lm_head_nll(h, w, t, block_n=8,
                                              block_v=128)),
            argnums=(0, 1))(h, w)
        np.testing.assert_allclose(float(loss), float(jnp.mean(l_ref)),
                                   rtol=5e-3)
        assert grads[0].dtype == jnp.bfloat16
        assert grads[1].dtype == jnp.bfloat16
        g_ref = jax.grad(lambda h, w: jnp.mean(self._ref(h, w, t)),
                         argnums=(0, 1))(h, w)
        for a, b in zip(grads, g_ref):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=0.1, atol=5e-3)

    def test_leading_batch_dims(self):
        from kungfu_tpu.ops.pallas.lm_head import lm_head_nll

        rng = np.random.default_rng(3)
        b, s, d, v = 2, 10, 32, 200
        h = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((d, v)) * 0.1, jnp.float32)
        t = jnp.asarray(rng.integers(0, v, (b, s)), jnp.int32)
        out = lm_head_nll(h, w, t, block_n=8, block_v=128)
        assert out.shape == (b, s)
        ref = self._ref(h.reshape(-1, d), w, t.reshape(-1)).reshape(b, s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=1e-6)

    def test_model_hidden_path_matches_apply(self):
        """Transformer.hidden + lm_head_nll == token_nll over apply's
        logits — the bench contestant computes the same training loss."""
        from kungfu_tpu.models.transformer import (Transformer,
                                                   TransformerConfig)
        from kungfu_tpu.ops.pallas.lm_head import lm_head_nll

        cfg = TransformerConfig(vocab_size=128, d_model=32, n_layers=1,
                                n_heads=2, d_ff=64, max_seq=16,
                                dtype="float32")
        model = Transformer(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(4)
        ids = jnp.asarray(rng.integers(0, 128, (2, 16)), jnp.int32)
        tgt = jnp.asarray(rng.integers(0, 128, (2, 16)), jnp.int32)
        logits = jax.jit(model.apply)(params, ids)
        lse_ref = -jnp.take_along_axis(
            jax.nn.log_softmax(logits), tgt[..., None], -1).squeeze(-1)
        h = jax.jit(model.hidden)(params, ids)
        fused = lm_head_nll(h, params["head"]["w"], tgt, block_n=8,
                            block_v=128)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(lse_ref),
                                   rtol=2e-5, atol=1e-5)

    def test_model_loss_lm_head_switch(self, monkeypatch):
        """KF_TPU_LM_HEAD=fused routes Transformer.loss through the
        fused head; the value matches the plain path."""
        from kungfu_tpu.models.transformer import (Transformer,
                                                   TransformerConfig)

        cfg = TransformerConfig(vocab_size=128, d_model=32, n_layers=1,
                                n_heads=2, d_ff=64, max_seq=16,
                                dtype="float32")
        model = Transformer(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(5)
        batch = (jnp.asarray(rng.integers(0, 128, (2, 16)), jnp.int32),
                 jnp.asarray(rng.integers(0, 128, (2, 16)), jnp.int32))
        monkeypatch.setenv("KF_TPU_LM_HEAD", "plain")
        plain = float(model.loss(params, batch, train=True))
        monkeypatch.setenv("KF_TPU_LM_HEAD", "fused")
        fused = float(model.loss(params, batch, train=True))
        np.testing.assert_allclose(fused, plain, rtol=2e-5)
        monkeypatch.setenv("KF_TPU_LM_HEAD", "bogus")
        with pytest.raises(ValueError, match="KF_TPU_LM_HEAD"):
            model.loss(params, batch)

    @pytest.mark.slow  # ~16s: fuzz sweep recompiles per shape
    def test_random_shape_sweep(self):
        """Randomized ragged shapes and block sizes: loss + grads must
        match the reference everywhere (pad/mask path fuzz)."""
        from kungfu_tpu.ops.pallas.lm_head import lm_head_nll

        rng = np.random.default_rng(11)
        for _ in range(5):
            n = int(rng.integers(1, 40))
            d = int(rng.integers(8, 96))
            v = int(rng.integers(16, 520))
            bn = int(rng.choice([8, 16, 32]))
            bv = int(rng.choice([128, 256]))
            h = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
            w = jnp.asarray(rng.standard_normal((d, v)) * 0.1, jnp.float32)
            t = jnp.asarray(rng.integers(0, v, n), jnp.int32)
            msg = f"n={n} d={d} v={v} bn={bn} bv={bv}"
            l_k = lm_head_nll(h, w, t, block_n=bn, block_v=bv)
            np.testing.assert_allclose(
                np.asarray(l_k), np.asarray(self._ref(h, w, t)),
                rtol=2e-5, atol=1e-5, err_msg=msg)
            g_ref = jax.grad(lambda h, w: jnp.mean(self._ref(h, w, t)),
                             argnums=(0, 1))(h, w)
            g_k = jax.grad(
                lambda h, w: jnp.mean(lm_head_nll(h, w, t, block_n=bn,
                                                  block_v=bv)),
                argnums=(0, 1))(h, w)
            for a, b in zip(g_k, g_ref):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=1e-5,
                                           err_msg=msg)
