"""Model zoo smoke + correctness tests (small shapes, CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.models import (
    MLP,
    ResNet,
    Transformer,
    TransformerConfig,
    VGG,
    fake_grads,
    fake_model_sizes,
    mnist_slp,
    nn,
)


class TestMLP:
    def test_slp_shapes_and_grad(self):
        m = mnist_slp()
        params = m.init(jax.random.PRNGKey(0))
        assert nn.num_params(params) == 7850
        x = np.random.RandomState(0).rand(4, 28, 28).astype(np.float32)
        y = np.array([1, 2, 3, 4])
        logits = m.apply(params, x)
        assert logits.shape == (4, 10)
        loss, grads = jax.value_and_grad(m.loss)(params, (x, y))
        assert np.isfinite(float(loss))
        assert grads["dense_0"]["w"].shape == (784, 10)

    def test_training_reduces_loss(self):
        m = MLP([32])
        params = m.init(jax.random.PRNGKey(1))
        rng = np.random.RandomState(1)
        x = rng.rand(64, 784).astype(np.float32)
        y = (x.sum(1) > x.sum(1).mean()).astype(np.int32)

        @jax.jit
        def step(p):
            l, g = jax.value_and_grad(m.loss)(p, (x, y))
            return l, jax.tree_util.tree_map(lambda a, b: a - 0.5 * b, p, g)

        l0, params = step(params)
        for _ in range(20):
            l, params = step(params)
        assert float(l) < float(l0)


class TestResNet:
    @pytest.mark.slow  # compile-heavy e2e; full tier + CI slow job
    def test_tiny_forward_backward(self):
        m = ResNet(50, num_classes=10, width=8)
        params, state = m.init(jax.random.PRNGKey(0))
        x = np.random.RandomState(0).rand(2, 32, 32, 3).astype(np.float32)
        y = np.array([1, 2])
        (loss, new_state), grads = jax.value_and_grad(m.loss, has_aux=True)(
            params, state, (x, y), train=True, dtype=jnp.float32
        )
        assert np.isfinite(float(loss))
        # BN running stats updated
        assert not np.allclose(
            np.asarray(new_state["stem_bn"]["mean"]), np.asarray(state["stem_bn"]["mean"])
        )
        # eval path
        logits, _ = m.apply(params, state, x, train=False, dtype=jnp.float32)
        assert logits.shape == (2, 10)

    def test_s2d_stem_matches_direct_conv(self):
        """The space-to-depth stem is the SAME linear map as the 7x7/s2
        conv (MXU lane packing, not an architecture change): outputs and
        the gradient w.r.t. the original 7x7 parameter must match the
        direct conv to float tolerance, and odd sizes fall back."""
        key = jax.random.PRNGKey(0)
        p = nn.conv_init(key, 3, 16, (7, 7))
        x = jnp.asarray(
            np.random.RandomState(1).randn(2, 64, 64, 3), jnp.float32
        )
        a = nn.conv_apply(p, x, stride=2)
        b = nn.conv_stem_s2d_apply(p, x)
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
        ga = jax.grad(lambda w: jnp.sum(
            nn.conv_apply({"w": w}, x, stride=2) ** 2))(p["w"])
        gb = jax.grad(lambda w: jnp.sum(
            nn.conv_stem_s2d_apply({"w": w}, x) ** 2))(p["w"])
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   rtol=1e-4, atol=1e-4)
        # odd spatial size: falls back to the direct conv path
        x_odd = x[:, :63, :63, :]
        np.testing.assert_allclose(
            np.asarray(nn.conv_stem_s2d_apply(p, x_odd)),
            np.asarray(nn.conv_apply(p, x_odd, stride=2)),
            rtol=1e-5, atol=1e-5)

    @pytest.mark.slow  # compile-heavy e2e; full tier + CI slow job
    def test_real_resnet50_param_count(self):
        m = ResNet(50, num_classes=1000)
        params, _ = m.init(jax.random.PRNGKey(0))
        n = nn.num_params(params)
        assert 25.4e6 < n < 25.8e6, n  # ~25.56M

    def test_deep_variants(self):
        """101/152 stage tables build and run (tiny width)."""
        for depth, blocks in ((101, 33), (152, 50)):
            m = ResNet(depth, num_classes=10, width=8)
            params, state = m.init(jax.random.PRNGKey(0))
            n_blocks = sum(
                1 for k in params if k[0] == "s" and k[1].isdigit()
            )
            assert n_blocks == blocks
            x = np.random.RandomState(0).rand(1, 32, 32, 3).astype(np.float32)
            logits, _ = m.apply(params, state, x, train=False, dtype=jnp.float32)
            assert logits.shape == (1, 10)


class TestVGG:
    @pytest.mark.slow  # compile-heavy e2e; full tier + CI slow job
    def test_tiny_forward_backward(self):
        m = VGG(11, num_classes=10, hidden=64)
        params, state = m.init(jax.random.PRNGKey(0))
        x = np.random.RandomState(0).rand(2, 32, 32, 3).astype(np.float32)
        y = np.array([1, 2])
        (loss, new_state), grads = jax.value_and_grad(m.loss, has_aux=True)(
            params, state, (x, y), train=True, dtype=jnp.float32
        )
        assert np.isfinite(float(loss))
        assert not np.allclose(
            np.asarray(new_state["conv0_bn"]["mean"]),
            np.asarray(state["conv0_bn"]["mean"]),
        )
        logits, _ = m.apply(params, state, x, train=False, dtype=jnp.float32)
        assert logits.shape == (2, 10)

    def test_no_bn_variant(self):
        m = VGG(11, num_classes=10, batch_norm=False, hidden=64)
        params, state = m.init(jax.random.PRNGKey(0))
        assert state == {}
        x = np.random.RandomState(0).rand(2, 32, 32, 3).astype(np.float32)
        logits, ns = m.apply(params, state, x, dtype=jnp.float32)
        assert logits.shape == (2, 10) and ns == {}

    def test_vgg16_param_count(self):
        m = VGG(16, num_classes=1000)
        params, _ = m.init(jax.random.PRNGKey(0))
        n = nn.num_params(params)
        # 14.71M conv + 2.10M fc1 + 4.10M head + BN affine (~8.5k x2)
        assert 20.5e6 < n < 21.5e6, n


class TestTransformer:
    @pytest.mark.parametrize("pos,causal", [("rope", True), ("learned", False)])
    def test_forward_backward(self, pos, causal):
        cfg = TransformerConfig(
            vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
            max_seq=16, causal=causal, pos=pos, dtype="float32",
        )
        m = Transformer(cfg)
        params = m.init(jax.random.PRNGKey(0))
        ids = np.random.RandomState(0).randint(0, 128, (2, 16))
        tgt = np.roll(ids, -1, axis=1)
        loss, grads = jax.jit(jax.value_and_grad(m.loss))(params, (ids, tgt))
        assert np.isfinite(float(loss))
        g = grads["layer_0"]["wq"]["w"]
        assert np.abs(np.asarray(g)).sum() > 0

    def test_causality(self):
        """Changing a future token must not change past logits."""
        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
            max_seq=8, causal=True, pos="rope", dtype="float32",
        )
        m = Transformer(cfg)
        params = m.init(jax.random.PRNGKey(0))
        ids = np.arange(8)[None, :] % 64
        apply = jax.jit(m.apply)
        logits1 = np.asarray(apply(params, ids))
        ids2 = ids.copy()
        ids2[0, -1] = (ids[0, -1] + 9) % 64
        logits2 = np.asarray(apply(params, ids2))
        np.testing.assert_allclose(logits1[0, :-1], logits2[0, :-1], atol=1e-5)
        assert not np.allclose(logits1[0, -1], logits2[0, -1])


class TestBNVariants:
    """The batch-norm elementwise chain's compute dtype is a pure
    precision change: identical f32 statistics, rounded output."""

    def _xpb(self, batch=32, ch=8):
        from kungfu_tpu.models import nn

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((batch, 4, 4, ch)), jnp.bfloat16)
        p = nn.batchnorm_init(ch)
        p["scale"] = jnp.asarray(rng.uniform(0.5, 1.5, ch), jnp.float32)
        p["bias"] = jnp.asarray(rng.standard_normal(ch), jnp.float32)
        st = nn.batchnorm_state_init(ch)
        return x, p, st

    def test_bn_compute_dtype_default_and_optout(self, monkeypatch):
        """Round-5 BN-tax fix: the elementwise chain defaults to the
        activation dtype; stats stay bit-identical f32; KF_TPU_BN_COMPUTE
        =f32 (or an explicit compute_dtype) restores the legacy chain."""
        from kungfu_tpu.models import nn

        x, p, st = self._xpb()
        monkeypatch.delenv("KF_TPU_BN_COMPUTE", raising=False)
        y_act, s_act = nn.batchnorm_apply(p, st, x, train=True)
        y_f32, s_f32 = nn.batchnorm_apply(p, st, x, train=True,
                                          compute_dtype=jnp.float32)
        for k in s_act:
            np.testing.assert_array_equal(np.asarray(s_act[k]),
                                          np.asarray(s_f32[k]))
        assert y_act.dtype == x.dtype == y_f32.dtype
        np.testing.assert_allclose(
            np.asarray(y_act, np.float32), np.asarray(y_f32, np.float32),
            atol=0.05, rtol=0.05)
        # env opt-out is exactly the explicit-f32 chain
        monkeypatch.setenv("KF_TPU_BN_COMPUTE", "f32")
        y_env, s_env = nn.batchnorm_apply(p, st, x, train=True)
        np.testing.assert_array_equal(np.asarray(y_env), np.asarray(y_f32))
        # f32 activations: both chains are the same f32 math
        xf = x.astype(jnp.float32)
        monkeypatch.delenv("KF_TPU_BN_COMPUTE", raising=False)
        ya, _ = nn.batchnorm_apply(p, st, xf, train=True)
        yb, _ = nn.batchnorm_apply(p, st, xf, train=True,
                                   compute_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))


class TestFakeModels:
    def test_totals(self):
        from kungfu_tpu.models.fake import total_params

        assert total_params("slp-mnist") == 7850
        assert 25e6 < total_params("resnet50-imagenet") < 26e6
        assert 130e6 < total_params("vgg16-imagenet") < 140e6
        assert 100e6 < total_params("bert") < 120e6

    def test_grads(self):
        gs = fake_grads("slp-mnist", stacked=4)
        assert gs[0].shape == (4, 7840)
        with pytest.raises(ValueError):
            fake_model_sizes("nope")
