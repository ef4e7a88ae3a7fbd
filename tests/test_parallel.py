"""Tests for the 4-D parallelism subsystem on the 8-device CPU mesh.

Methodology mirrors the reference's cross-checking strategy (SURVEY §4:
"cross-checking its collectives against jax.lax references"): every sharded
path is compared numerically against the unsharded single-device model.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from kungfu_tpu.models.transformer import Transformer, TransformerConfig, default_attention
from kungfu_tpu.parallel import (
    MeshPlan,
    ShardedTrainer,
    moe_apply,
    moe_init,
    ring_attention,
)

CFG = dict(
    vocab_size=64, d_model=64, n_layers=2, n_heads=4, d_ff=128,
    max_seq=32, causal=True, pos="rope", dtype="float32",
)


def _batch(B=8, S=32, vocab=64, seed=0):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, vocab, size=(B, S)), dtype=jnp.int32)
    tgt = jnp.asarray(rng.integers(0, vocab, size=(B, S)), dtype=jnp.int32)
    return ids, tgt


# -- mesh plan ------------------------------------------------------------
def test_mesh_plan_auto():
    p = MeshPlan.auto(8)
    assert p.size == 8
    assert p.dp == 2 and p.tp == 2 and p.sp == 2 and p.pp == 1
    p16 = MeshPlan.auto(16)
    assert p16.size == 16 and p16.pp == 2
    assert MeshPlan.auto(1).size == 1
    assert MeshPlan.auto(6).size == 6


# -- ring attention -------------------------------------------------------
@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    n_sp = 4
    B, H, S, D = 2, 2, 32, 16
    rng = np.random.default_rng(1)
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, S, D)), dtype=jnp.float32)
        for _ in range(3)
    )
    mesh = Mesh(np.array(jax.devices()[:n_sp]), ("sp",))
    f = shard_map(
        functools.partial(ring_attention, causal=causal, axis="sp"),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None),
    )
    out = jax.jit(f)(q, k, v)
    ref = default_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_flash_blocks_match_dense(causal):
    """block_impl='flash': per-round Pallas blocks (interpret mode here)
    merged by lse must equal dense attention — including the skipped
    fully-masked causal rounds and the diag/full branch split."""
    n_sp = 4
    B, H, S, D = 1, 2, 32, 16
    rng = np.random.default_rng(3)
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, S, D)), dtype=jnp.float32)
        for _ in range(3)
    )
    mesh = Mesh(np.array(jax.devices()[:n_sp]), ("sp",))
    f = shard_map(
        functools.partial(
            ring_attention, causal=causal, axis="sp", block_impl="flash"
        ),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None),
    )
    out = jax.jit(f)(q, k, v)
    ref = default_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    # gradients flow through the lse merge, the custom_vjp blocks (dq),
    # the lse-shifted delta (dk/dv), and the reverse-ppermute of the scan
    def loss_ring(q, k, v):
        return jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(default_attention(q, k, v, causal=causal).astype(jnp.float32) ** 2)

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(gr, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_ring_attention_grads_match_dense():
    n_sp = 4
    B, H, S, D = 1, 2, 16, 8
    rng = np.random.default_rng(2)
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, S, D)), dtype=jnp.float32)
        for _ in range(3)
    )
    mesh = Mesh(np.array(jax.devices()[:n_sp]), ("sp",))

    def ring_loss(q, k, v):
        f = shard_map(
            functools.partial(ring_attention, causal=True, axis="sp"),
            mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
        )
        return jnp.sum(jnp.square(f(q, k, v)))

    def dense_loss(q, k, v):
        return jnp.sum(jnp.square(default_attention(q, k, v, causal=True)))

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


# -- sharded trainer vs unsharded reference -------------------------------
PLANS = [
    MeshPlan(dp=1, pp=1, sp=1, tp=1),
    MeshPlan(dp=2, pp=1, sp=2, tp=2),
    MeshPlan(dp=2, pp=2, sp=1, tp=2),
    MeshPlan(dp=1, pp=2, sp=2, tp=2),
    MeshPlan(dp=8, pp=1, sp=1, tp=1),
]


@pytest.mark.parametrize("plan", PLANS, ids=str)
def test_sharded_loss_matches_reference(plan):
    cfg = TransformerConfig(**CFG)
    model = Transformer(cfg)
    tparams = model.init(jax.random.PRNGKey(0))
    batch = _batch()
    ref_loss = model.loss(tparams, batch, train=False)

    trainer = ShardedTrainer(cfg, plan, n_micro=2 if plan.pp > 1 else 1)
    params = trainer.from_transformer_params(tparams)
    state = {"params": params, "opt_state": trainer.tx.init(params), "step": 0}
    loss = trainer.loss(state, batch)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)


@pytest.mark.slow  # compile-heavy e2e; full tier + CI slow job
def test_sharded_trainer_schedule_matches_psum():
    """ShardedTrainer(schedule='ring'): the scheduled gradient sync must
    produce the same post-step params as the default psum path on a
    hierarchical dp×sp×tp mesh."""
    plan = MeshPlan(dp=2, pp=1, sp=2, tp=2)
    cfg = TransformerConfig(**CFG)
    model = Transformer(cfg)
    batch = _batch()

    outs = {}
    for sched in ("psum", "ring"):
        # fresh params per run: the donated step consumes buffers that
        # from_transformer_params may share with the source tree
        tparams = model.init(jax.random.PRNGKey(0))
        trainer = ShardedTrainer(cfg, plan, schedule=sched)
        params = trainer.from_transformer_params(tparams)
        state = {"params": params, "opt_state": trainer.tx.init(params),
                 "step": 0}
        state, loss = trainer.step(state, batch)
        assert np.isfinite(float(loss))
        outs[sched] = state["params"]
    for a, b in zip(jax.tree_util.tree_leaves(outs["psum"]),
                    jax.tree_util.tree_leaves(outs["ring"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_sharded_loss_fused_xent_matches(monkeypatch):
    """KF_TPU_XENT=fused routes the sharded head through the Pallas
    kernel (interpret mode off-TPU); the loss must match the plain
    log_softmax path — both per-stage masking and the mean reduction."""
    from kungfu_tpu.ops.pallas.xent import XENT_ENV

    monkeypatch.setenv("KF_TPU_XENT", "fused")
    XENT_ENV.reload()
    plan = MeshPlan(dp=2, pp=2, sp=1, tp=2)
    cfg = TransformerConfig(**CFG)
    model = Transformer(cfg)
    tparams = model.init(jax.random.PRNGKey(0))
    batch = _batch()
    monkeypatch.setenv("KF_TPU_XENT", "plain")
    XENT_ENV.reload()
    ref_loss = model.loss(tparams, batch, train=False)
    monkeypatch.setenv("KF_TPU_XENT", "fused")
    XENT_ENV.reload()

    trainer = ShardedTrainer(cfg, plan, n_micro=2)
    params = trainer.from_transformer_params(tparams)
    state = {"params": params, "opt_state": trainer.tx.init(params), "step": 0}
    loss = trainer.loss(state, batch)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)


@pytest.mark.slow  # compile-heavy e2e; full tier + CI slow job
@pytest.mark.parametrize("plan", [MeshPlan(dp=2, pp=1, sp=2, tp=2),
                                  MeshPlan(dp=2, pp=2, sp=1, tp=2)], ids=str)
def test_sharded_step_matches_reference(plan):
    """One SGD step under full sharding must produce the same params as the
    single-device step — validates every gradient-sync path."""
    cfg = TransformerConfig(**CFG)
    model = Transformer(cfg)
    tparams = model.init(jax.random.PRNGKey(0))
    batch = _batch()

    lr = 0.05
    ref_grads = jax.grad(lambda p: model.loss(p, batch, train=False))(tparams)
    ref_params = jax.tree_util.tree_map(lambda p, g: p - lr * g, tparams, ref_grads)

    trainer = ShardedTrainer(
        cfg, plan, tx=optax.sgd(lr), n_micro=2 if plan.pp > 1 else 1
    )
    params = trainer.from_transformer_params(tparams)
    state = {"params": params, "opt_state": trainer.tx.init(params),
             "step": jnp.zeros((), jnp.int32)}
    state, _ = trainer.step(state, batch)

    got = jax.device_get(state["params"])
    np.testing.assert_allclose(
        got["embed"]["table"], np.asarray(ref_params["embed"]["table"]),
        rtol=2e-4, atol=2e-5,
    )
    np.testing.assert_allclose(
        got["head"]["w"], np.asarray(ref_params["head"]["w"]), rtol=2e-4, atol=2e-5
    )
    for i in range(cfg.n_layers):
        np.testing.assert_allclose(
            got["layers"]["wq"]["w"][i],
            np.asarray(ref_params[f"layer_{i}"]["wq"]["w"]),
            rtol=2e-4, atol=2e-5,
        )
        np.testing.assert_allclose(
            got["layers"]["ffn_out"]["w"][i],
            np.asarray(ref_params[f"layer_{i}"]["ffn_out"]["w"]),
            rtol=2e-4, atol=2e-5,
        )
        np.testing.assert_allclose(
            got["layers"]["ln1"]["scale"][i],
            np.asarray(ref_params[f"layer_{i}"]["ln1"]["scale"]),
            rtol=2e-4, atol=2e-5,
        )


# -- MoE / expert parallelism ---------------------------------------------
def test_moe_ep_matches_local():
    """Token outputs with experts sharded over ep=2 equal the unsharded
    routing (capacity high enough that nothing drops)."""
    E, D, F, T = 4, 16, 32, 24
    params = moe_init(jax.random.PRNGKey(0), E, D, F, n_experts_global=E)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((T, D)), dtype=jnp.float32)

    y_ref, aux_ref = moe_apply(params, x, axis=None, n_experts_global=E,
                               capacity_factor=float(E))
    assert np.isfinite(float(aux_ref))

    ep = 2
    mesh = Mesh(np.array(jax.devices()[:ep]), ("ep",))
    # shard experts over ep; every rank routes its own half of the tokens
    lparams_spec = {"gate": {"w": P(None, None)}, "w_in": P("ep", None, None),
                    "w_out": P("ep", None, None)}

    def f(lp, xl):
        y, aux = moe_apply(lp, xl, axis="ep", n_experts_global=E,
                           capacity_factor=float(E))
        return y, jax.lax.pmean(aux, "ep")

    g = shard_map(f, mesh=mesh, in_specs=(lparams_spec, P("ep", None)),
                  out_specs=(P("ep", None), P()), check_vma=False)
    y_ep, aux_ep = jax.jit(g)(params, x)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref), rtol=2e-5, atol=2e-5)
    assert np.isfinite(float(aux_ep))


@pytest.mark.slow  # compile-heavy e2e; full tier + CI slow job
def test_moe_trainer_trains():
    """Full 4-D trainer with MoE FFNs: loss decreases on a repeated batch."""
    cfg = TransformerConfig(**CFG)
    plan = MeshPlan(dp=2, pp=1, sp=2, tp=2)
    trainer = ShardedTrainer(cfg, plan, n_experts=4, tx=optax.adam(1e-3),
                             capacity_factor=4.0)
    state = trainer.init(jax.random.PRNGKey(0))
    batch = _batch()
    losses = []
    for _ in range(4):
        state, loss = trainer.step(state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_pipeline_microbatch_counts():
    """Loss is independent of the number of microbatches."""
    cfg = TransformerConfig(**CFG)
    model = Transformer(cfg)
    tparams = model.init(jax.random.PRNGKey(0))
    batch = _batch()
    ref = float(model.loss(tparams, batch, train=False))
    for n_micro in (2, 4):
        plan = MeshPlan(dp=1, pp=2, sp=1, tp=1)
        trainer = ShardedTrainer(cfg, plan, n_micro=n_micro)
        params = trainer.from_transformer_params(tparams)
        state = {"params": params, "opt_state": trainer.tx.init(params), "step": 0}
        assert float(trainer.loss(state, batch)) == pytest.approx(ref, rel=1e-5)


@pytest.mark.slow  # compile-heavy e2e; full tier + CI slow job
def test_graft_entry_dryrun():
    import sys

    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_sync_batchnorm_matches_big_batch():
    """BN with axis_name over a dp mesh must equal single-device BN on
    the concatenated batch — both the normalized output and the running
    stats (the whole point of sync-BN; a per-shard-stats bug converges
    differently at scale and is invisible to loss-goes-down tests)."""
    from kungfu_tpu.models import nn as knn

    n_dp = 4
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((8, 6, 6, 3)), jnp.float32)
    p = knn.batchnorm_init(3)
    st = knn.batchnorm_state_init(3)

    ref_y, ref_stats = knn.batchnorm_apply(p, st, x, train=True)

    mesh = Mesh(np.array(jax.devices()[:n_dp]), ("dp",))
    f = shard_map(
        lambda xs: knn.batchnorm_apply(p, st, xs, train=True, axis_name="dp"),
        mesh=mesh, in_specs=P("dp"), out_specs=(P("dp"), P()),
    )
    y, stats = jax.jit(f)(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y), rtol=2e-5, atol=2e-5)
    for k in ("mean", "var"):
        np.testing.assert_allclose(
            np.asarray(stats[k]), np.asarray(ref_stats[k]), rtol=2e-5, atol=2e-5,
            err_msg=f"running {k} diverged from big-batch BN",
        )


class TestDPTrainStep:
    """dp_train_step: the DP-only helper over a Communicator mesh."""

    def _setup(self):
        from kungfu_tpu.comm.device import Communicator

        comm = Communicator()
        w_true = jnp.asarray([1.0, -2.0, 0.5, 3.0])
        rng = np.random.default_rng(0)
        X = jnp.asarray(rng.standard_normal((64, 4)), jnp.float32)
        Y = X @ w_true

        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] - y) ** 2)

        return comm, {"w": jnp.zeros(4)}, loss_fn, (X, Y)

    def test_sync_sgd_replicated_converges(self):
        from kungfu_tpu.optimizers import synchronous_sgd
        from kungfu_tpu.parallel.train import dp_train_step

        comm, params, loss_fn, batch = self._setup()
        tx = synchronous_sgd(optax.sgd(0.1), comm.axis)
        step = dp_train_step(loss_fn, tx, comm)
        state = tx.init(params)
        for _ in range(60):
            params, state, loss = step(params, state, batch)
        assert float(loss) < 1e-2

    def test_sync_sgd_equals_serial_large_batch(self):
        from kungfu_tpu.optimizers import synchronous_sgd
        from kungfu_tpu.parallel.train import dp_train_step

        comm, params, loss_fn, batch = self._setup()
        tx = synchronous_sgd(optax.sgd(0.05), comm.axis)
        step = dp_train_step(loss_fn, tx, comm)
        state = tx.init(params)
        p_dist, _, _ = step(params, state, batch)

        # serial reference: plain SGD on the mean of per-shard mean grads
        n = comm.size
        shards = [
            (batch[0][i * (64 // n):(i + 1) * (64 // n)],
             batch[1][i * (64 // n):(i + 1) * (64 // n)])
            for i in range(n)
        ]
        g = jax.tree_util.tree_map(
            lambda *gs: sum(gs) / n,
            *[jax.grad(loss_fn)(params, s) for s in shards],
        )
        p_ref = jax.tree_util.tree_map(lambda p, g_: p - 0.05 * g_, params, g)
        np.testing.assert_allclose(p_dist["w"], p_ref["w"], rtol=1e-5)

    def test_sma_stacked_replicas_diverge_then_track(self):
        from kungfu_tpu.optimizers import synchronous_averaging
        from kungfu_tpu.parallel.train import dp_train_step, stack_for_replicas

        comm, params, loss_fn, batch = self._setup()
        n = comm.size
        tx = synchronous_averaging(optax.sgd(0.05), comm.axis, alpha=0.2)
        step = dp_train_step(loss_fn, tx, comm, replicated_params=False)
        sp = stack_for_replicas(params, n)
        ss = stack_for_replicas(tx.init(params), n)
        for _ in range(40):
            sp, ss, loss = step(sp, ss, batch)
        assert float(loss) < 0.1
        # replicas stay near each other (pulled toward the average)
        w = np.asarray(sp["w"])
        assert np.max(np.std(w, axis=0)) < 0.2


@pytest.mark.parametrize("plan", [MeshPlan(dp=2, pp=1, sp=2, tp=2),
                                  MeshPlan(dp=2, pp=2, sp=1, tp=2)], ids=str)
def test_sharded_loss_learned_positions_matches(plan):
    """Learned (absolute) positions under full sharding: the pos_embed
    table rides the replicated layout, the lookup uses sp-global
    offsets, and the loss matches the unsharded model."""
    cfg = TransformerConfig(**{**CFG, "pos": "learned"})
    model = Transformer(cfg)
    tparams = model.init(jax.random.PRNGKey(0))
    batch = _batch()
    ref_loss = model.loss(tparams, batch, train=False)

    trainer = ShardedTrainer(cfg, plan, n_micro=2 if plan.pp > 1 else 1)
    params = trainer.from_transformer_params(tparams)
    assert "pos_embed" in params
    state = {"params": params, "opt_state": trainer.tx.init(params), "step": 0}
    loss = trainer.loss(state, batch)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)


def test_sharded_init_learned_positions():
    cfg = TransformerConfig(**{**CFG, "pos": "learned"})
    trainer = ShardedTrainer(cfg, MeshPlan(dp=2, pp=1, sp=1, tp=1))
    state = trainer.init(jax.random.PRNGKey(1))
    pe = state["params"]["pos_embed"]["table"]
    assert pe.shape == (cfg.max_seq, cfg.d_model)
    s, loss = trainer.step(state, _batch())
    assert np.isfinite(float(loss))


def test_sharded_fused_grad_sync_matches():
    """fuse_grads=True (one collective per sync-kind) must produce the
    same post-step params as the per-leaf sync on a hierarchical mesh,
    MoE expert grads included."""
    plan = MeshPlan(dp=2, pp=1, sp=2, tp=2)
    cfg = TransformerConfig(**CFG)
    model = Transformer(cfg)
    batch = _batch()

    outs = {}
    for fused in (False, True):
        tparams = model.init(jax.random.PRNGKey(0))
        trainer = ShardedTrainer(cfg, plan, tx=optax.sgd(0.05),
                                 fuse_grads=fused)
        params = trainer.from_transformer_params(tparams)
        state = {"params": params, "opt_state": trainer.tx.init(params),
                 "step": 0}
        state, loss = trainer.step(state, batch)
        assert np.isfinite(float(loss))
        outs[fused] = state["params"]
    for a, b in zip(jax.tree_util.tree_leaves(outs[False]),
                    jax.tree_util.tree_leaves(outs[True])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_sharded_fused_grad_sync_moe():
    """The 'expert' sync-kind rides the bucketed path too: fused and
    per-leaf sync must produce identical post-step params for an MoE
    trainer (same init, same batch)."""
    plan = MeshPlan(dp=2, pp=1, sp=1, tp=2)
    cfg = TransformerConfig(**CFG)
    batch = _batch()
    outs = {}
    for fused in (False, True):
        trainer = ShardedTrainer(cfg, plan, n_experts=2,
                                 tx=optax.sgd(0.05), fuse_grads=fused)
        state = trainer.init(jax.random.PRNGKey(1))
        state, loss = trainer.step(state, batch)
        assert np.isfinite(float(loss))
        outs[fused] = state["params"]
    for a, b in zip(jax.tree_util.tree_leaves(outs[False]),
                    jax.tree_util.tree_leaves(outs[True])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
