"""The 4-D sharded training step: dp x pp x sp x tp (+ EP over dp).

One ``shard_map`` over the :class:`~kungfu_tpu.parallel.mesh.MeshPlan`
mesh computes per-device gradients with every cross-device flow explicit:

* **dp** — gradient psum (the reference's allreduce, done as one XLA
  collective instead of the Go graph engine);
* **pp** — GPipe-style microbatch pipeline: a ``lax.scan`` over
  ``n_micro + pp - 1`` ticks, activations hopping stages via ``ppermute``
  (autodiff reverses the hops, giving the backward pipeline for free);
* **sp** — sequence sharding with ring attention
  (:mod:`kungfu_tpu.parallel.ring`);
* **tp** — Megatron column/row matmuls (:mod:`kungfu_tpu.parallel.tp`);
* **ep=dp** — optional switch-MoE FFNs with ``all_to_all`` token exchange
  (:mod:`kungfu_tpu.parallel.moe`).

Gradient synchronization is explicit and per-parameter-kind (see
:func:`sync_grads`): autodiff inside ``shard_map`` yields each rank's
d(own loss term)/d(own shard); collective transposes (ppermute, all_to_all,
and the tp custom-vjp pair) already route *sharded*-param flows, while
*replicated* params need the trailing psum — exactly the split the
reference handles with its group allreduce after local backprop
(``sync_sgd.py:58-109``), generalized to four axes.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, replace as _dc_replace
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from kungfu_tpu.models import nn
from kungfu_tpu.models.transformer import TransformerConfig, _rope
from kungfu_tpu.monitor import timeline
from kungfu_tpu.parallel import tp as tpmod
from kungfu_tpu.parallel.mesh import AXIS_DP, AXIS_PP, AXIS_SP, AXIS_TP, MeshPlan
from kungfu_tpu.parallel.moe import moe_apply
from kungfu_tpu.parallel.ring import ring_attention
from kungfu_tpu.utils import envs

MOE_AUX_COEF = 0.01


@dataclass(frozen=True)
class ParallelPlan:
    """THE parallelism configuration: every axis degree, the ZeRO stage,
    and the pipeline schedule in one value, consumed by every
    entrypoint instead of each hand-wiring its own axis combination —
    :class:`ShardedTrainer` (in-mesh dp/pp/sp/tp), :func:`dp_train_step`
    / :func:`~kungfu_tpu.parallel.zero.zero_train_step` (host/device DP
    + ZeRO), :class:`~kungfu_tpu.parallel.pp.HostPipeline` (cross-DCN
    pipeline), and the serving fleet
    (:class:`kungfu_tpu.serve.scale.ServeFleet`).

    Axis mapping follows the slice-major hierarchy (PR 8): **pp across
    the DCN** (one stage per slice — ``pp`` ≡ ``MEGASCALE_NUM_SLICES``
    on a multislice pod), **tp within the ICI** (never crosses a
    slice), **dp/ZeRO across the replicas inside a slice** (host world
    is ``pp × dp`` ranks).  ``to_slice_topology()`` exposes exactly
    that correspondence; :meth:`HostPipeline.__init__` validates the
    plan against the peer's live topology.
    """

    dp: int = 1
    tp: int = 1
    pp: int = 1
    sp: int = 1
    #: 0 = replicated optimizer; 1/2/3 route the ZeRO family
    zero_stage: int = 0
    #: pipeline microbatches (None -> pp, the minimum that fills it)
    n_micro: Optional[int] = None
    #: pipeline schedule: "1f1b" | "interleaved" | "sequential"
    pp_schedule: str = "1f1b"
    #: model chunks per stage for the interleaved schedule
    interleave: int = 1
    #: allreduce decomposition arm (ops.schedules.ALLREDUCE_SCHEDULES)
    collective_schedule: str = "psum"

    def __post_init__(self):
        from kungfu_tpu.parallel.pp import SCHEDULES

        for name, v in (("dp", self.dp), ("tp", self.tp),
                        ("pp", self.pp), ("sp", self.sp)):
            if v < 1:
                raise ValueError(f"{name}={v} must be >= 1")
        if self.zero_stage not in (0, 1, 2, 3):
            raise ValueError(f"zero_stage={self.zero_stage} not in 0..3")
        if self.pp_schedule not in SCHEDULES:
            raise ValueError(
                f"pp_schedule={self.pp_schedule!r}; one of {SCHEDULES}")
        if self.interleave < 1:
            raise ValueError(f"interleave={self.interleave} must be >= 1")
        if self.interleave > 1 and self.pp_schedule != "interleaved":
            raise ValueError(
                "interleave > 1 requires pp_schedule='interleaved'")
        if self.n_micro is not None and self.n_micro < 1:
            raise ValueError(f"n_micro={self.n_micro} must be >= 1")

    # -- shape -------------------------------------------------------------
    @property
    def size(self) -> int:
        """Device count of the in-mesh form (dp*pp*sp*tp)."""
        return self.dp * self.pp * self.sp * self.tp

    @property
    def host_size(self) -> int:
        """Host-plane world size of the cross-DCN form: one rank per
        (stage, dp lane); tp/sp ride each rank's LOCAL device mesh."""
        return self.dp * self.pp

    def mesh_plan(self) -> MeshPlan:
        return MeshPlan(dp=self.dp, pp=self.pp, sp=self.sp, tp=self.tp)

    def build_mesh(self, devices=None):
        return self.mesh_plan().build_mesh(devices)

    # -- pipeline geometry (stage-major = slice-major rank layout) ---------
    def stage_map(self, n_layers: int) -> List[Tuple[int, int]]:
        from kungfu_tpu.parallel.pp import stage_partition

        return stage_partition(n_layers, self.pp)

    def stage_of(self, rank: int) -> int:
        return rank // self.dp

    def dp_index(self, rank: int) -> int:
        return rank % self.dp

    def stage_ranks(self, stage: int) -> List[int]:
        return list(range(stage * self.dp, (stage + 1) * self.dp))

    def to_slice_topology(self):
        """The multislice topology this plan maps onto (PP across DCN
        slices, dp lanes within each), or None when single-stage."""
        if self.pp <= 1:
            return None
        from kungfu_tpu.elastic.slices import SliceTopology

        return SliceTopology(self.pp, self.dp)

    def with_stages(self, pp: int) -> "ParallelPlan":
        """The post-re-carve plan: same axes, ``pp`` stages (the
        elastic stage re-carve shrinks this, never dp/tp)."""
        return _dc_replace(self, pp=pp)

    # -- env contract ------------------------------------------------------
    @classmethod
    def from_env(cls, **overrides) -> "ParallelPlan":
        """Plan from the launch contract: ``KF_PP_STAGES``,
        ``KF_PP_MICROBATCHES`` (0 -> pp), ``KF_PP_SCHEDULE``
        (1f1b | interleaved | sequential); explicit kwargs win."""
        import os

        vals = dict(
            pp=envs.parse_int_env(envs.PP_STAGES, 1),
            n_micro=envs.parse_int_env(envs.PP_MICROBATCHES, 0) or None,
            pp_schedule=(os.environ.get(envs.PP_SCHEDULE, "")
                         or "1f1b").strip().lower(),
        )
        vals.update(overrides)
        return cls(**vals)

# parameter kinds → (psum axes, replication denominator axes)
_KIND_AXES = {
    # embed / ln_f / head: replicated everywhere; grads live on one pp stage
    "replicated": ((AXIS_DP, AXIS_PP, AXIS_SP, AXIS_TP), (AXIS_DP, AXIS_SP, AXIS_TP)),
    # per-layer params replicated over dp/sp/tp (layernorms, gate)
    "dense_layer": ((AXIS_DP, AXIS_SP, AXIS_TP), (AXIS_DP, AXIS_SP, AXIS_TP)),
    # tp-sharded weights: tp flows handled by the custom-vjp pair
    "tp_sharded": ((AXIS_DP, AXIS_SP), (AXIS_DP, AXIS_SP)),
    # expert weights: dp flows handled by all_to_all transpose
    "expert": ((AXIS_SP, AXIS_TP), (AXIS_DP, AXIS_SP, AXIS_TP)),
}


def _axis_prod(plan: MeshPlan, axes) -> int:
    sizes = {AXIS_DP: plan.dp, AXIS_PP: plan.pp, AXIS_SP: plan.sp, AXIS_TP: plan.tp}
    out = 1
    for a in axes:
        out *= sizes[a]
    return out


class ShardedTrainer:
    """Owns the mesh, the sharded parameter layout, and the jitted step."""

    def __init__(
        self,
        cfg: TransformerConfig,
        plan: Union[MeshPlan, "ParallelPlan"],
        n_experts: int = 0,
        n_micro: Optional[int] = None,
        tx: Optional[optax.GradientTransformation] = None,
        devices=None,
        capacity_factor: float = 1.25,
        schedule: str = "psum",
        fuse_grads: bool = False,
    ):
        if isinstance(plan, ParallelPlan):
            # the unified plan: axis degrees, microbatching, and the
            # collective schedule all come from one value
            if plan.zero_stage:
                raise ValueError(
                    "ShardedTrainer holds one replicated optimizer over "
                    "the mesh — ZeRO stages route through dp_train_step/"
                    "zero_train_step (device DP) or HostPipeline "
                    "(cross-DCN pp)")
            n_micro = n_micro or plan.n_micro
            # same disagreement contract as dp_train_step/zero_train_step:
            # an explicit non-default schedule kwarg must not be silently
            # clobbered by the plan (nor silently win over it)
            if schedule != "psum" and schedule != plan.collective_schedule:
                raise ValueError(
                    f"schedule={schedule!r} disagrees with "
                    f"plan.collective_schedule="
                    f"{plan.collective_schedule!r} — set it in the plan")
            schedule = plan.collective_schedule
            plan = plan.mesh_plan()
        if cfg.pos not in ("rope", "learned"):
            raise ValueError(f"unknown position mode {cfg.pos!r}")
        if cfg.n_layers % plan.pp:
            raise ValueError(f"n_layers {cfg.n_layers} % pp {plan.pp} != 0")
        if cfg.n_heads % plan.tp:
            raise ValueError(f"n_heads {cfg.n_heads} % tp {plan.tp} != 0")
        if cfg.d_ff % plan.tp:
            raise ValueError(f"d_ff {cfg.d_ff} % tp {plan.tp} != 0")
        if n_experts and n_experts % plan.ep:
            raise ValueError(f"n_experts {n_experts} % ep {plan.ep} != 0")
        from kungfu_tpu.ops.schedules import ALLREDUCE_SCHEDULES

        if schedule not in ALLREDUCE_SCHEDULES:
            raise ValueError(
                f"unknown schedule {schedule!r}; one of {ALLREDUCE_SCHEDULES}"
            )
        self.cfg = cfg
        self.plan = plan
        self.n_experts = n_experts
        self.capacity_factor = capacity_factor
        self.n_micro = n_micro or plan.pp
        self.tx = tx or optax.sgd(0.01)
        #: allreduce decomposition compiled into sync_grads
        #: (kungfu_tpu.ops.schedules; pass comm.strategy to honor an
        #: installed/autotuned choice)
        self.schedule = schedule
        #: bucket the gradient sync: one collective per sync-kind
        #: (exact — leaves of a kind share axes and denominator)
        self.fuse_grads = fuse_grads
        self.mesh = plan.build_mesh(devices)
        self.param_specs, self.param_kinds = self._layout()
        self._step_fn = None
        self._pulse_fn = None
        from kungfu_tpu.monitor import pulse as pulselib
        #: kf-pulse gradient-signal monitor (None when KF_PULSE_EVERY=0)
        self.pulse = pulselib.PulseMonitor.from_env()

    # -- parameter layout -------------------------------------------------
    def _layout(self):
        """(PartitionSpec tree, kind tree) for the stacked param pytree."""
        cfg, moe = self.cfg, self.n_experts > 0

        def dup(spec_kind):
            return spec_kind

        layer = {
            "ln1": {"scale": (P(AXIS_PP, None), "dense_layer"),
                    "bias": (P(AXIS_PP, None), "dense_layer")},
            "ln2": {"scale": (P(AXIS_PP, None), "dense_layer"),
                    "bias": (P(AXIS_PP, None), "dense_layer")},
            "wq": {"w": (P(AXIS_PP, None, AXIS_TP), "tp_sharded"),
                   "b": (P(AXIS_PP, AXIS_TP), "tp_sharded")},
            "wk": {"w": (P(AXIS_PP, None, AXIS_TP), "tp_sharded"),
                   "b": (P(AXIS_PP, AXIS_TP), "tp_sharded")},
            "wv": {"w": (P(AXIS_PP, None, AXIS_TP), "tp_sharded"),
                   "b": (P(AXIS_PP, AXIS_TP), "tp_sharded")},
            "wo": {"w": (P(AXIS_PP, AXIS_TP, None), "tp_sharded"),
                   "b": (P(AXIS_PP, None), "dense_layer")},
        }
        if moe:
            layer["gate"] = {"w": (P(AXIS_PP, None, None), "dense_layer")}
            layer["w_in"] = (P(AXIS_PP, AXIS_DP, None, None), "expert")
            layer["w_out"] = (P(AXIS_PP, AXIS_DP, None, None), "expert")
        else:
            layer["ffn_in"] = {"w": (P(AXIS_PP, None, AXIS_TP), "tp_sharded"),
                               "b": (P(AXIS_PP, AXIS_TP), "tp_sharded")}
            layer["ffn_out"] = {"w": (P(AXIS_PP, AXIS_TP, None), "tp_sharded"),
                                "b": (P(AXIS_PP, None), "dense_layer")}
        tree = {
            "embed": {"table": (P(None, None), "replicated")},
            "layers": layer,
            "ln_f": {"scale": (P(None), "replicated"), "bias": (P(None), "replicated")},
            "head": {"w": (P(None, None), "replicated")},
        }
        if cfg.pos == "learned":
            tree["pos_embed"] = {"table": (P(None, None), "replicated")}
        is_leaf = lambda x: isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)
        specs = jax.tree_util.tree_map(lambda t: t[0], tree, is_leaf=is_leaf)
        kinds = jax.tree_util.tree_map(lambda t: t[1], tree, is_leaf=is_leaf)
        return specs, kinds

    # -- init --------------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        params: Dict[str, Any] = {}
        key, k = jax.random.split(key)
        params["embed"] = nn.embedding_init(k, cfg.vocab_size, cfg.d_model)
        if cfg.pos == "learned":
            key, k = jax.random.split(key)
            params["pos_embed"] = nn.embedding_init(k, cfg.max_seq, cfg.d_model)
        per_layer = []
        for _ in range(cfg.n_layers):
            key, *ks = jax.random.split(key, 8)
            lp = {
                "ln1": nn.layernorm_init(cfg.d_model),
                "wq": nn.dense_init(ks[0], cfg.d_model, cfg.d_model),
                "wk": nn.dense_init(ks[1], cfg.d_model, cfg.d_model),
                "wv": nn.dense_init(ks[2], cfg.d_model, cfg.d_model),
                "wo": nn.dense_init(ks[3], cfg.d_model, cfg.d_model),
                "ln2": nn.layernorm_init(cfg.d_model),
            }
            if self.n_experts:
                lp["gate"] = {"w": nn.normal(ks[4], (cfg.d_model, self.n_experts))}
                lp["w_in"] = nn.glorot_uniform(ks[5], (self.n_experts, cfg.d_model, cfg.d_ff))
                lp["w_out"] = nn.glorot_uniform(ks[6], (self.n_experts, cfg.d_ff, cfg.d_model))
            else:
                lp["ffn_in"] = nn.dense_init(ks[4], cfg.d_model, cfg.d_ff)
                lp["ffn_out"] = nn.dense_init(ks[5], cfg.d_ff, cfg.d_model)
            per_layer.append(lp)
        params["layers"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *per_layer
        )
        params["ln_f"] = nn.layernorm_init(cfg.d_model)
        key, k = jax.random.split(key)
        params["head"] = nn.dense_init(k, cfg.d_model, cfg.vocab_size, use_bias=False)
        params = self.shard_params(params)
        opt_state = self.tx.init(params)
        return {"params": params, "opt_state": opt_state, "step": jnp.zeros((), jnp.int32)}

    def shard_params(self, params):
        """Place a (replicated/host) param pytree onto the mesh layout."""
        return jax.tree_util.tree_map(
            lambda x, spec: jax.device_put(x, NamedSharding(self.mesh, spec)),
            params,
            self.param_specs,
        )

    def from_transformer_params(self, tparams):
        """Pack per-layer ``Transformer.init`` params (dense FFN only) into
        the stacked sharded layout — used to cross-check against the
        unsharded model."""
        assert not self.n_experts
        L = self.cfg.n_layers
        stacked = {
            "embed": tparams["embed"],
            "layers": jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *[tparams[f"layer_{i}"] for i in range(L)]
            ),
            "ln_f": tparams["ln_f"],
            "head": tparams["head"],
        }
        if self.cfg.pos == "learned":
            stacked["pos_embed"] = tparams["pos_embed"]
        return self.shard_params(stacked)

    # -- the per-device math ----------------------------------------------
    def _block(self, lp, h, positions):
        """One transformer layer on local shards.  h: [B_mb, S_loc, D]
        replicated over tp; returns (h', aux)."""
        cfg, plan = self.cfg, self.plan
        dt = cfg.compute_dtype
        H_loc = cfg.n_heads // plan.tp

        x = nn.layernorm_apply(lp["ln1"], h)
        x = tpmod.tp_region_enter(x, AXIS_TP)
        q = tpmod.column_dense(lp["wq"], x, dtype=dt)
        k = tpmod.column_dense(lp["wk"], x, dtype=dt)
        v = tpmod.column_dense(lp["wv"], x, dtype=dt)

        def heads(t):
            B, S, _ = t.shape
            return t.reshape(B, S, H_loc, cfg.head_dim).transpose(0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)
        if cfg.pos == "rope":
            q, k = _rope(q, k, positions)
        o = ring_attention(q, k, v, causal=cfg.causal, axis=AXIS_SP)
        B, _, S, _ = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(B, S, H_loc * cfg.head_dim)
        h = h + tpmod.row_dense(lp["wo"], o, AXIS_TP, dtype=dt)

        x = nn.layernorm_apply(lp["ln2"], h)
        if self.n_experts:
            y, aux = moe_apply(
                {"gate": lp["gate"], "w_in": lp["w_in"], "w_out": lp["w_out"]},
                x,
                axis=AXIS_DP if plan.ep > 1 else None,
                n_experts_global=self.n_experts,
                capacity_factor=self.capacity_factor,
                dtype=dt,
            )
        else:
            x = tpmod.tp_region_enter(x, AXIS_TP)
            y = nn.gelu(tpmod.column_dense(lp["ffn_in"], x, dtype=dt))
            y = tpmod.row_dense(lp["ffn_out"], y, AXIS_TP, dtype=dt)
            aux = jnp.zeros((), jnp.float32)
        return h + y, aux

    def _local_loss(self, lparams, ids, targets):
        """Per-device loss term.  ids/targets: [B_loc, S_loc] local shards.
        Returns (own_term, nll_for_report, aux_for_report)."""
        cfg, plan = self.cfg, self.plan
        n_micro = self.n_micro
        Pp = plan.pp
        B_loc, S_loc = ids.shape
        assert B_loc % n_micro == 0, (B_loc, n_micro)
        B_mb = B_loc // n_micro

        sp_idx = jax.lax.axis_index(AXIS_SP)
        pp_idx = jax.lax.axis_index(AXIS_PP)
        pos = sp_idx * S_loc + jnp.arange(S_loc)
        positions = jnp.broadcast_to(pos, (B_mb, S_loc))

        ids_mb = ids.reshape(n_micro, B_mb, S_loc)
        tgt_mb = targets.reshape(n_micro, B_mb, S_loc)
        h0 = nn.embedding_apply(lparams["embed"], ids_mb, dtype=cfg.compute_dtype)
        if cfg.pos == "learned":
            # positions carry the sp-global offsets, so the learned table
            # lookup is shard-correct under sequence parallelism too
            pe = nn.embedding_apply(lparams["pos_embed"], positions,
                                    dtype=cfg.compute_dtype)
            h0 = h0 + pe[None]

        T = n_micro + Pp - 1
        if T > n_micro:
            pad = jnp.zeros((Pp - 1,) + h0.shape[1:], h0.dtype)
            h0 = jnp.concatenate([h0, pad], axis=0)

        def stage_fn(x):
            def layer_step(h, lp):
                h2, aux = self._block(lp, h, positions)
                return h2, aux

            h, auxs = jax.lax.scan(layer_step, x, lparams["layers"])
            return h, jnp.sum(auxs)

        perm = [(j, j + 1) for j in range(Pp - 1)]

        def tick(buf, x_t):
            inp = jnp.where(pp_idx == 0, x_t, buf)
            out, aux = stage_fn(inp)
            nxt = jax.lax.ppermute(out, AXIS_PP, perm) if Pp > 1 else out
            return nxt, (out, aux)

        buf0 = jnp.zeros(h0.shape[1:], h0.dtype)
        _, (outs, auxs) = jax.lax.scan(tick, buf0, h0)

        # microbatch m leaves the last stage at tick m + Pp - 1
        valid_outs = outs[Pp - 1 : Pp - 1 + n_micro]
        hf = nn.layernorm_apply(lparams["ln_f"], valid_outs)
        logits = nn.dense_apply(lparams["head"], hf).astype(jnp.float32)
        # fused-or-plain NLL: on the sharded path the [n_micro, B_mb,
        # S_loc, V] logits are the largest live tensor per device
        from kungfu_tpu.ops.pallas.xent import token_nll

        nll = token_nll(logits, tgt_mb)
        nll_term = jnp.where(pp_idx == Pp - 1, nll, 0.0)

        # aux from ticks where this stage processed a real microbatch
        t_idx = jnp.arange(T)
        valid = (t_idx >= pp_idx) & (t_idx < pp_idx + n_micro)
        aux_term = jnp.sum(auxs * valid) / n_micro

        own = nll_term + MOE_AUX_COEF * aux_term
        return own, (nll_term, aux_term)

    def sync_grads(self, grads):
        plan = self.plan
        from kungfu_tpu.ops.schedules import all_reduce_scheduled

        if not self.fuse_grads:
            def f(g, kind):
                axes, denom_axes = _KIND_AXES[kind]
                g = all_reduce_scheduled(g, axes, op="sum",
                                         schedule=self.schedule)
                return g / _axis_prod(plan, denom_axes)

            return jax.tree_util.tree_map(f, grads, self.param_kinds)

        # bucketed: ONE collective per sync-kind (leaves of a kind share
        # reduce axes and denominator, so fusing them is exact) — the
        # reference's fuse/defuse bucketing, per mesh-axis group here
        from kungfu_tpu.ops.fuse import defuse, fuse

        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_k = jax.tree_util.tree_leaves(self.param_kinds)
        for kind in sorted(set(flat_k)):
            idxs = [i for i, k in enumerate(flat_k) if k == kind]
            buf, spec = fuse([flat_g[i] for i in idxs])
            axes, denom_axes = _KIND_AXES[kind]
            buf = all_reduce_scheduled(buf, axes, op="sum",
                                       schedule=self.schedule)
            buf = buf / _axis_prod(plan, denom_axes)
            for i, g in zip(idxs, defuse(buf, spec)):
                flat_g[i] = g
        return jax.tree_util.tree_unflatten(treedef, flat_g)

    # -- jitted step -------------------------------------------------------
    def _pure_dp(self) -> bool:
        """True when the mesh is data-parallel ONLY — the shape where
        the two-batch GNS pair is defined (each dp rank holds a full
        model replica, so "one rank's gradient" is a real small-batch
        gradient).  tp/pp/sp/expert sharding splits the model itself;
        those meshes publish per-kind norms only."""
        p = self.plan
        return (p.pp == 1 and p.sp == 1 and p.tp == 1
                and self.n_experts == 0)

    def _build_step(self, with_pulse: bool = False):
        plan = self.plan
        pspecs = self.param_specs
        batch_spec = P(AXIS_DP, AXIS_SP)
        kinds = sorted(set(jax.tree_util.tree_leaves(self.param_kinds)))
        all_axes = (AXIS_DP, AXIS_PP, AXIS_SP, AXIS_TP)
        pure_dp = self._pure_dp()

        def per_device(lparams, ids, targets):
            grad_fn = jax.value_and_grad(self._local_loss, has_aux=True)
            (own, (nll, aux)), grads = grad_fn(lparams, ids, targets)
            gl = jnp.float32(0.0)
            if with_pulse and pure_dp:
                # kf-pulse small-batch side: this rank's full-replica
                # gradient square norm, MEANed across dp peers (the
                # plane's only extra collective — one scalar)
                gl = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree_util.tree_leaves(grads))
                gl = jax.lax.pmean(gl, AXIS_DP)
            grads = self.sync_grads(grads)
            group_sq = {}
            if with_pulse:
                # per-kind |g|^2 of the POST-sync gradients: leaves of
                # a kind are replicated over its psum axes and sharded
                # over the rest, so a psum over (all - psum_axes)
                # reassembles the exact global square norm — scalar
                # collectives only, on 1-in-`every` steps
                flat_g = jax.tree_util.tree_leaves(grads)
                flat_k = jax.tree_util.tree_leaves(self.param_kinds)
                for kind in kinds:
                    s = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for g, k in zip(flat_g, flat_k) if k == kind)
                    shard_axes = tuple(a for a in all_axes
                                       if a not in _KIND_AXES[kind][0])
                    if shard_axes:
                        s = jax.lax.psum(s, shard_axes)
                    group_sq[kind] = s
            # report: gather the stage-masked terms into global means
            nll = jax.lax.pmean(
                jax.lax.psum(nll, AXIS_PP), (AXIS_DP, AXIS_SP, AXIS_TP)
            )
            aux = jax.lax.pmean(
                jax.lax.psum(aux, AXIS_PP), (AXIS_DP, AXIS_SP, AXIS_TP)
            )
            if with_pulse:
                return grads, nll, aux, group_sq, gl
            return grads, nll, aux

        out_specs = ((pspecs, P(), P(), {k: P() for k in kinds}, P())
                     if with_pulse else (pspecs, P(), P()))
        sharded = shard_map(
            per_device,
            mesh=self.mesh,
            in_specs=(pspecs, batch_spec, batch_spec),
            out_specs=out_specs,
            check_vma=False,
        )

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(state, batch):
            ids, targets = batch
            if with_pulse:
                grads, nll, aux, group_sq, gl = sharded(
                    state["params"], ids, targets)
            else:
                grads, nll, aux = sharded(state["params"], ids, targets)
            updates, opt_state = self.tx.update(grads, state["opt_state"], state["params"])
            params = optax.apply_updates(state["params"], updates)
            out = (
                {"params": params, "opt_state": opt_state, "step": state["step"] + 1},
                nll + MOE_AUX_COEF * aux,
            )
            if with_pulse:
                return out + (group_sq, gl)
            return out

        return step

    def step(self, state, batch) -> Tuple[Dict[str, Any], jnp.ndarray]:
        """One full training step; batch = (ids, targets) global [B, S]."""
        if self._step_fn is None:
            self._step_fn = self._build_step()
        ids, targets = batch
        bspec = NamedSharding(self.mesh, P(AXIS_DP, AXIS_SP))
        ids = jax.device_put(jnp.asarray(ids), bspec)
        targets = jax.device_put(jnp.asarray(targets), bspec)
        mon = self.pulse
        if mon is not None and mon.should_sample():
            if self._pulse_fn is None:
                # compiled on the first pulse step only (runs shorter
                # than KF_PULSE_EVERY never pay this compile)
                self._pulse_fn = self._build_step(with_pulse=True)
            new_state, loss, group_sq, gl = self._pulse_fn(
                state, (ids, targets))
            self._publish_pulse(mon, group_sq, gl, int(ids.shape[0]))
            return new_state, loss
        return self._step_fn(state, (ids, targets))

    def _publish_pulse(self, mon, group_sq, gl, global_batch: int) -> None:
        with timeline.span("pulse", "sync"):  # the host waits here
            group_sq = {k: float(v) for k, v in group_sq.items()}
            gl = float(gl)
        norms = {k: math.sqrt(max(0.0, v)) for k, v in group_sq.items()}
        with timeline.span("pulse", "publish"):
            if self._pure_dp():
                n = int(self.plan.dp)
                # sorted fold: the replayed sum must not depend on the
                # param-kind dict's insertion order (docs/determinism.md)
                gg = sum(group_sq[k] for k in sorted(group_sq))
                b_small = max(1, global_batch // max(1, n))
                mon.update(gl, gg, b_small, n, group_norms=norms)
            else:
                # sharded meshes: the GNS pair is undefined (no rank holds
                # a full small-batch gradient) — norms are still exact
                mon.publish_norms(norms)

    # -- losses without update (for tests) ---------------------------------
    def loss(self, state, batch) -> jnp.ndarray:
        """Global loss (nll + aux) without updating — test/eval helper."""
        pspecs = self.param_specs

        def per_device(lparams, ids, targets):
            _, (nll, aux) = self._local_loss(lparams, ids, targets)
            nll = jax.lax.pmean(jax.lax.psum(nll, AXIS_PP), (AXIS_DP, AXIS_SP, AXIS_TP))
            aux = jax.lax.pmean(jax.lax.psum(aux, AXIS_PP), (AXIS_DP, AXIS_SP, AXIS_TP))
            return nll + MOE_AUX_COEF * aux

        f = shard_map(
            per_device,
            mesh=self.mesh,
            in_specs=(pspecs, P(AXIS_DP, AXIS_SP), P(AXIS_DP, AXIS_SP)),
            out_specs=P(),
            check_vma=False,
        )
        ids, targets = batch
        bspec = NamedSharding(self.mesh, P(AXIS_DP, AXIS_SP))
        ids = jax.device_put(jnp.asarray(ids), bspec)
        targets = jax.device_put(jnp.asarray(targets), bspec)
        return jax.jit(f)(state["params"], ids, targets)


def dp_train_step(
    loss_fn,
    tx,
    comm,
    replicated_params: bool = True,
    has_aux: bool = False,
    donate: bool = False,
    zero_stage: Optional[int] = None,
    plan: Optional[ParallelPlan] = None,
):
    """Pure data-parallel training step over a
    :class:`~kungfu_tpu.comm.device.Communicator` mesh.

    ``zero_stage`` (1/2/3) routes to the weight-update-sharded family
    (:func:`kungfu_tpu.parallel.zero.zero_train_step`): ``tx`` is then
    the **inner elementwise** optax transform (the ZeRO step owns the
    gradient collective itself — do not wrap in ``synchronous_sgd``) and
    the return value is a :class:`~kungfu_tpu.parallel.zero.ZeroStep`,
    which still unpacks as ``step, init_opt = ...`` for stages 1/2.

    The DP-only analog of :class:`ShardedTrainer` (and of the reference's
    whole training model — S-SGD over gradient buffers): ``loss_fn(params,
    batch) -> scalar`` runs per device on the batch shard, ``tx`` is any
    :mod:`kungfu_tpu.optimizers` transform bound to ``comm.axis`` (it does
    the gradient/weight collective).

    ``replicated_params=True`` (S-SGD/GNS/variance: psummed grads keep
    params identical) holds one replicated copy.  ``False`` (SMA/
    AdaptiveSGD: each replica owns diverging weights) expects params and
    opt_state **stacked** on a leading ``comm.size`` axis.

    ``has_aux=True`` threads non-trained model state (BatchNorm running
    stats): ``loss_fn(params, aux, batch) -> (loss, new_aux)``; the new
    aux is pmean'd over the mesh so replicas stay identical, and the step
    signature becomes ``step(params, aux, opt_state, batch) -> (params,
    aux, opt_state, loss)``.

    ``donate=True`` donates the train-state buffers to XLA (in-place
    update — halves HBM traffic/footprint for the state); the caller must
    not reuse the old params/opt_state after the call.

    Returns ``step(params[, aux], opt_state, batch) -> (params[, aux],
    opt_state, loss)`` jitted over the mesh; ``batch`` leading axis must
    be divisible by ``comm.size``.
    """
    if plan is not None:
        # the ParallelPlan route: this entrypoint is the pure-DP one —
        # other axes have their own consumers (ShardedTrainer for the
        # in-mesh 4-D step, parallel/pp.HostPipeline for cross-DCN pp)
        if plan.tp != 1 or plan.pp != 1 or plan.sp != 1:
            raise ValueError(
                f"dp_train_step is the dp-only entrypoint but the plan "
                f"carries tp={plan.tp} pp={plan.pp} sp={plan.sp} — use "
                "ShardedTrainer (one mesh) or HostPipeline (cross-DCN)")
        if zero_stage is not None and zero_stage != plan.zero_stage:
            raise ValueError(
                f"zero_stage={zero_stage} disagrees with "
                f"plan.zero_stage={plan.zero_stage}")
        if not plan.zero_stage and plan.collective_schedule != "psum":
            # the replicated dp step reduces with psum/pmean only —
            # silently ignoring the requested arm would defeat the
            # ParallelPlan contract (entrypoints CONSUME the plan)
            raise ValueError(
                f"dp_train_step's replicated step has no "
                f"{plan.collective_schedule!r} arm — use ShardedTrainer "
                "(in-mesh schedule arms) or a ZeRO stage (bucket "
                "schedules)")
        zero_stage = plan.zero_stage or None
    if zero_stage is not None:
        if has_aux or not replicated_params:
            raise ValueError(
                "zero_stage composes with the plain replicated-params, "
                "no-aux step only (the sharded update is elementwise over "
                "the fused flat buffer)")
        from kungfu_tpu.parallel.zero import zero_train_step

        # zero's bucket collectives speak FLAT_SCHEDULES ("lax" |
        # "pallas_ring"); the plan's allreduce arm maps onto them —
        # pallas_ring passes through, everything else is the lax default
        zsched = ("pallas_ring"
                  if plan is not None
                  and plan.collective_schedule == "pallas_ring" else "lax")
        return zero_train_step(loss_fn, tx, comm, stage=zero_stage,
                               donate=donate, schedule=zsched)
    mesh, axis = comm.mesh, comm.axis
    pspec = P() if replicated_params else P(axis)

    def body(params, aux, opt_state, batch):
        if has_aux:
            (loss, new_aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, aux, batch
            )
            # per-shard batch statistics diverge across replicas; average
            # them like the gradients so the replicated copy stays in sync
            new_aux = jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, axis)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                else a,
                new_aux,
            )
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            new_aux = aux
        # (tx's own gradient collective names itself grad_sync inside)
        with jax.named_scope("optimizer"):
            updates, new_state = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
        return new_params, new_aux, new_state, jax.lax.pmean(loss, axis)

    def body_stacked(params, aux, opt_state, batch):
        # strip/restore the per-replica leading axis around the same body
        squeeze = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
        unsqueeze = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
        p, a, s, l = body(squeeze(params), squeeze(aux), squeeze(opt_state), batch)
        return unsqueeze(p), unsqueeze(a), unsqueeze(s), l

    def batch_spec(x):
        return P(axis) if hasattr(x, "ndim") and x.ndim > 0 else P()

    inner = body if replicated_params else body_stacked

    def step4(params, aux, opt_state, batch):
        bspecs = jax.tree_util.tree_map(batch_spec, batch)
        f = shard_map(
            inner,
            mesh=mesh,
            in_specs=(pspec, pspec, pspec, bspecs),
            out_specs=(pspec, pspec, pspec, P()),
            check_vma=False,
        )
        return f(params, aux, opt_state, batch)

    if has_aux:
        donate_args = (0, 1, 2) if donate else ()
        return jax.jit(step4, donate_argnums=donate_args)

    def step3(params, opt_state, batch):
        p, _, s, l = step4(params, (), opt_state, batch)
        return p, s, l

    donate_args = (0, 1) if donate else ()
    base = jax.jit(step3, donate_argnums=donate_args)

    # -- kf-pulse: GNS/variance sampling on the replicated no-aux step --
    # replicated_params=False trains intentionally DIVERGED replicas
    # (SMA/AdaptiveSGD) — "one rank's gradient vs the mean" is not a
    # small/large-batch pair there, so only the S-SGD shape samples.
    from kungfu_tpu.monitor import pulse as pulselib

    mon = pulselib.PulseMonitor.from_env() if replicated_params else None
    if mon is None:
        return base

    from kungfu_tpu import ops
    from kungfu_tpu.ops.monitor import _sq_norm

    def body_pulse(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # small-batch side: per-rank square norm, MEANed across peers
        # (one extra scalar collective)
        g_local_sq = jax.lax.pmean(_sq_norm(grads), axis)
        # large-batch side: the mean gradient.  `tx` performs the
        # identical mean-allreduce inside update(); when the ops match
        # XLA CSEs the two psums into one, and this program only runs
        # on 1-in-`every` steps regardless
        with jax.named_scope("grad_sync"):
            avg = ops.group_all_reduce(grads, axis, op="mean")
        g_global_sq = _sq_norm(avg)
        with jax.named_scope("optimizer"):
            updates, new_state = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
        return (new_params, new_state, jax.lax.pmean(loss, axis),
                g_local_sq, g_global_sq)

    def pulse_outer(params, opt_state, batch):
        bspecs = jax.tree_util.tree_map(batch_spec, batch)
        f = shard_map(
            body_pulse,
            mesh=mesh,
            in_specs=(pspec, pspec, bspecs),
            out_specs=(pspec, pspec, P(), P(), P()),
            check_vma=False,
        )
        return f(params, opt_state, batch)

    # compiled lazily on the first pulse step (never, for runs shorter
    # than KF_PULSE_EVERY)
    pulse_jit = jax.jit(pulse_outer, donate_argnums=donate_args)
    n = int(comm.size)

    def stepped(params, opt_state, batch):
        if not mon.should_sample():
            with timeline.span("step", "train", pulse=0):
                with timeline.span("step", "dispatch"):
                    return base(params, opt_state, batch)
        with timeline.span("step", "train", pulse=1):
            with timeline.span("step", "dispatch"):
                p, s, loss, gl, gg = pulse_jit(params, opt_state, batch)
            with timeline.span("pulse", "sync"):
                gl, gg = float(gl), float(gg)  # the host waits here
            leaves = jax.tree_util.tree_leaves(batch)
            b_small = (max(1, int(leaves[0].shape[0]) // n)
                       if (leaves and n) else 1)
            with timeline.span("pulse", "publish"):
                mon.update(gl, gg, b_small, n,
                           group_norms={"flat": max(0.0, gg) ** 0.5})
            return p, s, loss

    stepped.pulse = mon  # introspection hook for tests/tools
    # the two jitted programs behind the wrapper, for callers that need
    # what only a jit object has (.lower, cost analysis, tracing the step
    # inside another compiled program)
    stepped.base = base
    stepped.pulse_step = pulse_jit
    return stepped


def stack_for_replicas(tree, n: int):
    """Tile a pytree onto a leading replica axis (for
    ``dp_train_step(replicated_params=False)``)."""
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (n,) + jnp.shape(a)), tree
    )
