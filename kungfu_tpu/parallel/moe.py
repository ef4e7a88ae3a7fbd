"""Mixture-of-experts with expert parallelism over a mesh axis.

Switch-style top-1 routing with capacity dropping, experts sharded one
group per ``ep`` rank, tokens moved to their expert's owner and back via
``lax.all_to_all`` (the TPU-idiomatic EP data path — a single fused ICI
all-to-all each way, instead of point-to-point sends).

Gradients: ``all_to_all`` transposes to itself, so expert-weight gradients
accumulate contributions from every rank's tokens without any explicit
cross-rank sync over ``ep``; see
:meth:`kungfu_tpu.parallel.train.ShardedTrainer.sync_grads` for the axis
bookkeeping.

Shapes (per device): tokens ``[T, D]``; global expert count ``E`` must be
divisible by the axis size; each rank owns ``E_local = E / ep`` experts
stacked as ``w_in [E_local, D, F]``, ``w_out [E_local, F, D]``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.lax import axis_size

from kungfu_tpu.models import nn


def moe_init(key, n_experts_local: int, d_model: int, d_ff: int, n_experts_global: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": nn.dense_init(k1, d_model, n_experts_global, use_bias=False),
        "w_in": nn.glorot_uniform(k2, (n_experts_local, d_model, d_ff)),
        "w_out": nn.glorot_uniform(k3, (n_experts_local, d_ff, d_model)),
    }


def moe_apply(
    params,
    x,
    axis: Optional[str],
    n_experts_global: int,
    capacity_factor: float = 1.25,
    dtype=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [..., D] local tokens → (y [..., D], aux_loss scalar).

    ``axis=None`` runs all experts locally (no EP) — the single-device
    reference used by tests.  ``aux_loss`` is the switch load-balancing
    term E * Σ_e f_e · p̄_e.
    """
    orig_shape = x.shape
    D = orig_shape[-1]
    xt = x.reshape(-1, D)
    T = xt.shape[0]
    E = n_experts_global
    ep = 1 if axis is None else axis_size(axis)

    logits = (xt.astype(jnp.float32) @ params["gate"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # [T, E]
    gate = jnp.max(probs, axis=-1)
    expert = jnp.argmax(probs, axis=-1)  # [T]

    cap = int(max(1, -(-T * capacity_factor // E)))
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # [T, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot  # 1-based slot per token
    keep = (pos > 0) & (pos <= cap)
    slot = jnp.where(keep, pos - 1, 0).astype(jnp.int32)
    dispatch = (
        onehot * keep
    )[:, :, None] * jax.nn.one_hot(jnp.max(slot, axis=-1), cap, dtype=jnp.float32)[:, None, :]
    combine = dispatch * gate[:, None, None]  # [T, E, C]

    # load-balance aux (computed on the full pre-drop distribution)
    frac_tokens = jnp.mean(onehot, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)

    expert_in = jnp.einsum("tec,td->ecd", dispatch, xt.astype(jnp.float32))  # [E, C, D]
    if axis is not None and ep > 1:
        # [E, C, D] -> each rank keeps its E_local experts, gathering every
        # rank's C slots for them: [E_local, ep*C, D]
        expert_in = jax.lax.all_to_all(
            expert_in, axis, split_axis=0, concat_axis=1, tiled=True
        )
    cd = dtype or x.dtype
    h = jnp.einsum("egd,edf->egf", expert_in.astype(cd), params["w_in"].astype(cd))
    h = nn.gelu(h)
    expert_out = jnp.einsum("egf,efd->egd", h, params["w_out"].astype(cd)).astype(
        jnp.float32
    )
    if axis is not None and ep > 1:
        expert_out = jax.lax.all_to_all(
            expert_out, axis, split_axis=1, concat_axis=0, tiled=True
        )
    y = jnp.einsum("tec,ecd->td", combine, expert_out)
    return y.reshape(orig_shape).astype(x.dtype), aux
