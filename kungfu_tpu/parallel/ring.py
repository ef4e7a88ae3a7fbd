"""Ring attention: exact attention over a sequence-sharded mesh axis.

Long-context support the TPU way (the reference has no attention at all,
SURVEY §5.7; this is the framework's sequence/context-parallel subsystem):
Q stays local, K/V blocks rotate around the ``sp`` ring via
``lax.ppermute`` while a streaming (online-softmax) accumulator folds each
block in — traffic rides the ICI ring, and compute/communication overlap
is XLA's job (each round's matmul hides the next block's permute).  On
TPU each round's block runs through the Pallas flash kernel and rounds
merge by lse (``block_impl`` below), taking per-device attention memory
from O((S/sp)²) scores to O(kernel block); off-TPU a jnp online-softmax
fold computes the same thing.

Differentiable: the backward pass is autodiff through the scan — ppermute
transposes to the inverse rotation, so cotangents counter-rotate around the
same ring (this *is* the ring-attention backward schedule).

Must run inside ``shard_map`` with ``axis`` a live mesh axis name; with
``sp == 1`` it degenerates to one masked flash-style block and is the
single-device attention path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.lax import axis_size


def ring_attention(q, k, v, causal: bool = True, axis: str = "sp",
                   block_impl: str = "auto",
                   kv_gather: Optional[str] = None):
    """q, k, v: [B, H, S_local, D] (sequence axis sharded over ``axis``).

    Returns [B, H, S_local, D] — the exact softmax attention output as if
    the full sequence were on one device.

    ``block_impl`` picks the per-round block computation:

    * ``einsum`` — jnp online-softmax fold (materializes one [S_local,
      S_local] f32 logits tile per round);
    * ``flash`` — the Pallas kernel via
      :func:`~kungfu_tpu.ops.pallas.attention.flash_attention_with_lse`,
      merged across rounds by lse: per-device memory drops to O(block)
      and causal runs *skip* fully-masked rounds' compute entirely
      (``lax.switch`` — the einsum path pays for them and discards);
    * ``auto`` — flash on TPU, einsum elsewhere (interpret-mode Pallas
      is too slow for the CPU test cluster).

    ``kv_gather`` swaps the n-round K/V *rotation* for ONE ring
    all-gather up front (:func:`kungfu_tpu.ops.schedules.
    all_gather_flat` — pass ``"pallas_ring"`` to ride the ICI kernels of
    :mod:`kungfu_tpu.ops.pallas.collectives`, or ``"lax"`` for the
    primitive): n ppermute program points collapse into one collective
    whose backward is the matching reduce-scatter of dK/dV (the gather
    kernel's custom vjp).  Trades the rotation's O(S_local²) working set
    for the gathered O(S_local · S_global) block — the short-sequence /
    bandwidth-rich regime; ``None`` (default) keeps the rotation.
    """
    if block_impl not in ("auto", "flash", "einsum"):
        raise ValueError(f"unknown block_impl {block_impl!r}")
    if kv_gather is not None:
        if block_impl == "flash":
            # the gathered path computes one masked einsum block — an
            # explicit flash request would be silently downgraded to the
            # O(S_local * S_global) logits tile the kernel exists to
            # avoid; refuse instead (auto/einsum opt in knowingly)
            raise ValueError(
                "kv_gather is einsum-block attention and cannot honor "
                "block_impl='flash'; use the ppermute rotation "
                "(kv_gather=None) for the flash path")
        from kungfu_tpu.ops.schedules import FLAT_SCHEDULES

        if kv_gather not in FLAT_SCHEDULES:
            raise ValueError(
                f"unknown kv_gather {kv_gather!r}; one of {FLAT_SCHEDULES}"
                " (or None for the ppermute rotation)")
        return _ring_kv_gather(q, k, v, causal, axis, kv_gather)
    if block_impl == "flash" or (
        block_impl == "auto" and jax.default_backend() == "tpu"
    ):
        return _ring_flash(q, k, v, causal, axis)
    return _ring_einsum(q, k, v, causal, axis)


def _ring_kv_gather(q, k, v, causal: bool, axis: str, schedule: str):
    """Gathered-K/V block attention: one ring all-gather of K and V over
    ``axis``, then a single masked online-softmax block per device.
    Exact — global causal positions mask the logits — and
    differentiable: the gather's transpose reduce-scatters dK/dV back to
    their owners (with ``schedule="pallas_ring"`` that is the ring
    kernel's custom vjp)."""
    from kungfu_tpu.ops.schedules import all_gather_flat

    n_sp = axis_size(axis)
    my_blk = jax.lax.axis_index(axis)
    B, H, S, D = q.shape

    def gather(t):
        flat = all_gather_flat(t.reshape(-1), [axis], schedule=schedule)
        # mesh-major rows = ring order: device j's [B, H, S, D] block
        return jnp.moveaxis(
            flat.reshape((n_sp,) + t.shape), 0, 2
        ).reshape(B, H, n_sp * S, D)

    kf = gather(k).astype(jnp.float32)
    vf = gather(v).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale, kf)
    if causal:
        q_pos = my_blk * S + jnp.arange(S)
        k_pos = jnp.arange(n_sp * S)
        mask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    m = jnp.maximum(m, -1e30)  # fully-masked rows stay finite
    p = jnp.exp(logits - m)
    if causal:
        p = jnp.where(mask, p, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return (out / denom).astype(q.dtype)


def _ring_flash(q, k, v, causal: bool, axis: str):
    """Flash-block ring: each round folds one rotating K/V block through
    the Pallas kernel; blocks merge by the standard online-softmax
    combine over (out, lse)."""
    from kungfu_tpu.ops.pallas._sharding import match_vma
    from kungfu_tpu.ops.pallas.attention import flash_attention_with_lse

    n_sp = axis_size(axis)
    my_blk = jax.lax.axis_index(axis)
    B, H, S, D = q.shape
    q3 = q.reshape(B * H, S, D)
    ring_vma = frozenset({axis})

    def _full(kb, vb):
        return flash_attention_with_lse(q3, kb, vb, causal=False)

    def _diag(kb, vb):
        return flash_attention_with_lse(q3, kb, vb, causal=True)

    def _masked(kb, vb):
        # future block under causal: zero contribution (lse = -inf);
        # match_vma gives all switch branches one output type
        return (
            match_vma(jnp.zeros_like(q3), ring_vma),
            match_vma(jnp.full((B * H, S), -jnp.inf, jnp.float32), ring_vma),
        )

    def fold(carry, _):
        kv, blk, m, l, acc = carry
        kb, vb = kv
        kb3 = kb.reshape(B * H, S, D)
        vb3 = vb.reshape(B * H, S, D)
        if causal:
            branch = jnp.where(
                blk > my_blk, 0, jnp.where(blk == my_blk, 2, 1)
            )
        else:
            branch = jnp.int32(1)
        out_i, lse_i = jax.lax.switch(
            branch, [_masked, _full, _diag], kb3, vb3
        )
        m_new = jnp.maximum(m, lse_i)
        # -inf - -inf is NaN: m is -inf before the first contributing
        # round (and m_new stays -inf if that round is masked too, which
        # a start-offset refactor could produce), so guard the operands,
        # not the result — a masked/virgin term must contribute exactly 0
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - jnp.where(jnp.isneginf(m_new), 0.0, m_new)))
        w = jnp.where(jnp.isneginf(lse_i), 0.0, jnp.exp(lse_i - jnp.where(jnp.isneginf(m_new), 0.0, m_new)))
        l = l * corr + w
        acc = acc * corr[..., None] + out_i.astype(jnp.float32) * w[..., None]
        perm = [((j + 1) % n_sp, j) for j in range(n_sp)]
        kv = jax.tree_util.tree_map(
            lambda t: jax.lax.ppermute(t, axis, perm), (kb, vb)
        )
        return (kv, (blk + 1) % n_sp, m_new, l, acc), None

    m0 = match_vma(jnp.full((B * H, S), -jnp.inf, jnp.float32), ring_vma)
    l0 = match_vma(jnp.zeros((B * H, S), jnp.float32), ring_vma)
    acc0 = match_vma(jnp.zeros((B * H, S, D), jnp.float32), ring_vma)
    (_, _, _, l, acc), _ = jax.lax.scan(
        fold, ((k, v), my_blk, m0, l0, acc0), None, length=n_sp
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype).reshape(B, H, S, D)


def _ring_einsum(q, k, v, causal: bool, axis: str):
    """jnp online-softmax ring fold (the original implementation)."""
    n_sp = axis_size(axis)
    my_blk = jax.lax.axis_index(axis)
    B, H, S, D = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    qf = q.astype(jnp.float32) * scale

    q_pos = my_blk * S + jnp.arange(S)  # global positions of local queries

    def fold(carry, _):
        kv, blk, m, l, acc = carry
        kb, vb = kv
        logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kb.astype(jnp.float32))
        if causal:
            k_pos = blk * S + jnp.arange(S)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((S, S), dtype=bool)
        m_new = jnp.maximum(m, jnp.max(jnp.where(mask, logits, -jnp.inf), axis=-1))
        # clamp so fully-masked rounds (future blocks under causal) keep
        # m finite and contribute exactly zero
        m_new = jnp.maximum(m_new, -1e30)
        p = jnp.where(mask, jnp.exp(logits - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32)
        )
        # rotate K/V: receive the next block from the ring neighbour
        perm = [((j + 1) % n_sp, j) for j in range(n_sp)]
        kv = jax.tree_util.tree_map(
            lambda t: jax.lax.ppermute(t, axis, perm), (kb, vb)
        )
        return (kv, (blk + 1) % n_sp, m_new, l, acc), None

    def vary(x):
        # mark the accumulators as varying over the ring axis so the scan
        # carry type matches (varying-manual-axes typing)
        return jax.lax.pcast(x, (axis,), to="varying")

    m0 = vary(jnp.full((B, H, S), -jnp.inf, jnp.float32))
    l0 = vary(jnp.zeros((B, H, S), jnp.float32))
    acc0 = vary(jnp.zeros((B, H, S, D), jnp.float32))
    (_, _, _, l, acc), _ = jax.lax.scan(
        fold, ((k, v), my_blk, m0, l0, acc0), None, length=n_sp
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def make_ring_attn(axis: str = "sp", block_impl: str = "auto"):
    """Adapter matching the ``attn_fn(q, k, v, causal)`` slot of
    :meth:`kungfu_tpu.models.transformer.Transformer.apply`."""

    def attn(q, k, v, causal):
        return ring_attention(
            q, k, v, causal=causal, axis=axis, block_impl=block_impl
        )

    return attn
