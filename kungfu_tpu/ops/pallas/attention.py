"""Flash attention as a Pallas TPU kernel.

Forward: a (batch*head, q-block, kv-block) grid; each step consumes ONE
[block_k, D] K/V tile, so VMEM residency is O(block) regardless of
sequence length (round-1 advisor finding: whole-sequence K/V BlockSpecs
spilled VMEM at long S, defeating the kernel's purpose).  The
online-softmax state (m/l running max/sum and the f32 output
accumulator) lives in VMEM scratch carried across the innermost grid
dimension; scores live one [block_q, block_k] tile at a time, feeding
the MXU via ``jnp.dot(..., preferred_element_type=f32)``.  Causal
masking skips all-masked kv blocks twice over: ``pl.when`` skips their
compute, and the K/V index maps clamp to the last needed block so
Pallas's revisit-elision skips their HBM→VMEM copies too — causal
attention does ~half the FLOPs *and* ~half the K/V traffic.

Backward (round 3): two Pallas kernels using the saved logsumexp rows —
the standard flash-attention recomputation

    P  = exp(Q K^T * scale - L)        (recomputed per tile)
    dV = P^T dO
    dP = dO V^T
    dS = P * (dP - rowsum(dO * O))
    dQ = dS K * scale ;  dK = dS^T Q * scale

split the way TPU memory wants it: a **dQ kernel** on a (bh, q-block,
kv-block) grid accumulating dQ in VMEM scratch while K/V tiles stream,
and a **dK/dV kernel** on a (bh, kv-block, q-block) grid accumulating
dK/dV while Q/dO/L/delta tiles stream — both O(block) VMEM, both with
the same causal skip + index-clamp revisit-elision as the forward (a
causal backward does ~half the FLOPs and ~half the tile traffic).  The
blocked-jnp backward is kept as the non-TPU fallback and as the
reference implementation the kernel tests compare against.  The whole
op is a ``custom_vjp`` — autodiff through the Pallas forward would
instead save every tile.

The reference framework has no attention at all (SURVEY §2.4/§5.7 — it
moves gradient buffers only); this kernel is part of the TPU build's
long-context subsystem together with :mod:`kungfu_tpu.parallel.ring`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: caps for the adaptive default block shape (see :func:`_default_blocks`).
#: A round-3 interleaved min-of-8 sweep on v5e (benchmarks/flash_sweep.py,
#: B4 H8 S2048 D128 causal) is monotonic in block_k: (128,128) 2.60 ms →
#: (256,1024) 0.34 ms fwd (7.7x, 101 TFLOP/s).  Large K/V tiles amortize
#: the per-grid-step overhead and keep the MXU fed; 16 MB VMEM fits
#: (256,1024) at D=128 with ~2.7 MB to spare.
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 1024
_NEG_INF = -1e30
#: per-row scalars (lse, delta) cross the pallas_call boundary replicated
#: across one full lane width — Mosaic's tiling only accepts (8k, 128)
#: tiles, so a bare row vector is not a legal block shape on TPU
_LANES = 128


from kungfu_tpu.ops.pallas._sharding import match_vma as _match_vma
from kungfu_tpu.ops.pallas._sharding import vma_of as _vma
from kungfu_tpu.ops.pallas._sharding import sds as _sds


def _causal_hi(qi, block_q, block_k):
    """Index of the LAST kv block a causal q-block ``qi`` attends to."""
    return jax.lax.div((qi + 1) * block_q + block_k - 1, block_k) - 1


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, m_s, l_s, acc_s, *,
                scale, causal, seq_len, block_q, block_k):
    """One (batch*head, q-block, kv-block) grid step; m/l/acc scratch
    carries online-softmax state across the kv dimension."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)

    if causal:
        j_hi = jnp.minimum(_causal_hi(qi, block_q, block_k), n_k - 1)
    else:
        j_hi = n_k - 1

    @pl.when(kj == 0)
    def _():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    @pl.when(kj <= j_hi)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale  # [block_q, D]
        kb = k_ref[0]  # [block_k, D]
        vb = v_ref[0]
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = k_pos < seq_len  # tail padding
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, _NEG_INF)
        m = m_s[:]  # [block_q, 1] (keepdims — Mosaic wants 2D)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # fully-masked rows (can only happen on padded tails) contribute 0
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_s[:] = l_s[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[:] = acc_s[:] * corr + jnp.dot(
            p.astype(v_ref.dtype), vb, preferred_element_type=jnp.float32
        )
        m_s[:] = m_new

    @pl.when(kj == j_hi)
    def _():
        l_safe = jnp.maximum(l_s[:], 1e-30)
        o_ref[0] = (acc_s[:] / l_safe).astype(o_ref.dtype)
        # logsumexp rows, saved for the backward recomputation.  Stored
        # lane-replicated [block_q, LANES]: Mosaic requires output tiles
        # whose last two dims are (8k, 128) — a [block_q] row vector is
        # not a legal tile, a lane-broadcast one is
        l_ref[0] = jnp.broadcast_to(
            m_s[:] + jnp.log(l_safe), (l_ref.shape[1], l_ref.shape[2])
        )


def _fwd_call(q, k, v, causal, block_q, block_k, interpret):
    """q,k,v: [BH, S, D] → (out [BH, S, D], lse [BH, S])."""
    bh, s, d = q.shape
    s_pad = ((s + block_q - 1) // block_q) * block_q
    s_pad = ((s_pad + block_k - 1) // block_k) * block_k
    if s_pad != s:
        pad = [(0, 0), (0, s_pad - s), (0, 0)]
        q, k, v = (jnp.pad(t, pad) for t in (q, k, v))
    n_k = s_pad // block_k
    grid = (bh, s_pad // block_q, n_k)
    kernel = functools.partial(
        _fwd_kernel,
        scale=1.0 / (d ** 0.5),
        causal=causal,
        seq_len=s,
        block_q=block_q,
        block_k=block_k,
    )

    if causal:
        # clamp the kv index for all-masked steps: the block index then
        # repeats, so Pallas elides the HBM→VMEM copy for skipped blocks
        def kv_index(b, i, j):
            return (b, jnp.minimum(j, _causal_hi(i, block_q, block_k)), 0)
    else:
        def kv_index(b, i, j):
            return (b, j, 0)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _sds((bh, s_pad, d), q.dtype, vma=_vma(q, k, v)),
            _sds((bh, s_pad, _LANES), jnp.float32, vma=_vma(q, k, v)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out[:, :s], lse[:, :s, 0]


def _bwd_blocked(q, k, v, out, lse, dout, causal, block_k, delta=None):
    """Blocked flash backward in jnp; [BH, S, D] operands.  ``delta``
    defaults to rowsum(dO·O); callers with an lse cotangent pass the
    shifted value (see ``_flash_pair_bwd``)."""
    bh, s, d = q.shape
    scale = 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32)
    of = out.astype(jnp.float32)
    dof = dout.astype(jnp.float32)
    if delta is None:
        delta = jnp.sum(dof * of, axis=-1)  # [BH, S]

    s_pad = ((s + block_k - 1) // block_k) * block_k
    if s_pad != s:
        pad3 = [(0, 0), (0, s_pad - s), (0, 0)]
        k = jnp.pad(k, pad3)
        v = jnp.pad(v, pad3)
    n_blk = s_pad // block_k
    kf = k.astype(jnp.float32).reshape(bh, n_blk, block_k, d)
    vf = v.astype(jnp.float32).reshape(bh, n_blk, block_k, d)

    q_pos = jnp.arange(s)

    def fold(dq, blk):
        j, kb, vb = blk  # kb/vb: [BH, block_k, D]
        s_blk = jnp.einsum("bqd,bkd->bqk", qf, kb) * scale
        k_pos = j * block_k + jnp.arange(block_k)
        mask = k_pos[None, :] < s
        if causal:
            mask = jnp.logical_and(mask, q_pos[:, None] >= k_pos[None, :])
        p = jnp.where(mask, jnp.exp(s_blk - lse[..., None]), 0.0)  # [BH,S,bk]
        dp = jnp.einsum("bqd,bkd->bqk", dof, vb)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, kb) * scale
        dk_b = jnp.einsum("bqk,bqd->bkd", ds, qf) * scale
        dv_b = jnp.einsum("bqk,bqd->bkd", p, dof)
        return dq, (dk_b, dv_b)

    dq0 = _match_vma(jnp.zeros((bh, s, d), jnp.float32), _vma(q, k, v, dout))
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        fold, dq0, (jnp.arange(n_blk), kf.transpose(1, 0, 2, 3), vf.transpose(1, 0, 2, 3))
    )
    dk = dk_blocks.transpose(1, 0, 2, 3).reshape(bh, s_pad, d)[:, :s]
    dv = dv_blocks.transpose(1, 0, 2, 3).reshape(bh, s_pad, d)[:, :s]
    return dq.astype(q.dtype), dk.astype(q.dtype), dv.astype(q.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_s, *, scale, causal, seq_len, block_q, block_k):
    """dQ on a (bh, q-block, kv-block) grid; K/V stream along the inner
    dim, dQ accumulates in VMEM scratch (mirror of the forward)."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)
    if causal:
        j_hi = jnp.minimum(_causal_hi(qi, block_q, block_k), n_k - 1)
    else:
        j_hi = n_k - 1

    @pl.when(kj == 0)
    def _():
        acc_s[:] = jnp.zeros_like(acc_s)

    @pl.when(kj <= j_hi)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale      # [bq, D]
        kb = k_ref[0]                                  # [bk, D]
        vb = v_ref[0]
        do = do_ref[0].astype(jnp.float32)             # [bq, D]
        lse = lse_ref[0][:, :1]                        # [bq, 1] (lane 0)
        delta = delta_ref[0][:, :1]                    # [bq, 1]
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = k_pos < seq_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)     # [bq, bk]
        dp = jnp.dot(do.astype(vb.dtype), vb.T,
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        acc_s[:] = acc_s[:] + jnp.dot(
            ds.astype(kb.dtype), kb, preferred_element_type=jnp.float32
        ) * scale

    @pl.when(kj == j_hi)
    def _():
        dq_ref[0] = acc_s[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_s, dv_s, *, scale, causal, seq_len,
                    block_q, block_k):
    """dK/dV on a (bh, kv-block, q-block) grid; Q/dO/L/delta stream along
    the inner dim, dK/dV accumulate in VMEM scratch."""
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)
    if causal:
        # first q block that attends to kv block kj
        i_lo = jax.lax.div(kj * block_k, block_q)
    else:
        i_lo = 0

    @pl.when(qi == 0)
    def _():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    @pl.when(qi >= i_lo)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale       # [bq, D]
        kb = k_ref[0]                                   # [bk, D]
        vb = v_ref[0]
        do = do_ref[0].astype(jnp.float32)              # [bq, D]
        lse = lse_ref[0][:, :1]                         # [bq, 1] (lane 0)
        delta = delta_ref[0][:, :1]
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = k_pos < seq_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)      # [bq, bk]
        dv_s[:] = dv_s[:] + jnp.dot(
            p.astype(do_ref.dtype).T, do.astype(do_ref.dtype),
            preferred_element_type=jnp.float32,
        )
        dp = jnp.dot(do.astype(vb.dtype), vb.T,
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        # q already carries `scale`, so dS^T (q*scale) == dK
        dk_s[:] = dk_s[:] + jnp.dot(
            ds.astype(q_ref.dtype).T, q.astype(q_ref.dtype),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == n_q - 1)
    def _():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, out, lse, dout, causal, block_q, block_k, interpret,
                delta=None):
    """Pallas backward: dq via a kv-streaming kernel, dk/dv via a
    q-streaming kernel; [BH, S, D] operands.  ``delta`` as in
    :func:`_bwd_blocked`."""
    bh, s, d = q.shape
    scale = 1.0 / (d ** 0.5)
    if delta is None:
        delta = jnp.sum(
            dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
        )  # [BH, S]

    s_pad = ((s + block_q - 1) // block_q) * block_q
    s_pad = ((s_pad + block_k - 1) // block_k) * block_k
    if s_pad != s:
        pad3 = [(0, 0), (0, s_pad - s), (0, 0)]
        q, k, v, dout = (jnp.pad(t, pad3) for t in (q, k, v, dout))
        # padded q rows: lse=+inf makes their P rows exp(s - inf) = 0
        lse = jnp.pad(lse, [(0, 0), (0, s_pad - s)], constant_values=1e30)
        delta = jnp.pad(delta, [(0, 0), (0, s_pad - s)])
    n_q = s_pad // block_q
    n_k = s_pad // block_k

    # per-row scalars enter the kernels lane-replicated (see _LANES)
    lse = jnp.broadcast_to(lse[..., None], (bh, s_pad, _LANES))
    delta = jnp.broadcast_to(delta[..., None], (bh, s_pad, _LANES))

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0))
    if causal:
        def kv_index(b, i, j):
            return (b, jnp.minimum(j, _causal_hi(i, block_q, block_k)), 0)
    else:
        def kv_index(b, i, j):
            return (b, j, 0)
    kv_spec = pl.BlockSpec((1, block_k, d), kv_index)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, seq_len=s,
            block_q=block_q, block_k=block_k,
        ),
        grid=(bh, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec],
        out_shape=[_sds((bh, s_pad, d), q.dtype,
                                        vma=_vma(q, k, v, dout))],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, dout, lse, delta)[0]

    # dk/dv grid: (bh, kv-block, q-block); clamp the q index upward for
    # causal so all-masked q blocks repeat their predecessor's tile and
    # Pallas elides the copies
    if causal:
        def q_index(b, j, i):
            return (b, jnp.maximum(i, jax.lax.div(j * block_k, block_q)), 0)
    else:
        def q_index(b, j, i):
            return (b, i, 0)
    qrow_index = q_index

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, seq_len=s,
            block_q=block_q, block_k=block_k,
        ),
        grid=(bh, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_q, _LANES), qrow_index),
            pl.BlockSpec((1, block_q, _LANES), qrow_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _sds((bh, s_pad, d), q.dtype, vma=_vma(q, k, v, dout)),
            _sds((bh, s_pad, d), q.dtype, vma=_vma(q, k, v, dout)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, dout, lse, delta)
    return dq[:, :s], dk[:, :s], dv[:, :s]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    out, _ = _fwd_call(q, k, v, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _fwd_call(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, dout):
    q, k, v, out, lse = res
    import os

    # compiled path (TPU): the Pallas backward kernels.  Interpret mode
    # (CPU test clusters) defaults to the blocked-jnp reference backward
    # — much faster than interpreting the kernels — unless KF_PALLAS_BWD
    # =pallas forces them (how the kernel numerics tests run off-TPU).
    if interpret and os.environ.get("KF_PALLAS_BWD", "") != "pallas":
        return _bwd_blocked(q, k, v, out, lse, dout, causal, block_k)
    return _bwd_pallas(
        q, k, v, out, lse, dout, causal, block_q, block_k, interpret
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_pair(q, k, v, causal, block_q, block_k, interpret):
    """Like :func:`_flash` but returns ``(out, lse)`` — the pair a
    cross-block online-softmax merge needs (ring attention folds each
    rotating K/V block via its lse)."""
    return _fwd_call(q, k, v, causal, block_q, block_k, interpret)


def _flash_pair_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _fwd_call(q, k, v, causal, block_q, block_k, interpret)
    return (out, lse), (q, k, v, out, lse)


def _flash_pair_bwd(causal, block_q, block_k, interpret, res, cts):
    """The lse cotangent needs no extra kernel: ∂lse_i/∂s_ij = p_ij, so
    its contribution to dS is ``p * dlse`` — and the backward kernels
    compute ``dS = p * (dp - delta)``, so shifting ``delta -= dlse``
    carries it through both the Pallas and the blocked-jnp paths."""
    q, k, v, out, lse = res
    dout, dlse = cts
    import os

    if interpret and os.environ.get("KF_PALLAS_BWD", "") != "pallas":
        bwd = _bwd_blocked_delta
    else:
        bwd = functools.partial(_bwd_pallas_delta, block_q=block_q,
                                interpret=interpret)
    delta = jnp.sum(
        dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ) - dlse.astype(jnp.float32)
    return bwd(q, k, v, out, lse, dout, delta, causal, block_k)


def _bwd_blocked_delta(q, k, v, out, lse, dout, delta, causal, block_k):
    return _bwd_blocked(q, k, v, out, lse, dout, causal, block_k, delta=delta)


def _bwd_pallas_delta(q, k, v, out, lse, dout, delta, causal, block_k, *,
                      block_q, interpret):
    return _bwd_pallas(q, k, v, out, lse, dout, causal, block_q, block_k,
                       interpret, delta=delta)


_flash_pair.defvjp(_flash_pair_fwd, _flash_pair_bwd)


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _default_blocks(s: int, block_q, block_k):
    """Resolve ``None`` block sizes: the largest power-of-two tile up to
    the capped default whose sequence padding stays proportionate — a big
    tile only pays off when it isn't mostly padding (S=1152 with a 1024
    block would pad to 2048 and nearly double the tile traffic; it gets
    256 → pad 1280).  Power-of-two choices keep block_q | block_k (or
    vice versa), so the pad length is just max(block_q, block_k)-aligned.
    """
    n = ((max(s, 1) + 127) // 128) * 128
    # tolerate up to ~25% padded rows (and never a whole extra 128-tile
    # on short sequences — the 127 keeps n=128 at a 128 block)
    allowance = max(n // 4, 127)

    def pick(cap):
        for opt in (1024, 512, 256, 128):
            if opt <= cap and ((n + opt - 1) // opt) * opt - n <= allowance:
                return opt
        return 128

    if block_q is None:
        block_q = pick(DEFAULT_BLOCK_Q)
    if block_k is None:
        block_k = pick(DEFAULT_BLOCK_K)
    return block_q, block_k


def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Fused attention for [B, H, S, D] (or [BH, S, D]) operands.

    Differentiable; numerically matches
    :func:`kungfu_tpu.models.transformer.default_attention` (softmax in
    f32).  ``interpret=None`` auto-selects interpreter mode off-TPU so
    the same call works on the CPU test cluster.  ``block_q``/``block_k``
    default to the swept TPU tiles (:func:`_default_blocks`).
    """
    if interpret is None:
        interpret = _use_interpret()
    block_q, block_k = _default_blocks(q.shape[-2], block_q, block_k)
    if q.ndim == 3:
        return _flash(q, k, v, causal, block_q, block_k, interpret)
    if q.ndim != 4:
        raise ValueError(f"expected [B,H,S,D] or [BH,S,D], got {q.shape}")
    b, h, s, d = q.shape
    out = _flash(
        q.reshape(b * h, s, d),
        k.reshape(b * h, s, d),
        v.reshape(b * h, s, d),
        causal, block_q, block_k, interpret,
    )
    return out.reshape(b, h, s, d)


def flash_attention_with_lse(
    q,
    k,
    v,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Fused attention returning ``(out, lse)`` for [BH, S, D] operands.

    Differentiable in both outputs (the lse cotangent folds into the
    backward's delta shift).  The lse rows let a caller merge multiple
    attention calls over disjoint K/V blocks with the standard
    online-softmax combine — :mod:`kungfu_tpu.parallel.ring` uses this
    as its per-round block primitive."""
    if interpret is None:
        interpret = _use_interpret()
    if q.ndim != 3:
        raise ValueError(f"expected [BH, S, D], got {q.shape}")
    block_q, block_k = _default_blocks(q.shape[-2], block_q, block_k)
    return _flash_pair(q, k, v, causal, block_q, block_k, interpret)


def make_flash_attn(block_q: Optional[int] = None, block_k: Optional[int] = None):
    """Adapter for the ``attn_fn(q, k, v, causal)`` slot of
    :meth:`kungfu_tpu.models.transformer.Transformer.apply`."""

    def attn(q, k, v, causal):
        return flash_attention(q, k, v, causal=causal, block_q=block_q, block_k=block_k)

    return attn
