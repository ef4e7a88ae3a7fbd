"""Flash attention as Pallas TPU kernels: a forward and two backward
kernels that compute only the score tiles the causal mask keeps.

One schedule serves all three (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``).  A grid step holds ``hold`` rows of the Q side and as
many of the K side in VMEM and walks the ``[block_q, block_k]`` score tiles of
that pair of holds *inside* the kernel, so the grid stays coarse (one
step a head where a head's sequence fits) while the mask is honoured
tile by tile:

- the walk is static: which tiles lie above the diagonal (never
  touched), across it (masked) or below it (whole) is known when the
  kernel is traced, so the walk is unrolled into straight-line code and
  the compiler overlaps one tile's products with the next one's softmax.
  A rolled loop with a bound at the diagonal cannot: every iteration
  then pays the matrix unit's latency, which at 128 x 128 tiles cost more
  than the masked half it saved (PERF.md, PR 28);
- neighbouring tiles of a row are computed as one *run* -- one product
  ``wide`` tiles across -- and only the run's tile on the diagonal pays
  the iota / compare / select;
- every product takes its operands as they arrive (bfloat16 in training)
  and accumulates in float32; ``scale`` multiplies the float32 scores.
  The softmax, ``lse``, ``delta`` and every accumulator are float32;
- no transpose of a score tile: ``Q K^T`` and ``dO V^T`` contract the
  last dimension of both sides; the forward and the dK/dV kernel hold
  the scores transposed (``K Q^T``: keys down the sublanes, queries
  along the lanes), so that the forward's running max and sum are rows
  one vreg high instead of columns one lane wide, and ``P^T`` and
  ``dS^T`` are what dK/dV's products need;
- the per-row statistics cross the kernel boundary as ``[BH, 1, S]``
  rows, S along the lanes: the forward writes and the dK/dV kernel reads
  them as they lie, the dQ kernel turns one tile's worth into a column.

:func:`tile_plan` chooses the sizes from ``(S, D, dtype, causal)``.
Where a head's whole sequence fits the VMEM budget the plan is
*resident* (one hold: the grid is one step a head, everything is
fetched once); where it does not it is *streamed* (a hold at a time
over the grid, online-softmax state and the gradient accumulators
carried in VMEM scratch; a pair of holds below the diagonal is walked
whole, the pair on it as the triangle, a pair above it neither computed
nor copied).  Same kernels, same walk; only the sizes differ.

Backward is the standard flash recomputation from the saved ``lse``:

    P  = exp(Q K^T * scale - L)        (recomputed per tile)
    dV = P^T dO
    dP = dO V^T
    dS = P * (dP - rowsum(dO * O))
    dQ = dS K * scale ;  dK = dS^T Q * scale

The blocked-jnp backward is the non-TPU fallback and the reference the
kernel tests compare against.  The whole op is a ``custom_vjp``.

What set the sizes (my chip run, PR 28; TPU v5 lite, bfloat16, causal,
``benchmarks/flash_sweep.py``, ms a call as forward / dQ / dK+dV):
at ``[B*H, S, D] = [64, 1024, 64]``, the train cells' call, the kernels
this file held before read 0.363 / 0.320 / 0.536 with (256, 1024)
blocks and float32 score products; these read 0.303 / 0.190 / 0.249 at
(128, 128) tiles in runs of 8 (``computed_share`` 1.124), 0.229 / 0.198
/ 0.265 at (256, 256) in runs of 2 (1.249) and 0.170 / 0.225 / 0.315
at (512, 512) (1.499); the same (128, 128) tiles walked by a rolled
``fori_loop`` with its bound at the diagonal read 1.252 / 0.863 / 0.794.
At ``[48, 2048, 64]``: 0.891 / 0.896 / 1.257 before, 0.623 / 0.488 /
0.657 at (256, 256) in runs of 2; at ``[32, 2048, 128]``: 0.629 / 0.562
/ 0.826 before, 0.272 / 0.306 / 0.401 at (256, 256) in runs of 2.  S
8192 (streamed) compiles for a described v5e and has not met a chip.
"""

from __future__ import annotations

import functools
import math
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kungfu_tpu.ops.pallas._sharding import match_vma as _match_vma
from kungfu_tpu.ops.pallas._sharding import sds as _sds
from kungfu_tpu.ops.pallas._sharding import vma_of as _vma

_NEG_INF = -1e30
#: contract the last dimension of both operands: ``A B^T`` with no
#: transpose of either
_NT = (((1,), (1,)), ((), ()))
#: contract the first dimension of both: ``A^T B``
_TN = (((0,), (0,)), ((), ()))
#: what one grid step may hold in VMEM: operands double-buffered, the
#: scratch and the row statistics (:func:`_vmem_bytes`).  The compiler
#: gives a kernel 16 MiB on a v5e; the rest is for a tile body's values.
VMEM_BUDGET_BYTES = 10 << 20


#: score elements one product may cover: neighbouring tiles of a row
#: are joined into runs of up to this many (:func:`tile_plan`'s ``wide``).
#: At (128, 128) tiles, S 1024, runs of 1 / 4 / 8 tiles read 0.850 /
#: 0.800 / 0.736 ms over the three kernels (my chip run, PR 28); 8 is a
#: whole row there, and twice this budget read the same.
RUN_ELEMENTS = 128 * 1024


class TilePlan(NamedTuple):
    """How the three kernels walk one ``[S, D]`` head (see module doc)."""

    path: str               # "resident" | "streamed"
    block_q: int            # a score tile is [block_q, block_k]
    block_k: int
    wide: int               # tiles of a row computed as one product
    hold: int               # rows of either side a grid step holds in VMEM
    s_pad: int              # S rounded up to whole tiles (and holds)
    computed_share: float   # score elements computed over those needed


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(hold: int, d: int, itemsize: int) -> int:
    """VMEM one grid step needs with ``hold`` rows on either side: the
    larger of the forward's (q k v o twice; m, l and acc with the rows
    along the lanes) and the dK/dV kernel's (q k v dO dk dv twice, two
    accumulators), lanes padded, and two statistics rows twice."""
    row = _round_up(d, 128)
    fwd = hold * (2 * 4 * row * itemsize + 2 * 8 * 4 + 4 * d)
    dkv = hold * (2 * 6 * row * itemsize + 2 * 4 * row)
    return max(fwd, dkv) + 2 * 2 * 8 * 4 * hold


def _computed_share(s: int, s_pad: int, block_q: int, block_k: int,
                    causal: bool) -> float:
    """Score elements in the tiles the kernels touch over the elements
    attention needs (the causal triangle, or the square)."""
    tiles = sum(_tile_kind(i, j, block_q, block_k, causal) != "skip"
                for i in range(s_pad // block_q)
                for j in range(s_pad // block_k))
    return tiles * block_q * block_k / (s * (s + 1) / 2 if causal else s * s)


def tile_plan(s: int, d: int, dtype, causal: bool,
              block_q: Optional[int] = None,
              block_k: Optional[int] = None) -> TilePlan:
    """The schedule for ``[S, D]`` heads of ``dtype``: tile sizes, what a
    grid step holds, and how much of what it computes the mask keeps.

    ``block_q`` / ``block_k`` given pass through.  Otherwise the tile is
    the largest of 512 / 256 / 128 that keeps eight tiles along the
    diagonal (``computed_share`` is ``(n + 1) / n`` at ``n`` tiles a
    side: 1.125 at eight) — a short S takes 128 and never pads a whole
    tile.  S is padded to whole tiles and no further; a hold is the most
    tiles that divide the sequence evenly and fit
    :data:`VMEM_BUDGET_BYTES` by :func:`_vmem_bytes` -- all of them where
    they fit (resident), else a part (streamed).
    """
    s = max(s, 1)
    auto = next((t for t in (512, 256) if s >= 8 * t), 128)
    block_q = auto if block_q is None else block_q
    block_k = auto if block_k is None else block_k
    tile = math.lcm(block_q, block_k)
    itemsize = jnp.dtype(dtype).itemsize
    s_pad = _round_up(s, tile)
    n = s_pad // tile
    fits = [g for g in range(1, n + 1) if n % g == 0
            and _vmem_bytes(g * tile, d, itemsize) <= VMEM_BUDGET_BYTES]
    hold = tile * max(fits, default=1)
    return TilePlan("resident" if hold == s_pad else "streamed", block_q,
                    block_k, max(1, RUN_ELEMENTS // (block_q * block_k)),
                    hold, s_pad,
                    _computed_share(s, s_pad, block_q, block_k, causal))


def _dot_nt(a, b):
    return jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    return jax.lax.dot_general(a, b, _TN, preferred_element_type=jnp.float32)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _tile_kind(i, j, block_q, block_k, causal):
    """What the causal mask makes of score tile (Q tile ``i``, K tile
    ``j``), both counted from the same row: ``"skip"`` above the
    diagonal, ``"mask"`` across it, ``"full"`` below."""
    if not causal or (j + 1) * block_k - 1 <= i * block_q:
        return "full"
    return "skip" if j * block_k > (i + 1) * block_q - 1 else "mask"


def _runs(kinds, wide):
    """``[(first, count, masked)]``: one row of tile ``kinds`` cut into
    runs of up to ``wide`` neighbours that are computed as one product;
    ``masked`` lists the run's tiles (by offset) that need the mask.
    Skipped tiles are left out."""
    live = [j for j, kind in enumerate(kinds) if kind != "skip"]
    return [(live[a], len(live[a:a + wide]),
             [t for t, j in enumerate(live[a:a + wide]) if kinds[j] == "mask"])
            for a in range(0, len(live), wide)]


def _mask_tiles(x, masked, size, axis, visible):
    """``x`` with the tiles ``masked`` (offsets, ``size`` wide along
    ``axis``) set to -inf where ``visible(offset, shape)`` is false; the
    run's other tiles are passed on untouched."""
    if not masked:
        return x
    parts = []
    for t in range(x.shape[axis] // size):
        part = jax.lax.slice_in_dim(x, t * size, (t + 1) * size, axis=axis)
        if t in masked:
            part = jnp.where(visible(t, part.shape), part, _NEG_INF)
        parts.append(part)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


def _visible(q0, k0, shape, q_axis, seq_len=None):
    """Which scores of the tile at rows ``q0``, keys ``k0`` attention
    keeps; queries run along ``q_axis`` of ``shape``.  Causal: key <=
    query, which also drops every padded key for a real query.  Not
    causal (``seq_len`` given): real keys."""
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    if seq_len is not None:
        return k_pos < seq_len
    return q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis) >= k_pos


def _hold_pairs(walk, causal, qb, kb, n_holds, tail=False):
    """Run ``walk(edge)`` for this grid step's (Q hold, K hold) pair.
    Under a causal mask a pair below the diagonal has every tile whole,
    the pair on it -- the edge -- has the triangle, a pair above it has
    nothing.  Not causal, the edge is the K hold with the padded tail,
    where the forward needs a mask (``tail``) and the others do not.
    (Always under a ``pl.when``: the off-chip interpreter refuses a
    literal operand in a kernel's top-level equations under
    ``shard_map``, and takes it inside a branch.)"""
    if causal:
        plain, edge = kb < qb, kb == qb
    elif tail:
        plain, edge = kb < n_holds - 1, kb == n_holds - 1
    else:
        plain, edge = None, kb < n_holds
    if n_holds > 1 and plain is not None:
        pl.when(plain)(lambda: walk(False))
    pl.when(edge)(lambda: walk(True))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                scale, causal, seq_len, s_pad, block_q, block_k, wide):
    """One (head, Q hold, K hold) grid step of the forward, scores
    transposed (``[bk, bq]``: keys down the sublanes, queries along the
    lanes), so that the running max and sum are rows of one vreg's
    height and ``acc`` is ``[D, bq]``.  m / l / acc scratch carries the
    online softmax across K holds."""
    qb, kb, n_kb = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    hold = q_ref.shape[0]
    n_q, n_k = hold // block_q, hold // block_k
    k_end = s_pad - hold                # where the last K hold starts

    @pl.when(kb == 0)
    def _():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def walk(edge):
        for i in range(n_q):
            r = slice(i * block_q, (i + 1) * block_q)
            q = q_ref[r, :]
            m, l, acc = m_s[:, r], l_s[:, r], acc_s[:, r]
            # not causal, the edge is the last K hold: its tiles that
            # reach past the sequence's end mask the padded keys
            kinds = [_tile_kind(i, j, block_q, block_k, edge) if causal else
                     "mask" if edge and k_end + (j + 1) * block_k > seq_len
                     else "full" for j in range(n_k)]
            for j, n, masked in _runs(kinds, wide):
                c = slice(j * block_k, (j + n) * block_k)
                kt, vt = k_ref[c, :], v_ref[c, :]
                # on the diagonal pair both offsets are the same
                off = 0 if causal else k_end
                st = _mask_tiles(
                    _dot_nt(kt, q) * scale, masked, block_k, 0,   # [bk, bq]
                    lambda t, shape: _visible(
                        i * block_q, off + (j + t) * block_k, shape, 1,
                        None if causal else seq_len))
                # tile 0 comes first and every query sees key 0, so m is a
                # real maximum from then on and a masked score's exp is 0
                m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))
                pt = jnp.exp(st - m_new)
                corr = jnp.exp(m - m_new)
                l = l * corr + jnp.sum(pt, axis=0, keepdims=True)
                acc = acc * corr + _dot_tn(vt, pt.astype(vt.dtype))  # [D, bq]
                m = m_new
            m_s[:, r], l_s[:, r], acc_s[:, r] = m, l, acc

    _hold_pairs(walk, causal, qb, kb, s_pad // hold, tail=s_pad > seq_len)

    @pl.when(kb == n_kb - 1)
    def _():
        l = jnp.maximum(l_s[:], 1e-30)
        o_ref[...] = (acc_s[:] / l).T.astype(o_ref.dtype)
        lse_ref[...] = m_s[:] + jnp.log(l)


def _k_hold(causal):
    """K-hold index map for a (head, Q hold, K hold) grid: past the
    diagonal a causal Q hold repeats its last K hold, so that Pallas
    elides the copy (the kernel does nothing there)."""
    if causal:
        return lambda b, i, j: (b, jnp.minimum(j, i), 0)
    return lambda b, i, j: (b, j, 0)


def _pad_rows(t, s_pad, value=0.0):
    """Pad axis 1 of ``t`` to ``s_pad``."""
    if t.shape[1] == s_pad:
        return t
    pad = [(0, 0)] * t.ndim
    pad[1] = (0, s_pad - t.shape[1])
    return jnp.pad(t, pad, constant_values=value)


# jitted: a model's layers call this with one shape, and the walk it
# unrolls is then traced and lowered once a program, not once a layer
@functools.partial(jax.jit, static_argnames=("causal", "plan", "interpret"))
def _fwd_call(q, k, v, causal, plan, interpret):
    """q,k,v: [BH, S, D] → (out [BH, S, D], lse [BH, S])."""
    bh, s, d = q.shape
    hold, s_pad = plan.hold, plan.s_pad
    q, k, v = (_pad_rows(t, s_pad) for t in (q, k, v))
    vma = _vma(q, k, v)

    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=1.0 / (d ** 0.5), causal=causal, seq_len=s,
            s_pad=s_pad, block_q=plan.block_q, block_k=plan.block_k,
            wide=plan.wide,
        ),
        grid=(bh, s_pad // hold, s_pad // hold),
        in_specs=[
            pl.BlockSpec((None, hold, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, hold, d), _k_hold(causal)),
            pl.BlockSpec((None, hold, d), _k_hold(causal)),
        ],
        out_specs=[
            pl.BlockSpec((None, hold, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, hold), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            _sds((bh, s_pad, d), q.dtype, vma=vma),
            _sds((bh, 1, s_pad), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, hold), jnp.float32),   # running max m
            pltpu.VMEM((1, hold), jnp.float32),   # running sum l
            pltpu.VMEM((d, hold), jnp.float32),   # output accumulator, [D, q]
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out[:, :s], lse[:, 0, :s]


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

    s_pad = _round_up(s, block_k)
    k, v = _pad_rows(k, s_pad), _pad_rows(v, s_pad)
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
                   dq_ref, acc_s, *, scale, causal, s_pad, block_q, block_k,
                   wide):
    """dQ on the forward's grid and walk: K/V tiles pass under each Q
    tile, dQ accumulates in VMEM scratch across K holds."""
    qb, kb, n_kb = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    hold = q_ref.shape[0]
    n_q, n_k = hold // block_q, hold // block_k

    @pl.when(kb == 0)
    def _():
        acc_s[:] = jnp.zeros_like(acc_s)

    def walk(edge):
        for i in range(n_q):
            r = slice(i * block_q, (i + 1) * block_q)
            q, do = q_ref[r, :], do_ref[r, :]
            # one tile's statistics, from a row along the lanes to a column
            lse = jnp.expand_dims(lse_ref[:, r][0], -1)      # [bq, 1]
            delta = jnp.expand_dims(delta_ref[:, r][0], -1)
            kinds = [_tile_kind(i, j, block_q, block_k, causal and edge)
                     for j in range(n_k)]
            dq = None
            for j, n, masked in _runs(kinds, wide):
                c = slice(j * block_k, (j + n) * block_k)
                kt, vt = k_ref[c, :], v_ref[c, :]
                s = _mask_tiles(
                    _dot_nt(q, kt) * scale, masked, block_k, 1,
                    lambda t, shape: _visible(
                        i * block_q, (j + t) * block_k, shape, 0))
                # a padded key is a zero row of k and v: it moves nothing
                p = jnp.exp(s - lse)                            # [bq, bk]
                ds = p * (_dot_nt(do, vt) - delta)
                step = _dot(ds.astype(kt.dtype), kt)
                dq = step if dq is None else dq + step
            acc_s[r, :] += dq

    _hold_pairs(walk, causal, qb, kb, s_pad // hold)

    @pl.when(kb == n_kb - 1)
    def _():
        dq_ref[...] = (acc_s[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_s, dv_s, *, scale, causal, s_pad,
                    block_q, block_k, wide):
    """dK/dV on a (head, K hold, Q hold) grid, scores transposed: a K
    tile's rows against the Q tiles from the diagonal on, so ``P^T`` and
    ``dS^T`` are what the products need and the statistics are read as
    the rows they are stored as.  Padded queries carry ``lse = 1e30``
    and so weigh nothing; a padded key's row of dk / dv is cut off."""
    kb, qb, n_qb = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    hold = q_ref.shape[0]
    n_q, n_k = hold // block_q, hold // block_k

    @pl.when(qb == 0)
    def _():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    def walk(edge):
        for j in range(n_k):
            r = slice(j * block_k, (j + 1) * block_k)
            kt, vt = k_ref[r, :], v_ref[r, :]
            kinds = [_tile_kind(i, j, block_q, block_k, causal and edge)
                     for i in range(n_q)]
            dk = dv = None
            for i, n, masked in _runs(kinds, wide):
                c = slice(i * block_q, (i + n) * block_q)
                qt, do = q_ref[c, :], do_ref[c, :]
                st = _mask_tiles(
                    _dot_nt(kt, qt) * scale, masked, block_q, 1,  # [bk, bq]
                    lambda t, shape: _visible(
                        (i + t) * block_q, j * block_k, shape, 1))
                pt = jnp.exp(st - lse_ref[:, c])
                dst = pt * (_dot_nt(vt, do) - delta_ref[:, c])
                dv_i = _dot(pt.astype(do.dtype), do)
                dk_i = _dot(dst.astype(qt.dtype), qt)
                dk, dv = ((dk_i, dv_i) if dk is None
                          else (dk + dk_i, dv + dv_i))
            dk_s[r, :] += dk
            dv_s[r, :] += dv

    # the pairs a K hold sees lie below the diagonal, where qb > kb
    _hold_pairs(walk, causal, qb, kb, s_pad // hold)

    @pl.when(qb == n_qb - 1)
    def _():
        dk_ref[...] = (dk_s[:] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_s[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "plan", "interpret"))
def _bwd_pallas(q, k, v, out, lse, dout, causal, plan, interpret, delta=None):
    """Pallas backward: dq with K/V passing under each Q tile, dk/dv with
    Q/dO passing under each K tile; [BH, S, D] operands.  ``delta`` as in
    :func:`_bwd_blocked`."""
    bh, s, d = q.shape
    hold, s_pad = plan.hold, plan.s_pad
    if delta is None:
        delta = jnp.sum(
            dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
        )  # [BH, S]
    q, k, v, dout = (_pad_rows(t, s_pad) for t in (q, k, v, dout))
    # padded q rows: lse=+inf makes their P rows exp(s - inf) = 0
    lse = _pad_rows(lse, s_pad, 1e30)[:, None, :]        # [BH, 1, S_pad]
    delta = _pad_rows(delta, s_pad)[:, None, :]
    vma = _vma(q, k, v, dout)
    kernel_args = dict(scale=1.0 / (d ** 0.5), causal=causal,
                       s_pad=s_pad, block_q=plan.block_q,
                       block_k=plan.block_k, wide=plan.wide)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    grid = (bh, s_pad // hold, s_pad // hold)
    q_spec = pl.BlockSpec((None, hold, d), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((None, 1, hold), lambda b, i, j: (b, 0, i))
    kv_spec = pl.BlockSpec((None, hold, d), _k_hold(causal))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kernel_args),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec],
        out_shape=[_sds((bh, s_pad, d), q.dtype, vma=vma)],
        scratch_shapes=[pltpu.VMEM((hold, d), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, dout, lse, delta)[0]

    # dk/dv grid: (head, K hold, Q hold); before the diagonal a causal K
    # hold repeats its first Q hold, so that Pallas elides the copies
    def q_hold(j, i):
        return jnp.maximum(i, j) if causal else i

    q_spec = pl.BlockSpec((None, hold, d), lambda b, j, i: (b, q_hold(j, i), 0))
    row_spec = pl.BlockSpec((None, 1, hold),
                            lambda b, j, i: (b, 0, q_hold(j, i)))
    kv_spec = pl.BlockSpec((None, hold, d), lambda b, j, i: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kernel_args),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[_sds((bh, s_pad, d), q.dtype, vma=vma)] * 2,
        scratch_shapes=[pltpu.VMEM((hold, d), jnp.float32)] * 2,
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, dout, lse, delta)
    return dq[:, :s], dk[:, :s], dv[:, :s]


def _backward(q, k, v, out, lse, dout, causal, plan, interpret, delta=None):
    """Compiled (TPU): the Pallas backward kernels.  Interpret mode (CPU
    test clusters) defaults to the blocked-jnp reference backward — much
    faster than interpreting the kernels — unless KF_PALLAS_BWD=pallas
    forces them (how the kernel numerics tests run off-TPU)."""
    if interpret and os.environ.get("KF_PALLAS_BWD", "") != "pallas":
        return _bwd_blocked(q, k, v, out, lse, dout, causal, plan.block_k,
                            delta=delta)
    return _bwd_pallas(q, k, v, out, lse, dout, causal, plan, interpret,
                       delta=delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, plan, interpret):
    out, _ = _fwd_call(q, k, v, causal, plan, interpret)
    return out


def _flash_fwd(q, k, v, causal, plan, interpret):
    out, lse = _fwd_call(q, k, v, causal, plan, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, plan, interpret, res, dout):
    return _backward(*res, dout, causal, plan, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_pair(q, k, v, causal, plan, interpret):
    """Like :func:`_flash` but returns ``(out, lse)`` — the pair a
    cross-block online-softmax merge needs (ring attention folds each
    rotating K/V block via its lse)."""
    return _fwd_call(q, k, v, causal, plan, interpret)


def _flash_pair_fwd(q, k, v, causal, plan, interpret):
    out, lse = _fwd_call(q, k, v, causal, plan, interpret)
    return (out, lse), (q, k, v, out, lse)


def _flash_pair_bwd(causal, plan, interpret, res, cts):
    """The lse cotangent needs no extra kernel: ∂lse_i/∂s_ij = p_ij, so
    its contribution to dS is ``p * dlse`` — and the backward kernels
    compute ``dS = p * (dp - delta)``, so shifting ``delta -= dlse``
    carries it through both the Pallas and the blocked-jnp paths."""
    out = res[3]
    dout, dlse = cts
    delta = jnp.sum(
        dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ) - dlse.astype(jnp.float32)
    return _backward(*res, dout, causal, plan, interpret, delta=delta)


_flash_pair.defvjp(_flash_pair_fwd, _flash_pair_bwd)


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


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
    override the score tile :func:`tile_plan` would choose.
    """
    if interpret is None:
        interpret = _use_interpret()
    if q.ndim not in (3, 4):
        raise ValueError(f"expected [B,H,S,D] or [BH,S,D], got {q.shape}")
    s, d = q.shape[-2:]
    plan = tile_plan(s, d, q.dtype, causal, block_q, block_k)
    out = _flash(q.reshape(-1, s, d), k.reshape(-1, s, d),
                 v.reshape(-1, s, d), causal, plan, interpret)
    return out.reshape(q.shape)


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
    plan = tile_plan(q.shape[1], q.shape[2], q.dtype, causal, block_q, block_k)
    return _flash_pair(q, k, v, causal, plan, interpret)


def make_flash_attn(block_q: Optional[int] = None, block_k: Optional[int] = None):
    """Adapter for the ``attn_fn(q, k, v, causal)`` slot of
    :meth:`kungfu_tpu.models.transformer.Transformer.apply`."""

    def attn(q, k, v, causal):
        return flash_attention(q, k, v, causal=causal, block_q=block_q, block_k=block_k)

    return attn
