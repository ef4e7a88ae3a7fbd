"""Fused softmax-cross-entropy as a Pallas TPU kernel.

For an LM head the logits tensor [B*S, V] (V ~ 32k) is the largest
activation in the model.  ``jax.nn.log_softmax`` + gather materializes a
second [B*S, V] tensor and autodiff saves more; this kernel streams the
vocab once per row block, producing only per-token ``loss`` and
``logsumexp`` — O(N) extra memory instead of O(N*V).

Backward (round 3): a Pallas kernel over the same (row, vocab) grid
recomputes the softmax per tile from the logits and the saved logsumexp
(``dlogits = (softmax - onehot(target)) * g``) — purely elementwise per
tile, no cross-tile state, so it is a single fused read(logits) →
write(dlogits) sweep.  The blocked-jnp backward is kept as the non-TPU
fallback and as the reference the kernel tests compare against.  (The
[N, V] dlogits output itself is required by the head matmul backward
and is unavoidable.)

Interpret mode on CPU for tests; compiled on TPU.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kungfu_tpu.ops.pallas._sharding import vma_of as _vma
from kungfu_tpu.ops.pallas._sharding import sds as _sds
from kungfu_tpu.utils.envs import LaunchKnobs

#: from a TPU v5e sweep before PR 1 (not repeated on today's code):
#: (256, 2048) tiles ran the fwd+bwd sweep ~1.5x faster than the round-3
#: (128, 512) defaults — big enough to pipeline HBM reads, small enough
#: for VMEM double-buffering
DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_V = 2048
_NEG_INF = -1e30
#: per-row values (targets, loss, lse, g) cross the pallas_call boundary
#: replicated across one full lane width — Mosaic's tiling only accepts
#: (8k, 128) tiles, so a bare row vector is not a legal block shape on TPU
_LANES = 128


def _fwd_kernel(logits_ref, targets_ref, loss_ref, lse_ref, m_ref, l_ref,
                t_ref, *, vocab, block_v, masked):
    """Grid = (row blocks, vocab blocks), vocab innermost.  One [block_n,
    block_v] logits tile lives in VMEM at a time; the online max/sumexp/
    target accumulators persist in scratch across the vocab sweep.

    ``masked`` is a compile-time flag, False whenever block_v divides the
    vocab — the tail-mask compare/selects then vanish from the hot loop.
    The target logit is accumulated IN the sweep: a round-3 experiment
    moved it to an XLA gather outside the kernel and lost 2x — a
    take_along_axis over [8k, 32k] costs 1.7-4.3 ms on v5e (TPU gathers
    serialize), dwarfing the per-element compare it saved."""
    j = pl.program_id(1)
    n_v = pl.num_programs(1)
    blk = logits_ref[...].astype(jnp.float32)  # [block_n, block_v]
    n = blk.shape[0]
    tgt = targets_ref[...][:, :1]  # [block_n, 1] (lane 0)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        t_ref[...] = jnp.zeros_like(t_ref)

    k_pos = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (n, block_v), 1)
    if masked:
        # one select suffices: exp(_NEG_INF - m_new) underflows to exactly
        # 0, so the sum needs no second mask
        blk = jnp.where(k_pos < vocab, blk, _NEG_INF)
    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(blk, axis=-1, keepdims=True))
    corr = jnp.exp(m - m_new)
    l_new = l_ref[...] * corr + jnp.sum(
        jnp.exp(blk - m_new), axis=-1, keepdims=True
    )
    # the target logit lives in exactly one vocab block
    is_tgt = k_pos == tgt
    t_new = t_ref[...] + jnp.sum(jnp.where(is_tgt, blk, 0.0), axis=-1, keepdims=True)
    m_ref[...] = m_new
    l_ref[...] = l_new
    t_ref[...] = t_new

    @pl.when(j == n_v - 1)
    def _():
        lse = m_new + jnp.log(jnp.maximum(l_new, 1e-30))
        lanes = loss_ref.shape
        loss_ref[...] = jnp.broadcast_to(lse - t_new, lanes)
        lse_ref[...] = jnp.broadcast_to(lse, lanes)


def _fwd_call(logits, targets, block_n, block_v, interpret):
    """logits [N, V], targets [N] → (loss [N], lse [N])."""
    n, v = logits.shape
    n_pad = ((n + block_n - 1) // block_n) * block_n
    v_pad = ((v + block_v - 1) // block_v) * block_v
    if n_pad != n or v_pad != v:
        logits = jnp.pad(logits, [(0, n_pad - n), (0, v_pad - v)])
        targets = jnp.pad(targets, [(0, n_pad - n)])
    kernel = functools.partial(
        _fwd_kernel, vocab=v, block_v=block_v, masked=v_pad != v
    )
    row = pl.BlockSpec((block_n, _LANES), lambda i, j: (i, 0))
    loss, lse = pl.pallas_call(
        kernel,
        grid=(n_pad // block_n, v_pad // block_v),
        in_specs=[
            pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
            row,
        ],
        out_specs=[row, row],
        out_shape=[
            _sds((n_pad, _LANES), jnp.float32, vma=_vma(logits, targets)),
            _sds((n_pad, _LANES), jnp.float32, vma=_vma(logits, targets)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="xent_fwd",
    )(logits, jnp.broadcast_to(targets[:, None], (n_pad, _LANES)))
    return loss[:n, 0], lse[:n, 0]


def _bwd_blocked(logits, targets, lse, g, block_v):
    """dlogits = (softmax - onehot) * g, computed vocab-block-wise.

    Blocks are sliced from the (possibly bf16) logits INSIDE the scan
    body and the result is cast back to the logits dtype per block, so
    live f32 memory stays one [N, block_v] tile — the only full-size
    tensor is the unavoidable dlogits output itself."""
    n, v = logits.shape
    v_pad = ((v + block_v - 1) // block_v) * block_v
    if v_pad != v:
        logits = jnp.pad(logits, [(0, 0), (0, v_pad - v)])
    n_blk = v_pad // block_v

    def fold(_, j):
        x_blk = jax.lax.dynamic_slice_in_dim(
            logits, j * block_v, block_v, axis=1
        ).astype(jnp.float32)
        k_pos = j * block_v + jnp.arange(block_v)
        p = jnp.where(k_pos[None, :] < v, jnp.exp(x_blk - lse[:, None]), 0.0)
        onehot = (k_pos[None, :] == targets[:, None]).astype(jnp.float32)
        d_blk = (p - onehot) * g[:, None]
        return None, d_blk.astype(logits.dtype)

    _, dblocks = jax.lax.scan(fold, None, jnp.arange(n_blk))
    return dblocks.transpose(1, 0, 2).reshape(n, v_pad)[:, :v]


def _bwd_kernel(logits_ref, targets_ref, lse_ref, g_ref, dl_ref, *,
                vocab, block_v, masked):
    """dlogits tile = (softmax - onehot) * g; stateless per grid step.
    ``masked`` as in :func:`_fwd_kernel` (the onehot iota is needed
    either way, but the tail-mask select is skipped when block_v divides
    the vocab)."""
    j = pl.program_id(1)
    blk = logits_ref[...].astype(jnp.float32)  # [block_n, block_v]
    n = blk.shape[0]
    k_pos = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (n, block_v), 1)
    lse = lse_ref[...][:, :1]  # [block_n, 1] (lane 0)
    g = g_ref[...][:, :1]
    p = jnp.exp(blk - lse)
    if masked:
        p = jnp.where(k_pos < vocab, p, 0.0)
    onehot = (k_pos == targets_ref[...][:, :1]).astype(jnp.float32)
    dl_ref[...] = ((p - onehot) * g).astype(dl_ref.dtype)


def _bwd_pallas(logits, targets, lse, g, block_n, block_v, interpret):
    n, v = logits.shape
    n_pad = ((n + block_n - 1) // block_n) * block_n
    v_pad = ((v + block_v - 1) // block_v) * block_v
    if n_pad != n or v_pad != v:
        logits = jnp.pad(logits, [(0, n_pad - n), (0, v_pad - v)])
        targets = jnp.pad(targets, [(0, n_pad - n)])
        # padded rows: lse=+inf zeroes their softmax, g=0 their gradient
        lse = jnp.pad(lse, [(0, n_pad - n)], constant_values=1e30)
        g = jnp.pad(g, [(0, n_pad - n)])
    row = pl.BlockSpec((block_n, _LANES), lambda i, j: (i, 0))
    lanes = lambda t: jnp.broadcast_to(t[:, None], (n_pad, _LANES))  # noqa: E731
    dlogits = pl.pallas_call(
        functools.partial(_bwd_kernel, vocab=v, block_v=block_v,
                          masked=v_pad != v),
        grid=(n_pad // block_n, v_pad // block_v),
        in_specs=[
            pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
            row, row, row,
        ],
        out_specs=pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
        out_shape=_sds((n_pad, v_pad), logits.dtype,
                                       vma=_vma(logits, targets, lse, g)),
        compiler_params=pltpu.CompilerParams(
            # stateless per tile: both grid dims are parallel
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="xent_bwd",
    )(logits, lanes(targets), lanes(lse), lanes(g))
    return dlogits[:n, :v]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _xent(logits, targets, block_n, block_v, interpret):
    loss, _ = _fwd_call(logits, targets, block_n, block_v, interpret)
    return loss


def _xent_fwd(logits, targets, block_n, block_v, interpret):
    loss, lse = _fwd_call(logits, targets, block_n, block_v, interpret)
    return loss, (logits, targets, lse)


def _xent_bwd(block_n, block_v, interpret, res, g):
    logits, targets, lse = res
    import os

    # compiled path (TPU): the Pallas backward kernel; interpret mode
    # falls back to blocked jnp unless KF_PALLAS_BWD=pallas forces the
    # kernel (how the numerics tests run off-TPU)
    if interpret and os.environ.get("KF_PALLAS_BWD", "") != "pallas":
        dlogits = _bwd_blocked(logits, targets, lse, g, block_v)
    else:
        dlogits = _bwd_pallas(
            logits, targets, lse, g, block_n, block_v, interpret
        )
    return dlogits.astype(logits.dtype), None


_xent.defvjp(_xent_fwd, _xent_bwd)


#: Per-shape kernel-vs-XLA routing thresholds, seeded from v5e
#: measurements taken before PR 1 and unmeasured on today's code: no cell
#: sits on the far side of either, and ROADMAP R8 decides them in one
#: (``scope_ms_per_step.head``).  What the old sweep read:
#:
#: * fwd-only: the kernel streams the logits once and beats XLA's
#:   materialized log-softmax ~2x at HBM scale (2.49 vs 5.00 ms at
#:   N=8192, V=32768).  Below ~4M logits elements both are microseconds
#:   and the pallas call overhead can lose — route XLA there.
#: * fwd+bwd (training): XLA fuses the dlogits-consumer epilogue into
#:   its backward sweep and wins ~2x (4.69 vs 2.30 ms at the same
#:   shape) — UNLESS its O(N*V) log-prob + residual set does not fit,
#:   where the kernel is the only variant that runs at all (the batch-8
#:   LM OOMs only the XLA path on 16 GiB).  The byte estimate is
#:   logits + f32 log-probs per element.
XENT_FWD_MIN_ELEMENTS = 1 << 22
XENT_TRAIN_XLA_BUDGET_MB = 2048


class _Knobs(LaunchKnobs):
    """The ``KF_TPU_XENT`` / ``KF_XENT_XLA_BUDGET_MB`` /
    ``KF_XENT_FWD_MIN_ELEMENTS`` routing knobs.

    These were always documented as launch-set (they pick which kernel
    gets traced for a shape and carry no cluster-size state), but the
    reads used to execute AT TRACE TIME inside jitted callers, each
    carrying a ``kflint: allow(recompile-hazard)`` waiver.  Hoisting the
    reads into the launch-knob base makes the documented semantics
    real — a mid-run env mutation never silently changes what the next
    trace compiles — and retires the waivers.  Tests and tools that
    mutate the environment call ``XENT_ENV.reload()`` afterwards (fresh
    processes, the normal launcher path, pick the values up at
    import)."""

    def _read(self) -> None:
        mode = os.environ.get("KF_TPU_XENT", "auto").lower()
        if mode == "xla":
            mode = "plain"  # long-standing alias
        if mode not in ("fused", "plain", "auto"):
            # fail loudly AT LOAD: a typo silently auto-routing (or
            # silently going plain, as pre-round-4 code did) hides the
            # misconfiguration
            raise ValueError(
                f"KF_TPU_XENT={mode!r}: one of fused | plain | xla | auto"
            )
        self.mode = mode
        self.budget_mb = int(os.environ.get(
            "KF_XENT_XLA_BUDGET_MB", str(XENT_TRAIN_XLA_BUDGET_MB)))
        self.fwd_min_elements = int(os.environ.get(
            "KF_XENT_FWD_MIN_ELEMENTS", str(XENT_FWD_MIN_ELEMENTS)))


XENT_ENV = _Knobs()


def _route_fused(n: int, v: int, itemsize: int, training: bool) -> bool:
    """True = take the Pallas kernel for this (shape, dtype, phase)."""
    if training:
        resid_bytes = n * v * (itemsize + 4)
        return resid_bytes > (XENT_ENV.budget_mb << 20)
    return n * v >= XENT_ENV.fwd_min_elements


def route_fused_lm_head(n_tokens: int, vocab: int) -> bool:
    """Should a training loss skip materializing logits entirely and take
    the fused LM-head kernel (:mod:`kungfu_tpu.ops.pallas.lm_head`)?

    Owns the one assumption callers kept duplicating: the plain path's
    logits are f32 (``Transformer.apply`` casts), so the residual bound
    is the training branch of :func:`_route_fused` at itemsize 4 — the
    same budget that routes :func:`token_nll` to the xent kernel."""
    return _route_fused(n_tokens, vocab, 4, training=True)


def token_nll(logits, targets, training: bool = True):
    """Mean next-token NLL with the fused/plain dispatch.

    The single owner of the ``KF_TPU_XENT`` switch (``fused`` | ``plain``
    | ``auto``): both the standalone
    :meth:`~kungfu_tpu.models.transformer.Transformer.loss` head and the
    sharded trainer's pipeline head route through here, so the mode
    semantics can't drift between the two loss paths.  Fused keeps the
    O(N·V) log-prob tensor and its autodiff residuals out of HBM.

    ``auto`` (the default) routes per shape on TPU via
    :func:`_route_fused` — the round-3 always-fused policy sent every
    caller to the kernel, including training shapes where XLA's fused
    backward is ~2x faster.  ``training=False`` lets eval-only callers
    opt into the fwd-only crossover (the kernel wins much earlier
    there); the default assumes gradients will flow.

    The mode is the launch-set :data:`XENT_ENV` knob — read at import,
    not at trace time; mutate the env then call ``XENT_ENV.reload()``
    to re-route (tests)."""
    mode = XENT_ENV.mode
    if mode == "fused":
        fused = True
    elif mode == "plain" or jax.default_backend() != "tpu":
        fused = False
    else:  # auto on TPU: per-shape routing
        v = logits.shape[-1]
        n = 1
        for d in logits.shape[:-1]:
            n *= d
        fused = _route_fused(n, v, jnp.dtype(logits.dtype).itemsize,
                             training)
    if fused:
        return jnp.mean(softmax_cross_entropy(logits, targets))
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1).squeeze(-1)
    return jnp.mean(nll)


def softmax_cross_entropy(
    logits,
    targets,
    block_n: Optional[int] = None,
    block_v: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Per-token NLL for ``logits`` [..., V] and int targets [...].

    Matches ``-log_softmax(logits)[target]`` numerically; differentiable
    w.r.t. logits.  ``block_v=None`` shrinks the default tile to the
    128-rounded vocab so small vocabs (tests, toy models) don't pad up
    to a whole 2048-wide block."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    v = logits.shape[-1]
    if block_v is None:
        block_v = min(DEFAULT_BLOCK_V, ((max(v, 1) + 127) // 128) * 128)
    if block_n is None:
        block_n = DEFAULT_BLOCK_N
    lead = logits.shape[:-1]
    out = _xent(
        logits.reshape(-1, v),
        targets.reshape(-1).astype(jnp.int32),
        block_n, block_v, interpret,
    )
    return out.reshape(lead)
