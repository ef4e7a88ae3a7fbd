"""The gated delta rule with a per-channel decay (the KDA layer of Kimi
Linear, arXiv:2510.26692), in the two forms a served model needs.  Per
head the layer keeps a matrix ``S`` ``[K, V]`` (keys by values) and, for
a token with query ``q`` and key ``k`` ``[K]``, value ``v`` ``[V]``,
decay ``a = exp(g)`` in ``(0, 1]^K`` and step ``b`` in ``[0, 2]``::

    S' = diag(a) S            the state forgets, a channel at its own rate
    S  = S' + b k (v - S'^T k)^T      ... and is corrected towards v at k
    o  = S^T q

The decay comes in as its LOGARITHM ``g <= 0`` everywhere: a product of
many decays underflows long before its logarithm loses a digit, and the
chunked form needs quotients of such products.

* :func:`kda_step` -- one token for every slot of a decode step.  Both
  read-outs come from ONE pass over the old state (``S'^T k = S^T (a *
  k)`` and ``S^T q = S^T (a * q) + (k . q) delta``), the update is a
  second pass that writes it back.  As XLA compiles it that is three
  trips over the state (it cannot hold a head's matrix across the
  reduction, so the update reads the state again); :func:`kda_update`,
  which a served layer calls, takes the same rule as one kernel on the
  TPU -- each head's matrix read once and written once, where it lies,
  and only the LIVE slots' (``ops/pallas/kda_step.py``) -- and this form
  everywhere else.  Either has the slab's shapes whatever is live; this
  form moves every slot's state under a select.
* :func:`kda_chunked` -- a whole prompt, ``CHUNK`` positions at a time.
  Within a chunk the corrections ``w_s = b_s (v_s - S'_s^T k_s)`` solve a
  unit lower-triangular system that does not involve the state the chunk
  starts from, so the systems of ALL chunks are solved at once; what is
  left to a ``lax.scan`` over the chunks is four small products a
  chunk.

Float32 on the vector unit for the one-token form, in XLA and in the
kernel alike (exact, and bound by the state's bytes anyway); XLA's
products at ``highest`` precision for the chunked one, which has no
kernel.  Neither has a backward pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: positions the chunked form takes at once
CHUNK = 64


def kda_step(S, q, k, v, g, b, live):
    """One token a slot: ``S`` ``[B, H, K, V]`` (any float dtype; the
    arithmetic is float32), ``q``/``k``/``g`` ``[B, H, K]``, ``v`` ``[B,
    H, V]``, ``b`` ``[B, H]``, ``live`` ``[B]`` bool -> (the new state,
    shaped and typed like ``S``; ``o`` ``[B, H, V]`` float32).  A slot
    that is not live gets its state back as it was read (its ``o`` is
    nobody's).  Every slot is processed every step: nothing here has a
    shape, a trip count or a branch that follows ``live``."""
    s = S.astype(F32)
    q, k, v, b = (x.astype(F32) for x in (q, k, v, b))
    a = jnp.exp(g.astype(F32))
    # S'^T k and S'^T q, as one reduction over the old state
    both = jnp.stack([a * k, a * q], axis=2)                  # [B, H, 2, K]
    read = jnp.sum(s[:, :, None] * both[..., None], axis=3)   # [B, H, 2, V]
    delta = b[..., None] * (v - read[:, :, 0])
    o = read[:, :, 1] + delta * jnp.sum(k * q, axis=-1, keepdims=True)
    new = a[..., None] * s + k[..., None] * delta[:, :, None, :]
    new = jnp.where(live[:, None, None, None], new, s)
    return new.astype(S.dtype), o


def kda_update_heads(heads: int, dk: int, dv: int, dtype):
    """The heads a grid step holds where a decode step's update of a
    state of ``heads`` matrices ``[dk, dv]`` is ONE fused kernel a layer
    (``ops/pallas/kda_step.py``), or None where it is :func:`kda_step`:
    off the TPU, and for shapes the kernel does not tile.  One choice,
    from the platform and the shapes, at trace time; the kernel's
    package is imported here and by no module's import, so a process
    that traces no such step never pays for it (PERF.md, PR 35)."""
    if jax.default_backend() != "tpu":
        return None
    from kungfu_tpu.ops.pallas import kda_step as kernel

    return kernel.head_block(heads, dk, dv, dtype)


def kda_moves(heads: int, dk: int, dv: int, dtype, live):
    """What the KDA layers of ONE decode step share, from the slots it is
    ``live`` for (``[B]`` bool): (``walk``, ``moved``).  Where
    :func:`kda_update_heads` gives a head block, ``walk`` is the kernel's
    plan of the blocks its grid steps hold (``kda_step.plan``: a few
    scalar operations, made once here and not once a layer) and
    ``moved`` its own count of the slots whose matrices a call reads and
    writes back -- the live ones; else ``walk`` is None and ``moved`` is
    ``B``: :func:`kda_step` moves every slot under its select."""
    block = kda_update_heads(heads, dk, dv, dtype)
    if not block:
        return None, live.shape[0]
    from kungfu_tpu.ops.pallas import kda_step as kernel

    return kernel.plan(live, heads // block), kernel.slots_walked(live)


def kda_update(state, q, k, v, g, b, live, walk=None):
    """:func:`kda_step` for one layer's state as the serving cache holds
    it, ``[1, B, H, K, V]`` -> (the new state, shaped like ``state``;
    ``o`` ``[B, H, V]`` float32): the kernel, which reads and writes a
    live slot's matrices once and in place and touches no other slot's
    (their ``o`` is zeros), where :func:`kda_update_heads` gives a head
    block -- ``walk`` is then :func:`kda_moves`' for ``live``, or made
    here -- else :func:`kda_step`, which moves every slot's whatever is
    live.  The shapes and the operations are the same whatever is live;
    the slots the kernel skips follow the data, and so does its time."""
    heads = kda_update_heads(*state.shape[2:], state.dtype)
    if heads:
        from kungfu_tpu.ops.pallas.kda_step import kda_step as kernel

        return kernel(state, q, k, v, g, b, live, heads=heads, walk=walk)
    new, o = kda_step(state[0], q, k, v, g, b, live)
    return new[None], o


def _mm(spec, x, y):
    return jnp.einsum(spec, x, y, precision=HIGHEST,
                      preferred_element_type=F32)


def kda_chunked(q, k, v, g, b, S0, n=None, chunk=None):
    """One sequence: ``q``/``k``/``g`` ``[T, H, K]``, ``v`` ``[T, H,
    V]``, ``b`` ``[T, H]``, from the state ``S0`` ``[H, K, V]`` -> (``o``
    ``[T, H, V]`` float32, the state after the first ``n`` positions,
    float32).  Positions ``>= n`` (a bucket's padding; ``n`` may be
    traced) are given ``a = 1, b = 0``, under which the rule leaves the
    state alone; their ``o`` is nobody's.  ``chunk`` positions at a time
    (:data:`CHUNK` where it is not given).

    With ``G_t`` the decay's logarithm summed from the chunk's start to
    ``t`` (inclusive) and ``D_ts = exp(G_t - G_s)`` for ``s <= t`` (a
    difference of logarithms, so never a quotient that overflows; its
    error is float32's at the size of ``G``, ``chunk`` times the strongest
    decay's logarithm: 1e-5 at the published layer's decays)::

        w = (I + diag(b) tril(A, -1))^-1 diag(b) (V - (e^G * K) S0)
            A_ts = sum_c k_t[c] k_s[c] D_ts[c]
        o = (e^G * Q) S0 + tril(B) w      B_ts = sum_c q_t[c] k_s[c] D_ts[c]
        S_end = diag(e^G_end) S0 + (K * D_end,s)^T w
    """
    t_len, heads, _ = q.shape
    dv = v.shape[-1]
    c = min(chunk or CHUNK, t_len)
    pad = -t_len % c
    if n is not None:       # (zeros are also what the padding to c adds)
        keep = jnp.arange(t_len) < n
        g = jnp.where(keep[:, None, None], g, 0.0)
        b = jnp.where(keep[:, None], b, 0.0)

    def chunks(x):
        """[T, H, ...] -> float32 [chunks, H, c, ...], zero-padded."""
        x = jnp.pad(x.astype(F32), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return jnp.moveaxis(x.reshape((-1, c) + x.shape[1:]), 2, 1)

    q, k, v, b = chunks(q), chunks(k), chunks(v), chunks(b)   # b [N, H, c]
    G = jnp.cumsum(chunks(g), axis=2)                         # [N, H, c, K]
    lower = jnp.tril(jnp.ones((c, c), bool))[..., None]

    def pairs(x):
        """A and B of one chunk.  The decays ``D`` ``[H, c, c, K]`` exist
        for one chunk at a time (134 MB at 64 heads of 128; for all the
        chunks of a 2,048-token prompt they were 4.3 GB of the compiled
        prefill)."""
        k, q, G = x
        k_d = k[:, None] * jnp.exp(jnp.where(
            lower, G[:, :, None] - G[:, None, :], -jnp.inf))
        return (jnp.sum(k[:, :, None] * k_d, axis=-1),
                jnp.sum(q[:, :, None] * k_d, axis=-1))

    A, B = jax.lax.map(pairs, (k, q, G))                      # [N, H, c, c]
    system = jnp.eye(c, dtype=F32) + b[..., None] * jnp.tril(A, -1)
    e_g = jnp.exp(G)
    rhs = b[..., None] * jnp.concatenate([v, e_g * k], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        system, rhs, lower=True, unit_diagonal=True)
    per_chunk = (solved[..., :dv], solved[..., dv:], e_g * q, jnp.tril(B),
                 k * jnp.exp(G[:, :, -1:] - G), e_g[:, :, -1])

    def one(S, x):
        u, wk, q_g, b_mat, k_end, g_end = x
        w = u - _mm("hck,hkv->hcv", wk, S)
        o = _mm("hck,hkv->hcv", q_g, S) + _mm("hts,hsv->htv", b_mat, w)
        return g_end[..., None] * S + _mm("hck,hcv->hkv", k_end, w), o

    S, o = jax.lax.scan(one, S0.astype(F32), per_chunk)
    o = jnp.moveaxis(o, 1, 2).reshape(t_len + pad, heads, dv)
    return o[:t_len], S


def causal_conv(u, w, tail, n=None):
    """A causal depthwise convolution over the last ``taps`` positions of
    one sequence: ``u`` ``[T, C]``, ``w`` ``[taps, C]`` (the last tap is
    the position's own), ``tail`` ``[taps - 1, C]`` the inputs of the
    positions before the first -> (``y`` ``[T, C]`` float32, the tail
    after ``n`` positions: the inputs at ``n - taps + 1 .. n - 1``, the
    old tail's where the sequence is shorter)."""
    t_len, taps = u.shape[0], w.shape[0]
    seen = jnp.concatenate([tail.astype(u.dtype), u], axis=0)
    y = sum(w[i].astype(F32) * seen[i:i + t_len].astype(F32)
            for i in range(taps))
    at = t_len if n is None else n
    return y, jax.lax.dynamic_slice_in_dim(seen, at, taps - 1, axis=0)
