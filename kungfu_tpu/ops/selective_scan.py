"""The selective state-space recurrence (Mamba, arXiv:2312.00752), in the
three forms a served model needs.  A layer of ``E`` channels keeps a
state ``h`` ``[N, E]`` (``N`` numbers a channel; kept with the channels
last, so that they lie along the TPU's lanes and the state is not
padded eightfold) and, for a token with input ``c`` ``[E]``, step ``dt``
``[E]`` (positive), input and output vectors ``B``, ``C`` ``[N]``::

    h[n, e] <- exp(dt[e] A[n, e]) h[n, e] + dt[e] c[e] B[n]
    y[e]     = sum_n h[n, e] C[n] + D[e] c[e]

with ``A`` ``[N, E]`` negative and ``D`` ``[E]`` the layer's own.  Every
channel's recurrence is linear and elementwise, ``h <- a h + b``.

* :func:`step` -- one token for every slot of a decode step: one
  elementwise pass over the state that also takes the read-out (the
  update and the sum over ``N`` fuse into one loop: the state is read
  once and written once, where it lies).  Its shape is the slab's
  whatever is live; ``live`` only selects what is written.
* :func:`serial` -- one sequence, a token at a time in a ``lax.scan``:
  the definition the tests hold the other two to.
* :func:`chunked` -- a whole prompt, ``CHUNK`` positions at a time: the
  pairs ``(a_t, b_t)`` of a chunk compose associatively (``(a, b) then
  (a', b') = (a a', a' b + b')``; decays are at most 1, so nothing
  overflows however strong they are), so a chunk is a
  ``lax.associative_scan`` over its positions and what is left to the
  ``lax.scan`` over chunks is the state carried from one to the next.
  The ``[CHUNK, N, E]`` pairs exist for one chunk at a time (21 MB each
  at 64 positions of 16 x 5,120; 335 MB for a whole 1,024-token bucket).

Float32 throughout, in XLA; neither has a backward pass nor a kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: positions the chunked form takes at once
CHUNK = 64


def step(h, c, dt, A, B, C, D, live):
    """One token a slot: ``h`` ``[S, N, E]`` (any float dtype; the
    arithmetic is float32), ``c``/``dt`` ``[S, E]``, ``B``/``C`` ``[S,
    N]``, ``A`` ``[N, E]``, ``D`` ``[E]``, ``live`` ``[S]`` bool -> (the
    new state, shaped and typed like ``h``; ``y`` ``[S, E]`` float32).  A
    slot that is not live gets its state back as it was read (its ``y``
    is nobody's).  Every slot is processed every step: nothing here has
    a shape, a trip count or a branch that follows ``live``."""
    s = h.astype(F32)
    c, dt, B, C = (x.astype(F32) for x in (c, dt, B, C))
    new = (jnp.exp(dt[:, None, :] * A) * s
           + (dt * c)[:, None, :] * B[:, :, None])
    y = jnp.sum(new * C[:, :, None], axis=1) + D * c
    new = jnp.where(live[:, None, None], new, s)
    return new.astype(h.dtype), y


def serial(c, dt, A, B, C, D, h0):
    """One sequence, token by token: ``c``/``dt`` ``[T, E]``, ``B``/``C``
    ``[T, N]``, from the state ``h0`` ``[N, E]`` -> (``y`` ``[T, E]``,
    the state after ``T`` tokens), float32."""
    def token(h, x):
        c, dt, B, C = x
        h = jnp.exp(dt * A) * h + (dt * c) * B[:, None]
        return h, jnp.sum(h * C[:, None], axis=0) + D * c

    h, y = jax.lax.scan(token, h0.astype(F32), tuple(
        x.astype(F32) for x in (c, dt, B, C)))
    return y, h


def _compose(first, then):
    a, b = first
    a2, b2 = then
    return a * a2, a2 * b + b2


def chunked(c, dt, A, B, C, D, h0, n=None, chunk=None):
    """One sequence, ``chunk`` positions at a time (:data:`CHUNK` where it
    is not given): the arguments of :func:`serial` -> (``y`` ``[T, E]``
    float32, the state after the first ``n`` positions, float32).
    Positions ``>= n`` (a bucket's padding; ``n`` may be traced) are
    given ``dt = 0``, under which the rule leaves the state alone
    (``a = 1, b = 0``); their ``y`` is nobody's."""
    t_len = c.shape[0]
    size = min(chunk or CHUNK, t_len)
    pad = -t_len % size
    c, dt, B, C = (x.astype(F32) for x in (c, dt, B, C))
    if n is not None:       # (zeros are also what the padding to size adds)
        dt = jnp.where((jnp.arange(t_len) < n)[:, None], dt, 0.0)

    def chunks(x):
        x = jnp.pad(x, ((0, pad), (0, 0)))
        return x.reshape((-1, size) + x.shape[1:])

    def one(h, x):
        c, dt, B, C = x                           # [size, E] and [size, N]
        a = jnp.exp(dt[:, None, :] * A)           # [size, N, E]
        b = (dt * c)[:, None, :] * B[:, :, None]
        a, b = jax.lax.associative_scan(_compose, (a, b), axis=0)
        hs = a * h + b                            # the state after each
        return hs[-1], jnp.sum(hs * C[:, :, None], axis=1) + D * c

    h, y = jax.lax.scan(one, h0.astype(F32),
                        (chunks(c), chunks(dt), chunks(B), chunks(C)))
    return y.reshape(t_len + pad, -1)[:t_len], h
