"""``evabyte`` (EvaByte), plainly: the forward pass in ``jax.numpy`` and
float32 with every matrix product at ``highest`` precision.  No kernels,
no cache, no batching, and nothing of the program: it is handed a
configuration file's sizes (``lib/evabyte.py``) and the weights the
benchmark made.  Written from the equations:

    r = float32(x);  h = RMSNorm(x) = x / sqrt(mean(x^2) + eps) (1 + g)
    q, k, v = h W_q, h W_k, h W_v in heads of D; q and k rotated by the
        position over the whole head width on split halves,
        (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin), 1 / theta^(2i / D)
    x = r + EVA(q, k, v) W_o
    x = x + (silu(h' W_gate) * (h' W_up)) W_down,    h' = RMSNorm'(x)
    logits = RMSNorm_f(x) W_head,  W_head d x (heads x ids); head j is
        byte t + 1 + j

EVA, a head with its own ``mu``, ``phi``; ``W`` the window, ``C`` the
chunk, ``w(t) = t // W``, chunk ``c`` the positions ``C c .. C c + C -
1``::

    alpha_cj = softmax over the chunk's j of (k_j . mu) / sqrt(D)
    k~_c = sum_j alpha_cj k_j + phi          v~_c = sum_j alpha_cj v_j
    E_t = {j : w(j) = w(t), j <= t}          R_t = {c : c < (W / C) w(t)}
    o_t = one softmax over E_t's exact rows and R_t's chunk rows of
          q_t . k / sqrt(D), times the values

The sequence is padded with zeros to whole windows (a position past the
end reaches none before it: exact rows are causal, and a chunk row is
seen only by windows after its own).  So that a sequence of 32,768
positions fits on the chip BESIDE the engine (the timed loop's slabs are
still there when the comparison runs), a layer is computed a window at a
time -- a window's rows need the window's own keys, all ``E_t`` can
hold, and the chunk rows of the windows before it, which a ``lax.scan``
carries -- and inside a window the attention :data:`QUERY_ROWS` query
rows at a time.

``cast`` is the hook of the control: applied to both inputs of every
matrix product, the pooling's and the attention's among them, so
``cast=to_fp8`` computes the same model in the nearest precision below
bfloat16.  The reference leaves it ``None``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from kfbench.lib.evabyte import sizes

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: query rows attended at once ([heads, rows, keys] float32 scores)
QUERY_ROWS = 256


def to_fp8(x):
    """Round to float8 e4m3 (3 bits of mantissa) and back."""
    return x.astype(jnp.float8_e4m3fn).astype(F32)


def _mm(a, b, cast, spec=None):
    a, b = a.astype(F32), b.astype(F32)
    if cast is not None:
        a, b = cast(a), cast(b)
    if spec:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(p, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * (1.0 + p["g"])


def _rotate(x, pos, theta):
    """x [S, H, D] at positions pos [S]: split halves."""
    half = x.shape[-1] // 2
    angles = pos[:, None].astype(F32) * theta ** (
        -jnp.arange(half, dtype=F32) / half)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(z, lp, x, cast):
    """x [S, d], S whole windows -> [S, d], a window at a time: a window
    needs its own rows and the chunk rows of the windows before it, and
    leaves its own chunk rows to those after."""
    s, d = x.shape
    h, hd, c, w = z["heads"], z["head_dim"], z["chunk"], z["window"]
    rows = min(QUERY_ROWS, w)
    scale = 1.0 / math.sqrt(hd)

    def window(carry, xs):
        k_rows, v_rows = carry                  # [S / C, H, D], zeros ahead
        xw, i = xs                              # [W, d], the window's index
        pos = i * w + jnp.arange(w)
        y = _rmsnorm(lp["ln_attn"], xw, z["eps"])
        q, k, v = (_mm(y, lp[name]["w"], cast).reshape(w, h, hd)
                   for name in ("wq", "wk", "wv"))
        q, k = _rotate(q, pos, z["theta"]), _rotate(k, pos, z["theta"])
        closed = jnp.arange(s // c) < (w // c) * i

        def some(j):
            t = j * rows + jnp.arange(rows)     # the queries, in the window
            qb = jax.lax.dynamic_slice_in_dim(q, j * rows, rows, 0)
            exact = _mm(qb, k, cast, "qhd,jhd->hqj") * scale
            exact = jnp.where(jnp.arange(w)[None, :] <= t[:, None], exact,
                              -jnp.inf)
            pooled = _mm(qb, k_rows, cast, "qhd,nhd->hqn") * scale
            pooled = jnp.where(closed[None, :], pooled, -jnp.inf)
            probs = jax.nn.softmax(jnp.concatenate([exact, pooled], -1),
                                   axis=-1)
            return _mm(probs[..., :w], v, cast, "hqj,jhd->qhd") \
                + _mm(probs[..., w:], v_rows, cast, "hqn,nhd->qhd")

        o = jax.lax.map(some, jnp.arange(w // rows)).reshape(w, h * hd)
        # this window's chunk rows, for the windows after it
        kc, vc = k.reshape(w // c, c, h, hd), v.reshape(w // c, c, h, hd)
        alpha = jax.nn.softmax(
            _mm(kc, lp["mu"], cast, "nchd,hd->nch") * scale, axis=1)
        k_rows = jax.lax.dynamic_update_slice_in_dim(
            k_rows, _mm(alpha, kc, cast, "nch,nchd->nhd")
            + lp["phi"].astype(F32), i * (w // c), 0)
        v_rows = jax.lax.dynamic_update_slice_in_dim(
            v_rows, _mm(alpha, vc, cast, "nch,nchd->nhd"), i * (w // c), 0)
        xw = xw + _mm(o, lp["wo"]["w"], cast)
        y = _rmsnorm(lp["ln_ffn"], xw, z["eps"])
        xw = xw + _mm(jax.nn.silu(_mm(y, lp["gate"]["w"], cast))
                      * _mm(y, lp["up"]["w"], cast), lp["down"]["w"], cast)
        return (k_rows, v_rows), xw

    none = jnp.zeros((s // c, h, hd), F32)
    _, out = jax.lax.scan(window, (none, none),
                          (x.reshape(s // w, w, d), jnp.arange(s // w)))
    return out.reshape(s, d)


def _stream(cfg, params, ids, cast):
    """ids [S] -> (the sizes, the normalised last stream [S, d])."""
    z = sizes(cfg)
    s = ids.shape[0]
    ids = jnp.pad(ids, (0, -s % z["window"]))
    x = params["embed"]["table"][ids].astype(F32)
    for li in range(z["layers"]):
        x = _block(z, params[f"layer_{li}"], x, cast)
    return z, _rmsnorm(params["ln_f"], x, z["eps"])[:s]


def all_logits(cfg, params, ids, cast=None):
    """ids [S] int32 -> logits [S, heads, ids] float32, one sequence:
    head ``j`` at position ``t`` is byte ``t + 1 + j``."""
    z, x = _stream(cfg, params, ids, cast)
    return _mm(x, params["head"]["w"], cast).reshape(
        ids.shape[0], z["pred_heads"], z["vocab"])


def logits(cfg, params, ids, cast=None):
    """ids [S] int32 -> the next byte's logits [S, ids] float32 (head
    0, the one that is decoded), one sequence."""
    z, x = _stream(cfg, params, ids, cast)
    return _mm(x, params["head"]["w"][:, :z["vocab"]], cast)
