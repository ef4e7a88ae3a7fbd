"""``cohere2_moe`` (Command A+), plainly: the published forward pass in
``jax.numpy`` and float32 with every matrix product at ``highest``
precision.  No kernels, no cache, no batching, and nothing of the
program: it is handed a configuration file's sizes (``lib/cohere2.py``)
and the weights the benchmark made.

Per layer, ``x = LayerNorm(h)`` (scale only), attention and the expert
layer both from ``x``, ``h <- h + attn + ffn``; window layers rotate q
and k on interleaved pairs and see ``0 <= q_pos - k_pos < window``, the
full layers see no positions and every earlier key; query head ``j``
reads key/value head ``j // (heads / kv_heads)``; the head is the
embedding itself times ``logit_scale``.

Departures from the published description, each the configuration
file's (``reduced`` / ``assumed``):

* the chip's share: only experts ``first .. first + held`` exist here.
  The router scores all ``router_width`` experts and normalises over all
  ``top_k`` chosen; what the absent experts would add is left out;
* the vocabulary is the held slice, the depth the held stage;
* ``average``: the mean of the shared experts, added to the routed sum;
* no selection bias on the router (the config has no key for one).

Beside 9.5 GB of bfloat16 weights nothing large may live long: a
projection or an expert is upcast where it is used (the experts one at
a time, in a scan), attention goes in blocks of query rows.

``cast`` is the hook of the control: applied to both inputs of every
matrix product, so ``cast=to_fp8`` computes the same model in the
nearest precision below bfloat16.  The reference leaves it ``None``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from kfbench.lib.cohere2 import sizes

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: query rows attended at once ([heads, rows, keys] float32 scores)
QUERY_ROWS = 128


def to_fp8(x):
    """Round to float8 e4m3 (3 bits of mantissa) and back."""
    return x.astype(jnp.float8_e4m3fn).astype(F32)


def _mm(a, b, cast):
    a, b = a.astype(F32), b.astype(F32)
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _layernorm(p, x, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"]


def _rotate(x, positions, theta):
    """x [S, heads, D]: pairs (2i, 2i+1) turned by pos * theta^(-2i/D)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None, None] * freqs
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _attention(z, q, k, v, window, cast):
    """q [S, H, D], k/v [S, G, D] -> [S, H * D]; ``window`` None: full."""
    s, h, d = q.shape
    g = z["kv_heads"]
    k = jnp.repeat(k, h // g, axis=1).transpose(1, 0, 2)     # [H, S, D]
    v = jnp.repeat(v, h // g, axis=1).transpose(1, 0, 2)
    rows = QUERY_ROWS if s % QUERY_ROWS == 0 else s
    k_pos = jnp.arange(s)

    def some(i):
        q_pos = i * rows + jnp.arange(rows)
        qb = jax.lax.dynamic_slice_in_dim(q, i * rows, rows, 0)
        scores = _mm(qb.transpose(1, 0, 2), k.transpose(0, 2, 1), cast
                     ) / math.sqrt(d)                         # [H, rows, S]
        see = k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            see = see & (q_pos[:, None] - k_pos[None, :] < window)
        probs = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
        return _mm(probs, v, cast).transpose(1, 0, 2)         # [rows, H, D]

    o = jax.lax.map(some, jnp.arange(s // rows))
    return o.reshape(s, h * d)


def _gated(x, gate, up, down, cast):
    return _mm(jax.nn.silu(_mm(x, gate, cast)) * _mm(x, up, cast), down, cast)


def _experts(z, p, x, cast):
    """routed (the held experts' part) + the mean of the shared."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"]["w"].astype(F32),
                                  precision=HIGHEST))         # [S, E]
    w, idx = jax.lax.top_k(s, z["top_k"])
    w = w / jnp.sum(w, axis=-1, keepdims=True)

    def add(acc, e):
        local, (gate, up, down) = e
        we = jnp.sum(jnp.where(idx == z["first"] + local, w, 0.0), axis=-1)
        return acc + we[:, None] * _gated(x, gate, up, down, cast), None

    ep = p["experts"]
    routed, _ = jax.lax.scan(
        add, jnp.zeros_like(x),
        (jnp.arange(z["held"]), (ep["gate"], ep["up"], ep["down"])))
    sp = p["shared"]
    shared, _ = jax.lax.scan(
        lambda acc, m: (acc + _gated(x, *m, cast), None),
        jnp.zeros_like(x), (sp["gate"], sp["up"], sp["down"]))
    return routed + shared / z["shared"]


def _block(z, lp, li, h, cast):
    s = h.shape[0]
    x = _layernorm(lp["ln"], h, z["eps"])
    q = _mm(x, lp["wq"]["w"], cast).reshape(s, z["heads"], z["head_dim"])
    k = _mm(x, lp["wk"]["w"], cast).reshape(s, z["kv_heads"], z["head_dim"])
    v = _mm(x, lp["wv"]["w"], cast).reshape(s, z["kv_heads"], z["head_dim"])
    window = None
    if (li + 1) % z["period"]:  # a window layer; the full ones: no positions
        window = z["window"]
        q = _rotate(q, jnp.arange(s), z["theta"])
        k = _rotate(k, jnp.arange(s), z["theta"])
    attn = _mm(_attention(z, q, k, v, window, cast), lp["wo"]["w"], cast)
    return h + attn + _experts(z, lp["moe"], x, cast)


def logits(cfg, params, ids, cast=None):
    """ids [S] int32 -> logits [S, vocab held] float32, one sequence."""
    z = sizes(cfg)
    table = params["embed"]["table"]
    h = table[ids].astype(F32)
    for li in range(z["layers"]):
        h = _block(z, params[f"layer_{li}"], li, h, cast)
    h = _layernorm(params["ln_f"], h, z["eps"])
    return z["logit_scale"] * _mm(h, table.astype(F32).T, cast)
