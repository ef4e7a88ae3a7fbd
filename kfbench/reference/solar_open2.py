"""``solar_open2`` (Solar Open 2), plainly: the forward pass in
``jax.numpy`` and float32 with every matrix product at ``highest``
precision.  No kernels, no cache, no chunks, no batching, and nothing of
the program: it is handed a configuration file's sizes
(``lib/solar_open2.py``) and the weights the benchmark made.

Per layer ``h += mixer(RMSNorm(h))``, ``h += moe(RMSNorm(h))``; a final
RMSNorm and an untied head.  The mixer of a layer in ``gqa_layers`` is
causal softmax attention with grouped heads and no positions, gated
elementwise, ``W_o [softmax(q k^T / sqrt(D)) v * sigmoid(W_gate x)]``.
Every other layer's is the gated delta rule with a per-channel decay
(KDA), TOKEN BY TOKEN in a ``lax.scan``: per head, from ``S = 0``, ::

    S' = diag(a_t) S;  S = S' + b_t k_t (v_t - S'^T k_t)^T;  o_t = S^T q_t

with ``q, k, v`` the SiLU of a causal depthwise convolution over the
last four positions (four shifted products) of ``W_q x, W_k x, W_v x``,
``q`` and ``k`` normalised a head (``q`` also divided by ``sqrt(K)``),
``a_t = exp(-exp(A_h) softplus(W_f2 W_f1 x_t + b_dt))``, ``b_t = 2
sigmoid(W_b x_t)``, and ``y_t = W_o [RMSNorm_head(o_t) * sigmoid(W_g2
W_g1 x_t + b_g)]``.  The FFN is the normalised sigmoid top-8 sum of the
held experts plus the shared expert.  Recurrence and convolution are
causal, so zero padding past a sequence's end cannot reach a position
before it.

Departures from the published description, each the configuration
file's (``reduced`` / ``assumed``):

* the chip's share: only experts ``first .. first + held`` exist here.
  The router scores all ``router_width`` experts and normalises over all
  ``top_k`` chosen; what the absent experts would add is left out;
* the vocabulary is the held slice, the depth one period of four;
* the program keeps ``W_q, W_k, W_v`` of a KDA layer side by side as one
  matrix ``w_qkv`` (and the three convolutions as one): the same numbers;
* ``q`` and ``k`` are normalised as ``x / sqrt(sum x^2 + 1e-6)``.

``cast`` is the hook of the control: applied to both inputs of every
matrix product -- the recurrence's two read-outs of the state among them
-- so ``cast=to_fp8`` computes the same model in the nearest precision
below bfloat16.  The reference leaves it ``None``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from kfbench.lib.solar_open2 import sizes

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
        * p["scale"]


def _gqa(z, p, x, cast):
    """x [S, d] -> [S, d]: ``heads`` query heads over ``kv_heads`` keys
    and values, causal, no positions, the gate before ``W_o``."""
    s = x.shape[0]
    h, g, hd = z["heads"], z["kv_heads"], z["head_dim"]
    q = _mm(x, p["wq"]["w"], cast).reshape(s, g, h // g, hd)
    k = _mm(x, p["wk"]["w"], cast).reshape(s, g, hd)
    v = _mm(x, p["wv"]["w"], cast).reshape(s, g, hd)
    rows = QUERY_ROWS if s % QUERY_ROWS == 0 else s
    pos = jnp.arange(s)

    def some(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * rows, rows, 0)
        scores = _mm(qb, k, cast, "qgjd,sgd->gjqs") / math.sqrt(hd)
        see = pos[None, :] <= (i * rows + jnp.arange(rows))[:, None]
        probs = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
        return _mm(probs, v, cast, "gjqs,sgd->qgjd")

    o = jax.lax.map(some, jnp.arange(s // rows)).reshape(s, h * hd)
    gate = jax.nn.sigmoid(_mm(x, p["w_gate"]["w"], cast))
    return _mm(o * gate, p["wo"]["w"], cast)


def _kda(z, p, x, cast):
    """x [S, d] -> [S, d]: the gated delta rule, a token at a time."""
    s = x.shape[0]
    h, hd, taps = z["kda_heads"], z["kda_dim"], z["taps"]
    c = h * hd
    u = _mm(x, p["w_qkv"]["w"], cast)                         # [S, 3c]
    # the convolution as shifted products: tap ``taps - 1`` is the
    # position's own, tap 0 the one ``taps - 1`` before it
    w = p["conv"].astype(F32)
    y = jax.nn.silu(sum(
        w[i] * jnp.pad(u, ((taps - 1 - i, 0), (0, 0)))[:s]
        for i in range(taps)))
    q, k, v = (y[:, i * c:(i + 1) * c].reshape(s, h, hd) for i in range(3))
    unit = lambda t: t / jnp.sqrt(
        jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) / math.sqrt(hd), unit(k)
    f = _mm(_mm(x, p["w_f1"]["w"], cast), p["w_f2"]["w"], cast) + p["b_dt"]
    a = jnp.exp(-jnp.exp(p["a_log"])[:, None]
                * jax.nn.softplus(f.reshape(s, h, hd)))
    b = 2.0 * jax.nn.sigmoid(_mm(x, p["w_b"]["w"], cast))     # [S, h]

    def token(S, t):
        q, k, v, a, b = t
        S = a[..., None] * S
        S = S + b[:, None, None] * k[..., None] * (
            v - _mm(S, k, cast, "hkv,hk->hv"))[:, None, :]
        return S, _mm(S, q, cast, "hkv,hk->hv")

    _, o = jax.lax.scan(token, jnp.zeros((h, hd, hd), F32), (q, k, v, a, b))
    gate = jax.nn.sigmoid(
        _mm(_mm(x, p["w_g1"]["w"], cast), p["w_g2"]["w"], cast)
        + p["b_g"].astype(F32))
    o = _rmsnorm(p["o_norm"], o, z["eps"]).reshape(s, c) * gate
    return _mm(o, p["wo"]["w"], cast)


def _gated(x, gate, up, down, cast):
    return _mm(jax.nn.silu(_mm(x, gate, cast)) * _mm(x, up, cast), down, cast)


def _experts(z, p, x, cast):
    """scale x routed (the held experts' part) + the mean of the shared."""
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"]["w"].astype(F32),
                                  precision=HIGHEST))         # [S, E]
    w, idx = jax.lax.top_k(s, z["top_k"])
    w = z["top_k_scale"] * w / jnp.sum(w, axis=-1, keepdims=True)

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
    x = _rmsnorm(lp["ln_mixer"], h, z["eps"])
    if li in z["gqa_layers"]:
        h = h + _gqa(z, lp["gqa"], x, cast)
    else:
        h = h + _kda(z, lp["kda"], x, cast)
    return h + _experts(z, lp["moe"], _rmsnorm(lp["ln_moe"], h, z["eps"]),
                        cast)


def logits(cfg, params, ids, cast=None):
    """ids [S] int32 -> logits [S, vocab held] float32, one sequence."""
    z = sizes(cfg)
    h = params["embed"]["table"][ids].astype(F32)
    for li in range(z["layers"]):
        h = _block(z, params[f"layer_{li}"], li, h, cast)
    h = _rmsnorm(params["ln_f"], h, z["eps"])
    return _mm(h, params["head"]["w"], cast)
