"""``pangu_ultra_moe`` (openPangu-Ultra-MoE), plainly: the published
forward pass in ``jax.numpy`` and float32 with every matrix product at
``highest`` precision.  No kernels, no cache, no batching, and nothing
of the program: it is handed a configuration file's sizes
(``lib/pangu.py``) and the weights the benchmark made.

Per layer (``sandwich_norm``: four RMSNorms) ``h = x + RMSNorm(MLA(
RMSNorm(x)))``, ``y = h + RMSNorm(FFN(RMSNorm(h)))``.  Latent attention in
its UN-ABSORBED form only: ``c_q = RMSNorm(W_dq u)``, ``q_h = W_uq,h c_q``
split into 128 un-rotated and 64 rotated values; ``[c_kv; k_r] = W_dkv
u``, ``c_kv`` normed, ``k_r`` rotated (one for all heads); per head and
row ``k_nope = W_uk,h c_kv``, ``v = W_uv,h c_kv``, formed for every row;
``score = (q_nope . k_nope + q_rope . k_r) / sqrt(192)``, causal.  The
first ``dense`` layers' FFN is a gated SiLU of the dense width; the
others' ``2.5 x`` the normalised sigmoid top-8 sum of the held experts
plus the shared expert.  A final RMSNorm and an untied head.

Departures from the published description, each the configuration
file's (``reduced`` / ``assumed``):

* the chip's share: only experts ``first .. first + held`` exist here.
  The router scores all ``router_width`` experts and normalises over all
  ``top_k`` chosen; what the absent experts would add is left out;
* the vocabulary is the held slice, the depth the held stage with one
  leading dense layer;
* rotary positions on interleaved pairs, no scaling; no selection bias
  and no expert groups on the router; the next-token module is not built.

Beside 6.8 GB of bfloat16 weights nothing large may live long: a
projection or an expert is upcast where it is used (the experts one at
a time, in a scan), attention goes by groups of heads and blocks of
query rows, the dense FFN in blocks of rows: the comparison runs beside
the weights in what the engine's slab left free (6.5 GB; the first form,
all heads at once, asked 6.7 and was refused on the chip).

``cast`` is the hook of the control: applied to both inputs of every
matrix product, so ``cast=to_fp8`` computes the same model in the
nearest precision below bfloat16.  The reference leaves it ``None``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from kfbench.lib.pangu import sizes

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: query rows attended at once ([heads, rows, keys] float32 scores)
QUERY_ROWS = 128
#: rows the dense FFN takes at once ([rows, dense width] float32, twice)
FFN_ROWS = 2048
#: heads attended at once
HEAD_GROUP = 16


def to_fp8(x):
    """Round to float8 e4m3 (3 bits of mantissa) and back."""
    return x.astype(jnp.float8_e4m3fn).astype(F32)


def _mm(a, b, cast):
    a, b = a.astype(F32), b.astype(F32)
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(p, x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * p["scale"]


def _rotate(x, positions, theta):
    """x [S, heads, D]: pairs (2i, 2i+1) turned by pos * theta^(-2i/D)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None, None] * freqs
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _by_rows(fn, x, rows):
    """``fn`` over ``x`` [S, ...], ``rows`` of them at a time."""
    s = x.shape[0]
    if s <= rows or s % rows:
        return fn(x)
    out = jax.lax.map(fn, x.reshape((s // rows, rows) + x.shape[1:]))
    return out.reshape((s,) + out.shape[2:])


def _attention(z, ap, u, cast):
    """Latent attention of one sequence, un-absorbed, the output
    projection applied: u [S, d] -> [S, d].  The heads are taken
    :data:`HEAD_GROUP` at a time (their queries, keys, values and scores
    exist only then; 128 heads' float32 keys and values of 16,384 rows
    are 2.1 GB, their queries 1.6), each group's part of ``W_o`` added
    to the sum."""
    s = u.shape[0]
    h, nope, rope, r = z["heads"], z["nope"], z["rope"], z["kv_rank"]
    g = math.gcd(h, HEAD_GROUP)
    pos = jnp.arange(s)
    c_q = _rmsnorm(ap["q_ln"], _mm(u, ap["wq_a"]["w"], cast), z["eps"])
    kv = _mm(u, ap["wkv_a"]["w"], cast)
    c_kv = _rmsnorm(ap["kv_ln"], kv[:, :r], z["eps"])         # [S, r]
    k_rope = _rotate(kv[:, None, r:], pos, z["theta"])[:, 0]  # [S, rope]
    rows = QUERY_ROWS if s % QUERY_ROWS == 0 else s
    scale = 1.0 / math.sqrt(nope + rope)

    def group(attn, w):
        wq, w_uk, w_uv, wo = w
        q = _mm(c_q, wq, cast).reshape(s, g, nope + rope)
        q_nope = q[..., :nope].transpose(1, 0, 2)             # [g, S, nope]
        q_rope = _rotate(q[..., nope:], pos, z["theta"]).transpose(1, 0, 2)
        # every row's own keys and values, every head's of the group
        k_nope = _mm(c_kv, w_uk.transpose(0, 2, 1), cast)     # [g, S, nope]
        v = _mm(c_kv, w_uv, cast)                             # [g, S, v]

        def some(i):
            q_pos = i * rows + jnp.arange(rows)
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * rows, rows, 1)
            scores = (_mm(cut(q_nope), k_nope.transpose(0, 2, 1), cast)
                      + _mm(cut(q_rope), k_rope.T, cast)) * scale
            see = pos[None, :] <= q_pos[:, None]              # [g, rows, S]
            probs = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
            return _mm(probs, v, cast).transpose(1, 0, 2)     # [rows, g, v]

        o = jax.lax.map(some, jnp.arange(s // rows)).reshape(s, -1)
        return attn + _mm(o, wo, cast), None

    d = ap["wo"]["w"].shape[1]
    by_group = (
        ap["wq_b"]["w"].reshape(-1, h // g, g * (nope + rope)
                                ).transpose(1, 0, 2),
        ap["w_uk"].reshape((h // g, g) + ap["w_uk"].shape[1:]),
        ap["w_uv"].reshape((h // g, g) + ap["w_uv"].shape[1:]),
        ap["wo"]["w"].reshape(h // g, g * z["v"], d))
    attn, _ = jax.lax.scan(group, jnp.zeros((s, d), F32), by_group)
    return attn


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
    eps = z["eps"]
    attn = _attention(z, lp["attn"], _rmsnorm(lp["ln_in"], h, eps), cast)
    h = h + _rmsnorm(lp["ln_post_attn"], attn, eps)
    x = _rmsnorm(lp["ln_pre_mlp"], h, eps)
    if li < z["dense"]:
        m = lp["mlp"]
        ffn = _by_rows(lambda x: _gated(x, m["gate"]["w"], m["up"]["w"],
                                        m["down"]["w"], cast), x, FFN_ROWS)
    else:
        ffn = _experts(z, lp["moe"], x, cast)
    return h + _rmsnorm(lp["ln_post_mlp"], ffn, eps)


def logits(cfg, params, ids, cast=None):
    """ids [S] int32 -> logits [S, vocab held] float32, one sequence."""
    z = sizes(cfg)
    h = params["embed"]["table"][ids].astype(F32)
    for li in range(z["layers"]):
        h = _block(z, params[f"layer_{li}"], li, h, cast)
    h = _rmsnorm(params["ln_f"], h, z["eps"])
    return _mm(h, params["head"]["w"], cast)
