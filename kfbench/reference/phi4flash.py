"""``phi4flash`` (Phi-4-mini-flash-reasoning; SambaY, arXiv:2507.06607),
plainly: the forward pass in ``jax.numpy`` and float32 with every matrix
product at ``highest`` precision.  No kernels, no cache, no chunks, no
last-row rule, and nothing of the program: it is handed a configuration
file's sizes (``lib/phi4flash.py``) and the weights the benchmark made,
and runs EVERY layer over EVERY position.

Per layer ``x += Mix(LN(x)); x += W_down(silu(g) * u)`` with ``[g, u] =
W_gate_up LN'(x)``; LayerNorm with weight and bias; a final LayerNorm and
the embedding as the head.  No positional encoding.  ``Mix`` by kind
(``lib/phi4flash.py::layer_kinds``):

* ``mamba``: ``[a, z] = W_in x``; ``c_t = silu(sum_j w_j a_{t-3+j} +
  b_conv)`` (four shifted products); ``[r, B, C] = W_x c``; ``dt =
  softplus(W_dt r + b_dt)``; TOKEN BY TOKEN in a ``lax.scan``, from ``h =
  0``: ``h <- exp(dt_t A) h + dt_t c_t B_t``, ``y_t = h C_t + D c_t``
  with ``A = -exp(a_log)``; ``Mix = W_out(y * silu(z))``.  The last such
  layer's ``y`` is ``m``.
* ``attn_window`` / ``attn_full``: ``[q, k, v] = W_qkv x + b``; heads in
  pairs by parity, query pair ``i`` over key/value pair ``i // 2``; ``o_i
  = P1_i V_j - lam P2_i V_j`` with ``P = softmax(q . k / sqrt(D))`` under
  the causal mask (and ``0 <= t - s < window`` in a window layer), ``V_j``
  both value heads of the pair side by side; ``o_i <- RMSNorm(o_i; g) (1
  - lam_init)``; ``Mix = W_o o + b_o``; ``lam = exp(lq1 . lk1) - exp(lq2
  . lk2) + lam_init``, ``lam_init = 0.8 - 0.6 exp(-0.3 l)``.  The full
  layer's ``k`` and ``v`` are what the cross layers read.
* ``gmu``: ``Mix = W_out(silu(W_in x) * m)``.
* ``attn_cross``: ``q = W_q x + b_q`` against the full layer's ``k`` and
  ``v``, causal; the rest as above with its own ``lam``, ``g``, ``W_o``.

Departures from the published description are the configuration file's
(``assumed``); of the layout, one: ``a_log`` is kept ``[state, inner]``
(the program's state lies so), the same numbers transposed.

``cast`` is the hook of the control: applied to both inputs of every
matrix product -- the recurrence's read-out and the attention's two
products among them -- so ``cast=to_fp8`` computes the same model in the
nearest precision below bfloat16.  The reference leaves it ``None``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from kfbench.lib.phi4flash import layer_kinds, sizes

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: query rows attended at once, and heads at once ([heads, rows, keys]
#: float32 scores: 42 MB at 10 x 256 x 4,096)
QUERY_ROWS = 256


def to_fp8(x):
    """Round to float8 e4m3 (3 bits of mantissa) and back; what lies past
    its largest number, 448, goes to that (the type has no infinity, and
    a recurrent state is not bounded by a norm)."""
    return jnp.clip(x, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(F32)


def _mm(a, b, cast, spec=None):
    a, b = a.astype(F32), b.astype(F32)
    if cast is not None:
        a, b = cast(a), cast(b)
    if spec:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    return jnp.matmul(a, b, precision=HIGHEST)


def _dense(p, x, cast):
    y = _mm(x, p["w"], cast)
    return y + p["b"].astype(F32) if "b" in p else y


def _layernorm(p, x, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _mamba(z, p, x, cast):
    """x [S, d] -> ([S, d], y [S, inner]): a token at a time."""
    s = x.shape[0]
    e, n, r, taps = z["inner"], z["state"], z["dt_rank"], z["taps"]
    az = _dense(p["w_in"], x, cast)
    a, gate = az[:, :e], az[:, e:]
    # the convolution as shifted products: tap ``taps - 1`` is the
    # position's own, tap 0 the one ``taps - 1`` before it
    w = p["conv"].astype(F32)
    c = jax.nn.silu(sum(
        w[i] * jnp.pad(a, ((taps - 1 - i, 0), (0, 0)))[:s]
        for i in range(taps)) + p["conv_b"].astype(F32))
    rbc = _dense(p["w_x"], c, cast)
    dt = jax.nn.softplus(_dense(p["w_dt"], rbc[:, :r], cast) + p["b_dt"])
    A = -jnp.exp(p["a_log"])                                  # [n, e]

    def token(h, t):
        c, dt, B, C = t
        h = jnp.exp(dt * A) * h + (dt * c) * B[:, None]
        return h, _mm(C, h, cast) + p["d"] * c

    _, y = jax.lax.scan(token, jnp.zeros((n, e), F32),
                        (c, dt, rbc[:, r:r + n], rbc[:, r + n:]))
    return _dense(p["w_out"], y * jax.nn.silu(gate), cast), y


def _softmax_attention(q, k, v, window, cast):
    """q [S, H, D] against k [S, H, D] and v [S, H, Dv], head for head:
    causal, and within ``window`` where one is given -> [S, H, Dv].  In
    blocks of query rows so that the scores fit."""
    s, _, d = q.shape
    rows = QUERY_ROWS if s % QUERY_ROWS == 0 else s
    pos = jnp.arange(s)

    def some(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * rows, rows, 0)
        at = i * rows + jnp.arange(rows)
        scores = _mm(qb, k, cast, "qhd,shd->hqs") / math.sqrt(d)
        see = pos[None, :] <= at[:, None]
        if window is not None:
            see = see & (at[:, None] - pos[None, :] < window)
        probs = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
        return _mm(probs, v, cast, "hqs,shv->qhv")

    return jax.lax.map(some, jnp.arange(s // rows)).reshape(
        s, q.shape[1], v.shape[-1])


def _differential(z, p, li, q, k, v, window, cast):
    """q [S, heads x D], k and v [S, kv_heads x D] -> [S, d]."""
    s = q.shape[0]
    hd, pairs, kv_pairs = z["head_dim"], z["heads"] // 2, z["kv_heads"] // 2
    q = q.reshape(s, pairs, 2, hd)
    k = k.reshape(s, kv_pairs, 2, hd)
    V = v.reshape(s, kv_pairs, 2 * hd)
    # query pair i reads key/value pair i // 2
    k = jnp.repeat(k, pairs // kv_pairs, axis=1)
    V = jnp.repeat(V, pairs // kv_pairs, axis=1)
    o1, o2 = (_softmax_attention(q[:, :, i], k[:, :, i], V, window, cast)
              for i in range(2))
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * li)
    lq1, lk1, lq2, lk2 = p["lam"].astype(F32)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam_init
    o = o1 - lam * o2                                         # [S, pairs, 2D]
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + z["eps"]) \
        * p["sub_norm"]["scale"] * (1.0 - lam_init)
    return _dense(p["wo"], o.reshape(s, pairs * 2 * hd), cast)


def _head(h, table, cast):
    """h [S, d] against the embedding [vocab, d] -> [S, vocab], a
    sixteenth of the vocabulary at a time: the float32 copy of the
    table (2.05 GB at 200,064 x 2,560) never exists beside the logits
    (3.28 GB at 4,096 positions) and the weights."""
    vocab = table.shape[0]
    rows = vocab // math.gcd(vocab, 16)

    def some(i, out):
        part = jax.lax.dynamic_slice_in_dim(table, i * rows, rows, 0)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _mm(h, part, cast, "sd,vd->sv"), i * rows, 1)

    return jax.lax.fori_loop(0, vocab // rows, some,
                             jnp.zeros((h.shape[0], vocab), F32))


def logits(cfg, params, ids, cast=None):
    """ids [S] int32 -> logits [S, vocab] float32, one sequence."""
    z = sizes(cfg)
    hq = z["heads"] * z["head_dim"]
    hkv = z["kv_heads"] * z["head_dim"]
    h = params["embed"]["table"][ids].astype(F32)
    m = kept = None
    for li, kind in enumerate(layer_kinds(z)):
        lp = params[f"layer_{li}"]
        x = _layernorm(lp["ln_mixer"], h, z["eps"])
        if kind == "mamba":
            mixed, m = _mamba(z, lp["mamba"], x, cast)   # the last one's stays
        elif kind == "gmu":
            p = lp["gmu"]
            mixed = _dense(p["w_out"], jax.nn.silu(_dense(p["w_in"], x, cast))
                           * m, cast)
        elif kind == "attn_cross":
            p = lp["attn"]
            mixed = _differential(z, p, li, _dense(p["wq"], x, cast), *kept,
                                  None, cast)
        else:
            p = lp["attn"]
            qkv = _dense(p["w_qkv"], x, cast)
            q, k, v = qkv[:, :hq], qkv[:, hq:hq + hkv], qkv[:, hq + hkv:]
            if kind == "attn_full":
                kept = (k, v)
            mixed = _differential(
                z, p, li, q, k, v,
                z["window"] if kind == "attn_window" else None, cast)
        h = h + mixed
        x = _layernorm(lp["ln_mlp"], h, z["eps"])
        gu = _dense(lp["mlp"]["w_gate_up"], x, cast)
        h = h + _dense(lp["mlp"]["w_down"], jax.nn.silu(gu[:, :z["ffn"]])
                       * gu[:, z["ffn"]:], cast)
    h = _layernorm(params["ln_f"], h, z["eps"])
    return _head(h, params["embed"]["table"], cast)
