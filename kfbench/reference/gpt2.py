"""GPT-2, plainly: the published forward pass, its next-token loss and
AdamW, in ``jax.numpy`` and float32 with every matrix product at
``highest`` precision.  No kernels, no cache, no batching tricks, and
nothing of the program: it is handed a configuration file's sizes and
the weights the benchmark made.

Departures from the published model are the configuration file's
(``assumed``): an untied head, and q/k/v as three matrices.

``cast`` is the hook of the control: it is applied to both inputs of
every matrix product (weights and activations), so ``cast=to_fp8``
computes the same model in the nearest precision below bfloat16.  The
reference itself leaves it ``None``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def to_fp8(x):
    """Round to float8 e4m3 (3 bits of mantissa) and back.  Gradients
    pass straight through: cast back through float8 unscaled they would
    underflow to nothing, which is no model of float8 arithmetic."""
    q = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, cast):
    if cast is not None:
        a, b = cast(a), cast(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _dense(p, x, cast):
    y = _mm(x, p["w"], cast)
    return y + p["b"] if "b" in p else y


def _layernorm(p, x, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(cfg, lp, h, cast):
    s, d = h.shape
    nh = cfg["n_head"]
    eps = cfg["layer_norm_epsilon"]
    x = _layernorm(lp["ln1"], h, eps)

    def heads(t):  # [S, d] -> [H, S, D]
        return t.reshape(s, nh, d // nh).transpose(1, 0, 2)

    q, k, v = (heads(_dense(lp[n], x, cast)) for n in ("wq", "wk", "wv"))
    scores = _mm(q, k.transpose(0, 2, 1), cast) / math.sqrt(d // nh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = _mm(probs, v, cast).transpose(1, 0, 2).reshape(s, d)
    h = h + _dense(lp["wo"], o, cast)
    x = _layernorm(lp["ln2"], h, eps)
    return h + _dense(lp["ffn_out"],
                      _gelu_new(_dense(lp["ffn_in"], x, cast)), cast)


def logits(cfg, params, ids, cast=None):
    """ids [S] int32 -> logits [S, vocab] float32, one sequence."""
    s = ids.shape[0]
    h = params["embed"]["table"][ids] + params["pos_embed"]["table"][:s]
    # one layer's activations at a time: a backward pass recomputes them
    block = jax.checkpoint(lambda lp, h: _block(cfg, lp, h, cast))
    for i in range(cfg["n_layer"]):
        h = block(params[f"layer_{i}"], h)
    h = _layernorm(params["ln_f"], h, cfg["layer_norm_epsilon"])
    return _mm(h, params["head"]["w"], cast)


def loss(cfg, params, ids, targets, cast=None):
    """Mean next-token negative log-likelihood of one sequence."""
    lg = logits(cfg, params, ids, cast)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def batch_loss_and_grad(cfg, params, ids, targets, cast=None):
    """Loss and gradient of the mean over a batch [B, S], one row at a
    time so that one row's activations are all that is ever held."""
    row = jax.value_and_grad(
        lambda p, i, t: loss(cfg, p, i, t, cast))

    def add(carry, it):
        l, g = row(params, *it)
        return (carry[0] + l, jax.tree_util.tree_map(jnp.add, carry[1], g)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, params))
    (l, g), _ = jax.lax.scan(add, zero, (ids, targets))
    n = ids.shape[0]
    return l / n, jax.tree_util.tree_map(lambda x: x / n, g)


def adamw_init(params):
    z = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"count": jnp.zeros((), jnp.int32), "mu": z,
            "nu": jax.tree_util.tree_map(jnp.zeros_like, params)}


def adamw_update(opt, params, grads, state):
    """Decoupled weight decay (Loshchilov & Hutter): the update of every
    leaf is -lr * (m_hat / (sqrt(v_hat) + eps) + wd * p)."""
    b1, b2 = opt["b1"], opt["b2"]
    count = state["count"] + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                state["mu"], grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                state["nu"], grads)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - opt["lr"] * (
            (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
            + opt["weight_decay"] * p),
        params, mu, nu)
    return new, {"count": count, "mu": mu, "nu": nu}
