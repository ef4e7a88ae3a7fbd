"""Minimal functional NN layer library.

Design: every layer is a pair of pure functions — ``*_init(key, ...) ->
params`` (a dict pytree) and ``*_apply(params, x, ...) -> y``.  No module
objects, no tracing magic: params are explicit pytrees that optimizers,
collectives, fusion, and checkpointing all see uniformly.  bf16-first: the
``dtype`` argument controls *compute/activation* dtype; params are kept in
float32 (the standard TPU mixed-precision recipe — MXU eats bf16, master
weights stay f32).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


# -- initializers --------------------------------------------------------
def glorot_uniform(key, shape, dtype=jnp.float32, in_axis=-2, out_axis=-1):
    fan_in = shape[in_axis] * (math.prod(shape[:-2]) if len(shape) > 2 else 1)
    fan_out = shape[out_axis] * (math.prod(shape[:-2]) if len(shape) > 2 else 1)
    limit = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -limit, limit)


def he_normal(key, shape, dtype=jnp.float32):
    fan_in = math.prod(shape[:-1])
    std = math.sqrt(2.0 / fan_in)
    return jax.random.normal(key, shape, dtype) * std


def normal(key, shape, dtype=jnp.float32, stddev=0.02):
    return jax.random.normal(key, shape, dtype) * stddev


# -- dense ---------------------------------------------------------------
def dense_init(key, in_dim: int, out_dim: int, use_bias: bool = True):
    p = {"w": glorot_uniform(key, (in_dim, out_dim))}
    if use_bias:
        p["b"] = jnp.zeros((out_dim,), jnp.float32)
    return p


def dense_apply(p, x, dtype=None):
    w = p["w"].astype(dtype) if dtype else p["w"]
    y = x @ w
    if "b" in p:
        y = y + (p["b"].astype(dtype) if dtype else p["b"])
    return y


# -- conv (NHWC) ---------------------------------------------------------
def conv_init(key, in_ch: int, out_ch: int, kernel: Tuple[int, int], use_bias: bool = False):
    p = {"w": he_normal(key, kernel + (in_ch, out_ch))}
    if use_bias:
        p["b"] = jnp.zeros((out_ch,), jnp.float32)
    return p


def conv_apply(p, x, stride: int = 1, padding="SAME", dtype=None):
    w = p["w"].astype(dtype) if dtype else p["w"]
    y = jax.lax.conv_general_dilated(
        x, w,
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    if "b" in p:
        y = y + (p["b"].astype(dtype) if dtype else p["b"])
    return y


def conv_stem_s2d_apply(p, x, dtype=None):
    """The classic TPU stem trick: a 7x7/stride-2 conv on [N,H,W,3] runs
    at 3/128 MXU lane efficiency; computing the SAME linear map as a
    4x4/stride-1 conv on 2x2 space-to-depth input ([N,H/2,W/2,12]) packs
    4x more channels per lane.  The trainable parameter stays the
    original [7,7,C,F] kernel (checkpoint-compatible; gradients flow
    through the rearrangement, which is pure indexing/zero-padding).

    Exactness: SAME padding for k=7,s=2 is (2,3), so output o reads
    input p = 2o+a-2, a in [0,7).  With p = 2i+di the taps become
    i = o+u-1, a = 2u+di for u in [0,4), di in {0,1} — a 4x4 kernel
    W'[u,v,(di,dj,c),f] = W[2u+di, 2v+dj, c, f] (the a=7 taps are
    zero-padded) over pad ((1,2),(1,2)) stride 1.  Matches the direct
    conv up to float reassociation.

    Falls back to :func:`conv_apply` when the shape doesn't fit the
    pattern (odd H/W, non-7x7 kernel).
    """
    kh, kw, c, f = p["w"].shape
    n, h, w_, xc = x.shape
    if (kh, kw) != (7, 7) or h % 2 or w_ % 2 or xc != c:
        return conv_apply(p, x, stride=2, dtype=dtype)
    wgt = p["w"].astype(dtype) if dtype else p["w"]
    x = x.reshape(n, h // 2, 2, w_ // 2, 2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w_ // 2, 4 * c)
    w8 = jnp.pad(wgt, ((0, 1), (0, 1), (0, 0), (0, 0)))
    w4 = w8.reshape(4, 2, 4, 2, c, f)
    w4 = w4.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c, f)
    y = jax.lax.conv_general_dilated(
        x, w4,
        window_strides=(1, 1),
        padding=((1, 2), (1, 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    if "b" in p:
        y = y + (p["b"].astype(dtype) if dtype else p["b"])
    return y


# -- norms ---------------------------------------------------------------
def batchnorm_init(ch: int):
    """Trainable affine params; running stats live in a separate state tree
    (see :func:`batchnorm_state_init`) so optimizers never see them."""
    return {"scale": jnp.ones((ch,), jnp.float32), "bias": jnp.zeros((ch,), jnp.float32)}


def batchnorm_state_init(ch: int):
    return {"mean": jnp.zeros((ch,), jnp.float32), "var": jnp.ones((ch,), jnp.float32)}


def batchnorm_apply(p, stats, x, train: bool, momentum=0.9, eps=1e-5,
                    axis_name=None, compute_dtype=None):
    """Returns (y, new_stats).  In train mode, batch stats; cross-replica
    mean via psum when ``axis_name`` given (sync BN over the DP axis).

    The statistics (moments, running stats) are ALWAYS f32.  The
    normalize/scale/shift elementwise chain — BN's big HBM reads and
    writes — runs in ``compute_dtype``: the activation dtype by default,
    so bf16 activations stay 2 bytes end to end (the round-4 BN-tax
    diagnosis, before PR 1 and unmeasured on today's code: the f32 chain
    cost ~20% of the ResNet-50 step; ROADMAP W7 is its cell; the per-channel
    mean/inv fold to scalars, so only bf16 rounding of the normalized
    output differs).  ``KF_TPU_BN_COMPUTE=f32`` restores the legacy
    all-f32 chain globally; an explicit ``compute_dtype`` wins."""
    xf = x.astype(jnp.float32)
    if train:
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(xf, axes)
        m2 = jnp.mean(jnp.square(xf), axes)
        if axis_name is not None:
            # sync-BN: average the raw moments, THEN form the variance —
            # pmean of per-shard variances drops the cross-shard mean
            # spread (E[var_s] != E[x^2] - E[x]^2 when shard means differ)
            mean = jax.lax.pmean(mean, axis_name)
            m2 = jax.lax.pmean(m2, axis_name)
        var = m2 - jnp.square(mean)
        new_stats = {
            "mean": momentum * stats["mean"] + (1 - momentum) * mean,
            "var": momentum * stats["var"] + (1 - momentum) * var,
        }
    else:
        mean, var = stats["mean"], stats["var"]
        new_stats = stats
    if compute_dtype is None:
        import os

        compute_dtype = (jnp.float32
                         if os.environ.get("KF_TPU_BN_COMPUTE") == "f32"
                         else x.dtype)
    cd = jnp.dtype(compute_dtype)
    inv = (jax.lax.rsqrt(var + eps) * p["scale"]).astype(cd)
    y = (xf.astype(cd) - mean.astype(cd)) * inv + p["bias"].astype(cd)
    return y.astype(x.dtype), new_stats


def layernorm_init(dim: int):
    return {"scale": jnp.ones((dim,), jnp.float32), "bias": jnp.zeros((dim,), jnp.float32)}


@jax.named_scope("norm")
def layernorm_apply(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), -1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps) * p["scale"]
    if "bias" in p:  # (a bias-free norm: models/cohere2_moe.py)
        y = y + p["bias"]
    return y.astype(x.dtype)


def rmsnorm_init(dim: int):
    return {"scale": jnp.ones((dim,), jnp.float32)}


def rmsnorm_apply(p, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * p["scale"]).astype(x.dtype)


# -- embedding -----------------------------------------------------------
def embedding_init(key, vocab: int, dim: int):
    return {"table": normal(key, (vocab, dim))}


def embedding_apply(p, ids, dtype=None):
    t = p["table"].astype(dtype) if dtype else p["table"]
    return jnp.take(t, ids, axis=0)


# -- misc ----------------------------------------------------------------
def dropout(key, x, rate: float, train: bool):
    if not train or rate <= 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def num_params(params) -> int:
    return sum(int(math.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))


def cast_floats(tree, dtype):
    """Cast floating leaves (for bf16 checkpoints / transfers)."""
    def f(l):
        if jnp.issubdtype(l.dtype, jnp.floating):
            return l.astype(dtype)
        return l

    return jax.tree_util.tree_map(f, tree)
