"""The ``solar_open2`` decoder (Solar Open 2): pre-norm residual blocks
whose token mixer is, three layers in four, a gated delta-rule
linear-attention layer (KDA: Kimi Linear, arXiv:2510.26692) and, one in
four, a gated softmax layer with grouped heads and no positions at all;
every layer's FFN a sparse expert layer (sigmoid top-k beside one shared
expert), an untied head, bfloat16 parameters.

With ``x = RMSNorm(h)`` a layer is ``h += mixer(x)``, then ``h +=
moe(RMSNorm(h))``.

**KDA mixer** (layers not in ``cfg.gqa_layers``).  Per head a matrix
``S`` ``[K, V]`` is all the layer keeps of the past, beside the last
``conv_kernel - 1`` inputs of a short convolution::

    q~, k~, v = SiLU(conv(W_q x)), SiLU(conv(W_k x)), SiLU(conv(W_v x))
    q, k      = q~ / |q~| / sqrt(K),  k~ / |k~|                  (a head)
    log a     = -exp(A_h) softplus(W_f2 W_f1 x + b_dt)           (a channel)
    b         = 2 sigmoid(W_b x)                                 (a head)
    S        <- diag(a) S;  S <- S + b k (v - S^T k)^T;  o = S^T q
    y         = W_o [RMSNorm_head(o) * sigmoid(W_g2 W_g1 x + b_g)]

(``ops/delta_rule.py`` has the recurrence's two forms.)  **GQA mixer**
(``cfg.gqa_layers``): ``q = W_q x`` in ``n_heads`` heads, ``k, v`` in
``n_kv_heads``, causal ``softmax(q k^T / sqrt(D)) v`` with no positions,
``y = W_o [attn * sigmoid(W_gate x)]``.

The layer is written once, :func:`block`; WHERE a layer's past lives is
the cache object's matter, as in ``models/cohere2_moe.py``.  A softmax
layer asks ``cache.write(li, k, v)`` once and ``cache.attend(li, q,
positions)``; a KDA layer ``cache.convolve(li, u, w)`` (the short
convolution over what came before the pass, which only the cache has)
and ``cache.recur(li, q, k, v, g, b)`` (the recurrence from the state
the cache holds).  :meth:`SolarOpen2.apply` brings a cache that starts
every sequence from nothing; ``serve/recurrent.py`` the engine's.
``kfbench/reference/solar_open2.py`` is the layer's plain twin.

A chip of an expert-parallel deployment holds ``cfg.experts_held`` of
the router's ``cfg.n_experts`` outputs and ``cfg.vocab_size`` rows of
the vocabulary.  This module imports no kernel package.

Scopes (docs/tracing.md): ``embed``, ``norm``, ``attn_proj`` (with
``kda_proj`` inside: a KDA layer's projections, gates and convolution),
``attn_core`` (``attn_full`` inside for a softmax layer; ``kda_state``
for the head norm here and the cache's one-token update, ``kda_chunk``
for its chunked form), ``mlp`` with ``moe_router`` / ``moe_experts`` /
``moe_shared`` inside, ``head``; ``kv_write`` is the cache's.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kungfu_tpu.models import experts, nn
from kungfu_tpu.models.cohere2_moe import blocked_attention
from kungfu_tpu.ops import delta_rule

F32 = jnp.float32


@dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196608       # rows held here
    d_model: int = 4096
    n_layers: int = 48             # layers held here (a pipeline stage's)
    init_layers: Optional[int] = None  # the whole model's (None: n_layers)
    #: the held layers that are softmax layers; the others are KDA layers
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    kda_heads: int = 64
    kda_head_dim: int = 128        # of keys and of values
    conv_kernel: int = 4
    gate_rank: int = 128           # of the decay's and the output gate's
    d_expert: int = 1280
    n_experts: int = 320           # the router's outputs
    experts_held: Tuple[int, int] = (0, 320)   # (first, count) held here
    top_k: int = 8
    n_shared: int = 1
    routed_scale: float = 1.0
    norm_eps: float = 1e-5
    init_std: float = 0.02
    max_seq: int = 4096
    dtype: str = "bfloat16"

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def is_gqa(self, li: int) -> bool:
        return li in self.gqa_layers

    @property
    def recurrent_layers(self) -> Tuple[int, ...]:
        """The layers that keep a state a slot and no rows a position:
        ``serve.kvcache.PageSpec`` counts a page over the others."""
        return tuple(i for i in range(self.n_layers) if not self.is_gqa(i))

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim


def _norm(p, x, eps):
    with jax.named_scope("norm"):
        return nn.rmsnorm_apply(p, x, eps=eps)


def gqa_mixer(cfg: SolarOpen2Config, p, li, x, positions, cache):
    """The gated softmax layer: ``x`` ``[B, Q, d]`` -> ``[B, Q, d]``."""
    dt = cfg.compute_dtype
    b, s, _ = x.shape
    g, hd = cfg.n_kv_heads, cfg.head_dim
    j = cfg.n_heads // g
    with jax.named_scope("attn_proj"):
        k = nn.dense_apply(p["wk"], x, dtype=dt).reshape(b, s, g, hd)
        v = nn.dense_apply(p["wv"], x, dtype=dt).reshape(b, s, g, hd)
    cache.write(li, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    with jax.named_scope("attn_proj"):
        # (the barrier keeps the heads' layout a matter of this small
        # activation and not of the weights: models/cohere2_moe.py)
        q = jax.lax.optimization_barrier(
            nn.dense_apply(p["wq"], x, dtype=dt)).reshape(b, s, g, j, hd)
        gate = jax.nn.sigmoid(
            nn.dense_apply(p["w_gate"], x, dtype=dt).astype(F32))
    o = cache.attend(li, q, positions).reshape(b, s, g * j * hd)
    with jax.named_scope("attn_proj"):
        return nn.dense_apply(p["wo"], (o.astype(F32) * gate).astype(dt),
                              dtype=dt)


def kda_mixer(cfg: SolarOpen2Config, p, li, x, cache):
    """The gated delta-rule layer: ``x`` ``[B, Q, d]`` -> ``[B, Q, d]``.
    Everything a token computes alone is here; the convolution's reach
    into the past and the recurrence are the cache's."""
    dt, eps = cfg.compute_dtype, cfg.norm_eps
    b, s, _ = x.shape
    h, hd, c = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_width
    heads = lambda t: t.reshape(b, s, h, hd)
    with jax.named_scope("attn_proj"), jax.named_scope("kda_proj"):
        u = nn.dense_apply(p["w_qkv"], x, dtype=dt)           # [B, Q, 3c]
        y = jax.nn.silu(cache.convolve(li, u, p["conv"]))     # float32
        q, k, v = (heads(y[..., i * c:(i + 1) * c]) for i in range(3))
        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
        q, k = unit(q) / math.sqrt(hd), unit(k)
        low = nn.dense_apply(p["w_f1"], x, dtype=dt)
        f = heads(nn.dense_apply(p["w_f2"], low, dtype=dt).astype(F32)
                  + p["b_dt"])
        g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(f)
        beta = 2.0 * jax.nn.sigmoid(
            nn.dense_apply(p["w_b"], x, dtype=dt).astype(F32))   # [B, Q, H]
        low = nn.dense_apply(p["w_g1"], x, dtype=dt)
        gate = jax.nn.sigmoid(heads(
            nn.dense_apply(p["w_g2"], low, dtype=dt).astype(F32) + p["b_g"]))
    o = cache.recur(li, q, k, v, g, beta)                     # [B, Q, H, V]
    with jax.named_scope("attn_core"), jax.named_scope("kda_state"):
        o = nn.rmsnorm_apply(p["o_norm"], o, eps=eps) * gate
    with jax.named_scope("attn_proj"), jax.named_scope("kda_proj"):
        return nn.dense_apply(p["wo"], o.reshape(b, s, c).astype(dt),
                              dtype=dt)


def block(cfg: SolarOpen2Config, lp, li: int, h, positions, cache, *,
          dense: bool, live=None):
    """One layer: ``h`` ``[B, Q, d]`` at ``positions`` ``[B, Q]`` ->
    (new ``h``, tokens per held expert ``[count]``).  ``cache`` is where
    this pass keeps the layer's past (module docstring); ``dense`` and
    ``live`` ``[B, Q]`` are the expert layer's (``experts.apply``)."""
    dt, eps = cfg.compute_dtype, cfg.norm_eps
    b, s, d = h.shape
    x = _norm(lp["ln_mixer"], h, eps)
    if cfg.is_gqa(li):
        mixed = gqa_mixer(cfg, lp["gqa"], li, x, positions, cache)
    else:
        mixed = kda_mixer(cfg, lp["kda"], li, x, cache)
    h = h + mixed
    x = _norm(lp["ln_moe"], h, eps)
    with jax.named_scope("mlp"):
        ffn, counts = experts.apply(
            lp["moe"], x.reshape(b * s, d), top_k=cfg.top_k,
            held=cfg.experts_held, dense=dense,
            live=None if live is None else live.reshape(b * s),
            scale=cfg.routed_scale)
    return (h.astype(F32) + ffn.reshape(b, s, d)).astype(dt), counts


class _FromNothing:
    """The cache of a plain forward pass: every sequence starts with no
    state, no convolution tail and no keys but its own."""

    def __init__(self, cfg):
        self.cfg = cfg

    def write(self, li, k, v):
        self.k, self.v = k, v

    @jax.named_scope("attn_core")
    def attend(self, li, q, positions):
        with jax.named_scope("attn_full"):
            return jax.vmap(lambda q, k, v, p: blocked_attention(
                q[None], k[None], v[None], p, 0, None)[0])(
                    q, self.k, self.v, positions)

    def convolve(self, li, u, w):
        tail = jnp.zeros((w.shape[0] - 1, u.shape[-1]), u.dtype)
        return jax.vmap(
            lambda u: delta_rule.causal_conv(u, w, tail)[0])(u)

    @jax.named_scope("attn_core")
    def recur(self, li, q, k, v, g, b):
        cfg = self.cfg
        zero = jnp.zeros((cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim),
                         F32)
        with jax.named_scope("kda_chunk"):
            return jax.vmap(lambda *a: delta_rule.kda_chunked(
                *a, zero)[0])(q, k, v, g, b)


class SolarOpen2:
    def __init__(self, config: SolarOpen2Config):
        self.cfg = config
        self._parts = self._flops_parts()

    def init(self, key) -> dict:
        """Normal(0, ``init_std``) in ``cfg.dtype``, the output
        projections (``wo``, the experts' ``down``) scaled by ``1 /
        sqrt(2 x depth)``, the depth of the whole model whichever of its
        layers are held here (``cfg.init_layers``).  A KDA layer's
        ``a_log`` is ``log U(1, 16)`` a head and its ``b_dt`` the inverse
        softplus of a step drawn log-uniformly from ``[0.001, 0.1]`` a
        channel (the published KDA layer's, so that a channel forgets
        between 0.1 % and 80 % a token), ``b_g`` zero, the convolution
        ``U(-1, 1) / sqrt(taps)``.  Norm scales, the routers, ``a_log``
        and ``b_dt`` stay float32."""
        cfg = self.cfg
        dt, std = cfg.compute_dtype, cfg.init_std
        out_std = std / math.sqrt(2 * (cfg.init_layers or cfg.n_layers))
        d, r = cfg.d_model, cfg.gate_rank
        hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        c, taps = cfg.kda_width, cfg.conv_kernel

        def w(k, shape, s=std):
            return {"w": nn.normal(k, shape, stddev=s).astype(dt)}

        def ones(n):
            return {"scale": jnp.ones((n,), F32)}

        key, k0, k1 = jax.random.split(key, 3)
        params = {"embed": {"table": w(k0, (cfg.vocab_size, d))["w"]},
                  "head": w(k1, (d, cfg.vocab_size)), "ln_f": ones(d)}
        for i in range(cfg.n_layers):
            key, km, kx = jax.random.split(key, 3)
            kx = jax.random.split(kx, 5 if cfg.is_gqa(i) else 10)
            lp = {"ln_mixer": ones(d), "ln_moe": ones(d)}
            if cfg.is_gqa(i):
                kq, kk, kv, kg, ko = kx
                lp["gqa"] = {
                    "wq": w(kq, (d, hq)), "wk": w(kk, (d, hkv)),
                    "wv": w(kv, (d, hkv)), "w_gate": w(kg, (d, hq)),
                    "wo": w(ko, (hq, d), out_std)}
            else:
                kq, kc, kf1, kf2, ka, kd, kb, kg1, kg2, ko = kx
                step = jnp.exp(jax.random.uniform(
                    kd, (c,), F32, math.log(1e-3), math.log(1e-1)))
                lp["kda"] = {
                    "w_qkv": w(kq, (d, 3 * c)),
                    "conv": jax.random.uniform(
                        kc, (taps, 3 * c), F32, -1.0, 1.0
                    ).astype(dt) / math.sqrt(taps),
                    "w_f1": w(kf1, (d, r)), "w_f2": w(kf2, (r, c)),
                    "a_log": jnp.log(jax.random.uniform(
                        ka, (cfg.kda_heads,), F32, 1.0, 16.0)),
                    "b_dt": step + jnp.log(-jnp.expm1(-step)),
                    "w_b": w(kb, (d, cfg.kda_heads)),
                    "w_g1": w(kg1, (d, r)), "w_g2": w(kg2, (r, c)),
                    "b_g": jnp.zeros((c,), dt),
                    "o_norm": ones(cfg.kda_head_dim),
                    "wo": w(ko, (c, d), out_std)}
            lp["moe"] = experts.init(
                km, d, cfg.d_expert, cfg.n_experts, cfg.experts_held,
                cfg.n_shared, std=std, out_std=out_std, dtype=dt)
            params[f"layer_{i}"] = lp
        return params

    @jax.named_scope("embed")
    def embed(self, params, ids):
        return nn.embedding_apply(params["embed"], ids,
                                  dtype=self.cfg.compute_dtype)

    def logits(self, params, h):
        """``h`` ``[..., d]`` after the last layer -> float32 logits
        over the held rows of the vocabulary (an untied head)."""
        h = _norm(params["ln_f"], h, self.cfg.norm_eps)
        with jax.named_scope("head"):
            return jnp.matmul(h, params["head"]["w"],
                              preferred_element_type=F32)

    def apply(self, params, ids, dense: bool = False):
        """ids ``[B, S]`` -> logits ``[B, S, vocab]`` float32: the plain
        forward pass, every sequence from an empty state (``dense``: the
        routed product's form, ``experts.apply``)."""
        cfg = self.cfg
        b, s = ids.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        h = self.embed(params, ids)
        for li in range(cfg.n_layers):
            h, _ = block(cfg, params[f"layer_{li}"], li, h, positions,
                         _FromNothing(cfg), dense=dense)
        return self.logits(params, h)

    def serve_caches(self, max_batch: int, max_seq: int):
        """What ``serve.engine.InferenceEngine`` serves this model
        through (the interface is in ``serve/caches.py``): a full-length
        slab for the softmax layers beside a state and a convolution
        tail a slot for the KDA layers."""
        from kungfu_tpu.serve.recurrent import HybridCaches

        return HybridCaches(self, max_batch, max_seq)

    # -- what a forward pass costs (the serving MFU gauge) ---------------
    # (constants worked out once; a step's live contexts summed as one
    # array: models/cohere2_moe.py)
    def _flops_parts(self):
        cfg = self.cfg
        d, r, c = cfg.d_model, cfg.gate_rank, cfg.kda_width
        n_gqa = len(cfg.gqa_layers)
        n_kda = cfg.n_layers - n_gqa
        gqa = 2 * d * cfg.head_dim * (3 * cfg.n_heads + 2 * cfg.n_kv_heads)
        # projections, the two low-rank gates, the convolution, and per
        # head the state's decay, correction and two read-outs
        kda = (2 * d * (4 * c + 2 * r + cfg.kda_heads) + 4 * r * c
               + 2 * cfg.conv_kernel * 3 * c
               + 7 * cfg.kda_heads * cfg.kda_head_dim ** 2)
        held = cfg.top_k * cfg.experts_held[1] / cfg.n_experts
        moe = 6 * d * cfg.d_expert * (cfg.n_shared + held) \
            + 2 * d * cfg.n_experts
        token = int(n_gqa * gqa + n_kda * kda + cfg.n_layers * moe)
        score = 4 * cfg.n_heads * cfg.head_dim  # one query row, one key
        return token, score * n_gqa, 2 * d * cfg.vocab_size

    def prefill_flops(self, tokens: int, start: int = 0) -> int:
        """The products over ``tokens`` new rows (the recurrence counted
        token by token, as the work there is to do whatever the chunked
        form spends), softmax attention into the context, one logits
        row."""
        token, score, head = self._parts
        seen = tokens * start + tokens * (tokens + 1) // 2
        return tokens * token + score * seen + head

    def decode_flops(self, contexts) -> int:
        """One decode position of each of the sequences whose contexts
        (keys attended, its own included) are given, summed."""
        token, score, head = self._parts
        contexts = np.asarray(contexts)
        return int(contexts.size * (token + head) + score * contexts.sum())
