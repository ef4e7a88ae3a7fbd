"""The ``evabyte`` decoder (EvaByte): a byte-level model whose attention
is EVA (arXiv:2302.04542, as the EvaByte release simplified it) -- exact
softmax inside an aligned window of ``window_size`` positions, every
``chunk_size`` positions before that window pooled into ONE learned
key/value row, one softmax over both -- in pre-norm blocks with a
float32 residual stream, RMSNorm with a unit offset, rotary positions on
split halves, a gated-SiLU FFN and ``n_pred_heads`` next-byte heads.

With ``W`` = ``window_size``, ``C`` = ``chunk_size``, ``w(t) = t // W``
and chunk ``c`` the positions ``C c .. C c + C - 1``, a head with its
own learned ``mu``, ``phi`` in ``R^D``::

    alpha_cj = softmax over the chunk's j of (k_j . mu) / sqrt(D)
    k~_c = sum_j alpha_cj k_j + phi        v~_c = sum_j alpha_cj v_j
    E_t  = {j : w(j) = w(t), j <= t}       the exact rows: the open window
    R_t  = {c : c < (W / C) w(t)}          chunk rows of CLOSED windows
    o_t  = softmax over E_t and R_t together of q_t . k / sqrt(D), times v

(keys rotated before they are pooled; a chunk row has no position of its
own).  A context of up to ``W`` positions is plain causal attention, and
one of ``n`` reads at most ``W + n / C`` rows a head.

The block is written once, :func:`block`: what differs between the
plain forward pass, a prefill into a cache and a decode step out of one
is WHERE a layer's rows and chunk rows live, so the block takes that as
an object, as ``models/cohere2_moe.py`` does -- ``cache.write(li, k, v,
mu, phi)`` is handed the layer's new rotated rows ``[B, H, Q, D]`` and
the pooling's parameters (when a chunk is complete is the cache's to
know), ``cache.attend(li, q, positions)`` takes queries ``[B, Q, H, D]``
and returns the attention's output shaped like them.
:meth:`EvaByte.apply` brings a cache of the rows it was just given;
``serve/pooled.py`` the engine's.  ``kfbench/reference/evabyte.py`` is
the block's plain twin.  This module imports no kernel package.

Scopes (docs/tracing.md): ``embed``, ``norm``, ``attn_proj``, ``mlp``,
``head`` here; ``attn_core`` with ``eva_attn`` inside (``eva_prefill``
in a prefill program) and ``kv_write`` with ``eva_pool`` inside are the
cache's.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from kungfu_tpu.models import nn

F32 = jnp.float32


@dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int = 320          # 256 byte values above 64 reserved ids
    d_model: int = 4096
    n_layers: int = 32             # layers held here (a pipeline stage's)
    init_layers: Optional[int] = None  # the whole model's (None: n_layers)
    n_heads: int = 32              # as many key/value heads: no grouping
    head_dim: int = 128
    d_ff: int = 11008
    chunk_size: int = 16
    window_size: int = 2048
    n_pred_heads: int = 8          # head j predicts byte t + 1 + j
    rope_theta: float = 100000.0
    norm_eps: float = 1e-5
    init_std: float = 0.01275
    max_seq: int = 32768
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.window_size % self.chunk_size:
            raise ValueError(
                f"window_size {self.window_size} is not whole chunks of "
                f"{self.chunk_size}")

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def window_chunks(self) -> int:
        """Chunk rows a closed window leaves."""
        return self.window_size // self.chunk_size

    #: a chunk row is made from other rows and seen only once its window
    #: has closed; the rule by which a page could hold one is not built
    #: (docs/serving.md), so ``serve.kvcache.PageSpec`` is told that no
    #: page of this model can be handed to a later request
    pages_reusable = False


def rope_halves(x, positions, theta: float):
    """Rotary positions over the whole head width on the split halves
    ``(x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin)``: ``x`` ``[..., D]``,
    ``positions`` broadcastable to ``x``'s leading axes.  Written as ``x
    * cos + (x R) * sin`` with ``R`` the ``[D, D]`` matrix of 0 and +-1
    that puts each element's partner in its place, signed -- exact in
    any dtype, and no slice or roll along the lanes
    (``cohere2_moe.rope_interleaved`` says what those cost on the TPU).
    The rotation itself is float32."""
    d = x.shape[-1]
    half = d // 2
    lane = jnp.arange(d)
    freqs = theta ** (-(lane % half).astype(F32) * 2.0 / d)
    angles = positions[..., None].astype(F32) * freqs          # [..., D]
    partner = (lane + half) % d
    turn = jnp.where(lane[:, None] == partner[None, :],
                     jnp.where(lane < half, 1.0, -1.0)[:, None], 0.0)
    out = (x.astype(F32) * jnp.cos(angles)
           + (x @ turn.astype(x.dtype)).astype(F32) * jnp.sin(angles))
    return out.astype(x.dtype)


def pool_chunks(k, v, mu, phi):
    """Chunk rows of whole chunks: ``k``, ``v`` ``[..., H, N, C, D]``
    (``N`` chunks of ``C`` rotated rows a head), ``mu``, ``phi`` ``[H,
    D]`` -> ``(k~, v~)`` ``[..., H, N, D]`` in ``k``'s dtype.  Logits,
    softmax and both weighted sums in float32."""
    kf, vf = k.astype(F32), v.astype(F32)
    logits = jnp.einsum("...hncd,hd->...hnc", kf, mu.astype(F32)
                        ) / math.sqrt(k.shape[-1])
    alpha = jax.nn.softmax(logits, axis=-1)
    kp = jnp.einsum("...hnc,...hncd->...hnd", alpha, kf) \
        + phi.astype(F32)[:, None, :]
    vp = jnp.einsum("...hnc,...hncd->...hnd", alpha, vf)
    return kp.astype(k.dtype), vp.astype(v.dtype)


def eva_attention(q, keys, values, see):
    """ONE softmax over whatever rows ``see`` lets a query read, exact
    rows and chunk rows alike: ``q`` ``[B, Q, H, D]``, ``keys`` /
    ``values`` ``[B, H, S, D]``, ``see`` broadcastable to ``[B, 1, Q,
    S]``, True = attend.  Operands in the compute dtype, float32 scores
    and softmax (``mixedp_attn``)."""
    scores = jnp.einsum("bqhd,bhsd->bhqs", q, keys,
                        preferred_element_type=F32) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(see, scores, -1e30), axis=-1)
    return jnp.einsum("bhqs,bhsd->bqhd", probs.astype(q.dtype), values,
                      preferred_element_type=F32).astype(q.dtype)


def visible(cfg: EvaByteConfig, positions, exact_pos, n_chunks: int):
    """What a query at each of ``positions`` ``[..., Q]`` may read of
    exact rows at the positions ``exact_pos`` ``[S]`` and of chunk rows
    ``0 .. n_chunks - 1``: ``(see_exact [..., Q, S], see_chunk [..., Q,
    n_chunks])``.  The equations' ``E_t`` and ``R_t``."""
    w = cfg.window_size
    t = positions[..., None]
    see_exact = (exact_pos // w == t // w) & (exact_pos <= t)
    see_chunk = jnp.arange(n_chunks) < cfg.window_chunks * (t // w)
    return see_exact, see_chunk


def _norm(p, x, eps, dtype):
    """RMSNorm with a unit offset (``norm_add_unit_offset``): the
    learned ``g`` starts at 0 and scales by ``1 + g``."""
    with jax.named_scope("norm"):
        return nn.rmsnorm_apply({"scale": 1.0 + p["g"]}, x, eps=eps
                                ).astype(dtype)


def block(cfg: EvaByteConfig, lp, li: int, h, positions, cache):
    """One layer: the float32 stream ``h`` ``[B, Q, d]`` at ``positions``
    ``[B, Q]`` -> the new stream.  ``cache`` is where this pass keeps the
    layer's rows and chunk rows (module docstring)."""
    dt, eps = cfg.compute_dtype, cfg.norm_eps
    b, s, _ = h.shape
    nh, hd = cfg.n_heads, cfg.head_dim
    x = _norm(lp["ln_attn"], h, eps, dt)
    with jax.named_scope("attn_proj"):
        heads = lambda t: t.reshape(b, s, nh, hd)
        at = positions[:, :, None]
        k = rope_halves(heads(nn.dense_apply(lp["wk"], x, dtype=dt)), at,
                        cfg.rope_theta)
        v = heads(nn.dense_apply(lp["wv"], x, dtype=dt))
    cache.write(li, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                lp["mu"], lp["phi"])
    with jax.named_scope("attn_proj"):
        # (the barrier keeps the heads' layout a matter of this small
        # activation and not of the weights: models/cohere2_moe.py)
        q = rope_halves(heads(jax.lax.optimization_barrier(
            nn.dense_apply(lp["wq"], x, dtype=dt))), at, cfg.rope_theta)
    o = cache.attend(li, q, positions).reshape(b, s, nh * hd)
    with jax.named_scope("attn_proj"):
        h = h + nn.dense_apply(lp["wo"], o, dtype=dt).astype(F32)
    x = _norm(lp["ln_ffn"], h, eps, dt)
    with jax.named_scope("mlp"):
        y = jax.nn.silu(nn.dense_apply(lp["gate"], x, dtype=dt)) \
            * nn.dense_apply(lp["up"], x, dtype=dt)
        return h + nn.dense_apply(lp["down"], y, dtype=dt).astype(F32)


class _OwnRows:
    """The cache of a plain forward pass: every layer attends over the
    rows it was just given and the chunk rows pooled from them -- the
    sequence padded to whole chunks, which causality keeps from any
    real query."""

    def __init__(self, cfg):
        self.cfg = cfg

    def write(self, li, k, v, mu, phi):
        c = self.cfg.chunk_size
        b, h, s, d = k.shape
        pad = -s % c
        chunks = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0))
                                   ).reshape(b, h, (s + pad) // c, c, d)
        with jax.named_scope("kv_write"), jax.named_scope("eva_pool"):
            kp, vp = pool_chunks(chunks(k), chunks(v), mu, phi)
        self.exact = s
        self.keys = jnp.concatenate([k, kp], axis=2)
        self.values = jnp.concatenate([v, vp], axis=2)

    @jax.named_scope("attn_core")
    def attend(self, li, q, positions):
        see = jnp.concatenate(visible(
            self.cfg, positions, jnp.arange(self.exact),
            self.keys.shape[2] - self.exact), axis=-1)
        with jax.named_scope("eva_attn"):
            return eva_attention(q, self.keys, self.values, see[:, None])


class EvaByte:
    def __init__(self, config: EvaByteConfig):
        self.cfg = config
        self._parts = self._flops_parts()

    def init(self, key) -> dict:
        """``init_fn`` ``v2`` as the configuration's file reads it:
        Normal(0, ``init_std``) in ``cfg.dtype``, the output projections
        (``wo``, ``down``) scaled by ``1 / sqrt(2 x depth)``, the depth
        of the whole model whichever of its layers are held here
        (``cfg.init_layers``); the norms' offsets 0, float32; ``mu`` and
        ``phi`` a normal clipped to ``[-1, 1]`` times ``D ** -0.5``.  The
        ``n_pred_heads`` heads are one matrix ``[d, heads x ids]``."""
        cfg = self.cfg
        dt, std = cfg.compute_dtype, cfg.init_std
        out_std = std / math.sqrt(2 * (cfg.init_layers or cfg.n_layers))
        d, hw, ff = cfg.d_model, cfg.n_heads * cfg.head_dim, cfg.d_ff

        def w(k, shape, s=std):
            return {"w": nn.normal(k, shape, stddev=s).astype(dt)}

        def g():
            return {"g": jnp.zeros((d,), F32)}

        def pooling(k):
            return (jnp.clip(jax.random.normal(
                k, (cfg.n_heads, cfg.head_dim), F32), -1.0, 1.0)
                * cfg.head_dim ** -0.5).astype(dt)

        key, k0, k1 = jax.random.split(key, 3)
        params = {"embed": {"table": w(k0, (cfg.vocab_size, d))["w"]},
                  "head": w(k1, (d, cfg.n_pred_heads * cfg.vocab_size)),
                  "ln_f": g()}
        for i in range(cfg.n_layers):
            key, *ks = jax.random.split(key, 10)
            params[f"layer_{i}"] = {
                "ln_attn": g(), "ln_ffn": g(),
                "wq": w(ks[0], (d, hw)), "wk": w(ks[1], (d, hw)),
                "wv": w(ks[2], (d, hw)), "wo": w(ks[3], (hw, d), out_std),
                "mu": pooling(ks[4]), "phi": pooling(ks[5]),
                "gate": w(ks[6], (d, ff)), "up": w(ks[7], (d, ff)),
                "down": w(ks[8], (ff, d), out_std)}
        return params

    @jax.named_scope("embed")
    def embed(self, params, ids):
        """The float32 stream (``fp32_skip_add``) of ``ids``."""
        return nn.embedding_apply(params["embed"], ids).astype(F32)

    def _head(self, params, h, columns: int):
        cfg = self.cfg
        h = _norm(params["ln_f"], h, cfg.norm_eps, cfg.compute_dtype)
        with jax.named_scope("head"):
            return jnp.matmul(h, params["head"]["w"][:, :columns],
                              preferred_element_type=F32)

    def logits(self, params, h):
        """``h`` ``[..., d]`` after the last layer -> float32 logits
        ``[..., n_pred_heads, ids]``: head ``j`` is byte ``t + 1 + j``."""
        cfg = self.cfg
        out = self._head(params, h, cfg.n_pred_heads * cfg.vocab_size)
        return out.reshape(h.shape[:-1] + (cfg.n_pred_heads, cfg.vocab_size))

    def next_logits(self, params, h):
        """Head 0 alone, ``[..., ids]``: the next byte, which is all the
        engine decodes (one byte a step; the further heads would be a
        self-draft, ROADMAP R7)."""
        return self._head(params, h, self.cfg.vocab_size)

    def apply(self, params, ids):
        """ids ``[B, S]`` -> logits ``[B, S, n_pred_heads, ids]``
        float32: the plain forward pass, every layer attending over the
        rows it just made."""
        cfg = self.cfg
        b, s = ids.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        h = self.embed(params, ids)
        for li in range(cfg.n_layers):
            h = block(cfg, params[f"layer_{li}"], li, h, positions,
                      _OwnRows(cfg))
        return self.logits(params, h)

    def serve_caches(self, max_batch: int, max_seq: int):
        """What ``serve.engine.InferenceEngine`` serves this model
        through (the interface is in ``serve/caches.py``): the open
        window's exact rows beside the chunk rows of every closed one."""
        from kungfu_tpu.serve.pooled import PooledCaches

        return PooledCaches(self, max_batch, max_seq)

    # -- what a forward pass costs (the serving MFU gauge) ---------------
    # (constants worked out once; a step's live contexts summed as one
    # array: models/cohere2_moe.py)
    def _flops_parts(self):
        cfg = self.cfg
        d, hw = cfg.d_model, cfg.n_heads * cfg.head_dim
        token = cfg.n_layers * (8 * d * hw + 6 * d * cfg.d_ff)
        score = 4 * hw * cfg.n_layers   # one query row, one key, a layer
        return token, score, 2 * d * cfg.vocab_size

    def rows_seen(self, contexts):
        """Rows a head reads for the newest of ``contexts`` positions
        (its own among them): the open window's exact rows and the
        closed windows' chunk rows, ``(exact, chunk)`` arrays."""
        cfg = self.cfg
        c = np.asarray(contexts, np.int64)
        closed = np.maximum(c - 1, 0) // cfg.window_size
        return c - cfg.window_size * closed, cfg.window_chunks * closed

    def prefill_flops(self, tokens: int, start: int = 0) -> int:
        """The products over ``tokens`` new rows, attention into what
        each may see, one logits row (the pooling is a thousandth)."""
        token, score, head = self._parts
        exact, chunk = self.rows_seen(start + 1 + np.arange(tokens))
        return int(tokens * token + score * (exact.sum() + chunk.sum())
                   + head)

    def decode_flops(self, contexts) -> int:
        """One decode position of each of the sequences whose contexts
        (rows' positions attended, its own included) are given, summed."""
        token, score, head = self._parts
        exact, chunk = self.rows_seen(contexts)
        return int(exact.size * (token + head)
                   + score * (exact.sum() + chunk.sum()))
