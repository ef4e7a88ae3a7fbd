"""The ``cohere2_moe`` decoder (Command A+): one LayerNorm feeding
attention and a sparse expert layer side by side, grouped-query heads,
a period of sliding-window layers (rotary positions on interleaved
pairs) closed by one full-attention layer that sees no positions at
all, tied and scaled head, bfloat16 parameters.

The block is written once, :func:`block`: what differs between a plain
forward pass, a prefill into a cache and a decode step out of one is
WHERE a layer's keys and values live, so the block takes that as an
object -- ``cache.write(li, k, v)``, ``cache.attend(li, q, positions)``
-- and every caller brings its own: :meth:`Cohere2Moe.apply` attends over
the keys it was just given, ``serve/windowed.py`` writes them into the
engine's two slabs first.
``kfbench/reference/cohere2_moe.py`` is the block's plain twin.

Layer ``li`` of a period of ``cfg.period``: the first ``period - 1`` are
window layers (``0 <= q_pos - k_pos < window``), the last is full.
With ``x = LayerNorm(h)``::

    attn = concat_j softmax(q_j k_{j // J}^T / sqrt(D)) v_{j // J}  Wo
    ffn  = sum_{e in top-k, held} w_e E_e(x) + mean_j S_j(x)     (experts.py)
    h   <- h + attn + ffn

A chip of an expert-parallel deployment holds ``cfg.experts_held`` of
the router's ``cfg.n_experts`` outputs and ``cfg.vocab_size`` rows of
the vocabulary; ids, logits and argmax are over those rows.

Scopes (docs/tracing.md): ``embed``, ``norm``, ``attn_proj``, ``mlp``
(with ``moe_router`` / ``moe_experts`` / ``moe_shared`` inside), ``head``
here; ``attn_core`` with ``attn_window`` / ``attn_full`` inside, and
``kv_write``, are the cache's.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kungfu_tpu.models import experts, nn

#: query rows a prefill attends at once (the scores of one block,
#: ``[heads, ATTN_BLOCK, keys]`` float32, are the largest temporary)
ATTN_BLOCK = 64
#: rows whose queries exist at once (``block``)
QUERY_CHUNK = 1024


@dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 262144       # rows held here
    d_model: int = 4096
    n_layers: int = 32             # layers held here (a pipeline stage's)
    init_layers: Optional[int] = None  # the whole model's (None: n_layers)
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    d_expert: int = 4096
    n_experts: int = 128           # the router's outputs
    experts_held: Tuple[int, int] = (0, 128)   # (first, count) held here
    top_k: int = 8
    n_shared: int = 4
    window: int = 4096
    period: int = 4                # period - 1 window layers, then one full
    rope_theta: float = 50000.0
    norm_eps: float = 1e-5
    logit_scale: float = 1.0
    init_std: float = 0.02
    max_seq: int = 8192
    dtype: str = "bfloat16"

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def is_window(self, li: int) -> bool:
        return (li + 1) % self.period != 0

    @property
    def window_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.n_layers) if self.is_window(i))

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.n_layers) if not self.is_window(i))


def rope_interleaved(x, positions, theta: float):
    """Rotary positions over the whole head width on the pairs
    ``(2i, 2i + 1)`` (the GPT-J form): ``x`` ``[..., D]``, ``positions``
    broadcastable to ``x``'s leading axes.

    ``out[2i] = x[2i] cos_i - x[2i+1] sin_i``, ``out[2i+1] = x[2i+1] cos_i
    + x[2i] sin_i``, written as ``x * cos + (x R) * sin`` where ``R``
    puts each element's partner in its place, signed: a ``[D, D]`` matrix
    of 0 and +-1 with one entry a column, so the product is exact in any
    dtype.  (A reshape to ``[..., D/2, 2]`` puts 2 on the lanes, and the
    TPU compiler then re-lays the projection's weights out every decode
    step; rolls along the lanes are padded to a tile each, 512 MB apiece
    in an 8192-token prefill.)  The rotation itself is float32."""
    d = x.shape[-1]
    lane = jnp.arange(d)
    freqs = theta ** (-(lane // 2 * 2).astype(jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., D]
    partner = lane + 1 - 2 * (lane % 2)
    turn = jnp.where(lane[:, None] == partner[None, :],
                     jnp.where(lane % 2 == 0, 1.0, -1.0)[:, None], 0.0)
    out = (x.astype(jnp.float32) * jnp.cos(angles)
           + (x @ turn.astype(x.dtype)).astype(jnp.float32) * jnp.sin(angles))
    return out.astype(x.dtype)


def attention(q, k, v, mask, scale=None):
    """Grouped-query attention: ``q`` ``[B, Q, G, J, D]`` (``J`` query
    heads read key/value head ``g``; the layout the projection gives,
    so no query is ever transposed), ``k``/``v`` ``[B, G, S, D]``,
    ``mask`` broadcastable to ``[B, 1, 1, Q, S]``, True = attend.
    Float32 logits and softmax, like every attention of the tree.  The
    scores are divided by ``sqrt(D)``, or multiplied by ``scale`` where
    one is given (``models/phi4flash.py``)."""
    logits = jnp.einsum("bqgjd,bgsd->bgjqs", q, k).astype(jnp.float32)
    logits = logits / math.sqrt(q.shape[-1]) if scale is None \
        else logits * scale
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bgjqs,bgsd->bqgjd", probs, v)


def blocked_attention(q, k, v, q_pos, k_pos0, window: Optional[int],
                      scale=None):
    """Attention of many query rows without their ``[heads, Q, S]``
    scores: :data:`ATTN_BLOCK` rows at a time, each block over the span
    of keys it can see.  ``q`` ``[1, Q, G, J, D]`` at positions
    ``q_pos`` ``[Q]`` (consecutive), ``k``/``v`` ``[1, G, S, D]`` at the
    consecutive positions ``k_pos0 + arange(S)``.  With a ``window`` a
    block reads ``window + ATTN_BLOCK`` keys and not ``S``; without one
    every key up to the query's own."""
    n_q, n_k = q.shape[1], k.shape[2]
    blk = n_q if n_q <= ATTN_BLOCK else math.gcd(n_q, ATTN_BLOCK)
    span = n_k if window is None else min(n_k, window + blk)

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_pos, i * blk, blk)
        # the first key the block's first row can see, as a row of k
        at = 0 if window is None else jnp.clip(
            qp[0] - window + 1 - k_pos0, 0, n_k - span)
        kb = jax.lax.dynamic_slice_in_dim(k, at, span, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(v, at, span, axis=2)
        kp = k_pos0 + at + jnp.arange(span)
        see = (kp[None, :] <= qp[:, None]) & (kp[None, :] >= 0)
        if window is not None:
            see = see & (qp[:, None] - kp[None, :] < window)
        return attention(qb, kb, vb, see, scale)[0]

    return jax.lax.map(one, jnp.arange(n_q // blk)).reshape(q.shape)


def block(cfg: Cohere2MoeConfig, lp, li: int, h, positions, cache, *,
          dense: bool, live=None):
    """One layer: ``h`` ``[B, Q, d]`` at ``positions`` ``[B, Q]`` ->
    (new ``h``, tokens per held expert ``[count]``).

    ``cache`` is where this pass keeps keys and values: ``cache.write(li,
    k, v)`` is handed the layer's new rows ``[B, G, Q, D]`` (rotated
    where the layer has positions), once; ``cache.attend(li, q,
    positions)`` then takes queries ``[B, C, G, J, D]`` at ``positions``
    ``[B, C]`` and returns the attention's output shaped like them.  Of
    many rows the queries are made, attended and projected back
    :data:`QUERY_CHUNK` at a time, so that no ``[Q, heads * D]`` array
    exists (268 MB in bfloat16 at 8192 rows, and the compiler keeps
    several).  ``dense`` and ``live`` ``[B, Q]`` are the expert layer's
    (``experts.apply``)."""
    dt = cfg.compute_dtype
    b, s, d = h.shape
    g, hd = cfg.n_kv_heads, cfg.head_dim
    j = cfg.n_heads // g
    rotate = cfg.is_window(li)  # the full layers see no positions at all
    x = nn.layernorm_apply(lp["ln"], h, eps=cfg.norm_eps)
    with jax.named_scope("attn_proj"):
        k = nn.dense_apply(lp["wk"], x, dtype=dt).reshape(b, s, g, hd)
        v = nn.dense_apply(lp["wv"], x, dtype=dt).reshape(b, s, g, hd)
        if rotate:
            k = rope_interleaved(k, positions[:, :, None], cfg.rope_theta)
    cache.write(li, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))

    def attention_of(x, positions):
        c = x.shape[1]
        with jax.named_scope("attn_proj"):
            # the barrier keeps the heads' layout a matter of this small
            # activation: without it the TPU compiler re-lays the WEIGHTS
            # out to suit the attention products, 134 MB of wq a layer
            # and decode step (seen in the compiled program)
            q = jax.lax.optimization_barrier(
                nn.dense_apply(lp["wq"], x, dtype=dt)).reshape(b, c, g, j, hd)
            if rotate:
                q = rope_interleaved(q, positions[:, :, None, None],
                                     cfg.rope_theta)
        o = cache.attend(li, q, positions)
        with jax.named_scope("attn_proj"):
            return nn.dense_apply(lp["wo"], o.reshape(b, c, g * j * hd),
                                  dtype=dt)

    if s <= QUERY_CHUNK or s % QUERY_CHUNK:
        attn = attention_of(x, positions)
    else:
        cut = lambda a: jnp.moveaxis(a.reshape(
            (b, s // QUERY_CHUNK, QUERY_CHUNK) + a.shape[2:]), 1, 0)
        attn = jnp.moveaxis(
            jax.lax.map(lambda c: attention_of(*c), (cut(x), cut(positions))),
            0, 1).reshape(b, s, d)
    with jax.named_scope("mlp"):
        ffn, counts = experts.apply(
            lp["moe"], x.reshape(b * s, d), top_k=cfg.top_k,
            held=cfg.experts_held, dense=dense,
            live=None if live is None else live.reshape(b * s))
    h = (h.astype(jnp.float32) + attn.astype(jnp.float32)
         + ffn.reshape(b, s, d)).astype(dt)
    return h, counts


class _OwnKeys:
    """The cache of a plain forward pass: every layer attends over the
    keys it was just given."""

    def __init__(self, cfg):
        self.cfg = cfg

    def write(self, li, k, v):
        self.k, self.v = k, v

    @jax.named_scope("attn_core")
    def attend(self, li, q, positions):
        window = self.cfg.window if self.cfg.is_window(li) else None
        return jax.vmap(lambda q, k, v, p: blocked_attention(
            q[None], k[None], v[None], p, 0, window)[0])(
                q, self.k, self.v, positions)


class Cohere2Moe:
    def __init__(self, config: Cohere2MoeConfig):
        self.cfg = config
        self._parts = self._flops_parts()

    def init(self, key) -> dict:
        """Normal(0, ``init_std``) in ``cfg.dtype``, the output
        projections (``wo``, the experts' ``down``) scaled by
        ``1 / sqrt(2 x depth)``, the depth of the whole model whichever
        of its layers are held here (``cfg.init_layers``); LayerNorm
        scales and the routers stay float32."""
        cfg = self.cfg
        dt, std = cfg.compute_dtype, cfg.init_std
        out_std = std / math.sqrt(2 * (cfg.init_layers or cfg.n_layers))
        d, hq = cfg.d_model, cfg.n_heads * cfg.head_dim
        hkv = cfg.n_kv_heads * cfg.head_dim

        def w(k, shape, s=std):
            return {"w": nn.normal(k, shape, stddev=s).astype(dt)}

        key, k0 = jax.random.split(key)
        params = {"embed": {
            "table": nn.normal(k0, (cfg.vocab_size, d), stddev=std).astype(dt)}}
        for i in range(cfg.n_layers):
            key, kq, kk, kv, ko, km = jax.random.split(key, 6)
            params[f"layer_{i}"] = {
                "ln": {"scale": jnp.ones((d,), jnp.float32)},
                "wq": w(kq, (d, hq)), "wk": w(kk, (d, hkv)),
                "wv": w(kv, (d, hkv)), "wo": w(ko, (hq, d), out_std),
                "moe": experts.init(
                    km, d, cfg.d_expert, cfg.n_experts, cfg.experts_held,
                    cfg.n_shared, std=std, out_std=out_std, dtype=dt)}
        params["ln_f"] = {"scale": jnp.ones((d,), jnp.float32)}
        return params

    @jax.named_scope("embed")
    def embed(self, params, ids):
        return nn.embedding_apply(params["embed"], ids,
                                  dtype=self.cfg.compute_dtype)

    def logits(self, params, h):
        """``h`` ``[..., d]`` after the last layer -> float32 logits
        over the held rows of the vocabulary, by the embedding itself."""
        h = nn.layernorm_apply(params["ln_f"], h, eps=self.cfg.norm_eps)
        with jax.named_scope("head"):
            return self.cfg.logit_scale * jnp.einsum(
                "...d,vd->...v", h, params["embed"]["table"],
                preferred_element_type=jnp.float32)

    def apply(self, params, ids, dense: bool = False):
        """ids ``[B, S]`` -> logits ``[B, S, vocab]`` float32: the plain
        forward pass, every layer attending over the keys it just made
        (``dense``: the routed product's form, ``experts.apply``)."""
        cfg = self.cfg
        b, s = ids.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

        h = self.embed(params, ids)
        for li in range(cfg.n_layers):
            h, _ = block(cfg, params[f"layer_{li}"], li, h, positions,
                         _OwnKeys(cfg), dense=dense)
        return self.logits(params, h)

    def serve_caches(self, max_batch: int, max_seq: int):
        """What ``serve.engine.InferenceEngine`` serves this model
        through (the interface is in ``serve/caches.py``): a ring for
        the window layers and a full-length slab for the others."""
        from kungfu_tpu.serve.windowed import WindowedCaches

        return WindowedCaches(self, max_batch, max_seq)

    # -- what a forward pass costs (the serving MFU gauge) ---------------
    # (the engine asks once a decode step, on the host's clock between two
    # device steps: constants are worked out once, and a step's live
    # contexts are summed as one array, not slot by slot)
    def _flops_parts(self):
        cfg = self.cfg
        d = cfg.d_model
        attn = 2 * d * cfg.head_dim * (2 * cfg.n_heads + 2 * cfg.n_kv_heads)
        held = cfg.top_k * cfg.experts_held[1] / cfg.n_experts
        ffn = 6 * d * cfg.d_expert * (cfg.n_shared + held)
        token = cfg.n_layers * int(attn + ffn + 2 * d * cfg.n_experts)
        score = 4 * cfg.n_heads * cfg.head_dim  # one query row, one key
        return (token, score * len(cfg.full_layers),
                score * len(cfg.window_layers), 2 * d * cfg.vocab_size)

    def prefill_flops(self, tokens: int, start: int = 0) -> int:
        """Like ``costmodel.serve_prefill_flops``: the products over
        ``tokens`` new rows (a token's expected share of the held
        experts), attention into the context each layer may see, one
        logits row."""
        token, full, window, head = self._parts
        seen_full = tokens * start + tokens * (tokens + 1) // 2
        seen_window = min(seen_full,
                          tokens * min(start + tokens, self.cfg.window))
        return tokens * token + full * seen_full + window * seen_window + head

    def decode_flops(self, contexts) -> int:
        """One decode position of each of the sequences whose contexts
        (keys attended, its own included) are given, summed."""
        token, full, window, head = self._parts
        contexts = np.asarray(contexts)
        return int(contexts.size * (token + head) + full * contexts.sum()
                   + window * np.minimum(contexts, self.cfg.window).sum())
