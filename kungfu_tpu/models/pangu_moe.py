"""The ``pangu_ultra_moe`` decoder (openPangu-Ultra-MoE): latent (MLA)
attention, four RMSNorms a layer (``sandwich_norm``), leading dense
gated-SiLU layers before sparse expert layers (sigmoid top-k, scaled,
beside one shared expert), an untied head, bfloat16 parameters.

With ``u = RMSNorm_in(x)`` a layer is::

    h = x + RMSNorm_post_attn(MLA(u))
    y = h + RMSNorm_post_mlp(FFN(RMSNorm_pre_mlp(h)))

**Latent attention.**  A position keeps ONE row for all query heads:
``c_kv = RMSNorm(W_dkv u)[:r]`` (``r = kv_lora_rank``) and the rotated
``k_r`` (``qk_rope_dim`` wide), ``r + rope`` values against ``heads x
(nope + rope + v)`` of per-head keys and values.  A query is ``c_q =
RMSNorm(W_dq u)``, ``q_h = W_uq,h c_q = [q_nope_h; q_rope_h]``, the
rotary part rotated; per head ``k_nope_h,s = W_uk,h c_kv,s``, ``v_h,s =
W_uv,h c_kv,s`` and ::

    score_h(t, s) = (q_nope_h,t . k_nope_h,s + q_rope_h,t . k_r,s) / sqrt(nope + rope)

The same function is computed in two orders, and which one is the
cache's choice (:func:`expanded_attention`, :func:`absorbed_attention`):

* *expanded* -- the keys and values of every row are formed from its
  ``c_kv`` and attention is the usual one.  Many query rows against rows
  read once: a prefill's, and the plain forward pass's.
* *absorbed* -- ``W_uk`` goes into the query (``q~_h = W_uk,h^T
  q_nope_h``, ``r`` wide) and ``W_uv`` into the output: scores and the
  weighted sum are taken over the latent rows themselves, and no key or
  value of a cached row is ever formed.  One query row a slot against a
  whole slab: a decode step's -- on the TPU one fused kernel a layer
  (``ops/pallas/latent_attention.py``), elsewhere and at sizes it does
  not tile XLA's two products.

The layer is written once, :func:`block`; WHERE the latent rows live is
the cache object's matter, as in ``models/cohere2_moe.py``: ``cache.write(li,
attn_params, c_kv, k_r)`` once a layer, ``cache.attend(li, attn_params,
q_nope, q_rope, positions)`` a chunk of queries.  :meth:`PanguMoe.apply`
attends over the rows it was just given, ``serve/latent.py`` writes them
into the engine's slab first.  ``kfbench/reference/pangu_moe.py`` is the
layer's plain twin (un-absorbed only).

``kv_b_proj`` is held as its two halves, ``w_uk`` ``[H, nope, r]`` and
``w_uv`` ``[H, r, v]``: the same numbers, laid out as both orders want
them (a product batched over the heads).  A chip of an expert-parallel
deployment holds ``cfg.experts_held`` of the router's ``cfg.n_experts``
outputs and ``cfg.vocab_size`` rows of the vocabulary.

Scopes (docs/tracing.md): ``embed``, ``norm``, ``attn_proj`` with
``mla_proj`` inside (down-projections and their norms, ``W_uq``, the
absorption of ``W_uk`` and ``W_uv``, ``W_o``), ``attn_core`` with
``mla_latent_attn`` inside (a decode step's scores, softmax and weighted
sum over the slab), ``mlp`` with ``dense_mlp`` or ``moe_router`` /
``moe_experts`` / ``moe_shared`` inside, ``head``; ``kv_write`` is the
cache's.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kungfu_tpu.models import experts, nn
from kungfu_tpu.models.cohere2_moe import rope_interleaved

F32 = jnp.float32
#: query rows and keys a prefill attends at once (the scores of one
#: tile, ``[heads, ATTN_BLOCK, KEY_CHUNK]`` float32, are 134 MB)
ATTN_BLOCK = 256
KEY_CHUNK = 1024
#: rows a layer takes at once past the write of its latent rows
#: (``block``); ``experts.SORTED_CHUNK`` is as many
QUERY_CHUNK = 1024


@dataclass(frozen=True)
class PanguMoeConfig:
    vocab_size: int = 153600       # rows held here
    d_model: int = 7680
    n_layers: int = 61             # layers held here (a pipeline stage's)
    init_layers: Optional[int] = None  # the whole model's (None: n_layers)
    n_dense: int = 3               # leading layers (held here) with a dense FFN
    n_heads: int = 128
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    d_ff: int = 18432              # the dense layers' width
    d_expert: int = 2048
    n_experts: int = 256           # the router's outputs
    experts_held: Tuple[int, int] = (0, 256)   # (first, count) held here
    top_k: int = 8
    n_shared: int = 1
    routed_scale: float = 2.5
    rope_theta: float = 25600000.0
    norm_eps: float = 1e-5
    init_std: float = 0.02
    max_seq: int = 16384
    dtype: str = "bfloat16"

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def is_dense(self, li: int) -> bool:
        return li < self.n_dense

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(range(self.n_dense, self.n_layers))

    @property
    def cache_row(self) -> Tuple[int, int, int]:
        """What one position of one layer keeps, as ``serve.kvcache.
        PageSpec`` counts it: (heads, width of the first part, width of
        the second) -- ONE row for all the query heads, ``c_kv`` and
        ``k_r``."""
        return 1, self.kv_lora_rank, self.qk_rope_dim

    @property
    def score_scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_nope_dim + self.qk_rope_dim)


def _norm(p, x, eps):
    with jax.named_scope("norm"):
        return nn.rmsnorm_apply(p, x, eps=eps)


def expand(ap, c_kv):
    """Latent rows ``[..., S, r]`` -> their per-head keys' un-rotated part
    ``[..., H, nope, S]`` and values ``[..., H, v, S]``: the rows last, as
    the products leave them on the TPU (asked for rows-first, each is
    followed by a copy of its 537 MB at 16,384 rows: seen in the compiled
    prefill)."""
    return (jnp.einsum("...sc,hnc->...hns", c_kv, ap["w_uk"]),
            jnp.einsum("...sc,hcv->...hvs", c_kv, ap["w_uv"]))


def expanded_attention(q_nope, q_rope, k_nope, k_rope, v, q_pos, scale):
    """The un-absorbed order: ``q_nope`` ``[Q, H, nope]`` and ``q_rope``
    ``[Q, H, rope]`` at the consecutive positions ``q_pos`` ``[Q]``, over
    the per-head ``k_nope`` ``[H, nope, S]`` and ``v`` ``[H, v, S]``
    (:func:`expand`) and the shared ``k_rope`` ``[S, rope]`` of the rows
    at positions ``arange(S)`` -> ``[Q, H, v]``.  The two parts of a
    score are two products summed in float32: the rotary key is never
    copied a head.

    :data:`ATTN_BLOCK` query rows at a time against :data:`KEY_CHUNK`
    keys at a time, the softmax carried across the chunks (running
    maximum, sum and weighted values, all float32), and a block walks
    only the chunks its last row can see: no ``[heads, Q, S]`` scores
    exist, and a prompt of ``P`` tokens costs ``P^2 / 2`` scores however
    long the slot is.  (One block over all ``S`` keys under a mask wrote
    and read 537 MB of scores a block whatever the prompt: 3.7 s a
    prefill, PERF.md, PR 33.)"""
    n_q, n_k = q_nope.shape[0], k_rope.shape[0]
    blk = n_q if n_q <= ATTN_BLOCK else math.gcd(n_q, ATTN_BLOCK)
    kc = n_k if n_k <= KEY_CHUNK else math.gcd(n_k, KEY_CHUNK)
    heads, dv = v.shape[:2]

    def one(i):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * blk, blk)
        qn, qr, qp = cut(q_nope), cut(q_rope), cut(q_pos)

        def chunk(j, carry):
            top, total, acc = carry
            keys = lambda a, axis: jax.lax.dynamic_slice_in_dim(
                a, j * kc, kc, axis)
            scores = (jnp.einsum("qhn,hns->hqs", qn, keys(k_nope, 2),
                                 preferred_element_type=F32)
                      + jnp.einsum("qhr,sr->hqs", qr, keys(k_rope, 0),
                                   preferred_element_type=F32)) * scale
            k_pos = j * kc + jnp.arange(kc)
            scores = jnp.where(k_pos[None, :] <= qp[:, None], scores, -1e30)
            new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
            probs = jnp.exp(scores - new_top[..., None])
            keep = jnp.exp(top - new_top)
            return (new_top, keep * total + jnp.sum(probs, axis=-1),
                    keep[..., None] * acc + jnp.einsum(
                        "hqs,hvs->hqv", probs.astype(v.dtype), keys(v, 2),
                        preferred_element_type=F32))

        # (chunk 0 holds position 0, which every row sees: the running
        # maximum is a real score from the first chunk on)
        seen = jnp.minimum(qp[-1] // kc + 1, n_k // kc)
        _, total, acc = jax.lax.fori_loop(0, seen, chunk, (
            jnp.full((heads, blk), -1e30, F32), jnp.zeros((heads, blk), F32),
            jnp.zeros((heads, blk, dv), F32)))
        return (acc / total[..., None]).astype(v.dtype).transpose(1, 0, 2)

    out = jax.lax.map(one, jnp.arange(n_q // blk))
    return out.reshape((n_q,) + out.shape[2:])


def absorbed_tile(heads: int, seq: int, r: int, rope: int, dtype):
    """The key tile with which a decode step's attention over a slab of
    ``seq`` positions is ONE fused kernel a layer
    (``ops/pallas/latent_attention.py``), or None where it is XLA's two
    products: off the TPU, and for shapes the kernel does not tile.  One
    choice, from the platform and the shapes, at trace time; the kernel's
    package is imported here and by no module's import, so a process
    that traces no such step never pays for it (PERF.md, PR 35)."""
    if jax.default_backend() != "tpu":
        return None
    from kungfu_tpu.ops.pallas import latent_attention

    return latent_attention.key_tile(seq, heads, r, rope, dtype)


def absorbed_products(q_lat, q_rope, c, k_r, li, n, scale):
    """Scores, softmax and weighted sum of the absorbed order as XLA's two
    products, the ``[B, H, S]`` float32 scores between them (arguments
    and result as ``latent_attention.latent_attn``'s, zeros for a slot
    that sees no row among them): what runs where the kernel does not,
    every row read under the mask, and what the tests hold the kernel
    to."""
    c_kv, k_rope = c[li][:, 0], k_r[li][:, 0]
    see = (jnp.arange(c.shape[3]) < n[:, None])[:, None]        # [B, 1, S]
    scores = (jnp.einsum("bhc,bsc->bhs", q_lat, c_kv,
                         preferred_element_type=F32)
              + jnp.einsum("bhr,bsr->bhs", q_rope, k_rope,
                           preferred_element_type=F32)) * scale
    probs = jnp.where(see, jax.nn.softmax(
        jnp.where(see, scores, -1e30), axis=-1), 0.0)
    return jnp.einsum("bhs,bsc->bhc", probs.astype(c_kv.dtype), c_kv)


def absorbed_attention(ap, q_nope, q_rope, c, k_r, li, n, scale):
    """The absorbed order, one query row a sequence: ``q_nope`` ``[B, H,
    nope]``, ``q_rope`` ``[B, H, rope]`` over layer ``li`` of the latent
    rows themselves, the slab's parts ``c`` ``[L, B, 1, S, r]`` and
    ``k_r`` ``[L, B, 1, S, rope]`` handed over whole, each sequence over
    its first ``n`` ``[B]`` rows (0: a slot the step is not for, which
    gets zeros) -> ``[B, H, v]``.  Scores, softmax and weighted sum are
    one kernel over the slab where :func:`absorbed_tile` gives a tile
    (the scores never reach memory, a latent row is read once and a tile
    no sequence sees is not read at all), else
    :func:`absorbed_products`.  Either has the slab's whole shape
    whatever is live; the kernel's time follows ``n``."""
    with jax.named_scope("attn_proj"), jax.named_scope("mla_proj"):
        q_lat = jnp.einsum("bhn,hnc->bhc", q_nope, ap["w_uk"])
    tile = absorbed_tile(q_lat.shape[1], c.shape[3], c.shape[-1],
                         k_r.shape[-1], c.dtype)
    with jax.named_scope("attn_core"), jax.named_scope("mla_latent_attn"):
        if tile:
            from kungfu_tpu.ops.pallas.latent_attention import latent_attn

            o_lat = latent_attn(q_lat, q_rope, c, k_r, li, n, scale,
                                tile=tile)
        else:
            o_lat = absorbed_products(q_lat, q_rope, c, k_r, li, n, scale)
    with jax.named_scope("attn_proj"), jax.named_scope("mla_proj"):
        return jnp.einsum("bhc,hcv->bhv", o_lat, ap["w_uv"])


def _by_chunks(fn, s: int, *rows):
    """``fn`` over ``rows`` (each ``[B, S, ...]``), :data:`QUERY_CHUNK` of
    the ``S`` rows at a time where there are more; of what ``fn`` returns
    (a tree) the leaves ``[B, C, ...]`` are joined back to ``[B, S,
    ...]`` and those without the two leading axes summed."""
    if s <= QUERY_CHUNK or s % QUERY_CHUNK:
        return fn(*rows)
    b = rows[0].shape[0]
    cut = lambda a: jnp.moveaxis(a.reshape(
        (b, s // QUERY_CHUNK, QUERY_CHUNK) + a.shape[2:]), 1, 0)
    out = jax.lax.map(lambda c: fn(*c), tuple(cut(a) for a in rows))
    return jax.tree_util.tree_map(
        lambda a: jnp.moveaxis(a, 0, 1).reshape((b, s) + a.shape[3:])
        if a.shape[1:3] == (b, QUERY_CHUNK) else jnp.sum(a, axis=0), out)


@jax.named_scope("dense_mlp")
def dense_mlp(p, x):
    """Gated SiLU of width ``d_ff``, float32 out (like the experts')."""
    g = x @ p["gate"]["w"]
    u = x @ p["up"]["w"]
    return jnp.matmul(jax.nn.silu(g) * u, p["down"]["w"],
                      preferred_element_type=F32)


def block(cfg: PanguMoeConfig, lp, li: int, h, positions, cache, *,
          dense: bool, live=None):
    """One layer: ``h`` ``[B, Q, d]`` at ``positions`` ``[B, Q]`` ->
    (new ``h``, tokens per held expert ``[count]``, or None from a dense
    layer).

    ``cache`` is where this pass keeps latent rows: ``cache.write(li, ap,
    c_kv, k_r)`` is handed the layer's new rows (``[B, Q, r]`` normed,
    ``[B, Q, rope]`` rotated) and the attention's parameters, once;
    ``cache.attend(li, ap, q_nope, q_rope, positions)`` then takes
    queries ``[B, C, H, nope]`` / ``[B, C, H, rope]`` (rotated) at
    ``positions`` ``[B, C]`` and returns the heads' outputs ``[B, C, H,
    v]``, in whichever order suits it (module docstring).  Everything
    after the write is a row's own matter but for the rows it attends
    over, so of many rows it is done :data:`QUERY_CHUNK` at a time, from
    the queries to the second residual add: no ``[Q, heads x 192]``
    queries (805 MB in bfloat16 at 16,384 rows), no ``[Q, d_ff]`` hidden
    rows and no float32 copy of the stream exist.  ``dense`` and ``live``
    ``[B, Q]`` are the expert layer's (``experts.apply``)."""
    dt, eps = cfg.compute_dtype, cfg.norm_eps
    b, s, d = h.shape
    nh, nope, r = cfg.n_heads, cfg.qk_nope_dim, cfg.kv_lora_rank
    ap = lp["attn"]

    def latent_of(h):
        x = _norm(lp["ln_in"], h, eps)
        with jax.named_scope("attn_proj"), jax.named_scope("mla_proj"):
            return nn.dense_apply(ap["wkv_a"], x, dtype=dt)

    kv = _by_chunks(latent_of, s, h)
    with jax.named_scope("attn_proj"), jax.named_scope("mla_proj"):
        c_kv = _norm(ap["kv_ln"], kv[..., :r], eps)
        k_r = rope_interleaved(kv[..., r:], positions, cfg.rope_theta)
    cache.write(li, ap, c_kv, k_r)

    def rest(h, positions, live):
        c = h.shape[1]
        x = _norm(lp["ln_in"], h, eps)
        with jax.named_scope("attn_proj"), jax.named_scope("mla_proj"):
            c_q = _norm(ap["q_ln"], nn.dense_apply(ap["wq_a"], x, dtype=dt),
                        eps)
            # (the barrier keeps the heads' layout a matter of this small
            # activation and not of the weights: models/cohere2_moe.py)
            q = jax.lax.optimization_barrier(
                nn.dense_apply(ap["wq_b"], c_q, dtype=dt)).reshape(
                    b, c, nh, nope + cfg.qk_rope_dim)
            q_rope = rope_interleaved(q[..., nope:], positions[:, :, None],
                                      cfg.rope_theta)
        o = cache.attend(li, ap, q[..., :nope], q_rope, positions)
        with jax.named_scope("attn_proj"), jax.named_scope("mla_proj"):
            attn = nn.dense_apply(ap["wo"], o.reshape(b, c, -1), dtype=dt)
        h = h + _norm(lp["ln_post_attn"], attn, eps)
        x = _norm(lp["ln_pre_mlp"], h, eps)
        with jax.named_scope("mlp"):
            if cfg.is_dense(li):
                ffn, counts = dense_mlp(lp["mlp"], x), None
            else:
                ffn, counts = experts.apply(
                    lp["moe"], x.reshape(b * c, d), top_k=cfg.top_k,
                    held=cfg.experts_held, dense=dense,
                    live=live.reshape(b * c), scale=cfg.routed_scale)
        ffn = _norm(lp["ln_post_mlp"], ffn.reshape(b, c, d), eps)
        return (h.astype(F32) + ffn).astype(dt), counts

    if live is None:
        live = jnp.ones((b, s), bool)
    return _by_chunks(rest, s, h, positions, live)


class _OwnRows:
    """The cache of a plain forward pass: every layer attends, in the
    expanded order, over the rows it was just given."""

    def __init__(self, cfg):
        self.cfg = cfg

    def write(self, li, ap, c_kv, k_r):
        self.keys = expand(ap, c_kv) + (k_r,)

    @jax.named_scope("attn_core")
    def attend(self, li, ap, q_nope, q_rope, positions):
        k_nope, v, k_r = self.keys
        return jax.vmap(lambda *a: expanded_attention(
            *a, self.cfg.score_scale))(q_nope, q_rope, k_nope, k_r, v,
                                       positions)


class PanguMoe:
    def __init__(self, config: PanguMoeConfig):
        self.cfg = config
        self._parts = self._flops_parts()

    def init(self, key) -> dict:
        """Normal(0, ``init_std``) in ``cfg.dtype``, the output
        projections (``wo``, every ``down``) scaled by ``1 / sqrt(2 x
        depth)``, the depth of the whole model whichever of its layers
        are held here (``cfg.init_layers``); the norms' scales and the
        routers stay float32."""
        cfg = self.cfg
        dt, std = cfg.compute_dtype, cfg.init_std
        out_std = std / math.sqrt(2 * (cfg.init_layers or cfg.n_layers))
        d, nh, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank

        def w(k, shape, s=std):
            return nn.normal(k, shape, stddev=s).astype(dt)

        def ones(n):
            return {"scale": jnp.ones((n,), F32)}

        key, k0, k1 = jax.random.split(key, 3)
        params = {"embed": {"table": w(k0, (cfg.vocab_size, d))},
                  "head": {"w": w(k1, (d, cfg.vocab_size))},
                  "ln_f": ones(d)}
        for i in range(cfg.n_layers):
            key, kqa, kqb, kkv, kuk, kuv, ko, km = jax.random.split(key, 8)
            lp = {name: ones(d) for name in (
                "ln_in", "ln_post_attn", "ln_pre_mlp", "ln_post_mlp")}
            lp["attn"] = {
                "wq_a": {"w": w(kqa, (d, cfg.q_lora_rank))},
                "q_ln": ones(cfg.q_lora_rank),
                "wq_b": {"w": w(kqb, (cfg.q_lora_rank, nh * (
                    cfg.qk_nope_dim + cfg.qk_rope_dim)))},
                "wkv_a": {"w": w(kkv, (d, r + cfg.qk_rope_dim))},
                "kv_ln": ones(r),
                "w_uk": w(kuk, (nh, cfg.qk_nope_dim, r)),
                "w_uv": w(kuv, (nh, r, cfg.v_head_dim)),
                "wo": {"w": w(ko, (nh * cfg.v_head_dim, d), out_std)}}
            if cfg.is_dense(i):
                kg, ku, kd = jax.random.split(km, 3)
                lp["mlp"] = {"gate": {"w": w(kg, (d, cfg.d_ff))},
                             "up": {"w": w(ku, (d, cfg.d_ff))},
                             "down": {"w": w(kd, (cfg.d_ff, d), out_std)}}
            else:
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
        forward pass, every layer attending over the rows it just made
        (``dense``: the routed product's form, ``experts.apply``)."""
        cfg = self.cfg
        b, s = ids.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        h = self.embed(params, ids)
        for li in range(cfg.n_layers):
            h, _ = block(cfg, params[f"layer_{li}"], li, h, positions,
                         _OwnRows(cfg), dense=dense)
        return self.logits(params, h)

    def serve_caches(self, max_batch: int, max_seq: int):
        """What ``serve.engine.InferenceEngine`` serves this model
        through (the interface is in ``serve/caches.py``): one latent
        slab, the prefill in the expanded order and the decode step in
        the absorbed one."""
        from kungfu_tpu.serve.latent import LatentCaches

        return LatentCaches(self, max_batch, max_seq)

    # -- what a forward pass costs (the serving MFU gauge) ---------------
    # (constants worked out once; a step's live contexts summed as one
    # array: models/cohere2_moe.py)
    def _flops_parts(self):
        cfg = self.cfg
        d, nh, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        # a token's products outside the attention over other rows; its
        # own row's keys and values (or their absorption) among them
        attn = 2 * (d * cfg.q_lora_rank + cfg.q_lora_rank * nh * qk
                    + d * (r + cfg.qk_rope_dim)
                    + nh * r * (cfg.qk_nope_dim + cfg.v_head_dim)
                    + nh * cfg.v_head_dim * d)
        held = cfg.top_k * cfg.experts_held[1] / cfg.n_experts
        moe = 6 * d * cfg.d_expert * (cfg.n_shared + held) \
            + 2 * d * cfg.n_experts
        n_moe = len(cfg.expert_layers)
        token = int(cfg.n_layers * attn + cfg.n_dense * 6 * d * cfg.d_ff
                    + n_moe * moe)
        return (token,
                cfg.n_layers * 2 * nh * (qk + cfg.v_head_dim),   # expanded
                cfg.n_layers * 2 * nh * (2 * r + cfg.qk_rope_dim),  # absorbed
                2 * d * cfg.vocab_size)

    def prefill_flops(self, tokens: int, start: int = 0) -> int:
        """The products over ``tokens`` new rows, expanded attention into
        the context, one logits row."""
        token, expanded, _, head = self._parts
        seen = tokens * start + tokens * (tokens + 1) // 2
        return tokens * token + expanded * seen + head

    def decode_flops(self, contexts) -> int:
        """One decode position of each of the sequences whose contexts
        (rows attended, its own included) are given, summed."""
        token, _, absorbed, head = self._parts
        contexts = np.asarray(contexts)
        return int(contexts.size * (token + head) + absorbed * contexts.sum())
