"""The ``phi4flash`` decoder (Phi-4-mini-flash-reasoning; the
architecture is SambaY, arXiv:2507.06607): a *self-decoder* whose layers
alternate a selective state-space mixer (Mamba) with differential
attention -- over a sliding window, and in its last layer over every
earlier position -- and a *cross-decoder* of as many layers again less
two, which alternate a Gated Memory Unit with cross attention and keep
NOTHING of their own: a GMU gates the last Mamba layer's scan output of
the SAME position, a cross layer attends over the ONE full layer's keys
and values.  LayerNorm with a bias, a gated FFN in every layer, a tied
head, no positional encoding anywhere, bfloat16 parameters.

With ``n_layers = 2 h`` (32: ``h`` 16) layer ``l`` is ::

    l even, l <= h      mamba        (9; layer h hands on its scan output m)
    l odd,  l <  h      attn_window  (8; a position sees itself and the
                                      window - 1 before it)
    l = h + 1           attn_full    (1; its rows are all the K/V there is)
    l even, l >= h + 2  gmu          (7)
    l odd,  l >= h + 3  attn_cross   (7)

and ``x += Mix_l(LN(x)); x += W_down(silu(g) * u)`` with ``[g, u] =
W_gate_up LN'(x)``.

**Mamba** (``ops/selective_scan.py`` has the recurrence): ``[a, z] =
W_in x``; ``c = silu(conv4(a) + b_conv)``; ``[r, B, C] = W_x c``; ``dt =
softplus(W_dt r + b_dt)``; ``h <- exp(dt A) h + dt c B``; ``y = h C + D
c``; ``Mix = W_out(y * silu(z))``; ``m = y``, before the gate.

**Differential attention** (arXiv:2410.05258).  Heads of width ``D`` go
in pairs by parity, ``q1_i = q_{2i}, q2_i = q_{2i+1}``; likewise ``k1_j,
k2_j``, and ``V_j = [v_{2j}; v_{2j+1}]`` of width ``2 D``; query pair
``i`` reads key/value pair ``i // 2``::

    o_i = softmax(q1_i k1_j^T / sqrt(D)) V_j - lam softmax(q2_i k2_j^T / sqrt(D)) V_j
    o_i <- RMSNorm_2D(o_i; g) (1 - lam_init)
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init,  lam_init = 0.8 - 0.6 exp(-0.3 l)

**One layout for every form of it**: a position's row is kept as the
PAIRS, ``[k_{2j}; k_{2j+1}]`` and ``V_j``, ``G' = n_kv_heads / 2`` rows
of width ``W' = 2 D`` (what the projection gives, reshaped), and the four
queries that read pair ``j`` are laid as ``[q, 0]`` (even parity) and
``[0, q]`` (odd) of width ``W'``: each score is the ``D``-wide product
(the zeros add 0.0), each softmax the map's own, each output ``P V_j``
with K and V read once -- ordinary grouped attention of ``J = 4`` query
rows over ``G'`` heads of ``W'``, scores times ``1 / sqrt(D)``.  A **cross
layer** makes queries only, and reads the full layer's rows so.

The layer is written once, :func:`block`; WHERE a layer's past lives is
the cache object's matter, as in the other served families:
``cache.write(li, k, v)`` and ``cache.attend(li, q, positions)`` for
attention (a cross layer only attends; the cache knows whose rows it
reads), ``cache.convolve(li, a, w)`` and ``cache.recur(li, c, dt, B, C,
A, D)`` for a Mamba layer; ``m`` goes from block to block through the
caller.  :meth:`Phi4Flash.apply` brings a cache that starts every
sequence from nothing and runs every layer over every position;
``serve/sambay.py`` the engine's, whose prefill runs the cross-decoder
over the prompt's LAST position alone.
``kfbench/reference/phi4flash.py`` is the layer's plain twin.  This
module imports no kernel package.

Scopes (docs/tracing.md): ``embed``, ``norm``, ``attn_proj`` (with
``ssm_proj`` inside: a Mamba layer's projections, convolution and gate;
``gmu``: a GMU, whole), ``attn_core`` (``ssm_state`` for the cache's
one-token update, ``ssm_scan`` for its chunked form; ``attn_window`` /
``attn_full`` / ``attn_cross`` for the attention and the differential
combination behind it), ``mlp`` with ``dense_mlp`` inside, ``head``;
``kv_write`` is the cache's.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kungfu_tpu.models import nn
from kungfu_tpu.models.cohere2_moe import blocked_attention
from kungfu_tpu.ops import selective_scan
from kungfu_tpu.ops.delta_rule import causal_conv

F32 = jnp.float32


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    d_model: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64
    d_ff: int = 10240
    window: int = 512              # positions a window layer sees, its own among them
    d_inner: int = 5120            # a Mamba layer's channels (and a GMU's)
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    norm_eps: float = 1e-5
    init_std: float = 0.02
    max_seq: int = 4096
    dtype: str = "bfloat16"

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def memory_layer(self) -> int:
        """The last Mamba layer: the GMUs gate its scan output."""
        return self.n_layers // 2

    @property
    def full_layer(self) -> int:
        """The one layer whose rows of every position are kept."""
        return self.n_layers // 2 + 1

    def kind(self, li: int) -> str:
        if li > self.full_layer:
            return "attn_cross" if li % 2 else "gmu"
        if li % 2 == 0:
            return "mamba"
        return "attn_full" if li == self.full_layer else "attn_window"

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i in range(self.n_layers) if self.kind(i) == kind)

    @property
    def recurrent_layers(self) -> Tuple[int, ...]:
        """The layers that keep a state a slot and no rows a position."""
        return self.layers_of("mamba")

    @property
    def window_layers(self) -> Tuple[int, ...]:
        return self.layers_of("attn_window")

    @property
    def cross_layers(self) -> Tuple[int, ...]:
        return self.layers_of("attn_cross")

    @property
    def row_layers(self) -> Tuple[int, ...]:
        """The layers that KEEP rows a position (``serve.kvcache.PageSpec``
        counts a page over them): the window layers and the full one.
        The cross layers read the full layer's and keep none."""
        return self.window_layers + (self.full_layer,)

    @property
    def pair_heads(self) -> int:
        """``G'``: key/value pairs a row holds."""
        return self.n_kv_heads // 2

    @property
    def pair_width(self) -> int:
        """``W'``: a pair's width, two heads'."""
        return 2 * self.head_dim

    def lam_init(self, li: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * li)


def paired_queries(q, g: int):
    """``q`` ``[B, Q, H, D]`` -> ``[B, Q, G', J, 2 D]`` (``J = H / G'``):
    the queries that read pair ``j`` side by side, an even head as ``[q,
    0]`` and an odd one as ``[0, q]``."""
    b, s, h, d = q.shape
    zero = jnp.zeros_like(q)
    odd = (jnp.arange(h) % 2 == 1)[:, None]
    wide = jnp.concatenate([jnp.where(odd, zero, q), jnp.where(odd, q, zero)],
                           axis=-1)
    return wide.reshape(b, s, g, h // g, 2 * d)


def differential(p, o, lam_init: float, eps: float):
    """The two maps' outputs ``o`` ``[B, Q, G', J, W']`` (row ``2 r`` of a
    pair the first map's, ``2 r + 1`` the second's) -> ``[B, Q, H D]``:
    their difference under ``lam``, the norm a pair, the heads laid back."""
    b, s, g, j, w = o.shape
    lq1, lk1, lq2, lk2 = p["lam"].astype(F32)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam_init
    o = o.astype(F32)
    o = o[:, :, :, 0::2] - lam * o[:, :, :, 1::2]
    o = nn.rmsnorm_apply(p["sub_norm"], o, eps=eps) * (1.0 - lam_init)
    return o.reshape(b, s, g * (j // 2) * w)


def attn_mixer(cfg: Phi4FlashConfig, p, li, kind, x, positions, cache):
    """Differential attention, self or cross: ``x`` ``[B, Q, d]`` ->
    ``[B, Q, d]``."""
    dt = cfg.compute_dtype
    b, s, _ = x.shape
    g, w, hd = cfg.pair_heads, cfg.pair_width, cfg.head_dim
    hq = cfg.n_heads * hd
    with jax.named_scope("attn_proj"):
        if kind == "attn_cross":
            q = nn.dense_apply(p["wq"], x, dtype=dt)
        else:
            qkv = nn.dense_apply(p["w_qkv"], x, dtype=dt)
            q = qkv[..., :hq]
            k, v = (qkv[..., hq + i * g * w:hq + (i + 1) * g * w
                        ].reshape(b, s, g, w).transpose(0, 2, 1, 3)
                    for i in range(2))
    if kind != "attn_cross":
        cache.write(li, k, v)
    with jax.named_scope("attn_proj"):
        # (the barrier keeps the heads' layout a matter of this small
        # activation and not of the weights: models/cohere2_moe.py)
        q = paired_queries(jax.lax.optimization_barrier(q).reshape(
            b, s, cfg.n_heads, hd), g)
    o = cache.attend(li, q, positions)
    with jax.named_scope("attn_core"), jax.named_scope(kind):
        o = differential(p, o, cfg.lam_init(li), cfg.norm_eps)
    with jax.named_scope("attn_proj"):
        return nn.dense_apply(p["wo"], o.astype(dt), dtype=dt)


def mamba_mixer(cfg: Phi4FlashConfig, p, li, x, cache):
    """The selective state-space layer: ``x`` ``[B, Q, d]`` -> (``[B, Q,
    d]``, the scan's output ``[B, Q, E]`` float32 before the gate).
    Everything a token computes alone is here; the convolution's reach
    into the past and the recurrence are the cache's."""
    dt = cfg.compute_dtype
    e, n, r = cfg.d_inner, cfg.d_state, cfg.dt_rank
    with jax.named_scope("attn_proj"), jax.named_scope("ssm_proj"):
        az = nn.dense_apply(p["w_in"], x, dtype=dt)
        a, z = az[..., :e], az[..., e:]
        c = jax.nn.silu(cache.convolve(li, a, p["conv"])
                        + p["conv_b"].astype(F32))            # float32
        rbc = nn.dense_apply(p["w_x"], c.astype(dt), dtype=dt)
        step = jax.nn.softplus(
            nn.dense_apply(p["w_dt"], rbc[..., :r], dtype=dt).astype(F32)
            + p["b_dt"])
        A = -jnp.exp(p["a_log"])
    y = cache.recur(li, c, step, rbc[..., r:r + n], rbc[..., r + n:], A,
                    p["d"])                                   # float32
    with jax.named_scope("attn_proj"), jax.named_scope("ssm_proj"):
        gated = y * jax.nn.silu(z.astype(F32))
        return nn.dense_apply(p["w_out"], gated.astype(dt), dtype=dt), y


def gmu_mixer(cfg: Phi4FlashConfig, p, x, m):
    """The Gated Memory Unit: ``x`` ``[B, Q, d]`` gates ``m`` ``[B, Q,
    E]``, the memory layer's scan output of the same positions."""
    dt = cfg.compute_dtype
    with jax.named_scope("attn_proj"), jax.named_scope("gmu"):
        gate = jax.nn.silu(nn.dense_apply(p["w_in"], x, dtype=dt).astype(F32))
        return nn.dense_apply(p["w_out"], (gate * m).astype(dt), dtype=dt)


def block(cfg: Phi4FlashConfig, lp, li: int, h, positions, cache, m=None):
    """One layer: ``h`` ``[B, Q, d]`` at ``positions`` ``[B, Q]`` -> (new
    ``h``, ``m``).  ``cache`` is where this pass keeps the layer's past
    (module docstring).  ``m`` ``[B, Q, E]`` is the memory layer's scan
    output: that layer returns it, a GMU reads it, every layer hands on
    what it was given."""
    dt, eps = cfg.compute_dtype, cfg.norm_eps
    kind = cfg.kind(li)
    x = nn.layernorm_apply(lp["ln_mixer"], h, eps=eps)
    if kind == "mamba":
        mixed, y = mamba_mixer(cfg, lp["mamba"], li, x, cache)
        if li == cfg.memory_layer:
            m = y
    elif kind == "gmu":
        mixed = gmu_mixer(cfg, lp["gmu"], x, m)
    else:
        mixed = attn_mixer(cfg, lp["attn"], li, kind, x, positions, cache)
    h = h + mixed
    x = nn.layernorm_apply(lp["ln_mlp"], h, eps=eps)
    with jax.named_scope("mlp"), jax.named_scope("dense_mlp"):
        gu = nn.dense_apply(lp["mlp"]["w_gate_up"], x, dtype=dt)
        act = (jax.nn.silu(gu[..., :cfg.d_ff].astype(F32))
               * gu[..., cfg.d_ff:].astype(F32)).astype(dt)
        return h + nn.dense_apply(lp["mlp"]["w_down"], act, dtype=dt), m


class _FromNothing:
    """The cache of a plain forward pass: every sequence starts with no
    state, no convolution tail and no keys but its own; the full layer's
    rows stay for the cross layers behind it."""

    def __init__(self, cfg):
        self.cfg = cfg

    def write(self, li, k, v):
        self.keys = (k, v)

    @jax.named_scope("attn_core")
    def attend(self, li, q, positions):
        kind = self.cfg.kind(li)
        window = self.cfg.window if kind == "attn_window" else None
        with jax.named_scope(kind):
            return jax.vmap(lambda q, k, v, p: blocked_attention(
                q[None], k[None], v[None], p, 0, window,
                1.0 / math.sqrt(self.cfg.head_dim))[0])(
                    q, *self.keys, positions)

    def convolve(self, li, a, w):
        tail = jnp.zeros((w.shape[0] - 1, a.shape[-1]), a.dtype)
        return jax.vmap(lambda a: causal_conv(a, w, tail)[0])(a)

    @jax.named_scope("attn_core")
    def recur(self, li, c, dt, B, C, A, D):
        zero = jnp.zeros((self.cfg.d_state, self.cfg.d_inner), F32)
        with jax.named_scope("ssm_scan"):
            return jax.vmap(lambda c, dt, B, C: selective_scan.chunked(
                c, dt, A, B, C, D, zero)[0])(c, dt, B, C)


class Phi4Flash:
    def __init__(self, config: Phi4FlashConfig):
        cfg = self.cfg = config
        if cfg.n_layers % 4 or cfg.n_layers < 8:
            raise ValueError("phi4flash alternates four kinds of layer over "
                             f"two halves: not {cfg.n_layers} layers")
        if cfg.n_kv_heads % 2 or cfg.n_heads % cfg.n_kv_heads:
            raise ValueError("differential attention pairs the heads: "
                             f"{cfg.n_heads} over {cfg.n_kv_heads}")
        self._parts = self._flops_parts()

    def init(self, key) -> dict:
        """Normal(0, ``init_std``) in ``cfg.dtype``, biases of the
        projections zero.  A Mamba layer's ``a_log`` is ``log(1 .. N)`` a
        channel (kept ``[N, E]``, the state's own layout), ``d`` one, its
        ``b_dt`` the inverse softplus of a step drawn log-uniformly from
        ``[0.001, 0.1]`` a channel, the convolution ``U(-1, 1) /
        sqrt(taps)`` with a zero bias; the four ``lam`` vectors normal
        0.1.  Norms, ``a_log``, ``d``, ``b_dt`` and ``lam`` stay
        float32."""
        cfg = self.cfg
        dt, std = cfg.compute_dtype, cfg.init_std
        d, e, n, r = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.dt_rank
        hq = cfg.n_heads * cfg.head_dim
        hkv = cfg.n_kv_heads * cfg.head_dim
        taps = cfg.d_conv

        def w(k, shape, bias=False):
            p = {"w": nn.normal(k, shape, stddev=std).astype(dt)}
            if bias:
                p["b"] = jnp.zeros((shape[1],), dt)
            return p

        def ln():
            return {"scale": jnp.ones((d,), F32), "bias": jnp.zeros((d,), F32)}

        def attn(kq, ko, kl, cross):
            first = {"wq": w(kq, (d, hq), True)} if cross else {
                "w_qkv": w(kq, (d, hq + 2 * hkv), True)}
            return dict(first, wo=w(ko, (hq, d), True),
                        lam=nn.normal(kl, (4, cfg.head_dim), stddev=0.1),
                        sub_norm={"scale": jnp.ones((cfg.pair_width,), F32)})

        key, k0 = jax.random.split(key)
        params = {"embed": {"table": w(k0, (cfg.vocab_size, d))["w"]},
                  "ln_f": ln()}
        for i in range(cfg.n_layers):
            key, km, kg, kd = jax.random.split(key, 4)
            km = jax.random.split(km, 6)    # (as many as a Mamba layer takes)
            kind = cfg.kind(i)
            lp = {"ln_mixer": ln(), "ln_mlp": ln(),
                  "mlp": {"w_gate_up": w(kg, (d, 2 * cfg.d_ff)),
                          "w_down": w(kd, (cfg.d_ff, d))}}
            if kind == "mamba":
                ki, kc, kx, kt, ks, ko = km
                step = jnp.exp(jax.random.uniform(
                    ks, (e,), F32, math.log(1e-3), math.log(1e-1)))
                lp["mamba"] = {
                    "w_in": w(ki, (d, 2 * e)),
                    "conv": jax.random.uniform(
                        kc, (taps, e), F32, -1.0, 1.0
                    ).astype(dt) / math.sqrt(taps),
                    "conv_b": jnp.zeros((e,), dt),
                    "w_x": w(kx, (e, r + 2 * n)), "w_dt": w(kt, (r, e)),
                    "b_dt": step + jnp.log(-jnp.expm1(-step)),
                    "a_log": jnp.log(jnp.broadcast_to(
                        jnp.arange(1, n + 1, dtype=F32)[:, None], (n, e))),
                    "d": jnp.ones((e,), F32),
                    "w_out": w(ko, (e, d))}
            elif kind == "gmu":
                ki, ko = km[:2]
                lp["gmu"] = {"w_in": w(ki, (d, e)), "w_out": w(ko, (e, d))}
            else:
                lp["attn"] = attn(*km[:3], kind == "attn_cross")
            params[f"layer_{i}"] = lp
        return params

    @jax.named_scope("embed")
    def embed(self, params, ids):
        return nn.embedding_apply(params["embed"], ids,
                                  dtype=self.cfg.compute_dtype)

    def logits(self, params, h):
        """``h`` ``[..., d]`` after the last layer -> float32 logits over
        the vocabulary, against the embedding itself (a tied head)."""
        h = nn.layernorm_apply(params["ln_f"], h, eps=self.cfg.norm_eps)
        with jax.named_scope("head"):
            return jnp.einsum("...d,vd->...v", h, params["embed"]["table"],
                              preferred_element_type=F32)

    def apply(self, params, ids):
        """ids ``[B, S]`` -> logits ``[B, S, vocab]`` float32: the plain
        forward pass, every sequence from an empty state and every layer
        over every position."""
        cfg = self.cfg
        b, s = ids.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        h, m, cache = self.embed(params, ids), None, _FromNothing(cfg)
        for li in range(cfg.n_layers):
            h, m = block(cfg, params[f"layer_{li}"], li, h, positions, cache,
                         m)
        return self.logits(params, h)

    def serve_caches(self, max_batch: int, max_seq: int):
        """What ``serve.engine.InferenceEngine`` serves this model
        through (the interface is in ``serve/caches.py``): a state and a
        convolution tail a slot for the Mamba layers, a ring for each
        window layer, and one slab of every position that the full layer
        writes and the cross layers read."""
        from kungfu_tpu.serve.sambay import SambaYCaches

        return SambaYCaches(self, max_batch, max_seq)

    # -- what a forward pass costs (the serving MFU gauge) ---------------
    def _flops_parts(self):
        """(a token's products in the self-decoder, in the cross-decoder,
        a score's in one reading layer, the head's)."""
        cfg = self.cfg
        d, e, n, r = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.dt_rank
        hq = cfg.n_heads * cfg.head_dim
        hkv = cfg.n_kv_heads * cfg.head_dim
        ffn = 6 * d * cfg.d_ff
        # projections, the convolution, and a channel's decay, update and
        # read-out over its N numbers
        mamba = (2 * d * 2 * e + 2 * cfg.d_conv * e + 2 * e * (r + 2 * n)
                 + 2 * r * e + 7 * n * e + 2 * e * d)
        self_attn = 2 * d * (hq + 2 * hkv) + 2 * hq * d
        n_self = len(cfg.window_layers) + 1
        lower = (len(cfg.recurrent_layers) * mamba + n_self * self_attn
                 + (cfg.full_layer + 1) * ffn)
        n_cross = len(cfg.cross_layers)
        upper = (n_cross * 4 * d * hq + len(cfg.layers_of("gmu")) * 4 * d * e
                 + (cfg.n_layers - cfg.full_layer - 1) * ffn)
        score = 4 * cfg.n_heads * cfg.head_dim  # one query row, one key
        return int(lower), int(upper), score, 2 * d * cfg.vocab_size

    def prefill_flops(self, tokens: int, start: int = 0) -> int:
        """The self-decoder's products over ``tokens`` new rows (the
        recurrence counted token by token), its attention into the
        window and into the context; the cross-decoder, its seven reads
        of the context and the logits for ONE row."""
        cfg = self.cfg
        lower, upper, score, head = self._parts
        seen = tokens * start + tokens * (tokens + 1) // 2
        # (a window layer's row sees at most ``window`` keys)
        near = int(np.minimum(start + np.arange(1, tokens + 1),
                              cfg.window).sum())
        return (tokens * lower + score * (seen + len(cfg.window_layers) * near)
                + upper + score * len(cfg.cross_layers) * (start + tokens)
                + head)

    def decode_flops(self, contexts) -> int:
        """One decode position of each of the sequences whose contexts
        (keys attended, its own included) are given, summed."""
        cfg = self.cfg
        lower, upper, score, head = self._parts
        contexts = np.asarray(contexts)
        full = (1 + len(cfg.cross_layers)) * contexts.sum()
        near = len(cfg.window_layers) * np.minimum(contexts, cfg.window).sum()
        return int(contexts.size * (lower + upper + head)
                   + score * (full + near))
