"""GPT/BERT-style transformer — the flagship model.

Fresh TPU-first design (the reference has no model code; its BERT appears
only as a gradient-size list, ``model_sizes.py``):

* pre-LN blocks, RoPE or learned positions, bf16 activations / f32 params;
* attention is pluggable: the default is plain softmax attention (XLA fuses
  it well at moderate sequence lengths); :mod:`kungfu_tpu.parallel` plugs
  in ring attention (sequence-parallel over the mesh) or the Pallas flash
  kernel for long context;
* shapes are MXU-friendly (`d_model`, `d_ff` multiples of 128) and all
  control flow is static — one trace, one compile;
* every layer's operations carry a ``jax.named_scope`` from one
  vocabulary (``embed``, ``norm``, ``attn_proj``, ``attn_core``, ``mlp``,
  ``head``; docs/tracing.md), so a device trace says which layer an
  operation belongs to.  Scopes are metadata: the compiled program is
  the same.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from kungfu_tpu.models import nn


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32128
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_seq: int = 2048
    dropout: float = 0.0
    causal: bool = True
    pos: str = "rope"  # "rope" | "learned"
    dtype: str = "bfloat16"

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


def _rope(q, k, positions):
    """Rotary position embedding on the head dim."""
    d = q.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (jnp.log(10000.0) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)

    def rot(x):
        # x: [B, H, S, D]; cos/sin: [B, S, half] -> broadcast over heads
        x1, x2 = x[..., :half], x[..., half:]
        c = cos[:, None, :, :].astype(x.dtype)
        s = sin[:, None, :, :].astype(x.dtype)
        return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)

    return rot(q), rot(k)


def pick_attention() -> Callable:
    """Attention impl for the current backend (``KF_TPU_ATTN`` overrides:
    ``auto`` | ``xla`` | ``flash``).  ``auto`` uses the Pallas flash
    kernel on TPU — fused online softmax, no [S, S] score matrix in HBM —
    and plain XLA attention elsewhere (the interpreter-mode kernel is for
    tests, far too slow as a CPU default)."""
    import os

    mode = os.environ.get("KF_TPU_ATTN", "auto").lower()
    if mode == "xla":
        return default_attention
    if mode == "flash" or (mode == "auto" and jax.default_backend() == "tpu"):
        from kungfu_tpu.ops.pallas import make_flash_attn

        return make_flash_attn()
    return default_attention


def default_attention(q, k, v, causal: bool, segment_positions=None):
    """Plain softmax attention.  q,k,v: [B, H, S, D] (bf16).  Logits and
    softmax in f32 for stability; output back in input dtype."""
    d = q.shape[-1]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(d)
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        q_pos = jnp.arange(s_q)[:, None]
        k_pos = jnp.arange(s_k)[None, :]
        if segment_positions is not None:
            q_pos = q_pos + segment_positions[0]
            k_pos = k_pos + segment_positions[1]
        logits = jnp.where(q_pos >= k_pos, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


class Transformer:
    def __init__(self, config: TransformerConfig):
        self.cfg = config

    # -- init ------------------------------------------------------------
    def init(self, key) -> dict:
        cfg = self.cfg
        params = {}
        key, k1, k2 = jax.random.split(key, 3)
        params["embed"] = nn.embedding_init(k1, cfg.vocab_size, cfg.d_model)
        if cfg.pos == "learned":
            params["pos_embed"] = nn.embedding_init(k2, cfg.max_seq, cfg.d_model)
        for i in range(cfg.n_layers):
            key, *ks = jax.random.split(key, 7)
            params[f"layer_{i}"] = {
                "ln1": nn.layernorm_init(cfg.d_model),
                "wq": nn.dense_init(ks[0], cfg.d_model, cfg.d_model),
                "wk": nn.dense_init(ks[1], cfg.d_model, cfg.d_model),
                "wv": nn.dense_init(ks[2], cfg.d_model, cfg.d_model),
                "wo": nn.dense_init(ks[3], cfg.d_model, cfg.d_model),
                "ln2": nn.layernorm_init(cfg.d_model),
                "ffn_in": nn.dense_init(ks[4], cfg.d_model, cfg.d_ff),
                "ffn_out": nn.dense_init(ks[5], cfg.d_ff, cfg.d_model),
            }
        params["ln_f"] = nn.layernorm_init(cfg.d_model)
        key, k = jax.random.split(key)
        params["head"] = nn.dense_init(k, cfg.d_model, cfg.vocab_size, use_bias=False)
        return params

    # -- apply -----------------------------------------------------------
    def apply(
        self,
        params,
        ids,
        train: bool = False,
        rng=None,
        attn_fn: Optional[Callable] = None,
        positions=None,
    ):
        """ids: [B, S] int32 → logits [B, S, vocab] f32.

        ``attn_fn(q, k, v, causal)`` overrides attention (ring attention /
        flash kernel); ``positions`` overrides token positions (sequence
        parallelism passes the global positions of the local shard)."""
        h = self.hidden(params, ids, train=train, rng=rng, attn_fn=attn_fn,
                        positions=positions)
        with jax.named_scope("head"):
            return nn.dense_apply(params["head"], h).astype(jnp.float32)

    def hidden(self, params, ids, train: bool = False, rng=None,
               attn_fn: Optional[Callable] = None, positions=None):
        """Features after the final norm, BEFORE the LM head — the input
        the fused LM-head kernel (:func:`kungfu_tpu.ops.pallas.lm_head.
        lm_head_nll`) consumes together with ``params["head"]["w"]``, so
        the [*, vocab] logits never materialize."""
        cfg = self.cfg
        dt = cfg.compute_dtype
        attn = attn_fn or pick_attention()
        B, S = ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        with jax.named_scope("embed"):
            h = nn.embedding_apply(params["embed"], ids, dtype=dt)
            if cfg.pos == "learned":
                h = h + nn.embedding_apply(params["pos_embed"], positions,
                                           dtype=dt)
        for i in range(cfg.n_layers):
            lp = params[f"layer_{i}"]
            x = nn.layernorm_apply(lp["ln1"], h)
            with jax.named_scope("attn_proj"):
                q = self._heads(nn.dense_apply(lp["wq"], x, dtype=dt))
                k = self._heads(nn.dense_apply(lp["wk"], x, dtype=dt))
                v = self._heads(nn.dense_apply(lp["wv"], x, dtype=dt))
                if cfg.pos == "rope":
                    q, k = _rope(q, k, positions)
            with jax.named_scope("attn_core"):
                o = attn(q, k, v, cfg.causal)
            with jax.named_scope("attn_proj"):
                o = self._merge(o)
                h = h + nn.dense_apply(lp["wo"], o, dtype=dt)
            x = nn.layernorm_apply(lp["ln2"], h)
            with jax.named_scope("mlp"):
                y = nn.gelu(nn.dense_apply(lp["ffn_in"], x, dtype=dt))
                if train and cfg.dropout > 0 and rng is not None:
                    rng, sub = jax.random.split(rng)
                    y = nn.dropout(sub, y, cfg.dropout, train)
                h = h + nn.dense_apply(lp["ffn_out"], y, dtype=dt)
        return nn.layernorm_apply(params["ln_f"], h)

    def _heads(self, x):
        B, S, _ = x.shape
        return x.reshape(B, S, self.cfg.n_heads, self.cfg.head_dim).transpose(0, 2, 1, 3)

    def _merge(self, x):
        B, H, S, D = x.shape
        return x.transpose(0, 2, 1, 3).reshape(B, S, H * D)

    def serve_caches(self, max_batch: int, max_seq: int):
        """What ``serve.engine.InferenceEngine`` serves this model
        through: its slabs, its prefill and decode bodies and the host's
        side of a page (the interface is in ``serve/caches.py``)."""
        from kungfu_tpu.serve.caches import DenseCaches

        return DenseCaches(self, max_batch, max_seq)

    def loss(self, params, batch, train: bool = True, rng=None, attn_fn=None, positions=None):
        """Next-token LM loss; batch = (ids, targets) both [B, S].

        ``KF_TPU_LM_HEAD`` (``fused`` | ``plain`` | ``auto``, default
        auto) selects the head implementation: ``fused`` computes the
        NLL straight from the pre-head features with the fused LM-head
        kernel pair (:func:`kungfu_tpu.ops.pallas.lm_head.lm_head_nll`
        — neither logits nor dlogits reach HBM); ``auto`` takes it on
        TPU exactly when the plain path's O(N·V) residual set would
        blow the same HBM budget the xent router uses (the shapes where
        XLA OOMs outright).  Otherwise the logits materialize and
        :func:`token_nll`'s own router picks the xent implementation."""
        import os

        from kungfu_tpu.ops.pallas.xent import (route_fused_lm_head,
                                                token_nll)

        ids, targets = batch
        mode = os.environ.get("KF_TPU_LM_HEAD", "auto").lower()
        if mode not in ("fused", "plain", "auto"):
            raise ValueError(
                f"KF_TPU_LM_HEAD={mode!r}: one of fused | plain | auto")
        fused_head = mode == "fused"
        if mode == "auto" and train and jax.default_backend() == "tpu":
            fused_head = route_fused_lm_head(ids.size, self.cfg.vocab_size)
        if fused_head:
            from kungfu_tpu.ops.pallas.lm_head import lm_head_nll

            h = self.hidden(params, ids, train=train, rng=rng,
                            attn_fn=attn_fn, positions=positions)
            with jax.named_scope("head"):
                return jnp.mean(lm_head_nll(h, params["head"]["w"], targets))
        logits = self.apply(params, ids, train=train, rng=rng, attn_fn=attn_fn, positions=positions)
        # train also steers the xent router: eval-only calls take the
        # fwd-only crossover (the kernel wins much earlier without a
        # backward to fuse)
        with jax.named_scope("head"):
            return token_nll(logits, targets, training=train)


def bert_base() -> Transformer:
    """BERT-base sized (the reference's benchmark size list model)."""
    return Transformer(
        TransformerConfig(
            vocab_size=30528, d_model=768, n_layers=12, n_heads=12, d_ff=3072,
            causal=False, pos="learned", max_seq=512,
        )
    )


def gpt_small(vocab: int = 32128, max_seq: int = 2048) -> Transformer:
    return Transformer(
        TransformerConfig(
            vocab_size=vocab, d_model=768, n_layers=12, n_heads=12, d_ff=3072,
            causal=True, pos="rope", max_seq=max_seq,
        )
    )
