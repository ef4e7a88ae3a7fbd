"""The cache of latent (MLA) attention (``models/pangu_moe.py``): per
position and layer ONE row shared by all the query heads, in two parts of
different width,

* ``c`` ``[L, B, 1, S, r]`` -- the compressed key/value row ``c_kv``
  after its norm (``r = kv_lora_rank``),
* ``k_r`` ``[L, B, 1, S, rope]`` -- the heads' shared rotary key, stored
  already rotated,

``r + rope`` values a position a layer (1,152 bytes at 512 + 64 in
bfloat16) where per-head keys and values would be ``heads x (nope + rope
+ v)`` (81,920 bytes at 128 heads of 192 and 128).  Both parts keep every
position of every layer, so a page is always whole and the host's side of
one is the dense model's, a part at a time.

:class:`LatentCaches` is what ``InferenceEngine`` asks of such a model
(the interface of ``serve/caches.py``).  Both bodies drive the model's
ONE ``block``; what differs is the order the attention is computed in:

* the **prefill** writes the chunk's rows and then EXPANDS the slot's
  rows -- the chunk's own, and a restored prefix's before them -- to
  per-head keys and values, once a layer, for the many query rows that
  read them (``expanded_attention``);
* the **decode** step writes one row a slot (in place, through the
  aligned-window read-select-write of ``caches.write_rows``, one loop
  for ``c`` and one for ``k_r``) and attends in the ABSORBED order over the slab
  itself (``absorbed_attention``, which is handed both parts whole and
  the layer's index): no key or value of a cached row is ever formed.
  On the TPU that is one fused kernel a layer
  (``ops/pallas/latent_attention.py``, imported when the step is traced
  and not before), elsewhere XLA's two products; ``read`` says which as
  ``latent_attn_kernel``.  The step hands either the rows each slot may
  see -- ``pos + 1`` of a live slot, none of another -- and the kernel
  walks only the key tiles those reach: no operation's SHAPE follows
  what is live, the kernel's time does (docs/serving.md), and the rows
  it walked are counted by the step and stated as ``latent_rows_read``
  (XLA's form reads every position of every slot under its mask, and
  says so).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from kungfu_tpu.models import pangu_moe as arch
from kungfu_tpu.serve.caches import (pages_in_order, row_windows, slot_rows,
                                     write_rows)

#: what a decode step's ``out`` says behind the slots' tokens
_SAYS = ("experts_touched", "expert_load_max", "assigned",
         "latent_rows_live", "latent_rows_walked")


def _slot_of(slab, li, slot):
    """Layer ``li``, slot ``slot`` of a part as ``[S, W]``, by one dynamic
    slice (taking the layer first would materialise its slots)."""
    return jax.lax.dynamic_slice(
        slab, (li, slot, 0, 0, 0), (1, 1) + slab.shape[2:],
        allow_negative_indices=False)[0, 0, 0]


class LatentCaches:
    def __init__(self, model: arch.PanguMoe, max_batch: int, max_seq: int):
        self.model = model
        cfg = self.cfg = model.cfg
        self.batch, self.seq = int(max_batch), int(max_seq)
        #: the experts a decode step's routing is counted over
        self.held = len(cfg.expert_layers) * cfg.experts_held[1]
        self.prefill_flops = model.prefill_flops
        self.decode_flops = model.decode_flops

    # -- the slab --------------------------------------------------------
    def shapes(self):
        cfg = self.cfg
        lead = (cfg.n_layers, self.batch, 1, self.seq)
        return lead + (cfg.kv_lora_rank,), lead + (cfg.qk_rope_dim,)

    def new_slabs(self):
        dt = self.cfg.compute_dtype
        return tuple(jnp.zeros(s, dt) for s in self.shapes())

    # -- the two forward passes ------------------------------------------
    def decode(self, params, c, k_r, last_ids, pos, live):
        """One token for every slot (``last_ids``/``pos``/``live``
        ``[B]``; a slot that is not live computes what nobody reads,
        writes no row and is counted nowhere).  Returns the slab and ONE
        int32 vector: the ``B`` tokens, then what the step says of itself
        (:data:`_SAYS`): its routing over the live slots and the expert
        layers, and the latent rows of live contexts beside those its
        attention read, a layer."""
        cfg, model = self.cfg, self.model
        at = row_windows(pos, self.seq, live)
        # what a slot may see: its first ``pos + 1`` rows, and none where
        # the step is not for it
        visible = jnp.where(live, pos + 1, 0)
        tile = self.attn_tile()
        if tile:
            from kungfu_tpu.ops.pallas.latent_attention import rows_walked

            walked = rows_walked(visible, tile)
        else:       # XLA's form reads every row of every slot under a mask
            walked = self.batch * self.seq

        class Step:
            """A decode step's cache: one row a slot into the slab,
            absorbed attention over the slab itself."""

            def write(_, li, ap, c_new, r_new):
                nonlocal c, k_r
                with jax.named_scope("kv_write"):
                    c = write_rows(c, li, c_new[:, None], at)
                    k_r = write_rows(k_r, li, r_new[:, None], at)

            def attend(_, li, ap, q_nope, q_rope, positions):
                return arch.absorbed_attention(
                    ap, q_nope[:, 0], q_rope[:, 0], c, k_r, li, visible,
                    cfg.score_scale)[:, None]

        h = model.embed(params, last_ids[:, None])
        counts = []
        for li in range(cfg.n_layers):
            h, n = arch.block(cfg, params[f"layer_{li}"], li, h,
                              pos[:, None], Step(), dense=True,
                              live=live[:, None])
            if n is not None:
                counts.append(n)
        tok = jnp.argmax(model.logits(params, h[:, 0]), axis=-1)
        with jax.named_scope("moe_router"):
            counts = jnp.stack(counts)
            says = jnp.stack([
                jnp.sum(counts > 0), jnp.max(counts), jnp.sum(counts),
                jnp.sum(visible), walked])
        return c, k_r, jnp.concatenate([tok, says]).astype(jnp.int32)

    def new_out(self):
        return jnp.zeros(self.batch + len(_SAYS), jnp.int32)

    def attn_tile(self):
        """The key tile of a decode step's fused attention kernel, None
        where it is XLA's two products: ``absorbed_attention``'s own
        choice, made from the platform and the slab's shape when the
        step is traced."""
        cfg = self.cfg
        return arch.absorbed_tile(
            cfg.n_heads, self.seq, cfg.kv_lora_rank, cfg.qk_rope_dim,
            cfg.compute_dtype)

    @functools.cached_property
    def latent_attn_kernel(self) -> int:
        """1 where a decode step's attention is the fused kernel, 0
        where it is XLA's two products."""
        return int(self.attn_tile() is not None)

    def read(self, out, contexts):
        """A decode step's ``out`` on the host: the slots' tokens, and
        what it says of itself as attrs of the span that waits for them
        (docs/tracing.md).  A latent row is no K/V row: this cache
        states ``latent_rows_*`` and no ``kv_*``, and its step counts
        both itself: the live rows (the sum of ``contexts``, which is
        not needed here) and the rows its attention read."""
        out = np.asarray(jax.device_get(out))
        says = dict(zip(_SAYS, out[self.batch:].tolist()))
        says["experts_held"] = self.held
        # the rows READ are the step's own count: the tiles the kernel
        # walked, or every row where XLA's form ran
        says["latent_rows_read"] = says.pop("latent_rows_walked")
        says["latent_attn_kernel"] = self.latent_attn_kernel
        says["expert_load_mean"] = says.pop("assigned") / self.held
        return out[:self.batch], says

    def prefill(self, params, c, k_r, ids, n, start, slot):
        """``ids`` ``[P]`` (the prompt past ``start`` cached positions,
        zero-padded past ``n``) into ``slot``: every layer's rows go to
        ``[start, start + P)`` of the slab (the padding lands where the
        decode writes before anyone reads), and the queries attend, tile
        by tile, over the slot's rows expanded to keys and values.
        Returns the greedy token after row ``n - 1``."""
        cfg, model = self.cfg, self.model
        positions = start + jnp.arange(ids.shape[0])

        class Prompt:
            """A prefill's cache: the new rows go into the slab, and the
            slot's rows come out expanded."""

            def write(me, li, ap, c_new, r_new):
                nonlocal c, k_r
                with jax.named_scope("kv_write"):
                    at = (li, slot, 0, start, 0)
                    c = jax.lax.dynamic_update_slice(
                        c, c_new[None, None], at,
                        allow_negative_indices=False)
                    k_r = jax.lax.dynamic_update_slice(
                        k_r, r_new[None, None], at,
                        allow_negative_indices=False)
                with jax.named_scope("attn_proj"), \
                        jax.named_scope("mla_proj"):
                    me.keys = arch.expand(ap, _slot_of(c, li, slot)) + (
                        _slot_of(k_r, li, slot),)

            @jax.named_scope("attn_core")
            def attend(me, li, ap, q_nope, q_rope, positions):
                k_nope, v, rope = me.keys
                return arch.expanded_attention(
                    q_nope[0], q_rope[0], k_nope, rope, v, positions[0],
                    cfg.score_scale)[None]

        h = model.embed(params, ids[None])
        for li in range(cfg.n_layers):
            h, _ = arch.block(cfg, params[f"layer_{li}"], li, h,
                              positions[None], Prompt(), dense=False)
        row = jax.lax.dynamic_index_in_dim(h, n - 1, axis=1, keepdims=False)
        tok = jnp.argmax(model.logits(params, row)[0], axis=-1)
        return c, k_r, tok.astype(jnp.int32)

    # -- the host's side of a page ---------------------------------------
    def empty_pages(self, rows: int):
        dt = self.cfg.compute_dtype
        return tuple(np.zeros((s[0], 1, rows, s[-1]), dt)
                     for s in self.shapes())

    @staticmethod
    def pages_to_slot(data, n_cached: int, rows: int, page_tokens: int):
        return pages_in_order(data, rows, page_tokens)

    @staticmethod
    def rows_of_slot(slab, slot: int, lo: int, hi: int, total: int):
        return slot_rows(slab, slot, lo, hi), 0
