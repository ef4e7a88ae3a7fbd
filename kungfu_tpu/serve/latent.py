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
position of every layer (``caches.FullRows`` of two widths, for their
shapes and zeros), so a page is always whole and the host's side of one
is the body's, a part at a time.

:class:`LatentCaches` is what ``InferenceEngine`` asks of such a model
(``serve/caches.py``).  Its rows are no per-head keys, so how they are
written and read is its own; what differs between the two passes is the
order the attention is computed in:

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

from kungfu_tpu.models import pangu_moe as arch
from kungfu_tpu.serve.caches import (ROUTING, Caches, FullRows, of_slot,
                                     routing_attrs, routing_says,
                                     row_windows, visible, write_rows)


class _Pass:
    def __init__(self, c, k_r):
        self.c, self.k_r = c, k_r

    def parts(self):
        return self.c, self.k_r


class _Step(_Pass):
    """A decode step's cache: one row a slot into the slab, absorbed
    attention over the slab itself."""

    def __init__(self, caches, c, k_r, pos, live):
        super().__init__(c, k_r)
        self.scale = caches.cfg.score_scale
        self.at = row_windows(pos, caches.seq, live)
        self.visible = visible(pos, live)
        tile = caches.attn_tile()
        if tile:
            from kungfu_tpu.ops.pallas.latent_attention import rows_walked

            self.walked = rows_walked(self.visible, tile)
        else:       # XLA's form reads every row of every slot under a mask
            self.walked = caches.batch * caches.seq

    def write(self, li, ap, c_new, r_new):
        with jax.named_scope("kv_write"):
            self.c = write_rows(self.c, li, c_new[:, None], self.at)
            self.k_r = write_rows(self.k_r, li, r_new[:, None], self.at)

    def attend(self, li, ap, q_nope, q_rope, positions):
        return arch.absorbed_attention(
            ap, q_nope[:, 0], q_rope[:, 0], self.c, self.k_r, li,
            self.visible, self.scale)[:, None]

    def says(self, counts):
        """Its routing over the live slots and the expert layers, and
        the latent rows of live contexts beside those its attention
        read, a layer."""
        with jax.named_scope("moe_router"):
            return jnp.stack(routing_says(counts) + [
                jnp.sum(self.visible), self.walked])


class _Prompt(_Pass):
    """A prefill's cache: the new rows go into the slab, and the slot's
    rows come out expanded."""

    def __init__(self, caches, c, k_r, start, slot):
        super().__init__(c, k_r)
        self.scale, self.start, self.slot = (caches.cfg.score_scale, start,
                                             slot)

    def write(self, li, ap, c_new, r_new):
        slot, at = self.slot, (li, self.slot, 0, self.start, 0)
        with jax.named_scope("kv_write"):
            self.c = jax.lax.dynamic_update_slice(
                self.c, c_new[None, None], at, allow_negative_indices=False)
            self.k_r = jax.lax.dynamic_update_slice(
                self.k_r, r_new[None, None], at, allow_negative_indices=False)
        with jax.named_scope("attn_proj"), jax.named_scope("mla_proj"):
            self.keys = arch.expand(ap, of_slot(self.c, li, slot, 3)) + (
                of_slot(self.k_r, li, slot, 3),)

    @jax.named_scope("attn_core")
    def attend(self, li, ap, q_nope, q_rope, positions):
        k_nope, v, rope = self.keys
        return arch.expanded_attention(
            q_nope[0], q_rope[0], k_nope, rope, v, positions[0],
            self.scale)[None]


class LatentCaches(Caches):
    says = ROUTING + ("latent_rows_live", "latent_rows_walked")
    block = staticmethod(arch.block)

    def __init__(self, model: arch.PanguMoe, max_batch: int, max_seq: int):
        super().__init__(model, max_batch, max_seq)
        cfg = self.cfg
        self.stores = (FullRows(
            cfg.n_layers, self.batch, 1, self.seq,
            (cfg.kv_lora_rank, cfg.qk_rope_dim), cfg.compute_dtype),)
        #: the experts a decode step's routing is counted over
        self.held = len(cfg.expert_layers) * cfg.experts_held[1]

    def shapes(self):
        return self.stores[0].shapes

    def step(self, c, k_r, pos, live):
        return _Step(self, c, k_r, pos, live)

    def prompt(self, c, k_r, p_len, n, start, slot):
        return _Prompt(self, c, k_r, start, slot)

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

    def attrs(self, says, contexts):
        """A latent row is no K/V row: this cache states
        ``latent_rows_*`` and no ``kv_*``, and its step counts both
        itself: the live rows (the sum of ``contexts``, which is not
        needed here) and the rows its attention read -- the tiles the
        kernel walked, or every row where XLA's form ran."""
        says["latent_rows_read"] = says.pop("latent_rows_walked")
        says["latent_attn_kernel"] = self.latent_attn_kernel
        return routing_attrs(says, self.held)
