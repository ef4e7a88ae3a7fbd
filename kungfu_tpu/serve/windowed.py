"""Two stores for a model that mixes window and full attention
(``models/cohere2_moe.py``): the engine's K and V are each a pair of
slabs,

* ``window`` ``[Lw, B, G, W, D]`` -- a ring of ``W = min(window,
  max_seq)`` rows a slot for every window layer (``caches.RingRows``),
* ``full`` ``[Lf, B, G, max_seq, D]`` -- every position of every full
  layer (``caches.FullRows``),

with ``G`` the key/value heads (not the query heads).  Neither asks for a
kernel (ROADMAP S2 b): both attend under a mask on every platform.

:class:`WindowedCaches` is what ``InferenceEngine`` asks of such a model
(``serve/caches.py``): it says which layer keeps its rows where, counts
the step's routing, and joins the two stores' halves of a page.

Pages (docs/serving.md): a page holds ``page_tokens`` positions of EVERY
layer.  When a request completes, the rings hold only the window layers'
last ``W`` positions, so an earlier page is committed without them (not
``whole``): it still serves the full layers of a longer prefix, but a
prefix of ``n`` positions can be reused only if the pages covering
``[n - W, n)`` are whole -- ``KVCachePool.reusable``.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from kungfu_tpu.models import cohere2_moe as arch
from kungfu_tpu.serve.caches import (ROUTING, Caches, FullRows, Pass,
                                     RingRows, kv_rows, routing_attrs,
                                     routing_says)


class _Step(Pass):
    @staticmethod
    def says(counts):
        return jnp.stack(routing_says(counts))


class WindowedCaches(Caches):
    says = ROUTING
    block = staticmethod(arch.block)

    def __init__(self, model: arch.Cohere2Moe, max_batch: int, max_seq: int):
        super().__init__(model, max_batch, max_seq)
        cfg = self.cfg
        row = (cfg.head_dim, cfg.compute_dtype)
        self.rings = RingRows(len(cfg.window_layers), self.batch,
                              cfg.n_kv_heads, cfg.window, self.seq, *row)
        self.full = FullRows(len(cfg.full_layers), self.batch,
                             cfg.n_kv_heads, self.seq, *row)
        self.stores = (self.rings, self.full)
        #: a layer's place in its slab
        self.at = self.place(cfg.window_layers, cfg.full_layers)
        #: the experts a decode step's routing is counted over
        self.held = cfg.n_layers * cfg.experts_held[1]

    def shapes(self):
        """(the rings of K or of V, the full slab of K or of V)."""
        return self.rings.shapes[0], self.full.shapes[0]

    def where(self, li):
        if self.cfg.is_window(li):
            return 0, self.at[li], "attn_window"
        return 1, self.at[li], "attn_full"

    # -- the two forward passes ------------------------------------------
    def step(self, k, v, pos, live):
        (kw, kf), (vw, vf) = k, v
        rings, full = self.rings, self.full
        see_w, see_f = rings.see(pos), full.see(pos)
        return _Step(self, rings.step(kw, vw, see_w, rings.writer(pos, live)),
                     full.step(kf, vf, see_f, full.writer(pos, live)))

    def prompt(self, k, v, p_len, n, start, slot):
        (kw, kf), (vw, vf) = k, v
        return Pass(
            self, self.rings.prompt(kw, vw, p_len, start + n - 1, start, slot),
            self.full.prompt(kf, vf, start, slot))

    def attrs(self, says, contexts):
        """Its routing, counted by the step, and the K/V rows its
        ``contexts`` had to read of the rings and the full slabs beside
        the rows it did read, all of them."""
        return dict(kv_rows(contexts, self.stores),
                    **routing_attrs(says, self.held))

    # -- the host's side of a page ---------------------------------------
    def pages_to_slot(self, data, n_cached: int, rows: int, page_tokens: int):
        """The K (or V) of a cached prefix's pages, ``[L, G, T, D]`` each
        in order, as what the restore program writes into a slot: the
        window layers' last ``W`` positions at their ring rows, the full
        layers' ``n_cached`` positions padded to ``rows``."""
        wl, fl = list(self.cfg.window_layers), list(self.cfg.full_layers)
        return (self.rings.pages_to_slot([page[wl] for page in data],
                                         n_cached, page_tokens),
                self.full.pages_to_slot([page[fl] for page in data], rows,
                                        page_tokens))

    def rows_of_slot(self, slab, slot: int, lo: int, hi: int, total: int):
        """Positions ``[lo, hi)`` of a finished request's K (or V) as
        page data ``[L, G, hi - lo, D]``, and the first position whose
        window layers' rows still exist."""
        cfg = self.cfg
        ring, kept_from = self.rings.rows_of_slot(slab[0], slot, lo, hi,
                                                  total)
        full = self.full.rows_of_slot(slab[1], slot, lo, hi)
        out = np.zeros((cfg.n_layers,) + full.shape[1:], full.dtype)
        out[list(cfg.window_layers)] = ring
        out[list(cfg.full_layers)] = full
        return out, kept_from
