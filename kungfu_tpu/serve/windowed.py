"""Two caches for a model that mixes window and full attention
(``models/cohere2_moe.py``): the engine's K and V are each a pair of
slabs,

* ``window`` ``[Lw, B, G, W, D]`` -- one ring of ``W = min(window,
  max_seq)`` rows a slot for every window layer: position ``p`` lives at
  row ``p % W``, stored already rotated, so a row needs no position to
  be read; a row is valid once written for this request, which the mask
  works out from the slot's position alone;
* ``full`` ``[Lf, B, G, max_seq, D]`` -- every position of every full
  layer, as ``caches.DenseCaches`` keeps all of its layers,

with ``G`` the key/value heads (not the query heads).  A slot of 8192
positions then costs a window layer 4096 rows, however long its context.

:class:`WindowedCaches` is what ``InferenceEngine`` asks of such a model
(the interface of ``serve/caches.py``, where the dense ``Transformer``'s
answer is): the slabs, the prefill and decode bodies (both drive the
model's ONE ``block`` with a cache that writes the layer's new rows into
its slab and attends over what the layer may see), and the host's side
of a page: which rows of a finished request still exist, and where a
cached prefix's rows go.  Every write is in place and donated like the
dense model's: a decode step writes one row a slot through the
aligned-window read-select-write of ``caches.write_rows``, a prefill one
aligned block.

Pages (docs/serving.md): a page holds ``page_tokens`` positions of EVERY
layer.  When a request completes, the rings hold only the window layers'
last ``W`` positions, so an earlier page is committed without them (not
``whole``): it still serves the full layers of a longer prefix, but a
prefix of ``n`` positions can be reused only if the pages covering
``[n - W, n)`` are whole -- ``KVCachePool.reusable``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from kungfu_tpu.models import cohere2_moe as arch
from kungfu_tpu.serve.caches import (kv_rows, layer_slot, put_rows,
                                     row_windows, write_rows)


class WindowedCaches:
    def __init__(self, model: arch.Cohere2Moe, max_batch: int, max_seq: int):
        self.model = model
        cfg = self.cfg = model.cfg
        self.batch, self.seq = int(max_batch), int(max_seq)
        self.ring = min(cfg.window, self.seq)
        #: a layer's place in its slab
        self.place = {li: i for group in (cfg.window_layers, cfg.full_layers)
                      for i, li in enumerate(group)}
        #: the experts a decode step's routing is counted over
        self.held = cfg.n_layers * cfg.experts_held[1]
        self.prefill_flops = model.prefill_flops
        self.decode_flops = model.decode_flops

    # -- the slabs -------------------------------------------------------
    def shapes(self):
        cfg = self.cfg
        tail = (cfg.n_kv_heads,)
        return ((len(cfg.window_layers), self.batch) + tail
                + (self.ring, cfg.head_dim),
                (len(cfg.full_layers), self.batch) + tail
                + (self.seq, cfg.head_dim))

    def new_slabs(self):
        dt = self.cfg.compute_dtype
        return tuple(tuple(jnp.zeros(s, dt) for s in self.shapes())
                     for _ in "kv")

    # -- the two forward passes ------------------------------------------
    def decode(self, params, k, v, last_ids, pos, live):
        """One token for every slot (``last_ids``/``pos``/``live``
        ``[B]``; a slot that is not live computes what nobody reads,
        writes no row -- a ring holds exactly ``W``, so a row too many
        would overwrite one that is yet to be committed -- and is
        counted nowhere).  Returns the slabs and ONE int32 vector: the
        ``B`` tokens, then the step's routing over the live slots and all
        layers -- held experts that received a token, the busiest
        expert's tokens, the tokens received in all -- so that the host's
        one read brings both."""
        cfg, model, ring = self.cfg, self.model, self.ring
        (kw, kf), (vw, vf) = k, v
        rows = jnp.arange(ring)
        # ring row r holds the last position <= pos that lands on it
        see_w = (pos[:, None] - (pos[:, None] - rows) % ring >= 0
                 )[:, None, None, None]
        see_f = (jnp.arange(self.seq) <= pos[:, None])[:, None, None, None]
        at_w = row_windows(pos % ring, ring, live)
        at_f = row_windows(pos, self.seq, live)

        class Step:
            """A decode step's cache: one row a slot into each slab,
            attention over the slab itself."""

            def write(_, li, kn, vn):
                nonlocal kw, vw, kf, vf
                i = self.place[li]
                with jax.named_scope("kv_write"):
                    if cfg.is_window(li):
                        kw = write_rows(kw, i, kn, at_w)
                        vw = write_rows(vw, i, vn, at_w)
                    else:
                        kf = write_rows(kf, i, kn, at_f)
                        vf = write_rows(vf, i, vn, at_f)

            @jax.named_scope("attn_core")
            def attend(_, li, q, positions):
                i = self.place[li]
                if cfg.is_window(li):
                    with jax.named_scope("attn_window"):
                        return arch.attention(q, kw[i], vw[i], see_w)
                with jax.named_scope("attn_full"):
                    return arch.attention(q, kf[i], vf[i], see_f)

        h = model.embed(params, last_ids[:, None])
        counts = []
        for li in range(cfg.n_layers):
            h, c = arch.block(cfg, params[f"layer_{li}"], li, h,
                              pos[:, None], Step(), dense=True,
                              live=live[:, None])
            counts.append(c)
        tok = jnp.argmax(model.logits(params, h[:, 0]), axis=-1)
        with jax.named_scope("moe_router"):
            counts = jnp.stack(counts)
            routing = jnp.stack([jnp.sum(counts > 0), jnp.max(counts),
                                 jnp.sum(counts)])
        return ((kw, kf), (vw, vf),
                jnp.concatenate([tok, routing]).astype(jnp.int32))

    def new_out(self):
        return jnp.zeros(self.batch + 3, jnp.int32)

    def read(self, out, contexts):
        """A decode step's ``out`` on the host: the slots' tokens, and
        as attrs of the span that waits for them (docs/tracing.md) its
        routing, counted by the step, and the K/V rows its ``contexts``
        had to read of the rings and the full slabs beside the rows it
        did read, all of them."""
        out = np.asarray(jax.device_get(out))
        touched, load_max, assigned = out[self.batch:].tolist()
        return out[:self.batch], dict(
            kv_rows(contexts, self.shapes(), self.cfg.compute_dtype),
            experts_touched=touched, experts_held=self.held,
            expert_load_max=load_max,
            expert_load_mean=assigned / self.held)

    def prefill(self, params, k, v, ids, n, start, slot):
        """``ids`` ``[P]`` (the prompt past ``start`` cached positions,
        zero-padded past ``n``) into ``slot``: each full layer's rows go
        to ``[start, start + P)`` of its slab (the padding lands where
        the decode writes before anyone reads); each window layer's ring
        ends up holding the last ``W`` REAL positions, whichever of them
        are new.  Attention is by blocks of query rows over the keys each
        may see (``blocked_attention``), so no ``[heads, P, S]`` scores
        exist.  Returns the greedy token after row ``n - 1``."""
        cfg, model, ring = self.cfg, self.model, self.ring
        (kw, kf), (vw, vf) = k, v
        p_len = ids.shape[0]
        positions = start + jnp.arange(p_len)
        last = start + n - 1
        rows = jnp.arange(ring)
        # the ring before: position start - W + i sits at row (start + i) % W
        unroll = (start + rows) % ring
        # the ring after: row r holds the last real position landing on it,
        # a new one where that is not before ``start``
        held = last - (last - rows) % ring
        fresh = (held >= start)[:, None]
        source = jnp.clip(held - start, 0, p_len - 1)

        class Prompt:
            """A prefill's cache.  A full layer's new rows go into the
            slab and the slot's rows come out as its keys; a window
            layer's keys are the ring before (in position order) and the
            new rows, and the ring is then written."""

            def write(me, li, kn, vn):
                nonlocal kw, vw, kf, vf
                i = self.place[li]
                if not cfg.is_window(li):
                    with jax.named_scope("kv_write"):
                        kf = put_rows(kf, kn, (i, slot, 0, start, 0))
                        vf = put_rows(vf, vn, (i, slot, 0, start, 0))
                    me.keys = (layer_slot(kf, i, slot),
                               layer_slot(vf, i, slot), 0, None)
                    return
                # (the barrier: the slot's old rows are taken out before
                # the ring is written, or the compiler, reading them where
                # they are used, copies the whole slab to keep them)
                old_k, old_v = jax.lax.optimization_barrier(
                    (layer_slot(kw, i, slot), layer_slot(vw, i, slot)))
                me.keys = (jnp.concatenate([old_k[:, :, unroll], kn], axis=2),
                           jnp.concatenate([old_v[:, :, unroll], vn], axis=2),
                           start - ring, cfg.window)
                with jax.named_scope("kv_write"):
                    kw = put_rows(
                        kw, jnp.where(fresh, kn[:, :, source], old_k),
                        (i, slot, 0, 0, 0))
                    vw = put_rows(
                        vw, jnp.where(fresh, vn[:, :, source], old_v),
                        (i, slot, 0, 0, 0))

            @jax.named_scope("attn_core")
            def attend(me, li, q, positions):
                k, v, first, window = me.keys
                with jax.named_scope("attn_window" if window else "attn_full"):
                    return arch.blocked_attention(q, k, v, positions[0],
                                                  first, window)

        h = model.embed(params, ids[None])
        for li in range(cfg.n_layers):
            h, _ = arch.block(cfg, params[f"layer_{li}"], li, h,
                              positions[None], Prompt(), dense=False)
        row = jax.lax.dynamic_index_in_dim(h, n - 1, axis=1, keepdims=False)
        tok = jnp.argmax(model.logits(params, row)[0], axis=-1)
        return (kw, kf), (vw, vf), tok.astype(jnp.int32)

    # -- the host's side of a page ---------------------------------------
    def _empty_part(self, rows: int):
        (lw, _, g, w, d), (lf, _, _, _, _) = self.shapes()
        dt = self.cfg.compute_dtype
        return (np.zeros((lw, g, w, d), dt), np.zeros((lf, g, rows, d), dt))

    def empty_pages(self, rows: int):
        """What :meth:`pages_to_slot` gives, for K and for V, for
        ``rows`` cached positions that hold nothing (the engine's
        warm-up)."""
        part = self._empty_part(rows)
        return part, part

    def pages_to_slot(self, data, n_cached: int, rows: int, page_tokens: int):
        """The K (or V) of a cached prefix's pages, ``[L, G, T, D]`` each
        in order, as what the restore program writes into a slot: the
        window layers' last ``W`` positions at their ring rows, the full
        layers' ``n_cached`` positions padded to ``rows``."""
        ring, out_f = self._empty_part(rows)
        w, t = self.ring, page_tokens
        wl, fl = list(self.cfg.window_layers), list(self.cfg.full_layers)
        for i, page in enumerate(data):
            out_f[:, :, i * t:(i + 1) * t] = page[fl]
            if (i + 1) * t > n_cached - w:
                ring[:, :, np.arange(i * t, (i + 1) * t) % w] = page[wl]
        return ring, out_f

    def rows_of_slot(self, slab, slot: int, lo: int, hi: int, total: int):
        """Positions ``[lo, hi)`` of a finished request's K (or V) as
        page data ``[L, G, hi - lo, D]``, and the first position whose
        window layers' rows still exist: rows were made for positions
        ``[0, total - 1)``, and the rings keep the last ``W`` of them
        (earlier ones read zero here, and their pages are not whole)."""
        cfg, w = self.cfg, self.ring
        ring, full = slab
        ring = np.asarray(jax.device_get(ring[:, slot]))
        full = np.asarray(jax.device_get(full[:, slot, :, lo:hi]))
        out = np.zeros((cfg.n_layers,) + full.shape[1:], full.dtype)
        out[list(cfg.full_layers)] = full
        kept_from = max(0, total - 1 - w)
        at = np.arange(max(lo, kept_from), hi)
        out[np.ix_(list(cfg.window_layers), range(full.shape[1]),
                   at - lo)] = ring[:, :, at % w]
        return out, kept_from
