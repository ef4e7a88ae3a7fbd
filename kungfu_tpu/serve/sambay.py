"""Three kinds of content in one slot, for a model whose layers are of
five kinds (``models/phi4flash.py``: SambaY).  The engine's two parts
are each a triple,

* ``k`` = (``ring_k`` ``[Lw, B, G', W, W']``, ``full_k`` ``[1, B, G',
  max_seq, W']``, ``state``: ``Lm`` arrays ``[1, B, N, E]`` float32),
* ``v`` = (``ring_v``, ``full_v``, ``tails``: ``Lm`` arrays ``[1, B,
  taps - 1, E]``),

``ring`` the ``Lw`` window layers' last ``W = min(window, max_seq)``
positions (position ``p`` at row ``p % W``, as ``serve/windowed.py``
keeps them), ``full`` every position of the ONE full-attention layer,
``state`` and ``tails`` what the ``Lm`` Mamba layers keep of a request
however long it is (a state a slot: ``serve/caches.py``; an array a
layer, for the reason ``serve/recurrent.py`` gives).  A row is ``G'``
PAIRS of key (or value) heads, ``W'`` = two heads wide: the layout in
which differential attention is ordinary grouped attention
(``models/phi4flash.py``).  The layers of the cross-decoder keep
NOTHING: a GMU reads the last Mamba layer's output of its own position,
a cross layer attends over ``full`` -- **rows kept once and read by
several layers** (one self and ``Lc`` cross reads a step), which is why
every count of ``read`` is A READING LAYER's and not a keeping one's.

:class:`SambaYCaches` is what ``InferenceEngine`` asks of such a model.
Both bodies drive the model's ONE ``block``:

* the **prefill** has two halves of unequal length.  The self-decoder
  (Mamba, window and full layers) runs over the whole bucket: rings and
  slab are written as ``serve/windowed.py`` writes its own, convolution
  and recurrence go on from the slot's tails and state where ``start >
  0`` and from nothing where it is 0 -- that is how a reused slot
  forgets the request before -- in the chunked form, the padding past
  ``n`` masked so that what is written back is the state of exactly
  ``n`` tokens.  The cross-decoder runs over row ``n - 1`` ALONE:
  nothing in it is kept for a later position, and the engine wants the
  last row's token only.  That is the architecture's own prefill and
  exact;
* the **decode** step writes one row a slot into each ring and the slab
  -- on the TPU one kernel a KEEPING layer, K and V together
  (``ops/pallas/row_write.py``: nine calls where ``caches.write_rows``'
  window updates, one a slot and layer, were 1,152 operations of a
  step's 3,900 and some 1,300 scalar ones beside them) -- attends -- one
  kernel a READING layer, ``ops/pallas/decode_attention.py``, over the
  ring's first ``min(pos + 1, W)`` rows or the slab's first ``pos + 1``
  (0 where the step is not ``live`` for the slot), each K/V row read
  once a call; off the TPU, and at the tiny sizes' heads,
  ``caches.write_rows`` and ``cohere2_moe.attention`` over every row
  under a mask -- and moves every slot's state in place
  (``selective_scan.step``: one elementwise pass that also takes the
  read-out); a slot the step is not live for keeps state, tail and
  rows.  Every slot is processed every step and no operation's shape
  follows what is live.

Pages: a Mamba layer keeps nothing at a page's end that a later request
could start from, so no page of this family is ever ``whole``
(``PageSpec.unpaged``, from ``cfg.recurrent_layers``): the engine looks
up no prefix, commits nothing, and never asks this cache for
``rows_of_slot`` or ``pages_to_slot``.  ``PageSpec.for_model`` counts
the 23 layers that are not recurrent where 9 keep rows; an unpaged
spec's ``n_layers`` is read by nothing, so it is left so.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from kungfu_tpu.models import cohere2_moe, phi4flash as arch
from kungfu_tpu.ops import selective_scan
from kungfu_tpu.ops.delta_rule import causal_conv
from kungfu_tpu.serve.caches import (kv_rows, layer_slot, of_slot, put_rows,
                                     row_windows, to_slot, write_rows)

F32 = jnp.float32
#: of the Mamba layers' states (the convolution tails are in the compute
#: dtype).  Both bodies compute in float32 and store in whatever the
#: state they are handed is
STATE_DTYPE = jnp.dtype("float32")
#: rows of the aligned window that ``caches.write_rows``, a decode step's
#: row write off the TPU, reads and writes back: a pair's 128 values lie
#: along the lanes, so a slab's rows are its tiles' sublanes, 16 bfloat16
#: rows a tile (``serve/pooled.py`` measured what the default of 128
#: costs there; on the TPU ``ops/pallas/row_write.py`` takes the same)
ROW_WINDOW = 16
#: what a decode step's ``out`` says behind the slots' tokens
_SAYS = ("state_slots_live", "kv_rows_walked")


class SambaYCaches:
    def __init__(self, model: arch.Phi4Flash, max_batch: int, max_seq: int):
        self.model = model
        cfg = self.cfg = model.cfg
        self.batch, self.seq = int(max_batch), int(max_seq)
        self.ring = min(cfg.window, self.seq)
        self.scale = 1.0 / math.sqrt(cfg.head_dim)
        #: a layer's place in its kind's parts
        self.place = {li: i for group in (cfg.window_layers,
                                          cfg.recurrent_layers)
                      for i, li in enumerate(group)}
        #: the layers that read the full slab: its own and the cross ones
        self.full_readers = 1 + len(cfg.cross_layers)
        self.prefill_flops = model.prefill_flops
        self.decode_flops = model.decode_flops
        #: what the Mamba layers keep for all the slots: the bytes a
        #: decode step reads (and writes back) whatever is live
        _, _, state, tails = self.shapes()
        self.state_bytes = len(cfg.recurrent_layers) * int(
            np.prod(state) * STATE_DTYPE.itemsize
            + np.prod(tails) * cfg.compute_dtype.itemsize)

    # -- the parts ---------------------------------------------------------
    def shapes(self):
        """(the rings of K or of V, the full slab of K or of V, ONE
        layer's state, ONE layer's tails)."""
        cfg = self.cfg
        row = (cfg.pair_heads,)
        return ((len(cfg.window_layers), self.batch) + row
                + (self.ring, cfg.pair_width),
                (1, self.batch) + row + (self.seq, cfg.pair_width),
                (1, self.batch, cfg.d_state, cfg.d_inner),
                (1, self.batch, cfg.d_conv - 1, cfg.d_inner))

    def read_shapes(self):
        """The rings and the slab as ``caches.kv_rows`` counts them, a
        READING layer each: the slab once for the full layer and once for
        every cross layer."""
        ring, full, _, _ = self.shapes()
        return ring, (self.full_readers,) + full[1:]

    def new_slabs(self):
        cfg = self.cfg
        ring, full, state, tails = self.shapes()
        dt = cfg.compute_dtype
        a_layer = lambda shape, dtype: tuple(
            jnp.zeros(shape, dtype) for _ in cfg.recurrent_layers)
        return ((jnp.zeros(ring, dt), jnp.zeros(full, dt),
                 a_layer(state, STATE_DTYPE)),
                (jnp.zeros(ring, dt), jnp.zeros(full, dt),
                 a_layer(tails, dt)))

    # -- the two forward passes ------------------------------------------
    def decode(self, params, k, v, last_ids, pos, live):
        """One token for every slot (``last_ids``/``pos``/``live``
        ``[B]``; a slot that is not live computes what nobody reads,
        writes no row, keeps its state and tail, and is counted
        nowhere).  Returns the parts and ONE int32 vector: the ``B``
        tokens, then what the step says of itself (:data:`_SAYS`): the
        slots whose state it moved and the K/V rows its attention read,
        summed over the reading layers."""
        cfg, model, ring = self.cfg, self.model, self.ring
        (kw, kf, state), (vw, vf, tails) = k, v
        state, tails = list(state), list(tails)
        tile_w, tile_f = self.attn_tiles
        if tile_f:
            from kungfu_tpu.ops.pallas import decode_attention as kernel
            from kungfu_tpu.ops.pallas import row_write

            def put(ks, vs, i, kn, vn, at):
                return row_write.write_rows(ks, vs, i, kn, vn, at, live)

            # what a slot may see, as the kernel takes it: the slab's
            # first ``pos + 1`` rows, a ring's first ``min(pos + 1, W)``
            # (all of it once the request is past the window: softmax
            # does not mind the order), none where the step is not for it
            see_f = jnp.where(live, pos + 1, 0)
            see_w = jnp.minimum(see_f, ring)
            walked = (len(cfg.window_layers) * kernel.rows_walked(see_w, tile_w)
                      + self.full_readers * kernel.rows_walked(see_f, tile_f))
        else:       # XLA's form reads every row of every slot under a mask
            rows = jnp.arange(ring)
            # ring row r holds the last position <= pos that lands on it
            see_w = (pos[:, None] - (pos[:, None] - rows) % ring >= 0
                     )[:, None, None, None]
            see_f = (jnp.arange(self.seq) <= pos[:, None]
                     )[:, None, None, None]
            walked = self.batch * (len(cfg.window_layers) * ring
                                   + self.full_readers * self.seq)

            def put(ks, vs, i, kn, vn, at):
                windows = row_windows(at, ks.shape[3], live, ROW_WINDOW)
                return (write_rows(ks, i, kn, windows),
                        write_rows(vs, i, vn, windows))

        class Step:
            """A decode step's cache: one row a slot into the layer's
            ring or the slab, attention over the slab itself; one token
            into every slot's state."""

            def write(_, li, kn, vn):
                nonlocal kw, vw, kf, vf
                with jax.named_scope("kv_write"):
                    if li == cfg.full_layer:
                        kf, vf = put(kf, vf, 0, kn, vn, pos)
                    else:
                        kw, vw = put(kw, vw, self.place[li], kn, vn,
                                     pos % ring)

            @jax.named_scope("attn_core")
            def attend(_, li, q, positions):
                kind = cfg.kind(li)
                if kind == "attn_window":
                    i, ks, vs, see, tile = self.place[li], kw, vw, see_w, tile_w
                else:
                    i, ks, vs, see, tile = 0, kf, vf, see_f, tile_f
                with jax.named_scope(kind):
                    if tile:
                        return kernel.decode_attn(
                            q[:, 0], ks, vs, i, see, tile=tile,
                            scale=self.scale)[:, None]
                    return cohere2_moe.attention(q, ks[i], vs[i], see,
                                                 self.scale)

            def convolve(_, li, a, w):
                i = self.place[li]
                seen = jnp.concatenate([tails[i][0], a], axis=1)  # [B, taps, E]
                tails[i] = jnp.where(live[:, None, None], seen[:, 1:],
                                     tails[i][0])[None]
                return jnp.einsum("bte,te->be", seen.astype(F32),
                                  w.astype(F32))[:, None]

            @jax.named_scope("attn_core")
            def recur(_, li, c, dt, B, C, A, D):
                i = self.place[li]
                with jax.named_scope("ssm_state"):
                    new, y = selective_scan.step(
                        state[i][0], c[:, 0], dt[:, 0], A, B[:, 0], C[:, 0],
                        D, live)
                    state[i] = new[None]
                return y[:, None]

        h, m, step = model.embed(params, last_ids[:, None]), None, Step()
        for li in range(cfg.n_layers):
            h, m = arch.block(cfg, params[f"layer_{li}"], li, h,
                              pos[:, None], step, m)
        tok = jnp.argmax(model.logits(params, h[:, 0]), axis=-1)
        says = jnp.stack([jnp.sum(live), walked])
        return ((kw, kf, tuple(state)), (vw, vf, tuple(tails)),
                jnp.concatenate([tok, says]).astype(jnp.int32))

    def new_out(self):
        return jnp.zeros(self.batch + len(_SAYS), jnp.int32)

    @functools.cached_property
    def attn_tiles(self):
        """(the rings' key tile, the slab's) with which a decode step's
        attention is ONE kernel a reading layer that walks only the
        tiles a live context reaches
        (``ops/pallas/decode_attention.py``), or ``(None, None)`` where
        it is XLA's two products over every row
        (``cohere2_moe.attention``): off the TPU, and for shapes the
        kernel does not tile.  One choice, from the platform and the
        shapes, made once: the step that is traced and the span that
        says which form ran read the same.  The kernel's package is
        imported here and by no module's import (PERF.md, PR 35)."""
        if jax.default_backend() != "tpu":
            return None, None
        from kungfu_tpu.ops.pallas import decode_attention

        cfg = self.cfg
        tiles = tuple(decode_attention.key_tile(
            rows, cfg.pair_heads, cfg.n_heads // cfg.pair_heads,
            cfg.pair_width, cfg.compute_dtype) for rows in (self.ring,
                                                            self.seq))
        return tiles if all(tiles) else (None, None)

    @property
    def kv_attn_kernel(self) -> int:
        """1 where a decode step's attention is the fused kernel, 0
        where it is ``cohere2_moe.attention`` (:attr:`attn_tiles`)."""
        return int(self.attn_tiles[1] is not None)

    def read(self, out, contexts):
        """A decode step's ``out`` on the host: the slots' tokens, and
        what it says of itself as attrs of the span that waits for them
        (docs/tracing.md).  Of the rows, counted A READING LAYER: a live
        slot at context ``c`` has to read ``c`` rows of the slab for the
        full layer and for each cross layer and ``min(c, W)`` of each
        ring (``kv_rows_live``, of which ``kv_rows_live_full`` are the
        slab's) and writes one row a KEEPING layer; ``kv_rows_read`` is
        the step's own count, the tiles its kernel walked over all its
        calls or every row where XLA's form ran.  Of the Mamba layers'
        states, the slots it was for beside those it moved."""
        out = np.asarray(jax.device_get(out))
        says = dict(zip(_SAYS, out[self.batch:].tolist()))
        says.update(kv_rows(contexts, self.read_shapes(),
                            self.cfg.compute_dtype))
        says["kv_rows_read"] = says.pop("kv_rows_walked")
        says["kv_rows_written"] = len(self.cfg.row_layers) * len(contexts)
        says["kv_rows_live_full"] = self.full_readers * int(
            np.asarray(contexts).sum())
        says["kv_attn_kernel"] = self.kv_attn_kernel
        # ``decode`` moves every slot's state whatever is live: a step
        # that reads fewer has to say so here (serve/caches.py)
        says["state_slots_read"] = self.batch
        says["state_bytes_read"] = self.state_bytes
        return out[:self.batch], says

    def prefill(self, params, k, v, ids, n, start, slot):
        """``ids`` ``[P]`` (the prompt past ``start`` positions the slot
        already holds, zero-padded past ``n``) into ``slot``: the full
        layer's rows go to ``[start, start + P)`` of the slab (the
        padding lands where the decode writes before anyone reads); each
        ring ends up holding the last ``W`` REAL positions; a Mamba layer
        goes on from the slot's state and tail, or from nothing where
        ``start`` is 0, and leaves those of ``start + n`` tokens.  The
        cross-decoder runs over row ``n - 1`` alone.  Returns the greedy
        token after that row."""
        cfg, model, ring = self.cfg, self.model, self.ring
        (kw, kf, state), (vw, vf, tails) = k, v
        state, tails = list(state), list(tails)
        p_len = ids.shape[0]
        positions = start + jnp.arange(p_len)
        last = start + n - 1
        goes_on = start > 0
        rows = jnp.arange(ring)
        # (the ring before and after: serve/windowed.py)
        unroll = (start + rows) % ring
        held = last - (last - rows) % ring
        fresh = (held >= start)[:, None]
        source = jnp.clip(held - start, 0, p_len - 1)

        class Prompt:
            """A prefill's cache.  The full layer's new rows go into the
            slab and the slot's rows come out as keys, for it and for
            the cross layers behind it; a window layer's keys are the
            ring before (in position order) and the new rows, and the
            ring is then written; state and tail come out of the slot,
            or start empty, and go back."""

            def write(me, li, kn, vn):
                nonlocal kw, vw, kf, vf
                if li == cfg.full_layer:
                    with jax.named_scope("kv_write"):
                        kf = put_rows(kf, kn, (0, slot, 0, start, 0))
                        vf = put_rows(vf, vn, (0, slot, 0, start, 0))
                    me.keys = (layer_slot(kf, 0, slot),
                               layer_slot(vf, 0, slot), 0, None)
                    return
                i = self.place[li]
                # (the barrier: serve/windowed.py)
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
                ks, vs, first, window = me.keys
                with jax.named_scope(cfg.kind(li)):
                    return cohere2_moe.blocked_attention(
                        q, ks, vs, positions[0], first, window, self.scale)

            def convolve(_, li, a, w):
                i = self.place[li]
                tail = jnp.where(goes_on, of_slot(tails[i], 0, slot), 0)
                y, tail = causal_conv(a[0], w, tail, n)
                with jax.named_scope("kv_write"):
                    tails[i] = to_slot(tails[i], 0, slot, tail)
                return y[None]

            @jax.named_scope("attn_core")
            def recur(_, li, c, dt, B, C, A, D):
                i = self.place[li]
                with jax.named_scope("ssm_scan"):
                    h0 = jnp.where(goes_on, of_slot(state[i], 0, slot
                                                    ).astype(F32), 0.0)
                    y, h_n = selective_scan.chunked(
                        c[0], dt[0], A, B[0], C[0], D, h0, n)
                with jax.named_scope("kv_write"):
                    state[i] = to_slot(state[i], 0, slot, h_n)
                return y[None]

        h, m, prompt = model.embed(params, ids[None]), None, Prompt()
        for li in range(cfg.full_layer + 1):
            h, m = arch.block(cfg, params[f"layer_{li}"], li, h,
                              positions[None], prompt, m)
        with jax.named_scope("cross_tail"):
            # the cross-decoder keeps nothing: one row of it is all that
            # anyone reads
            h, m = (jax.lax.dynamic_slice_in_dim(x, n - 1, 1, axis=1)
                    for x in (h, m))
            at = jnp.reshape(last, (1, 1))
            for li in range(cfg.full_layer + 1, cfg.n_layers):
                h, m = arch.block(cfg, params[f"layer_{li}"], li, h, at,
                                  prompt, m)
        tok = jnp.argmax(model.logits(params, h[:, 0])[0], axis=-1)
        return ((kw, kf, tuple(state)), (vw, vf, tuple(tails)),
                tok.astype(jnp.int32))

    # -- the host's side of a page ---------------------------------------
    def empty_pages(self, rows: int):
        """What the restore program writes into a slot for ``rows``
        positions that hold nothing: zero rows for the slab, an empty
        ring, and, whatever ``rows`` is, an empty state and an empty
        tail."""
        cfg = self.cfg
        dt = cfg.compute_dtype
        (lw, _, g, ring, w), _, state, tails = self.shapes()
        # (host arrays, only read: one for all the layers will do)
        a_layer = lambda shape, dtype: (np.zeros(
            shape[:1] + shape[2:], dtype),) * len(cfg.recurrent_layers)
        rings, slab = np.zeros((lw, g, ring, w), dt), np.zeros(
            (1, g, rows, w), dt)
        return ((rings, slab, a_layer(state, STATE_DTYPE)),
                (rings, slab, a_layer(tails, dt)))
