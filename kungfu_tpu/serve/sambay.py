"""Three kinds of content in one slot, for a model whose layers are of
five kinds (``models/phi4flash.py``: SambaY).  The engine's two parts
are each a triple,

* ``k`` = (``ring_k`` ``[Lw, B, G', W, W']``, ``full_k`` ``[1, B, G',
  max_seq, W']``, ``state``: ``Lm`` arrays ``[1, B, N, E]`` float32),
* ``v`` = (``ring_v``, ``full_v``, ``tails``: ``Lm`` arrays ``[1, B,
  taps - 1, E]``),

``ring`` the ``Lw`` window layers' last ``W = min(window, max_seq)``
positions (``caches.RingRows``), ``full`` every position of the ONE
full-attention layer (``caches.FullRows``), ``state`` and ``tails`` what
the ``Lm`` Mamba layers keep of a request however long it is
(``caches.SlotStates``).  A row is ``G'`` PAIRS of key (or value) heads,
``W'`` = two heads wide: the layout in which differential attention is
ordinary grouped attention (``models/phi4flash.py``), under the scale of
ONE head's width.  The layers of the cross-decoder keep NOTHING: a GMU
reads the last Mamba layer's output of its own position, a cross layer
attends over ``full`` -- **rows kept once and read by several layers**
(one self and ``Lc`` cross reads a step: the slab's store is told its
``readers``), which is why every count of ``read`` is A READING LAYER's
and not a keeping one's.

:class:`SambaYCaches` is what ``InferenceEngine`` asks of such a model
(``serve/caches.py``).  What is its own:

* which layer goes to which store, and that it asks both stores of rows
  for both kernels: on the TPU a decode step writes its rows by one
  kernel a KEEPING layer, K and V together (``ops/pallas/row_write.py``:
  nine calls where ``caches.write_rows``' window updates, one a slot and
  layer, were 1,152 operations of a step's 3,900 and some 1,300 scalar
  ones beside them) and attends by one kernel a READING layer
  (``ops/pallas/decode_attention.py``), each K/V row read once a call;
* the states' update: ``selective_scan.step``, one elementwise pass over
  EVERY slot's state that also takes the read-out (a slot the step is
  not live for keeps state, tail and rows), and
  ``selective_scan.chunked`` in a prefill;
* its two loops.  A layer hands the memory ``m`` on to the next.  The
  **prefill** has two halves of unequal length: the self-decoder (Mamba,
  window and full layers) runs over the whole bucket; the cross-decoder
  runs over row ``n - 1`` ALONE: nothing in it is kept for a later
  position, and the engine wants the last row's token only.  That is
  the architecture's own prefill and exact.

Pages: a Mamba layer keeps nothing at a page's end that a later request
could start from, so no page of this family is ever ``whole``
(``PageSpec.unpaged``, from ``cfg.recurrent_layers``): the engine looks
up no prefix, commits nothing, and never asks this cache for
``rows_of_slot`` or ``pages_to_slot``.  ``PageSpec.for_model`` counts
the 23 layers that are not recurrent where 9 keep rows; an unpaged
spec's ``n_layers`` is read by nothing, so it is left so.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from kungfu_tpu.models import phi4flash as arch
from kungfu_tpu.ops import selective_scan
from kungfu_tpu.serve.caches import (Caches, FullRows, Pass, RingRows,
                                     SlotStates, kv_rows, visible)


class _Step(Pass):
    def __init__(self, caches, rings, full, states, live):
        super().__init__(caches, rings, full, states)
        self.live = live
        # the rows the step's attention reads, summed over the reading
        # layers, counted here whatever the layers then do
        self.walked = (rings.store.readers * rings.walked
                       + full.store.readers * full.walked)

    def recur(self, li, c, dt, B, C, A, D):
        def update(state):
            new, y = selective_scan.step(state[0], c[:, 0], dt[:, 0], A,
                                         B[:, 0], C[:, 0], D, self.live)
            return new[None], y

        states, i, _ = self.of(li)
        return states.recur(i, "ssm_state", update)

    def says(self, counts):
        return jnp.stack([jnp.sum(self.live), self.walked])


class _Prompt(Pass):
    def __init__(self, caches, last, *passes):
        super().__init__(caches, *passes)
        #: the position of the last real row: the cross-decoder's only one
        self.last = last

    def recur(self, li, c, dt, B, C, A, D):
        states, i, _ = self.of(li)
        return states.recur(
            i, "ssm_scan", lambda state: selective_scan.chunked(
                c[0], dt[0], A, B[0], C[0], D, state, states.n))


class SambaYCaches(Caches):
    says = ("state_slots_live", "kv_rows_walked")

    def __init__(self, model: arch.Phi4Flash, max_batch: int, max_seq: int):
        super().__init__(model, max_batch, max_seq)
        cfg = self.cfg
        row = dict(group=cfg.n_heads // cfg.pair_heads, row_kernel=True,
                   scale=1.0 / math.sqrt(cfg.head_dim))
        self.rings = RingRows(
            len(cfg.window_layers), self.batch, cfg.pair_heads, cfg.window,
            self.seq, cfg.pair_width, cfg.compute_dtype, **row)
        #: read by the layer that keeps it and by the cross ones
        self.full = FullRows(
            1, self.batch, cfg.pair_heads, self.seq, cfg.pair_width,
            cfg.compute_dtype, readers=1 + len(cfg.cross_layers), **row)
        self.states = SlotStates(
            len(cfg.recurrent_layers), self.batch,
            (cfg.d_state, cfg.d_inner), (cfg.d_conv - 1, cfg.d_inner),
            cfg.compute_dtype)
        self.stores = (self.rings, self.full, self.states)
        #: a layer's place in its kind's parts
        self.at = self.place(cfg.window_layers, cfg.recurrent_layers)

    def where(self, li):
        """The cross layers keep nothing: they read the slab's one
        layer, as the full layer does."""
        kind = self.cfg.kind(li)
        if kind == "attn_window":
            return 0, self.at[li], kind
        return (2, self.at[li], None) if kind == "mamba" else (1, 0, kind)

    @property
    def attn_tiles(self):
        """(the rings' key tile, the slab's): ``caches.FullRows.tile``."""
        return self.rings.tile, self.full.tile

    @property
    def kv_attn_kernel(self) -> int:
        """1 where every reading layer's attention is the fused kernel."""
        return int(all(self.attn_tiles))

    # -- the two forward passes ------------------------------------------
    def step(self, k, v, pos, live):
        (kw, kf, state), (vw, vf, tails) = k, v
        rings, full = self.rings, self.full
        sees = visible(pos, live)
        return _Step(
            self,
            rings.step(kw, vw, rings.see(pos, sees), rings.writer(pos, live)),
            full.step(kf, vf, full.see(pos, sees), full.writer(pos, live)),
            self.states.step(state, tails, live), live)

    def layers(self, params, h, positions, step, live):
        cfg, m = self.cfg, None
        for li in range(cfg.n_layers):
            h, m = arch.block(cfg, params[f"layer_{li}"], li, h,
                              positions[:, None], step, m)
        return h, ()

    def prompt(self, k, v, p_len, n, start, slot):
        (kw, kf, state), (vw, vf, tails) = k, v
        last = start + n - 1
        states = self.states.prompt(state, tails, n, start, slot)
        return _Prompt(
            self, last, self.rings.prompt(kw, vw, p_len, last, start, slot),
            self.full.prompt(kf, vf, start, slot), states)

    def prompt_row(self, params, h, positions, prompt, n):
        cfg, m = self.cfg, None
        for li in range(cfg.full_layer + 1):
            h, m = arch.block(cfg, params[f"layer_{li}"], li, h,
                              positions[None], prompt, m)
        with jax.named_scope("cross_tail"):
            # the cross-decoder keeps nothing: one row of it is all that
            # anyone reads
            h, m = (jax.lax.dynamic_slice_in_dim(x, n - 1, 1, axis=1)
                    for x in (h, m))
            at = jnp.reshape(prompt.last, (1, 1))
            for li in range(cfg.full_layer + 1, cfg.n_layers):
                h, m = arch.block(cfg, params[f"layer_{li}"], li, h, at,
                                  prompt, m)
        return h[:, 0]

    def attrs(self, says, contexts):
        """Of the rows, counted A READING LAYER: a live slot at context
        ``c`` has to read ``c`` rows of the slab for the full layer and
        for each cross layer and ``min(c, W)`` of each ring
        (``kv_rows_live``, of which ``kv_rows_live_full`` are the
        slab's) and writes one row a KEEPING layer; ``kv_rows_read`` is
        the step's own count, the tiles its kernels walked over all
        their calls or every row where XLA's form ran.  Of the Mamba
        layers' states, the slots it was for beside those it moved:
        every slot's, whatever is live."""
        says.update(kv_rows(contexts, (self.rings, self.full)))
        says["kv_rows_read"] = says.pop("kv_rows_walked")
        says["kv_rows_live_full"] = self.full.readers * int(
            np.asarray(contexts).sum())
        says["kv_attn_kernel"] = self.kv_attn_kernel
        says.update(self.states.attrs(self.batch))
        return says
