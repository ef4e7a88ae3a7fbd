"""What ``InferenceEngine`` asks of the model it serves, and the dense
``Transformer``'s answer.

The engine is the scheduler: requests, slots, admission, pages, spans.
Everything that depends on what a model keeps of a request, and how it
lays that out, it asks of one object, ``model.serve_caches(max_batch,
max_seq)``.  What is kept comes in TWO PARTS, called ``k`` and ``v``
after the dense model's, each an array or a tree of arrays whose first
two axes are ``[layers, slots]``.  The engine never looks inside a part:
it hands the pair on, and writes into one slot of every array of it.  An
array of a part is of one of three kinds:

* **rows a position** ``[layers, slots, heads, positions, width]``:
  per-head keys and values of one shape in the dense model and in
  ``serve/windowed.py``; in ``serve/latent.py`` one compressed row for
  all the heads and their shared rotary key, of different widths.  Rows
  can be cut into pages of positions: a finished request's are fetched
  and committed (``rows_of_slot``), a later request with the same prefix
  gets them back (``pages_to_slot``) and prefills only what follows.
* **a state a slot** ``[layers, slots, ...]`` with no axis of positions:
  what a recurrent layer makes of ALL the positions so far
  (``serve/recurrent.py``: a matrix a head and a short convolution's
  last inputs).  It has one value, the newest: nothing in it is the
  state at a page's end, so no page of positions can rebuild it.  A
  model that has such a part says so to the pool (``PageSpec.unpaged``,
  from the config's ``recurrent_layers``); its pages are then never
  ``whole``, ``KVCachePool.reusable`` is false for them, and the engine
  looks up no prefix, reserves no page and commits none: ``rows_of_slot``
  and ``pages_to_slot`` are never asked of it.  What the cache owes
  instead: a prefill from ``start == 0`` starts the slot's state from
  nothing, whatever the slot held; a prefill of a padded bucket leaves
  the state of exactly ``n`` tokens; a decode step leaves the state of a
  slot it is not ``live`` for as it was.
* **rows that stand for several positions** ``[layers, slots, heads,
  rows, width]`` whose rows are no positions: ``serve/pooled.py`` keeps,
  beside the exact rows of the open window of positions, one *chunk
  row* for every few positions before it, MADE from those positions'
  rows by a learned pooling when the chunk is complete and seen by no
  query until its window has closed.  A page of positions could hold a
  closed window's chunk rows; the rule by which a prefix would be
  restored from them is not built, so such a model is unpaged too
  (``cfg.pages_reusable`` False) and owes what the state a slot owes: a
  prefill starts the slot from nothing whatever it held, a padded
  bucket leaves the rows and chunk rows of exactly ``n`` positions, a
  decode step leaves a slot it is not ``live`` for as it was.

A part may hold several kinds side by side (``serve/sambay.py``: a state
a slot, rings and rows of every position in one slot), and **rows may be
kept once and read by several layers**: there one layer keeps every
position's rows and seven further layers, which keep nothing, attend
over them.  The engine sees none of it.  What such a cache owes is in
its ``read``: the counts below are then A READING LAYER's -- a context
of ``c`` owes ``c`` rows for every layer that reads the slab, not for
every layer that keeps one -- while ``kv_rows_written`` stays the
KEEPING layers'.  (Such a family is unpaged here, so nothing reads
how many layers its ``PageSpec`` counts a page over.)

``new_slabs()``
    the device cache as the pair ``(k, v)``.
``prefill(params, k, v, ids, n, start, slot)`` -> ``(k, v, token)``
    the body of the prefill program: ``ids`` ``[P]`` (the prompt past
    the ``start`` positions the slot already holds, zero-padded past
    ``n``) into ``slot``; the greedy token after row ``n - 1``.
``decode(params, k, v, last_ids, pos, live)`` -> ``(k, v, out)``
    the body of the decode program: one token for every slot.  ``live``
    ``[B]`` says which slots the step is for: the row of any other is
    NOT written, and its state not moved (the engine dispatches a step
    before it has read the one before, so a slot it leaves out may hold a
    request that is finishing and whose rows are yet to be committed:
    docs/serving.md).  ``out`` is ONE array whose first ``B`` entries are
    the slots' tokens (the next step takes them from it on the device),
    so that the host's one read brings all a step has to say;
    ``read(out, contexts)`` takes it apart.
``new_out()``
    what stands for a step's ``out`` before any step ran: zeros of its
    shape and dtype.
``read(out, contexts)`` -> ``(tokens [B], attrs)``
    on the host: fetch a decode step's ``out`` (the host waits here), the
    slots' tokens and what the step has to say of itself besides, as
    attrs of the ``kf:serve.decode_read`` span that is open meanwhile
    (docs/tracing.md has the table).  ``contexts`` is the engine's: the
    positions each row of the step attended over, its own new one among
    them, for the rows whose request is still there when the step is
    read (one that ended on ``eos_id`` in the step before was not
    ``live`` in the program either).  Every cache says, of each kind of
    content it keeps, **what the step had to read and what it did
    read**, under one noun a kind:

    * ``kv_rows_live``, ``kv_rows_read``, ``kv_rows_written``,
      ``kv_row_bytes`` -- per-head key and value rows a position
      (:func:`kv_rows`: the dense slabs, the window rings and full
      slabs, the hybrid cache's slab), summed over the layers that keep
      them: a full layer has to read ``contexts[i]`` rows of slot ``i``,
      a window layer at most its ring; a row's bytes are one layer's in
      both parts;
    * ``latent_rows_live``, ``latent_rows_read`` -- compressed rows a
      position (``serve/latent.py``), a layer's;
    * ``state_slots_live``, ``state_slots_read``, ``state_bytes_read``
      -- a state a slot (``serve/recurrent.py``, ``serve/sambay.py``);
    * ``summary_rows_live``, ``summary_rows_read``,
      ``summary_rows_written`` -- rows that stand for several positions
      (``serve/pooled.py``: the chunk rows of closed windows a context
      has to read, those the step read, and the chunks it completed),
      beside ``kv_rows_*`` for the open window's exact rows.

    The ``*_live`` of rows a position is the sum of ``contexts``, which
    the host has (the latent cache's step counts it itself, and the two
    are one number: tests/test_serve_kv_rows.py); ``*_read`` is stated by
    the cache from its parts' shapes, because every decode body here
    reads every position of every slot of every part under a mask,
    whatever is live.  **A step that reads fewer has to say so here**:
    a kernel that skips dead rows or dead slots counts what it read in
    the step, puts it into ``out`` and states that.  The metrics that
    divide the one by the other, and the rooflines counted over the live
    work, are only true while this holds.
``empty_pages(rows)`` -> ``(ks, vs)``
    what the restore program writes into a slot for ``rows`` cached
    positions that hold nothing, one for each part (each a tree shaped
    like its part of the slabs, without the slot axis): zeros -- of
    ``rows`` positions for rows a position, an empty state for a state a
    slot.
``pages_to_slot(data, n_cached, rows, page_tokens)``
    the same for ONE part of a cached prefix: that part of its pages,
    ``[L, H, T, W]`` each and in order, as what the restore program
    writes.
``rows_of_slot(slab, slot, lo, hi, total)`` -> ``(rows, kept_from)``
    positions ``[lo, hi)`` of one part of a finished request of
    ``total`` tokens as page data ``[L, H, hi - lo, W]``, and the first
    position all of whose layers' rows still exist (pages before it are
    not ``whole``: ``KVCachePool.reusable``).
``prefill_flops(tokens, start)``, ``decode_flops(contexts)``
    the analytic cost of a prefill and of a decode step over its live
    contexts, for the serving MFU gauge.

Both bodies take the slabs donated and write them in place; the restore
program is the engine's own (a ``dynamic_update_slice`` into one slot of
every leaf, whatever axes follow its slots).  ``serve.kvcache.PageSpec``
counts a page's bytes from the two parts' widths over the layers that
keep rows.  :class:`DenseCaches` is the dense ``Transformer``'s (one slab
``[L, B, H, S, D]`` for K and one for V, every layer keeping every
position); ``serve/windowed.py`` the one of a model that mixes window and
full attention layers; ``serve/latent.py`` the one of latent attention;
``serve/recurrent.py`` the one of a model most of whose layers keep a
state a slot; ``serve/pooled.py`` the one of attention over pooled chunk
rows; ``serve/sambay.py`` the one of three kinds of content in a slot
whose full rows are read by more layers than keep them.
:func:`row_windows` and :func:`write_rows`, the in-place write of
one row a slot, are shared by all six, :func:`layer_slot` and
:func:`put_rows` by the two that keep rings, :func:`of_slot` and
:func:`to_slot` by the two that keep a state a slot,
:func:`pages_in_order` and :func:`slot_rows`, the host's side of a part
that keeps every position, by the first and the third.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from kungfu_tpu.models import nn
from kungfu_tpu.models.transformer import _rope
from kungfu_tpu.ops import costmodel


def row_windows(pos, s_max, live, width: int = 128):
    """Per slot ``b``: where the aligned window of ``S`` that holds
    position ``pos[b]`` starts, and which of its rows that is -- none of
    them where ``live[b]`` is false: such a slot's window is written
    back as it was read (the row to hit is a scalar, so the select over
    the window is the same vector work either way).  Each start is a
    scalar ``p // w * w`` on purpose: from that the compiler knows the
    window is tile-aligned and updates it in place; sliced out of a
    vector of starts it no longer does, and the write takes five times
    as long (tests/test_tpu_compile.py).  ``width`` is the window's
    rows: 128 suits every layout the compiler gives a slab; a cache
    whose rows are a tile's sublanes may ask for fewer
    (``serve/pooled.py``)."""
    w = math.gcd(s_max, width)              # divides S: never clamped
    lane = jnp.arange(w)[:, None]
    return [(p // w * w, lane == jnp.where(l, p % w, -1))
            for p, l in zip(pos, live)]


def write_rows(slab, li, new, windows):
    """Row ``b`` of ``new`` [B, H, 1, D] into layer ``li`` of
    ``slab`` at slot ``b``'s position, in place, as a
    read-modify-write of the window of :func:`row_windows`.  The TPU
    lays ``S`` along the lanes, 128 to a tile, so that window is what
    a one-row ``dynamic_update_slice`` touches anyway, unrolled: 5 us
    and 140 KB of code a slot and layer.  Window by window, XLA fuses
    the slice, the select and the update of K and V into one in-place
    loop (2.9 against 6.9 ms a step at GPT-2 large, PERF.md PR 25).
    A scatter would be one operation, but the compiler lays the whole
    slab out anew around it (1.2 s a step)."""
    size = (1, 1, slab.shape[2], windows[0][1].shape[0], slab.shape[4])
    for b, (start, hit) in enumerate(windows):
        at = (li, b, 0, start, 0)
        old = jax.lax.dynamic_slice(slab, at, size,
                                    allow_negative_indices=False)
        slab = jax.lax.dynamic_update_slice(
            slab, jnp.where(hit, jax.lax.slice_in_dim(new, b, b + 1),
                            old), at,
            allow_negative_indices=False)
    return slab


def layer_slot(slab, li, slot):
    """Layer ``li``, slot ``slot`` of a slab ``[L, B, G, S, D]`` as ``[1,
    G, S, D]``, by one dynamic slice (taking the layer first would
    materialise its slots)."""
    return jax.lax.dynamic_slice(
        slab, (li, slot, 0, 0, 0), (1, 1) + slab.shape[2:],
        allow_negative_indices=False)[0]


def put_rows(slab, rows, at):
    """``rows`` ``[1, G, n, D]`` of one layer and slot into ``slab`` at
    ``at``, in place."""
    return jax.lax.dynamic_update_slice(slab, rows[None], at,
                                        allow_negative_indices=False)


def of_slot(part, li, slot):
    """Layer ``li``, slot ``slot`` of a part that keeps a state a slot,
    without those two axes, by one dynamic slice (taking the layer first
    would materialise its slots)."""
    return jax.lax.dynamic_slice(
        part, (li, slot) + (0,) * (part.ndim - 2), (1, 1) + part.shape[2:],
        allow_negative_indices=False)[0, 0]


def to_slot(part, li, slot, new):
    """``new`` as layer ``li``, slot ``slot`` of such a part, in place."""
    return jax.lax.dynamic_update_slice(
        part, new[None, None].astype(part.dtype),
        (li, slot) + (0,) * (part.ndim - 2), allow_negative_indices=False)


def pages_in_order(data, rows: int, page_tokens: int):
    """One part of a cached prefix's pages, ``[L, H, T, W]`` each and in
    order, as ``[L, H, rows, W]``: zeros past the last page."""
    first, t = data[0], page_tokens
    out = np.zeros(first.shape[:2] + (rows,) + first.shape[3:], first.dtype)
    for i, page in enumerate(data):
        out[:, :, i * t:(i + 1) * t] = page
    return out


def slot_rows(slab, slot: int, lo: int, hi: int):
    """Positions ``[lo, hi)`` of ``slot`` of a slab ``[L, B, H, S, W]``
    on the host, ``[L, H, hi - lo, W]``."""
    return np.asarray(jax.device_get(slab[:, slot, :, lo:hi, :]))


def kv_rows(contexts, slabs, dtype) -> dict:
    """The ``kv_*`` attrs of a decode step over ``slabs``, the shapes
    ``[layers, slots, heads, rows, width]`` of K's arrays of rows a
    position (V's are the same), for the ``contexts`` the engine hands
    ``read``.  A slot's context has to read ``min(context, rows)`` rows
    of each layer of a slab (a ring holds no more; a full slab's
    ``rows`` is ``max_seq``, which no context passes) and writes one;
    the bodies here READ every row of every slot of every slab, which
    is what ``kv_rows_read`` states: a step that reads fewer has to say
    so there.  On the host, one vectorised call a slab and step."""
    contexts = np.asarray(contexts)
    live = read = layers = 0
    for n, slots, _, rows, _ in slabs:
        live += n * int(np.minimum(contexts, rows).sum())
        read += n * slots * rows
        layers += n
    _, _, heads, _, width = slabs[0]
    return {"kv_rows_live": live, "kv_rows_read": read,
            "kv_rows_written": layers * len(contexts),
            "kv_row_bytes": 2 * heads * width * jnp.dtype(dtype).itemsize}


class DenseCaches:
    """The dense ``Transformer`` through the engine: K and V one slab
    ``[L, B, H, S, D]`` each in the compute dtype."""

    def __init__(self, model, max_batch: int, max_seq: int):
        self.model = model
        cfg = self.cfg = model.cfg
        self.batch, self.seq = int(max_batch), int(max_seq)
        #: of K's slab, and of V's
        self.shape = (cfg.n_layers, self.batch, cfg.n_heads, self.seq,
                      cfg.head_dim)

    def new_slabs(self):
        dt = self.cfg.compute_dtype
        return jnp.zeros(self.shape, dt), jnp.zeros(self.shape, dt)

    # -- forward passes --------------------------------------------------
    @jax.named_scope("attn_proj")
    def _layer_qkv(self, lp, x, positions):
        cfg = self.cfg
        dt = cfg.compute_dtype

        def heads(t):
            b, s, _ = t.shape
            return t.reshape(b, s, cfg.n_heads, cfg.head_dim
                             ).transpose(0, 2, 1, 3)

        q = heads(nn.dense_apply(lp["wq"], x, dtype=dt))
        k = heads(nn.dense_apply(lp["wk"], x, dtype=dt))
        v = heads(nn.dense_apply(lp["wv"], x, dtype=dt))
        if cfg.pos == "rope":
            q, k = _rope(q, k, positions)
        return q, k, v

    @staticmethod
    @jax.named_scope("attn_core")
    def _attend(q, keys, values, mask):
        """q [B,H,Q,D] over keys/values [B,H,S,D]; mask [B,1,Q,S] (or
        broadcastable) True = attend.  f32 logits/softmax like the
        training path."""
        d = q.shape[-1]
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, keys
                            ).astype(jnp.float32) / jnp.sqrt(d)
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, values)

    @staticmethod
    def _merge(x):
        b, h, s, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)

    def _out_and_mlp(self, lp, h, o):
        """The rest of a block after attention ``o`` [B,H,Q,D]: the
        output projection and the MLP, each added to the stream ``h``."""
        dt = self.cfg.compute_dtype
        with jax.named_scope("attn_proj"):
            h = h + nn.dense_apply(lp["wo"], self._merge(o), dtype=dt)
        x = nn.layernorm_apply(lp["ln2"], h)
        with jax.named_scope("mlp"):
            y = nn.gelu(nn.dense_apply(lp["ffn_in"], x, dtype=dt))
            return h + nn.dense_apply(lp["ffn_out"], y, dtype=dt)

    def prefill(self, params, k_slab, v_slab, ids, n, start, slot):
        """ids [S_pad] (suffix, zero-padded past ``n``); writes K/V at
        positions ``[start, start + S_pad)`` of ``slot`` and returns the
        greedy next token after the last REAL row (``n - 1``)."""
        cfg = self.cfg
        dt = cfg.compute_dtype
        s_pad = ids.shape[0]
        s_max = k_slab.shape[3]
        positions = start + jnp.arange(s_pad)
        with jax.named_scope("embed"):
            h = nn.embedding_apply(params["embed"], ids[None], dtype=dt)
            if cfg.pos == "learned":
                h = h + nn.embedding_apply(params["pos_embed"],
                                           positions[None], dtype=dt)
        q_pos = positions
        key_pos = jnp.arange(s_max)
        mask = (key_pos[None, :] <= q_pos[:, None])[None, None]  # [1,1,Q,S]
        for li in range(cfg.n_layers):
            lp = params[f"layer_{li}"]
            x = nn.layernorm_apply(lp["ln1"], h)
            q, k, v = self._layer_qkv(lp, x, positions[None])
            with jax.named_scope("kv_write"):
                # (no index is negative: normalising them is a third of
                # this function's tracing time, once a bucket)
                k_slab = jax.lax.dynamic_update_slice(
                    k_slab, k[None], (li, slot, 0, start, 0),
                    allow_negative_indices=False)
                v_slab = jax.lax.dynamic_update_slice(
                    v_slab, v[None], (li, slot, 0, start, 0),
                    allow_negative_indices=False)
            with jax.named_scope("attn_core"):
                # one dynamic slice of (layer, slot): taking the layer
                # first would materialise all its slots
                at, size = (li, slot, 0, 0, 0), (1, 1) + k_slab.shape[2:]
                keys = jax.lax.dynamic_slice(
                    k_slab, at, size, allow_negative_indices=False)[0]
                values = jax.lax.dynamic_slice(
                    v_slab, at, size, allow_negative_indices=False)[0]
                o = self._attend(q, keys, values, mask)
            h = self._out_and_mlp(lp, h, o)
        h = nn.layernorm_apply(params["ln_f"], h)
        with jax.named_scope("head"):
            last = jax.lax.dynamic_index_in_dim(h, n - 1, axis=1,
                                                keepdims=False)
            logits = nn.dense_apply(params["head"], last).astype(jnp.float32)
            tok = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)
        return k_slab, v_slab, tok

    def decode(self, params, k_slab, v_slab, last_ids, pos, live):
        """One token for every slot: ``last_ids``/``pos``/``live`` are
        [B]; a live slot's new K/V lands at its ``pos`` and attention
        covers ``[0, pos]``.  The others compute garbage nobody reads,
        and write nothing."""
        cfg = self.cfg
        dt = cfg.compute_dtype
        s_max = k_slab.shape[3]
        positions = pos[:, None]                     # [B, 1]
        with jax.named_scope("embed"):
            h = nn.embedding_apply(params["embed"], last_ids[:, None],
                                   dtype=dt)
            if cfg.pos == "learned":
                h = h + nn.embedding_apply(params["pos_embed"], positions,
                                           dtype=dt)
        mask = (jnp.arange(s_max)[None, :] <= positions)[:, None, None, :]
        windows = row_windows(pos, s_max, live)
        for li in range(cfg.n_layers):
            lp = params[f"layer_{li}"]
            x = nn.layernorm_apply(lp["ln1"], h)
            q, k, v = self._layer_qkv(lp, x, positions)
            with jax.named_scope("kv_write"):
                k_slab = write_rows(k_slab, li, k, windows)
                v_slab = write_rows(v_slab, li, v, windows)
            h = self._out_and_mlp(lp, h, self._attend(
                q, k_slab[li], v_slab[li], mask))
        h = nn.layernorm_apply(params["ln_f"], h)
        with jax.named_scope("head"):
            logits = nn.dense_apply(params["head"], h[:, 0]
                                    ).astype(jnp.float32)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return k_slab, v_slab, tok

    def new_out(self):
        return jnp.zeros(self.batch, jnp.int32)

    def read(self, out, contexts):
        return np.asarray(jax.device_get(out)), kv_rows(
            contexts, (self.shape,), self.cfg.compute_dtype)

    # -- the host's side of a page ---------------------------------------
    def empty_pages(self, rows: int):
        cfg = self.cfg
        part = np.zeros((cfg.n_layers, cfg.n_heads, rows, cfg.head_dim),
                        cfg.compute_dtype)
        return part, part

    @staticmethod
    def pages_to_slot(data, n_cached: int, rows: int, page_tokens: int):
        return pages_in_order(data, rows, page_tokens)

    @staticmethod
    def rows_of_slot(slab, slot: int, lo: int, hi: int, total: int):
        return slot_rows(slab, slot, lo, hi), 0

    # -- what a forward pass costs (the serving MFU gauge) ---------------
    def prefill_flops(self, tokens: int, start: int = 0) -> int:
        return costmodel.serve_prefill_flops(self.cfg, tokens, start)

    def decode_flops(self, contexts) -> int:
        return sum(costmodel.serve_decode_flops(self.cfg, int(n))
                   for n in contexts)
