"""What ``InferenceEngine`` asks of the model it serves, the ONE body
that answers it for every family, the stores a family's parts are made
of, and the dense ``Transformer``'s answer.

The engine is the scheduler: requests, slots, admission, pages, spans.
Everything that depends on what a model keeps of a request, and how it
lays that out, it asks of one object, ``model.serve_caches(max_batch,
max_seq)``.  What is kept comes in TWO PARTS, called ``k`` and ``v``
after the dense model's, each an array or a tree of arrays whose first
two axes are ``[layers, slots]``.  The engine never looks inside a part:
it hands the pair on, and writes into one slot of every array of it.  An
array of a part is of one of three kinds:

* **rows a position** ``[layers, slots, heads, positions, width]``
  (:class:`FullRows`, :class:`RingRows`; ``serve/latent.py``'s two
  widths).  Rows can be cut into pages of positions: a finished
  request's are fetched and committed (``rows_of_slot``), a later
  request with the same prefix gets them back (``pages_to_slot``) and
  prefills only what follows.
* **a state a slot** ``[layers, slots, ...]`` with no axis of positions
  (:class:`SlotStates`): what a recurrent layer makes of ALL the
  positions so far.  It has one value, the newest: nothing in it is the
  state at a page's end, so no page of positions can rebuild it.  A
  model that has such a part says so to the pool (``PageSpec.unpaged``,
  from the config's ``recurrent_layers``); its pages are then never
  ``whole``, ``KVCachePool.reusable`` is false for them, and the engine
  looks up no prefix, reserves no page and commits none: ``rows_of_slot``
  and ``pages_to_slot`` are never asked of it.  What the cache owes
  instead: a prefill from ``start == 0`` starts the slot from nothing,
  whatever the slot held; a prefill of a padded bucket leaves the state
  of exactly ``n`` tokens; a decode step leaves a slot it is not
  ``live`` for as it was.
* **rows that stand for several positions** (``serve/pooled.py``: one
  chunk row for every few positions, MADE from those positions' rows).
  The rule by which a prefix would be restored from pages of them is
  not built, so such a model is unpaged too (``cfg.pages_reusable``
  False) and owes what the state a slot owes.

A part may hold several kinds side by side, and **rows may be kept once
and read by several layers** (``serve/sambay.py``): what ``read`` counts
is then A READING LAYER's -- a context of ``c`` owes ``c`` rows for
every layer that reads the slab, not for every layer that keeps one --
while ``kv_rows_written`` stays the KEEPING layers'.

The interface is :class:`Caches`' methods, where each says what it owes:
``new_slabs``, ``prefill``, ``decode``, ``new_out``, ``read``,
``empty_pages``, ``pages_to_slot``, ``rows_of_slot``, ``prefill_flops``,
``decode_flops``.  Both programs take the slabs donated and write them
in place; the restore program is the engine's own (a
``dynamic_update_slice`` into one slot of every leaf, whatever axes
follow its slots).  ``serve.kvcache.PageSpec`` counts a page's bytes
from the two parts' widths over the layers that keep rows.

What ``read`` states, of each kind of content a cache keeps, is **what
the step had to read and what it did read**, under one noun a kind
(docs/tracing.md has the table): ``kv_rows_*`` and ``kv_row_bytes`` for
per-head rows a position (:func:`kv_rows`), ``latent_rows_*`` for
compressed ones, ``state_slots_*`` and ``state_bytes_read`` for a state
a slot (:meth:`SlotStates.attrs`), ``summary_rows_*`` for rows that
stand for several positions.  The ``*_live`` of rows a position is the
sum of the ``contexts`` the engine hands ``read``; ``*_read`` is stated
from the parts' shapes where a decode body reads every position of every
slot under a mask, whatever is live.  **A step that reads fewer has to
say so here**: a kernel that skips dead rows or dead slots counts what
it read in the step, puts it into ``out`` and states that.  The metrics
that divide the one by the other, and the rooflines counted over the
live work, are only true while this holds.

A family's file derives the body and says what is its own: which of its
layers keeps what in which store, what its ``arch.block`` is handed,
what its step counts.  The programs' TEXT is held to
(tests/test_cohere2_moe.py, tests/test_tpu_compile.py): a change here
that makes another operation, or the same in another order, shows there.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from kungfu_tpu.models import cohere2_moe, nn
from kungfu_tpu.models.transformer import _rope
from kungfu_tpu.ops import costmodel
from kungfu_tpu.ops.delta_rule import causal_conv

F32 = jnp.float32
#: of a recurrent layer's state (the published layers keep it so; the
#: convolution tails are in the compute dtype).  Both bodies compute in
#: float32 and store in whatever the state they are handed is
STATE_DTYPE = jnp.dtype("float32")
#: rows of the aligned window :func:`write_rows` reads and writes back
#: where a row's 128 values lie along the lanes, so that a slab's rows
#: are its tiles' sublanes, 16 bfloat16 rows a tile: a window of one tile
#: (128 KB of a 32-head row) where :func:`row_windows`' default of 128
#: rows moves 1 MiB each way -- 18.86 against 20.31-21.88 ms a step of
#: ``serve/pooled.py``'s cache alone on the chip (PERF.md, PR 40)
ROW_WINDOW = 16
#: what the step of a family whose layers route says of its routing
#: behind the slots' tokens (:func:`routing_says`)
ROUTING = ("experts_touched", "expert_load_max", "assigned")


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
    (:data:`ROW_WINDOW`)."""
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


def put_rows(slab, rows, at):
    """``rows`` ``[1, G, n, D]`` of one layer and slot into ``slab`` at
    ``at``, in place.  (No index is negative: normalising them is a
    third of a prefill's tracing time, once a bucket.)"""
    return jax.lax.dynamic_update_slice(slab, rows[None], at,
                                        allow_negative_indices=False)


def of_slot(part, li, slot, drop: int = 2):
    """Layer ``li``, slot ``slot`` of a part ``[layers, slots, ...]``
    without its first ``drop`` axes (1: a slab's rows ``[1, G, S, D]``;
    2: a state a slot), by one dynamic slice (taking the layer first
    would materialise its slots)."""
    return jax.lax.dynamic_slice(
        part, (li, slot) + (0,) * (part.ndim - 2), (1, 1) + part.shape[2:],
        allow_negative_indices=False)[(0,) * drop]


def to_slot(part, li, slot, new):
    """``new`` as layer ``li``, slot ``slot`` of a part that keeps a
    state a slot, in place."""
    return jax.lax.dynamic_update_slice(
        part, new[None, None].astype(part.dtype),
        (li, slot) + (0,) * (part.ndim - 2), allow_negative_indices=False)


def visible(pos, live):
    """What a slot may see, as a kernel that walks a slab's live tiles
    takes it: its first ``pos + 1`` rows, and none where the step is not
    for it."""
    return jnp.where(live, pos + 1, 0)


def kv_rows(contexts, stores) -> dict:
    """The ``kv_*`` attrs of a decode step over ``stores`` of rows a
    position (:class:`FullRows`, :class:`RingRows`), for the
    ``contexts`` the engine hands ``read``.  A slot's context has to
    read ``min(context, rows)`` rows of a store for each layer that
    READS it (a ring holds no more; a full slab's ``rows`` is
    ``max_seq``, which no context passes) and writes one for each that
    KEEPS it; ``kv_rows_read`` is every row of every slot, which is what
    a body that attends under a mask reads: a step that reads fewer has
    to say so there.  On the host, one vectorised call a store and
    step."""
    contexts = np.asarray(contexts)
    live = read = layers = 0
    for store in stores:
        live += store.readers * int(np.minimum(contexts, store.rows).sum())
        read += store.readers * store.batch * store.rows
        layers += store.layers
    first = stores[0]
    return {"kv_rows_live": live, "kv_rows_read": read,
            "kv_rows_written": layers * len(contexts),
            "kv_row_bytes": 2 * first.heads * first.widths[0]
            * first.dtype.itemsize}


def routing_says(counts):
    """The routing of a decode step over its live slots, from its
    layers' tokens a held expert (``experts.apply``'s counts), as
    :data:`ROUTING` names it: held experts that received a token, the
    busiest expert's tokens, the tokens received in all."""
    with jax.named_scope("moe_router"):
        counts = jnp.stack(counts)
        return [jnp.sum(counts > 0), jnp.max(counts), jnp.sum(counts)]


def routing_attrs(says: dict, held: int) -> dict:
    """:data:`ROUTING` as ``read`` states it, over the ``held`` experts
    the step's routing was counted over."""
    says["experts_held"] = held
    says["expert_load_mean"] = says.pop("assigned") / held
    return says


def _kernel():
    """``ops/pallas/decode_attention.py``, imported when a cache first
    asks which form its attention takes on the TPU and by no module's
    import: a process that traces no such step never pays for the
    package (PERF.md, PR 35)."""
    from kungfu_tpu.ops.pallas import decode_attention

    return decode_attention


# -- the stores: one a kind of content -----------------------------------
class _Rows:
    """Per-head key and value rows of some layers: a slab for K and one
    for V, ``[layers, slots, heads, rows, width]`` each (``width`` a
    pair where V's rows are another width than K's), with ``heads`` the
    key/value heads.  What both kinds of rows share: the shapes and
    their zeros, how a decode step writes its row and attends, and which
    form of each runs -- **kernels are what a family asks for**, by
    ``group`` and ``row_kernel``; a store that is asked for none lowers
    to XLA's forms on every platform."""

    def __init__(self, layers: int, batch: int, heads: int, rows: int, width,
                 dtype, *, readers: int = None, group: int = None,
                 row_kernel: bool = False, scale: float = None):
        self.layers, self.batch, self.heads, self.rows = (layers, batch,
                                                          heads, rows)
        self.widths = (width, width) if isinstance(width, int) else width
        self.dtype = jnp.dtype(dtype)
        #: the layers that attend over these rows, where they are more
        #: than the layers that keep them
        self.readers = layers if readers is None else readers
        #: query heads a key/value head, where the family asks for the
        #: kernel that walks only the tiles a live context reaches
        self.group = group
        #: whether it asks for the kernel that writes a step's rows
        self.row_kernel = row_kernel
        #: what multiplies the scores where it is not ``1 / sqrt(width)``
        self.scale = scale

    @property
    def shapes(self):
        """(of K's slab, of V's)."""
        lead = (self.layers, self.batch, self.heads, self.rows)
        return tuple(lead + (w,) for w in self.widths)

    def new(self):
        return tuple(jnp.zeros(s, self.dtype) for s in self.shapes)

    def empty(self, rows: int):
        """What the restore program writes into a slot of K's slab and
        of V's for ``rows`` positions that hold nothing: zeros, of as
        many rows as the store restores for them (:meth:`restored`)."""
        return tuple(np.zeros((self.layers, self.heads, self.restored(rows),
                               w), self.dtype) for w in self.widths)

    @functools.cached_property
    def tile(self):
        """The key tile with which a decode step's attention over the
        slab is ONE kernel a reading layer that walks only the tiles a
        live context reaches (``ops/pallas/decode_attention.py``), or
        None where it is XLA's two products over every row under a mask
        (``cohere2_moe.attention``): where the family asked for none,
        off the TPU, and for shapes the kernel does not tile.  One
        choice, from the platform and the slab's shape, made when first
        asked for and the same from then on: the step that is traced and
        the span that says which form ran read the same."""
        if self.group is None or jax.default_backend() != "tpu":
            return None
        return _kernel().key_tile(self.rows, self.heads, self.group,
                                  self.widths[0], self.dtype)

    @property
    def kernel(self) -> int:
        """1 where a decode step's attention over the slab is the fused
        kernel, 0 where it is ``cohere2_moe.attention`` (:attr:`tile`):
        ``kv_attn_kernel`` on ``kf:serve.decode_read``."""
        return int(self.tile is not None)

    def writer(self, pos, live):
        """How a decode step at ``pos`` ``[B]`` writes its row of the
        slots it is ``live`` for: ``put(ks, vs, i, kn, vn) -> (ks, vs)``,
        the rows ``kn``/``vn`` ``[B, G, 1, D]`` into layer ``i`` of the
        two slabs.  That is :func:`write_rows` by windows made here,
        once for all the layers' calls.  Where the family asked for the
        row kernel it is ``ops/pallas/row_write.py`` wherever the
        attention is a kernel too -- K and V together, one call a layer
        where the window updates are one a slot and layer -- and
        elsewhere :func:`write_rows` by windows of :data:`ROW_WINDOW`,
        each call working out its own (ROADMAP D18: the one place to
        engage the kernel from, for the families that do not ask yet)."""
        if not self.row_kernel:
            windows = row_windows(self.row(pos), self.rows, live)
            return lambda ks, vs, i, kn, vn: (
                write_rows(ks, i, kn, windows), write_rows(vs, i, vn, windows))
        if self.tile:
            from kungfu_tpu.ops.pallas import row_write

            return lambda ks, vs, i, kn, vn: row_write.write_rows(
                ks, vs, i, kn, vn, self.row(pos), live)

        def put(ks, vs, i, kn, vn):
            windows = row_windows(self.row(pos), self.rows, live, ROW_WINDOW)
            return write_rows(ks, i, kn, windows), write_rows(vs, i, vn,
                                                              windows)

        return put

    def step(self, ks, vs, see, put):
        return _RowsStep(self, ks, vs, see, put)


class _RowsStep:
    """A decode step over a rows store's slabs ``ks``/``vs``: one row a
    slot into a layer (``put``: :meth:`_Rows.writer`), attention over
    the slab itself for the rows ``see`` lets each slot read
    (``FullRows.see``, ``RingRows.see``)."""

    def __init__(self, store, ks, vs, see, put):
        self.store, self.ks, self.vs, self.see, self.put = (store, ks, vs,
                                                            see, put)

    def write(self, i, kn, vn):
        with jax.named_scope("kv_write"):
            self.ks, self.vs = self.put(self.ks, self.vs, i, kn, vn)

    def attend(self, i, q, positions):
        """``q`` ``[B, 1, G, J, D]`` over layer ``i``'s rows."""
        store = self.store
        if store.tile:
            return _kernel().decode_attn(
                q[:, 0], self.ks, self.vs, i, self.see, tile=store.tile,
                scale=store.scale)[:, None]
        return cohere2_moe.attention(q, self.ks[i], self.vs[i], self.see,
                                     store.scale)

    @functools.cached_property
    def walked(self):
        """Rows of K (as many of V) ONE reading layer's attention reads
        in this step: the tiles the kernel walks, counted in the step,
        or every row of every slot where XLA's form runs."""
        store = self.store
        if store.tile:
            return _kernel().rows_walked(self.see, store.tile)
        return store.batch * store.rows

    def parts(self):
        return self.ks, self.vs


class FullRows(_Rows):
    """Rows of every position: ``rows`` is ``max_seq``, position ``p``
    lives at row ``p``, and a page is always whole."""

    @staticmethod
    def row(pos):
        return pos

    @staticmethod
    def restored(rows: int) -> int:
        return rows

    def see(self, pos, visible=None):
        """What each slot of a decode step at ``pos`` may read: the
        count ``visible`` (:func:`visible`) where the attention is the
        kernel, elsewhere the mask of its first ``pos + 1`` rows."""
        if self.tile:
            return visible
        return (jnp.arange(self.rows) <= pos[:, None])[:, None, None, None]

    def prompt(self, ks, vs, start, slot):
        return _FullPrompt(self, ks, vs, start, slot)

    # -- the host's side of a page ---------------------------------------
    @staticmethod
    def pages_to_slot(data, rows: int, page_tokens: int):
        """One part of a cached prefix's pages, ``[L, H, T, W]`` each and
        in order, as ``[L, H, rows, W]``: zeros past the last page."""
        first, t = data[0], page_tokens
        out = np.zeros(first.shape[:2] + (rows,) + first.shape[3:],
                       first.dtype)
        for i, page in enumerate(data):
            out[:, :, i * t:(i + 1) * t] = page
        return out

    @staticmethod
    def rows_of_slot(slab, slot: int, lo: int, hi: int):
        """Positions ``[lo, hi)`` of ``slot`` of a slab ``[L, B, H, S,
        W]`` on the host, ``[L, H, hi - lo, W]``."""
        return np.asarray(jax.device_get(slab[:, slot, :, lo:hi, :]))


class _FullPrompt:
    """A prefill into ``slot`` of a full-rows store's slabs ``ks``/``vs``
    past the ``start`` positions the slot already holds: a layer's new
    rows go to ``[start, start + P)`` (the padding lands where the decode
    writes before anyone reads), and the slot's rows come out as its
    ``keys`` (keys, values, the first one's position, the window) -- for
    the layer that wrote them and for any that reads the slab behind
    it."""

    def __init__(self, store, ks, vs, start, slot):
        self.store, self.ks, self.vs, self.start, self.slot = (
            store, ks, vs, start, slot)

    def write(self, i, kn, vn):
        at = (i, self.slot, 0, self.start, 0)
        with jax.named_scope("kv_write"):
            self.ks, self.vs = put_rows(self.ks, kn, at), put_rows(self.vs,
                                                                   vn, at)
        self.keys = (of_slot(self.ks, i, self.slot, 1),
                     of_slot(self.vs, i, self.slot, 1), 0, None)

    def attend(self, i, q, positions):
        """``q`` ``[1, C, G, J, D]`` at ``positions`` ``[1, C]`` over
        the keys the last ``write`` left, by blocks of query rows over
        the keys each may see, so that no ``[heads, P, S]`` scores
        exist."""
        return cohere2_moe.blocked_attention(
            q, *self.keys[:2], positions[0], *self.keys[2:], self.store.scale)

    parts = _RowsStep.parts


class RingRows(_Rows):
    """The last ``rows = min(window, max_seq)`` positions of a slot:
    position ``p`` lives at row ``p % rows``, stored already rotated, so
    a row needs no position to be read; a row is valid once written for
    this request, which the mask works out from the slot's position
    alone.  A slot of 8192 positions then costs a window layer 4096
    rows, however long its context.  A decode step writes no row of a
    slot it is not live for: a ring holds exactly ``rows``, so a row too
    many would overwrite one that is yet to be committed."""

    def __init__(self, layers, batch, heads, window: int, max_seq: int,
                 width, dtype, **asks):
        super().__init__(layers, batch, heads, min(window, max_seq), width,
                         dtype, **asks)
        self.window = window

    def row(self, pos):
        return pos % self.rows

    def restored(self, rows: int) -> int:
        return self.rows        # a ring is restored whole

    def see(self, pos, visible=None):
        """What each slot of a decode step at ``pos`` may read: where
        the attention is the kernel the count of its first rows, all of
        them once the request is past the window (softmax does not mind
        the order); elsewhere the mask of the rows written for this
        request."""
        if self.tile:
            return jnp.minimum(visible, self.rows)
        rows = jnp.arange(self.rows)
        # ring row r holds the last position <= pos that lands on it
        return (pos[:, None] - (pos[:, None] - rows) % self.rows >= 0
                )[:, None, None, None]

    def prompt(self, ks, vs, p_len: int, last, start, slot):
        return _RingPrompt(self, ks, vs, p_len, last, start, slot)

    # -- the host's side of a page ---------------------------------------
    def pages_to_slot(self, data, n_cached: int, page_tokens: int):
        """The window layers' part of a cached prefix's pages, ``[L, G,
        T, D]`` each and in order, as the ring the restore program
        writes: the last ``rows`` of ``n_cached`` positions at their
        ring rows."""
        (ring, _), w, t = self.empty(0), self.rows, page_tokens
        for i, page in enumerate(data):
            if (i + 1) * t > n_cached - w:
                ring[:, :, np.arange(i * t, (i + 1) * t) % w] = page
        return ring

    def rows_of_slot(self, slab, slot: int, lo: int, hi: int, total: int):
        """Positions ``[lo, hi)`` of a finished request of ``total``
        tokens as ``[L, G, hi - lo, D]``, and the first position whose
        rows still exist: rows were made for positions ``[0, total -
        1)``, and the ring keeps the last ``rows`` of them (earlier ones
        read zero here, and their pages are not whole)."""
        w = self.rows
        ring = np.asarray(jax.device_get(slab[:, slot]))
        out = np.zeros(ring.shape[:2] + (hi - lo,) + ring.shape[3:],
                       ring.dtype)
        kept_from = max(0, total - 1 - w)
        at = np.arange(max(lo, kept_from), hi)
        out[:, :, at - lo] = ring[:, :, at % w]
        return out, kept_from


class _RingPrompt(_FullPrompt):
    """The same over a ring store, of ``p_len`` positions, the last
    REAL one at ``last``: a layer's
    keys are the ring before (in position order) and the new rows, and
    the ring is then written so that it ends up holding the last
    ``rows`` real positions, whichever of them are new."""

    def __init__(self, store, ks, vs, p_len, last, start, slot):
        super().__init__(store, ks, vs, start, slot)
        ring = store.rows
        rows = jnp.arange(ring)
        # the ring before: position start - W + i sits at row (start + i) % W
        self.unroll = (start + rows) % ring
        # the ring after: row r holds the last real position landing on it,
        # a new one where that is not before ``start``
        held = last - (last - rows) % ring
        self.fresh = (held >= start)[:, None]
        self.source = jnp.clip(held - start, 0, p_len - 1)

    def write(self, i, kn, vn):
        store, slot = self.store, self.slot
        # (the barrier: the slot's old rows are taken out before the ring
        # is written, or the compiler, reading them where they are used,
        # copies the whole slab to keep them)
        old_k, old_v = jax.lax.optimization_barrier(
            (of_slot(self.ks, i, slot, 1), of_slot(self.vs, i, slot, 1)))
        self.keys = (
            jnp.concatenate([old_k[:, :, self.unroll], kn], axis=2),
            jnp.concatenate([old_v[:, :, self.unroll], vn], axis=2),
            self.start - store.rows, store.window)
        with jax.named_scope("kv_write"):
            self.ks = put_rows(
                self.ks, jnp.where(self.fresh, kn[:, :, self.source], old_k),
                (i, slot, 0, 0, 0))
            self.vs = put_rows(
                self.vs, jnp.where(self.fresh, vn[:, :, self.source], old_v),
                (i, slot, 0, 0, 0))


class SlotStates:
    """A state and a tail a slot: what ``layers`` recurrent layers keep
    of a request however long it is -- ``state`` ``[1, B, ...]`` in
    float32 in part ``k``, and in part ``v`` the ``taps - 1`` last
    inputs of the short convolution before it, ``[1, B, taps - 1, C]``.
    Each is an array a LAYER and not one for all of them: a decode step
    reads a layer's state, works out the correction and writes it back
    where it was, and out of one array for all the layers the TPU
    compiler first copies the layer it is about to update (537 MB a
    layer and step at ``serve/recurrent.py``'s sizes, seen in the
    compiled step; an array of its own it updates where it lies).

    The update itself is the family's (``delta_rule``,
    ``selective_scan``) and is handed in; the frame around it is here,
    and with it what the module's docstring says a state a slot owes: a
    prefill goes on from the slot's state and tail where ``start > 0``
    and from nothing where it is 0 -- that is how a reused slot forgets
    the request before."""

    def __init__(self, layers: int, batch: int, state: tuple, tail: tuple,
                 dtype):
        self.layers, self.batch = layers, batch
        #: (ONE layer's state, ONE layer's tails)
        self.shapes = ((1, batch) + tuple(state), (1, batch) + tuple(tail))
        self.dtypes = (STATE_DTYPE, jnp.dtype(dtype))
        #: a slot's states, which a decode step reads (and writes back)
        #: for the slots it moves, and every slot's tails, which it moves
        #: whatever is live
        self.slot_state_bytes = layers * int(
            np.prod(state) * STATE_DTYPE.itemsize)
        self.tail_bytes = layers * int(
            np.prod(self.shapes[1]) * self.dtypes[1].itemsize)

    def new(self):
        return tuple(tuple(jnp.zeros(s, d) for _ in range(self.layers))
                     for s, d in zip(self.shapes, self.dtypes))

    def empty(self, rows: int):
        """An empty state and an empty tail, whatever ``rows`` is (host
        arrays, only read: one for all the layers will do)."""
        return tuple((np.zeros(s[:1] + s[2:], d),) * self.layers
                     for s, d in zip(self.shapes, self.dtypes))

    def attrs(self, moved: int) -> dict:
        """What ``read`` states of a step that moved ``moved`` slots'
        states (its own count where a kernel skips dead slots, every
        slot where the update is an elementwise pass over them all)."""
        return {"state_slots_read": moved, "state_bytes_read":
                moved * self.slot_state_bytes + self.tail_bytes}

    def step(self, state, tails, live):
        return _StatesStep(state, tails, live)

    def prompt(self, state, tails, n, start, slot):
        return _StatesPrompt(state, tails, n, start, slot)


class _StatesStep:
    """A decode step over the layers' ``state`` and ``tails``: one token
    into every slot's, but a slot the step is not ``live`` for keeps its
    tail (XLA's select, which still moves every slot's) and -- the
    update's business -- its state."""

    def __init__(self, state, tails, live):
        self.state, self.tails, self.live = list(state), list(tails), live

    def convolve(self, i, u, w):
        """The convolution ``w`` ``[taps, C]`` at the new inputs ``u``
        ``[B, 1, C]`` of layer ``i`` -> ``[B, 1, C]`` float32."""
        seen = jnp.concatenate([self.tails[i][0], u], axis=1)  # [B, taps, C]
        self.tails[i] = jnp.where(self.live[:, None, None], seen[:, 1:],
                                  self.tails[i][0])[None]
        return jnp.einsum("btc,tc->bc", seen.astype(F32),
                          w.astype(F32))[:, None]

    @jax.named_scope("attn_core")
    def recur(self, i, scope: str, update):
        """``update(state) -> (new state, out [B, ...])`` on layer
        ``i``'s, under the ``scope`` the device trace knows it by."""
        with jax.named_scope(scope):
            self.state[i], out = update(self.state[i])
        return out[:, None]

    def parts(self):
        return tuple(self.state), tuple(self.tails)


class _StatesPrompt:
    """A prefill of ``n`` real tokens from ``start`` into ``slot``, in
    the chunked forms: the bucket's padding past ``n`` is masked so that
    what is written back is the state of exactly ``n`` tokens and the
    tail at ``n - taps + 1 .. n - 1``."""

    def __init__(self, state, tails, n, start, slot):
        self.state, self.tails = list(state), list(tails)
        self.n, self.slot, self.goes_on = n, slot, start > 0

    def convolve(self, i, u, w):
        """``delta_rule.causal_conv`` of ``u`` ``[1, P, C]`` behind the
        slot's tail -> ``[1, P, C]`` float32."""
        tail = jnp.where(self.goes_on, of_slot(self.tails[i], 0, self.slot),
                         0)
        y, tail = causal_conv(u[0], w, tail, self.n)
        with jax.named_scope("kv_write"):
            self.tails[i] = to_slot(self.tails[i], 0, self.slot, tail)
        return y[None]

    @jax.named_scope("attn_core")
    def recur(self, i, scope: str, chunked):
        """``chunked(state before) -> (out [P, ...], state after n)`` on
        layer ``i``'s, under ``scope``."""
        with jax.named_scope(scope):
            before = jnp.where(self.goes_on, of_slot(
                self.state[i], 0, self.slot).astype(F32), 0.0)
            out, after = chunked(before)
        with jax.named_scope("kv_write"):
            self.state[i] = to_slot(self.state[i], 0, self.slot, after)
        return out[None]

    parts = _StatesStep.parts


# -- what a block is handed ----------------------------------------------
class Pass:
    """What ``arch.block`` is handed, in a decode step and in a prefill,
    by a family whose layers keep per-head rows and states in stores:
    ``passes`` are its stores' steps (or prompts) in the parts' order,
    and ``caches.where(li)`` says of layer ``li`` (which of them it
    keeps its past in -- or reads, where it keeps none --, its place
    there, the scope the device trace knows its attention by).  The
    family derives it for what is its own: a recurrent layer's
    ``recur``, and a step's ``says``."""

    def __init__(self, caches, *passes):
        self.caches, self.passes = caches, passes

    def of(self, li):
        store, i, scope = self.caches.where(li)
        return self.passes[store], i, scope

    def write(self, li, kn, vn):
        rows, i, _ = self.of(li)
        rows.write(i, kn, vn)

    @jax.named_scope("attn_core")
    def attend(self, li, q, positions):
        rows, i, scope = self.of(li)
        with jax.named_scope(scope):
            return rows.attend(i, q, positions)

    def convolve(self, li, u, w):
        states, i, _ = self.of(li)
        return states.convolve(i, u, w)

    def parts(self):
        return tuple(zip(*(each.parts() for each in self.passes)))


# -- the body ------------------------------------------------------------
class Caches:
    """What every family's cache manager is: the frame of the two
    programs, a decode step's ``out`` and its reading, the empty parts.
    A family derives it and supplies

    * ``stores``: what its parts are made of, in the parts' order -- one
      store's halves are the parts themselves, several stores' are the
      parts' entries;
    * ``says``: the names of what its step's ``out`` holds behind the
      tokens, and ``attrs(says, contexts)``: what ``read`` makes of them;
    * ``step(k, v, pos, live)`` and ``prompt(k, v, p_len, n, start,
      slot)``: the object its ``arch.block`` is handed in a decode step
      and in a prefill, with ``parts()`` giving back ``(k, v)`` and, the
      step's, ``says(counts)`` the vector of what :attr:`says` names;
    * ``block``, its ``arch.block``, where its layers are the plain loop
      of :meth:`layers`; where they are not, :meth:`layers` and
      :meth:`prompt_row` themselves."""

    #: what a decode step's ``out`` says behind the slots' tokens
    says: tuple = ()
    stores: tuple = ()

    def __init__(self, model, max_batch: int, max_seq: int):
        self.model, self.cfg = model, model.cfg
        self.batch, self.seq = int(max_batch), int(max_seq)

    @staticmethod
    def place(*groups) -> dict:
        """layer -> its place in its own group's store."""
        return {li: i for group in groups for i, li in enumerate(group)}

    # -- the parts ---------------------------------------------------------
    def _parts(self, of):
        halves = tuple(zip(*(of(store) for store in self.stores)))
        return halves if len(self.stores) > 1 else tuple(h[0] for h in halves)

    def new_slabs(self):
        """The device cache as the pair ``(k, v)``."""
        return self._parts(lambda store: store.new())

    def empty_pages(self, rows: int):
        """What the restore program writes into a slot for ``rows``
        cached positions that hold nothing, one for each part (each a
        tree shaped like its part of the slabs, without the slot axis):
        every store's zeros."""
        return self._parts(lambda store: store.empty(rows))

    # -- the two forward passes ------------------------------------------
    def logits(self, params, h):
        return self.model.logits(params, h)

    def layers(self, params, h, positions, cache, live=None):
        """The plain loop: every layer's ``block`` over ``h`` with the
        pass's ``cache`` -- a decode step's ``[B, 1, d]`` at
        ``positions`` ``[B]`` where ``live`` is given, else a prompt's
        ``[1, P, d]`` at ``positions`` ``[P]`` -> (``h``, the layers'
        routing counts)."""
        decode, counts = live is not None, []
        for li in range(self.cfg.n_layers):
            h, count = self.block(
                self.cfg, params[f"layer_{li}"], li, h,
                positions[:, None] if decode else positions[None], cache,
                dense=decode, live=live[:, None] if decode else None)
            if count is not None:
                counts.append(count)
        return h, counts

    def decode(self, params, k, v, last_ids, pos, live):
        """The body of the decode program: one token for every slot
        (``last_ids``/``pos``/``live`` ``[B]``).  ``live`` says which
        slots the step is for: any other computes what nobody reads,
        writes no row, keeps its state and is counted nowhere (the
        engine dispatches a step before it has read the one before, so a
        slot it leaves out may hold a request that is finishing and
        whose rows are yet to be committed: docs/serving.md).  Returns
        the parts and ``out``, ONE int32 vector: the ``B`` tokens (the
        next step takes them from it on the device), then what the step
        says of itself (:attr:`says`), so that the host's one read
        brings both."""
        step = self.step(k, v, pos, live)
        h = self.model.embed(params, last_ids[:, None])
        h, counts = self.layers(params, h, pos, step, live)
        tok = jnp.argmax(self.logits(params, h[:, 0]), axis=-1)
        return (*step.parts(), jnp.concatenate(
            [tok, step.says(counts)]).astype(jnp.int32))

    def prompt_row(self, params, h, positions, prompt, n):
        """The prompt's stream ``h`` through the layers -> row ``n - 1``
        of it, ``[1, d]``."""
        h, _ = self.layers(params, h, positions, prompt)
        return jax.lax.dynamic_index_in_dim(h, n - 1, axis=1, keepdims=False)

    def prefill(self, params, k, v, ids, n, start, slot):
        """The body of the prefill program: ``ids`` ``[P]`` (the prompt
        past the ``start`` positions the slot already holds, zero-padded
        past ``n``) into ``slot``; returns the parts and the greedy
        token after row ``n - 1``."""
        positions = start + jnp.arange(ids.shape[0])
        prompt = self.prompt(k, v, ids.shape[0], n, start, slot)
        h = self.model.embed(params, ids[None])
        tok = self.greedy(params, self.prompt_row(params, h, positions,
                                                  prompt, n))
        return (*prompt.parts(), tok)

    def greedy(self, params, row):
        return jnp.argmax(self.logits(params, row)[0], axis=-1
                          ).astype(jnp.int32)

    # -- a decode step's ``out`` ---------------------------------------------
    def new_out(self):
        """What stands for a step's ``out`` before any step ran."""
        return jnp.zeros(self.batch + len(self.says), jnp.int32)

    def read(self, out, contexts):
        """A decode step's ``out`` on the host (the host waits here):
        the slots' tokens, and what the step says of itself as attrs of
        the ``kf:serve.decode_read`` span that is open meanwhile.
        ``contexts`` is the engine's: the positions each row of the step
        attended over, its own new one among them, for the rows whose
        request is still there when the step is read (one that ended on
        ``eos_id`` in the step before was not ``live`` in the program
        either)."""
        out = np.asarray(jax.device_get(out))
        says = dict(zip(self.says, out[self.batch:].tolist()))
        return out[:self.batch], self.attrs(says, contexts)

    # -- the host's side of a page: every position of every layer kept -------
    @staticmethod
    def pages_to_slot(data, n_cached: int, rows: int, page_tokens: int):
        """ONE part of a cached prefix of ``n_cached`` positions -- that
        part of its pages, ``[L, H, T, W]`` each and in order -- as what
        the restore program writes for ``rows`` positions."""
        return FullRows.pages_to_slot(data, rows, page_tokens)

    @staticmethod
    def rows_of_slot(slab, slot: int, lo: int, hi: int, total: int):
        """Positions ``[lo, hi)`` of one part of a finished request of
        ``total`` tokens as page data ``[L, H, hi - lo, W]``, and the
        first position all of whose layers' rows still exist (pages
        before it are not ``whole``: ``KVCachePool.reusable``)."""
        return FullRows.rows_of_slot(slab, slot, lo, hi), 0

    # -- what a prefill and a decode step over its live contexts cost,
    # analytically (the serving MFU gauge) ------------------------------------
    def prefill_flops(self, tokens: int, start: int = 0) -> int:
        return self.model.prefill_flops(tokens, start)

    def decode_flops(self, contexts) -> int:
        return self.model.decode_flops(contexts)


class DenseCaches(Caches):
    """The dense ``Transformer`` through the engine: K and V one slab
    ``[L, B, H, S, D]`` each in the compute dtype, every layer keeping
    every position.  Of the body it takes the parts, ``out`` and the
    host's side of a page; its two passes are its own layer code
    (ROADMAP D1)."""

    def __init__(self, model, max_batch: int, max_seq: int):
        super().__init__(model, max_batch, max_seq)
        cfg = self.cfg
        self.stores = (FullRows(cfg.n_layers, self.batch, cfg.n_heads,
                                self.seq, cfg.head_dim, cfg.compute_dtype),)
        #: of K's slab, and of V's
        self.shape = self.stores[0].shapes[0]

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

    def attrs(self, says, contexts):
        return kv_rows(contexts, self.stores)

    # -- what a forward pass costs (the serving MFU gauge) ---------------
    def prefill_flops(self, tokens: int, start: int = 0) -> int:
        return costmodel.serve_prefill_flops(self.cfg, tokens, start)

    def decode_flops(self, contexts) -> int:
        return sum(costmodel.serve_decode_flops(self.cfg, int(n))
                   for n in contexts)
