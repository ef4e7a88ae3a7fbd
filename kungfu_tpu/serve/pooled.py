"""The cache of a model whose attention reads **rows that stand for
several positions** (``models/evabyte.py``: EVA attention).  A layer
keeps, a slot and head,

* the **exact rows** of the open window: the rotated key and the value
  of every position since the last multiple of ``W = window_size``, at
  most ``W`` of them -- rows a position, which all stop being needed
  together when the window closes; and
* one **chunk row** for every ``C = chunk_size`` positions: a key and a
  value pooled from the chunk's ``C`` exact rows by a learned softmax
  (``evabyte.pool_chunks``) when the chunk is complete.  It is MADE from
  other rows of the cache, and no query sees it until the window it lies
  in has closed.

Both live in one slab a part, ``k`` and ``v`` ``[L, B, H, W + max_seq /
C, D]``: rows ``[0, W)`` are the open window's (position ``t`` at row ``t
mod W``), row ``W + c`` is chunk ``c``'s.  What a query at ``t`` may read
is then a matter of the mask alone (``visible_rows``): the exact rows
``j <= t mod W`` and the chunk rows ``c < (W / C) (t // W)``.  **Closing
a window moves nothing**: the next position's mask lets its chunk rows
in and its exact rows out, and those are overwritten one by one as the
new window fills.  At ``max_seq`` 32,768, ``W`` 2,048 and ``C`` 16 a slot
and layer is 4,096 rows where full attention keeps 32,768.

:class:`PooledCaches` is what ``InferenceEngine`` asks of such a model
(``serve/caches.py``: the body's decode frame and ``out``; the slab, what
a step does to it and the prefill's walk are its own):

* the **decode** step, for every slot: writes the new row
  (``caches.write_rows``); pools the chunk the new row lies in from the
  slab's own rows and writes that chunk row -- EVERY step, finished or
  not: the row of an unfinished chunk belongs to the open window, so no
  query can see it, and the step that completes the chunk overwrites it
  with the pooling of all ``C`` rows, those a prefill left among them;
  and attends over the slab -- on the TPU one kernel a layer,
  ``ops/pallas/decode_attention.py``, which is handed the slab whole
  and what each slot may see as TWO RUNS of its rows (``visible_runs``:
  ``pos mod W + 1`` exact rows from row 0, ``(W / C) (pos div W)`` chunk
  rows from row ``W``; none where the step is not live for the slot)
  and walks only the key tiles of those runs, under ONE softmax; off
  the TPU, and at sizes the kernel does not tile (the tests' heads of
  16; a window that is not whole tiles), ``evabyte.eva_attention`` over
  every row under ``visible_rows``; ``eva_attn_kernel`` on
  ``kf:serve.decode_read`` says which, and ``kv_rows_read`` and
  ``summary_rows_read`` the rows of each run the step itself counted as
  read.  So the three events of a slot's life (a row, a chunk
  completed, a window closed) cost the same operations every step: no
  branch, no shape that follows a position (ROADMAP's lesson from PR
  26); of the times, the attention kernel's follows the live contexts
  (the tiles it skips; PERF.md, PR 41).  A slot the step is not
  ``live`` for keeps every row as it was;
* the **prefill** walks the prompt a window at a time inside the one
  program, so that its temporaries are a window's and not the bucket's:
  each window's rows go into the slot's exact rows, attend to themselves
  and to the chunk rows of the windows before (``ATTN_BLOCK`` query rows
  at a time), and are then pooled into the window's chunk rows.  Windows
  wholly past ``n`` are never walked; of the last one, rows past ``n``
  and chunks that ``n`` cuts are written as zeros, so what a padded
  bucket leaves is exactly the rows and chunk rows of ``n`` positions.
  A prefill always starts a slot from nothing: whatever the slot held
  lies past the mask until it is overwritten.

Pages: a page of positions would hold a closed window's chunk rows whole
and exact rows only for an unfinished window; the rule by which a prefix
could be restored from such pages is not built (docs/serving.md), so
this family's pages are never handed on (``PageSpec.unpaged``): the
engine looks up no prefix, commits nothing, never calls a prefill with
``start > 0`` and never asks for ``rows_of_slot`` or ``pages_to_slot``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from kungfu_tpu.models import evabyte as arch
from kungfu_tpu.serve.caches import (ROW_WINDOW, Caches, FullRows, kv_rows,
                                     row_windows, write_rows)

#: query rows a prefill attends at once (the scores of one block,
#: ``[heads, ATTN_BLOCK, rows]`` float32, are its largest temporary:
#: 134 MB at 32 heads over 4,096 rows)
ATTN_BLOCK = 256


class _Slab(FullRows):
    """K's slab and V's, ``W`` exact rows and ``max_seq / C`` chunk rows
    a slot and layer."""

    def __init__(self, layers, batch, heads, exact, chunks, width, dtype):
        super().__init__(layers, batch, heads, exact + chunks, width, dtype)
        self.exact = exact

    def restored(self, rows: int) -> int:
        """Zero exact rows, as many as the open window of ``rows``
        positions could hold (the engine's warm-up asks)."""
        return min(rows, self.exact)


class _Step:
    """A decode step's cache: one row a slot into the slab, the chunk it
    lies in pooled again from the slab, attention over the slab
    itself."""

    def __init__(self, caches, k, v, pos, live):
        cfg = caches.cfg
        self.caches, self.k, self.v, self.pos, self.live = (caches, k, v, pos,
                                                            live)
        w, c = caches.exact, cfg.chunk_size
        rows = caches.shape[3]
        self.row_at = row_windows(pos % w, rows, live, ROW_WINDOW)
        self.chunk_at = row_windows(w + pos // c, rows, live, ROW_WINDOW)
        self.chunk_from = pos % w // c * c  # the new row's chunk, in the slab
        self.tile = caches.attn_tile
        if self.tile:
            from kungfu_tpu.ops.pallas import decode_attention as kernel

            self.kernel = kernel
            self.visible = caches.visible_runs(pos, live)
            self.walked = [kernel.rows_walked(n, self.tile)
                           for n in self.visible]
        else:       # XLA's form reads every row of every slot under a mask
            self.see = caches.visible_rows(pos)[:, None, None, :]
            self.walked = [caches.batch * w, caches.batch * caches.chunks]

    def write(self, li, kn, vn, mu, phi):
        cfg = self.caches.cfg
        size = (1, 1, cfg.n_heads, cfg.chunk_size, cfg.head_dim)
        with jax.named_scope("kv_write"):
            self.k = write_rows(self.k, li, kn, self.row_at)
            self.v = write_rows(self.v, li, vn, self.row_at)
            with jax.named_scope("eva_pool"):
                chunk = lambda slab: jnp.concatenate([
                    jax.lax.dynamic_slice(
                        slab, (li, b, 0, self.chunk_from[b], 0), size,
                        allow_negative_indices=False)[0]
                    for b in range(self.caches.batch)])[:, :, None]
                kp, vp = arch.pool_chunks(chunk(self.k), chunk(self.v), mu,
                                          phi)
                self.k = write_rows(self.k, li, kp, self.chunk_at)
                self.v = write_rows(self.v, li, vp, self.chunk_at)

    @jax.named_scope("attn_core")
    def attend(self, li, q, positions):
        with jax.named_scope("eva_attn"):
            if self.tile:   # one query head a key/value head: [B, H, 1, D]
                return self.kernel.decode_attn(
                    q[:, 0, :, None], self.k, self.v, li, self.visible,
                    tile=self.tile, starts=(0, self.caches.exact)
                )[:, None, :, 0]
            return arch.eva_attention(q, self.k[li], self.v[li], self.see)

    def parts(self):
        return self.k, self.v

    def says(self, counts):
        """Each summed over the layers: the chunks the step completed,
        and the exact rows and the chunk rows its attention read."""
        c = self.caches.cfg.chunk_size
        return self.caches.cfg.n_layers * jnp.stack(
            [jnp.sum(self.live & (self.pos % c == c - 1)), *self.walked])


class PooledCaches(Caches):
    says = ("summary_rows_written", "kv_rows_walked", "summary_rows_walked")

    def __init__(self, model: arch.EvaByte, max_batch: int, max_seq: int):
        super().__init__(model, max_batch, max_seq)
        cfg = self.cfg
        if self.seq % cfg.chunk_size or (
                self.seq > cfg.window_size and self.seq % cfg.window_size):
            raise ValueError(
                f"max_seq {self.seq} is not whole windows of "
                f"{cfg.window_size} (or, under one, whole chunks of "
                f"{cfg.chunk_size})")
        #: exact rows and chunk rows of a slot and layer
        self.exact, self.chunks = cfg.window_size, self.seq // cfg.chunk_size
        self.stores = (_Slab(cfg.n_layers, self.batch, cfg.n_heads,
                             self.exact, self.chunks, cfg.head_dim,
                             cfg.compute_dtype),)
        #: of K's slab, and of V's
        self.shape = self.stores[0].shapes[0]
        #: the open window's exact rows as ``read`` counts them: rows
        #: ``[0, W)`` of the slab, no array of their own
        self.open = FullRows(cfg.n_layers, self.batch, cfg.n_heads,
                             self.exact, cfg.head_dim, cfg.compute_dtype)

    def visible_rows(self, pos, n_chunks=None):
        """Which rows of a slot's slab a query at each of ``pos``
        ``[...]`` may read, ``[..., W + n_chunks]``: the open window's
        exact rows up to its own and the closed windows' chunk rows."""
        w = self.exact
        n_chunks = self.chunks if n_chunks is None else n_chunks
        exact = jnp.arange(w) <= (pos % w)[..., None]
        chunk = jnp.arange(n_chunks) < (
            self.cfg.window_chunks * (pos // w))[..., None]
        return jnp.concatenate([exact, chunk], axis=-1)

    def visible_runs(self, pos, live):
        """:meth:`visible_rows` of a decode step's ``pos`` ``[B]`` as
        the kernel takes it, ``[2, B]``: the rows are two runs of the
        slab's, the open window's exact rows from row 0 and the closed
        windows' chunk rows from row ``W``; how many of each a slot may
        see, none where the step is not ``live`` for it."""
        w = self.exact
        return jnp.where(live, jnp.stack(
            [pos % w + 1, self.cfg.window_chunks * (pos // w)]), 0)

    # -- the two forward passes ------------------------------------------
    def logits(self, params, h):
        return self.model.next_logits(params, h)

    def step(self, k, v, pos, live):
        return _Step(self, k, v, pos, live)

    def layers(self, params, h, positions, step, live):
        cfg = self.cfg
        for li in range(cfg.n_layers):
            h = arch.block(cfg, params[f"layer_{li}"], li, h,
                           positions[:, None], step)
        return h, ()

    def attrs(self, says, contexts):
        """What the step's ``contexts`` had to read of each kind of row
        beside what it did read: a context of ``c`` positions has ``c -
        W floor((c - 1) / W)`` exact rows a layer (``kv_rows_*``) and
        ``(W / C) floor((c - 1) / W)`` chunk rows (``summary_rows_*``);
        the rows it read of each kind (the tiles of each run its kernel
        walked, or every row of every slot) and the chunks it completed
        the step counted itself; ``eva_attn_kernel`` says which form of
        the attention ran."""
        exact, chunk = self.model.rows_seen(contexts)
        says.update(kv_rows(exact, (self.open,)))
        says["summary_rows_live"] = self.cfg.n_layers * int(chunk.sum())
        says["kv_rows_read"] = says.pop("kv_rows_walked")
        says["summary_rows_read"] = says.pop("summary_rows_walked")
        says["eva_attn_kernel"] = self.eva_attn_kernel
        return says

    @functools.cached_property
    def attn_tile(self):
        """The key tile with which a decode step's attention over the
        slab is ONE kernel a layer that walks only the tiles of a slot's
        two visible runs (``ops/pallas/decode_attention.py``), or None
        where it is ``eva_attention`` over every row under the mask: off
        the TPU, and for shapes the kernel does not tile (a window that
        is not whole tiles among them).  Chosen and imported as
        ``caches.FullRows.tile`` is, for two runs."""
        if jax.default_backend() != "tpu":
            return None
        from kungfu_tpu.ops.pallas import decode_attention

        cfg = self.cfg
        return decode_attention.key_tile(
            self.shape[3], cfg.n_heads, 1, cfg.head_dim, cfg.compute_dtype,
            starts=(0, self.exact))

    @property
    def eva_attn_kernel(self) -> int:
        """1 where a decode step's attention over the slab is the fused
        kernel, 0 where it is ``eva_attention`` (:attr:`attn_tile`)."""
        return int(self.attn_tile is not None)

    def prefill(self, params, k, v, ids, n, start, slot):
        """``ids`` ``[P]`` (the prompt, zero-padded past ``n``) into
        ``slot`` from nothing (``start`` is 0: no page of this family is
        ever restored), a window at a time; returns the greedy token
        after row ``n - 1``."""
        cfg, model = self.cfg, self.model
        w, c = self.exact, cfg.chunk_size
        p = ids.shape[0]
        span = min(p, w)                    # positions walked at once
        if p % span or span % c:
            raise ValueError(
                f"a prefill bucket of {p} is not whole windows of {w} "
                f"(or, under one, whole chunks of {c})")
        blk = ATTN_BLOCK if span % ATTN_BLOCK == 0 else span
        seen = w + p // c                   # rows a query of this bucket reads

        def window(i, carry):
            k, v, _ = carry
            at = i * span
            positions = at + jnp.arange(span)
            real = positions < n

            class Window:
                """A window's cache: its rows into the slot's exact
                rows, attention over the slot's slab, its chunks pooled
                into the slot's chunk rows."""

                def write(_, li, kn, vn, mu, phi):
                    nonlocal k, v
                    whole = (positions[c - 1::c] < n)[:, None]
                    with jax.named_scope("kv_write"):
                        with jax.named_scope("eva_pool"):
                            chunks = lambda t: t.reshape(
                                1, cfg.n_heads, span // c, c, cfg.head_dim)
                            kp, vp = arch.pool_chunks(chunks(kn), chunks(vn),
                                                      mu, phi)
                        def put(slab, new, pooled):
                            slab = jax.lax.dynamic_update_slice(
                                slab, jnp.where(real[:, None], new, 0)[None],
                                (li, slot, 0, 0, 0),
                                allow_negative_indices=False)
                            return jax.lax.dynamic_update_slice(
                                slab, jnp.where(whole, pooled, 0)[None],
                                (li, slot, 0, w + at // c, 0),
                                allow_negative_indices=False)

                        k, v = put(k, kn, kp), put(v, vn, vp)

                @jax.named_scope("attn_core")
                def attend(_, li, q, positions):
                    keys, values = (jax.lax.dynamic_slice(
                        slab, (li, slot, 0, 0, 0),
                        (1, 1, cfg.n_heads, seen, cfg.head_dim),
                        allow_negative_indices=False)[0] for slab in (k, v))

                    def some(j):
                        qb = jax.lax.dynamic_slice_in_dim(q, j * blk, blk, 1)
                        pb = jax.lax.dynamic_slice_in_dim(positions[0],
                                                          j * blk, blk)
                        see = self.visible_rows(pb, p // c)
                        return arch.eva_attention(qb, keys, values, see)[0]

                    with jax.named_scope("eva_prefill"):
                        return jax.lax.map(some, jnp.arange(span // blk)
                                           ).reshape(q.shape)

            h = model.embed(params, jax.lax.dynamic_slice_in_dim(
                ids, at, span)[None])
            for li in range(cfg.n_layers):
                h = arch.block(cfg, params[f"layer_{li}"], li, h,
                               positions[None], Window())
            # (of the last window walked, the one row ``n - 1`` lies in)
            return k, v, jax.lax.dynamic_index_in_dim(
                h, (n - 1) % span, axis=1, keepdims=False)

        k, v, row = jax.lax.fori_loop(
            0, (n + span - 1) // span, window,
            (k, v, jnp.zeros((1, cfg.d_model), arch.F32)))
        return k, v, self.greedy(params, row)

