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
(the interface is in ``serve/caches.py``).  Both bodies drive the model's
ONE ``block``:

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
import numpy as np

from kungfu_tpu.models import evabyte as arch
from kungfu_tpu.serve.caches import kv_rows, row_windows, write_rows

#: query rows a prefill attends at once (the scores of one block,
#: ``[heads, ATTN_BLOCK, rows]`` float32, are its largest temporary:
#: 134 MB at 32 heads over 4,096 rows)
ATTN_BLOCK = 256
#: rows of the aligned window a decode step's row write reads and writes
#: back (``caches.write_rows``).  Heads of 128 lie along the lanes, so a
#: slab's rows are its tiles' sublanes, 16 bfloat16 rows a tile: a window
#: of one tile (128 KB of a 32-head row) where ``row_windows``' default of
#: 128 rows moves 1 MiB each way -- 18.86 against 20.31-21.88 ms a step
#: of this cache alone on the chip (PERF.md, PR 40)
ROW_WINDOW = 16
#: what a decode step's ``out`` says behind the slots' tokens
_SAYS = ("summary_rows_written", "kv_rows_walked", "summary_rows_walked")


class PooledCaches:
    def __init__(self, model: arch.EvaByte, max_batch: int, max_seq: int):
        self.model = model
        cfg = self.cfg = model.cfg
        self.batch, self.seq = int(max_batch), int(max_seq)
        if self.seq % cfg.chunk_size or (
                self.seq > cfg.window_size and self.seq % cfg.window_size):
            raise ValueError(
                f"max_seq {self.seq} is not whole windows of "
                f"{cfg.window_size} (or, under one, whole chunks of "
                f"{cfg.chunk_size})")
        #: exact rows and chunk rows of a slot and layer
        self.exact, self.chunks = cfg.window_size, self.seq // cfg.chunk_size
        #: of K's slab, and of V's
        self.shape = (cfg.n_layers, self.batch, cfg.n_heads,
                      self.exact + self.chunks, cfg.head_dim)
        self.prefill_flops = model.prefill_flops
        self.decode_flops = model.decode_flops

    def new_slabs(self):
        dt = self.cfg.compute_dtype
        return jnp.zeros(self.shape, dt), jnp.zeros(self.shape, dt)

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
    def decode(self, params, k, v, last_ids, pos, live):
        """One token for every slot (``last_ids``/``pos``/``live``
        ``[B]``; a slot that is not live computes what nobody reads and
        writes nothing).  Returns the slabs and ONE int32 vector: the
        ``B`` tokens, then what the step says of itself, each summed
        over the layers (:data:`_SAYS`): the chunks it completed, and
        the exact rows and the chunk rows its attention read."""
        cfg, model = self.cfg, self.model
        w, c = self.exact, cfg.chunk_size
        rows = self.shape[3]
        row_at = row_windows(pos % w, rows, live, ROW_WINDOW)
        chunk_at = row_windows(w + pos // c, rows, live, ROW_WINDOW)
        chunk_from = pos % w // c * c       # the new row's chunk, in the slab
        size = (1, 1, cfg.n_heads, c, cfg.head_dim)
        tile = self.attn_tile
        if tile:
            from kungfu_tpu.ops.pallas import decode_attention as kernel

            visible = self.visible_runs(pos, live)
            walked = [kernel.rows_walked(n, tile) for n in visible]
        else:       # XLA's form reads every row of every slot under a mask
            see = self.visible_rows(pos)[:, None, None, :]
            walked = [self.batch * w, self.batch * self.chunks]

        class Step:
            """A decode step's cache: one row a slot into the slab, the
            chunk it lies in pooled again from the slab, attention over
            the slab itself."""

            def write(_, li, kn, vn, mu, phi):
                nonlocal k, v
                with jax.named_scope("kv_write"):
                    k = write_rows(k, li, kn, row_at)
                    v = write_rows(v, li, vn, row_at)
                    with jax.named_scope("eva_pool"):
                        chunk = lambda slab: jnp.concatenate([
                            jax.lax.dynamic_slice(
                                slab, (li, b, 0, chunk_from[b], 0), size,
                                allow_negative_indices=False)[0]
                            for b in range(self.batch)])[:, :, None]
                        kp, vp = arch.pool_chunks(chunk(k), chunk(v), mu, phi)
                        k = write_rows(k, li, kp, chunk_at)
                        v = write_rows(v, li, vp, chunk_at)

            @jax.named_scope("attn_core")
            def attend(_, li, q, positions):
                with jax.named_scope("eva_attn"):
                    if tile:    # one query head a key/value head: [B, H, 1, D]
                        return kernel.decode_attn(
                            q[:, 0, :, None], k, v, li, visible, tile=tile,
                            starts=(0, w))[:, None, :, 0]
                    return arch.eva_attention(q, k[li], v[li], see)

        h = model.embed(params, last_ids[:, None])
        for li in range(cfg.n_layers):
            h = arch.block(cfg, params[f"layer_{li}"], li, h, pos[:, None],
                           Step())
        tok = jnp.argmax(model.next_logits(params, h[:, 0]), axis=-1)
        says = cfg.n_layers * jnp.stack(
            [jnp.sum(live & (pos % c == c - 1)), *walked])
        return k, v, jnp.concatenate([tok, says]).astype(jnp.int32)

    def new_out(self):
        return jnp.zeros(self.batch + len(_SAYS), jnp.int32)

    def read(self, out, contexts):
        """A decode step's ``out`` on the host: the slots' tokens, and as
        attrs of the span that waits for them (docs/tracing.md) what the
        step's ``contexts`` had to read of each kind of row beside what
        it did read: a context of ``c`` positions has ``c - W floor((c -
        1) / W)`` exact rows a layer (``kv_rows_*``) and ``(W / C)
        floor((c - 1) / W)`` chunk rows (``summary_rows_*``); the rows
        it read of each kind (the tiles its kernel walked, or every row
        of every slot) and the chunks it completed the step counted
        itself; ``eva_attn_kernel`` says which form of the attention
        ran."""
        cfg = self.cfg
        out = np.asarray(jax.device_get(out))
        says = dict(zip(_SAYS, out[self.batch:].tolist()))
        exact, chunk = self.model.rows_seen(contexts)
        l, slots, heads, _, width = self.shape
        says.update(kv_rows(exact, ((l, slots, heads, self.exact, width),),
                            cfg.compute_dtype))
        says["summary_rows_live"] = l * int(chunk.sum())
        # ... of which the rows READ are the step's own counts: the tiles
        # of each run the kernel walked, or every row where XLA's form ran
        says["kv_rows_read"] = says.pop("kv_rows_walked")
        says["summary_rows_read"] = says.pop("summary_rows_walked")
        says["eva_attn_kernel"] = self.eva_attn_kernel
        return out[:self.batch], says

    @functools.cached_property
    def attn_tile(self):
        """The key tile with which a decode step's attention over the
        slab is ONE kernel a layer that walks only the tiles of a slot's
        two visible runs (``ops/pallas/decode_attention.py``), or None
        where it is ``eva_attention`` over every row under the mask: off
        the TPU, and for shapes the kernel does not tile (a window that
        is not whole tiles among them).  One choice, from the platform
        and the slab's shape, made once: the step that is traced and the
        span that says which form ran read the same.  The kernel's
        package is imported here and by no module's import, so a process
        that traces no such step never pays for it (PERF.md, PR 35)."""
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
        tok = jnp.argmax(model.next_logits(params, row)[0], axis=-1)
        return k, v, tok.astype(jnp.int32)

    # -- the host's side of a page ---------------------------------------
    def empty_pages(self, rows: int):
        """What the restore program writes into a slot for ``rows``
        positions that hold nothing (the engine's warm-up asks): zero
        exact rows, as many as the open window of ``rows`` positions
        could hold."""
        _, _, heads, _, width = self.shape
        part = np.zeros((self.cfg.n_layers, heads, min(rows, self.exact),
                         width), self.cfg.compute_dtype)
        return part, part
