"""Continuous-batching inference engine.

One engine = one replica: it owns the params, a device-side KV slab of
``max_batch`` decode slots, and a :class:`~kungfu_tpu.serve.kvcache.
KVCachePool` for host-side page accounting.  The loop discipline is
**decode-priority continuous batching** (the Orca/vLLM scheduling
shape): every :meth:`step` first admits at most ``admit_per_step``
pending prefills into free slots, then runs ONE jit-compiled decode
step for ALL active slots — new requests join the running batch between
decode steps instead of waiting for a batch boundary, and long prompts
cannot starve in-flight decodes.

The loop runs **one decode step ahead of the host**.  A step's tokens
are the next step's input, and they stay on the device: :meth:`step`
dispatches step n+1 from step n's output as it left the program, and
only then waits for step n's tokens.  The host knows every slot's
position and which slots go on without seeing a token (a request whose
token in flight is its ``max_new``-th is left out), so the device runs
steps back to back and a token's event arrives one call after its
step was dispatched, when the host really has it.  What the host cannot
know is a request that ends on ``eos_id``: the step behind has then
computed its slot once more, and the program itself keeps that row out
of the cache (``_decode_fn``).  A call that admits does so first: the
prefill queues on the device behind the step in flight and starts the
moment that step ends (docs/serving.md, "One step ahead").

Phases are jit-compiled with static shapes (one trace per prefill
length bucket + one decode trace — the recompile-hazard discipline):

* **prefill** — forward over the un-cached prompt suffix, writing K/V
  into the slab at ``[cached, prompt_len)`` and emitting the first
  generated token.  The cached prefix comes straight out of the paged
  pool (prefix-chain hit), so a shared system prompt costs its pages'
  load, not its FLOPs (``tests/test_serve.py::TestEngine`` counts the
  prefill tokens it saves).
* **decode** — one token for every active slot: write one K/V row per
  slot at the slot's position, attend over ``[0, pos]`` of the slab
  itself, greedy argmax (greedy on purpose: a replayed request
  deterministically re-derives the same continuation from its committed
  prefix, docs/serving.md).

The slabs (for the dense transformer ``[L, B, H, S, D]``, one for K and
one for V) are updated in place by all three programs that write them (prefill, decode and the
restore of cached pages): each takes them donated and writes only the
rows it adds, so no call copies a slab or a layer of it.  A slab handed
to one of these programs is gone; the engine, and anyone who calls the
jitted functions, keeps only the returned pair.

The engine knows no model by name.  What depends on how a model lays
its keys and values out -- the slabs, the bodies of the prefill and
decode programs, the host's side of a page, a step's cost -- it asks of
``model.serve_caches(max_batch, max_seq)`` (the interface is written
out in ``serve/caches.py``): the dense ``Transformer`` answers with one
slab for K and one for V (``caches.DenseCaches``), a model that mixes
window and full attention layers with a ring and a full-length slab for
each (``serve/windowed.py``), latent attention with one compressed row
for all heads and their shared rotary key (``serve/latent.py``: there
``_k`` and ``_v`` differ in width), a model most of whose layers keep a
recurrent state with a slab for the others beside a matrix and a
convolution tail a slot (``serve/recurrent.py``: parts that are no rows
a position at all, so nothing of a request can be put into a page; the
pool's spec says so, ``PageSpec.unpaged``, and the engine then looks
up no prefix, reserves no page and commits none), a model whose
attention reads rows pooled from other rows with the open window's exact
rows beside one chunk row for every few positions of the closed ones
(``serve/pooled.py``: unpaged too, until the rule by which a page could
hold a chunk row is built).  Scheduler, slots, pool and spans are the
same for all five.

Fault surface: the engine is process-local and carries no collective
state — worker death is handled ABOVE it by the router's replay ladder
(serve/router.py); the engine only guarantees that completed requests
committed their full pages to the pool first.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from kungfu_tpu.monitor import timeline
from kungfu_tpu.ops import costmodel
from kungfu_tpu.serve import slo
from kungfu_tpu.serve.kvcache import CacheExhausted, KVCachePool, PageSpec
from kungfu_tpu.utils import envs

DEFAULT_MAX_BATCH = 8
DEFAULT_MAX_TOKENS = 256


class _Req:
    __slots__ = ("rid", "tokens", "max_new", "generated", "slot", "pages",
                 "reused", "computed", "submitted_s", "admitted_s",
                 "first_token_s", "canceled", "trace", "parent")

    def __init__(self, rid: str, tokens: Sequence[int], max_new: int,
                 trace=None):
        self.rid = rid
        self.tokens = tuple(int(t) for t in tokens)
        self.max_new = int(max_new)
        # kf-xray causal context (the router's trace, via the frame meta)
        self.trace, self.parent = timeline.parse_trace_context(trace)
        self.generated: List[int] = []
        self.slot = -1
        self.pages: List[int] = []
        self.reused = 0
        self.computed = 0
        self.submitted_s = time.perf_counter()
        self.admitted_s = 0.0
        self.first_token_s = 0.0
        self.canceled = False

    @property
    def total_len(self) -> int:
        return len(self.tokens) + len(self.generated)


class _Flight(NamedTuple):
    """A dispatched decode step whose tokens the host has not read."""
    out: jax.Array                  # the program's ``out``, on the device
    rows: Dict[int, _Req]           # slot -> the request it computes
    contexts: np.ndarray            # positions each row attends over
    dispatched_s: float


class InferenceEngine:
    """Single-replica continuous-batching decode loop (one per serving
    worker; thread-safe submit, single-threaded :meth:`step`)."""

    def __init__(self, model, params, *,
                 pool: Optional[KVCachePool] = None,
                 max_batch: Optional[int] = None,
                 max_seq: Optional[int] = None,
                 page_tokens: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 admit_per_step: int = 1,
                 rank: Optional[int] = None,
                 plan=None):
        cfg = model.cfg
        if plan is not None:
            # the unified ParallelPlan route (parallel/train.py): a
            # serving worker is one dp replica of the whole model —
            # pipelined/TP-sharded serving engines are future work, so
            # a plan asking for them must fail loudly here, not
            # silently serve an unsharded model
            if plan.pp != 1 or plan.tp != 1 or plan.sp != 1:
                raise NotImplementedError(
                    f"InferenceEngine serves one full-model replica per "
                    f"worker; plan carries pp={plan.pp} tp={plan.tp} "
                    f"sp={plan.sp} (TP-sharded serving is ROADMAP work)")
            if plan.zero_stage:
                raise ValueError("serving holds no optimizer state — "
                                 "plan.zero_stage must be 0")
        self.plan = plan
        self.model = model
        self.params = params
        self.rank = rank
        self.eos_id = eos_id
        self.admit_per_step = max(1, int(admit_per_step))
        self.max_batch = int(max_batch if max_batch is not None
                             else envs.parse_int_env(envs.SERVE_MAX_BATCH,
                                                     DEFAULT_MAX_BATCH))
        self.max_seq = int(max_seq or cfg.max_seq)
        self.pool = pool if pool is not None else KVCachePool(
            PageSpec.for_model(cfg, page_tokens=page_tokens))
        self._page_tokens = self.pool.spec.page_tokens
        # False where no page can hold a request's prefix (a layer keeps a
        # state a slot, or rows made from other rows): none is looked up,
        # reserved or committed
        self._paged = not self.pool.spec.unpaged
        self._width = self.max_batch  # admitted width (policy-adjustable)
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._pending: "deque[_Req]" = deque()
        self._active: Dict[int, _Req] = {}       # slot -> request
        self._free_slots = list(range(self.max_batch - 1, -1, -1))
        self._steps = 0
        # the model's side of the cache (serve/caches.py)
        self._caches = model.serve_caches(self.max_batch, self.max_seq)
        self._k, self._v = self._caches.new_slabs()
        # the newest decode step's ``out`` (its first max_batch entries
        # the slots' tokens), which the next step takes on the device;
        # and that step itself, until the host has read its tokens
        self._out = self._caches.new_out()
        self._flight: Optional[_Flight] = None
        self._delivered_s = 0.0         # when tokens last reached the host
        # Every program that writes the slabs takes them donated and
        # updates them in place, on every backend: a call that copied
        # them would move 3 GB at GPT-2 large (PERF.md, PR 25).  The
        # rule that brings: a slab handed to one of these is deleted --
        # keep only the returned pair.
        self._decode_j = jax.jit(self._decode_fn, donate_argnums=(1, 2))
        self._prefill_j = jax.jit(self._prefill_fn, donate_argnums=(1, 2))
        self._restore_j = jax.jit(self._restore_fn, donate_argnums=(0, 1))
        # kf-xray serving MFU: analytic prefill/decode FLOPs accumulate
        # per step into the kf_model_flops_s gauge (+ kf_mfu when a chip
        # peak is known; None on the CPU mesh — docs/xray.md)
        self._mfu = costmodel.MFUMeter(rank=rank)

    # -- the three programs ----------------------------------------------
    # (methods, jitted under these names: the device trace knows the
    # programs as ``jit__prefill_fn`` / ``jit__decode_fn``)
    def _prefill_fn(self, params, k_slab, v_slab, ids, n, start, slot):
        """ids [S_pad] (suffix, zero-padded past ``n``) into ``slot``
        past ``start`` cached positions; returns the greedy next token
        after the last REAL row (``n - 1``)."""
        return self._caches.prefill(params, k_slab, v_slab, ids, n, start,
                                    slot)

    def _decode_fn(self, params, k_slab, v_slab, prev, fresh, pos):
        """One token for every slot the step is for, those at a ``pos``
        [B] above 0: its last token is ``fresh`` [B] where the host knows
        it (a slot admitted since the step before) and, at -1, the one
        the step before made for it (``prev``, that step's ``out``, read
        here and not on the host).  The new K/V lands at the slot's
        ``pos`` -- unless the last token is ``eos_id``: the host
        dispatched this step before it could see that the request had
        ended, and a row written for it could replace one that is yet to
        be committed.  The other slots compute garbage nobody reads and
        write nothing."""
        last = jnp.where(fresh >= 0, fresh, prev[:self.max_batch])
        live = pos > 0
        if self.eos_id is not None:
            live &= last != self.eos_id
        return self._caches.decode(params, k_slab, v_slab, last, pos, live)

    @staticmethod
    @jax.named_scope("kv_write")
    def _restore_fn(k_slab, v_slab, ks, vs, slot):
        """Cached pages ``ks``/``vs`` [L, H, R, D] back into positions
        ``[0, R)`` of ``slot`` (of every slab, where K and V are several:
        a ring is restored whole, and so is a part that is a state a
        slot, whatever axes follow its slots)."""
        def put(slab, rows):
            return jax.lax.dynamic_update_slice(
                slab, rows[:, None], (0, slot) + (0,) * (slab.ndim - 2),
                allow_negative_indices=False)

        return (jax.tree_util.tree_map(put, k_slab, ks),
                jax.tree_util.tree_map(put, v_slab, vs))

    def _prefill_bucket(self, n: int) -> int:
        """Static prefill length: the smallest power-of-two multiple of
        the page size holding ``n`` (one compile per bucket, ever)."""
        b = max(self._page_tokens, 1)
        while b < n:
            b *= 2
        return min(b, self.max_seq)

    def warmup(self, prompt_lens: Sequence[int] = (8,)) -> None:
        """Compile the decode step and EVERY prefill bucket up to the
        one covering ``max(prompt_lens)`` before serving starts.
        Cold-start compiles otherwise land on a live request's clock —
        long enough to stall the worker loop (decode AND its liveness
        keepalives) and read as a dead worker.  The smaller rungs are
        not optional: a prefix-cache hit prefills only its SUFFIX, so
        the first reuse of a warmed long prompt would otherwise compile
        the smallest bucket mid-service — exactly the stall this method
        exists to pay up front."""
        top = self._prefill_bucket(max(max(prompt_lens), 1))
        buckets, b = [], max(self._page_tokens, 1)
        while b < top:
            buckets.append(b)
            b *= 2
        buckets.append(top)
        for s_pad in buckets:
            # the slabs are donated, so each call's pair replaces the
            # engine's; what the calls write (zeros and one row of slot 0,
            # before any request) nobody reads
            ks, vs = self._caches.empty_pages(s_pad)
            self._k, self._v = self._restore_j(self._k, self._v, ks, vs,
                                               jnp.int32(0))
            self._k, self._v, tok = self._prefill_j(
                self.params, self._k, self._v, jnp.zeros(s_pad, jnp.int32),
                jnp.int32(1), jnp.int32(0), jnp.int32(0))
        nobody = jnp.zeros(self.max_batch, jnp.int32)  # position 0: not live
        self._k, self._v, self._out = self._decode_j(
            self.params, self._k, self._v, self._out,
            jnp.full(self.max_batch, -1, jnp.int32), nobody)
        self._out.block_until_ready()

    # -- scheduling ------------------------------------------------------
    @property
    def width(self) -> int:
        return self._width

    def set_width(self, w: int) -> int:
        """Admitted decode width (<= max_batch); the policy layer's
        batch-width controller moves this, never the slab shape."""
        with self._lock:
            self._width = max(1, min(int(w), self.max_batch))
            return self._width

    def submit(self, rid: str, tokens: Sequence[int], max_new: int,
               trace: Optional[str] = None) -> None:
        if not tokens:
            raise ValueError("empty prompt")
        if len(tokens) + max_new > self.max_seq:
            raise ValueError(
                f"request {rid!r}: {len(tokens)} prompt + {max_new} new "
                f"tokens exceeds max_seq {self.max_seq}")
        req = _Req(rid, tokens, max_new, trace=trace)
        with self._wake:
            self._pending.append(req)
            self._wake.notify_all()

    def cancel(self, rid: str) -> bool:
        """Drop a request.  Pending requests leave immediately; an
        ACTIVE (or mid-admission) request is only FLAGGED — the step
        thread retires it at the next boundary.  Retirement must stay
        single-threaded: a cross-thread release here would race
        ``_complete``'s page commit (put_page_data on a freed page)."""
        with self._lock:
            for i, r in enumerate(self._pending):
                if r.rid == rid:
                    del self._pending[i]
                    return True
            for r in self._active.values():
                if r.rid == rid:
                    r.canceled = True
                    return True
        return False

    @property
    def pending_count(self) -> int:
        with self._lock:
            return len(self._pending)

    @property
    def active_count(self) -> int:
        with self._lock:
            return len(self._active)

    def wait_for_work(self, timeout: float) -> bool:
        """Park the loop thread until work arrives (bounded)."""
        with self._wake:
            if self._pending or self._active:
                return True
            return self._wake.wait(timeout)

    # -- admission (prefill phase) ---------------------------------------
    def _try_admit(self, req: _Req) -> bool:
        with timeline.span("serve", "admit", rank=self.rank, rid=req.rid,
                           **timeline.context_attrs(req.trace, req.parent)
                           ) as sp:
            return self._admit(req, sp)

    def _admit(self, req: _Req, sp) -> bool:
        T = self._page_tokens
        budget = len(req.tokens) + req.max_new
        n_pages = -(-budget // T)
        cached_pages, n_cached = (self.pool.lookup(req.tokens)
                                  if self._paged else ([], 0))
        # Give reuse back, a page at a time, until what is left can be
        # used.  At least one prompt token must run the forward -- the
        # last row's hidden state is where the first generated token
        # comes from.  The padded prefill must FIT the slab past the
        # cached offset: start + bucket(suffix) > max_seq would make
        # dynamic_update_slice silently clamp the write over the restored
        # prefix (corrupt K/V that _complete would then commit into the
        # prefix chain); n_cached = 0 always fits, since submit() bounds
        # the prompt by max_seq.  And the pool must still hold every row
        # the prefix's last positions need (window layers keep only their
        # last rows: KVCachePool.reusable).
        max_reuse = ((len(req.tokens) - 1) // T) * T
        while n_cached > 0 and (
                n_cached > max_reuse
                or n_cached + self._prefill_bucket(len(req.tokens) - n_cached)
                > self.max_seq
                or not self.pool.reusable(cached_pages)):
            self.pool.release([cached_pages.pop()])
            n_cached -= T
        try:
            fresh = self.pool.alloc(n_pages - len(cached_pages)
                                    if self._paged else 0)
        except CacheExhausted:
            self.pool.release(cached_pages)
            return False
        req.pages = cached_pages + fresh
        req.reused = n_cached
        with self._lock:
            slot = self._free_slots.pop()
        req.slot = slot
        req.admitted_s = time.perf_counter()
        if n_cached:
            self._restore(slot, cached_pages, n_cached)
        suffix = req.tokens[n_cached:]
        s_pad = self._prefill_bucket(len(suffix))
        ids = np.zeros(s_pad, np.int32)
        ids[:len(suffix)] = suffix
        sp.set_metadata(tokens=len(suffix), reused=n_cached,
                        pages=len(req.pages))
        with timeline.span("serve", "prefill", rank=self.rank,
                           rid=req.rid, bucket=s_pad):
            self._k, self._v, tok = self._prefill_j(
                self.params, self._k, self._v, jnp.asarray(ids),
                jnp.int32(len(suffix)), jnp.int32(n_cached), jnp.int32(slot))
        req.computed = len(suffix)
        self._mfu.add_flops(self._caches.prefill_flops(len(suffix),
                                                       n_cached))
        with timeline.span("serve", "prefill_read", rank=self.rank,
                           rid=req.rid):
            tok = int(tok)  # the host waits for the prefill here
        req.first_token_s = time.perf_counter()
        req.generated.append(tok)
        slo.count_prefill(computed=len(suffix), reused=n_cached)
        with self._lock:
            self._active[slot] = req
        return True

    def _restore(self, slot: int, pages: List[int], n_cached: int) -> None:
        """Write the cached prefix's pages into ``slot``, padded with
        zeros to a prefill bucket (one restore program per bucket; the
        padding lands where the prefill and decode write before anyone
        reads)."""
        data = [self.pool.page_data(p) for p in pages]
        ks, vs = (self._caches.pages_to_slot(
            [d[i] for d in data], n_cached, self._prefill_bucket(n_cached),
            self._page_tokens) for i in (0, 1))
        self._k, self._v = self._restore_j(self._k, self._v, ks, vs,
                                           jnp.int32(slot))

    # -- completion ------------------------------------------------------
    def _retire_locked(self, slot: int, req: _Req) -> None:
        # idempotent: a cancel() racing the decode loop must not free a
        # slot twice or double-release pages
        if self._active.pop(slot, None) is None:
            return
        self._free_slots.append(slot)
        if req.pages:
            self.pool.release(req.pages)
            req.pages = []

    def _complete(self, slot: int, req: _Req) -> dict:
        with timeline.span("serve", "complete", rank=self.rank, rid=req.rid,
                           **timeline.context_attrs(req.trace, req.parent)
                           ) as sp:
            return self._commit_and_retire(slot, req, sp)

    def _commit_and_retire(self, slot: int, req: _Req, sp) -> dict:
        T = self._page_tokens
        # commit the full pages this request produced (beyond the reused
        # prefix) so the next shared-prefix request skips their prefill
        seq = list(req.tokens) + req.generated
        # K/V exists for positions [0, total_len - 1): the final token
        # was emitted but never ran through the stack
        full = (req.total_len - 1) // T
        first_new = req.reused // T
        committed = fetched = 0
        if full > first_new and req.pages:  # (no pages where not _paged)
            # (kept_from: the first position every layer still has)
            (kb, kept_from), (vb, _) = (
                self._caches.rows_of_slot(slab, req.slot, first_new * T,
                                          full * T, req.total_len)
                for slab in (self._k, self._v))
            for p in range(first_new, full):
                lo = (p - first_new) * T
                self.pool.put_page_data(req.pages[p],
                                        kb[:, :, lo:lo + T, :],
                                        vb[:, :, lo:lo + T, :],
                                        whole=p * T >= kept_from)
            self.pool.commit_chain(seq[:full * T], req.pages[:full])
            committed, fetched = full - first_new, kb.nbytes + vb.nbytes
        sp.set_metadata(pages=committed, bytes=fetched)
        done_s = time.perf_counter()
        stats = {
            "rid": req.rid,
            "tokens": list(req.generated),
            "ttft_s": req.first_token_s - req.submitted_s,
            "queue_s": req.admitted_s - req.submitted_s,
            "engine_s": done_s - req.submitted_s,
            "reused_tokens": req.reused,
            "computed_tokens": req.computed,
        }
        slo.observe_ttft(stats["ttft_s"])
        with self._lock:
            self._retire_locked(slot, req)
        return stats

    def _is_done(self, req: _Req) -> bool:
        if len(req.generated) >= req.max_new:
            return True
        return self.eos_id is not None and req.generated[-1] == self.eos_id

    # -- the step --------------------------------------------------------
    def step(self) -> List[dict]:
        """One continuous-batching iteration: admit (bounded), dispatch
        the next decode step for every slot that goes on, read the
        tokens of the step the previous call dispatched, retire finished
        requests.  Returns events: ``{"kind": "admit"|"token"|"done",
        ...}`` in the order the host came to know them."""
        with self._lock:
            pending, active = len(self._pending), len(self._active)
        with timeline.span("serve", "step", rank=self.rank,
                           pending=pending, active=active):
            return self._step()

    def _step(self) -> List[dict]:
        events: List[dict] = []
        self._steps += 1
        t_step0 = time.perf_counter()
        # consume cancel flags on the step thread (the only retirer)
        with self._lock:
            doomed = [(s, r) for s, r in self._active.items() if r.canceled]
            for s, r in doomed:
                self._retire_locked(s, r)
        admitted = 0
        while admitted < self.admit_per_step:
            with self._lock:
                can = (self._pending and self._free_slots
                       and len(self._active) < self._width)
                req = self._pending.popleft() if can else None
            if req is None:
                break
            if not self._try_admit(req):
                with self._lock:
                    self._pending.appendleft(req)  # FCFS: keep its turn
                break
            admitted += 1
            events.append({"kind": "admit", "rid": req.rid,
                           "reused": req.reused, "computed": req.computed})
            events.append({"kind": "token", "rid": req.rid,
                           "tok": req.generated[-1], "n": 1})
            if self._is_done(req):
                events.append({"kind": "done", **self._complete(req.slot, req)})
        # step n+1 goes to the device, then step n's tokens come back
        behind = self._flight
        self._flight = self._dispatch(behind)
        if behind is not None:
            self._read(behind, events)
        self._mfu.step(wall_s=time.perf_counter() - t_step0)
        slo.note_active(self.active_count)
        return events

    def _dispatch(self, behind: Optional[_Flight]) -> Optional[_Flight]:
        """Start a decode step for every active request that goes on by
        what the host can know, behind the unread step ``behind`` (or
        None): a request with a token in flight takes it from the device
        and stands one position further; one whose token in flight is
        its last by ``max_new`` is left out."""
        with self._lock:
            active = dict(self._active)
        B = self.max_batch
        fresh = np.full(B, -1, np.int32)
        pos = np.zeros(B, np.int32)
        rows: Dict[int, _Req] = {}
        for slot, r in active.items():
            flying = int(behind is not None and behind.rows.get(slot) is r)
            if len(r.generated) + flying >= r.max_new:
                continue
            rows[slot] = r
            pos[slot] = r.total_len + flying - 1
            if not flying:
                fresh[slot] = r.generated[-1]
        if not rows:
            return None
        t0 = time.perf_counter()
        # batch: the slots the step is for; width: the slots the decode
        # program computes, live or not; ahead: dispatched before the
        # step behind it was read
        with timeline.span("serve", "decode", rank=self.rank,
                           batch=len(rows), width=B,
                           ahead=int(behind is not None)):
            self._k, self._v, self._out = self._decode_j(
                self.params, self._k, self._v, self._out,
                jnp.asarray(fresh), jnp.asarray(pos))
        return _Flight(self._out, rows, pos[list(rows)] + 1, t0)

    def _read(self, flight: _Flight, events: List[dict]) -> None:
        """Wait for the tokens of a dispatched step and hand them out.
        A row whose request is gone meanwhile (it ended on ``eos_id`` in
        the step before, or was cancelled) is dropped."""
        with timeline.span("serve", "decode_read", rank=self.rank) as sp:
            with self._lock:
                kept = [self._active.get(slot) is r
                        for slot, r in flight.rows.items()]
            # the host waits here; what the step says of itself besides
            # its tokens (an expert model's routing; what it had to read
            # of the cache for the contexts of the rows that are kept,
            # and what it did read) goes on the span that is open when
            # it is known
            toks, attrs = self._caches.read(flight.out,
                                            flight.contexts[kept])
            rows = [row for row, k in zip(flight.rows.items(), kept) if k]
            sp.set_metadata(discarded=len(kept) - len(rows), **attrs)
        now = time.perf_counter()
        if rows:
            # what a client feels: the time between two deliveries (a
            # step after an idle stretch or an admission: since it left)
            slo.observe_token(now - max(flight.dispatched_s,
                                        self._delivered_s))
            self._delivered_s = now
        self._mfu.add_flops(self._caches.decode_flops(flight.contexts))
        toks = toks.tolist()  # (one conversion, not two a slot)
        for slot, r in rows:
            r.generated.append(toks[slot])
            events.append({"kind": "token", "rid": r.rid,
                           "tok": toks[slot], "n": len(r.generated)})
            if self._is_done(r):
                events.append({"kind": "done", **self._complete(slot, r)})

    def drain(self, max_steps: int = 10_000) -> List[dict]:
        """Run steps until idle (tests / local mode), the step in flight
        read too (behind a request that ended on ``eos_id`` one is left
        with nobody's row in it); bounded so a non-terminating request
        cannot wedge the caller."""
        out: List[dict] = []
        for _ in range(max_steps):
            if not (self.pending_count or self.active_count
                    or self._flight is not None):
                break
            out.extend(self.step())
        return out
