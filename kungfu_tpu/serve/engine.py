"""Continuous-batching inference engine over the flagship transformer.

One engine = one replica: it owns the params, a device-side KV slab of
``max_batch`` decode slots, and a :class:`~kungfu_tpu.serve.kvcache.
KVCachePool` for host-side page accounting.  The loop discipline is
**decode-priority continuous batching** (the Orca/vLLM scheduling
shape): every :meth:`step` first admits at most ``admit_per_step``
pending prefills into free slots, then runs ONE jit-compiled decode
step for ALL active slots — new requests join the running batch between
decode steps instead of waiting for a batch boundary, and long prompts
cannot starve in-flight decodes.

Phases are jit-compiled with static shapes (one trace per prefill
length bucket + one decode trace — the recompile-hazard discipline):

* **prefill** — forward over the un-cached prompt suffix, writing K/V
  into the slab at ``[cached, prompt_len)`` and emitting the first
  generated token.  The cached prefix comes straight out of the paged
  pool (prefix-chain hit), so a shared system prompt costs its pages'
  load, not its FLOPs — the measured delta in ``bench.py --serve``.
* **decode** — one token for every active slot: write one K/V row per
  slot at the slot's position, attend over ``[0, pos]`` of the slab
  itself, greedy argmax (greedy on purpose: a replayed request
  deterministically re-derives the same continuation from its committed
  prefix, docs/serving.md).

The slabs (``[L, B, H, S, D]``, one for K and one for V) are updated in
place by all three programs that write them (prefill, decode and the
restore of cached pages): each takes them donated and writes only the
rows it adds, so no call copies a slab or a layer of it.  A slab handed
to one of these programs is gone; the engine, and anyone who calls the
jitted functions, keeps only the returned pair.

Fault surface: the engine is process-local and carries no collective
state — worker death is handled ABOVE it by the router's replay ladder
(serve/router.py); the engine only guarantees that completed requests
committed their full pages to the pool first.
"""

from __future__ import annotations

import math
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from kungfu_tpu.models import nn
from kungfu_tpu.models.transformer import Transformer, _rope
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


class InferenceEngine:
    """Single-replica continuous-batching decode loop (one per serving
    worker; thread-safe submit, single-threaded :meth:`step`)."""

    def __init__(self, model: Transformer, params, *,
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
        self._width = self.max_batch  # admitted width (policy-adjustable)
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._pending: "deque[_Req]" = deque()
        self._active: Dict[int, _Req] = {}       # slot -> request
        self._free_slots = list(range(self.max_batch - 1, -1, -1))
        self._steps = 0
        # device KV slab: [L, B, H, S, D] in compute dtype
        L, B, H, S, D = (cfg.n_layers, self.max_batch, cfg.n_heads,
                        self.max_seq, cfg.head_dim)
        dt = cfg.compute_dtype
        self._k = jnp.zeros((L, B, H, S, D), dt)
        self._v = jnp.zeros((L, B, H, S, D), dt)
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

    # -- forward passes --------------------------------------------------
    @jax.named_scope("attn_proj")
    def _layer_qkv(self, lp, x, positions):
        cfg = self.model.cfg
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

    def _merge(self, x):
        b, h, s, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)

    def _out_and_mlp(self, lp, h, o):
        """The rest of a block after attention ``o`` [B,H,Q,D]: the
        output projection and the MLP, each added to the stream ``h``."""
        dt = self.model.cfg.compute_dtype
        with jax.named_scope("attn_proj"):
            h = h + nn.dense_apply(lp["wo"], self._merge(o), dtype=dt)
        x = nn.layernorm_apply(lp["ln2"], h)
        with jax.named_scope("mlp"):
            y = nn.gelu(nn.dense_apply(lp["ffn_in"], x, dtype=dt))
            return h + nn.dense_apply(lp["ffn_out"], y, dtype=dt)

    def _prefill_fn(self, params, k_slab, v_slab, ids, n, start, slot):
        """ids [S_pad] (suffix, zero-padded past ``n``); writes K/V at
        positions ``[start, start + S_pad)`` of ``slot`` and returns the
        greedy next token after the last REAL row (``n - 1``)."""
        cfg = self.model.cfg
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

    @staticmethod
    def _row_windows(pos, s_max):
        """Per slot ``b``: where the aligned window of ``S`` that holds
        position ``pos[b]`` starts, and which of its rows that is.  Each
        start is a scalar ``p // w * w`` on purpose: from that the
        compiler knows the window is tile-aligned and updates it in
        place; sliced out of a vector of starts it no longer does, and
        the write takes five times as long (tests/test_tpu_compile.py)."""
        w = math.gcd(s_max, 128)                # divides S: never clamped
        lane = jnp.arange(w)[:, None]
        return [(p // w * w, lane == p % w) for p in pos]

    @staticmethod
    def _write_rows(slab, li, new, windows):
        """Row ``b`` of ``new`` [B, H, 1, D] into layer ``li`` of
        ``slab`` at slot ``b``'s position, in place, as a
        read-modify-write of the window of :meth:`_row_windows`.  The TPU
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

    def _decode_fn(self, params, k_slab, v_slab, last_ids, pos):
        """One token for every slot: ``last_ids``/``pos`` are [B]; the
        new K/V lands at each slot's ``pos`` and attention covers
        ``[0, pos]``.  Inactive slots compute garbage nobody reads."""
        cfg = self.model.cfg
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
        windows = self._row_windows(pos, s_max)
        for li in range(cfg.n_layers):
            lp = params[f"layer_{li}"]
            x = nn.layernorm_apply(lp["ln1"], h)
            q, k, v = self._layer_qkv(lp, x, positions)
            with jax.named_scope("kv_write"):
                k_slab = self._write_rows(k_slab, li, k, windows)
                v_slab = self._write_rows(v_slab, li, v, windows)
            h = self._out_and_mlp(lp, h, self._attend(
                q, k_slab[li], v_slab[li], mask))
        h = nn.layernorm_apply(params["ln_f"], h)
        with jax.named_scope("head"):
            logits = nn.dense_apply(params["head"], h[:, 0]
                                    ).astype(jnp.float32)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return k_slab, v_slab, tok

    @staticmethod
    @jax.named_scope("kv_write")
    def _restore_fn(k_slab, v_slab, ks, vs, slot):
        """Cached pages ``ks``/``vs`` [L, H, R, D] back into positions
        ``[0, R)`` of ``slot``."""
        at = (0, slot, 0, 0, 0)
        return (jax.lax.dynamic_update_slice(k_slab, ks[:, None], at,
                                             allow_negative_indices=False),
                jax.lax.dynamic_update_slice(v_slab, vs[:, None], at,
                                             allow_negative_indices=False))

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
        cfg = self.model.cfg
        for s_pad in buckets:
            # the slabs are donated, so each call's pair replaces the
            # engine's; what the calls write (zeros and one row of slot 0,
            # before any request) nobody reads
            pages = jnp.zeros((cfg.n_layers, cfg.n_heads, s_pad,
                               cfg.head_dim), cfg.compute_dtype)
            self._k, self._v = self._restore_j(self._k, self._v, pages,
                                               pages, jnp.int32(0))
            self._k, self._v, tok = self._prefill_j(
                self.params, self._k, self._v, jnp.zeros(s_pad, jnp.int32),
                jnp.int32(1), jnp.int32(0), jnp.int32(0))
        self._k, self._v, tok = self._decode_j(
            self.params, self._k, self._v,
            jnp.zeros(self.max_batch, jnp.int32),
            jnp.zeros(self.max_batch, jnp.int32))
        tok.block_until_ready()

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
        cached_pages, n_cached = self.pool.lookup(req.tokens)
        # at least one prompt token must run the forward — the last row's
        # hidden state is where the first generated token comes from
        max_reuse = ((len(req.tokens) - 1) // T) * T
        while n_cached > max_reuse:
            self.pool.release([cached_pages.pop()])
            n_cached -= T
        # the padded prefill must FIT the slab past the cached offset:
        # start + bucket(suffix) > max_seq would make dynamic_update_slice
        # silently clamp the write over the restored prefix (corrupt K/V
        # that _complete would then commit into the prefix chain).  Give
        # reuse back until the rounded suffix fits — n_cached = 0 always
        # does, since submit() bounds the prompt by max_seq
        while n_cached > 0 and (
                n_cached + self._prefill_bucket(len(req.tokens) - n_cached)
                > self.max_seq):
            self.pool.release([cached_pages.pop()])
            n_cached -= T
        try:
            fresh = self.pool.alloc(n_pages - len(cached_pages))
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
        self._mfu.add_flops(costmodel.serve_prefill_flops(
            self.model.cfg, len(suffix), n_cached))
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
        cfg, T = self.model.cfg, self._page_tokens
        shape = (cfg.n_layers, cfg.n_heads, self._prefill_bucket(n_cached),
                 cfg.head_dim)
        ks = np.zeros(shape, cfg.compute_dtype)
        vs = np.zeros(shape, cfg.compute_dtype)
        for i, p in enumerate(pages):
            ks[:, :, i * T:(i + 1) * T], vs[:, :, i * T:(i + 1) * T] = (
                self.pool.page_data(p))
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
        if full > first_new and req.pages:
            kb = np.asarray(jax.device_get(
                self._k[:, req.slot, :, first_new * T:full * T, :]))
            vb = np.asarray(jax.device_get(
                self._v[:, req.slot, :, first_new * T:full * T, :]))
            for p in range(first_new, full):
                lo = (p - first_new) * T
                self.pool.put_page_data(req.pages[p],
                                        kb[:, :, lo:lo + T, :],
                                        vb[:, :, lo:lo + T, :])
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
        """One continuous-batching iteration: admit (bounded), decode
        every active slot, retire finished requests.  Returns events:
        ``{"kind": "admit"|"token"|"done", ...}`` in occurrence order."""
        with self._lock:
            pending, active = len(self._pending), len(self._active)
        with timeline.span("serve", "step", rank=self.rank,
                           pending=pending, active=active):
            return self._step()

    def _step(self) -> List[dict]:
        events: List[dict] = []
        self._steps += 1
        t_step0 = time.perf_counter()
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
        # consume cancel flags on the step thread (the only retirer)
        with self._lock:
            doomed = [(s, r) for s, r in self._active.items() if r.canceled]
            for s, r in doomed:
                self._retire_locked(s, r)
        with self._lock:
            active = dict(self._active)
        if active:
            t0 = time.perf_counter()
            # batch: the live slots; width: the slots the decode program
            # computes, live or not
            with timeline.span("serve", "decode", rank=self.rank,
                               batch=len(active), width=self.max_batch):
                B = self.max_batch
                last = np.zeros(B, np.int32)
                pos = np.zeros(B, np.int32)
                for slot, r in active.items():
                    last[slot] = r.generated[-1]
                    pos[slot] = r.total_len - 1
                self._k, self._v, nxt = self._decode_j(
                    self.params, self._k, self._v,
                    jnp.asarray(last), jnp.asarray(pos))
            with timeline.span("serve", "decode_read", rank=self.rank):
                nxt = np.asarray(jax.device_get(nxt))  # the host waits here
            slo.observe_token(time.perf_counter() - t0)
            cfg = self.model.cfg
            self._mfu.add_flops(sum(
                costmodel.serve_decode_flops(cfg, int(pos[slot]) + 1)
                for slot in active))
            for slot, r in active.items():
                r.generated.append(int(nxt[slot]))
                events.append({"kind": "token", "rid": r.rid,
                               "tok": int(nxt[slot]), "n": len(r.generated)})
                if self._is_done(r):
                    events.append({"kind": "done", **self._complete(slot, r)})
        self._mfu.step(wall_s=time.perf_counter() - t_step0)
        slo.note_active(self.active_count)
        return events

    def drain(self, max_steps: int = 10_000) -> List[dict]:
        """Run steps until idle (tests / local mode); bounded so a
        non-terminating request cannot wedge the caller."""
        out: List[dict] = []
        for _ in range(max_steps):
            if not (self.pending_count or self.active_count):
                break
            out.extend(self.step())
        return out
