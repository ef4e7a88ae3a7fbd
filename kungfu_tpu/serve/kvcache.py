"""Paged KV-cache block manager: fixed-size pages, free-list allocation,
prefix-hash reuse, LRU eviction.

The serving plane's memory system, deliberately **pure** (numpy +
stdlib, no jax, no sockets) so its invariants are unit-testable the way
:mod:`kungfu_tpu.elastic.slices` is: the decode engine holds the device
slab; this pool owns the *host-side* pages — capacity accounting,
prefix-reuse bookkeeping, and the replay source of truth.

Model: a page holds what ``page_tokens`` consecutive positions keep for
every layer, in two parts: K and V, ``[n_layers, n_heads, page_tokens,
head_dim]`` each -- or, for latent attention, ONE compressed row for all
the heads and their shared rotary key, ``[n_layers, 1, page_tokens,
kv_lora_rank]`` and ``[n_layers, 1, page_tokens, qk_rope_dim]``
(``PageSpec.v_head_dim``: the second part's width where it differs).  A
request reserves ``ceil(total_tokens / page_tokens)`` pages at
admission — admission control is capacity-real, not optimistic — and
releases them at completion.  Completed *full* pages are committed
under a **prefix chain hash** (hash of all tokens up to and including
the page), so a later request sharing the prefix re-acquires the same
pages instead of recomputing their prefill: the classic shared-system-
prompt win.  Committed pages with no live reference park in an LRU;
allocation evicts from it when the free list runs dry.

Footprint contract: every allocation/release updates the
``kf_kv_cache_bytes`` gauge (allocated pages x page bytes) — the
serving analog of ``kf_opt_state_bytes``, flowing through aggregator
snapshots to the kftop serving view (docs/serving.md).

Invariants (tests/test_kvcache.py):

* a released, recycled page is never referenced by a live request;
* refcounts balance: acquire/release round-trips return the pool to
  its starting footprint;
* eviction only ever takes zero-reference committed pages;
* the gauge equals ``(capacity - free) * page_bytes`` at all times.

Durability (kf-persist): committed pages are *portable*.
:meth:`KVCachePool.snapshot_committed` images them as a flat numpy dict
(prefix tokens + K/V + content digest) that rides a
:class:`~kungfu_tpu.elastic.persist.PersistPlane` manifest's
``replicated`` payload; :meth:`KVCachePool.restore_committed` verifies
and re-commits them into a fresh pool after a preemption, so a restarted
serve worker's first request over a known prefix reuses prefill instead
of recomputing it (docs/persistence.md).
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from kungfu_tpu.monitor.registry import REGISTRY
from kungfu_tpu.utils import envs

#: default tokens per page (KF_SERVE_PAGE_TOKENS overrides)
DEFAULT_PAGE_TOKENS = 16
#: default pool capacity in pages (KF_SERVE_KV_PAGES overrides)
DEFAULT_CAPACITY_PAGES = 512

GAUGE = "kf_kv_cache_bytes"


class CacheExhausted(RuntimeError):
    """Allocation failed: free list empty and nothing evictable.  The
    typed admission-control signal — the scheduler keeps the request
    queued instead of thrashing live requests' pages."""


@dataclass(frozen=True)
class PageSpec:
    """Geometry of one page: the two parts a position keeps, for every
    layer of a model."""

    n_layers: int
    n_heads: int
    head_dim: int
    page_tokens: int
    dtype: str = "float32"
    #: width of the second part where it is not the first's (0: K and V
    #: alike; a latent cache keeps ``c_kv`` and a narrower ``k_r``)
    v_head_dim: int = 0
    #: positions a window layer keeps (0: every layer keeps every one).
    #: A page committed once a request's window layers had moved past it
    #: is not ``whole``, and a prefix can be reused only if the pages
    #: over its last ``window`` positions are (:meth:`KVCachePool.reusable`)
    window: int = 0
    #: True: what the model keeps of a request cannot be handed to a later
    #: one page by page, so no page is ever ``whole`` and no prefix
    #: reusable: the engine looks none up and commits none.  Either some
    #: layers keep a state a slot and no rows a position
    #: (``serve/recurrent.py``; ``n_layers`` counts the others): nothing
    #: such a layer holds at a page's end can be put into a page.  Or
    #: the model says so of itself (``cfg.pages_reusable`` False;
    #: ``serve/pooled.py``: rows made from other rows, whose page rule is
    #: not built)
    unpaged: bool = False

    @property
    def widths(self) -> Tuple[int, int]:
        """The last axis of a page's two parts."""
        return self.head_dim, self.v_head_dim or self.head_dim

    def part_shape(self, part: int) -> Tuple[int, int, int, int]:
        """``[n_layers, n_heads, page_tokens, width]`` of part 0 or 1."""
        return (self.n_layers, self.n_heads, self.page_tokens,
                self.widths[part])

    @property
    def page_bytes(self) -> int:
        # both parts, all layers, page_tokens rows of [n_heads, width]
        return (self.n_layers * self.n_heads * self.page_tokens
                * sum(self.widths) * np.dtype(self.dtype).itemsize)

    @classmethod
    def for_model(cls, cfg, page_tokens: Optional[int] = None,
                  dtype: Optional[str] = None) -> "PageSpec":
        """Spec from a model's config: ``TransformerConfig``; one with
        fewer key/value heads than query heads and window layers
        (``Cohere2MoeConfig``); or one that says itself what a position
        keeps, ``cache_row = (heads, first width, second width)`` (a
        latent cache: ``PanguMoeConfig``).  ``page_tokens`` defaults from
        the ``KF_SERVE_PAGE_TOKENS`` env."""
        if page_tokens is None:
            page_tokens = envs.parse_int_env(envs.SERVE_PAGE_TOKENS,
                                             DEFAULT_PAGE_TOKENS)
        row = getattr(cfg, "cache_row", None)
        heads, width, second = row or (
            getattr(cfg, "n_kv_heads", cfg.n_heads), cfg.head_dim, 0)
        stateful = len(getattr(cfg, "recurrent_layers", ()))
        unpaged = bool(stateful) or not getattr(cfg, "pages_reusable", True)
        return cls(n_layers=cfg.n_layers - stateful, n_heads=heads,
                   head_dim=width, page_tokens=int(page_tokens),
                   dtype=dtype or cfg.dtype, window=getattr(cfg, "window", 0),
                   v_head_dim=second, unpaged=unpaged)


def chain_hashes(tokens: Sequence[int], page_tokens: int) -> List[bytes]:
    """One digest per FULL page of ``tokens``: digest *i* covers tokens
    ``[0, (i+1)*page_tokens)`` — a chain, so two sequences share page
    *i* exactly when their whole prefixes up to it agree (page-local
    hashing would alias different contexts onto one K/V block, which is
    silent cross-request corruption, not reuse)."""
    out: List[bytes] = []
    h = hashlib.blake2b(b"kf-kv-chain", digest_size=16)
    for i in range(len(tokens) // page_tokens):
        page = tokens[i * page_tokens:(i + 1) * page_tokens]
        h = h.copy()
        h.update(np.asarray(page, np.int64).tobytes())
        out.append(h.digest())
    return out


def _content_digest(k: np.ndarray, v: np.ndarray) -> bytes:
    """Digest over a page's K/V bytes — the torn-write detector for
    snapshotted pages (the chain hash covers only the *tokens*; a page
    whose data rotted in transit would otherwise restore cleanly under
    a valid key and serve garbage attention)."""
    h = hashlib.blake2b(b"kf-kv-page", digest_size=16)
    h.update(np.ascontiguousarray(k).tobytes())
    h.update(np.ascontiguousarray(v).tobytes())
    return h.digest()


class _Page:
    __slots__ = ("k", "v", "key", "refs", "prefix", "whole")

    def __init__(self):
        self.k: Optional[np.ndarray] = None   # [L, H, T, D]
        self.v: Optional[np.ndarray] = None
        self.key: Optional[bytes] = None      # chain hash when committed
        self.refs = 0
        #: False: the window layers' rows had been overwritten when the
        #: page was filled, and it holds the other layers' only
        self.whole = True
        #: the covering token prefix (all tokens the chain hash digests)
        #: — kept so a committed page is *portable*: a snapshot carries
        #: (prefix, K, V) and a restoring pool re-derives the chain hash
        #: from the tokens instead of trusting a stored key (kf-persist)
        self.prefix: Optional[np.ndarray] = None


class KVCachePool:
    """Thread-safe page pool (the worker's engine loop and the channel
    handler both touch it)."""

    def __init__(self, spec: PageSpec,
                 capacity_pages: Optional[int] = None):
        if capacity_pages is None:
            capacity_pages = envs.parse_int_env(envs.SERVE_KV_PAGES,
                                                DEFAULT_CAPACITY_PAGES)
        self.spec = spec
        self.capacity = int(capacity_pages)
        self._lock = threading.Lock()
        self._free: List[int] = list(range(self.capacity - 1, -1, -1))
        self._pages: Dict[int, _Page] = {}
        #: chain hash -> page id, for committed pages (live or parked)
        self._by_key: Dict[bytes, int] = {}
        #: zero-ref committed pages, LRU order (oldest first)
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self._evictions = 0
        self._update_gauge()

    # -- accounting ------------------------------------------------------
    def _update_gauge(self) -> None:
        REGISTRY.gauge(GAUGE).set(
            (self.capacity - len(self._free)) * self.spec.page_bytes)

    @property
    def footprint_bytes(self) -> int:
        with self._lock:
            return (self.capacity - len(self._free)) * self.spec.page_bytes

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def cached_pages(self) -> int:
        """Committed pages currently parked with zero references."""
        with self._lock:
            return len(self._lru)

    @property
    def evictions(self) -> int:
        with self._lock:
            return self._evictions

    # -- allocation ------------------------------------------------------
    def _take_one_locked(self) -> int:
        if self._free:
            pid = self._free.pop()
        elif self._lru:
            # evict the coldest zero-ref committed page — committed
            # data is a recomputable cache, live requests' pages are not
            pid, _ = self._lru.popitem(last=False)
            page = self._pages.pop(pid)
            assert page.refs == 0, "evicting a referenced page"
            if page.key is not None:
                self._by_key.pop(page.key, None)
            self._evictions += 1
        else:
            raise CacheExhausted(
                f"kv cache exhausted: {self.capacity} pages all referenced "
                f"by live requests (page={self.spec.page_tokens} tokens)")
        self._pages[pid] = _Page()
        self._pages[pid].refs = 1
        return pid

    def alloc(self, n: int) -> List[int]:
        """Reserve ``n`` fresh pages (refcount 1 to the caller), evicting
        cold committed pages as needed.  All-or-nothing: on
        :class:`CacheExhausted` no page moved."""
        with self._lock:
            if n > len(self._free) + len(self._lru):
                raise CacheExhausted(
                    f"need {n} pages, {len(self._free)} free + "
                    f"{len(self._lru)} evictable of {self.capacity}")
            out = [self._take_one_locked() for _ in range(n)]
            self._update_gauge()
            return out

    def release(self, page_ids: Sequence[int]) -> None:
        """Drop one reference per page.  Zero-ref committed pages park
        in the LRU (reusable); zero-ref uncommitted pages return to the
        free list — their data is dead and must never be served."""
        with self._lock:
            for pid in page_ids:
                page = self._pages.get(pid)
                if page is None or page.refs <= 0:
                    raise ValueError(f"release of non-live page {pid}")
                page.refs -= 1
                if page.refs == 0:
                    if page.key is not None:
                        self._lru[pid] = None
                        self._lru.move_to_end(pid)
                    else:
                        del self._pages[pid]
                        self._free.append(pid)
            self._update_gauge()

    # -- page data -------------------------------------------------------
    def put_page_data(self, pid: int, k: np.ndarray, v: np.ndarray,
                      whole: bool = True) -> None:
        """Fill a reserved page's host copy (``spec.part_shape`` each).
        ``whole=False``: the window layers' part is not there any more."""
        want = self.spec.part_shape(0), self.spec.part_shape(1)
        if (tuple(k.shape), tuple(v.shape)) != want:
            raise ValueError(
                f"page data shapes {k.shape}, {v.shape} != {want}")
        with self._lock:
            page = self._pages.get(pid)
            if page is None or page.refs <= 0:
                raise ValueError(f"put_page_data on non-live page {pid}")
            page.k = np.ascontiguousarray(k)
            page.v = np.ascontiguousarray(v)
            page.whole = bool(whole)

    def page_data(self, pid: int) -> Tuple[np.ndarray, np.ndarray]:
        with self._lock:
            page = self._pages.get(pid)
            if page is None or page.refs <= 0:
                raise ValueError(f"page_data on non-live page {pid}")
            if page.k is None or page.v is None:
                raise ValueError(f"page {pid} holds no data")
            return page.k, page.v

    # -- prefix reuse ----------------------------------------------------
    def commit_chain(self, tokens: Sequence[int],
                     page_ids: Sequence[int]) -> int:
        """Register the caller's filled pages under the prefix chain of
        ``tokens`` (only FULL pages commit).  A chain link already
        committed keeps the incumbent page (first writer wins — both
        hold identical K/V by construction).  Returns committed count.
        The caller still holds its references; release() parks the
        committed ones in the LRU."""
        digests = chain_hashes(tokens, self.spec.page_tokens)
        committed = 0
        with self._lock:
            for i, (digest, pid) in enumerate(zip(digests, page_ids)):
                page = self._pages.get(pid)
                if page is None or page.refs <= 0:
                    raise ValueError(f"commit of non-live page {pid}")
                if page.k is None:
                    break  # pages are filled in order; stop at the gap
                if digest in self._by_key:
                    # ... unless the incumbent lacks the window layers'
                    # rows and this page has them: it takes the data
                    held = self._pages[self._by_key[digest]]
                    if page.whole and not held.whole:
                        held.k, held.v, held.whole = page.k, page.v, True
                    continue
                page.key = digest
                page.prefix = np.asarray(
                    tokens[:(i + 1) * self.spec.page_tokens], np.int64)
                self._by_key[digest] = pid
                committed += 1
        return committed

    def lookup(self, tokens: Sequence[int]) -> Tuple[List[int], int]:
        """Longest committed prefix of ``tokens``: ``(page_ids,
        n_cached_tokens)``.  Returned pages are RETAINED for the caller
        (refcount +1, pulled out of the LRU) — they cannot be evicted
        under the request that is about to attend to them."""
        digests = chain_hashes(tokens, self.spec.page_tokens)
        out: List[int] = []
        with self._lock:
            for digest in digests:
                pid = self._by_key.get(digest)
                if pid is None:
                    break
                page = self._pages[pid]
                page.refs += 1
                if page.refs == 1:
                    self._lru.pop(pid, None)
                out.append(pid)
            return out, len(out) * self.spec.page_tokens

    def reusable(self, page_ids: Sequence[int]) -> bool:
        """May the prefix these pages cover (a chain, in order) be
        restored into a slot?  Its last ``spec.window`` positions must
        come from whole pages: a window layer attends to them next, and
        a page that is not whole no longer has them.  (The full layers'
        rows are in every page, so earlier pages need not be whole.)  Of
        a model whose pages cannot be handed on (``spec.unpaged``)
        never."""
        if self.spec.unpaged:
            return False
        if not self.spec.window:
            return True
        tail = -(-self.spec.window // self.spec.page_tokens)
        with self._lock:
            return all(self._pages[pid].whole for pid in page_ids[-tail:])

    # -- durable snapshot (kf-persist) -----------------------------------
    def snapshot_committed(self) -> Dict[str, np.ndarray]:
        """Portable image of every committed page that still holds data:
        flat ``{name: array}`` suitable as a :class:`~kungfu_tpu.elastic.
        persist.PersistPlane` ``replicated`` dict.  Per page *j*:
        ``kv{j}_p`` covering token prefix (int64), ``kv{j}_k``/``kv{j}_v``
        the K/V blocks, ``kv{j}_c`` a content digest over the K/V bytes.
        The chain hash itself is deliberately NOT stored — the restoring
        pool recomputes it from the prefix tokens, so a page can only
        ever re-enter a cache under the key its own tokens derive."""
        out: Dict[str, np.ndarray] = {}
        with self._lock:
            j = 0
            for pid in self._by_key.values():
                page = self._pages.get(pid)
                if (page is None or page.k is None or page.v is None
                        or page.prefix is None):
                    continue
                out[f"kv{j}_p"] = np.array(page.prefix, np.int64)
                out[f"kv{j}_k"] = np.array(page.k)
                out[f"kv{j}_v"] = np.array(page.v)
                out[f"kv{j}_c"] = np.frombuffer(
                    _content_digest(page.k, page.v), np.uint8).copy()
                if not page.whole:
                    out[f"kv{j}_w"] = np.zeros((), np.uint8)
                j += 1
        return out

    def restore_committed(self, snap: Dict[str, np.ndarray]
                          ) -> Tuple[int, int]:
        """Re-commit a :meth:`snapshot_committed` image into THIS pool:
        ``(restored, rejected)``.  Every page is verified before
        adoption — prefix length must tile whole pages, K/V shapes must
        match this pool's spec, and the content digest must reproduce
        (a torn/corrupted page is *rejected*, never served).  The chain
        hash is recomputed from the prefix tokens via
        :func:`chain_hashes`; a digest already committed here keeps the
        incumbent (idempotent restore).  A pool too full to adopt a
        verified page counts it rejected — restore never evicts live
        requests' pages."""
        restored = rejected = 0
        pt = self.spec.page_tokens
        shapes = self.spec.part_shape(0), self.spec.part_shape(1)
        idx = sorted(int(name[2:-2]) for name in snap
                     if name.startswith("kv") and name.endswith("_p")
                     and name[2:-2].isdigit())
        for j in idx:
            prefix = snap.get(f"kv{j}_p")
            k = snap.get(f"kv{j}_k")
            v = snap.get(f"kv{j}_v")
            want = snap.get(f"kv{j}_c")
            if (prefix is None or k is None or v is None or want is None
                    or len(prefix) == 0 or len(prefix) % pt
                    or (tuple(np.shape(k)), tuple(np.shape(v))) != shapes):
                rejected += 1
                continue
            k = np.ascontiguousarray(k, np.dtype(self.spec.dtype))
            v = np.ascontiguousarray(v, np.dtype(self.spec.dtype))
            if _content_digest(k, v) != bytes(np.asarray(want, np.uint8)):
                rejected += 1
                continue
            digest = chain_hashes(
                np.asarray(prefix, np.int64).tolist(), pt)[-1]
            whole = bool(np.all(snap.get(f"kv{j}_w", 1)))
            if self._adopt_committed(digest, prefix, k, v, whole):
                restored += 1
            else:
                rejected += 1
        return restored, rejected

    def _adopt_committed(self, digest: bytes, prefix: np.ndarray,
                         k: np.ndarray, v: np.ndarray,
                         whole: bool = True) -> bool:
        """Install a verified page as committed + parked (zero refs, in
        the LRU).  ``True`` also when the digest is already committed —
        the restore's goal state holds either way."""
        with self._lock:
            if digest in self._by_key:
                return True
            if not self._free and not self._lru:
                return False  # only live pages left; never steal those
            pid = self._take_one_locked()
            page = self._pages[pid]
            page.k = np.ascontiguousarray(k)
            page.v = np.ascontiguousarray(v)
            page.prefix = np.asarray(prefix, np.int64)
            page.whole = whole
            page.key = digest
            self._by_key[digest] = pid
            page.refs = 0
            self._lru[pid] = None
            self._lru.move_to_end(pid)
            self._update_gauge()
            return True

    # -- introspection ---------------------------------------------------
    def live_refs(self) -> Dict[int, int]:
        """``{page id: refcount}`` for referenced pages (tests)."""
        with self._lock:
            return {pid: p.refs for pid, p in self._pages.items()
                    if p.refs > 0}

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "capacity": self.capacity,
                "free": len(self._free),
                "cached": len(self._lru),
                "live": sum(1 for p in self._pages.values() if p.refs > 0),
                "evictions": self._evictions,
                "bytes": (self.capacity - len(self._free))
                * self.spec.page_bytes,
            }
