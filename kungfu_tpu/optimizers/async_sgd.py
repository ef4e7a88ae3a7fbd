"""PairAveraging — AD-PSGD asynchronous gossip.

Reference ``async_sgd.py:71-142`` + ``peer_to_peer.cpp``: each step a
worker (1) pulls a random peer's model from that peer's in-memory
versioned store, (2) averages it 0.5/0.5 into its own weights, (3) applies
its local gradients, (4) publishes the new model.  No collectives, no
global synchronization — by design.  On TPU this runs on the **host
channel** (CPU NICs), not the ICI: gossip is deliberately not a collective,
and pulling a ~100MB model is control-plane-scale traffic that overlaps
with device compute.

The model travels as one fused bf16/f32 buffer (reference fuses into a
``ModelBuffer`` too, ``model_buffer.hpp:13-53``).
"""

from __future__ import annotations

import random
import threading
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from kungfu_tpu.ops.fuse import defuse, fuse
from kungfu_tpu.utils.log import get_logger

_log = get_logger("pair-avg")


class PairAveragingOptimizer:
    """Host-driven gossip optimizer.

    Usage::

        opt = PairAveragingOptimizer(optax.sgd(0.1), peer)
        state = opt.init(params)            # publishes + barrier
        params, state = opt.step(params, grads, state)
    """

    def __init__(
        self,
        inner: optax.GradientTransformation,
        peer=None,
        name: str = "model",
        selector: str = "random",
        fuse_dtype=jnp.float32,
        seed: int = 0,
    ):
        if peer is None:
            from kungfu_tpu.python import init as _init

            peer = _init()
        self.inner = inner
        self.peer = peer
        self.name = name
        self.selector = selector
        self.fuse_dtype = fuse_dtype
        self._rng = random.Random(seed + peer.rank())
        self._rr_next = 0
        self._step_count = 0
        self._recv_buf = None  # reused registered-receive buffer
        #: cumulative wall seconds / bytes spent inside blob pulls (their
        #: ratio is the pull bandwidth)
        self.pull_seconds = 0.0
        self.pull_bytes = 0
        #: steps that averaged with a pulled model / fell back to local
        self.averaged_steps = 0
        self.local_steps = 0

        # ONE compiled program per step flavor: average with the pulled
        # model (when a pull landed), apply local gradients, and return
        # the updated params together with their fused buffer — so the
        # publish is a zero-copy view of jit output, not a re-fuse +
        # tobytes (two full-model copies per step gone)
        def _step(params, grads, state, other_buf):
            if other_buf is not None:
                mine, spec = fuse(params, dtype=self.fuse_dtype)
                params = defuse(0.5 * mine + 0.5 * other_buf, spec)
            updates, state = self.inner.update(grads, state, params)
            params = optax.apply_updates(params, updates)
            out_buf, _ = fuse(params, dtype=self.fuse_dtype)
            return params, state, out_buf

        self._step_avg_jit = jax.jit(_step)
        self._step_local_jit = jax.jit(
            lambda params, grads, state: _step(params, grads, state, None)
        )

    # -- store IO --------------------------------------------------------
    # The model travels as RAW BYTES (a uint8 view): the store/serve/
    # registered-receive chain rides the buffer protocol, which ml_dtypes
    # extension dtypes (bfloat16 — the fuse_dtype that HALVES gossip wire
    # bytes) do not export.  The view is zero-copy both ways.
    def _serialize(self, params):
        buf, _ = fuse(params, dtype=self.fuse_dtype)
        # np.asarray of a CPU-resident jax array is a zero-copy readonly
        # view; the store takes it without snapshotting (copy=False) —
        # jax arrays are immutable, so the handover is safe
        return np.asarray(buf).view(np.uint8)

    def _deserialize_buf(self, blob):
        raw = (np.frombuffer(blob, np.uint8)
               if isinstance(blob, (bytes, bytearray, memoryview))
               else np.asarray(blob).view(np.uint8))
        return jnp.asarray(raw.view(np.dtype(self.fuse_dtype)))

    def _model_nbytes(self, params) -> int:
        numel = int(np.sum([int(np.prod(l.shape)) for l in
                            jax.tree_util.tree_leaves(params)]))
        return numel * np.dtype(self.fuse_dtype).itemsize

    def _publish(self, params) -> None:
        self.peer.save(self.name, self._serialize(params),
                       version=str(self._step_count), copy=False)

    def _publish_buf(self, fused) -> None:
        self.peer.save(self.name, np.asarray(fused).view(np.uint8),
                       version=str(self._step_count), copy=False)

    def _select_peer(self) -> Optional[int]:
        n, me = self.peer.size(), self.peer.rank()
        others = [r for r in range(n) if r != me]
        if not others:
            return None
        if self.selector == "roundrobin":
            target = others[self._rr_next % len(others)]
            self._rr_next += 1
            return target
        return self._rng.choice(others)

    # -- optimizer surface -----------------------------------------------
    def init(self, params) -> optax.OptState:
        """Publish the initial model and barrier so every peer has
        something to serve before the first pull (reference
        ``async_sgd.py:110-120``: save fused model + barrier at step 0)."""
        self._publish(params)
        self.peer.barrier()
        return self.inner.init(params)

    def _pull(self, target):
        """Pull the target's fused model into the reused receive buffer
        (socket→buffer on the native backend).  Returns the filled numpy
        view or None."""
        import time as _time

        if self._recv_buf is None:
            self._recv_buf = np.empty(self._model_nbytes(self._last_params),
                                      np.uint8)
        t0 = _time.perf_counter()
        try:
            # misses are tolerated by design — bound the connect ladder
            # so a dead target costs seconds, not 500x200 ms on the
            # critical path
            got = self.peer.request_into(target, self.name,
                                         self._recv_buf, send_retries=25)
        except (TimeoutError, ConnectionError, OSError) as e:
            _log.debug("pull from %d failed: %s", target, e)
            return None
        dt = _time.perf_counter() - t0
        if got is None:
            return None
        self.pull_seconds += dt
        self.pull_bytes += memoryview(got).nbytes
        return got

    def step(self, params, grads, state):
        """One gossip step; returns ``(new_params, new_state)``."""
        self._last_params = params
        target = self._select_peer()
        other = None
        if target is not None:
            blob = self._pull(target)
            if blob is not None:
                other = self._deserialize_buf(blob)
            else:
                _log.debug("peer %d had no %r yet", target, self.name)
        if other is not None:
            params, state, fused = self._step_avg_jit(params, grads, state, other)
            self.averaged_steps += 1
        else:
            params, state, fused = self._step_local_jit(params, grads, state)
            self.local_steps += 1
        self._step_count += 1
        self._publish_buf(fused)
        return params, state


class _ModelPuller(threading.Thread):
    """Free-running background model puller with triple-buffered landings.

    The reference keeps the training step off the wire with a
    double-buffered background request plus a memcpy on landing
    (``tensorflow/ops/cpu/peer_to_peer.cpp:156-258``: prefetch_buf →
    model_buf copy under a mutex).  Here three slots rotate ownership so a
    landing is a pointer swap, never a model-sized copy:

    * ``writing`` — the slot the in-flight registered receive fills
      (socket→buffer on the native backend),
    * ``ready`` — the freshest landed model, waiting to be taken,
    * ``read`` — checked out by the consumer's last :meth:`take`.

    With one writer and one consumer, at most one slot is in each state,
    so three suffice and no state ever tears.  The consumer's read slot is
    only recycled by its *next* take — by then the jitted step that
    averaged with it has materialized (the publish synchronizes on the
    fused output), so the puller never overwrites bytes a computation
    might still read.
    """

    def __init__(
        self,
        peer,
        name: str,
        nbytes: int,
        select: Callable[[], Optional[int]],
        pull_timeout: float = 10.0,
        min_interval: float = 0.0,
        paced: bool = False,
    ):
        super().__init__(name=f"kf-gossip-pull-{name}", daemon=True)
        self.peer = peer
        self.blob_name = name
        self._select = select
        # raw byte buffers: the wire rides the buffer protocol, which
        # ml_dtypes fuse dtypes (bfloat16) do not export — the consumer
        # reinterprets on take (PairAveragingOptimizer._deserialize_buf)
        self._slots = [np.empty(nbytes, np.uint8) for _ in range(3)]
        self._free = [0, 1, 2]
        self._ready: Optional[int] = None
        self._read: Optional[int] = None
        self._lock = threading.Lock()
        self._stop_evt = threading.Event()
        self.landed = threading.Event()  #: set on every landing
        self.pull_timeout = pull_timeout
        self.min_interval = min_interval
        #: paced mode: pull only when :meth:`kick`ed, at most one in
        #: flight — the reference's one-prefetch-per-step rate limit
        #: (``AsyncRequestModel``: ``if (!is_requesting_) ...``), which
        #: keeps the wire from starving the step it overlaps with
        self.paced = paced
        self._kick = threading.Event()
        #: landing sequence number (0 = nothing landed yet)
        self.seq = 0
        self._take_seq = 0
        self.pull_seconds = 0.0
        self.pull_bytes = 0
        self.misses = 0

    def kick(self) -> None:
        """Request one pull (paced mode); no-op when one is in flight."""
        self._kick.set()

    # -- puller side ------------------------------------------------------
    def run(self) -> None:  # noqa: D102
        while not self._stop_evt.is_set():
            if self.paced:
                if not self._kick.wait(0.1):
                    continue
                self._kick.clear()
            try:
                target = self._select()
            except Exception as e:  # noqa: BLE001 — elastic churn can
                # momentarily drop self from the worker list (rank()
                # raises); the puller must outlive it
                _log.debug("peer selection failed: %s", e)
                target = None
            if target is None:
                self._stop_evt.wait(0.05)
                continue
            with self._lock:
                w = self._free.pop()
            t0 = time.perf_counter()
            try:
                # bounded connect ladder: a dead target must fail within
                # ~pull_timeout, or close() could not join this thread
                # and the peer teardown would race the in-flight call
                got = self.peer.request_into(
                    target, self.blob_name, self._slots[w],
                    timeout=self.pull_timeout,
                    send_retries=max(1, int(self.pull_timeout / 0.2)),
                )
            except Exception as e:  # noqa: BLE001 — peer churn is normal
                _log.debug("async pull from %d failed: %s", target, e)
                got = None
            dt = time.perf_counter() - t0
            landed = got is not None and memoryview(got).nbytes == \
                self._slots[w].nbytes
            if landed and got is not self._slots[w]:
                # size-matched blob that took the queued path (or the
                # local-serve path): land it via one copy
                self._slots[w][:] = np.frombuffer(got, self._slots[w].dtype)
            with self._lock:
                if landed:
                    if self._ready is not None:
                        self._free.append(self._ready)
                    self._ready = w
                    self.seq += 1
                    self.pull_seconds += dt
                    self.pull_bytes += self._slots[w].nbytes
                else:
                    self._free.append(w)
                    self.misses += 1
            if landed:
                self.landed.set()
            if self.min_interval:
                self._stop_evt.wait(self.min_interval)

    # -- consumer side ----------------------------------------------------
    def take(self):
        """Return ``(buf, seq)`` of the freshest landed model, or ``None``
        when nothing has landed yet.  Reuses the previous landing when no
        new one arrived (reference semantics: the step averages with
        whatever the background request last delivered)."""
        with self._lock:
            if self._ready is not None:
                if self._read is not None:
                    self._free.append(self._read)
                self._read, self._ready = self._ready, None
                self._take_seq = self.seq
            if self._read is None:
                return None
            return self._slots[self._read], self._take_seq

    def wait_landed(self, timeout: float) -> bool:
        """Block until a landing newer than the last take (bounded)."""
        self.landed.clear()
        with self._lock:
            if self._ready is not None:
                return True
        return self.landed.wait(timeout)

    def close(self, timeout: Optional[float] = None) -> None:
        self._stop_evt.set()
        if self.is_alive():
            # worst-case in-flight pull: the bounded connect ladder
            # (~pull_timeout), the registered wait (pull_timeout), and
            # the size-mismatch fallback recv (pull_timeout) in sequence
            waited = (timeout if timeout is not None
                      else 3.0 * self.pull_timeout + 5.0)
            self.join(waited)
            if self.is_alive():
                # teardown proceeding under a live pull would race the
                # channel free (the C++ ApiGuard makes the close wait,
                # but the situation deserves a loud trace)
                _log.warning(
                    "gossip puller still in flight after %.0fs join; "
                    "channel close will drain it", waited)


class AsyncPairAveragingOptimizer(PairAveragingOptimizer):
    """AD-PSGD with the pull **off** the critical path.

    Parity with the reference's ``AsyncModelAveraging`` /
    ``AsyncRequestModel`` pair
    (``tensorflow/ops/cpu/peer_to_peer.cpp:156-258,411-466``): a
    background thread keeps pulling a peer's fused model; ``step()``
    averages with the last *landed* model and never waits on the wire
    (after the blocking first pull, which the reference also does).

    ``max_staleness`` bounds divergence: when the same landed model has
    been consumed that many consecutive steps (the wire has stalled),
    the step blocks — bounded by ``pull_timeout`` — for a fresh landing.
    The reference has no such bound; AD-PSGD's convergence proof assumes
    bounded staleness, so the knob defaults on (16) rather than off.
    """

    def __init__(self, *args, max_staleness: Optional[int] = 16,
                 pull_timeout: float = 10.0, min_interval: float = 0.0,
                 **kwargs):
        super().__init__(*args, **kwargs)
        self.max_staleness = max_staleness
        self._pull_timeout = pull_timeout
        self._min_interval = min_interval
        self._puller: Optional[_ModelPuller] = None
        self._consumed_seq = 0
        self._consumed_same = 0

    def _ensure_puller(self, params) -> None:
        if self._puller is not None:
            return
        self._puller = _ModelPuller(
            self.peer, self.name, self._model_nbytes(params),
            self._select_peer, pull_timeout=self._pull_timeout,
            min_interval=self._min_interval, paced=True,
        )
        self._puller.start()
        self._puller.kick()  # first pull starts racing the first step

    def init(self, params) -> optax.OptState:
        state = super().init(params)
        self._ensure_puller(params)
        return state

    def _await_landing(self) -> bool:
        """Kick-and-wait until a landing (bounded by pull_timeout).  The
        paced puller parks after a miss, so the kick must come first and
        must repeat while waiting — a missed pull (target down, blob not
        yet published) otherwise turns every wait into a guaranteed
        timeout with zero chance of success."""
        deadline = time.monotonic() + self._pull_timeout
        while True:
            self._puller.kick()
            left = deadline - time.monotonic()
            if left <= 0:
                return False
            if self._puller.wait_landed(min(0.5, left)):
                return True

    def step(self, params, grads, state):
        self._last_params = params
        self._ensure_puller(params)
        if self._puller.seq == 0:
            # blocking first pull, like the reference's synchronous
            # Request before the prefetch loop starts
            self._await_landing()
        elif (self.max_staleness is not None
              and self._consumed_same >= self.max_staleness):
            _log.debug("staleness bound hit (%d); waiting for a landing",
                       self._consumed_same)
            self._await_landing()
        took = self._puller.take()
        # start the next pull now — it overlaps this step's compute and
        # publish, landing in time for a later step
        self._puller.kick()
        if took is not None:
            buf, seq = took
            self._consumed_same = (self._consumed_same + 1
                                   if seq == self._consumed_seq else 0)
            self._consumed_seq = seq
            other = self._deserialize_buf(buf)
            params, state, fused = self._step_avg_jit(params, grads, state,
                                                      other)
            self.averaged_steps += 1
        else:
            params, state, fused = self._step_local_jit(params, grads, state)
            self.local_steps += 1
        self._step_count += 1
        self._publish_buf(fused)
        # surface the puller's wire accounting through the same fields the
        # blocking optimizer exposes, so benchmarks read one interface
        self.pull_seconds = self._puller.pull_seconds
        self.pull_bytes = self._puller.pull_bytes
        return params, state

    def close(self) -> None:
        """Stop the background puller (idempotent)."""
        if self._puller is not None:
            self._puller.close()
            self._puller = None
