"""The 3-rank in-process training mesh of the xray and sentinel gates.

Three host-plane peers in one process (STAR strategy, python transport so
the chaos hooks apply) train a small transformer: a step is a timed batch
fetch (the ``input`` span), one real jitted forward and backward pass a
rank, and an all-reduce of a gradient-sized buffer.  Chaos ``delay``
clauses throttle the 0<->1 link: ``WIRE_MS`` on both send directions, so
every rank of the barrier collective pays the wire, and twice that on
rank 1's receive from rank 0, so rank 1 leaves each collective last by a
margin no scheduling jitter can flip — the straggler a verdict must name.
"""

from __future__ import annotations

import os
import threading
import time

WIRE_MS = 30
RANKS = 3
BATCH, SEQ = 2, 32


def link_delay_spec(after_step: int | None = None) -> str:
    """The planted fault; with ``after_step`` it stays inert until
    ``chaos.note_step`` announces that step."""
    gate = "" if after_step is None else f",after_step={after_step}"
    return ";".join([
        f"delay:ms={WIRE_MS},rank=0,peer=1,on=send{gate}",
        f"delay:ms={WIRE_MS},rank=1,peer=0,on=send{gate}",
        f"delay:ms={2 * WIRE_MS},rank=1,peer=0,on=recv{gate}",
    ])


class ChaosMesh:
    """Build with the chaos spec before anything else reads the
    environment; ``close()`` when done."""

    def __init__(self, chaos_spec: str):
        os.environ["KF_NATIVE_ENGINE"] = "0"  # chaos hooks ride the py path
        os.environ["KF_CONFIG_ENABLE_TRACE"] = "1"
        os.environ.setdefault("KF_CONFIG_LOG_LEVEL", "WARNING")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ["KF_CHAOS_SPEC"] = chaos_spec

        import jax
        import jax.numpy as jnp
        import numpy as np

        from kungfu_tpu.models.transformer import (Transformer,
                                                   TransformerConfig)
        from kungfu_tpu.peer import Peer
        from kungfu_tpu.plan import Cluster, PeerList, parse_strategy
        from kungfu_tpu.runner.mp import _free_ports
        from kungfu_tpu.utils.envs import Config

        self.cfg = TransformerConfig(vocab_size=512, d_model=128, n_layers=2,
                                     n_heads=4, d_ff=512, max_seq=64)
        model = Transformer(self.cfg)
        self._params = model.init(jax.random.PRNGKey(0))
        self._grad = jax.jit(
            jax.grad(lambda p, ids, tg: model.loss(p, (ids, tg))))
        # compile outside the steps the gates judge
        warm = jnp.zeros((BATCH, SEQ), jnp.int32)
        jax.block_until_ready(self._grad(self._params, warm, warm))

        # kernel-assigned ports: two checkouts can run a gate side by side
        *ports, runner_port = _free_ports(RANKS + 1)
        workers = PeerList.parse(",".join(f"127.0.0.1:{p}" for p in ports))
        cluster = Cluster(PeerList.parse(f"127.0.0.1:{runner_port}"), workers)
        self.peers = [Peer(Config(self_id=w, cluster=cluster))
                      for w in workers]
        for p in self.peers:
            p.config.strategy = parse_strategy("STAR")
            p.start()

        self._grad_buf = np.ones(50_000, np.float32)  # ~200 KiB on the wire
        # one Generator a rank thread: numpy Generators are not thread-safe
        self._rngs = [np.random.default_rng(r) for r in range(RANKS)]

    def _rank_step(self, rank: int, pace_s: float) -> float:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from kungfu_tpu.monitor import timeline

        t0 = time.perf_counter()
        with timeline.span("input", "batch.next", rank=rank):
            ids = self._rngs[rank].integers(
                0, self.cfg.vocab_size, (BATCH, SEQ)).astype(np.int32)
        ids = jnp.asarray(ids)
        jax.block_until_ready(self._grad(self._params, ids, ids))
        out = self.peers[rank].engine().all_reduce(self._grad_buf, op="sum")
        assert float(out[0]) == float(RANKS)
        pad = pace_s - (time.perf_counter() - t0)
        if pad > 0:
            time.sleep(pad)
        return time.perf_counter() - t0

    def step(self, pace_s: float = 0.0, timeout: float = 120.0):
        """One step on every rank at once; each rank's seconds.  A step
        shorter than ``pace_s`` sleeps up to it (an input-bound loop's
        fixed cadence)."""
        outs, errs = [None] * RANKS, []

        def wrap(rank):
            try:
                outs[rank] = self._rank_step(rank, pace_s)
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=wrap, args=(r,), daemon=True)
              for r in range(RANKS)]
        for t in ts:
            t.start()
        deadline = time.monotonic() + timeout
        for t in ts:
            t.join(max(0.0, deadline - time.monotonic()))
        if errs:
            raise errs[0]
        if any(t.is_alive() for t in ts):
            raise TimeoutError("chaos mesh hung")
        return outs

    def close(self) -> None:
        for p in self.peers:
            p.close()
        os.environ.pop("KF_CHAOS_SPEC", None)
