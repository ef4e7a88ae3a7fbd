"""Pipeline parallelism over the host plane: 1F1B microbatch schedules
driven by async collective handles, with elastic stage re-carving.

The in-mesh pipeline (:class:`~kungfu_tpu.parallel.train.ShardedTrainer`)
runs GPipe ticks as ``lax.scan`` + ``ppermute`` inside ONE ``shard_map``
— right for a single XLA mesh, useless across the DCN where each slice
is its own process world.  This module is the cross-DCN pipeline axis:

* **stages** — contiguous layer ranges of the flagship transformer
  (:func:`stage_partition`); stage 0 owns the embedding, the last stage
  owns ``ln_f`` + the LM head and computes the loss.
* **activation hops** — point-to-point sends/recvs on the collective
  engine's async plane (:meth:`~kungfu_tpu.comm.engine.CollectiveEngine.
  send_async` / ``recv_async``): every hop is a PR-10
  :class:`~kungfu_tpu.comm.engine.CollectiveHandle` whose tag is fixed
  at issue time, so the ``handle-discipline`` lint polices its lifetime
  and the prefetched recv hides the DCN latency under stage compute.
* **schedule** — :func:`schedule_1f1b` (one-forward-one-backward: the
  steady state holds ≤ ``warmup+1`` live activations instead of all
  ``n_micro``), :func:`schedule_interleaved` (each stage owns ``v``
  non-adjacent layer chunks — the virtual-stage schedule is derived by
  a greedy dependency simulation, so any ``v`` is deadlock-free by
  construction), and :func:`schedule_sequential` (the naive baseline
  ``examples/pp_demo.py`` runs 1F1B against).
* **ZeRO composition** — gradients reduce-scatter over the stage's DP
  group in buckets issued as async handles the moment that stage's last
  backward retires; the PP drain (the bubble) hides the DP wire exactly
  the way PR 10's depth-k pipeline hides bucket latency.  Sum order is
  fixed (dp-member order) so the composition stays bitwise against the
  replicated reference.
* **elastic re-carve** — :class:`StageBoundary` commits the stage's
  params + ZeRO opt chunks at the step boundary and ring-mirrors them
  one stage back (same dp lane: ``stride = dp`` ranks — on a multislice
  pod that is exactly one SLICE back, so a whole dead slice's stage
  survives on its predecessor).  On slice loss the survivors re-balance
  layers over the remaining stages via the pure
  :func:`stage_recarve_plan` every rank computes identically (the
  ``reshard_plan`` pattern) instead of aborting — wired into the
  recovery ladder as rung 10 (``elastic/shrink.py``,
  docs/fault_tolerance.md).

Mapping: PP runs across the DCN (slice) axis, TP within the ICI — a
stage rank with ``plan.tp > 1`` shard_maps its layer math over its own
local device mesh (Megatron column/row via :mod:`kungfu_tpu.parallel.
tp`), so the host world is ``pp × dp`` ranks and tensor parallelism
never crosses a slice (docs/pipeline.md).
"""

from __future__ import annotations

import io
import math
import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from kungfu_tpu.monitor import timeline
from kungfu_tpu.utils.log import get_logger

_log = get_logger("pp")

#: schedule vocabulary (KF_PP_SCHEDULE / ParallelPlan.pp_schedule)
SCHEDULES = ("1f1b", "interleaved", "sequential")

#: outstanding async p2p handles the pipeline keeps in flight; must stay
#: below the engine async pool (8 workers) or queued sends could starve
#: behind blocked recvs (see CollectiveEngine.recv_async)
_MAX_INFLIGHT_SENDS = 4
_PREFETCH = 2


# -- pure stage / schedule math --------------------------------------------
def stage_partition(n_layers: int, n_stages: int) -> List[Tuple[int, int]]:
    """Contiguous ``[lo, hi)`` layer range per stage, balanced with the
    remainder spread over the EARLIEST stages (they do not carry the
    LM-head loss work).  Pure and deterministic — every rank computes
    the identical map, like ``reshard_plan``."""
    if n_stages < 1:
        raise ValueError(f"n_stages must be >= 1, got {n_stages}")
    if n_layers < n_stages:
        raise ValueError(
            f"{n_layers} layers cannot fill {n_stages} stages "
            "(a stage with no layers would forward its input unchanged "
            "— shrink the stage count instead)")
    base, rem = divmod(n_layers, n_stages)
    out, lo = [], 0
    for s in range(n_stages):
        hi = lo + base + (1 if s < rem else 0)
        out.append((lo, hi))
        lo = hi
    return out


def interleaved_partition(n_layers: int, n_stages: int,
                          v: int) -> List[List[Tuple[int, int]]]:
    """Layer ranges for the interleaved schedule: ``n_stages * v``
    contiguous groups; stage ``s`` owns groups ``[s, s + S, s + 2S, …]``
    (chunk ``c`` of stage ``s`` is virtual stage ``c * S + s``).
    Returns ``[stage][chunk] -> (lo, hi)``."""
    if v < 1:
        raise ValueError(f"interleave must be >= 1, got {v}")
    groups = stage_partition(n_layers, n_stages * v)
    return [[groups[c * n_stages + s] for c in range(v)]
            for s in range(n_stages)]


def schedule_1f1b(n_micro: int, n_stages: int, stage: int
                  ) -> List[Tuple[str, int, int]]:
    """The classic one-forward-one-backward op list for ``stage``:
    ``[(kind, microbatch, chunk=0)]`` with kinds ``"F"``/``"B"``.
    Warmup ``min(S - 1 - stage, m)`` forwards, steady-state F/B pairs,
    backward drain.  Backwards retire in microbatch order on every
    stage — the property that keeps gradient accumulation bitwise
    against the sequential reference."""
    if not 0 <= stage < n_stages:
        raise ValueError(f"stage {stage} outside {n_stages} stages")
    warm = min(n_stages - 1 - stage, n_micro)
    ops: List[Tuple[str, int, int]] = []
    for m in range(warm):
        ops.append(("F", m, 0))
    for k in range(n_micro - warm):
        ops.append(("F", warm + k, 0))
        ops.append(("B", k, 0))
    for m in range(n_micro - warm, n_micro):
        ops.append(("B", m, 0))
    return ops


def schedule_sequential(n_micro: int, n_stages: int, stage: int
                        ) -> List[Tuple[str, int, int]]:
    """Naive sequential microbatching — each microbatch runs its full
    forward AND backward through the whole pipe before the next starts,
    so every DCN hop sits on the critical path.  The baseline
    ``examples/pp_demo.py`` runs 1F1B against."""
    del n_stages, stage
    ops: List[Tuple[str, int, int]] = []
    for m in range(n_micro):
        ops.append(("F", m, 0))
        ops.append(("B", m, 0))
    return ops


def schedule_interleaved(n_micro: int, n_stages: int, stage: int,
                         v: int) -> List[Tuple[str, int, int]]:
    """Interleaved (virtual-stage) schedule: stage ``s`` executes ops
    for its ``v`` chunks, ordered by a greedy global simulation of the
    ``S*v``-virtual-stage dependency DAG (each physical stage runs one
    ready op per tick, preferring backwards — the 1F1B shape emerges).
    Simulated, not formula'd: the op order is then consistent with a
    valid global schedule by construction, so the blocking recvs of a
    real run can never deadlock, for any ``(m, S, v)``."""
    if v == 1:
        return schedule_1f1b(n_micro, n_stages, stage)
    V = n_stages * v
    f_done = [[False] * n_micro for _ in range(V)]
    b_done = [[False] * n_micro for _ in range(V)]
    per_stage: List[List[Tuple[str, int, int]]] = [
        [] for _ in range(n_stages)]
    remaining = 2 * V * n_micro

    def ready(phys: int):
        """Best ready op for a physical stage: prefer B (drain memory),
        then the lowest (chunk, microbatch) F — deterministic.  Both
        kinds advance strictly in microbatch order per chunk, so
        gradient accumulation order matches the sequential reference
        (the bitwise contract)."""
        best = None
        for c in range(v):
            vs = c * n_stages + phys
            mb_b = next((m for m in range(n_micro)
                         if not b_done[vs][m]), None)
            if mb_b is not None and f_done[vs][mb_b] and (
                    vs == V - 1 or b_done[vs + 1][mb_b]):
                return ("B", mb_b, c)
            if best is None:
                mb_f = next((m for m in range(n_micro)
                             if not f_done[vs][m]), None)
                if mb_f is not None and (
                        vs == 0 or f_done[vs - 1][mb_f]):
                    best = ("F", mb_f, c)
        return best

    while remaining:
        progressed = False
        for phys in range(n_stages):
            op = ready(phys)
            if op is None:
                continue
            kind, m, c = op
            vs = c * n_stages + phys
            (f_done if kind == "F" else b_done)[vs][m] = True
            per_stage[phys].append(op)
            remaining -= 1
            progressed = True
        if not progressed:  # pragma: no cover - the DAG always has a root
            raise AssertionError("interleaved schedule wedged")
    return per_stage[stage]


def build_schedule(name: str, n_micro: int, n_stages: int, stage: int,
                   v: int = 1) -> List[Tuple[str, int, int]]:
    if name not in SCHEDULES:
        raise ValueError(f"unknown pp schedule {name!r}; one of {SCHEDULES}")
    if name == "interleaved":
        return schedule_interleaved(n_micro, n_stages, stage, v)
    if name == "sequential":
        return schedule_sequential(n_micro, n_stages, stage)
    if v != 1:
        raise ValueError("interleave > 1 requires the interleaved schedule")
    return schedule_1f1b(n_micro, n_stages, stage)


# -- pure re-carve planning -------------------------------------------------
#: pseudo-layer ids for the edge-owned params in recarve plans
_UNIT_EMBED = -1
_UNIT_FINAL = -2


def stage_recarve_plan(n_layers: int, old_n: int, new_n: int
                       ) -> List[Tuple[int, int, int]]:
    """Pure unit-move plan for an ``old_n -> new_n`` stage re-balance:
    ``[(unit, old_stage, new_stage)]`` where unit is a layer index, or
    ``-1`` (embedding block, stage 0's) / ``-2`` (ln_f + head, the last
    stage's).  Every rank computes the identical plan — the
    ``reshard_plan`` pattern at stage granularity.  Units whose owner
    does not change are omitted only when old and new stage indices
    AND maps coincide; callers move exactly what the plan lists."""
    old_map = stage_partition(n_layers, old_n)
    new_map = stage_partition(n_layers, new_n)

    def old_owner(layer: int) -> int:
        for s, (lo, hi) in enumerate(old_map):
            if lo <= layer < hi:
                return s
        raise AssertionError(layer)

    def new_owner(layer: int) -> int:
        for s, (lo, hi) in enumerate(new_map):
            if lo <= layer < hi:
                return s
        raise AssertionError(layer)

    plan = [(_UNIT_EMBED, 0, 0), (_UNIT_FINAL, old_n - 1, new_n - 1)]
    plan += [(l, old_owner(l), new_owner(l)) for l in range(n_layers)]
    return plan


def _chunk_splits(old_off: int, new_off: int, length: int,
                  oc: int, nc: int):
    """Split one contiguous flat segment by the chunk boundaries of BOTH
    the old geometry (chunk width ``oc``) and the new (``nc``):
    yields ``(old_member, new_member, old_off, new_off, len)``."""
    done = 0
    while done < length:
        oo, no = old_off + done, new_off + done
        jo, jn = oo // oc, no // nc
        lim = min(length - done,
                  (jo + 1) * oc - oo,
                  (jn + 1) * nc - no)
        yield (jo, jn, oo, no, lim)
        done += lim


# -- per-stage transformer compute ------------------------------------------
def stacked_from_transformer(cfg, tparams) -> dict:
    """Pack per-layer :meth:`Transformer.init` params into the stacked
    layout the pipeline carves stages from (same layout as
    :meth:`ShardedTrainer.from_transformer_params`, host-side)."""
    import jax
    import jax.numpy as jnp

    L = cfg.n_layers
    stacked = {
        "embed": tparams["embed"],
        "layers": jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[tparams[f"layer_{i}"]
                                         for i in range(L)]),
        "ln_f": tparams["ln_f"],
        "head": tparams["head"],
    }
    if cfg.pos == "learned":
        stacked["pos_embed"] = tparams["pos_embed"]
    return stacked


def init_stacked_params(cfg, key) -> dict:
    """Fresh stacked full-model params (flagship transformer init)."""
    from kungfu_tpu.models.transformer import Transformer

    return stacked_from_transformer(cfg, Transformer(cfg).init(key))


def slice_stage_params(cfg, full_stacked, lo: int, hi: int,
                       first: bool, last: bool) -> dict:
    """This stage's param subtree out of the full stacked tree."""
    import jax

    out = {"layers": jax.tree_util.tree_map(
        lambda a: a[lo:hi], full_stacked["layers"])}
    if first:
        out["embed"] = full_stacked["embed"]
        if cfg.pos == "learned":
            out["pos_embed"] = full_stacked["pos_embed"]
    if last:
        out["ln_f"] = full_stacked["ln_f"]
        out["head"] = full_stacked["head"]
    return out


def stage_param_shapes(cfg, lo: int, hi: int, first: bool,
                       last: bool) -> dict:
    """Shape/dtype skeleton of a stage's param subtree — pure (derived
    from the config alone), so EVERY rank can compute EVERY stage's
    flat layout for the re-carve plan without holding its data."""
    import jax
    import jax.numpy as jnp

    D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    n = hi - lo
    f32 = jnp.float32

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, f32)

    layer = {
        "ln1": {"scale": s(n, D), "bias": s(n, D)},
        "ln2": {"scale": s(n, D), "bias": s(n, D)},
        "wq": {"w": s(n, D, D), "b": s(n, D)},
        "wk": {"w": s(n, D, D), "b": s(n, D)},
        "wv": {"w": s(n, D, D), "b": s(n, D)},
        "wo": {"w": s(n, D, D), "b": s(n, D)},
        "ffn_in": {"w": s(n, D, F), "b": s(n, F)},
        "ffn_out": {"w": s(n, F, D), "b": s(n, D)},
    }
    out = {"layers": layer}
    if first:
        out["embed"] = {"table": s(V, D)}
        if cfg.pos == "learned":
            out["pos_embed"] = {"table": s(cfg.max_seq, D)}
    if last:
        out["ln_f"] = {"scale": s(D), "bias": s(D)}
        out["head"] = {"w": s(D, V)}
    return out


def _flat_layout(shapes_tree, lo: int):
    """Flat-offset layout of a stage param tree in ``tree_flatten``
    order: ``[(key, global_row0, rows, rowsize, offset)]``.  ``key`` is
    the path tuple with the layer dimension factored out (a "layers"
    leaf's rows are GLOBAL layer indices ``[lo, hi)``); edge leaves are
    single rows keyed by their path."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path(shapes_tree)
    out = []
    off = 0
    for path, leaf in leaves:
        key = tuple(getattr(p, "key", str(p)) for p in path)
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        if key and key[0] == "layers":
            rows = int(leaf.shape[0])
            out.append((key, lo, rows, size // max(rows, 1), off))
        else:
            out.append((key, 0, 1, size, off))
        off += size
    return out, off


def stage_flat_layouts(cfg, stage_map: Sequence[Tuple[int, int]]):
    """``([layout_per_stage], [total_per_stage])`` for a stage map —
    the pure geometry the re-carve segment plan is computed from."""
    layouts, totals = [], []
    n = len(stage_map)
    for s, (lo, hi) in enumerate(stage_map):
        lay, total = _flat_layout(
            stage_param_shapes(cfg, lo, hi, s == 0, s == n - 1), lo)
        layouts.append(lay)
        totals.append(total)
    return layouts, totals


def flat_recarve_segments(cfg, old_map, new_map):
    """Pure flat-segment plan between two stage maps:
    ``[(old_stage, old_off, new_stage, new_off, length)]`` — for every
    leaf row range of every NEW stage, the contiguous span of the OLD
    stage flat holding the same values.  Segments tile every new stage
    flat exactly (property-tested).  Unit ownership comes from
    :func:`stage_recarve_plan` — ONE computation of "who owns layer l /
    the edges", shared by the unit-level plan and this transport
    plan."""
    old_lay, _ = stage_flat_layouts(cfg, old_map)
    new_lay, _ = stage_flat_layouts(cfg, new_map)
    S_old, S_new = len(old_map), len(new_map)
    if old_map != stage_partition(cfg.n_layers, S_old) \
            or new_map != stage_partition(cfg.n_layers, S_new):
        raise ValueError(
            "stage maps must be stage_partition outputs (the canonical "
            "balanced carve every rank derives identically)")
    unit_plan = stage_recarve_plan(cfg.n_layers, S_old, S_new)
    owner = {u: os_ for (u, os_, _) in unit_plan}

    def old_home(key, grow):
        """(old_stage, offset) of one row of leaf ``key``."""
        if key[0] == "layers":
            s = owner[grow]
            for k, gr0, rows, rowsize, off in old_lay[s]:
                if k == key:
                    return s, off + (grow - gr0) * rowsize, rowsize
            raise AssertionError((key, grow))
        s = owner[_UNIT_EMBED if key[0] in ("embed", "pos_embed")
                  else _UNIT_FINAL]
        for k, _, _, rowsize, off in old_lay[s]:
            if k == key:
                return s, off, rowsize
        raise AssertionError(key)

    segs = []
    for ns in range(S_new):
        for key, gr0, rows, rowsize, noff in new_lay[ns]:
            r = 0
            while r < rows:
                os_, ooff, rs = old_home(key, gr0 + r)
                assert rs == rowsize, (key, rs, rowsize)
                # extend over consecutive rows living contiguously in
                # the SAME old stage
                lo, hi = old_map[os_] if key[0] == "layers" else (0, 0)
                if key[0] == "layers":
                    run = min(rows - r, hi - (gr0 + r))
                else:
                    run = rows - r
                segs.append((os_, ooff, ns, noff + r * rowsize,
                             run * rowsize))
                r += run
    return segs


# -- the per-stage compute module -------------------------------------------
class StageModule:
    """One pipeline stage's transformer math: the layer range
    ``[lo, hi)`` (+ embedding on the first stage, final norm + LM head
    + loss on the last), with forward, recompute-backward
    (activation recomputation — the 1F1B memory contract), and optional
    tensor parallelism over a LOCAL device mesh (TP stays within the
    ICI; only activations cross the DCN)."""

    def __init__(self, cfg, lo: int, hi: int, *, first: bool, last: bool,
                 tp: int = 1, devices=None):
        import jax

        self.cfg, self.lo, self.hi = cfg, int(lo), int(hi)
        self.first, self.last = bool(first), bool(last)
        self.tp = int(tp)
        self.mesh = None
        if self.tp > 1:
            from jax.sharding import Mesh

            if cfg.n_heads % self.tp or cfg.d_ff % self.tp:
                raise ValueError(
                    f"tp={self.tp} must divide n_heads ({cfg.n_heads}) "
                    f"and d_ff ({cfg.d_ff})")
            devs = list(devices) if devices is not None else jax.devices()
            if len(devs) < self.tp:
                raise ValueError(
                    f"tp={self.tp} needs {self.tp} local devices, "
                    f"have {len(devs)}")
            self.mesh = Mesh(np.asarray(devs[: self.tp]), ("tp",))
        self._jit_fwd = jax.jit(self._fwd)
        self._jit_bwd = jax.jit(self._bwd)
        self._jit_loss_bwd = jax.jit(self._loss_bwd)

    # -- parameter layout -------------------------------------------------
    def param_specs(self):
        """PartitionSpecs over the local tp mesh (None when tp == 1)."""
        from jax.sharding import PartitionSpec as P

        if self.mesh is None:
            return None
        col = {"w": P(None, None, "tp"), "b": P(None, "tp")}
        layer = {
            "ln1": {"scale": P(None, None), "bias": P(None, None)},
            "ln2": {"scale": P(None, None), "bias": P(None, None)},
            "wq": dict(col), "wk": dict(col), "wv": dict(col),
            "wo": {"w": P(None, "tp", None), "b": P(None, None)},
            "ffn_in": dict(col),
            "ffn_out": {"w": P(None, "tp", None), "b": P(None, None)},
        }
        out = {"layers": layer}
        if self.first:
            out["embed"] = {"table": P(None, None)}
            if self.cfg.pos == "learned":
                out["pos_embed"] = {"table": P(None, None)}
        if self.last:
            out["ln_f"] = {"scale": P(None), "bias": P(None)}
            out["head"] = {"w": P(None, None)}
        return out

    def place(self, params):
        """Put a host stage-param tree onto this module's device layout
        (tp-sharded over the local mesh when tp > 1)."""
        import jax

        if self.mesh is None:
            return jax.tree_util.tree_map(jax.numpy.asarray, params)
        from jax.sharding import NamedSharding

        return jax.tree_util.tree_map(
            lambda x, spec: jax.device_put(
                x, NamedSharding(self.mesh, spec)),
            params, self.param_specs())

    # -- math --------------------------------------------------------------
    def _positions(self, B: int, S: int):
        import jax.numpy as jnp

        return jnp.broadcast_to(jnp.arange(S), (B, S))

    def _embed(self, params, ids):
        from kungfu_tpu.models import nn

        cfg = self.cfg
        h = nn.embedding_apply(params["embed"], ids,
                               dtype=cfg.compute_dtype)
        if cfg.pos == "learned":
            h = h + nn.embedding_apply(
                params["pos_embed"], self._positions(*ids.shape),
                dtype=cfg.compute_dtype)
        return h

    def _layers_dense(self, params, h, positions):
        """The tp == 1 layer loop — byte-for-byte the flagship
        :meth:`Transformer.hidden` block math."""
        import jax
        import jax.numpy as jnp

        from kungfu_tpu.models import nn
        from kungfu_tpu.models.transformer import _rope, default_attention

        cfg = self.cfg
        dt = cfg.compute_dtype
        H, Hd = cfg.n_heads, cfg.head_dim

        def heads(t):
            B, S, _ = t.shape
            return t.reshape(B, S, H, Hd).transpose(0, 2, 1, 3)

        def merge(t):
            B, Hn, S, D = t.shape
            return t.transpose(0, 2, 1, 3).reshape(B, S, Hn * D)

        for i in range(self.hi - self.lo):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            x = nn.layernorm_apply(lp["ln1"], h)
            q = heads(nn.dense_apply(lp["wq"], x, dtype=dt))
            k = heads(nn.dense_apply(lp["wk"], x, dtype=dt))
            v = heads(nn.dense_apply(lp["wv"], x, dtype=dt))
            if cfg.pos == "rope":
                q, k = _rope(q, k, positions)
            o = default_attention(q, k, v, cfg.causal)
            h = h + nn.dense_apply(lp["wo"], merge(o), dtype=dt)
            x = nn.layernorm_apply(lp["ln2"], h)
            y = nn.gelu(nn.dense_apply(lp["ffn_in"], x, dtype=dt))
            h = h + nn.dense_apply(lp["ffn_out"], y, dtype=dt)
        return h

    def _layers_tp(self, params, h, positions):
        """The tp > 1 layer loop under shard_map over the local mesh:
        Megatron column/row matmuls with the paired psum vjps
        (:mod:`kungfu_tpu.parallel.tp`), attention over the local
        head shard."""
        import jax

        from kungfu_tpu.models import nn
        from kungfu_tpu.models.transformer import _rope, default_attention
        from kungfu_tpu.parallel import tp as tpmod
        from jax import shard_map

        cfg = self.cfg
        dt = cfg.compute_dtype
        H_loc, Hd = cfg.n_heads // self.tp, cfg.head_dim

        def per_device(lparams, h, positions):
            def heads(t):
                B, S, _ = t.shape
                return t.reshape(B, S, H_loc, Hd).transpose(0, 2, 1, 3)

            def merge(t):
                B, Hn, S, D = t.shape
                return t.transpose(0, 2, 1, 3).reshape(B, S, Hn * D)

            for i in range(self.hi - self.lo):
                lp = jax.tree_util.tree_map(
                    lambda a: a[i], lparams["layers"])
                x = nn.layernorm_apply(lp["ln1"], h)
                x = tpmod.tp_region_enter(x, "tp")
                q = heads(tpmod.column_dense(lp["wq"], x, dtype=dt))
                k = heads(tpmod.column_dense(lp["wk"], x, dtype=dt))
                v = heads(tpmod.column_dense(lp["wv"], x, dtype=dt))
                if cfg.pos == "rope":
                    q, k = _rope(q, k, positions)
                o = default_attention(q, k, v, cfg.causal)
                h = h + tpmod.row_dense(lp["wo"], merge(o), "tp", dtype=dt)
                x = nn.layernorm_apply(lp["ln2"], h)
                x = tpmod.tp_region_enter(x, "tp")
                y = nn.gelu(tpmod.column_dense(lp["ffn_in"], x, dtype=dt))
                h = h + tpmod.row_dense(lp["ffn_out"], y, "tp", dtype=dt)
            return h

        from jax.sharding import PartitionSpec as P

        lay_specs = {"layers": self.param_specs()["layers"]}
        f = shard_map(
            per_device, mesh=self.mesh,
            in_specs=(lay_specs, P(), P()), out_specs=P(),
            check_vma=False,
        )
        return f({"layers": params["layers"]}, h, positions)

    def _hidden(self, params, x):
        import jax.numpy as jnp

        if self.first:
            positions = self._positions(*x.shape)
            h = self._embed(params, x)
        else:
            B, S = x.shape[0], x.shape[1]
            positions = self._positions(B, S)
            h = jnp.asarray(x, self.cfg.compute_dtype)
        if self.mesh is not None:
            return self._layers_tp(params, h, positions)
        return self._layers_dense(params, h, positions)

    def _fwd(self, params, x):
        return self._hidden(params, x)

    def _loss(self, params, x, targets):
        import jax.numpy as jnp

        from kungfu_tpu.models import nn
        from kungfu_tpu.ops.pallas.xent import token_nll

        h = self._hidden(params, x)
        hf = nn.layernorm_apply(params["ln_f"], h)
        logits = nn.dense_apply(params["head"], hf).astype(jnp.float32)
        return token_nll(logits, targets)

    def _bwd(self, params, x, dout):
        import jax

        if self.first:
            _, vjpf = jax.vjp(lambda p: self._fwd(p, x), params)
            (dparams,) = vjpf(dout)
            return dparams, None
        _, vjpf = jax.vjp(self._fwd, params, x)
        return vjpf(dout)

    def _loss_bwd(self, params, x, targets):
        import jax
        import jax.numpy as jnp

        if self.first:
            loss, vjpf = jax.vjp(lambda p: self._loss(p, x, targets),
                                 params)
            (dparams,) = vjpf(jnp.ones((), jnp.float32))
            return loss, dparams, None
        loss, vjpf = jax.vjp(
            lambda p, xx: self._loss(p, xx, targets), params, x)
        dparams, dx = vjpf(jnp.ones((), jnp.float32))
        return loss, dparams, dx

    # -- public ------------------------------------------------------------
    def forward(self, params, x):
        """Stage forward; ``x`` is int ids on the first stage, the
        incoming activation elsewhere."""
        return self._jit_fwd(params, x)

    def backward(self, params, x, dout):
        """Recompute-backward: ``(dparams, dx)`` (``dx`` None on the
        first stage — token ids have no cotangent)."""
        return self._jit_bwd(params, x, dout)

    def loss_backward(self, params, x, targets):
        """Last stage only: ``(loss, dparams, dx)`` — the loss forward
        and its vjp in one jitted call (seed 1.0)."""
        if not self.last:
            raise ValueError("loss_backward belongs to the last stage")
        return self._jit_loss_bwd(params, x, targets)


# -- elastic stage boundary -------------------------------------------------
class StageBoundary:
    """Committed step boundary of ONE rank's pipeline stage: the stage
    params as a flat host vector (+ treedef/shapes for restore) and the
    ZeRO-2 optimizer chunk, with a ring-buddy mirror one stage back in
    the SAME dp lane (``stride = dp`` ranks = one slice on a multislice
    pod) so a whole dead stage re-carves from its predecessor — the
    :class:`~kungfu_tpu.elastic.reshard.ZeroBoundary` discipline
    applied to the pipeline axis."""

    def __init__(self):
        self._lock = threading.Lock()
        self._step: Optional[int] = None
        self._cfg = None
        self._stage: Optional[int] = None
        self._n_stages: Optional[int] = None
        self._dp: int = 1
        self._dp_index: int = 0
        self._zero: int = 0
        #: flat stage params [total_s] f32 (params are replicated
        #: within the stage's dp group, so every member holds the full
        #: stage flat)
        self._pflat: Optional[np.ndarray] = None
        #: ZeRO-2 optimizer vec leaves: {leaf_idx: [chunk] np}
        self._opt_vec: Dict[int, np.ndarray] = {}
        self._opt_scal: Dict[int, np.ndarray] = {}
        self._opt_treedef = None
        self._opt_dtypes: Dict[int, np.dtype] = {}
        #: mirror of the successor stage (same dp lane)
        self._buddy: Optional[dict] = None
        self._buddy_stage: Optional[int] = None

    # -- commit ------------------------------------------------------------
    def commit(self, step: int, cfg, stage: int, n_stages: int, dp: int,
               dp_index: int, params, opt_state, zero_stage: int) -> None:
        """Host-copy this rank's stage state as of completed step
        ``step``.  ``opt_state`` is the ZeRO-2 chunk tree (leaves are
        ``[ceil(total/dp)]`` vectors or scalars); a replicated
        (``zero_stage == 0``) optimizer must be stateless — its
        vector leaves have no flat-chunk geometry to re-carve."""
        import jax

        leaves = jax.tree_util.tree_leaves(params)
        pflat = np.concatenate([np.asarray(l).ravel().astype(np.float32)
                                for l in leaves]) if leaves else np.zeros(0)
        oleaves, otd = jax.tree_util.tree_flatten(opt_state)
        vec, scal = {}, {}
        for i, l in enumerate(oleaves):
            a = np.array(l)
            if a.ndim >= 1:
                if zero_stage != 2:
                    raise ValueError(
                        "StageBoundary carries optimizer state through a "
                        "stage re-carve only in the ZeRO-2 flat-chunk "
                        "geometry — use zero_stage=2 or a stateless inner")
                vec[i] = a
            else:
                scal[i] = a
        with self._lock:
            self._step = int(step)
            self._cfg = cfg
            self._stage, self._n_stages = int(stage), int(n_stages)
            self._dp, self._dp_index = int(dp), int(dp_index)
            self._zero = int(zero_stage)
            self._pflat = pflat
            self._opt_vec, self._opt_scal = vec, scal
            self._opt_treedef = otd
            self._opt_dtypes = {i: a.dtype for i, a in vec.items()}
            self._buddy = None
            self._buddy_stage = None

    def step(self) -> Optional[int]:
        with self._lock:
            return self._step

    @property
    def stage(self) -> Optional[int]:
        with self._lock:
            return self._stage

    # -- ring-buddy mirror --------------------------------------------------
    def _blob(self) -> bytes:
        bio = io.BytesIO()
        np.savez(
            bio, pflat=self._pflat,
            meta=np.array([self._step, self._stage, self._n_stages,
                           self._dp, self._dp_index, self._zero], np.int64),
            **{f"v{i}": a for i, a in self._opt_vec.items()},
        )
        return bio.getvalue()

    def replicate_ring(self, chan, workers, tag: str) -> None:
        """Mirror this rank's committed stage onto the same dp lane of
        the PREDECESSOR stage (``stride = dp`` ranks back, ring-wrapped)
        and adopt the successor's — after this, a whole dead stage's
        params and opt chunks survive one stage (= one slice) earlier.
        ``tag`` must be identical on every rank."""
        with self._lock:
            if self._step is None:
                raise ValueError("replicate_ring before any commit")
            blob = self._blob()
            dp, stage, n_stages = self._dp, self._stage, self._n_stages
            dp_index = self._dp_index
        if n_stages < 2:
            return
        world = n_stages * dp
        me = stage * dp + dp_index
        pred = workers[(me - dp) % world]
        succ = workers[(me + dp) % world]
        name = f"kf.ppbuddy.{tag}"
        timeline.event("pp", "buddy-replicate", rank=me,
                       nbytes=len(blob), stage=stage)
        chan.send(pred, name, blob)
        from kungfu_tpu.elastic.reshard import _recv_or_fail

        raw = _recv_or_fail(chan, succ, (me + dp) % world,
                            "pp-buddy", name)
        with np.load(io.BytesIO(raw)) as z:
            buddy = {
                "pflat": z["pflat"],
                "meta": z["meta"],
                "vec": {int(k[1:]): z[k] for k in z.files
                        if k.startswith("v")},
            }
        with self._lock:
            self._buddy = buddy
            self._buddy_stage = (stage + 1) % n_stages

    # -- re-carve -----------------------------------------------------------
    def recarve(self, new_n_stages: int, peer=None, old_workers=None,
                new_workers=None, tag: str = "0",
                dead: Optional[Sequence[int]] = None,
                expect_step: Optional[int] = None) -> None:
        """Re-balance the committed stage state for a
        ``new_n_stages``-stage world (same dp width).  Leaderless: every
        participant computes the same :func:`flat_recarve_segments`
        plan and moves only the spans it owns or will own; dead stages'
        spans are served from the ring-buddy mirror on their
        predecessor (same dp lane).  ``dead`` is the confirmed dead set
        of OLD ranks; whole stages only (the slice ladder excludes
        slices whole).  ``expect_step`` gates against survivors whose
        boundaries committed different steps (the ZeroBoundary
        policy)."""
        with self._lock:
            if self._step is None:
                raise ValueError("recarve before any commit")
            step = self._step
            cfg = self._cfg
            old_n, dp = self._n_stages, self._dp
            my_stage, my_dp = self._stage, self._dp_index
            pflat = self._pflat
            opt_vec = dict(self._opt_vec)
            buddy, buddy_stage = self._buddy, self._buddy_stage
            zero = self._zero
        if expect_step is not None and step >= 0 and step != int(expect_step):
            raise ValueError(
                f"stage boundary committed at step {step} but the cluster "
                f"agreed to replay from step {expect_step} — a re-carve "
                "would blend states from different steps; escalate to the "
                "checkpoint restart")
        if not 1 <= new_n_stages:
            raise ValueError(f"new_n_stages must be >= 1, {new_n_stages}")
        old_map = stage_partition(cfg.n_layers, old_n)
        new_map = stage_partition(cfg.n_layers, new_n_stages)
        dead = {int(d) for d in (dead or ())}
        dead_stages = sorted({d // dp for d in dead})
        for s in dead_stages:
            members = set(range(s * dp, (s + 1) * dp))
            if not members <= dead:
                raise ValueError(
                    f"stage {s} is partially dead ({sorted(dead & members)}"
                    f" of {sorted(members)}) — the recovery ladder excludes "
                    "failure domains whole; re-run the slice verdict")
        alive_stages = [s for s in range(old_n) if s not in dead_stages]

        def server_stage(os_: int) -> Tuple[int, bool]:
            """(old stage whose ranks serve ``os_``'s spans, via_buddy)."""
            if os_ not in dead_stages:
                return os_, False
            pred = (os_ - 1) % old_n
            if pred in dead_stages:
                raise ValueError(
                    f"stage {os_} is dead and so is its buddy predecessor "
                    f"{pred} — stage unrecoverable (mirror redundancy "
                    "covers one failure domain; escalate to the "
                    "checkpoint restart)")
            return pred, True

        # recoverability first, BEFORE anything moves (and before the
        # wiring checks — data loss outranks a missing argument): every
        # dead stage must have an alive buddy predecessor, and when
        # THIS rank is that predecessor it must actually hold the
        # mirror — committed at THIS boundary's step.  The step check
        # matters: replicate_ring runs off the step path, so a rank one
        # commit ahead can mirror a NEWER successor state; serving a
        # dead stage from a different step would silently blend two
        # optimizer states — the exact failure the expect_step gate
        # exists to prevent (own step is already gated against it above)
        for s in dead_stages:
            serv0, _ = server_stage(s)
            if serv0 == my_stage:
                if buddy is None or buddy_stage != s:
                    raise ValueError(
                        f"stage {s} is dead and this rank holds no "
                        "mirror of it (replicate_ring was never run on "
                        "this boundary) — stage unrecoverable")
                bstep = int(buddy["meta"][0])
                if bstep != step:
                    raise ValueError(
                        f"stage {s}'s mirror was replicated at step "
                        f"{bstep} but this boundary committed step "
                        f"{step} — serving it would blend states from "
                        "different steps; escalate to the checkpoint "
                        "restart")
        if (old_n > 1 or new_n_stages > 1) and (
                peer is None or old_workers is None or new_workers is None):
            # all three or none: a missing worker list would silently
            # skip the remote sends in phase 1 and then crash the
            # receiving rank with a raw TypeError in phase 2
            raise ValueError(
                "multi-stage recarve needs peer + old_workers + "
                "new_workers (the typed configuration contract of the "
                "recovery path)")
        # staying = alive stages whose ranks are members of the NEW
        # world; alive-but-leaving stages (a planned resize's leavers)
        # still SERVE their spans before detaching, exactly like
        # ZeroBoundary's leavers
        if old_workers is not None and new_workers is not None:
            staying = [s for s in alive_stages
                       if new_workers.rank(old_workers[s * dp]) is not None]
        else:
            staying = alive_stages
        if len(staying) != new_n_stages:
            raise ValueError(
                f"{len(staying)} staying stages cannot carve "
                f"{new_n_stages} new stages (dp width is fixed)")
        # old-stage index -> new-stage index over the stayers
        new_of_old = {os_: ns for ns, os_ in enumerate(staying)}
        my_new_stage = new_of_old.get(my_stage)
        segs = flat_recarve_segments(cfg, old_map, new_map)
        timeline.event("pp", "stage-recarve", old_n=old_n,
                       new_n=new_n_stages, dead=dead_stages,
                       segments=len(segs))

        _, old_totals = stage_flat_layouts(cfg, old_map)
        _, new_totals = stage_flat_layouts(cfg, new_map)

        def old_rank(os_: int, j: int) -> int:
            return os_ * dp + j

        def new_rank(ns: int, j: int) -> int:
            return ns * dp + j

        chan = peer.channel if peer is not None else None
        me_addr = peer.config.self_id if peer is not None else None

        def local_flat(os_: int, via_buddy: bool) -> np.ndarray:
            if via_buddy:
                if buddy is None or buddy_stage != os_:
                    raise ValueError(
                        f"stage {os_} is dead and this rank holds no "
                        "mirror of it (replicate_ring was never run on "
                        "this boundary) — stage unrecoverable")
                return buddy["pflat"]
            return pflat

        def local_vec(os_: int, via_buddy: bool) -> Dict[int, np.ndarray]:
            if via_buddy:
                return buddy["vec"]
            return opt_vec

        # --- params: replicated within the stage, so the server for a
        # span toward (ns, j) is (server_stage, j) — same lane, zero
        # cross-lane traffic, and the whole-dead-stage case is LOCAL
        # (the mirror lives exactly where the data is needed).
        from kungfu_tpu.elastic.reshard import _recv_or_fail

        def seg_name(kind: str, i: int) -> str:
            return f"kf.pprc.{tag}.{kind}{i}"

        new_pflat = (np.zeros(new_totals[my_new_stage], np.float32)
                     if my_new_stage is not None else None)
        oc = {s: max(1, math.ceil(old_totals[s] / dp))
              for s in range(old_n)}
        nc = {s: max(1, math.ceil(new_totals[s] / dp))
              for s in range(new_n_stages)}
        new_vec: Dict[int, np.ndarray] = {}
        if zero == 2 and self._opt_dtypes and my_new_stage is not None:
            new_vec = {i: np.zeros(nc[my_new_stage], dt)
                       for i, dt in self._opt_dtypes.items()}

        # PHASE 1 — serve: every span this rank hosts that lands on
        # another rank is sent BEFORE any receive (the channel buffers
        # frames, so serve-all-then-assemble cannot deadlock — two
        # ranks that interleaved send/recv in plan order could each
        # block on a recv the other only reaches later).  Local spans
        # copy in place here too.
        for i, (os_, ooff, ns, noff, ln) in enumerate(segs):
            serv, via_buddy = server_stage(os_)
            if serv == my_stage:
                dst = new_rank(ns, my_dp)
                src_flat = local_flat(os_, via_buddy)
                if my_new_stage is not None and ns == my_new_stage:
                    new_pflat[noff:noff + ln] = src_flat[ooff:ooff + ln]
                elif new_workers is not None \
                        and new_workers[dst] != me_addr:
                    chan.send(new_workers[dst], seg_name("p", i),
                              np.ascontiguousarray(
                                  src_flat[ooff:ooff + ln]))
            if zero == 2 and self._opt_dtypes:
                for (jo, jn, oo, no, l) in _chunk_splits(
                        ooff, noff, ln, oc[os_], nc[ns]):
                    if not (serv == my_stage and jo == my_dp):
                        continue
                    vecs = local_vec(os_, via_buddy)
                    base = jo * oc[os_]
                    dst_is_me = (my_new_stage is not None
                                 and ns == my_new_stage and jn == my_dp)
                    if dst_is_me:
                        for k, arr in vecs.items():
                            new_vec[k][no - jn * nc[ns]:
                                       no - jn * nc[ns] + l] = \
                                arr[oo - base:oo - base + l]
                    else:
                        dst = new_rank(ns, jn)
                        for k, arr in vecs.items():
                            chan.send(
                                new_workers[dst],
                                seg_name(f"z{k}.", i) + f".{oo}",
                                np.ascontiguousarray(
                                    arr[oo - base:oo - base + l]))

        # PHASE 2 — assemble: receive every remote span of my new stage
        for i, (os_, ooff, ns, noff, ln) in enumerate(segs):
            serv, via_buddy = server_stage(os_)
            if my_new_stage is not None and ns == my_new_stage \
                    and serv != my_stage:
                raw = _recv_or_fail(
                    chan, old_workers[old_rank(serv, my_dp)],
                    old_rank(serv, my_dp), "pp-recarve",
                    seg_name("p", i))
                got = np.frombuffer(raw, np.float32)
                if got.shape[0] != ln:
                    raise ValueError(
                        f"recarve segment p{i}: expected {ln} "
                        f"elements, got {got.shape[0]}")
                new_pflat[noff:noff + ln] = got
            if zero == 2 and self._opt_dtypes:
                for (jo, jn, oo, no, l) in _chunk_splits(
                        ooff, noff, ln, oc[os_], nc[ns]):
                    dst_is_me = (my_new_stage is not None
                                 and ns == my_new_stage and jn == my_dp)
                    if not dst_is_me or (serv == my_stage and jo == my_dp):
                        continue
                    src = old_rank(serv, jo)
                    for k in new_vec:
                        raw = _recv_or_fail(
                            chan, old_workers[src], src, "pp-recarve",
                            seg_name(f"z{k}.", i) + f".{oo}")
                        got = np.frombuffer(raw, self._opt_dtypes[k])
                        if got.shape[0] != l:
                            raise ValueError(
                                f"recarve opt segment {i}@{oo}: "
                                f"expected {l}, got {got.shape[0]}")
                        new_vec[k][no - jn * nc[ns]:
                                   no - jn * nc[ns] + l] = got

        with self._lock:
            if my_new_stage is None:
                # leaver/dead lane: served its spans; drop stale state
                self._pflat = None
                self._opt_vec = {}
                return
            self._stage = my_new_stage
            self._n_stages = int(new_n_stages)
            self._pflat = new_pflat
            self._opt_vec = new_vec
            self._buddy = None
            self._buddy_stage = None

    # -- restore ------------------------------------------------------------
    def restore(self):
        """``(stage, n_stages, params_tree, opt_state)`` from the
        (re-carved) boundary — the new :class:`HostPipeline` epoch's
        starting state."""
        import jax

        with self._lock:
            if self._pflat is None:
                raise ValueError("restore before commit (or on a leaver)")
            cfg, stage, n = self._cfg, self._stage, self._n_stages
            pflat = self._pflat
            vec, scal = dict(self._opt_vec), dict(self._opt_scal)
            otd = self._opt_treedef
        lo, hi = stage_partition(cfg.n_layers, n)[stage]
        shapes = stage_param_shapes(cfg, lo, hi, stage == 0, stage == n - 1)
        leaves, treedef = jax.tree_util.tree_flatten(shapes)
        out, off = [], 0
        for leaf in leaves:
            size = int(np.prod(leaf.shape)) if leaf.shape else 1
            out.append(pflat[off:off + size].reshape(leaf.shape))
            off += size
        params = jax.tree_util.tree_unflatten(treedef, out)
        opt = None
        if otd is not None:
            n_leaves = otd.num_leaves
            oleaves = []
            for i in range(n_leaves):
                if i in vec:
                    oleaves.append(jax.numpy.asarray(vec[i]))
                else:
                    oleaves.append(jax.numpy.asarray(scal[i]))
            opt = jax.tree_util.tree_unflatten(otd, oleaves)
        return stage, n, params, opt


def recarve_stages_after_shrink(peer, boundary: StageBoundary,
                                old_workers,
                                expect_step: Optional[int] = None) -> None:
    """Shrink-recovery rung 10: re-balance pipeline stages across the
    survivors.  Call AFTER ``shrink_to_survivors`` succeeded
    (``peer.cluster.workers`` is the shrunk list); ``old_workers`` is
    the pre-shrink membership the boundary was committed under.  The
    dead set is derived the same way the ZeRO re-carve derives it:
    every old rank absent from the survivor list is confirmed dead."""
    new_workers = peer.cluster.workers
    dead = [r for r, w in enumerate(old_workers)
            if new_workers.rank(w) is None]
    dp = max(1, boundary._dp)
    if len(new_workers) % dp:
        raise ValueError(
            f"surviving world of {len(new_workers)} does not tile the "
            f"dp width {dp} — stage re-carve needs whole dp groups")
    boundary.recarve(
        len(new_workers) // dp, peer=peer, old_workers=old_workers,
        new_workers=new_workers, tag=f"v{peer.cluster_version}",
        dead=dead, expect_step=expect_step,
    )


# -- the host-plane pipeline runner ----------------------------------------
@dataclass
class _PendingRecv:
    handle: object
    dtype: object
    shape: tuple


class HostPipeline:
    """Runs one rank's side of the cross-DCN pipeline: the 1F1B (or
    interleaved / sequential) schedule over async p2p handles, with the
    stage's DP gradient sync — replicated or ZeRO-2 bucketed
    reduce-scatter — overlapped into the drain.

    World layout is stage-major (= slice-major, PR 8): rank ``r`` is
    stage ``r // dp``, dp lane ``r % dp``; activations flow within a
    lane, gradients reduce within a stage.  ``plan`` is a
    :class:`~kungfu_tpu.parallel.train.ParallelPlan` with
    ``pp * dp == len(engine.peers)``; ``tp`` shards the stage math over
    this rank's LOCAL devices (TP never crosses the DCN)."""

    def __init__(self, engine, plan, cfg, *, full_params=None,
                 stage_params=None, inner=None, devices=None, peer=None,
                 n_buckets: int = 2, prefetch: int = _PREFETCH):
        import jax
        import optax

        self.engine = engine
        self.plan = plan
        self.cfg = cfg
        self.peer = peer
        world = len(engine.peers)
        if plan.pp * plan.dp != world:
            raise ValueError(
                f"plan pp={plan.pp} x dp={plan.dp} does not tile the "
                f"{world}-rank world")
        if plan.zero_stage not in (0, 2):
            raise ValueError(
                "HostPipeline composes ZeRO-2 (bucketed reduce-scatter) "
                f"or replicated DP — zero_stage={plan.zero_stage}")
        if peer is not None:
            topo = peer.slice_topology()
            if topo is not None and (topo.num_slices != plan.pp
                                     or topo.ranks_per_slice != plan.dp):
                raise ValueError(
                    f"plan (pp={plan.pp}, dp={plan.dp}) disagrees with "
                    f"the slice topology {topo} — PP maps across the DCN "
                    "slice axis (one stage per slice)")
        self.rank = engine.rank
        self.stage = self.rank // plan.dp
        self.dp_index = self.rank % plan.dp
        self.v = plan.interleave if plan.pp_schedule == "interleaved" else 1
        self.n_micro = plan.n_micro or plan.pp
        self._S, self._V = plan.pp, plan.pp * self.v
        part = interleaved_partition(cfg.n_layers, plan.pp, self.v)
        self.mods: List[StageModule] = []
        self.params: List[dict] = []
        for c in range(self.v):
            lo, hi = part[self.stage][c]
            vs = c * self._S + self.stage
            mod = StageModule(cfg, lo, hi, first=vs == 0,
                              last=vs == self._V - 1, tp=plan.tp,
                              devices=devices)
            self.mods.append(mod)
            if stage_params is not None:
                sp = stage_params if self.v == 1 else stage_params[c]
            elif full_params is not None:
                sp = slice_stage_params(cfg, full_params, lo, hi,
                                        vs == 0, vs == self._V - 1)
            else:
                raise ValueError("need full_params or stage_params")
            self.params.append(mod.place(sp))
        self.inner = inner if inner is not None else optax.sgd(0.01)
        self._n_buckets = max(1, int(n_buckets))
        self._prefetch = max(0, int(prefetch))
        # ZeRO-2 opt state: one flat chunk per chunk-module; replicated:
        # full tree per module
        self.opt_state: List[object] = []
        self._flat_shapes: List[list] = []
        for c in range(self.v):
            leaves = jax.tree_util.tree_leaves(self.params[c])
            total = sum(int(np.prod(np.shape(l))) for l in leaves)
            self._flat_shapes.append(total)
            if plan.zero_stage == 2:
                chunk = max(1, math.ceil(total / plan.dp))
                self.opt_state.append(
                    self.inner.init(jax.numpy.zeros((chunk,),
                                                    jax.numpy.float32)))
            else:
                self.opt_state.append(self.inner.init(self.params[c]))
        self._step = 0
        #: the op list is a pure function of (schedule, m, S, stage, v)
        #: — all fixed at construction; the interleaved variant's
        #: greedy DAG simulation is O(S·v·m²) and must not re-run on
        #: the per-step hot path
        self._ops = build_schedule(self.plan.pp_schedule, self.n_micro,
                                   self._S, self.stage, self.v)
        # the steady-state in-flight set — prefetched activation recvs,
        # bounded sends, and the act+grad pair the current op touches —
        # must fit the engine's async worker pool, or a full pool stalls
        # submission mid-schedule while every peer waits on the frame we
        # never sent: a distributed deadlock, not a slowdown.  Validate
        # at construction (proto-verify pins the same bound statically).
        from kungfu_tpu.comm.engine import ASYNC_POOL_WORKERS
        window = self._prefetch + _MAX_INFLIGHT_SENDS + 2
        if window > ASYNC_POOL_WORKERS:
            raise ValueError(
                f"pipeline in-flight window {window} (prefetch="
                f"{self._prefetch} + max sends {_MAX_INFLIGHT_SENDS} + 2)"
                f" exceeds the async pool ({ASYNC_POOL_WORKERS} workers);"
                f" lower prefetch= or widen ASYNC_POOL_WORKERS")
        #: tag namespace keyed by the channel epoch token: a rebuilt
        #: post-shrink engine gets a fresh token, so a replayed step's
        #: tags can never collide with the dead epoch's stragglers
        self._tagbase = f"pp.e{getattr(engine.channel, 'token', 0)}"
        # the schedule needs warmup+drain handles in flight; widen the
        # engine window (local backpressure knob, kf-overlap)
        engine.set_overlap_depth(
            max(engine.overlap_depth, self._prefetch + _MAX_INFLIGHT_SENDS
                + 2))

    # -- geometry ----------------------------------------------------------
    def _phys(self, vs: int) -> int:
        return vs % self._S

    def _peer_rank(self, stage: int) -> int:
        return stage * self.plan.dp + self.dp_index

    def _dp_rank(self, j: int) -> int:
        return self.stage * self.plan.dp + j

    def _act_tag(self, mb: int, vs: int) -> str:
        return f"{self._tagbase}.t{self._step}.f{mb}.v{vs}"

    def _grad_tag(self, mb: int, vs: int) -> str:
        return f"{self._tagbase}.t{self._step}.b{mb}.v{vs}"

    def _op_dep(self, op) -> Optional[Tuple[str, int, tuple]]:
        """(tag, src_rank, (dtype, shape)) this op blocks on, or None."""
        kind, mb, c = op
        vs = c * self._S + self.stage
        B_mb = self._B_mb
        S = self._seq
        act_shape = (B_mb, S, self.cfg.d_model)
        dt = np.dtype(self.cfg.compute_dtype)
        if kind == "F":
            if vs == 0:
                return None
            return (self._act_tag(mb, vs), self._peer_rank(
                self._phys(vs - 1)), (dt, act_shape))
        if vs == self._V - 1:
            return None
        return (self._grad_tag(mb, vs), self._peer_rank(
            self._phys(vs + 1)), (dt, act_shape))

    def warmup(self, B_loc: int, seq: int) -> None:
        """Compile every stage's jitted entry points on dummy shapes —
        locally, with NO wire traffic.  A cold jit (multi-second under
        the tp shard_map vjps) sitting inside the first step's recv
        window would read as a dead peer to the per-peer deadline, the
        same reason the serve engine warms every prefill bucket."""
        m = self.n_micro
        if B_loc % m:
            raise ValueError(f"batch {B_loc} % n_micro {m} != 0")
        B_mb = B_loc // m
        dt = np.dtype(self.cfg.compute_dtype)
        ids = np.zeros((B_mb, seq), np.int32)
        act = np.zeros((B_mb, seq, self.cfg.d_model), dt)
        tgt = np.zeros((B_mb, seq), np.int32)
        for c, mod in enumerate(self.mods):
            p = self.params[c]
            x = ids if mod.first else act
            if mod.last:
                mod.loss_backward(p, x, tgt)
            else:
                mod.forward(p, x)
                mod.backward(p, x, act)

    # -- the step ----------------------------------------------------------
    def train_step(self, ids, targets) -> Optional[float]:
        """One full training step over this rank's dp-lane batch shard
        ``(ids, targets)`` of shape ``[B_loc, S]``; returns the mean
        microbatch loss on last-stage ranks, None elsewhere."""
        import jax

        ids = np.asarray(ids)
        targets = np.asarray(targets)
        m = self.n_micro
        B_loc, S = ids.shape
        if B_loc % m:
            raise ValueError(f"batch {B_loc} % n_micro {m} != 0")
        self._B_mb, self._seq = B_loc // m, S
        ids_mb = ids.reshape(m, self._B_mb, S)
        tgt_mb = targets.reshape(m, self._B_mb, S)
        ops = self._ops
        grads = [None] * self.v
        b_done = [0] * self.v
        losses: List[float] = []
        x_in: Dict[Tuple[int, int], object] = {}
        recvs: Dict[str, _PendingRecv] = {}
        sends: List[object] = []
        dp_pending: List[tuple] = []
        prefetch_on = self.plan.pp_schedule != "sequential"

        def ensure_recv(idx: int) -> None:
            if not prefetch_on:
                return
            for op in ops[idx: idx + 1 + self._prefetch]:
                dep = self._op_dep(op)
                if dep is None or dep[0] in recvs:
                    continue
                tag, src, (dt, shape) = dep
                recvs[tag] = _PendingRecv(
                    self.engine.recv_async(src, tag, dtype=dt,
                                           shape=shape), dt, shape)

        def wait_dep(op):
            dep = self._op_dep(op)
            if dep is None:
                return None
            tag, src, (dt, shape) = dep
            pr = recvs.pop(tag, None)
            kind, mb, c = op
            with timeline.span("pp", "bubble", rank=self.rank,
                               stage=self.stage, mb=mb, tag=tag):
                if pr is not None:
                    return pr.handle.wait()
                return self.engine.recv_from(src, tag, dtype=dt,
                                             shape=shape)

        def push_send(rank: int, arr, tag: str) -> None:
            h = self.engine.send_async(rank, np.ascontiguousarray(arr),
                                       tag)
            sends.append(h)
            while len(sends) > _MAX_INFLIGHT_SENDS:
                sends.pop(0).wait()

        ensure_recv(0)
        for idx, op in enumerate(ops):
            ensure_recv(idx + 1)
            kind, mb, c = op
            vs = c * self._S + self.stage
            mod, params = self.mods[c], self.params[c]
            if kind == "F":
                x = ids_mb[mb] if vs == 0 else wait_dep(op)
                x_in[(mb, c)] = x
                if vs < self._V - 1:
                    with timeline.span("pp", "fwd", rank=self.rank,
                                       stage=self.stage, mb=mb, chunk=c):
                        out = mod.forward(params, x)
                    push_send(self._peer_rank(self._phys(vs + 1)),
                              np.asarray(out), self._act_tag(mb, vs + 1))
                # last virtual stage: forward work happens fused into
                # the loss vjp at B — the schedule's B follows at once
                continue
            # backward
            x = x_in.pop((mb, c))
            if vs == self._V - 1:
                with timeline.span("pp", "bwd", rank=self.rank,
                                   stage=self.stage, mb=mb, chunk=c):
                    loss, dparams, dx = mod.loss_backward(
                        params, x, tgt_mb[mb])
                losses.append(float(loss))
            else:
                dout = wait_dep(op)
                with timeline.span("pp", "bwd", rank=self.rank,
                                   stage=self.stage, mb=mb, chunk=c):
                    dparams, dx = mod.backward(params, x, dout)
            if vs > 0:
                push_send(self._peer_rank(self._phys(vs - 1)),
                          np.asarray(dx), self._grad_tag(mb, vs - 1))
            grads[c] = dparams if grads[c] is None else \
                jax.tree_util.tree_map(jax.numpy.add, grads[c], dparams)
            b_done[c] += 1
            if b_done[c] == m:
                # this chunk's gradient is final: issue its DP
                # reduce-scatter NOW — the send rides the remaining
                # drain (the bubble hides the DP wire)
                dp_pending.append(self._dp_sync_begin(c, grads[c]))

        for h in sends:
            h.wait()
        for pend in dp_pending:
            self._dp_sync_finish(pend)
        self._step += 1
        return float(np.mean(losses)) if losses else None

    # -- DP gradient sync ---------------------------------------------------
    def _bucket_spans(self, width: int) -> List[Tuple[int, int]]:
        nb = min(self._n_buckets, max(1, width))
        base, rem = divmod(width, nb)
        spans, off = [], 0
        for b in range(nb):
            w = base + (1 if b < rem else 0)
            if w:
                spans.append((off, w))
            off += w
        return spans

    def _dp_sync_begin(self, c: int, gtree):
        """Flatten chunk ``c``'s grads and ISSUE the per-bucket
        reduce-scatter sends as async handles; returns the pending
        state ``_dp_sync_finish`` completes.  With dp == 1 there is no
        wire — the pending state is just the local flat."""
        import jax

        dp = self.plan.dp
        leaves = jax.tree_util.tree_leaves(gtree)
        flat = np.concatenate(
            [np.asarray(l, np.float32).ravel() for l in leaves]) \
            if leaves else np.zeros(0, np.float32)
        chunkw = max(1, math.ceil(max(flat.shape[0], 1) / dp))
        padded = np.zeros(dp * chunkw, np.float32)
        padded[: flat.shape[0]] = flat
        view = padded.reshape(dp, chunkw)
        spans = self._bucket_spans(chunkw)
        handles: List[object] = []
        tb = f"{self._tagbase}.t{self._step}.rs.c{c}"
        for b, (off, w) in enumerate(spans):
            for j in range(dp):
                if j == self.dp_index:
                    continue
                h = self.engine.send_async(
                    self._dp_rank(j),
                    np.ascontiguousarray(view[j, off:off + w]),
                    f"{tb}.b{b}.o{self.dp_index}")
                handles.append(h)
                while len(handles) > _MAX_INFLIGHT_SENDS:
                    handles.pop(0).wait()
        return (c, view, spans, handles)

    def _dp_sync_finish(self, pend) -> None:
        """Receive the peers' contributions bucket by bucket (summed in
        dp-member order — the bitwise contract), normalize by
        ``m * dp``, run the optimizer (ZeRO-2: on this member's chunk
        only, then all-gather the updated param chunks; replicated:
        all-gather the reduced grad and update locally).  Bucket b+1's
        recvs are posted before bucket b is summed — the depth-k
        bucket pipeline shape."""
        c, view, spans, handles = pend
        dp, m = self.plan.dp, self.n_micro
        chunkw = view.shape[1]
        tb = f"{self._tagbase}.t{self._step}.rs.c{c}"
        rhs: Dict[Tuple[int, int], object] = {}

        def post(b: int) -> None:
            if b >= len(spans):
                return
            _, w = spans[b]
            for j in range(dp):
                if j != self.dp_index:
                    rhs[(b, j)] = self.engine.recv_async(
                        self._dp_rank(j), f"{tb}.b{b}.o{j}",
                        dtype=np.float32, shape=(w,))

        acc = np.zeros(chunkw, np.float32)
        post(0)
        for b, (off, w) in enumerate(spans):
            post(b + 1)
            parts = [view[self.dp_index, off:off + w] if j == self.dp_index
                     else rhs.pop((b, j)).wait() for j in range(dp)]
            s = parts[0].copy()
            for p in parts[1:]:
                s += p
            acc[off:off + w] = s
        for h in handles:
            h.wait()
        acc /= (m * dp)
        self._apply_update(c, acc, chunkw)

    def _apply_update(self, c: int, grad_chunk: np.ndarray,
                      chunkw: int) -> None:
        """Optimizer step from MY reduced gradient chunk.  ZeRO-2:
        elementwise update on the chunk, all-gather the updated param
        chunks (each member's optimizer state never exceeds 1/dp of the
        stage).  Replicated: all-gather the reduced grad chunks to the
        full gradient and update the whole tree locally."""
        import jax
        import jax.numpy as jnp
        import optax

        dp = self.plan.dp
        total = self._flat_shapes[c]
        leaves, treedef = jax.tree_util.tree_flatten(self.params[c])
        sizes = [int(np.prod(np.shape(l))) for l in leaves]

        def unflatten(flat: np.ndarray):
            out, off = [], 0
            for l, sz in zip(leaves, sizes):
                out.append(jnp.asarray(
                    flat[off:off + sz]).reshape(np.shape(l)))
                off += sz
            return jax.tree_util.tree_unflatten(treedef, out)

        def exchange_chunks(mine: np.ndarray, what: str) -> np.ndarray:
            """All-gather equal chunks over the dp group (member order);
            returns the concatenated [dp*chunkw] flat."""
            tb = f"{self._tagbase}.t{self._step}.{what}.c{c}"
            hs, pending = [], {}
            for j in range(dp):
                if j == self.dp_index:
                    continue
                hs.append(self.engine.send_async(
                    self._dp_rank(j), np.ascontiguousarray(mine),
                    f"{tb}.o{self.dp_index}"))
                pending[j] = self.engine.recv_async(
                    self._dp_rank(j), f"{tb}.o{j}", dtype=np.float32,
                    shape=(chunkw,))
            full = np.zeros(dp * chunkw, np.float32)
            for j in range(dp):
                full[j * chunkw:(j + 1) * chunkw] = (
                    mine if j == self.dp_index else pending[j].wait())
            for h in hs:
                h.wait()
            return full

        if self.plan.zero_stage == 2:
            pflat = np.concatenate(
                [np.asarray(l, np.float32).ravel() for l in leaves]) \
                if leaves else np.zeros(0, np.float32)
            padded = np.zeros(dp * chunkw, np.float32)
            padded[:total] = pflat
            mine = jnp.asarray(
                padded[self.dp_index * chunkw:
                       (self.dp_index + 1) * chunkw])
            upd, self.opt_state[c] = self.inner.update(
                jnp.asarray(grad_chunk), self.opt_state[c], mine)
            new_mine = np.asarray(optax.apply_updates(mine, upd),
                                  dtype=np.float32)
            new_flat = (exchange_chunks(new_mine, "ag") if dp > 1
                        else new_mine)[:total]
            self.params[c] = self.mods[c].place(unflatten(new_flat))
            return
        gfull = (exchange_chunks(np.asarray(grad_chunk, np.float32), "gg")
                 if dp > 1 else grad_chunk)[:total]
        gtree = unflatten(gfull)
        upd, self.opt_state[c] = self.inner.update(
            gtree, self.opt_state[c], self.params[c])
        self.params[c] = self.mods[c].place(
            optax.apply_updates(self.params[c], upd))

    # -- elastic boundary ---------------------------------------------------
    def commit_boundary(self, boundary: StageBoundary) -> None:
        """Commit this rank's stage state at the CURRENT step (call
        right after a completed ``train_step``).  v == 1 only: the
        interleaved variant's chunks have no single contiguous stage
        flat to re-carve (schedule-level feature, not an elastic one)."""
        if self.v != 1:
            raise ValueError(
                "stage boundaries support the non-interleaved pipeline "
                "(one chunk per stage)")
        boundary.commit(
            self._step, self.cfg, self.stage, self._S, self.plan.dp,
            self.dp_index, self.params[0], self.opt_state[0],
            self.plan.zero_stage)

    @classmethod
    def from_boundary(cls, engine, plan, cfg, boundary: StageBoundary,
                      *, inner=None, devices=None, peer=None,
                      n_buckets: int = 2) -> "HostPipeline":
        """Rebuild a pipeline for the post-re-carve world from a
        re-carved :class:`StageBoundary` (params AND ZeRO-2 optimizer
        chunks restored bitwise)."""
        stage, n, params, opt = boundary.restore()
        if plan.pp != n:
            raise ValueError(
                f"plan.pp={plan.pp} but the boundary is carved for {n} "
                "stages — recarve first")
        pipe = cls(engine, plan, cfg, stage_params=params, inner=inner,
                   devices=devices, peer=peer, n_buckets=n_buckets)
        if opt is not None:
            pipe.opt_state[0] = opt
        pipe._step = boundary.step() or 0
        return pipe

    # -- reporting ----------------------------------------------------------
    @property
    def step_count(self) -> int:
        return self._step

    def stage_layers(self, c: int = 0) -> Tuple[int, int]:
        return self.mods[c].lo, self.mods[c].hi


def merge_stage_trees(cfg, n_stages: int, v: int, trees) -> dict:
    """Reassemble per-virtual-stage param-SHAPED trees (params, or any
    tree mirroring them — an optimizer trace, a gradient) into the full
    stacked tree.  ``trees[vs]`` must have the stage-subtree structure
    of virtual stage ``vs`` (:func:`slice_stage_params`)."""
    import jax
    import jax.numpy as jnp

    S, V = n_stages, n_stages * v
    part = interleaved_partition(cfg.n_layers, S, v)
    full: dict = {}
    layer_rows: List[object] = [None] * cfg.n_layers
    for vs in range(V):
        c, s = vs // S, vs % S
        lo, hi = part[s][c]
        for i, l in enumerate(range(lo, hi)):
            layer_rows[l] = jax.tree_util.tree_map(
                lambda a, ii=i: a[ii], trees[vs]["layers"])
        if vs == 0:
            full["embed"] = trees[vs]["embed"]
            if cfg.pos == "learned":
                full["pos_embed"] = trees[vs]["pos_embed"]
        if vs == V - 1:
            full["ln_f"] = trees[vs]["ln_f"]
            full["head"] = trees[vs]["head"]
    full["layers"] = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *layer_rows)
    return full


def reference_pipeline_step(cfg, plan, full_params, shards, inner,
                            opt_states=None):
    """Single-process fixed-world reference: the SAME stage modules and
    the SAME dp-member numpy reductions run sequentially — the bitwise
    yardstick the 1F1B tests pin the distributed run against.

    ``shards`` is ``[(ids, targets)]`` per dp lane; returns
    ``(new_full_params, losses_per_lane_mean, opt_states)`` where
    ``opt_states`` round-trips for multi-step references."""
    import jax
    import jax.numpy as jnp
    import optax

    S, v = plan.pp, (plan.interleave
                     if plan.pp_schedule == "interleaved" else 1)
    V = S * v
    m = plan.n_micro or S
    dp = plan.dp
    part = interleaved_partition(cfg.n_layers, S, v)
    mods, params = [], []
    for vs in range(V):
        c, s = vs // S, vs % S
        lo, hi = part[s][c]
        mod = StageModule(cfg, lo, hi, first=vs == 0, last=vs == V - 1,
                          tp=plan.tp)
        mods.append(mod)
        params.append(mod.place(slice_stage_params(
            cfg, full_params, lo, hi, vs == 0, vs == V - 1)))
    lane_grads: List[List[object]] = []
    losses = []
    for d in range(dp):
        ids, targets = shards[d]
        ids_mb = np.asarray(ids).reshape(m, -1, np.asarray(ids).shape[-1])
        tgt_mb = np.asarray(targets).reshape(m, -1,
                                             np.asarray(targets).shape[-1])
        acts: Dict[Tuple[int, int], object] = {}
        grads: List[object] = [None] * V
        lane_loss = []
        for mb in range(m):
            x = ids_mb[mb]
            for vs in range(V):
                acts[(vs, mb)] = x
                if vs < V - 1:
                    x = np.asarray(mods[vs].forward(params[vs], x))
        for mb in range(m):
            loss, dparams, dx = mods[V - 1].loss_backward(
                params[V - 1], acts[(V - 1, mb)], tgt_mb[mb])
            lane_loss.append(float(loss))
            grads[V - 1] = dparams if grads[V - 1] is None else \
                jax.tree_util.tree_map(jnp.add, grads[V - 1], dparams)
            for vs in range(V - 2, -1, -1):
                dparams, dx2 = mods[vs].backward(
                    params[vs], acts[(vs, mb)], np.asarray(dx))
                grads[vs] = dparams if grads[vs] is None else \
                    jax.tree_util.tree_map(jnp.add, grads[vs], dparams)
                dx = dx2
        lane_grads.append(grads)
        losses.append(float(np.mean(lane_loss)))
    # dp reduction in member order, then one normalize — the exact
    # numpy math of HostPipeline._dp_sync_finish
    new_states = []
    opt_states = opt_states or [None] * V
    for vs in range(V):
        flats = []
        for d in range(dp):
            leaves = jax.tree_util.tree_leaves(lane_grads[d][vs])
            flats.append(np.concatenate(
                [np.asarray(l, np.float32).ravel() for l in leaves]))
        acc = flats[0].copy()
        for f in flats[1:]:
            acc += f
        acc /= (m * dp)
        pleaves, ptd = jax.tree_util.tree_flatten(params[vs])
        sizes = [int(np.prod(np.shape(l))) for l in pleaves]
        gl, off = [], 0
        for l, sz in zip(pleaves, sizes):
            gl.append(jnp.asarray(acc[off:off + sz]).reshape(np.shape(l)))
            off += sz
        gtree = jax.tree_util.tree_unflatten(ptd, gl)
        st = opt_states[vs] if opt_states[vs] is not None \
            else inner.init(params[vs])
        upd, st = inner.update(gtree, st, params[vs])
        params[vs] = optax.apply_updates(params[vs], upd)
        new_states.append(st)
    full = merge_stage_trees(cfg, S, v, params)
    return full, losses, new_states
