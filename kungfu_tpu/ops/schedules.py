"""Device-plane allreduce *schedules* — strategy choice, TPU-style.

The reference adapts its allreduce by swapping per-message routing graphs
(8 named topologies, ``base/strategy.go:10-22``, swapped at runtime with
barrier+consensus, ``session/adaptation.go:8-28``).  On TPU the compiler
owns message routing, so "strategy" becomes **which collective
decomposition gets compiled** (SURVEY §7 step 9): the same allreduce can
lower as

* ``psum`` — one HLO all-reduce; XLA picks the algorithm (default).
* ``two_stage`` — explicit reduce-scatter + all-gather
  (``lax.psum_scatter`` + tiled ``all_gather``): the bandwidth-optimal
  decomposition materialized in the program, which lets XLA schedule the
  two phases independently around neighboring compute.
* ``ring`` — a manual ``ppermute`` ring (n-1 reduce-scatter steps +
  n-1 all-gather steps): every hop is an explicit program point, the
  shape that overlap experiments and the scaling-book recipes reason
  about.
* ``pallas_ring`` — the ring written BELOW XLA: the Pallas ICI kernels
  of :mod:`kungfu_tpu.ops.pallas.collectives`, whose RDMA hops overlap
  the fold math inside one kernel (double-buffered working slots) —
  compiled on TPU, the bitwise-identical lax emulation elsewhere.

All four produce the same values (sum/mean/min/max; see per-schedule
notes), verified against ``lax.psum`` in ``tests/test_schedules.py``.
Swapping = re-jitting with a different ``schedule=`` — the moral
equivalent of the reference's ``SetGlobalStrategy``, with consensus
handled by the same driver machinery as the host plane
(:mod:`kungfu_tpu.monitor.adaptive`).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.lax import axis_size


Axis = Union[str, Tuple[str, ...]]

#: selectable device-plane allreduce schedules (also the device bandit's
#: arm set — kungfu_tpu.monitor.adapt_device learns a winner per payload
#: bucket and installs it with Communicator.set_bucket_strategy)
ALLREDUCE_SCHEDULES = ("psum", "two_stage", "ring", "pallas_ring")

#: schedules selectable for the flat reduce-scatter / all-gather pair
#: below ("lax" = the psum_scatter/all_gather primitives XLA lowers;
#: "pallas_ring" = the in-kernel-overlap ring of ops/pallas/collectives)
FLAT_SCHEDULES = ("lax", "pallas_ring")

#: payload-size buckets for the per-bucket schedule table
#: (:meth:`kungfu_tpu.comm.device.Communicator.set_bucket_strategy`): the
#: best decomposition shifts with payload size — small control tensors
#: are latency-bound (one fused HLO all-reduce wins), large fused
#: gradient buckets are bandwidth-bound (the explicit two-stage/ring
#: decompositions win; PAPERS.md 2011.03641) — so each bucket learns its
#: own winner.  Edges are upper bounds in bytes; the last bucket is
#: unbounded.
SIZE_BUCKETS = ("small", "large")
SIZE_BUCKET_EDGES = (256 << 10,)  # small: < 256 KiB; large: the rest


def size_bucket(nbytes: int) -> int:
    """Bucket index for a payload of ``nbytes`` (0-based, ascending)."""
    for i, edge in enumerate(SIZE_BUCKET_EDGES):
        if nbytes < edge:
            return i
    return len(SIZE_BUCKET_EDGES)

_OPS = {
    "sum": jnp.add,
    "mean": jnp.add,  # sum then divide at the end
    "min": jnp.minimum,
    "max": jnp.maximum,
}
def _pad_identity(op: str, dtype):
    """Identity element for the fold — op- and dtype-aware (an inf pad
    in an int buffer would overflow; a zero pad would corrupt min/max;
    bool has neither iinfo nor inf)."""
    if op in ("sum", "mean"):
        return 0
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf if op == "min" else -jnp.inf
    if dtype == jnp.bool_:
        return op == "min"  # True is min's identity, False is max's
    info = jnp.iinfo(dtype)
    return info.max if op == "min" else info.min


def _flatten_pad(a, n: int, op: str):
    """Flatten to [n, chunk] with an op-identity pad (zeros would corrupt
    min/max tails)."""
    flat = a.reshape(-1)
    chunk = max(1, math.ceil(flat.size / n))
    pad = n * chunk - flat.size
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.full((pad,), _pad_identity(op, flat.dtype), flat.dtype)]
        )
    return flat.reshape(n, chunk), flat.size - pad


def _ring_all_reduce_leaf(a, axis_name: str, op: str):
    """ppermute ring: n-1 reduce-scatter hops, n-1 all-gather hops.

    Step s of reduce-scatter: rank r sends chunk (r-s) mod n, receives
    chunk (r-s-1) mod n from rank r-1 and folds it in; after n-1 steps
    rank r owns the fully reduced chunk (r+1) mod n, which then travels
    the ring unreduced for n-1 more steps.
    """
    n = axis_size(axis_name)
    if n == 1:
        return a
    idx = lax.axis_index(axis_name)
    fold = _OPS[op]
    parts, size = _flatten_pad(a, n, op)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def rs_step(s, parts):
        send_i = (idx - s) % n
        recv_i = (idx - s - 1) % n
        buf = lax.dynamic_index_in_dim(parts, send_i, axis=0, keepdims=False)
        got = lax.ppermute(buf, axis_name, perm)
        cur = lax.dynamic_index_in_dim(parts, recv_i, axis=0, keepdims=False)
        return lax.dynamic_update_index_in_dim(
            parts, fold(cur, got), recv_i, axis=0
        )

    parts = lax.fori_loop(0, n - 1, rs_step, parts)

    def ag_step(s, parts):
        send_i = (idx + 1 - s) % n
        recv_i = (idx - s) % n
        buf = lax.dynamic_index_in_dim(parts, send_i, axis=0, keepdims=False)
        got = lax.ppermute(buf, axis_name, perm)
        return lax.dynamic_update_index_in_dim(parts, got, recv_i, axis=0)

    parts = lax.fori_loop(0, n - 1, ag_step, parts)
    return parts.reshape(-1)[:size].reshape(a.shape)


def _two_stage_all_reduce_leaf(a, axis_name: str, op: str):
    """Explicit reduce-scatter + all-gather.  ``psum_scatter`` is
    sum-only; min/max fall back to the ring schedule (same explicit
    two-phase shape, correct op)."""
    n = axis_size(axis_name)
    if n == 1:
        return a
    if op in ("min", "max"):
        return _ring_all_reduce_leaf(a, axis_name, op)
    parts, size = _flatten_pad(a, n, op)
    flat = parts.reshape(-1)
    mine = lax.psum_scatter(flat, axis_name, scatter_dimension=0, tiled=True)
    out = lax.all_gather(mine, axis_name, axis=0, tiled=True)
    return out[:size].reshape(a.shape)


def _pallas_ring_all_reduce_leaf(a, axis_name: str, op: str):
    """The ``pallas_ring`` schedule: ring reduce-scatter + ring
    all-gather through the ICI kernels of
    :mod:`kungfu_tpu.ops.pallas.collectives` (compiled on TPU, the
    bitwise-identical lax emulation elsewhere).  Sum-only like the
    kernels; min/max fall back to the lax ring schedule."""
    if op in ("min", "max"):
        return _ring_all_reduce_leaf(a, axis_name, op)
    from kungfu_tpu.ops.pallas.collectives import ring_all_reduce

    return ring_all_reduce(a, axis_name)


_PSUM_FOLD = {"sum": lax.psum, "min": lax.pmin, "max": lax.pmax}


# -- bucketed reduce-scatter / all-gather (ZeRO weight-update sharding) ----
#
# The gradient-bucket fusion above (one flat buffer, one collective) folded
# into reduce-scatter-sized pieces: the flat [n*chunk] buffer is viewed as
# [n, chunk] in mesh-major device order and bucketed along the CHUNK
# dimension, so every bucket's scatter lands each device a contiguous slice
# of its own chunk and the concatenation over buckets reproduces the
# exact contiguous per-device chunk layout of the un-bucketed scatter.
# That invariant is what keeps the ZeRO optimizer-state geometry (and its
# elastic re-shard/snapshot machinery) identical whether the step ran one
# collective or B of them.  B explicit collectives in the program also give
# XLA independent program points to overlap with neighboring compute — the
# same reason `two_stage` exists (docstring above).

def _dep_fence(pair):
    """Value-identity scheduling fence: ``(a, b) -> (a, b)`` bitwise
    unchanged, but the compiler may not start computing the outputs
    before BOTH inputs exist.  This is how the bucket loops express a
    depth-k window *inside the traced program*: fencing bucket i's
    operand on bucket i-k's result bounds how many bucket collectives
    XLA can hold in flight (and therefore how much gathered live range
    it can accumulate) without changing a single output bit.

    ``lax.optimization_barrier`` differentiates to the same barrier on
    the cotangents, so the ZeRO-3 gradient path (an all-gather whose
    transpose IS the reduce-scatter) gets the same window on the
    backward collectives for free."""
    return lax.optimization_barrier(pair)


def bucket_widths(chunk: int, n: int, itemsize: int,
                  bucket_bytes: int) -> List[int]:
    """Per-bucket column widths partitioning ``chunk`` so each bucket's
    collective operand ([n, width] flattened) is ~``bucket_bytes``.
    Always at least one bucket; the last takes the remainder."""
    if chunk <= 0:
        return [chunk] if chunk else []
    per_bucket = max(1, bucket_bytes // max(1, n * itemsize))
    widths = []
    off = 0
    while off < chunk:
        w = min(per_bucket, chunk - off)
        widths.append(w)
        off += w
    return widths


def _check_flat_schedule(schedule: str) -> None:
    if schedule not in FLAT_SCHEDULES:
        raise ValueError(
            f"unknown flat schedule {schedule!r}; one of {FLAT_SCHEDULES}")


def reduce_scatter_flat(g, axes: Sequence[str], chunk: int,
                        widths: Optional[Sequence[int]] = None,
                        serial: bool = False, schedule: str = "lax"):
    """Bucketed reduce-scatter of a flat mesh-major buffer.

    ``g``: per-device ``[n*chunk]`` (the full fused gradient, VMA-varying
    inside shard_map); returns this device's reduced ``[chunk]`` slice,
    where the device's flat index is mesh-major over ``axes`` (outer axis
    first — the same order :mod:`kungfu_tpu.parallel.zero` scatters in).
    ``axes`` must already be filtered to the non-trivial mesh axes; empty
    ``axes`` means a 1-device world and the buffer IS the chunk.

    The default (pipelined) form leaves every bucket's collective
    data-independent, so XLA may overlap them with each other and with
    neighboring compute.  ``serial=True`` is the reference shape — each
    bucket's operand is fenced on the previous bucket's result, forcing
    one collective in flight at a time.  The two forms are **bitwise
    identical** for every bucket count, including the 1-bucket and
    padded-tail degenerate cases (pinned in ``tests/test_schedules.py``):
    the fence is a value identity, and each bucket's reduction order is
    fixed by its own collective either way.  ``serial`` exists as the
    regression control the overlap bench diffs against — never as a
    production path.

    ``schedule="pallas_ring"`` scatters each bucket over the OUTER mesh
    axis through the in-kernel-overlap ring kernel
    (:func:`kungfu_tpu.ops.pallas.collectives.ring_reduce_scatter`;
    inner axes keep the lax primitive) — same mesh-major bucket
    geometry, so the ZeRO shard layout is byte-identical; the reduction
    ORDER is the ring's (docs/pallas_collectives.md), so cross-schedule
    comparisons are allclose, not bitwise."""
    _check_flat_schedule(schedule)
    if not axes:
        return g[:chunk]
    n = 1
    for ax in axes:
        n *= axis_size(ax)
    widths = list(widths) if widths else [chunk]
    g2 = g.reshape(n, chunk)
    if schedule == "pallas_ring":
        from kungfu_tpu.ops.pallas.collectives import ring_reduce_scatter
    parts = []
    off = 0
    for w in widths:
        slab = g2[:, off:off + w].reshape(-1)
        if serial and parts:
            slab, _ = _dep_fence((slab, parts[-1]))
        for i, ax in enumerate(axes):
            if schedule == "pallas_ring" and i == 0:
                slab = ring_reduce_scatter(slab, ax)
            else:
                slab = lax.psum_scatter(
                    slab, ax, scatter_dimension=0, tiled=True)
        parts.append(slab)
        off += w
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return out


def all_gather_flat(shard, axes: Sequence[str],
                    widths: Optional[Sequence[int]] = None,
                    prefetch: bool = False, schedule: str = "lax"):
    """Bucketed all-gather: inverse layout of :func:`reduce_scatter_flat`.

    ``shard``: this device's ``[chunk]`` slice; returns the mesh-major
    ``[n*chunk]`` full buffer on every device.  Differentiable — the
    transpose of each bucket's tiled all-gather is the matching tiled
    psum-scatter, so ``grad(loss(all_gather_flat(p)))`` arrives already
    reduce-scattered (the ZeRO-3 gradient path costs no extra collective).

    ``prefetch=True`` double-buffers the bucket gathers: bucket i's
    operand is fenced on bucket i-2's gathered result, so at most two
    gathers are in flight — the next bucket prefetches while the current
    one retires, but XLA cannot widen the window to all B buckets and
    hold B gathered slabs (n× their shard size each) live at once.  The
    fence is a value identity (bitwise-pinned against ``prefetch=False``)
    and its custom backward applies the same window to the transposed
    reduce-scatters, so the ZeRO-3 gradient path is double-buffered in
    both directions.

    ``schedule="pallas_ring"`` gathers each bucket over the OUTER mesh
    axis through the in-kernel-overlap ring kernel
    (:func:`kungfu_tpu.ops.pallas.collectives.ring_all_gather`; inner
    axes keep the lax primitive).  Gathering is pure data movement, so
    the result is bitwise-identical to the lax schedule; the kernel's
    custom vjp IS the ring reduce-scatter, so the ZeRO-3 gradient path
    keeps its transpose shape."""
    _check_flat_schedule(schedule)
    if not axes:
        return shard
    n = 1
    for ax in axes:
        n *= axis_size(ax)
    chunk = shard.shape[0]
    widths = list(widths) if widths else [chunk]
    if schedule == "pallas_ring":
        from kungfu_tpu.ops.pallas.collectives import ring_all_gather
    slabs = []
    off = 0
    for w in widths:
        piece = shard[off:off + w]
        if prefetch and len(slabs) >= 2:
            piece, _ = _dep_fence((piece, slabs[-2]))
        rev = tuple(reversed(axes))
        for i, ax in enumerate(rev):
            if schedule == "pallas_ring" and i == len(rev) - 1:
                piece = ring_all_gather(piece, ax)
            else:
                piece = lax.all_gather(piece, ax, axis=0, tiled=True)
        slabs.append(piece.reshape(n, w))
        off += w
    full = slabs[0] if len(slabs) == 1 else jnp.concatenate(slabs, axis=1)
    return full.reshape(-1)


#: jaxpr primitives that move bytes between devices, with the per-rank
#: ring-convention wire cost as a multiple of the per-device operand size
#: (s = operand bytes, k = axis size): all-reduce moves 2(k-1)/k*s, a
#: scatter/gather half of that, a permute exactly s.
_COLLECTIVE_COST = {
    "psum": lambda s, k: 2.0 * (k - 1) / k * s,
    "pmin": lambda s, k: 2.0 * (k - 1) / k * s,
    "pmax": lambda s, k: 2.0 * (k - 1) / k * s,
    "reduce_scatter": lambda s, k: (k - 1) / k * s,
    "all_gather": lambda s, k: (k - 1) * s,  # s = the shard being gathered
    "ppermute": lambda s, k: float(s),
    "all_to_all": lambda s, k: (k - 1) / k * s,
}


def traced_collective_bytes(fn, *args, axis_sizes: Dict[str, int]):
    """Per-rank wire bytes per call of ``fn``, measured from its traced
    jaxpr: every cross-device collective primitive actually present in
    the program is costed with the standard ring convention (table
    above).  This is a measurement of the *program XLA compiles* — not an
    estimate from a formula about what the program ought to do — so a
    step that silently all-reduces where it claims to reduce-scatter
    shows up as 2x in the bench row.  ``axis_sizes`` maps mesh axis names
    to sizes (``dict(zip(mesh.axis_names, mesh.devices.shape))``) — the
    walk runs outside any trace, where ``lax.axis_size`` is unavailable.
    Partitioner-inserted transfers (the all-gather a replicated
    ``with_sharding_constraint`` compiles to) happen after tracing and
    are NOT counted; account those analytically
    (:func:`kungfu_tpu.parallel.zero.zero_comm_bytes`).

    Returns ``{primitive_name: bytes}`` (floats, summed over every call
    site reached; scan/fori bodies count once per trace occurrence, not
    per trip)."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    out: Dict[str, float] = {}

    def axis_total(axis_name) -> int:
        axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        k = 1
        for ax in axes:
            k *= int(axis_sizes.get(ax, 1))
        return max(k, 1)

    def walk(jp):
        for eqn in jp.eqns:
            # under shard_map's vma typing a psum of a varying value
            # traces as `psum_invariant`: the same all-reduce
            prim = eqn.primitive.name.removesuffix("_invariant")
            cost = _COLLECTIVE_COST.get(prim)
            if cost is not None:
                k = axis_total(eqn.params.get("axes")
                               or eqn.params.get("axis_name") or ())
                if k > 1:
                    s = sum(
                        int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                        for v in eqn.invars if hasattr(v, "aval")
                        and hasattr(v.aval, "shape")
                    )
                    out[prim] = out.get(prim, 0.0) + cost(s, k)
            for sub in eqn.params.values():
                if hasattr(sub, "eqns"):
                    walk(sub)
                elif hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    walk(sub.jaxpr)
                elif isinstance(sub, (list, tuple)):
                    for s2 in sub:
                        if hasattr(s2, "eqns"):
                            walk(s2)
                        elif hasattr(s2, "jaxpr") and hasattr(s2.jaxpr, "eqns"):
                            walk(s2.jaxpr)

    walk(jaxpr.jaxpr)
    return out


def all_reduce_scheduled(x, axis: Axis, op: str = "sum",
                         schedule: str = "psum"):
    """Allreduce a tensor/pytree across ``axis`` with an explicit
    schedule.  ``schedule='psum'`` is :func:`kungfu_tpu.ops.all_reduce`;
    the others decompose the collective in-program (docstring above).
    Jit/shard_map-composable; every schedule returns the same values.

    ``axis`` may be a tuple of mesh axis names in outer-to-inner order
    (e.g. a hierarchical communicator's ``(host, local)``): the schedule
    applies to the FIRST non-trivial axis — the cross-host stage — after
    the inner axes reduce with one-hop psum over ICI, the reference's
    local/cross split (``session/strategy.go:176-210``).
    """
    if op not in _OPS:
        raise ValueError(f"unsupported op {op!r}")
    if schedule not in ALLREDUCE_SCHEDULES:
        raise ValueError(
            f"unknown schedule {schedule!r}; one of {ALLREDUCE_SCHEDULES}"
        )
    if schedule == "psum":
        from kungfu_tpu.ops.collective import all_reduce

        return all_reduce(x, axis, op=op)
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    sched_leaf = {
        "ring": _ring_all_reduce_leaf,
        "two_stage": _two_stage_all_reduce_leaf,
        "pallas_ring": _pallas_ring_all_reduce_leaf,
    }[schedule]
    base = "sum" if op == "mean" else op

    def leaf(a):
        sizes = [axis_size(ax) for ax in axes]
        real = [ax for ax, s in zip(axes, sizes) if s > 1] or [axes[0]]
        for ax in real[1:]:  # inner (intra-host) stages: one-hop psum
            a = _PSUM_FOLD[base](a, ax)
        a = sched_leaf(a, axis_name=real[0], op=base)
        if op == "mean":
            a = a / math.prod(sizes)
        return a

    return jax.tree_util.tree_map(leaf, x)
