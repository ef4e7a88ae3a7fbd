"""Weight-update sharding (ZeRO-1) — the optimizer step, data-parallel.

Plain S-SGD makes every replica apply the identical optimizer update to
the full parameter set: n copies of the update FLOPs, n copies of the
optimizer state in HBM.  Weight-update sharding (the "Automatic
Cross-Replica Sharding of Weight Update in Data-Parallel Training"
technique from the TPU MLPerf submissions; ZeRO stage 1 elsewhere)
splits the update instead:

    reduce-scatter(grads) → each replica owns 1/n of the flat gradient
    inner update on the owned shard (momentum/Adam state: 1/n per chip)
    all-gather(updated params) → everyone replicated again

For any ELEMENTWISE inner transform (sgd, momentum, adam, adamw,
rmsprop, …) the sharded update is exactly the full update restricted to
the shard, so the result matches
:func:`~kungfu_tpu.optimizers.synchronous_sgd` to float tolerance — the
win is n× less optimizer-state memory and n× fewer update FLOPs, paid
with an all-gather of params instead of an all-reduce of grads (the
same bytes on the wire: reduce-scatter + all-gather IS the
bandwidth-optimal all-reduce decomposition, cf.
:mod:`kungfu_tpu.ops.schedules`).

Non-elementwise transforms (``clip_by_global_norm``, anything that
mixes statistics across parameters) are NOT shard-equivalent — compose
them on the gradient side before this wrapper if needed.

Structure note: the scatter + shard update run inside ``shard_map``
(their outputs are genuinely sharded, declared ``P(axes)``); the param
re-gather is left to the enclosing jit — ``defuse`` of the sharded flat
buffer makes XLA's partitioner insert the all-gather, which also keeps
shard_map's varying-manual-axes checking fully on (an in-body
``all_gather`` result cannot be declared replicated without disabling
the check).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.lax import axis_size
from jax.sharding import NamedSharding, PartitionSpec as P

from kungfu_tpu.ops.fuse import defuse, fuse


def zero1_train_step(loss_fn, inner: optax.GradientTransformation, comm,
                     average: bool = True, donate: bool = False):
    """Build a ZeRO-1 data-parallel training step over ``comm``'s mesh.

    ``loss_fn(params, batch) -> scalar`` runs per device on its batch
    shard (same contract as
    :func:`~kungfu_tpu.parallel.train.dp_train_step`); ``inner`` is any
    elementwise optax transform.

    Returns ``(step, init_opt)``:

    * ``init_opt(params) -> opt_shard`` — the optimizer state over the
      mesh-sharded flat parameter buffer (each device holds 1/n; build
      once per mesh epoch).
    * ``step(params, opt_shard, batch) -> (params, opt_shard, loss)`` —
      jitted over the mesh; params replicated in/out, ``batch`` leading
      axis divisible by ``comm.size``.
    """
    mesh, axes = comm.mesh, comm.axis
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    n = comm.size

    def build(params):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        buf, spec = fuse(zeros)
        total = int(buf.shape[-1])
        chunk = math.ceil(total / n)
        padded = chunk * n
        flat_dtype = spec.fused_dtype
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        # OUTER-axis-first scatter: the chunk device (i_h, i_l) ends up
        # owning then sits at flat offset (i_h*n_l + i_l)*chunk — the
        # same mesh-major order P(axes) uses to assemble the global
        # buffer, so the enclosing jit's defuse reads chunks back in
        # place (inner-first scattering produces local-major content and
        # a permuted parameter tree on hierarchical meshes)
        scatter_axes = [ax for ax in axes_t if sizes[ax] > 1]

        # optimizer-state pytree structure over one shard: vector leaves
        # are sharded over the mesh, scalar leaves (e.g. Adam's count)
        # are replicated
        state_shapes = jax.eval_shape(
            inner.init, jax.ShapeDtypeStruct((chunk,), flat_dtype)
        )
        state_specs = jax.tree_util.tree_map(
            lambda s: P(axes) if s.ndim else P(), state_shapes
        )

        def my_offset():
            off, seg = jnp.int32(0), padded
            for ax in scatter_axes:
                seg = seg // axis_size(ax)
                off = off + lax.axis_index(ax) * seg
            return off

        def flat_of(tree):
            b, _ = fuse(tree)
            pad = padded - total
            if pad:
                b = jnp.concatenate([b, jnp.zeros((pad,), b.dtype)])
            return b.astype(flat_dtype)

        def init_body(params):
            shard = lax.dynamic_slice(
                flat_of(params), (my_offset(),), (chunk,)
            )
            return inner.init(shard)

        init_opt = jax.jit(shard_map(
            init_body, mesh=mesh, in_specs=(P(),), out_specs=state_specs,
        ))

        def step_body(params, opt_shard, batch):
            # differentiate w.r.t. a per-device VARYING view of the
            # params: against the replicated view, autodiff inserts a
            # full cotangent psum (an all-reduce — the exact collective
            # this technique replaces), and the scatter below would
            # re-sum the already-summed gradients on top (measured n^2)
            from kungfu_tpu.ops.pallas._sharding import match_vma

            p_var = jax.tree_util.tree_map(
                lambda a: match_vma(a, frozenset(axes_t)), params
            )
            loss, grads = jax.value_and_grad(loss_fn)(p_var, batch)
            g = flat_of(grads)
            for ax in scatter_axes:
                g = lax.psum_scatter(g, ax, scatter_dimension=0, tiled=True)
            if average:
                g = g / n
            p_shard = lax.dynamic_slice(
                flat_of(params), (my_offset(),), (chunk,)
            )
            updates, opt_shard = inner.update(g, opt_shard, p_shard)
            p_shard = optax.apply_updates(p_shard, updates)
            loss = lax.pmean(loss, axes)
            return p_shard, opt_shard, loss

        inner_step = shard_map(
            step_body, mesh=mesh,
            in_specs=(P(), state_specs, P(axes)),
            out_specs=(P(axes), state_specs, P()),
        )

        def outer(params, opt_shard, batch):
            p_flat, opt_shard, loss = inner_step(params, opt_shard, batch)
            # p_flat is the sharded [padded] buffer; defuse's slices make
            # the partitioner insert the all-gather back to replicated —
            # PINNED, not left to compiler choice: a sharded params
            # output would poison every replicated-convention consumer
            # (resync, host snapshots) on multi-controller meshes
            from jax.sharding import NamedSharding

            rep = NamedSharding(mesh, P())
            new_params = jax.tree_util.tree_map(
                lambda a: jax.lax.with_sharding_constraint(a, rep),
                defuse(p_flat[:total], spec),
            )
            return new_params, opt_shard, loss

        return (
            jax.jit(outer, donate_argnums=(0, 1) if donate else ()),
            init_opt,
        )

    # the flat geometry depends on the param structure AND leaf
    # shapes/dtypes (the fuse spec bakes both in); build lazily on first
    # use and cache per full abstract signature
    cache = {}

    def _get(params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        key = (treedef,
               tuple((tuple(l.shape), str(l.dtype)) for l in leaves))
        if key not in cache:
            cache[key] = build(params)
        return cache[key]

    def init_opt(params):
        return _get(params)[1](params)

    def step(params, opt_shard, batch):
        return _get(params)[0](params, opt_shard, batch)

    return step, init_opt


def zero1_reshard(opt_shard, params, new_comm, peer=None, snapshot=None):
    """Re-place a ZeRO-1 optimizer shard onto a NEW mesh epoch.

    The sharded state's geometry (chunk = ceil(total/n), mesh-major
    scatter order) is baked into each vector leaf, so an elastic resize
    cannot just keep training — the state must be re-chunked for the
    new world size.  Each vector leaf is unpadded to the true parameter
    count (recovered from ``params``), re-padded to the NEW chunk
    geometry, and placed sharded over the new mesh; scalar leaves (e.g.
    Adam's step count) are re-placed replicated.  Values are exactly
    preserved, so training continues as if the optimizer had always run
    at the new size — the same guarantee the elementwise-equivalence of
    the step itself gives.

    Two modes:

    * **Single-controller** (simulated peers / one host), no
      ``snapshot``: every old chunk is addressable — direct runtime
      re-placement, no host channel involved.
    * **Multi-controller** (or an explicit ``snapshot``): the old
      chunks live in other processes — some of which a shrink just
      retired — so the state must have been captured with
      :func:`zero1_snapshot` over the OLD epoch's membership *before*
      the resize (rank 0 holds the blob; the chunk owners may no longer
      be reachable afterwards).  Rank 0 passes it as ``snapshot``;
      everyone else passes ``None`` and receives it over ``peer``'s
      host channel.  ``opt_shard`` supplies only the state STRUCTURE
      here (a joiner passes its fresh ``init_opt(params)``) — vector
      geometry is synthesized for the new mesh, values come from the
      snapshot.  This folds the former snapshot→restore detour under
      the one reshard entry point (reference elastic-state contract:
      ``peer/peer.go:236-276``).
    """
    from jax.sharding import NamedSharding

    total = int(np.sum([int(np.prod(l.shape)) for l in
                        jax.tree_util.tree_leaves(params)]))
    n = new_comm.size
    chunk = math.ceil(total / n)
    padded = chunk * n

    if new_comm._multiproc or snapshot is not None:
        # host-plane path: structure from opt_shard, geometry synthesized
        # for the new mesh, values from the (broadcast) snapshot
        fresh = jax.tree_util.tree_map(
            lambda a: (a if getattr(a, "ndim", 0) == 0
                       else jax.ShapeDtypeStruct((padded,), a.dtype)),
            opt_shard,
        )
        return zero1_restore(snapshot, fresh, params, peer, new_comm)

    sharded = NamedSharding(new_comm.mesh, P(new_comm.axis))
    replicated = new_comm.replicated_sharding()

    def leaf(a):
        if getattr(a, "ndim", 0) == 0:
            return jax.device_put(jnp.asarray(a), replicated)
        return jax.device_put(_repad(np.asarray(a), total, padded), sharded)

    return jax.tree_util.tree_map(leaf, opt_shard)


def _repad(full: np.ndarray, total: int, new_padded: int) -> np.ndarray:
    """Unpad a flat state vector to the true parameter count and re-pad
    for a new chunk geometry — shared by reshard and restore so their
    geometry (and its misuse diagnostic) cannot drift."""
    if full.shape[0] < total:
        # the state was built for MORE parameters than ``params`` holds
        # (e.g. a trainable-only subtree was passed): truncating would
        # silently corrupt the optimizer state
        raise ValueError(
            f"optimizer state vector has {full.shape[0]} elements but "
            f"params fuse to {total} — zero1 reshard/restore needs the "
            "SAME param tree the state was built from"
        )
    buf = np.zeros((new_padded,), full.dtype)
    buf[:total] = full[:total]
    return buf


def zero1_snapshot(opt_shard, peer=None):
    """End-of-epoch HOST snapshot of the sharded optimizer state.

    Each member contributes its addressable chunks over the host channel
    (state_bytes/n each — no HBM spike; only rank 0's HOST RAM holds the
    assembled state on the snapshot side.  :func:`zero1_restore` then
    broadcasts the blob, so each member transiently holds ~state_bytes
    in host RAM while re-chunking — host RAM, not HBM, so the 1/n HBM
    contract is untouched; a per-range scatter is the future
    optimization).  Rank 0 returns the blob, everyone else ``None``.
    The elastic contract is the coordinator's: **rank 0 must survive
    the resize** (it is the peer proposing it).

    Without a channel (single-process / simulated peers) every chunk is
    addressable locally and the blob is assembled in place.
    """
    import io

    chan = getattr(peer, "channel", None) if peer is not None else None
    leaves, _ = jax.tree_util.tree_flatten(opt_shard)
    parts = {}
    scalars = {}
    for i, leaf in enumerate(leaves):
        if getattr(leaf, "ndim", 0) == 0:
            scalars[f"s{i}"] = np.asarray(leaf)
            continue
        if chan is None and not leaf.is_fully_addressable:
            # mirror zero1_reshard's misuse guard: packing only the
            # local 1/n without a channel to gather the rest would
            # build a silently incomplete snapshot
            raise ValueError(
                "zero1_snapshot without a host channel needs fully "
                "addressable state (multi-controller meshes must pass "
                "the peer)"
            )
        for s in leaf.addressable_shards:
            start = s.index[0].start or 0
            parts[f"l{i}_o{start}"] = np.asarray(s.data)

    def pack(d):
        bio = io.BytesIO()
        np.savez(bio, **d)
        return bio.getvalue()

    if chan is None:
        merged = dict(parts)
        merged.update(scalars)
        return pack(merged)
    rank = peer.rank()
    name = f"kf.z1snap.v{peer.cluster_version}"
    gathered = chan.gather_bytes(pack(parts), peer.cluster.workers, name)
    if rank != 0:
        return None
    merged = {}
    for blob in gathered:
        with np.load(io.BytesIO(blob)) as z:
            for k in z.files:
                merged[k] = z[k]
    merged.update(scalars)  # replicated: rank 0's copy is everyone's
    return pack(merged)


def zero1_restore(snapshot, fresh_opt_shard, params, peer=None,
                  new_comm=None):
    """Rebuild the sharded optimizer state on a NEW mesh epoch from a
    :func:`zero1_snapshot` blob.

    ``fresh_opt_shard`` is ``init_opt(params)`` from the NEW epoch's
    :func:`zero1_train_step` — it supplies the state STRUCTURE and the
    new chunk geometry (joiners have no old state to supply either);
    its values are overwritten.  Rank 0 passes the blob; other members
    pass ``None`` and receive it over the host channel."""
    import io

    chan = getattr(peer, "channel", None) if peer is not None else None
    if chan is not None:
        if peer.rank() == 0 and snapshot is None:
            # fail HERE, before the broadcast: a bare assert inside
            # broadcast_bytes would kill rank 0 and leave every other
            # member stalling in recv until its timeout
            raise ValueError(
                "zero1_restore: rank 0 must supply the snapshot blob"
            )
        name = f"kf.z1rest.v{peer.cluster_version}"
        snapshot = chan.broadcast_bytes(snapshot, peer.cluster.workers, name)
    if snapshot is None:
        raise ValueError("zero1_restore: no snapshot (rank 0 must supply it)")
    total = int(np.sum([int(np.prod(l.shape)) for l in
                        jax.tree_util.tree_leaves(params)]))
    leaves, treedef = jax.tree_util.tree_flatten(fresh_opt_shard)
    with np.load(io.BytesIO(snapshot)) as z:
        by_leaf = {}
        for k in z.files:
            if k.startswith("s"):
                by_leaf[("s", int(k[1:]))] = z[k]
            else:
                li, off = k[1:].split("_o")
                by_leaf.setdefault(("l", int(li)), []).append(
                    (int(off), z[k]))

    sharded = None
    if new_comm is not None:
        from jax.sharding import NamedSharding

        sharded = NamedSharding(new_comm.mesh, P(new_comm.axis))
    out = []
    for i, leaf in enumerate(leaves):
        if getattr(leaf, "ndim", 0) == 0:
            val = by_leaf.get(("s", i))
            if val is None:
                out.append(leaf)
            elif new_comm is not None:
                out.append(jax.device_put(jnp.asarray(val),
                                          new_comm.replicated_sharding()))
            else:
                out.append(jnp.asarray(val))
            continue
        chunks = sorted(by_leaf.get(("l", i), []))
        if not chunks:
            raise ValueError(f"snapshot holds no chunks for state leaf {i}")
        # chunks must tile [0, covered) with no interior gap: a
        # count-based check misses a hole whenever the old padding is at
        # least one chunk wide, silently restoring zeros into momentum
        expected = 0
        for off, c in chunks:
            if off != expected:
                raise ValueError(
                    f"snapshot leaf {i}: chunk gap at offset {expected} "
                    f"(next chunk starts at {off}) — a contributing "
                    "member's chunks are missing"
                )
            expected = off + c.shape[0]
        full = np.concatenate([c for _, c in chunks])
        buf = _repad(full, total, int(leaf.shape[0]))  # NEW padded size
        out.append(jax.device_put(buf, sharded) if sharded is not None
                   else jnp.asarray(buf))
    return jax.tree_util.tree_unflatten(treedef, out)


def opt_state_bytes(opt_state) -> int:
    """Total bytes across an optimizer-state pytree (for the memory
    assertion in tests/benchmarks)."""
    return sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(opt_state)
        if hasattr(l, "shape") and hasattr(l, "dtype")
    )


def opt_state_bytes_per_device(opt_state) -> int:
    """Worst-case PER-DEVICE optimizer-state footprint: for each device,
    the bytes of every state shard it actually holds (a replicated leaf
    counts fully on every device; a 1/n-sharded leaf counts one chunk).
    This is the number the ZeRO memory claim is about — `opt_state_bytes`
    reports the global total, which is identical for replicated and
    sharded state and therefore cannot witness the sharding."""
    per: dict = {}
    for l in jax.tree_util.tree_leaves(opt_state):
        if isinstance(l, jax.Array):
            for s in l.addressable_shards:
                per[s.device] = per.get(s.device, 0) + int(s.data.nbytes)
        elif hasattr(l, "nbytes"):
            per[None] = per.get(None, 0) + int(l.nbytes)
    return max(per.values(), default=0)


def record_opt_state_gauge(opt_state) -> int:
    """Publish this rank's optimizer-state footprint as the
    ``kf_opt_state_bytes`` gauge (rendered by ``/metrics``, pushed to the
    aggregator, shown by kftop).  Returns the recorded bytes."""
    from kungfu_tpu.monitor.registry import REGISTRY

    nbytes = opt_state_bytes_per_device(opt_state)
    REGISTRY.gauge("kf_opt_state_bytes").set(nbytes)
    return nbytes


# ==========================================================================
# ZeRO-2 / ZeRO-3: bucketed reduce-scatter -> sharded update -> all-gather
# ==========================================================================
#
# Stage semantics (PAPERS.md 2004.13336 is the stage-1/2 blueprint; the
# DeepSpeed stage numbering is the vocabulary everyone searches for):
#
# ========  =======================  ==========================  ============
# stage     gradient collective      params between steps        opt state
# ========  =======================  ==========================  ============
# 1         all-reduce (2(n-1)/n*N)  replicated                  1/n sharded
# 2         reduce-scatter           replicated                  1/n sharded
#           ((n-1)/n*N)
# 3         reduce-scatter (via the  1/n SHARDED; all-gathered   1/n sharded
#           all-gather transpose)    bucket-wise JIT inside
#                                    the step
# ========  =======================  ==========================  ============
#
# plus the parameter all-gather every stage pays once per step ((n-1)/n*N;
# stage 3 pays it *inside* the step, stages 1/2 at the step boundary via
# the partitioner).  So stage 2 halves the gradient comm of the stage-1
# all-reduce path — counted from the traced program in
# ``tests/test_zero.py::TestZeroCommVolume`` — and stage 3
# additionally drops the at-rest parameter replication to 1/n.
#
# The persistent sharded-state GEOMETRY is IDENTICAL across stages (and to
# :func:`zero1_train_step`): flat fused buffer, ceil(total/n) chunk per
# device, mesh-major contiguous.  That single invariant is what lets ONE
# elastic re-shard machinery (snapshot/restore, and the p2p re-carve
# below) serve every stage, including ZeRO-3's parameter shards.


class _ZeroGeometry:
    """Flat-buffer geometry + compiled helpers for one (params, mesh)."""

    def __init__(self, params, comm, inner, bucket_bytes: int):
        from kungfu_tpu.ops.schedules import bucket_widths

        mesh, axes = comm.mesh, comm.axis
        self.axes = axes
        self.axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
        self.n = comm.size
        self.mesh = mesh
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        buf, spec = fuse(zeros)
        self.spec = spec
        self.total = int(buf.shape[-1])
        self.chunk = math.ceil(self.total / self.n)
        self.padded = self.chunk * self.n
        self.flat_dtype = spec.fused_dtype
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.scatter_axes = [ax for ax in self.axes_t if sizes[ax] > 1]
        self.widths = bucket_widths(
            self.chunk, self.n, jnp.dtype(self.flat_dtype).itemsize,
            bucket_bytes)
        state_shapes = jax.eval_shape(
            inner.init, jax.ShapeDtypeStruct((self.chunk,), self.flat_dtype)
        )
        self.state_specs = jax.tree_util.tree_map(
            lambda s: P(axes) if s.ndim else P(), state_shapes
        )

    def my_offset(self):
        off, seg = jnp.int32(0), self.padded
        for ax in self.scatter_axes:
            seg = seg // axis_size(ax)
            off = off + lax.axis_index(ax) * seg
        return off

    def flat_of(self, tree):
        b, _ = fuse(tree)
        pad = self.padded - self.total
        if pad:
            b = jnp.concatenate([b, jnp.zeros((pad,), b.dtype)])
        return b.astype(self.flat_dtype)


class ZeroStep:
    """A staged weight-update-sharded training step.

    Stages 1/2 keep the :func:`zero1_train_step` calling convention
    (``step(params, opt_shard, batch)``, params replicated in/out) —
    unpacking ``step, init_opt = zero_train_step(...)`` keeps working.
    Stage 3 stores parameters SHARDED between steps: call
    :meth:`init_params` once to carve the flat shard, then
    ``step(p_shard, opt_shard, batch)``; :meth:`gather_params`
    reassembles the full tree for eval/checkpoint/re-sync.

    kf-pulse: stages 1/2 carry a second jit program (``step_pulse``)
    that additionally returns the (local, reduced) gradient square-norm
    pair; :attr:`pulse` gates which program runs per step
    (``KF_PULSE_EVERY``) and publishes ``kf_gns`` /
    ``kf_grad_variance`` / ``kf_grad_norm{group="flat"}``.  Off steps
    and ``KF_PULSE_EVERY=0`` runs execute the bare program untouched.
    """

    def __init__(self, loss_fn, inner, comm, stage: int, average: bool,
                 donate: bool, bucket_bytes: int, schedule: str = "lax"):
        if stage not in (1, 2, 3):
            raise ValueError(f"ZeRO stage must be 1, 2 or 3, got {stage}")
        from kungfu_tpu.ops.schedules import FLAT_SCHEDULES

        if schedule not in FLAT_SCHEDULES:
            raise ValueError(
                f"unknown schedule {schedule!r}; one of {FLAT_SCHEDULES}")
        self.stage = stage
        self.comm = comm
        self._loss_fn = loss_fn
        self._inner = inner
        self._average = average
        self._donate = donate
        self._bucket_bytes = int(bucket_bytes)
        #: flat-collective schedule compiled into the bucket loops
        #: ("lax" | "pallas_ring"); the shard GEOMETRY is identical
        #: either way, so snapshots/re-shards are schedule-agnostic
        self._schedule = schedule
        self._cache = {}
        self._g3 = None  # stage-3 active geometry (set by init_params)
        from kungfu_tpu.monitor import pulse as pulselib
        #: kf-pulse gradient-signal monitor (None when KF_PULSE_EVERY=0).
        #: Stages 1/2 only: stage 3 never materializes a per-rank FULL
        #: flat gradient (the backward pass emits the bucketed
        #: reduce-scatter directly), so the small-batch side of the GNS
        #: pair does not exist there without a second gradient pass.
        self.pulse = (pulselib.PulseMonitor.from_env()
                      if stage in (1, 2) else None)

    # -- back-compat unpacking: step, init_opt = zero_train_step(...) -----
    def __iter__(self):
        return iter((self.step, self.init_opt))

    # -- dp_train_step contract: the returned object IS the step ----------
    def __call__(self, params, opt_shard, batch):
        return self.step(params, opt_shard, batch)

    # -- public API -------------------------------------------------------
    def step(self, params, opt_shard, batch):
        if self.stage == 3:
            built = self._require_g3()
            return built["step"](params, opt_shard, batch)
        built = self._get(params)
        mon = self.pulse
        if mon is not None and mon.should_sample():
            # kf-pulse step: the SECOND jit program returns the
            # already-reduced square-norm pair on top of the normal
            # outputs; off steps run the bare program untouched
            p, opt_shard, loss, gl, gg = built["step_pulse"](
                params, opt_shard, batch)
            self._publish_pulse(mon, float(gl), float(gg), batch)
            return p, opt_shard, loss
        return built["step"](params, opt_shard, batch)

    def _publish_pulse(self, mon, g_local_sq, g_global_sq, batch):
        n = int(self.comm.size)
        leaves = jax.tree_util.tree_leaves(batch)
        b_small = (int(leaves[0].shape[0]) // n) if (leaves and n) else 1
        mon.update(g_local_sq, g_global_sq, max(1, b_small), n,
                   group_norms={
                       "flat": math.sqrt(max(0.0, g_global_sq))})

    def jitted(self, params):
        """The jit program :meth:`step` runs for this parameter tree, for
        callers that need what only a jit object has (``.lower``, cost
        analysis)."""
        built = self._require_g3() if self.stage == 3 else self._get(params)
        return built["step"]

    def init_opt(self, params):
        out = self._get(params)["init_opt"](params)
        record_opt_state_gauge(out)
        return out

    def init_params(self, params):
        """Stage 3: carve the replicated param tree into the flat
        mesh-sharded buffer the step trains on.  Stages 1/2: identity."""
        if self.stage != 3:
            return params
        built = self._get(params)
        self._g3 = built
        return built["init_params"](params)

    def gather_params(self, p):
        """Stage 3: all-gather the flat shard back into the full param
        tree (replicated — for eval/checkpoint/resync).  Stages 1/2:
        identity (params are already replicated)."""
        if self.stage != 3:
            return p
        built = self._require_g3()
        return built["gather_params"](p)

    def comm_bytes(self, params) -> dict:
        """Analytic per-rank wire bytes per step for THIS model on THIS
        mesh (ring convention; see :func:`zero_comm_bytes`)."""
        g = self._geometry_of(params)
        return zero_comm_bytes(g.total, g.n, self.stage,
                               jnp.dtype(g.flat_dtype).itemsize)

    # -- internals --------------------------------------------------------
    def _require_g3(self):
        if self._g3 is None:
            raise RuntimeError(
                "stage-3 step called before init_params (the parameter "
                "shard carve defines the step's geometry)")
        return self._g3

    def _geometry_of(self, params):
        return self._get(params)["geo"]

    def _get(self, params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        key = (treedef,
               tuple((tuple(l.shape), str(l.dtype)) for l in leaves))
        if key not in self._cache:
            self._cache[key] = self._build(params)
        return self._cache[key]

    def _build(self, params):
        geo = _ZeroGeometry(params, self.comm, self._inner,
                            self._bucket_bytes)
        mesh, axes = geo.mesh, geo.axes
        inner, average, donate = self._inner, self._average, self._donate
        loss_fn = self._loss_fn
        n, chunk, total = geo.n, geo.chunk, geo.total
        state_specs = geo.state_specs
        from kungfu_tpu.ops.schedules import (all_gather_flat,
                                              reduce_scatter_flat)

        def init_body(p):
            shard = lax.dynamic_slice(
                geo.flat_of(p), (geo.my_offset(),), (chunk,))
            return inner.init(shard)

        init_opt = jax.jit(shard_map(
            init_body, mesh=mesh, in_specs=(P(),), out_specs=state_specs))

        rep = NamedSharding(mesh, P())

        def regather(p_flat):
            # the partitioner inserts the (bucketable) all-gather for the
            # replicated constraint — PINNED, same reasoning as zero1
            return jax.tree_util.tree_map(
                lambda a: jax.lax.with_sharding_constraint(a, rep),
                defuse(p_flat[:total], geo.spec),
            )

        if self.stage in (1, 2):
            from kungfu_tpu.ops.pallas._sharding import match_vma

            def make_body(with_pulse):
                def step_body(p, opt_shard, batch):
                    p_var = jax.tree_util.tree_map(
                        lambda a: match_vma(a, frozenset(geo.axes_t)), p)
                    loss, grads = jax.value_and_grad(loss_fn)(p_var, batch)
                    g = geo.flat_of(grads)
                    gl_sq = gg_sq = None
                    if with_pulse:
                        # kf-pulse small-batch side: this rank's flat
                        # gradient square norm.  The cross-peer MEAN
                        # lands below — stage 1 pmeans it directly;
                        # stage 2 folds it into ONE stacked psum with
                        # the shard term, so a pulse sample costs a
                        # single extra scalar collective either way
                        gl_sq = jnp.sum(
                            jnp.square(g.astype(jnp.float32)))
                    if self.stage == 1:
                        # the classic ZeRO-1 all-reduce path: every device
                        # sees the full reduced gradient, then updates only
                        # its own chunk — 2x the wire bytes of the stage-2
                        # reduce-scatter (the measured delta in bench --zero)
                        for ax in geo.scatter_axes:
                            g = lax.psum(g, ax)
                        if with_pulse:
                            for ax in geo.scatter_axes:
                                gl_sq = lax.pmean(gl_sq, ax)
                            # g is the full SUMMED gradient (replicated):
                            # |mean|^2 = |sum|^2 / n^2 — no collective
                            gg_sq = jnp.sum(
                                jnp.square(g.astype(jnp.float32))
                            ) / float(n * n)
                        g_shard = lax.dynamic_slice(
                            g, (geo.my_offset(),), (chunk,))
                    else:
                        g_shard = reduce_scatter_flat(
                            g, geo.scatter_axes, chunk, geo.widths,
                            schedule=self._schedule)
                        if with_pulse:
                            # the shards tile the summed flat buffer
                            # disjointly, so psum of the shard square
                            # norms IS |sum|^2; stacked with the local
                            # term both scalars ride one psum (psum/n
                            # is bitwise what pmean lowers to)
                            pair = jnp.stack([gl_sq, jnp.sum(
                                jnp.square(g_shard.astype(jnp.float32)))])
                            for ax in geo.scatter_axes:
                                pair = lax.psum(pair, ax)
                            gl_sq = pair[0] / float(n)
                            gg_sq = pair[1] / float(n * n)
                    if average:
                        g_shard = g_shard / n
                    p_shard = lax.dynamic_slice(
                        geo.flat_of(p), (geo.my_offset(),), (chunk,))
                    updates, opt_shard = inner.update(
                        g_shard, opt_shard, p_shard)
                    p_shard = optax.apply_updates(p_shard, updates)
                    loss = lax.pmean(loss, axes)
                    if with_pulse:
                        # the pair was reduced over scatter_axes only; a
                        # size-1 mesh axis (one host: kf_host) moves no
                        # data but still types it as varying, which the
                        # replicated out_spec rejects — the identity
                        # pmean over those axes clears it
                        for ax in geo.axes_t:
                            if ax not in geo.scatter_axes:
                                gl_sq = lax.pmean(gl_sq, ax)
                                gg_sq = lax.pmean(gg_sq, ax)
                        return p_shard, opt_shard, loss, gl_sq, gg_sq
                    return p_shard, opt_shard, loss
                return step_body

            inner_step = shard_map(
                make_body(False), mesh=mesh,
                in_specs=(P(), state_specs, P(axes)),
                out_specs=(P(axes), state_specs, P()),
            )
            inner_pulse = shard_map(
                make_body(True), mesh=mesh,
                in_specs=(P(), state_specs, P(axes)),
                out_specs=(P(axes), state_specs, P(), P(), P()),
            )

            def outer(p, opt_shard, batch):
                p_flat, opt_shard, loss = inner_step(p, opt_shard, batch)
                return regather(p_flat), opt_shard, loss

            def outer_pulse(p, opt_shard, batch):
                p_flat, opt_shard, loss, gl, gg = inner_pulse(
                    p, opt_shard, batch)
                return regather(p_flat), opt_shard, loss, gl, gg

            step = jax.jit(outer, donate_argnums=(0, 1) if donate else ())
            # compiled lazily on the first pulse step (never, for runs
            # shorter than KF_PULSE_EVERY)
            step_pulse = jax.jit(
                outer_pulse, donate_argnums=(0, 1) if donate else ())
            return {"geo": geo, "step": step, "step_pulse": step_pulse,
                    "init_opt": init_opt}

        # -- stage 3: params live sharded; gather is JIT inside the step --
        def init_params_body(p):
            return lax.dynamic_slice(
                geo.flat_of(p), (geo.my_offset(),), (chunk,))

        init_params = jax.jit(shard_map(
            init_params_body, mesh=mesh, in_specs=(P(),),
            out_specs=P(axes)))

        def step3_body(p_loc, opt_shard, batch):
            def loss_of(ps):
                # bucket-wise all-gather INSIDE the step: parameters are
                # only ever full in-flight; the transpose of each tiled
                # all-gather is the matching tiled psum-scatter, so the
                # backward pass emits the bucketed gradient
                # reduce-scatter with no extra collective written here.
                # prefetch=True double-buffers the bucket gathers (and,
                # via the fence's custom vjp, the transposed backward
                # reduce-scatters): the next bucket's wire time hides
                # under the current one's retirement without letting
                # XLA hold every gathered slab live at once — values
                # bitwise identical (tests/test_schedules.py pins it)
                full = all_gather_flat(ps, geo.scatter_axes, geo.widths,
                                       prefetch=True,
                                       schedule=self._schedule)
                return loss_fn(defuse(full[:total], geo.spec), batch)

            loss, g_shard = jax.value_and_grad(loss_of)(p_loc)
            if average:
                g_shard = g_shard / n
            updates, opt_shard = inner.update(g_shard, opt_shard, p_loc)
            p_loc = optax.apply_updates(p_loc, updates)
            loss = lax.pmean(loss, axes)
            return p_loc, opt_shard, loss

        step3 = jax.jit(
            shard_map(
                step3_body, mesh=mesh,
                in_specs=(P(axes), state_specs, P(axes)),
                out_specs=(P(axes), state_specs, P()),
            ),
            donate_argnums=(0, 1) if donate else (),
        )

        gather_params = jax.jit(regather)
        return {"geo": geo, "step": step3, "init_opt": init_opt,
                "init_params": init_params, "gather_params": gather_params}


def zero_train_step(loss_fn, inner: optax.GradientTransformation, comm,
                    stage: Optional[int] = None, average: bool = True,
                    donate: bool = False,
                    bucket_bytes: int = 4 << 20,
                    schedule: Optional[str] = None,
                    plan=None) -> ZeroStep:
    """Build a staged ZeRO data-parallel training step over ``comm``.

    ``stage``: 1 = all-reduce grads + sharded update (the classic ZeRO-1
    path, kept as the measured comm baseline), 2 = bucketed
    reduce-scatter grads (half the gradient wire bytes), 3 = stage 2
    plus parameters sharded 1/n between steps with bucket-wise
    just-in-time all-gather inside the step.  ``bucket_bytes`` sizes the
    reduce-scatter/all-gather buckets (the gradient-bucket fusion of
    ``ops/schedules.py`` folded to collective-sized pieces).

    Returns a :class:`ZeroStep`; for stages 1/2 ``step, init_opt =
    zero_train_step(...)`` unpacks like :func:`zero1_train_step`.  The
    sharded state geometry is identical across stages and to ZeRO-1, so
    :func:`zero_snapshot` / :func:`zero_restore` / :func:`zero_reshard` /
    :func:`zero_reshard_p2p` apply unchanged (stage 3's parameter shard
    is re-carved by the same machinery — it is just one more flat
    state vector).

    ``schedule`` selects the bucket collectives' implementation:
    ``"lax"`` (default — ``psum_scatter``/``all_gather`` primitives) or
    ``"pallas_ring"`` (the in-kernel-overlap ICI ring kernels of
    :mod:`kungfu_tpu.ops.pallas.collectives`; the stage-3 gather's
    custom vjp keeps the transposed gradient reduce-scatter).  The
    sharded state geometry is identical either way.

    ``plan`` (a :class:`~kungfu_tpu.parallel.train.ParallelPlan`)
    supplies ``stage`` from ``plan.zero_stage`` and maps
    ``plan.collective_schedule`` onto the bucket vocabulary — the
    unified-plan route every entrypoint shares.  Both ``stage`` and
    ``schedule`` default to None so an EXPLICIT argument is
    distinguishable from the default: one that disagrees with the plan
    raises instead of being silently replaced."""
    if plan is not None:
        if plan.tp != 1 or plan.pp != 1 or plan.sp != 1:
            raise ValueError(
                f"zero_train_step shards over ONE dp axis but the plan "
                f"carries tp={plan.tp} pp={plan.pp} sp={plan.sp}")
        if not plan.zero_stage:
            raise ValueError("plan.zero_stage is 0 — use dp_train_step")
        if stage is not None and stage != plan.zero_stage:
            raise ValueError(
                f"stage={stage} disagrees with plan.zero_stage="
                f"{plan.zero_stage} — set it in the plan")
        plan_sched = ("pallas_ring"
                      if plan.collective_schedule == "pallas_ring"
                      else "lax")
        if schedule is not None and schedule != plan_sched:
            raise ValueError(
                f"schedule={schedule!r} disagrees with "
                f"plan.collective_schedule="
                f"{plan.collective_schedule!r} — set it in the plan")
        stage = plan.zero_stage
        schedule = plan_sched
    return ZeroStep(loss_fn, inner, comm,
                    2 if stage is None else stage, average, donate,
                    bucket_bytes, "lax" if schedule is None else schedule)


def zero_comm_bytes(total_params: int, n: int, stage: int,
                    itemsize: int = 4) -> dict:
    """Analytic per-rank wire bytes per training step (ring convention):
    the honest denominator for
    the traced :func:`~kungfu_tpu.ops.schedules.traced_collective_bytes`
    counts.  Keys: ``grad_bytes`` (all-reduce at stage 1, reduce-scatter at
    stages 2/3), ``param_bytes`` (the per-step parameter all-gather —
    partitioner-inserted at stages 1/2, explicit in-step at stage 3) and
    their ``total_bytes``."""
    if n < 1:
        raise ValueError(f"n must be >= 1, got {n}")
    padded = math.ceil(total_params / n) * n if n else total_params
    rs = (n - 1) / n * padded * itemsize
    grad = 2.0 * rs if stage == 1 else rs
    return {
        "grad_bytes": grad,
        "param_bytes": rs,
        "total_bytes": grad + rs,
        "padded_params": padded,
    }


# -- host-plane bucket pipelining (kf-overlap) -----------------------------
#
# The multi-process data path (CPU test clusters, between-mesh-epoch
# phases) runs the ZeRO bucket loops over the host engine, where
# communication is real wall time the Python loop used to serialize:
# issue bucket i, WAIT, do bucket i's optimizer math, issue bucket i+1 —
# wire and compute adding instead of overlapping.  The helpers below are
# the depth-k software pipeline over the engine's async handles: issue
# bucket i+k while bucket i's math runs.  Bucket order, tags, and
# per-bucket arithmetic are IDENTICAL to the serial loop (one geometry,
# PR 7's invariant), so serial and pipelined runs produce bitwise-equal
# results — only the wall clock moves (``examples/overlap_pipeline.py``
# asserts both).


def host_bucket_spans(chunk: int, widths) -> list:
    """``[(offset, width)]`` bucket tiling of one rank's chunk — shared
    by the serial and pipelined loops so their geometry cannot drift."""
    spans = []
    off = 0
    for w in widths:
        spans.append((off, int(w)))
        off += int(w)
    if off != chunk:
        raise ValueError(f"widths {list(widths)} do not tile chunk {chunk}")
    return spans


def host_bucket_pipeline(engine, flat, widths, compute, *, op: str = "sum",
                         pipelined: bool = True,
                         depth: Optional[int] = None,
                         name: str = "zp") -> list:
    """Bucketed host-plane reduce-scatter with a depth-k software
    pipeline: ``flat`` is this rank's full mesh-major ``[n*chunk]``
    buffer (the fused gradient), bucket b's collective operand is the
    ``[n, width_b]`` column slab — the exact device-plane
    :func:`~kungfu_tpu.ops.schedules.reduce_scatter_flat` geometry, so
    concatenating the per-bucket results reproduces this rank's
    contiguous chunk.  ``compute(i, reduced)`` runs each bucket's local
    math (optimizer update on the owned slice) and its results are
    returned in bucket order.

    ``pipelined=True`` issues bucket ``i+depth``'s reduce-scatter
    *before* running bucket ``i``'s compute, so wire time hides under
    math (and under other buckets' wire time — the engine's bounded
    window runs up to ``depth`` collectives concurrently).  The serial
    form is the reference loop: issue, wait, compute, repeat.  Tags are
    explicit and identical in both forms, so the two are wire-compatible
    and bitwise-equal in results."""
    n = len(engine.peers)
    if len(flat) % n:
        raise ValueError(f"flat buffer ({len(flat)}) must tile {n} ranks")
    chunk = len(flat) // n
    g2 = np.asarray(flat).reshape(n, chunk)
    spans = host_bucket_spans(chunk, widths)

    def slab(i):
        off, w = spans[i]
        return np.ascontiguousarray(g2[:, off:off + w]).reshape(-1)

    if not pipelined:
        return [compute(i, engine.reduce_scatter(
                    slab(i), op=op, name=f"{name}.b{i}"))
                for i in range(len(spans))]

    if depth is None:
        depth = engine.overlap_depth
    if depth < 1:
        # same guard as engine.set_overlap_depth: an empty prefill would
        # otherwise surface as a bare IndexError on the first popleft
        raise ValueError(f"pipeline depth must be >= 1, got {depth}")
    from collections import deque

    handles = deque(
        engine.reduce_scatter_async(slab(i), op=op, name=f"{name}.b{i}")
        for i in range(min(depth, len(spans))))
    outs = []
    for i in range(len(spans)):
        reduced = handles.popleft().wait()
        nxt = i + depth
        if nxt < len(spans):
            # issue BEFORE compute: bucket nxt's wire time runs under
            # bucket i's optimizer math — the pipeline's whole point
            handles.append(engine.reduce_scatter_async(
                slab(nxt), op=op, name=f"{name}.b{nxt}"))
        outs.append(compute(i, reduced))
    return outs


def host_bucket_all_gather(engine, shard, widths, *, pipelined: bool = True,
                           depth: Optional[int] = None,
                           name: str = "zg"):
    """Bucketed host-plane all-gather of this rank's ``[chunk]`` shard
    back to the mesh-major ``[n*chunk]`` full buffer — the ZeRO-3
    parameter path's host-plane analog of
    :func:`~kungfu_tpu.ops.schedules.all_gather_flat`.  Pipelined form
    keeps up to ``depth`` bucket gathers in flight; results are
    assembled in bucket order either way (bitwise-equal)."""
    n = len(engine.peers)
    chunk = len(shard)
    spans = host_bucket_spans(chunk, widths)
    shard = np.asarray(shard)

    def assemble(pieces):
        full = np.empty((n, chunk), shard.dtype)
        for (off, w), piece in zip(spans, pieces):
            full[:, off:off + w] = piece.reshape(n, w)
        return full.reshape(-1)

    if not pipelined:
        return assemble([
            engine.all_gather(shard[off:off + w], name=f"{name}.b{i}")
            for i, (off, w) in enumerate(spans)])

    if depth is None:
        depth = engine.overlap_depth
    if depth < 1:
        raise ValueError(f"pipeline depth must be >= 1, got {depth}")
    from collections import deque

    handles = deque(
        engine.all_gather_async(shard[spans[i][0]:spans[i][0] + spans[i][1]],
                                name=f"{name}.b{i}")
        for i in range(min(depth, len(spans))))
    pieces = []
    for i in range(len(spans)):
        got = handles.popleft().wait()
        nxt = i + depth
        if nxt < len(spans):
            off, w = spans[nxt]
            handles.append(engine.all_gather_async(
                shard[off:off + w], name=f"{name}.b{nxt}"))
        pieces.append(got)
    return assemble(pieces)


# -- generalized (stage-agnostic) elastic state movement -------------------
# The snapshot/restore/reshard trio below IS zero1's: every stage shares
# the flat chunk geometry, so the zero1_* machinery already moves any
# stage's state (including ZeRO-3 parameter shards).  The aliases make
# call sites say what they mean.
zero_snapshot = zero1_snapshot
zero_restore = zero1_restore
zero_reshard = zero1_reshard


def reshard_plan(total: int, old_n: int, new_n: int):
    """Pure segment-exchange plan for an old_n -> new_n re-carve of a
    flat ``total``-element state vector: ``[(old_rank, new_rank, start,
    length)]`` in global flat offsets, covering exactly ``[0, total)``
    (padding is zeros by construction on both sides and never moves).
    Every rank computes the identical plan — the whole point: the
    exchange needs no leader and no gather, each rank moves only the
    O(total/n) bytes it owns or will own."""
    if old_n < 1 or new_n < 1:
        raise ValueError(f"world sizes must be >= 1 ({old_n} -> {new_n})")
    oc = math.ceil(total / old_n)
    nc = math.ceil(total / new_n)
    segs = []
    for r in range(new_n):
        lo, hi = r * nc, min((r + 1) * nc, total)
        if lo >= hi:
            continue  # new rank holds pure padding
        for o in range(lo // oc, (hi - 1) // oc + 1):
            s = max(lo, o * oc)
            e = min(hi, (o + 1) * oc, total)
            if s < e:
                segs.append((o, r, s, e - s))
    return segs


def _vector_leaves(tree):
    """(index, leaf) of the sharded flat state vectors (ndim >= 1)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return leaves, treedef


def _local_chunk(leaf, chunk: int):
    """(offset, np.ndarray) of THIS process's chunk of a sharded flat
    state vector.  Single-controller arrays are fully addressable — the
    caller slices per-rank chunks out of the returned full buffer
    instead (offset None signals that)."""
    if leaf.is_fully_addressable:
        return None, np.asarray(leaf)
    shards = leaf.addressable_shards
    if len(shards) != 1:
        raise NotImplementedError(
            "zero_reshard_p2p assumes one device per process (one chunk "
            f"per rank); this process holds {len(shards)} shards")
    s = shards[0]
    return int(s.index[0].start or 0), np.asarray(s.data)


def _place_sharded(new_comm, full_np=None, my_chunk=None):
    """Place a flat state vector on ``new_comm``'s mesh, sharded P(axes):
    from the full host buffer (single-controller) or from this process's
    chunk (multi-controller, one device per process)."""
    from jax.sharding import NamedSharding

    sharded = NamedSharding(new_comm.mesh, P(new_comm.axis))
    if not new_comm._multiproc:
        return jax.device_put(full_np, sharded)
    devs = [d for d in new_comm.mesh.devices.ravel()
            if d.process_index == jax.process_index()]
    if len(devs) != 1:
        raise NotImplementedError(
            "zero_reshard_p2p placement assumes one device per process")
    n = new_comm.size
    shape = (my_chunk.shape[0] * n,)
    return jax.make_array_from_single_device_arrays(
        shape, sharded, [jax.device_put(my_chunk, devs[0])])


def zero_reshard_p2p(opt_shard, params, new_comm, peer=None,
                     new_workers=None, old_n: Optional[int] = None,
                     tag: str = "0"):
    """Peer-to-peer elastic re-carve of sharded ZeRO state: every member
    of the OLD membership sends exactly the segments of its own chunk
    that the NEW geometry assigns elsewhere, every member of the NEW
    membership assembles its chunk from those segments — **no gather to
    a leader, no full-state blob anywhere** (contrast
    :func:`zero_snapshot` + :func:`zero_restore`, which funnel
    state_bytes through rank 0's host RAM).  Per-rank traffic is
    O(total/old_n + total/new_n).

    Call it at the step boundary BEFORE the resize is applied, on every
    old member (leavers serve their segments and return ``None``) and on
    every new member that was an old member.  Joiners that held no old
    chunk receive everything, including the replicated scalar leaves
    (served by old rank 0): pass their fresh ``init_opt(params)`` as
    ``opt_shard`` for structure.

    Single-controller worlds (every chunk addressable) re-carve by pure
    slicing — bit-identical to the channel path, which the tests pin.

    ``tag`` must be identical on every participant (use the agreed NEW
    cluster version); it keys the rendezvous names."""
    total = int(np.sum([int(np.prod(l.shape)) for l in
                        jax.tree_util.tree_leaves(params)]))
    new_n = new_comm.size
    new_chunk = math.ceil(total / new_n)
    new_padded = new_chunk * new_n

    leaves, treedef = _vector_leaves(opt_shard)
    vec_idx = [i for i, l in enumerate(leaves)
               if getattr(l, "ndim", 0) >= 1]

    chan = getattr(peer, "channel", None) if peer is not None else None
    if chan is None:
        # single-controller: every old chunk is addressable; replay the
        # exact segment plan in numpy (same data movement as the wire
        # path, minus the wire)
        if old_n is None:
            for i in vec_idx:
                old_n = len(leaves[i].sharding.device_set)
                break
            else:
                old_n = new_n
        plan = reshard_plan(total, old_n, new_n)
        out = []
        for i, leaf in enumerate(leaves):
            if i not in vec_idx:
                out.append(jax.device_put(jnp.asarray(leaf),
                                          new_comm.replicated_sharding()))
                continue
            full = np.asarray(leaf)
            if full.shape[0] < total:
                raise ValueError(
                    f"state vector has {full.shape[0]} elements but params "
                    f"fuse to {total} — same param tree required")
            buf = np.zeros((new_padded,), full.dtype)
            for (_, _, s, ln) in plan:
                buf[s:s + ln] = full[s:s + ln]
            out.append(_place_sharded(new_comm, full_np=buf))
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- host-channel exchange --------------------------------------------
    old_workers = peer.cluster.workers
    if new_workers is None:
        raise ValueError("zero_reshard_p2p over a channel needs the agreed "
                         "new worker list")
    if old_n is None:
        old_n = len(old_workers)
    my_old = old_workers.rank(peer.config.self_id)
    my_new = new_workers.rank(peer.config.self_id)
    plan = reshard_plan(total, old_n, new_n)
    old_chunk = math.ceil(total / old_n)

    def seg_name(i, s):
        return f"kf.zrs.{tag}.l{i}.o{s}"

    import io

    # planned-resize exchange still runs next to live peers: convert a
    # raw channel timeout (a death mid-exchange) into the typed
    # PeerFailureError the recovery contract promises, same as the
    # committed-boundary path in elastic/reshard.py
    from kungfu_tpu.elastic.reshard import _recv_or_fail

    # 1) serve: every segment my old chunk owns, destined elsewhere
    if my_old is not None:
        for i in vec_idx:
            off, mine = _local_chunk(leaves[i], old_chunk)
            if off is None:  # fully addressable leaf in a multiproc world
                off = my_old * old_chunk
                mine = mine[off:off + old_chunk]
            for (o, r, s, ln) in plan:
                if o != my_old:
                    continue
                dst = new_workers[r]
                if dst == peer.config.self_id:
                    continue
                chan.send(dst, seg_name(i, s),
                          np.ascontiguousarray(mine[s - off:s - off + ln]))
        if my_old == 0:
            # scalars for pure joiners (replicated leaves have no owner)
            scal = {f"s{i}": np.asarray(l) for i, l in enumerate(leaves)
                    if i not in vec_idx}
            blob = io.BytesIO()
            np.savez(blob, **scal)
            for w in new_workers:
                if old_workers.rank(w) is None:
                    chan.send(w, f"kf.zrs.{tag}.scalars", blob.getvalue())

    if my_new is None:
        return None  # leaver: served its segments, holds nothing now

    # 2) assemble my new chunk
    scalars = None
    if my_old is None:
        with np.load(io.BytesIO(_recv_or_fail(
                chan, old_workers[0], 0, "zero-reshard",
                f"kf.zrs.{tag}.scalars"))) as z:
            scalars = {k: z[k] for k in z.files}
    out = []
    for i, leaf in enumerate(leaves):
        if i not in vec_idx:
            val = (scalars[f"s{i}"] if scalars is not None
                   else np.asarray(leaf))
            out.append(jax.device_put(jnp.asarray(val),
                                      new_comm.replicated_sharding()))
            continue
        off = mine = None
        if my_old is not None:
            off, mine = _local_chunk(leaf, old_chunk)
            if off is None:
                off = my_old * old_chunk
                mine = mine[off:off + old_chunk]
        buf = np.zeros((new_chunk,), leaf.dtype)
        lo = my_new * new_chunk
        for (o, r, s, ln) in plan:
            if r != my_new:
                continue
            if o == my_old:
                buf[s - lo:s - lo + ln] = mine[s - off:s - off + ln]
            else:
                got = np.frombuffer(
                    _recv_or_fail(chan, old_workers[o], o, "zero-reshard",
                                  seg_name(i, s)),
                    dtype=buf.dtype)
                if got.shape[0] != ln:
                    raise ValueError(
                        f"reshard segment {seg_name(i, s)}: expected {ln} "
                        f"elements, got {got.shape[0]}")
                buf[s - lo:s - lo + ln] = got
        out.append(_place_sharded(new_comm, my_chunk=buf))
    return jax.tree_util.tree_unflatten(treedef, out)
