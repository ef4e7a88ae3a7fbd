"""Pallas ICI collectives: ring reduce-scatter / all-gather kernels.

The overlap plane (kf-overlap) hides wire time on the *host* plane and
leans on XLA's default double-buffering on the device plane; this module
writes the device collectives themselves — the 1810.11112
communication/computation-overlap design space pushed below XLA, at the
pod-scale regime 1909.09756 identifies (collectives inside ICI).  Two
kernel families, each in a unidirectional and a bidirectional form:

* **ring reduce-scatter** — the per-device ``[n*chunk]`` mesh-major flat
  buffer is carved into ``n`` chunks; partial sums travel the ring and
  each device ends with its own fully reduced chunk.  Inside ONE
  ``pallas_call``, each step's RDMA (``make_async_remote_copy``) is
  started, the *local* HBM→VMEM chunk prefetch rides the same window,
  and the fold (``recv + local``) executes while the send DMA is still
  draining — chunk *i*'s reduction runs while chunk *i±1*'s copy is in
  flight, double-buffered working slots throughout.
* **ring all-gather** — the inverse movement: each device's ``[chunk]``
  shard travels the ring; the VMEM→HBM output drain of the chunk
  received at step *s* overlaps the step-*s+1* forward RDMA.

The bidirectional forms split the chunk's sublane rows into two bands
that travel clockwise and counter-clockwise at once, halving per-link
bytes on the (full-duplex) ICI ring.

Geometry contract — identical to :mod:`kungfu_tpu.ops.schedules`: the
flat buffer is viewed ``[n, chunk]`` in mesh-major device order, device
``r`` owns chunk ``r``, and bucket concatenation reproduces the exact
un-bucketed per-device layout (the ZeRO-2/3 invariant).  That is what
lets ``reduce_scatter_flat``/``all_gather_flat`` swap these kernels in
per bucket without moving a single optimizer-state byte.

Implementation routing (``impl`` argument, default from the launch-set
``KF_PALLAS_COLLECTIVES`` env — read ONCE at import, never in traced
code):

* ``pallas`` — the kernels; compiled on TPU, ``interpret=True``
  elsewhere (the bitwise test/bench mode — the interpreter is a
  correctness tool, not a transport);
* ``lax`` — a pure ``lax.ppermute`` ring with the IDENTICAL hop order
  and fold-operand order, so its results are **bitwise-identical** to
  the kernels (pinned in ``tests/test_pallas_collectives.py``);
* ``auto`` (default) — ``pallas`` on TPU, ``lax`` elsewhere (same
  policy as :func:`kungfu_tpu.parallel.ring.ring_attention`'s
  ``block_impl="auto"``: interpret-mode Pallas is far too slow for the
  CPU test cluster, and the emulation computes the same bits).

Reduction-order contract: a ring reduce-scatter's chunk ``c`` folds
contributions in ring order starting at device ``c±1`` —
``((x[c+1] + x[c+2]) + ...) + x[c]`` for the clockwise direction — which
for floats differs bitwise from XLA's ``lax.psum_scatter`` association
in general.  The kernels are therefore pinned bitwise against the
order-matched lax emulation on arbitrary floats, and against
``lax.psum_scatter`` itself on order-exact data (ints, and
integer-valued floats whose sums are exactly representable); all-gather
is pure data movement and is pinned bitwise against ``lax.all_gather``
unconditionally.  See docs/pallas_collectives.md.

Both collectives are differentiable as a custom-vjp pair: the backward
of the all-gather IS the ring reduce-scatter of the cotangent (and vice
versa), so the ZeRO-3 gradient path keeps its "transpose of the gather
is the scatter" shape when it rides ``schedule="pallas_ring"``.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.lax import axis_size

from kungfu_tpu.ops.pallas._sharding import match_vma as _match_vma
from kungfu_tpu.ops.pallas._sharding import sds as _sds
from kungfu_tpu.ops.pallas._sharding import vma_of as _vma
from kungfu_tpu.utils.envs import LaunchKnobs

_LANE = 128

#: selectable implementations (module docstring)
IMPLS = ("auto", "pallas", "lax")


class _Knobs(LaunchKnobs):
    """``KF_PALLAS_COLLECTIVES`` — the default ``impl`` for every ring
    collective call that does not pass one explicitly.  Launch-set by
    design (it selects which program gets traced; no cluster-size state
    to go stale): read at import / :meth:`reload`, never in traced
    code."""

    def _read(self) -> None:
        impl = os.environ.get("KF_PALLAS_COLLECTIVES", "auto").lower()
        if impl not in IMPLS:
            raise ValueError(
                f"KF_PALLAS_COLLECTIVES={impl!r}: one of {IMPLS}")
        self.impl = impl


ENV = _Knobs()


def _use_pallas(impl) -> bool:
    impl = impl if impl is not None else ENV.impl
    if impl not in IMPLS:
        raise ValueError(f"impl {impl!r}: one of {IMPLS} (or None)")
    if impl == "pallas":
        return True
    if impl == "lax":
        return False
    return jax.default_backend() == "tpu"


# -- geometry --------------------------------------------------------------

def _sublane(dtype) -> int:
    """Minimum second-to-last tile dim for ``dtype`` (f32 8, bf16 16,
    int8/fp8 32 — the Mosaic tiling table)."""
    size = jnp.dtype(dtype).itemsize
    if size >= 4:
        return 8
    if size == 2:
        return 16
    return 32


def _tile_rows(chunk: int, dtype) -> int:
    """Rows of the padded ``[rows, 128]`` chunk tile."""
    sub = _sublane(dtype)
    rows = -(-chunk // _LANE)
    return max(sub, -(-rows // sub) * sub)


def _band_rows(rows: int, dtype) -> int:
    """Clockwise band height of the bidirectional row split (0 = the
    chunk is too short to split; callers fall back to unidirectional).
    Shared by kernel and emulation so the per-band fold orders — and
    therefore the bits — agree."""
    sub = _sublane(dtype)
    if rows < 2 * sub:
        return 0
    return -(-(rows // 2) // sub) * sub


def _chunk_view(flat, n: int, chunk: int):
    """``[n*chunk]`` flat → padded ``[n, rows, 128]`` mesh-major view."""
    rows = _tile_rows(chunk, flat.dtype)
    pad = rows * _LANE - chunk
    g = flat.reshape(n, chunk)
    if pad:
        g = jnp.concatenate([g, jnp.zeros((n, pad), g.dtype)], axis=-1)
    return g.reshape(n, rows, _LANE)


def _shard_view(shard, chunk: int):
    """``[chunk]`` shard → padded ``[rows, 128]`` tile."""
    rows = _tile_rows(chunk, shard.dtype)
    pad = rows * _LANE - chunk
    if pad:
        shard = jnp.concatenate(
            [shard, jnp.zeros((pad,), shard.dtype)])
    return shard.reshape(rows, _LANE)


def ring_wire_bytes(nbytes: int, n: int, kind: str = "reduce_scatter") -> float:
    """Analytic per-rank ICI wire bytes of one ring collective over a
    per-device payload of ``nbytes`` (the ring convention of
    :data:`kungfu_tpu.ops.schedules._COLLECTIVE_COST`): a reduce-scatter
    moves ``(n-1)/n * nbytes``, an all-gather ``(n-1) * nbytes`` (its
    payload being the shard), an all-reduce the sum of both.  Direction
    count does not change the BYTES — the bidirectional forms move the
    same total over twice the links in half the steps."""
    if kind == "reduce_scatter":
        return (n - 1) / n * nbytes
    if kind == "all_gather":
        return (n - 1) * nbytes
    if kind == "all_reduce":
        return 2.0 * (n - 1) / n * nbytes
    raise ValueError(f"unknown kind {kind!r}")


# -- the order-matched lax emulation ---------------------------------------
#
# One hop = one lax.ppermute; the fold is `received + local` with the
# receive operand FIRST — the exact operand order the kernels use, so
# emulation and kernel are bitwise-identical on every input (pinned in
# tests/test_pallas_collectives.py).  Chunk c's partial starts at device
# c+sign, hops in `sign` direction, and lands fully reduced on its owner
# after n-1 hops.

def _take(parts, idx):
    return lax.dynamic_index_in_dim(parts, idx, axis=0, keepdims=False)


def _rs_dir_emul(parts, axis: str, sign: int):
    """parts: [n, rows, 128]; returns this device's reduced [rows, 128]."""
    n = axis_size(axis)
    me = lax.axis_index(axis)
    perm = [(i, (i + sign) % n) for i in range(n)]
    acc = _take(parts, (me - sign) % n)
    for s in range(n - 1):
        got = lax.ppermute(acc, axis, perm)
        acc = got + _take(parts, (me - sign * (s + 2)) % n)
    return acc


def _ag_dir_emul(tile, axis: str, sign: int):
    """tile: [rows, 128]; returns the gathered [n, rows, 128]."""
    n = axis_size(axis)
    me = lax.axis_index(axis)
    perm = [(i, (i + sign) % n) for i in range(n)]
    # match the tile's varying manual axes up front (vma-typed jax): the
    # zeros are unvarying but every update writes varying data
    out = _match_vma(jnp.zeros((n,) + tile.shape, tile.dtype),
                     _vma(tile) | frozenset({axis}))
    out = lax.dynamic_update_index_in_dim(out, tile, me, axis=0)
    buf = tile
    for s in range(n - 1):
        buf = lax.ppermute(buf, axis, perm)
        out = lax.dynamic_update_index_in_dim(
            out, buf, (me - sign * (s + 1)) % n, axis=0)
    return out


def _rs_emul(parts, axis: str, bidirectional: bool):
    rows = parts.shape[1]
    band = _band_rows(rows, parts.dtype) if bidirectional else 0
    if not band:
        return _rs_dir_emul(parts, axis, +1)
    return jnp.concatenate(
        [_rs_dir_emul(parts[:, :band], axis, +1),
         _rs_dir_emul(parts[:, band:], axis, -1)], axis=0)


def _ag_emul(tile, axis: str, bidirectional: bool):
    rows = tile.shape[0]
    band = _band_rows(rows, tile.dtype) if bidirectional else 0
    if not band:
        return _ag_dir_emul(tile, axis, +1)
    return jnp.concatenate(
        [_ag_dir_emul(tile[:band], axis, +1),
         _ag_dir_emul(tile[band:], axis, -1)], axis=1)


# -- the kernels -----------------------------------------------------------
#
# Protocol per direction (sign = +1 clockwise / -1 counter-clockwise),
# device `me`, neighbors dst = me+sign (where our RDMA lands) and
# src = me-sign (who lands in ours):
#
#   reduce-scatter: acc slots [2], recv slots [2], local-prefetch slots
#   [2].  Step s: start the RDMA of the current partial (acc[s%2] →
#   dst's recv[s%2]); start the HBM→VMEM prefetch of the local chunk the
#   fold needs; wait_recv; fold `recv + local` into acc[(s+1)%2] (or the
#   output on the last step) WHILE the send DMA drains; wait_send.  The
#   fold-while-sending is the in-kernel overlap; the slot alternation
#   plus the per-step wait_send/ack make the 2-deep buffers safe.
#
#   all-gather: working slots [2] double as send source and landing
#   zone.  Step s: forward slot s%2; wait_recv of slot (s+1)%2; start
#   the VMEM→HBM output drain of the received chunk — it overlaps the
#   forward's send drain — wait_send, wait the drain.
#
# Flow control (compiled only; the interpreter executes DMAs in program
# order and does not implement remote semaphore_signal): a REGULAR ack
# semaphore — after consuming the slot our upstream neighbor wrote, we
# signal it; a sender re-uses a remote slot (step s+2) only after that
# ack.  Kernel entry is fenced by the standard neighbor barrier
# (get_barrier_semaphore + collective_id) so no RDMA lands before its
# target kernel is live.

_LOGICAL = pltpu.DeviceIdType.LOGICAL


def _neighbor_barrier(left, right):
    bar = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(bar, inc=1, device_id=left,
                           device_id_type=_LOGICAL)
    pltpu.semaphore_signal(bar, inc=1, device_id=right,
                           device_id_type=_LOGICAL)
    pltpu.semaphore_wait(bar, 2)


def _rs_kernel(x_ref, o_ref, acc_ref, recv_ref, loc_ref, send_sem,
               recv_sem, copy_sem, ack_sem, *, axis, n, band, rows,
               interpret):
    """Ring reduce-scatter over ``axis``.  x_ref: [n, rows, 128] (ANY);
    o_ref: [rows, 128] (VMEM).  ``band`` > 0 splits rows into a
    clockwise band [0:band] and a counter-clockwise band [band:]."""
    me = lax.axis_index(axis)
    dirs = ((+1, 0, band if band else rows),) if not band else (
        (+1, 0, band), (-1, band, rows))
    nbr = {+1: lax.rem(me + 1, n), -1: lax.rem(me + n - 1, n)}
    if not interpret:
        _neighbor_barrier(nbr[-1], nbr[+1])

    # seed: the step-0 partial is the local chunk owned by the device
    # one hop upstream (chunk me-sign)
    for d, (sign, lo, hi) in enumerate(dirs):
        seed = pltpu.make_async_copy(
            x_ref.at[lax.rem(me - sign + n, n), pl.ds(lo, hi - lo)],
            acc_ref.at[d, 0, pl.ds(0, hi - lo)],
            copy_sem.at[d, 0])
        seed.start()
        seed.wait()

    for s in range(n - 1):
        slot, nslot = s % 2, (s + 1) % 2
        rdmas, locals_ = [], []
        for d, (sign, lo, hi) in enumerate(dirs):
            if not interpret and s >= 2:
                # downstream consumed the slot we are about to overwrite
                pltpu.semaphore_wait(ack_sem.at[d], 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=acc_ref.at[d, slot, pl.ds(0, hi - lo)],
                dst_ref=recv_ref.at[d, slot, pl.ds(0, hi - lo)],
                send_sem=send_sem.at[d, slot],
                recv_sem=recv_sem.at[d, slot],
                device_id=nbr[sign],
                device_id_type=_LOGICAL)
            rdma.start()
            # overlap 1: the local-chunk prefetch rides the RDMA window
            lcp = pltpu.make_async_copy(
                x_ref.at[lax.rem(me - sign * (s + 2) + 2 * n * n, n),
                         pl.ds(lo, hi - lo)],
                loc_ref.at[d, slot, pl.ds(0, hi - lo)],
                copy_sem.at[d, slot])
            lcp.start()
            rdmas.append(rdma)
            locals_.append(lcp)
        for d, (sign, lo, hi) in enumerate(dirs):
            rdmas[d].wait_recv()
            locals_[d].wait()
            span = pl.ds(0, hi - lo)
            # overlap 2: the fold executes while the send DMA drains
            # (wait_send comes after); operand order `recv + local` is
            # the emulation's — bitwise contract
            folded = recv_ref[d, slot, span] + loc_ref[d, slot, span]
            if s + 1 < n - 1:
                acc_ref[d, nslot, span] = folded
            else:
                o_ref[pl.ds(lo, hi - lo)] = folded
            rdmas[d].wait_send()
            if not interpret and s <= n - 4:
                # tell upstream its step-s write is consumed.  Signaled
                # ONLY when a wait will consume it — upstream waits at
                # its steps 2..n-2 for our folds of steps 0..n-4 — so
                # the ack semaphore drains to exactly zero at kernel end
                # (a trailing signal would strand a nonzero count into
                # the next invocation and break the slot-reuse fence)
                pltpu.semaphore_signal(
                    ack_sem.at[d], inc=1,
                    device_id=nbr[-sign], device_id_type=_LOGICAL)


def _ag_kernel(x_ref, o_ref, buf_ref, send_sem, recv_sem, copy_sem,
               ack_sem, *, axis, n, band, rows, interpret):
    """Ring all-gather over ``axis``.  x_ref: [rows, 128] (ANY);
    o_ref: [n, rows, 128] (ANY)."""
    me = lax.axis_index(axis)
    dirs = ((+1, 0, band if band else rows),) if not band else (
        (+1, 0, band), (-1, band, rows))
    nbr = {+1: lax.rem(me + 1, n), -1: lax.rem(me + n - 1, n)}

    # own chunk: into working slot 0 and output row `me`
    own_out = pltpu.make_async_copy(
        x_ref, o_ref.at[me], copy_sem.at[0, 0])
    own_out.start()
    for d, (sign, lo, hi) in enumerate(dirs):
        seed = pltpu.make_async_copy(
            x_ref.at[pl.ds(lo, hi - lo)],
            buf_ref.at[d, 0, pl.ds(0, hi - lo)],
            copy_sem.at[d, 1])
        seed.start()
        seed.wait()
    own_out.wait()
    if not interpret:
        _neighbor_barrier(nbr[-1], nbr[+1])

    for s in range(n - 1):
        slot, nslot = s % 2, (s + 1) % 2
        rdmas, drains = [], []
        for d, (sign, lo, hi) in enumerate(dirs):
            if not interpret and s >= 2:
                pltpu.semaphore_wait(ack_sem.at[d], 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=buf_ref.at[d, slot, pl.ds(0, hi - lo)],
                dst_ref=buf_ref.at[d, nslot, pl.ds(0, hi - lo)],
                send_sem=send_sem.at[d, slot],
                recv_sem=recv_sem.at[d, nslot],
                device_id=nbr[sign],
                device_id_type=_LOGICAL)
            rdma.start()
            rdmas.append(rdma)
        for d, (sign, lo, hi) in enumerate(dirs):
            rdmas[d].wait_recv()
            # overlap: the VMEM→HBM output drain of the received chunk
            # runs while this step's forward send is still draining
            drain = pltpu.make_async_copy(
                buf_ref.at[d, nslot, pl.ds(0, hi - lo)],
                o_ref.at[lax.rem(me - sign * (s + 1) + 2 * n * n, n),
                         pl.ds(lo, hi - lo)],
                copy_sem.at[d, slot])
            drain.start()
            drains.append(drain)
        for d, (sign, lo, hi) in enumerate(dirs):
            rdmas[d].wait_send()
            drains[d].wait()
            if not interpret and 1 <= s <= n - 3:
                # the slot our upstream wrote at step s-1 is now fully
                # consumed (forwarded at step s, drained at step s-1).
                # Signaled only for writes a future wait guards (upstream
                # waits at its steps 2..n-2 for writes 0..n-4, i.e. our
                # signals at steps 1..n-3): the semaphore drains to zero
                # at kernel end
                pltpu.semaphore_signal(
                    ack_sem.at[d], inc=1,
                    device_id=nbr[-sign], device_id_type=_LOGICAL)


#: VMEM a compiled ring kernel's scratch may take: Mosaic's default
#: scoped limit on a TPU v5e (the kernels pass no ``vmem_limit_bytes``).
#: Found by compiling for a described v5e:2x2 with libtpu 0.0.34: 15 MiB
#: of scratch compiles and 18 MiB is refused, in every kernel form.
_VMEM_SCRATCH_BUDGET = 16 << 20


def _check_vmem(name: str, slots: int, rows: int, dtype) -> None:
    """The kernels hold whole chunks in VMEM (``slots`` buffers of
    ``rows x 128``), so a chunk past the budget cannot compile.  Say so
    at trace time, with the numbers, instead of leaving the caller a
    compiler dump from the middle of a training step."""
    chunk = rows * _LANE * jnp.dtype(dtype).itemsize
    if slots * chunk > _VMEM_SCRATCH_BUDGET:
        mib = 1 << 20
        raise ValueError(
            f"{name}(impl='pallas'): a {chunk / mib:.1f} MiB chunk per "
            f"device needs {slots * chunk / mib:.1f} MiB of VMEM scratch "
            f"({slots} whole-chunk buffers) and the kernel's budget is "
            f"{_VMEM_SCRATCH_BUDGET / mib:.0f} MiB — send chunks of at "
            f"most {_VMEM_SCRATCH_BUDGET // slots / mib:.2f} MiB "
            f"(bucket the payload, as the ZeRO schedules do) or use the "
            f"lax schedule")


def _rs_pallas(parts, axis: str, n: int, bidirectional: bool,
               interpret: bool):
    rows = parts.shape[1]
    band = _band_rows(rows, parts.dtype) if bidirectional else 0
    ndir = 2 if band else 1
    if not interpret:
        _check_vmem("ring_reduce_scatter", 3 * ndir * 2, rows, parts.dtype)
    kernel = functools.partial(
        _rs_kernel, axis=axis, n=n, band=band, rows=rows,
        interpret=interpret)
    return pl.pallas_call(
        kernel,
        out_shape=_sds((rows, _LANE), parts.dtype,
                       vma=_vma(parts) | frozenset({axis})),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((ndir, 2, rows, _LANE), parts.dtype),  # acc
            pltpu.VMEM((ndir, 2, rows, _LANE), parts.dtype),  # recv
            pltpu.VMEM((ndir, 2, rows, _LANE), parts.dtype),  # local
            pltpu.SemaphoreType.DMA((ndir, 2)),               # send
            pltpu.SemaphoreType.DMA((ndir, 2)),               # recv
            pltpu.SemaphoreType.DMA((ndir, 2)),               # copies
            pltpu.SemaphoreType.REGULAR((ndir,)),             # acks
        ],
        compiler_params=pltpu.CompilerParams(collective_id=1),
        interpret=interpret,
        name="ring_reduce_scatter",
    )(parts)


def _ag_pallas(tile, axis: str, n: int, bidirectional: bool,
               interpret: bool):
    rows = tile.shape[0]
    band = _band_rows(rows, tile.dtype) if bidirectional else 0
    ndir = 2 if band else 1
    if not interpret:
        _check_vmem("ring_all_gather", ndir * 2, rows, tile.dtype)
    kernel = functools.partial(
        _ag_kernel, axis=axis, n=n, band=band, rows=rows,
        interpret=interpret)
    return pl.pallas_call(
        kernel,
        out_shape=_sds((n, rows, _LANE), tile.dtype,
                       vma=_vma(tile) | frozenset({axis})),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((ndir, 2, rows, _LANE), tile.dtype),   # slots
            pltpu.SemaphoreType.DMA((ndir, 2)),               # send
            pltpu.SemaphoreType.DMA((ndir, 2)),               # recv
            pltpu.SemaphoreType.DMA((ndir, 2)),               # copies
            pltpu.SemaphoreType.REGULAR((ndir,)),             # acks
        ],
        compiler_params=pltpu.CompilerParams(collective_id=2),
        interpret=interpret,
        name="ring_all_gather",
    )(tile)


# -- differentiable cores (custom-vjp pair) --------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _rs_core(flat, axis, bidirectional, use_pallas, interpret):
    n = axis_size(axis)
    chunk = flat.shape[0] // n
    parts = _chunk_view(flat, n, chunk)
    if use_pallas:
        tile = _rs_pallas(parts, axis, n, bidirectional, interpret)
    else:
        tile = _rs_emul(parts, axis, bidirectional)
    return tile.reshape(-1)[:chunk]


def _rs_fwd(flat, axis, bidirectional, use_pallas, interpret):
    return _rs_core(flat, axis, bidirectional, use_pallas, interpret), None


def _rs_bwd(axis, bidirectional, use_pallas, interpret, _, ct):
    # transpose of the tiled reduce-scatter is the tiled all-gather
    return (_ag_core(ct, axis, bidirectional, use_pallas, interpret),)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _ag_core(shard, axis, bidirectional, use_pallas, interpret):
    n = axis_size(axis)
    chunk = shard.shape[0]
    tile = _shard_view(shard, chunk)
    if use_pallas:
        full = _ag_pallas(tile, axis, n, bidirectional, interpret)
    else:
        full = _ag_emul(tile, axis, bidirectional)
    return full.reshape(n, -1)[:, :chunk].reshape(-1)


def _ag_fwd(shard, axis, bidirectional, use_pallas, interpret):
    return _ag_core(shard, axis, bidirectional, use_pallas, interpret), None


def _ag_bwd(axis, bidirectional, use_pallas, interpret, _, ct):
    # transpose of the tiled all-gather is the reduce-scatter — the
    # ZeRO-3 gradient arrives already scattered, ring order
    return (_rs_core(ct, axis, bidirectional, use_pallas, interpret),)


_rs_core.defvjp(_rs_fwd, _rs_bwd)
_ag_core.defvjp(_ag_fwd, _ag_bwd)


# -- public API ------------------------------------------------------------

def _resolve(impl, interpret):
    use_pallas = _use_pallas(impl)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return use_pallas, bool(interpret)


def ring_reduce_scatter(flat, axis: str, *, bidirectional: bool = False,
                        impl=None, interpret=None):
    """Ring reduce-scatter (sum) of a per-device mesh-major ``[n*chunk]``
    flat buffer over mesh ``axis``; returns this device's reduced
    ``[chunk]`` slice (device ``r`` owns chunk ``r`` — the
    :func:`kungfu_tpu.ops.schedules.reduce_scatter_flat` geometry).
    Must run inside ``shard_map`` with ``axis`` a live mesh axis; the
    buffer length must divide by the axis size (callers pad — the
    schedule layer's bucket geometry already does).  Differentiable:
    the vjp is the matching ring all-gather."""
    n = axis_size(axis)
    if n == 1:
        return flat
    if flat.ndim != 1 or flat.shape[0] % n:
        raise ValueError(
            f"ring_reduce_scatter wants a flat [n*chunk] buffer over "
            f"n={n}, got shape {flat.shape}")
    use_pallas, interp = _resolve(impl, interpret)
    return _rs_core(flat, axis, bool(bidirectional), use_pallas, interp)


def ring_all_gather(shard, axis: str, *, bidirectional: bool = False,
                    impl=None, interpret=None):
    """Ring all-gather of a per-device ``[chunk]`` shard over mesh
    ``axis``; returns the mesh-major ``[n*chunk]`` concatenation (the
    :func:`kungfu_tpu.ops.schedules.all_gather_flat` geometry, bitwise —
    gathering is pure data movement).  Differentiable: the vjp is the
    matching ring reduce-scatter, so a ZeRO-3-style loss-of-gathered-
    params arrives already scattered."""
    n = axis_size(axis)
    if n == 1:
        return shard
    if shard.ndim != 1:
        raise ValueError(
            f"ring_all_gather wants a flat [chunk] shard, got {shard.shape}")
    use_pallas, interp = _resolve(impl, interpret)
    return _ag_core(shard, axis, bool(bidirectional), use_pallas, interp)


def ring_all_reduce(x, axis: str, *, bidirectional: bool = False,
                    impl=None, interpret=None):
    """Ring all-reduce (sum) of an arbitrary-shaped per-device tensor:
    reduce-scatter then all-gather through the same kernels — the
    ``pallas_ring`` arm of :func:`kungfu_tpu.ops.schedules.
    all_reduce_scheduled`.  Sum only (``psum_scatter`` parity); min/max
    ride the lax ring schedule instead."""
    n = axis_size(axis)
    if n == 1:
        return x
    from kungfu_tpu.ops.schedules import _flatten_pad

    parts, size = _flatten_pad(x, n, "sum")
    flat = parts.reshape(-1)
    shard = ring_reduce_scatter(flat, axis, bidirectional=bidirectional,
                                impl=impl, interpret=interpret)
    full = ring_all_gather(shard, axis, bidirectional=bidirectional,
                           impl=impl, interpret=interpret)
    return full[:size].reshape(x.shape)
