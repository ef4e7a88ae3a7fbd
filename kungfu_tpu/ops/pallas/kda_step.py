"""A decode step's update of a KDA layer's state as ONE Pallas TPU kernel
a layer: what ``ops/delta_rule.py::kda_step`` computes, with every LIVE
slot's matrices read from HBM once and written back once, to the array
they came from, and no other slot's touched.

For slot ``b`` the step is live for and head ``h``, with ``a = exp(g)``,
all in float32 on the vector unit::

    read  = S^T [a*k, a*q]                 both read-outs, from the OLD state
    delta = beta * (v - read[0])
    o     = read[1] + (k . q) * delta
    S_out = a[:, None] * S + k[:, None] * delta[None, :]

and for a slot it is not live for ``S_out = S`` and ``o = 0``.

The state comes **as the engine holds a layer's**, ``[1, B, H, K, V]``
float32: the slot and the head block are picked by the BlockSpec's index
map, the output aliases the input, so XLA slices, copies and lays out
nothing around the call.  ``q``, ``k``, ``g`` ``[B, H, K]``, ``v`` ``[B,
H, V]`` and ``b`` ``[B, H, 1]`` come as the layer leaves them; what the
update wants of them along the state's ROWS (``a``, ``k``, ``a*k``,
``a*q``: ``K`` values a head, which lie along the lanes) is turned in
the kernel, one ``[128, K]`` transpose a grid step, into columns whose
lane ``h`` broadcasts over a head's matrix.

The grid is (slot, head block), every slot of every head whatever is
live, and **a grid step whose slot is not live neither computes nor
copies** (PR 46): the walk sits inside ``pl.when(live[b])``, and the
index maps of the state, in and out, follow a plan of three prefetched
vectors of scalars (:func:`plan`) under which a dead step names exactly
the block -- slot AND head block -- that the step before it held.
Pallas copies a block in only when the block named changes and writes an
output block back only when the next step names another (or after the
last step), so a dead step moves none of the state's 2 MB: only the five
vectors' 8 KB in and ``o``'s zeros out, whose blocks stay a slot each
(the table below has why).  **The output aliases the input, so the plan is
also what keeps the buffers honest**: a dead step that named any other
block than its predecessor's would have the live slot's new state
written over that block; the dead slots before the first live one name
that slot's FIRST block, which its own step fills before anything is
written back; and with nothing live every step names one block, which
the first step copies through so that it comes out holding what it held
(:func:`_kernel`'s last clause).  The plan depends on the order of the
walk, so neither grid axis is ``"parallel"`` (one TensorCore on this
chip: nothing is lost).  ``tests/test_kda_step_kernel.py`` runs all of
this in the TPU interpreter, which keeps one buffer a block (NaNs until
written) and copies as the chip does, with NaNs planted in every dead
slot.  The operations and their shapes are the same whatever is live;
the slots they skip follow the data, and so does the kernel's time
(PERF.md, PR 46; PR 26's refusal was of a step that differed between
two runs of the same code, and since PR 43 the schedule is the traffic
file's).  :func:`slots_walked` is the count of slots moved, which the
step that calls the kernel owes its cache's ``read``.

What the skip costs and saves (my chip runs, PR 46; TPU v5 lite, the
cell's layer ``[1, 128, 64, 128, 128]``, 16 heads a grid step, ms a call
as the kernel's own events in a profiler trace, 60 calls one behind the
other on a donated state; *scattered*: the live slots drawn at random
among the 128, *packed*: the first ``n``; the parent's kernel, which
walks every slot, 1.663 whatever is live; live slots' states and ``o``
bit-equal to the parent's on the chip, dead slots' states untouched):

=====================================  =====  =====  =====  =====  =====  =====
live slots of 128                          0      1     32     49     96    128
=====================================  =====  =====  =====  =====  =====  =====
**the vectors' blocks a slot each**,
scattered                              0.111  0.123  0.560  0.784  1.343  1.665
the same, packed                                      0.499  0.705  1.276
the vectors' blocks follow the plan
too, scattered                         0.141  0.153  0.590  0.813  1.362  1.668
... and no zeros written for ``o``     0.138  0.150  0.588  0.811  1.360  1.668
=====================================  =====  =====  =====  =====  =====  =====

So a live slot costs 13.0 us a layer where every slot is live (the
parent's rate: 0.002 ms dearer a call for the ``pl.when`` a step), a
skipped grid step **0.22 us** -- the call with nothing live, 0.111 ms, is
its 512 skipped steps and no floor beside them (49 packed slots: 49 x
13.0 us + 316 x 0.215 us) -- and a run of live slots that starts behind
dead ones some 2.6 us more, its first copy hiding under nothing (49
scattered slots read 0.08 ms over 49 packed; 49 packed at the END of the
slab read 0.707, one live slot 0.123 wherever it lies).  Index maps
that follow the plan for the five vectors too save a dead step their
8 KB of copies and cost it more in scalar work than that: 0.28 us a
skipped step, 0.03 ms a call, so the vectors' blocks and ``o``'s stay a
slot each.  The zeros of a dead slot's ``o`` cost 0.003 ms a call.  In
the cell's own trace a call is 0.51-0.53 ms at a median of 31 live slots
against 1.67 (PERF.md, PR 46).

A grid step holds ``heads`` matrices (:func:`head_block`, from the
shapes) and walks them one at a time in straight-line code: a ``128 x
128`` matrix is 16 vector registers, so both read-outs and the update
are taken from one load of it.  What set the sizes (my chip runs, PR 37;
TPU v5 lite, a layer's state ``[1, 128, 64, 128, 128]``, ms a call, each
a mean of 60 calls one behind the other; the state read and written
once is 1.074 GB, 1.31 ms at 819 GB/s; XLA's ``kda_step`` 2.41): 1.685
at 32 heads a grid step, **1.691 at 16**, 1.890 at 8.  A kernel that
only copies the block through the same BlockSpecs read 1.681 and 1.684,
so the arithmetic (with its 64 lane broadcasts a head) hides under the
copies at 16 heads and up, and what is left is the memory's own rate
for a stream that is read and written back: 638-655 GB/s whatever moved
it -- blocks of 4 to 64 heads, of two slots, a hand-written ring of 3 to
12 buffers in flight, one DMA from HBM to HBM (608).  Two other forms
of the same arithmetic read the same to the third digit (``a S`` first
and three columns a head, 1.682; all read-outs first, then the update,
1.685): 16 heads are taken because 32 are no faster and hold twice the
VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
#: what a grid step may hold in VMEM (:func:`_vmem_bytes`): the reason is
#: ``latent_attention.VMEM_BUDGET_BYTES``'s (what a kernel took beyond
#: would be taken from the weights XLA moves into VMEM ahead of the
#: products around the call)
VMEM_BUDGET_BYTES = 14 << 20
#: rows of the per-head vectors one transpose turns into columns: a lane
#: tile, so four vectors of at most 32 heads
_ROWS = 128
#: heads a grid step may hold, in the order they are tried
_HEADS = (16, 8, 32)


def _vmem_bytes(heads: int, dk: int, dv: int) -> int:
    """VMEM a grid step needs at ``heads`` matrices: the state's block in
    and out, the five vectors in and ``o`` out (``b`` a lane tile wide),
    all twice (the pipeline's two buffers), and the columns."""
    state = 2 * heads * dk * dv * 4
    vectors = heads * (3 * dk + 2 * dv + 128) * 4
    return 2 * (state + vectors) + dk * _ROWS * 4


def head_block(heads: int, dk: int, dv: int, dtype) -> int | None:
    """Heads a grid step holds, for a state of ``heads`` matrices ``[dk,
    dv]``: 16 (1 MB of state at the published layer's 128 x 128, in and
    out and twice over 4 MB), else 8 or 32, whichever divides ``heads``
    and fits :data:`VMEM_BUDGET_BYTES`; None where the shapes do not
    tile or the state is not float32 (the caller then has XLA's
    ``kda_step``)."""
    if jnp.dtype(dtype) != F32 or dk % 128 or dv % 128:
        return None
    return next((n for n in _HEADS if heads % n == 0
                 and _vmem_bytes(n, dk, dv) <= VMEM_BUDGET_BYTES), None)


def plan(live, blocks: int):
    """The block of the state each grid step holds, for the slots a decode
    step is ``live`` for (``[B]``, bool or int) and ``blocks`` head
    blocks a slot: (``at``, ``lo``, ``hi``) ``[B]`` int32.  Grid step
    ``(b, j)`` holds head block ``clip(j, lo[b], hi[b])`` of slot
    ``at[b]``: a live slot its own blocks in order; a dead slot the LAST
    block of the last live slot before it, which is the block the grid
    step before it held; the dead slots before the first live one that
    slot's FIRST block, which its own first step then holds (block 0 of
    slot 0 with nothing live).  So a dead step never names a block that
    the step before did not, and nothing is copied in or written back
    for it.  A few scalar operations, made once a decode step and shared
    by its layers' calls."""
    live = jnp.asarray(live) != 0
    slots = jnp.arange(live.shape[0], dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(live, slots, -1))
    at = jnp.where(last < 0, jnp.argmax(live).astype(jnp.int32), last)
    hi = jnp.where(last < 0, 0, blocks - 1).astype(jnp.int32)
    return at, jnp.where(live, 0, hi), hi


def slots_walked(live):
    """The slots whose matrices a call moves (reads, and writes back):
    the live ones.  What the step that calls :func:`kda_step` owes its
    cache's ``read`` (``serve/caches.py``: "a step that reads fewer has
    to say so")."""
    return jnp.sum(jnp.asarray(live) != 0)


def _kernel(live_ref, at_ref, lo_ref, hi_ref, q_ref, k_ref, g_ref, v_ref,
            b_ref, s_ref, s_out_ref, o_ref, cols_s):
    """One (slot, head block) grid step.  ``q_ref``, ``k_ref``, ``g_ref``
    ``[heads, K]``, ``v_ref`` ``[heads, V]``, ``b_ref`` ``[heads, 1]``,
    ``s_ref`` and ``s_out_ref`` ``[heads, K, V]``, ``o_ref`` ``[heads,
    V]``; ``cols_s`` ``[K, 128]`` holds ``a``, ``k``, ``a*k``, ``a*q`` of
    head ``h`` in lanes ``h``, ``heads + h``, ``2 heads + h``, ``3 heads
    + h``.  On a step whose slot is not live the state's blocks, in and
    out, are the ones the step before held (:func:`plan`)."""
    heads = s_ref.shape[0]
    slot = pl.program_id(0)
    live = live_ref[slot] != 0

    @pl.when(live)
    def _():
        q, k = q_ref[...], k_ref[...]
        a = jnp.exp(g_ref[...])
        rows = [a, k, a * k, a * q]
        if 4 * heads < _ROWS:
            rows.append(jnp.zeros((_ROWS - 4 * heads, k.shape[1]), F32))
        cols_s[...] = jnp.concatenate(rows, axis=0).T
        kq = jnp.sum(k * q, axis=1, keepdims=True)            # [heads, 1]
        beta, v = b_ref[...], v_ref[...]
        for h in range(heads):
            s = s_ref[h]                                      # [K, V]
            a_c, k_c, ak_c, aq_c = (
                cols_s[:, i * heads + h:i * heads + h + 1] for i in range(4))
            delta = beta[h:h + 1] * (
                v[h:h + 1] - jnp.sum(s * ak_c, axis=0, keepdims=True))
            o_ref[h:h + 1, :] = jnp.sum(s * aq_c, axis=0, keepdims=True) \
                + kq[h:h + 1] * delta
            s_out_ref[h] = a_c * s + k_c * delta

    # a dead slot's matrices are neither read nor written; its ``o`` is
    # zeros, as ``decode_attn`` and ``latent_attn`` give a slot with no row
    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    # the state's output block is written back when the block named
    # changes, and after the last step.  A dead step names the block of
    # the step before, whose buffer a live step filled -- but the very
    # first step has none before it: the block it names (the first live
    # slot's first, which that slot's step fills later; slot 0's with
    # nothing live, which no step fills) is copied through
    @pl.when(jnp.logical_not(live) & (slot == 0) & (pl.program_id(1) == 0))
    def _():
        s_out_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _call(live, walk, q, k, g, v, b, state, heads, interpret):
    _, n_b, n_h, dk, dv = state.shape

    def vector(width):
        return pl.BlockSpec((None, heads, width), lambda i, j, *_: (i, j, 0))

    # the state's block alone follows the plan: the vectors' stay a slot
    # each, 8 KB copied for a dead step too, which the sweep read lower
    # than five more index maps that follow it (the module's docstring)
    matrices = pl.BlockSpec(
        (None, None, heads, dk, dv),
        lambda i, j, live, at, lo, hi: (0, at[i], jnp.clip(j, lo[i], hi[i]),
                                        0, 0))
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_b, n_h // heads),
            in_specs=[vector(dk), vector(dk), vector(dk), vector(dv),
                      vector(1), matrices],
            out_specs=[matrices, vector(dv)],
            scratch_shapes=[pltpu.VMEM((dk, _ROWS), F32)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((n_b, n_h, dv), F32)],
        # the state (operand 9, the four prefetched vectors counted) is
        # written back where it was read
        input_output_aliases={9: 0},
        # the blocks a step holds depend on the steps before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_step",
    )(live, *walk, q, k, g, v, b, state)


def kda_step(state, q, k, v, g, b, live, *, heads=None, walk=None,
             interpret=False):
    """``delta_rule.kda_step`` for one layer's state as the serving cache
    holds it: ``state`` ``[1, B, H, K, V]`` float32, ``q``/``k``/``g``
    ``[B, H, K]``, ``v`` ``[B, H, V]``, ``b`` ``[B, H]``, ``live`` ``[B]``
    bool -> (the new state, shaped like ``state`` and in its place where
    the caller donates it; ``o`` ``[B, H, V]`` float32, zeros for a slot
    that is not live).  Only the live slots' matrices are moved.
    ``heads`` defaults to :func:`head_block`'s, which has to give one;
    ``walk`` is :func:`plan`'s vectors for ``live`` and ``H // heads``
    where the caller has made them for several layers' calls.  With
    ``interpret`` the call runs in the TPU interpreter, which keeps a
    buffer a block and copies one only when the block named changes, as
    the chip does."""
    n_h, dk, dv = state.shape[2:]
    tiles = head_block(n_h, dk, dv, state.dtype)
    heads = heads or tiles
    if not tiles or heads not in _HEADS or n_h % heads \
            or _vmem_bytes(heads, dk, dv) > VMEM_BUDGET_BYTES:
        raise ValueError(f"a state of {n_h} matrices {dk} x {dv} in "
                         f"{state.dtype} does not tile (head block {heads})")
    q, k, g, v, b = (x.astype(F32) for x in (q, k, g, v, b))
    if walk is None:
        walk = plan(live, n_h // heads)
    return _call(live.astype(jnp.int32), tuple(walk), q, k, g, v,
                 b[..., None], state, int(heads),
                 pltpu.InterpretParams() if interpret else False)
