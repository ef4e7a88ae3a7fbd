"""A decode step's update of a KDA layer's state as ONE Pallas TPU kernel
a layer: what ``ops/delta_rule.py::kda_step`` computes, with every
head's matrix read from HBM once and written back once, to the array it
came from.

For slot ``b`` and head ``h``, with ``a = exp(g)``, all in float32 on the
vector unit::

    read  = S^T [a*k, a*q]                 both read-outs, from the OLD state
    delta = beta * (v - read[0])
    o     = read[1] + (k . q) * delta
    S_new = a[:, None] * S + k[:, None] * delta[None, :]
    S_out = S_new where live[b] else S

The state comes **as the engine holds a layer's**, ``[1, B, H, K, V]``
float32: the slot and the head block are picked by the BlockSpec's index
map, the output aliases the input, so XLA slices, copies and lays out
nothing around the call.  ``q``, ``k``, ``g`` ``[B, H, K]``, ``v`` ``[B,
H, V]`` and ``b`` ``[B, H, 1]`` come as the layer leaves them; what the
update wants of them along the state's ROWS (``a``, ``k``, ``a*k``,
``a*q``: ``K`` values a head, which lie along the lanes) is turned in
the kernel, one ``[128, K]`` transpose a grid step, into columns whose
lane ``h`` broadcasts over a head's matrix.

The grid is (slot, head block) and is **every slot of every head,
whatever is live**: ``live`` arrives as prefetched scalars and only
selects what is written back, so the kernel's time follows nothing
(PERF.md, PR 26: no operation of a decode step may follow the data).  A
later change that skips dead slots is one ``pl.when`` around
:func:`_kernel`'s walk and an index map that follows ``live``; it owes
the proof of steadiness that change owes (ROADMAP R6 e).

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


def _kernel(live_ref, q_ref, k_ref, g_ref, v_ref, b_ref, s_ref,
            s_out_ref, o_ref, cols_s):
    """One (slot, head block) grid step.  ``q_ref``, ``k_ref``, ``g_ref``
    ``[heads, K]``, ``v_ref`` ``[heads, V]``, ``b_ref`` ``[heads, 1]``,
    ``s_ref`` and ``s_out_ref`` ``[heads, K, V]``, ``o_ref`` ``[heads,
    V]``; ``cols_s`` ``[K, 128]`` holds ``a``, ``k``, ``a*k``, ``a*q`` of
    head ``h`` in lanes ``h``, ``heads + h``, ``2 heads + h``, ``3 heads
    + h``."""
    heads = s_ref.shape[0]
    # (a change that skips dead slots wraps the walk below in
    # ``pl.when(live)``; today every slot is walked and ``live`` only
    # selects what is written)
    live = live_ref[pl.program_id(0)] != 0
    q, k = q_ref[...], k_ref[...]
    a = jnp.exp(g_ref[...])
    rows = [a, k, a * k, a * q]
    if 4 * heads < _ROWS:
        rows.append(jnp.zeros((_ROWS - 4 * heads, k.shape[1]), F32))
    cols_s[...] = jnp.concatenate(rows, axis=0).T
    kq = jnp.sum(k * q, axis=1, keepdims=True)                # [heads, 1]
    beta, v = b_ref[...], v_ref[...]
    for h in range(heads):
        s = s_ref[h]                                          # [K, V]
        a_c, k_c, ak_c, aq_c = (
            cols_s[:, i * heads + h:i * heads + h + 1] for i in range(4))
        delta = beta[h:h + 1] * (
            v[h:h + 1] - jnp.sum(s * ak_c, axis=0, keepdims=True))
        o_ref[h:h + 1, :] = jnp.sum(s * aq_c, axis=0, keepdims=True) \
            + kq[h:h + 1] * delta
        s_out_ref[h] = jnp.where(live, a_c * s + k_c * delta, s)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _call(live, q, k, g, v, b, state, heads, interpret):
    _, n_b, n_h, dk, dv = state.shape

    def vector(width):
        return pl.BlockSpec((None, heads, width),
                            lambda i, j, live: (i, j, 0))

    matrices = pl.BlockSpec((None, None, heads, dk, dv),
                            lambda i, j, live: (0, i, j, 0, 0))
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_b, n_h // heads),
            in_specs=[vector(dk), vector(dk), vector(dk), vector(dv),
                      vector(1), matrices],
            out_specs=[matrices, vector(dv)],
            scratch_shapes=[pltpu.VMEM((dk, _ROWS), F32)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((n_b, n_h, dv), F32)],
        # the state (operand 6, the prefetched ``live`` counted) is
        # written back where it was read
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="kda_step",
    )(live, q, k, g, v, b, state)


def kda_step(state, q, k, v, g, b, live, *, heads=None, interpret=False):
    """``delta_rule.kda_step`` for one layer's state as the serving cache
    holds it: ``state`` ``[1, B, H, K, V]`` float32, ``q``/``k``/``g``
    ``[B, H, K]``, ``v`` ``[B, H, V]``, ``b`` ``[B, H]``, ``live`` ``[B]``
    bool -> (the new state, shaped like ``state`` and in its place where
    the caller donates it; ``o`` ``[B, H, V]`` float32).  ``heads``
    defaults to :func:`head_block`'s, which has to give one."""
    n_h, dk, dv = state.shape[2:]
    tiles = head_block(n_h, dk, dv, state.dtype)
    heads = heads or tiles
    if not tiles or heads not in _HEADS or n_h % heads \
            or _vmem_bytes(heads, dk, dv) > VMEM_BUDGET_BYTES:
        raise ValueError(f"a state of {n_h} matrices {dk} x {dv} in "
                         f"{state.dtype} does not tile (head block {heads})")
    q, k, g, v, b = (x.astype(F32) for x in (q, k, g, v, b))
    return _call(live.astype(jnp.int32), q, k, g, v, b[..., None], state,
                 int(heads), bool(interpret))
