"""A decode step's softmax attention over a slab of per-head K/V rows as
ONE Pallas TPU kernel a layer, which walks **only the key tiles a slot's
context reaches**: what ``models/cohere2_moe.py::attention`` computes
for one query row a slot, with the scores never in HBM and a row the
slot cannot see never read.

One query row a slot, ``q`` ``[B, G, J, D]`` (``J`` query heads read
key/value head ``g``), against the slab **as the engine holds it**,
``K`` and ``V`` ``[L, B, G, S, D]``: the layer and the slot are picked
by the BlockSpecs' index maps from prefetched scalars, so nothing of the
slab is sliced, copied or laid out again by XLA around the call.

``n[b]`` is the number of rows slot ``b`` may see, and they are the
slab's FIRST ``n[b]``: ``pos[b] + 1`` for a slab that keeps every
position, ``min(pos[b] + 1, ring)`` for a ring (softmax does not mind
the order its keys come in), 0 for a slot the step is not live for.
The grid is (slot, key tile).  A tile whose first row is ``>= n[b]``
does nothing (``pl.when``) and its index map repeats the block the
step before it held -- the slot's last live tile, or for a slot with no
row at all the last tile of the live slot before it -- so that nothing
is copied for it either; what is left of such a grid step is its own
0.2 us.  The operations are the same whatever is live; the tiles they
skip follow the data, and so does the kernel's time (PERF.md, PR 39, has
the six seeds that say by how much).  :func:`rows_walked` is the count
of rows the walk did read, which the step that calls the kernel owes its
cache's ``read`` (``serve/caches.py``: "a step that reads fewer has to
say so").

Within a live tile, for all ``G`` key/value heads as one batched
product each way, the arithmetic of ``cohere2_moe.attention`` and
nothing narrower::

    s  = (q k^T) / sqrt(D)            bf16 operands, f32 scores [G, J, T]
    p  = exp(s - running max)         rows >= n[b] masked
    o += bf16(p) v                    f32 accumulator [G, J, D]

and the output ``[B, G, J, D]`` is the accumulator over the running sum
at the slot's last grid step; a slot with ``n[b] == 0`` gets zeros, not
0/0.

What set the sizes (my chip runs, PR 39; TPU v5 lite, the reasoning
cell's slab ``[1, 128, 8, 4096, 128]`` x 2 = 2.15 GB under 8 query heads
a group, ms a layer's call, each the best of three means of 40 calls one
behind the other; *cell*: 55 of 128 slots live at contexts of 64 to
3,200, 91,284 live rows; *whole*: every row of every slot live; XLA's
two products 2.915 whatever is live):

===========================================  =====  =====  ==========
form, keys a grid step                        cell  whole  GB/s whole
===========================================  =====  =====  ==========
**both products batched over the 8 heads,
512** (105,984 rows walked)                  0.789  2.863         750
the same, 1,024 (118,784 walked)             0.814  2.862         750
a head at a time, straight-line code, 512    0.872  2.883         745
the same, 1,024 / 256                        0.902  2.864 / 4.648
... its tile walked 256 keys at a time, 512  1.214  4.445         483
the copies alone (a live tile touched in
one row a head), 512 / 1,024                 0.742  2.862         750
a slot with no row copies its own tile 0
(a head at a time, 512)                      1.081  2.880
===========================================  =====  =====  ==========

So a live tile of 512 keys (2 MiB of K and V) costs 2.8 us, the memory's
own rate for a stream that is only read; the arithmetic -- 8 query rows
a product, a sixteenth of the matrix unit's -- hides under the copies to
0.05 ms, so the other orientation (queries held still, scores ``[tile,
J]``) had nothing to win and was not built; products of 256 keys do
bind.  With nothing live a call reads 0.21 ms at 512 and at 1,024 (1,024
and 512 skipped steps): that loop is bound by its dispatch.  In the
cell's own trace the call is 0.708 ms at 100,864 rows walked (197 live
tiles of 2.8 us and 827 skipped steps of 0.19 us) against the two
fusions' 3.114.  Repeating the block before it for a slot with no row
saves 0.21 ms (73 slots' tile 0).  A ``cost_estimate`` on the call (the
whole slab's bytes, or a quarter) compiles to the same schedule around
it: 11.506 and 11.503 ms a decode step against 11.503.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
#: what a grid step may hold in VMEM (:func:`_vmem_bytes`): the reason is
#: ``latent_attention.VMEM_BUDGET_BYTES``'s (what a kernel took beyond the
#: 16 MiB it gets unasked would be taken from the weights XLA moves into
#: VMEM ahead of the products around the call)
VMEM_BUDGET_BYTES = 14 << 20
#: keys a grid step may hold, in the order they are tried
_TILES = (512, 1024, 256, 128)


def _vmem_bytes(tile: int, g: int, j: int, d: int, itemsize: int) -> int:
    """VMEM a grid step needs at ``tile`` keys: K's and V's tile, the
    queries and the output, all twice (the pipeline's two buffers), the
    accumulator and the two statistics (a lane tile wide each), and the
    float32 scores two and a half times over (the scores, their
    exponentials, the bfloat16 copy)."""
    rows = -(-j // 16) * 16
    slab = 2 * 2 * g * tile * d * itemsize
    small = 2 * 2 * g * rows * d * itemsize + 4 * g * rows * (d + 2 * 128)
    return slab + small + 10 * g * rows * tile


def key_tile(s: int, g: int, j: int, d: int, dtype) -> int | None:
    """Keys a grid step holds, for a slab of ``s`` positions under ``g``
    key/value heads of ``d`` read by ``j`` query heads each: the first
    of :data:`_TILES` that divides ``s`` and fits
    :data:`VMEM_BUDGET_BYTES`; None where the shapes do not tile or the
    slab is not bfloat16 (the caller then has XLA's two products).  A
    head of 64 lies with its POSITIONS along the lanes (the dense
    model's slab): another kernel's."""
    dtype = jnp.dtype(dtype)
    if dtype != jnp.bfloat16 or d % 128 or j % 8:
        return None
    return next((t for t in _TILES if s % t == 0 and _vmem_bytes(
        t, g, j, d, dtype.itemsize) <= VMEM_BUDGET_BYTES), None)


def rows_walked(n, tile: int):
    """Rows of ONE layer's K (as many of V) the kernel reads for the
    visible counts ``n`` ``[B]``: every live tile whole."""
    return jnp.sum(-(-n // tile) * tile)


def _kernel(li_ref, n_ref, at_ref, lo_ref, hi_ref, q_ref, k_ref, v_ref,
            o_ref, m_s, l_s, acc_s, *, scale):
    """One (slot, key tile) grid step.  ``q_ref`` and ``o_ref`` ``[G, J,
    D]``, ``k_ref`` and ``v_ref`` ``[G, tile, D]``; the scratch carries
    the running maximum and sum ``[G, J, 1]`` and the accumulator ``[G,
    J, D]`` across a slot's tiles."""
    b, t, n_t = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    tile = k_ref.shape[1]
    n = n_ref[b]

    @pl.when(t == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(t * tile < n)
    def _():
        v = v_ref[...]
        s = jnp.einsum("gjd,gtd->gjt", q_ref[...], k_ref[...],
                       preferred_element_type=jnp.float32) * scale
        seen = t * tile + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, tile), 2) < n
        s = jnp.where(seen, s, _NEG_INF)                    # [G, J, tile]
        # the tile's first row is visible: the maximum is a real one from
        # the first live tile on, and a masked score's exp is 0
        m = m_s[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        keep = jnp.exp(m - m_new)
        l_s[...] = keep * l_s[...] + jnp.sum(p, axis=2, keepdims=True)
        acc_s[...] = keep * acc_s[...] + jnp.einsum(
            "gjt,gtd->gjd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(t == n_t - 1)
    def _():
        l = l_s[...]
        o_ref[...] = (acc_s[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


def _walk(n, tile: int):
    """The blocks a slot's grid steps hold, as (``at``, ``lo``, ``hi``)
    ``[B]``: at step ``t`` slot ``b`` holds tile ``clip(t, lo[b],
    hi[b])`` of slot ``at[b]``.  A slot with rows walks its own tiles up
    to its last live one and stays there; a slot with none stays on the
    block the live slot before it ended on (tile 0 of slot 0 where there
    is none), so that its steps copy nothing."""
    slots = jnp.arange(n.shape[0], dtype=jnp.int32)
    at = jnp.maximum(jax.lax.cummax(jnp.where(n > 0, slots, -1)), 0)
    hi = jnp.maximum(-(-n[at] // tile) - 1, 0)
    return at, jnp.where(n > 0, 0, hi), hi


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _call(li, n, q, k, v, tile, interpret):
    n_b, g, j, d = q.shape
    s = k.shape[3]

    def rows(b, t, li, n, at, lo, hi):
        return li[0], at[b], 0, jnp.clip(t, lo[b], hi[b]), 0

    heads = pl.BlockSpec((None, g, j, d), lambda b, t, *_: (b, 0, 0, 0))
    tiles = pl.BlockSpec((None, None, g, tile, d), rows)
    return pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_b, s // tile),
            in_specs=[heads, tiles, tiles],
            out_specs=heads,
            scratch_shapes=[
                pltpu.VMEM((g, j, 1), jnp.float32),     # running maximum
                pltpu.VMEM((g, j, 1), jnp.float32),     # running sum
                pltpu.VMEM((g, j, d), jnp.float32),     # weighted rows
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attn",
    )(li, n, *_walk(n, tile), q, k, v)


def decode_attn(q, k, v, li, n, *, tile, interpret=False):
    """``softmax(mask(q K^T / sqrt(D))) V`` of layer ``li`` for every
    slot: ``q`` ``[B, G, J, D]``, the slab's ``k`` and ``v`` ``[L, B, G,
    S, D]`` whole, ``n`` ``[B]`` the rows each slot may see (its first
    ``n[b]``; 0: none, and zeros come back) -> ``[B, G, J, D]``.
    ``tile`` is :func:`key_tile`'s for the slab, or another that tiles
    it."""
    g, j, d = q.shape[1:]
    s = k.shape[3]
    if key_tile(s, g, j, d, k.dtype) is None or s % tile or tile % 128:
        raise ValueError(f"a slab of {s} positions, {g} x {j} heads of {d} "
                         f"in {k.dtype} does not tile (key tile {tile})")
    return _call(jnp.asarray(li, jnp.int32).reshape(1), n.astype(jnp.int32),
                 q, k, v, int(tile), bool(interpret))
