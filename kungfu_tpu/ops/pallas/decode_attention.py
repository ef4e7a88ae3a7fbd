"""A decode step's softmax attention over a slab of per-head K/V rows as
ONE Pallas TPU kernel a layer, which walks **only the key tiles a slot's
context reaches**: what ``models/cohere2_moe.py::attention`` (and, over
two runs of rows, ``models/evabyte.py::eva_attention``) computes for one
query row a slot, with the scores never in HBM and a row the slot cannot
see never read.

One query row a slot, ``q`` ``[B, G, J, D]`` (``J`` query heads read
key/value head ``g``; any ``J``: the rows are padded to eights of zeros
around the call, so ``J`` = 1, a model that groups no heads, is served),
against the slab **as the engine holds it**, ``K`` and ``V`` ``[L, B, G,
S, D]``: the layer and the slot are picked by the BlockSpecs' index maps
from prefetched scalars, so nothing of the slab is sliced, copied or
laid out again by XLA around the call.

What a slot may see is a static number ``R`` of **runs** of the slab's
rows, each a static first row on a tile's edge (``starts``) and a count
a slot, ``n[r, b]``: run ``r`` of slot ``b`` is the rows ``[starts[r],
starts[r] + n[r, b])``.  ``R`` = 1 from row 0 is a slab's FIRST ``n[b]``
rows: ``pos[b] + 1`` for a slab that keeps every position, ``min(pos[b]
+ 1, ring)`` for a ring (softmax does not mind the order its keys come
in).  ``R`` = 2 is ``serve/pooled.py``'s slab: the open window's exact
rows from row 0 and the closed windows' chunk rows from row ``W``, under
ONE softmax -- the running maximum and sum carry from a run's last tile
into the next run's first, which is EVA attention's definition and no
approximation of it.  All counts 0 is a slot the step is not live for.
The grid is (slot, step), as many steps as the slab has tiles.  A slot's
steps hold its first run's live tiles, then the next run's, and once
they are through (``pl.when``) the index map repeats the block the step
before held -- the slot's last live tile, or for a slot with no row at
all the last tile of the live slot before it -- so that nothing is
copied for it either; what is left of such a grid step is its own 0.2
us.  The operations are the same whatever is live; the tiles they skip
follow the data, and so does the kernel's time (PERF.md, PRs 39 and 41,
say by how much).  :func:`rows_walked` is the count of rows the walk did
read, which the step that calls the kernel owes its cache's ``read``
(``serve/caches.py``: "a step that reads fewer has to say so").

The tile is planned by BYTES (:func:`key_tile`, :func:`_vmem_bytes`): 8
key/value heads take 512 keys a grid step, 32 take 256 (4 MiB of K and
V; 512 would be 8 MiB, 19.75 MiB with both buffers and the scores).

Within a live tile, for all ``G`` key/value heads as one batched
product each way, the arithmetic of ``cohere2_moe.attention`` and of
``eva_attention`` and nothing narrower::

    s  = (q k^T) / sqrt(D)            bf16 operands, f32 scores [G, J, T]
    p  = exp(s - running max)         rows >= the run's count masked
    o += bf16(p) v                    f32 accumulator [G, J, D]

and the output ``[B, G, J, D]`` is the accumulator over the running sum
at the slot's last grid step; a slot with no row gets zeros, not 0/0.

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

And for 32 key/value heads read by ONE query head each over two runs (my
chip runs, PR 41; TPU v5 lite, the docbytes cell's slab ``[8, 16, 32,
4096, 128]`` x 2 = 8.59 GB, runs from rows 0 and 2,048, ms a layer's
call, each the best of three means of 160 calls, eight layers' calls a
dispatch; *cell*: 7 of 16 slots live at positions 9,300 to 21,000,
12,447 live rows; *whole*: every row of every slot; *none*: no slot
live; XLA's ``eva_attention`` 1.489 whatever is live):

=========================================  =====  =====  =====  ==========
form, heads x keys a grid step              cell  whole   none  GB/s whole
=========================================  =====  =====  =====  ==========
**all 32 heads x 256, the one query row
padded to 8 around the call** (13,568
rows walked, 256 grid steps)               0.342  1.431  0.100         750
the same, queries padded beforehand        0.352  1.432  0.106         750
32 x 128 (512 grid steps)                  0.371  1.432  0.102         750
a head-group axis, 8 x 512 (15,872 rows
walked, 512 grid steps)                    0.475  1.430  0.135         751
the same, 16 x 512 / 16 x 256              0.420  1.431  0.099 / 0.128
                                         / 0.411
=========================================  =====  =====  =====  ==========

So a live tile of 256 keys of 32 heads (4 MiB of K and V: 32 segments of
64 KiB each way) streams at PR 39's rate, 5.6 us, and 32 products of 8
query rows hide under its copies as 8 did; the head-group grid that
would have kept PR 39's block walks a sixth more rows (tiles of 512) and
twice the steps for nothing, and was not built.  The pad and the slice
around the call cost nothing that a timing shows.  With nothing live a
call is 0.10 ms whether its grid has 256 steps or 512 (0.12 with two
slots live), eight calls 0.8 ms of a step: that floor is NOT the query
and output blocks copied a slot -- the query row padded to a whole
packed tile of 16, the query block taken from the live slot before, and
queries and outputs resident in VMEM for the whole call all read
0.101-0.109 ms with nothing live and 0.344-0.349 in the cell's set (a
second sweep, same run) -- and what it is has not been found.
"""

from __future__ import annotations

import functools
import itertools
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
#: query rows a head the products are made with: ``j`` is padded up to a
#: whole number of these (the matrix unit's operand wants a sublane tile)
_Q_ROWS = 8


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


def key_tile(s: int, g: int, j: int, d: int, dtype, starts=(0,)) -> int | None:
    """Keys a grid step holds, for a slab of ``s`` positions under ``g``
    key/value heads of ``d`` read by ``j`` query heads each, whose
    visible rows are runs that start at the rows ``starts``: the first
    of :data:`_TILES` that divides ``s`` and every start and whose BYTES
    fit :data:`VMEM_BUDGET_BYTES` (8 heads take 512 keys, 32 heads 256);
    None where the shapes do not tile or the slab is not bfloat16 (the
    caller then has XLA's two products).  A head of 64 lies with its
    POSITIONS along the lanes (the dense model's slab): another
    kernel's."""
    dtype = jnp.dtype(dtype)
    if dtype != jnp.bfloat16 or d % 128:
        return None
    return next((t for t in _TILES
                 if not any(x % t for x in (s,) + tuple(starts))
                 and _vmem_bytes(t, g, j, d, dtype.itemsize)
                 <= VMEM_BUDGET_BYTES), None)


def rows_walked(n, tile: int):
    """Rows of ONE layer's K (as many of V) the kernel reads for the
    visible counts ``n`` (``[B]``, or ``[R, B]`` for all the runs
    together): every live tile whole."""
    return jnp.sum(-(-n // tile) * tile)


def _kernel(li_ref, n_ref, at_ref, lo_ref, hi_ref, *refs, scale, runs):
    """One (slot, grid step) of the walk.  ``refs`` is, behind the
    later runs' first steps and jumps where there is more than one run
    (:func:`_walk`), ``q_ref`` and ``o_ref`` ``[G, J, D]``, ``k_ref``
    and ``v_ref`` ``[G, tile, D]``, and the scratch that carries the
    running maximum and sum ``[G, J, 1]`` and the accumulator ``[G, J,
    D]`` across a slot's tiles, of whichever run."""
    *later, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s = refs
    b, t, n_t = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    n_b = pl.num_programs(0)
    tile = k_ref.shape[1]
    # the first row this step's tile holds OF ITS RUN, and that run's
    # count: a step at or past a later run's first is that run's
    first, n = t * tile, n_ref[b]
    for r in range(1, runs):
        off = later[0][(r - 1) * n_b + b]
        first = jnp.where(t >= off, (t - off) * tile, first)
        n = jnp.where(t >= off, n_ref[r * n_b + b], n)

    @pl.when(t == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(first < n)
    def _():
        v = v_ref[...]
        s = jnp.einsum("gjd,gtd->gjt", q_ref[...], k_ref[...],
                       preferred_element_type=jnp.float32) * scale
        seen = first + jax.lax.broadcasted_iota(
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


def _walk(n, tile: int, starts=(0,)):
    """The blocks a slot's grid steps hold, for the visible counts ``n``
    (``[R, B]``, or ``[B]`` for one run) of runs that start at the rows
    ``starts``: (``at``, ``lo``, ``hi``) ``[B]`` and, where there are
    later runs, their (``off``, ``jump``) ``[(R - 1) B]``.  A slot's
    live tiles are numbered through its runs in order, run ``r``'s from
    ``off[r]`` on (the live tiles of the runs before it); at step ``t``
    slot ``b`` holds live tile ``u = clip(t, lo[b], hi[b])`` of slot
    ``at[b]``, which is the slab's tile ``u`` plus the ``jump`` of every
    later run with ``off <= u`` (from behind a run's last live tile to
    the next run's first row).  A slot with rows walks its own live
    tiles up to the last one and stays there; a slot with none stays on
    the block the live slot before it ended on (tile 0 of slot 0 where
    there is none), so that its steps copy nothing."""
    n = n.reshape(len(starts), -1)
    slots = jnp.arange(n.shape[1], dtype=jnp.int32)
    live = functools.reduce(jnp.logical_or, [x > 0 for x in n])
    at = jnp.maximum(jax.lax.cummax(jnp.where(live, slots, -1)), 0)
    # run ``r``'s first live tile, and the last run's last
    off = list(itertools.accumulate(-(-x[at] // tile) for x in n))
    hi = jnp.maximum(off[-1] - 1, 0)
    walk = at, jnp.where(live, 0, hi), hi
    if len(starts) == 1:
        return walk
    off = jnp.stack(off[:-1])
    # tile ``u`` of run ``r`` is the slab's ``u + shift[r]``; no jump
    # where slot 0 and those behind it have no row (tile 0)
    shift = jnp.asarray(starts[1:], jnp.int32)[:, None] // tile - off
    jump = jnp.where(live[at], jnp.diff(
        shift, axis=0, prepend=jnp.zeros_like(shift[:1])), 0)
    return walk + (off.reshape(-1), jump.reshape(-1))


def _tile_at(b, t, lo, hi, off=None, jump=None):
    """The slab's tile that grid step ``t`` of slot ``b`` holds (of slot
    ``at[b]``), from :func:`_walk`'s vectors."""
    u = tile = jnp.clip(t, lo[b], hi[b])
    for r in range(0 if off is None else off.shape[0] // lo.shape[0]):
        i = r * lo.shape[0] + b
        tile = tile + jnp.where(u >= off[i], jump[i], 0)
    return tile


@functools.partial(jax.jit, static_argnames=("tile", "starts", "scale",
                                             "interpret"))
def _call(li, n, q, k, v, tile, starts, scale, interpret):
    n_b, g, j, d = q.shape
    s = k.shape[3]
    walk = _walk(n, tile, starts)

    def rows(b, t, li, n, at, *steps):
        return li[0], at[b], 0, _tile_at(b, t, *steps), 0

    heads = pl.BlockSpec((None, g, j, d), lambda b, t, *_: (b, 0, 0, 0))
    tiles = pl.BlockSpec((None, None, g, tile, d), rows)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, runs=len(starts)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(walk),
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
    )(li, n.reshape(-1), *walk, q, k, v)


def decode_attn(q, k, v, li, n, *, tile, starts=(0,), scale=None,
                interpret=False):
    """``softmax(mask(q K^T / sqrt(D))) V`` of layer ``li`` for every
    slot, ONE softmax over all the rows it may see: ``q`` ``[B, G, J,
    D]``, the slab's ``k`` and ``v`` ``[L, B, G, S, D]`` whole, ``n``
    the rows each slot may see -> ``[B, G, J, D]``.  The visible rows
    are ``len(starts)`` runs: run ``r`` is the slab's rows ``[starts[r],
    starts[r] + n[r, b])``, which end before the next run starts; ``n``
    is ``[R, B]``, or ``[B]`` for the one run from row 0 (all 0: none,
    and zeros come back).  ``tile`` is :func:`key_tile`'s for the slab
    and the starts, or another that tiles both.  ``scale`` multiplies the
    scores where it is not ``1 / sqrt(D)`` (``serve/sambay.py``: rows of
    128 that hold two heads of 64, each query zero in the other's
    half)."""
    g, j, d = q.shape[1:]
    s = k.shape[3]
    starts = tuple(int(x) for x in starts)
    if starts[0] or list(starts) != sorted(set(starts)):
        raise ValueError(f"runs start at row 0 and in order, not {starts}")
    if key_tile(s, g, j, d, k.dtype, starts) is None or tile % 128 or any(
            x % tile for x in (s,) + starts):
        raise ValueError(f"a slab of {s} positions in runs from {starts}, "
                         f"{g} x {j} heads of {d} in {k.dtype} does not "
                         f"tile (key tile {tile})")
    # (a head's query rows padded to whole sublane tiles of zeros, whose
    # outputs nobody reads: one query head a key/value head is one row)
    pad = -j % _Q_ROWS
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    out = _call(jnp.asarray(li, jnp.int32).reshape(1),
                n.astype(jnp.int32), q, k, v, int(tile), starts,
                1.0 / math.sqrt(d) if scale is None else float(scale),
                bool(interpret))
    return out[:, :, :j] if pad else out
