"""A decode step's attention over a latent (MLA) slab as ONE Pallas TPU
kernel a layer: the scores, the softmax and the weighted sum of the
absorbed order (``models/pangu_moe.py::absorbed_attention``), with the
scores never in HBM and each latent row read once for both products.

One query row a slot -- the absorbed ``q_lat`` ``[B, H, r]`` and the
rotated ``q_rope`` ``[B, H, rope]`` -- against the two parts of the slab
**as the engine holds them**, ``c`` ``[L, B, 1, S, r]`` and ``k_r`` ``[L,
B, 1, S, rope]``: the layer and the slot are picked by the BlockSpecs'
index maps, so nothing of the slab is sliced, copied or laid out again
by XLA around the call.  (The TPU keeps an array whose last dimension is
under a lane tile's 128 with the dimension before it along the lanes:
``k_r`` lies as ``[.., rope, S]``, which is the form the kernel takes it
in.)

What a slot may see is the slab's FIRST ``n[b]`` rows -- ``pos[b] + 1``
of a slot the step is for, 0 of any other -- prefetched as the layer's
index is.  The grid is (slot, key tile), as many steps a slot as the slab
has tiles, and a step whose tile starts at or past ``n[b]`` is neither
computed (``pl.when``) nor copied: its index maps name the block the
step before held -- the slot's last live tile, or for a slot with no row
the last live tile of the live slot before it (``decode_attention.py``'s
planner, one run from row 0) -- and Pallas copies a block only when it
changes.  Such a tile would have added ``exp(-1e30 - m) = 0`` to the
running sum and kept the accumulator as it was, so a live slot's output
is the whole walk's to the bit; a slot with no row gets zeros, not 0/0.
The operations are the same whatever is live; the tiles they skip follow
the data, and so does the kernel's time (PERF.md, PR 45).
:func:`rows_walked` is the count of rows the walk did read, which the
step that calls the kernel owes its cache's ``read`` (``serve/caches.py``:
"a step that reads fewer has to say so").

A grid step holds ``key_tile`` rows (:func:`key_tile`, from the shapes,
to fit v5e's VMEM) and walks them :data:`CHUNK` at a time in straight-
line code, so that one chunk's products overlap the next one's softmax:

    s  = (q_lat c^T + q_rope k_r^T) * scale       bf16 operands, f32 sum
    p  = exp(s - running max)                     rows >= n[b] masked
    o += bf16(p) c                                f32 accumulator [H, r]

and the output ``[B, H, r]`` is the accumulator over the running sum at
the slot's last grid step.  What set the sizes (my chip runs, PR 35; TPU
v5 lite, ``[B, H, S, r + rope]`` = ``[32, 128, 16384, 512 + 64]``, ms a
layer's call with every tile walked; the slab's bytes at 819 GB/s and its
products at 197 TFLOP/s are 0.74 each, XLA's two products with the
softmax between them 3.50): 0.93 at 4,096 keys a grid step walked 2,048
at a time, 0.99 walked 1,024 at a time.  The same walk with the scores
transposed (``[keys, H]``, the queries held still in the matrix unit,
statistics one vreg high, as in ``attention.py``) read 1.09 / 1.26 / 1.27
/ 1.42 at chunks of 2,048 / 1,024 / 512 / 256: its second product wants
every latent tile transposed, and that does not hide.

And what the skip costs and saves (my chip runs, PR 45; the same slab,
3.02 GB, five layers' calls a dispatch, ms a call as the kernel's own
events in a profiler trace, 15 a reading; *one*: one slot live at 10,000
rows; *cell*: two, at 10,000 and 6,300 -- the longctx cell's 16,300 live
rows a layer; *three* / *eight*: 26,500 rows in three slots, 58,000 in
eight; *whole*: every row of every slot; *none*: no slot live; the
parent's whole walk 0.920 whatever is live):

================================  =====  =====  =====  =====  =====  =====
keys a grid step (rows walked
in *cell*)                          one   cell  three  eight  whole   none
================================  =====  =====  =====  =====  =====  =====
**4,096, walked 2,048 at a time**
(20,480)                          0.071  0.091  0.125  0.213  0.927  0.044
2,048 (18,432)                    0.078  0.096  0.121  0.194  0.966  0.058
1,024 (17,408)                    0.111  0.126  0.148  0.221  1.095  0.089
4,096 walked 1,024 at a time      0.072  0.094  0.129  0.220  0.979  0.044
4,096, a chunk of a live tile
past ``n[b]`` not computed               0.089  0.117  0.204  0.969  0.044
4,096, a dead slot's query
blocks the live slot's before it         0.085  0.119  0.207  0.927  0.037
================================  =====  =====  =====  =====  =====  =====

So a live tile of 4,096 keys (4.7 MB of both parts) costs 6.9 us where
every tile is live -- the whole walk's own rate, 0.007 ms dearer a call
for the ``pl.when`` a step -- and 9 us where a slot has one to three of
them (its first copy hides under nothing); a skipped grid step 0.11 us;
and a call with nothing live 0.044 ms, of which 0.030 does not follow
the number of steps (128, 256 and 512 of them read 0.044, 0.058, 0.089).
Half the keys a step walk a tenth fewer rows at this cell's contexts and
lose it again in 128 more steps: 4,096 stays, and 2,048 would win by a
tenth only where eight slots or more are live.  Not computing the chunks
of a live tile that lie past ``n[b]`` (operations only: the tile is
copied whole) read 0.002-0.009 ms better and 0.04 worse with every row
live; taking a dead slot's query blocks from the live slot before it
0.006-0.007 better: under 0.05 ms of a 9.7 ms step over its five calls,
and neither was built.  In the cell's own trace the call is 0.062 ms
with one slot live at 13,600 rows (16,384 walked) against the whole
walk's 0.910.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the planner of the kernel that skips the same way over per-head K/V
# slabs, one run from row 0; ``rows_walked(n, tile)`` is what a step that
# calls :func:`latent_attn` owes its cache's ``read`` (serve/latent.py)
from kungfu_tpu.ops.pallas.decode_attention import (  # noqa: F401
    _tile_at, _walk, rows_walked)

_NEG_INF = -1e30
#: contract the last dimension of both operands: ``A B^T``
_NT = (((1,), (1,)), ((), ()))
#: keys one product covers: a tile is walked this many at a time
CHUNK = 2048
#: what a grid step may hold in VMEM (:func:`_vmem_bytes`).  The compiler
#: gives a kernel 16 MiB on a v5e and the kernel asks for no more: what it
#: took beyond would be taken from the weights XLA moves into VMEM ahead
#: of the products around the call.
VMEM_BUDGET_BYTES = 14 << 20


def _vmem_bytes(tile: int, h: int, r: int, rope: int, itemsize: int) -> int:
    """VMEM a grid step needs at ``tile`` keys: both parts of the slab's
    tile and the queries twice (the pipeline's two buffers), the output
    twice, the accumulator and the two statistics columns (a lane tile
    wide each), and a chunk's float32 scores two and a half times over
    (the scores, their exponentials, the bfloat16 copy)."""
    lanes = lambda n: -(-n // 128) * 128
    slab = 2 * tile * (r + rope) * itemsize
    queries = 2 * h * (r + lanes(rope)) * itemsize
    out = 2 * h * r * itemsize + 4 * h * r + 2 * 4 * h * 128
    return slab + queries + out + 10 * h * min(tile, CHUNK)


def key_tile(s: int, h: int, r: int, rope: int, dtype) -> int | None:
    """Keys a grid step holds, for a slab of ``s`` positions under ``h``
    heads: the largest of 4096 ... 128 that divides ``s`` and fits
    :data:`VMEM_BUDGET_BYTES`; None where the shapes do not tile (the
    caller then has XLA's two products)."""
    itemsize = jnp.dtype(dtype).itemsize
    if h % 8 or r % 128 or rope % 16:
        return None
    return next((t for t in (4096, 2048, 1024, 512, 256, 128)
                 if s % t == 0 and _vmem_bytes(t, h, r, rope, itemsize)
                 <= VMEM_BUDGET_BYTES), None)


def _kernel(li_ref, n_ref, at_ref, lo_ref, hi_ref, ql_ref, qr_ref, c_ref,
            kr_ref, o_ref, m_s, l_s, acc_s, *, scale, chunk):
    """One (slot, key tile) grid step.  ``ql_ref`` ``[H, r]``, ``qr_ref``
    ``[H, rope]``, ``c_ref`` ``[tile, r]``, ``kr_ref`` ``[rope, tile]``;
    the scratch carries the running maximum and sum ``[H, 1]`` and the
    accumulator ``[H, r]`` across a slot's tiles."""
    b, j, n_j = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    tile = c_ref.shape[0]
    n = n_ref[b]

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # a tile wholly past what the slot may see would add ``exp(-1e30 - m)
    # = 0`` to the sum and keep the accumulator as it is: it is not walked
    # (and was not copied: :func:`_call`'s index maps)
    @pl.when(j * tile < n)
    def _():
        ql, qr = ql_ref[...], qr_ref[...]
        m, l, acc = m_s[...], l_s[...], acc_s[...]
        for k0 in range(0, tile, chunk):
            c = c_ref[k0:k0 + chunk, :]
            s = (jax.lax.dot_general(ql, c, _NT,
                                     preferred_element_type=jnp.float32)
                 + jnp.dot(qr, kr_ref[:, k0:k0 + chunk],
                           preferred_element_type=jnp.float32)) * scale
            k_pos = j * tile + k0 + jax.lax.broadcasted_iota(
                jnp.int32, (1, chunk), 1)
            s = jnp.where(k_pos < n, s, _NEG_INF)             # [H, chunk]
            # row 0 is in the first chunk walked and its slot sees it: m
            # is a real maximum from then on and a masked score's exp is 0
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            keep = jnp.exp(m - m_new)
            l = keep * l + jnp.sum(p, axis=1, keepdims=True)
            acc = keep * acc + jnp.dot(p.astype(c.dtype), c,
                                       preferred_element_type=jnp.float32)
            m = m_new
        m_s[...], l_s[...], acc_s[...] = m, l, acc

    @pl.when(j == n_j - 1)
    def _():
        l = l_s[...]
        o_ref[...] = (acc_s[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "tile", "chunk",
                                             "interpret"))
def _call(li, n, q_lat, q_rope, c, k_r, scale, tile, chunk, interpret):
    """As :func:`latent_attn`, with ``k_r`` ``[L, B, 1, rope, S]``."""
    n_b, h, r = q_lat.shape
    rope, s = k_r.shape[-2:]
    # grid step ``j`` of slot ``b`` holds tile ``clip(j, lo[b], hi[b])``
    # of slot ``at[b]``: past a slot's last live tile the block the step
    # before held, which is then not copied again
    walk = _walk(n, tile)

    def slot(b, j, *_):
        return b, 0, 0

    def rows(b, j, li, n, at, lo, hi):
        return li[0], at[b], 0, _tile_at(b, j, lo, hi), 0

    def lanes(b, j, li, n, at, lo, hi):
        return li[0], at[b], 0, 0, _tile_at(b, j, lo, hi)

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(walk),
            grid=(n_b, s // tile),
            in_specs=[
                pl.BlockSpec((None, h, r), slot),
                pl.BlockSpec((None, h, rope), slot),
                pl.BlockSpec((None, None, None, tile, r), rows),
                pl.BlockSpec((None, None, None, rope, tile), lanes),
            ],
            out_specs=pl.BlockSpec((None, h, r), slot),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),    # running maximum
                pltpu.VMEM((h, 1), jnp.float32),    # running sum
                pltpu.VMEM((h, r), jnp.float32),    # weighted rows
            ]),
        out_shape=jax.ShapeDtypeStruct((n_b, h, r), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="latent_attn",
    )(li, n, *walk, q_lat, q_rope, c, k_r)


def latent_attn(q_lat, q_rope, c, k_r, li, n, scale, *, tile=None,
                chunk=None, interpret=False):
    """``softmax(mask((q_lat c^T + q_rope k_r^T) * scale)) c`` of layer
    ``li`` for every slot: ``q_lat`` ``[B, H, r]``, ``q_rope`` ``[B, H,
    rope]``, the slab's parts ``c`` ``[L, B, 1, S, r]`` and ``k_r`` ``[L,
    B, 1, S, rope]`` whole, ``n`` ``[B]`` the rows each slot may see, its
    first ``n[b]`` (``pos + 1`` of a live slot; 0: none, and zeros come
    back) -> ``[B, H, r]``.  ``tile`` defaults to
    :func:`key_tile`'s, which has to give one, ``chunk`` to
    :data:`CHUNK`.

    The kernel takes ``k_r`` positions last, as the TPU holds it: the
    ``swapaxes`` here compiles to a bitcast (``tests/test_tpu_compile.
    py``).  ``q_lat`` leaves the product batched over the heads that
    makes it heads first, and is copied (4 MB at the cell's size) into
    the slots-first order a slot's block is cut from: cut heads first, a
    block would leave out the dimension before the last, which the TPU
    tiles."""
    h, r = q_lat.shape[1:]
    s, rope = k_r.shape[-2:]
    if tile is None:
        tile = key_tile(s, h, r, rope, c.dtype)
    chunk = min(tile or 0, CHUNK if chunk is None else chunk)
    if not tile or s % tile or tile % chunk:
        raise ValueError(f"a slab of {s} positions, {h} heads of {r} + "
                         f"{rope} does not tile (key tile {tile})")
    return _call(jnp.asarray(li, jnp.int32).reshape(1),
                 n.astype(jnp.int32), q_lat, q_rope, c,
                 jnp.swapaxes(k_r, 3, 4), float(scale), int(tile),
                 int(chunk), bool(interpret))
