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

The grid is (slot, key tile) and is **the whole slab of every slot,
whatever is live**: the mask ``position <= pos[b]`` alone follows the
data, so the kernel's time follows nothing (PERF.md, PR 26: no operation
of a decode step may follow the data).  A later change that skips dead
tiles is one ``pl.when`` around :func:`_kernel`'s walk, on the tile's
first position.

A grid step holds ``key_tile`` rows (:func:`key_tile`, from the shapes,
to fit v5e's VMEM) and walks them :data:`CHUNK` at a time in straight-
line code, so that one chunk's products overlap the next one's softmax:

    s  = (q_lat c^T + q_rope k_r^T) * scale       bf16 operands, f32 sum
    p  = exp(s - running max)                     masked past pos[b]
    o += bf16(p) c                                f32 accumulator [H, r]

and the output ``[B, H, r]`` is the accumulator over the running sum at
the last tile.  What set the sizes (my chip runs, PR 35; TPU v5 lite,
``[B, H, S, r + rope]`` = ``[32, 128, 16384, 512 + 64]``, ms a layer's
call; the slab's bytes at 819 GB/s and its products at 197 TFLOP/s are
0.74 each, XLA's two products with the softmax between them 3.50): 0.93
at 4,096 keys a grid step walked 2,048 at a time, 0.99 walked 1,024 at a
time.  The same walk with the scores transposed (``[keys, H]``, the
queries held still in the matrix unit, statistics one vreg high, as in
``attention.py``) read 1.09 / 1.26 / 1.27 / 1.42 at chunks of 2,048 /
1,024 / 512 / 256: its second product wants every latent tile
transposed, and that does not hide.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


def _kernel(li_ref, pos_ref, ql_ref, qr_ref, c_ref, kr_ref, o_ref,
            m_s, l_s, acc_s, *, scale, chunk):
    """One (slot, key tile) grid step.  ``ql_ref`` ``[H, r]``, ``qr_ref``
    ``[H, rope]``, ``c_ref`` ``[tile, r]``, ``kr_ref`` ``[rope, tile]``;
    the scratch carries the running maximum and sum ``[H, 1]`` and the
    accumulator ``[H, r]`` across a slot's tiles."""
    b, j, n_j = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    tile = c_ref.shape[0]

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # (a change that skips the tiles past a slot's context wraps this
    # walk in ``pl.when(j * tile <= pos_ref[b])``; today every tile of
    # every slot is walked and the mask alone follows the data)
    pos = pos_ref[b]
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
        s = jnp.where(k_pos <= pos, s, _NEG_INF)              # [H, chunk]
        # position 0 is in the first chunk and every slot sees it: m is a
        # real maximum from then on and a masked score's exp is 0
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
        o_ref[...] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "tile", "chunk",
                                             "interpret"))
def _call(li, pos, q_lat, q_rope, c, k_r, scale, tile, chunk, interpret):
    """As :func:`latent_attn`, with ``k_r`` ``[L, B, 1, rope, S]``."""
    n_b, h, r = q_lat.shape
    rope, s = k_r.shape[-2:]
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_b, s // tile),
            in_specs=[
                pl.BlockSpec((None, h, r), lambda b, j, li, pos: (b, 0, 0)),
                pl.BlockSpec((None, h, rope),
                             lambda b, j, li, pos: (b, 0, 0)),
                pl.BlockSpec((None, None, None, tile, r),
                             lambda b, j, li, pos: (li[0], b, 0, j, 0)),
                pl.BlockSpec((None, None, None, rope, tile),
                             lambda b, j, li, pos: (li[0], b, 0, 0, j)),
            ],
            out_specs=pl.BlockSpec((None, h, r),
                                   lambda b, j, li, pos: (b, 0, 0)),
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
    )(li, pos, q_lat, q_rope, c, k_r)


def latent_attn(q_lat, q_rope, c, k_r, li, pos, scale, *, tile=None,
                chunk=None, interpret=False):
    """``softmax(mask((q_lat c^T + q_rope k_r^T) * scale)) c`` of layer
    ``li`` for every slot: ``q_lat`` ``[B, H, r]``, ``q_rope`` ``[B, H,
    rope]``, the slab's parts ``c`` ``[L, B, 1, S, r]`` and ``k_r`` ``[L,
    B, 1, S, rope]`` whole, ``pos`` ``[B]`` the last position each slot
    attends to -> ``[B, H, r]``.  ``tile`` defaults to
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
                 pos.astype(jnp.int32), q_lat, q_rope, c,
                 jnp.swapaxes(k_r, 3, 4), float(scale), int(tile),
                 int(chunk), bool(interpret))
