"""A decode step's row write -- one new K row and one new V row a slot
into a layer of the slabs the engine holds -- as ONE Pallas TPU kernel
a layer, in place.

``serve/caches.py::write_rows`` is the same write in XLA: a
read-select-write of the aligned window that holds the row, which the
compiler fuses into one in-place loop **a slot and layer**.  At 128
slots and nine keeping layers that is 1,152 operations a decode step
(and some 1,300 scalar ones that find the windows) of the 3,900 the step
executes, 1.75 us each.  The kernel is the same read-select-write with
the slot as its grid: the window's tile is picked by the BlockSpecs'
index maps from the prefetched positions, K and V go through one call,
and both slabs are aliased to its results, so nothing of a slab is
sliced, copied or laid out again around it.  A slot the step is not
live for has its window written back as it was read, as ``write_rows``
does: the operations are the same whatever is live.

``k``, ``v`` ``[L, B, G, S, D]`` whole, ``D`` along the lanes and the
positions along the sublanes (``D % 128 == 0``: the layout the compiler
gives such a slab); the window is a tile's sublanes, :func:`window`
rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def window(dtype) -> int:
    """Rows of the window a write reads and writes back: the sublanes
    of one tile of ``dtype`` (8 of four bytes, 16 of two)."""
    return 32 // jnp.dtype(dtype).itemsize


def fits(s: int, d: int, dtype) -> bool:
    """Whether a slab of ``s`` positions of ``d`` values is one this
    kernel writes."""
    return d % 128 == 0 and s % window(dtype) == 0


def _kernel(li_ref, tile_ref, hit_ref, kn_ref, vn_ref, k_ref, v_ref,
            ko_ref, vo_ref):
    hit = hit_ref[pl.program_id(0)]
    # (the select in float32: a row's broadcast down the sublanes is a
    # 32-bit one; both conversions are exact)
    row = jax.lax.broadcasted_iota(jnp.int32, k_ref.shape, 1) == hit
    for new, old, out in ((kn_ref, k_ref, ko_ref), (vn_ref, v_ref, vo_ref)):
        out[...] = jnp.where(row, new[...].astype(jnp.float32),
                             old[...].astype(jnp.float32)).astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _write(li, tile, hit, kn, vn, k, v, interpret):
    n_b, g, _, d = kn.shape
    w = window(k.dtype)
    rows = pl.BlockSpec((None, g, 1, d), lambda b, *_: (b, 0, 0, 0))
    tiles = pl.BlockSpec((None, None, g, w, d),
                         lambda b, li, tile, hit: (li[0], b, 0, tile[b], 0))
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n_b,),
            in_specs=[rows, rows, tiles, tiles],
            out_specs=[tiles, tiles]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        # (operands count from the prefetched scalars)
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="row_write",
    )(li, tile, hit, kn, vn, k, v)


def write_rows(k, v, li, kn, vn, pos, live, *, interpret=False):
    """Row ``b`` of ``kn`` and of ``vn`` ``[B, G, 1, D]`` into layer
    ``li`` of ``k`` and ``v`` ``[L, B, G, S, D]`` at slot ``b``'s
    position ``pos[b]`` (``0 <= pos[b] < S``), nothing where ``live[b]``
    is false -> the two slabs, which are the operands' buffers."""
    s, d = k.shape[3:]
    if k.shape != v.shape or not fits(s, d, k.dtype):
        raise ValueError(f"slabs {k.shape} and {v.shape} in {k.dtype} are "
                         "not ones this kernel writes")
    w = window(k.dtype)
    pos = pos.astype(jnp.int32)
    return _write(jnp.asarray(li, jnp.int32).reshape(1), pos // w,
                  jnp.where(live, pos % w, -1), kn.astype(k.dtype),
                  vn.astype(v.dtype), k, v, bool(interpret))
