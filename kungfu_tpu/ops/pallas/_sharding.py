"""Varying-manual-axes (vma) helpers for shard_map typing.

Under ``shard_map`` every value carries the set of mesh axes it varies
over; pallas ``out_shape`` structs must declare it, and scan carries /
switch branches must type-match their varying counterparts.  One shared
implementation so the workaround changes in one place when jax's typing
evolves.
"""

from __future__ import annotations

import jax


def sds(shape, dtype, vma=frozenset()):
    """``jax.ShapeDtypeStruct`` declaring its varying manual axes."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))


def vma_of(*operands) -> frozenset:
    """Union of the operands' varying manual axes (empty outside
    ``shard_map``)."""
    vs = set()
    for o in operands:
        vs |= set(jax.typeof(o).vma)
    return frozenset(vs)


def match_vma(t, vma: frozenset):
    """Mark ``t`` varying over any axes in ``vma`` it doesn't carry yet
    (pcast rejects varying→varying, and an empty cast)."""
    missing = tuple(a for a in vma if a not in jax.typeof(t).vma)
    if not missing:
        return t
    return jax.lax.pcast(t, missing, to="varying")
