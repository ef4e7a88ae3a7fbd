"""Collective ops for use inside jit/shard_map code.

Each takes ``axis`` — one name or tuple of mesh axis names (use
``Communicator.axis`` for the global world).  These lower to single XLA HLO
collectives; no chunking/strategy machinery is needed on TPU (the compiler
tiles transfers over the ICI torus; cf. reference
``session/session.go:292-321`` which hand-chunks into 1 MiB pieces).
"""

from __future__ import annotations

from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.lax import axis_size

Axis = Union[str, Tuple[str, ...]]


def peer_rank(axis: Axis):
    """Global index along ``axis`` (reference `Rank` op, topology.cpp)."""
    if isinstance(axis, str):
        return jax.lax.axis_index(axis)
    idx = jnp.int32(0)
    for a in axis:
        idx = idx * axis_size(a) + jax.lax.axis_index(a)
    return idx


def peer_size(axis: Axis) -> int:
    if isinstance(axis, str):
        return axis_size(axis)
    n = 1
    for a in axis:
        n *= axis_size(a)
    return n


def all_reduce(x, axis: Axis, op: str = "sum"):
    """Allreduce one tensor or pytree across ``axis``."""
    if op == "sum":
        f = lambda a: jax.lax.psum(a, axis)
    elif op == "mean":
        f = lambda a: jax.lax.pmean(a, axis)
    elif op == "min":
        f = lambda a: jax.lax.pmin(a, axis)
    elif op == "max":
        f = lambda a: jax.lax.pmax(a, axis)
    else:
        raise ValueError(f"unsupported op {op!r}")
    return jax.tree_util.tree_map(f, x)


def group_all_reduce(tensors, axis: Axis, op: str = "sum"):
    """Allreduce a pytree of gradients in one logical group
    (reference ``group_all_reduce``, collective.py:67-69).  XLA fuses the
    resulting psums; no manual bucketing required."""
    return all_reduce(tensors, axis, op)


def all_gather(x, axis: Axis, tiled: bool = False):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.all_gather(a, axis, axis=0, tiled=tiled), x
    )


def broadcast(x, axis: Axis, root: int = 0):
    """Every peer gets peer ``root``'s value."""

    def leaf(a):
        # where() not mask-multiply: a NaN/Inf on a non-root peer must not
        # poison the psum (0*NaN == NaN) — broadcast exists precisely to
        # recover diverged replicas from root's good copy.
        contrib = jnp.where(peer_rank(axis) == root, a, jnp.zeros_like(a))
        return jax.lax.psum(contrib, axis)

    return jax.tree_util.tree_map(leaf, x)


def barrier_value(axis: Axis):
    """A data dependency that forces cross-peer synchronization."""
    return jax.lax.psum(jnp.int32(1), axis)
