"""Synchronous SGD — allreduce gradients, then inner update."""

from __future__ import annotations

import jax
import optax

from kungfu_tpu import ops


def synchronous_sgd(
    inner: optax.GradientTransformation,
    axis,
    average: bool = True,
    schedule: str = "psum",
    fuse_grads: bool = False,
) -> optax.GradientTransformation:
    """The S-SGD wrapper (reference ``sync_sgd.py:58-109``: group allreduce
    then grad/np).  ``inner`` is any optax optimizer; ``axis`` the mesh
    axis name(s).  With ``average=False`` gradients are summed (the caller
    scales the LR instead).

    ``schedule`` selects the allreduce decomposition that gets COMPILED
    into the training step (``kungfu_tpu.ops.schedules``; pass
    ``comm.strategy`` to honor a ``set_strategy``/``autotune_strategy``
    choice).  A strategy swap therefore means rebuilding the optimizer
    and re-jitting — on TPU the strategy lives in the program, not in a
    per-message router.

    ``fuse_grads=True`` buckets the whole gradient pytree into ONE flat
    buffer before the collective (reference fuse/defuse,
    ``python/kungfu/ops/__init__.py:29-46``): one psum of N bytes instead
    of one per leaf.  XLA often fuses per-leaf psums on TPU anyway; the
    explicit bucket pins it — and on meshes where each collective carries
    fixed dispatch overhead (many-leaf models, virtual/CPU meshes, ring
    or two-stage schedules whose per-leaf program is long) it is a
    measured win.  Costs one fuse/defuse reshape pass in-program."""

    def init(params):
        return inner.init(params)

    def update(grads, state, params=None):
        op = "mean" if average else "sum"
        with jax.named_scope("grad_sync"):
            if fuse_grads:
                from kungfu_tpu.ops.fuse import defuse, fuse

                buf, spec = fuse(grads)
                buf = ops.all_reduce_scheduled(buf, axis, op=op,
                                               schedule=schedule)
                grads = defuse(buf, spec)
            else:
                # schedule="psum" dispatches to the same all_reduce that
                # group_all_reduce wraps — one call site for every schedule
                grads = ops.all_reduce_scheduled(grads, axis, op=op,
                                                 schedule=schedule)
        with jax.named_scope("optimizer"):
            return inner.update(grads, state, params)

    return optax.GradientTransformation(init, update)
