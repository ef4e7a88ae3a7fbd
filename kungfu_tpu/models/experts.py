"""A sparse expert layer as one chip of an expert-parallel deployment
holds it: sigmoid scores over every published expert, top-k with the
chosen scores normalised (and scaled, where the model has a
``routed_scaling_factor``), gated-SiLU experts, shared experts averaged,
no capacity and no dropped token.

The layer is told which experts it holds (``held = (first, count)``, a
range of the router's outputs).  It routes every token over ALL the
router's outputs, chooses and normalises over all ``top_k`` chosen, and
computes the part of the result its own experts give; what the experts
held elsewhere would add is left out (with every expert held that is
nothing).  On one chip there is no exchange, and nothing here stands in
for one.  ``parallel/moe.py`` is the other expert layer of the tree
(Switch top-1 with a capacity, for ``ShardedTrainer``); this one is the
serving path's.

The routed product comes in two forms that give the same numbers:

* :func:`experts_dense` -- every held expert over every token, the
  unchosen pairs weighted by zero: one batched product ``[E, T, d] x
  [E, d, f]`` whose shapes, and so whose time on the device, do not
  depend on the routing.  The decode step's: a few dozen tokens against
  50 M parameters an expert, bound by reading the weights, which a
  deployment's step reads every one of anyway.
* :func:`experts_sorted` -- the (token, pick) pairs sorted by expert and
  each product one :func:`jax.lax.ragged_dot` over the segments.  The
  prefill's: thousands of tokens, every expert touched, and the dense
  form would do ``E / top_k`` times the work.

Scopes (docs/tracing.md): ``moe_router`` (scores, choice, counters),
``moe_experts`` (the routed product and its weighted sum), ``moe_shared``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from kungfu_tpu.models import nn

HIGHEST = jax.lax.Precision.HIGHEST
#: tokens the sorted form takes at once (``top_k`` rows each: 8192 rows
#: of 4096 at the published widths, 64 MB in bfloat16)
SORTED_CHUNK = 1024


def init(key, d_model: int, d_expert: int, n_experts: int,
         held: Tuple[int, int], n_shared: int, *, std: float = 0.02,
         out_std: float = 0.02, dtype=jnp.bfloat16):
    """Router over ``n_experts`` (float32), the ``held`` experts and
    ``n_shared`` shared ones, each ``gate``/``up`` ``[n, d, f]`` and
    ``down`` ``[n, f, d]`` in ``dtype``."""
    kr, ke, ks = jax.random.split(key, 3)

    def stack(k, n):
        kg, ku, kd = jax.random.split(k, 3)
        return {
            "gate": nn.normal(kg, (n, d_model, d_expert), stddev=std
                              ).astype(dtype),
            "up": nn.normal(ku, (n, d_model, d_expert), stddev=std
                            ).astype(dtype),
            "down": nn.normal(kd, (n, d_expert, d_model), stddev=out_std
                              ).astype(dtype)}

    return {"router": {"w": nn.normal(kr, (d_model, n_experts), stddev=std)},
            "experts": stack(ke, held[1]), "shared": stack(ks, n_shared)}


def _picks(idx, held: Tuple[int, int]):
    """``[T, k, count]`` bool: pick ``k`` of token ``t`` is held expert
    ``e``."""
    first, count = held
    return (idx - first)[..., None] == jnp.arange(count)


@jax.named_scope("moe_router")
def route(p, x, top_k: int, held: Tuple[int, int], live=None,
          scale: float = 1.0):
    """x ``[T, d]`` -> the ``top_k`` experts of every token ``[T, k]``,
    their weights ``[T, k]`` float32, and the tokens each held expert
    received ``[count]`` int32 (rows not ``live`` are counted nowhere).

    ``s = sigmoid(x Wr)`` in float32 at full precision -- a choice is
    discrete, and a product rounded to bfloat16 flips near-ties -- then
    the k largest, each divided by the sum of the k and multiplied by
    ``scale``."""
    s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), p["w"],
                                  precision=HIGHEST))
    w, idx = jax.lax.top_k(s, top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    if scale != 1.0:
        w = w * scale
    picked = _picks(idx, held)
    if live is not None:
        picked = picked & live[:, None, None]
    return idx, w, jnp.sum(picked, axis=(0, 1), dtype=jnp.int32)


def _gated(x, gate, up, down, spec_in, spec_out):
    g = jnp.einsum(spec_in, x, gate)
    u = jnp.einsum(spec_in, x, up)
    return jnp.einsum(spec_out, jax.nn.silu(g) * u, down,
                      preferred_element_type=jnp.float32)


@jax.named_scope("moe_experts")
def experts_dense(p, x, idx, w, held: Tuple[int, int]):
    """``sum_k w_k E_k(x)`` over the picks whose expert is held, float32
    ``[T, d]``, as one batched product over every held expert and every
    token: nothing about its shapes depends on ``idx``."""
    # [T, E]: a token's weight for each held expert, zero where unchosen
    we = jnp.sum(jnp.where(_picks(idx, held), w[..., None], 0.0), axis=1)
    xe = jnp.broadcast_to(x, (held[1],) + x.shape)            # [E, T, d]
    y = _gated(xe, p["gate"], p["up"], p["down"],
               "etd,edf->etf", "etf,efd->etd")
    return jnp.einsum("etd,te->td", y, we)


@jax.named_scope("moe_experts")
def experts_sorted(p, x, idx, w, held: Tuple[int, int]):
    """The same sum as :func:`experts_dense`, by sorting the (token,
    pick) pairs by expert: an expert's tokens are one segment of the
    sorted rows, and each of the three products one grouped product over
    the segments."""
    first, count = held
    k = idx.shape[-1]
    here = (idx >= first) & (idx < first + count)
    # pairs with no expert here sort behind the last segment, where no
    # group reaches them: multiplied by nothing, read by nobody
    key = jnp.where(here, idx - first, count).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(count), axis=0,
                    dtype=jnp.int32)
    place = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    xs = x[order // k]                                        # [T*k, d]
    g = jax.lax.ragged_dot(xs, p["gate"], sizes)
    u = jax.lax.ragged_dot(xs, p["up"], sizes)
    ys = jax.lax.ragged_dot(jax.nn.silu(g) * u, p["down"], sizes,
                            preferred_element_type=jnp.float32)
    y = jnp.where(here[..., None], ys[place.reshape(idx.shape)], 0.0)
    return jnp.sum(y * w[..., None], axis=1)


@jax.named_scope("moe_shared")
def shared_mean(p, x):
    """The mean of the shared experts, float32 ``[T, d]``: one product
    over all of them (the sum over experts joins the sum over their
    width)."""
    n = p["gate"].shape[0]
    xs = jnp.broadcast_to(x, (n,) + x.shape)                  # [n, T, d]
    return _gated(xs, p["gate"], p["up"], p["down"],
                  "jtd,jdf->jtf", "jtf,jfd->td") / n


def apply(p, x, *, top_k: int, held: Tuple[int, int], dense: bool,
          live=None, scale: float = 1.0):
    """x ``[T, d]`` -> (``routed + shared`` float32 ``[T, d]``, tokens
    per held expert ``[count]``).  ``dense`` picks the routed product's
    form (above); ``live`` ``[T]`` bool says which rows count; ``scale``
    multiplies the routed weights (``route``), not the shared part.  The
    sorted form takes :data:`SORTED_CHUNK` tokens at a time, so that
    what it sorts and what the experts make of it stay small beside the
    weights."""
    def layer(x, live):
        idx, w, counts = route(p["router"], x, top_k, held, live, scale)
        product = experts_dense if dense else experts_sorted
        y = product(p["experts"], x, idx, w, held)
        return y + shared_mean(p["shared"], x), counts

    t = x.shape[0]
    if dense or t <= SORTED_CHUNK or t % SORTED_CHUNK:
        return layer(x, live)
    if live is None:
        live = jnp.ones((t,), bool)
    cut = lambda a: a.reshape((t // SORTED_CHUNK, SORTED_CHUNK) + a.shape[1:])
    y, counts = jax.lax.map(lambda c: layer(*c), (cut(x), cut(live)))
    return y.reshape(t, -1), jnp.sum(counts, axis=0)
