"""Two kinds of content in one manager, for a model most of whose layers
keep a recurrent state (``models/solar_open2.py``): the engine's two
parts are each a pair,

* ``k`` = (``k_rows`` ``[Lg, B, G, max_seq, D]``, ``state``: ``Lk``
  arrays ``[1, B, H, K, V]`` float32),
* ``v`` = (``v_rows`` ``[Lg, B, G, max_seq, D]``, ``tails``: ``Lk``
  arrays ``[1, B, taps - 1, 3 H K]``),

the rows those of the ``Lg`` softmax layers (``caches.FullRows``; they
see no positions, so a row needs none to be read), ``state`` and
``tails`` what the ``Lk`` KDA layers keep of a request however long it
is (``caches.SlotStates``): a matrix a head (4.19 MB a slot and layer at
64 heads of 128 x 128) and the short convolution's last inputs.

:class:`HybridCaches` is what ``InferenceEngine`` asks of such a model
(``serve/caches.py``).  What is its own:

* it asks the rows' store for the attention kernel, so that on the TPU a
  decode step's attention is one kernel a softmax layer that walks only
  the key tiles a live context reaches (at the tiny sizes' heads of 8,
  and off the TPU, ``cohere2_moe.attention`` under a mask);
  ``kv_attn_kernel`` on ``kf:serve.decode_read`` says which, and
  ``kv_rows_read`` the rows the step itself counted as read;
* the states' update is ``delta_rule.kda_update`` in a decode step -- on
  the TPU one kernel a layer, ``ops/pallas/kda_step.py``, which reads a
  head's matrix once, takes both read-outs and the update from it and
  writes it back where it lay, and neither copies nor computes a slot
  the step is not ``live`` for; its grid steps follow a plan of a few
  scalars, ``delta_rule.kda_moves``, made once a step for the three
  layers' calls; off the TPU, and at the tiny sizes' heads of 8, XLA's
  ``delta_rule.kda_step``, three trips over every slot's state under a
  select -- and ``delta_rule.kda_chunked`` in a prefill;
  ``kda_step_kernel`` says which form ran, and ``state_slots_read`` the
  slots whose matrices the step itself counted as moved.

No operation's shape follows what is live; of the times, both kernels'
do -- the attention's follows the live contexts (the tiles it skips:
PERF.md, PR 39), the update's the live slots (PERF.md, PR 46) -- and the
tails' shift still moves every slot's (docs/serving.md).

Pages: a KDA layer keeps nothing at a page's end that a later request
could start from, so no page of this family is ever ``whole``
(``PageSpec.recurrent``): the engine looks up no prefix, commits
nothing, and never asks this cache for ``rows_of_slot`` or
``pages_to_slot``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from kungfu_tpu.models import solar_open2 as arch
from kungfu_tpu.ops import delta_rule
from kungfu_tpu.serve.caches import (ROUTING, STATE_DTYPE, Caches, FullRows,
                                     Pass, SlotStates, kv_rows, of_slot,
                                     routing_attrs, routing_says, visible)


class _Step(Pass):
    def __init__(self, caches, rows, states, live):
        super().__init__(caches, rows, states)
        cfg, self.live = caches.cfg, live
        # the rows ONE layer's attention reads, counted here whatever
        # the layers then do
        self.walked = rows.walked
        # the blocks the KDA kernel's grid steps hold, once for the three
        # layers' calls, and the slots whose matrices a call moves: the
        # live ones, or all of them where XLA's form runs
        with jax.named_scope("attn_core"), jax.named_scope("kda_state"):
            hd = cfg.kda_head_dim
            self.walk, self.moved = delta_rule.kda_moves(
                cfg.kda_heads, hd, hd, STATE_DTYPE, live)

    def recur(self, li, q, k, v, g, b):
        states, i, _ = self.of(li)
        return states.recur(
            i, "kda_state", lambda state: delta_rule.kda_update(
                state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], b[:, 0],
                self.live, self.walk))

    def says(self, counts):
        with jax.named_scope("moe_router"):
            return jnp.stack(routing_says(counts) + [
                jnp.sum(self.live), self.moved,
                self.caches.rows.layers * self.walked])


class _Prompt(Pass):
    def write(self, li, kn, vn):
        super().write(li, kn, vn)
        # (this family's prefill takes the slot's rows without their
        # layer axis and puts it back -- one operation more than the
        # store's own, which the compiler folds -- because its programs'
        # text is held to: ROADMAP D19)
        rows, i, _ = self.of(li)
        rows.keys = tuple(of_slot(s, i, rows.slot)[None]
                          for s in rows.parts()) + rows.keys[2:]

    def recur(self, li, q, k, v, g, b):
        states, i, _ = self.of(li)
        return states.recur(
            i, "kda_chunk", lambda state: delta_rule.kda_chunked(
                q[0], k[0], v[0], g[0], b[0], state, states.n))


class HybridCaches(Caches):
    says = ROUTING + ("state_slots_live", "state_slots_moved",
                      "kv_rows_walked")
    block = staticmethod(arch.block)

    def __init__(self, model: arch.SolarOpen2, max_batch: int, max_seq: int):
        super().__init__(model, max_batch, max_seq)
        cfg = self.cfg
        hd = cfg.kda_head_dim
        self.rows = FullRows(
            len(cfg.gqa_layers), self.batch, cfg.n_kv_heads, self.seq,
            cfg.head_dim, cfg.compute_dtype,
            group=cfg.n_heads // cfg.n_kv_heads)
        self.states = SlotStates(
            len(cfg.recurrent_layers), self.batch, (cfg.kda_heads, hd, hd),
            (cfg.conv_kernel - 1, 3 * cfg.kda_width), cfg.compute_dtype)
        self.stores = (self.rows, self.states)
        #: a layer's place in its kind's parts
        self.at = self.place(cfg.gqa_layers, cfg.recurrent_layers)
        #: the experts a decode step's routing is counted over
        self.held = cfg.n_layers * cfg.experts_held[1]

    def where(self, li):
        if li in self.cfg.recurrent_layers:
            return 1, self.at[li], None
        return 0, self.at[li], "attn_full"

    attn_tile = property(lambda self: self.rows.tile)
    kv_attn_kernel = property(lambda self: self.rows.kernel)

    @functools.cached_property
    def kda_step_kernel(self) -> int:
        """1 where a decode step's update of a layer's state is the
        fused kernel, 0 where it is XLA's ``kda_step``:
        ``delta_rule.kda_update``'s own choice, made from the platform
        and the state's shape when the step is traced."""
        cfg = self.cfg
        return int(delta_rule.kda_update_heads(
            cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim,
            STATE_DTYPE) is not None)

    # -- the two forward passes ------------------------------------------
    def step(self, k, v, pos, live):
        (kr, state), (vr, tails) = k, v
        rows = self.rows
        put = rows.writer(pos, live)
        return _Step(self, rows.step(kr, vr, rows.see(pos, visible(pos, live)),
                                     put),
                     self.states.step(state, tails, live), live)

    def prompt(self, k, v, p_len, n, start, slot):
        (kr, state), (vr, tails) = k, v
        return _Prompt(self, self.rows.prompt(kr, vr, start, slot),
                       self.states.prompt(state, tails, n, start, slot))

    def attrs(self, says, contexts):
        """Of the softmax layers' slab the K/V rows its ``contexts`` had
        to read beside the rows it did -- the step's own count: the
        tiles the kernel walked, or every row where XLA's form ran -- and
        of the KDA layers' states the slots it was for beside those
        whose matrices it moved: the live ones as the kernel counted
        them, or every slot where XLA's form ran; the tails are moved
        for every slot by either."""
        says.update(kv_rows(contexts, (self.rows,)))
        says["kv_rows_read"] = says.pop("kv_rows_walked")
        says["kv_attn_kernel"] = self.kv_attn_kernel
        says.update(self.states.attrs(says.pop("state_slots_moved")))
        says["kda_step_kernel"] = self.kda_step_kernel
        return routing_attrs(says, self.held)
