"""Two kinds of cache in one manager, for a model most of whose layers
keep a recurrent state (``models/solar_open2.py``): the engine's two
parts are each a pair,

* ``k`` = (``k_rows`` ``[Lg, B, G, max_seq, D]``, ``state``: ``Lk``
  arrays ``[1, B, H, K, V]`` float32),
* ``v`` = (``v_rows`` ``[Lg, B, G, max_seq, D]``, ``tails``: ``Lk``
  arrays ``[1, B, taps - 1, 3 H K]``),

the rows those of the ``Lg`` softmax layers (every position of every
one, as ``serve/windowed.py`` keeps its full layers; they see no
positions, so a row needs none to be read), ``state`` and ``tails`` what
the ``Lk`` KDA layers keep of a request however long it is: a matrix a
head (4.19 MB a slot and layer at 64 heads of 128 x 128) and the short
convolution's last inputs.  Neither has an axis of positions: it is a
part that is *a state a slot* (``serve/caches.py``).  Each is an
array a layer and not one for all of them: a decode step reads a layer's
matrices, works out the correction and writes them back where they were,
and out of one array for all the layers the TPU compiler first copies the
layer it is about to update (537 MB a layer and step, seen in the
compiled step; an array of its own it updates where it lies).

:class:`HybridCaches` is what ``InferenceEngine`` asks of such a model.
Both bodies drive the model's ONE ``block``:

* the **prefill** writes a softmax layer's rows into the slab and
  attends over the slot's; a KDA layer's convolution and recurrence
  continue from the slot's own tails and state where ``start > 0`` and
  from nothing where it is 0 -- that is how a reused slot forgets the
  request before -- in the chunked form, the bucket's padding past ``n``
  masked so that what is written back is the state of exactly ``n``
  tokens and the tail at ``n - taps + 1 .. n - 1``;
* the **decode** step writes one row a slot (``caches.write_rows``),
  attends over the slab -- on the TPU one kernel a softmax layer,
  ``ops/pallas/decode_attention.py``, which is handed the slab whole and
  the rows each slot may see (``pos + 1`` where it is live, else 0) and
  walks only the key tiles below that count; off the TPU, and at the
  tiny sizes' heads of 8, ``cohere2_moe.attention`` over every row under
  a mask; ``kv_attn_kernel`` on ``kf:serve.decode_read`` says which, and
  ``kv_rows_read`` the rows the step itself counted as read -- and
  updates the live slots' states in place (``delta_rule.kda_update``:
  on the TPU one kernel a layer, ``ops/pallas/kda_step.py``, which
  reads a head's matrix once, takes both read-outs and the update from
  it and writes it back where it lay, and neither copies nor computes a
  slot the step is not ``live`` for -- its grid steps follow a plan of
  a few scalars, ``delta_rule.kda_moves``, made once a step for the
  three layers' calls; off the TPU, and at the tiny sizes' heads of 8,
  XLA's ``delta_rule.kda_step``, three trips over every slot's state
  under a select; ``kda_step_kernel`` on ``kf:serve.decode_read`` says
  which, and ``state_slots_read`` the slots whose matrices the step
  itself counted as moved); a slot the step is not ``live`` for keeps
  its state and its tail (the loop runs one step ahead, so such a slot
  may hold a request that has just ended, or nothing).  No operation's
  shape follows what is live; of the times, both kernels' do -- the
  attention's follows the live contexts (the tiles it skips: PERF.md,
  PR 39), the update's the live slots (PERF.md, PR 46) -- and the
  tails' shift, XLA's select, still moves every slot's
  (docs/serving.md).

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
import numpy as np

from kungfu_tpu.models import cohere2_moe, solar_open2 as arch
from kungfu_tpu.ops import delta_rule
from kungfu_tpu.serve.caches import (kv_rows, of_slot, row_windows, to_slot,
                                     write_rows)

F32 = jnp.float32
#: of the KDA layers' matrices (the published layer keeps them so; the
#: convolution tails are in the compute dtype).  Both bodies compute in
#: float32 and store in whatever the state they are handed is
STATE_DTYPE = jnp.dtype("float32")
#: what a decode step's ``out`` says behind the slots' tokens
_SAYS = ("experts_touched", "expert_load_max", "assigned",
         "state_slots_live", "state_slots_moved", "kv_rows_walked")


class HybridCaches:
    def __init__(self, model: arch.SolarOpen2, max_batch: int, max_seq: int):
        self.model = model
        cfg = self.cfg = model.cfg
        self.batch, self.seq = int(max_batch), int(max_seq)
        #: a layer's place in its kind's parts
        self.place = {li: i for group in (cfg.gqa_layers,
                                          cfg.recurrent_layers)
                      for i, li in enumerate(group)}
        #: the experts a decode step's routing is counted over
        self.held = cfg.n_layers * cfg.experts_held[1]
        self.prefill_flops = model.prefill_flops
        self.decode_flops = model.decode_flops
        #: what the KDA layers keep: a slot's matrices, which a decode
        #: step reads (and writes back) for the slots it moves, and every
        #: slot's convolution tails, which it moves whatever is live
        _, state, tails = self.shapes()
        self.slot_state_bytes = len(cfg.recurrent_layers) * int(
            np.prod(state[2:]) * STATE_DTYPE.itemsize)
        self.tail_bytes = len(cfg.recurrent_layers) * int(
            np.prod(tails) * cfg.compute_dtype.itemsize)

    # -- the parts ---------------------------------------------------------
    def shapes(self):
        """(rows of K or of V, ONE layer's state, ONE layer's tails)."""
        cfg = self.cfg
        hd = cfg.kda_head_dim
        return ((len(cfg.gqa_layers), self.batch, cfg.n_kv_heads, self.seq,
                 cfg.head_dim),
                (1, self.batch, cfg.kda_heads, hd, hd),
                (1, self.batch, cfg.conv_kernel - 1, 3 * cfg.kda_width))

    def new_slabs(self):
        cfg = self.cfg
        rows, state, tails = self.shapes()
        dt = cfg.compute_dtype
        a_layer = lambda shape, dtype: tuple(
            jnp.zeros(shape, dtype) for _ in cfg.recurrent_layers)
        return ((jnp.zeros(rows, dt), a_layer(state, STATE_DTYPE)),
                (jnp.zeros(rows, dt), a_layer(tails, dt)))

    # -- the two forward passes ------------------------------------------
    def decode(self, params, k, v, last_ids, pos, live):
        """One token for every slot (``last_ids``/``pos``/``live``
        ``[B]``; a slot that is not live computes what nobody reads,
        writes no row, keeps its state and tail, and is counted
        nowhere).  Returns the parts and ONE int32 vector: the ``B``
        tokens, then what the step says of itself (:data:`_SAYS`): its
        routing over the live slots and all layers, the slots it was
        for, the slots whose matrices it moved, and the K/V rows its
        attention read."""
        cfg, model = self.cfg, self.model
        (kr, state), (vr, tails) = k, v
        state, tails = list(state), list(tails)
        at = row_windows(pos, self.seq, live)
        tile = self.attn_tile
        if tile:
            from kungfu_tpu.ops.pallas import decode_attention as kernel

            # what a slot may see, as the kernel takes it: its first
            # ``pos + 1`` rows, and none where the step is not for it
            visible = jnp.where(live, pos + 1, 0)
            walked = kernel.rows_walked(visible, tile)
        else:       # XLA's form reads every row of every slot under a mask
            see = (jnp.arange(self.seq) <= pos[:, None])[:, None, None, None]
            walked = self.batch * self.seq
        # the blocks the KDA kernel's grid steps hold, once for the three
        # layers' calls, and the slots whose matrices a call moves: the
        # live ones, or all of them where XLA's form runs
        with jax.named_scope("attn_core"), jax.named_scope("kda_state"):
            hd = cfg.kda_head_dim
            kda_walk, moved = delta_rule.kda_moves(
                cfg.kda_heads, hd, hd, STATE_DTYPE, live)

        class Step:
            """A decode step's cache: one row a slot into the slab and
            attention over the slab itself; one token into every live
            slot's state."""

            def write(_, li, kn, vn):
                nonlocal kr, vr
                with jax.named_scope("kv_write"):
                    kr = write_rows(kr, self.place[li], kn, at)
                    vr = write_rows(vr, self.place[li], vn, at)

            @jax.named_scope("attn_core")
            def attend(_, li, q, positions):
                i = self.place[li]
                with jax.named_scope("attn_full"):
                    if tile:
                        return kernel.decode_attn(q[:, 0], kr, vr, i,
                                                  visible, tile=tile)[:, None]
                    return cohere2_moe.attention(q, kr[i], vr[i], see)

            def convolve(_, li, u, w):
                i = self.place[li]
                seen = jnp.concatenate([tails[i][0], u], axis=1)  # [B, taps, C]
                tails[i] = jnp.where(live[:, None, None], seen[:, 1:],
                                     tails[i][0])[None]
                return jnp.einsum("btc,tc->bc", seen.astype(F32),
                                  w.astype(F32))[:, None]

            @jax.named_scope("attn_core")
            def recur(_, li, q, k, v, g, b):
                i = self.place[li]
                with jax.named_scope("kda_state"):
                    state[i], o = delta_rule.kda_update(
                        state[i], q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                        b[:, 0], live, kda_walk)
                return o[:, None]

        h = model.embed(params, last_ids[:, None])
        counts = []
        for li in range(cfg.n_layers):
            h, count = arch.block(cfg, params[f"layer_{li}"], li, h,
                                  pos[:, None], Step(), dense=True,
                                  live=live[:, None])
            counts.append(count)
        tok = jnp.argmax(model.logits(params, h[:, 0]), axis=-1)
        with jax.named_scope("moe_router"):
            counts = jnp.stack(counts)
            says = jnp.stack([jnp.sum(counts > 0), jnp.max(counts),
                              jnp.sum(counts), jnp.sum(live), moved,
                              len(cfg.gqa_layers) * walked])
        return ((kr, tuple(state)), (vr, tuple(tails)),
                jnp.concatenate([tok, says]).astype(jnp.int32))

    def new_out(self):
        return jnp.zeros(self.batch + len(_SAYS), jnp.int32)

    @functools.cached_property
    def attn_tile(self):
        """The key tile with which a decode step's attention over the
        slab is ONE kernel a softmax layer that walks only the tiles a
        live context reaches (``ops/pallas/decode_attention.py``), or
        None where it is XLA's two products over every row
        (``cohere2_moe.attention``): off the TPU, and for shapes the
        kernel does not tile.  One choice, from the platform and the
        slab's shape, made once: the step that is traced and the span
        that says which form ran read the same.  The kernel's package is
        imported here and by no module's import, so a process that
        traces no such step never pays for it (PERF.md, PR 35)."""
        if jax.default_backend() != "tpu":
            return None
        from kungfu_tpu.ops.pallas import decode_attention

        cfg = self.cfg
        return decode_attention.key_tile(
            self.seq, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads,
            cfg.head_dim, cfg.compute_dtype)

    @property
    def kv_attn_kernel(self) -> int:
        """1 where a decode step's attention over the slab is the fused
        kernel, 0 where it is ``cohere2_moe.attention``
        (:attr:`attn_tile`)."""
        return int(self.attn_tile is not None)

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

    def read(self, out, contexts):
        """A decode step's ``out`` on the host: the slots' tokens, and
        what it says of itself as attrs of the span that waits for them
        (docs/tracing.md): of the softmax layers' slab the K/V rows its
        ``contexts`` had to read beside the rows it did, of the KDA
        layers' states the slots it was for beside those it moved."""
        out = np.asarray(jax.device_get(out))
        says = dict(zip(_SAYS, out[self.batch:].tolist()))
        says.update(kv_rows(contexts, self.shapes()[:1],
                            self.cfg.compute_dtype))
        # ... of which the rows READ are the step's own count: the tiles
        # the kernel walked, or every row where XLA's form ran
        says["kv_rows_read"] = says.pop("kv_rows_walked")
        says["kv_attn_kernel"] = self.kv_attn_kernel
        says["experts_held"] = self.held
        says["expert_load_mean"] = says.pop("assigned") / self.held
        # ... and of the states the slots whose matrices the step moved:
        # the live ones as the kernel counted them, or every slot where
        # XLA's form ran; the tails are moved for every slot by either
        moved = says["state_slots_read"] = says.pop("state_slots_moved")
        says["state_bytes_read"] = (moved * self.slot_state_bytes
                                    + self.tail_bytes)
        says["kda_step_kernel"] = self.kda_step_kernel
        return out[:self.batch], says

    def prefill(self, params, k, v, ids, n, start, slot):
        """``ids`` ``[P]`` (the prompt past ``start`` positions the slot
        already holds, zero-padded past ``n``) into ``slot``: a softmax
        layer's rows go to ``[start, start + P)`` of the slab (the
        padding lands where the decode writes before anyone reads); a
        KDA layer goes on from the slot's state and tail, or from
        nothing where ``start`` is 0, and leaves those of ``start + n``
        tokens.  Returns the greedy token after row ``n - 1``."""
        cfg, model = self.cfg, self.model
        (kr, state), (vr, tails) = k, v
        state, tails = list(state), list(tails)
        positions = start + jnp.arange(ids.shape[0])
        goes_on = start > 0

        class Prompt:
            """A prefill's cache: the new rows go into the slab and the
            slot's rows come out as keys; the state and the tail come
            out of the slot, or start empty, and go back."""

            def write(me, li, kn, vn):
                nonlocal kr, vr
                i = self.place[li]
                with jax.named_scope("kv_write"):
                    kr = jax.lax.dynamic_update_slice(
                        kr, kn[None], (i, slot, 0, start, 0),
                        allow_negative_indices=False)
                    vr = jax.lax.dynamic_update_slice(
                        vr, vn[None], (i, slot, 0, start, 0),
                        allow_negative_indices=False)
                me.keys = (of_slot(kr, i, slot)[None],
                           of_slot(vr, i, slot)[None])

            @jax.named_scope("attn_core")
            def attend(me, li, q, positions):
                with jax.named_scope("attn_full"):
                    return cohere2_moe.blocked_attention(
                        q, *me.keys, positions[0], 0, None)

            def convolve(_, li, u, w):
                i = self.place[li]
                tail = jnp.where(goes_on, of_slot(tails[i], 0, slot), 0)
                y, tail = delta_rule.causal_conv(u[0], w, tail, n)
                with jax.named_scope("kv_write"):
                    tails[i] = to_slot(tails[i], 0, slot, tail)
                return y[None]

            @jax.named_scope("attn_core")
            def recur(_, li, q, k, v, g, b):
                i = self.place[li]
                with jax.named_scope("kda_chunk"):
                    s0 = jnp.where(goes_on, of_slot(state[i], 0, slot
                                                    ).astype(F32), 0.0)
                    o, s_n = delta_rule.kda_chunked(
                        q[0], k[0], v[0], g[0], b[0], s0, n)
                with jax.named_scope("kv_write"):
                    state[i] = to_slot(state[i], 0, slot, s_n)
                return o[None]

        h = model.embed(params, ids[None])
        for li in range(cfg.n_layers):
            h, _ = arch.block(cfg, params[f"layer_{li}"], li, h,
                              positions[None], Prompt(), dense=False)
        row = jax.lax.dynamic_index_in_dim(h, n - 1, axis=1, keepdims=False)
        tok = jnp.argmax(model.logits(params, row)[0], axis=-1)
        return (kr, tuple(state)), (vr, tuple(tails)), tok.astype(jnp.int32)

    # -- the host's side of a page ---------------------------------------
    def empty_pages(self, rows: int):
        """What the restore program writes into a slot for ``rows``
        positions that hold nothing: zero rows for the softmax layers
        and, whatever ``rows`` is, an empty state and an empty tail."""
        cfg = self.cfg
        dt = cfg.compute_dtype
        (lg, _, g, _, d), state, tails = self.shapes()
        part = np.zeros((lg, g, rows, d), dt)
        # (host arrays, only read: one for all the layers will do)
        a_layer = lambda shape, dtype: (np.zeros(
            shape[:1] + shape[2:], dtype),) * len(cfg.recurrent_layers)
        return ((part, a_layer(state, STATE_DTYPE)),
                (part, a_layer(tails, dt)))
