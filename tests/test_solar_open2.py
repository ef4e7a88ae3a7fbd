"""``solar_open2`` (Solar Open 2) at a tiny size on the CPU, against the
benchmark's plain reference (``kfbench/reference/solar_open2.py``: the
recurrence token by token), on logits and not tokens: the plain forward
pass, the chunked recurrence against the serial one and the one-token
form, a bucket's padding, a prefill in two pieces, the engine's prefill
and decode through ``HybridCaches``, slots reused and slots left out of
a step, pages that are never whole, and the chip's share of the expert
layer.

The weights are the adapter's (bfloat16 leaves from a seed), computed in
float32 at ``highest`` on both sides, so the two agree to rounding.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tests import _lookahead  # noqa: E402

from kfbench.lib import files  # noqa: E402
from kungfu_tpu.models import experts, solar_open2 as arch  # noqa: E402
from kungfu_tpu.ops import delta_rule  # noqa: E402
from kungfu_tpu.serve.engine import InferenceEngine  # noqa: E402
from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec  # noqa: E402

MAX_SEQ, PAGE = 32, 4
#: logits reach 10; the forward pass and the engine read 2e-5 to 6e-5
#: over four seeds of the weights (float32 at ``highest`` on both sides),
#: the float8 reference 0.4 to 0.9
TOL = 3e-4


def tiny_cfg(first=0, held=16):
    """The configuration file's keys at the tiny size: hidden 64, one
    period of four layers (layer 0 softmax with 4 query heads over 2
    key/value heads of 8, layers 1-3 KDA with 4 heads of 8 x 8, gates of
    rank 8), 16 experts of width 32 (top-4, one shared)."""
    return dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=4,
        gqa_layers=[0, 4, 8], num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, linear_attn_config=dict(
            short_conv_kernel_size=4, head_dim=8, num_heads=4,
            num_kv_heads=None),
        kda_gate_rank=8, moe_intermediate_size=32, router_width=16,
        experts_held_first=first, n_routed_experts=held,
        num_experts_per_tok=4, n_shared_experts=1, routed_scaling_factor=1,
        rms_norm_eps=1e-5, initializer_range=0.5,
        num_hidden_layers_published=4, n_positions=MAX_SEQ)


@pytest.fixture(scope="module")
def ref():
    return files.load_reference("solar_open2")


@pytest.fixture(scope="module")
def adapter():
    return files.load_adapter("solar_open2")


def build(adapter, cfg, seed=0):
    """(the program's model in float32, the adapter's weights)."""
    model = adapter.program_model(cfg)
    params = jax.jit(lambda k: adapter.init_params(cfg, k))(
        jax.random.PRNGKey(seed))
    return arch.SolarOpen2(dataclasses.replace(model.cfg, dtype="float32")), \
        params


def engine(model, params, slots=3, capacity=2, eos_id=None):
    """(A pool of two pages: the engine reserves none for this family.)"""
    return InferenceEngine(
        model, params, max_batch=slots, max_seq=MAX_SEQ, eos_id=eos_id,
        pool=KVCachePool(PageSpec.for_model(model.cfg, page_tokens=PAGE),
                         capacity_pages=capacity))


def recording(model):
    """``model`` with every logits row the jitted programs compute kept,
    in the order computed."""
    rows, plain = [], model.logits

    def logits(params, h):
        out = plain(params, h)
        jax.debug.callback(lambda x: rows.append(np.asarray(x)), out)
        return out

    model.logits = logits
    return rows


def ids_of(seed, n, vocab=96):
    return np.random.default_rng(seed).integers(0, vocab, n).tolist()


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- the recurrence, alone ---------------------------------------------------
#: the chunked form against the serial one, on outputs and states of size
#: 1 to 4: it reads 2e-6 to 3e-5 over these cases (the serial form itself
#: lies 2e-7 from float64's)
RULE_TOL = 1e-4


def tokens_of(seed, t, h=3, k=8, v=8, near_two=False):
    """Random inputs of the recurrence: unit keys, queries over sqrt(K),
    decays from next to none (``g`` -0.01) to all but total (-12 a
    token), steps over (0, 2) or, with ``near_two``, within a twentieth
    of 2."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (t, h, k))) / np.sqrt(k)
    kk = unit(jax.random.normal(ks[1], (t, h, k)))
    vv = jax.random.normal(ks[2], (t, h, v))
    g = -jnp.minimum(jnp.exp(1.5 * jax.random.normal(ks[3], (t, h, k)) - 1),
                     12.0)
    b = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (t, h))
                           + (8.0 if near_two else 0.0))
    return q, kk, vv, g, b, jax.random.normal(ks[5], (h, k, v))


def serial(q, k, v, g, b, S0):
    """The rule as written, a token at a time."""
    def token(S, x):
        q, k, v, g, b = x
        S = jnp.exp(g)[..., None] * S
        S = S + b[:, None, None] * k[..., None] * (
            v - jnp.einsum("hkv,hk->hv", S, k))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q)

    S, o = jax.lax.scan(token, S0, (q, k, v, g, b))
    return o, S


@pytest.mark.parametrize("near_two", [False, True], ids=["b_any", "b_near_2"])
@pytest.mark.parametrize("t,chunk", [(37, 8), (64, 16), (5, 64), (130, 64)])
def test_chunked_equals_the_serial_scan(t, chunk, near_two):
    """At lengths that are and are not a multiple of the chunk, decays
    whose product over a chunk underflows float32 (a quotient of two such
    products would be 0 / 0), and steps at the edge of the rule's
    stability.  The tolerance is float32's at the size of a chunk's
    summed logarithms (``kda_chunked``), on outputs of size 1."""
    *x, S0 = tokens_of(t, t, near_two=near_two)
    if near_two:
        assert float(x[4].min()) > 1.9
    if min(t, chunk) >= 64:
        assert float(jnp.sum(x[3][:64], axis=0).min()) < -88  # e^-88
    want_o, want_S = serial(*x, S0)
    o, S = delta_rule.kda_chunked(*x, S0, chunk=chunk)
    np.testing.assert_allclose(o, want_o, atol=RULE_TOL, rtol=0)
    np.testing.assert_allclose(S, want_S, atol=RULE_TOL, rtol=0)


def test_one_token_steps_equal_the_serial_scan():
    """``kda_step`` repeated, two slots of which one is live: the live
    one follows the scan, the other keeps its state to the bit."""
    *x, S0 = tokens_of(3, 21)
    want_o, want_S = serial(*x, S0)
    S = jnp.stack([S0, S0])
    live = jnp.asarray([True, False])
    for t in range(21):
        S, o = delta_rule.kda_step(S, *(jnp.stack([a[t], a[t]]) for a in x),
                                   live)
        np.testing.assert_allclose(o[0], want_o[t], atol=1e-5, rtol=0)
    np.testing.assert_allclose(S[0], want_S, atol=1e-5, rtol=0)
    assert bool(jnp.all(S[1] == S0))


@pytest.mark.parametrize("n", [1, 7, 16, 23])
def test_a_padded_bucket_leaves_the_state_and_tail_of_n_tokens(n):
    """Positions ``>= n`` (garbage there on purpose) move neither the
    state nor the outputs before them, and the convolution's tail is the
    inputs at ``n - 3 .. n - 1``, the old tail's where ``n < 3``."""
    *x, S0 = tokens_of(5, 24)
    want_o, want_S = serial(*(a[:n] for a in x), S0)
    o, S = delta_rule.kda_chunked(*x, S0, n=jnp.int32(n), chunk=8)
    np.testing.assert_allclose(o[:n], want_o, atol=RULE_TOL, rtol=0)
    np.testing.assert_allclose(S, want_S, atol=RULE_TOL, rtol=0)
    u = jax.random.normal(jax.random.PRNGKey(1), (24, 6))
    w = jax.random.normal(jax.random.PRNGKey(2), (4, 6))
    old = jax.random.normal(jax.random.PRNGKey(3), (3, 6))
    y, tail = delta_rule.causal_conv(u, w, old, jnp.int32(n))
    seen = np.concatenate([old, u])
    np.testing.assert_allclose(tail, seen[n:n + 3], atol=0, rtol=0)
    for t in (0, 2, 11):
        np.testing.assert_allclose(
            y[t], sum(w[i] * seen[t + i] for i in range(4)), atol=1e-6,
            rtol=0)


def test_the_recurrence_in_two_pieces_equals_one():
    """From the state and the tail the first piece left, the second goes
    on as if there had been no cut (what a chunked prefill will need)."""
    *x, S0 = tokens_of(6, 40)
    o, S = delta_rule.kda_chunked(*x, S0, chunk=8)
    o1, S1 = delta_rule.kda_chunked(*(a[:13] for a in x), S0, chunk=8)
    o2, S2 = delta_rule.kda_chunked(*(a[13:] for a in x), S1, chunk=8)
    np.testing.assert_allclose(jnp.concatenate([o1, o2]), o, atol=2e-5,
                               rtol=0)
    np.testing.assert_allclose(S2, S, atol=RULE_TOL, rtol=0)


# -- the layer, plainly -----------------------------------------------------

@pytest.mark.parametrize("dense", [False, True], ids=["sorted", "dense"])
@pytest.mark.parametrize("first,held", [(0, 16), (4, 4)],
                         ids=["every_expert", "a_share"])
def test_forward_pass_equals_the_reference(ref, adapter, first, held, dense):
    cfg = tiny_cfg(first, held)
    model, params = build(adapter, cfg)
    assert model.cfg.gqa_layers == (0,)
    assert model.cfg.recurrent_layers == (1, 2, 3)
    ids = jnp.asarray(ids_of(1, 27), jnp.int32)
    want = ref.logits(cfg, params, ids)
    got = model.apply(params, ids[None], dense=dense)[0]
    assert float(jnp.abs(want).max()) > 0.5     # logits that say something
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_a_lower_precision_is_told_apart(ref, adapter):
    """The reference in float8 lies far outside the tolerance the tests
    here hold the program to."""
    cfg = tiny_cfg()
    _, params = build(adapter, cfg)
    ids = jnp.asarray(ids_of(1, 16), jnp.int32)
    gap = jnp.abs(ref.logits(cfg, params, ids, ref.to_fp8)
                  - ref.logits(cfg, params, ids)).max()
    assert float(gap) > 100 * TOL


def test_every_mixer_moves_the_logits(ref, adapter):
    """Neither kind of layer is a pass-through at these weights: with a
    layer's output projection zeroed the reference's logits move."""
    cfg = tiny_cfg()
    _, params = build(adapter, cfg)
    ids = jnp.asarray(ids_of(2, 12), jnp.int32)
    want = ref.logits(cfg, params, ids)
    for li, kind in ((0, "gqa"), (2, "kda")):
        cut = jax.tree_util.tree_map(lambda x: x, params)
        cut[f"layer_{li}"][kind]["wo"] = {
            "w": jnp.zeros_like(params[f"layer_{li}"][kind]["wo"]["w"])}
        assert float(jnp.abs(ref.logits(cfg, cut, ids) - want).max()) > 0.05


# -- through the engine ---------------------------------------------------------

@pytest.mark.parametrize("prompt_len,new", [(5, 22), (19, 9), (24, 6)],
                         ids=["decode_mostly", "prefill_two_buckets",
                              "prefill_mostly"])
def test_engine_prefill_then_decode_equals_the_full_forward_pass(
        ref, adapter, monkeypatch, prompt_len, new):
    # (chunks small enough that a prefill's scan walks several)
    monkeypatch.setattr(delta_rule, "CHUNK", 4)
    cfg = tiny_cfg(2, 8)
    model, params = build(adapter, cfg)
    rows = recording(model)
    eng = engine(model, params)
    prompt = ids_of(7, prompt_len)
    eng.submit("a", prompt, new)
    done = [e for e in eng.drain() if e["kind"] == "done"][0]
    seq = prompt + done["tokens"]
    want = np.asarray(ref.logits(cfg, params, jnp.asarray(seq, jnp.int32)))
    slot = 0                                     # the first slot handed out
    got = [rows[0][0]] + [r[slot] for r in rows[1:]]
    assert len(got) == new
    for i, row in enumerate(got):
        np.testing.assert_allclose(row, want[prompt_len - 1 + i], atol=TOL,
                                   rtol=0, err_msg=f"token {i}")
        assert done["tokens"][i] == int(np.argmax(row))


def test_a_prefill_in_two_pieces_equals_one(adapter):
    """The prefill program from ``start > 0`` goes on from the slot's
    own state, tail and rows: 9 tokens and then 10 leave the slot, and
    choose the token, that 19 at once do; and a prefill from ``start ==
    0`` into a slot that held another request starts from nothing."""
    cfg = tiny_cfg()
    model, params = build(adapter, cfg)
    caches = model.serve_caches(2, MAX_SEQ)
    prefill = jax.jit(caches.prefill)
    ids = np.asarray(ids_of(9, 19), np.int32)

    def padded(part, width):
        out = np.zeros(width, np.int32)
        out[:len(part)] = part
        return jnp.asarray(out)

    i32 = jnp.int32
    k, v = caches.new_slabs()
    k1, v1, tok1 = prefill(params, k, v, padded(ids, 32), i32(19), i32(0),
                           i32(1))
    # ... into a slot another request has left its state in
    k, v, _ = prefill(params, *caches.new_slabs(), padded(ids_of(8, 30), 32),
                      i32(30), i32(0), i32(1))
    k, v, _ = prefill(params, k, v, padded(ids[:9], 16), i32(9), i32(0),
                      i32(1))
    k2, v2, tok2 = prefill(params, k, v, padded(ids[9:], 16), i32(10), i32(9),
                           i32(1))
    assert int(tok1) == int(tok2)
    (_, s1), (_, t1) = k1, v1
    (r2, s2), (_, t2) = k2, v2
    for layer in range(3):          # (an array a KDA layer, [1, slots, ...])
        assert float(jnp.abs(s1[layer][:, 1]).max()) > 0.01
        np.testing.assert_allclose(s2[layer][:, 1], s1[layer][:, 1],
                                   atol=RULE_TOL, rtol=0)
        np.testing.assert_allclose(t2[layer][:, 1], t1[layer][:, 1],
                                   atol=RULE_TOL, rtol=0)
        # the other slot was never touched
        assert not bool(jnp.any(s2[layer][:, 0]))
        assert not bool(jnp.any(t2[layer][:, 0]))
    np.testing.assert_allclose(r2[:, 1, :, :19], k1[0][:, 1, :, :19],
                               atol=RULE_TOL, rtol=0)


def test_a_reused_slot_gives_what_a_fresh_engine_gives(ref, adapter):
    """One slot, a long request and then a short one: the second finds
    the first's state, tail and rows in its slot and must not see them --
    its logits are the reference's, and its tokens a fresh engine's."""
    cfg = tiny_cfg(0, 4)
    model, params = build(adapter, cfg)
    rows = recording(model)
    eng = engine(model, params, slots=1)
    first, second = ids_of(11, 21), ids_of(12, 6)
    eng.submit("long", first, 10)
    eng.submit("short", second, 8)
    done = {e["rid"]: e["tokens"] for e in eng.drain() if e["kind"] == "done"}
    want = np.asarray(ref.logits(cfg, params, jnp.asarray(
        second + done["short"], jnp.int32)))
    got = [r[0] for r in rows[-8:]]       # (a prefill's row is [1, vocab])
    for i, row in enumerate(got):
        np.testing.assert_allclose(row, want[len(second) - 1 + i], atol=TOL,
                                   rtol=0, err_msg=f"token {i}")
    fresh = engine(model, params, slots=1)
    fresh.submit("short", second, 8)
    assert [e for e in fresh.drain() if e["kind"] == "done"][0]["tokens"] \
        == done["short"]


def test_a_slot_that_is_not_live_keeps_its_state_across_a_step(adapter):
    """A decode step for slot 0 alone: slot 1's state, tail and rows come
    back to the bit, slot 0's all move."""
    cfg = tiny_cfg()
    model, params = build(adapter, cfg)
    caches = model.serve_caches(2, MAX_SEQ)
    i32 = jnp.int32
    k, v = caches.new_slabs()
    for slot, seed in ((0, 13), (1, 14)):
        ids = np.zeros(8, np.int32)
        ids[:6] = ids_of(seed, 6)
        k, v, _ = jax.jit(caches.prefill)(params, k, v, jnp.asarray(ids),
                                          i32(6), i32(0), i32(slot))
    before = jax.tree_util.tree_map(np.asarray, (k, v))
    k, v, out = jax.jit(caches.decode)(
        params, k, v, jnp.asarray([5, 7], i32), jnp.asarray([6, 6], i32),
        jnp.asarray([True, False]))
    for was, now in zip(jax.tree_util.tree_leaves(before),
                        jax.tree_util.tree_leaves((k, v))):
        now = np.asarray(now)
        assert np.array_equal(now[:, 1], was[:, 1])
        assert not np.array_equal(now[:, 0], was[:, 0])
    toks, says = caches.read(out, np.asarray([7]))
    assert toks.shape == (2,) and says["state_slots_live"] == 1


def test_staggered_requests_over_reused_slots(ref, adapter):
    """Requests admitted mid-flight at different positions, more of them
    than slots: each one's tokens are what the reference puts first, by a
    margin or not at all (a tie at float32's rounding may go either
    way)."""
    cfg = tiny_cfg(0, 4)
    model, params = build(adapter, cfg)
    eng = engine(model, params, slots=2)
    prompts = {f"r{i}": ids_of(20 + i, n) for i, n in
               enumerate((3, 19, 9, 26, 12))}
    for rid, p in prompts.items():
        eng.submit(rid, p, MAX_SEQ - len(p) if len(p) > 20 else 6)
    done = {e["rid"]: e["tokens"] for e in eng.drain() if e["kind"] == "done"}
    assert set(done) == set(prompts)
    forward = jax.jit(lambda p, ids: ref.logits(cfg, p, ids))
    for rid, toks in done.items():
        seq = prompts[rid] + toks              # (padded: causal, one compile)
        ids = np.zeros(MAX_SEQ + 6, np.int32)
        ids[:len(seq)] = seq
        lg = np.asarray(forward(params, jnp.asarray(ids)))
        at = len(prompts[rid]) - 1
        for i, t in enumerate(toks):
            assert lg[at + i].max() - lg[at + i, t] <= TOL, (rid, i)


def test_a_request_that_ends_on_eos_leaves_the_next_a_clean_slot(ref, adapter):
    """The loop runs one step ahead: when a request ends on ``eos_id``
    the step behind it has already been dispatched for its slot, and the
    program leaves that slot's state alone (``live``); the next request
    into the slot then decodes what the reference decodes."""
    cfg = tiny_cfg(0, 4)
    model, params = build(adapter, cfg)
    probe = engine(model, params, slots=1)
    probe.submit("a", ids_of(15, 7), 6)
    toks = [e for e in probe.drain() if e["kind"] == "done"][0]["tokens"]
    eng = engine(model, params, slots=1, eos_id=toks[2])
    eng.submit("a", ids_of(15, 7), 6)
    eng.submit("b", ids_of(16, 9), 5)
    done = {e["rid"]: e["tokens"] for e in eng.drain() if e["kind"] == "done"}
    assert done["a"] == toks[:toks.index(toks[2]) + 1]
    seq = ids_of(16, 9) + done["b"]
    lg = np.asarray(ref.logits(cfg, params, jnp.asarray(seq, jnp.int32)))
    for i, t in enumerate(done["b"]):
        if t == toks[2]:
            break
        assert lg[8 + i].max() - lg[8 + i, t] <= TOL, i


# -- pages that are never whole -------------------------------------------

def test_the_engine_looks_up_no_prefix_and_commits_nothing(adapter,
                                                           monkeypatch):
    """``PageSpec.for_model`` takes the config and counts a page over the
    softmax layers alone; the pool says no prefix of this family is
    reusable, and the engine neither reserves, looks up nor commits a
    page: the same prompt twice is prefilled twice, and a completion
    fetches no bytes."""
    cfg = tiny_cfg()
    model, params = build(adapter, cfg)
    spec = PageSpec.for_model(model.cfg, page_tokens=PAGE)
    assert spec.unpaged and (spec.n_layers, spec.n_heads, spec.head_dim) \
        == (1, 2, 8)
    eng = engine(model, params, slots=2, capacity=2)
    assert not eng.pool.reusable([])
    spans = _lookahead.record_spans(monkeypatch)
    prompt = ids_of(17, 13)
    for rid in ("a", "b"):
        eng.submit(rid, prompt, 3)
        done = [e for e in eng.drain() if e["kind"] == "done"]
        assert done[0]["reused_tokens"] == 0
        assert done[0]["computed_tokens"] == 13
    assert eng.pool.stats()["free"] == 2 and eng.pool.cached_pages == 0
    completes = [s for s in spans if s.name == "complete"]
    assert [(s.attrs["pages"], s.attrs["bytes"]) for s in completes] \
        == [(0, 0), (0, 0)]
    admits = [s for s in spans if s.name == "admit"]
    assert [(s.attrs["reused"], s.attrs["pages"]) for s in admits] \
        == [(0, 0), (0, 0)]
    # the families whose layers all keep rows are paged as before
    from kungfu_tpu.models.transformer import TransformerConfig

    dense = PageSpec.for_model(TransformerConfig(
        vocab_size=97, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq=32), page_tokens=PAGE)
    assert not dense.unpaged and dense.n_layers == 2


def test_decode_says_what_it_moved_and_routed(adapter, monkeypatch):
    """Behind the tokens, on the ``kf:serve.decode_read`` span of the step
    they belong to: the routing over the live slots and all four layers,
    and the slots whose state the step moved beside the slots and bytes
    it read -- every slot's, whatever is live."""
    cfg = tiny_cfg(0, 16)
    model, params = build(adapter, cfg)
    eng = engine(model, params, slots=3)
    spans = _lookahead.record_spans(monkeypatch)

    def last(name):
        return [s for s in spans if s.name == name][-1].attrs

    eng.submit("a", ids_of(3, 5), 4)
    eng.step()                          # admits a, dispatches its step
    eng.step()                          # the next step, then that one read
    r = last("decode_read")
    # one live slot, every expert held: top-4 of each of 4 layers
    assert r["experts_touched"] == 16 and r["expert_load_max"] == 1
    assert r["experts_held"] == 64 and r["expert_load_mean"] == 16 / 64
    assert r["state_slots_live"] == 1 and r["state_slots_read"] == 3
    # three KDA layers x three slots x (4 heads of 8 x 8 float32 and a
    # tail of 3 x 96 in the compute dtype, float32 here)
    assert r["state_bytes_read"] == 3 * 3 * (4 * 8 * 8 * 4 + 3 * 96 * 4)
    assert r["discarded"] == 0
    eng.submit("b", ids_of(4, 7), 4)
    eng.step()                          # admits b, dispatches a and b; reads
    eng.step()                          # a's last token is in flight: b alone
    assert last("decode_read")["state_slots_live"] == 2
    eng.step()
    assert last("decode_read")["state_slots_live"] == 1
    assert last("decode_read")["state_slots_read"] == 3


# -- the softmax layer's slab through the kernel that walks live tiles -----
#: wide enough for ``ops/pallas/decode_attention.py``: 16 query heads
#: over 2 key/value heads of 128, a slab of three tiles of 128 positions;
#: the KDA layers stay at 4 heads of 8 (XLA's ``kda_step`` everywhere)
WIDE_SEQ, WIDE_TILE = 384, 128


def wide_model(adapter):
    """(the program's model in bfloat16, its weights)."""
    cfg = dict(tiny_cfg(), num_attention_heads=16, num_key_value_heads=2,
               head_dim=128, n_positions=WIDE_SEQ)
    model = adapter.program_model(cfg)
    return model, jax.jit(model.init)(jax.random.PRNGKey(5))


says_tpu = _lookahead.says_tpu


def walked(n):
    return sum(-(-x // WIDE_TILE) * WIDE_TILE for x in n)


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_read_states_the_rows_the_step_itself_counted(adapter, monkeypatch,
                                                      backend):
    """``kv_rows_read`` on ``kf:serve.decode_read`` is the step's own
    count, brought back behind its tokens: where the kernel runs, every
    live slot's tiles up to its newest row and nothing of a slot the
    step is not for; where XLA's form runs, every row of every slot.
    ``kv_attn_kernel`` says which, and the host's counts stand beside
    it as they were."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    model, _ = wide_model(adapter)
    caches = model.serve_caches(4, WIDE_SEQ)
    kernel = int(backend == "tpu")
    assert caches.attn_tile == (WIDE_TILE if kernel else None)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    k, v = jax.eval_shape(caches.new_slabs)
    slots = jax.ShapeDtypeStruct((4,), jnp.int32)
    text = str(jax.make_jaxpr(caches.decode)(
        params, k, v, slots, slots, jax.ShapeDtypeStruct((4,), bool)))
    assert text.count("name=decode_attn") == kernel
    assert len(caches.new_out()) == 4 + 6
    # what the step of slots at positions 4, 127, 128 (not live) and 300
    # puts behind its tokens, taken apart by ``read``
    pos, live = np.asarray([4, 127, 128, 300]), [True, True, False, True]
    n = [p + 1 if l else 0 for p, l in zip(pos, live)]
    count = walked(n) if kernel else 4 * WIDE_SEQ
    assert walked(n) == 128 + 128 + 0 + 384
    out = np.asarray([7, 8, 9, 10, 12, 1, 12, 3, 3, count], np.int32)
    tokens, says = caches.read(out, np.asarray([5, 128, 301]))
    assert tokens.tolist() == [7, 8, 9, 10]
    assert says["kv_rows_read"] == count and says["kv_attn_kernel"] == kernel
    assert says["kv_rows_live"] == 5 + 128 + 301
    assert says["kv_rows_written"] == 3 and says["kv_row_bytes"] == 1024
    assert says["state_slots_live"] == 3 and "kv_rows_walked" not in says
    # ... and the slots whose matrices it says it moved (PR 46)
    assert says["state_slots_read"] == 3 and "state_slots_moved" not in says


def test_the_step_counts_the_rows_its_kernel_walks(adapter, monkeypatch):
    """One traced decode step, the kernel interpreted: behind the tokens
    stands ``sum ceil(n / tile) * tile`` over the live slots, a slot the
    step is not for counted nowhere and its rows untouched."""
    says_tpu(monkeypatch)
    model, params = wide_model(adapter)
    caches = model.serve_caches(4, WIDE_SEQ)
    k, v = caches.new_slabs()
    fill = lambda r, a: jax.random.normal(r, a.shape, jnp.float32
                                          ).astype(a.dtype)
    r = jax.random.split(jax.random.PRNGKey(6), 2)
    k, v = (fill(r[0], k[0]), k[1]), (fill(r[1], v[0]), v[1])
    pos = jnp.asarray([4, 127, 128, 300], jnp.int32)
    live = jnp.asarray([True, True, False, True])
    (kr, _), _, out = jax.jit(caches.decode)(
        params, k, v, jnp.asarray([5, 9, 11, 2], jnp.int32), pos, live)
    _, says = caches.read(out, np.asarray([5, 128, 301]))
    assert says["kv_rows_read"] == walked([5, 128, 0, 301]) == 640
    assert says["kv_attn_kernel"] == 1 and says["state_slots_live"] == 3
    assert np.array_equal(np.asarray(kr)[:, 2], np.asarray(k[0])[:, 2])


def test_every_softmax_layer_is_handed_the_slots_visible_rows(adapter):
    """Two periods of four layers, softmax layers 0 and 4: the second
    one's kernel, too, gets the rows each SLOT may see (a layer's block
    returns its experts' counts, another vector of another length: PR
    39's review).  One decode step over a filled slab with the kernel
    interpreted and with XLA's form: what the layers behind the second
    softmax layer leave in their states and tails, and the counts behind
    the tokens, are the same."""
    def step(kernel):
        with pytest.MonkeyPatch.context() as steer:
            if kernel:
                says_tpu(steer)
            cfg = dict(tiny_cfg(), num_attention_heads=16,
                       num_key_value_heads=2, head_dim=128,
                       n_positions=WIDE_SEQ, num_hidden_layers=8,
                       num_hidden_layers_published=8)
            model = adapter.program_model(cfg)
            params = jax.jit(model.init)(jax.random.PRNGKey(5))
            caches = model.serve_caches(4, WIDE_SEQ)
            assert caches.kv_attn_kernel == int(kernel)
            k, v = caches.new_slabs()
            r = jax.random.split(jax.random.PRNGKey(6), 2)
            fill = lambda r, a: jax.random.normal(r, a.shape, jnp.float32
                                                  ).astype(a.dtype)
            k, v = (fill(r[0], k[0]), k[1]), (fill(r[1], v[0]), v[1])
            args = (params, k, v, jnp.asarray([5, 9, 11, 2], jnp.int32),
                    jnp.asarray([4, 127, 128, 300], jnp.int32),
                    jnp.asarray([True, True, False, True]))
            # two calls of ONE traced kernel: both layers hand it
            # operands of the same shapes
            text = str(jax.make_jaxpr(caches.decode)(*args))
            assert text.count("jit[name=_call ") == 2 * kernel
            assert text.count("name=decode_attn") == kernel
            (_, state), (_, tails), out = jax.jit(caches.decode)(*args)
        return ([np.asarray(x, np.float32) for x in state + tails],
                np.asarray(out))

    (got, out), (want, plain) = step(True), step(False)
    assert len(got) == 2 * 6
    # bfloat16 weights of size 0.5: the two forms round apart by 1-8 % of
    # an array's largest entry (13), and by 33-78 % in the three layers
    # behind a second softmax layer that is handed another vector
    for a, b in zip(got, want):
        assert np.abs(a - b).max() < 0.15 * np.abs(b).max()
    # the live slots' tokens and their number; the rows read are the
    # step's own: two layers' live tiles against two whole slabs
    live = [0, 1, 3, 4 + 3]
    assert out[live].tolist() == plain[live].tolist()
    assert out[-1] == 2 * walked([5, 128, 0, 301])
    assert plain[-1] == 2 * 4 * WIDE_SEQ


def test_the_engine_through_the_kernel_serves_xlas_tokens(adapter):
    """Two requests through ``InferenceEngine``, one whose context
    crosses a tile's edge while it decodes, with the kernel interpreted
    and with XLA's form: the same tokens, and on every
    ``kf:serve.decode_read`` span which form ran and rows read that are
    whole tiles under the kernel and the whole slab without it."""
    def serve(kernel):
        with pytest.MonkeyPatch.context() as steer:
            if kernel:
                says_tpu(steer)
            model, params = wide_model(adapter)
            eng = InferenceEngine(
                model, params, max_batch=3, max_seq=WIDE_SEQ,
                pool=KVCachePool(PageSpec.for_model(model.cfg,
                                                    page_tokens=PAGE),
                                 capacity_pages=2))
            spans = _lookahead.record_spans(steer)
            # (seeds under which every served token leads the runner-up
            # by 0.85 in logits of size 10: the two forms round apart by
            # 0.1-0.25)
            eng.submit("a", ids_of(26, 5), 4)
            eng.submit("b", ids_of(27, 126), 4)     # decodes rows 126 .. 128
            done = {e["rid"]: e["tokens"] for e in eng.drain()
                    if e["kind"] == "done"}
        return done, [s.attrs for s in spans if s.name == "decode_read"]

    (kernel, reads), (xla, plain) = serve(True), serve(False)
    assert kernel == xla and len(kernel["a"]) == len(kernel["b"]) == 4
    assert reads and all(r["kv_attn_kernel"] == 1 for r in reads)
    assert all(r["kv_rows_read"] % WIDE_TILE == 0
               and r["kv_rows_live"] <= r["kv_rows_read"] < 3 * WIDE_SEQ
               for r in reads if r["kv_rows_live"])
    # a alone in its first tile; a beside b, a tile each; b alone in two,
    # its context past 128
    pairs = [(r["kv_rows_live"], r["kv_rows_read"]) for r in reads]
    assert pairs == [(6, 128), (7 + 127, 256), (8 + 128, 256), (129, 256)]
    assert plain and all(r["kv_attn_kernel"] == 0
                         and r["kv_rows_read"] == 3 * WIDE_SEQ for r in plain)


def test_the_engine_serves_it_without_knowing_it():
    """``engine.py`` imports no model and tests for no class
    (tests/test_cohere2_moe.py reads its source); this model's answer to
    ``serve_caches`` has what the engine asks of a cache whose pages are
    never whole, and a state and a tail in its empty pages."""
    model = files.load_adapter("solar_open2").program_model(tiny_cfg())
    caches = model.serve_caches(3, MAX_SEQ)
    for name in ("new_slabs", "new_out", "prefill", "decode", "read",
                 "empty_pages", "prefill_flops", "decode_flops"):
        assert callable(getattr(caches, name)), name
    (rows, state), (rows_v, tails) = caches.empty_pages(8)
    assert rows.shape == rows_v.shape == (1, 2, 8, 8)
    assert [(x.shape, x.dtype) for x in state] == [
        ((1, 4, 8, 8), np.float32)] * 3
    assert [x.shape for x in tails] == [(1, 3, 96)] * 3
    (kr, s), (vr, t) = caches.new_slabs()
    assert kr.shape == vr.shape == (1, 3, 2, MAX_SEQ, 8)
    # an array a KDA layer (serve/recurrent.py says why)
    assert [(x.shape, x.dtype) for x in s] == [
        ((1, 3, 4, 8, 8), jnp.float32)] * 3
    assert [(x.shape, x.dtype) for x in t] == [
        ((1, 3, 3, 96), jnp.bfloat16)] * 3
    assert caches.decode_flops([5, 9]) > caches.decode_flops([5, 8]) > 0
    assert caches.prefill_flops(8, 4) > caches.prefill_flops(8) > 0


def test_importing_the_models_loads_no_kernel_package():
    """``import kungfu_tpu.models`` (every serving cell's set-up pays
    it) pulls in neither Pallas nor the recurrence's caches: a fresh
    interpreter's modules, read after the import."""
    import subprocess

    code = ("import sys; sys.path.insert(0, %r); import kungfu_tpu.models; "
            "import kungfu_tpu.models.solar_open2; "
            "bad = [m for m in sys.modules if 'pallas' in m "
            "or m == 'kungfu_tpu.serve.recurrent']; print(bad)" % ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[]"


# -- the share: 32 chips' routed parts, the shared expert once ------------

def test_the_shares_add_up_to_the_uncut_layer(ref, adapter):
    cfg = dict(tiny_cfg(0, 64), router_width=64, num_experts_per_tok=8)
    _, whole = build(adapter, cfg)
    z = files.load_module("lib", "solar_open2").sizes(cfg)
    lp = whole["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (11, 64), jnp.float32)
    want = ref._experts(z, lp, x, None)
    shared = experts.shared_mean(lp["shared"], x)
    total = shared
    for first in range(0, 64, 2):            # 32 chips, two experts each
        part = dict(lp, experts=jax.tree_util.tree_map(
            lambda w: w[first:first + 2], lp["experts"]))
        y, _ = experts.apply(part, x, top_k=8, held=(first, 2), dense=True)
        y2, _ = experts.apply(part, x, top_k=8, held=(first, 2), dense=False)
        np.testing.assert_allclose(y, y2, atol=TOL, rtol=0)
        # each share against the reference told the same share
        zc = dict(z, first=first, held=2)
        np.testing.assert_allclose(y, ref._experts(zc, part, x, None),
                                   atol=TOL, rtol=0)
        total = total + (y - shared)
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)


def test_one_initialisation_scaled_by_the_whole_models_depth(adapter):
    """The adapter's weights ARE the program's ``init``; the output
    projections' scale follows the published depth, not the layers held
    here; the decays' parameters lie where the published layer puts
    them, and the leaves have the stated types."""
    cfg = dict(tiny_cfg(), num_hidden_layers_published=48)
    model = adapter.program_model(cfg)
    assert (model.cfg.n_layers, model.cfg.init_layers) == (4, 48)
    key = jax.random.PRNGKey(3)
    ours, theirs = adapter.init_params(cfg, key), model.init(key)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a.dtype == b.dtype and bool(jnp.all(a == b)),
        ours, theirs))
    std = lambda w: float(jnp.std(w.astype(jnp.float32)))
    kda, gqa = ours["layer_2"]["kda"], ours["layer_0"]["gqa"]
    for w in (kda["w_qkv"]["w"], gqa["wq"]["w"], gqa["w_gate"]["w"]):
        assert std(w) == pytest.approx(0.5, rel=0.1)
    for w in (kda["wo"]["w"], gqa["wo"]["w"],
              ours["layer_2"]["moe"]["experts"]["down"]):
        assert std(w) == pytest.approx(0.5 / (2 * 48) ** 0.5, rel=0.15)
    a = jnp.exp(-jnp.exp(kda["a_log"])[:, None]
                * jax.nn.softplus(kda["b_dt"]).reshape(4, 8))
    assert 0.19 < float(a.min()) and float(a.max()) < 0.9991
    for leaf in (kda["a_log"], kda["b_dt"], kda["o_norm"]["scale"],
                 ours["layer_1"]["moe"]["router"]["w"],
                 ours["layer_1"]["ln_moe"]["scale"]):
        assert leaf.dtype == jnp.float32
    for leaf in (kda["conv"], kda["b_g"], kda["w_b"]["w"],
                 ours["head"]["w"]):
        assert leaf.dtype == jnp.bfloat16
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(ours))
    assert n == adapter.n_params(cfg)
