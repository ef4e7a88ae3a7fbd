"""``pangu_ultra_moe`` (openPangu-Ultra-MoE) at a tiny size on the CPU,
against the benchmark's plain reference (``kfbench/reference/
pangu_moe.py``, un-absorbed latent attention only), on logits and not
tokens: the plain forward pass, the absorbed order against the expanded
one over the same rows, the engine's prefill (expanded) and decode
(absorbed) through the latent cache, prefix hits that restore latent
pages, what a page holds, the chip's share of the expert layer, a router
that sends every token to one expert, and the stack's two kinds of FFN.

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
from kungfu_tpu.models import experts, pangu_moe as arch  # noqa: E402
from kungfu_tpu.serve.engine import InferenceEngine  # noqa: E402
from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec  # noqa: E402

MAX_SEQ, PAGE = 32, 4
RANK, ROPE = 16, 4
#: logits reach 16, and every sandwich norm brings a sublayer's output
#: back to full scale, rounding and all: the forward pass and the engine
#: read 0.7e-4 to 1.2e-4 over four seeds of the weights (float32 at
#: ``highest`` on both sides), the float8 reference 10 to 14
TOL = 5e-4


def tiny_cfg(first=0, held=16, layers=3, dense=1):
    """The configuration file's keys at the tiny size: hidden 64, 4 heads
    of 8 + 4 / 8 over latents of 24 and 16, a dense layer of width 96 and
    expert layers of 16 experts of width 32 (top-4, scaled by 2.5, one
    shared)."""
    return dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=layers,
        first_k_dense_replace=dense, num_attention_heads=4,
        num_key_value_heads=4, qk_nope_head_dim=8, qk_rope_head_dim=ROPE,
        v_head_dim=8, q_lora_rank=24, kv_lora_rank=RANK,
        intermediate_size=96, moe_intermediate_size=32, router_width=16,
        experts_held_first=first, n_routed_experts=held,
        num_experts_per_tok=4, n_shared_experts=1,
        routed_scaling_factor=2.5, rope_theta=25600000, rms_norm_eps=1e-5,
        initializer_range=0.5, num_hidden_layers_published=layers,
        n_positions=MAX_SEQ)


@pytest.fixture(scope="module")
def ref():
    return files.load_reference("pangu_moe")


@pytest.fixture(scope="module")
def adapter():
    return files.load_adapter("pangu_moe")


def build(adapter, cfg, seed=0):
    """(the program's model in float32, the adapter's weights)."""
    model = adapter.program_model(cfg)
    params = jax.jit(lambda k: adapter.init_params(cfg, k))(
        jax.random.PRNGKey(seed))
    return arch.PanguMoe(dataclasses.replace(model.cfg, dtype="float32")), \
        params


def engine(model, params, slots=3, capacity=64, eos_id=None):
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


# -- the layer, plainly -----------------------------------------------------

@pytest.mark.parametrize("dense", [False, True], ids=["sorted", "dense"])
@pytest.mark.parametrize("first,held", [(0, 16), (4, 4)],
                         ids=["every_expert", "a_share"])
def test_forward_pass_equals_the_reference(ref, adapter, first, held, dense):
    cfg = tiny_cfg(first, held)
    model, params = build(adapter, cfg)
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


@pytest.mark.parametrize("depth,dense", [(2, 1), (3, 1), (4, 2)])
def test_dense_and_expert_layers_are_both_present(adapter, depth, dense):
    """``first_k_dense_replace``: the leading layers carry a dense gated
    FFN of the dense width and no router, the others experts and no dense
    FFN -- the first stack of the tree whose layers differ."""
    cfg = tiny_cfg(layers=depth, dense=dense)
    model, params = build(adapter, cfg)
    for li in range(depth):
        lp = params[f"layer_{li}"]
        assert ("mlp" in lp) == (li < dense) and ("moe" in lp) == (li >= dense)
        assert set(lp) - {"mlp", "moe", "attn"} == {
            "ln_in", "ln_post_attn", "ln_pre_mlp", "ln_post_mlp"}
    assert params["layer_0"]["mlp"]["gate"]["w"].shape == (64, 96)
    assert params[f"layer_{depth - 1}"]["moe"]["experts"]["gate"].shape == (
        16, 64, 32)
    assert model.cfg.expert_layers == tuple(range(dense, depth))
    assert params["head"]["w"].shape == (64, 96)       # untied
    assert "w" not in params["embed"]


# -- the two orders of one attention ---------------------------------------

@pytest.mark.parametrize("contexts", [(1, 32, 7), (20, 3, 11)])
def test_the_absorbed_order_equals_the_expanded_one(adapter, contexts):
    """One query row a slot over the latent rows themselves (``W_uk`` in
    the query, ``W_uv`` in the output: a decode step's order) against
    the keys and values of every row formed first (a prefill's, and the
    reference's), on the same rows of the same cache."""
    cfg = tiny_cfg()
    model, params = build(adapter, cfg)
    ap = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32),
                                params["layer_1"]["attn"])
    b, heads = len(contexts), 4
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    c_kv = jax.random.normal(ks[0], (b, MAX_SEQ, RANK))
    k_r = jax.random.normal(ks[1], (b, MAX_SEQ, ROPE))
    q_nope = jax.random.normal(ks[2], (b, heads, 8))
    q_rope = jax.random.normal(ks[3], (b, heads, ROPE))
    pos = jnp.asarray(contexts) - 1
    scale = model.cfg.score_scale
    assert scale == pytest.approx(1 / 12 ** 0.5)
    # (the slab's parts whole, as the decode step hands them: layer 1 of
    # two, the other one zeros)
    slab = lambda rows: jnp.stack([jnp.zeros_like(rows), rows])[:, :, None]
    got = arch.absorbed_attention(ap, q_nope, q_rope, slab(c_kv), slab(k_r),
                                  1, pos + 1, scale)
    k_nope, v = arch.expand(ap, c_kv)
    assert k_nope.shape == v.shape == (b, heads, 8, MAX_SEQ)
    for i in range(b):
        want = arch.expanded_attention(
            q_nope[i][None], q_rope[i][None], k_nope[i], k_r[i], v[i],
            pos[i][None], scale)[0]
        np.testing.assert_allclose(got[i], want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("first", [0, 5, 16])
def test_the_expanded_order_by_tiles_equals_one_tile(adapter, monkeypatch,
                                                     first):
    """Blocks of 4 query rows against chunks of 8 keys, the softmax
    carried across the chunks and the chunks past a block's last row not
    walked, give what one tile over every key under the mask gives --
    for queries at the start of the slot and past ``first`` cached rows."""
    cfg = tiny_cfg()
    model, params = build(adapter, cfg)
    ap = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32),
                                params["layer_0"]["attn"])
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    n_q = 12
    k_nope, v = arch.expand(ap, 3 * jax.random.normal(ks[0], (MAX_SEQ, RANK)))
    k_r = jax.random.normal(ks[1], (MAX_SEQ, ROPE))
    q = (jax.random.normal(ks[2], (n_q, 4, 8)),
         jax.random.normal(ks[3], (n_q, 4, ROPE)))
    q_pos = first + jnp.arange(n_q)
    want = arch.expanded_attention(*q, k_nope, k_r, v, q_pos, 0.3)
    monkeypatch.setattr(arch, "ATTN_BLOCK", 4)
    monkeypatch.setattr(arch, "KEY_CHUNK", 8)
    got = arch.expanded_attention(*q, k_nope, k_r, v, q_pos, 0.3)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)   # values to 14
    # by hand, the last row: every key up to its position, one softmax
    t = n_q - 1
    see = int(q_pos[t]) + 1
    s = (jnp.einsum("hn,hns->hs", q[0][t], k_nope[:, :, :see])
         + jnp.einsum("hr,sr->hs", q[1][t], k_r[:see])) * 0.3
    hand = jnp.einsum("hs,hvs->hv", jax.nn.softmax(s, axis=-1),
                      v[:, :, :see])
    np.testing.assert_allclose(got[t], hand, atol=2e-5, rtol=0)


# -- prefill, then decode, through the latent cache -------------------------

@pytest.mark.parametrize("prompt_len,new", [(5, 22), (19, 9), (24, 6)],
                         ids=["decode_mostly", "prefill_two_buckets",
                              "prefill_mostly"])
def test_engine_prefill_then_decode_equals_the_full_forward_pass(
        ref, adapter, monkeypatch, prompt_len, new):
    # (tiles small enough that a prefill walks several, and skips some)
    monkeypatch.setattr(arch, "ATTN_BLOCK", 4)
    monkeypatch.setattr(arch, "KEY_CHUNK", 8)
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


def test_decode_says_what_it_read_and_routed(adapter, monkeypatch):
    """Behind the tokens, on the ``kf:serve.decode_read`` span of the step
    they belong to: the routing over the live slots and the EXPERT layers
    (the dense layer has none), and the latent rows of live contexts
    beside the rows the step read -- the whole slab, whatever is live."""
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
    # one live slot, every expert held: top-4 of each of 2 expert layers
    assert r["experts_touched"] == 8 and r["expert_load_max"] == 1
    assert r["experts_held"] == 32 and r["expert_load_mean"] == 8 / 32
    # its context: 5 prompt rows and the row the step wrote
    assert r["latent_rows_live"] == 6
    assert r["latent_rows_read"] == 3 * MAX_SEQ
    assert r["discarded"] == 0
    eng.submit("b", ids_of(4, 7), 4)
    eng.step()                          # admits b, dispatches a and b; reads
    assert last("decode_read")["latent_rows_live"] == 7
    eng.step()                          # a's last token is in flight: b alone
    assert last("decode_read")["latent_rows_live"] == 8 + 8
    assert last("decode_read")["expert_load_mean"] == 16 / 32
    eng.step()
    assert last("decode_read")["latent_rows_live"] == 9
    assert last("decode_read")["latent_rows_read"] == 3 * MAX_SEQ


# -- one decode step ahead of the host, over the latent slab ----------------
#: rid -> (prompt, max_new): _lookahead.mixed_run's roles
MIXED = {"by_n": (ids_of(31, 5), 9), "stops": (ids_of(32, 11), 21),
         "dropped": (ids_of(33, 6), 20), "late": (ids_of(34, 3), 12),
         "next": (ids_of(35, 4), 5)}


@pytest.fixture(scope="module")
def mixed(ref, adapter):
    """The mixed set through the latent cache with an ``eos_id`` that
    ends ``stops`` early: (model, params, what the plain float32
    reference decodes, events, slots, engine)."""
    cfg = tiny_cfg(0, 16)
    with jax.default_matmul_precision("highest"):
        model, params = build(adapter, cfg)
        forward = jax.jit(lambda p, ids: ref.logits(cfg, p, ids))

        def decode(prompt, n):
            """Greedy, a full forward pass a token (padded: causal)."""
            seq = list(prompt)
            for _ in range(n):
                ids = np.zeros(MAX_SEQ, np.int32)
                ids[:len(seq)] = seq
                seq.append(int(np.argmax(np.asarray(
                    forward(params, jnp.asarray(ids)))[len(seq) - 1])))
            return seq[len(prompt):]

        reference = {rid: decode(*a) for rid, a in MIXED.items()}
        eos = _lookahead.pick_eos(reference, MIXED, earliest=8)
        want = {rid: _lookahead.until_eos(toks, eos)
                for rid, toks in reference.items()}
        eng = engine(model, params, eos_id=eos)
        events, slots = _lookahead.mixed_run(eng, MIXED)
    return model, params, want, events, slots, eng


def test_mixed_requests_one_step_ahead_decode_what_the_reference_decodes(
        mixed):
    """One ends by ``max_new``, one on ``eos_id``, one is cancelled with
    its step in flight, one is admitted while others decode, one takes
    the slot the discarded row left: token for token the plain
    reference's, every ``done`` returned."""
    model, params, want, events, slots, eng = mixed
    got = _lookahead.tokens_of(events)
    assert set(got) == set(MIXED) - {"dropped"}
    assert got == {rid: want[rid] for rid in got}
    assert got["stops"][-1] == eng.eos_id
    assert len(got["by_n"]) == 9


@pytest.mark.parametrize("against", ["same_schedule", "alone"])
def test_committed_pages_one_step_ahead_hold_the_same_bytes(mixed, against):
    """The discarded row of the request that ended on ``eos_id``, and the
    row of a slot a step leaves out, are kept out of the slab by the
    program: the pool holds byte for byte what engines hold that never
    compute such a row."""
    model, params, want, events, slots, eng = mixed
    assert all(whole for _, _, _, whole
               in _lookahead.committed(eng.pool).values())
    _lookahead.check_committed(lambda: engine(model, params), MIXED, want,
                               events, slots, eng.pool, against)


# -- pages of latent rows -----------------------------------------------------

def test_a_prefix_hit_restores_latent_pages_and_gives_the_references_logits(
        ref, adapter, monkeypatch):
    monkeypatch.setattr(arch, "ATTN_BLOCK", 2)     # (several tiles a prefill)
    monkeypatch.setattr(arch, "KEY_CHUNK", 8)
    """The second request's prefill starts past 16 restored positions:
    its queries attend over keys and values EXPANDED from the restored
    latent rows and from its own, and the decode over both, absorbed."""
    cfg = tiny_cfg(2, 8)
    model, params = build(adapter, cfg)
    rows = recording(model)
    eng = engine(model, params)
    shared = ids_of(11, 4 * PAGE + 1)
    eng.submit("first", shared[:4 * PAGE], 1)    # rows end on a page's edge
    eng.drain()
    del rows[:]
    prompt = shared + ids_of(13, 5)
    eng.submit("second", prompt, 5)
    events = eng.drain()
    admit = [e for e in events if e["kind"] == "admit"][0]
    assert admit["reused"] == 4 * PAGE and admit["computed"] == 6
    done = [e for e in events if e["kind"] == "done"][0]
    seq = prompt + done["tokens"]
    want = np.asarray(ref.logits(cfg, params, jnp.asarray(seq, jnp.int32)))
    got = [rows[0][0]] + [r[0] for r in rows[1:]]   # (slot 0 again)
    for i, row in enumerate(got):
        np.testing.assert_allclose(row, want[len(prompt) - 1 + i], atol=TOL,
                                   rtol=0, err_msg=f"token {i}")


def test_a_committed_page_holds_the_latent_row_and_nothing_else(
        ref, adapter, monkeypatch):
    """A page is ``c_kv`` (after its norm) and ``k_r`` (after its
    rotation) of PAGE positions of every layer, ``r + rope`` values a
    position a layer against ``heads x (nope + rope + v)`` of per-head
    keys and values; ``PageSpec.page_bytes``, the pool's gauge and the
    ``bytes`` of ``kf:serve.complete`` all count exactly that."""
    cfg = tiny_cfg()
    model, params = build(adapter, cfg)
    spec = PageSpec.for_model(model.cfg, page_tokens=PAGE)
    assert (spec.n_layers, spec.n_heads, spec.widths) == (3, 1, (RANK, ROPE))
    assert spec.part_shape(0) == (3, 1, PAGE, RANK)
    assert spec.part_shape(1) == (3, 1, PAGE, ROPE)
    assert spec.page_bytes == 3 * PAGE * (RANK + ROPE) * 4      # float32
    per_head = 4 * (8 + ROPE + 8)
    assert per_head / (RANK + ROPE) == 4.0       # (71 x at the published sizes)
    spans = _lookahead.record_spans(monkeypatch)
    eng = engine(model, params)
    c, k_r = eng._k, eng._v
    assert c.shape == (3, 3, 1, MAX_SEQ, RANK)
    assert k_r.shape == (3, 3, 1, MAX_SEQ, ROPE)
    prompt = ids_of(51, 10)
    eng.submit("a", prompt, 4)                   # rows for positions [0, 13)
    eng.drain()
    done = [s for s in spans if s.name == "complete"][-1].attrs
    assert done["pages"] == 3 and done["bytes"] == 3 * spec.page_bytes
    pages, n = eng.pool.lookup(prompt)
    assert n == 2 * PAGE
    k, v = eng.pool.page_data(pages[1])
    assert k.shape == spec.part_shape(0) and v.shape == spec.part_shape(1)
    # layer 0's rows are a function of the embedding alone: by hand
    z = files.load_module("lib", "pangu").sizes(cfg)
    lp = params["layer_0"]
    u = ref._rmsnorm(lp["ln_in"], params["embed"]["table"][
        jnp.asarray(prompt[PAGE:2 * PAGE])].astype(jnp.float32), z["eps"])
    kv = u @ lp["attn"]["wkv_a"]["w"].astype(jnp.float32)
    np.testing.assert_allclose(
        k[0, 0], ref._rmsnorm(lp["attn"]["kv_ln"], kv[:, :RANK], z["eps"]),
        atol=2e-5, rtol=0)
    np.testing.assert_allclose(
        v[0, 0], ref._rotate(kv[:, None, RANK:], jnp.arange(PAGE, 2 * PAGE),
                             z["theta"])[:, 0], atol=2e-5, rtol=0)
    eng.pool.release(pages)


def test_latent_pages_survive_a_snapshot(adapter):
    cfg = tiny_cfg()
    model, params = build(adapter, cfg)
    eng = engine(model, params)
    eng.submit("long", ids_of(41, 25), 4)
    eng.drain()
    snap = eng.pool.snapshot_committed()
    assert snap["kv0_k"].shape[-1] == RANK and snap["kv0_v"].shape[-1] == ROPE
    fresh = KVCachePool(eng.pool.spec, capacity_pages=16)
    assert fresh.restore_committed(snap) == (7, 0)
    assert _lookahead.committed(fresh) == _lookahead.committed(eng.pool)
    # a pool of per-head pages takes none of them
    other = KVCachePool(dataclasses.replace(eng.pool.spec, v_head_dim=0),
                        capacity_pages=16)
    assert other.restore_committed(snap) == (0, 7)


def test_the_engine_serves_it_without_knowing_it():
    """``engine.py`` imports no model and tests for no class
    (tests/test_cohere2_moe.py reads its source); the latent model's
    answer to ``serve_caches`` has the whole interface."""
    model = files.load_adapter("pangu_moe").program_model(tiny_cfg())
    caches = model.serve_caches(3, MAX_SEQ)
    for name in ("new_slabs", "new_out", "prefill", "decode", "read",
                 "empty_pages", "pages_to_slot", "rows_of_slot",
                 "prefill_flops", "decode_flops"):
        assert callable(getattr(caches, name)), name
    ks, vs = caches.empty_pages(8)
    assert ks.shape == (3, 1, 8, RANK) and vs.shape == (3, 1, 8, ROPE)
    assert caches.decode_flops([5, 9]) > caches.decode_flops([5, 8]) > 0
    assert caches.prefill_flops(8, 4) > caches.prefill_flops(8) > 0


# -- the share: eight chips' routed parts, the shared expert once ---------

def test_the_shares_add_up_to_the_uncut_layer(ref, adapter):
    whole_cfg = tiny_cfg(0, 16)
    _, whole = build(adapter, whole_cfg)
    z = files.load_module("lib", "pangu").sizes(whole_cfg)
    lp = whole["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (11, 64), jnp.float32)
    want = ref._experts(z, lp, x, None)
    shared = experts.shared_mean(lp["shared"], x)
    total = shared
    for first in range(0, 16, 2):                # eight chips, two experts each
        part = dict(lp, experts=jax.tree_util.tree_map(
            lambda w: w[first:first + 2], lp["experts"]))
        y, _ = experts.apply(part, x, top_k=4, held=(first, 2), dense=True,
                             scale=2.5)
        y2, _ = experts.apply(part, x, top_k=4, held=(first, 2), dense=False,
                              scale=2.5)
        np.testing.assert_allclose(y, y2, atol=TOL, rtol=0)
        # each share against the reference told the same share
        zc = dict(z, first=first, held=2)
        np.testing.assert_allclose(y, ref._experts(zc, part, x, None),
                                   atol=TOL, rtol=0)
        total = total + (y - shared)
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)
    # the scale multiplies the routed part and not the shared expert
    plain, _ = experts.apply(lp, x, top_k=4, held=(0, 16), dense=True)
    np.testing.assert_allclose(want - shared, 2.5 * (plain - shared),
                               atol=TOL, rtol=0)


# -- dropless -----------------------------------------------------------------

@pytest.mark.parametrize("dense", [False, True], ids=["sorted", "dense"])
def test_no_token_is_dropped_when_every_token_picks_one_expert(ref, adapter,
                                                               dense):
    cfg = tiny_cfg(4, 4)
    _, params = build(adapter, cfg)
    lp = jax.tree_util.tree_map(lambda x: x, params["layer_1"]["moe"])
    # expert 5 (held) gets every token; 0-2 (held elsewhere) the other picks
    bias = jnp.zeros((64, 16)).at[:, 5].set(50.0).at[:, :3].set(40.0)
    lp["router"] = {"w": jnp.abs(lp["router"]["w"]) * 0 + bias}
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (40, 64))) + 0.1
    y, counts = experts.apply(lp, x, top_k=4, held=(4, 4), dense=dense,
                              scale=2.5)
    assert counts.tolist() == [0, 40, 0, 0]
    z = dict(files.load_module("lib", "pangu").sizes(cfg))
    np.testing.assert_allclose(y, ref._experts(z, lp, x, None), atol=TOL,
                               rtol=0)
    # every token got expert 5's output at a quarter of the weight, x 2.5
    e5 = jax.tree_util.tree_map(lambda w: w[1].astype(jnp.float32),
                                lp["experts"])
    own = (jax.nn.silu(x @ e5["gate"]) * (x @ e5["up"])) @ e5["down"]
    routed = y - experts.shared_mean(lp["shared"], x)
    np.testing.assert_allclose(routed, 2.5 * own / 4, atol=TOL, rtol=1e-4)


def test_one_initialisation_scaled_by_the_whole_models_depth(adapter):
    """The adapter's weights ARE the program's ``init``; the output
    projections' scale follows the published depth, not the layers held
    here (``init_layers``)."""
    cfg = dict(tiny_cfg(), num_hidden_layers_published=36)
    model = adapter.program_model(cfg)
    assert (model.cfg.n_layers, model.cfg.n_dense, model.cfg.init_layers) \
        == (3, 1, 36)
    key = jax.random.PRNGKey(3)
    ours, theirs = adapter.init_params(cfg, key), model.init(key)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a.dtype == b.dtype and bool(jnp.all(a == b)),
        ours, theirs))
    std = lambda w: float(jnp.std(w.astype(jnp.float32)))
    ap = ours["layer_2"]["attn"]
    for w in (ap["wq_a"]["w"], ap["wq_b"]["w"], ap["wkv_a"]["w"], ap["w_uk"],
              ap["w_uv"], ours["layer_0"]["mlp"]["up"]["w"]):
        assert std(w) == pytest.approx(0.5, rel=0.1)
    for w in (ap["wo"]["w"], ours["layer_0"]["mlp"]["down"]["w"],
              ours["layer_2"]["moe"]["experts"]["down"],
              ours["layer_2"]["moe"]["shared"]["down"]):
        assert std(w) == pytest.approx(0.5 / (2 * 36) ** 0.5, rel=0.1)
    assert ours["layer_2"]["moe"]["router"]["w"].dtype == ap["q_ln"][
        "scale"].dtype == ours["layer_1"]["ln_post_mlp"]["scale"].dtype \
        == jnp.float32 and ap["w_uk"].dtype == jnp.bfloat16
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(ours))
    assert n == adapter.n_params(cfg)
