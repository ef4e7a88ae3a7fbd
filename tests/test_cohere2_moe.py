"""``cohere2_moe`` (Command A+) at a tiny size on the CPU, against the
benchmark's plain reference (``kfbench/reference/cohere2_moe.py``), on
logits and not tokens: the plain forward pass, the engine's prefill and
decode through its two caches on a context of three windows, the chip's
share of the expert layer, a router that sends every token to one
expert, prefix hits over window layers, and the dense GPT-2 programs
unchanged.

The weights are the adapter's (bfloat16 leaves from a seed), computed in
float32 at ``highest`` on both sides, so the two agree to rounding.
"""

import dataclasses
import hashlib
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
from kungfu_tpu.models import experts  # noqa: E402
from kungfu_tpu.models.cohere2_moe import Cohere2Moe  # noqa: E402
from kungfu_tpu.models.transformer import (Transformer,  # noqa: E402
                                           TransformerConfig)
from kungfu_tpu.serve.engine import InferenceEngine  # noqa: E402
from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec  # noqa: E402

WINDOW, MAX_SEQ, PAGE = 8, 32, 4
TOL = 2e-4   # logits reach 30: float32 rounding over four layers


def tiny_cfg(first=0, held=16):
    """The configuration file's keys at the tiny size: hidden 64, 16
    query over 2 key/value heads of 8, 16 experts of width 32 (top-4, 2
    shared), window 8, one period of 4 layers."""
    return dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=16, num_key_value_heads=2, head_dim=8,
        intermediate_size=32, router_width=16, experts_held_first=first,
        num_experts=held, num_experts_per_tok=4, num_shared_experts=2,
        sliding_window=WINDOW, layer_types=["sliding_attention"] * 3
        + ["full_attention"], rope_theta=50000, layer_norm_eps=1e-5,
        logit_scale=0.75, initializer_range=0.5,
        num_hidden_layers_published=4, n_positions=MAX_SEQ)


@pytest.fixture(scope="module")
def ref():
    return files.load_reference("cohere2_moe")


@pytest.fixture(scope="module")
def adapter():
    return files.load_adapter("cohere2_moe")


def build(adapter, cfg, seed=0):
    """(the program's model in float32, the adapter's weights)."""
    model = adapter.program_model(cfg)
    params = jax.jit(lambda k: adapter.init_params(cfg, k))(
        jax.random.PRNGKey(seed))
    return Cohere2Moe(dataclasses.replace(model.cfg, dtype="float32")), params


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


# -- the block, plainly ---------------------------------------------------

@pytest.mark.parametrize("dense", [False, True], ids=["sorted", "dense"])
@pytest.mark.parametrize("first,held", [(0, 16), (4, 4)],
                         ids=["every_expert", "a_share"])
def test_forward_pass_equals_the_reference(ref, adapter, first, held, dense):
    cfg = tiny_cfg(first, held)
    model, params = build(adapter, cfg)
    ids = jnp.asarray(ids_of(1, 3 * WINDOW + 3), jnp.int32)
    want = ref.logits(cfg, params, ids)
    got = model.apply(params, ids[None], dense=dense)[0]
    assert float(jnp.abs(want).max()) > 0.5     # logits that say something
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_a_lower_precision_is_told_apart(ref, adapter):
    """The reference in float8 lies far outside the tolerance the tests
    above hold the program to."""
    cfg = tiny_cfg()
    _, params = build(adapter, cfg)
    ids = jnp.asarray(ids_of(1, 16), jnp.int32)
    gap = jnp.abs(ref.logits(cfg, params, ids, ref.to_fp8)
                  - ref.logits(cfg, params, ids)).max()
    assert float(gap) > 100 * TOL


# -- (a) prefill, then decode, through the engine's two caches -------------

@pytest.mark.parametrize("prompt_len,new", [(5, 22), (2 * WINDOW + 3, 9),
                                            (3 * WINDOW, 6)],
                         ids=["decode_wraps", "prefill_past_window",
                              "prefill_three_windows"])
def test_engine_prefill_then_decode_equals_the_full_forward_pass(
        ref, adapter, prompt_len, new):
    cfg = tiny_cfg(2, 8)
    model, params = build(adapter, cfg)
    rows = recording(model)
    eng = engine(model, params)
    prompt = ids_of(7, prompt_len)
    eng.submit("a", prompt, new)
    done = [e for e in eng.drain() if e["kind"] == "done"][0]
    seq = prompt + done["tokens"]
    assert len(seq) > 3 * WINDOW                 # the ring wrapped
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
    for rid, toks in done.items():
        seq = prompts[rid] + toks
        lg = np.asarray(ref.logits(cfg, params, jnp.asarray(seq, jnp.int32)))
        at = len(prompts[rid]) - 1
        for i, t in enumerate(toks):
            assert lg[at + i].max() - lg[at + i, t] <= TOL, (rid, i)


def test_decode_returns_the_routing_of_live_slots_only(adapter, monkeypatch):
    """Behind the tokens: held experts that received a live token, the
    busiest one's tokens, the tokens received -- over the live slots and
    all layers; the engine puts them on the ``kf:serve.decode_read`` span
    of the step they belong to."""
    cfg = tiny_cfg(0, 16)
    model, params = build(adapter, cfg)
    eng = engine(model, params, slots=3)
    spans = _lookahead.record_spans(monkeypatch)

    def last(name):
        return [s for s in spans if s.name == name][-1].attrs

    eng.submit("a", ids_of(3, 5), 4)
    eng.step()                          # admits a, dispatches its step
    assert last("decode")["batch"] == 1 and last("decode")["ahead"] == 0
    eng.step()                          # the next step, then that one read
    r = last("decode_read")
    # one live slot, every expert held: top-4 of each of 4 layers
    assert r["experts_touched"] == 16 and r["expert_load_max"] == 1
    assert r["experts_held"] == 64 and r["expert_load_mean"] == 16 / 64
    assert r["discarded"] == 0
    assert last("decode")["batch"] == 1 and last("decode")["ahead"] == 1
    eng.submit("b", ids_of(4, 7), 4)
    eng.step()                          # admits b, dispatches a and b, reads
    assert last("decode")["batch"] == 2 and last("decode")["ahead"] == 1
    assert last("decode_read")["expert_load_mean"] == 16 / 64
    eng.step()                          # a's last token is in flight: b alone
    assert last("decode")["batch"] == 1
    assert last("decode_read")["expert_load_mean"] == 32 / 64
    assert last("decode_read")["experts_touched"] <= 32
    # a dense model's step has nothing to add to its span
    assert "experts_touched" not in last("decode")


# -- one decode step ahead of the host, over the rings ---------------------
#: rid -> (prompt, max_new): _lookahead.mixed_run's roles.  ``stops`` has
#: a prompt longer than the window and room to wrap the ring twice
MIXED = {"by_n": (ids_of(31, 5), 9), "stops": (ids_of(32, 11), 21),
         "dropped": (ids_of(33, 6), 20), "late": (ids_of(34, 3), 12),
         "next": (ids_of(35, 4), 5)}


@pytest.fixture(scope="module")
def mixed(ref, adapter):
    """The mixed set through the two caches with an ``eos_id`` that ends
    ``stops`` early, past the window: (model, params, what the plain
    float32 reference decodes, events, slots, engine)."""
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
    """One ends by ``max_new``, one on ``eos_id`` with more tokens than
    the window holds, one is cancelled with its step in flight, one is
    admitted while others decode, one takes the slot the discarded row
    left: token for token the plain reference's, every ``done`` returned."""
    model, params, want, events, slots, eng = mixed
    got = _lookahead.tokens_of(events)
    assert set(got) == set(MIXED) - {"dropped"}
    assert got == {rid: want[rid] for rid in got}
    assert got["stops"][-1] == eng.eos_id
    assert WINDOW < len(MIXED["stops"][0]) + len(got["stops"]) < MAX_SEQ
    assert len(got["by_n"]) == 9


@pytest.mark.parametrize("against", ["same_schedule", "alone"])
def test_committed_pages_one_step_ahead_hold_the_same_bytes(mixed, against):
    """The discarded row of the request that ended on ``eos_id`` would
    land on the ring row of a position the commit still counts as held,
    and the row of a slot left out on one that is yet to be committed:
    the pool holds byte for byte, whole or not, what engines hold that
    never compute such a row (tests/test_serve.py has the dense twin)."""
    model, params, want, events, slots, eng = mixed
    assert not all(whole for _, _, _, whole
                   in _lookahead.committed(eng.pool).values())
    _lookahead.check_committed(lambda: engine(model, params), MIXED, want,
                               events, slots, eng.pool, against)


# -- (b) the share: eight chips' routed parts, the shared experts once ----

def test_the_shares_add_up_to_the_uncut_layer(ref, adapter):
    whole_cfg = tiny_cfg(0, 16)
    _, whole = build(adapter, whole_cfg)
    z = files.load_module("lib", "cohere2").sizes(whole_cfg)
    lp = whole["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (11, 64), jnp.float32)
    want = ref._experts(z, lp, x, None)
    shared = experts.shared_mean(lp["shared"], x)
    total = shared
    for first in range(0, 16, 2):                # eight chips, two experts each
        part = dict(lp, experts=jax.tree_util.tree_map(
            lambda w: w[first:first + 2], lp["experts"]))
        y, _ = experts.apply(part, x, top_k=4, held=(first, 2), dense=True)
        y2, _ = experts.apply(part, x, top_k=4, held=(first, 2), dense=False)
        np.testing.assert_allclose(y, y2, atol=TOL, rtol=0)
        # each share against the reference told the same share
        zc = dict(z, first=first, held=2)
        np.testing.assert_allclose(y, ref._experts(zc, part, x, None),
                                   atol=TOL, rtol=0)
        total = total + (y - shared)
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)


# -- (c) dropless ----------------------------------------------------------

@pytest.mark.parametrize("dense", [False, True], ids=["sorted", "dense"])
def test_no_token_is_dropped_when_every_token_picks_one_expert(ref, adapter,
                                                               dense):
    cfg = tiny_cfg(4, 4)
    _, params = build(adapter, cfg)
    lp = jax.tree_util.tree_map(lambda x: x, params["layer_0"]["moe"])
    # expert 5 (held) gets every token; 0-2 (held elsewhere) the other picks
    bias = jnp.zeros((64, 16)).at[:, 5].set(50.0).at[:, :3].set(40.0)
    lp["router"] = {"w": jnp.abs(lp["router"]["w"]) * 0 + bias}
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (40, 64))) + 0.1
    y, counts = experts.apply(lp, x, top_k=4, held=(4, 4), dense=dense)
    assert counts.tolist() == [0, 40, 0, 0]
    z = dict(files.load_module("lib", "cohere2").sizes(cfg))
    np.testing.assert_allclose(y, ref._experts(z, lp, x, None), atol=TOL,
                               rtol=0)
    # every token got expert 5's output at a quarter of the weight
    e5 = jax.tree_util.tree_map(lambda w: w[1].astype(jnp.float32),
                                lp["experts"])
    own = (jax.nn.silu(x @ e5["gate"]) * (x @ e5["up"])) @ e5["down"]
    routed = y - experts.shared_mean(lp["shared"], x)
    np.testing.assert_allclose(routed, own / 4, atol=TOL, rtol=1e-4)


# -- (d) pages of window layers ---------------------------------------------

def test_a_prefix_hit_past_the_window_gives_the_references_logits(ref,
                                                                  adapter):
    cfg = tiny_cfg(2, 8)
    model, params = build(adapter, cfg)
    rows = recording(model)
    eng = engine(model, params)
    shared = ids_of(11, 4 * PAGE + 1)            # two windows and a row
    eng.submit("first", shared[:4 * PAGE], 1)    # rows end on a page's edge
    eng.drain()
    del rows[:]
    prompt = shared + ids_of(13, 5)
    eng.submit("second", prompt, 5)
    events = eng.drain()
    admit = [e for e in events if e["kind"] == "admit"][0]
    assert admit["reused"] == 4 * PAGE           # 16 > the window of 8
    done = [e for e in events if e["kind"] == "done"][0]
    seq = prompt + done["tokens"]
    want = np.asarray(ref.logits(cfg, params, jnp.asarray(seq, jnp.int32)))
    got = [rows[0][0]] + [r[0] for r in rows[1:]]   # (slot 0 again)
    for i, row in enumerate(got):
        np.testing.assert_allclose(row, want[len(prompt) - 1 + i], atol=TOL,
                                   rtol=0, err_msg=f"token {i}")


def test_a_chain_whose_window_rows_are_gone_is_not_reused(ref, adapter):
    """A request whose rows end at position 28 leaves its window layers'
    last 8: its pages before position 20 are committed without them.  A later prompt
    sharing 12 tokens may not restore them (it would attend over rows
    that were overwritten); one sharing all 28 may, and both get the
    reference's tokens."""
    cfg = tiny_cfg(2, 8)
    model, params = build(adapter, cfg)
    eng = engine(model, params)
    long_prompt = ids_of(31, 25)
    eng.submit("long", long_prompt, 4)
    first = [e for e in eng.drain() if e["kind"] == "done"][0]
    seq = long_prompt + first["tokens"]          # rows exist for [0, 28)
    pool = eng.pool
    pages, n = pool.lookup(seq)
    assert n == 28
    whole = [pool._pages[p].whole for p in pages]
    assert whole == [False] * 5 + [True] * 2     # 20 // 4 = 5
    assert pool.reusable(pages) and not pool.reusable(pages[:3])
    assert pool.reusable(pages[:7]) and not pool.reusable(pages[:6])
    pool.release(pages)

    def served(rid, prompt, new):
        eng.submit(rid, prompt, new)
        ev = eng.drain()
        admit = [e for e in ev if e["kind"] == "admit"][0]
        toks = [e for e in ev if e["kind"] == "done"][0]["tokens"]
        full = prompt + toks
        lg = np.asarray(ref.logits(cfg, params,
                                   jnp.asarray(full, jnp.int32)))
        for i, t in enumerate(toks):
            assert lg[len(prompt) - 1 + i].max() \
                - lg[len(prompt) - 1 + i, t] <= TOL, (rid, i)
        return admit["reused"]

    assert served("same", seq[:28] + ids_of(33, 2), 2) == 28
    # 12 shared tokens would need the rows of [4, 12): gone
    short = seq[:12] + ids_of(32, 1)
    assert served("short", short, 4) == 0
    # its own rows end at 16 and are whole from 8: the chain's third link
    # takes them (the first two stay as they were), and 16 can be reused
    pages, n = pool.lookup(short + [0, 0, 0])
    assert n == 12
    assert [pool._pages[p].whole for p in pages] == [False, False, True]
    pool.release(pages)
    eng.submit("short2", short, 4)               # greedy: the same 16 rows
    again = [e for e in eng.drain() if e["kind"] == "done"][0]["tokens"]
    assert served("short_again", short + again[:3] + ids_of(34, 2), 2) == 16


def test_a_page_that_is_not_whole_survives_a_snapshot(adapter):
    cfg = tiny_cfg()
    model, params = build(adapter, cfg)
    eng = engine(model, params)
    eng.submit("long", ids_of(41, 25), 4)
    eng.drain()
    snap = eng.pool.snapshot_committed()
    fresh = KVCachePool(eng.pool.spec, capacity_pages=16)
    assert fresh.restore_committed(snap) == (7, 0)
    flags = sorted(p.whole for p in fresh._pages.values())
    assert flags == [False] * 5 + [True] * 2


def test_page_spec_counts_key_value_heads(adapter):
    model = adapter.program_model(tiny_cfg())
    spec = PageSpec.for_model(model.cfg, page_tokens=PAGE)
    assert (spec.n_layers, spec.n_heads, spec.head_dim, spec.window) == (
        4, 2, 8, WINDOW)
    eng = engine(model, None)
    (kw, kf), (vw, vf) = eng._k, eng._v
    assert kw.shape == vw.shape == (3, 3, 2, WINDOW, 8)
    assert kf.shape == vf.shape == (1, 3, 2, MAX_SEQ, 8)


def test_the_engine_names_no_model_family():
    """The scheduler asks ``model.serve_caches`` for everything that
    depends on a model's cache layout: its source imports no model, tests
    for no class and looks at no size of the token vector.  A new family
    brings its own answer (``serve/caches.py``) and no branch here."""
    import inspect

    from kungfu_tpu.serve import engine as mod

    src = inspect.getsource(mod)
    code = "\n".join(ln.split("#")[0] for part in src.split('"""')[::2]
                     for ln in part.splitlines())
    assert "kungfu_tpu.models" not in code
    assert "isinstance" not in code and ".size" not in code
    for model in (Transformer(TransformerConfig(
            vocab_size=97, d_model=32, n_layers=1, n_heads=4, d_ff=64,
            max_seq=32)), files.load_adapter("cohere2_moe").program_model(
                tiny_cfg())):
        caches = model.serve_caches(3, MAX_SEQ)
        for name in ("new_slabs", "prefill", "decode", "read", "empty_pages",
                     "pages_to_slot", "rows_of_slot", "prefill_flops",
                     "decode_flops"):
            assert callable(getattr(caches, name)), (type(caches), name)


def test_one_initialisation_scaled_by_the_whole_models_depth(adapter):
    """The adapter's weights ARE the program's ``init``; the output
    projections' scale follows the published depth, not the layers held
    here (``init_layers``)."""
    cfg = dict(tiny_cfg(), num_hidden_layers_published=36)
    model = adapter.program_model(cfg)
    assert (model.cfg.n_layers, model.cfg.init_layers) == (4, 36)
    key = jax.random.PRNGKey(3)
    ours, theirs = adapter.init_params(cfg, key), model.init(key)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a.dtype == b.dtype and bool(jnp.all(a == b)),
        ours, theirs))
    lp = ours["layer_2"]
    std = lambda w: float(jnp.std(w.astype(jnp.float32)))
    assert std(lp["wq"]["w"]) == pytest.approx(0.5, rel=0.05)
    for w in (lp["wo"]["w"], lp["moe"]["experts"]["down"],
              lp["moe"]["shared"]["down"]):
        assert std(w) == pytest.approx(0.5 / (2 * 36) ** 0.5, rel=0.05)
    assert lp["moe"]["router"]["w"].dtype == lp["ln"]["scale"].dtype \
        == jnp.float32 and lp["wq"]["w"].dtype == jnp.bfloat16


# -- (e) the engine's programs are what they were ---------------------------

#: sha256 of the lowered text of the engine's three programs at the size
#: below, as the commit before this model lowered them (PR 25's tree).
#: A PR that changes the dense path on purpose records its own.
GPT2_PROGRAMS = {
    "decode": "27ba8358f322633b0d3314a33806b063ab7f5ed8181766275c7320c26bb487c4",
    "prefill": "3213586b14c887b542fe403c8f4bab439f9c5f541bf8942be4b754ed27095e31",
    "restore": "98d26d159b3a0ab4227b24739a9cdf57bb6888c63efda7b1929ca9f30b72aa69",
}
#: the same of every other family's, at the tiny sizes of
#: tests/test_serve_kv_rows.py, as PR 46's tree lowered them: a change
#: that only moves the cache managers' code between functions
#: (``serve/caches.py`` and the five beside it) makes the same operations
#: in the same order, and this is where that shows.  A PR that changes a
#: family's programs on purpose records its own and says so.
FAMILY_PROGRAMS = {
    "windowed": {
        "decode": "04933d22f01ac79b52bcfc6b33264f89609b4e6b1e05fb5f8e07fa56b1530201",
        "prefill": "2a04752cd01b4ebab0996b5491f37cc716535db5d4feae02e1dd93933ad69167",
        "restore": "3e35572dd5a0f4dd08662aafedddd7b6d123a804e0b7b4c28855f9575ea2157e"},
    "latent": {
        "decode": "fc9130c744f27df5414433d8cf815e3fc7709edff9dc063284e86f0d740d141f",
        "prefill": "0d30be405e8e5aa65b1843ec40ba8fb062b7f00eb3e5fb4c7a4104d43ecb9ca9",
        "restore": "2e93b82f1dd2edad2b03cb44be9bdaf7dffc2fd46d731fc40f112b5cdeac8071"},
    "hybrid": {
        "decode": "b0e86192dddffa9dcf6be767b4c2e69e0e78a0dff91a40898430459b359db560",
        "prefill": "9405c7a260b7c7347597084353c57cfb662c06c0fb5bd92f3b8c6b3a46b65c48",
        "restore": "4ee3607d4cd1bc7d13b92c292eb1ae2df1b5a5f5651d4d36fc70ec862b2065e5"},
    "pooled": {
        "decode": "ff320ef623366bdd9b9aa3dc02639b257cdd79a72c0a250a3c3ed439f32ed5d2",
        "prefill": "fa41561ba9719189be36dbeff261b4fca2f7885696bc190e1c156b1683309347",
        "restore": "8fd5d357aa58959f3b66b135260d867767ff076fd3f62ac4b232e99dab257735"},
    "sambay": {
        "decode": "7d0c3356bfadf794cb3ec52876acec632c8173747c5a06458437b7211d7ac549",
        "prefill": "4a3eeaef26c7d7835b8a1a9ac95b9ae4363d158c008617744091232b6da8f9bb",
        "restore": "7891542302e4843bf799bd0b7f31d2e5ab4e6af11697493c0afb10f74d52b5f5"},
}
#: family -> tokens of the prefill bucket lowered (the pooled cache's
#: two windows of 32; a page's worth twice for the others)
BUCKET = {"pooled": 64}


def gpt2_engine():
    cfg = TransformerConfig(vocab_size=97, d_model=32, n_layers=2,
                            n_heads=4, d_ff=64, max_seq=32, pos="learned",
                            dtype="bfloat16")
    m = Transformer(cfg)
    return InferenceEngine(
        m, m.init(jax.random.PRNGKey(0)), max_batch=3, max_seq=32,
        pool=KVCachePool(PageSpec.for_model(cfg, page_tokens=4),
                         capacity_pages=16))


@pytest.fixture(scope="module")
def engines():
    """family -> its engine, built when first asked for (``gpt2`` at the
    size ``GPT2_PROGRAMS`` was recorded at)."""
    import functools

    from tests import test_serve_kv_rows as tiny

    built = functools.lru_cache(None)(tiny.build_all)

    @functools.lru_cache(None)
    def of(family):
        with jax.default_matmul_precision("default"):
            return gpt2_engine() if family == "gpt2" else tiny.engine(
                built(), family)

    return of


@pytest.mark.parametrize("program", ["decode", "prefill", "restore"])
@pytest.mark.parametrize("family", ["gpt2", "windowed", "latent", "hybrid",
                                    "pooled", "sambay"])
def test_programs_lower_bitwise_as_before(engines, family, program):
    eng = engines(family)
    slots = jnp.zeros(eng.max_batch, jnp.int32)
    i0, bucket = jnp.int32(0), BUCKET.get(family, 8)
    with jax.default_matmul_precision("default"):
        lowered = {
            "decode": lambda: eng._decode_j.lower(
                eng.params, eng._k, eng._v, eng._out, slots, slots),
            "prefill": lambda: eng._prefill_j.lower(
                eng.params, eng._k, eng._v, jnp.zeros(bucket, jnp.int32),
                i0, i0, i0),
            "restore": lambda: eng._restore_j.lower(
                eng._k, eng._v, *eng._caches.empty_pages(bucket), i0),
        }[program]()
    want = GPT2_PROGRAMS if family == "gpt2" else FAMILY_PROGRAMS[family]
    got = hashlib.sha256(lowered.as_text().encode()).hexdigest()
    assert got == want[program], got
