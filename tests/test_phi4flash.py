"""``phi4flash`` (Phi-4-mini-flash-reasoning: SambaY) at a tiny size on
the CPU, against the benchmark's plain reference
(``kfbench/reference/phi4flash.py``: the recurrence token by token, every
layer over every position), on logits and not tokens: the selective scan
in its three forms, a bucket's padding, the plain forward pass, every
mixer's mark on the logits, the window's edge, the engine's prefill --
whose cross-decoder runs over the last row alone -- and decode through
``SambaYCaches``, slots reused and slots left out of a step, pages that
are never whole, what ``read`` says, the differential layer through
the attention kernel's layout, and a step's rows through the kernel that
writes them.

The weights are the adapter's (bfloat16 leaves from a seed, biases and
``lam`` vectors drawn non-zero here), computed in float32 at ``highest``
on both sides, so the two agree to rounding.
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
from kungfu_tpu.models import phi4flash as arch  # noqa: E402
from kungfu_tpu.ops import selective_scan  # noqa: E402
from kungfu_tpu.serve.engine import InferenceEngine  # noqa: E402
from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec  # noqa: E402

MAX_SEQ, PAGE, WINDOW = 32, 4, 6
#: logits reach 4; the forward pass and the engine read 8e-6 to 3e-5
#: (float32 at ``highest`` on both sides), the float8 reference 1.6
TOL = 3e-4
#: layer -> kind at the tiny size's eight layers
KINDS = ["mamba", "attn_window", "mamba", "attn_window", "mamba",
         "attn_full", "gmu", "attn_cross"]


def tiny_cfg(**over):
    """The configuration file's keys at the tiny size: hidden 64, eight
    layers (Mamba 0, 2, 4 of 128 channels x 4 states, window 1, 3, full
    5, GMU 6, cross 7), 8 query heads over 4 key/value heads of 8 (two
    pairs of 16), a window of 6."""
    return dict(
        dict(vocab_size=96, hidden_size=64, intermediate_size=128,
             num_hidden_layers=8, num_attention_heads=8,
             num_key_value_heads=4, sliding_window=WINDOW, mb_per_layer=2,
             mamba=dict(d_state=4, d_conv=4, expand=2), layer_norm_eps=1e-5,
             initializer_range=0.15, n_positions=MAX_SEQ), **over)


@pytest.fixture(scope="module")
def ref():
    return files.load_reference("phi4flash")


@pytest.fixture(scope="module")
def adapter():
    return files.load_adapter("phi4flash")


def roughened(params, seed=99):
    """The weights with every bias of a projection and of the
    convolution drawn non-zero (the initialisation has them at zero, and
    a bias that is zero tests nothing), and the ``lam`` vectors drawn
    large enough that ``lam`` leaves ``lam_init`` by a few tenths."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 200))
    size = {"b": 0.3, "conv_b": 0.3, "lam": 0.5}

    def walk(tree):
        if isinstance(tree, dict):
            return {k: (size[k] * jax.random.normal(
                next(keys), v.shape, jnp.float32).astype(v.dtype)
                if k in size else walk(v)) for k, v in tree.items()}
        return tree

    return walk(params)


def build(adapter, cfg, seed=0):
    """(the program's model in float32, the adapter's weights)."""
    model = adapter.program_model(cfg)
    params = roughened(jax.jit(lambda k: adapter.init_params(cfg, k))(
        jax.random.PRNGKey(seed)))
    return arch.Phi4Flash(dataclasses.replace(model.cfg, dtype="float32")), \
        params


@pytest.fixture(scope="module")
def tiny(ref, adapter):
    """(the tiny configuration, the program's model, the weights, the
    reference's and the program's plain forward passes, each traced once
    a length)."""
    cfg = tiny_cfg()
    model, params = build(adapter, cfg)
    with jax.default_matmul_precision("highest"):
        plain = jax.jit(lambda p, ids, cast=None: ref.logits(cfg, p, ids, cast),
                        static_argnums=2)
        ours = jax.jit(lambda p, ids: model.apply(p, ids[None])[0])
    return cfg, model, params, plain, ours


def fresh(tiny):
    """(the tiny configuration, a model object of its own -- a test may
    hang a recorder on it -- and the shared weights)."""
    cfg, model, params, _, _ = tiny
    return cfg, arch.Phi4Flash(model.cfg), params


def reference_rows(tiny, seq):
    """The reference's logits for ``seq``, padded to one length for all
    the engine's tests (causal: padding cannot reach back; one trace)."""
    ids = np.zeros(MAX_SEQ + 6, np.int32)
    ids[:len(seq)] = seq
    return np.asarray(tiny[3](tiny[2], jnp.asarray(ids)))[:len(seq)]


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
#: 1 to 10: it reads 1e-6 to 4e-6 over these cases
SCAN_TOL = 3e-5


def tokens_of(seed, t, e=10, n=4, strong=False):
    """Random inputs of the recurrence: steps from a thousandth to ten
    (with ``strong``: every one over 5, decays of ``e^-80`` a token) and
    ``A`` from -1 to -16."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    c = jax.random.normal(ks[0], (t, e))
    dt = jnp.exp(jax.random.uniform(ks[1], (t, e), jnp.float32,
                                    np.log(5.0 if strong else 1e-3),
                                    np.log(10.0)))
    A = -jnp.exp(jax.random.uniform(ks[2], (n, e), jnp.float32, 0.0,
                                    np.log(16.0)))
    B, C = (jax.random.normal(k, (t, n)) for k in ks[3:5])
    D = jnp.linspace(0.5, 1.5, e)
    return (c, dt, A, B, C, D), jax.random.normal(ks[5], (n, e))


@pytest.mark.parametrize("strong", [False, True], ids=["dt_any", "dt_strong"])
@pytest.mark.parametrize("t,chunk", [(37, 8), (64, 16), (5, 64), (130, 64)])
def test_chunked_scan_equals_the_serial_scan(t, chunk, strong):
    """At lengths that are and are not a multiple of the chunk, and at
    decays whose product over a chunk underflows float32: the pairs
    compose as products of numbers at most 1, so nothing overflows."""
    x, h0 = tokens_of(t, t, strong=strong)
    if strong:
        assert float((x[1][:8, None, :] * x[2][None]).sum(0).max()) < -30
    want_y, want_h = selective_scan.serial(*x, h0)
    y, h = selective_scan.chunked(*x, h0, chunk=chunk)
    np.testing.assert_allclose(y, want_y, atol=SCAN_TOL, rtol=0)
    np.testing.assert_allclose(h, want_h, atol=SCAN_TOL, rtol=0)


def test_one_token_steps_equal_the_serial_scan():
    """``step`` repeated, two slots of which one is live: the live one
    follows the scan, the other keeps its state to the bit."""
    (c, dt, A, B, C, D), h0 = tokens_of(3, 21)
    want_y, want_h = selective_scan.serial(c, dt, A, B, C, D, h0)
    h = jnp.stack([h0, h0])
    live = jnp.asarray([True, False])
    two = lambda x: jnp.stack([x, x])
    for t in range(21):
        h, y = selective_scan.step(h, two(c[t]), two(dt[t]), A, two(B[t]),
                                   two(C[t]), D, live)
        np.testing.assert_allclose(y[0], want_y[t], atol=1e-5, rtol=0)
    np.testing.assert_allclose(h[0], want_h, atol=1e-5, rtol=0)
    assert bool(jnp.all(h[1] == h0))


@pytest.mark.parametrize("n", [1, 7, 16, 23])
def test_a_padded_bucket_leaves_the_state_of_n_tokens(n):
    """Positions ``>= n`` (garbage there on purpose) move neither the
    state nor the outputs before them."""
    (c, dt, A, B, C, D), h0 = tokens_of(5, 24)
    want_y, want_h = selective_scan.serial(c[:n], dt[:n], A, B[:n], C[:n], D,
                                           h0)
    y, h = selective_scan.chunked(c, dt, A, B, C, D, h0, n=jnp.int32(n),
                                  chunk=8)
    np.testing.assert_allclose(y[:n], want_y, atol=SCAN_TOL, rtol=0)
    np.testing.assert_allclose(h, want_h, atol=SCAN_TOL, rtol=0)


def test_the_scan_in_two_pieces_equals_one():
    """From the state the first piece left, the second goes on as if
    there had been no cut."""
    (c, dt, A, B, C, D), h0 = tokens_of(6, 40)
    y, h = selective_scan.chunked(c, dt, A, B, C, D, h0, chunk=8)
    y1, h1 = selective_scan.chunked(c[:13], dt[:13], A, B[:13], C[:13], D, h0,
                                    chunk=8)
    y2, h2 = selective_scan.chunked(c[13:], dt[13:], A, B[13:], C[13:], D, h1,
                                    chunk=8)
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), y, atol=SCAN_TOL,
                               rtol=0)
    np.testing.assert_allclose(h2, h, atol=SCAN_TOL, rtol=0)


# -- the layers, plainly ---------------------------------------------------------

def test_the_layer_map_is_the_published_one(adapter):
    """At the published depth: nine Mamba layers beside eight window
    layers and the full one, then seven GMUs and seven cross layers; 16
    hands on ``m``, 17 the rows; and the parameters are the published
    3.85 B."""
    cfg = files.load_config("Phi-4-mini-flash-reasoning")
    c = adapter.program_model(cfg).cfg
    assert c.recurrent_layers == tuple(range(0, 17, 2))
    assert c.window_layers == tuple(range(1, 16, 2))
    assert (c.memory_layer, c.full_layer) == (16, 17)
    assert c.layers_of("gmu") == tuple(range(18, 32, 2))
    assert c.cross_layers == tuple(range(19, 32, 2))
    assert c.row_layers == c.window_layers + (17,)
    assert (c.pair_heads, c.pair_width, c.window, c.vocab_size) \
        == (10, 128, 512, 200064)
    assert adapter.n_params(cfg) == 3_852_562_944
    assert cfg["reduced"] == {} and cfg["num_hidden_layers"] == 32
    tiny = adapter.program_model(tiny_cfg()).cfg
    assert [tiny.kind(i) for i in range(8)] == KINDS


@pytest.mark.parametrize("seed", [0, 1])
def test_forward_pass_equals_the_reference(adapter, tiny, seed):
    cfg, _, _, plain, ours = tiny
    _, params = build(adapter, cfg, seed)
    ids = jnp.asarray(ids_of(1, 27), jnp.int32)
    want = plain(params, ids)
    assert float(jnp.abs(want).max()) > 0.5     # logits that say something
    np.testing.assert_allclose(ours(params, ids), want, atol=TOL, rtol=0)


def test_a_lower_precision_is_told_apart(ref, tiny):
    """The reference in float8 lies far outside the tolerance the tests
    here hold the program to."""
    _, _, params, plain, _ = tiny
    ids = jnp.asarray(ids_of(1, 12), jnp.int32)
    gap = jnp.abs(plain(params, ids, ref.to_fp8) - plain(params, ids)).max()
    assert float(gap) > 100 * TOL


def _cut(params, path, to=0.0):
    """``params`` with the leaf at ``path`` replaced by ``to`` all over."""
    out = jax.tree_util.tree_map(lambda x: x, params)
    node = out
    for key in path[:-1]:
        node = node[key]
    node[path[-1]] = jnp.full_like(node[path[-1]], to)
    return out


#: what is cut -> the path of the leaf zeroed (``m`` is cut at its
#: source: ``d`` and the read-out of the memory layer cannot be zeroed
#: apart, so the GMU's only other input, its gate's weights, stands for
#: it below, and ``m`` itself is cut in the program: the next test)
MIXERS = {
    "a_mamba_layer": ("layer_2", "mamba", "w_out", "w"),
    "a_ring_layer": ("layer_3", "attn", "wo", "w"),
    "the_full_layer": ("layer_5", "attn", "wo", "w"),
    "a_gmu": ("layer_6", "gmu", "w_out", "w"),
    "a_cross_layer": ("layer_7", "attn", "wo", "w"),
    "a_lam_of_a_ring_layer": ("layer_1", "attn", "lam"),
    "a_lam_of_the_cross_layer": ("layer_7", "attn", "lam"),
    "the_qkv_bias": ("layer_5", "attn", "w_qkv", "b"),
    "the_cross_query_bias": ("layer_7", "attn", "wq", "b"),
    "an_output_bias": ("layer_3", "attn", "wo", "b"),
    "the_convolution_bias": ("layer_0", "mamba", "conv_b"),
    "the_step_bias": ("layer_4", "mamba", "b_dt"),
    "the_skip_d": ("layer_4", "mamba", "d"),
    "the_sub_norm": ("layer_7", "attn", "sub_norm", "scale"),
}


@pytest.mark.parametrize("what", sorted(MIXERS))
def test_every_mixer_moves_the_logits(tiny, what):
    """No kind of layer, no ``lam``, no bias is a pass-through at these
    weights: with it zeroed the reference's logits move, and the program
    moves with them (so neither side left it out)."""
    _, _, params, plain, ours = tiny
    ids = jnp.asarray(ids_of(2, 12), jnp.int32)
    want = plain(params, ids)
    cut = _cut(params, MIXERS[what])
    moved = plain(cut, ids)
    assert float(jnp.abs(moved - want).max()) > 0.02, what
    np.testing.assert_allclose(ours(cut, ids), moved, atol=TOL, rtol=0)


def test_the_memory_moves_the_logits_and_comes_from_the_last_mamba_layer(
        tiny):
    """``m`` is the memory layer's scan output: with that layer's ``C``
    read-out and skip cut (``w_x``'s last columns and ``d``: ``y = 0``,
    so ``m = 0``) the GMU adds nothing and the logits are those of a
    model whose GMU is cut as well; with an EARLIER Mamba layer's cut
    instead they are not."""
    _, _, params, plain, _ = tiny
    ids = jnp.asarray(ids_of(2, 12), jnp.int32)

    def silent(params, li):
        p = params[f"layer_{li}"]["mamba"]
        out = _cut(params, (f"layer_{li}", "mamba", "d"))
        w = p["w_x"]["w"]
        out[f"layer_{li}"]["mamba"]["w_x"] = {"w": w.at[:, -4:].set(0)}
        return out

    no_gmu = lambda params: _cut(params, ("layer_6", "gmu", "w_out", "w"))
    want = plain(params, ids)
    quiet = plain(silent(params, 4), ids)
    assert float(jnp.abs(quiet - want).max()) > 0.02
    np.testing.assert_allclose(plain(no_gmu(silent(params, 4)), ids), quiet,
                               atol=1e-5, rtol=0)
    other = plain(silent(params, 2), ids)
    assert float(jnp.abs(plain(no_gmu(silent(params, 2)), ids)
                         - other).max()) > 0.02


def test_the_window_sees_itself_and_the_five_before_it(tiny):
    """With the full layer and the cross layer cut and Mamba made deaf
    (its input projection zero: a layer that mixes no positions), the
    only way one position reaches another is a window layer: two window
    layers of 6 reach 10 back.  The last row's logits move with the row
    10 before it and not with the one 11 before; in the program as in
    the reference."""
    _, _, params, plain, ours = tiny
    for li, leaf in ((5, ("attn", "wo")), (7, ("attn", "wo"))):
        for part in ("w", "b"):
            params = _cut(params, (f"layer_{li}",) + leaf + (part,))
    for li in (0, 2, 4):
        params = _cut(params, (f"layer_{li}", "mamba", "w_in", "w"))
    base = ids_of(3, 20)
    last = lambda fn, ids: np.asarray(fn(params, jnp.asarray(ids, jnp.int32))
                                      )[-1]
    for fn in (plain, ours):
        want = last(fn, base)
        inside, outside = list(base), list(base)
        inside[19 - 2 * (WINDOW - 1)] = (base[9] + 1) % 96
        outside[19 - 2 * (WINDOW - 1) - 1] = (base[8] + 1) % 96
        assert np.abs(last(fn, inside) - want).max() > 1e-3
        assert np.abs(last(fn, outside) - want).max() == 0.0


# -- through the engine ---------------------------------------------------------

@pytest.mark.parametrize("prompt_len,new", [(5, 22), (19, 9), (24, 6)],
                         ids=["decode_mostly", "prefill_two_buckets",
                              "prefill_mostly"])
def test_engine_prefill_then_decode_equals_the_full_forward_pass(
        tiny, monkeypatch, prompt_len, new):
    """Also the test that the last-row prefill is exact: the engine's
    first token comes from a cross-decoder that ran over ONE row, the
    reference's from every layer over every row.  Contexts run past the
    window of 6, so rings wrap."""
    # (chunks small enough that a prefill's scan walks several)
    monkeypatch.setattr(selective_scan, "CHUNK", 4)
    cfg, model, params = fresh(tiny)
    rows = recording(model)
    eng = engine(model, params)
    prompt = ids_of(7, prompt_len)
    eng.submit("a", prompt, new)
    done = [e for e in eng.drain() if e["kind"] == "done"][0]
    want = reference_rows(tiny, prompt + done["tokens"])
    slot = 0                                     # the first slot handed out
    got = [rows[0][0]] + [r[slot] for r in rows[1:]]
    assert len(got) == new
    for i, row in enumerate(got):
        np.testing.assert_allclose(row, want[prompt_len - 1 + i], atol=TOL,
                                   rtol=0, err_msg=f"token {i}")
        assert done["tokens"][i] == int(np.argmax(row))


def test_a_prefill_in_two_pieces_equals_one(tiny):
    """The prefill program from ``start > 0`` goes on from the slot's
    own state, tail, rings and rows: 9 tokens and then 10 leave the
    slot, and choose the token, that 19 at once do; and a prefill from
    ``start == 0`` into a slot that held another request starts from
    nothing."""
    cfg, model, params = fresh(tiny)
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
    (w1, f1, s1), (_, _, t1) = k1, v1
    (w2, f2, s2), (_, _, t2) = k2, v2
    for layer in range(3):          # (an array a Mamba layer, [1, slots, ...])
        assert float(jnp.abs(s1[layer][:, 1]).max()) > 0.01
        np.testing.assert_allclose(s2[layer][:, 1], s1[layer][:, 1],
                                   atol=1e-4, rtol=1e-5)
        np.testing.assert_allclose(t2[layer][:, 1], t1[layer][:, 1],
                                   atol=1e-4, rtol=1e-5)
        # the other slot was never touched
        assert not bool(jnp.any(s2[layer][:, 0]))
        assert not bool(jnp.any(t2[layer][:, 0]))
    np.testing.assert_allclose(f2[:, 1, :, :19], f1[:, 1, :, :19], atol=1e-4,
                               rtol=0)
    # the rings hold the last six positions, 13 .. 18, wherever they came
    np.testing.assert_allclose(w2[:, 1], w1[:, 1], atol=1e-4, rtol=0)


def test_a_reused_slot_gives_what_a_fresh_engine_gives(tiny):
    """One slot, a long request and then a short one: the second finds
    the first's state, tail, rings and rows in its slot and must not see
    them -- its logits are the reference's, and its tokens a fresh
    engine's."""
    cfg, model, params = fresh(tiny)
    rows = recording(model)
    eng = engine(model, params, slots=1)
    first, second = ids_of(11, 21), ids_of(12, 4)
    eng.submit("long", first, 10)
    eng.submit("short", second, 8)
    done = {e["rid"]: e["tokens"] for e in eng.drain() if e["kind"] == "done"}
    want = reference_rows(tiny, second + done["short"])
    got = [r[0] for r in rows[-8:]]       # (a prefill's row is [1, vocab])
    for i, row in enumerate(got):
        np.testing.assert_allclose(row, want[len(second) - 1 + i], atol=TOL,
                                   rtol=0, err_msg=f"token {i}")
    alone = engine(model, params, slots=1)
    alone.submit("short", second, 8)
    assert [e for e in alone.drain() if e["kind"] == "done"][0]["tokens"] \
        == done["short"]


def test_a_slot_that_is_not_live_keeps_state_tail_and_rows(tiny):
    """A decode step for slot 0 alone: slot 1's state, tails, rings and
    rows come back to the bit, slot 0's all move."""
    cfg, model, params = fresh(tiny)
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
    assert len(jax.tree_util.tree_leaves((k, v))) == 2 * (2 + 3)
    for was, now in zip(jax.tree_util.tree_leaves(before),
                        jax.tree_util.tree_leaves((k, v))):
        now = np.asarray(now)
        assert np.array_equal(now[:, 1], was[:, 1])
        assert not np.array_equal(now[:, 0], was[:, 0])
    toks, says = caches.read(out, np.asarray([7]))
    assert toks.shape == (2,) and says["state_slots_live"] == 1


def test_staggered_requests_over_reused_slots(tiny):
    """Requests admitted mid-flight at different positions, more of them
    than slots: each one's tokens are what the reference puts first, by a
    margin or not at all (a tie at float32's rounding may go either
    way)."""
    cfg, model, params = fresh(tiny)
    eng = engine(model, params, slots=2)
    prompts = {f"r{i}": ids_of(20 + i, n) for i, n in
               enumerate((3, 19, 9, 26, 12))}
    for rid, p in prompts.items():
        eng.submit(rid, p, MAX_SEQ - len(p) if len(p) > 20 else 6)
    done = {e["rid"]: e["tokens"] for e in eng.drain() if e["kind"] == "done"}
    assert set(done) == set(prompts)
    for rid, toks in done.items():
        lg = reference_rows(tiny, prompts[rid] + toks)
        at = len(prompts[rid]) - 1
        for i, t in enumerate(toks):
            assert lg[at + i].max() - lg[at + i, t] <= TOL, (rid, i)


def test_a_request_that_ends_on_eos_leaves_the_next_a_clean_slot(tiny):
    """The loop runs one step ahead: when a request ends on ``eos_id``
    the step behind it has already been dispatched for its slot, and the
    program leaves that slot alone (``live``); the next request into the
    slot then decodes what the reference decodes."""
    cfg, model, params = fresh(tiny)
    probe = engine(model, params, slots=1)
    probe.submit("a", ids_of(15, 7), 6)
    toks = [e for e in probe.drain() if e["kind"] == "done"][0]["tokens"]
    eng = engine(model, params, slots=1, eos_id=toks[2])
    eng.submit("a", ids_of(15, 7), 6)
    eng.submit("b", ids_of(16, 9), 5)
    done = {e["rid"]: e["tokens"] for e in eng.drain() if e["kind"] == "done"}
    assert done["a"] == toks[:toks.index(toks[2]) + 1]
    lg = reference_rows(tiny, ids_of(16, 9) + done["b"])
    for i, t in enumerate(done["b"]):
        if t == toks[2]:
            break
        assert lg[8 + i].max() - lg[8 + i, t] <= TOL, i


# -- pages that are never whole -------------------------------------------

def test_the_engine_looks_up_no_prefix_and_commits_nothing(tiny, monkeypatch):
    """The family is unpaged (a Mamba state cannot be restored from a
    page): the pool says no prefix of it is reusable, and the engine
    neither reserves, looks up nor commits a page.  (How many layers
    the spec counts a page over is then read by nothing.)"""
    cfg, model, params = fresh(tiny)
    assert PageSpec.for_model(model.cfg, page_tokens=PAGE).unpaged
    assert PageSpec.for_model(arch.Phi4FlashConfig(), page_tokens=256).unpaged
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


def test_read_says_what_the_step_moved(tiny, monkeypatch):
    """Behind the tokens, on the ``kf:serve.decode_read`` span of the step
    they belong to, counted A READING LAYER: a live slot at context ``c``
    owes ``c`` rows of the slab twice (the full layer and the cross
    layer) and ``min(c, 6)`` of each of two rings, and writes three; XLA's
    form reads every row of every slot; every slot's state is moved."""
    cfg, model, params = fresh(tiny)
    eng = engine(model, params, slots=3)
    spans = _lookahead.record_spans(monkeypatch)

    def last(name):
        return [s for s in spans if s.name == name][-1].attrs

    eng.submit("a", ids_of(3, 9), 4)
    eng.step()                          # admits a, dispatches its step
    eng.step()                          # the next step, then that one read
    r = last("decode_read")
    assert r["kv_rows_live"] == 2 * 10 + 2 * 6 and r["kv_rows_live_full"] == 20
    assert r["kv_rows_written"] == 3 and r["kv_attn_kernel"] == 0
    assert r["kv_rows_read"] == 3 * (2 * MAX_SEQ + 2 * WINDOW)
    # two pairs of 16 in K and in V, float32 here
    assert r["kv_row_bytes"] == 2 * 2 * 16 * 4
    assert r["state_slots_live"] == 1 and r["state_slots_read"] == 3
    # three Mamba layers x three slots x (4 x 128 float32 and a tail of
    # 3 x 128 in the compute dtype, float32 here)
    assert r["state_bytes_read"] == 3 * 3 * (4 * 128 * 4 + 3 * 128 * 4)
    assert r["discarded"] == 0
    eng.submit("b", ids_of(4, 3), 4)
    eng.step()                          # admits b, dispatches a and b; reads
    eng.step()                          # a's last token is in flight: b alone
    assert last("decode_read")["state_slots_live"] == 2
    assert last("decode_read")["kv_rows_live"] == (2 * 12 + 2 * 6) \
        + (2 * 4 + 2 * 4)
    eng.step()
    assert last("decode_read")["state_slots_live"] == 1


# -- the differential layer through the kernel that walks live tiles --------
#: wide enough for ``ops/pallas/decode_attention.py``: 8 query heads over
#: 4 key/value heads of 64 (two pairs of 128), a window of 128 and a slab
#: of three tiles of 128 positions
WIDE_SEQ, WIDE_TILE = 384, 128

says_tpu = _lookahead.says_tpu


def wide_model(adapter):
    """(the program's model in bfloat16, its weights)."""
    cfg = tiny_cfg(hidden_size=512, num_attention_heads=8,
                   num_key_value_heads=4, sliding_window=WIDE_TILE,
                   n_positions=WIDE_SEQ)
    model = adapter.program_model(cfg)
    return model, roughened(jax.jit(model.init)(jax.random.PRNGKey(5)))


def walked(n, readers=2, rings=2):
    tiles = lambda x: -(-x // WIDE_TILE) * WIDE_TILE
    return sum(readers * tiles(x) + rings * tiles(min(x, WIDE_TILE))
               for x in n)


def test_the_kernels_layout_equals_the_xla_form(adapter):
    """One decode step over a filled cache with the kernel interpreted
    and with XLA's form: the same tokens, states and new rows to
    bfloat16's rounding, and behind the tokens the rows each form read
    -- one ``decode_attn`` call a READING layer (two rings, the full
    layer, the cross layer) with the scale of a 64-wide head."""
    def step(kernel):
        with pytest.MonkeyPatch.context() as steer:
            if kernel:
                says_tpu(steer)
            model, params = wide_model(adapter)
            caches = model.serve_caches(4, WIDE_SEQ)
            assert caches.kv_attn_kernel == int(kernel)
            assert caches.attn_tiles == ((WIDE_TILE,) * 2 if kernel
                                         else (None, None))
            k, v = caches.new_slabs()
            r = iter(jax.random.split(jax.random.PRNGKey(6), 4))
            fill = lambda a: jax.random.normal(next(r), a.shape, jnp.float32
                                               ).astype(a.dtype)
            k, v = (fill(k[0]), fill(k[1]), k[2]), (fill(v[0]), fill(v[1]),
                                                    v[2])
            args = (params, k, v, jnp.asarray([5, 9, 11, 2], jnp.int32),
                    jnp.asarray([4, 127, 128, 300], jnp.int32),
                    jnp.asarray([True, True, False, True]))
            text = str(jax.make_jaxpr(caches.decode)(*args))
            assert text.count("name=decode_attn") == (2 if kernel else 0)
            assert text.count("jit[name=_call ") == 4 * kernel
            # (and the rows by ``row_write.py``, rings and slab, beside
            # ``caches.write_rows``' window updates without it)
            assert text.count("name=row_write") == (2 if kernel else 0)
            assert ("dynamic_update_slice" in text) != kernel
            (_, kf, state), _, out = jax.jit(caches.decode)(*args)
        return ([np.asarray(x, np.float32) for x in state],
                np.asarray(kf, np.float32), np.asarray(out))

    (got, rows, out), (want, rows_x, plain) = step(True), step(False)
    for a, b in zip(got, want):
        assert np.abs(a - b).max() < 0.05 * np.abs(b).max()
    assert np.abs(rows - rows_x).max() < 0.05 * np.abs(rows_x).max()
    assert out[[0, 1, 3]].tolist() == plain[[0, 1, 3]].tolist()
    assert out[4:].tolist() == [3, walked([5, 128, 0, 301])]
    assert walked([5, 128, 0, 301]) == 2 * (128 + 128 + 384) + 2 * 3 * 128
    assert plain[4:].tolist() == [3, 4 * (2 * WIDE_SEQ + 2 * WIDE_TILE)]


def test_the_paired_layout_is_the_two_softmax_maps():
    """The kernel's arithmetic by hand: queries laid ``[q, 0]`` and ``[0,
    q]`` over rows ``[k1; k2]`` give the scores of the 64-wide products
    (the zeros add 0.0), so the attention over pairs is each map's own
    softmax times both value heads -- in XLA's form and, with the
    ``scale`` keyword, in the kernel's (interpreted; under its default
    scale, a 128-wide head's, the same call reads far off)."""
    from kungfu_tpu.models.cohere2_moe import attention
    from kungfu_tpu.ops.pallas.decode_attention import decode_attn

    r = jax.random.split(jax.random.PRNGKey(0), 3)
    bf = lambda x: x.astype(jnp.bfloat16)
    q = bf(jax.random.normal(r[0], (1, 1, 8, 64)))          # [B, Q, H, D]
    k = bf(jax.random.normal(r[1], (1, 1, 2, 128, 128)))    # [L, B, G', S, 2 D]
    v = bf(jax.random.normal(r[2], (1, 1, 2, 128, 128)))
    wide, n = arch.paired_queries(q, 2), 9
    see = (jnp.arange(128) < n)[None, None, None, None]
    xla = attention(wide, k[0], v[0], see, 0.125)[0, 0]
    fused = decode_attn(wide[:, 0], k, v, 0, jnp.asarray([n]), tile=128,
                        scale=0.125, interpret=True)[0]
    unscaled = decode_attn(wide[:, 0], k, v, 0, jnp.asarray([n]), tile=128,
                           interpret=True)[0]
    f32 = lambda x: np.asarray(x, np.float32)
    for h in range(8):
        j, half = h // 4, h % 2
        keys = f32(k[0, 0, j, :n, 64 * half:64 * half + 64])
        p = jax.nn.softmax(keys @ f32(q[0, 0, h]) / 8.0)
        want = p @ f32(v[0, 0, j, :n])
        for got in (xla, fused):
            np.testing.assert_allclose(f32(got[j, h % 4]), want, atol=0.03,
                                       rtol=0)
        assert np.abs(f32(unscaled[j, h % 4]) - want).max() > 0.1


def test_the_engine_through_the_kernel_serves_xlas_tokens(adapter):
    """Two requests through ``InferenceEngine``, one whose context
    crosses a tile's edge (and the window's) while it decodes, with the
    kernel interpreted and with XLA's form: the same tokens, and on
    every ``kf:serve.decode_read`` span which form ran and rows read
    that are whole tiles under the kernel and every row without it."""
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
            eng.submit("a", ids_of(26, 5), 4)
            eng.submit("b", ids_of(27, 126), 4)     # decodes rows 126 .. 128
            done = {e["rid"]: e["tokens"] for e in eng.drain()
                    if e["kind"] == "done"}
        return done, [s.attrs for s in spans if s.name == "decode_read"]

    (kernel, reads), (xla, plain) = serve(True), serve(False)
    assert len(kernel["a"]) == len(kernel["b"]) == 4
    # (bfloat16 at random weights: the two forms round apart, and a
    # near-tie may go either way; the first tokens come from the prefill,
    # which is one form)
    assert kernel["a"][0] == xla["a"][0] and kernel["b"][0] == xla["b"][0]
    assert reads and all(r["kv_attn_kernel"] == 1 for r in reads)
    pairs = [(r["kv_rows_live"], r["kv_rows_read"]) for r in reads]
    live = lambda *cs: sum(2 * c + 2 * min(c, WIDE_TILE) for c in cs)
    assert pairs == [(live(6), walked([6])), (live(7, 127), walked([7, 127])),
                     (live(8, 128), walked([8, 128])),
                     (live(129), walked([129]))]
    assert walked([129]) == 2 * 256 + 2 * 128
    assert plain and all(
        r["kv_attn_kernel"] == 0
        and r["kv_rows_read"] == 3 * (2 * WIDE_SEQ + 2 * WIDE_TILE)
        for r in plain)


# -- a decode step's rows through the kernel that writes them ---------------
@pytest.mark.parametrize("dtype,layer,pos,live", [
    ("bfloat16", 1, [0, 15, 16, 63, 37], [1, 1, 0, 1, 1]),
    ("bfloat16", 0, [31, 32, 33, 47, 48], [1, 1, 1, 1, 1]),
    ("bfloat16", 2, [5, 5, 5, 5, 5], [0, 0, 0, 0, 0]),
    ("float32", 1, [7, 8, 9, 56, 63], [1, 0, 1, 1, 1]),
], ids=["tile_edges", "all_live", "none_live", "four_bytes"])
def test_the_row_kernel_writes_what_the_window_updates_write(dtype, layer,
                                                             pos, live):
    """``ops/pallas/row_write.py`` (interpreted) against
    ``caches.write_rows``: the same two slabs bit for bit, rows on a
    tile's first and last sublane, a slot that is not live written back
    as it was, and no other layer, slot or row touched."""
    from kungfu_tpu.ops.pallas import row_write
    from kungfu_tpu.serve import caches

    r = jax.random.split(jax.random.PRNGKey(3), 4)
    draw = lambda key, shape: jax.random.normal(key, shape, jnp.float32
                                                ).astype(dtype)
    k, v = draw(r[0], (3, 5, 2, 64, 128)), draw(r[1], (3, 5, 2, 64, 128))
    kn, vn = draw(r[2], (5, 2, 1, 128)), draw(r[3], (5, 2, 1, 128))
    pos, live = jnp.asarray(pos, jnp.int32), jnp.asarray(live, bool)
    got_k, got_v = row_write.write_rows(k, v, layer, kn, vn, pos, live,
                                        interpret=True)
    at = caches.row_windows(pos, 64, live, row_write.window(dtype))
    want_k = caches.write_rows(k, layer, kn, at)
    want_v = caches.write_rows(v, layer, vn, at)
    assert got_k.dtype == k.dtype and bool(jnp.all(got_k == want_k))
    assert bool(jnp.all(got_v == want_v))
    changed = np.asarray(jnp.any(got_k != k, axis=(2, 4)))   # [L, B, S]
    hits = np.zeros_like(changed)
    for b in range(5):
        hits[layer, b, int(pos[b])] = bool(live[b])
    assert (changed == hits).all()


def test_the_row_kernel_refuses_a_slab_it_does_not_tile():
    from kungfu_tpu.ops.pallas import row_write

    assert row_write.window("bfloat16") == 16
    assert row_write.fits(512, 128, "bfloat16")
    assert not row_write.fits(512, 64, "bfloat16")
    assert not row_write.fits(24, 128, "bfloat16")
    k = jnp.zeros((1, 2, 2, 32, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="not ones this kernel writes"):
        row_write.write_rows(k, k, 0, k[0, :, :, :1], k[0, :, :, :1],
                             jnp.zeros(2, jnp.int32), jnp.ones(2, bool))


def test_the_engine_serves_it_without_knowing_it():
    """``engine.py`` imports no model and tests for no class
    (tests/test_cohere2_moe.py reads its source); this model's answer to
    ``serve_caches`` has what the engine asks of a cache whose pages are
    never whole: three kinds of content a slot."""
    src = open(os.path.join(ROOT, "kungfu_tpu", "serve", "engine.py")).read()
    assert "phi4" not in src.lower() and "sambay" not in src.lower()
    model = files.load_adapter("phi4flash").program_model(tiny_cfg())
    caches = model.serve_caches(3, MAX_SEQ)
    for name in ("new_slabs", "new_out", "prefill", "decode", "read",
                 "empty_pages", "prefill_flops", "decode_flops"):
        assert callable(getattr(caches, name)), name
    (ring, rows, state), (ring_v, rows_v, tails) = caches.empty_pages(8)
    assert ring.shape == ring_v.shape == (2, 2, WINDOW, 16)
    assert rows.shape == rows_v.shape == (1, 2, 8, 16)
    assert [(x.shape, x.dtype) for x in state] == [((1, 4, 128), np.float32)] * 3
    assert [x.shape for x in tails] == [(1, 3, 128)] * 3
    (kw, kf, s), (vw, vf, t) = caches.new_slabs()
    assert kw.shape == vw.shape == (2, 3, 2, WINDOW, 16)
    assert kf.shape == vf.shape == (1, 3, 2, MAX_SEQ, 16)
    # an array a Mamba layer (serve/recurrent.py says why), the channels
    # along the lanes
    assert [(x.shape, x.dtype) for x in s] == [
        ((1, 3, 4, 128), jnp.float32)] * 3
    assert [(x.shape, x.dtype) for x in t] == [
        ((1, 3, 3, 128), jnp.bfloat16)] * 3
    assert len(caches.new_out()) == 3 + 2
    assert caches.decode_flops([5, 9]) > caches.decode_flops([5, 8]) > 0
    assert caches.prefill_flops(8, 4) > caches.prefill_flops(8) > 0
    # the cross-decoder is counted once a prefill, not once a token
    lower, upper, _, _ = model._parts
    assert caches.prefill_flops(9) - caches.prefill_flops(8) < lower + upper


def test_importing_the_models_loads_no_kernel_package():
    """``import kungfu_tpu.models`` (every serving cell's set-up pays
    it) pulls in neither Pallas nor this family's caches: a fresh
    interpreter's modules, read after the import."""
    import subprocess

    code = ("import sys; sys.path.insert(0, %r); import kungfu_tpu.models; "
            "import kungfu_tpu.models.phi4flash; "
            "bad = [m for m in sys.modules if 'pallas' in m "
            "or m in ('kungfu_tpu.serve.sambay', "
            "'kungfu_tpu.serve.recurrent')]; print(bad)" % ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[]"


def test_one_initialisation_with_the_stated_types(adapter):
    """The adapter's weights ARE the program's ``init``; the state-space
    parameters lie where the published layer puts them, and the leaves
    have the stated types and count."""
    cfg = tiny_cfg()
    model = adapter.program_model(cfg)
    key = jax.random.PRNGKey(3)
    ours, theirs = adapter.init_params(cfg, key), model.init(key)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a.dtype == b.dtype and bool(jnp.all(a == b)),
        ours, theirs))
    std = lambda w: float(jnp.std(w.astype(jnp.float32)))
    mamba, attn = ours["layer_2"]["mamba"], ours["layer_1"]["attn"]
    for w in (mamba["w_in"]["w"], attn["w_qkv"]["w"], attn["wo"]["w"],
              ours["layer_6"]["gmu"]["w_in"]["w"], ours["embed"]["table"]):
        assert std(w) == pytest.approx(0.15, rel=0.1)
    assert std(attn["lam"]) == pytest.approx(0.1, rel=0.5)     # (32 numbers)
    np.testing.assert_allclose(jnp.exp(mamba["a_log"])[:, 0], [1, 2, 3, 4],
                               rtol=1e-6)
    step = jax.nn.softplus(mamba["b_dt"])
    assert 1e-3 <= float(step.min()) and float(step.max()) <= 0.1 + 1e-6
    assert bool(jnp.all(mamba["d"] == 1)) and not bool(jnp.any(mamba["conv_b"]))
    for leaf in (mamba["a_log"], mamba["b_dt"], mamba["d"], attn["lam"],
                 attn["sub_norm"]["scale"], ours["layer_1"]["ln_mlp"]["bias"]):
        assert leaf.dtype == jnp.float32
    for leaf in (mamba["conv"], mamba["conv_b"], attn["w_qkv"]["b"],
                 ours["layer_7"]["attn"]["wq"]["b"], ours["embed"]["table"]):
        assert leaf.dtype == jnp.bfloat16
    assert "head" not in ours           # tied: the embedding is the head
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(ours))
    assert n == adapter.n_params(cfg)
