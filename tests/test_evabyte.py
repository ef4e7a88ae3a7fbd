"""``evabyte`` (EvaByte: EVA attention) at a tiny size on the CPU --
hidden 64, 4 heads of 16, windows of 32 in chunks of 4, 3 layers --
against the benchmark's plain reference (``kfbench/reference/
evabyte.py``, written from the equations), on logits and not tokens: the
plain forward pass on all eight heads, plain causal attention up to a
window, the engine's prefill and decode through ``PooledCaches`` across
chunk and window edges, what ``mu`` and ``phi`` can and cannot move,
slots reused, buckets padded and slots left out of a step, requests
admitted steps apart, what a decode step says of the rows it read, the
decode step through the kernel that walks only a slot's live exact rows
and chunk rows (``ops/pallas/decode_attention.py``, interpreted) against
XLA's form, and the parameter count at the cell's configuration.

The weights are the adapter's (bfloat16 leaves from a seed), computed in
float32 at ``highest`` on both sides, so the two agree to rounding.
"""

import dataclasses
import math
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
from kungfu_tpu.models import evabyte as arch  # noqa: E402
from kungfu_tpu.serve.engine import InferenceEngine  # noqa: E402
from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec  # noqa: E402

WINDOW, CHUNK, MAX_SEQ, PAGE = 32, 4, 128, 8
#: logits reach 12; the forward pass and the engine read 2e-5 to 6e-5
#: (float32 at ``highest`` on both sides)
TOL = 3e-4


def tiny_cfg(**over):
    """The configuration file's keys at the tiny size."""
    return dict(dict(
        vocab_size=320, hidden_size=64, num_hidden_layers=3,
        num_hidden_layers_published=3, num_attention_heads=4,
        num_key_value_heads=4, intermediate_size=96, chunk_size=CHUNK,
        window_size=WINDOW, num_pred_heads=8, rms_norm_eps=1e-5,
        rope_theta=100000, init_std=0.3, n_positions=MAX_SEQ,
        max_position_embeddings=MAX_SEQ), **over)


@pytest.fixture(scope="module")
def ref():
    return files.load_reference("evabyte")


@pytest.fixture(scope="module")
def adapter():
    return files.load_adapter("evabyte")


@pytest.fixture(scope="module")
def built(adapter):
    """(the configuration, the program's model in float32, the adapter's
    weights) -- one model for the module; a test that records logits
    takes a model of its own (:func:`fresh`)."""
    cfg = tiny_cfg()
    params = jax.jit(lambda k: adapter.init_params(cfg, k))(
        jax.random.PRNGKey(0))
    return cfg, fresh(adapter, cfg), params


def fresh(adapter, cfg):
    return arch.EvaByte(dataclasses.replace(
        adapter.program_model(cfg).cfg, dtype="float32"))


def engine(model, params, slots=2, page=PAGE, eos_id=None):
    """(A pool of two pages: the engine reserves none for this family.)"""
    return InferenceEngine(
        model, params, max_batch=slots, max_seq=MAX_SEQ, eos_id=eos_id,
        pool=KVCachePool(PageSpec.for_model(model.cfg, page_tokens=page),
                         capacity_pages=2))


def recording(model):
    """``model`` with every next-byte logits row the jitted programs
    compute kept, in the order computed."""
    rows, plain = [], model.next_logits

    def next_logits(params, h):
        out = plain(params, h)
        jax.debug.callback(lambda x: rows.append(np.asarray(x)), out)
        return out

    model.next_logits = next_logits
    return rows


def ids_of(seed, n):
    return np.random.default_rng(seed).integers(0, 320, n).tolist()


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- (a), (b): the plain forward pass ----------------------------------------

def test_plain_forward_equals_the_reference_on_all_eight_heads(ref, built):
    """Two and a half windows: the last query reads eight exact rows and
    the sixteen chunk rows of two closed windows."""
    cfg, model, params = built
    ids = jnp.asarray(ids_of(0, 80), jnp.int32)
    got = np.asarray(model.apply(params, ids[None]))[0]
    want = np.asarray(ref.all_logits(cfg, params, ids))
    assert got.shape == want.shape == (80, 8, 320)
    assert np.abs(want).max() > 5
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # head 0 is what the engine decodes and what ``logits`` gives
    np.testing.assert_allclose(
        np.asarray(ref.logits(cfg, params, ids)), want[:, 0], atol=1e-6,
        rtol=0)


def plain_causal_logits(cfg, params, ids):
    """The same weights under plain causal softmax attention over every
    position, with no window, chunk, ``mu`` or ``phi``: [S, heads x ids]."""
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    s, h, d = len(ids), cfg["num_attention_heads"], 16
    norm = lambda p, x: x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + 1e-5) * (1 + p["g"])
    angles = jnp.arange(s)[:, None] * 100000.0 ** (
        -jnp.arange(d // 2) / (d // 2))
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]

    def rot(x):
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    x = f32(params["embed"]["table"])[jnp.asarray(ids)]
    for li in range(cfg["num_hidden_layers"]):
        lp = params[f"layer_{li}"]
        y = norm(lp["ln_attn"], x)
        q, k, v = (jnp.matmul(y, f32(lp[n]["w"])).reshape(s, h, d)
                   for n in ("wq", "wk", "wv"))
        scores = jnp.einsum("qhd,khd->hqk", rot(q), rot(k)) / math.sqrt(d)
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
        x = x + jnp.matmul(o.reshape(s, h * d), f32(lp["wo"]["w"]))
        y = norm(lp["ln_ffn"], x)
        x = x + jnp.matmul(jax.nn.silu(jnp.matmul(y, f32(lp["gate"]["w"])))
                           * jnp.matmul(y, f32(lp["up"]["w"])),
                           f32(lp["down"]["w"]))
    return jnp.matmul(norm(params["ln_f"], x), f32(params["head"]["w"]))


@pytest.mark.parametrize("n", [5, WINDOW], ids=["inside", "a_whole_window"])
def test_up_to_a_window_it_is_plain_causal_attention(ref, built, n):
    cfg, model, params = built
    ids = ids_of(1, n)
    want = np.asarray(plain_causal_logits(cfg, params, ids)).reshape(n, 8, 320)
    got = np.asarray(model.apply(params, jnp.asarray(ids)[None]))[0]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    np.testing.assert_allclose(
        np.asarray(ref.all_logits(cfg, params, jnp.asarray(ids))), want,
        atol=TOL, rtol=0)
    # ... and one position further it no longer is
    ids = ids_of(1, WINDOW + 1)
    past = np.asarray(plain_causal_logits(cfg, params, ids))[-1]
    here = np.asarray(model.apply(params, jnp.asarray(ids)[None]))[0, -1]
    assert np.abs(here.reshape(-1) - past).max() > 100 * TOL


# -- (c): through the engine --------------------------------------------------

@pytest.mark.parametrize("new", [6, 40, 70], ids=[
    "over_a_chunks_edge", "over_a_windows_edge", "over_two_windows_edges"])
@pytest.mark.parametrize("prompt_len", [10, 12, WINDOW], ids=[
    "from_mid_chunk", "from_a_chunks_edge", "from_a_windows_edge"])
def test_engine_prefill_then_decode_equals_the_full_forward_pass(
        ref, adapter, built, prompt_len, new):
    """Every served position's logits are the reference's: a chunk the
    prefill left open is pooled by the decode step that completes it,
    from rows of both; a window that closes hands its chunk rows to the
    next position and takes its exact rows away."""
    cfg, _, params = built
    model = fresh(adapter, cfg)
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


# -- (d): what the pooling's parameters can reach ----------------------------

@pytest.mark.parametrize("which", ["mu", "phi"])
def test_the_pooling_moves_no_logit_before_a_window_has_closed(built, which):
    cfg, model, params = built
    ids = jnp.asarray(ids_of(2, 2 * WINDOW + 5), jnp.int32)[None]
    moved = jax.tree_util.tree_map(lambda x: x, params)
    for li in range(3):
        moved[f"layer_{li}"] = dict(
            params[f"layer_{li}"],
            **{which: params[f"layer_{li}"][which] + 0.5})
    was = np.asarray(model.apply(params, ids))[0]
    now = np.asarray(model.apply(moved, ids))[0]
    assert np.array_equal(now[:WINDOW], was[:WINDOW])
    for at in (WINDOW, 2 * WINDOW - 1, 2 * WINDOW + 4):
        assert np.abs(now[at] - was[at]).max() > 100 * TOL, at


def test_a_chunk_row_of_the_open_window_is_seen_by_no_query(built):
    """Forty positions: window 1 is open and two of its chunks complete.
    Whatever stands in the chunk rows of the open window and after -- as
    it would in a slot another request has used -- the next step's token
    and every row it writes are the same; a chunk row of the CLOSED
    window moves them."""
    cfg, model, params = built
    caches = model.serve_caches(1, MAX_SEQ)
    ids = np.zeros(64, np.int32)
    ids[:40] = ids_of(3, 40)
    i32 = jnp.int32
    k, v, tok = jax.jit(caches.prefill)(
        params, *caches.new_slabs(), jnp.asarray(ids), i32(40), i32(0), i32(0))
    closed = WINDOW // CHUNK            # chunk rows 0..7 are window 0's
    step = jax.jit(caches.decode)
    args = (tok[None], jnp.asarray([40], i32), jnp.asarray([True]))

    def after(poison_from, poison_to):
        at = slice(WINDOW + poison_from, WINDOW + poison_to)
        _, _, out = step(params, k.at[:, :, :, at].set(50.0),
                         v.at[:, :, :, at].set(-50.0), *args)
        return np.asarray(out)

    _, _, plain = step(params, k, v, *args)
    assert np.array_equal(after(closed, MAX_SEQ // CHUNK), np.asarray(plain))
    assert not np.array_equal(after(closed - 1, closed), np.asarray(plain))


# -- (e): slots reused, buckets padded, slots left out -----------------------

def test_a_reused_slot_starts_from_nothing(ref, adapter, built):
    """One slot, a request of three windows and then one of a window
    and a half: the second finds the first's exact rows and chunk rows
    in its slot and must not see them."""
    cfg, _, params = built
    model = fresh(adapter, cfg)
    rows = recording(model)
    eng = engine(model, params, slots=1)
    first, second = ids_of(11, 90), ids_of(12, 21)
    eng.submit("long", first, 20)
    eng.submit("short", second, 30)
    done = {e["rid"]: e["tokens"] for e in eng.drain() if e["kind"] == "done"}
    want = np.asarray(ref.logits(cfg, params, jnp.asarray(
        second + done["short"], jnp.int32)))
    got = [r[0] for r in rows[-30:]]      # (a prefill's row is [1, ids])
    for i, row in enumerate(got):
        np.testing.assert_allclose(row, want[len(second) - 1 + i], atol=TOL,
                                   rtol=0, err_msg=f"token {i}")


@pytest.mark.parametrize("n", [9, 32, 43], ids=[
    "mid_chunk", "a_whole_window", "into_the_second_window"])
def test_a_padded_bucket_leaves_the_rows_and_chunk_rows_of_n_positions(
        built, n):
    """The same ``n`` ids in buckets of 64 and of 128: the same token,
    and in the slot exactly the exact rows of the open window's real
    positions and the chunk rows of the chunks ``n`` completes; a chunk
    cut by ``n`` is not pooled yet, and nothing past it is written."""
    cfg, model, params = built
    caches = model.serve_caches(2, MAX_SEQ)
    prefill = jax.jit(caches.prefill)
    i32 = jnp.int32

    def into(bucket):
        ids = np.zeros(bucket, np.int32)
        ids[:n] = ids_of(4, n)
        return prefill(params, *caches.new_slabs(), jnp.asarray(ids), i32(n),
                       i32(0), i32(1))

    k1, v1, tok1 = into(64)
    k2, v2, tok2 = into(128)
    assert int(tok1) == int(tok2)
    for a, b in ((k1, k2), (v1, v2)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
        a = np.asarray(a)
        assert not a[:, 0].any()                     # the other slot
        opened = (n - 1) // WINDOW * WINDOW          # the open window's start
        held = np.abs(a[:, 1]).max(axis=(0, 1, 3)) > 0   # rows that hold any
        assert held[:n - opened].all() and not held[n - opened:WINDOW].any()
        assert held[WINDOW:WINDOW + n // CHUNK].all()
        assert not held[WINDOW + n // CHUNK:].any()


def test_a_slot_the_step_is_not_live_for_is_bit_for_bit_as_it_was(built):
    cfg, model, params = built
    caches = model.serve_caches(2, MAX_SEQ)
    i32 = jnp.int32
    k, v = caches.new_slabs()
    for slot, seed in ((0, 13), (1, 14)):
        ids = np.zeros(16, np.int32)
        ids[:11] = ids_of(seed, 11)
        k, v, _ = jax.jit(caches.prefill)(params, k, v, jnp.asarray(ids),
                                          i32(11), i32(0), i32(slot))
    before = np.asarray(k), np.asarray(v)
    # position 11 completes a chunk: both slots would write a chunk row
    k, v, out = jax.jit(caches.decode)(
        params, k, v, jnp.asarray([5, 7], i32), jnp.asarray([11, 11], i32),
        jnp.asarray([True, False]))
    for was, now in zip(before, (np.asarray(k), np.asarray(v))):
        assert np.array_equal(now[:, 1], was[:, 1])
        assert not np.array_equal(now[:, 0, :, 11], was[:, 0, :, 11])
        assert not np.array_equal(now[:, 0, :, WINDOW + 2],
                                  was[:, 0, :, WINDOW + 2])
    toks, says = caches.read(out, np.asarray([12]))
    assert toks.shape == (2,) and says["summary_rows_written"] == 3


# -- (f): requests admitted steps apart ---------------------------------------

def test_requests_of_different_lengths_admitted_steps_apart(ref, built):
    """More requests than slots, at different positions of their windows
    and chunks in every step: each one's tokens are what the reference
    puts first, by a margin or not at all (a tie at float32's rounding
    may go either way)."""
    cfg, model, params = built
    eng = engine(model, params, slots=2)
    prompts = {f"r{i}": ids_of(20 + i, n) for i, n in
               enumerate((3, 61, 30, 33, 17))}
    new = {"r0": 40, "r1": 50, "r2": 9, "r3": 35, "r4": 20}
    events = []
    for rid, p in prompts.items():
        eng.submit(rid, p, new[rid])
        events += eng.step() + eng.step()
    events += eng.drain()
    done = {e["rid"]: e["tokens"] for e in events if e["kind"] == "done"}
    assert set(done) == set(prompts)
    forward = jax.jit(lambda p, ids: ref.logits(cfg, p, ids))
    for rid, toks in done.items():
        seq = prompts[rid] + toks              # (padded: causal, one compile)
        ids = np.zeros(MAX_SEQ, np.int32)
        ids[:len(seq)] = seq
        lg = np.asarray(forward(params, jnp.asarray(ids)))
        at = len(prompts[rid]) - 1
        assert len(toks) == new[rid]
        for i, t in enumerate(toks):
            assert lg[at + i].max() - lg[at + i, t] <= TOL, (rid, i)


# -- (g): what a decode step says of itself -----------------------------------

def test_decode_read_counts_exact_rows_and_chunk_rows_by_the_formulas(
        built, monkeypatch):
    """On every ``kf:serve.decode_read``: the exact rows and chunk rows
    the contexts of the rows it handed out had to read -- ``c - W
    floor((c - 1) / W)`` and ``(W / C) floor((c - 1) / W)`` a layer --
    the rows the step read whatever was live (every row of every slot),
    one row written a context and layer, and the chunks the step
    completed, which it counted itself."""
    cfg, model, params = built
    eng = engine(model, params, slots=3)
    spans = _lookahead.record_spans(monkeypatch)
    asked = {"a": (ids_of(30, 5), 40), "b": (ids_of(31, 62), 9),
             "c": (ids_of(32, 31), 70), "d": (ids_of(33, 90), 12)}
    reads = _lookahead.decode_reads(eng, spans, asked)
    assert len(reads) > 70
    layers, slots = 3, 3
    for attrs, contexts in reads:
        c = np.asarray(contexts)
        closed = (c - 1) // WINDOW
        assert attrs["kv_rows_live"] == layers * int(
            (c - WINDOW * closed).sum())
        assert attrs["summary_rows_live"] == layers * int(
            (WINDOW // CHUNK * closed).sum())
        assert attrs["kv_rows_read"] == layers * slots * WINDOW
        assert attrs["summary_rows_read"] == layers * slots * MAX_SEQ // CHUNK
        assert attrs["kv_rows_written"] == layers * len(c)
        # 4 heads of 16, K and V, float32 here
        assert attrs["kv_row_bytes"] == 2 * 4 * 16 * 4
        assert attrs["summary_rows_written"] == layers * int(
            (c % CHUNK == 0).sum())
        assert attrs["discarded"] == 0
    assert any(a["summary_rows_live"] for a, _ in reads)
    assert any(a["summary_rows_written"] for a, _ in reads)


# -- (g'): the decode attention as the kernel that walks live tiles -----------
#: wide enough for ``ops/pallas/decode_attention.py``: 2 heads of 128 in
#: bfloat16, windows of 128 in chunks of 4, slots of 512 positions -- a
#: slot and layer keeps 128 exact rows and 128 chunk rows (32 a closed
#: window), one tile of 128 each
WIDE_W, WIDE_SEQ, WIDE_TILE = 128, 512, 128


def wide(adapter):
    """(the program's model in bfloat16, the adapter's weights)."""
    cfg = tiny_cfg(hidden_size=256, num_attention_heads=2,
                   num_key_value_heads=2, window_size=WIDE_W,
                   init_std=0.05, n_positions=WIDE_SEQ,
                   max_position_embeddings=WIDE_SEQ)
    return adapter.program_model(cfg), jax.jit(
        lambda k: adapter.init_params(cfg, k))(jax.random.PRNGKey(3))


says_tpu = _lookahead.says_tpu


def walked(rows):
    return sum(-(-x // WIDE_TILE) * WIDE_TILE for x in rows)


def test_decode_through_the_kernel_equals_xlas_form_token_and_row(adapter):
    """Slot 0 from position 122, slot 2 from 250, slot 1 left out, twelve
    steps: chunks complete in both, slot 0's first window closes at 128
    (its exact run falls from 128 rows to 1 as 32 chunk rows come into
    sight) and slot 2's second at 256.  With ``eva_attention`` under the
    mask and then, fed the same tokens, with the kernel interpreted:
    every step's logits to bfloat16's rounding (0.01-0.02 at logits of
    size 3), hence the same token by a margin or not at all, the same
    rows in both slabs, and the slot the step is not for bit for bit as
    it was."""
    i32 = jnp.int32

    def run(kernel, fed=None):
        with pytest.MonkeyPatch.context() as steer:
            if kernel:
                says_tpu(steer)
            model, params = wide(adapter)
            logits = recording(model)
            caches = model.serve_caches(3, WIDE_SEQ)
            assert caches.attn_tile == (WIDE_TILE if kernel else None)
            assert caches.eva_attn_kernel == int(kernel)
            k, v = caches.new_slabs()
            prefill = jax.jit(caches.prefill)
            last = np.zeros(3, np.int32)
            for slot, seed, n in ((0, 41, 122), (1, 42, 40), (2, 43, 250)):
                ids = np.zeros(-(-n // WIDE_W) * WIDE_W, np.int32)
                ids[:n] = ids_of(seed, n)
                k, v, tok = prefill(params, k, v, jnp.asarray(ids), i32(n),
                                    i32(0), i32(slot))
                last[slot] = int(tok)
            text = str(jax.make_jaxpr(caches.decode)(
                params, k, v, jnp.asarray(last), jnp.zeros(3, i32),
                jnp.ones(3, bool)))
            assert text.count("name=decode_attn") == int(kernel)
            step = jax.jit(caches.decode)
            pos, live = np.asarray([122, 40, 250]), [True, False, True]
            idle = np.asarray(k)[:, 1], np.asarray(v)[:, 1]
            del logits[:]
            tokens, said = [], []
            for i in range(12):
                k, v, out = step(params, k, v, jnp.asarray(last),
                                 jnp.asarray(pos, i32), jnp.asarray(live))
                toks, says = caches.read(out, (pos[[0, 2]] + 1).tolist())
                tokens.append(toks)
                said.append(says)
                last = np.where(live, toks if fed is None else fed[i],
                                last).astype(np.int32)
                pos = pos + np.asarray(live)
            for was, now in zip(idle, (k, v)):
                assert np.array_equal(np.asarray(now)[:, 1], was)
        return (tokens, [row[[0, 2]] for row in logits], said,
                np.asarray(k, np.float32), np.asarray(v, np.float32))

    want, rows_x, plain, k2, v2 = run(False)
    got, rows_k, said, k1, v1 = run(True, fed=want)
    assert len(rows_x) == len(rows_k) == 12
    for i, (a, b) in enumerate(zip(rows_k, rows_x)):
        assert np.abs(b).max() > 1.5
        np.testing.assert_allclose(a, b, atol=0.05, rtol=0,
                                   err_msg=f"step {i}")
        for slot, row in zip((0, 2), b):
            assert row.max() - row[got[i][slot]] <= 0.05, (i, slot)
    assert len({tuple(t[[0, 2]]) for t in want}) > 6
    for a, b in ((k1, k2), (v1, v2)):
        assert np.abs(b).max() > 1
        np.testing.assert_allclose(a, b, atol=0.02 * np.abs(b).max(), rtol=0)
    layers, slots = 3, 3
    for i, (kernel, xla) in enumerate(zip(said, plain)):
        # step ``i`` reads slot 0 at position 122 + i and slot 2 at 250 + i
        exact = [(122 + i) % WIDE_W + 1, (250 + i) % WIDE_W + 1]
        chunk = [32 * ((122 + i) // WIDE_W), 32 * ((250 + i) // WIDE_W)]
        assert kernel["kv_rows_live"] == xla["kv_rows_live"] \
            == layers * sum(exact)
        assert kernel["summary_rows_live"] == xla["summary_rows_live"] \
            == layers * sum(chunk)
        assert kernel["kv_rows_read"] == layers * walked(exact) \
            == layers * 2 * WIDE_TILE
        assert kernel["summary_rows_read"] == layers * walked(chunk) \
            == layers * WIDE_TILE * ((i >= 6) + 1)
        assert xla["kv_rows_read"] == layers * slots * WIDE_W
        assert xla["summary_rows_read"] == layers * slots * WIDE_SEQ // CHUNK
        assert (kernel["eva_attn_kernel"], xla["eva_attn_kernel"]) == (1, 0)
        for says in (kernel, xla):
            assert says["summary_rows_written"] == layers * sum(
                p % CHUNK == CHUNK - 1 for p in (122 + i, 250 + i))
            assert "kv_rows_walked" not in says \
                and "summary_rows_walked" not in says


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_read_states_the_rows_of_each_kind_the_step_counted(adapter,
                                                            monkeypatch,
                                                            backend):
    """``kv_rows_read`` and ``summary_rows_read`` are what the step put
    behind its tokens, whichever form ran, and ``eva_attn_kernel`` says
    which: the choice is the cache's, made once from the platform and
    the slab's shape (a window that is not whole tiles has XLA's form
    on the TPU too)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    model, _ = wide(adapter)
    caches = model.serve_caches(4, WIDE_SEQ)
    kernel = int(backend == "tpu")
    assert caches.attn_tile == (WIDE_TILE if kernel else None)
    assert len(caches.new_out()) == 4 + 3
    out = np.asarray([7, 8, 9, 10, 6, 768, 384], np.int32)
    tokens, says = caches.read(out, np.asarray([5, 128, 301]))
    assert tokens.tolist() == [7, 8, 9, 10]
    assert (says["kv_rows_read"], says["summary_rows_read"]) == (768, 384)
    assert says["eva_attn_kernel"] == kernel
    assert says["kv_rows_live"] == 3 * (5 + 128 + 45)
    assert says["summary_rows_live"] == 3 * (0 + 0 + 64)
    assert says["summary_rows_written"] == 6
    assert says["kv_rows_written"] == 9 and says["kv_row_bytes"] == 1024
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert caches.eva_attn_kernel == kernel
    # heads of 16, float32: the tiny model has XLA's form everywhere
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tiny = adapter.program_model(tiny_cfg()).serve_caches(2, MAX_SEQ)
    assert tiny.attn_tile is None and tiny.eva_attn_kernel == 0
    # ... and so has a window of 96 rows, which no tile divides
    cfg = tiny_cfg(hidden_size=256, num_attention_heads=2,
                   num_key_value_heads=2, window_size=96, n_positions=384,
                   max_position_embeddings=384)
    assert adapter.program_model(cfg).serve_caches(2, 384).attn_tile is None


def test_the_engine_through_the_kernel_serves_xlas_tokens(adapter):
    """Three requests through ``InferenceEngine`` over two slots, one of
    which closes a window while it decodes, with the kernel interpreted
    and with XLA's form: on every ``kf:serve.decode_read`` the same
    live rows, which form ran, and rows read that are
    whole tiles of each run -- at least the live rows, at most a tile a
    run, slot and layer more -- under the kernel and the whole slab
    without it."""
    asked = {"a": (ids_of(51, 120), 14), "b": (ids_of(52, 30), 6),
             "c": (ids_of(53, 250), 9)}

    def serve(kernel):
        with pytest.MonkeyPatch.context() as steer:
            if kernel:
                says_tpu(steer)
            model, params = wide(adapter)
            eng = InferenceEngine(
                model, params, max_batch=2, max_seq=WIDE_SEQ,
                pool=KVCachePool(PageSpec.for_model(model.cfg,
                                                    page_tokens=PAGE),
                                 capacity_pages=2))
            spans = _lookahead.record_spans(steer)
            reads = _lookahead.decode_reads(eng, spans, asked)
        return reads

    kernel, plain = serve(True), serve(False)
    layers, slots = 3, 2
    assert len(kernel) == len(plain) > 12
    assert [c for _, c in kernel] == [c for _, c in plain]
    exactly = 0
    for (says, contexts), (xla, _) in zip(kernel, plain):
        assert says["eva_attn_kernel"] == 1 and xla["eva_attn_kernel"] == 0
        assert xla["kv_rows_read"] == layers * slots * WIDE_W
        assert xla["summary_rows_read"] == layers * slots * WIDE_SEQ // CHUNK
        for live, read in (("kv_rows_live", "kv_rows_read"),
                           ("summary_rows_live", "summary_rows_read")):
            assert says[live] == xla[live]
            assert says[read] % (layers * WIDE_TILE) == 0
            assert says[live] <= says[read] <= says[live] \
                + layers * slots * WIDE_TILE
        c = np.asarray(contexts)
        closed = (c - 1) // WIDE_W
        exactly += (says["kv_rows_read"], says["summary_rows_read"]) == (
            layers * walked(c - WIDE_W * closed),
            layers * walked(32 * closed))
    # (a step dispatched before a request's last token was read still
    # walks that slot: one such step a request)
    assert exactly >= len(kernel) - len(asked)
    assert any(s["summary_rows_read"] for s, _ in kernel)
    assert not all(s["summary_rows_read"] for s, _ in kernel)


# -- (h): the configuration's parameter count, and the pool's spec ------------

def test_the_parameter_count_is_the_trees_at_the_cells_configuration(adapter):
    cfg = files.load_config("EvaByte")
    assert adapter.n_params(cfg) == 1_630_932_992
    tree = jax.eval_shape(adapter.program_model(cfg).init,
                          jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(tree)
    assert sum(int(np.prod(x.shape)) for x in leaves) == adapter.n_params(cfg)
    # bfloat16 but for the norms' offsets
    f32 = sum(int(np.prod(x.shape)) for x in leaves if x.dtype == "float32")
    assert f32 == (2 * 8 + 1) * 4096
    model = adapter.program_model(cfg)
    assert (model.cfg.n_layers, model.cfg.init_layers, model.cfg.n_heads,
            model.cfg.head_dim, model.cfg.d_ff, model.cfg.chunk_size,
            model.cfg.window_size, model.cfg.n_pred_heads,
            model.cfg.max_seq) == (8, 32, 32, 128, 11008, 16, 2048, 8, 32768)
    caches = model.serve_caches(16, 32768)
    # 2,048 exact rows and 2,048 chunk rows a slot and layer: 64 MiB, K and V
    assert caches.shape == (8, 16, 32, 4096, 128)
    assert 2 * np.prod(caches.shape[2:]) * 2 == 64 << 20


def test_the_engine_looks_up_no_prefix_and_commits_nothing(built,
                                                           monkeypatch):
    """No page of this family is handed on (``PageSpec.unpaged``, from
    the config's ``pages_reusable``): the same prompt twice is prefilled
    twice, and a completion fetches no bytes."""
    cfg, model, params = built
    spec = PageSpec.for_model(model.cfg, page_tokens=PAGE)
    assert spec.unpaged and (spec.n_layers, spec.n_heads, spec.head_dim) \
        == (3, 4, 16)
    eng = engine(model, params, slots=1)
    assert not eng.pool.reusable([])
    spans = _lookahead.record_spans(monkeypatch)
    prompt = ids_of(17, 19)
    for rid in ("a", "b"):
        eng.submit(rid, prompt, 3)
        done = [e for e in eng.drain() if e["kind"] == "done"]
        assert done[0]["reused_tokens"] == 0
        assert done[0]["computed_tokens"] == 19
    assert eng.pool.stats()["free"] == 2 and eng.pool.cached_pages == 0
    completes = [s for s in spans if s.name == "complete"]
    assert [(s.attrs["pages"], s.attrs["bytes"]) for s in completes] \
        == [(0, 0), (0, 0)]


def test_the_engine_serves_it_without_knowing_it(adapter):
    """``engine.py`` names no model (tests/test_cohere2_moe.py reads its
    source); this model's answer to ``serve_caches`` has what the engine
    asks of a cache whose pages are never handed on, and a slot's length
    has to be whole windows."""
    model = adapter.program_model(tiny_cfg())
    caches = model.serve_caches(3, MAX_SEQ)
    for name in ("new_slabs", "new_out", "prefill", "decode", "read",
                 "empty_pages", "prefill_flops", "decode_flops"):
        assert callable(getattr(caches, name)), name
    k, v = caches.new_slabs()
    assert k.shape == v.shape == (3, 3, 4, WINDOW + MAX_SEQ // CHUNK, 16)
    ks, vs = caches.empty_pages(64)
    assert ks.shape == vs.shape == (3, 4, WINDOW, 16)
    # (the tokens, then the chunks completed and the rows of each kind read)
    assert len(caches.new_out()) == 3 + 3
    # a context past a window reads its chunk rows, not its positions
    assert caches.decode_flops([33]) < caches.decode_flops([32])
    assert caches.decode_flops([5, 9]) > caches.decode_flops([5, 8]) > 0
    assert caches.prefill_flops(40) > caches.prefill_flops(32) > 0
    with pytest.raises(ValueError, match="whole windows"):
        model.serve_caches(3, MAX_SEQ + CHUNK)
    src = open(os.path.join(ROOT, "kungfu_tpu/serve/engine.py")).read()
    assert "evabyte" not in src.lower() and "pooled" not in src.split(
        '"""', 2)[2]


def test_importing_the_model_loads_no_kernel_package():
    import subprocess

    code = ("import sys; sys.path.insert(0, %r); import kungfu_tpu.models; "
            "import kungfu_tpu.models.evabyte; "
            "bad = [m for m in sys.modules if 'pallas' in m "
            "or m == 'kungfu_tpu.serve.pooled']; print(bad)" % ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[]"
