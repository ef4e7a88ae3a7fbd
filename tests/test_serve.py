"""kf-serve: the elastic inference plane (tier-1).

Covers the engine (continuous batching, greedy parity with the
full-context transformer, prefix-reuse accounting), the router
(admission, typed overload, the dead-worker/dead-slice replay ladder
over live in-process Peers), the chaos request-path clauses
(``drop_request``, ``delay:on=serve``), the serving policies, and the
kv-gauge/SLO flow through aggregator snapshots to the kftop serving
view (docs/serving.md).
"""

import functools
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu import chaos
from kungfu_tpu.comm.faults import RequestLostError, ServeOverloadError
from kungfu_tpu.models.transformer import Transformer, TransformerConfig
from kungfu_tpu.monitor.registry import REGISTRY
from kungfu_tpu.serve.engine import InferenceEngine
from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec
from kungfu_tpu.serve.router import ServeRouter, ServeWorker

from tests import _lookahead

CFG = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2,
                        d_ff=64, max_seq=128, dtype="float32")


@pytest.fixture(scope="module")
def model_and_params():
    model = Transformer(CFG)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _fresh_chaos():
    chaos.reset()
    yield
    chaos.reset()


def make_engine(model_and_params, pages=128, max_batch=4, page_tokens=8,
                rank=None, eos_id=None):
    model, params = model_and_params
    pool = KVCachePool(PageSpec.for_model(CFG, page_tokens=page_tokens),
                       capacity_pages=pages)
    return InferenceEngine(model, params, pool=pool, max_batch=max_batch,
                           max_seq=CFG.max_seq, rank=rank, eos_id=eos_id)


@functools.lru_cache(maxsize=None)
def _jitted_apply(model):
    return jax.jit(model.apply)


def reference_tokens(model, params, prompt, n):
    """Greedy continuation by the training-path forward, one full-context
    pass per token.  The context is zero-padded to ``max_seq`` so that
    one compiled forward serves every length (an eager ``model.apply``
    compiled every op again for each new length, ~4 s a token): the
    model is causal, so the row that is read never sees the padding."""
    out = list(prompt)
    for _ in range(n):
        ids = np.zeros((1, model.cfg.max_seq), np.int32)
        ids[0, :len(out)] = out
        logits = _jitted_apply(model)(params, ids)
        out.append(int(np.argmax(np.asarray(logits)[0, len(out) - 1])))
    return out[len(prompt):]


# -- engine -----------------------------------------------------------------
class TestEngine:
    def test_greedy_matches_full_context_reference(self, model_and_params):
        """The paged prefill/decode pair must be the SAME function as the
        training-path transformer: greedy tokens agree exactly."""
        model, params = model_and_params
        eng = make_engine(model_and_params)
        eng.submit("a", [1, 2, 3, 4, 5], 6)
        done = [e for e in eng.drain() if e["kind"] == "done"]
        assert done[0]["tokens"] == reference_tokens(
            model, params, [1, 2, 3, 4, 5], 6)

    def test_continuous_batching_admits_mid_flight(self, model_and_params):
        """A request arriving mid-decode joins the running batch at the
        next step boundary — no batch-boundary wait."""
        eng = make_engine(model_and_params)
        eng.submit("long", [1, 2, 3], 30)
        for _ in range(5):
            eng.step()
        assert eng.active_count == 1
        eng.submit("late", [9, 8], 5)
        eng.step()
        assert eng.active_count == 2  # joined while "long" still decodes
        done = {e["rid"] for e in eng.drain() if e["kind"] == "done"}
        assert done == {"long", "late"}

    def test_decode_priority_bounded_admission(self, model_and_params):
        """At most admit_per_step prefills per step: a burst of prompts
        cannot stall the decode of active requests."""
        eng = make_engine(model_and_params)
        for i in range(3):
            eng.submit(f"r{i}", [1 + i, 2, 3], 4)
        evs = eng.step()
        assert sum(e["kind"] == "admit" for e in evs) == 1
        assert eng.pending_count == 2

    def test_prefix_reuse_reduces_prefill_work(self, model_and_params):
        """A shared prefix prefills only its un-cached suffix."""
        eng = make_engine(model_and_params)
        shared = list(range(1, 20))  # 19 tokens: 2 full pages of 8
        eng.submit("first", shared + [21], 4)
        eng.drain()
        eng.submit("second", shared + [22], 4)
        evs = eng.drain()
        adm = [e for e in evs if e["kind"] == "admit"][0]
        assert adm["reused"] == 16
        assert adm["computed"] == 4  # 20 total - 16 cached
        done = [e for e in evs if e["kind"] == "done"][0]
        assert done["reused_tokens"] == 16

    def test_reused_prefix_decodes_identically(self, model_and_params):
        """Cache-hit prefill (pages loaded, suffix computed) must produce
        the same continuation as the cold run."""
        model, params = model_and_params
        eng = make_engine(model_and_params)
        prompt = list(range(1, 18))
        eng.submit("cold", prompt, 6)
        cold = [e for e in eng.drain() if e["kind"] == "done"][0]
        eng.submit("warm", prompt, 6)
        evs = eng.drain()
        assert [e for e in evs if e["kind"] == "admit"][0]["reused"] == 16
        warm = [e for e in evs if e["kind"] == "done"][0]
        assert warm["tokens"] == cold["tokens"]

    def test_long_prompt_after_cached_prefix_stays_correct(
            self, model_and_params):
        """Regression: with a cached prefix, the padded prefill bucket
        must still FIT the slab (start + bucket(suffix) <= max_seq) —
        the overflow used to make dynamic_update_slice clamp the write
        over the restored prefix and silently corrupt the K/V (then
        commit the corruption into the prefix chain)."""
        model, params = model_and_params
        eng = make_engine(model_and_params)  # page 8, max_seq 128
        shared = list(range(1, 17))  # 2 committed pages after request A
        eng.submit("seed", shared + [30], 4)
        eng.drain()
        # B shares the prefix but its suffix bucket (128) cannot sit at
        # offset 16: admission must give the reuse back, not corrupt
        prompt_b = shared + [(31 + i) % 90 for i in range(100)]  # 116 toks
        eng.submit("long", prompt_b, 6)
        evs = eng.drain()
        adm = [e for e in evs if e["kind"] == "admit"][0]
        assert adm["reused"] + eng._prefill_bucket(116 - adm["reused"]) \
            <= eng.max_seq
        done = [e for e in evs if e["kind"] == "done"][0]
        assert done["tokens"] == reference_tokens(model, params, prompt_b, 6)

    def test_cancel_active_is_deferred_to_step_thread(self,
                                                      model_and_params):
        """cancel() of an ACTIVE request only flags it; the step thread
        retires it at the next boundary (a cross-thread release would
        race _complete's page commit)."""
        eng = make_engine(model_and_params)
        eng.submit("victim", [1, 2, 3], 30)
        eng.step()
        assert eng.active_count == 1
        held = eng.pool.stats()["live"]
        assert eng.cancel("victim") is True
        assert eng.active_count == 1  # flagged, not yet retired
        eng.step()
        assert eng.active_count == 0
        assert eng.pool.stats()["live"] < held  # pages released
        assert eng.cancel("victim") is False  # already gone

    def test_cache_exhaustion_keeps_request_pending(self, model_and_params):
        """Admission control is capacity-real: a request that cannot
        reserve its pages queues (FCFS) instead of thrashing live ones."""
        # 5 pages of 8 tokens; each request needs ceil((4+20)/8) = 3
        eng = make_engine(model_and_params, pages=5)
        eng.submit("a", [1, 2, 3, 4], 20)
        eng.submit("b", [5, 6, 7, 8], 20)
        eng.step()
        assert eng.active_count == 1 and eng.pending_count == 1
        done = [e for e in eng.drain() if e["kind"] == "done"]
        assert {e["rid"] for e in done} == {"a", "b"}

    def test_width_control(self, model_and_params):
        eng = make_engine(model_and_params, max_batch=4)
        assert eng.set_width(2) == 2
        for i in range(3):
            eng.submit(f"r{i}", [1 + i, 2], 20)
        for _ in range(4):
            eng.step()
        assert eng.active_count == 2  # width caps admission below slots
        assert eng.set_width(99) == 4  # clamped to max_batch
        eng.drain()

    def test_kv_gauge_tracks_pool(self, model_and_params):
        eng = make_engine(model_and_params)
        eng.submit("a", [1, 2, 3], 4)
        eng.step()
        assert (REGISTRY.gauge("kf_kv_cache_bytes").value
                == eng.pool.footprint_bytes > 0)
        eng.drain()


# -- the slab is written in place -------------------------------------------
def _lower_slab_program(eng, program):
    """One of the three programs that write the slabs, lowered over the
    engine's own: (lowered, position of k_slab among the arguments)."""
    z = jnp.zeros(eng.max_batch, jnp.int32)
    i0 = jnp.int32(0)
    if program == "decode":
        return eng._decode_j.lower(eng.params, eng._k, eng._v, z, z, z), 1
    if program == "prefill":
        return eng._prefill_j.lower(
            eng.params, eng._k, eng._v, jnp.zeros(16, jnp.int32),
            jnp.int32(3), i0, i0), 1
    cfg = eng.model.cfg
    pages = jnp.zeros((cfg.n_layers, cfg.n_heads, 8, cfg.head_dim),
                      cfg.compute_dtype)
    return eng._restore_j.lower(eng._k, eng._v, pages, pages, i0), 0


class TestSlabInPlace:
    """Donation's one rule: a slab handed to a program is gone, and the
    engine holds the returned pair (docs/serving.md)."""

    @pytest.mark.parametrize("phase", ["warmup", "admission", "decode",
                                       "prefix_hit"])
    def test_engine_keeps_only_the_returned_slabs(self, model_and_params,
                                                  phase):
        model, params = model_and_params
        eng = make_engine(model_and_params)
        shared = list(range(1, 20))  # 2 full pages of 8
        if phase == "decode":
            eng.submit("a", shared, 6)
            eng.step()
        elif phase == "prefix_hit":
            eng.submit("first", shared + [21], 4)
            eng.drain()
        old = (eng._k, eng._v)
        if phase == "warmup":
            eng.warmup((20,))
            evs = []
        else:
            if phase == "admission":
                eng.submit("a", shared, 6)
            elif phase == "prefix_hit":
                eng.submit("second", shared + [22], 4)
            evs = eng.step()
        kinds = [e["kind"] for e in evs]
        assert ("admit" in kinds) == (phase in ("admission", "prefix_hit"))
        if phase == "prefix_hit":
            assert evs[0]["reused"] == 16
        assert all(a.is_deleted() for a in old)
        for a in (eng._k, eng._v):
            assert not a.is_deleted()
            assert np.isfinite(np.asarray(a)).all()
        # and the engine goes on serving from them
        eng.drain()
        eng.submit("after", [5, 6, 7], 5)
        done = [e for e in eng.drain() if e["kind"] == "done"]
        assert done[0]["tokens"] == reference_tokens(model, params,
                                                     [5, 6, 7], 5)

    @pytest.mark.parametrize("program", ["decode", "prefill", "restore"])
    def test_lowered_program_aliases_both_slabs(self, model_and_params,
                                                program):
        eng = make_engine(model_and_params)
        lowered, at = _lower_slab_program(eng, program)
        args = lowered.args_info[0]
        assert args[at].donated and args[at + 1].donated
        others = [a for i, a in enumerate(args) if i not in (at, at + 1)]
        assert not any(a.donated for a in jax.tree_util.tree_leaves(others))
        shape = "x".join(str(n) for n in eng._k.shape)
        aliased = re.findall(
            rf"tensor<{shape}x\w+> {{tf\.aliasing_output = (\d+) : i32}}",
            lowered.as_text())
        assert aliased == ["0", "1"]  # k_slab -> first result, v_slab -> second

    @pytest.mark.parametrize("max_batch,warm", [(2, False), (2, True),
                                                (3, False)])
    def test_staggered_run_matches_full_context_reference(
            self, model_and_params, max_batch, warm):
        """Requests admitted mid-flight at different positions, slots
        reused after completions, one request restored from the prefix
        cache: every one yields, token for token, what the training-path
        forward yields alone."""
        model, params = model_and_params
        eng = make_engine(model_and_params, max_batch=max_batch)
        if warm:
            eng.warmup((40,))
        shared = list(range(1, 17))  # 2 full pages of 8
        asked = {"r0": (shared + [40, 41, 42], 7),
                 "r1": ([9, 8, 7, 6, 5], 12),
                 "r2": ([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5], 3),
                 "r3": (shared + [50, 51], 9),
                 "r4": ([60, 61], 10)}
        events, slots = [], {}

        def step(n=1):
            for _ in range(n):
                events.extend(eng.step())
                for slot, r in eng._active.items():
                    slots[r.rid] = slot

        def finished(rid):
            return any(e["kind"] == "done" and e["rid"] == rid
                       for e in events)

        eng.submit("r0", *asked["r0"])
        step(3)
        eng.submit("r1", *asked["r1"])   # joins at another position
        step(2)
        eng.submit("r2", *asked["r2"])   # max_batch 2: waits for a slot
        while not finished("r0"):
            step()
        eng.submit("r3", *asked["r3"])   # r0's committed pages come back
        eng.submit("r4", *asked["r4"])
        while eng.pending_count or eng.active_count:
            step()
        reused = {e["rid"]: e["reused"] for e in events
                  if e["kind"] == "admit"}
        assert reused == {"r0": 0, "r1": 0, "r2": 0, "r3": 16, "r4": 0}
        assert len(set(slots.values())) < len(slots)  # a slot served twice
        done = {e["rid"]: e["tokens"] for e in events if e["kind"] == "done"}
        assert done == {rid: reference_tokens(model, params, prompt, n)
                        for rid, (prompt, n) in asked.items()}


# -- one decode step ahead of the host --------------------------------------
#: rid -> (prompt, max_new): _lookahead.mixed_run's roles
MIXED = {"by_n": (list(range(1, 12)), 9), "stops": ([9, 8, 7, 6, 5], 24),
         "dropped": ([3, 1, 4, 1, 5, 9], 30), "late": ([60, 61], 14),
         "next": ([5, 6, 7], 6)}


def make_eos_engine(model_and_params, eos_id):
    """Three slots (``mixed_run``'s), pages of 4: more of them commit."""
    return make_engine(model_and_params, max_batch=3, page_tokens=4,
                       eos_id=eos_id)


class TestOneStepAhead:
    """Step n+1 is dispatched from the device's own tokens before step
    n's are read (docs/serving.md, "One step ahead")."""

    @pytest.fixture(scope="class")
    def mixed(self, model_and_params):
        """The mixed set served with an ``eos_id`` that ends ``stops``
        early: (eos, what the reference decodes, events, slots, engine)."""
        model, params = model_and_params
        reference = {rid: reference_tokens(model, params, prompt, n)
                     for rid, (prompt, n) in MIXED.items()}
        eos = _lookahead.pick_eos(reference, MIXED, earliest=8)
        want = {rid: _lookahead.until_eos(toks, eos)
                for rid, toks in reference.items()}
        eng = make_eos_engine(model_and_params, eos)
        events, slots = _lookahead.mixed_run(eng, MIXED)
        return eos, want, events, slots, eng

    def test_mixed_requests_decode_what_the_reference_decodes(self, mixed):
        """One ends by ``max_new``, one on ``eos_id``, one is cancelled
        with its step in flight, one is admitted while others decode, one
        takes the slot a discarded row left: token for token the
        full-context forward's, and ``drain()`` returns every ``done``."""
        eos, want, events, slots, eng = mixed
        got = _lookahead.tokens_of(events)
        assert set(got) == set(MIXED) - {"dropped"}
        assert got == {rid: want[rid] for rid in got}
        assert got["stops"][-1] == eos and len(got["stops"]) < 24
        assert len(got["by_n"]) == 9 and eos not in got["by_n"]
        # every token was announced once, in order, a call after its step
        for rid, toks in got.items():
            assert [e["tok"] for e in events
                    if e["kind"] == "token" and e["rid"] == rid] == toks

    @pytest.mark.parametrize("against", ["same_schedule", "alone"])
    def test_committed_pages_hold_the_same_bytes(self, model_and_params,
                                                 mixed, against):
        """The pool after the mixed run holds, byte for byte and under
        the same chains, what engines hold that never discard a row:
        ``same_schedule`` a fresh engine without ``eos_id`` given the
        same requests at the lengths they stopped at (what differs is
        the discarded row alone); ``alone`` one fresh engine a request,
        which therefore never has a step in flight behind a request that
        is finishing."""
        eos, want, events, slots, eng = mixed
        _lookahead.check_committed(
            lambda: make_eos_engine(model_and_params, None), MIXED, want,
            events, slots, eng.pool, against)

    def test_read_of_step_n_follows_dispatch_of_step_n_plus_1(
            self, model_and_params, monkeypatch):
        """The order is held by counting, not timing: in the steady state
        the read of step n is entered only after the decode program was
        called n+1 times, on a call that admits too (its prefill queues
        on the device behind the step in flight); ``ahead`` is 1 on those
        steps and 0 where nothing was in flight; ``discarded`` is 1 on
        the step after an ``eos_id`` ending and nowhere else."""
        model, params = model_and_params
        asked = {"stops": MIXED["stops"], "goes_on": ([60, 61], 20)}
        want = {rid: reference_tokens(model, params, *a)
                for rid, a in asked.items()}
        eos = _lookahead.pick_eos(want, asked, earliest=5)
        n_stops = want["stops"].index(eos) + 1
        eng = make_eos_engine(model_and_params, eos)
        spans = _lookahead.record_spans(monkeypatch)
        calls = []
        decode_j, read = eng._decode_j, eng._caches.read

        def counted_decode(*a):
            calls.append("decode")
            return decode_j(*a)

        def counted_read(out, contexts):
            calls.append("read")
            return read(out, contexts)

        eng._decode_j = counted_decode
        monkeypatch.setattr(eng._caches, "read", counted_read)
        for rid, a in asked.items():
            eng.submit(rid, *a)
        events = []
        for _ in range(3):
            events.extend(eng.step())
        # call 1 admits and dispatches step 1; call 2 admits the second
        # request, dispatches step 2 and reads step 1; call 3 is the
        # steady state: step 3 goes out, then step 2 is read
        assert calls == ["decode", "decode", "read", "decode", "read"]
        events.extend(eng.drain())
        reads = [i for i, c in enumerate(calls) if c == "read"]
        for n, at in enumerate(reads[:-1], 1):
            assert calls[:at].count("decode") == n + 1, n
        assert calls[:reads[-1]].count("decode") == len(reads)  # the last
        decodes = [s.attrs for s in spans if s.name == "decode"]
        assert [d["ahead"] for d in decodes] == [0] + [1] * (
            len(decodes) - 1)
        got = _lookahead.tokens_of(events)
        assert got == {"stops": want["stops"][:n_stops],
                       "goes_on": want["goes_on"]}
        # "stops" made its first token in its prefill and its k-th in
        # step k - 1: the eos in step n_stops - 1.  The step behind that
        # one computed its slot once more; the one after leaves it out
        discarded = [s.attrs["discarded"] for s in spans
                     if s.name == "decode_read"]
        assert len(discarded) == len(decodes)
        assert discarded == [int(i == n_stops - 1)
                             for i in range(len(discarded))]
        assert [d["batch"] for d in decodes[n_stops - 2:n_stops + 1]] \
            == [2, 2, 1]

    def test_nothing_compiles_after_two_token_warm_ups(self,
                                                       model_and_params):
        """Warmed the benchmark's way (two-token requests through
        ``submit``/``drain``, one a prefill bucket and page count), a
        longer run over the same buckets and page counts, with
        admissions, completions and steps in flight mixed, adds no entry
        to the cache of any of the engine's jitted programs."""
        eng = make_eos_engine(model_and_params, None)
        asked = dict(MIXED, stops=(MIXED["stops"][0], 12))
        lens = {rid: len(p) for rid, (p, _) in asked.items()}
        pages = sorted({(lens[rid] + n - 1) // 4
                        for rid, (_, n) in asked.items()})
        warm = [4 * f - 1 for f in pages if f >= 1]
        warm += [b for b in sorted({eng._prefill_bucket(n)
                                    for n in lens.values()})
                 if b not in {eng._prefill_bucket(n) for n in warm}]
        for i, n in enumerate(warm):
            # (no prompt of the run starts as a warm-up's does: no hit)
            eng.submit(f"warm{i}",
                       [63] + [(7 * i + j) % 60 + 1 for j in range(n - 1)], 2)
        assert len(_lookahead.tokens_of(eng.drain())) == len(warm)
        programs = (eng._decode_j, eng._prefill_j, eng._restore_j)
        before = [p._cache_size() for p in programs]
        assert before[0] == 1
        events, _ = _lookahead.mixed_run(eng, asked)
        assert len(_lookahead.tokens_of(events)) == 4
        assert [p._cache_size() for p in programs] == before

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_random_schedules_decode_what_the_reference_decodes(
            self, model_and_params, seed):
        """Arrivals, cancellations and width changes at random calls,
        with an ``eos_id`` the model makes often: every request that was
        not cancelled returns the reference's tokens up to its end, and
        no page stays held."""
        model, params = model_and_params
        rng = np.random.default_rng(seed)
        eos = 29
        eng = make_eos_engine(model_and_params, eos)
        asked = {f"r{i}": (rng.integers(1, 60, rng.integers(1, 21)).tolist(),
                           int(rng.integers(1, 17))) for i in range(12)}
        waiting, cancelled, events = list(asked), set(), []
        while waiting or eng.pending_count or eng.active_count:
            if waiting and rng.random() < 0.5:
                rid = waiting.pop(0)
                eng.submit(rid, *asked[rid])
            if rng.random() < 0.1:
                live = sorted(r.rid for r in eng._active.values())
                if live:
                    victim = live[rng.integers(len(live))]
                    assert eng.cancel(victim)
                    cancelled.add(victim)
            if rng.random() < 0.15:
                eng.set_width(int(rng.integers(1, 4)))
            events.extend(eng.step())
        got = _lookahead.tokens_of(events)
        assert set(got) == set(asked) - cancelled and len(got) >= 6
        for rid, toks in got.items():
            want = reference_tokens(model, params, *asked[rid])
            assert toks == _lookahead.until_eos(want, eos), rid
        assert eng.pool.stats()["live"] == 0 and eng._flight is None

    def test_drain_reads_the_step_behind_an_eos_ending(self, model_and_params,
                                                       monkeypatch):
        """The only request ends on ``eos_id`` with a step in flight that
        holds nobody's row but its own: ``drain()`` reads that step too
        and leaves none behind."""
        model, params = model_and_params
        asked = {"stops": MIXED["stops"]}
        want = {"stops": reference_tokens(model, params, *asked["stops"])}
        eos = _lookahead.pick_eos(want, asked)
        eng = make_eos_engine(model_and_params, eos)
        spans = _lookahead.record_spans(monkeypatch)
        eng.submit("stops", *asked["stops"])
        got = _lookahead.tokens_of(eng.drain())
        assert got == {"stops": _lookahead.until_eos(want["stops"], eos)}
        assert eng._flight is None and eng.pool.stats()["live"] == 0
        reads = [s.attrs["discarded"] for s in spans
                 if s.name == "decode_read"]
        assert reads[-1] == 1 and not any(reads[:-1])
        assert len(reads) == len([s for s in spans if s.name == "decode"])

    def test_cancelling_everything_leaves_nothing_behind(self,
                                                         model_and_params):
        """A step whose every row was cancelled is read and dropped; the
        engine is idle and serves the next request from the same slots."""
        model, params = model_and_params
        eng = make_eos_engine(model_and_params, None)
        for rid in ("by_n", "stops"):
            eng.submit(rid, *MIXED[rid])
        for _ in range(4):
            eng.step()
        assert len(eng._flight.rows) == 2
        assert eng.cancel("by_n") and eng.cancel("stops")
        assert eng.active_count == 2        # the step in flight counts
        assert eng.drain() == [] and eng.active_count == 0
        assert eng.pool.stats()["live"] == 0
        eng.submit("after", *MIXED["next"])
        done = _lookahead.tokens_of(eng.drain())
        assert done["after"] == reference_tokens(model, params,
                                                 *MIXED["next"])


# -- chaos request-path clauses --------------------------------------------
class TestServeChaos:
    def test_spec_parses_request_clauses(self):
        clauses = chaos.parse_spec(
            "drop_request:rank=1,count=2,every=3;delay:ms=5,on=serve")
        assert [c.kind for c in clauses] == ["drop_request", "delay"]
        assert clauses[0].get("count") == 2
        assert clauses[1].get("on") == "serve"

    @pytest.mark.parametrize("bad", [
        "drop_request:peer=1",     # param not valid for kind
        "delay:on=route",          # bad on= value
    ])
    def test_junk_fails_loudly(self, bad):
        with pytest.raises(ValueError):
            chaos.parse_spec(bad)

    def test_drop_request_deterministic(self, monkeypatch):
        """every=2,count=2: exactly the 2nd and 4th matching requests
        drop, on the scoped rank only — same determinism contract as
        every other clause."""
        monkeypatch.setenv("KF_CHAOS_SPEC",
                           "drop_request:rank=1,every=2,count=2")
        ctl = chaos.controller_for(1)
        got = [ctl.on_serve_request(f"r{i}") for i in range(6)]
        assert got == [False, True, False, True, False, False]
        other = chaos.controller_for(0)
        assert not any(other.on_serve_request(f"r{i}") for i in range(4))

    def test_delay_on_serve_straggles(self, monkeypatch):
        monkeypatch.setenv("KF_CHAOS_SPEC", "delay:ms=30,on=serve,rank=0")
        ctl = chaos.controller_for(0)
        t0 = time.perf_counter()
        assert ctl.on_serve_request("r0") is False  # delayed, not dropped
        assert time.perf_counter() - t0 >= 0.025

    def test_unset_spec_is_noop(self, monkeypatch):
        monkeypatch.delenv("KF_CHAOS_SPEC", raising=False)
        assert chaos.controller_for(1) is None


# -- live router over in-process peers --------------------------------------
def make_cluster(n, base_port, monkeypatch, model_and_params,
                 worker_ranks=None, router_rank=None, commit_every=2,
                 **router_kw):
    from kungfu_tpu.peer import Peer
    from kungfu_tpu.plan import Cluster, PeerList
    from kungfu_tpu.utils.envs import Config

    monkeypatch.setenv("KF_TPU_HOST_TRANSPORT", "python")
    monkeypatch.setenv("KF_NATIVE_ENGINE", "0")
    workers = PeerList.parse(
        ",".join(f"127.0.0.1:{base_port + i}" for i in range(n)))
    runners = PeerList.parse(f"127.0.0.1:{base_port + 99}")
    cluster = Cluster(runners, workers)
    peers = [Peer(Config(self_id=w, cluster=cluster)) for w in workers]
    for p in peers:
        p.start()
    router_rank = n - 1 if router_rank is None else router_rank
    worker_ranks = (worker_ranks if worker_ranks is not None
                    else [r for r in range(n) if r != router_rank])
    servers = []
    for r in worker_ranks:
        eng = make_engine(model_and_params, rank=r)
        eng.warmup(prompt_lens=(4,))
        servers.append(ServeWorker(peers[r], eng,
                                   commit_every=commit_every).start())
    router = ServeRouter(peers[router_rank], worker_ranks=worker_ranks,
                         **router_kw)
    return peers, servers, router


def teardown_cluster(peers, servers, router):
    router.close()
    for s in servers:
        if not s.dead:
            s.stop()
    for p in peers:
        try:
            p.close()
        except Exception:  # noqa: BLE001 — dead peers already closed
            pass


class TestRouterLive:
    def test_completion_and_typed_overload(self, monkeypatch,
                                           model_and_params):
        peers, servers, router = make_cluster(
            3, 26110, monkeypatch, model_and_params,
            queue_depth=2, deadline_s=10.0)
        try:
            h1 = router.submit([1, 2, 3], 30)
            h2 = router.submit([4, 5, 6], 30)
            with pytest.raises(ServeOverloadError):
                router.submit([7, 8, 9], 30)  # third in-flight > depth 2
            assert len(h1.wait(60)) == 30 and len(h2.wait(60)) == 30
            # queue drained: admission works again
            assert len(router.submit([7, 8, 9], 5).wait(60)) == 5
            assert router.completed == 3 and router.dead_workers == []
        finally:
            teardown_cluster(peers, servers, router)

    @pytest.mark.slow  # ~75s: live 3-worker cluster + chaos kill + replay
    def test_worker_kill_replays_on_survivor(self, monkeypatch,
                                             model_and_params):
        """The SLO-gated fault scenario: a chaos-killed worker's
        in-flight requests replay from their committed positions on the
        survivor, token-identical to a clean run — zero lost requests."""
        monkeypatch.setenv("KF_CHAOS_SPEC", "die:step=6,rank=0,mode=raise")
        peers, servers, router = make_cluster(
            3, 26130, monkeypatch, model_and_params,
            deadline_s=2.0, strike_limit=2)
        model, params = model_and_params
        try:
            hs = [router.submit([9, 8, 7, i], 40) for i in range(4)]
            outs = [h.wait(90) for h in hs]
            assert all(len(o) == 40 for o in outs)
            assert router.dead_workers == [0]
            assert router.replayed >= 1 and servers[0].dead
            # replayed continuations equal the deterministic reference
            assert outs[0] == reference_tokens(model, params, [9, 8, 7, 0],
                                               40)
        finally:
            teardown_cluster(peers, servers, router)

    @pytest.mark.slow  # ~20s live cluster; flaky under full-suite load
    def test_slice_kill_excludes_whole_slice(self, monkeypatch,
                                             model_and_params):
        """die_slice kills both ranks of slice 1; the router expands the
        dead set to slice grain (training-ladder semantics) and the
        surviving slice absorbs the replays."""
        from kungfu_tpu.elastic.slices import SliceTopology

        monkeypatch.setenv("KF_CHAOS_SPEC",
                           "die_slice:slice=1,step=6,mode=raise,rps=2")
        peers, servers, router = make_cluster(
            5, 26150, monkeypatch, model_and_params,
            worker_ranks=[0, 1, 2, 3], router_rank=4,
            deadline_s=2.0, strike_limit=1, topology=SliceTopology(2, 2))
        try:
            hs = [router.submit([3, 2, 1, i], 40) for i in range(6)]
            outs = [h.wait(120) for h in hs]
            assert all(len(o) == 40 for o in outs)
            assert router.dead_workers == [2, 3]  # the whole slice
            assert router.live_workers == [0, 1]
            assert servers[2].dead and servers[3].dead
            assert router.replayed >= 1
        finally:
            teardown_cluster(peers, servers, router)

    def test_dropped_request_replays_without_killing_worker(
            self, monkeypatch, model_and_params):
        """A chaos-dropped frame expires its deadline and replays, but a
        single strike must NOT mark the worker dead."""
        monkeypatch.setenv("KF_CHAOS_SPEC", "drop_request:count=1")
        peers, servers, router = make_cluster(
            2, 26170, monkeypatch, model_and_params,
            deadline_s=1.0, strike_limit=2)
        try:
            h = router.submit([5, 4, 3], 6)
            assert len(h.wait(60)) == 6
            assert router.replayed == 1
            assert router.dead_workers == []
        finally:
            teardown_cluster(peers, servers, router)

    def test_all_workers_dead_is_typed_loss(self, monkeypatch,
                                            model_and_params):
        monkeypatch.setenv("KF_CHAOS_SPEC",
                           "die:step=4,rank=0,mode=raise")
        peers, servers, router = make_cluster(
            2, 26190, monkeypatch, model_and_params,
            deadline_s=1.5, strike_limit=1)
        try:
            h = router.submit([1, 2, 3], 60)
            with pytest.raises(RequestLostError) as ei:
                h.wait(60)
            assert ei.value.rid == h.rid
            assert router.live_workers == []
        finally:
            teardown_cluster(peers, servers, router)


class TestReplayBudget:
    def test_committed_eos_ends_the_request(self):
        """A committed tail ending in EOS is a finished generation:
        replay must not decode past it (the deterministic-replay
        contract would break)."""
        from kungfu_tpu.serve.router import remaining_budget

        assert remaining_budget(10, [5, 6, 2], eos_id=2) == 0
        assert remaining_budget(10, [5, 6, 2], eos_id=None) == 7
        assert remaining_budget(10, [5, 2, 6], eos_id=2) == 7  # not tail
        assert remaining_budget(10, [], eos_id=2) == 10
        assert remaining_budget(3, [1, 2, 3], eos_id=None) == 0


# -- policies ---------------------------------------------------------------
class TestServePolicies:
    def test_batch_width_controller_hysteresis(self):
        from kungfu_tpu.policy.serve import BatchWidthController
        from kungfu_tpu.serve.slo import SLOTargets

        widths = []
        ctl = BatchWidthController(
            lambda w: (widths.append(w) or w), lo=1, hi=4, start=2,
            targets=SLOTargets(e2e_s=1.0), cooldown_steps=1)
        assert ctl.width == 2
        assert ctl.observe(queued=5, e2e_ms=100.0) == 3   # widen
        assert ctl.observe(queued=5, e2e_ms=100.0) == 3   # cooldown
        assert ctl.observe(queued=5, e2e_ms=100.0) == 4
        ctl._cool = 0
        assert ctl.observe(queued=0, e2e_ms=5000.0) == 3  # SLO blown
        ctl._cool = 0
        assert ctl.observe(queued=0, e2e_ms=None) == 3    # no signal: hold

    def test_autoscale_policy_intents(self):
        from kungfu_tpu.policy.base import PolicyContext
        from kungfu_tpu.policy.serve import ServeAutoscalePolicy
        from kungfu_tpu.serve.slo import SLOTargets

        pol = ServeAutoscalePolicy(targets=SLOTargets(e2e_s=1.0),
                                   scale_up_queue=3, min_workers=1,
                                   cooldown_steps=0)
        ctx = PolicyContext(cluster_size=2)
        ctx.metrics.update(serve_queued=5, serve_e2e_ms=2500.0)
        pol.after_step(ctx)
        assert ctx.requested_size == 3  # overload: scale up
        ctx.requested_size = None
        ctx.metrics.update(serve_queued=0, serve_active=0,
                           serve_e2e_ms=50.0)
        pol.after_step(ctx)
        assert ctx.requested_size == 1  # idle: scale down
        ctx.requested_size = None
        ctx.cluster_size = 1
        pol.after_step(ctx)
        assert ctx.requested_size is None  # floored at min_workers

    def test_serve_signals_from_view(self):
        from kungfu_tpu.policy.serve import serve_signals

        assert serve_signals({"serving": None}) is None
        sig = serve_signals({"serving": {
            "active": 2, "queued": 7, "completed": 10, "rejected": 1,
            "replayed": 3, "ttft_ms": 40.0, "e2e_ms": 900.0,
            "kv_bytes": 4096}})
        assert sig["queued"] == 7 and sig["e2e_ms"] == 900.0


# -- observability flow ------------------------------------------------------
class TestServeObservability:
    def test_kv_gauge_and_slo_flow_to_cluster_view(self, model_and_params):
        """kf_kv_cache_bytes + the serve counters/histograms ride the
        existing snapshot schema into the aggregator's serving rollup —
        the same flow test kf_opt_state_bytes has."""
        from kungfu_tpu.monitor.aggregator import (ClusterAggregator,
                                                   RankReporter, field)

        eng = make_engine(model_and_params)
        eng.submit("obs", [1, 2, 3, 4, 5, 6, 7, 8, 9], 4)
        eng.drain()
        footprint = eng.pool.footprint_bytes  # committed pages parked
        rep = RankReporter(rank=0, server_url="http://127.0.0.1:1",
                           slice_id=None)
        snap = rep.snapshot_once()
        assert field(snap, "gauges")["kf_kv_cache_bytes"] == footprint
        agg = ClusterAggregator(stale_after=60.0)
        agg.ingest(snap)
        view = agg.cluster_view()
        srv = field(view, "serving")
        assert srv is not None
        assert field(srv, "kv_bytes") == footprint
        # worker-side latency histograms rode the snapshot deltas
        lat = field(field(view, "ranks")[0], "latency")
        assert any(k.startswith("kf_serve_ttft_seconds") for k in lat)

    def test_kftop_renders_serving_section(self):
        from kungfu_tpu.monitor import kftop

        assert kftop.self_check() == 0
