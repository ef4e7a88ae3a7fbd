"""What every cache says on ``kf:serve.decode_read`` of what a decode
step had to read of it and what it did read (``serve/caches.py``'s
``read(out, contexts)``), at tiny sizes on the CPU, with the loop one
step ahead: the dense slabs, the window rings beside a full slab and
the hybrid cache's slab state ``kv_rows_live`` / ``kv_rows_read`` /
``kv_rows_written`` / ``kv_row_bytes`` as a plain count from the
requests' own lengths gives them, through reused slots and past a
request that ends on ``eos_id``; the latent cache's step counts its live
rows itself, and the host's ``contexts`` sum to that count on every
step; and none of it is in the step: each family's decode program lowers
to the same text whatever its cache's ``read`` is.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from tests import _lookahead  # noqa: E402
from tests import test_cohere2_moe as windowed  # noqa: E402
from tests import test_evabyte as pooled  # noqa: E402
from tests import test_pangu_moe as latent  # noqa: E402
from tests import test_phi4flash as sambay  # noqa: E402
from tests import test_solar_open2 as hybrid  # noqa: E402

from kfbench.lib import files  # noqa: E402
from kungfu_tpu.models.transformer import (Transformer,  # noqa: E402
                                           TransformerConfig)
from kungfu_tpu.serve.engine import InferenceEngine  # noqa: E402
from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec  # noqa: E402

MAX_SEQ, SLOTS = 32, 3
DENSE = TransformerConfig(vocab_size=96, d_model=32, n_layers=2, n_heads=2,
                          d_ff=64, max_seq=MAX_SEQ, dtype="float32")
#: rid -> (prompt length, max_new): more requests than slots, contexts
#: on both sides of the tiny window of 8, none past 32
LENGTHS = {"a": (3, 9), "b": (11, 6), "c": (6, 4), "d": (19, 8),
           "e": (5, 12)}
ASKED = {rid: (np.random.default_rng(40 + i).integers(0, 96, p).tolist(), n)
         for i, (rid, (p, n)) in enumerate(LENGTHS.items())}
#: family -> ([(layers, rows a slot) of each slab of K/V rows], bytes of
#: one layer's row in K and V: heads x width x 2 parts x float32; what a
#: decode step's ``out`` holds behind the slots' tokens)
KV = {"dense": ([(2, MAX_SEQ)], 2 * 16 * 2 * 4, 0),
      "windowed": ([(3, windowed.WINDOW), (1, MAX_SEQ)], 2 * 8 * 2 * 4, 3),
      "hybrid": ([(1, MAX_SEQ)], 2 * 8 * 2 * 4, 6)}


def build_all():
    """family -> (model, params), each at its own tests' tiny size."""
    out = {"dense": (Transformer(DENSE),
                     Transformer(DENSE).init(jax.random.PRNGKey(0)))}
    for name, mod, family in (("windowed", windowed, "cohere2_moe"),
                              ("hybrid", hybrid, "solar_open2"),
                              ("latent", latent, "pangu_moe"),
                              ("sambay", sambay, "phi4flash")):
        out[name] = mod.build(files.load_adapter(family), mod.tiny_cfg())
    adapter, cfg = files.load_adapter("evabyte"), pooled.tiny_cfg()
    out["pooled"] = (pooled.fresh(adapter, cfg), jax.jit(
        lambda k: adapter.init_params(cfg, k))(jax.random.PRNGKey(0)))
    return out


@pytest.fixture(scope="module")
def built():
    return build_all()


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def engine(built, family, eos_id=None):
    """(The pooled cache's at its own tests' size: its windows of 32
    want more than ``MAX_SEQ`` positions to be several.)"""
    model, params = built[family]
    if family == "pooled":
        return pooled.engine(model, params, slots=SLOTS, eos_id=eos_id)
    return InferenceEngine(
        model, params, max_batch=SLOTS, max_seq=MAX_SEQ, eos_id=eos_id,
        pool=KVCachePool(PageSpec.for_model(model.cfg, page_tokens=4),
                         capacity_pages=64))


def _run(built, family):
    """The events of ``ASKED`` through a fresh engine without ``eos_id``."""
    eng = engine(built, family)
    for rid, (prompt, max_new) in ASKED.items():
        eng.submit(rid, prompt, max_new)
    return eng.drain()


def plain_kv_rows(contexts, slabs, row_bytes) -> dict:
    return {"kv_rows_live": sum(n * min(c, rows) for n, rows in slabs
                                for c in contexts),
            "kv_rows_read": sum(n * SLOTS * rows for n, rows in slabs),
            "kv_rows_written": sum(n for n, _ in slabs) * len(contexts),
            "kv_row_bytes": row_bytes}


def an_early_end(tokens: dict) -> int:
    """A token that, as ``eos_id``, ends some request of ``tokens`` (rid
    -> what it generated without one) early by a decode step, with steps
    still to come: the first that a request makes for the first time at
    its second place or later."""
    for toks in tokens.values():
        for i in range(1, len(toks) - 2):
            if toks[i] not in toks[:i]:
                return toks[i]
    raise AssertionError("no token ends a request early")


@pytest.mark.parametrize("stops", [False, True], ids=["by_n", "eos"])
@pytest.mark.parametrize("family", list(KV))
def test_every_decode_read_states_the_kv_rows_a_plain_count_gives(
        built, monkeypatch, family, stops):
    slabs, row_bytes, _ = KV[family]
    eos = an_early_end(_lookahead.tokens_of(_run(built, family))
                       ) if stops else None
    eng = engine(built, family, eos_id=eos)
    spans = _lookahead.record_spans(monkeypatch)
    reads = _lookahead.decode_reads(eng, spans, ASKED)
    assert len(reads) >= 12
    for attrs, contexts in reads:
        want = plain_kv_rows(contexts, slabs, row_bytes)
        assert {k: attrs[k] for k in want} == want, (attrs, contexts)
        assert all(isinstance(attrs[k], int) for k in want)
        assert attrs["kv_rows_live"] <= attrs["kv_rows_read"]
    # reused slots, several rows a step, and (windowed) a capped context
    assert max(len(c) for _, c in reads) == SLOTS
    assert max(max(c) for _, c in reads if c) > windowed.WINDOW
    dropped = sum(attrs["discarded"] for attrs, _ in reads)
    assert (dropped > 0) == stops
    if stops:   # the step read behind a request that ended counts no row of it
        assert any(a["discarded"] and a["kv_rows_written"]
                   == sum(n for n, _ in slabs) * len(c) for a, c in reads)
    # what each family said before is still there
    said = {"windowed": "experts_touched", "hybrid": "state_slots_live"}
    assert all(said.get(family, "discarded") in a for a, _ in reads)


@pytest.mark.parametrize("stops", [False, True], ids=["by_n", "eos"])
def test_the_hosts_contexts_sum_to_the_latent_steps_own_count(
        built, monkeypatch, stops):
    """``latent_rows_live`` is counted in the step, over its ``live``
    slots; ``contexts`` is what the engine hands ``read`` on the host:
    one quantity, on every step, past an ``eos_id`` too -- so a cache
    whose ``*_live`` the host counts states what its step would."""
    eos = an_early_end(_lookahead.tokens_of(_run(built, "latent"))
                       ) if stops else None
    eng = engine(built, "latent", eos_id=eos)
    handed, plain = [], eng._caches.read

    def read(out, contexts):
        handed.append(np.asarray(contexts).tolist())
        return plain(out, contexts)

    eng._caches.read = read
    spans = _lookahead.record_spans(monkeypatch)
    reads = _lookahead.decode_reads(eng, spans, ASKED)
    assert len(reads) == len(handed) >= 12
    for (attrs, contexts), mine in zip(reads, handed):
        assert sorted(mine) == sorted(contexts)
        assert attrs["latent_rows_live"] == sum(mine)
        assert attrs["latent_rows_read"] == SLOTS * MAX_SEQ
        assert not any(k.startswith("kv_") for k in attrs)
    assert (sum(a["discarded"] for a, _ in reads) > 0) == stops


@pytest.mark.parametrize("family", ["dense", "windowed", "hybrid", "latent"])
def test_nothing_of_it_is_in_the_step(built, family):
    """The counts are the host's: the decode program lowers to the same
    text with the cache's ``read`` and with one that states nothing, and
    its ``out`` is as long as it was -- but for the hybrid cache's and,
    since PR 45, the latent cache's, which carry the counts that ARE
    the step's: the rows its attention read (``kv_rows_read``,
    tests/test_solar_open2.py; ``latent_rows_read``,
    tests/test_latent_attention.py) and, the hybrid cache's since PR 46,
    the slots whose matrices it moved (``state_slots_read``,
    tests/test_kda_step_kernel.py)."""
    i32 = jnp.zeros(SLOTS, jnp.int32)

    def lowered(eng):
        return eng._decode_j.lower(eng.params, eng._k, eng._v, eng._out,
                                   i32, i32).as_text()

    eng, bare = engine(built, family), engine(built, family)
    bare._caches.read = lambda out, contexts=None: (np.asarray(out), {})
    assert lowered(eng) == lowered(bare)
    says = {"latent": 5}.get(family) or KV[family][2]
    assert eng._caches.new_out().shape == (SLOTS + says,)


# -- the one body holds every family to the contract ------------------
_KV = {"kv_rows_live", "kv_rows_read", "kv_rows_written", "kv_row_bytes"}
_ROUTING = {"experts_touched", "experts_held", "expert_load_max",
            "expert_load_mean"}
_STATE = {"state_slots_live", "state_slots_read", "state_bytes_read"}
#: family -> the attrs its cache states on ``kf:serve.decode_read``, as
#: docs/tracing.md's table lists them (``discarded`` is the engine's)
READS = {
    "dense": _KV,
    "windowed": _KV | _ROUTING,
    "hybrid": _KV | _ROUTING | _STATE | {"kv_attn_kernel", "kda_step_kernel"},
    "latent": _ROUTING | {"latent_rows_live", "latent_rows_read",
                          "latent_attn_kernel"},
    "pooled": _KV | {"summary_rows_live", "summary_rows_read",
                     "summary_rows_written", "eva_attn_kernel"},
    "sambay": _KV | _STATE | {"kv_rows_live_full", "kv_attn_kernel"},
}


@pytest.mark.parametrize("family", list(READS))
def test_the_body_holds_every_family_to_the_contract(built, family):
    """``serve/caches.py::Caches`` makes ``out`` and takes it apart for
    every family: as long as the tokens and what the family's ``says``
    names, read back as the slots' tokens and exactly the attrs the
    docs' table gives that family -- before any step ran, and for no
    live context."""
    caches = engine(built, family)._caches
    out = caches.new_out()
    assert out.shape == (caches.batch + len(caches.says),)
    assert out.dtype == jnp.int32
    tokens, attrs = caches.read(out, [])
    assert tokens.shape == (caches.batch,) and not tokens.any()
    assert set(attrs) == READS[family]
    table = open(os.path.join(ROOT, "docs", "tracing.md")).read()
    assert all(f"`{name}`" in table for name in attrs)
