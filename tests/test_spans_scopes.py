"""The program's spans on the profiler's clock and its scopes on the
device: ``timeline.span`` writes ``kf:<kind>.<name>`` annotations into a
``jax.profiler`` trace whether or not the ring records; the engine's
step and the pulse wrapper are split into their phases; the lowered
programs carry every scope of the vocabulary (docs/tracing.md)."""

import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kungfu_tpu.models.transformer import Transformer, TransformerConfig
from kungfu_tpu.monitor import timeline
from kungfu_tpu.utils import trace as tracecfg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2,
                        d_ff=64, max_seq=64, pos="learned", dtype="float32")


@pytest.fixture(autouse=True)
def _ring_off(monkeypatch):
    monkeypatch.delenv(tracecfg.ENABLE_TRACE, raising=False)
    timeline.reset()
    yield
    timeline.reset()


def profiled(tmp_path, fn):
    """Run ``fn`` under a profiler session; return its result and the
    trace's ``kf:`` events as dicts (name, start, end, stats), by start."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = [{"name": e.name, "start": e.start_ns,
               "end": e.start_ns + e.duration_ns, "stats": dict(e.stats)}
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("kf:")]
    return result, sorted(events, key=lambda e: (e["start"], -e["end"]))


def inside(child, parent):
    return parent["start"] <= child["start"] and child["end"] <= parent["end"]


def named(events, name):
    return [e for e in events if e["name"] == name]


# -- the serving engine ------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    from kungfu_tpu.serve.engine import InferenceEngine
    from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec

    model = Transformer(CFG)
    eng = InferenceEngine(
        model, model.init(jax.random.PRNGKey(0)), max_batch=4, max_seq=64,
        pool=KVCachePool(PageSpec.for_model(CFG, page_tokens=8),
                         capacity_pages=32))
    eng.warmup(prompt_lens=(20,))
    return eng


def test_engine_step_is_split_into_its_phases(engine, tmp_path):
    def serve():
        engine.submit("a", list(range(1, 12)), 5)   # 11 + 5: one full page
        engine.submit("b", list(range(3, 23)), 3)   # 20 + 3: two
        return engine.drain()

    events, spans = profiled(tmp_path, serve)
    assert timeline.snapshot() == []  # the ring is off; the trace is not
    steps = named(spans, "kf:serve.step")
    assert len(steps) >= 4
    assert steps[0]["stats"] == {"pending": 2, "active": 0}
    # every other span of the table, each inside one step
    for name in ("admit", "prefill", "prefill_read", "decode", "decode_read",
                 "complete"):
        found = named(spans, f"kf:serve.{name}")
        assert found, name
        for e in found:
            assert sum(inside(e, s) for s in steps) == 1, name
    # admission: one per request, prefill and its read-back inside it
    admits = named(spans, "kf:serve.admit")
    assert [a["stats"]["rid"] for a in admits] == ["a", "b"]
    assert admits[0]["stats"] == {"rid": "a", "tokens": 11, "reused": 0,
                                  "pages": 2}
    for a in admits:
        rid = a["stats"]["rid"]
        (pre,) = [e for e in named(spans, "kf:serve.prefill")
                  if e["stats"]["rid"] == rid]
        (read,) = [e for e in named(spans, "kf:serve.prefill_read")
                   if e["stats"]["rid"] == rid]
        assert inside(pre, a) and inside(read, a) and pre["end"] <= read["start"]
    assert admits[1]["stats"]["tokens"] == 20
    assert named(spans, "kf:serve.prefill")[1]["stats"]["bucket"] == 32
    # decode: batch is the live slots of that step, width the slab's
    decodes = named(spans, "kf:serve.decode")
    assert all(d["stats"]["width"] == 4 for d in decodes)
    assert [d["stats"]["batch"] for d in decodes[:2]] == [1, 2]
    for d, r in zip(decodes, named(spans, "kf:serve.decode_read")):
        assert d["end"] <= r["start"]
    # one complete per done event, with the request's rid and its commit
    done = [e for e in events if e["kind"] == "done"]
    completes = named(spans, "kf:serve.complete")
    assert sorted(c["stats"]["rid"] for c in completes) == sorted(
        e["rid"] for e in done) == ["a", "b"]
    by_rid = {c["stats"]["rid"]: c["stats"] for c in completes}
    page = 2 * 8 * CFG.n_layers * CFG.d_model * 4  # k and v, f32
    assert by_rid["a"]["pages"] == 1 and by_rid["a"]["bytes"] == page
    assert by_rid["b"]["pages"] == 2 and by_rid["b"]["bytes"] == 2 * page


def test_request_trace_context_reaches_the_annotations(engine, tmp_path):
    def serve():
        engine.submit("c", [5, 6, 7], 2, trace="t9@s0.router")
        return engine.drain()

    _, spans = profiled(tmp_path, serve)
    for name in ("kf:serve.admit", "kf:serve.complete"):
        (e,) = named(spans, name)
        assert e["stats"]["rid"] == "c"
        assert (e["stats"]["trace"], e["stats"]["parent"]) == (
            "t9", "s0.router")


# -- the training step -------------------------------------------------------

@pytest.fixture(scope="module")
def train_step():
    from kungfu_tpu.comm.device import Communicator
    from kungfu_tpu.optimizers import synchronous_sgd
    from kungfu_tpu.parallel.train import dp_train_step

    old = os.environ.get("KF_PULSE_EVERY")
    os.environ["KF_PULSE_EVERY"] = "2"
    try:
        comm = Communicator()
        model = Transformer(CFG)
        tx = synchronous_sgd(optax.adamw(1e-3), comm.axis)
        step = dp_train_step(model.loss, tx, comm)
    finally:
        if old is None:
            del os.environ["KF_PULSE_EVERY"]
        else:
            os.environ["KF_PULSE_EVERY"] = old
    rep = comm.replicated_sharding()
    params = jax.device_put(model.init(jax.random.PRNGKey(1)), rep)
    opt_state = jax.jit(tx.init, out_shardings=rep)(params)
    ids = jnp.asarray(np.arange(2 * comm.size * 16).reshape(-1, 16) % 64,
                      jnp.int32)
    return step, params, opt_state, (ids, ids)


def test_pulse_wrapper_is_split_into_its_phases(train_step, tmp_path):
    step, params, opt_state, batch = train_step
    assert step.pulse.every == 2
    for _ in range(2):  # compile both programs outside the session
        params, opt_state, _ = step(params, opt_state, batch)

    def four_steps():
        p, s = params, opt_state
        for _ in range(4):
            p, s, loss = step(p, s, batch)
        return float(loss)

    loss, spans = profiled(tmp_path, four_steps)
    assert np.isfinite(loss)
    trains = named(spans, "kf:step.train")
    assert [t["stats"]["pulse"] for t in trains] == [0, 1, 0, 1]
    dispatches = named(spans, "kf:step.dispatch")
    assert len(dispatches) == 4
    for t, d in zip(trains, dispatches):
        assert inside(d, t)
    # the host sync and the publish exist only in the sampled steps
    syncs = named(spans, "kf:pulse.sync")
    publishes = named(spans, "kf:pulse.publish")
    assert len(syncs) == len(publishes) == 2
    pulse_steps = [t for t in trains if t["stats"]["pulse"] == 1]
    for t, sy, pu in zip(pulse_steps, syncs, publishes):
        assert inside(sy, t) and inside(pu, t) and sy["end"] <= pu["start"]


# -- the scopes --------------------------------------------------------------

MODEL_SCOPES = ("embed", "norm", "attn_proj", "attn_core", "mlp", "head")


def scopes_in(lowered):
    import re

    return set(re.findall(r"[A-Za-z_]\w*", " ".join(
        re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))))


def test_train_step_carries_every_scope(train_step):
    step, params, opt_state, batch = train_step
    for prog in (step.base, step.pulse_step):
        found = scopes_in(prog.lower(params, opt_state, batch))
        assert found >= set(MODEL_SCOPES) | {"optimizer", "grad_sync"}
    # the names are metadata: the program's text does not hold them
    text = step.base.lower(params, opt_state, batch).as_text()
    assert "attn_core" not in text and "grad_sync" not in text


def test_decode_and_prefill_carry_every_scope(engine):
    z = jnp.zeros(engine.max_batch, jnp.int32)
    found = scopes_in(engine._decode_j.lower(
        engine.params, engine._k, engine._v, z, z, z))
    assert found >= set(MODEL_SCOPES) | {"kv_write"}
    found = scopes_in(engine._prefill_j.lower(
        engine.params, engine._k, engine._v, jnp.zeros(16, jnp.int32),
        jnp.int32(3), jnp.int32(0), jnp.int32(0)))
    assert found >= set(MODEL_SCOPES) | {"kv_write"}


# -- timeline.span itself ----------------------------------------------------

def test_annotation_is_written_with_the_ring_off(tmp_path):
    def region():
        with timeline.span("collective", "probe", rank=3, op="all_reduce",
                           nbytes=64, shape=(4, 4)) as sp:
            sp.set_metadata(late=1.5)

    _, spans = profiled(tmp_path, region)
    (e,) = spans
    assert e["name"] == "kf:collective.probe"
    # scalar attrs are the event's stats; the rank and the tuple are not
    assert e["stats"] == {"op": "all_reduce", "nbytes": 64, "late": 1.5}
    assert timeline.snapshot() == []


def test_ring_and_annotation_together(tmp_path, monkeypatch):
    monkeypatch.setenv(tracecfg.ENABLE_TRACE, "1")

    def region():
        with timeline.span("input", "prefetch.next", batch=2):
            pass

    _, spans = profiled(tmp_path, region)
    assert [e["name"] for e in spans] == ["kf:input.prefetch.next"]
    (ev,) = timeline.snapshot()
    assert (ev["kind"], ev["name"], ev["attrs"]["batch"]) == (
        "input", "prefetch.next", 2)


def test_span_works_where_jax_was_never_imported():
    # (kungfu_tpu/__init__ imports jax, so the packages above timeline
    # are stood in for by bare namespaces)
    code = """
import sys, types
for pkg in ("kungfu_tpu", "kungfu_tpu.monitor", "kungfu_tpu.utils"):
    m = types.ModuleType(pkg)
    m.__path__ = [sys.argv[1] + "/" + pkg.replace(".", "/")]
    sys.modules[pkg] = m
from kungfu_tpu.monitor import timeline
with timeline.span("signal", "quiet", n=1) as sp:
    sp.set_metadata(m=2)
assert timeline.snapshot() == []
with timeline.span("signal", "loud", force=True, n=1) as sp:
    sp.set_metadata(m=2)
(ev,) = timeline.snapshot()
assert ev["attrs"]["n"] == 1 and ev["attrs"]["m"] == 2
assert "jax" not in sys.modules, "timeline imported jax"
print("ok")
"""
    p = subprocess.run([sys.executable, "-c", code, ROOT],
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr[-2000:]
