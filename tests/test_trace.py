"""The one tracing system (reference TRACE_SCOPE analog): what
``utils/trace.py``'s scopes promised, held by ``timeline.span`` -- off
by default, the env enables, durations reach the ring, nesting, an
exception still records, attrs set on the way, the all-reduce emits a
span."""

import time

import pytest

from kungfu_tpu.monitor import timeline
from kungfu_tpu.utils import trace


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv(trace.ENABLE_TRACE, raising=False)
    timeline.reset()
    yield
    timeline.reset()


class TestSpanAsScope:
    def test_disabled_by_default(self):
        with timeline.span("mark", "quiet-op"):
            pass
        assert timeline.snapshot() == []

    def test_records_durations(self):
        for _ in range(2):
            with timeline.span("mark", "op-a", force=True):
                time.sleep(0.01)
        durs = [e["dur"] for e in timeline.snapshot() if e["name"] == "op-a"]
        assert len(durs) == 2
        assert sum(durs) >= 0.02 and min(durs) >= 0.01

    def test_env_enables(self, monkeypatch):
        monkeypatch.setenv(trace.ENABLE_TRACE, "true")
        with timeline.span("mark", "op-env"):
            pass
        assert [e["name"] for e in timeline.snapshot()] == ["op-env"]

    def test_nested_spans_link_parent_to_child(self):
        with timeline.span("mark", "outer", force=True):
            with timeline.span("mark", "inner", force=True):
                pass
        inner, outer = timeline.snapshot()  # inner closes first
        assert (inner["name"], outer["name"]) == ("inner", "outer")
        assert inner["attrs"]["parent"] == outer["attrs"]["span"]
        assert outer["dur"] >= inner["dur"]

    def test_exception_still_records(self):
        with pytest.raises(ValueError):
            with timeline.span("mark", "boom", force=True):
                raise ValueError("x")
        (ev,) = timeline.snapshot()
        assert ev["name"] == "boom" and ev["attrs"]["error"] == "ValueError"
        # and the ambient context is unwound: the next span has no parent
        with timeline.span("mark", "after", force=True):
            pass
        assert "parent" not in timeline.snapshot()[-1]["attrs"]


class TestAttrsSetOnTheWay:
    def test_set_metadata_reaches_the_ring(self):
        with timeline.span("serve", "complete", force=True, rid="r1") as sp:
            sp.set_metadata(pages=3, bytes=4096)
        (ev,) = timeline.snapshot()
        assert ev["attrs"]["rid"] == "r1"
        assert (ev["attrs"]["pages"], ev["attrs"]["bytes"]) == (3, 4096)
        # the disabled path takes the same call and records nothing
        with timeline.span("serve", "complete", rid="r2") as sp:
            sp.set_metadata(pages=1)
        assert len(timeline.snapshot()) == 1


class TestEngineIntegration:
    def test_allreduce_emits_span(self, monkeypatch):
        """The collective engine's hot path is traced when enabled."""
        import threading

        import numpy as np

        monkeypatch.setenv(trace.ENABLE_TRACE, "1")
        from kungfu_tpu.comm.engine import CollectiveEngine
        from kungfu_tpu.comm.host import HostChannel
        from kungfu_tpu.plan import PeerID, PeerList
        from kungfu_tpu.plan.strategy import Strategy

        peers = PeerList.of(*(PeerID("127.0.0.1", 23100 + i) for i in range(2)))
        chans = [HostChannel(p, bind_host="127.0.0.1") for p in peers]
        engines = [
            CollectiveEngine(c, peers, strategy=Strategy.STAR) for c in chans
        ]
        outs = [None, None]

        def run(i):
            outs[i] = engines[i].all_reduce(np.ones(4, np.float32))

        ts = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        for c in chans:
            c.close()
        np.testing.assert_allclose(outs[0], 2 * np.ones(4))
        spans = [e for e in timeline.snapshot() if e["kind"] == "collective"
                 and e["name"].startswith("engine.all_reduce[")]
        assert len(spans) == 2 and all(e["dur"] > 0 for e in spans)
