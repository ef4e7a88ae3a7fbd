"""Flight recorder tests: ring bounding, spans, dumps, registry,
``kftrace`` merge + straggler analysis, and the /metrics rendering."""

import json
import os
import socket
import subprocess
import sys
import urllib.request

import pytest

from kungfu_tpu.monitor import timeline, traceview
from kungfu_tpu.monitor.registry import (
    REGISTRY,
    Histogram,
    MetricsRegistry,
)
from kungfu_tpu.utils import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv(trace.ENABLE_TRACE, raising=False)
    monkeypatch.delenv(timeline.DUMP_ENV, raising=False)
    monkeypatch.delenv(timeline.CAP_ENV, raising=False)
    timeline.reset()
    timeline.set_rank(None)
    yield
    timeline.reset()
    timeline.set_rank(None)


class TestRing:
    def test_bounding_and_drop_counting(self):
        timeline.reset(cap=8)
        for i in range(20):
            timeline.event("mark", f"m{i}", force=True)
        snap = timeline.snapshot()
        assert len(snap) == 8
        assert timeline.dropped() == 12
        # flight-recorder semantics: the NEWEST events survive
        assert [e["name"] for e in snap] == [f"m{i}" for i in range(12, 20)]

    def test_cap_from_env(self, monkeypatch):
        monkeypatch.setenv(timeline.CAP_ENV, "4")
        timeline.reset()
        for i in range(10):
            timeline.event("mark", f"m{i}", force=True)
        assert len(timeline.snapshot()) == 4
        assert timeline.dropped() == 6

    def test_drop_counter_published(self):
        before = REGISTRY.counter("kf_timeline_dropped_total").value
        timeline.reset(cap=2)
        for i in range(5):
            timeline.event("mark", f"m{i}", force=True)
        assert REGISTRY.counter("kf_timeline_dropped_total").value == before + 3

    def test_step_and_rank_stamping(self):
        timeline.set_rank(7)
        timeline.set_step(42)
        timeline.event("mark", "a", force=True)
        timeline.event("mark", "b", rank=3, force=True)
        a, b = timeline.snapshot()
        assert (a["rank"], a["step"]) == (7, 42)
        assert b["rank"] == 3  # explicit rank wins over the default


class TestSpan:
    def test_nesting_records_both(self):
        with timeline.span("collective", "outer", rank=0, force=True):
            with timeline.span("collective", "inner", rank=0, force=True):
                pass
        names = [e["name"] for e in timeline.snapshot()]
        # inner closes (and records) first
        assert names == ["inner", "outer"]
        for e in timeline.snapshot():
            assert e["dur"] > 0

    def test_exception_annotated_and_recorded(self):
        with pytest.raises(ValueError):
            with timeline.span("collective", "boom", force=True):
                raise ValueError("x")
        (ev,) = timeline.snapshot()
        assert ev["attrs"]["error"] == "ValueError"

    def test_duration_reaches_ring_and_histogram_alike(self):
        h = REGISTRY.histogram("kf_collective_latency_seconds",
                               plane="device", op="spanned-op")
        before, total = h.count, h.summary()["sum"]
        with timeline.span("device", "spanned-op", force=True):
            pass
        (ev,) = timeline.snapshot()
        assert h.count == before + 1
        # without an op attr the histogram is labeled by the span's name,
        # and what it observed is the ring's dur
        assert h.summary()["sum"] - total == pytest.approx(ev["dur"])

    def test_collective_span_feeds_latency_histogram(self):
        h = REGISTRY.histogram("kf_collective_latency_seconds",
                               plane="collective", op="probe_op")
        before = h.count
        with timeline.span("collective", "engine.probe", force=True,
                           op="probe_op", tag="t0"):
            pass
        assert h.count == before + 1


class TestDisabledPath:
    def test_span_is_the_bare_annotation(self):
        import jax

        s1 = timeline.span("collective", "a")
        assert type(s1) is jax.profiler.TraceAnnotation  # no ring object
        h = REGISTRY.histogram("kf_collective_latency_seconds",
                               plane="collective", op="a")
        before = h.count
        with s1:
            pass
        assert timeline.snapshot() == []
        assert h.count == before

    def test_event_records_nothing(self):
        timeline.event("mark", "quiet")
        timeline.event("send", "frame", nbytes=100)
        assert timeline.snapshot() == []
        assert timeline.dropped() == 0

    def test_env_enables(self, monkeypatch):
        monkeypatch.setenv(trace.ENABLE_TRACE, "1")
        timeline.event("mark", "loud")
        assert len(timeline.snapshot()) == 1

    def test_counted_kinds_tick_even_when_disabled(self):
        before = REGISTRY.counter("kf_engine_retries_total").value
        timeline.event("retry", "some-op", peer=1, attempt=0)
        assert REGISTRY.counter("kf_engine_retries_total").value == before + 1
        assert timeline.snapshot() == []  # counter ticked, ring untouched

    def test_chaos_counter_labeled_by_fault(self):
        before = REGISTRY.counter("kf_chaos_injections_total",
                                  what="delay").value
        timeline.event("chaos", "delay", ms=5)
        assert REGISTRY.counter(
            "kf_chaos_injections_total", what="delay").value == before + 1


class TestDump:
    def test_jsonl_round_trip(self, tmp_path):
        timeline.set_rank(3)
        with timeline.span("collective", "engine.all_reduce[16B]", rank=3,
                           force=True, op="all_reduce", tag="g", nbytes=16):
            pass
        timeline.event("chaos", "delay", rank=3, force=True, ms=7)
        path = str(tmp_path / "d.jsonl")
        n = timeline.dump(path)
        assert n == 2
        header, events = traceview.load_dump(path)
        assert header["rank"] == 3 and header["kftrace"] == 1
        assert [e["kind"] for e in events] == ["collective", "chaos"]
        assert events[0]["attrs"]["nbytes"] == 16

    def test_maybe_dump_env_dir(self, tmp_path, monkeypatch):
        monkeypatch.setenv(timeline.DUMP_ENV, str(tmp_path))
        timeline.set_rank(1)
        timeline.event("mark", "x", force=True)
        out = timeline.maybe_dump()
        assert out is not None and out.startswith(str(tmp_path))
        assert os.path.basename(out).startswith("trace-r1-")
        _, events = traceview.load_dump(out)
        assert len(events) == 1

    def test_maybe_dump_noop_without_env(self):
        timeline.event("mark", "x", force=True)
        assert timeline.maybe_dump() is None

    def test_maybe_dump_noop_when_empty(self, tmp_path, monkeypatch):
        monkeypatch.setenv(timeline.DUMP_ENV, str(tmp_path))
        assert timeline.maybe_dump() is None

    def test_self_check_rejects_corrupt_dump(self, tmp_path):
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"kftrace": 1}\n{"kind": "mark"}\n')
        assert traceview.self_check([str(bad)]) == 1
        good = tmp_path / "good.jsonl"
        timeline.event("mark", "ok", force=True)
        timeline.dump(str(good))
        assert traceview.self_check([str(good)]) == 0

    def test_unknown_kind_rejected(self, tmp_path):
        bad = tmp_path / "k.jsonl"
        bad.write_text(json.dumps({
            "ts": 0.0, "rank": 0, "step": -1, "kind": "bogus",
            "name": "x", "dur": 0.0, "attrs": {},
        }) + "\n")
        with pytest.raises(traceview.DumpError):
            traceview.load_dump(str(bad))


class TestRegistry:
    def test_counter_gauge_render(self):
        reg = MetricsRegistry()
        reg.counter("c_total", kind="x").inc(3)
        reg.gauge("g").set(1.5)
        text = reg.render_prometheus()
        assert 'c_total{kind="x"} 3' in text
        assert "g 1.5" in text

    def test_histogram_percentiles(self):
        h = Histogram()
        for ms in range(1, 101):  # 1..100 ms
            h.observe(ms / 1000.0)
        s = h.summary()
        assert s["count"] == 100
        assert s["min"] == pytest.approx(0.001)
        assert s["max"] == pytest.approx(0.1)
        assert 0.03 <= s["p50"] <= 0.08  # true median 50.5 ms, bucketed
        assert 0.08 <= s["p95"] <= 0.11
        assert s["p99"] <= s["max"] + 1e-9

    def test_histogram_render_lines(self):
        reg = MetricsRegistry()
        reg.histogram("lat_seconds", op="ar").observe(0.003)
        text = reg.render_prometheus()
        assert 'lat_seconds_bucket{le="+Inf",op="ar"} 1' in text
        assert 'lat_seconds_count{op="ar"} 1' in text
        assert "lat_seconds_sum" in text

    def test_help_type_headers_once_per_family(self):
        """Stock-scraper metadata: # HELP/# TYPE per metric family (one
        header even across label variants), sample lines untouched."""
        reg = MetricsRegistry()
        reg.counter("kf_engine_retries_total").inc(2)
        reg.gauge("g").set(1.5)
        reg.histogram("lat_seconds", op="a").observe(0.003)
        reg.histogram("lat_seconds", op="b").observe(0.004)
        text = reg.render_prometheus()
        # known metric gets its curated help line; unknown the fallback
        assert ("# HELP kf_engine_retries_total engine send retries "
                "after transient wire faults") in text
        assert "# TYPE kf_engine_retries_total counter" in text
        assert "# HELP g kungfu-tpu metric" in text
        assert "# TYPE g gauge" in text
        assert text.count("# TYPE lat_seconds histogram") == 1
        # metadata precedes the family's first sample
        lines = text.splitlines()
        assert lines.index("# TYPE kf_engine_retries_total counter") \
            < lines.index("kf_engine_retries_total 2")
        # sample encoding byte-compatible with the pre-HELP rendering
        assert "kf_engine_retries_total 2" in lines
        assert "g 1.5" in lines
        assert 'lat_seconds_bucket{le="+Inf",op="a"} 1' in text

    def test_type_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("m")
        with pytest.raises(TypeError):
            reg.gauge("m")

    def test_span_latency_histogram_has_tails(self):
        for _ in range(3):
            with timeline.span("collective", "tailed", force=True,
                               op="tailed_op"):
                pass
        s = REGISTRY.histogram("kf_collective_latency_seconds",
                               plane="collective", op="tailed_op").summary()
        assert s["count"] >= 3
        assert s["min"] <= s["p50"] <= s["max"] + 1e-9
        assert s["p95"] >= s["p50"] - 1e-9


def _span_ev(ts, rank, step, op, tag, dur):
    return {"ts": ts, "rank": rank, "step": step, "kind": "collective",
            "name": f"engine.{op}", "dur": dur,
            "attrs": {"op": op, "tag": tag}}


def _write_dump(path, rank, events):
    with open(path, "w", encoding="utf-8") as f:
        f.write(json.dumps({"kftrace": 1, "rank": rank, "pid": 100 + rank,
                            "dropped": 0, "wall": 0.0}) + "\n")
        for ev in events:
            f.write(json.dumps(ev) + "\n")


@pytest.fixture
def planted_dumps(tmp_path):
    """3 synthetic rank dumps: rank 2 is 10x slower on every collective
    and carries a chaos delay inside its slow windows."""
    paths = []
    for rank in range(3):
        events = []
        for step in range(3):
            t = 100.0 + step
            dur = 0.10 if rank == 2 else 0.01
            events.append(_span_ev(t, rank, step, "all_reduce",
                                   f"grad{step}", dur))
            if rank == 2:
                events.append({"ts": t + 0.02, "rank": 2, "step": step,
                               "kind": "chaos", "name": "delay",
                               "dur": 0.0, "attrs": {"ms": 80}})
        p = str(tmp_path / f"trace-r{rank}.jsonl")
        _write_dump(p, rank, events)
        paths.append(p)
    return paths


class TestKftrace:
    def test_straggler_report_names_planted_rank(self, planted_dumps):
        events = traceview.load_all(planted_dumps)
        assert traceview.straggler_verdict(events) == 2
        report = traceview.render_report(events)
        assert "straggler verdict: rank 2" in report
        assert "step 0: rank 2" in report
        # the injected delay overlaps the spike and is attributed
        assert "chaos:delay@rank2" in report

    def test_skew_rows(self, planted_dumps):
        events = traceview.load_all(planted_dumps)
        rows = traceview.skew_rows(events)
        assert len(rows) == 3  # one group per step's grad tag
        for r in rows:
            assert r["slowest_rank"] == 2
            assert r["skew_s"] == pytest.approx(0.09, rel=0.01)

    def test_chrome_trace_merge(self, planted_dumps):
        events = traceview.load_all(planted_dumps)
        trace_obj = traceview.chrome_trace(events)
        te = trace_obj["traceEvents"]
        assert {e["pid"] for e in te} == {0, 1, 2}
        assert any(e.get("ph") == "X" for e in te)  # spans
        assert any(e.get("ph") == "i" for e in te)  # chaos instants
        # rebased timestamps: earliest event at ts 0
        assert min(e["ts"] for e in te if e["ph"] != "M") == 0.0

    def test_merge_cli(self, planted_dumps, tmp_path, capsys):
        out = str(tmp_path / "trace.json")
        rc = traceview.main(["merge", "-o", out] + planted_dumps)
        assert rc == 0
        with open(out) as f:
            obj = json.load(f)
        assert "traceEvents" in obj and len(obj["traceEvents"]) > 9

    def test_report_cli(self, planted_dumps, capsys):
        rc = traceview.main(["report"] + planted_dumps)
        assert rc == 0
        assert "straggler verdict: rank 2" in capsys.readouterr().out

    def test_script_self_check(self):
        rc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "kftrace"),
             "--self-check"],
            capture_output=True, timeout=60,
        )
        assert rc.returncode == 0, rc.stdout.decode() + rc.stderr.decode()


class TestMetricsServer:
    def _scrape(self, port):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
            return r.read().decode()

    def test_ephemeral_port_and_histogram_lines(self):
        from kungfu_tpu.monitor.metrics import MetricsServer, NetMonitor

        REGISTRY.histogram("kf_collective_latency_seconds",
                           plane="collective", op="scrape_probe").observe(0.02)
        m = NetMonitor(period=0.1)
        s = MetricsServer(m, port=0).start()
        try:
            assert s.port != 0  # the ACTUAL bound port is exposed
            text = self._scrape(s.port)
            assert "kf_collective_latency_seconds_bucket" in text
            assert 'op="scrape_probe"' in text
            assert "kf_collective_latency_seconds_count" in text
        finally:
            s.stop()

    def test_broken_extra_fn_does_not_500_the_scrape(self):
        """A raised exception inside extra_fn must not take the whole
        endpoint down: healthy sections render, the failure appears as a
        comment line (legal exposition-format noise)."""
        from kungfu_tpu.monitor.metrics import MetricsServer, NetMonitor

        REGISTRY.counter("kf_scrape_probe_total").inc()
        m = NetMonitor(period=0.1)
        m.egress("peer:1", 512)

        def broken_extra():
            raise RuntimeError("gns collector exploded")

        s = MetricsServer(m, port=0, extra_fn=broken_extra).start()
        try:
            text = self._scrape(s.port)  # 200, not 500
            assert 'kf_egress_bytes_total{peer="peer:1"} 512' in text
            assert "kf_scrape_probe_total 1" in text
            assert "# error: extra_fn: RuntimeError: gns collector exploded" in text
        finally:
            s.stop()

    def test_registry_render_error_isolated(self, monkeypatch):
        from kungfu_tpu.monitor import metrics as metrics_mod
        from kungfu_tpu.monitor.metrics import MetricsServer, NetMonitor

        m = NetMonitor(period=0.1)
        m.ingress("peer:2", 64)
        monkeypatch.setattr(
            metrics_mod.REGISTRY, "render_prometheus",
            lambda: (_ for _ in ()).throw(ValueError("bad metric")))
        s = MetricsServer(m, port=0).start()
        try:
            text = self._scrape(s.port)
            assert 'kf_ingress_bytes_total{peer="peer:2"} 64' in text
            assert "# error: registry: ValueError: bad metric" in text
        finally:
            s.stop()

    def test_taken_port_degrades_to_ephemeral(self):
        from kungfu_tpu.monitor.metrics import MetricsServer, NetMonitor

        squatter = socket.socket()
        squatter.bind(("0.0.0.0", 0))
        squatter.listen(1)
        taken = squatter.getsockname()[1]
        try:
            m = NetMonitor(period=0.1)
            s = MetricsServer(m, port=taken).start()  # must NOT raise
            try:
                assert s.port != taken
                assert "kf" in self._scrape(s.port) or self._scrape(s.port) == "\n"
            finally:
                s.stop()
        finally:
            squatter.close()


class TestEngineIntegration:
    def test_collective_spans_and_frame_marks(self, monkeypatch):
        """A 2-peer allreduce under tracing leaves rank-attributed
        collective spans plus send/recv frame marks in the ring."""
        import threading

        import numpy as np

        monkeypatch.setenv(trace.ENABLE_TRACE, "1")
        from kungfu_tpu.comm.engine import CollectiveEngine
        from kungfu_tpu.comm.host import HostChannel
        from kungfu_tpu.plan import PeerID, PeerList
        from kungfu_tpu.plan.strategy import Strategy

        peers = PeerList.of(*(PeerID("127.0.0.1", 23150 + i) for i in range(2)))
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
        snap = timeline.snapshot()
        colls = [e for e in snap if e["kind"] == "collective"]
        assert {e["rank"] for e in colls} == {0, 1}
        assert all(e["attrs"]["op"] == "all_reduce" for e in colls)
        assert all(e["dur"] > 0 for e in colls)
        # both peers share one rendezvous tag — kftrace's skew unit
        assert len({e["attrs"]["tag"] for e in colls}) == 1
