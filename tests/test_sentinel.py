"""kf-sentinel tests: the durable history rings, the deterministic
detector math, the aggregator's judging plane (edge-triggered alerts +
incident flight records), the ``/alerts`` route, offline==online verdict
equality, and the disabled-path cost contract."""

import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import pytest

from kungfu_tpu.monitor import detect, history, kfhist, timeline
from kungfu_tpu.monitor import sentinel as sentinellib
from kungfu_tpu.monitor.aggregator import (
    ClusterAggregator,
    RankReporter,
    field,
    make_snapshot,
)
from kungfu_tpu.monitor.registry import REGISTRY
from kungfu_tpu.monitor.sentinel import Sentinel, extract_series

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: every KF_SENTINEL_* token (plus the shared serve-SLO budgets) — the
#: knob-parity tests must see a clean environment
_SENTINEL_ENVS = (
    "KF_SENTINEL_DIR", "KF_SENTINEL_KEEP_BYTES", "KF_SENTINEL_PERIOD",
    "KF_SENTINEL_WINDOW", "KF_SENTINEL_THRESHOLD", "KF_SENTINEL_MFU_FLOOR",
    "KF_SENTINEL_STEP_CEILING_S", "KF_SENTINEL_WARMUP_STEPS",
    "KF_SENTINEL_INCIDENT_WINDOW", "KF_SENTINEL_SLO_SHORT",
    "KF_SENTINEL_SLO_LONG", "KF_SERVE_SLO_TTFT_MS", "KF_SERVE_SLO_E2E_MS",
)


@pytest.fixture(autouse=True)
def _clean_sentinel_env(monkeypatch):
    for tok in _SENTINEL_ENVS:
        monkeypatch.delenv(tok, raising=False)


def _mesh(tmp_path, **kw):
    """Fake-clock aggregator + attached sentinel: one ingest per logical
    step, clock bumped 1 s after each, so exactly one sentinel sample
    lands per ingest (period_s=1.0) — deterministic cadence."""
    clock = [1000.0]
    agg = ClusterAggregator(stale_after=3600.0, time_fn=lambda: clock[0])
    kw.setdefault("window", 4)
    s = Sentinel(str(tmp_path), period_s=1.0, **kw)
    agg.attach_sentinel(s)
    return agg, s, clock


def _drive(agg, clock, step, step_time_s, **extra):
    agg.ingest(make_snapshot(rank=0, step=step, step_time_s=step_time_s,
                             wall=clock[0], **extra))
    clock[0] += 1.0


class TestDetect:
    def test_no_verdict_until_two_windows(self):
        assert detect.changepoint([0.1] * 7, window=4) is None
        assert detect.changepoint([0.1] * 8, window=4) is not None

    def test_clean_series_stays_flat(self):
        xs = [0.1 + (i % 5) * 1e-4 for i in range(32)]
        v = detect.changepoint(xs, window=8)
        assert v is not None and not v["shifted"] and v["direction"] == "flat"

    def test_planted_step_time_shift_detected_up(self):
        xs = [0.1] * 24 + [0.13] * 8  # a 30 ms regression on a 100 ms step
        v = detect.changepoint(xs, window=8)
        assert v["shifted"] and v["direction"] == "up"
        assert v["score"] >= v["threshold"]

    def test_detection_latency_within_two_windows(self):
        # feed the series one sample at a time, exactly how the online
        # plane accumulates: the planted shift must be called within
        # K=2 windows of its onset
        window, onset = 4, 16
        xs = [0.1] * onset
        fired_at = None
        for i in range(4 * window):
            xs.append(0.13)
            v = detect.changepoint(xs, window=window)
            if v and v["shifted"]:
                fired_at = i + 1
                break
        assert fired_at is not None and fired_at <= 2 * window

    def test_mfu_drop_is_direction_down(self):
        xs = [0.5] * 24 + [0.3] * 8
        v = detect.changepoint(xs, window=8)
        assert v["shifted"] and v["direction"] == "down"

    def test_tail_normalization_equality(self):
        # a caller holding MORE history must compute the identical
        # verdict — the offline==online equality rests on this
        xs = [0.1 + (i % 7) * 1e-3 for i in range(100)] + [0.2] * 8
        window = 8
        tail = xs[-(detect.BASELINE_WINDOWS + 1) * window:]
        assert detect.changepoint(xs, window=window) \
            == detect.changepoint(tail, window=window)

    def test_quiet_series_needs_relative_move(self):
        # MAD 0: a float-ulp wiggle must NOT alert (the rel_floor guard)
        xs = [1.0] * 24 + [1.0 + 1e-9] * 8
        v = detect.changepoint(xs, window=8)
        assert not v["shifted"]

    def test_burn_fraction_needs_full_window(self):
        assert detect.burn_fraction([900.0] * 3, 500.0, window=4) is None
        b = detect.burn_fraction([100.0, 900.0, 900.0, 100.0], 500.0,
                                 window=4)
        assert b["over"] == 2 and b["frac"] == 0.5

    def test_slo_burn_two_window_rule(self):
        # sustained burn: both windows over their fractions
        burn = detect.slo_burn([100.0] * 18 + [900.0] * 6, 500.0,
                               6, 24, 0.5, 0.25)
        assert burn["burning"]
        # one old blip: the short window is clean -> not burning
        burn = detect.slo_burn([100.0, 900.0] + [100.0] * 22, 500.0,
                               6, 24, 0.5, 0.25)
        assert not burn["burning"]

    def test_window_verdicts_drops_short_series(self):
        out = detect.window_verdicts(
            {"long": [0.1] * 16, "short": [0.1] * 3}, window=4)
        assert "long" in out and "short" not in out


class TestHistoryRing:
    def test_roundtrip_segmentation_and_order(self, tmp_path):
        d = str(tmp_path)
        ring = history.HistoryRing(d, "s", keep_bytes=1 << 20,
                                   segment_records=4)
        for i in range(10):
            ring.append({"i": i})
        # 10 appends at 4/segment: 2 sealed + 1 open file
        assert len(history._segments(d, "s")) == 3
        recs = history.read_stream(d, "s")
        assert [r["i"] for r in recs] == list(range(10))
        assert history.streams(d) == ["s"]
        # atomic rewrite discipline: no *.tmp orphan survives an append
        assert not [n for n in os.listdir(d) if n.endswith(".tmp")]

    def test_torn_line_skipped_not_fatal(self, tmp_path):
        d = str(tmp_path)
        ring = history.HistoryRing(d, "s", keep_bytes=1 << 20,
                                   segment_records=4)
        for i in range(8):
            ring.append({"i": i})
        segs = history._segments(d, "s")
        with open(segs[0][1], "ab") as f:
            f.write(b'{"torn": ')        # a crash mid-line
        with open(segs[1][1], "ab") as f:
            f.write(b"[1, 2, 3]\n")      # valid JSON, wrong shape
        recs, skipped = history.scan_stream(d, "s")
        assert [r["i"] for r in recs] == list(range(8))
        assert skipped == 2

    def test_gc_drops_oldest_sealed_only(self, tmp_path):
        d = str(tmp_path)
        ring = history.HistoryRing(d, "s", keep_bytes=40,
                                   segment_records=2)
        for i in range(10):
            ring.append({"i": i})
        recs = history.read_stream(d, "s")
        vals = [r["i"] for r in recs]
        # survivors are a strict SUFFIX: oldest dropped, newest kept
        assert 0 < len(vals) < 10
        assert vals == list(range(10))[-len(vals):]
        remaining = [seq for seq, _ in history._segments(d, "s")]
        assert remaining and remaining[0] > 0

    def test_gc_never_collects_open_segment(self, tmp_path):
        d = str(tmp_path)
        ring = history.HistoryRing(d, "s", keep_bytes=1,
                                   segment_records=100)
        for i in range(5):
            ring.append({"i": i})
        assert ring.gc() == 0
        assert len(history.read_stream(d, "s")) == 5

    def test_restart_opens_fresh_segment(self, tmp_path):
        d = str(tmp_path)
        a = history.HistoryRing(d, "s", keep_bytes=1 << 20,
                                segment_records=10)
        for i in range(3):
            a.append({"i": i})
        b = history.HistoryRing(d, "s", keep_bytes=1 << 20,
                                segment_records=10)
        # never appends into a predecessor's open file
        assert b._seq == a._seq + 1
        b.append({"i": 3})
        assert [r["i"] for r in history.read_stream(d, "s")] \
            == [0, 1, 2, 3]

    def test_bad_stream_name_rejected(self, tmp_path):
        for bad in ("", "a/b", ".hidden"):
            with pytest.raises(ValueError):
                history.HistoryRing(str(tmp_path), bad)


class TestExtractSeries:
    def test_rollup_fields(self):
        view = {
            "ranks": [
                {"rank": 0, "step": 7, "step_time_s": 0.1,
                 "counters": {"kf_jit_compiles_total": 3},
                 "gauges": {'kf_device_memory_bytes{kind="in_use"}': 5.0},
                 "net": {"egress_bytes": 10}},
                {"rank": 1, "step": 6, "step_time_s": 0.3,
                 "counters": {}, "gauges": {}, "net": {"egress_bytes": 2}},
            ],
            "xray": {"mfu": {"0": 0.2, "1": 0.4}, "phase_seconds":
                     {"compute": 1.5}},
            "serving": {"ttft_ms": 120.0, "e2e_ms": 900.0, "kv_bytes": 64},
        }
        s = extract_series(view)
        assert s["step_time_s"] == pytest.approx(0.2)
        assert s["step"] == 7.0 and s["egress_bytes"] == 12.0
        assert s["jit_compiles"] == 3.0 and s["device_mem_bytes"] == 5.0
        assert s["mfu"] == pytest.approx(0.3)
        assert s["phase_compute"] == 1.5
        assert s["ttft_ms"] == 120.0 and s["e2e_ms"] == 900.0

    def test_part_time_series_simply_absent(self):
        s = extract_series({"ranks": [{"rank": 0, "step": 1}]})
        assert "step_time_s" not in s and "mfu" not in s
        assert "egress_bytes" in s  # rows present -> net rollup present


class TestSentinelOnline:
    def test_no_false_positive_then_regression_alert(self, tmp_path):
        agg, s, clock = _mesh(tmp_path)
        for i in range(16):
            _drive(agg, clock, i, 0.1)
        assert s.alerts_view()["alerts"] == []      # clean phase silent
        fired_after = None
        for j in range(16):
            _drive(agg, clock, 16 + j, 0.25)
            fired = [a for a in s.alerts_view()["alerts"]
                     if a["rule"] == "regress:step_time_s"]
            if fired:
                fired_after = j + 1
                break
        # online detection within K=2 windows of the onset
        assert fired_after is not None and fired_after <= 2 * s.window
        # edge-triggered: the rule stays active but does not re-fire
        for j in range(4):
            _drive(agg, clock, 32 + j, 0.25)
        av = s.alerts_view()
        assert "regress:step_time_s" in av["active"]
        assert len([a for a in av["alerts"]
                    if a["rule"] == "regress:step_time_s"]) == 1

    def test_watermark_edge_refire_after_recovery(self, tmp_path):
        agg, s, clock = _mesh(tmp_path, step_ceiling_s=0.2)
        _drive(agg, clock, 0, 0.3)
        _drive(agg, clock, 1, 0.3)      # still over: no re-fire
        _drive(agg, clock, 2, 0.1)      # recovered
        _drive(agg, clock, 3, 0.3)      # fires again
        rules = [a["rule"] for a in s.alerts_view()["alerts"]]
        assert rules == ["watermark:step_time", "watermark:step_time"]

    def test_alert_ticks_counter_and_timeline(self, tmp_path):
        before = REGISTRY.counter("kf_alerts_total",
                                  rule="watermark:step_time").value
        agg, s, clock = _mesh(tmp_path, step_ceiling_s=0.2)
        _drive(agg, clock, 0, 0.5)
        after = REGISTRY.counter("kf_alerts_total",
                                 rule="watermark:step_time").value
        assert after == before + 1

    def test_incident_bundle_and_offline_replay_equality(self, tmp_path):
        agg, s, clock = _mesh(tmp_path)
        for i in range(16):
            _drive(agg, clock, i, 0.1)
        for j in range(8):
            _drive(agg, clock, 16 + j, 0.25)
        fired = [a for a in s.alerts_view()["alerts"]
                 if a["rule"] == "regress:step_time_s"]
        assert fired and fired[0]["incident"]
        with open(fired[0]["incident"], "r", encoding="utf-8") as f:
            bundle = json.load(f)
        assert bundle["kfincident"] == 1
        assert bundle["alert"]["rule"] == "regress:step_time_s"
        assert len(bundle["timeline_tail"]) <= sentinellib.INCIDENT_EVENT_TAIL
        assert "regress:step_time_s" in bundle["config"]["active_alerts"]
        # THE acceptance equality: kfhist --verdict --upto history_n over
        # the durable history reproduces the incident's verdicts exactly
        offline = kfhist.verdict_from_dir(
            str(tmp_path), upto=bundle["history_n"],
            window=s.window, threshold=s.threshold)
        assert json.dumps(offline["verdicts"], sort_keys=True) \
            == json.dumps(bundle["verdicts"], sort_keys=True)
        assert offline["verdicts"]["step_time_s"]["shifted"]
        # per-rank stream recorded alongside the cluster rollup
        assert "rank-0" in history.streams(str(tmp_path))

    def test_incident_timeline_tail_bounded(self, tmp_path):
        s = Sentinel(str(tmp_path), step_ceiling_s=0.2, window=4)
        view = {"wall": 1.0, "ranks": [{"rank": 0, "step": 0,
                                        "step_time_s": 0.5}]}
        events = [{"ts": float(i), "rank": 0, "kind": "collective",
                   "name": "engine.all_reduce", "dur": 0.001}
                  for i in range(400)]
        fired = s.observe(view, events)
        assert [a["rule"] for a in fired] == ["watermark:step_time"]
        with open(fired[0]["incident"], "r", encoding="utf-8") as f:
            bundle = json.load(f)
        assert len(bundle["timeline_tail"]) \
            == sentinellib.INCIDENT_EVENT_TAIL
        # the newest events are the ones kept
        assert bundle["timeline_tail"][-1]["ts"] == 399.0

    def test_sloburn_rule_fires_on_sustained_burn(self, tmp_path):
        s = Sentinel(str(tmp_path), window=4, slo_short=2, slo_long=4,
                     slo_budgets={"ttft_ms": 500.0})
        fired = []
        for i in range(4):
            fired = s.observe({"wall": float(i), "ranks": [],
                               "serving": {"ttft_ms": 900.0,
                                           "e2e_ms": 100.0,
                                           "kv_bytes": 0}})
        assert [a["rule"] for a in fired] == ["sloburn:ttft_ms"]
        assert fired[0]["evidence"]["burning"]

    def test_sloburn_silent_on_single_blip(self, tmp_path):
        s = Sentinel(str(tmp_path), window=4, slo_short=2, slo_long=4,
                     slo_budgets={"ttft_ms": 500.0})
        for i, v in enumerate([100.0, 900.0, 100.0, 100.0]):
            fired = s.observe({"wall": float(i), "ranks": [],
                               "serving": {"ttft_ms": v, "e2e_ms": 100.0,
                                           "kv_bytes": 0}})
            assert fired == []

    def test_watermark_mfu_floor(self, tmp_path):
        s = Sentinel(str(tmp_path), mfu_floor=0.3, window=4)
        view = {"wall": 1.0, "ranks": [],
                "xray": {"mfu": {"0": 0.2}, "phase_seconds": {}}}
        fired = s.observe(view)
        assert [a["rule"] for a in fired] == ["watermark:mfu"]
        assert s.observe(view) == []    # edge-triggered

    def test_watermark_stale_slice(self, tmp_path):
        s = Sentinel(str(tmp_path), window=4)
        fired = s.observe({"wall": 1.0, "ranks": [], "stale_slices": [1]})
        assert [a["rule"] for a in fired] == ["watermark:stale_slice"]
        assert fired[0]["evidence"]["slices"] == [1]

    def test_watermark_ckpt_age(self, tmp_path):
        s = Sentinel(str(tmp_path), window=4)
        row = {"rank": 2, "step": 5, "step_time_s": 0.1,
               "gauges": {"kf_ckpt_period_seconds": 10.0,
                          "kf_ckpt_age_seconds": 40.0}}
        fired = s.observe({"wall": 1.0, "ranks": [row]})
        assert [a["rule"] for a in fired] == ["watermark:ckpt_age"]
        assert fired[0]["evidence"]["ranks"][0]["rank"] == 2

    def test_watermark_recompile_steady(self, tmp_path):
        s = Sentinel(str(tmp_path), warmup_steps=4, window=4)

        def view(step, compiles):
            return {"wall": float(step), "ranks": [
                {"rank": 0, "step": step, "step_time_s": 0.1,
                 "counters": {"kf_jit_compiles_total": compiles}}]}

        assert s.observe(view(2, 10)) == []   # warmup: compiles are free
        assert s.observe(view(5, 3)) == []    # baseline pinned here
        assert s.observe(view(6, 3)) == []    # steady: no growth
        fired = s.observe(view(7, 4))         # a post-warmup recompile
        assert [a["rule"] for a in fired] == ["watermark:recompile_steady"]
        assert fired[0]["evidence"]["baseline"] == 3.0


class TestDisabledPath:
    def test_from_env_none_without_dir(self):
        assert Sentinel.from_env() is None

    def test_from_env_parses_knobs(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KF_SENTINEL_DIR", str(tmp_path))
        monkeypatch.setenv("KF_SENTINEL_WINDOW", "5")
        monkeypatch.setenv("KF_SENTINEL_THRESHOLD", "6.5")
        s = Sentinel.from_env()
        assert s is not None and s.window == 5 and s.threshold == 6.5
        assert s.root == str(tmp_path)
        assert s.period_s == sentinellib.DEFAULT_PERIOD_S

    def test_disabled_aggregator_byte_identical(self, tmp_path):
        # the cost contract: attaching a sentinel only ADDS the alerts
        # section; with no sentinel the view and the prometheus render
        # are byte-identical to the pre-sentinel plane
        clock = [1000.0]
        plain = ClusterAggregator(stale_after=3600.0,
                                  time_fn=lambda: clock[0])
        judged = ClusterAggregator(stale_after=3600.0,
                                   time_fn=lambda: clock[0])
        judged.attach_sentinel(Sentinel(str(tmp_path), window=4))
        for agg in (plain, judged):
            for i in range(4):
                agg.ingest(make_snapshot(rank=0, step=i, step_time_s=0.1,
                                         wall=clock[0]))
        assert plain._sentinel is None
        va, vb = plain.cluster_view(), judged.cluster_view()
        assert "alerts" not in va and "alerts" in vb
        vb = {k: v for k, v in vb.items() if k != "alerts"}
        assert json.dumps(va, sort_keys=True) \
            == json.dumps(vb, sort_keys=True)
        assert "kf_cluster_alerts_active" not in plain.render_prometheus()
        assert "kf_cluster_alerts_active" in judged.render_prometheus()


class TestKnobParity:
    def test_env_tokens_shared(self):
        from kungfu_tpu.utils import envs
        assert envs.SENTINEL_DIR == history.DIR_ENV
        assert envs.SENTINEL_KEEP_BYTES == history.KEEP_BYTES_ENV
        assert envs.SENTINEL_WINDOW == sentinellib.WINDOW_ENV
        assert envs.SENTINEL_THRESHOLD == sentinellib.THRESHOLD_ENV
        assert envs.SERVE_SLO_TTFT_MS == sentinellib.TTFT_BUDGET_ENV
        assert envs.SERVE_SLO_E2E_MS == sentinellib.E2E_BUDGET_ENV

    def test_sentinel_knob_defaults_pinned(self):
        # envs.sentinel_knobs() and the monitor/sentinel.py mirror
        # constants must agree (the stubbed kfhist context reads the
        # mirrors; kfrun reads envs) — the documented contract
        from kungfu_tpu.utils import envs
        k = envs.sentinel_knobs()
        assert k["dir"] == ""
        assert k["keep_bytes"] == history.DEFAULT_KEEP_BYTES
        assert k["period_s"] == sentinellib.DEFAULT_PERIOD_S
        assert k["window"] == detect.DEFAULT_WINDOW
        assert k["threshold"] == detect.DEFAULT_THRESHOLD
        assert k["warmup_steps"] == sentinellib.DEFAULT_WARMUP_STEPS
        assert k["incident_window"] == sentinellib.DEFAULT_INCIDENT_WINDOW
        assert k["slo_short"] == sentinellib.DEFAULT_SLO_SHORT
        assert k["slo_long"] == sentinellib.DEFAULT_SLO_LONG

    def test_slo_rules_defaults_pinned(self):
        from kungfu_tpu.serve.slo import SLORules
        r = SLORules()
        assert r.ttft_budget_ms == sentinellib.DEFAULT_TTFT_BUDGET_MS
        assert r.e2e_budget_ms == sentinellib.DEFAULT_E2E_BUDGET_MS
        assert r.short_window == sentinellib.DEFAULT_SLO_SHORT
        assert r.long_window == sentinellib.DEFAULT_SLO_LONG
        assert r.short_frac == sentinellib.DEFAULT_SLO_SHORT_FRAC
        assert r.long_frac == sentinellib.DEFAULT_SLO_LONG_FRAC


class TestAlertsRoute:
    @pytest.fixture
    def server(self):
        from kungfu_tpu.elastic.configserver import ConfigServer
        from kungfu_tpu.plan import Cluster, PeerList

        workers = PeerList.parse(
            "127.0.0.1:27431,127.0.0.1:27432,127.0.0.1:27433")
        cluster = Cluster(PeerList.parse("127.0.0.1:38093"), workers)
        agg = ClusterAggregator(stale_after=60.0)
        srv = ConfigServer(port=0, cluster=cluster, aggregator=agg).start()
        yield srv, agg, f"http://127.0.0.1:{srv.port}"
        srv.stop()

    def test_alerts_route_404_then_200(self, server, tmp_path):
        srv, agg, base = server
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/alerts", timeout=5)
        assert ei.value.code == 404
        agg.attach_sentinel(Sentinel(str(tmp_path), window=4,
                                     step_ceiling_s=0.2))
        agg.ingest(make_snapshot(rank=0, step=1, step_time_s=0.5))
        with urllib.request.urlopen(base + "/alerts", timeout=5) as resp:
            payload = json.loads(resp.read().decode())
        assert payload["kfsentinel"] == 1
        assert "watermark:step_time" in payload["active"]
        assert payload["alerts"][0]["rule"] == "watermark:step_time"
        # the /cluster view carries the same section
        with urllib.request.urlopen(base + "/cluster", timeout=5) as resp:
            view = json.loads(resp.read().decode())
        assert field(view, "alerts")["active"] == payload["active"]


class TestKftopAlerts:
    def test_render_view_alerts_section(self, tmp_path):
        from kungfu_tpu.monitor import kftop

        agg, s, clock = _mesh(tmp_path, step_ceiling_s=0.2)
        _drive(agg, clock, 0, 0.5)
        text = kftop.render_view(agg.cluster_view())
        assert "== ALERTS" in text and "watermark:step_time" in text

    def test_render_view_no_section_without_sentinel(self):
        from kungfu_tpu.monitor import kftop

        agg = ClusterAggregator(stale_after=60.0)
        agg.ingest(make_snapshot(rank=0, step=1, step_time_s=0.1))
        assert "== ALERTS" not in kftop.render_view(agg.cluster_view())


class TestPolicySignals:
    def test_signals_from_alerts_payload(self, tmp_path):
        from kungfu_tpu.policy import sentinel_signals

        s = Sentinel(str(tmp_path), window=4, step_ceiling_s=0.2)
        s.observe({"wall": 1.0, "ranks": [{"rank": 0, "step": 0,
                                           "step_time_s": 0.5}]})
        sig = sentinel_signals(s.alerts_view())
        assert sig["firing"] and sig["watermarks"] == ["step_time"]
        assert sig["fired_total"] == 1
        # plane off: None, distinguishable from "no alerts"
        assert sentinel_signals({"ranks": []}) is None


class TestReporterHooks:
    def test_pre_snapshot_fn_exception_guarded(self):
        def boom():
            raise RuntimeError("gauge poll failed")

        rep = RankReporter(0, "http://127.0.0.1:1/get",
                           pre_snapshot_fn=boom)
        snap = rep.snapshot_once()     # must not raise
        assert field(snap, "rank") == 0

    def test_publish_device_memory_none_safe(self):
        from kungfu_tpu.monitor.metrics import publish_device_memory

        assert isinstance(publish_device_memory(), bool)

    def test_install_compile_metrics_idempotent_and_ticks(self):
        import jax
        import numpy as np

        from kungfu_tpu.monitor.metrics import install_compile_metrics

        install_compile_metrics()
        install_compile_metrics()  # a second install must not double-count
        before = REGISTRY.counter("kf_jit_compiles_total").value
        jax.jit(lambda x: x * 2 + 1)(np.arange(7, dtype="float32"))
        assert REGISTRY.counter("kf_jit_compiles_total").value == before + 1


class TestChaosAfterStep:
    def test_parse_after_step(self):
        from kungfu_tpu.chaos.spec import parse_spec

        c = parse_spec("delay:ms=5,rank=0,peer=1,after_step=16")[0]
        assert c.kind == "delay" and c.get("after_step") == 16

    def test_clause_inert_until_armed(self):
        from kungfu_tpu.chaos.inject import ChaosController
        from kungfu_tpu.chaos.spec import parse_spec

        clauses = parse_spec("delay:ms=0,after_step=3")
        ctl = ChaosController(clauses, rank=0, seed=1)
        ctl.on_send(1, "t", b"")
        ctl.on_step(2)
        ctl.on_send(1, "t", b"")
        assert ctl._matched == {}          # inert: nothing counted
        ctl.on_step(3)
        ctl.on_send(1, "t", b"")
        assert ctl._matched == {0: 1}      # armed: events count now

    def test_every_strides_armed_events_only(self):
        from kungfu_tpu.chaos.inject import ChaosController
        from kungfu_tpu.chaos.spec import parse_spec

        clauses = parse_spec("delay:ms=0,every=2,after_step=1")
        ctl = ChaosController(clauses, rank=0, seed=1)
        for _ in range(5):                  # pre-onset traffic is free
            ctl.on_send(1, "t", b"")
        ctl.on_step(1)
        for _ in range(2):
            ctl.on_send(1, "t", b"")
        # the every=2 stride counts from the ONSET, not process start
        assert ctl._matched == {0: 2}


class TestScripts:
    def _run(self, script, *args):
        return subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", script), *args],
            capture_output=True, text=True, timeout=120)

    def test_kfhist_self_check(self):
        r = self._run("kfhist", "--self-check")
        assert r.returncode == 0, r.stderr
        assert "self-check ok" in r.stdout

    def test_kfhist_cli_list_and_verdict(self, tmp_path):
        ring = history.HistoryRing(str(tmp_path), "cluster",
                                   keep_bytes=1 << 20, segment_records=8)
        for i in range(24):
            st = 0.1 if i < 16 else 0.25
            ring.append({"kfhist": 1, "wall": float(i),
                         "series": {"step_time_s": st}})
        r = self._run("kfhist", "--dir", str(tmp_path), "--list", "--json")
        assert r.returncode == 0, r.stderr
        assert json.loads(r.stdout)["cluster"]["records"] == 24
        r = self._run("kfhist", "--dir", str(tmp_path), "--verdict",
                      "--window", "4", "--json")
        assert r.returncode == 0, r.stderr
        v = json.loads(r.stdout)["verdicts"]["step_time_s"]
        assert v["shifted"] and v["direction"] == "up"


@pytest.mark.slow
class TestLiveMesh:
    def test_three_rank_offline_online_equality(self):
        # the full acceptance drill (also the check.sh sentinel-gate):
        # 3-rank paced training mesh, chaos delays armed mid-run via
        # after_step, online alert within K windows, incident names the
        # planted rank, kfhist replay identical to the incident verdicts
        sys.path.insert(0, ROOT)
        try:
            from examples import sentinel_gate
            row = sentinel_gate.run()
        finally:
            sys.path.remove(ROOT)
        assert all(row["checks"].values()), row["checks"]
