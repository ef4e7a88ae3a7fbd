"""kf-xray: causal tracing, critical-path attribution, MFU (tier-1).

Covers the cost model (analytic params/FLOPs pinned against a real
``init()`` tree), the timeline causal triple (derived collective trace
ids, ambient ``trace_ctx``, wire-format round-trip), the pure
attribution math (interval union, phase split, critical path, verdict
determinism), the REPORT_KINDS⊇XRAY_KINDS contract the offline==online
guarantee rests on, the chaos-run satellite (a planted 30 ms link delay
must be attributed identically by ``kftrace --critical-path`` and the
live aggregator, naming the planted edge), and the serve-plane
distributed trace (router → worker → engine as ONE trace id).
See docs/xray.md.
"""

import json
import threading
import time

import numpy as np
import pytest

from kungfu_tpu.monitor import skew as skewlib
from kungfu_tpu.monitor import timeline, traceview
from kungfu_tpu.monitor import xray as xraylib
from kungfu_tpu.monitor.aggregator import (REPORT_KINDS, ClusterAggregator,
                                           make_snapshot)
from kungfu_tpu.monitor.registry import REGISTRY
from kungfu_tpu.ops import costmodel


@pytest.fixture(autouse=True)
def _fresh_timeline():
    timeline.reset()
    yield
    timeline.reset()


# -- cost model -------------------------------------------------------------
class TestCostModel:
    def _count_leaves(self, tree):
        import jax

        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(tree))

    @pytest.mark.parametrize("pos", ["rope", "learned"])
    def test_param_count_matches_real_init(self, pos):
        import jax

        from kungfu_tpu.models.transformer import (Transformer,
                                                   TransformerConfig)

        cfg = TransformerConfig(vocab_size=96, d_model=32, n_layers=2,
                                n_heads=2, d_ff=64, max_seq=16, pos=pos)
        params = Transformer(cfg).init(jax.random.PRNGKey(0))
        assert (costmodel.transformer_param_count(cfg)
                == self._count_leaves(params))

    def test_train_is_three_forwards_and_layers_scale(self):
        from kungfu_tpu.models.transformer import TransformerConfig

        cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                                n_heads=4, d_ff=256, max_seq=64)
        fwd = costmodel.forward_flops(cfg, 4, 32)
        assert costmodel.train_step_flops(cfg, 4, 32) == 3 * fwd
        cfg4 = TransformerConfig(vocab_size=128, d_model=64, n_layers=4,
                                 n_heads=4, d_ff=256, max_seq=64)
        # doubling depth doubles everything except the (depth-free) head
        head = 2 * 4 * 32 * cfg.d_model * cfg.vocab_size
        assert (costmodel.forward_flops(cfg4, 4, 32) - head
                == 2 * (fwd - head))

    def test_prefill_equals_decode_sum_modulo_heads(self):
        """Prefilling t tokens does the same matmul+attention work as t
        decode steps over the growing context; only the LM head differs
        (prefill computes ONE logits row, decode computes one per
        token)."""
        from kungfu_tpu.models.transformer import TransformerConfig

        cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                                n_heads=4, d_ff=256, max_seq=64)
        t = 7
        head = 2 * cfg.d_model * cfg.vocab_size
        decode_sum = sum(costmodel.serve_decode_flops(cfg, i)
                         for i in range(1, t + 1))
        assert costmodel.serve_prefill_flops(cfg, t) == (
            decode_sum - (t - 1) * head)

    def test_prefill_with_cached_prefix_costs_less(self):
        from kungfu_tpu.models.transformer import TransformerConfig

        cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                                n_heads=4, d_ff=256, max_seq=64)
        full = costmodel.serve_prefill_flops(cfg, 16, start=0)
        suffix = costmodel.serve_prefill_flops(cfg, 8, start=8)
        assert 0 < suffix < full
        assert costmodel.serve_prefill_flops(cfg, 0, start=16) == 0

    def test_peak_env_override_wins(self, monkeypatch):
        monkeypatch.setenv(costmodel.PEAK_ENV, "1e15")
        assert costmodel.chip_peak_flops() == 1e15
        monkeypatch.setenv(costmodel.PEAK_ENV, "not-a-number")
        # malformed override falls through to detection (CPU -> None)
        assert costmodel.chip_peak_flops() is None

    def test_kv_bytes_per_token(self):
        from kungfu_tpu.models.transformer import TransformerConfig

        cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=3,
                                n_heads=4, d_ff=256, max_seq=64)
        # K+V, per layer, head_dim x heads, bf16
        assert costmodel.kv_bytes_per_token(cfg) == 2 * 3 * 64 * 2

    def test_mfu_meter_gauges_and_xray_mark(self, monkeypatch):
        monkeypatch.setenv("KF_CONFIG_ENABLE_TRACE", "1")
        meter = costmodel.MFUMeter(step_flops=1_000_000, peak_flops=1e8)
        rate = meter.step(wall_s=0.1,
                          phases={"compute": 0.08, "comm_exposed": 0.02})
        assert rate == pytest.approx(1e7)
        assert meter.mfu == pytest.approx(0.1)
        snap = REGISTRY.snapshot()
        assert snap["kf_mfu"] == pytest.approx(0.1)
        assert snap["kf_model_flops_s"] == pytest.approx(1e7)
        assert snap['kf_step_phase_seconds{phase="compute"}'] == (
            pytest.approx(0.08))
        marks = [e for e in timeline.snapshot() if e["kind"] == "xray"]
        assert marks and marks[-1]["attrs"]["mfu"] == pytest.approx(0.1)

    def test_mfu_meter_accumulates_serving_flops(self):
        meter = costmodel.MFUMeter(peak_flops=None, detect_peak=False)
        meter.add_flops(500)
        meter.add_flops(500)
        assert meter.step(wall_s=0.001) == pytest.approx(1e6)
        assert meter.mfu is None  # no peak -> model-FLOPs rate only


# -- causal triple (timeline) ----------------------------------------------
class TestTraceContext:
    def test_collective_trace_id_is_pure(self):
        a = timeline.collective_trace_id(3, 17, "all_reduce", "ar5")
        assert a == timeline.collective_trace_id(3, 17, "all_reduce", "ar5")
        assert a != timeline.collective_trace_id(4, 17, "all_reduce", "ar5")

    def test_wire_form_round_trip(self):
        tc = timeline.format_trace_context("srv.r1", "s0.7")
        assert timeline.parse_trace_context(tc) == ("srv.r1", "s0.7")
        assert timeline.format_trace_context("t") == "t"
        assert timeline.parse_trace_context("t") == ("t", None)
        assert timeline.parse_trace_context(None) == (None, None)
        assert timeline.parse_trace_context(7) == (None, None)
        # an empty trace id must stay unlinked, never group as ""
        assert timeline.parse_trace_context("@x") == (None, None)
        assert timeline.context_attrs("", "x") == {}
        assert timeline.context_attrs("t") == {"trace": "t"}
        assert timeline.context_attrs("t", "p") == {"trace": "t",
                                                    "parent": "p"}
        assert timeline.format_trace_context(None) is None

    def test_span_triple_nests(self):
        with timeline.span("collective", "outer", force=True,
                           trace="T1") as outer:
            with timeline.span("device", "inner", force=True) as inner:
                timeline.event("mark", "leaf", force=True)
        evs = {e["name"]: e for e in timeline.snapshot()}
        assert evs["outer"]["attrs"]["trace"] == "T1"
        assert evs["outer"]["attrs"]["span"] == outer.span_id
        assert "parent" not in evs["outer"]["attrs"]
        # the inner span inherits the trace and hangs off the outer span
        assert evs["inner"]["attrs"]["trace"] == "T1"
        assert evs["inner"]["attrs"]["parent"] == outer.span_id
        # the mark inherits from the innermost enclosing span
        assert evs["leaf"]["attrs"]["trace"] == "T1"
        assert evs["leaf"]["attrs"]["parent"] == inner.span_id

    def test_trace_ctx_reenters_received_context(self):
        with timeline.trace_ctx("srv.9", "s0.router"):
            timeline.event("serve", "request-recv", force=True)
        ev = timeline.snapshot()[-1]
        assert ev["attrs"]["trace"] == "srv.9"
        assert ev["attrs"]["parent"] == "s0.router"

    def test_explicit_trace_wins_over_ambient(self):
        with timeline.trace_ctx("ambient"):
            timeline.event("mark", "m", force=True, trace="explicit")
        assert timeline.snapshot()[-1]["attrs"]["trace"] == "explicit"

    def test_span_ids_unique_and_reset(self):
        with timeline.span("mark", "a", force=True) as a:
            pass
        with timeline.span("mark", "b", force=True) as b:
            pass
        assert a.span_id != b.span_id
        timeline.reset()
        with timeline.span("mark", "c", force=True) as c:
            pass
        assert c.span_id == a.span_id  # counter re-anchored per capture

    def test_threads_have_independent_ambient_context(self):
        seen = {}

        def other():
            seen["ctx"] = timeline.current_trace()

        with timeline.trace_ctx("T", "p"):
            t = threading.Thread(target=other)
            t.start()
            t.join()
        assert seen["ctx"] == (None, None)


# -- pure attribution math --------------------------------------------------
def _span_ev(rank, step, ts, dur, op="all_reduce", tag="ar0",
             kind="collective", **attrs):
    return {"ts": ts, "rank": rank, "step": step, "kind": kind,
            "name": f"engine.{op}", "dur": dur,
            "attrs": {"op": op, "tag": tag, **attrs}}


def _mark_ev(rank, step, ts, kind, name, **attrs):
    return {"ts": ts, "rank": rank, "step": step, "kind": kind,
            "name": name, "dur": 0.0, "attrs": attrs}


class TestXrayMath:
    def test_union_len_merges_overlaps(self):
        assert xraylib._union_len([]) == 0.0
        assert xraylib._union_len([(0, 1), (0.5, 2), (3, 4)]) == (
            pytest.approx(3.0))
        assert xraylib._union_len([(1, 1), (2, 1)]) == 0.0  # degenerate

    def test_rank_phase_split(self):
        evs = [
            _span_ev(0, 1, 10.0, 0.3, tag="sync"),          # exposed
            _span_ev(0, 1, 10.4, 0.2, tag="async"),         # hidden
            _mark_ev(0, 1, 10.35, "overlap", "issue", tag="async"),
            {"ts": 10.7, "rank": 0, "step": 1, "kind": "input",
             "name": "prefetch.next", "dur": 0.1, "attrs": {}},
            _mark_ev(0, 1, 11.0, "overlap", "complete", tag="async"),
        ]
        split = xraylib.rank_phase_split(evs)
        assert split["wall_s"] == pytest.approx(1.0)
        assert split["comm_exposed"] == pytest.approx(0.3)
        assert split["comm_hidden"] == pytest.approx(0.2)
        assert split["input_stall"] == pytest.approx(0.1)
        assert split["compute"] == pytest.approx(0.4)

    def test_step_attribution_names_culprit_edge(self):
        evs = [
            _span_ev(0, 2, 100.0, 0.01, tag="g"),
            _span_ev(1, 2, 100.0, 0.06, tag="g"),   # the straggler
            _span_ev(2, 2, 100.0, 0.02, tag="g"),
        ]
        rows = xraylib.step_attribution(evs)
        assert len(rows) == 1
        r = rows[0]
        assert r["step"] == 2 and r["critical_rank"] == 1
        assert r["culprit"]["slowest_rank"] == 1
        assert r["culprit"]["fastest_rank"] == 0
        assert r["phases"]["straggler_wait"] == pytest.approx(0.05)
        # critical rank's comm minus the skew excess
        assert r["phases"]["comm_exposed"] == pytest.approx(0.01)

    def test_critical_path_orders_barriers_and_gaps(self):
        evs = [
            _span_ev(0, 1, 10.0, 0.02, tag="a"),
            _span_ev(1, 1, 10.0, 0.05, tag="a"),
            _span_ev(0, 1, 10.2, 0.04, tag="b"),
            _span_ev(1, 1, 10.2, 0.01, tag="b"),
        ]
        hops = xraylib.critical_path(evs, step=1)
        kinds = [(h["kind"], h.get("tag"), h["rank"]) for h in hops]
        assert kinds == [("collective", "a", 1), ("gap", None, 0),
                         ("collective", "b", 0)]
        assert hops[1]["dur_s"] == pytest.approx(0.15)
        assert hops[0]["skew_s"] == pytest.approx(0.03)

    def test_verdict_matches_skew_and_is_deterministic(self):
        evs = [_span_ev(r, s, 100.0 + s, 0.01 * (r + 1) + 0.05 * (r == 2),
                        tag=f"t{s}")
               for r in range(3) for s in range(4)]
        v1 = xraylib.verdict(evs)
        v2 = xraylib.verdict(list(reversed(evs)))  # arrival order moot
        assert v1 == v2
        assert v1["straggler"] == skewlib.straggler_verdict(evs)
        assert v1["steps_seen"] == 4

    def test_report_kinds_superset_contract(self):
        """The offline==online guarantee: every kind the attribution
        consumes must be forwarded by the live reporter."""
        assert xraylib.XRAY_KINDS <= REPORT_KINDS
        assert xraylib.XRAY_KINDS <= timeline.EVENT_KINDS

    def test_online_view_none_when_nothing_attributable(self):
        assert xraylib.online_view([]) is None
        assert xraylib.render_report([]).startswith("kf-xray: 0")

    def test_window_env(self, monkeypatch):
        monkeypatch.setenv(xraylib.WINDOW_ENV, "3")
        evs = [_span_ev(r, s, 100.0 + s, 0.01 + 0.01 * r, tag=f"t{s}")
               for r in range(2) for s in range(9)]
        view = xraylib.online_view(evs)
        assert len(view["steps"]) == 3
        assert view["verdict"]["steps_seen"] == 3


# -- the chaos satellite: offline == online, planted edge named -------------
def _make_peers(base_port, n=3):
    from kungfu_tpu.peer import Peer
    from kungfu_tpu.plan import Cluster, PeerList, parse_strategy
    from kungfu_tpu.utils.envs import Config

    workers = PeerList.parse(
        ",".join(f"127.0.0.1:{base_port + i}" for i in range(n)))
    runners = PeerList.parse(f"127.0.0.1:{base_port + 99}")
    cluster = Cluster(runners, workers)
    peers = [Peer(Config(self_id=w, cluster=cluster)) for w in workers]
    for p in peers:
        p.config.strategy = parse_strategy("STAR")
        p.start()
    return peers


def _run_world(fns, timeout=60.0):
    outs, errs = [None] * len(fns), []

    def wrap(i, f):
        try:
            outs[i] = f()
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=wrap, args=(i, f), daemon=True)
          for i, f in enumerate(fns)]
    for t in ts:
        t.start()
    deadline = time.monotonic() + timeout
    for t in ts:
        t.join(max(0.0, deadline - time.monotonic()))
    if errs:
        raise errs[0]
    assert not any(t.is_alive() for t in ts), "xray world hung"
    return outs


class TestChaosAttribution:
    def test_planted_link_delay_attributed_identically(self, monkeypatch,
                                                       tmp_path):
        """ISSUE 14 satellite: 3-rank chaos run with 30 ms planted on
        the 0<->1 link — the offline critical path (through the REAL
        kftrace dump+load path) and the online aggregator verdict name
        the planted slow edge, asserted identical."""
        from kungfu_tpu import chaos

        wire_ms = 30
        monkeypatch.setenv("KF_NATIVE_ENGINE", "0")
        monkeypatch.setenv("KF_CONFIG_ENABLE_TRACE", "1")
        monkeypatch.setenv(
            "KF_CHAOS_SPEC",
            f"delay:ms={wire_ms},rank=0,peer=1,on=send;"
            f"delay:ms={wire_ms},rank=1,peer=0,on=send;"
            f"delay:ms={2 * wire_ms},rank=1,peer=0,on=recv")
        chaos.reset()
        peers = _make_peers(27310)
        buf = np.ones(20_000, np.float32)
        timeline.reset()
        try:
            for step in range(6):
                timeline.set_step(step)
                _run_world([
                    lambda p=p: p.engine().all_reduce(buf, op="sum")
                    for p in peers])
        finally:
            for p in peers:
                p.close()
            chaos.reset()
        events = timeline.snapshot()
        # offline: dump -> kftrace load path -> verdict
        dump = tmp_path / "xray.jsonl"
        timeline.dump(str(dump))
        loaded = traceview.load_all([str(dump)])
        offline = xraylib.verdict(loaded)
        # online: live aggregator fed REPORT_KINDS-filtered snapshots
        agg = ClusterAggregator(stale_after=3600.0)
        for r in range(3):
            agg.ingest(make_snapshot(
                rank=r, pid=0, wall=time.time(), step=5, step_time_s=0.1,
                counters={}, gauges={}, latency={},
                events=[e for e in events
                        if e["rank"] == r and e["kind"] in REPORT_KINDS],
                net={}, strategy="STAR"))
        online = (agg.cluster_view()["xray"] or {})["verdict"]
        # ONE implementation: the verdicts are identical, not just alike
        assert json.loads(json.dumps(offline)) == json.loads(
            json.dumps(online))
        # ...and they name the planted edge: rank 1 (the delayed legs)
        assert offline["straggler"] == 1
        assert offline["culprit"]["slowest_rank"] == 1
        assert offline["culprit"]["skew_s"] >= 0.5 * wire_ms / 1e3
        assert offline["dominant"] == "comm_exposed"
        # the spans carry the derived cross-rank trace id: same step +
        # tag -> same trace on every rank, no wire bytes spent
        colls = [e for e in loaded if e["kind"] == "collective"
                 and e["step"] == 3]
        by_trace = {}
        for e in colls:
            by_trace.setdefault(e["attrs"]["trace"], set()).add(e["rank"])
        assert any(ranks == {0, 1, 2} for ranks in by_trace.values())
        # the offline CLI renders the same culprit
        report = xraylib.render_report(loaded)
        assert "culprit edge" in report and "rank 1" in report

    def test_kftrace_critical_path_cli(self, monkeypatch, tmp_path,
                                       capsys):
        timeline.reset()
        with timeline.span("collective", "engine.all_reduce", rank=0,
                           force=True, op="all_reduce", tag="t0"):
            time.sleep(0.002)
        dump = tmp_path / "d.jsonl"
        timeline.dump(str(dump))
        assert traceview.main(["--critical-path", str(dump)]) == 0
        out = capsys.readouterr().out
        assert "kf-xray:" in out and "per-step attribution" in out
        # no dumps -> usage error, not a crash
        assert traceview.main(["--critical-path"]) == 2


# -- serve plane: one trace router -> worker -> engine ----------------------
class TestServeDistributedTrace:
    def test_one_request_is_one_trace(self, monkeypatch):
        import jax

        from kungfu_tpu.models.transformer import (Transformer,
                                                   TransformerConfig)
        from kungfu_tpu.serve.engine import InferenceEngine
        from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec
        from kungfu_tpu.serve.router import ServeRouter, ServeWorker

        cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                                n_heads=2, d_ff=64, max_seq=128,
                                dtype="float32")
        model = Transformer(cfg)
        params = model.init(jax.random.PRNGKey(0))
        monkeypatch.setenv("KF_TPU_HOST_TRANSPORT", "python")
        monkeypatch.setenv("KF_NATIVE_ENGINE", "0")
        monkeypatch.setenv("KF_CONFIG_ENABLE_TRACE", "1")
        peers = _make_peers(27350, n=2)
        timeline.reset()
        eng = InferenceEngine(
            model, params,
            pool=KVCachePool(PageSpec.for_model(cfg, page_tokens=8),
                             capacity_pages=64),
            max_batch=2, max_seq=cfg.max_seq, rank=0)
        eng.warmup(prompt_lens=(4,))
        worker = ServeWorker(peers[0], eng, commit_every=2).start()
        router = ServeRouter(peers[1], worker_ranks=[0])
        try:
            h = router.submit([1, 2, 3], 6)
            toks = h.wait(60)
            assert len(toks) == 6
            trace = h.trace
            evs = [e for e in timeline.snapshot()
                   if (e["attrs"] or {}).get("trace") == trace]
            kinds = {(e["kind"], e["name"]) for e in evs}
            # router admission + completion, the worker's frame receipt,
            # and the engine's admission with the prefill inside it: ONE
            # distributed trace
            assert ("request", "accept") in kinds
            assert ("request", "complete") in kinds
            assert ("serve", "request-recv") in kinds
            assert ("serve", "prefill") in kinds
            admit = next(e for e in evs if e["name"] == "admit")
            assert admit["attrs"]["parent"] == h.router_span
            prefill = next(e for e in evs if e["name"] == "prefill")
            assert prefill["attrs"]["parent"] == admit["attrs"]["span"]
            recv = next(e for e in evs if e["name"] == "request-recv")
            assert recv["attrs"]["parent"] == h.router_span
        finally:
            router.close()
            worker.stop()
            for p in peers:
                p.close()

    def test_serving_engine_exports_model_flops_rate(self, monkeypatch):
        import jax

        from kungfu_tpu.models.transformer import (Transformer,
                                                   TransformerConfig)
        from kungfu_tpu.serve.engine import InferenceEngine
        from kungfu_tpu.serve.kvcache import KVCachePool, PageSpec

        cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                                n_heads=2, d_ff=64, max_seq=128,
                                dtype="float32")
        model = Transformer(cfg)
        params = model.init(jax.random.PRNGKey(0))
        REGISTRY.gauge("kf_model_flops_s").set(0.0)
        eng = InferenceEngine(
            model, params,
            pool=KVCachePool(PageSpec.for_model(cfg, page_tokens=8),
                             capacity_pages=64),
            max_batch=2, max_seq=cfg.max_seq, rank=0)
        eng.submit("r1", [1, 2, 3, 4], 8)
        eng.drain()
        assert REGISTRY.snapshot()["kf_model_flops_s"] > 0
        assert eng._mfu.mfu is None  # CPU: rate only, no fake MFU


# -- aggregator / kftop flow ------------------------------------------------
class TestXrayLivePlane:
    def _snap(self, rank, events, gauges=None, counters=None):
        return make_snapshot(
            rank=rank, pid=0, wall=time.time(), step=1, step_time_s=0.1,
            counters=counters or {}, gauges=gauges or {}, latency={},
            events=events, net={}, strategy="")

    def test_cluster_view_xray_section_and_prometheus(self):
        agg = ClusterAggregator(stale_after=3600.0)
        for r in range(2):
            agg.ingest(self._snap(
                r, [_span_ev(r, 1, 50.0, 0.01 + 0.04 * r, tag="g")],
                gauges=({"kf_mfu": 0.37, "kf_model_flops_s": 2e12,
                         'kf_step_phase_seconds{phase="compute"}': 0.2}
                        if r == 0 else None),
                counters={"kf_timeline_dropped_total": 9} if r else None))
        view = agg.cluster_view()
        xr = view["xray"]
        assert xr["verdict"]["culprit"]["slowest_rank"] == 1
        assert xr["mfu"] == {0: 0.37}
        assert xr["model_flops_s"] == pytest.approx(2e12)
        assert xr["phase_seconds"] == {"compute": pytest.approx(0.2)}
        assert xr["dropped_events"] == {1: 9}
        prom = agg.render_prometheus()
        assert 'kf_cluster_mfu{rank="0"} 0.37' in prom
        assert "kf_cluster_model_flops_s 2e+12" in prom
        assert 'kf_cluster_step_phase_seconds{phase="compute"}' in prom

    def test_kftop_renders_xray_and_trace_loss(self):
        from kungfu_tpu.monitor import kftop

        agg = ClusterAggregator(stale_after=3600.0)
        agg.ingest(self._snap(
            0, [_span_ev(0, 1, 50.0, 0.01, tag="g"),
                _span_ev(1, 1, 50.0, 0.05, tag="g")],
            gauges={"kf_mfu": 0.37},
            counters={"kf_timeline_dropped_total": 4}))
        text = kftop.render_view(json.loads(json.dumps(agg.cluster_view())))
        assert "== XRAY" in text
        assert "culprit" in text and "rank 1" in text
        assert "TRACE LOSS" in text and "rank 0: 4" in text

    def test_phase_gauges_average_across_ranks(self):
        """The cluster phase rollup is the MEAN over exporting ranks —
        kftop renders it under a per-step label, and a 4-rank sum would
        read as a 4x-inflated step."""
        agg = ClusterAggregator(stale_after=3600.0)
        for r in range(4):
            agg.ingest(self._snap(
                r, [_span_ev(r, 1, 50.0, 0.01, tag="g")],
                gauges={'kf_step_phase_seconds{phase="compute"}': 0.1,
                        "kf_model_flops_s": 1e9}))
        xr = agg.cluster_view()["xray"]
        assert xr["phase_seconds"] == {"compute": pytest.approx(0.1)}
        # rates DO sum across ranks
        assert xr["model_flops_s"] == pytest.approx(4e9)

    def test_trace_loss_survives_unattributable_window(self):
        """A lossy ring alone must keep the xray section (and the kftop
        TRACE LOSS alarm) alive even when the surviving window holds
        nothing attributable — that is exactly when drops matter."""
        from kungfu_tpu.monitor import kftop

        agg = ClusterAggregator(stale_after=3600.0)
        agg.ingest(self._snap(0, [],
                              counters={"kf_timeline_dropped_total": 12}))
        view = agg.cluster_view()
        assert view["xray"]["dropped_events"] == {0: 12}
        assert view["xray"]["verdict"] is None
        text = kftop.render_view(json.loads(json.dumps(view)))
        assert "TRACE LOSS" in text and "rank 0: 12" in text

    def test_kftop_window_mean_fallback_divides_totals(self):
        """Without per-step gauges the XRAY phases render as the window
        MEAN per step, never the raw multi-step totals."""
        from kungfu_tpu.monitor import kftop

        agg = ClusterAggregator(stale_after=3600.0)
        evs = [_span_ev(r, s, 50.0 + s, 0.1, tag=f"g{s}")
               for r in range(2) for s in range(4)]
        agg.ingest(self._snap(0, evs))
        text = kftop.render_view(json.loads(json.dumps(agg.cluster_view())))
        assert "window mean" in text
        # 4 steps x 100 ms comm must render ~100 ms/step, not ~400 ms
        assert "comm_exposed 400.0ms" not in text

    def test_kftop_self_check_still_green(self):
        from kungfu_tpu.monitor import kftop

        assert kftop.self_check() == 0

    def test_kftrace_self_check_covers_serve_kinds(self, capsys):
        assert traceview.self_check([]) == 0
        assert "serve/request" in capsys.readouterr().out
