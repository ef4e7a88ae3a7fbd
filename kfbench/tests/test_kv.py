"""The readers of what a decode step read of its per-head K/V rows
(``lib/kv.py``, ``metrics/kv_rows_live_share.py``,
``metrics/kv_attn_roofline.py``, ``metrics/decode_step_spread.py``) and
of the engine's first-token clock (``metrics/first_token_ms_per_ktok.py``):
on a trace small enough to work out by hand, on a recorded excerpt of a
chip trace of the chat cell (``recorded_kv_chat.json``, cut by
``tools/kv_excerpt.py``), on a program that states no ``kv_*`` attr (the
parent of the PR that added them), and in a rehearsal's line."""

import json
import os

import pytest

from conftest import rehearse
from kfbench.lib import decode_paths, files, kv, spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))
CHAT, ROW = "gpt2l-serve-chat", 5120


def metric(name, facts):
    return files.load_module("metrics", name).read(facts, {"name": name})


def facts_of(ops, runs, read_stats, run_seconds=None, config="gpt2-large"):
    """A traced run's facts as the readers find them: the decode
    program's operations by scope path inside its ``runs``, a
    ``kf:serve.decode_read`` span for each of ``read_stats``, and the
    runs' device times as ``lib/trace.py`` reduced them."""
    host = [spans.Span("kf:serve.decode_read", float(i), 0.5, dict(st))
            for i, st in enumerate(read_stats)]
    seconds = run_seconds or [e - s for s, e in runs]
    reduced = trace.Trace({"/device:TPU:0": {"ops": [], "modules": [
        ("jit__decode_fn", float(i), d) for i, d in enumerate(seconds)]
        + [("jit__prefill_fn", 99.0, 7.0)]}}, [])
    return {"trace": {"dir": "/nonexistent", "reduced": {"trace": reduced},
                      "decode_paths": (len(runs), decode_paths.path_seconds(
                          ops, runs)),
                      "spans": spans.Spans(host, [], [])},
            "peaks": {"hbm_bytes_per_s": 800e9},
            "spec": {"config": files.load_config(config)}}


def said(live, written, read=1000, row=ROW):
    return {"kv_rows_live": live, "kv_rows_read": read,
            "kv_rows_written": written, "kv_row_bytes": row}


def dense_tiny(**over):
    """Three decode runs of 4 s (0-4, 10-14, 20-24): in each the write
    0-1, the attention 1-3 (a ``while`` holding its body 1-2), the MLP
    3-4; a prefill's attention 5-9 outside them.  The steps had to read
    100, 200 and 300 rows and wrote 10 each, of 1000 read."""
    def at(t0):
        return [(p, t0 + a, t0 + b) for p, a, b in (
            ("jit(_decode_fn)/kv_write/dynamic_update_slice", 0, 1),
            ("jit(_decode_fn)/attn_core/while", 1, 3),
            ("jit(_decode_fn)/attn_core/while/body/dot_general", 1, 2),
            ("jit(_decode_fn)/mlp/dot_general", 3, 4))]

    ops = at(0) + at(10) + at(20) + [
        ("jit(_prefill_fn)/attn_core/dot_general", 5, 9)]
    return facts_of(ops, [(0, 4), (10, 14), (20, 24)],
                    [said(100, 10), said(200, 10), said(300, 10)], **over)


def test_the_three_readers_by_hand():
    f = dense_tiny()
    assert metric("kv_rows_live_share", f) == pytest.approx(
        100 * (0.1 + 0.2 + 0.3) / 3)
    # the write and the attention, the while and its body one stretch; the
    # prefill's attention is in no decode run, the MLP is no part of it
    assert kv.attn_ms_per_run(f) == pytest.approx(3000.0)
    least = (200 + 10) * ROW / 800e9
    assert kv.least_seconds(f) == pytest.approx(least)
    assert metric("kv_attn_roofline", f) == pytest.approx(100 * least / 3.0)
    # equal runs do not spread; 4.0, 4.0, 4.0, 4.2, 4.4: the 90th
    # percentile 4.32, the 10th and the median 4.0
    assert metric("decode_step_spread", f) == 0.0
    g = dense_tiny(run_seconds=[4.0, 4.4, 4.0, 4.2, 4.0])
    assert metric("decode_step_spread", g) == pytest.approx(100 * 0.32 / 4)


def test_a_program_with_window_and_full_scopes_is_timed_by_those():
    """Where the path names the kind of layer, ``attn_core`` alone is
    not the attention over K/V rows: a hybrid program's recurrent update
    runs under it too."""
    def at(t0):
        return [(p, t0 + a, t0 + b) for p, a, b in (
            ("jit(_decode_fn)/kv_write/select_n", 0, .5),
            ("jit(_decode_fn)/attn_core/attn_window/bgjqs", .5, 1.5),
            ("jit(_decode_fn)/attn_core/attn_full/bgjqs", 1.5, 2),
            ("jit(_decode_fn)/attn_core/kda_state/pallas_call", 2, 4))]

    runs = [(0, 4), (10, 14), (20, 24)]
    f = facts_of(at(0) + at(10) + at(20), runs, [said(50, 4, row=4096)] * 3,
                 config="command-a-plus-05-2026")
    assert kv.attn_ms_per_run(f) == pytest.approx(2000.0)
    assert metric("kv_attn_roofline", f) == pytest.approx(
        100 * 54 * 4096 / 800e9 / 2.0)


def test_a_row_narrower_than_the_configurations_is_refused():
    assert kv.config_row_bytes(files.load_config("gpt2-large")) == 5120
    assert kv.config_row_bytes(files.load_config(
        "command-a-plus-05-2026")) == 4096
    assert kv.config_row_bytes(files.load_config("Solar-Open2-250B")) == 4096
    assert kv.config_row_bytes(files.load_config(
        "openPangu-Ultra-MoE-718B")) is None
    narrow = dense_tiny()
    for s in narrow["trace"]["spans"].host:
        s.stats["kv_row_bytes"] = ROW // 2
    with pytest.raises(ValueError, match="narrower"):
        metric("kv_attn_roofline", narrow)
    moving = dense_tiny()
    moving["trace"]["spans"].host[1].stats["kv_row_bytes"] = 2 * ROW
    with pytest.raises(ValueError, match="narrower"):
        metric("kv_attn_roofline", moving)
    # a wider row (a float32 cache) is the program's to state
    wide = dense_tiny()
    for s in wide["trace"]["spans"].host:
        s.stats["kv_row_bytes"] = 2 * ROW
    assert metric("kv_attn_roofline", wide) == pytest.approx(
        2 * metric("kv_attn_roofline", dense_tiny()))


def test_a_program_without_the_attrs_gives_nothing_to_the_span_readers():
    """The parent with these files laid over it: its spans carry no
    ``kv_*`` attr, its decode runs are in the trace all the same."""
    bare = dense_tiny(run_seconds=[4.0, 4.4, 4.0, 4.2, 4.0])
    for s in bare["trace"]["spans"].host:
        s.stats.clear()
    assert metric("kv_rows_live_share", bare) is None
    assert metric("kv_attn_roofline", bare) is None
    assert metric("decode_step_spread", bare) == pytest.approx(8.0)
    for name in ("kv_rows_live_share", "kv_attn_roofline",
                 "decode_step_spread"):
        assert metric(name, {"trace": None}) is None   # a run not traced
    # a program that carries no scope at all (read back from a cache the
    # parent filled) has nothing to time
    unscoped = dense_tiny()
    unscoped["trace"]["decode_paths"] = (3, {"": 12.0})
    assert metric("kv_attn_roofline", unscoped) is None


def test_the_first_token_clock_over_the_windows_requests():
    def req(prompt, ttft, queue, counted=True):
        return {"in_window": counted, "prompt_len": prompt,
                "engine_ttft_s": ttft, "queue_s": queue}

    f = {"serve": {"requests": [
        req(1000, 0.25, 0.05), req(3000, 0.45, 0.25),
        req(500, 9.0, 1.0, counted=False),      # due before the window
        req(2000, None, None)]}}                # never finished
    assert metric("first_token_ms_per_ktok", f) == pytest.approx(
        1e6 * (0.2 + 0.2) / 4000)
    assert metric("first_token_ms_per_ktok",
                  {"serve": {"requests": []}}) is None


def recorded():
    path = os.path.join(HERE, "recorded_kv_chat.json")
    if not os.path.exists(path):
        pytest.skip("recorded_kv_chat.json not recorded")
    with open(path) as f:
        rec = json.load(f)
    ops = [(rec["paths"][p], s, e) for p, s, e in rec["ops"]]
    f = facts_of(ops, [tuple(r) for r in rec["runs"]], rec["read_stats"],
                 run_seconds=rec["run_seconds"])
    f["peaks"]["hbm_bytes_per_s"] = rec["hbm_bytes_per_s"]
    return f, rec


def test_readers_on_a_recorded_excerpt_of_the_chip():
    """``gpt2l-serve-chat`` on a TPU v5 lite (PR 38): every traced step's
    attrs and every decode run's time, so those two read what the whole
    trace read to the last digit; the first three runs' operations, so
    the roofline reads it to a hundredth of itself."""
    f, rec = recorded()
    whole = rec["whole_trace"]
    assert len(rec["read_stats"]) > 100 and len(rec["run_seconds"]) > 100
    assert all(s["kv_row_bytes"] == ROW and s["kv_rows_read"]
               == 36 * 16 * 1024 for s in rec["read_stats"])
    assert metric("kv_rows_live_share", f) == pytest.approx(
        whole["kv_rows_live_share"], rel=1e-12)
    assert metric("decode_step_spread", f) == pytest.approx(
        whole["decode_step_spread"], rel=1e-9)
    assert kv.least_seconds(f) == pytest.approx(whole["least_seconds"],
                                                rel=1e-12)
    assert kv.attn_ms_per_run(f) == pytest.approx(whole["attn_ms_per_run"],
                                                  rel=1e-2)
    assert metric("kv_attn_roofline", f) == pytest.approx(
        whole["kv_attn_roofline"], rel=1e-2)
    # the numbers themselves, as PERF.md has them for that run
    assert 0 < whole["kv_attn_roofline"] < whole["kv_rows_live_share"] < 10
    assert 5.5 < whole["attn_ms_per_run"] < 6.5
    assert whole["decode_step_spread"] < 1.0


def test_the_chat_cells_rehearsal_prints_the_new_readers(bench):
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["name"] in ("kv_rows_live_share", "kv_attn_roofline",
                             "decode_step_spread", "first_token_ms_per_ktok")}
    assert len(mine) == 4 and all(CHAT in m["workloads"]
                                  for m in mine.values())
    assert "pangu-serve-longctx" not in mine["kv_rows_live_share"][
        "workloads"] + mine["kv_attn_roofline"]["workloads"]
    rc, last, out = rehearse(CHAT, trace=1)
    assert rc == 0, out[-3000:]
    # (the CPU's trace has the host's spans and no device)
    assert 0 < last["metrics"]["rehearsal.kv_rows_live_share"]["value"] < 100
    assert last["metrics"]["rehearsal.first_token_ms_per_ktok"]["value"] > 0
    assert "rehearsal.kv_attn_roofline" not in last["metrics"]
    assert "rehearsal.decode_step_spread" not in last["metrics"]
