"""The ``cohere2_moe`` family's files: the bytes function of
``moe_experts_roofline`` worked out by hand, the new readers on a span
excerpt small enough to check by hand (and None from a program that
writes neither scope nor attr), the traffic file's multiset whatever the
seed, the tiny preset the adapter makes of ``--rehearse``'s overlay, and
the cell's rehearsal."""

import pytest

from conftest import one_schedule_whatever_the_seed, rehearse
from kfbench.lib import cohere2, decode_paths, files, spans

CELL = "cmdaplus-serve-mixedlen"


def test_decode_expert_bytes_by_hand():
    cfg = files.load_config("command-a-plus-05-2026")
    weights = 4 * 16 * 3 * 4096 * 4096 * 2        # the issue's 6.44 GB
    assert weights == 6_442_450_944
    rows = 4 * 16 * 32 * (4096 * 2 + 2 * 2 * 4096 * 2 + 4096 * 4)
    combine = 4 * (32 * 16 * 4 + 32 * 4096 * 4)
    assert cohere2.decode_expert_bytes(cfg, 32) == weights + rows + combine
    # the weights are all but the whole of it: a step is bound by them
    assert weights / cohere2.decode_expert_bytes(cfg, 32) > 0.98
    # at 819 GB/s that is the 7.9 ms a step cannot go under
    assert 7.8e-3 < cohere2.decode_expert_bytes(cfg, 32) / 819e9 < 8.1e-3


def test_sizes_as_published_and_as_cut():
    z = cohere2.sizes(files.load_config("command-a-plus-05-2026"))
    assert (z["d"], z["heads"], z["kv_heads"], z["head_dim"]) == (
        4096, 128, 8, 128)
    assert (z["expert_width"], z["router_width"], z["top_k"], z["shared"]
            ) == (4096, 128, 8, 4)
    assert (z["window"], z["period"], z["theta"]) == (4096, 4, 50000.0)
    assert (z["layers"], z["held"], z["first"], z["vocab"]) == (
        4, 16, 0, 32768)
    adapter = files.load_adapter("cohere2_moe")
    cfg = files.load_config("command-a-plus-05-2026")
    assert adapter.n_params(cfg) == 4_733_292_544   # 9.47 GB in bfloat16


def test_the_overlay_of_a_rehearsal_gives_a_tiny_model():
    cfg = dict(files.load_config("command-a-plus-05-2026"),
               **files.load_json("rehearsal.json")["config"])
    z = cohere2.sizes(cfg)
    assert (z["d"], z["layers"], z["heads"], z["kv_heads"]) == (64, 4, 16, 2)
    assert z["held"] < z["router_width"] and z["window"] == 16
    assert files.load_adapter("cohere2_moe").n_params(cfg) < 1e6


def decode_facts():
    """Three decode runs (0-4, 10-14, 20-23.5) and a prefill (5-9).  In
    each decode run: router 0-0.5, experts 0.5-2.5 (a ``while`` 0.5-2.5
    holding its body 0.5-1.5), shared 2.5-3, window attention 3-3.5; the
    third run stops there, the first two add full attention 3.5-4.  The
    prefill's experts (5-9) are outside every decode run."""
    def at(t0, full=True):
        ops = [("jit(_decode_fn)/mlp/moe_router/dot_general", 0, .5),
               ("jit(_decode_fn)/mlp/moe_experts/while", .5, 2.5),
               ("jit(_decode_fn)/mlp/moe_experts/while/body/dot_general",
                .5, 1.5),
               ("jit(_decode_fn)/mlp/moe_shared/dot_general", 2.5, 3),
               ("jit(_decode_fn)/attn_core/attn_window/bgjqs", 3, 3.5)]
        if full:
            ops.append(("jit(_decode_fn)/attn_core/attn_full/bgjqs", 3.5, 4))
        return [(p, t0 + a, t0 + b) for p, a, b in ops]

    ops = at(0) + at(10) + at(20, full=False) + [
        ("jit(_prefill_fn)/mlp/moe_experts/ragged_dot", 5, 9)]
    runs = [(0, 4), (10, 14), (20, 23.5)]
    host = [spans.Span("kf:serve.decode_read", t, 1, dict(
        experts_touched=touched, experts_held=64,
        expert_load_max=top, expert_load_mean=mean))
        for t, touched, top, mean in (
            (0, 16, 1, 0.25), (10, 24, 2, 0.5), (20, 32, 3, 0.5))]
    # the decode spans themselves say nothing of the routing
    host += [spans.Span("kf:serve.decode", t, 1, dict(batch=1, width=4))
             for t in (0, 10, 20)]
    return {"trace": {"dir": "/nonexistent",
                      "decode_paths": (3, decode_paths.path_seconds(ops, runs)),
                      "spans": spans.Spans(host, [], [])},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "spec": {"config": files.load_config("command-a-plus-05-2026"),
                     "traffic": files.load_traffic("mixedlen-open")}}


def metric(name, facts):
    return files.load_module("metrics", name).read(facts, {"name": name})


def test_decode_path_readers_walk_the_whole_scope_path():
    f = decode_facts()
    assert metric("decode_path_ms.moe_router", f) == pytest.approx(500.0)
    # the while and its body are one stretch, counted once; the prefill's
    # experts are in no decode run
    assert metric("decode_path_ms.moe_experts", f) == pytest.approx(2000.0)
    assert metric("decode_path_ms.moe_shared", f) == pytest.approx(500.0)
    assert metric("decode_path_ms.attn_window", f) == pytest.approx(500.0)
    assert metric("decode_path_ms.attn_full", f) == pytest.approx(1e3 / 3)


def test_routing_readers_take_the_spans_that_carry_attrs():
    f = decode_facts()
    assert metric("moe_experts_touched", f) == pytest.approx(
        100 * (16 + 24 + 32) / 64 / 3)
    assert metric("moe_load_max_over_mean", f) == pytest.approx(
        (4 + 4 + 6) / 3)
    # 2 s under moe_experts against 7.9 ms of bytes
    byts = cohere2.decode_expert_bytes(f["spec"]["config"], 32)
    assert metric("moe_experts_roofline", f) == pytest.approx(
        100 * byts / 819e9 / 2.0)


def test_a_program_without_the_scopes_or_attrs_gives_nothing_to_read():
    """The parent with these files laid over it, a run that was not
    traced, and a traced run of a dense model: None, never a raise."""
    bench = files.load_benchmark()
    # the family's eight readers: this cell heads the list of each,
    # whichever later cells joined it
    new = ["decode_path_ms.moe_router", "decode_path_ms.moe_experts",
           "decode_path_ms.moe_shared", "decode_path_ms.attn_window",
           "decode_path_ms.attn_full", "moe_experts_roofline",
           "moe_experts_touched", "moe_load_max_over_mean"]
    lists = {m["name"]: m.get("workloads", ()) for m in bench["per_layer"]}
    assert all(lists[name][0] == CELL for name in new)
    dense = decode_facts()
    dense["trace"]["decode_paths"] = (3, {"": 9.0})   # operations, no scope
    dense["trace"]["spans"] = spans.Spans(
        [spans.Span(name, t, 1, attrs) for t in (0, 10, 20)
         for name, attrs in (("kf:serve.decode", dict(batch=1, width=4)),
                             ("kf:serve.decode_read", {}))], [], [])
    for name in new:
        assert metric(name, {"trace": None}) is None, name
        assert metric(name, dense) is None, name


def test_the_traffic_offers_one_multiset_whatever_the_seed():
    tr, schedule = one_schedule_whatever_the_seed("mixedlen-open")
    prompts = [p for _, p, _, w in schedule if w]
    assert min(prompts) >= 128 and max(prompts) <= 7168
    past_window = sum(p > 4096 for p in prompts) / len(prompts)
    assert 0.1 < past_window < 0.3                   # "about a fifth"
    assert tr["max_total"] == tr["engine"]["max_seq"]
    e = tr["engine"]
    assert e["max_seq"] % e["page_tokens"] == 0 and 4096 % e["page_tokens"] == 0


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(bench, trace):
    rc, last, out = rehearse(CELL, trace=trace)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True, out[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert all(k.startswith("rehearsal.") for k in last["metrics"])
    if not trace:
        assert set(last["metrics"]) == {"rehearsal.itl_p50_ms",
                                        "rehearsal.setup_s"}


def recorded_facts():
    """The first three decode runs of a chip trace of the cell's engine
    with 8 live slots (``recorded_decode_paths.json``, cut by
    ``tools/decode_by_occupancy.py --excerpt``; TPU v5 lite, PR 27).  It
    was recorded while a step's routing attrs rode on the NEXT
    ``kf:serve.decode`` span; they are kept under ``read_spans``, as the
    attrs of ``kf:serve.decode_read`` that they are now."""
    import json
    import os

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "recorded_decode_paths.json")) as f:
        rec = json.load(f)
    ops = [tuple(o) for o in rec["ops"]]
    runs = [tuple(r) for r in rec["runs"]]
    host = [spans.Span("kf:serve.decode_read", start, dur, stats)
            for start, dur, stats in rec["read_spans"]]
    facts = decode_facts()
    facts["trace"].update(
        decode_paths=(len(runs), decode_paths.path_seconds(ops, runs)),
        spans=spans.Spans(host, [], []))
    return facts, rec


def test_readers_on_a_recorded_excerpt_of_the_chip():
    f, rec = recorded_facts()
    # what the same tool printed for the whole trace (12 runs), to 0.2 %
    want = {"moe_router": 0.01942, "moe_experts": 8.5395, "moe_shared": 2.1543,
            "attn_window": 2.2152, "attn_full": 1.4720}
    for scope, ms in want.items():
        assert metric("decode_path_ms." + scope, f) == pytest.approx(
            ms, rel=2e-3), scope
    # a run is 16.71 ms of which the scopes above are 14.4
    assert sum(e - s for s, e in rec["runs"]) / 3 == pytest.approx(
        16.71e-3, rel=1e-3)
    assert 93.0 < metric("moe_experts_roofline", f) < 94.5
    # 8 live slots: one token a held expert a layer, 29-31 of 64 touched
    assert metric("moe_experts_touched", f) == pytest.approx(
        100 * sum(s[2]["experts_touched"] for s in rec["read_spans"])
        / 64 / len(rec["read_spans"]))
    assert 40 < metric("moe_experts_touched", f) < 50
    assert 4 < metric("moe_load_max_over_mean", f) < 7
    # the scope paths as the profiler keeps them
    paths = {p for p, _, _ in rec["ops"]}
    assert "jit(_decode_fn)/mlp/moe_experts/etd,edf->etf/dot_general:" in paths
    assert any("/attn_core/attn_window/" in p for p in paths)
    assert not any("ragged" in p for p in paths)
