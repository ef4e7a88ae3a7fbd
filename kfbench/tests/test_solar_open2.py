"""The ``solar_open2`` family's files: the sizes as published and as cut,
the parameter count against the tree's leaves, the bytes a decode step's
KDA layers have to move worked out by hand, the tiny preset the adapter
makes of ``--rehearse``'s overlay, the two new readers on a span excerpt
small enough to check by hand (and None from a program that writes
neither scope nor attr), the traffic file's multiset whatever the seed,
and the cell's rehearsal."""

import pytest

from conftest import (BIG, EXPERT_CELL_READERS,
                      one_schedule_whatever_the_seed, rehearse)
from kfbench.lib import decode_paths, files, solar_open2, spans

CELL = "solar2-serve-reasoning"
CONFIG = "Solar-Open2-250B"


def test_sizes_as_published_and_as_cut():
    cfg = files.load_config(CONFIG)
    z = solar_open2.sizes(cfg)
    assert (z["d"], z["heads"], z["kv_heads"], z["head_dim"]) == (
        4096, 64, 8, 128)
    assert (z["kda_heads"], z["kda_dim"], z["taps"], z["gate_rank"]) == (
        64, 128, 4, 128)
    assert (z["expert_width"], z["router_width"], z["top_k"], z["shared"],
            z["top_k_scale"]) == (1280, 320, 8, 1, 1.0)
    assert (z["layers"], z["gqa_layers"], z["held"], z["first"], z["vocab"],
            z["init_layers"]) == (4, (0,), 10, 0, 24576, 48)
    # every number of the catalog's row but the three the file lists
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_hidden_layers": 48,
        "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "vocab_size": 196608,
        "intermediate_size": 10240, "moe_intermediate_size": 1280,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "tie_word_embeddings": False, "max_position_embeddings": 1048576,
        "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
        "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
        "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "n_routed_experts": 320,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    differ = {k for k, v in published.items() if cfg[k] != v}
    assert differ == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    entry = [c for c in files.load_benchmark()["configs"]
             if c["name"] == CONFIG][0]
    assert set(entry["reduced"]) == differ and len(entry["source"]) <= 200
    assert cfg["source"].startswith(entry["source"])
    for key in differ:       # the published number stands beside each
        assert cfg[key + "_published"] == published[key]
    assert "32 chips share each layer" in cfg["deployment"]
    assert "12 pipeline stages" in cfg["deployment"]


def test_the_parameter_count_is_the_trees():
    import jax
    import numpy as np

    cfg = files.load_config(CONFIG)
    adapter = files.load_adapter("solar_open2")
    assert adapter.n_params(cfg) == 1_420_939_840
    # the issue's parts: a GQA and a KDA mixer, an expert
    z = solar_open2.sizes(cfg)
    assert solar_open2.kda_layer_params(z) == 137_740_480
    assert 4096 * 128 * (3 * 64 + 2 * 8) == 109_051_904
    tree = jax.eval_shape(adapter.program_model(cfg).init,
                          jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(tree)
    assert sum(int(np.prod(x.shape)) for x in leaves) == adapter.n_params(cfg)
    # bfloat16 but for the routers, the norms' scales, a_log and b_dt
    f32 = sum(int(np.prod(x.shape)) for x in leaves if x.dtype == "float32")
    assert f32 == 4 * (4096 * 320 + 2 * 4096) + 4096 \
        + 3 * (64 + 8192 + 128)
    tiny = dict(cfg, **files.load_json("rehearsal.json")["config"])
    tree = jax.eval_shape(adapter.program_model(tiny).init,
                          jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        tree)) == adapter.n_params(tiny)


def test_decode_state_bytes_by_hand():
    cfg = files.load_config(CONFIG)
    # a slot of one KDA layer: 64 heads of 128 x 128 float32, and a tail
    # of 3 inputs of 3 x 8192 bfloat16 channels
    slot = 64 * 128 * 128 * 4 + 3 * 24576 * 2
    assert slot == 4_194_304 + 147_456
    weights = 3 * 137_740_480 * 2                # three layers, bfloat16
    assert solar_open2.decode_state_bytes(cfg, 0) == weights
    # read and written once, three layers
    assert solar_open2.decode_state_bytes(cfg, 1) - weights == 3 * 2 * slot
    whole = solar_open2.decode_state_bytes(cfg, 128)
    assert whole == weights + 128 * 3 * 2 * slot
    # the issue's count: 2 x 1.61 GB of state (and 0.11 of tails), 0.83 of
    # weights: 4.9-5.1 ms at 819 GB/s
    assert whole == pytest.approx(2 * 1.61e9 + 0.113e9 + 0.826e9, rel=2e-3)
    assert 4.9e-3 < whole / 819e9 < 5.1e-3


def test_the_overlay_of_a_rehearsal_gives_a_tiny_model():
    cfg = dict(files.load_config(CONFIG),
               **files.load_json("rehearsal.json")["config"])
    z = solar_open2.sizes(cfg)
    assert (z["d"], z["layers"], z["gqa_layers"], z["heads"]) == (
        64, 4, (0,), 8)
    assert z["held"] < z["router_width"] and z["kv_heads"] < z["heads"]
    adapter = files.load_adapter("solar_open2")
    assert adapter.n_params(cfg) < 1e6
    model = adapter.program_model(cfg)
    assert model.cfg.max_seq == 128
    assert model.cfg.recurrent_layers == (1, 2, 3)


def decode_facts(attrs=True):
    """Three decode runs (0-4, 10-14, 20-24).  In each: the KDA layers'
    projections 0-1, their state 1-3 (a read-out 1-2 and the update 2-3),
    the softmax layer 3-3.5, the row write 3.5-4.  The prefill's chunked
    recurrence (5-9) is outside every decode run.  The steps moved 8
    slots' states, of which 2, 4 and 6 were live."""
    def at(t0):
        ops = [("jit(_decode_fn)/attn_proj/kda_proj/dot_general", 0, 1),
               ("jit(_decode_fn)/attn_core/kda_state/reduce_sum", 1, 2),
               ("jit(_decode_fn)/attn_core/kda_state/select_n", 2, 3),
               ("jit(_decode_fn)/attn_core/attn_full/dot_general", 3, 3.5),
               ("jit(_decode_fn)/kv_write/dynamic_update_slice", 3.5, 4)]
        return [(p, t0 + a, t0 + b) for p, a, b in ops]

    ops = at(0) + at(10) + at(20) + [
        ("jit(_prefill_fn)/attn_core/kda_chunk/while", 5, 9)]
    runs = [(0, 4), (10, 14), (20, 24)]
    host = [spans.Span("kf:serve.decode_read", t, 1, dict(
        state_slots_live=live, state_slots_read=8,
        state_bytes_read=8 * 3 * 4_341_760) if attrs else {})
        for t, live in ((0, 2), (10, 4), (20, 6))]
    return {"trace": {"dir": "/nonexistent",
                      "decode_paths": (3, decode_paths.path_seconds(ops, runs)),
                      "spans": spans.Spans(host, [], [])},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "spec": {"config": files.load_config(CONFIG),
                     "traffic": files.load_traffic("reasoning-open")}}


def metric(name, facts):
    return files.load_module("metrics", name).read(facts, {"name": name})


def test_the_new_readers_by_hand():
    f = decode_facts()
    assert metric("decode_path_ms.kda_proj", f) == pytest.approx(1000.0)
    assert metric("decode_path_ms.kda_state", f) == pytest.approx(2000.0)
    assert metric("decode_path_ms.attn_full", f) == pytest.approx(500.0)
    assert metric("state_slots_live_share", f) == pytest.approx(
        100 * (2 + 4 + 6) / 3 / 8)
    # 4 live slots a step on the mean, over the 3 s under both scopes
    least = solar_open2.decode_state_bytes(f["spec"]["config"], 4) / 819e9
    assert metric("kda_state_roofline", f) == pytest.approx(
        100 * least / 3.0)


def test_the_roofline_counts_live_slots_and_two_trips():
    """Every slot live and the state moved once each way in the least
    time the chip could take reads 100 %; XLA's three trips over it for
    the same slots read under that, and a tenth of the slots live for the
    same device time still less."""
    f = decode_facts()
    cfg = f["spec"]["config"]
    least = solar_open2.decode_state_bytes(cfg, 128) / 819e9

    def reads(live, took):
        f["trace"]["spans"] = spans.Spans(
            [spans.Span("kf:serve.decode_read", t, 1, dict(
                state_slots_live=live, state_slots_read=128))
             for t in (0, 10, 20)], [], [])
        f["trace"]["decode_paths"] = (3, {
            "jit(_decode_fn)/attn_core/kda_state/x": 3 * took * 0.8,
            "jit(_decode_fn)/attn_proj/kda_proj/y": 3 * took * 0.2})
        return metric("kda_state_roofline", f)

    assert reads(128, least) == pytest.approx(100.0)
    state = 128 * 3 * 64 * 128 * 128 * 4 / 819e9     # one trip over it
    assert 70 < reads(128, least + state) < 75
    assert reads(12.8, least) < 30


def test_a_program_without_the_scopes_or_attrs_gives_nothing_to_read():
    """The parent with these files laid over it, a run that was not
    traced, and a traced run of a model without a recurrent state: None,
    never a raise."""
    bench = files.load_benchmark()
    new = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [CELL]]
    assert sorted(new) == sorted([
        "decode_path_ms.kda_proj", "decode_path_ms.kda_state",
        "kda_state_roofline", "state_slots_live_share"])
    other = decode_facts(attrs=False)
    other["trace"]["decode_paths"] = (3, {"": 9.0})   # operations, no scope
    for name in new:
        assert metric(name, {"trace": None}) is None, name
        assert metric(name, other) is None, name
    # the scopes without the attrs give no share
    assert metric("kda_state_roofline", decode_facts(attrs=False)) is None
    # ... and the cell is on the list of every reader it shares with both
    # expert cells, on ``attn_full`` and on the judged metric
    lists = {m["name"]: m.get("workloads", ()) for m in bench["per_layer"]}
    for name in EXPERT_CELL_READERS + (
            "decode_path_ms.attn_full", "kv_rows_live_share",
            "kv_attn_roofline"):
        assert CELL in lists[name], name
    for name in ("moe_experts_roofline", "decode_path_ms.attn_window",
                 "prefill_ms_per_ktok.chat", "latent_rows_live_share"):
        assert CELL not in lists[name], name
    judged = [m for m in bench["end_to_end"] if m["name"] == "itl_p50_ms"][0]
    assert CELL in judged["workloads"]


def test_the_traffic_offers_one_multiset_whatever_the_seed():
    tr, schedule = one_schedule_whatever_the_seed("reasoning-open")
    prompts = sorted(p for _, p, _, w in schedule if w)
    outputs = sorted(o for _, _, o, w in schedule if w)
    assert prompts[0] >= 64 and prompts[-1] <= 2048
    assert outputs[0] >= 384 and outputs[-1] <= 3072
    assert 400 < prompts[len(prompts) // 2] < 650
    assert 1300 < outputs[len(outputs) // 2] < 1800
    assert tr["max_total"] == tr["engine"]["max_seq"]
    e = tr["engine"]
    assert (e["max_batch"], e["max_seq"], e["page_tokens"], e["kv_pages"]) \
        == (128, 4096, 256, 64)
    assert e["max_seq"] % e["page_tokens"] == 0
    cell = files.cell(files.load_benchmark(), CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "reasoning-open", CONFIG)
    assert tr["rate_rps"] > 0 and "PLACEHOLDER" not in tr["rate_reason"]
    assert f"{tr['rate_rps']:g}/s" in cell["why"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(bench, trace):
    rc, last, out = rehearse(CELL, seed=BIG, trace=trace)
    assert rc == 0, out[-3000:]
    assert last["correct"] is True, out[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert all(k.startswith("rehearsal.") for k in last["metrics"])
    if not trace:
        assert set(last["metrics"]) == {"rehearsal.itl_p50_ms",
                                        "rehearsal.setup_s"}
    else:   # the span attrs are there off the chip too
        assert 0 < last["metrics"]["rehearsal.state_slots_live_share"][
            "value"] <= 100
