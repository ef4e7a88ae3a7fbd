"""The ``phi4flash`` family's files: the sizes as published (nothing is
cut), the parameter count against the tree's leaves, the bytes a decode
step's state-space layers and attention have to move worked out by hand,
the tiny preset the adapter makes of ``--rehearse``'s overlay, the new
readers on a span excerpt small enough to check by hand, on a recorded
excerpt of the cell's own chip trace
(``recorded_sambay_mathreason.json``, cut by ``tools/sambay_excerpt.py``)
and on a program that writes neither scope nor attr (None, never a
raise), the traffic file's multiset whatever the seed, and the cell's
rehearsal."""

import json
import os

import pytest

from conftest import one_schedule_whatever_the_seed, rehearse
from kfbench.lib import decode_paths, files, phi4flash, spans

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "phi4flash-serve-mathreason"
CONFIG = "Phi-4-mini-flash-reasoning"
BIG = 2 ** 31 + 4444
ROW = 5120
NEW = ("decode_path_ms.ssm_proj", "decode_path_ms.ssm_state",
       "decode_path_ms.gmu", "decode_path_ms.attn_cross",
       "ssm_state_roofline", "sambay_attn_roofline")
#: a slot's state and tail in one Mamba layer, and that layer's weights
SLOT, MAMBA = 5120 * 16 * 4 + 3 * 5120 * 2, 41_241_600


def test_sizes_as_published_and_nothing_cut():
    cfg = files.load_config(CONFIG)
    z = phi4flash.sizes(cfg)
    assert (z["d"], z["heads"], z["kv_heads"], z["head_dim"], z["ffn"]) == (
        2560, 40, 20, 64, 10240)
    assert (z["layers"], z["vocab"], z["window"], z["eps"], z["std"]) == (
        32, 200064, 512, 1e-5, 0.02)
    assert (z["inner"], z["state"], z["taps"], z["dt_rank"]) == (
        5120, 16, 4, 160)
    kinds = phi4flash.layer_kinds(z)
    assert [kinds.count(k) for k in ("mamba", "attn_window", "attn_full",
                                     "gmu", "attn_cross")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "attn_full"
    assert kinds[18:] == ["gmu", "attn_cross"] * 7
    # every number of the catalog's row, as it is
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    assert {k for k, v in published.items() if cfg[k] != v} == set()
    assert cfg["reduced"] == {} and cfg["family"] == "phi4flash"
    entry = [c for c in files.load_benchmark()["configs"]
             if c["name"] == CONFIG][0]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert cfg["source"].startswith(entry["source"])
    assert "WHOLE model" in cfg["deployment"]
    assert "3,852,562,944" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 12
    assert any("no network" in a for a in cfg["assumed"])


def test_the_parameter_count_is_the_trees():
    import jax
    import numpy as np

    cfg = files.load_config(CONFIG)
    adapter = files.load_adapter("phi4flash")
    z = phi4flash.sizes(cfg)
    assert phi4flash.mamba_layer_params(z) == MAMBA
    assert adapter.n_params(cfg) == (
        32 * 78_643_200 + 9 * MAMBA + 9 * 19_668_864 + 7 * 13_112_704
        + 7 * 26_214_400 + 332_800 + 512_163_840) == 3_852_562_944
    tree = jax.eval_shape(adapter.program_model(cfg).init,
                          jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        tree)) == adapter.n_params(cfg)
    tiny = dict(cfg, **files.load_json("rehearsal.json")["config"])
    tree = jax.eval_shape(adapter.program_model(tiny).init,
                          jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        tree)) == adapter.n_params(tiny) < 1e6


def test_decode_bytes_by_hand():
    cfg = files.load_config(CONFIG)
    # a row of one layer: 20 key/value heads of 64 (ten pairs of 128), K
    # and V, bfloat16
    assert phi4flash.row_bytes(cfg) == 20 * 64 * 2 * 2 == ROW
    assert phi4flash.decode_attn_bytes(cfg, 0) == 0
    # one live slot at a context of 1,500: the slab's rows eight times,
    # 512 of each of eight rings, nine rows written
    rows = 8 * 1500 + 8 * 512 + 9
    assert phi4flash.decode_attn_bytes(cfg, rows) == rows * ROW == 82_457_600
    # sixty such slots in the least time the chip could take: 6 ms
    assert 60 * rows * ROW / 819e9 == pytest.approx(6.04e-3, rel=1e-2)
    # the nine Mamba layers: with nothing live their weights alone, then
    # each live slot's state and tail read and written once
    assert SLOT == 358_400
    assert phi4flash.decode_state_bytes(cfg, 0) == 9 * 2 * MAMBA
    assert phi4flash.decode_state_bytes(cfg, 60) == 9 * (
        2 * SLOT * 60 + 2 * MAMBA) == 1_129_420_800
    # ... where the program moves all 128 slots' states, 0.83 GB of them
    assert 9 * 2 * SLOT * 128 == 825_753_600


def test_the_overlay_of_a_rehearsal_gives_a_tiny_model():
    cfg = dict(files.load_config(CONFIG),
               **files.load_json("rehearsal.json")["config"])
    z = phi4flash.sizes(cfg)
    assert (z["d"], z["layers"], z["heads"], z["kv_heads"], z["head_dim"],
            z["ffn"]) == (64, 8, 8, 4, 8, 256)
    assert (z["window"], z["inner"], z["state"], z["dt_rank"]) == (
        16, 128, 4, 4)
    model = files.load_adapter("phi4flash").program_model(cfg)
    assert model.cfg.max_seq == 128 and len(model.cfg.recurrent_layers) == 3
    assert [model.cfg.kind(i) for i in range(8)] == phi4flash.layer_kinds(z)


#: a decode run's operations by scope path: (path, start, end)
STEP = [("jit(_decode_fn)/attn_proj/ssm_proj/dot_general", 0, 1),
        ("jit(_decode_fn)/attn_core/ssm_state/fusion", 1, 2),
        ("jit(_decode_fn)/attn_proj/dot_general", 2, 2.5),
        ("jit(_decode_fn)/kv_write/dynamic_update_slice", 2.5, 3),
        ("jit(_decode_fn)/attn_core/attn_window/decode_attn", 3, 3.5),
        ("jit(_decode_fn)/attn_core/attn_full/decode_attn", 3.5, 4.5),
        ("jit(_decode_fn)/attn_proj/gmu/dot_general", 4.5, 5),
        ("jit(_decode_fn)/attn_core/attn_cross/decode_attn", 5, 7),
        ("jit(_decode_fn)/mlp/dense_mlp/dot_general", 7, 8)]


def decode_facts(attrs=True):
    """Three decode runs (0-8, 10-18, 20-28), each :data:`STEP`; a
    prefill's scan (30-34) is outside every decode run.  The steps moved
    128 slots' states for 40 / 60 / 80 live ones and read 1,000,000 rows
    where their live contexts had to read 100,000 / 200,000 / 300,000,
    writing 360 / 540 / 720."""
    ops = [(p, t0 + a, t0 + b) for t0 in (0, 10, 20) for p, a, b in STEP] + [
        ("jit(_prefill_fn)/attn_core/ssm_scan/while", 30, 34)]
    runs = [(0, 8), (10, 18), (20, 28)]
    host = [spans.Span("kf:serve.decode_read", t, 1, dict(
        kv_rows_live=live, kv_rows_read=1_000_000, kv_rows_written=9 * slots,
        kv_row_bytes=ROW, kv_rows_live_full=live // 2,
        state_slots_live=slots, state_slots_read=128,
        state_bytes_read=9 * 128 * SLOT) if attrs else {})
        for t, live, slots in ((0, 100_000, 40), (10, 200_000, 60),
                               (20, 300_000, 80))]
    return {"trace": {"dir": "/nonexistent",
                      "decode_paths": (3, decode_paths.path_seconds(ops, runs)),
                      "spans": spans.Spans(host, [], [])},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "spec": {"config": files.load_config(CONFIG),
                     "traffic": files.load_traffic("mathreason-open")}}


def metric(name, facts):
    return files.load_module("metrics", name).read(facts, {"name": name})


def test_the_new_readers_by_hand():
    f = decode_facts()
    for name, ms in (("ssm_proj", 1000.0), ("ssm_state", 1000.0),
                     ("gmu", 500.0), ("attn_cross", 2000.0),
                     ("attn_window", 500.0), ("attn_full", 1000.0),
                     ("dense_mlp", 1000.0)):
        assert metric("decode_path_ms." + name, f) == pytest.approx(ms), name
    assert metric("state_slots_live_share", f) == pytest.approx(
        100 * 60 / 128)
    assert metric("kv_rows_live_share", f) == pytest.approx(20.0)
    # the mean step: 60 live slots' states beside the weights, over the
    # 2 s under the projections and the update together
    least = 9 * (2 * SLOT * 60 + 2 * MAMBA) / 819e9
    assert metric("ssm_state_roofline", f) == pytest.approx(100 * least / 2)
    # ... and 200,000 rows read, 540 written, over the 4 s under the row
    # write and the three kinds of attention
    least = (200_000 + 540) * ROW / 819e9
    assert metric("sambay_attn_roofline", f) == pytest.approx(
        100 * least / 4)


def test_the_rooflines_count_live_work_and_hold_the_row_to_its_widths():
    """Every slot live and its state moved once in the least time the
    chip could take reads 100 %, half of them live for the same device
    time about half (the weights stay); likewise the rows; and a row
    narrower than the configuration's is refused."""
    f = decode_facts()

    def reads(slots, rows, took, row=ROW):
        f["trace"]["spans"] = spans.Spans(
            [spans.Span("kf:serve.decode_read", t, 1, dict(
                kv_rows_live=rows, kv_rows_read=10 ** 7, kv_rows_written=0,
                kv_row_bytes=row, state_slots_live=slots,
                state_slots_read=128)) for t in (0, 10, 20)], [], [])
        f["trace"]["decode_paths"] = (3, {
            "jit(_decode_fn)/attn_core/ssm_state/x": 3 * took * 0.6,
            "jit(_decode_fn)/attn_proj/ssm_proj/y": 3 * took * 0.4,
            "jit(_decode_fn)/attn_core/attn_cross/a": 3 * took * 0.7,
            "jit(_decode_fn)/attn_core/attn_full/b": 3 * took * 0.1,
            "jit(_decode_fn)/attn_core/attn_window/c": 3 * took * 0.1,
            "jit(_decode_fn)/kv_write/z": 3 * took * 0.1})
        return (metric("ssm_state_roofline", f),
                metric("sambay_attn_roofline", f))

    cfg = files.load_config(CONFIG)
    least = phi4flash.decode_state_bytes(cfg, 128) / 819e9
    rows = least * 819e9 / ROW          # as many rows as take that long
    state, attn = reads(128, rows, least)
    assert state == pytest.approx(100.0) and attn == pytest.approx(100.0)
    state, attn = reads(64, rows / 2, least)
    assert attn == pytest.approx(50.0)
    assert state == pytest.approx(100 * (1 - 9 * SLOT * 128 / (
        9 * (2 * SLOT * 128 + 2 * MAMBA))))
    with pytest.raises(ValueError, match="kv_row_bytes"):
        reads(128, rows, least, row=ROW // 2)


def test_a_program_without_the_scopes_or_attrs_gives_nothing_to_read():
    """The parent with these files laid over it, a run that was not
    traced, and a traced run of another family: None, never a raise."""
    bench = files.load_benchmark()
    lists = {m["name"]: m.get("workloads", ()) for m in bench["per_layer"]}
    # (the cell that brought a reader stands first on its list: a later
    # cell that joins the list does not make this test stale)
    assert all(lists[name][0] == CELL for name in NEW)
    other = decode_facts(attrs=False)
    other["trace"]["decode_paths"] = (3, {"": 9.0})   # operations, no scope
    for name in NEW:
        assert metric(name, {"trace": None}) is None, name
        assert metric(name, other) is None, name
    # the scopes without the attrs give no share, nor the attrs without
    # the scopes (another family's decode program: the hybrid cache's)
    assert metric("ssm_state_roofline", decode_facts(attrs=False)) is None
    assert metric("sambay_attn_roofline", decode_facts(attrs=False)) is None
    hybrid = decode_facts()
    hybrid["trace"]["decode_paths"] = (3, {
        "jit(_decode_fn)/attn_core/attn_full/decode_attn": 3.0,
        "jit(_decode_fn)/attn_core/kda_state/kda_step": 3.0,
        "jit(_decode_fn)/kv_write/select_n": 1.0})
    assert metric("sambay_attn_roofline", hybrid) is None
    assert metric("ssm_state_roofline", hybrid) is None
    assert metric("decode_path_ms.attn_cross", hybrid) == 0.0
    # the cell is on the judged metric's list, and on no list whose
    # reader finds nothing in it or is bound to other families
    judged = [m for m in bench["end_to_end"] if m["name"] == "itl_p50_ms"][0]
    assert CELL in judged["workloads"]
    for name in ("kv_attn_roofline", "kda_state_roofline",
                 "admit_ms_per_req", "commit_ms_per_req",
                 "commit_mb_per_req", "prefill_ms_per_ktok.chat",
                 "decode_path_ms.moe_experts", "moe_experts_touched"):
        assert CELL not in lists[name], name
    for name in ("kv_rows_live_share", "state_slots_live_share",
                 "decode_step_spread", "first_token_ms_per_ktok",
                 "scope_ms_per_step.kv_write", "decode_path_ms.attn_window",
                 "decode_path_ms.attn_full", "decode_path_ms.dense_mlp",
                 "engine_host_ms_per_step", "decode_slot_use"):
        assert CELL in lists[name], name


def recorded():
    with open(os.path.join(HERE, "recorded_sambay_mathreason.json")) as f:
        rec = json.load(f)
    ops = [(rec["paths"][p], s, e) for p, s, e in rec["ops"]]
    runs = [tuple(r) for r in rec["runs"]]
    host = [spans.Span("kf:serve.decode_read", float(i), 0.5, dict(st))
            for i, st in enumerate(rec["read_stats"])]
    return {"trace": {"dir": "/nonexistent",
                      "decode_paths": (len(runs), decode_paths.path_seconds(
                          ops, runs)),
                      "spans": spans.Spans(host, [], [])},
            "peaks": {"hbm_bytes_per_s": rec["hbm_bytes_per_s"]},
            "spec": {"config": files.load_config(CONFIG)}}, rec


def test_readers_on_a_recorded_excerpt_of_the_chip():
    """The cell on a TPU v5 lite at its 1.2/s (PR 44, seed 4400000222):
    every traced step's attrs, so the two shares read what the whole
    trace read to the last digit; the first runs' operations, so the
    times and the rooflines read it to a tenth of themselves (the step
    follows the live contexts)."""
    f, rec = recorded()
    whole = rec["whole_trace"]
    assert rec["cell"] == CELL and len(rec["read_stats"]) > 100
    assert all(s["kv_row_bytes"] == ROW and s["state_slots_read"] == 128
               and s["state_bytes_read"] == 9 * 128 * SLOT
               and s["kv_rows_live"] <= s["kv_rows_read"]
               and 0 < s["kv_rows_live_full"] < s["kv_rows_live"]
               for s in rec["read_stats"])
    for name in ("state_slots_live_share", "kv_rows_live_share"):
        assert metric(name, f) == pytest.approx(whole[name], rel=1e-12)
        assert 0 < whole[name] <= 100
    for name in ("decode_path_ms.ssm_proj", "decode_path_ms.ssm_state",
                 "decode_path_ms.gmu", "decode_path_ms.attn_cross",
                 "decode_path_ms.attn_window", "decode_path_ms.attn_full",
                 "ssm_state_roofline", "sambay_attn_roofline"):
        assert metric(name, f) == pytest.approx(whole[name], rel=0.1), name
    assert 0 < whole["ssm_state_roofline"] < 100
    assert 0 < whole["sambay_attn_roofline"] < 100
    # seven cross reads of the slab against one
    assert whole["decode_path_ms.attn_cross"] \
        > 3 * whole["decode_path_ms.attn_full"]


def test_the_traffic_offers_one_multiset_whatever_the_seed():
    tr, schedule = one_schedule_whatever_the_seed("mathreason-open")
    prompts = sorted(p for _, p, _, _ in schedule)
    outputs = sorted(o for _, _, o, _ in schedule)
    assert prompts[0] >= 48 and prompts[-1] <= 1024
    assert outputs[0] >= 768 and outputs[-1] <= 3072
    assert 300 < prompts[len(prompts) // 2] < 480
    assert 1800 < outputs[len(outputs) // 2] < 2300
    # no pair is clipped, and every context passes the window of 512
    assert tr["prompt"]["max"] + tr["output"]["max"] <= tr["max_total"]
    assert tr["output"]["min"] > 512
    assert tr["max_total"] == tr["engine"]["max_seq"]
    e = tr["engine"]
    assert (e["max_batch"], e["max_seq"], e["page_tokens"], e["kv_pages"]) \
        == (128, 4096, 256, 64)
    assert (tr["pairing_seed"], tr["check"]["sample_requests"]) == (4444, 6)
    cell = files.cell(files.load_benchmark(), CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "mathreason-open", CONFIG)
    assert tr["rate_rps"] > 0 and "PROVISIONAL" not in tr["rate_reason"]
    assert "PROVISIONAL" not in tr["check"]["limits_reason"]
    # one median lifetime of pre-roll
    assert tr["preroll_s"] == 40


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
        for name in ("state_slots_live_share", "kv_rows_live_share"):
            assert 0 < last["metrics"]["rehearsal." + name]["value"] <= 100
