"""The ``evabyte`` family's files: the sizes as published and as cut, the
parameter count against the tree's leaves, the bytes a decode step's
attention has to move worked out by hand, the tiny preset the adapter
makes of ``--rehearse``'s overlay, the new readers on a span excerpt
small enough to check by hand, on a recorded excerpt of the cell's own
chip trace (``recorded_eva_docbytes.json``, cut by
``tools/eva_excerpt.py``) and on a program that writes neither scope nor
attr (None, never a raise), the traffic file's multiset whatever the
seed, and the cell's rehearsal."""

import json
import os

import pytest

from conftest import one_schedule_whatever_the_seed, rehearse
from kfbench.lib import decode_paths, evabyte, files, spans

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "evabyte-serve-docbytes"
CONFIG = "EvaByte"
BIG = 2 ** 31 + 4040
ROW = 16384
NEW = ("decode_path_ms.eva_attn", "decode_path_ms.eva_pool",
       "summary_rows_live_share", "eva_attn_roofline")


def test_sizes_as_published_and_as_cut():
    cfg = files.load_config(CONFIG)
    z = evabyte.sizes(cfg)
    assert (z["d"], z["heads"], z["head_dim"], z["ffn"]) == (
        4096, 32, 128, 11008)
    assert (z["chunk"], z["window"], z["vocab"], z["pred_heads"]) == (
        16, 2048, 320, 8)
    assert (z["layers"], z["init_layers"], z["eps"], z["theta"], z["std"]) \
        == (8, 32, 1e-5, 100000.0, 0.01275)
    # every number of the catalog's row but the one the file lists
    published = {
        "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
        "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
        "hidden_act": "silu", "hidden_size": 4096,
        "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275,
        "intermediate_size": 11008, "lazy_init": True,
        "max_position_embeddings": 32768, "max_seq_length": 32768,
        "mixedp_attn": True, "model_type": "evabyte",
        "norm_add_unit_offset": True, "num_attention_heads": 32,
        "num_chunks": None, "num_hidden_layers": 32,
        "num_key_value_heads": 32, "num_pred_heads": 8,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 320,
        "window_size": 2048}
    differ = {k for k, v in published.items() if cfg[k] != v}
    assert differ == set(cfg["reduced"]) == {"num_hidden_layers"}
    entry = [c for c in files.load_benchmark()["configs"]
             if c["name"] == CONFIG][0]
    assert set(entry["reduced"]) == differ and len(entry["source"]) <= 200
    assert cfg["source"].startswith(entry["source"])
    assert cfg["num_hidden_layers_published"] == 32
    assert "one chip a layer" in cfg["deployment"]
    assert "four-stage pipeline" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 10
    assert any("no network" in a for a in cfg["assumed"])


def test_the_parameter_count_is_the_trees():
    import jax
    import numpy as np

    cfg = files.load_config(CONFIG)
    adapter = files.load_adapter("evabyte")
    z = evabyte.sizes(cfg)
    assert evabyte.layer_params(z) == 202_391_552
    assert adapter.n_params(cfg) == 8 * 202_391_552 + 320 * 4096 \
        + 4096 * 2560 + 4096 == 1_630_932_992
    tree = jax.eval_shape(adapter.program_model(cfg).init,
                          jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        tree)) == adapter.n_params(cfg)
    tiny = dict(cfg, **files.load_json("rehearsal.json")["config"])
    tree = jax.eval_shape(adapter.program_model(tiny).init,
                          jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        tree)) == adapter.n_params(tiny) < 1e6


def test_decode_attn_bytes_by_hand():
    cfg = files.load_config(CONFIG)
    # a row of one layer: 32 heads of 128, K and V, bfloat16
    assert evabyte.row_bytes(cfg) == 32 * 128 * 2 * 2 == ROW
    assert evabyte.decode_attn_bytes(cfg, 0) == 0
    # one live slot at a context of 14,000 bytes: six closed windows'
    # chunk rows and the open window's exact rows, a row written, a layer
    exact, chunk = 14000 - 6 * 2048, 6 * 128
    assert (exact, chunk) == (1712, 768)
    one = evabyte.decode_attn_bytes(cfg, 8 * (exact + chunk + 1))
    assert one == 8 * 2481 * ROW == 325_189_632
    # ... where the program reads 8 x 4,096 rows of that slot under a mask
    assert 8 * 4096 * ROW == 536_870_912
    # sixteen such slots in the least time the chip could take: 6.4 ms
    assert 16 * one / 819e9 == pytest.approx(6.35e-3, rel=1e-2)


def test_the_overlay_of_a_rehearsal_gives_a_tiny_model():
    cfg = dict(files.load_config(CONFIG),
               **files.load_json("rehearsal.json")["config"])
    z = evabyte.sizes(cfg)
    assert (z["d"], z["layers"], z["heads"], z["head_dim"], z["ffn"]) == (
        64, 2, 4, 16, 256)
    assert (z["chunk"], z["window"], z["pred_heads"]) == (4, 32, 8)
    model = files.load_adapter("evabyte").program_model(cfg)
    assert model.cfg.max_seq == 128 and model.cfg.pages_reusable is False


def decode_facts(attrs=True):
    """Three decode runs (0-4, 10-14, 20-24).  In each: the projections
    0-1, the row write 1-1.5 with the pooling inside it 1.5-2, the
    attention 2-3.5, the FFN 3.5-4.  A prefill's attention (5-9) is
    outside every decode run.  The steps read 8 x 4 x 2,048 exact and as
    many chunk rows; their live contexts had to read 1,000 / 2,000 /
    3,000 exact and 500 / 600 / 700 chunk rows, wrote 16 rows each and
    completed 0 / 8 / 16 chunks."""
    def at(t0):
        ops = [("jit(_decode_fn)/attn_proj/dot_general", 0, 1),
               ("jit(_decode_fn)/kv_write/dynamic_update_slice", 1, 1.5),
               ("jit(_decode_fn)/kv_write/eva_pool/reduce_sum", 1.5, 2),
               ("jit(_decode_fn)/attn_core/eva_attn/dot_general", 2, 3.5),
               ("jit(_decode_fn)/mlp/dot_general", 3.5, 4)]
        return [(p, t0 + a, t0 + b) for p, a, b in ops]

    ops = at(0) + at(10) + at(20) + [
        ("jit(_prefill_fn)/attn_core/eva_prefill/while", 5, 9)]
    runs = [(0, 4), (10, 14), (20, 24)]
    host = [spans.Span("kf:serve.decode_read", t, 1, dict(
        kv_rows_live=live, kv_rows_read=65536, kv_rows_written=16,
        kv_row_bytes=ROW, summary_rows_live=chunk, summary_rows_read=65536,
        summary_rows_written=done) if attrs else {})
        for t, live, chunk, done in ((0, 1000, 500, 0), (10, 2000, 600, 8),
                                     (20, 3000, 700, 16))]
    return {"trace": {"dir": "/nonexistent",
                      "decode_paths": (3, decode_paths.path_seconds(ops, runs)),
                      "spans": spans.Spans(host, [], [])},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "spec": {"config": files.load_config(CONFIG),
                     "traffic": files.load_traffic("docbytes-open")}}


def metric(name, facts):
    return files.load_module("metrics", name).read(facts, {"name": name})


def test_the_new_readers_by_hand():
    f = decode_facts()
    assert metric("decode_path_ms.eva_attn", f) == pytest.approx(1500.0)
    assert metric("decode_path_ms.eva_pool", f) == pytest.approx(500.0)
    assert metric("summary_rows_live_share", f) == pytest.approx(
        100 * (500 + 600 + 700) / 3 / 65536)
    assert metric("kv_rows_live_share", f) == pytest.approx(
        100 * (1000 + 2000 + 3000) / 3 / 65536)
    # the mean step: 2,000 + 600 rows read, 16 + 8 written, over the
    # 2.5 s under the write, the pooling and the attention together
    least = (2000 + 600 + 16 + 8) * ROW / 819e9
    assert metric("eva_attn_roofline", f) == pytest.approx(
        100 * least / 2.5)


def test_the_roofline_counts_live_rows_and_holds_the_row_to_its_widths():
    """Every row of every slot live and moved once in the least time the
    chip could take reads 100 %; a tenth of them live for the same
    device time a tenth of that; and a row narrower than the
    configuration's is refused."""
    f = decode_facts()

    def reads(rows, took, row=ROW):
        f["trace"]["spans"] = spans.Spans(
            [spans.Span("kf:serve.decode_read", t, 1, dict(
                kv_rows_live=rows, kv_rows_read=65536, kv_rows_written=0,
                kv_row_bytes=row, summary_rows_live=rows,
                summary_rows_read=65536, summary_rows_written=0))
             for t in (0, 10, 20)], [], [])
        f["trace"]["decode_paths"] = (3, {
            "jit(_decode_fn)/attn_core/eva_attn/x": 3 * took * 0.8,
            "jit(_decode_fn)/kv_write/eva_pool/y": 3 * took * 0.1,
            "jit(_decode_fn)/kv_write/z": 3 * took * 0.1})
        return metric("eva_attn_roofline", f)

    least = 2 * 65536 * ROW / 819e9
    assert reads(65536, least) == pytest.approx(100.0)
    assert reads(6553.6, least) == pytest.approx(10.0)
    with pytest.raises(ValueError, match="kv_row_bytes"):
        reads(65536, least, row=ROW // 2)


def test_a_program_without_the_scopes_or_attrs_gives_nothing_to_read():
    """The parent with these files laid over it, a run that was not
    traced, and a traced run of a model without chunk rows: None, never
    a raise."""
    bench = files.load_benchmark()
    new = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [CELL]]
    assert sorted(new) == sorted(NEW)
    other = decode_facts(attrs=False)
    other["trace"]["decode_paths"] = (3, {"": 9.0})   # operations, no scope
    for name in new:
        assert metric(name, {"trace": None}) is None, name
        assert metric(name, other) is None, name
    # the scopes without the attrs give no share, nor the attrs without
    # the scopes (another family's decode program)
    assert metric("eva_attn_roofline", decode_facts(attrs=False)) is None
    assert metric("summary_rows_live_share", decode_facts(attrs=False)) is None
    dense = decode_facts()
    dense["trace"]["decode_paths"] = (3, {
        "jit(_decode_fn)/attn_core/dot_general": 3.0,
        "jit(_decode_fn)/kv_write/select_n": 1.0})
    assert metric("eva_attn_roofline", dense) is None
    assert metric("decode_path_ms.eva_attn", dense) == 0.0
    # the cell is on the judged metric's list, and on no list whose
    # reader finds nothing in it
    judged = [m for m in bench["end_to_end"] if m["name"] == "itl_p50_ms"][0]
    assert CELL in judged["workloads"]
    lists = {m["name"]: m.get("workloads", ()) for m in bench["per_layer"]}
    for name in ("kv_attn_roofline", "admit_ms_per_req", "commit_ms_per_req",
                 "commit_mb_per_req", "prefill_ms_per_ktok.chat"):
        assert CELL not in lists[name], name
    for name in ("kv_rows_live_share", "decode_step_spread",
                 "first_token_ms_per_ktok", "scope_ms_per_step.kv_write"):
        assert CELL in lists[name], name


def recorded():
    with open(os.path.join(HERE, "recorded_eva_docbytes.json")) as f:
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
    """The cell on a TPU v5 lite (PR 40): every traced step's attrs, so
    the two shares read what the whole trace read to the last digit; the
    first three runs' operations, so the times and the roofline read it
    to a few hundredths of themselves."""
    f, rec = recorded()
    whole = rec["whole_trace"]
    assert rec["cell"] == CELL and len(rec["read_stats"]) > 100
    assert all(s["kv_row_bytes"] == ROW
               and s["kv_rows_read"] == 8 * 16 * 2048
               and s["summary_rows_read"] == 8 * 16 * 2048
               for s in rec["read_stats"])
    for name in ("summary_rows_live_share", "kv_rows_live_share"):
        assert metric(name, f) == pytest.approx(whole[name], rel=1e-12)
        assert 0 < whole[name] <= 100
    for name in ("decode_path_ms.eva_attn", "decode_path_ms.eva_pool",
                 "eva_attn_roofline"):
        assert metric(name, f) == pytest.approx(whole[name], rel=5e-2), name
    assert 0 < whole["eva_attn_roofline"] < 100
    assert whole["decode_path_ms.eva_attn"] > whole["decode_path_ms.eva_pool"]


def test_the_traffic_offers_one_multiset_whatever_the_seed():
    tr, schedule = one_schedule_whatever_the_seed("docbytes-open")
    prompts = sorted(p for _, p, _, _ in schedule)
    outputs = sorted(o for _, _, o, _ in schedule)
    assert prompts[0] >= 2048 and prompts[-1] <= 28672
    assert outputs[0] >= 256 and outputs[-1] <= 3072
    assert 9000 < prompts[len(prompts) // 2] < 16000
    assert 800 < outputs[len(outputs) // 2] < 1300
    assert tr["max_total"] == tr["engine"]["max_seq"]
    e = tr["engine"]
    assert (e["max_batch"], e["max_seq"], e["page_tokens"]) == (
        16, 32768, 2048)
    # buckets 2,048 to 32,768: five prefill programs
    assert e["max_seq"] // e["page_tokens"] == 16
    cell = files.cell(files.load_benchmark(), CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "docbytes-open", CONFIG)
    assert tr["rate_rps"] > 0 and "PLACEHOLDER" not in tr["rate_reason"]
    assert "PLACEHOLDER" not in tr["check"]["limits_reason"]
    assert f"{tr['rate_rps']:g}/s" in cell["why"]
    # a median request's lifetime lies inside the pre-roll
    assert tr["preroll_s"] >= 20


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
        for name in ("summary_rows_live_share", "kv_rows_live_share"):
            assert 0 < last["metrics"]["rehearsal." + name]["value"] <= 100
