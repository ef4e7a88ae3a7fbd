"""``lib/spans.py``: the program's spans and scopes read from a trace --
self time, innermost-span idle attribution and scope sums on intervals
small enough to work out by hand, the file's wire format on a file made
here, the recorded excerpts of real chip traces kept beside this file
(``recorded_spans_*.json``, cut by ``tools/dump_spans.py``), and the
readers' names in a rehearsal's line."""

import json
import os

import pytest

from conftest import CELLS, rehearse
from kfbench.lib import files, spans

HERE = os.path.dirname(os.path.abspath(__file__))


def S(name, start, dur, thread="main", **stats):
    return spans.Span("kf:" + name, start, dur, stats, thread)


def serve_tiny():
    """Three engine steps on one thread.  Step 0 (0-10): admit 1-5 with
    prefill 2-3 and prefill_read 3-4.5 inside, decode 5-6, decode_read
    6-9.  Step 1 (10-20): decode 10-11, decode_read 11-14, complete
    14-19.  Step 2 (21-25): decode 21-22, decode_read 22-24.5.  The
    device runs 3-4, 6-9, 11-14 and 22-24 (inside jit__decode_fn runs
    6-9, 11-14, 22-24; the 3-4 is the prefill's; 11-13 is a ``while``
    with its body's two operations on the line as well)."""
    host = [
        S("serve.step", 0, 10, pending=1, active=0),
        S("serve.admit", 1, 4, rid="a", tokens=5, reused=0, pages=1),
        S("serve.prefill", 2, 1, rid="a", bucket=16),
        S("serve.prefill_read", 3, 1.5, rid="a"),
        S("serve.decode", 5, 1, batch=1, width=4),
        S("serve.decode_read", 6, 3),
        S("serve.step", 10, 10, pending=0, active=1),
        S("serve.decode", 10, 1, batch=1, width=4),
        S("serve.decode_read", 11, 3),
        S("serve.complete", 14, 5, rid="a", pages=2, bytes=4_000_000),
        S("serve.step", 21, 4, pending=0, active=1),
        S("serve.decode", 21, 1, batch=2, width=4),
        S("serve.decode_read", 22, 2.5),
        S("input.prefetch", 0, 30, thread="other"),  # not the step's thread
    ]
    ops = [("attn_proj", 3.0, 1.0, "fusion bf16[1]"),
           ("kv_write", 6.0, 2.0, "copy bf16[2]"),
           ("attn_core", 8.0, 0.5, "fusion bf16[3]"),
           (None, 8.5, 0.5, "copy-done bf16[2]"),
           (None, 11.0, 2.0, "while s32[]"),        # holds the next two
           ("kv_write", 11.0, 1.5, "copy bf16[2]"),
           ("kv_write", 12.5, 0.5, "copy bf16[2]"),
           ("mlp", 13.0, 1.0, "fusion bf16[4]"),
           ("kv_write", 22.0, 1.0, "copy bf16[2]"),
           ("norm", 23.0, 1.0, "fusion f32[5]")]
    modules = [("jit__prefill_fn", 3.0, 1.0), ("jit__decode_fn", 6.0, 3.0),
               ("jit__decode_fn", 11.0, 3.0), ("jit__decode_fn", 22.0, 2.0)]
    return spans.Spans(host, ops, modules)


def facts_of(sp):
    return {"trace": sp and {"dir": "/nonexistent", "spans": sp}}


def metric(name, sp):
    entry = {"name": name}
    return files.load_module("metrics", name).read(facts_of(sp), entry)


def test_scope_of_takes_the_innermost_name_of_the_vocabulary():
    assert spans.scope_of("jit(step3)/transpose(jvp(norm))/mul") == "norm"
    assert spans.scope_of("jit(step3)/jvp(attn_core)/flash_fwd/pallas_call"
                          ) == "attn_core"
    assert spans.scope_of("jit(step3)/optimizer/grad_sync/psum") == "grad_sync"
    assert spans.scope_of("jit(step3)/optimizer/optimizer/mul") == "optimizer"
    assert spans.scope_of("jit(_decode_fn)/attn_proj/norm/rsqrt") == "norm"
    # a primitive or a program that happens to bear a scope's name is not one
    assert spans.scope_of("jit(head)/mul") is None
    assert spans.scope_of("jit(f)/jvp()/norm") is None
    assert spans.scope_of("") is None and spans.scope_of(None) is None


def test_self_time_and_children_stay_on_the_parents_thread():
    sp = serve_tiny()
    step0, step1, step2 = sp.named("serve.step")
    assert [c.name for c in sp.children(step0, "serve.prefill_read",
                                        "serve.decode_read")] == [
        "kf:serve.prefill_read", "kf:serve.decode_read"]
    assert sp.self_seconds(step0, "serve.prefill_read",
                           "serve.decode_read") == 10 - 1.5 - 3
    (admit,) = sp.named("serve.admit")
    assert sp.self_seconds(admit, "serve.prefill", "serve.prefill_read") == 1.5
    assert sp.children(step2, "input.prefetch") == []


def test_idle_goes_to_the_innermost_open_span_and_adds_up():
    sp = serve_tiny()
    # the stretch is 3-24; the device is idle 4-6, 9-11, 14-22
    idle = spans.idle_by_span(sp, "serve.step")
    assert idle == pytest.approx({
        "kf:serve.prefill_read": 0.5,   # 4-4.5
        "kf:serve.admit": 0.5,          # 4.5-5
        "kf:serve.decode": 3.0,         # 5-6, 10-11, 21-22
        "kf:serve.decode_read": 0.0,
        "kf:serve.prefill": 0.0,
        "kf:serve.step": 2.0,           # 9-10, 19-20
        "kf:serve.complete": 5.0,       # 14-19
        None: 1.0,                      # 20-21, between steps
    })
    assert sum(idle.values()) == pytest.approx((24 - 3) - 9)
    # as the metrics split it, per step
    got = {x: metric("idle_ms_per_step." + x, sp)
           for x in ("complete", "admit", "decode", "serve_other")}
    assert got == pytest.approx({"complete": 5e3 / 3, "admit": 1e3 / 3,
                                 "decode": 3e3 / 3, "serve_other": 3e3 / 3})
    assert sum(got.values()) * 3 / 1e3 == pytest.approx(12.0)


def test_scope_sums_inside_the_decode_runs_add_up():
    sp = serve_tiny()
    assert spans.scope_seconds(sp) == {"attn_proj": 1.0, "kv_write": 5.0,
                                       "attn_core": 0.5, None: 0.5,
                                       "mlp": 1.0, "norm": 1.0}
    inside = spans.scope_seconds(sp, r"^jit__decode_fn")
    assert inside["attn_proj"] == 0           # the prefill's
    got = {x: metric("scope_ms_per_step." + x, sp) for x in (
        "kv_write", "decode_attn_core", "decode_dense", "decode_unscoped")}
    assert got == pytest.approx({
        "kv_write": 5e3 / 3, "decode_attn_core": 0.5e3 / 3,
        "decode_dense": 2e3 / 3, "decode_unscoped": 0.5e3 / 3})
    assert sum(got.values()) * 3 / 1e3 == pytest.approx(sum(inside.values()))


def test_the_serving_readers():
    sp = serve_tiny()
    assert metric("engine_host_ms_per_step", sp) == pytest.approx(5.5e3)
    assert metric("decode_read_ms", sp) == pytest.approx(3e3)
    assert metric("decode_slot_use", sp) == pytest.approx(100 * (1 + 1 + 2) / 12)
    # one admission, one completion: fewer than three samples
    assert metric("admit_ms_per_req", sp) is None
    assert metric("commit_ms_per_req", sp) is None
    assert metric("commit_mb_per_req", sp) is None
    sp.host += [S("serve.complete", 30 + i, 2, rid=str(i), pages=1,
                  bytes=1_000_000) for i in range(2)]
    assert metric("commit_ms_per_req", sp) == pytest.approx(3e3)
    assert metric("commit_mb_per_req", sp) == pytest.approx(2.0)


def train_tiny():
    """Four steps of 10 on one thread, the last a pulse step whose sync
    waits 34-39; the device runs 1-33 and 35-39."""
    host = []
    for i in range(4):
        host += [S("step.train", 10 * i, 2 if i < 3 else 9.5,
                   pulse=int(i == 3)),
                 S("step.dispatch", 10 * i + 0.5, 1)]
    host += [S("pulse.sync", 34, 5), S("pulse.publish", 39, 0.25)]
    ops = [("attn_core", 1.0, 12.0, "flash_fwd"), ("optimizer", 13.0, 10.0,
           "fusion f32[8]"), (None, 23.0, 2.0, "copy-start f32[8]"),
           ("grad_sync", 25.0, 8.0, "all-reduce f32[8]"),
           ("head", 35.0, 4.0, "fusion f32[9]")]
    modules = [("jit_step3", 1.0 + 8 * i, 8.0) for i in range(3)] + [
        ("jit_pulse_outer", 25.0, 14.0)]
    return spans.Spans(host, ops, modules)


def test_the_training_readers():
    sp = train_tiny()
    assert metric("train_dispatch_ms", sp) == pytest.approx(1e3)
    assert metric("pulse_sync_ms", sp) is None      # one sample
    # idle 33-35 of the stretch 1-39: 33-34 under step.train, 34-35 under sync
    got = {x: metric("idle_ms_per_step." + x, sp)
           for x in ("pulse_sync", "dispatch", "train_other")}
    assert got == pytest.approx({"pulse_sync": 1e3 / 4, "dispatch": 0.0,
                                 "train_other": 1e3 / 4})
    scopes = {x: metric("scope_ms_per_step." + x, sp) for x in (
        "embed", "norm", "attn_proj", "attn_core", "mlp", "head",
        "optimizer", "grad_sync", "train_unscoped")}
    assert scopes["attn_core"] == pytest.approx(12e3 / 4)
    assert scopes["train_unscoped"] == pytest.approx(2e3 / 4)
    assert scopes["embed"] == 0.0
    assert sum(scopes.values()) * 4 / 1e3 == pytest.approx(36.0)


def test_a_program_without_spans_or_scopes_gives_nothing_to_read():
    """The parent of the PR that added them: the benchmark's files laid
    over it must leave every new metric out, and not raise."""
    bare = spans.Spans([], [(None, 0.0, 1.0, "fusion f32[8]")] * 4,
                       [("jit_step3", 0.0, 1.0)] * 4
                       + [("jit__decode_fn", 0.0, 1.0)] * 4)
    bench = files.load_benchmark()
    new = [m["name"] for m in bench["per_layer"]
           if m["source"] == "program_span"
           or m["name"].startswith("scope_ms_per_step.")]
    assert len(new) >= 28
    for name in new:
        assert metric(name, bare) is None, name
        assert metric(name, None) is None, name   # a run that was not traced
    assert files.load_module("metrics", "train_dispatch_ms").read(
        {"trace": None}, {"name": "train_dispatch_ms"}) is None


# -- the file's wire format ----------------------------------------------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def test_scope_paths_from_the_files_metadata(tmp_path):
    def plane(name, events, stat_names):
        body = field(2, name)
        body += field(3, field(2, "XLA Ops") + field(4, field(1, 7) * 500))
        for key, (op, stats) in events.items():
            meta = field(1, key) + field(2, op) + b"".join(
                field(5, s) for s in stats)
            body += field(4, field(1, key) + field(2, meta))
        for key, n in stat_names.items():
            body += field(5, field(1, key) + field(2, field(1, key) + field(2, n)))
        return field(1, body)

    names = {1: "tf_op", 2: "hlo_category", 3: "flops",
             9: "jit(step3)/jvp(mlp)/dot_general"}
    device = plane("/device:TPU:0", {
        7: ("%fusion.1 = f32[8]{0} fusion(...)", [
            field(1, 2) + field(5, "non-fusion elementwise"),
            field(1, 1) + field(5, "jit(step3)/transpose(jvp(norm))/mul"),
            field(1, 3) + field(3, 4096)]),
        8: ("%dot.2 = bf16[8,8]{1,0} convolution(...)", [
            field(1, 1) + field(7, 9)]),          # a value by reference
        9: ("%copy.3 = f32[8]{0} copy(...)", [
            field(1, 2) + field(5, "data formatting")]),
    }, names)
    host = plane("/host:CPU", {1: ("kf:serve.step", [
        field(1, 1) + field(5, "jit(x)/norm/mul")])}, {1: "tf_op"})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(device + host)
    assert spans.scope_paths(str(path)) == {
        "%fusion.1 = f32[8]{0} fusion(...)":
            "jit(step3)/transpose(jvp(norm))/mul",
        "%dot.2 = bf16[8,8]{1,0} convolution(...)":
            "jit(step3)/jvp(mlp)/dot_general"}


# -- recorded excerpts of chip traces -----------------------------------------

def recorded(name):
    path = os.path.join(HERE, name)
    if not os.path.exists(path):
        pytest.skip(f"{name} not recorded")
    with open(path) as f:
        d = json.load(f)
    return spans.Spans(
        [spans.Span(n, s, x, stats, thread)
         for n, s, x, stats, thread in d["host"]],
        [(d["scopes"][sc], s, x, d["labels"][lb]) for sc, s, x, lb in d["ops"]],
        [tuple(m) for m in d["modules"]])


@pytest.mark.parametrize("cell,step", [("train", "step.train"),
                                       ("chat", "serve.step")])
def test_recorded_excerpt(cell, step):
    """An excerpt of the cell's trace (TPU v5 lite, PR 24): what the
    readers found there then, to the last digit, and that the parts add
    up to the whole."""
    sp = recorded(f"recorded_spans_{cell}.json")
    with open(os.path.join(HERE, f"recorded_spans_{cell}.expect.json")) as f:
        want = json.load(f)
    idle = spans.idle_by_span(sp, step)
    busy = spans.trace.union((s, s + d) for _, s, d, _ in sp.ops)
    stretch = busy[-1][1] - busy[0][0]
    assert sum(idle.values()) == pytest.approx(
        stretch - spans.trace.total(busy), rel=1e-9, abs=1e-12)
    by = spans.scope_seconds(sp)
    # own seconds: nothing counted twice, so they add up to the busy time
    assert sum(by.values()) == pytest.approx(spans.trace.total(busy), rel=1e-6)
    got = {
        "spans": {n: len(sp.named(n[3:])) for n in sorted(
            {s.name for s in sp.host})},
        "idle_s": {str(k): v for k, v in idle.items()},
        "scope_s": {str(k): v for k, v in by.items()},
    }
    assert got["spans"] == want["spans"]
    assert got["idle_s"] == pytest.approx(want["idle_s"], rel=1e-9, abs=1e-12)
    assert got["scope_s"] == pytest.approx(want["scope_s"], rel=1e-9)


# -- the readers' names in a rehearsal's line ---------------------------------

@pytest.mark.parametrize("workload", CELLS)
def test_a_traced_rehearsal_prints_the_span_read_metrics(bench, workload):
    """The CPU's trace has the host's spans (and no device): what reads
    only those is in the line, under ``rehearsal.`` names."""
    rc, last, out = rehearse(workload, trace=1)
    assert rc == 0, out[-3000:]
    mine = {m["name"] for m in bench["per_layer"]
            if workload in m.get("workloads", ())
            and m["source"] == "program_span"
            and not m["name"].startswith("idle_ms_per_step.")}
    assert mine
    assert {"rehearsal." + n for n in mine} <= set(last["metrics"]), (
        sorted(last["metrics"]))
