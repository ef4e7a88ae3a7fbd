"""The trace reduction: interval arithmetic on traces small enough to
work out by hand, and the recorded excerpts of real chip traces kept
beside this file (``recorded_*.json``, cut by ``tools/dump_trace.py``)."""

import json
import os

import pytest

from kfbench.lib import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def tiny():
    """Two devices.  Device a: a matmul 0-4, an all-reduce 3-7 (3-4
    hidden behind the matmul), a copy 9-10.  Device b: busy 0-2 only."""
    return trace.Trace(
        {"a": {"ops": [("fusion f32[8]", 0.0, 4.0),
                       ("all-reduce f32[8]", 3.0, 4.0),
                       ("copy f32[8]", 9.0, 1.0)],
               "modules": [("jit_step3", 0.0, 7.0), ("jit_step3", 9.0, 1.0),
                           ("jit_pulse_outer", 20.0, 3.0)]},
         "b": {"ops": [("fusion f32[8]", 0.0, 2.0)], "modules": []}},
        [("kfb:step", 6.0, 1.5), ("kfb:feed", 8.0, 0.5),
         ("kfb:read_loss", 7.5, 2.5)])


def test_interval_arithmetic():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert trace.total([[0, 3], [5, 6]]) == 4
    assert trace.subtract([[0, 10]], [[1, 2], [4, 11]]) == [[0, 1], [2, 4]]
    assert trace.subtract([[0, 1], [2, 3]], []) == [[0, 1], [2, 3]]


def test_busy_kernel_and_collective_time():
    t = tiny()
    assert trace.busy_seconds(t) == (8.0 + 2.0) / 2   # a: 0-7 and 9-10
    assert trace.op_seconds(t, r"^fusion") == (4.0 + 2.0) / 2
    assert trace.op_count(t, r"^copy") == 0.5
    assert trace.exposed_collective_seconds(t) == 3.0 / 2  # 4-7 on a
    assert trace.module_runs(t, r"^jit_step3") == [7.0, 1.0]
    assert trace.top_ops(t, 2) == [["fusion f32[8]", 3.0],
                                   ["all-reduce f32[8]", 2.0]]


def test_idle_gaps_go_to_the_innermost_annotation():
    t = tiny()
    # device a is idle 7-9 of the window 0-10: 7-7.5 lies under step,
    # 7.5-8 and 8.5-9 under read_loss, 8-8.5 under feed nested in it
    gaps = dict(trace.idle_gaps(t, (0.0, 10.0)))
    assert gaps == {"kfb:step": 0.5, "kfb:read_loss": 1.0, "kfb:feed": 0.5}
    assert dict(trace.idle_gaps(t, (0.0, 12.0)))["host:unannotated"] == 2.0


def test_labels_of_libtpus_operation_names():
    assert trace.op_label(
        "%copy.1446 = bf16[36,16,20,1024,64]{3,4,2,1,0:T(8,128)(2,1)} "
        "copy(bf16[36,16,20,1024,64]{3,4,2,1,0} %k_slab.1)"
    ) == "copy bf16[36,16,20,1024,64]"
    assert trace.op_label(
        "%copy-start.181 = (s32[16]{0:T(128)S(1)}, s32[16]{0}, u32[]) "
        "copy-start(s32[16] %pos.1)") == "copy-start s32[16]"
    assert trace.op_label("fusion.12") == "fusion"


def recorded(name):
    path = os.path.join(HERE, name)
    if not os.path.exists(path):
        pytest.skip(f"{name} not recorded")
    with open(path) as f:
        return trace.Trace.from_json(json.load(f))


def test_recorded_train_trace():
    """An excerpt of gpt2m-train-1chip's trace (TPU v5 lite, PR 23)."""
    t = recorded("recorded_train.json")
    with open(os.path.join(HERE, "recorded_train.expect.json")) as f:
        want = json.load(f)
    red = trace.reduce(t, 1)
    assert 0 < red["busy_s"] <= red["window_s"]
    got = {
        "busy_s": red["busy_s"], "window_s": red["window_s"],
        "flash_fwd_calls": trace.op_count(t, r"flash_fwd(?![a-z])"),
        "flash_bwd_dq_calls": trace.op_count(t, r"flash_bwd_dq(?![a-z])"),
        "flash_bwd_dkv_calls": trace.op_count(t, r"flash_bwd_dkv(?![a-z])"),
        "flash_s": trace.op_seconds(t, r"flash_(fwd|bwd_dq|bwd_dkv)"),
        "steps": len(trace.module_runs(t, r"^jit_(step3|pulse_outer)")),
        "exposed_collective_s": trace.exposed_collective_seconds(t),
    }
    assert got == pytest.approx(want, rel=1e-9)
    # the three kernels' names do not shadow each other
    assert got["flash_bwd_dq_calls"] and got["flash_bwd_dkv_calls"]
    assert trace.op_count(t, r"flash_bwd_d") == (
        got["flash_bwd_dq_calls"] + got["flash_bwd_dkv_calls"])
