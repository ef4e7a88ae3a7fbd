"""The FLOP and byte functions against counts made by hand, and the
peaks table's rules."""

import pytest

from kfbench.lib import files, flops, peaks


def test_gpt2_medium_by_hand():
    cfg = files.load_config("gpt2-medium")
    # per layer 4 * 1024^2 + 2 * 1024 * 4096 = 12,582,912; head 1024 * 50257
    assert flops.matmul_params(cfg) == 24 * 12_582_912 + 51_463_168
    # causal pairs of 1024: 524,800, i.e. 512.5 a token; 4 * 1024 * 24 each
    per_token = 3 * (2 * 353_453_056 + 4 * 1024 * 24 * 512.5)
    assert flops.train_flops_per_token(cfg, 1024) == per_token
    assert files.load_adapter("gpt2").n_params(cfg) == 406_286_336


def test_gpt2_large_by_hand():
    cfg = files.load_config("gpt2-large")
    # per layer 4 * 1280^2 + 2 * 1280 * 5120 = 19,660,800; head 1280 * 50257
    assert flops.matmul_params(cfg) == 36 * 19_660_800 + 64_328_960
    assert files.load_adapter("gpt2").n_params(cfg) == 838_359_040


def test_flash_calls_by_hand():
    # 64 heads of [1024, 64]: one product is 2 * 524,800 * 64 = 67,174,400
    one = 67_174_400 * 64
    arr = 1024 * 64 * 2 * 64
    assert flops.flash_call("flash_fwd", 64, 1024, 64) == (2 * one, 4 * arr)
    assert flops.flash_call("flash_bwd_dq", 64, 1024, 64) == (3 * one, 5 * arr)
    assert flops.flash_call("flash_bwd_dkv", 64, 1024, 64) == (4 * one, 6 * arr)
    ops, byts = flops.flash_call("flash_fwd", 64, 1024, 64)
    seconds, bound = flops.roofline_seconds(ops, byts, peaks.of("TPU v5 lite"))
    assert bound == "compute" and seconds == ops / 197e12


def test_peaks_are_keyed_by_the_exact_kind():
    p = peaks.of("TPU v5 lite")
    assert (p["bf16_flops"], p["hbm_bytes_per_s"], p["hbm_bytes"],
            p["ici_bits_per_s"]) == (197e12, 819e9, 16e9, 1600e9)
    assert p["source"]
    for unknown in ("TPU v5", "TPU v5e", "cpu", ""):
        with pytest.raises(KeyError):
            peaks.of(unknown)


def test_a_share_over_100_fails_the_run():
    ok = {"mfu.train": {"value": 45.0, "unit": "%"},
          "device_idle_share.train": {"value": 250.0, "unit": "%"}}
    peaks.check_shares(ok)
    for name in ("flash_roofline", "mfu.train"):
        with pytest.raises(SystemExit):
            peaks.check_shares({name: {"value": 100.5, "unit": "%"}})
