"""Benchmark-harness smoke tests (the reference runs its benches in CI:
ci.yaml adaptation bench step, monitor bench)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_bench(script, *args, timeout=300, subdir="benchmarks"):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, subdir, script), "--quick", *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_bus_bandwidth_formula():
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from allreduce import bus_bandwidth

    assert bus_bandwidth(1 << 30, 2, 1.0) == pytest.approx(1.0)
    assert bus_bandwidth(1 << 30, 4, 0.5) == pytest.approx(3.0)


@pytest.mark.slow
class TestHarnesses:
    def test_allreduce_host(self):
        out = run_bench("allreduce.py", "--backend", "host", "--np", "2")
        assert out["metric"] == "allreduce_bus_bandwidth"
        assert out["value"] > 0

    def test_allreduce_device(self):
        out = run_bench("allreduce.py", "--cpu-mesh", "4")
        assert out["np"] == 4
        assert out["value"] > 0

    def test_system_transformer(self):
        out = run_bench("system.py", "--model", "transformer",
                        "--optimizer", "sync-sgd", "--cpu-mesh", "2")
        assert out["value"] > 0
        assert out["final_loss"] > 0

    def test_adaptation(self):
        out = run_bench("adaptation.py", "--cpu-mesh", "4")
        assert out["metric"] == "resize_transition_latency"
        assert len(out["transitions"]) >= 2

    def test_system_vgg(self):
        out = run_bench("system.py", "--model", "vgg16",
                        "--optimizer", "sync-sgd", "--cpu-mesh", "2")
        assert out["metric"] == "vgg16_sync-sgd_throughput"
        assert out["value"] > 0 and out["unit"] == "images/sec"

    def test_system_bert_sma(self):
        """BASELINE config 3: BERT-base-shaped + SynchronousAveraging."""
        out = run_bench("system.py", "--model", "bert", "--optimizer", "sma",
                        "--cpu-mesh", "2")
        assert out["metric"] == "bert_sma_throughput"
        assert out["value"] > 0 and out["unit"] == "sequences/sec"

    def test_scaling_sweep(self):
        """The scaling-ladder harness (reference benchmark_kungfu_scaling
        analog): per-size throughput + efficiency in one JSON."""
        # outer timeout > sum of per-size inner timeouts, so two rungs
        # individually within budget cannot kill the test
        out = run_bench("scaling.py", "--sizes", "1,2", "--quick",
                        "--timeout", "200", timeout=520)
        assert out["metric"] == "transformer_sync-sgd_scaling"
        assert set(out["throughput_by_np"]) == {"1", "2"}
        assert out["throughput_by_np"]["1"] > 0
        assert out["baseline_np"] == 1
        assert out["overhead_retention_vs_np1"]["1"] == 1.0

    def test_system_zero1(self):
        """Weight-update sharding through the throughput harness."""
        out = run_bench("system.py", "--model", "transformer",
                        "--optimizer", "zero1", "--cpu-mesh", "2")
        assert out["metric"] == "transformer_zero1_throughput"
        assert out["value"] > 0 and out["final_loss"] > 0

    def test_gossip(self):
        """BASELINE config 4: PairAveraging gossip over the p2p store."""
        out = run_bench("gossip.py", "--np", "2", "--model", "slp-mnist",
                        "--steps", "3", "--warmup", "1",
                        "--base-port", "28700")
        assert out["metric"] == "pair_averaging_gossip_steps_per_sec"
        assert out["value"] > 0 and out["np"] == 2


class TestMeasureGroup:
    """bench.py's interleaved chained-K timing harness."""

    @staticmethod
    def _measure_group():
        sys.path.insert(0, REPO)
        from bench import measure_group

        return measure_group

    def test_times_every_contestant(self):
        measure_group = self._measure_group()
        import jax.numpy as jnp

        t = measure_group(
            {"a": lambda c: c * 1.0001, "b": lambda c: c * 1.0002},
            jnp.ones((8,)), k_lo=1, k_hi=3, rounds=1,
        )
        assert set(t) == {"a", "b"}
        assert all(v > 0 for v in t.values())

    def test_on_error_skip_maps_to_none(self):
        measure_group = self._measure_group()
        import jax.numpy as jnp

        def boom(c):
            raise RuntimeError("does not lower")

        t = measure_group(
            {"ok": lambda c: c * 1.0001, "bad": boom},
            jnp.ones((8,)), k_lo=1, k_hi=2, rounds=1, on_error="skip",
        )
        assert t["bad"] is None and t["ok"] > 0

    def test_on_error_raise_propagates(self):
        measure_group = self._measure_group()
        import jax.numpy as jnp

        def boom(c):
            raise RuntimeError("does not lower")

        with pytest.raises(RuntimeError):
            measure_group({"bad": boom}, jnp.ones((8,)), k_lo=1, k_hi=2)

    def test_respan_grows_fast_contestants(self, capsys):
        """A contestant whose K-separation is below target_sep gets its
        hi program rebuilt with a bigger span (the jitter defense)."""
        measure_group = self._measure_group()
        import jax.numpy as jnp

        t = measure_group(
            {"fast": lambda c: c * 1.0001},
            jnp.ones((8,)), k_lo=1, k_hi=3, rounds=2,
            target_sep=0.005, max_rounds=4,
        )
        assert t["fast"] > 0
        assert "re-span" in capsys.readouterr().err

    def test_rounds_1_skips_respan_and_settle(self, capsys):
        measure_group = self._measure_group()
        import jax.numpy as jnp

        t = measure_group(
            {"fast": lambda c: c * 1.0001},
            jnp.ones((8,)), k_lo=1, k_hi=3, rounds=1, target_sep=10.0,
        )
        assert t["fast"] > 0
        err = capsys.readouterr().err
        assert "re-span" not in err and "settled" not in err


@pytest.mark.slow
class TestBenchPayloads:
    def test_lm_quick(self):
        """bench.py --lm: the kernels-in-anger payload, CPU/interpret."""
        out = run_bench("bench.py", "--payload", "lm", "--cpu",
                        "--steps", "2", timeout=420, subdir="")
        assert out["metric"] == "gpt_small_sync_sgd_tokens_per_sec_per_chip"
        assert out["value"] > 0 and out["unit"] == "tokens/sec"
        # vs_baseline is t_xla / t_pallas (the kernel path's speedup; <1
        # expected in CPU interpret mode), not a reference baseline
        assert out["vs_baseline"] > 0
        assert out["final_loss"] is not None

    def test_resnet_quick(self):
        """The driver's headline payload: framework-path ResNet training.
        (--quick pins batch/img/steps itself, so no --steps here — the
        payload would ignore it.)"""
        out = run_bench("bench.py", "--payload", "resnet", "--cpu",
                        timeout=420, subdir="")
        assert out["metric"] == "resnet50_sync_sgd_images_per_sec_per_chip"
        assert out["value"] > 0 and out["unit"] == "images/sec"
        assert out["final_loss"] is not None
        assert "dp_train_step" in out["framework_path"]

    def test_allreduce(self):
        """Under pytest the conftest's XLA_FLAGS leak an 8-device virtual
        CPU platform into the subprocess (psum path); standalone it sees
        one device (read+write floor).  Both are valid payload branches."""
        out = run_bench("bench.py", "--payload", "allreduce", "--cpu",
                        subdir="")
        assert out["metric"] == "allreduce_bus_bandwidth"
        assert out["value"] > 0 and out["n_devices"] in (1, 8)


class TestZeroPayload:
    def test_zero_rows_and_comm_claim(self):
        """bench.py --zero on the CPU-mesh harness: all four rows
        present, and the measured ZeRO-2 wire bytes hold the <=55%
        claim against the ZeRO-1 all-reduce path."""
        out = run_bench("bench.py", "--payload", "zero", "--cpu-mesh", "4",
                        subdir="")
        assert out["metric"] == "zero2_traced_comm_bytes_vs_zero1"
        assert 0 < out["value"] <= 0.55
        rows = out["rows"]
        assert set(rows) == {"bare", "zero1", "zero2", "zero3"}
        # the bare baseline all-reduces (psum), zero2 reduce-scatters
        assert "psum" in rows["bare"]["traced_comm_bytes_per_rank"]
        assert "reduce_scatter" in rows["zero2"]["traced_comm_bytes_per_rank"]
        assert "all_gather" in rows["zero3"]["traced_comm_bytes_per_rank"]
        # replicated optimizer state is ~n x the sharded per-rank shard
        n = out["n_devices"]
        assert rows["bare"]["opt_state_bytes_per_rank"] > (
            (n - 1) * rows["zero2"]["opt_state_bytes_per_rank"])
        for r in rows.values():
            assert r["step_ms"] is None or r["step_ms"] > 0
