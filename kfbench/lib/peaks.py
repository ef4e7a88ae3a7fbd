"""The chips' published peaks, keyed by the exact ``device_kind`` JAX
reports.  A device that is not here is an error, never a default."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture table):
    # 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s ICI per chip
    "TPU v5 lite": {
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e system "
                  "architecture)",
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


def of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks recorded for device_kind {device_kind!r}: add a row "
            f"with its source to kfbench/lib/peaks.py (known: {list(PEAKS)})")
    return PEAKS[device_kind]


def check_shares(metrics: dict) -> None:
    """A share of a roofline or of a peak cannot pass 100 %: a reading
    above it means the operations or bytes are counted too high or the
    time leaves out part of the work, and the run fails on it."""
    for name, m in metrics.items():
        if m["unit"] == "%" and (name.endswith("_roofline") or "mfu" in name):
            if not 0 <= m["value"] <= 100:
                raise SystemExit(
                    f"kfbench: {name} reads {m['value']} %, outside 0-100: "
                    "its operations, bytes or time are counted wrongly")
