"""Published peaks of one chip, keyed by the exact ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" system architecture page: 197
TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1 600 Gbit/s of
inter-chip interconnect per chip. Taken over from
``tpu_engine/profiler.py::PEAK_FLOPS_BF16`` (which has FLOP/s only, matches by
substring and knows no bandwidth); here a kind that is not in the table is an
error, never a default."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
