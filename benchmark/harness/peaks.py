"""Published peaks of the chips the benchmark knows, keyed by jax's ``device_kind``.

Copied from ``bench.py::PEAK_FLOPS`` (one row), with the memory bandwidth
added. Source: Google Cloud documentation, "TPU v5e" system architecture:
197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip, 16 GB of HBM.
A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} is not in benchmark/harness/peaks.py "
            f"(known: {sorted(PEAKS)}); add its published peaks with their "
            "source before measuring on it") from None
