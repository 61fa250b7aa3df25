"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
A device that is not in the table is an error, never a default."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
    # 1,600 Gbit/s inter-chip interconnect per chip.
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add a row "
            "with its source to benchmarks/peaks.py") from None


def crc_verify_min_seconds(nbytes: int, device_kind: str) -> float:
    """The least time the chip could take to verify ``nbytes``: each byte
    read once from HBM (HBM-bound; the GF(2) folds are a few integer ops per
    word, far under the VPU's rate at that bandwidth)."""
    return nbytes / peaks_for(device_kind)["hbm_bytes_per_s"]
