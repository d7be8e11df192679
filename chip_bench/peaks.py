"""Peak rates of one chip, keyed by ``device_kind`` exactly as JAX reports it.

Source: Google Cloud TPU documentation, system architecture pages ("TPU v4",
"TPU v5e", "TPU v6e"): bf16 peak FLOP/s with a multiply-add counted as 2,
and HBM bytes/s.  A device that is not listed is an error, never a default
(copied from ``bench.py``'s table, which a later PR may delete).
"""

from __future__ import annotations

PEAKS = {
    "TPU v4": {"bf16_flops": 275e12, "hbm_bytes_per_s": 1228e9},
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v6 lite": {"bf16_flops": 918e12, "hbm_bytes_per_s": 1640e9},
}


def peak(device_kind: str, what: str = "bf16_flops") -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise ValueError(
            f"no {what} peak known for device_kind {device_kind!r}; add it "
            f"to chip_bench/peaks.py with its source") from None
