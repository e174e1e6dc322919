"""Published peaks of the accelerators the benchmark runs on, keyed by the
``device_kind`` string JAX reports.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s). JAX names this chip
"TPU v5 lite". A device kind that is not in the table is an error, not
a default: a roofline share against a guessed peak means nothing.
"""

from __future__ import annotations

_V5E = {
    "source": "Google Cloud documentation, TPU v5e",
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes": 16e9,
    "hbm_bytes_per_s": 819e9,
}

PEAKS = {
    "TPU v5 lite": _V5E,
}


def peak(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; raises KeyError for a
    kind the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to chipbench/peaks.py "
                       f"with their source") from None
