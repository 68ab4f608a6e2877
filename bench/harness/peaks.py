"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a roofline
share against a guessed peak would be a number with no meaning.
"""

from __future__ import annotations

#: device_kind -> peaks of ONE chip.  TPU v5e: Google Cloud documentation,
#: "TPU v5e" (system architecture table): 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB HBM2 at 819 GB/s.  The chip has no float64 unit and no published
#: float64 peak, so float64 work is held against the bf16 peak.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e system architecture",
    },
}


def lookup(device_kind: str) -> dict:
    """Peaks of ``device_kind``; ``KeyError`` for a device not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
