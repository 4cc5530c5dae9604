"""Roofline peak tables — the single source of truth (ISSUE 5 satellite).

``training/metrics.py`` reads these for ``train.py``'s MFU log line, so
a new chip generation is added in exactly one place. Public numbers
throughout.
"""

from __future__ import annotations

from typing import Dict, Optional

__all__ = [
    "PEAK_FLOPS",
    "PEAK_HBM_BYTES",
    "peak_flops_per_chip",
    "peak_hbm_bytes_per_chip",
]

# Peak dense bf16 FLOP/s per chip, for MFU.
# Ordering matters for the longest-prefix lookup below: "TPU v5 lite"
# must precede "TPU v5" so a v5e never reads the v5p row. "v6e"/"v6 lite"
# and "v7"/"v7x" are spelling aliases — PJRT device_kind strings have
# historically used both forms within a generation.
PEAK_FLOPS: Dict[str, float] = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,  # v5p (bare "TPU v5" device_kind spelling)
    "TPU v6 lite": 918e12,  # v6e (Trillium)
    "TPU v6e": 918e12,
    "TPU v7x": 2307e12,
    "TPU v7": 2307e12,  # Ironwood: bf16 half of the 4614 TFLOP/s fp8 peak
}

# Peak HBM bandwidth per chip (bytes/s), for memory-bound rooflines
# (KV-cached decode streams the whole parameter set per token, so its
# ceiling is bandwidth, not FLOPs).
PEAK_HBM_BYTES: Dict[str, float] = {
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,  # v5e
    "TPU v5e": 819e9,
    "TPU v5p": 2765e9,
    "TPU v5": 2765e9,  # v5p (bare "TPU v5" device_kind spelling)
    "TPU v6 lite": 1640e9,  # v6e (Trillium)
    "TPU v6e": 1640e9,
    "TPU v7x": 7370e9,
    "TPU v7": 7370e9,  # Ironwood
}


def _chip_lookup(table: Dict[str, float]) -> Optional[float]:
    """This process's chip in ``table`` — longest-prefix-wins by dict
    order (see the ordering note above). A non-TPU backend has no peak
    (None: callers drop the roofline figures). A TPU whose ``device_kind``
    is missing from the table is an error, not a default: a utilization
    quietly dropped, or computed against another chip's peak, reads as a
    measurement."""
    import jax  # lazy: the telemetry package must import without a backend

    device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    for name, val in table.items():
        if device.device_kind.startswith(name):
            return val
    raise LookupError(
        f"TPU device_kind {device.device_kind!r} is not in the peak tables "
        f"of telemetry/peaks.py (known: {sorted(table)}); add its published "
        "peak there before asking for a utilization on it"
    )


def peak_flops_per_chip() -> Optional[float]:
    return _chip_lookup(PEAK_FLOPS)


def peak_hbm_bytes_per_chip() -> Optional[float]:
    return _chip_lookup(PEAK_HBM_BYTES)
