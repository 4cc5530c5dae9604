"""The device gate, the table of published peaks and the memory fields.

The table is the benchmark's own copy (the program keeps one in
``telemetry/peaks.py``; a later PR may change that one, not this). A device
that is not in the table is an error, never a default: a utilization worked
out against another chip's peak reads as a measurement.
"""

from __future__ import annotations

from typing import Dict, List

#: per chip: dense bf16 FLOP/s, HBM bytes/s, HBM bytes. Sources: Google Cloud
#: documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM2e, 819 GB/s) and the
#: matching pages for v4, v5p and v6e. Longest prefix wins, so "TPU v5 lite"
#: stands before "TPU v5".
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v4": {"flops": 275e12, "hbm_bytes_s": 1228e9, "hbm_bytes": 32e9},
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"flops": 197e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5p": {"flops": 459e12, "hbm_bytes_s": 2765e9, "hbm_bytes": 95e9},
    "TPU v5": {"flops": 459e12, "hbm_bytes_s": 2765e9, "hbm_bytes": 95e9},
    "TPU v6 lite": {"flops": 918e12, "hbm_bytes_s": 1640e9, "hbm_bytes": 32e9},
    "TPU v6e": {"flops": 918e12, "hbm_bytes_s": 1640e9, "hbm_bytes": 32e9},
}


class NoChip(RuntimeError):
    """JAX found no accelerator the cell can run on."""


def peaks_for(kind: str) -> Dict[str, float]:
    for name, row in PEAKS.items():
        if kind.startswith(name):
            return row
    raise NoChip(
        f"device kind {kind!r} is not in the benchmark's table of peaks "
        f"({sorted(PEAKS)}): add its published peaks before measuring on it")


def require_tpu(chips: int) -> List:
    """The first ``chips`` devices, or ``NoChip``: no CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(
            f"the benchmark measures on a TPU and JAX found platform "
            f"{devices[0].platform!r} ({len(devices)} device(s))")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX found {len(devices)}")
    peaks_for(devices[0].device_kind)
    return devices[:chips]


def open_chip(chips: int) -> tuple:
    """What every entry point does before its first compile: place the
    persistent cache where the program places it (the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``), let
    every program into it however quick its compile, and pass the device
    gate. Returns (devices, cache directory); ``NoChip`` if there is no chip
    or no program to measure."""
    try:
        import jax
        from mingpt_distributed_tpu.utils import startup
    except ImportError as e:
        raise NoChip(f"nothing to measure here: {e}") from e
    cache_dir = startup.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return require_tpu(chips), cache_dir


def describe(devices: List) -> dict:
    """The ``device`` object of the result line, as JAX reports it."""
    import jax

    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices())}


def memory(stats: List[dict]) -> dict:
    """The ``device`` memory fields from each chip's ``memory_stats()``, read
    when the window closed; of several chips, the fullest.

    On the TPU runtime the allocator's ``peak_bytes_in_use`` counts buffers
    (weights, optimizer state, the KV pool, batches) and leaves out what a
    running program takes for its temporaries, which the runtime books apart
    as ``bytes_reserved``: under the 124M training step the compiler plans
    at 12.2 GB the allocator peaked at 2.27 GB and 10.21 GB were reserved
    (my chip runs, PR 22). So neither statistic is the peak on the chip, and
    their sum is no peak either: the two high-water marks need not fall
    together (9.55 + 7.85 GB were logged under a limit of 16.91 GB).

    ``memory_peak_bytes`` is what was taken while the largest program ran:
    the buffers resident at the window's close, over which that program runs
    (``bytes_in_use``: nothing transient is alive between two rounds or two
    steps), plus the largest reservation (``peak_bytes_reserved``). The
    statistics it is made of stand beside it, with ``bytes_limit``, so a
    reader can take another view. A runtime without them is an error: a
    guessed peak reads as a measurement."""
    def fullest(s: dict) -> int:
        return int(s["bytes_in_use"]) + int(s["peak_bytes_reserved"])

    s = max(stats, key=fullest)
    return {"memory_peak_bytes": fullest(s),
            "bytes_in_use": int(s["bytes_in_use"]),
            "peak_bytes_in_use": int(s["peak_bytes_in_use"]),
            "peak_bytes_reserved": int(s["peak_bytes_reserved"]),
            "bytes_limit": int(s["bytes_limit"])}
