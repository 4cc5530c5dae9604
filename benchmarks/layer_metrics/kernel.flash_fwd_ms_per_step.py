"""Layer ``kernel``: device milliseconds a training step spends in the flash
forward kernel (the Mosaic calls named ``flash_fwd``; under ``remat`` the
forward runs again in the backward pass and is counted both times)."""

from benchmarks.harness import spans


def read(ev):
    return spans.flash_ms_per_step(ev, "flash_fwd")
