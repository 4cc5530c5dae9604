"""Layer ``kernel``: device milliseconds a training step spends in the flash
backward kernels (the Mosaic calls named ``flash_bwd_dq``, ``flash_bwd_dkv``
or ``flash_bwd_fused``)."""

from benchmarks.harness import spans


def read(ev):
    return spans.flash_ms_per_step(ev, "flash_bwd")
