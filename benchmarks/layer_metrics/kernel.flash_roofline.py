"""Layer ``kernel``: the flash kernels' share of their roofline, in percent.
The least time the chip could take for one step's attention, which is FLOPs
from shapes (``harness/flops.flash_train_flops``: causal half, forward and
backward, this chip's rows) over the bf16 peak, over the kernels' measured
device time a step, or bytes over the HBM peak where that is the larger. At
T=1,024 the bound is compute: the kernels move 8·B·T·d elements against
7·B·T²·d FLOPs."""

from benchmarks.harness import device, trace


def read(ev):
    tr = ev.get("trace")
    if tr is None or not ev.get("trace_steps"):
        return None
    flash = trace.class_s(tr, trace.window_of(tr), trace.is_mosaic)
    if not flash:
        return None
    peaks = device.peaks_for(ev["device_kind"])
    least = max(ev["flash_flops_per_step_chip"] / peaks["flops"],
                ev["flash_bytes_per_step_chip"] / peaks["hbm_bytes_s"])
    return 100.0 * least / (flash / ev["trace_steps"])
