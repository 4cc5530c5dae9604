"""Layer ``trainer``: model FLOP/s utilization. Tokens a second and chip at
the window's median step time (the steps around the profiler's start and stop
left out), times the benchmark's FLOPs a token (``harness/flops.py``: 6·N plus the
full T² attention term, recomputation not counted), over the table's bf16
peak. It is ``train_tok_s_chip`` times a constant, so it moves with it."""

import statistics

from benchmarks.harness import device


def read(ev):
    steps = ev.get("step_s")
    if not steps:
        return None
    tok_s_chip = ev["tokens_per_step"] / statistics.median(steps) / ev["chips"]
    peak = device.peaks_for(ev["device_kind"])["flops"]
    return tok_s_chip * ev["train_flops_per_token"] / peak
