"""Layer ``kernel``: the grouped gated-MLP kernel's share of its roofline, in
percent: the least time the chip could take for the routed experts' blocks of
the traced window, over the device time of the Mosaic calls whose own
instruction name holds ``grouped_swiglu`` or ``grouped_reglu`` (the ``name=``
of the ``pallas_call`` in ``ops/moe.py``, one a gate activation) in that
window, prefills' and decode steps' alike.

The least time is the larger of two, both from the program's device-side
counters differenced over the window and the configuration's published
widths (``hidden_size`` d, ``moe_ffn_hidden_size`` f):

  operations: ``moe_routed_rows`` x 6 d f (a routed row through gate, up and
      down: three matmuls of 2 d f) over the bf16 peak of the table
      (``harness/device.py``). The rows a block is padded with are computed
      and not counted.
  bytes: ``moe_expert_runs`` x 3 d f x 2 B (every expert that held a row, a
      call and layer, read once in bfloat16) over the HBM peak. The blocks'
      rows in and out are not counted: a bound from below.

A decode round of some tens of lanes is bound by the bytes, a prefill of
thousands of tokens by the operations; the window holds both and the share
is of their sum's larger bound. Bounds from below, so a reading over 100 says
the time leaves out part of the kernels or a counter counts too much. A trace
without such a call (an XLA loop in its place), a program without the
counters, a configuration without the widths, or a window in which no block
ran reports nothing."""

from benchmarks.harness import device, trace

WEIGHT_BYTES = 2        # bfloat16, as the configuration states
KERNELS = ("grouped_swiglu", "grouped_reglu")


def least_seconds(config, rows: float, expert_runs: float, peaks) -> float:
    """The roofline of ``rows`` routed rows through ``expert_runs`` experts
    read once each: the larger of operations over the compute peak and
    bytes over the HBM peak."""
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    flops = rows * 6 * d * f
    read_bytes = expert_runs * 3 * d * f * WEIGHT_BYTES
    return max(flops / peaks["flops"], read_bytes / peaks["hbm_bytes_s"])


def read(ev):
    tr, play = ev.get("trace"), ev.get("play")
    if tr is None or play is None:
        return None
    if play.trace_close is None or play.trace_open is None:
        return None
    config = ev["cell"].config
    if "hidden_size" not in config or "moe_ffn_hidden_size" not in config:
        return None
    moved = []
    for field in ("moe_routed_rows", "moe_expert_runs"):
        closed, opened = (c.get(field) for c in (play.trace_close,
                                                 play.trace_open))
        if closed is None or opened is None or closed <= opened:
            return None
        moved.append(closed - opened)
    if not any(d.ops for d in tr.devices.values()):
        return None
    seconds = trace.class_s(
        tr, trace.window_of(tr),
        lambda e: trace.is_mosaic(e) and any(k in e.base for k in KERNELS))
    if not seconds:
        return None
    least = least_seconds(config, *moved, device.peaks_for(ev["device_kind"]))
    return 100.0 * least / seconds
