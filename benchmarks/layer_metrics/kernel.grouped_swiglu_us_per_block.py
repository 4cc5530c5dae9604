"""Layer ``kernel``: device microseconds a block of the routed experts' layout
costs in the grouped-SwiGLU kernel: the time of the Mosaic calls whose own
instruction name holds ``grouped_swiglu`` (the ``name=`` of the
``pallas_call`` in ``ops/moe.py``) in the traced window, prefills' and decode
steps' alike, over the blocks the experts' loops ran in that window (the
program's device-side counter ``moe_blocks_run``, closed less opened, as
``moe.blocks_run_share`` reads it). A trace without such a call (the parent's
program, which runs the blocks in an XLA loop), a program without the
counter, or a window in which no block ran reports nothing."""

from benchmarks.harness import trace


def read(ev):
    tr, play = ev.get("trace"), ev.get("play")
    if tr is None or play is None:
        return None
    if play.trace_close is None or play.trace_open is None:
        return None
    closed, opened = (c.get("moe_blocks_run") for c in (play.trace_close,
                                                        play.trace_open))
    if closed is None or opened is None or closed <= opened:
        return None
    if not any(d.ops for d in tr.devices.values()):
        return None
    seconds = trace.class_s(
        tr, trace.window_of(tr),
        lambda e: trace.is_mosaic(e) and "grouped_swiglu" in e.base)
    return 1e6 * seconds / (closed - opened) if seconds else None
