"""Layer ``experts``: blocks the experts' loop took through an expert over the
blocks its layouts had, in percent, over the traced window (prefills' and
decode steps' alike, all expert layers): 100 where every block of the static
worst-case layout costs a read of an expert's weights, less where only the
blocks that hold a request's route are run. Source: the program's device-side
counter (``ServingMetrics.summary()``'s ``moe_blocks_run`` and
``moe_blocks_laid``), differenced over the window. A program without the
counters, or a window in which no layout was laid, reports nothing."""


def read(ev):
    play = ev.get("play")
    if play is None or play.trace_close is None or play.trace_open is None:
        return None
    moved = []
    for field in ("moe_blocks_run", "moe_blocks_laid"):
        closed, opened = (c.get(field) for c in (play.trace_close,
                                                 play.trace_open))
        if closed is None or opened is None:
            return None
        moved.append(closed - opened)
    ran, laid = moved
    return 100.0 * ran / laid if laid > 0 else None
