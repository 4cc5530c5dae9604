"""Layer ``kv_pool``: of the rows the decode steps read of the window layers'
rings, the share that lay inside their lanes' windows, in percent, over the
traced window: what a step that attended only the rows inside each lane's
window would read of what it reads. Under 100 by the ring's one row a lane
that has left the window (the row the lane's own replaces), by whole blocks
read of a lane younger than the window, and by blocks read for all lanes
together that some lanes do not need. Source: the program's own rule,
counted by the scheduler (``ServingMetrics.summary()``'s ``ring_rows_live``
and ``ring_rows_read``), differenced over the window. A program without the
counters, or a window without a decode step, reports nothing."""


def read(ev):
    play = ev.get("play")
    if play is None or play.trace_close is None or play.trace_open is None:
        return None
    moved = []
    for field in ("ring_rows_live", "ring_rows_read"):
        closed, opened = (c.get(field) for c in (play.trace_close,
                                                 play.trace_open))
        if closed is None or opened is None:
            return None
        moved.append(closed - opened)
    live, read_rows = moved
    return 100.0 * live / read_rows if read_rows > 0 else None
