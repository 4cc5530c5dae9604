"""Layer ``kv_pool``: rows of the pool's slots the decode steps read over rows
those slots reserve (``n_slots`` x ``block_size`` a step), in percent, over the
traced window: 100 where every step reads every slot whole, less where the
step stops at the furthest live lane's position. Beside ``kv.live_row_share``
it says what a read that stopped at each lane's own position would still
save. Source: the program's own rule, counted by the scheduler
(``ServingMetrics.summary()``'s ``decode_rows_read`` and
``decode_rows_reserved``), differenced over the window. A program without the
counters, or a window without a decode step, reports nothing."""


def read(ev):
    play = ev.get("play")
    if play is None or play.trace_close is None or play.trace_open is None:
        return None
    moved = []
    for field in ("decode_rows_read", "decode_rows_reserved"):
        closed, opened = (c.get(field) for c in (play.trace_close,
                                                 play.trace_open))
        if closed is None or opened is None:
            return None
        moved.append(closed - opened)
    read_rows, reserved = moved
    return 100.0 * read_rows / reserved if reserved > 0 else None
