"""Layer ``attention``: of the rows at or before their queries, the share the
sparse layers' decode steps attended, in percent, over the traced window:
100 while every live lane stands below the selection's ``dense_len``, near
(``topk`` x ``block_size`` + the window) / context above it. Source: the
program's device-side counter (``ServingMetrics.summary()``'s
``sparse_rows_attended`` and ``sparse_rows_live``, each summed over sparse
layers and live lanes a round), differenced over the window. A program
without the counter, or a window without a decode round, reports nothing."""


def read(ev):
    play = ev.get("play")
    if play is None or play.trace_close is None or play.trace_open is None:
        return None
    moved = []
    for field in ("sparse_rows_attended", "sparse_rows_live"):
        closed, opened = (c.get(field) for c in (play.trace_close,
                                                 play.trace_open))
        if closed is None or opened is None:
            return None
        moved.append(closed - opened)
    attended, live = moved
    return 100.0 * attended / live if live > 0 else None
