"""Layer ``kv_pool``: bytes of the window layers' rings a slot holds beside
its rows, whatever the slot's request has read: a sliding layer of a stack
of ``layer_types`` keeps its last ``sliding_window`` keys and values a slot
and no row a position. Source: the program's ``ring_bytes_per_slot`` gauge
(``ServingMetrics.summary()``), read at the traced window's close. Beside
``kv_bytes_per_row`` x ``block_size`` it is what a slot costs; what the ring
saves is the rows the window layers would hold at a row a position. A
program without the gauge, or a model without a ring, reports nothing."""


def read(ev):
    play = ev.get("play")
    if play is None or play.trace_close is None:
        return None
    return play.trace_close.get("ring_bytes_per_slot") or None
