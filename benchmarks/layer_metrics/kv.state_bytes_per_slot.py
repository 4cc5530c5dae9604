"""Layer ``kv_pool``: bytes of recurrent state a slot holds beside its rows,
whatever the slot's request has read: a hybrid stack's linear layers keep a
float32 (heads, size, size) state each. Source: the program's
``state_bytes_per_slot`` gauge (``ServingMetrics.summary()``), read at the
traced window's close. A decode round reads and writes every live lane's
state once, so a state kept in fewer bits shows here first. A program
without the gauge, or a model without a state, reports nothing."""


def read(ev):
    play = ev.get("play")
    if play is None or play.trace_close is None:
        return None
    return play.trace_close.get("state_bytes_per_slot") or None
