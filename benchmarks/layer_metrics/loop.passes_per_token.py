"""Layer ``engine``: passes of the layers a token took, the mean over the
tokens of requests the traced window's prefill and decode programs ran: the
program's own device-side counter (``generate.LOOP_PASSES``, fetched by
``ServingMetrics.summary()``: ``loop_token_passes`` over ``loop_tokens``),
differenced over the window. A looped stack at the one exit threshold that
is built reads its pass count, ``total_ut_steps``; anything less is
mathematics left out. A program without the counter (one whose layers run
once, the parent's) reports nothing."""


def read(ev):
    play = ev.get("play")
    if play is None or play.trace_open is None or play.trace_close is None:
        return None
    delta = {}
    for field in ("loop_token_passes", "loop_tokens"):
        a, b = play.trace_open.get(field), play.trace_close.get(field)
        if a is None or b is None:
            return None
        delta[field] = b - a
    if not delta["loop_tokens"]:
        return None
    return delta["loop_token_passes"] / delta["loop_tokens"]
