"""Layer ``scheduler``: of the decode steps launched in the traced window, the
share, in percent, that the scheduler launched before it waited for the step
before them, whose tokens they take on the device
(``InferenceServer.step``): the device then goes from one step into the next
and the host's launch, wake-up and emit fall in a running step's shadow. A
round syncs first where a token in flight is a request's last by length (or
a lane speculates), and the launch after such a round is not ahead, so the
share says how much of the traffic the mechanism reaches. Source: the
scheduler's own counters (``ServingMetrics.summary()``'s
``decode_rounds_ahead`` and ``decode_launches``), differenced over the
window. A program without the counters, or a window without a launch,
reports nothing."""


def read(ev):
    play = ev.get("play")
    if play is None or play.trace_close is None or play.trace_open is None:
        return None
    moved = []
    for field in ("decode_rounds_ahead", "decode_launches"):
        closed, opened = (c.get(field) for c in (play.trace_close,
                                                 play.trace_open))
        if closed is None or opened is None:
            return None
        moved.append(closed - opened)
    ahead, launches = moved
    return 100.0 * ahead / launches if launches > 0 else None
