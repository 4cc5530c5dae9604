"""Layer ``engine``: the decode step's share of its memory roofline, in
percent: the bytes a step of a looped stack cannot avoid reading, over the
HBM peak of the table (``harness/device.py``), over the mean device time of
the traced window's decode runs (``_decode_impl``, found by its jit name).

The bytes, from the published keys of the configuration file and the
program's counters, a lower bound (no activation, norm scale or written row
is counted, and nothing twice):

  passes x the trunk's matmul weights: ``total_ut_steps`` x
      ``num_hidden_layers`` x (q and o: 2 x hidden x heads x head_dim; k and
      v: 2 x hidden x kv_heads x head_dim; gate, up, down: 3 x hidden x
      intermediate) x 2 B, the same weights once a pass
  + the head, hidden x vocabulary x 2 B (the embedding is a gather of a row
      a lane)
  + the rows of the pool the step read: ``decode_rows_read`` (rows of every
      slot, a plane, by the program's own rule, differenced over the window)
      over ``steps``, times the gauge ``kv_bytes_per_row`` (all planes' keys
      and values of one row)

The step is bound by bytes, not operations (five lanes a weight row), so the
share of the compute peak is not reported. A bound from below: a reading
over 100 says the time leaves out part of the step or the bytes are counted
too high."""

import statistics

from benchmarks.harness import device, trace

WEIGHT_BYTES = 2        # bfloat16, as the configuration states


def step_bytes(config, rows_read_a_step: float, kv_bytes_per_row: float):
    d, hd = config["hidden_size"], config["head_dim"]
    attention = 2 * d * hd * (config["num_attention_heads"]
                              + config["num_key_value_heads"])
    trunk = config["num_hidden_layers"] * (
        attention + 3 * d * config["intermediate_size"])
    return (WEIGHT_BYTES * (config["total_ut_steps"] * trunk
                            + d * config["vocab_size"])
            + rows_read_a_step * kv_bytes_per_row)


def read(ev):
    tr, play = ev.get("trace"), ev.get("play")
    if tr is None or play is None or play.trace_open is None \
            or play.trace_close is None:
        return None
    config = ev["cell"].config
    if "total_ut_steps" not in config:
        return None
    delta = {}
    for field in ("decode_rows_read", "steps"):
        a, b = play.trace_open.get(field), play.trace_close.get(field)
        if a is None or b is None:
            return None
        delta[field] = b - a
    row_bytes = play.trace_close.get("kv_bytes_per_row")
    runs = trace.program_runs(tr, trace.window_of(tr), "decode_impl")
    if not runs or not delta["steps"] or not row_bytes:
        return None
    needed = step_bytes(config, delta["decode_rows_read"] / delta["steps"],
                        row_bytes)
    least_s = needed / device.peaks_for(ev["device_kind"])["hbm_bytes_s"]
    return 100.0 * least_s / statistics.fmean(runs)
