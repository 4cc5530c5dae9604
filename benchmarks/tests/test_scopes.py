"""The join of the program's instruction -> scope table to the trace, on
planes with known answers: two runs of the decode program inside the window
(one more outside it), operations under two scopes, one under none, one the
table lacks, a ``while`` container, and another program's operations."""

import pytest

from benchmarks.harness import scopes, spec, trace
from benchmarks.tests.test_trace import Line, Plane, ev

_NEW = ("attention.decode_ms_per_step", "sparse.select_attend_ms_per_step",
        "moe.experts_ms_per_step", "kv.row_write_ms_per_step",
        "engine.sample_ms_per_step", "engine.unscoped_ms_per_step",
        "engine.scope_join_share")
_TABLE = {"fusion.1": "cached_attn", "fusion.2": "kv_layout", "copy.3": "",
          "while.4": "cached_attn", "fusion.5": "cached_attn",
          "fusion.9": "sample"}       # fusion.9 never runs in the window


def _record(name, table, **kw):
    return dict(kind="program", name=name, ts=1.0, family="decode",
                variant="", scopes=table, **kw)


def _run(lo, lost):
    """One run of the decode program from ``lo`` ms, 10 ms long: 2 ms of
    attention, 1 of it inside a ``while`` whose own event spans 1.5; 3 ms of
    row writes; 0.5 ms under no scope; ``lost`` ms of an instruction no
    table has. On the chip a name is the whole instruction."""
    ms = 1e6
    at = lambda a, b: (lo * ms + a * ms, lo * ms + b * ms)
    return [
        ev("%fusion.1 = bf16[4,8]{1,0} fusion(bf16[4,8]{1,0} %p.1), kind=kLoop",
           *at(0, 1)),
        ev("%while.4 = (s32[], bf16[4,8]{1,0}) while(%tuple.2)", *at(1, 2.5)),
        ev("%fusion.5", *at(1.2, 2.2)),
        ev("%fusion.2 = bf16[4,8]{1,0} fusion(%fusion.5), kind=kLoop", *at(3, 6)),
        ev("%copy.3", *at(6, 6.5)),
        ev("%fusion.77 = f32[2]{0} fusion()", *at(7, 7 + lost)),
    ]


def _evidence(records):
    ms = 1e6
    modules = [ev("jit__prefill_impl(4)", 2 * ms, 8 * ms),
               ev("jit__decode_impl(3)", 10 * ms, 20 * ms),
               ev("jit__decode_impl(3)", 30 * ms, 40 * ms),
               ev("jit__decode_impl(3)", 95 * ms, 105 * ms)]   # cut by the window
    ops = ([ev("%fusion.1", 2 * ms, 8 * ms)]       # the prefill's own fusion.1
           + _run(10, 1.0) + _run(30, 2.0) + _run(95, 1.0))
    tr = trace.from_planes([
        Plane("/device:TPU:0", [Line("XLA Modules", modules),
                                Line("XLA Ops", ops)]),
        Plane("/host:CPU", [Line("python", [ev("bench.window", 0, 100 * ms)])])])
    return {"kind": "serve", "trace": tr, "program_spans": records}


def _read(metric, ev_):
    return spec.load_reader(metric).read(ev_)


def test_ms_by_scope_sums_the_runs_inside_the_window_by_hand():
    got = scopes.ms_by_scope(_evidence(
        [dict(kind="span", name="serve.decode_round", ts=1.0, dur_s=0.01),
         _record("jit__decode_impl", _TABLE)]), "decode_impl")
    assert got["runs"] == 2
    # the while's own event never counts: its body's fusion.5 does
    assert got["by_scope"] == pytest.approx({"cached_attn": 2.0,
                                             "kv_layout": 3.0})
    assert got["unscoped_ms"] == pytest.approx(0.5)
    assert got["unmatched_ms"] == pytest.approx(1.5)    # (1 + 2) / 2


def test_the_new_readers_read_their_scopes_and_sum_to_the_run():
    ev_ = _evidence([_record("jit__decode_impl", _TABLE)])
    got = {m: _read(m, ev_) for m in _NEW}
    assert got["attention.decode_ms_per_step"] == pytest.approx(2.0)
    assert got["kv.row_write_ms_per_step"] == pytest.approx(3.0)
    assert got["engine.unscoped_ms_per_step"] == pytest.approx(0.5)
    # scopes the program has nothing under read 0, not None
    assert got["engine.sample_ms_per_step"] == 0.0
    assert got["moe.experts_ms_per_step"] == 0.0
    assert got["sparse.select_attend_ms_per_step"] == 0.0
    # matched 5.5 of 7.0 ms: the table lacks an instruction that ran
    assert got["engine.scope_join_share"] == pytest.approx(100 * 5.5 / 7.0)
    assert got["engine.scope_join_share"] < 100
    times = sum(v for m, v in got.items() if m.endswith("_ms_per_step")
                and m != "sparse.select_attend_ms_per_step")
    assert times == pytest.approx(5.5)


def test_a_table_of_another_program_shows_in_the_join_share():
    ev_ = _evidence([_record("jit__decode_impl", {"fusion.77": "sample"})])
    assert _read("engine.scope_join_share", ev_) == pytest.approx(
        100 * 1.5 / 7.0)


def test_each_run_is_read_with_the_table_that_knows_it_best():
    """Two records of one jit name (two prefill buckets): the one that holds
    the run's instructions is used, not the first."""
    other = {"fusion.1": "sample", "fusion.300": ""}
    got = scopes.ms_by_scope(_evidence(
        [_record("jit__decode_impl", other),
         _record("jit__decode_impl", _TABLE)]), "decode_impl")
    assert got["by_scope"] == pytest.approx({"cached_attn": 2.0,
                                             "kv_layout": 3.0})
    # and a program_part that names another program takes its runs and ops
    pre = scopes.ms_by_scope(_evidence(
        [_record("jit__prefill_impl", {"fusion.1": "kv_layout"})]),
        "prefill_impl")
    assert pre["runs"] == 1 and pre["by_scope"] == pytest.approx(
        {"kv_layout": 6.0})


@pytest.mark.parametrize("metric", _NEW)
def test_without_a_program_record_every_new_reader_reads_none(metric):
    """The parent commit's tracer files no ``program`` record; its line is
    printed all the same, without the new metrics."""
    spans_only = [dict(kind="span", name="serve.decode_round", ts=1.0,
                       dur_s=0.01, id=1, parent=None)]
    assert _read(metric, _evidence(spans_only)) is None
    assert _read(metric, _evidence([])) is None
    with_record = _evidence([_record("jit__decode_impl", _TABLE)])
    assert _read(metric, dict(with_record, trace=None)) is None
    assert _read(metric, {"kind": "serve"}) is None
    # a record of another program only, or a window without a decode run
    assert _read(metric, _evidence(
        [_record("jit__prefill_impl", _TABLE)])) is None
    no_run = _evidence([_record("jit__decode_impl", _TABLE)])
    dev = no_run["trace"].devices[0]
    dev.modules[:] = [m for m in dev.modules if "decode" not in m.name]
    assert _read(metric, no_run) is None
