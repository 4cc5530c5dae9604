"""The span and name readers on synthetic evidence with known answers: a ring
of span records with ``parent`` links, and a trace built with ``from_planes``
from hand-made events that carry the kernels' and the programs' names."""

import pytest

from benchmarks.harness import spans, spec, trace
from benchmarks.tests.test_trace import Line, Plane, ev

EPOCH_NS = 1_000_000_000          # the profiler clock's zero, as an epoch
MOSAIC = ', custom_call_target="tpu_custom_call"'


def span(name, lo_ms, dur_ms, id_, parent=None, **attrs):
    """A ring record that starts ``lo_ms`` after the trace's zero."""
    return dict(kind="span", name=name, ts=(EPOCH_NS + lo_ms * 1e6) / 1e9,
                dur_s=dur_ms / 1e3, depth=0 if parent is None else 1,
                id=id_, parent=parent, **attrs)


def round_records(lo_ms, first_id, fold, launch, sync, emit, self_ms=1.0):
    total = fold + launch + sync + emit + self_ms
    i = first_id
    return [
        span("serve.fold_keys", lo_ms + 0.5, fold, i + 1, i, lanes=3),
        span("serve.decode_launch", lo_ms + 0.5 + fold, launch, i + 2, i),
        span("serve.decode_sync", lo_ms + 0.5 + fold + launch, sync, i + 3, i),
        span("serve.emit", lo_ms + 0.5 + fold + launch + sync, emit, i + 4, i),
        span("serve.decode_round", lo_ms, total, i, None, lanes=3),
    ]


def serve_trace():
    """A window of 100 ms with two whole rounds inside, [10, 40) and
    [50, 90) ms; the first runs three small unnamed-by-us programs, a prefill
    and the decode program, the second one small program and the decode."""
    ms = 1e6
    modules = [
        ev("jit__threefry_fold_in(1)", 11 * ms, 11.1 * ms),
        ev("jit__threefry_fold_in(1)", 12 * ms, 12.1 * ms),
        ev("jit_concatenate(2)", 13 * ms, 13.1 * ms),
        ev("jit__prefill_impl(4)", 14 * ms, 15 * ms),
        ev("jit__decode_impl(3)", 20 * ms, 38 * ms),
        ev("jit__threefry_fold_in(1)", 45 * ms, 45.1 * ms),   # between rounds
        ev("jit_concatenate(2)", 51 * ms, 51.1 * ms),
        ev("jit__decode_impl(3)", 60 * ms, 85 * ms),
        ev("jit__threefry_fold_in(1)", 96 * ms, 96.1 * ms),   # a cut round's
    ]
    ops = [ev("%fusion.1", 20 * ms, 38 * ms), ev("%fusion.1", 60 * ms, 85 * ms)]
    host = Plane("/host:CPU", [Line("python", [
        ev("bench.window", 0, 100 * ms),
        ev("bench.round", 9 * ms, 41 * ms), ev("bench.round", 49 * ms, 91 * ms),
    ])])
    return trace.from_planes([
        Plane("/device:TPU:0", [Line("XLA Modules", modules),
                                Line("XLA Ops", ops)]),
        host,
        Plane("Task Environment", [], [("profile_start_time", EPOCH_NS)]),
    ])


@pytest.fixture
def serve_ev():
    records = (
        round_records(-20, 1, 5, 1, 10, 1)          # began before the window
        + [span("serve.queue_wait", 9.2, 0.4, 20, 19, request_id="r1"),
           span("serve.admit", 9.5, 0.3, 19, None, request_id="r1")]
        + round_records(10, 30, fold=6.0, launch=2.0, sync=18.0, emit=3.0)
        + [span("serve.queue_wait", 43.0, 6.0, 41, None, request_id="r2"),
           span("serve.queue_wait", 44.0, 5.2, 42, None, request_id="r3")]
        + round_records(50, 50, fold=8.0, launch=2.0, sync=26.0, emit=3.0)
        + round_records(95, 60, 5, 1, 10, 1)        # ends after the window
        + [dict(kind="event", name="log", ts=1.0, depth=0)]
    )
    return {"kind": "serve", "trace": serve_trace(), "program_spans": records}


def read(metric, ev_):
    return spec.load_reader(metric).read(ev_)


def test_round_split_reads_the_children_of_the_rounds_inside_the_window(serve_ev):
    assert [r["id"] for r, _ in spans.rounds(serve_ev)] == [30, 50]
    got = {m: read(m, serve_ev) for m in (
        "sched.fold_keys_ms_per_round", "sched.launch_ms_per_round",
        "engine.sync_wait_ms_per_round", "sched.emit_ms_per_round",
        "sched.round_self_ms")}
    assert got["sched.fold_keys_ms_per_round"] == pytest.approx(7.0)
    assert got["sched.launch_ms_per_round"] == pytest.approx(2.0)
    assert got["engine.sync_wait_ms_per_round"] == pytest.approx(22.0)
    assert got["sched.emit_ms_per_round"] == pytest.approx(3.0)
    assert got["sched.round_self_ms"] == pytest.approx(1.0)
    # the five sum to the mean round: (30 + 40) / 2
    assert sum(got.values()) == pytest.approx(35.0)


def test_self_time_follows_parent_links_not_names(serve_ev):
    """A span of another round's name under this round counts against it; one
    with no parent does not."""
    serve_ev["program_spans"] += [
        span("serve.log_flush", 30.0, 0.5, 70, 30),
        span("serve.fold_keys", 31.0, 9.0, 71, None)]
    assert read("sched.round_self_ms", serve_ev) == pytest.approx(0.75)
    assert read("sched.fold_keys_ms_per_round", serve_ev) == pytest.approx(7.0)


def test_aux_programs_are_those_started_inside_a_round(serve_ev):
    # round one: two fold_in and a concatenate (prefill and decode left out);
    # round two: one concatenate
    assert read("sched.aux_programs_per_round", serve_ev) == pytest.approx(2.0)


def test_queue_wait_is_the_median_over_waits_that_ended_in_the_window(serve_ev):
    assert read("sched.queue_wait_ms_p50", serve_ev) == pytest.approx(5.2)


@pytest.mark.parametrize("metric", [
    "sched.fold_keys_ms_per_round", "sched.launch_ms_per_round",
    "sched.emit_ms_per_round", "sched.round_self_ms",
    "engine.sync_wait_ms_per_round", "sched.aux_programs_per_round",
    "sched.queue_wait_ms_p50"])
def test_a_program_without_span_ids_leaves_the_metric_out(serve_ev, metric):
    """The parent commit's records have no ``id`` and no children."""
    old = [{k: v for k, v in r.items() if k not in ("id", "parent")}
           for r in serve_ev["program_spans"]
           if r["name"] in ("serve.decode_round", "serve.admit", "log")]
    assert read(metric, dict(serve_ev, program_spans=old)) is None
    assert read(metric, dict(serve_ev, trace=None)) is None
    assert read(metric, {"kind": "serve"}) is None


def train_ev():
    """Two chips, two steps. Each chip: forward 10 + 10, backward dq 15 and
    dkv 25 (chip 1: 35); the backward calls name the forward's outputs."""
    def plane(index, dkv):
        ops = [
            ev("%fusion.1", 0, 100),
            ev("%flash_fwd.3 = (bf16[2,8]{1,0}) custom-call(bf16[2,8]{1,0} "
               "%fusion.1)" + MOSAIC, 100, 110),
            ev("%jvp_flash_fwd_.3 = (bf16[2,8]{1,0}) custom-call(bf16[2,8]{1,0} "
               "%fusion.1)" + MOSAIC, 120, 130),
            ev("%flash_bwd_dq.4 = bf16[2,8]{1,0} custom-call(bf16[2,8]{1,0} "
               "%flash_fwd.3)" + MOSAIC, 200, 215),
            ev("%transpose_jvp_flash_bwd_dkv__.1 = bf16[2,8]{1,0} custom-call("
               "bf16[2,8]{1,0} %flash_fwd.3)" + MOSAIC, 300, 300 + dkv),
            # reads a kernel's output and is no kernel
            ev("%fusion.2 = bf16[2,8]{1,0} fusion(bf16[2,8]{1,0} %flash_fwd.3)",
               400, 450),
        ]
        return Plane(f"/device:TPU:{index}", [
            Line("XLA Modules", [ev("jit_train_step(7)", 0, 450)]),
            Line("XLA Ops", ops)])
    tr = trace.from_planes([
        plane(0, 25), plane(1, 35),
        Plane("/host:CPU", [Line("python", [ev("bench.window", 0, 500)])])])
    return {"kind": "train", "trace": tr, "trace_steps": 2}


def test_flash_time_is_split_by_the_kernels_own_names():
    ev_ = train_ev()
    fwd = read("kernel.flash_fwd_ms_per_step", ev_)
    bwd = read("kernel.flash_bwd_ms_per_step", ev_)
    assert fwd == pytest.approx(1e3 * 20e-9 / 2)
    assert bwd == pytest.approx(1e3 * (15 + 30) * 1e-9 / 2)
    # together they are the time kernel.flash_busy_share is made of
    tr = ev_["trace"]
    mosaic = trace.class_s(tr, trace.window_of(tr), trace.is_mosaic)
    assert (fwd + bwd) * 2 / 1e3 == pytest.approx(mosaic)


def test_unnamed_kernels_leave_the_flash_split_out():
    """The parent commit's kernels show as ``jvp__`` and ``shard_map``."""
    tr = trace.from_planes([Plane("/device:TPU:0", [
        Line("XLA Modules", [ev("jit_train_step(7)", 0, 50)]),
        Line("XLA Ops", [ev("%jvp__.1 = bf16[2]{0} custom-call()" + MOSAIC,
                            0, 50)])])])
    ev_ = {"kind": "train", "trace": tr, "trace_steps": 1}
    assert read("kernel.flash_fwd_ms_per_step", ev_) is None
    assert read("kernel.flash_bwd_ms_per_step", ev_) is None
    assert read("kernel.flash_fwd_ms_per_step", {"kind": "train"}) is None
