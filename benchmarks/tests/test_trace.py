"""The trace reduction on a synthetic trace with known answers. The objects
are shaped like ``jax.profiler.ProfileData``: planes with a name and lines,
lines with a name and events, events with name, start_ns, duration_ns and
stats."""

import dataclasses
from typing import List

import pytest

from benchmarks.harness import trace


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Line:
    name: str
    events: List[Ev]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]
    stats: list = dataclasses.field(default_factory=list)


def ev(name, lo, hi, **stats):
    return Ev(name, float(lo), float(hi - lo), list(stats.items()))


def device_plane(index, shift=0):
    """One chip's timeline. Busy: [0,150) and [200,400); idle: [150,200) and
    [400,500). ``shift`` moves the second fusion, for the second chip."""
    ops = [
        ev("%fusion.1", 0, 100),
        ev('%jvp__.1 = bf16[2,8]{1,0} custom-call(bf16[2,8]{1,0} %fusion.1), '
           'custom_call_target="tpu_custom_call"', 100, 150),
        ev("%all-gather-start.1", 200, 210),
        # a consumer names the kernel among its operands: not a kernel itself
        ev("%fusion.2 = bf16[2,8]{1,0} fusion(bf16[2,8]{1,0} %pallas_call.1)",
           205 + shift, 260),                       # hides 5 of the start
        ev("%all-gather-done.1", 260, 300),         # nothing hides it
        ev("%while.1", 300, 400),                   # a container
        ev("%fusion.3", 310, 350),
        ev("%fusion.4", 360, 400),
    ]
    modules = [ev("jit_train_step(7)", 0, 150), ev("jit_train_step(7)", 200, 400),
               ev("jit__unknown(9)", 410, 420), ev("jit__fold_in(3)", 425, 426),
               ev("jit__unknown(8)", 430, 460),
               # the device's clock ahead of the host's: "before" its call
               ev("jit__unknown(9)", 470, 480)]
    return Plane(f"/device:TPU:{index}",
                 [Line("XLA Modules", modules), Line("XLA Ops", ops),
                  Line("Steps", [ev("0", 0, 400)])])


def host_plane():
    return Plane("/host:CPU", [Line("python", [
        ev("bench.window", 0, 500),
        ev("bench.round", 0, 250),
        ev("bench.data", 140, 210),
        ev("bench.inner", 150, 170),
        ev("bench.wait", 380, 500),
        ev("PjitFunction(train_step)", 0, 10),      # JAX's own, not ours
        ev("PjitFunction(_decode_impl)", 410.02, 413),  # clocks: 20 ps "late"
        ev("PjitFunction(_fold_in)", 421, 422),
        ev("PjitFunction(_prefill_impl)", 428, 429),
        ev("PjitFunction(_prefill_impl)", 428, 428.5),  # the inner layer's
        ev("PjitFunction(_decode_impl)", 472, 473),
        ev("PjRtCpuExecutable::Execute", 0, 10),
    ])])


@pytest.fixture
def tr(monkeypatch):
    # the synthetic timeline is a few hundred ns long: scale the slack between
    # the host's and the device's clocks down with it
    monkeypatch.setattr(trace, "CLOCK_SLACK_NS", 0.1)
    monkeypatch.setattr(trace, "MAX_CLOCK_SKEW_NS", 5.0)
    return trace.from_planes([
        device_plane(0), device_plane(1, shift=5), host_plane(),
        Plane("Task Environment", [], [("profile_start_time", 1_000_000_000)]),
        Plane("/device:TPU:0 SparseCore 0", []),    # not a chip's own plane
    ])


def test_planes_are_sorted_into_devices_annotations_and_epoch(tr):
    assert sorted(tr.devices) == [0, 1]
    assert [e.name for e in tr.annotations][:2] == ["bench.window", "bench.round"]
    assert all(e.name.startswith("bench.") for e in tr.annotations)
    assert tr.start_unix_ns == 1_000_000_000
    assert trace.window_of(tr) == (0.0, 500.0)


def test_busy_is_the_union_and_containers_do_not_count_twice(tr):
    window = trace.window_of(tr)
    assert trace.busy(tr.devices[0], window) == [(0.0, 150.0), (200.0, 400.0)]
    assert trace.busy_s(tr, window) == pytest.approx(350e-9)
    # by class: the while's 100 ns are its body's 80, not 180
    compute = trace.class_s(tr, window, trace.is_compute)
    assert compute == pytest.approx((100 + 50 + 55 + 40 + 40 - 2.5) * 1e-9)


def test_mosaic_calls_are_found_by_their_own_attribute(tr):
    window = trace.window_of(tr)
    assert trace.class_s(tr, window, trace.is_mosaic) == pytest.approx(50e-9)
    assert [e.base for e in tr.devices[0].ops][:3] == [
        "fusion", "jvp__", "all-gather"]


def test_exposed_collective_time_is_what_no_compute_hides(tr):
    window = trace.window_of(tr)
    # chip 0: [200,205) of the start and all of the done; chip 1: the whole
    # start (its fusion begins at 210) and all of the done
    assert trace.exposed_collective_s(tr, window) == pytest.approx(
        ((5 + 40) + (10 + 40)) / 2 * 1e-9)


def test_program_runs_are_found_by_jit_name_inside_the_window(tr):
    window = trace.window_of(tr)
    assert trace.program_runs(tr, window, "train_step") == pytest.approx(
        [150e-9, 200e-9])
    assert trace.program_runs(tr, (0.0, 405.0), "decode_impl") == []


def test_unnamed_programs_take_the_name_of_the_call_that_launched_them(tr):
    assert trace.program_names(tr, tr.devices[0]) == [
        "jit_train_step", "jit_train_step", "jit__decode_impl", "jit__fold_in",
        "jit__prefill_impl", "jit__decode_impl"]
    window = trace.window_of(tr)
    assert trace.program_runs(tr, window, "decode_impl") == pytest.approx(
        [10e-9, 10e-9])
    assert trace.program_runs(tr, window, "prefill_impl") == pytest.approx([30e-9])


def test_idle_gaps_are_named_after_the_innermost_open_span(tr):
    window = trace.window_of(tr)
    gaps = dict(map(tuple, trace.idle_gaps_by_span(
        tr, window, trace.annotation_spans(tr))))
    assert gaps == pytest.approx(
        {"wait": 100e-9, "data": 30e-9, "inner": 20e-9})
    # without spans every gap is unnamed
    assert trace.idle_gaps_by_span(tr, window, []) == [["(no span)", 150e-9]]


def test_host_time_of_a_round_is_its_length_minus_device_busy(tr):
    assert trace.host_s_per_round(tr, trace.window_of(tr)) == pytest.approx(
        [50e-9])


def test_top_device_ops_average_over_devices_and_mark_mosaic(tr):
    top = dict(map(tuple, trace.top_device_ops(tr, trace.window_of(tr))))
    assert top["mosaic:jvp__"] == pytest.approx(50e-9)
    assert top["all-gather"] == pytest.approx(50e-9)
    assert "while" not in top


def test_program_spans_are_used_only_where_they_fall_inside_rounds(tr):
    good = [{"kind": "span", "name": "serve.decode_round",
             "ts": 1.0 + 20e-9, "dur_s": 100e-9}]
    assert trace.program_spans(tr, good) == [
        ("serve.decode_round", pytest.approx(20.0), pytest.approx(120.0))]
    skewed = [{"kind": "span", "name": "serve.decode_round",
               "ts": 1.0 + 200e-9, "dur_s": 100e-9}]
    assert trace.program_spans(tr, skewed) == []


@pytest.mark.parametrize("a, b, want", [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(2, 4), (6, 12)], [(0, 2), (4, 6)]),
    ([(0, 10), (20, 30)], [(5, 25)], [(0, 5), (25, 30)]),
    ([(0, 10)], [(0, 10)], []),
])
def test_subtract(a, b, want):
    assert trace.subtract(a, b) == want


def test_union_merges_touching_and_nested_intervals():
    assert trace.union([(5, 7), (0, 3), (3, 4), (1, 2), (9, 9)]) == [
        (0, 4), (5, 7)]


# ---------------------------------------------------------------------------
# a recorded trace: the first 40 ms of a GPT-2 124M training step on a
# TPU v5e (PR 22, `benchmarks/tests/record_trace.py`), cut at 40 ms
# ---------------------------------------------------------------------------


def recorded():
    import gzip
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "data",
                        "train_trace_v5e_40ms.json.gz")
    with gzip.open(path) as f:
        doc = json.load(f)
    return [Plane(p["name"],
                  [Line(l["name"], [Ev(n, t, d, [tuple(kv) for kv in st])
                                    for n, t, d, st in l["events"]])
                   for l in p["lines"]], [tuple(kv) for kv in p["stats"]])
            for p in doc]


def test_recorded_trace_is_read_as_the_chip_wrote_it():
    tr = trace.from_planes(recorded())
    dev = tr.devices[0]
    assert len(dev.ops) == 494 and len(dev.modules) == 1
    assert dev.modules[0].name.startswith("jit_train_step(")
    # on the chip an operation's name is its whole HLO instruction
    assert all(e.name.startswith("%") and " = " in e.name for e in dev.ops)
    flash = [e for e in dev.ops if trace.is_mosaic(e)]
    assert len(flash) == 7 and {e.base for e in flash} == {"jvp__"}
    assert all(" custom-call(" in e.name for e in flash)
    # their consumers name them among their operands and are not kernels
    assert any("%pallas_call" in e.name and not trace.is_mosaic(e)
               for e in dev.ops)
    lo, hi = dev.ops[0].start_ns, dev.ops[0].start_ns + 40e6
    busy = trace.total(trace.busy(dev, (lo, hi)))
    assert 0.99 < busy / (hi - lo) <= 1.0
    assert trace.class_s(tr, (lo, hi), trace.is_mosaic) == pytest.approx(
        sum(e.dur_ns for e in flash) / 1e9)
    assert trace.window_of(tr)[0] == pytest.approx(50.54e6, rel=1e-3)
    assert [name for name, _, _ in trace.annotation_spans(tr)][:3] == [
        "window", "data", "dispatch"]
