"""Tier-1 runs the rest of the benchmark's self-check (``benchmarks/tests``,
all but ``test_arch``: see ``test_benchmark_arch.py``) as it is, by import:
the public names of each module in ``MODULES`` (cases, fixtures, helpers)
are collected here, as ``from module import *`` would bring them.
"""

import importlib

import pytest

MODULES = ("test_flops", "test_model_scopes", "test_reference", "test_scopes",
           "test_serve_blocks", "test_spans", "test_spec", "test_trace",
           "test_traffic")

_OWNER = {}     # public name -> the module of MODULES that defines it
for _name in MODULES:
    pytest.register_assert_rewrite(f"benchmarks.tests.{_name}")
    _mod = importlib.import_module(f"benchmarks.tests.{_name}")
    for _attr, _obj in vars(_mod).items():
        if not _attr.startswith("_"):
            globals()[_attr] = _obj
            if getattr(_obj, "__module__", None) == _mod.__name__:
                _OWNER.setdefault(_attr, []).append(_name)


def test_no_case_shadows_another():
    """A case or fixture defined in two of the modules would be collected
    here once, and the other would silently not run."""
    assert {a: m for a, m in _OWNER.items() if len(m) > 1} == {}


def test_the_manifest_s_new_entries_have_readers_and_cells_that_report():
    """PR 55's case, as it can still hold. ``benchmarks/tests/
    test_model_scopes.py`` states it for the manifest PR 55 left: the
    thirteen ``model.*`` entries the *last* of ``per_layer``, five serving
    cells each. A PR that brings a cell appends its name to those lists and
    its own metrics after them (PR 59), and may edit no file under
    ``benchmarks/``, so tier-1 holds what a later manifest keeps: the
    thirteen are there, each with its reader, its source and cells of its
    kind that report what it moves; where in the list they stand is a PR's
    history, which no test holds (ISSUE 63). (A ``benchmark`` PR restates
    the case in place: ``PERF.md`` section 7.)"""
    import os

    from benchmarks.harness import spec
    from benchmarks.tests import test_model_scopes as pr55

    manifest = spec.load_manifest()
    reports = {m["name"]: set(m.get("workloads") or (
        w["name"] for w in manifest["workloads"]))
        for m in manifest["end_to_end"]}
    kinds = {w["name"]: w["traffic"].split("-")[0]
             for w in manifest["workloads"]}
    added = [m for m in manifest["per_layer"] if m["layer"] == "model"]
    assert sorted(m["name"] for m in added) == sorted(
        (*pr55._DECODE.values(), *pr55._TRAIN.values(), pr55._SHARE))
    for m in added:
        assert os.path.exists(os.path.join(
            spec.ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
        assert callable(spec.load_reader(m["name"]).read)
        assert m["source"] == "device_trace"
        assert set(m["workloads"]) <= reports[m["moves"]], m["name"]
        want = "train" if ".train_" in m["name"] else "serve"
        assert {kinds[w] for w in m["workloads"]} == {want}
        assert len(m["workloads"]) >= (2 if want == "train" else 5)


def _experts_evidence(calls, cell="kanana-2-30b-a3b.serve-long-decode",
                      kernel="grouped_swiglu"):
    """A serving cell's evidence as ``test_readers_absent`` records it, its
    trace holding ``calls`` Mosaic calls named after the experts' kernel
    (30 and 50 us the first two, inside the 1 s window) and a third the
    window cuts away, its counter of blocks run moved by four."""
    from benchmarks.harness import trace
    from benchmarks.tests import test_readers_absent as absent
    from benchmarks.tests.test_spans import EPOCH_NS, MOSAIC

    whole, filed = absent.recorded(cell)
    us = 1e3
    ops = [ev("%fusion.11 = f32[8]{0} fusion()", 10 * us, 40 * us)]
    for n, (lo, hi) in enumerate([(100, 130), (200, 250)][:calls]):
        ops.append(ev(f"%{kernel}.{n + 2} = f32[175,8,2048]{{2,1,0}} "
                      f"custom-call(s32[] %ran){MOSAIC}", lo * us, hi * us))
    if calls:
        # another kernel's call that names this one's output, and this
        # kernel's call outside the window: neither counts
        ops.append(ev("%flash_fwd.9 = bf16[8]{0} custom-call(f32[8]{0} "
                      f"%{kernel}.2){MOSAIC}", 300 * us, 320 * us))
        ops.append(ev(f"%{kernel}.4 = f32[8]{{0}} custom-call(){MOSAIC}",
                      2e6 * us, 2e6 * us + 70 * us))
    whole["trace"] = trace.from_planes([
        Plane("/device:TPU:0", [
            Line("XLA Modules", [ev("jit__decode_impl(3)", 0, 400 * us)]),
            Line("XLA Ops", ops)]),
        Plane("/host:CPU", [Line("python", [ev("bench.window", 0, 1e6 * us)])]),
        Plane("Task Environment", [], [("profile_start_time", EPOCH_NS)])])
    for counters, level in ((whole["play"].trace_open, 10),
                            (whole["play"].trace_close, 14)):
        counters["moe_blocks_run"] = level
    return whole, filed


def test_the_experts_kernel_s_reader_reads_its_calls_over_the_blocks_run(
        monkeypatch):
    """PR 60's reader, which no file of ``benchmarks/tests`` can hold whole
    (``test_readers_absent.py`` records no serving trace with a Mosaic call
    and may not be edited): two ``grouped_swiglu`` calls of 30 and 50 us in
    the window over a counter that moved by four blocks read 20 us a block;
    with anything it reads taken away, by ``test_readers_absent``'s own
    list, it reads None or what it read, and never raises; on a trace
    without such a call (the parent's program) and on a counter that did not
    move it reads None."""
    import copy

    from benchmarks.harness import spec
    from benchmarks.tests import test_readers_absent as absent

    read = spec.load_reader("kernel.grouped_swiglu_us_per_block").read
    whole, filed = _experts_evidence(2)
    value = read(copy.copy(whole))
    assert value == pytest.approx(20.0)
    for lack, take in absent.LACKS.items():
        evidence, files = take(copy.copy(whole), filed)
        absent._filing(monkeypatch, files)
        got = read(evidence)
        assert got is None or (lack not in ("nothing", "no-trace")
                               and isinstance(got, float)), lack
        if lack in ("no-trace", "no-counter-field", "no-traced-counters",
                    "no-generator-s-records", "nothing",
                    "no-run-of-the-program"):   # another trace, no call
            assert got is None, lack
        else:
            assert got == value, lack
    assert read(_experts_evidence(0)[0]) is None        # the parent's trace
    still = _experts_evidence(2)[0]
    still["play"].trace_close["moe_blocks_run"] = 10
    assert read(still) is None
    # entered in the manifest for the two cells that run routed experts
    entry = [m for m in spec.load_manifest()["per_layer"]
             if m["name"] == "kernel.grouped_swiglu_us_per_block"]
    assert [(m["layer"], m["source"], m["better"], m["moves"]) for m in entry
            ] == [("kernel", "device_trace", "lower", "itl_p50_ms")]
    assert entry[0]["workloads"] == [
        "kanana-2-30b-a3b.serve-long-decode", "laguna-xs.2.serve-long-decode"]


@pytest.mark.parametrize("kernel", ["grouped_reglu", "grouped_swiglu"])
def test_the_grouped_kernel_s_roofline_reads_its_calls_under_either_name(
        monkeypatch, kernel):
    """PR 61's Mosaic reader, held here for PR 60's reason: two calls of the
    experts' kernel of 30 and 50 us in the window, under the name of either
    gate activation, over counters that moved by 24 routed rows through 4
    experts. The bound is the bytes: four experts of 3 x 2560 x 768 in
    bfloat16 at the table's HBM peak, 57.6 us of the 80. With anything it
    reads taken away it reads None or what it read and never raises; without
    a call, without the counter (the parent's program) and under a
    configuration that publishes other keys for its widths it reads None."""
    import copy

    from benchmarks.harness import device, spec
    from benchmarks.tests import test_readers_absent as absent

    cell = "smallthinker-21b-a3b.serve-past-window"
    reader = spec.load_reader("kernel.grouped_glu_roofline")

    def evidence(calls=2, rows=24, runs=4, cell=cell):
        whole, filed = _experts_evidence(calls, cell, kernel)
        for counters, level in ((whole["play"].trace_open, 0),
                                (whole["play"].trace_close, 1)):
            counters["moe_routed_rows"] = 1000 + level * rows
            counters["moe_expert_runs"] = 500 + level * runs
        return whole, filed

    whole, filed = evidence()
    value = reader.read(copy.copy(whole))
    peaks = device.peaks_for("TPU v5 lite")
    weights = 4 * 3 * 2560 * 768 * 2
    assert weights / peaks["hbm_bytes_s"] > 24 * 6 * 2560 * 768 / peaks["flops"]
    assert value == pytest.approx(100 * weights / peaks["hbm_bytes_s"] / 80e-6)
    assert 70 < value < 75
    # many rows through the same four experts: bound by the operations
    busy = reader.read(evidence(rows=40_000)[0])
    assert busy == pytest.approx(
        100 * 40_000 * 6 * 2560 * 768 / peaks["flops"] / 80e-6)
    for lack, take in absent.LACKS.items():
        got_evidence, files = take(copy.copy(whole), filed)
        absent._filing(monkeypatch, files)
        got = reader.read(got_evidence)
        assert got is None or got == value, lack
        if lack in ("no-trace", "no-counter-field", "no-traced-counters",
                    "nothing"):
            assert got is None, lack
    assert reader.read(evidence(calls=0)[0]) is None    # an XLA loop ran them
    assert reader.read(evidence(runs=0)[0]) is None     # no block ran
    parent = evidence()[0]["play"]      # its summary has no such field
    parent.trace_open, parent.trace_close = (
        {k: v for k, v in c.items() if k != "moe_expert_runs"}
        for c in (parent.trace_open, parent.trace_close))
    assert reader.read(dict(whole, play=parent)) is None
    # kanana's configuration publishes ``moe_intermediate_size``
    assert reader.read(evidence(
        cell="kanana-2-30b-a3b.serve-long-decode")[0]) is None
    entry = [m for m in spec.load_manifest()["per_layer"]
             if m["name"] == "kernel.grouped_glu_roofline"]
    assert [(m["unit"], m["layer"], m["source"], m["better"], m["moves"],
             m["workloads"]) for m in entry] == [
        ("%", "kernel", "device_trace", "higher", "itl_p50_ms", [cell])]


def test_the_walk_s_kernel_s_roofline_reads_its_calls_over_the_rows_read(
        monkeypatch):
    """PR 62's Mosaic reader, held here for PR 60's reason: two
    ``rows_attend`` calls of 30 and 50 us in the window over counters that
    moved by 8,192 rows of the full layers, a plane (4,096 B a row, both
    planes) and 12,288 rows of the rings, all three (2,048 B a row of one):
    58.7 MB at the table's HBM peak, 71.7 us of the 80. A stack without rings reads the full layers'
    rows alone. With anything it reads taken away it reads None or what it
    read and never raises; without a call (the XLA walk: the parent's
    program), without a gauge, and where no row was read it reads None."""
    import copy

    from benchmarks.harness import device, spec
    from benchmarks.tests import test_readers_absent as absent

    cell = "smallthinker-21b-a3b.serve-past-window"
    reader = spec.load_reader("kernel.rows_attend_roofline")

    def evidence(calls=2, rows=8192, ring=12288, cell=cell, **gauges):
        whole, filed = _experts_evidence(calls, cell, "rows_attend")
        for counters, level in ((whole["play"].trace_open, 0),
                                (whole["play"].trace_close, 1)):
            counters.update(dict(
                decode_rows_read=1000 + level * rows,
                ring_rows_read=None if ring is None else 500 + level * ring,
                kv_bytes_per_row=4096,
                ring_bytes_per_slot=None if ring is None else 6144 * 4096,
                ring_rows_per_slot=None if ring is None else 4096,
                ring_planes=None if ring is None else 3), **gauges)
        return whole, filed

    whole, filed = evidence()
    value = reader.read(copy.copy(whole))
    peak = device.peaks_for("TPU v5 lite")["hbm_bytes_s"]
    assert value == pytest.approx(
        100 * (8192 * 4096 + 12288 * 2048) / peak / 80e-6)
    assert 85 < value < 95
    # a stack that keeps no ring (``summary()`` gives None for its fields)
    assert reader.read(evidence(ring=None, cell="gpt2-124m.serve-decode")[0]) \
        == pytest.approx(100 * 8192 * 4096 / peak / 80e-6)
    for lack, take in absent.LACKS.items():
        got_evidence, files = take(copy.copy(whole), filed)
        absent._filing(monkeypatch, files)
        got = reader.read(got_evidence)
        assert got is None or got == value, lack
        if lack in ("no-trace", "no-counter-field", "no-traced-counters",
                    "no-generator-s-records", "nothing",
                    "no-run-of-the-program"):
            assert got is None, lack
    assert reader.read(evidence(calls=0)[0]) is None    # the XLA walk ran
    assert reader.read(evidence(rows=0, ring=0)[0]) is None
    assert reader.read(evidence(kv_bytes_per_row=None)[0]) is None
    assert reader.read(evidence(ring_rows_per_slot=None)[0]) is None
    assert reader.read(evidence(ring_planes=None)[0]) is None   # the parent's
    entry = [m for m in spec.load_manifest()["per_layer"]
             if m["name"] == "kernel.rows_attend_roofline"]
    assert [(m["unit"], m["layer"], m["source"], m["better"], m["moves"])
            for m in entry] == [
        ("%", "kernel", "device_trace", "higher", "itl_p50_ms")]
    assert entry[0]["workloads"] == [
        "gpt2-124m.serve-decode", "laguna-xs.2.serve-long-decode", cell]
