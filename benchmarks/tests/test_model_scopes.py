"""The ``model`` layer's readers (PR 55) on planes and tables with known
answers: a decode step and a training step whose every scope runs for a
stated time; the two sum identities; None, never an exception, on evidence
shaped like the parent's; and the manifest's new entries."""

import os

import pytest

from benchmarks.harness import model_scopes, scopes, spec, trace
from benchmarks.tests.test_trace import Line, Plane, ev
from mingpt_distributed_tpu.telemetry import programs

_DECODE = {m: "model.decode_%s_ms_per_step" % m
           for m in ("proj", "ffn", "norm", "head", "embed")}
_TRAIN = {m: "model.train_%s_ms_per_step" % m
          for m in ("proj", "attn", "ffn", "norm", "ce", "optimizer",
                    "unscoped")}
_SHARE = "model.train_scope_join_share"
_OLD_TIMES = ("attention.decode_ms_per_step", "moe.experts_ms_per_step",
              "kv.row_write_ms_per_step", "engine.sample_ms_per_step",
              "engine.unscoped_ms_per_step")

# instruction -> (scope, ms a run); ``None``: no table has it
_DECODE_OPS = {
    "fusion.1": ("qkv", 1.0), "fusion.2": ("attn_out", 0.5),
    "fusion.3": ("ffn", 2.0), "fusion.4": ("norm", 0.25),
    "fusion.5": ("head", 0.75), "gather.6": ("embed", 0.125),
    "fusion.7": ("cached_attn", 1.5), "fusion.8": ("kv_layout", 0.5),
    "fusion.9": ("sample", 0.0625), "copy.10": ("", 0.25),
    "fusion.11": ("moe_experts", 1.0), "fusion.12": ("exit_gate", 0.03125),
}
_TRAIN_OPS = {
    "fusion.1": ("qkv", 30.0), "fusion.2": ("attn_out", 10.0),
    "custom-call.3": ("attn", 50.0), "add.13": ("attn", 20.0),
    "fusion.4": ("ffn", 80.0), "fusion.5": ("mlp", 2.0),
    "fusion.6": ("norm", 12.0), "fusion.7": ("ce", 70.0),
    "fusion.8": ("optimizer", 3.5), "gather.9": ("embed", 4.0),
    "copy.10": ("", 6.5), "fusion.99": (None, 1.0),
}


def _table(ops):
    return {name: scope for name, (scope, _) in ops.items()
            if scope is not None}


def _record(name, table, **kw):
    return dict({"kind": "program", "name": name, "ts": 1.0, "family": "f",
                 "variant": "", "scopes": table,
                 "lowered_scopes": sorted(set(table.values()) - {""})}, **kw)


def _evidence(jit_name, ops, records=None, kind="serve"):
    """Two runs of the program inside a 1 s window, each operation once a
    run back to back, and a third run the window cuts."""
    ms = 1e6
    modules, events = [], []
    for start in (10.0, 400.0, 995.0):
        at = start
        for name, (_, dur) in ops.items():
            events.append(ev("%" + name + " = f32[8]{0} fusion()",
                             at * ms, (at + dur) * ms))
            at += dur
        modules.append(ev(jit_name + "(3)", start * ms, at * ms))
    tr = trace.from_planes([
        Plane("/device:TPU:0", [Line("XLA Modules", modules),
                                Line("XLA Ops", events)]),
        Plane("/host:CPU", [Line("python",
                                 [ev("bench.window", 0, 1000 * ms)])])])
    out = {"kind": kind, "trace": tr}
    if records is not None:
        out["program_spans"] = records
    return out


def _serve(record=None):
    return _evidence("jit__decode_impl", _DECODE_OPS, [
        _record("jit__decode_impl", _table(_DECODE_OPS))
        if record is None else record])


@pytest.fixture
def train(monkeypatch):
    """``train(record)``: a training cell's evidence (no ``program_spans``
    of its own) in a process whose step filed ``record``."""
    def make(record="default"):
        if record == "default":
            record = _record("jit_train_step", _table(_TRAIN_OPS))
        monkeypatch.setattr(programs, "filed_records",
                            lambda: [] if record is None else [record])
        return _evidence("jit_train_step", _TRAIN_OPS, kind="train")
    return make


def _read(metric, ev_):
    return spec.load_reader(metric).read(ev_)


def test_the_decode_readers_read_their_scopes():
    ev_ = _serve()
    got = {m: _read(name, ev_) for m, name in _DECODE.items()}
    assert got == pytest.approx({"proj": 1.5, "ffn": 2.0, "norm": 0.25,
                                 "head": 0.75, "embed": 0.125})
    # the experts inside ``ffn`` keep their own row, and what no scope
    # covers is the true residue now
    assert _read("moe.experts_ms_per_step", ev_) == pytest.approx(1.0)
    assert _read("engine.unscoped_ms_per_step", ev_) == pytest.approx(0.25)


def test_a_cell_s_time_metrics_sum_to_the_decode_run_less_the_exit_gate():
    ev_ = _serve()
    times = sum(_read(m, ev_) for m in (*_OLD_TIMES, *_DECODE.values()))
    run = sum(ms for _, ms in _DECODE_OPS.values())
    assert times == pytest.approx(run - _DECODE_OPS["fusion.12"][1])
    assert _read("engine.scope_join_share", ev_) == pytest.approx(100.0)


def test_the_training_readers_read_the_table_the_step_filed(train):
    ev_ = train()
    got = {m: _read(name, ev_) for m, name in _TRAIN.items()}
    assert got == pytest.approx({
        "proj": 40.0, "attn": 70.0, "ffn": 82.0, "norm": 12.0, "ce": 70.0,
        "optimizer": 3.5, "unscoped": 10.5})
    # 288 of 289 ms on instructions the table knows
    assert _read(_SHARE, ev_) == pytest.approx(100 * 288.0 / 289.0)
    # the evidence itself is as the cell made it: the join got a copy
    assert "program_spans" not in ev_ and ev_["kind"] == "train"


def test_the_training_times_sum_to_the_join_s_total_of_a_step(train):
    ev_ = train()
    times = sum(_read(name, ev_) for name in _TRAIN.values())
    got = scopes.ms_by_scope(model_scopes.train_evidence(ev_), "train_step")
    assert got["runs"] == 2
    assert times == pytest.approx(
        got["unscoped_ms"] + sum(got["by_scope"].values()))
    assert times == pytest.approx(288.0)


def test_the_filed_records_are_read_once_a_run(train, monkeypatch):
    ev_ = train()
    calls = []
    made = programs.filed_records()
    monkeypatch.setattr(programs, "filed_records",
                        lambda: calls.append(1) or made)
    for name in (*_TRAIN.values(), _SHARE):
        assert _read(name, ev_) is not None
    assert calls == [1]


@pytest.mark.parametrize("metric", sorted(_DECODE.values()))
def test_on_the_parent_s_evidence_a_decode_reader_reads_none(metric):
    """Traced runs use this benchmark on the parent's program too: a PR 34
    table (no mark of the layer's, no ``lowered_scopes``), no record, no
    trace, a window without a run, a stale table."""
    pr34 = {"fusion.7": "cached_attn", "fusion.8": "kv_layout",
            "fusion.9": "sample", **{k: "" for k in (
                "fusion.1", "fusion.2", "fusion.3", "fusion.4", "fusion.5",
                "gather.6", "copy.10")}}
    parent = dict(kind="program", name="jit__decode_impl", ts=1.0,
                  family="decode", variant="", scopes=pr34)
    assert _read(metric, _serve(parent)) is None
    # the parent's own readers still read it
    assert _read("engine.unscoped_ms_per_step", _serve(parent)) is not None
    # a table with ``lowered_scopes`` but none of this reader's scopes in it
    assert _read(metric, _serve(_record("jit__decode_impl", pr34))) is None
    assert _read(metric, _evidence("jit__decode_impl", _DECODE_OPS, [])) is None
    assert _read(metric, {"kind": "serve"}) is None
    assert _read(metric, dict(_serve(), trace=None)) is None
    assert _read(metric, _serve(_record(
        "jit__prefill_impl", _table(_DECODE_OPS)))) is None
    no_run = _serve()
    dev = no_run["trace"].devices[0]
    dev.modules[:] = [m for m in dev.modules if "decode" not in m.name]
    assert _read(metric, no_run) is None
    stale = _record("jit__decode_impl", _table(_DECODE_OPS),
                    stale_scopes=["ffn", "qkv"])
    assert _read(metric, _serve(stale)) is None


@pytest.mark.parametrize("metric", sorted((*_TRAIN.values(), _SHARE)))
def test_on_the_parent_s_program_a_training_reader_reads_none(
        metric, train, monkeypatch):
    """No ``filed_records`` (the parent's ``telemetry.programs``), nothing
    filed, a table from before the marks, a stale table, no trace, a window
    without a step, a maker that raises: None, and never an exception."""
    assert _read(metric, train(None)) is None
    pr34 = {name: scope if scope in ("attn", "mlp", "ce", "optimizer") else ""
            for name, scope in _table(_TRAIN_OPS).items()}
    assert _read(metric, train(dict(
        kind="program", name="jit_train_step", ts=1.0, family="train_step",
        variant="dense", scopes=pr34))) is None
    assert _read(metric, train(_record(
        "jit_train_step", _table(_TRAIN_OPS), stale_scopes=["qkv"]))) is None
    assert _read(metric, dict(train(), trace=None)) is None
    no_run = train()
    dev = no_run["trace"].devices[0]
    dev.modules[:] = []
    assert _read(metric, no_run) is None

    def broken():
        raise RuntimeError("the lowering is gone")
    ev_ = train()
    monkeypatch.setattr(programs, "filed_records", broken)
    assert _read(metric, ev_) is None
    ev_ = train()
    monkeypatch.delattr(programs, "filed_records")
    assert _read(metric, ev_) is None


def test_the_manifest_s_new_entries_have_readers_and_cells_that_report():
    manifest = spec.load_manifest()
    reports = {m["name"]: set(m.get("workloads") or (
        w["name"] for w in manifest["workloads"]))
        for m in manifest["end_to_end"]}
    kinds = {w["name"]: w["traffic"].split("-")[0]
             for w in manifest["workloads"]}
    added = [m for m in manifest["per_layer"] if m["layer"] == "model"]
    assert sorted(m["name"] for m in added) == sorted(
        (*_DECODE.values(), *_TRAIN.values(), _SHARE))
    assert manifest["per_layer"][-len(added):] == added     # appended
    for m in added:
        assert os.path.exists(os.path.join(
            spec.ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
        assert callable(spec.load_reader(m["name"]).read)
        assert m["source"] == "device_trace"
        assert set(m["workloads"]) <= reports[m["moves"]], m["name"]
        want = "train" if ".train_" in m["name"] else "serve"
        assert {kinds[w] for w in m["workloads"]} == {want}
        assert len(m["workloads"]) == (2 if want == "train" else 5)
