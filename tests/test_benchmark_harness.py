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
    thirteen stand together in PR 55's order, each with its reader, its
    source and cells of its kind that report what it moves. (A ``benchmark``
    PR restates the case in place: ``PERF.md`` section 7.)"""
    import os

    from benchmarks.harness import spec
    from benchmarks.tests import test_model_scopes as pr55

    manifest = spec.load_manifest()
    reports = {m["name"]: set(m.get("workloads") or (
        w["name"] for w in manifest["workloads"]))
        for m in manifest["end_to_end"]}
    kinds = {w["name"]: w["traffic"].split("-")[0]
             for w in manifest["workloads"]}
    at = [i for i, m in enumerate(manifest["per_layer"])
          if m["layer"] == "model"]
    added = [manifest["per_layer"][i] for i in at]
    assert sorted(m["name"] for m in added) == sorted(
        (*pr55._DECODE.values(), *pr55._TRAIN.values(), pr55._SHARE))
    assert at == list(range(at[0], at[0] + len(at)))    # appended together
    for m in added:
        assert os.path.exists(os.path.join(
            spec.ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
        assert callable(spec.load_reader(m["name"]).read)
        assert m["source"] == "device_trace"
        assert set(m["workloads"]) <= reports[m["moves"]], m["name"]
        want = "train" if ".train_" in m["name"] else "serve"
        assert {kinds[w] for w in m["workloads"]} == {want}
        assert len(m["workloads"]) >= (2 if want == "train" else 5)
