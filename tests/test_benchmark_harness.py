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
