"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``. Everything that belongs to it sits in
files of its own, so a later PR adds files and entries and edits nothing:

  benchmarks/configs/<configuration>.json   sizes as run, source, program map
  benchmarks/mixes/<traffic>.json           the traffic or the job
  benchmarks/cells/<cell>.json              what was found once on the chip
  benchmarks/layer_metrics/<metric>.py      one reader per per-layer metric
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
#: traces and other leavings of a run; listed in .gitignore
WORK = os.path.join(ROOT, ".bench_work")


class SpecError(ValueError):
    """The manifest or one of the cell's files does not hold together."""


def _load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise SpecError(f"{path} is not a JSON object")
    return doc


def load_manifest() -> dict:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]      # benchmarks/configs/<configuration>.json
    mix: Dict[str, Any]         # benchmarks/mixes/<traffic>.json
    found: Dict[str, Any]       # benchmarks/cells/<cell>.json
    end_to_end: List[dict]      # the manifest's entries that apply here
    per_layer: List[dict]

    @property
    def kind(self) -> str:
        return self.mix["kind"]


def load_cell(name: str, override: Dict[str, Any] | None = None) -> Cell:
    """``override`` replaces keys of the cell's own file: the searches that
    fix a rate, a slot count or a layout use it, the driver never does."""
    manifest = load_manifest()
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if not entries:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise SpecError(f"no workload {name!r} in BENCHMARK.json ({known})")
    entry = entries[0]
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == entry["config"])
    config = _load_json(os.path.join(ROOT, cfg_entry["file"]))
    mix = _load_json(os.path.join(BENCH, "mixes", entry["traffic"] + ".json"))
    found = _load_json(os.path.join(BENCH, "cells", name + ".json"))
    found.update(override or {})
    if mix.get("kind") not in ("train", "serve"):
        raise SpecError(f"mix {entry['traffic']!r}: kind must be train or serve")
    return Cell(
        name=name, chips=int(entry["chips"]), config=config, mix=mix,
        found=found,
        end_to_end=[m for m in manifest["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _applies(m, name)],
    )


def load_reader(metric_name: str):
    """The module ``benchmarks/layer_metrics/<metric>.py``; it has ``read``."""
    path = os.path.join(BENCH, "layer_metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric_name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reference(config: Dict[str, Any]):
    """The plain reference a configuration names, a file under benchmarks/."""
    path = os.path.join(BENCH, config["reference"])
    spec = importlib.util.spec_from_file_location(
        "reference_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def server_options(cell: Cell) -> Dict[str, Any]:
    """Keyword arguments of the program's ``InferenceServer``, as data: the
    mix's ``server`` (what the traffic asks for: prefill length and buckets,
    and any other option of the server such as ``prefill_chunk``,
    ``prefix_cache_mb``, ``kv_dtype``, ``spec_k``) under the cell's ``server``
    (what was found once on the chip: ``n_slots``). They pass through
    unread, as a training mix's ``optimizer`` does to ``OptimizerConfig``."""
    return {**cell.mix.get("server", {}), **cell.found.get("server", {})}


def gpt_config(cell: Cell, *, training: bool):
    """The program's ``GPTConfig`` for this cell, checked against the sizes
    the configuration file publishes: ``program.key_map`` says which program
    field each published key lands in, and a disagreement is an error."""
    from mingpt_distributed_tpu.config import GPTConfig

    program = cell.config["program"]
    kwargs = dict(program["gpt_config"])
    for key in ("embd_pdrop", "resid_pdrop", "attn_pdrop"):
        # the published training rates; serving runs every dropout at 0
        kwargs[key] = float(cell.config[key]) if training else 0.0
    if training:
        kwargs["remat"] = bool(cell.found.get("remat", False))
    cfg = GPTConfig.make(**kwargs)
    for published, field in program["key_map"].items():
        if getattr(cfg, field) != cell.config[published]:
            raise SpecError(
                f"{cell.name}: the program runs {field}="
                f"{getattr(cfg, field)!r} but the configuration publishes "
                f"{published}={cell.config[published]!r}")
    return cfg
