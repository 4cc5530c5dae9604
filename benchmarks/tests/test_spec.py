"""The manifest and the files it names hold together."""

import os

import pytest

from benchmarks.harness import spec

MANIFEST = spec.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_manifest_has_exactly_the_contract_keys():
    assert sorted(MANIFEST) == sorted([
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"])
    assert MANIFEST["command"][-1] == "benchmarks/run.py"
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    assert all(0.01 <= m["bound"] <= 0.1 for m in MANIFEST["end_to_end"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_and_its_metrics_fit_the_contract(name):
    cell = spec.load_cell(name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])
        assert callable(spec.load_reader(m["name"]).read)
    assert hasattr(spec.load_reference(cell.config), "hidden")


@pytest.mark.parametrize("name", CELLS)
def test_the_program_runs_the_published_sizes(name):
    cell = spec.load_cell(name)
    cfg = spec.gpt_config(cell, training=cell.kind == "train")
    key_map = cell.config["program"]["key_map"]
    # depth, heads and width are mapped whatever the source calls them
    assert {"n_layer", "n_head", "n_embd"} <= set(key_map.values())
    for published, field in key_map.items():
        assert getattr(cfg, field) == cell.config[published], published
    assert cfg.attn_pdrop == 0.0
    assert (cfg.resid_pdrop > 0) == (cell.kind == "train")


def test_a_wrong_size_is_refused():
    cell = spec.load_cell(CELLS[0])
    cell.config["n_layer"] += 1
    with pytest.raises(spec.SpecError):
        spec.gpt_config(cell, training=True)


def test_every_reader_file_is_named_in_the_manifest():
    named = {m["name"] + ".py" for m in MANIFEST["per_layer"]}
    assert set(os.listdir(os.path.join(spec.BENCH, "layer_metrics"))) - {
        "__pycache__"} == named


def test_memory_peak_is_resident_buffers_plus_the_largest_reservation():
    from benchmarks.harness import device

    # as logged on the v5e under gpt2-xl.serve-prefill at 6 slots (PR 22):
    # the two high-water marks add up to more than the chip offers
    chip = {"bytes_in_use": 8_519_000_000, "peak_bytes_in_use": 9_554_000_000,
            "peak_bytes_reserved": 7_853_000_000, "bytes_limit": 16_909_000_000}
    emptier = dict(chip, bytes_in_use=1_000_000_000)
    m = device.memory([emptier, chip])
    assert m["memory_peak_bytes"] == 8_519_000_000 + 7_853_000_000
    assert m["memory_peak_bytes"] <= m["bytes_limit"]
    assert m["peak_bytes_in_use"] == 9_554_000_000
    with pytest.raises(KeyError):
        device.memory([{"peak_bytes_in_use": 1, "bytes_limit": 2}])


def test_server_options_are_the_mix_s_under_the_cell_s():
    cell = spec.load_cell("gpt2-124m.serve-decode")
    assert spec.server_options(cell) == {
        "prefill_len": 256, "prefill_buckets": [64, 128, 256], "n_slots": 64}
    wider = spec.load_cell("gpt2-124m.serve-decode",
                           {"server": {"n_slots": 96, "kv_dtype": "int8"}})
    assert spec.server_options(wider)["kv_dtype"] == "int8"
