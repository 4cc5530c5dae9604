"""The benchmark's cell ``kanana-2-30b-a3b.serve-long-decode`` at a tiny size
on the CPU, through the path the driver runs: ``rehearse.tiny`` +
``serve_cell.Driver`` + ``check.serve_verdict`` with the configuration's own
reference, in bfloat16 with the routes followed (as
``benchmarks/tests/test_arch.py`` does for the fixture), and the new
per-layer readers on the run's evidence."""

import dataclasses
import json
import types

import jax
import numpy as np
import pytest

import stacks
from benchmarks import rehearse
from benchmarks.harness import check, serve_cell, spec
from stack_contract import (  # noqa: F401
    cell_run, stack,
    test_the_cell_agrees_with_its_reference_through_the_whole_path)

#: the latent stack's entry, for its cell (three layers: the dense one and
#: two that route, ``STACK.cell_sizes``) and the cell's own assertions
STACK = dataclasses.replace(stacks.LATENT, seed=2_500_000_001)
NEW_READERS = ("moe.load_max_over_mean", "moe.dropped_rows",
               "moe.rows_per_expert_round", "kv.bytes_per_live_token")


def test_the_configuration_holds_the_published_widths():
    cell = spec.load_cell(STACK.cell)
    config = cell.config
    assert config["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert (config["hidden_size"], config["kv_lora_rank"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["n_routed_experts"], config["num_experts_per_tok"],
            config["vocab_size"]) == (2048, 512, 6144, 768, 128, 6, 128256)
    cfg = spec.gpt_config(cell, training=False)
    assert cfg.param_dtype == cfg.dtype == "bfloat16"
    assert spec.server_options(cell) == {
        "prefill_len": 4096, "prefill_buckets": [1024, 2048, 4096],
        "n_slots": cell.found["server"]["n_slots"]}
    wrong = dataclasses.replace(cell, config=dict(config, kv_lora_rank=256))
    with pytest.raises(spec.SpecError, match="kv_lora_rank"):
        spec.gpt_config(wrong, training=False)


def test_tiny_shrinks_every_size_the_reference_reads():
    cell = stacks.tiny_cell(STACK)
    cfg = spec.gpt_config(cell, training=False)
    assert (cfg.n_layer, cfg.n_dense_layers, cfg.kv_lora_rank,
            cfg.qk_head_dim, cfg.dense_width, cfg.expert_width,
            cfg.n_experts, cfg.moe_top_k) == (2, 1, 32, 24, 192, 48, 8, 2)
    config = cell.config
    assert (config["kv_lora_rank"], config["qk_rope_head_dim"],
            config["head_dim"], config["n_routed_experts"],
            config["intermediate_size"]) == (32, 8, 8, 8, 192)


def test_the_counters_reach_the_readers(cell_run):
    play = cell_run["evidence"]["play"]
    closed = play.close_counters
    assert closed["moe_dropped_rows"] == 0
    assert closed["moe_routed_rows"] > play.open_counters["moe_routed_rows"]
    assert closed["kv_bytes_per_row"] == 3 * (8 + 32) * 2
    assert closed["program_weights_cast"] == 0
    # untraced: the readers find nothing and say so
    for name in NEW_READERS:
        assert spec.load_reader(name).read(cell_run["evidence"]) is None
    # a traced window's two readings
    play.trace_open, play.trace_close = play.open_counters, closed
    play.trace_rounds, play.trace_live_rows = 10, 400
    values = {name: spec.load_reader(name).read(cell_run["evidence"])
              for name in NEW_READERS}
    assert values["moe.dropped_rows"] == 0
    assert values["moe.load_max_over_mean"] >= 1.0
    assert values["moe.rows_per_expert_round"] > 0
    assert values["kv.bytes_per_live_token"] == pytest.approx(
        240 * play.n_slots * play.block_size / 40)


def test_the_read_row_share_reader_reads_the_scheduler_s_counters(cell_run):
    """``kv.read_row_share`` (PR 33): rows the decode steps of the traced
    window read over rows their slots reserve, from two readings of
    ``summary()``; nothing where the program has no such counters (the
    parent's) or the window held no decode step."""
    read = spec.load_reader("kv.read_row_share").read
    play = serve_cell.Play(n_slots=64, block_size=8192)
    play.trace_open = {"decode_rows_read": 64 * 5120 * 10,
                       "decode_rows_reserved": 64 * 8192 * 10}
    play.trace_close = {"decode_rows_read": 64 * (5120 * 10 + 4096 * 30),
                        "decode_rows_reserved": 64 * 8192 * 40}
    assert read({"play": play}) == 50.0
    play.trace_close = dict(play.trace_open)
    assert read({"play": play}) is None           # no decode step
    play.trace_open, play.trace_close = {"steps": 1}, {"steps": 9}
    assert read({"play": play}) is None           # the parent's summary
    assert read({"play": None}) is None
    # the rehearsal's tiny slots are read in one pass (a house of a few KB:
    # ``attention.step_block``): every step reads them whole
    run = cell_run["evidence"]["play"]
    opened, closed = run.open_counters, run.close_counters
    untraced = dataclasses.replace(run, trace_open=None, trace_close=None)
    assert read({"play": untraced}) is None
    assert closed["decode_rows_reserved"] > opened["decode_rows_reserved"]
    assert closed["decode_rows_read"] == closed["decode_rows_reserved"]
    traced = dataclasses.replace(run, trace_open=opened, trace_close=closed)
    assert read({"play": traced}) == 100.0
    cell = spec.load_cell(STACK.cell)
    assert "kv.read_row_share" in {m["name"] for m in cell.per_layer}
    for other in ("gpt2-124m.serve-decode", "minicpm-sala.serve-long-context"):
        assert "kv.read_row_share" not in {
            m["name"] for m in spec.load_cell(other).per_layer}


def test_the_blocks_run_share_reader_reads_the_experts_counters(cell_run):
    """``moe.blocks_run_share`` (PR 35): blocks the experts' loops of the
    traced window took through an expert over the blocks their layouts
    had, from two readings of ``summary()``; nothing where the program has
    no such counters (the parent's, a dense model's ``None``) or the window
    laid nothing out."""
    read = spec.load_reader("moe.blocks_run_share").read
    play = serve_cell.Play(n_slots=64, block_size=8192)
    play.trace_open = {"moe_blocks_run": 5 * 90 * 10,
                       "moe_blocks_laid": 5 * 175 * 10}
    play.trace_close = {"moe_blocks_run": 5 * (90 * 10 + 70 * 30),
                        "moe_blocks_laid": 5 * 175 * 40}
    assert read({"play": play}) == 40.0
    play.trace_close = dict(play.trace_open)
    assert read({"play": play}) is None           # no step in the window
    play.trace_open, play.trace_close = {"steps": 1}, {"steps": 9}
    assert read({"play": play}) is None           # the parent's summary
    play.trace_open = play.trace_close = {"moe_blocks_run": None,
                                          "moe_blocks_laid": None}
    assert read({"play": play}) is None           # a dense model's
    assert read({"play": None}) is None
    # the rehearsal's run: free lanes and padded buckets lay nothing out
    run = cell_run["evidence"]["play"]
    opened, closed = run.open_counters, run.close_counters
    untraced = dataclasses.replace(run, trace_open=None, trace_close=None)
    assert read({"play": untraced}) is None
    assert closed["moe_blocks_laid"] > opened["moe_blocks_laid"]
    traced = dataclasses.replace(run, trace_open=opened, trace_close=closed)
    assert 0.0 < read({"play": traced}) < 100.0
    cell = spec.load_cell(STACK.cell)
    assert "moe.blocks_run_share" in {m["name"] for m in cell.per_layer}
    for other in ("gpt2-124m.serve-decode", "gpt2-xl.serve-prefill",
                  "minicpm-sala.serve-long-context"):
        assert "moe.blocks_run_share" not in {
            m["name"] for m in spec.load_cell(other).per_layer}


def test_the_new_readers_return_none_for_a_dense_cell():
    """On a cell of the parent's (or the parent itself, whose summary lacks
    the fields) there is nothing to read, and no reader raises."""
    cell = rehearse.tiny(spec.load_cell("gpt2-124m.serve-decode"))
    driver = serve_cell.Driver(cell, STACK.seed, traced=False)
    reading = driver._counters()
    play = serve_cell.Play(n_slots=4, block_size=128, trace_rounds=3,
                           trace_live_rows=30, trace_open=reading,
                           trace_close=reading)
    evidence = {"play": play, "cell": cell}
    for name in NEW_READERS[:3]:
        assert spec.load_reader(name).read(evidence) is None
    stripped = {k: v for k, v in reading.items()
                if not k.startswith(("moe_", "kv_bytes"))}
    play.trace_open = play.trace_close = stripped
    for name in NEW_READERS:
        assert spec.load_reader(name).read(evidence) is None


def test_a_lower_precision_fails_the_verdict():
    """The nearest precision below the one the configuration states: the
    reference's own cached rows rounded to 8-bit floats before they are
    compared (what an fp8 pool would hold) lie outside the law."""
    cell = stacks.tiny_cell(STACK, n_layer=3)
    reference = spec.load_reference(cell.config)
    driver = serve_cell.Driver(cell, STACK.seed, traced=False)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 384, size=n, dtype=np.int32) for n in (24, 40)]
    good = check.serve_verdict(reference, cell.config, driver.server,
                               prompts, 4)
    assert good["ok"], good
    pool = driver.server.engine.pool
    fp8 = jax.numpy.float8_e4m3fn

    class Rounding:
        """The engine, but every program's rows pass through 8 bits."""
        def __init__(self, engine):
            self._engine = engine

        def __getattr__(self, name):
            return getattr(self._engine, name)

        def _round(self):
            pool.cache = {
                n: a if a.ndim != 5 else a.astype(fp8).astype(a.dtype)
                for n, a in pool.cache.items()}

        def prefill_chunk_call(self, *args):
            out = self._engine.prefill_chunk_call(*args)
            self._round()
            return out

        def decode_step(self, *args):
            out = self._engine.decode_step(*args)
            self._round()
            return out

    bad = check.serve_verdict(
        reference, cell.config,
        types.SimpleNamespace(engine=Rounding(driver.server.engine)),
        prompts, 4)
    assert bad["ok"] is False
    worst = max(max(c["k_rel"], c["v_rel"]) for c in bad["cases"])
    assert worst > 2 * bad["kv_rel_tol"]


def test_rehearse_runs_the_cell_and_prints_its_routes(capsys):
    rehearse.rehearse_run(spec.load_cell(STACK.cell))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["agrees_with_reference"] is True and line["failed"] == 0
    assert line["compiled_in_window"] == 0
    assert {line["readers"][name] for name in NEW_READERS} == {"read"}
