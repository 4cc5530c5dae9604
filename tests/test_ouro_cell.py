"""The benchmark's cell ``ouro-2.6b.serve-looped-decode`` at a tiny size on
the CPU, through the path the driver runs: ``rehearse.tiny`` +
``serve_cell.Driver`` + ``check.serve_verdict`` with the configuration's own
reference, in bfloat16; the cell's two readers on the run's evidence; and
negative controls the verdict refuses, each a case."""

import dataclasses
import json
import types

import jax
import numpy as np
import pytest

import stacks
from benchmarks import rehearse
from benchmarks.harness import check, serve_cell, spec
from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.serving import InferenceServer
from stack_contract import (  # noqa: F401
    cell_run, stack,
    test_the_cell_agrees_with_its_reference_through_the_whole_path)

STACK = stacks.OURO
NEW_READERS = ("loop.passes_per_token", "engine.decode_hbm_roofline")
# the tiny cell's
PASSES, LAYERS = stacks.OURO_CELL_PASSES, stacks.OURO_CELL_LAYERS


def test_the_configuration_holds_every_published_key():
    cell = spec.load_cell(STACK.cell)
    config = cell.config
    assert config["reduced"] == ["max_position_embeddings"]
    assert (config["num_hidden_layers"], config["hidden_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["intermediate_size"],
            config["vocab_size"], config["total_ut_steps"],
            config["early_exit_threshold"], config["rope_theta"],
            config["rms_norm_eps"], config["max_position_embeddings"]) == (
        48, 2048, 16, 16, 128, 5632, 49152, 4, 1, 1000000, 1e-6, 1024)
    assert config["layer_types"] == ["full_attention"] * 48
    assert config["model_type"] == "ouro" and config["rope_scaling"] is None
    cfg = spec.gpt_config(cell, training=False)
    assert cfg.param_dtype == cfg.dtype == "bfloat16"
    assert (cfg.n_passes, cfg.post_norms, cfg.exit_gate, cfg.cache_planes) \
        == (4, True, True, 192)
    assert spec.server_options(cell) == {
        "prefill_len": 384, "prefill_buckets": [192, 384],
        "n_slots": cell.found["server"]["n_slots"]}
    # a token's cache: 192 planes of 16 heads of 128, keys and values, 2 B
    shapes = gen.cache_leaf_shapes(cfg, 1)
    assert sum(np.prod(s) // 1024 for s in shapes.values()) * 2 == 1_572_864
    cells = [w for w in spec.load_manifest()["workloads"]
             if w["config"] == "ouro-2.6b"]
    assert [(w["name"], w["chips"]) for w in cells] == [(STACK.cell, 1)]


@pytest.mark.parametrize("key,value", [
    ("total_ut_steps", 2), ("num_attention_heads", 32),
    ("num_key_value_heads", 4), ("hidden_size", 1024), ("head_dim", 64),
    ("intermediate_size", 8192), ("early_exit_threshold", 0.5),
    ("num_hidden_layers", 24)])
def test_run_refuses_a_size_the_program_does_not_run(key, value):
    cell = spec.load_cell(STACK.cell)
    wrong = dataclasses.replace(cell, config=dict(cell.config, **{key: value}))
    with pytest.raises(spec.SpecError, match=key):
        spec.gpt_config(wrong, training=False)


def test_tiny_shrinks_every_size_the_reference_reads():
    cell = stacks.tiny_cell(STACK)
    cfg = spec.gpt_config(cell, training=False)
    assert (cfg.n_layer, cfg.n_passes, cfg.n_head, cfg.head_dim,
            cfg.dense_width, cfg.cache_planes) == (
        LAYERS, PASSES, 3, 32, 256, PASSES * LAYERS)
    config = cell.config
    assert (config["total_ut_steps"], config["head_dim"],
            config["num_key_value_heads"], config["intermediate_size"]) == (
        PASSES, 32, 3, 256)


def test_the_counters_reach_the_readers(cell_run):
    play = cell_run["evidence"]["play"]
    opened, closed = play.open_counters, play.close_counters
    assert closed["kv_bytes_per_row"] == PASSES * LAYERS * 2 * 3 * 32 * 2
    assert closed["loop_tokens"] > opened["loop_tokens"]
    assert closed["loop_token_passes"] == PASSES * closed["loop_tokens"]
    assert "loop_exit_mass" not in closed     # a list: the summary's alone
    # untraced: the readers find nothing and say so
    for name in NEW_READERS:
        assert spec.load_reader(name).read(cell_run["evidence"]) is None
    traced = dataclasses.replace(play, trace_open=opened, trace_close=closed)
    evidence = dict(cell_run["evidence"], play=traced)
    assert spec.load_reader("loop.passes_per_token").read(evidence) == PASSES
    # no device trace on the CPU: no time to hold the bytes against
    assert spec.load_reader("engine.decode_hbm_roofline").read(evidence) \
        is None
    cell = spec.load_cell(STACK.cell)
    assert set(NEW_READERS) <= {m["name"] for m in cell.per_layer}
    assert "engine.prefill_ms_per_ktok" not in {
        m["name"] for m in cell.per_layer}
    for other in ("gpt2-124m.serve-decode", "gpt2-xl.serve-prefill",
                  "kanana-2-30b-a3b.serve-long-decode",
                  "minicpm-sala.serve-long-context"):
        assert not set(NEW_READERS) & {
            m["name"] for m in spec.load_cell(other).per_layer}


def test_passes_per_token_reads_two_readings_of_the_counter():
    read = spec.load_reader("loop.passes_per_token").read
    play = serve_cell.Play(n_slots=5, block_size=1024)
    play.trace_open = {"loop_token_passes": 400.0, "loop_tokens": 100.0}
    play.trace_close = {"loop_token_passes": 2000.0, "loop_tokens": 500.0}
    assert read({"play": play}) == 4.0
    play.trace_close = {"loop_token_passes": 1900.0, "loop_tokens": 500.0}
    assert read({"play": play}) == 3.75         # a pass left out shows
    play.trace_close = dict(play.trace_open)
    assert read({"play": play}) is None         # no token in the window
    play.trace_open = play.trace_close = {"loop_token_passes": None,
                                          "loop_tokens": None}
    assert read({"play": play}) is None         # layers that run once
    play.trace_open, play.trace_close = {"steps": 1}, {"steps": 9}
    assert read({"play": play}) is None         # the parent's summary
    assert read({"play": None}) is None


def test_the_roofline_s_bytes_are_the_arithmetic_of_the_issue(monkeypatch):
    """4 x 4.93 GB of trunk weights, 0.2 GB of head and five whole slots of
    192 planes: 28.0 GB a step, 34.2 ms at the table's 819 GB/s."""
    reader = spec.load_reader("engine.decode_hbm_roofline")
    cell = spec.load_cell(STACK.cell)
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    want = 2 * (4 * 48 * layer + 2048 * 49152) + 5 * 1024 * 1_572_864
    assert reader.step_bytes(cell.config, 5 * 1024, 1_572_864) == want
    assert want == 27_984_396_288
    play = serve_cell.Play(n_slots=5, block_size=1024)
    play.trace_open = {"decode_rows_read": 5 * 1024 * 10, "steps": 10,
                       "kv_bytes_per_row": 1_572_864}
    play.trace_close = {"decode_rows_read": 5 * 1024 * 90, "steps": 90,
                        "kv_bytes_per_row": 1_572_864}
    evidence = {"play": play, "cell": cell, "device_kind": "TPU v5 lite",
                "trace": object()}
    monkeypatch.setattr(reader.trace, "window_of", lambda tr: (0, 1))
    monkeypatch.setattr(reader.trace, "program_runs",
                        lambda tr, window, name: [0.038, 0.038, 0.0385])
    share = reader.read(evidence)
    assert share == pytest.approx(100 * want / 819e9 / (0.1145 / 3))
    assert 85 < share < 95
    monkeypatch.setattr(reader.trace, "program_runs", lambda *a: [])
    assert reader.read(evidence) is None        # no decode run in the trace
    assert reader.read({"play": play, "cell": cell, "trace": None}) is None
    play.trace_open = play.trace_close = {"steps": 3}
    assert reader.read(evidence) is None        # the parent's summary
    dense = spec.load_cell("gpt2-124m.serve-decode")
    assert reader.read(dict(evidence, cell=dense)) is None


# -- negative controls: a faulty program the verdict refuses ------------------

def prompts_for(cell):
    rng = np.random.default_rng(0)
    vocab = spec.gpt_config(cell, training=False).vocab_size
    return [rng.integers(0, vocab, size=n, dtype=np.int32) for n in (24, 40)]


def server_of(cell, **cfg_change):
    """The cell's server over the sound model's weights, its programs traced
    under a configuration changed by ``cfg_change`` (and by whatever the
    test has patched before the call)."""
    sound = spec.gpt_config(cell, training=False)
    return InferenceServer(
        serve_cell.init_params(sound, STACK.seed),
        dataclasses.replace(sound, **cfg_change), warmup=False,
        **spec.server_options(cell))


def verdict_of(cell, server):
    return check.serve_verdict(spec.load_reference(cell.config), cell.config,
                               server, prompts_for(cell), 4)


def norm_only_before_the_head(monkeypatch):
    """The final norm no pass carries: the passes run on unnormed, and the
    norm stands before the head alone."""
    real_norm, real_head = gpt._norm, gen._head_logits

    def hidden(params, *args, **kwargs):
        lnf = params["lnf_scale"]
        skipping = lambda x, scale, bias, cfg: x if scale is lnf \
            else real_norm(x, scale, bias, cfg)
        monkeypatch.setattr(gpt, "_norm", skipping)
        try:
            return real_hidden(params, *args, **kwargs)
        finally:
            monkeypatch.setattr(gpt, "_norm", real_norm)

    real_hidden = gen._forward_cached_hidden
    monkeypatch.setattr(gen, "_forward_cached_hidden", hidden)
    monkeypatch.setattr(
        gen, "_head_logits", lambda params, x, cfg: real_head(
            params, real_norm(x, params["lnf_scale"], None, cfg), cfg))


def previous_pass_s_plane(monkeypatch):
    """Pass t of a layer attends pass t-1's keys and values."""
    real = gen.attn_ops.causal_attend_step
    monkeypatch.setattr(
        gen.attn_ops, "causal_attend_step",
        lambda q, k, v, plane, *args, **kwargs: real(
            q, k, v, jax.numpy.maximum(plane - LAYERS, 0), *args, **kwargs))


def one_pass_dropped(monkeypatch):
    """The program runs one pass fewer over a cache of all the planes: the
    last pass's planes are never written, and the head reads the pass
    before."""
    monkeypatch.setattr(GPTConfig, "cache_planes",
                        property(lambda self: PASSES * self.n_layer))
    return dict(n_passes=PASSES - 1)


@pytest.fixture(scope="module")
def sound():
    cell = stacks.tiny_cell(STACK)
    verdict = verdict_of(cell, server_of(cell))
    assert verdict["ok"], verdict
    return cell, verdict


@pytest.mark.parametrize("fault", [
    "one-pass-dropped", "previous-pass-plane", "post-norms-left-out",
    "final-norm-not-carried"])
def test_the_verdict_refuses_a_faulty_loop(sound, fault, monkeypatch):
    cell, good = sound
    change = {}
    if fault == "one-pass-dropped":
        change = one_pass_dropped(monkeypatch)
    elif fault == "previous-pass-plane":
        previous_pass_s_plane(monkeypatch)
    elif fault == "post-norms-left-out":
        change = dict(post_norms=False)
    else:
        norm_only_before_the_head(monkeypatch)
    bad = verdict_of(cell, server_of(cell, **change))
    assert bad["ok"] is False
    over = [name for c in bad["cases"]
            for name, (value, limit) in c["compared"].items() if value > limit]
    assert over, bad
    worst = max(max(c["k_rel"], c["v_rel"]) for c in bad["cases"])
    assert worst > 2 * good["kv_rel_tol"]


def test_a_lower_precision_fails_the_verdict(sound):
    """The nearest precision below the one the configuration states: every
    program's rows passed through 8-bit floats (what an fp8 pool would
    hold) lie outside the twin's tolerance."""
    cell, good = sound
    server = server_of(cell)
    pool = server.engine.pool
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

    bad = verdict_of(cell, types.SimpleNamespace(
        engine=Rounding(server.engine)))
    assert bad["ok"] is False
    # a row's own rounding stands out in the first plane, whose twin is the
    # smallest
    assert any(c["compared"]["k_rel_layer"][0] > c["compared"]["k_rel_layer"][1]
               for c in bad["cases"])
    worst = max(max(c["k_rel"], c["v_rel"]) for c in bad["cases"])
    assert worst > 2 * good["kv_rel_tol"]


def test_rehearse_runs_the_cell_and_its_readers(capsys):
    rehearse.rehearse_run(spec.load_cell(STACK.cell))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["agrees_with_reference"] is True and line["failed"] == 0
    assert line["compiled_in_window"] == 0
    assert line["readers"]["loop.passes_per_token"] == "read"
    # its time is the device trace's and its peak the chip's table's
    assert line["readers"]["engine.decode_hbm_roofline"] in (
        "nothing", "needs the chip's peak")
    assert line["readers"]["kv.bytes_per_live_token"] == "read"
