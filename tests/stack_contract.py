"""What a family of served stacks must prove, written once (ISSUE 63).

A family's file (``tests/test_<family>.py``) names its entry of
``tests/stacks.py`` as ``STACK``, imports from here the laws that hold for
it together with ``pytest_generate_tests`` (as ``tests/test_benchmark_arch.py``
imports the benchmark's self-check), and keeps what is peculiar to it. The
families stay separate files: ``--dist loadfile`` balances by file.

A law reads its stack through the ``stack`` fixture; what it needs of the
family (the sentences, the planted faults, the readers, the published
widths) is that entry's data, and a family's extra assertions inside a
shared law are a hook the entry carries.
"""

import dataclasses
import json
import types

import jax
import numpy as np
import pytest

import stacks
from benchmarks.harness import spec
from mingpt_distributed_tpu.config import ConfigError, GPTConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt


@pytest.fixture(scope="module")
def stack(request) -> stacks.Stack:
    return request.module.STACK


@pytest.fixture(scope="module")
def model(stack):
    """``(cfg, params)`` of the tiny program in float32."""
    return stacks.model(stack)


@pytest.fixture(scope="module")
def reference(stack):
    return stacks.reference_of(stack)


@pytest.fixture(scope="module")
def cell_run(stack):
    return stacks.cell_run(stack)


def pytest_generate_tests(metafunc):
    """``refused`` and ``plant`` are the stack's own tables, a case each."""
    stack = metafunc.module.STACK
    if "refused" in metafunc.fixturenames:
        metafunc.parametrize("refused", stack.refused, ids=[
            "-".join(over) for over, _ in stack.refused])
    if "plant" in metafunc.fixturenames:
        metafunc.parametrize("plant", stack.faults, ids=[
            f.__name__.strip("_") for f in stack.faults])


# -- the program against the reference, float32 ------------------------------
# Tolerances: both sides are float32 and differ in the order of their sums
# (the program attends in blocks under a running softmax and runs its experts
# in blocks of rows, the reference in blocks of queries and pairs): logits of
# order 1 agree to a few 1e-7, and 2e-6 is five times what the worst case
# reads. A planted fault moves them by 1e-3 and more.

def test_the_full_forward_is_the_reference_s(stack):
    cfg, params = stacks.model(stack)
    tokens = stacks.tokens_of(cfg, 2, stack.forward_len)
    logits, loss = stacks.forward(params, tokens, cfg, targets=tokens)
    reference, sizes = stacks.reference_of(stack), stacks.sizes_of(stack, cfg)
    programs = stacks.reference_programs(stack, sizes)
    w = reference.weights_from_program(params)
    x, *rest = programs.hidden(w, tokens)
    np.testing.assert_allclose(logits, programs.logits(w, x),
                               atol=2e-6)
    np.testing.assert_allclose(loss, programs.loss(w, tokens, tokens),
                               atol=1e-5)
    if stack.forward_hook:
        stack.forward_hook(types.SimpleNamespace(
            stack=stack, cfg=cfg, reference=reference, programs=programs,
            sizes=sizes, w=w, tokens=tokens, x=x, rest=rest))


def test_the_cached_path_is_the_uncached_forward(stack):
    """``gpt.forward`` without a cache (every row; a window layer masked by
    age, a linear layer's state never kept) against solo ``generate`` (a
    prefill, then steps of one token under one offset): greedy, each new
    token is the full forward's argmax at its position."""
    cfg, params = stacks.model(stack)
    tokens = stacks.tokens_of(cfg, 2, 12)
    out = np.asarray(gen.generate(params, cfg, tokens, 50))
    logits, _ = stacks.forward(params, stacks.padded(out, cfg), cfg)
    np.testing.assert_array_equal(
        out[:, 12:], np.argmax(logits[:, 11:61], -1))


def test_training_and_a_split_mesh_are_refused_by_the_forward(stack):
    cfg, params = stacks.model(stack)
    tokens = stacks.tokens_of(cfg, 1, 16)
    with pytest.raises(NotImplementedError, match="not trained"):
        gpt.forward(params, tokens, cfg, rng=jax.random.key(0),
                    deterministic=False)
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:2]).reshape(2), ("tp",))
    with pytest.raises(NotImplementedError, match="not split over pp or tp"):
        gpt.forward(params, tokens, cfg, mesh=mesh)


# -- what is not built is refused, a sentence each ---------------------------

def test_combinations_that_are_not_built_are_refused_with_a_sentence(
        stack, refused):
    over, sentence = refused
    with pytest.raises(ConfigError, match=sentence):
        stacks.tiny_cfg(stack, **over)


# -- precision: what the check lets through and what it does not -------------

def test_in_bfloat16_the_engine_holds_the_check_s_law(stack):
    """bfloat16 weights and activations, as the cell is served, through
    ``check.serve_verdict``'s own law (``harness/check.py`` has the
    reasons): the stack's prompts, its decode steps, the routes followed
    where it routes."""
    cfg, params = stacks.model(stack, **stacks.BF16)
    verdict = stacks.verdict_of(
        stack, cfg, params, stacks.sizes_of(stack, cfg),
        stack.verdict_lengths, stack.verdict_steps)
    assert verdict["ok"], json.dumps(verdict)[:2000]
    assert len(verdict["cases"]) == len(stack.verdict_lengths)
    if stack.verdict_hook:
        stack.verdict_hook(verdict)


def test_a_planted_fault_reads_not_ok(stack, monkeypatch, plant):
    """The tiny program with one thing wrong, against the reference under
    the true sizes and the same weights (or the program as it is against a
    reference with one thing wrong): the verdict is not ``ok``."""
    cfg, params = stacks.model(stack, **stacks.BF16)
    faulty, sizes = plant(monkeypatch, cfg, stacks.sizes_of(stack, cfg))
    verdict = stacks.verdict_of(
        stack, faulty, params, sizes, stack.verdict_lengths,
        stack.verdict_steps,
        weights=stacks.reference_of(stack).weights_from_program(params),
        prefill_buckets=stack.serve["prefill_buckets"][-1:])
    assert not verdict["ok"], json.dumps(verdict["cases"][0]["compared"])
    if stack.fault_hook:
        stack.fault_hook(verdict)


# -- the cell through the path the driver runs --------------------------------

def test_the_cell_agrees_with_its_reference_through_the_whole_path(
        stack, cell_run):
    """bfloat16, the engine's own programs, ``serve_cell.Driver`` and
    ``check.serve_verdict`` as the driver runs them: the cached rows inside
    the twin's law (a routed reference having followed the program's
    routes), no program compiled in the window."""
    verdict = cell_run["verdict"]
    assert verdict["ok"], verdict
    assert verdict["compiled_in_window"] == 0
    assert len(verdict["cases"]) == stack.cell_cases
    if stack.cell_hook:
        stack.cell_hook(verdict)
    assert cell_run["failed"] == 0 and cell_run["attempted"] > 0


def test_the_manifest_lists_the_cell_where_it_reports(stack):
    """Laws, not positions: the cell's chips and end-to-end metrics; every
    reader the family needs is one of the cell's, with its unit and layer,
    moves a metric the cell reports and names the cell among its workloads
    (another cell may join them); a reader that finds nothing to read here
    is not listed. Where in the manifest's lists an entry stands is a PR's
    history, which no test holds."""
    cell = spec.load_cell(stack.cell)
    assert cell.chips == 1
    reported = [m["name"] for m in cell.end_to_end]
    assert reported == list(stack.end_to_end)
    listed = {m["name"]: m for m in cell.per_layer}
    for name, unit, layer in stack.readers:
        metric = listed[name]
        assert (metric["unit"], metric["layer"]) == (unit, layer), name
        assert metric["moves"] == stack.readers_move in reported, name
        assert stack.cell in metric.get("workloads", [stack.cell]), name
        assert callable(spec.load_reader(name).read)
    for name in stack.absent_readers:
        assert name not in listed, name
    manifest = spec.load_manifest()
    entry, = [c for c in manifest["configs"] if c["name"] == stack.config]
    assert entry["reduced"] == cell.config["reduced"]
    assert entry["source"] == cell.config["source"]


# -- the preset, the configuration file, the sizes ----------------------------

def test_the_preset_is_the_published_model(stack):
    cfg = GPTConfig.make(model_type=stack.preset)
    assert {field: getattr(cfg, field) for field in stack.published} \
        == stack.published
    if stack.weight_count:
        count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(
            jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg))))
        low, high = stack.weight_count
        assert low <= count <= high
    if stack.preset_hook:
        stack.preset_hook(stack, cfg)


def test_the_configuration_file_holds_the_published_widths(stack):
    cell = spec.load_cell(stack.cell)
    config = cell.config
    assert config["reduced"] == list(stack.reduced)
    assert {key: config[key] for key in stack.widths} == stack.widths
    cfg = spec.gpt_config(cell, training=False)
    assert cfg.param_dtype == cfg.dtype == "bfloat16"
    assert spec.server_options(cell) == {
        **stack.server_options, "n_slots": cell.found["server"]["n_slots"]}
    for key in stack.assumed:
        assert key in config["assumed"]
    for key, value in stack.wrong_widths:
        wrong = dataclasses.replace(cell, config=dict(config, **{key: value}))
        with pytest.raises(spec.SpecError, match=key):
            spec.gpt_config(wrong, training=False)
    if stack.config_hook:
        stack.config_hook(stack, cell, config)


def test_the_slot_and_the_weights_are_the_size_the_configuration_states(stack):
    cfg = spec.gpt_config(spec.load_cell(stack.cell), training=False)
    size = {n: int(np.prod(s)) * 2
            for n, s in gen.cache_leaf_shapes(cfg, 1).items()}
    assert size["k"] + size["v"] == stack.slot_bytes["rows"]
    assert size[gen.RING_K] + size[gen.RING_V] == stack.slot_bytes["rings"]
    assert sum(size.values()) == stack.slot_bytes["all"]
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(
        jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg))))
    low, high = stack.weight_count
    assert low <= count <= high
