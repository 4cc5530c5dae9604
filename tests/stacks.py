"""The suite's tiny served stacks, in one table (ISSUE 63).

A family of served stacks (a model the benchmark serves, or a form of row
``tests/test_wide_rows.py`` walks) is one entry here: the ``GPTConfig``
arguments that make it that family at a tiny size, its tiny ``spec.Cell``
where the benchmark has one, its plain reference, and what the laws of
``tests/stack_contract.py`` read of it (the sentences it refuses with, its
planted faults, its readers in the manifest, its published widths). A new
model is an entry here, a ``tests/test_<family>.py`` that names it and holds
what is peculiar to it; not a copy of the last family's file.

What costs is paid once a stack and session: parameters come from one jitted
``gpt.init``, token batches from NumPy, the forwards and the references run
under ``jax.jit`` at one padded length (a causal stack reads nothing after a
position: ``tests/test_generate.py`` holds it for every family), each an
``oracles.shared_jit``, so that a test that patches what one traces is served
a trace of its own. Every server test of the family files asserts on a fresh
engine (compile counts, a freed slot, migration), so each builds its own and
none is shared; ``serve`` drives one (idle before it submits, drained before
it returns), and the persistent cache hands a stack's programs to the next
engine of it.

Sizes are shared wherever a law does not need another: the benchmark's
families run ``rehearse.tiny``'s (a block of 128 rows, a vocabulary of 384,
two slots, buckets of 32 and 64); the hybrid preset keeps its vocabulary of
96 and a bucket of 96 (its selection starts at 48 rows and the laws cross
it), the looped and the latent stacks a block of 64 (the references' hand
computed byte counts), and the forms of ``test_wide_rows.py`` a block of 32
(every pinned digest of ``program_digests.py`` is made at it).
"""

import dataclasses
import functools
import json
import math
import types
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import rehearse
from benchmarks.harness import check, compiles, serve_cell, spec
from mingpt_distributed_tpu.config import (
    FULL_ATTN, MODEL_PRESETS, SPARSE, WINDOW_ATTN, GPTConfig)
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.ops import moe
from mingpt_distributed_tpu.serving import InferenceServer, Request
from oracles import shared_jit

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
F32 = dict(dtype="float32", param_dtype="float32")
BF16 = dict(dtype="bfloat16", param_dtype="bfloat16")
OFF = dict(embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0)
#: the server every family is served by unless a law needs another
SERVE = dict(n_slots=2, prefill_len=64, prefill_buckets=[32, 64])
WINDOW = 16


@dataclasses.dataclass(frozen=True, eq=False)
class Stack:
    """One family. ``gpt()``: the tiny program's ``GPTConfig`` arguments in
    float32. The rest is what the contract reads, each under the law that
    reads it (``tests/stack_contract.py``)."""
    name: str
    gpt: Callable[[], Dict[str, Any]]
    cell: Optional[str] = None          # the benchmark's cell
    config: Optional[str] = None        # its entry of the manifest's configs
    reference: Optional[str] = None     # where no cell names it
    sizes: Optional[Dict] = None        # what it reads, where no key_map
    seed: int = 2_500_000_001           # past 32 signed bits, as the driver's
    init_key: int = 3
    #: norms' scales and biases off their initial values, where a factor
    #: left out would not show
    perturbed: bool = False
    serve: Dict[str, Any] = dataclasses.field(default_factory=lambda: SERVE)
    #: the full forward against the reference: tokens a row (not a whole
    #: number of the attention's blocks), and the family's own assertions on
    #: what the law saw (a namespace: cfg, reference, programs, sizes, w,
    #: tokens, x, rest)
    forward_len: int = 100
    forward_hook: Optional[Callable] = None
    #: the check's law in bfloat16: prompt lengths, decode steps, the
    #: family's own assertions on the verdict
    verdict_lengths: Tuple[int, ...] = ()
    verdict_steps: int = 8
    verdict_hook: Optional[Callable] = None
    #: (monkeypatch, cfg, sizes) -> (cfg, sizes): one thing wrong a function;
    #: the family's own assertions on a verdict that is not ``ok``
    faults: Tuple[Callable, ...] = ()
    fault_hook: Optional[Callable] = None
    #: (GPTConfig overrides, the sentence's pattern)
    refused: Tuple[Tuple[Dict, str], ...] = ()
    #: the cell through the driver's path: cases, and the family's own
    #: assertions on the verdict
    cell_sizes: Optional[Dict[str, int]] = None
    cell_cases: int = 3
    cell_hook: Optional[Callable] = None
    #: the manifest: end-to-end names, the readers the family needs as
    #: (name, unit, layer), the metric they move, and those that must not
    #: be listed
    end_to_end: Tuple[str, ...] = ("itl_p50_ms", "setup_s")
    readers: Tuple[Tuple[str, str, str], ...] = ()
    readers_move: str = "itl_p50_ms"
    absent_readers: Tuple[str, ...] = ()
    #: the preset against the published model
    preset: Optional[str] = None
    published: Optional[Dict[str, Any]] = None
    preset_hook: Optional[Callable] = None
    #: the configuration file: its ``reduced``, published (key, value)s,
    #: the server's options, ``assumed``'s keys, (key, value)s it refuses
    reduced: Tuple[str, ...] = ()
    widths: Optional[Dict[str, Any]] = None
    server_options: Optional[Dict[str, Any]] = None
    assumed: Tuple[str, ...] = ()
    wrong_widths: Tuple[Tuple[str, Any], ...] = ()
    config_hook: Optional[Callable] = None
    #: a slot's and the weights' sizes at the published widths
    slot_bytes: Optional[Dict[str, int]] = None
    weight_count: Optional[Tuple[int, int]] = None     # inclusive range


# -- made once a session ------------------------------------------------------

@functools.cache
def _tiny_cell(name: str, sizes: Tuple) -> spec.Cell:
    return rehearse.tiny(spec.load_cell(name), sizes=dict(sizes) or None)


def tiny_cell(stack, **sizes) -> spec.Cell:
    """The stack's cell at ``rehearse.tiny``'s size (a cell's name will do)."""
    name = stack if isinstance(stack, str) else stack.cell
    return _tiny_cell(name, tuple(sorted(sizes.items())))


def tiny_cfg(stack: Stack, **over) -> GPTConfig:
    """The stack's tiny program, float32 unless ``over`` says another."""
    return GPTConfig.make(**{**stack.gpt(), **over})


@functools.cache
def _tokens(vocab: int, batch: int, t: int, seed: int) -> np.ndarray:
    tokens = np.random.default_rng(seed).integers(
        0, vocab, (batch, t), dtype=np.int32)
    tokens.setflags(write=False)
    return tokens


def tokens_of(cfg: GPTConfig, batch: int, t: int, seed: int = 1) -> np.ndarray:
    return _tokens(cfg.vocab_size, batch, t, seed)


_init = jax.jit(gpt.init, static_argnums=1)


@functools.partial(jax.jit, static_argnums=1)
def _init_perturbed(key, cfg):
    params = gpt.init(key, cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    return jax.tree.unflatten(tree, [
        (a + 0.01 * jax.random.normal(k, a.shape)).astype(a.dtype)
        for a, k in zip(leaves, keys)])


@functools.cache
def _model(stack: Stack, over: Tuple):
    cfg = tiny_cfg(stack, **dict(over))
    init = _init_perturbed if stack.perturbed else _init
    return cfg, init(jax.random.key(stack.init_key), cfg)


def model(stack: Stack, **over):
    """``(cfg, params)``, the same objects every time a session asks."""
    return _model(stack, tuple(sorted(over.items())))


def sizes_of(stack: Stack, cfg: GPTConfig) -> Dict:
    """What the reference reads of a configuration file, from the program's
    config: the cell's own ``key_map``, applied as ``rehearse.tiny`` does."""
    if stack.sizes is not None:
        return dict(stack.sizes)
    key_map = spec.load_cell(stack.cell).config["program"]["key_map"]
    return {published: getattr(cfg, field)
            for published, field in key_map.items()}


@functools.cache
def reference_of(stack: Stack):
    config = spec.load_cell(stack.cell).config if stack.reference is None \
        else {"reference": stack.reference}
    return spec.load_reference(config)


@functools.cache
def _reference_programs(stack: Stack, sizes_key: str):
    reference, sizes = reference_of(stack), json.loads(sizes_key)
    made = dict(
        hidden=shared_jit(lambda w, tokens, experts=None: reference.hidden(
            w, tokens, sizes, **({} if experts is None
                                 else {"experts": experts}))),
        logits=shared_jit(reference.logits))
    if hasattr(reference, "loss"):
        made["loss"] = shared_jit(
            lambda w, tokens, targets: reference.loss(
                w, tokens, targets, sizes))
    if hasattr(reference, "states"):
        made["states"] = shared_jit(
            lambda w, tokens: reference.states(w, tokens, sizes))
    return types.SimpleNamespace(**made)


def reference_programs(stack: Stack, sizes: Dict):
    """The reference's ``hidden``, ``logits``, ``loss`` and ``states`` under
    ``sizes``, each one jitted program a shape and session."""
    return _reference_programs(stack, json.dumps(sizes, sort_keys=True))


forward = shared_jit(gpt.forward, static_argnums=2,
                     static_argnames=("return_gates",))
forward_cached = shared_jit(gen._forward_cached, static_argnums=4)
forward_cached_hidden = shared_jit(gen._forward_cached_hidden,
                                   static_argnums=4)


def padded(tokens, cfg: GPTConfig) -> np.ndarray:
    """``tokens`` (B, T) with zeros after them up to the block's length: a
    causal stack's answers at the first T positions are those of ``tokens``
    alone, and every length shares one compiled forward."""
    tokens = np.asarray(tokens, np.int32)
    out = np.zeros((tokens.shape[0], cfg.block_size), np.int32)
    out[:, :tokens.shape[1]] = tokens
    return out


# -- servers ------------------------------------------------------------------

def serve(server: InferenceServer, prompts, budgets):
    """``prompts`` through ``server`` under greedy choice, ``budgets`` new
    tokens each (or one number for all): every request's tokens. The server
    is idle before and drained after."""
    assert server.engine.pool.used_count == 0, "the server is busy"
    if isinstance(budgets, int):
        budgets = [budgets] * len(prompts)
    handles = [server.submit(Request(prompt=[int(t) for t in p],
                                     max_new_tokens=n, do_sample=False))
               for p, n in zip(prompts, budgets)]
    while server.step():
        pass
    assert all(h.finished for h in handles)
    assert server.engine.pool.used_count == 0
    return [h.tokens for h in handles]


def verdict_of(stack: Stack, cfg, params, sizes, lengths, steps,
               weights=None, **options):
    """``check.serve_verdict`` over prompts of ``lengths`` from the stack's
    seed, ``steps`` decode steps each, through a server of its own.
    ``weights``: what the reference computes with, where the program's tree
    is not the model's (a planted fault)."""
    reference = reference_of(stack)
    if weights is not None:
        reference = types.SimpleNamespace(**{
            **vars(reference), "weights_from_program": lambda _: weights})
    server = InferenceServer(params, cfg, **{**stack.serve, "warmup": True,
                                             **options})
    rng = np.random.default_rng(stack.seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lengths]
    return check.serve_verdict(reference, sizes, server, prompts, steps)


def cell_run(stack: Stack):
    """The tiny cell through ``serve_cell.run``, as the driver runs it (the
    contract's ``cell_run`` fixture keeps it, once a module)."""
    return serve_cell.run(
        tiny_cell(stack, **(stack.cell_sizes or {})), seed=stack.seed,
        seconds=1.0, traced=False, devices=jax.devices()[:1], t_process=0.0,
        compiles=compiles.CompileCounter())


def catalog_row(name: str) -> Dict:
    with open(CATALOG) as f:
        return next(row for row in map(json.loads, f) if row["name"] == name)


def _cell_gpt(cell: str):
    return lambda: {**tiny_cell(cell).config["program"]["gpt_config"], **F32}


def _replacing(name, **fields):
    """A planted fault that is a field of the configuration, under ``name``."""
    def plant(monkeypatch, cfg, sizes):
        return dataclasses.replace(cfg, **fields), sizes
    plant.__name__ = name
    return plant


# -- laguna: kinds of softmax attention layer, a gate a head -------------------

def _laguna_forward(seen):
    cfg, (ks, vs, router) = seen.cfg, seen.rest
    batch, t = seen.tokens.shape
    assert seen.reference.cached_layers(seen.sizes) == (0, 4)
    assert ks.shape == vs.shape == (2, batch, t, cfg.kv_heads, cfg.head_dim)
    assert router.shape == (5, batch, t, cfg.n_experts)


def _rings_verdict(verdict):
    """Five layers route; the two full ones cache rows."""
    for case in verdict["cases"]:
        assert len(case["k_rel_layers"]) == 2      # the full layers' planes
        assert len(case["route_margin_layers"]) == 5


def _laguna_verdict(verdict):
    _rings_verdict(verdict)
    for case in verdict["cases"]:
        assert set(case["compared"]) >= {"k_in_8bit", "v_in_8bit"}


def _laguna_cell(verdict):
    for case in verdict["cases"]:
        assert len(case["k_rel_layers"]) == len(case["v_rel_layers"]) == 2
        assert case["route_banded_layers"][0] == 0      # the dense layer


def _no_gate(monkeypatch, cfg, sizes):
    real = gpt.attention_out
    monkeypatch.setattr(gpt, "attention_out", lambda att, blk, *a, **kw: real(
        att, {n: v for n, v in blk.items() if n != "w_hg"}, *a, **kw))
    return cfg, sizes


def _sigmoid_gates(monkeypatch, cfg, sizes):
    def routes(h, w_router, *, top_k, route_scale):
        z = h.astype(jnp.float32) @ w_router.astype(jnp.float32)
        chosen = jax.lax.top_k(z, top_k)[1]
        gates = jnp.take_along_axis(jax.nn.sigmoid(z), chosen, axis=-1)
        return (chosen.astype(jnp.int32),
                gates / gates.sum(-1, keepdims=True) * route_scale, z)

    monkeypatch.setattr(moe, "softmax_routes", routes)
    return cfg, sizes


def _the_other_kind_s_rotation(monkeypatch, cfg, sizes):
    real = GPTConfig.rope_spec
    other = {FULL_ATTN: WINDOW_ATTN, WINDOW_ATTN: FULL_ATTN}
    monkeypatch.setattr(GPTConfig, "rope_spec",
                        lambda self, kind=None: real(self, other[kind]))
    return cfg, sizes


def _laguna_config(stack, cell, config):
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert config["rope_parameters"]["full_attention"]["factor"] == 64
    row = catalog_row("Laguna-XS.2")
    assert config["source"] == row["source_url"]
    n = config["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] == (value[:n] if isinstance(value, list)
                                   else config[key])
        else:
            assert config[key] == value, key


LAGUNA = Stack(
    name="laguna", cell="laguna-xs.2.serve-long-decode", config="laguna-xs.2",
    gpt=_cell_gpt("laguna-xs.2.serve-long-decode"),
    forward_hook=_laguna_forward,
    verdict_lengths=(21, 40, 60), verdict_hook=_laguna_verdict,
    faults=(
        _no_gate, _sigmoid_gates,
        _replacing("_window_one_row_short", attention_window=WINDOW - 1),
        _the_other_kind_s_rotation,
        _replacing("_unscaled_routed_sum", moe_route_scale=1.0)),
    refused=(
        (dict(layer_types=["full_attention"] * 4), "for each of the 5 layers"),
        (dict(layer_types=["sliding_attention"] * 5),
         "needs a full attention"),
        (dict(attention_window=None), "set it"),
        (dict(attention="flash"), "built for attention='einsum'"),
        (dict(rmsnorm=False), "needs rope, rmsnorm and swiglu"),
        (dict(window_n_head=7), "not divisible by the 2 KV heads"),
        (dict(rope_fraction=0.2), "an even number of them"),
        (dict(rope_yarn=[1.0, 4096, 64, 1, 1.0]), "rope_yarn is"),
        (dict(head_size=0), "it is positive"),
        (dict(post_norms=True), "are not written for it"),
        (dict(pp_microbatches=2), "is not pipelined"),
        (dict(rope_interleave=True), "no rope_interleave"),
        (dict(moe_dropless=False, n_shared_experts=0, moe_route_scale=1.0),
         "routes without dropping"),
        (dict(moe_dropless=False), "built for the dropless route only"),
        (dict(moe_norm_topk=False), "renormalises the chosen experts'"),
        (dict(layer_types=None), "belong to a stack of layer_types")),
    cell_cases=3, cell_hook=_laguna_cell,
    readers=(
        ("attention.ring_ms_per_step", "ms", "attention"),
        ("kv.ring_bytes_per_slot", "bytes", "kv_pool"),
        ("kv.ring_read_row_share", "%", "kv_pool"),
        ("kernel.rows_attend_roofline", "%", "kernel")),
    # the experts a layer come from ``first_k_dense_replace`` and
    # ``n_routed_experts``, which this configuration does not publish
    absent_readers=("moe.rows_per_expert_round", "engine.decode_hbm_roofline"),
    reduced=("num_hidden_layers", "layer_types", "mlp_layer_types",
             "num_attention_heads_per_layer", "max_position_embeddings"),
    widths=dict(
        hidden_size=2048, head_dim=128, num_key_value_heads=8,
        num_attention_heads=48, intermediate_size=8192, num_experts=256,
        num_experts_per_tok=8, moe_intermediate_size=512,
        shared_expert_intermediate_size=512, sliding_window=512,
        vocab_size=100352, moe_routed_scaling_factor=2.5),
    server_options=dict(prefill_len=4096, prefill_buckets=[1024, 2048, 4096]),
    assumed=("weights", "router scoring", "norm_topk_prob", "gating"),
    wrong_widths=(("sliding_window", 256),
                  ("num_attention_heads_per_layer", [48] * 5)),
    config_hook=_laguna_config,
    # 67.1 MB of rows, 3 rings of 512 rows of 4 KB
    slot_bytes={"rows": 2 * 8192 * 4096, "rings": 6_291_456,
                "all": 73_400_320},
    weight_count=(3_869_000_001, 3_870_999_999))


# -- smallthinker: a router on the attention's input, ReLU-gated experts ------

def _smallthinker_forward(seen):
    _laguna_forward(seen)
    # every layer routes, and the reference's own choice under a table of
    # entries of -1 is its choice under none
    table = -np.ones((5, *seen.tokens.shape, seen.cfg.moe_top_k), np.int32)
    np.testing.assert_array_equal(
        seen.x, seen.programs.hidden(seen.w, seen.tokens, table)[0])


def _smallthinker_cell(verdict):
    for case in verdict["cases"]:
        assert len(case["k_rel_layers"]) == len(case["v_rel_layers"]) == 2
        assert len(case["route_banded_layers"]) == 5


def _no_rotation_on_the_window_layers(monkeypatch, cfg, sizes):
    real = GPTConfig.rope_spec
    monkeypatch.setattr(
        GPTConfig, "rope_spec", lambda self, kind=None: (
            (0,) + real(self, kind)[1:]) if kind == WINDOW_ATTN
        else real(self, kind))
    return cfg, sizes


def _gates_over_all_experts(monkeypatch, cfg, sizes):
    def routes(h, w_router, *, top_k, route_scale):
        z = h.astype(jnp.float32) @ w_router.astype(jnp.float32)
        chosen = jax.lax.top_k(z, top_k)[1]
        gates = jnp.take_along_axis(jax.nn.softmax(z, -1), chosen, axis=-1)
        return chosen.astype(jnp.int32), gates * route_scale, z

    monkeypatch.setattr(moe, "softmax_routes", routes)
    return cfg, sizes


SMALLTHINKER = Stack(
    name="smallthinker", cell="smallthinker-21b-a3b.serve-past-window",
    config="smallthinker-21b-a3b", seed=2_610_000_001,
    gpt=_cell_gpt("smallthinker-21b-a3b.serve-past-window"),
    forward_hook=_smallthinker_forward,
    verdict_lengths=(6, 12, 60), verdict_hook=_rings_verdict,
    faults=(
        _replacing("_router_on_the_mlp_s_input", moe_router_input="mlp"),
        _replacing("_silu_gate", expert_act="silu"),
        _replacing("_rotation_on_the_full_layers", rope_fraction=1.0),
        _no_rotation_on_the_window_layers,
        _replacing("_window_one_row_short", attention_window=WINDOW - 1),
        _gates_over_all_experts),
    refused=(
        (dict(expert_act="gelu"), "is 'silu' or 'relu'"),
        (dict(moe_router_input="residual"), "'mlp' or 'attn'"),
        (dict(moe_dropless=False), "are the dropless route's"),
        (dict(n_shared_experts=1), "with shared experts is not written"),
        (dict(rope_fraction=0.2), "or none"),
        (dict(window_rope_fraction=0.0), "no kind rotates"),
        (dict(rope_yarn=[64.0, 4096, 64, 1, 1.4]), "they rotate nothing"),
        (dict(swiglu=False), "needs n_experts > 0 and swiglu"),
        (dict(attention="flash"), "built for attention='einsum'"),
        (dict(pp_microbatches=2), "is not pipelined")),
    cell_cases=2, cell_hook=_smallthinker_cell,
    readers=(
        ("moe.route_ms_per_step", "ms", "experts"),
        ("kernel.grouped_glu_roofline", "%", "kernel"),
        ("moe.expert_runs_per_step", "experts", "experts"),
        ("attention.ring_ms_per_step", "ms", "attention"),
        ("kv.ring_bytes_per_slot", "bytes", "kv_pool"),
        ("kv.ring_read_row_share", "%", "kv_pool"),
        ("kernel.rows_attend_roofline", "%", "kernel")),
    # the reader that names the SiLU kernel's calls alone: this cell's
    # kernel is ``grouped_reglu``
    absent_readers=("kernel.grouped_swiglu_us_per_block",
                    "moe.rows_per_expert_round"),
    # 67.1 MB of rows, 3 rings of 4,096 rows of 2 KB
    slot_bytes={"rows": 2 * 16384 * 2048, "rings": 3 * 4096 * 2048,
                "all": 92_274_688},
    weight_count=(5 * 398_627_840 + 777_912_320 + 2_560,) * 2)


# -- minicpm-sala: lightning linear attention beside block-sparse attention ----

def _minicpm_forward(seen):
    cfg, (ks, vs) = seen.cfg, seen.rest
    assert ks.shape == vs.shape == (
        2, *seen.tokens.shape, 1, cfg.kv_heads * cfg.head_dim)


def _minicpm_verdict(verdict):
    assert [c["bucket"] for c in verdict["cases"]] == [32, 64, 96]


def _minicpm_cell(verdict):
    for case in verdict["cases"]:
        assert len(case["k_rel_layers"]) == len(case["v_rel_layers"]) == 2


def _minicpm_preset(stack, cfg):
    assert cfg.mixer_layers(SPARSE) == (0, 9, 16, 17, 22, 29, 30, 31)
    assert len(cfg.mixer_layers("lightning-attn")) == 24
    assert math.isclose(cfg.residual_scale, 1.4 / 32 ** 0.5)
    shapes = gen.cache_leaf_shapes(dataclasses.replace(cfg, block_size=32768),
                                   16)
    assert shapes == {"k": (8, 16, 32768, 1, 256), "v": (8, 16, 32768, 1, 256),
                      gen.POOLED: (8, 16, 2048, 1, 256),
                      gen.STATE: (24, 16, 32, 128, 128)}


def _minicpm_config(stack, cell, config):
    published = MODEL_PRESETS["minicpm-sala"]["mixer_types"]
    assert tuple(config["mixer_types"]) == published[7:23]
    cfg = spec.gpt_config(cell, training=False)
    assert math.isclose(cfg.residual_scale, 1.4 / 32 ** 0.5)
    assert len(cfg.mixer_layers(SPARSE)) == 4
    assert cfg.mixer_types[-1] == SPARSE
    wrong = dataclasses.replace(cell, config=dict(
        config, sparse_config=dict(config["sparse_config"], topk=32)))
    with pytest.raises(spec.SpecError, match="sparse_config"):
        spec.gpt_config(wrong, training=False)


MINICPM = Stack(
    name="minicpm", cell="minicpm-sala.serve-long-context",
    config="minicpm-sala",
    gpt=lambda: dict(MODEL_PRESETS["minicpm-sala-tiny"]),
    serve=dict(n_slots=2, prefill_len=96, prefill_buckets=[32, 64, 96]),
    forward_len=112, forward_hook=_minicpm_forward,
    # three prompts, the longest past ``dense_len``
    verdict_lengths=(30, 60, 90), verdict_steps=24,
    verdict_hook=_minicpm_verdict,
    refused=(
        (dict(attention="flash"), "built for attention='einsum'"),
        (dict(attention="ring"), "built for attention='einsum'"),
        (dict(attention="ulysses"), "built for attention='einsum'"),
        (dict(attention_window=64), "no attention_window"),
        (dict(attn_logit_softcap=30.0), "no attention_window"),
        (dict(pp_microbatches=2), "not pipelined"),
        (dict(rope_interleave=True), "rope_interleave is not written"),
        (dict(n_experts=4), "latent attention and experts are not written"),
        (dict(rope=False), "needs rope, rmsnorm and swiglu"),
        (dict(mixer_types=("lightning-attn",) * 4), "needs a sparse layer"),
        (dict(mixer_types=("minicpm4",) * 3), "for each of the 4 layers"),
        (dict(mixer_types=("minicpm4", "mamba", "minicpm4", "minicpm4")),
         "for each of the 4 layers"),
        (dict(lightning_head_dim=15), "an even lightning_head_dim"),
        (dict(sparse_block_size=24), "multiples of sparse_kernel_stride"),
        (dict(mixer_types=None), "belong to a hybrid stack")),
    cell_cases=3, cell_hook=_minicpm_cell,
    readers=(
        ("kv.state_bytes_per_slot", "bytes", "kv_pool"),
        ("sparse.attended_row_share", "%", "attention"),
        ("kv.bytes_per_live_token", "bytes", "kv_pool"),
        ("kv.live_row_share", "%", "kv_pool"),
        ("engine.decode_step_ms_p50", "ms", "engine"),
        ("sched.host_ms_per_round", "ms", "scheduler")),
    # a traced window at 0.28 requests a second can hold no prefill at all
    absent_readers=("engine.prefill_ms_per_ktok", "sched.queue_wait_ms_p50"),
    preset="minicpm-sala",
    published=dict(n_layer=32, n_embd=4096, n_head=32, kv_heads=2,
                   head_dim=128, dense_width=16384, vocab_size=73448,
                   block_size=524288, head_divisor=16.0, scale_emb=12.0),
    preset_hook=_minicpm_preset, weight_count=(9_400_000_001, 9_599_999_999),
    reduced=("num_hidden_layers", "mixer_types", "max_position_embeddings"),
    widths=dict(hidden_size=4096, intermediate_size=16384,
                num_attention_heads=32, num_key_value_heads=2, head_dim=128,
                lightning_nh=32, lightning_head_dim=128, vocab_size=73448,
                scale_emb=12, scale_depth=1.4, dim_model_base=256),
    server_options=dict(prefill_len=32768,
                        prefill_buckets=[8192, 16384, 32768]),
    assumed=("sparse_config", "slopes", "topk", "dense_len", "max-pool",
             "mup_denominator", "weights"),
    wrong_widths=(("lightning_nh", 16),),
    config_hook=_minicpm_config)


# -- kanana: a latent cache, a dense layer before sigmoid-routed experts -------

KANANA_CELL = "kanana-2-30b-a3b.serve-long-decode"
LATENT_VOCAB, LATENT_BLOCK = 211, 64
LATENT_GPT = dict(
    n_layer=3, n_head=4, n_embd=64, vocab_size=LATENT_VOCAB,
    block_size=LATENT_BLOCK, attention="einsum", rope=True, rope_theta=500.0,
    rope_interleave=True, rmsnorm=True, swiglu=True, norm_eps=1e-6,
    tie_weights=False, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_dense_layers=1, ffn_dim=96,
    n_experts=8, moe_top_k=3, moe_ffn_dim=24, n_shared_experts=2,
    moe_scoring="sigmoid", moe_route_scale=2.448, **OFF, **F32)
#: the published keys the reference reads, as the tiny program has them
LATENT_SIZES = dict(
    num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rope_theta=500.0,
    rope_interleave=True, rms_norm_eps=1e-6, first_k_dense_replace=1,
    num_experts_per_tok=3, norm_topk_prob=True, routed_scaling_factor=2.448,
    scoring_func="sigmoid", q_lora_rank=None, n_group=1, topk_group=1)


def _latent_verdict(verdict):
    for case in verdict["cases"]:
        assert len(case["route_banded_layers"]) == 3
        assert case["route_banded_layers"][0] == 0      # the dense layer
        assert max(case["k_rel_layers"] + case["v_rel_layers"]) \
            <= verdict["kv_rel_tol"]


def _a_float32_reference_of_another_rope(monkeypatch, cfg, sizes):
    return cfg, {**sizes, "rope_interleave": False}


def _latent_fault(verdict):
    """The reference that fails still followed the three layers' routes."""
    for case in verdict["cases"]:
        assert len(case["route_banded_layers"]) == 3
        assert case["route_banded_layers"][0] == 0      # the dense layer


def _kanana_cell(verdict):
    for case in verdict["cases"]:
        assert len(case["k_rel_layers"]) == len(case["v_rel_layers"]) == 3
        assert max(case["k_rel_layers"] + case["v_rel_layers"]) \
            <= verdict["kv_rel_tol"]
        assert case["route_banded_layers"][0] == 0      # the dense layer


def _kanana_preset(stack, cfg):
    assert cfg.param_dtype == "bfloat16" and cfg.moe_scoring == "sigmoid"
    # the benchmark's configuration is the preset but for its two cuts
    program = spec.load_cell(KANANA_CELL).config["program"]["gpt_config"]
    cut = GPTConfig.make(**program)
    assert dataclasses.replace(
        cfg, model_type=None, n_layer=6, block_size=8192) == cut
    shapes = gen.cache_leaf_shapes(cut, 64)
    assert shapes == {"k": (6, 64, 8192, 1, 64), "v": (6, 64, 8192, 1, 512)}


LATENT = Stack(
    name="latent", gpt=lambda: dict(LATENT_GPT),
    cell=KANANA_CELL, config="kanana-2-30b-a3b",
    reference="references/deepseek_v3.py", sizes=LATENT_SIZES,
    init_key=0, perturbed=True, seed=1,
    serve=dict(n_slots=3, prefill_len=32, prefill_buckets=[16, 32]),
    verdict_lengths=(12, 27), verdict_steps=4, verdict_hook=_latent_verdict,
    faults=(_a_float32_reference_of_another_rope,), fault_hook=_latent_fault,
    refused=(
        (dict(attention="flash"), "einsum"),
        (dict(rmsnorm=False), "rope and rmsnorm"),
        (dict(qk_rope_head_dim=7), "even"),
        (dict(kv_lora_rank=0), "set kv_lora_rank"),
        (dict(swiglu=False), "swiglu"),
        (dict(moe_scoring="softmax"), "sigmoid"),
        (dict(moe_scoring="tanh"), "unknown moe_scoring"),
        (dict(n_dense_layers=4), "n_dense_layers"),
        (dict(n_experts=0, moe_scoring="softmax", n_shared_experts=0,
              moe_route_scale=1.0), "lead an expert model"),
        (dict(param_dtype="float16"), "param_dtype")),
    cell_sizes=dict(n_layer=3), cell_cases=3, cell_hook=_kanana_cell,
    preset="kanana-2-30b-a3b-instruct-2601",
    published=dict(n_layer=48, n_head=32, n_embd=2048, vocab_size=128256,
                   block_size=32768, kv_lora_rank=512, qk_head_dim=192,
                   v_head_dim=128, dense_width=6144, expert_width=768),
    preset_hook=_kanana_preset)


# -- ouro: the layers run n_passes times over one set of weights ---------------

OURO_PASSES, OURO_LAYERS, OURO_BLOCK, OURO_VOCAB = 4, 3, 64, 96
LOOPED_GPT = dict(
    n_layer=OURO_LAYERS, n_head=4, n_embd=64, vocab_size=OURO_VOCAB,
    block_size=OURO_BLOCK, rope=True, rope_theta=1e6, swiglu=True,
    rmsnorm=True, norm_eps=1e-6, tie_weights=False, ffn_dim=160,
    n_passes=OURO_PASSES, post_norms=True, exit_gate=True, dtype="float32",
    **OFF)
LOOPED_SIZES = dict(
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    rope_theta=1e6, rms_norm_eps=1e-6, total_ut_steps=OURO_PASSES,
    early_exit_threshold=1)
OURO_CELL_PASSES, OURO_CELL_LAYERS = 3, 2       # the tiny cell's


def _ouro_cell(verdict):
    planes = OURO_CELL_PASSES * OURO_CELL_LAYERS
    # both prefill buckets are exercised
    assert {c["bucket"] for c in verdict["cases"]} == {32, 64}
    for case in verdict["cases"]:
        assert len(case["k_rel_layers"]) == len(case["v_rel_layers"]) \
            == len(case["twin_k_rel_layers"]) == planes
        assert 0 < max(case["twin_k_rel"], case["twin_v_rel"]) \
            < check.SERVE_TWIN_CEILING
        assert case["kv_ratio"] <= 1.05     # the program reads its twin's


OURO = Stack(
    name="ouro", gpt=lambda: dict(LOOPED_GPT),
    cell="ouro-2.6b.serve-looped-decode", config="ouro-2.6b",
    reference="references/ouro.py", sizes=LOOPED_SIZES, init_key=0,
    seed=2_500_000_037,
    serve=dict(n_slots=2, prefill_len=32, prefill_buckets=[8, 16, 32]),
    cell_cases=3, cell_hook=_ouro_cell)


STACKS = {s.name: s for s in (LAGUNA, SMALLTHINKER, MINICPM, LATENT, OURO)}


# -- the forms of a row (tests/test_wide_rows.py, program_digests.py) ----------

WIDE_TINY = dict(n_layer=2, n_head=4, n_embd=32, vocab_size=64, block_size=32,
                 dtype="float32", **OFF)
ROPE = dict(rope=True, swiglu=True, rmsnorm=True, tie_weights=False)
#: tiny models by what decides the row's shape
FORMS = {
    # four heads of 32: one lane tile a row
    "mha": dict(WIDE_TINY, n_embd=128),
    # four heads of 64: two
    "two-tiles": dict(WIDE_TINY, n_embd=256),
    # eight query heads over four KV heads of 32, rotated
    "gqa-rope": dict(WIDE_TINY, n_head=8, n_kv_head=4, n_embd=256, **ROPE),
    "window-softcap": dict(WIDE_TINY, n_embd=128, attention_window=6,
                           attn_logit_softcap=3.0),
    "looped": dict(WIDE_TINY, n_embd=128, n_passes=2, post_norms=True,
                   exit_gate=True, **ROPE),
    # what the rule passes by: a width of no whole tiles (XL's 25 x 64 is
    # 12.5; here 5 x 64 and 4 x 8), one KV head, heads of 128, a latent, a
    # hybrid stack's rows beside a state
    "five-heads-of-64": dict(WIDE_TINY, n_head=5, n_embd=320),
    "narrow": dict(WIDE_TINY),
    "mqa-rope": dict(WIDE_TINY, n_kv_head=1, **ROPE),
    "heads-of-128": dict(WIDE_TINY, n_head=2, n_embd=256),
    "looped-heads-of-128": dict(WIDE_TINY, n_head=2, n_embd=256, n_passes=2,
                                post_norms=True, exit_gate=True, **ROPE),
    "latent": dict(WIDE_TINY, rope=True, rope_interleave=True, swiglu=True,
                   rmsnorm=True, tie_weights=False, kv_lora_rank=16,
                   qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                   n_dense_layers=1, ffn_dim=48, n_experts=8, moe_top_k=2,
                   moe_ffn_dim=16, n_shared_experts=2, moe_scoring="sigmoid",
                   moe_route_scale=2.448),
    "hybrid": dict(model_type="minicpm-sala-tiny"),
    # the capacity route (mixtral-tiny's keys): softmax-routed experts, each
    # with room for its share of the tokens
    "capacity": dict(WIDE_TINY, n_kv_head=2, n_experts=4, moe_top_k=2, **ROPE),
}


def per_head(monkeypatch):
    """The rule as it was: every head an axis entry of its own."""
    monkeypatch.setattr(gen, "LANE_TILE", 1)


@functools.cache
def _form_model(form: str, over: Tuple):
    cfg = GPTConfig.make(**{**FORMS[form], **dict(over)})
    return cfg, _init(jax.random.key(1), cfg)


def form_model(form: str, **over):
    """``(cfg, params)`` of a form of row, made once a session."""
    return _form_model(form, tuple(sorted(over.items())))
