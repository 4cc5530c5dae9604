"""The ``program`` record (ISSUE 34): every compiled program hands over which
named scope each of its instructions came from, filed by its owner as a
pinned record of its tracer. CPU, tiny engine and tiny trainer."""

import ast
import json
import os
import re
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import monitoring

import mingpt_distributed_tpu
from mingpt_distributed_tpu.analysis.hlo_audit import (
    collective_inventory,
    lower_programs,
)
from mingpt_distributed_tpu.config import (
    DataConfig,
    GPTConfig,
    MeshConfig,
    OptimizerConfig,
    TrainerConfig,
)
from mingpt_distributed_tpu.data.char_dataset import CharDataset
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.parallel import mesh as mesh_lib
from mingpt_distributed_tpu.serving import InferenceServer, Request
from mingpt_distributed_tpu.serving.engine import DecodeEngine
from mingpt_distributed_tpu.telemetry import SpanTracer
from mingpt_distributed_tpu.telemetry import programs as program_lib
from mingpt_distributed_tpu.telemetry.programs import SCOPES, scope_table
from mingpt_distributed_tpu.training.trainer import GPTTrainer
from program_digests import kernel_matmuls, pallas_calls

LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@pytest.fixture(scope="module")
def cfg_params():
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32")
    return cfg, gpt.init(jax.random.key(0), cfg)


@pytest.fixture
def lowerings():
    """Counts the programs JAX lowers while the test runs."""
    seen = []
    listener = lambda name, _secs, **_kw: name == LOWERED and seen.append(name)
    monitoring.register_event_duration_secs_listener(listener)
    yield seen
    monitoring.unregister_event_duration_listener(listener)


@pytest.fixture(scope="module", autouse=True)
def executables_of_this_tree():
    """The suite's persistent cache keys a program less its scope names
    (``test_a_mark_is_no_part_of_the_persistent_cache_s_key``): a cache an
    earlier state of the code filled would serve these tests that state's
    tables. They compile their own."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def programs_of(tracer):
    return [r for r in tracer.records() if r["kind"] == "program"]


def make_trainer(tmp_path, mesh_cfg, n_devices, gpt_kw=None, block_size=16,
                 batch_size=16, **trainer_kw):
    ds = CharDataset(
        DataConfig(path="<inline>", block_size=block_size, train_split=0.9),
        text="the step is lowered as it runs, shardings and all. " * 60)
    train, test = ds.split()
    gcfg = GPTConfig.make(**{**dict(
        n_layer=2, n_head=2, n_embd=32, vocab_size=ds.vocab_size,
        block_size=block_size, embd_pdrop=0.0, resid_pdrop=0.0,
        attn_pdrop=0.0, dtype="float32"), **(gpt_kw or {})})
    tcfg = TrainerConfig.make(
        max_epochs=1, batch_size=batch_size, grad_norm_clip=1.0,
        save_every=100,
        log_every=1000, seed=7, snapshot_path=str(tmp_path / "s.msgpack"),
        **trainer_kw)
    mesh = mesh_lib.make_mesh(mesh_cfg, devices=jax.devices()[:n_devices])
    return GPTTrainer(tcfg, gcfg, OptimizerConfig(learning_rate=1e-2), train,
                      test, mesh=mesh)


# ---------------------------------------------------------------------
# scope_table: the compiled text's own op_name metadata
# ---------------------------------------------------------------------

SYNTH = textwrap.dedent("""\
    HloModule jit__decode_impl, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

    %fused_computation (param_0: f32[8]) -> f32[8] {
      %param_0 = f32[8]{0} parameter(0)
      ROOT %inside.1 = f32[8]{0} negate(%param_0), metadata={op_name="jit(f)/sample/neg"}
    }

    %body.2 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
      %arg = (s32[], f32[8]{0:T(8)S(1)}) parameter(0)
      %gte.1 = f32[8]{0} get-tuple-element(%arg), index=1
      %fusion.7 = f32[8]{0:T(8)S(1)} fusion(%gte.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/latent_attn/while/body/mul"}
      %call.3 = f32[8]{0} call(%fusion.7), to_apply=%called.4
      ROOT %tuple.9 = (s32[], f32[8]{0}) tuple(%gte.1, %call.3)
    }

    %cond.2 (arg.1: (s32[], f32[8])) -> pred[] {
      %arg.1 = (s32[], f32[8]{0}) parameter(0)
      ROOT %lt.1 = pred[] compare(%arg.1, %arg.1), direction=LT
    }

    %called.4 (p: f32[8]) -> f32[8] {
      %p = f32[8]{0} parameter(0)
      ROOT %copy.5 = f32[8]{0} copy(%p), metadata={op_name="jit(f)/transpose(jvp(attn))/copy"}
    }

    %branch_a (pa: f32[8]) -> f32[8] {
      %pa = f32[8]{0} parameter(0)
      ROOT %sort.1 = f32[8]{0} sort(%pa), dimensions={0}, to_apply=%cond.2, metadata={op_name="jit(f)/sample/cond/branch_1_fun/sort"}
    }

    %branch_b (pb: f32[8]) -> f32[8] {
      ROOT %pb = f32[8]{0} parameter(0)
    }

    %never_run (pn: f32[8]) -> f32[8] {
      ROOT %pn = f32[8]{0} parameter(0)
    }

    ENTRY %main.1 (Arg_0.1: f32[8]) -> f32[8] {
      %Arg_0.1 = f32[8]{0} parameter(0)
      %tuple.1 = (s32[], f32[8]{0}) tuple(%Arg_0.1, %Arg_0.1)
      %while.1 = (s32[], /*index=1*/f32[8]{0:T(8)S(1)}) while(%tuple.1), condition=%cond.2, body=%body.2, metadata={op_name="jit(f)/latent_attn/while"}
      %gte.2 = f32[8]{0} get-tuple-element(%while.1), index=1
      %conditional.1 = f32[8]{0} conditional(%gte.2, %gte.2, %gte.2), branch_computations={%branch_a, %branch_b}, metadata={op_name="jit(f)/sample/cond"}
      %dus.1 = f32[8]{0} dynamic-update-slice(%conditional.1, %gte.2), metadata={op_name="jit(f)/kv_layout/dynamic_update_slice"}
      ROOT %fusion.8 = f32[8]{0} fusion(%dus.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/not_a_scope/kv_layout_not/add"}
    }
    """)


def test_scope_table_follows_containers_and_not_fusions():
    table = scope_table(SYNTH)
    # the entry's own, scoped or not: every instruction is in the table
    assert table["while.1"] == "latent_attn"
    assert table["dus.1"] == "kv_layout"
    assert table["conditional.1"] == "sample"
    assert table["fusion.8"] == "" and table["tuple.1"] == ""
    # inside the while's body, its call and the conditional's branches the
    # scope stays; a transform's wrapping is seen through
    assert table["fusion.7"] == "latent_attn"
    assert table["copy.5"] == "attn"
    assert table["sort.1"] == "sample"
    assert table["lt.1"] == "" and table["pb"] == ""
    # a fused computation's insides and a computation nothing runs are not
    # instructions a profile shows
    assert "inside.1" not in table and "pn" not in table


def test_an_instruction_inside_a_real_while_keeps_its_scope():
    def f(x):
        with jax.named_scope("kv_layout"):
            x = jax.lax.fori_loop(
                0, 3, lambda i, c: jnp.sin(c) * i.astype(c.dtype), x)
        return x + 1

    text = jax.jit(f).lower(jnp.ones(8)).compile().as_text()
    table = scope_table(text)
    bodies = re.findall(r"body=%?([\w.\-]+)", text)
    assert bodies, "the loop was unrolled: make the test's loop longer"
    body_text = text.split("%" + bodies[0] + " ", 1)[1].split("\n}", 1)[0]
    inside = [m for m in re.findall(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=", body_text,
                                    re.M) if table.get(m) == "kv_layout"]
    assert inside, "no instruction of the while's body is under the scope"
    assert "" in table.values()         # the add outside the scope


def test_scopes_is_the_list_of_the_packages_named_scope_literals():
    root = os.path.dirname(mingpt_distributed_tpu.__file__)
    used = {}
    for folder, _, files in os.walk(root):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) and isinstance(
                        node.func, ast.Attribute) \
                        and node.func.attr == "named_scope":
                    assert len(node.args) == 1 and isinstance(
                        node.args[0], ast.Constant), \
                        f"{path}:{node.lineno}: a scope is a string literal"
                    used.setdefault(node.args[0].value, []).append(
                        f"{os.path.relpath(path, root)}:{node.lineno}")
    assert set(used) - set(SCOPES) == set(), \
        f"named_scope literals missing from telemetry.programs.SCOPES: {used}"
    assert set(SCOPES) - set(used) == set(), "SCOPES holds names nothing uses"
    assert len(set(SCOPES)) == len(SCOPES)


# ---------------------------------------------------------------------
# SpanTracer.pin
# ---------------------------------------------------------------------


def test_a_pinned_record_outlives_the_ring_and_is_made_once():
    calls = []

    def make():
        calls.append(1)
        return [{"name": "jit_f", "ts": 1.0, "scopes": {"a": ""}}]

    tracer = SpanTracer(capacity=2)
    tracer.pin("program", make)
    assert calls == []                      # nobody has read yet
    for i in range(10):
        with tracer.span("s", i=i):
            pass
    first, second = tracer.records(), tracer.records()
    assert calls == [1]
    assert [r["kind"] for r in first] == ["program", "span", "span"]
    assert first[0]["name"] == "jit_f" and first == second
    assert [r["i"] for r in first[1:]] == [8, 9] and tracer.dropped == 8


def test_a_disabled_tracer_never_calls_make():
    tracer = SpanTracer(enabled=False)
    tracer.pin("program", lambda: pytest.fail("made for a disabled tracer"))
    assert tracer.records() == []


@pytest.mark.parametrize("attach_first", [True, False],
                         ids=["sink-then-pin", "pin-then-sink"])
def test_the_jsonl_sink_gets_the_pinned_record_once(tmp_path, attach_first):
    path = tmp_path / "spans.jsonl"
    tracer = SpanTracer()
    make = lambda: [{"name": "jit_f", "ts": 2.0, "family": "decode",
                     "variant": "", "scopes": {"fusion.1": "sample"}}]
    if attach_first:
        tracer.attach_jsonl(str(path))
        tracer.pin("program", make)
    else:
        tracer.pin("program", make)
        tracer.attach_jsonl(str(path))
    with tracer.span("s"):
        pass
    tracer.records()
    tracer.close()
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["kind"] for r in lines] == ["program", "span"]
    assert lines[0]["scopes"] == {"fusion.1": "sample"}
    assert lines[0]["name"] == "jit_f" and lines[0]["ts"] == 2.0


# ---------------------------------------------------------------------
# the owners' records
# ---------------------------------------------------------------------


def test_the_servers_records_are_of_the_programs_that_ran(cfg_params,
                                                         lowerings):
    cfg, params = cfg_params
    tracer = SpanTracer()
    server = InferenceServer(params, cfg, n_slots=2, tracer=tracer,
                             warmup=True, prefill_buckets=(8, 16))
    server.submit(Request(prompt=[1, 2, 3], max_new_tokens=3))
    server.run_until_drained()
    counts, lowered = server.compile_counts(), len(lowerings)
    records = programs_of(tracer)
    # made from the executables the jit calls built: nothing lowered again,
    # and the jit call caches (the recompile watchdog's counts) as they were
    assert len(lowerings) == lowered
    assert server.compile_counts() == counts
    assert server.watchdog.recompiles == 0
    assert [(r["name"], r["family"], r["variant"]) for r in records] == [
        *(("jit__prefill_impl", "prefill", f"b{b}")
          for b in server.engine.buckets),
        ("jit__decode_impl", "decode", "")]
    assert len(server.engine.buckets) == 3
    decode = records[-1]["scopes"]
    scoped = set(decode.values())
    assert {"sample", "kv_layout", "cached_attn", ""} <= scoped <= \
        set(SCOPES) | {""}
    assert all(isinstance(k, str) and not k.startswith("%") for k in decode)
    # the table is of the text the audit reads, through the one lowering loop
    art = lower_programs(
        p for p in server.engine.programs() if p[0] == "decode")
    assert scope_table(art[("decode", "")].hlo_text) == decode
    assert "sample" in set(records[0]["scopes"].values())
    json.dumps(records)                     # a record is plain data


def test_a_server_with_a_disabled_tracer_lowers_nothing_more(cfg_params,
                                                             lowerings):
    cfg, params = cfg_params

    def build(tracer):
        before = len(lowerings)
        server = InferenceServer(params, cfg, n_slots=2, tracer=tracer,
                                 warmup=True, prefill_buckets=(8, 16))
        server.submit(Request(prompt=[1, 2, 3], max_new_tokens=3))
        server.run_until_drained()
        server.tracer.records()
        return len(lowerings) - before, server.compile_counts()

    with_default = build(None)              # the server's own disabled tracer
    disabled = build(SpanTracer(enabled=False))
    enabled = build(SpanTracer())
    assert with_default == disabled == enabled


def test_programs_hands_over_abstract_values_with_the_live_shardings(
        cfg_params):
    cfg, params = cfg_params
    mesh = mesh_lib.make_mesh(MeshConfig(dp=1, tp=2),
                              devices=jax.devices()[:2])
    server = InferenceServer(params, cfg, n_slots=2, mesh=mesh)
    for _family, _variant, _jitted, args, _kw in server.engine.programs():
        live = jax.tree.leaves(server.engine.program_params) \
            + jax.tree.leaves(server.engine.pool.cache)
        handed = jax.tree.leaves(args[:2])
        assert len(handed) == len(live)
        for a, b in zip(handed, live):
            assert isinstance(a, jax.ShapeDtypeStruct)
            assert (a.shape, a.dtype, a.sharding) == (
                b.shape, b.dtype, b.sharding)
        assert not any(isinstance(x, jax.Array)
                       for x in jax.tree.leaves(args))
    # an uncommitted array's sharding is left out, as a call leaves it out
    got = program_lib.abstract({"w": jnp.ones(3), "n": np.int32(1)})
    assert got["w"].sharding is None and got["n"].shape == ()


def test_the_trainers_record_names_the_step(tmp_path):
    trainer = make_trainer(tmp_path, MeshConfig(dp=1), 1)
    [record] = programs_of(trainer.tracer)
    assert (record["name"], record["family"], record["variant"]) == (
        "jit_train_step", "train_step", "dense")
    assert {"attn", "mlp", "ce", "optimizer", ""} <= set(
        record["scopes"].values())


#: the parts of a layer and the stack's two ends (PR 55)
PARTS = {"qkv", "attn_out", "ffn", "norm", "head", "embed"}


@pytest.fixture(scope="module")
def served(cfg_params):
    cfg, params = cfg_params
    tracer = SpanTracer()
    InferenceServer(params, cfg, n_slots=2, tracer=tracer, warmup=True,
                    prefill_buckets=(8, 16))
    return programs_of(tracer)


@pytest.mark.parametrize("family", ["prefill", "decode"])
def test_a_served_table_names_the_parts_of_a_layer(served, family):
    """Every part of a layer says which part it is in every program that
    runs it: the cached forward's tables hold the six marks, so that
    ``engine.unscoped_ms_per_step`` is the true residue (PERF.md, section
    3). ``attn`` and ``mlp`` stay ``gpt._block``'s own: the cached bodies
    carry neither."""
    records = [r for r in served if r["family"] == family]
    assert records and {r["family"] for r in served} == {"prefill", "decode"}
    for record in records:
        scoped = set(record["scopes"].values())
        assert PARTS <= scoped, (record["variant"], PARTS - scoped)
        assert not {"attn", "mlp", "ce", "optimizer"} & scoped
        assert PARTS <= set(record["lowered_scopes"])
        assert "stale_scopes" not in record


@pytest.mark.parametrize("arch, own", [
    ("latent-experts", {"latent_attn", "moe_experts", "moe_shared"}),
    ("hybrid", {"lightning_step", "sparse_select", "sparse_attend"}),
    ("looped", {"cached_attn", "exit_gate"})])
def test_an_architecture_s_own_scopes_stay_innermost(arch, own):
    """``moe_experts`` inside ``ffn``, a mixer's step between ``qkv`` and
    ``attn_out``: the innermost mark names an instruction, so the readers
    of PRs 34-37 keep their rows, and the parts' marks take the rest."""
    from test_cast_once import model

    cfg, params = model(arch, "bfloat16")
    engine = DecodeEngine(params, cfg, n_slots=3)
    [record] = program_lib.program_records(
        p for p in engine.programs() if p[0] == "decode")
    scoped = set(record["scopes"].values())
    assert own | PARTS <= scoped, (own | PARTS) - scoped
    assert "stale_scopes" not in record


def test_the_trainers_table_holds_the_parts_with_attn_and_mlp_the_residue(
        tmp_path):
    """Training's head stays inside ``ce``; ``attn`` and ``mlp`` are what
    is left of a sublayer once projections and norms are named (the
    attention itself, the residual sums, dropout)."""
    [step] = programs_of(make_trainer(tmp_path, MeshConfig(dp=1), 1).tracer)
    assert step["family"] == "train_step" and "stale_scopes" not in step
    scoped = set(step["scopes"].values())
    assert (PARTS - {"head"}) | {"attn", "mlp", "ce", "optimizer"} <= scoped
    assert "head" not in scoped
    # the MLP's matmuls left ``mlp`` and the projections' left ``attn``,
    # which keeps the attention's own two products
    trainer = make_trainer(tmp_path, MeshConfig(dp=1), 1)
    batch = trainer._put_batch(next(iter(trainer.train_iter.epoch_batches())))
    text = trainer._train_step.lower(
        trainer.state, batch, trainer.base_rng).as_text(debug_info=True)
    dots = [program_lib._scope_of(name) for name in re.findall(
        r'loc\("([^"]*dot_general)"\(', text)]
    assert {"qkv", "attn", "attn_out", "ffn"} <= set(dots)
    assert "mlp" not in dots


@pytest.fixture
def index(monkeypatch):
    """An index of this test's own in the process's place."""
    fresh = program_lib._Index(capacity=8)
    monkeypatch.setattr(program_lib, "FILED", fresh)
    return fresh


@pytest.mark.parametrize("mesh_cfg, n_devices", [
    (MeshConfig(dp=1), 1), (MeshConfig(dp=1, fsdp=4), 4)],
    ids=["one-device", "fsdp4"])
def test_a_step_files_itself_at_its_first_call_and_holds_no_array(
        tmp_path, index, lowerings, mesh_cfg, n_devices):
    """The benchmark's training loop drives ``_put_batch`` and
    ``_train_step`` itself and drops the trainer: the step's table must
    outlive both, cost a step one flag test, and be made, when somebody
    reads, from the executable the calls built (XL's step takes minutes to
    compile: a second lowering ended PR 52's traced run)."""
    trainer = make_trainer(tmp_path, mesh_cfg, n_devices)
    assert len(index) == 0                  # nothing is filed by building
    jax.make_jaxpr(trainer._train_step)(
        trainer.state, trainer._put_batch(
            next(iter(trainer.train_iter.epoch_batches()))),
        trainer.base_rng)
    assert len(index) == 0                  # nor by a trace of the wrapper
    for xy in list(trainer.train_iter.epoch_batches())[:2]:
        trainer.state, m = trainer._train_step(
            trainer.state, trainer._put_batch(xy), trainer.base_rng)
    jax.block_until_ready(m)
    assert len(index) == 1                  # once, at the first call
    (_, _, jitted, args, kwargs), _ = next(iter(index._entries.values()))
    assert jitted is trainer._train_step.jitted and kwargs == {}
    assert all(isinstance(x, jax.ShapeDtypeStruct)
               for x in jax.tree.leaves(args))
    # the owner and its buffers go, as ``train_cell.run`` lets them
    for leaf in jax.tree.leaves(trainer.state):
        leaf.delete()
    del trainer, jitted, args
    before = len(lowerings)
    [record] = program_lib.filed_records()
    assert len(lowerings) == before         # lowering and executable: the
    assert (record["kind"], record["name"], record["family"]) == (
        "program", "jit_train_step", "train_step")      # jit's own caches
    assert PARTS - {"head"} <= set(record["scopes"].values())
    assert "stale_scopes" not in record
    if n_devices > 1:       # the table of the sharded step that ran
        assert any(re.match(r"all-(gather|reduce)|reduce-scatter", k)
                   for k in record["scopes"])
    assert program_lib.filed_records() == [record]      # made once, kept
    assert len(lowerings) == before


def test_the_tracers_record_is_the_filed_one(tmp_path, index):
    """``tracer.pin`` is built on the index's record, not a second maker:
    before any call the trainer files the step itself, with the abstract
    values ``programs()`` states."""
    trainer = make_trainer(tmp_path, MeshConfig(dp=1), 1)
    [pinned] = programs_of(trainer.tracer)
    [filed] = program_lib.filed_records()
    assert pinned == filed and len(index) == 1


def test_the_profile_window_leaves_the_tables_beside_the_trace(tmp_path,
                                                               index):
    """``TrainerConfig.profile_dir``: when the trainer's own window closes,
    ``programs.json`` holds the filed records, so the profile can be summed
    by scope without ``spans_jsonl``."""
    trainer = make_trainer(tmp_path, MeshConfig(dp=1), 1, max_steps=3,
                           profile_dir=str(tmp_path / "profile"),
                           profile_steps=(1, 2))
    trainer.train()
    with open(tmp_path / "profile" / "programs.json") as f:
        [record] = json.load(f)
    assert record["name"] == "jit_train_step" and record["kind"] == "program"
    assert {"qkv", "ffn", "ce"} <= set(record["scopes"].values())


def test_a_mark_is_no_part_of_the_persistent_cache_s_key(tmp_path):
    """JAX's persistent cache keys a program less its debug information,
    and a ``named_scope`` is debug information: the second of two
    lowerings that differ only in a mark is served the first's executable,
    whose text carries the first's names. The record says so
    (``stale_scopes``), and a reader takes nothing from it."""
    from jax.experimental.compilation_cache import compilation_cache

    def marked(scope):
        def f(x, w):
            with jax.named_scope(scope):
                y = x @ w
            return jnp.sin(y) + 1.0
        return jax.jit(f)

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    was = {name: getattr(jax.config, name) for name in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        [first] = program_lib.program_records(
            [("f", "", marked("qkv"), (x, x), {})])
        [second] = program_lib.program_records(
            [("f", "", marked("ffn"), (x, x), {})])
    finally:
        for name, value in was.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    assert "stale_scopes" not in first
    assert first["lowered_scopes"] == ["qkv"]
    assert "qkv" in set(first["scopes"].values())
    assert second["lowered_scopes"] == ["ffn"]
    assert "qkv" in set(second["scopes"].values())      # the first's names
    assert second["stale_scopes"] == ["ffn", "qkv"]


def test_the_trainers_step_takes_the_fused_flash_backward(tmp_path):
    """PR 50: the step a flash trainer runs holds, a layer, one forward
    kernel and ONE backward kernel named ``flash_bwd_fused`` (the name the
    benchmark's ``kernel.flash_bwd_ms_per_step`` and ``breakdown`` read),
    and neither of the split pair; its ``program`` record files them under
    ``attn``. On the CPU the interpreted kernel is a loop of plain
    instructions, so the names are counted in the step's own jaxpr here;
    ``tests/test_cast_once.py`` holds the record's table of a step compiled
    for the described chip."""
    trainer = make_trainer(
        tmp_path, MeshConfig(dp=1), 1,
        gpt_kw=dict(n_layer=3, n_embd=128, attention="flash",
                    unroll_layers=True))
    batch = trainer._put_batch(next(iter(trainer.train_iter.epoch_batches())))
    jaxpr = jax.make_jaxpr(trainer._train_step)(
        trainer.state, batch, trainer.base_rng)
    # by equation, not by text: since PR 54 the layers share one jitted
    # caller a kernel, whose body the text prints once
    calls = {n: len(pallas_calls(jaxpr, n)) for n in (
        "flash_fwd", "flash_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv")}
    assert calls == {"flash_fwd": 3, "flash_bwd_fused": 3,
                     "flash_bwd_dq": 0, "flash_bwd_dkv": 0}
    [record] = programs_of(trainer.tracer)
    assert record["family"] == "train_step"
    assert "attn" in set(record["scopes"].values())


def test_the_trainers_step_walks_diagonal_cells_as_staircases(tmp_path):
    """PR 54: at the training cells' kernel shape (T = 1,024, so blocks of
    512; heads of 64 in pairs) the step a flash trainer runs holds, in
    every layer's ``flash_fwd`` and ``flash_bwd_fused``, a diagonal body of
    four row groups a sub-head: 2 x 2 x 4 and 5 x 2 x 4 ``dot_general``s
    beside the full cell's 2 x 2 and 5 x 2. Traced, never run;
    ``tests/test_cast_once.py`` holds the kernels' names in a step
    compiled for the described chip, ``tests/test_flash_attention.py``
    the counts at the other shapes."""
    trainer = make_trainer(
        tmp_path, MeshConfig(dp=1), 1, block_size=1024, batch_size=2,
        gpt_kw=dict(n_layer=3, n_embd=128, attention="flash",
                    dtype="bfloat16", unroll_layers=True))
    batch = trainer._put_batch(next(iter(trainer.train_iter.epoch_batches())))
    jaxpr = jax.make_jaxpr(trainer._train_step)(
        trainer.state, batch, trainer.base_rng)
    assert kernel_matmuls(jaxpr, "flash_fwd") == [[16, 4]] * 3
    assert kernel_matmuls(jaxpr, "flash_bwd_fused") == [[40, 10]] * 3


def test_the_trainers_spans_jsonl_holds_the_record(tmp_path):
    path = tmp_path / "spans.jsonl"
    trainer = make_trainer(tmp_path, MeshConfig(dp=1), 1,
                           spans_jsonl=str(path))
    trainer.tracer.close()
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    [record] = [r for r in lines if r["kind"] == "program"]
    assert record["name"] == "jit_train_step" and "optimizer" in set(
        record["scopes"].values())


def test_on_a_mesh_the_trainers_table_is_of_the_step_that_runs(tmp_path):
    """fsdp=4 on four CPU devices: the record's table holds the collectives
    of the executable a call with the live, sharded state builds."""
    trainer = make_trainer(tmp_path, MeshConfig(dp=1, fsdp=4), 4)
    batch = trainer._put_batch(next(iter(trainer.train_iter.epoch_batches())))
    # the step files itself with this call's own abstract values (PR 55)
    trainer.state, _ = trainer._train_step(
        trainer.state, batch, trainer.base_rng)
    ran = trainer._train_step.lower(
        trainer.state, batch, trainer.base_rng).compile().as_text()
    assert {c["op"] for c in collective_inventory(ran)} >= {
        "all-gather", "all-reduce"}
    [record] = programs_of(trainer.tracer)
    assert record["scopes"] == scope_table(ran)
    named = lambda table: sorted(
        k for k in table if re.match(r"all-(gather|reduce)|reduce-scatter", k))
    assert named(record["scopes"]) and \
        named(record["scopes"]) == named(scope_table(ran))
