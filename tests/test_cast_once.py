"""The serving engine casts its weights to the compute dtype once (PR 29).

``DecodeEngine`` hands its programs ``program_params``: the tree it was
given, but for the leaves the cached forward reads only through
``.astype(<compute dtype>)``, which ``generate.cast_once_params`` stores in
``cfg.dtype`` at construction. What must hold, on the CPU at tiny sizes:

* the arithmetic is unchanged, bit for bit: the engine's own prefill and
  decode programs give the same tokens (greedy and sampling lanes) and the
  same cache on the tree handed in and on the tree they are given, for
  every architecture the tests build;
* a float32 engine holds one tree: ``program_params`` IS ``params``;
* ``engine.params`` stays the tree that was handed in (the benchmark
  digests it and hands it to its float32 reference);
* under a mesh every leaf of ``program_params`` keeps the sharding of the
  leaf it was cast from;
* the compiled decode program converts no weight, and ``ServingMetrics``
  says what the programs read;
* nor does it write a projection's weight again (PR 49): where the product
  of ``wq``, ``wk`` or ``wv`` is turned per head, a decode step keeps it
  behind a boundary (``gpt.head_projection``), which adds its own equations
  to the decode program and nothing else, changes no number, and leaves
  every prefill program as it was.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from mingpt_distributed_tpu.config import ConfigError, GPTConfig, MeshConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.ops import attention as attn_ops
from mingpt_distributed_tpu.ops import layers as L
from mingpt_distributed_tpu.parallel import mesh as mesh_lib
from mingpt_distributed_tpu.serving import InferenceServer
from mingpt_distributed_tpu.serving.engine import DecodeEngine

BLOCK = 32
SLOTS = 3
STEPS = 8
BASE = dict(n_layer=2, n_head=4, n_embd=32, vocab_size=64, block_size=BLOCK,
            embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0)
LLAMA = dict(rope=True, swiglu=True, rmsnorm=True, n_kv_head=2)
ARCHS = {
    "gpt2-untied": dict(tie_weights=False),
    "gpt2-tied": dict(tie_weights=True),
    "rope-swiglu-rmsnorm": dict(LLAMA, tie_weights=False),
    # benchmarks/tests/fixtures/rope-experts.json, cut as its "tiny" is
    "rope-experts": dict(rope=True, swiglu=True, rmsnorm=True, ffn_mult=0.5,
                         n_experts=8, moe_top_k=2, moe_capacity_factor=4.0,
                         tie_weights=False),
    # benchmarks/configs/kanana-2-30b-a3b.json, cut as its "tiny" is: a
    # latent cache, a dense layer before dropless experts and a shared one
    "latent-experts": dict(rope=True, rope_interleave=True, swiglu=True,
                           rmsnorm=True, tie_weights=False, kv_lora_rank=16,
                           qk_nope_head_dim=8, qk_rope_head_dim=4,
                           v_head_dim=8, n_dense_layers=1, ffn_dim=48,
                           n_experts=8, moe_top_k=2, moe_ffn_dim=16,
                           n_shared_experts=2, moe_scoring="sigmoid",
                           moe_route_scale=2.448),
    # benchmarks/configs/minicpm-sala.json, cut further than its "tiny": a
    # linear layer's state under a sparse layer's rows, gated and scaled
    "hybrid": dict(LLAMA, tie_weights=False,
                   mixer_types=("lightning-attn", "minicpm4"),
                   lightning_heads=4, lightning_head_dim=8, qk_norm=True,
                   output_gate=True, scale_emb=12.0, scale_depth=1.4,
                   scale_depth_layers=32, dim_model_base=8,
                   sparse_kernel_size=4, sparse_kernel_stride=2,
                   sparse_block_size=8, sparse_topk=2, sparse_window=8,
                   sparse_dense_len=16),
    # benchmarks/configs/ouro-2.6b.json, cut further than its "tiny": the
    # layers run three times, a norm after each sublayer, an exit gate
    "looped": dict(rope=True, swiglu=True, rmsnorm=True, tie_weights=False,
                   ffn_dim=48, n_passes=3, post_norms=True, exit_gate=True,
                   residual_dtype="float32"),
}


@functools.cache
def model(arch, dtype):
    """Config and parameters with every leaf away from its initial value:
    biases, norm scales and ``wpe`` start as zeros and ones, where a cast
    too many would not show. Made once a session (op by op: the draws of a
    shape are one small program whatever the architecture, where one jitted
    ``init`` an architecture costs 1.6 s each)."""
    cfg = GPTConfig.make(**BASE, **ARCHS[arch], dtype=dtype)
    params = gpt.init(jax.random.key(11), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(12), len(leaves))
    leaves = [a + 0.05 * jax.random.normal(k, a.shape, a.dtype)
              for a, k in zip(leaves, keys)]
    return cfg, jax.tree.unflatten(tree, leaves)


def leaves_by_path(tree):
    return {jax.tree_util.keystr(p): a
            for p, a in jax.tree_util.tree_leaves_with_path(tree)}


def leaf_name(path):
    """``"['blocks']['wq']"`` -> ``"wq"``."""
    return path.rsplit("'", 2)[-2]


def bits(a):
    a = np.asarray(a)
    return a.view(np.uint16) if a.dtype == jnp.bfloat16 else a


def serve(engine, tree):
    """A prefill a lane and STEPS decode steps through the engine's own
    programs on ``tree``, from an empty pool: lane 0 greedy, lane 1 sampling
    under top-k, lane 2 under top-p. Returns (every token, the pool)."""
    cache = jax.tree.map(jnp.zeros_like, engine.pool.cache)
    if engine.kv_sharding is not None:
        # the rows' leaves; a counter stays whole on every device
        cache = {n: jax.device_put(a, engine.kv_sharding) if a.ndim == 5
                 else a for n, a in cache.items()}
    temps = np.array([1.0, 0.8, 1.3], np.float32)
    top_ks = np.array([0, 5, 0], np.int32)
    top_ps = np.array([1.0, 1.0, 0.9], np.float32)
    sample = np.array([False, True, True])
    seeds = np.array([3, 2_147_483_659, 77], np.uint32)
    lengths = [5, 9, 16]
    rng = np.random.default_rng(5)
    toks = []
    cur = np.zeros(SLOTS, np.int32)
    for slot, n in enumerate(lengths):
        padded = np.zeros(16, np.int32)
        padded[:n] = rng.integers(1, BASE["vocab_size"], size=n)
        tok, cache = engine._prefill_jit(
            tree, cache, padded, np.int32(n), np.int32(0), np.int32(slot),
            temps[slot], top_ks[slot], top_ps[slot], sample[slot],
            seeds[slot])
        cur[slot] = int(tok)
    toks.append(cur.copy())
    pos = np.array(lengths, np.int32)
    for step in range(STEPS):
        nxt, cache = engine._decode_jit(
            tree, cache, cur, pos, temps, top_ks, top_ps, sample, seeds,
            np.full(SLOTS, step + 1, np.int32))
        cur = np.asarray(nxt)
        toks.append(cur.copy())
        pos = pos + 1
    return np.stack(toks), {n: bits(a) for n, a in cache.items()}


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_programs_read_a_tree_cast_once(arch, dtype, tp):
    cfg, params = model(arch, dtype)
    mesh = None
    if tp > 1:
        mesh = mesh_lib.make_mesh(MeshConfig(tp=tp),
                                  devices=jax.devices()[:tp])
        refused = "latent" if cfg.kv_lora_rank else \
            "hybrid stack" if cfg.mixer_types else None
        if refused:
            with pytest.raises(ConfigError, match=refused):
                DecodeEngine(params, cfg, n_slots=SLOTS, mesh=mesh)
            return
    engine = DecodeEngine(params, cfg, n_slots=SLOTS, prefill_len=16,
                          prefill_buckets=[16], mesh=mesh)
    handed, held = leaves_by_path(params), leaves_by_path(engine.params)
    read = leaves_by_path(engine.program_params)
    assert handed.keys() == held.keys() == read.keys()

    # engine.params: the tree handed in (placed by the engine under a mesh)
    for path, leaf in handed.items():
        if mesh is None:
            assert held[path] is leaf, path
        else:
            assert held[path].dtype == leaf.dtype == jnp.float32
            np.testing.assert_array_equal(held[path], leaf)
    if mesh is not None:
        want = leaves_by_path(mesh_lib.param_shardings(mesh, params))
        for path in held:
            assert held[path].sharding == want[path], path
            assert read[path].sharding == held[path].sharding, path

    # nor is a leaf committed that was not: a committed argument commits the
    # programs' outputs, and the next call with them is another jit entry
    # (the recompile watchdog counts it)
    for path in held:
        assert read[path].committed == held[path].committed == (
            mesh is not None), path

    cast = {p for p in read if read[p] is not held[p]}
    assert engine.n_cast_leaves == len(cast)
    if dtype == "float32":
        assert engine.program_params is engine.params
        assert not cast
        return

    for path in cast:
        assert leaf_name(path) in gen._CAST_ONLY_BLOCK_LEAVES | {"head"}
        assert read[path].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            bits(read[path]), bits(held[path].astype(jnp.bfloat16)))
    kept = {leaf_name(p) for p in read if p not in cast}
    assert {"wte", "lnf_scale", "ln1_scale", "ln2_scale"} <= kept
    if cfg.post_norms:
        assert {"ln1_post_scale", "ln2_post_scale", "exit_gate_w",
                "exit_gate_b"} <= kept
    assert kept.isdisjoint(gen._CAST_ONLY_BLOCK_LEAVES | {"head"})
    assert ("['head']" in cast) == (not cfg.tie_weights)

    toks_handed, pool_handed = serve(engine, engine.params)
    toks_read, pool_read = serve(engine, engine.program_params)
    np.testing.assert_array_equal(toks_handed, toks_read)
    # sampling lanes do sample: they leave the greedy lane's choices
    assert len({tuple(toks_read[:, lane]) for lane in range(SLOTS)}) == SLOTS
    for name in pool_handed:
        assert pool_read[name].any()
        np.testing.assert_array_equal(pool_handed[name], pool_read[name])
    if cfg.n_passes > 1:
        assert pool_read["k"].shape[0] == cfg.n_passes * cfg.n_layer
        # every plane was written: a pass that shared another's would not
        assert all(plane.any() for plane in pool_read["k"])


def weight_converts(text, params):
    """``convert`` instructions of compiled HLO ``text`` that take float32
    to bfloat16 and whose operand has the shape of a leaf of ``params`` that
    ``cast_once_params`` casts: the whole leaf, or one layer of it."""
    shapes = {params["head"].shape} if "head" in params else set()
    for stack in ("blocks", "dense_blocks"):
        for name, a in params.get(stack, {}).items():
            if name in gen._CAST_ONLY_BLOCK_LEAVES:
                shapes |= {a.shape, a.shape[1:], (1,) + a.shape[1:]}
    # the text names an operand without its shape: take it from the line
    # that defines the operand
    defined = {m.group(1): (m.group(2), m.group(3)) for m in re.finditer(
        r"%([\w.\-]+) = (\w+)\[([\d,]*)\]", text)}
    found = []
    for m in re.finditer(r"= bf16\[[\d,]*\]\S* convert\(%([\w.\-]+)\)", text):
        dtype, dims = defined[m.group(1)]
        if dtype == "f32" and tuple(
                int(d) for d in dims.split(",") if d) in shapes:
            found.append(m.group(0))
    return found


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip to compile for. The CPU backend
    computes bfloat16 in float32, so its compiled text is full of converts
    on either tree and says nothing; the TPU's compiler is installed here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile would be written to the persistent cache and can never
    # be read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("arch", ["gpt2-untied", "rope-experts",
                                  "latent-experts"])
def test_decode_program_converts_no_weight(arch, one_chip):
    cfg, params = model(arch, "bfloat16")
    engine = DecodeEngine(params, cfg, n_slots=SLOTS)
    # what the engine yields for the audit is what the serving loop runs
    (_, _, jitted, args, kwargs), = [
        p for p in engine.programs() if p[0] == "decode"]
    sizes = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    assert jitted is engine._decode_jit
    assert sizes(args[0]) == sizes(engine.program_params)

    def compiled_text(tree):
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            (tree,) + args[1:])
        return jitted.lower(*shapes, **kwargs).compile().as_text()

    assert weight_converts(compiled_text(engine.params), params)
    assert not weight_converts(compiled_text(engine.program_params), params)


#: the decode step's boundaries an architecture of ARCHS takes: three
#: projections a layer and pass where rope or ``qk_norm`` turns their product
#: per head, the latent's one (``wq``), none in GPT-2
BOUNDARIES = {"gpt2-untied": 0, "gpt2-tied": 0, "rope-swiglu-rmsnorm": 6,
              "rope-experts": 6, "latent-experts": 2, "hybrid": 6,
              "looped": 18}


def without_boundary(monkeypatch):
    """The parent's projections: ``L.dense`` and nothing after it."""
    monkeypatch.setattr(gpt, "head_projection",
                        lambda h, w, b, turned: L.dense(h, w, b))


def traces(engine):
    """{(family, variant): the program's jaxpr}, traced as ``programs()``
    states them."""
    return {(family, variant): jitted.trace(*args, **kwargs).jaxpr
            for family, variant, jitted, args, kwargs in engine.programs()}


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_the_boundary_adds_its_own_equations_and_changes_no_number(
        arch, monkeypatch):
    """The decode program is the parent's with one ``optimization_barrier``
    a turned projection, equation for equation; every prefill program is
    the parent's, text for text; the engine counts the boundaries off the
    program's own trace; and tokens (greedy and sampling lanes) and pool are
    the parent's bit for bit."""
    cfg, params = model(arch, "bfloat16")
    build = lambda: DecodeEngine(params, cfg, n_slots=SLOTS, prefill_len=16,
                                 prefill_buckets=[16])
    engine = build()
    new = traces(engine)
    toks, pool = serve(engine, engine.program_params)
    with monkeypatch.context() as patch:
        without_boundary(patch)
        parent = build()
        old = traces(parent)
        toks_parent, pool_parent = serve(parent, parent.program_params)

    names = lambda jaxpr: [e.primitive.name for e in jaxpr.eqns]
    decode = names(new["decode", ""])
    assert decode.count("optimization_barrier") == BOUNDARIES[arch]
    assert [n for n in decode if n != "optimization_barrier"] \
        == names(old["decode", ""])
    assert "optimization_barrier" not in names(old["decode", ""])
    assert engine.head_boundaries() == BOUNDARIES[arch]
    assert parent.head_boundaries() == 0
    for key in new.keys() - {("decode", "")}:
        assert str(new[key]) == str(old[key]), key

    np.testing.assert_array_equal(toks, toks_parent)
    assert pool.keys() == pool_parent.keys()
    for name in pool:
        np.testing.assert_array_equal(pool[name], pool_parent[name])


def test_a_barrier_over_anything_but_a_product_is_not_counted():
    """``gpt.head_boundaries`` reads a trace: a projection's product behind
    a barrier, with its bias or without, at any depth; not the barrier the
    walked attention keeps over a block of cached rows."""
    def program(x, w, b, rows):
        held = jax.lax.optimization_barrier(x @ w)
        biased = jax.lax.optimization_barrier(x @ w + b)
        inner = jax.lax.fori_loop(0, 2, lambda i, c: c + jnp.sum(
            jax.lax.optimization_barrier(x @ w)), 0.0)
        return held, biased, inner, jax.lax.optimization_barrier(rows[:2])
    x, w = jnp.ones((2, 4)), jnp.ones((4, 4))
    jaxpr = jax.make_jaxpr(program)(x, w, jnp.ones(4), jnp.ones((3, 4)))
    assert str(jaxpr).count("optimization_barrier") == 4
    assert gpt.head_boundaries(jaxpr.jaxpr) == 3


def test_the_summary_counts_the_boundaries_and_traces_nothing_more(
        monkeypatch):
    """``decode_head_boundaries`` rides every ``summary()``; after a warm-up
    it is read off the trace the warm-up's own step made (the jit's), so a
    serving loop's first reading of its counters traces nothing."""
    cfg, params = model("hybrid", "bfloat16")
    steps = []
    real = gpt.head_projection

    def counted(h, w, b, turned):
        steps.append(h.shape[1] == 1)
        return real(h, w, b, turned)
    monkeypatch.setattr(gpt, "head_projection", counted)
    server = InferenceServer(params, cfg, n_slots=SLOTS, warmup=True)
    assert sum(steps) == BOUNDARIES["hybrid"]     # the decode program, once
    traced = len(steps)
    assert server.metrics.summary()["decode_head_boundaries"] \
        == BOUNDARIES["hybrid"]
    assert len(steps) == traced
    assert server.compile_counts()["decode"] == 1
    cold = InferenceServer(params, cfg, n_slots=SLOTS, warmup=False)
    assert cold.metrics.summary()["decode_head_boundaries"] \
        == BOUNDARIES["hybrid"]
    assert cold.compile_counts()["decode"] == 0


#: small stacks at widths that fill lane tiles (four heads of 128 over a
#: width of 512, four layers: two to a mixer's stack), where the chip's
#: compiler does to a projection's weight what it does at a cell's sizes
TILED = dict(n_layer=4, n_head=4, n_embd=512, vocab_size=256, block_size=512,
             embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="bfloat16",
             tie_weights=False, rope=True, swiglu=True, rmsnorm=True)
TILED_FORMS = {
    "rope": dict(),
    "qk-norm-hybrid": dict(
        n_kv_head=1, mixer_types=("lightning-attn", "minicpm4") * 2,
        lightning_heads=4, lightning_head_dim=128, qk_norm=True,
        output_gate=True, scale_emb=12.0, scale_depth=1.4,
        scale_depth_layers=32, dim_model_base=256, sparse_kernel_size=32,
        sparse_kernel_stride=16, sparse_block_size=64, sparse_topk=4,
        sparse_window=128, sparse_dense_len=128),
    "latent": dict(rope_interleave=True, kv_lora_rank=128,
                   qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=64),
}


def weights_laid_out_again(text, params):
    """Arrays of compiled HLO ``text`` shaped as one layer's ``wq``, ``wk``
    or ``wv`` and laid out columns major (``{0,1``): a stack keeps a layer's
    matrix rows major, and its matmul reads it so, so such an array is the
    weight written out again, transposed, for whatever reads the product."""
    shapes = {a.shape[1:] for stack in params.values()
              if isinstance(stack, dict) for name, a in stack.items()
              if name in ("wq", "wk", "wv")}
    return [m for d, n in sorted(shapes) for m in re.findall(
        rf"%[\w.\-]+ = \(?bf16\[{d},{n}\]\{{0,1\S*", text)]


@pytest.mark.parametrize("form", sorted(TILED_FORMS))
def test_decode_program_writes_no_projection_weight_again(form, one_chip,
                                                          monkeypatch):
    """The decode program compiled for the chip reads each layer's q/k/v
    (the latent's q) weight where it lies in its stack (PR 49): no array of
    a projection weight's shape is written transposed. Without the boundary
    (the parent's projections) the chip's compiler writes every layer's out
    again, every step, to have the per-head product in the layout its norm
    or rotation wants: 3.8 + 1.6 ms of minicpm's 22.95 ms step."""
    cfg = GPTConfig.make(**TILED, **TILED_FORMS[form])
    params = jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg))

    def compiled_text():
        engine = DecodeEngine(
            jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), params), cfg,
            n_slots=8)
        (_, _, jitted, args, kwargs), = [
            p for p in engine.programs() if p[0] == "decode"]
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), args)
        return jitted.lower(*shapes, **kwargs).compile().as_text()

    assert not weights_laid_out_again(compiled_text(), params)
    without_boundary(monkeypatch)
    assert weights_laid_out_again(compiled_text(), params)


def slice_sized(text, lanes, rows, ops=("select", "copy", "convert")):
    """Instructions of compiled HLO ``text`` of the kinds ``ops`` whose
    result holds a layer's slice of the pool, ``(.., lanes, rows, heads,
    size)`` or, one head dropped, ``(lanes, rows, size)``, fused or not."""
    return re.findall(
        rf"= \w+\[(?:\d+,)*{lanes},{rows},\d+(?:,\d+)?\]\S* "
        rf"(?:{'|'.join(ops)})\(.*", text)


#: sizes at which the chip's compiler gives the attention's products to the
#: MXU, as it does at a cell's (at the tiny ones above it spells them out
#: elementwise, and what is then fused around them says nothing)
WIDE = dict(n_layer=2, n_head=8, n_embd=256, vocab_size=64, block_size=512,
            embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="bfloat16",
            tie_weights=False)
WIDE_FORMS = {
    # eight query heads beside one KV head: a matmul a lane, as the latent
    # form's is (one query head a KV head is a matrix-vector product, which
    # the compiler spells out elementwise at these few lanes)
    "per-head": dict(n_kv_head=1),
    # eight KV heads of 32, a row of 256: heads side by side (PR 39), read
    # whole against queries spread to the row's width
    "side-by-side": dict(),
    "latent": dict(rope=True, rope_interleave=True, swiglu=True, rmsnorm=True,
                   kv_lora_rank=128, qk_nope_head_dim=64,
                   qk_rope_head_dim=64, v_head_dim=64),
}


@pytest.mark.parametrize("form,block", [
    ("per-head", 1024), ("per-head", 128), ("latent", 1024), ("latent", 128),
    ("side-by-side", 1024), ("side-by-side", 128)],
    ids=["per-head-one-pass", "per-head-walked", "latent-one-pass",
         "latent-walked", "side-by-side-one-pass", "side-by-side-walked"])
def test_decode_program_reads_the_cache_as_it_lies(form, block, one_chip,
                                                   walk_in_blocks):
    """The decode program compiled for the chip selects, copies and converts
    nothing of a slice's size (PR 33): each layer reads the pool's buffers
    where they lie, a lane's slot in one block or in several (the walk is a
    loop either way since PR 45), and attends the lanes' new rows beside
    them. Laying the rows over the slice first, as the step
    did before, is the control: the chip's compiler keeps that select."""
    walk_in_blocks(block)
    lanes = 8
    cfg = GPTConfig.make(**WIDE, **WIDE_FORMS[form])
    params = jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg))
    engine = DecodeEngine(
        jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), params), cfg,
        n_slots=lanes)
    (_, _, jitted, args, kwargs), = [
        p for p in engine.programs() if p[0] == "decode"]
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    text = jitted.lower(*on_chip(args), **kwargs).compile().as_text()
    assert "while" in text
    assert not slice_sized(text, lanes, cfg.block_size)

    def laid_over(cache, rows, positions, q):
        k = gen._lay_rows_over(cache["k"][0], rows, positions)
        return jnp.einsum("bhd,bskd->bhs", q, k)
    shape = engine.pool.cache["k"].shape
    control = jax.jit(laid_over).lower(*on_chip((
        engine.pool.cache, jnp.zeros((lanes, 1) + shape[3:], jnp.bfloat16),
        jnp.zeros(lanes, jnp.int32),
        jnp.zeros((lanes, 4, shape[-1]), jnp.bfloat16))))
    assert slice_sized(control.compile().as_text(), lanes, cfg.block_size,
                       ops=("select",))


@pytest.mark.parametrize("form", ["loop", "kernel"])
def test_the_experts_loop_takes_the_stacked_leaves_as_they_lie(
        form, one_chip, monkeypatch):
    """A routed model's decode and prefill programs compiled for the chip
    (PR 35): the stacked expert leaves are operands of the experts' loop,
    whose trip count is the blocks that hold a row, and the chip's
    compiler hands them over by reference: nothing of a layer's experts'
    size or the stack's is copied, sliced or fused into a new buffer. A
    layer sliced out of the stack before the loop is the control: the
    chip's compiler copies it whole (PR 30: 1.1 GB a layer at kanana's
    sizes). Both forms of the cached path (PR 60): the XLA loop, which a
    process off the chip keeps, and the Pallas kernel the chip's programs
    hold, one Mosaic call a layer named ``grouped_swiglu`` under the
    experts' scope and no loop of dots, which Mosaic takes at 8 rows a
    block (the decode step's) and at the prefill's; the kernel is reached
    as a compile rehearsal reaches it, through ``flash_attention._interpret``."""
    from mingpt_distributed_tpu.ops import flash_attention, moe

    if form == "kernel":
        monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    moe._run_blocks.clear_cache()

    cfg = GPTConfig.make(**{
        **WIDE, **WIDE_FORMS["latent"], "n_layer": 3, "n_dense_layers": 1,
        "ffn_dim": 512, "n_experts": 12, "moe_top_k": 2, "moe_ffn_dim": 128,
        "n_shared_experts": 1, "moe_scoring": "sigmoid",
        "moe_route_scale": 2.448, "param_dtype": "bfloat16"})
    params = jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg))
    engine = DecodeEngine(
        jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), params), cfg,
        n_slots=8)
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    widths = {(cfg.n_embd, cfg.expert_width), (cfg.expert_width, cfg.n_embd)}

    def expert_sized(text):
        """Instructions that make a buffer shaped as one layer's (E, D, F)
        experts or the two-layer stack's: anything but the leaf itself seen
        under another shape."""
        made = [(m.group(0), [int(n) for n in m.group(1).split(",")])
                for m in re.finditer(
                    r"= \w+\[([\d,]+)\]\S* "
                    r"(?!parameter|bitcast|get-tuple-element)[\w-]+\(.*", text)]
        return [line for line, dims in made if tuple(dims[-2:]) in widths
                and np.prod(dims[:-2]) in (cfg.n_experts, 2 * cfg.n_experts)]

    for family in ("decode", "prefill"):
        _, _, jitted, args, kwargs = [
            p for p in engine.programs() if p[0] == family][-1]
        text = jitted.lower(*on_chip(args), **kwargs).compile().as_text()
        kernels = re.findall(
            r'%([\w.\-]+) = .*custom_call_target="tpu_custom_call".*'
            r'op_name="([^"]*)"', text)
        if form == "loop":
            assert "moe_experts/while/body/dot_general" in text  # the loop
            assert not kernels
        else:
            assert "moe_experts/while/body/dot_general" not in text
            assert len(kernels) == cfg.n_layer - cfg.n_dense_layers
            assert all("grouped_swiglu" in name and "moe_experts" in scope
                       for name, scope in kernels)
        # at this toy size a two-layer stack (1.5 MB) fits the core's fast
        # memory whole, and for a Mosaic call the compiler prefetches it
        # there in slices (``S(1)``): no copy in HBM, and nothing a cell's
        # 6 GB stack can meet (`rehearse.py compile`, PERF.md, PR 60)
        assert not [line for line in expert_sized(text)
                    if form == "loop" or "S(1)" not in line]
    moe._run_blocks.clear_cache()

    def sliced_first(x, chosen, blocks):
        return moe.grouped_swiglu(x, chosen, *(
            blocks[n][1] for n in ("w_eg", "w_e1", "w_e2")))[0]
    control = jax.jit(sliced_first).lower(*on_chip((
        jnp.zeros((8, cfg.n_embd), jnp.bfloat16), jnp.zeros((8, 2), jnp.int32),
        params["blocks"])))
    assert expert_sized(control.compile().as_text())


FLASH_CALLS = ("flash_fwd", "flash_bwd_fused", "flash_bwd_dq",
               "flash_bwd_dkv")


@pytest.fixture
def flash_compiled(monkeypatch):
    """``ops.flash_attention`` with its kernels compiled, not interpreted
    (the module asks the backend, which is the CPU here), and no variable
    steering its choices. The kernels' jitted callers (PR 54) keep what they
    traced, which holds the backend's answer: forgotten on the way in and on
    the way out, so no trace of one kind meets a test of the other."""
    from mingpt_distributed_tpu.ops import flash_attention as flash
    monkeypatch.setattr(flash, "_interpret", lambda: False)
    monkeypatch.delenv("FLASH_BLOCK", raising=False)
    monkeypatch.delenv("FLASH_LAYOUT", raising=False)
    callers = (flash._native_forward, flash._native_backward)
    for jitted in callers:
        jitted.clear_cache()
    yield flash
    for jitted in callers:
        jitted.clear_cache()


def mosaic_calls(text):
    """How many Mosaic calls of a compiled text are named after each of the
    flash kernels (the instruction's own name: a backward call also names
    the forward's outputs among its operands)."""
    names = re.findall(
        r'^\s*(?:ROOT )?%([\w.\-]+) = .*custom_call_target="tpu_custom_call"',
        text, re.M)
    return {call: sum(call in name for name in names) for call in FLASH_CALLS}


@pytest.mark.parametrize("shape", [(1, 1024, 12, 64), (1, 1024, 25, 64),
                                   (1, 2048, 2, 128), (1, 256, 12, 64)],
                         ids=["124m", "xl-odd-heads", "hd128-pack1",
                              "block256"])
def test_the_flash_backward_compiles_as_one_kernel(shape, one_chip,
                                                   flash_compiled):
    """PR 50: at the training cells' shapes (XL's 25 heads through the
    zero-head pad) the gradient of ``causal_attention`` is the forward and
    ONE dq+dk+dv Mosaic kernel, and the chip's compiler takes it: the dq
    slab's dynamic leading index, its VMEM and, since PR 54, the diagonal
    cells' staircases (row and lane slices of 128, a group's slices of the
    scratch; one head a cell and a block of 256 beside the cells' shapes).
    Interpret mode says nothing of any."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    text = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_compiled.causal_attention(q, k, v)
                                .astype(jnp.float32)),
        argnums=(0, 1, 2))).lower(x, x, x).compile().as_text()
    assert mosaic_calls(text) == {"flash_fwd": 1, "flash_bwd_fused": 1,
                                  "flash_bwd_dq": 0, "flash_bwd_dkv": 0}


def test_a_training_step_s_table_names_the_fused_backward(one_chip,
                                                          flash_compiled):
    """The engagement the benchmark reads (PR 50): the scope table a
    trainer's ``program`` record holds (``telemetry.programs.scope_table``
    of the compiled step) lists, for a flash model with its layers written
    out, ``n_layer`` instructions named ``flash_bwd_fused`` under ``attn``
    and none of the split pair. On the CPU the interpreted kernel is a
    loop of plain instructions that carry the name in their ``op_name``
    only, so this table is made of a step compiled for the described
    chip."""
    from mingpt_distributed_tpu.telemetry.programs import scope_table
    cfg = GPTConfig.make(
        n_layer=3, n_head=2, n_embd=128, vocab_size=64, block_size=256,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="bfloat16",
        attention="flash", unroll_layers=True)
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = jax.eval_shape(lambda: gpt.init(jax.random.key(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 256), jnp.int32)

    def loss(params, tokens):
        return gpt.forward(params, tokens, cfg, targets=tokens,
                           return_logits=False)[1]

    table = scope_table(jax.jit(jax.grad(loss)).lower(
        *on_chip((params, tokens))).compile().as_text())
    named = lambda part: sorted(k for k in table if part in k)
    assert len(named("flash_bwd_fused")) == cfg.n_layer
    assert {table[k] for k in named("flash_bwd_fused")} == {"attn"}
    assert len(named("flash_fwd")) == cfg.n_layer
    assert not named("flash_bwd_dq") and not named("flash_bwd_dkv")


@pytest.mark.parametrize("unroll", [False, True], ids=["scan", "unrolled"])
def test_the_chunked_loss_compiles_to_three_matmuls_and_one_chunk_live(
        unroll, one_chip):
    """PR 56, as the chip's compiler plans it at the published vocabulary
    (a quarter of the 124M cell's batch and a third of its width): the
    differentiated loss holds three head-sized matmuls a chunk (the
    compiler's own rematerialisation adds a fourth where memory runs out),
    and its temporaries are about one chunk's float32 logits. Without the
    barrier between the unrolled chunks this compiler ran all eight logits
    matmuls first: 8.0 chunks live here, 6.6 GB at the cell's size."""
    b, t, d, v, n = 8, 1024, 256, 50257, 8
    on_chip = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(
        lambda x, w, tgt: gpt.chunked_cross_entropy(
            x, w.astype(x.dtype), tgt, n, unroll=unroll),
        argnums=(0, 1))).lower(
            on_chip((b, t, d), jnp.bfloat16), on_chip((d, v), jnp.float32),
            on_chip((b, t), jnp.int32)).compile()
    matmuls = re.findall(r"= \S+ convolution\(", compiled.as_text())
    assert len(matmuls) == (3 * n if unroll else 3)
    chunk_logits = b * (t // n) * v * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * chunk_logits


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_metrics_say_what_the_programs_read(dtype):
    cfg, params = model("gpt2-untied", dtype)
    server = InferenceServer(params, cfg, n_slots=2, warmup=False)
    got = server.metrics.summary()
    n_params = gpt.param_count(params)
    if dtype == "float32":
        assert got["program_weights_cast"] == 0
        assert got["program_weight_bytes"] == 4 * n_params
        return
    # 6 matmul weights and their 6 biases a block (stacked: one leaf each)
    # and the head
    assert got["program_weights_cast"] == 13
    cast = sum(a.size for n, a in params["blocks"].items()
               if n in gen._CAST_ONLY_BLOCK_LEAVES) + params["head"].size
    assert got["program_weight_bytes"] == 4 * n_params - 2 * cast
