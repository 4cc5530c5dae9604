"""A per-head cache keeps a position's heads side by side wherever a head
fills no lane tile of its own and the heads together fill whole ones (ISSUE
39): ``generate.cache_leaf_shapes`` is the one rule, by the config's widths;
the rows and the tokens of everything the serving path does over them
(prefix store, migration, speculation, an int8 pool, ``tp``) are the
per-head pool's (the same sums with zero products beside them: equal to
float32 rounding, the tokens exactly); the models the rule passes by trace
to the parent's programs. CPU, tiny, float32."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stacks
from benchmarks.harness import check, serve_cell, spec
from mingpt_distributed_tpu.config import GPTConfig, MeshConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.ops import attention as attn_ops
from mingpt_distributed_tpu.parallel import mesh as mesh_lib
from mingpt_distributed_tpu.serving import InferenceServer
from mingpt_distributed_tpu.serving import engine as engine_mod
from mingpt_distributed_tpu.serving import quant as quant_lib
from mingpt_distributed_tpu.serving.engine import DecodeEngine
from mingpt_distributed_tpu.telemetry import render_prometheus
from program_digests import engine_digest as digest
from program_digests import forward_digest

#: the forms of a row and their tiny models: ``tests/stacks.py``'s table
#: (``program_digests.py`` reads ``FORMS``, ``model`` and ``per_head`` here)
TINY, FORMS, model = stacks.WIDE_TINY, stacks.FORMS, stacks.form_model
per_head = stacks.per_head
#: the forms whose heads lie side by side, which this PR moved
WIDE = ("mha", "two-tiles", "gqa-rope", "window-softcap", "looped")


# -- the rule -------------------------------------------------------------------

@pytest.mark.parametrize("name, sizes, row, heads, tiles", [
    ("gpt2-124m", dict(model_type="gpt2"), (1, 768), 12, 72),
    ("gpt2-xl", dict(model_type="gpt2-xl"), (25, 64), 1, 4800),
    ("five-heads-of-64", FORMS["five-heads-of-64"], (5, 64), 1, 40),
    ("narrow", FORMS["narrow"], (4, 8), 1, 4),
    ("llama-yaml", dict(model_type="llama-tiny"), (1, 128), 2, 4),
    ("gpt-micro", dict(model_type="gpt-micro"), (1, 128), 4, 4),
    ("mha", FORMS["mha"], (1, 128), 4, 2),
    ("two-tiles", FORMS["two-tiles"], (1, 256), 4, 4),
    ("gqa-rope", FORMS["gqa-rope"], (1, 128), 4, 2),
    ("mqa-rope", FORMS["mqa-rope"], (1, 8), 1, 2),
    ("looped", FORMS["looped"], (1, 128), 4, 4),
    ("heads-of-128", FORMS["heads-of-128"], (2, 128), 1, 2 * 2 * 128 // 16),
    ("heads-of-256", dict(TINY, n_head=1, n_embd=256), (1, 256), 1, 4),
    ("looped-heads-of-128", FORMS["looped-heads-of-128"], (2, 128), 1,
     4 * 2 * 128 // 16),
    ("latent", FORMS["latent"], (1, 4), 1, 2),
    ("hybrid", FORMS["hybrid"], None, None, None),
])
def test_the_row_s_shape_is_decided_by_the_widths(name, sizes, row, heads,
                                                  tiles):
    """Heads narrower than a lane tile that together fill whole tiles lie
    side by side; a width of no whole tiles, a head of 128 or more, a latent
    and a hybrid stack's rows are what they were. 124M at its published
    widths: 768 wide, 72 tiles a lane's write where the per-head leaf made
    it 576; XL's 1,600 is 12.5 tiles and keeps its 4,800."""
    cfg = GPTConfig.make(**sizes)
    shapes = gen.cache_leaf_shapes(cfg, 3)
    if name == "hybrid":
        width = cfg.kv_heads * cfg.head_dim
        assert shapes["k"][3:] == shapes["v"][3:] == (1, width)
        assert gen.row_heads(cfg) == cfg.kv_heads
        return
    assert shapes["k"][:3] == (cfg.cache_planes, 3, cfg.block_size)
    assert shapes["k"][3:] == row
    if name != "latent":
        assert shapes["v"] == shapes["k"]
        assert np.prod(row) == cfg.kv_heads * cfg.head_dim
    assert gen.row_heads(cfg) == heads
    assert gen.row_tiles(cfg) == tiles
    if cfg.n_embd <= 512:
        cache = gen.init_cache(cfg, 3)
        assert {n: a.shape for n, a in cache.items()} == shapes


def test_the_per_head_leaf_touched_eight_times_the_tiles(monkeypatch):
    cfg = GPTConfig.make(model_type="gpt2")
    xl = GPTConfig.make(model_type="gpt2-xl")
    wide = gen.row_tiles(cfg), gen.row_tiles(xl)
    per_head(monkeypatch)
    assert gen.cache_leaf_shapes(cfg, 1)["k"] == (12, 1, 1024, 12, 64)
    assert (gen.row_tiles(cfg), gen.row_tiles(xl)) == (576, 4800)
    assert wide == (72, 4800)


# -- the attention views a row as heads ------------------------------------------

@pytest.mark.parametrize("case", [
    dict(), dict(window=5), dict(logit_softcap=3.0),
    dict(window=7, logit_softcap=2.0)],
    ids=["plain", "window", "softcap", "window-and-softcap"])
@pytest.mark.parametrize("kv_heads", [4, 2, 1], ids=["mha", "gqa", "mqa"])
@pytest.mark.parametrize("walk", [False, True], ids=["one-pass", "walked"])
def test_the_step_over_a_wide_cache_is_the_step_over_a_per_head_one(
        case, kv_heads, walk, walk_in_blocks):
    """``causal_attend_step`` reads whole rows where they lie, the queries
    spread to the rows' width: the per-head sums with zero products beside
    them, so equal to float32 rounding."""
    keys = jax.random.split(jax.random.key(4), 5)
    lanes, rows, heads, size = 3, 32, 4, 8
    # walked, or as the rule has so small a house: in one pass
    walk = walk_in_blocks(8)(rows) if walk else attn_ops.StepWalk(rows, 1, 0)
    q = jax.random.normal(keys[0], (lanes, 1, heads, size))
    k_cache, v_cache = (jax.random.normal(k, (2, lanes, rows, kv_heads, size))
                        for k in keys[1:3])
    k_new, v_new = (jax.random.normal(k, (lanes, 1, kv_heads, size))
                    for k in keys[3:5])
    positions = jnp.array([0, 13, rows - 1])
    side_by_side = lambda a: a.reshape(*a.shape[:-2], 1, kv_heads * size)
    step = jax.jit(lambda q, k, v, k_new, v_new, at: (
        attn_ops.causal_attend_step(q, k, v, 1, k_new, v_new, at, walk,
                                    **case)))
    want = step(q, k_cache, v_cache, k_new, v_new, positions)
    got = step(q, side_by_side(k_cache), side_by_side(v_cache),
               side_by_side(k_new), side_by_side(v_new), positions)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_as_heads_is_a_view_of_the_same_numbers_in_the_same_order():
    rows = jnp.arange(2 * 5 * 12, dtype=jnp.float32).reshape(2, 5, 1, 12)
    heads = attn_ops.as_heads(rows, 4)
    assert heads.shape == (2, 5, 3, 4)
    np.testing.assert_array_equal(heads[1, 2, 1], rows[1, 2, 0, 4:8])
    assert attn_ops.as_heads(heads, 4) is heads


# -- the cached forward, wide against per-head and against gpt.forward -----------

def cached_run(cfg, params, tokens, cache):
    """A chunk of 9 and of 6 tokens in two lanes, then three decode steps
    with the lanes at different positions and a third lane parked: every
    step's logits and the cache at the end. Under a jit of the call's own:
    the rule and the walk are patches the config does not show."""
    lane = lambda c, s: {n: a[:, s:s + 1] if a.ndim == 5 else a
                         for n, a in c.items()}
    forward = jax.jit(lambda tokens, cache, offset, **kw: gen._forward_cached(
        params, tokens, cache, offset, cfg, **kw))
    out = []
    for slot, n in ((0, 9), (1, 6)):
        logits, one = forward(tokens[slot:slot + 1, :n], lane(cache, slot), 0)
        out.append(logits)
        cache = {name: a if a.ndim != 5 else cache[name].at[:, slot].set(a[:, 0])
                 for name, a in one.items()}
    positions = np.array([9, 6, cfg.block_size - 1])
    live = np.asarray([True, True, False])
    for step in range(3):
        at = positions + np.array([step, step, 0])
        feed = np.asarray([tokens[0, 9 + step], tokens[1, 6 + step], 0],
                          tokens.dtype)[:, None]
        logits, cache = forward(
            feed, cache, at, valid=live[:, None],
            frontier=engine_mod.decode_frontier(jnp.asarray(at),
                                                jnp.asarray(live)))
        out.append(logits[:2])
    return out, cache


@pytest.mark.parametrize("walk", [False, True], ids=["one-pass", "walked"])
@pytest.mark.parametrize("form", WIDE)
def test_prefill_then_decode_over_wide_rows_is_the_per_head_cache_s(
        form, walk, monkeypatch, walk_in_blocks):
    """Logits of every program and the rows left in the cache are the
    per-head cache's: bit for bit after the chunks (a chunk views its
    lane's rows as heads), to float32 rounding after the steps (whole rows
    against spread queries), and the full forward's likewise."""
    if walk:
        walk_in_blocks(8)
    cfg, params = model(form)
    tokens = stacks.tokens_of(cfg, 2, 13, seed=2)
    counters = {gen.LOOP_PASSES: gen.init_loop_passes(cfg)} \
        if gen.init_loop_passes(cfg) is not None else {}
    wide = dict(gen.init_cache(cfg, 3), **counters)
    assert wide["k"].shape[3:] == (1, cfg.kv_heads * cfg.head_dim)
    got, got_cache = cached_run(cfg, params, tokens, wide)
    per_head(monkeypatch)
    narrow = dict(gen.init_cache(cfg, 3), **counters)
    assert narrow["k"].shape[3:] == (cfg.kv_heads, cfg.head_dim)
    want, want_cache = cached_run(cfg, params, tokens, narrow)
    for a, b in zip(got[:2], want[:2]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(got[2:], want[2:]):
        np.testing.assert_allclose(a, b, rtol=0, atol=5e-6)
        np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))
    for name in ("k", "v"):
        rows = attn_ops.as_heads(got_cache[name], cfg.head_dim)
        np.testing.assert_allclose(rows[:, :2], want_cache[name][:, :2],
                                   rtol=0, atol=5e-6)
        for lane, n in ((0, 9), (1, 6)):    # the chunks' rows: the same bits
            np.testing.assert_array_equal(rows[0, lane, :n],
                                          want_cache[name][0, lane, :n])
    full, _ = stacks.forward(params, tokens, cfg)
    np.testing.assert_allclose(got[0][0], full[0, 8], atol=2e-5)
    np.testing.assert_allclose(got[1][0], full[1, 5], atol=2e-5)
    for step in range(3):
        np.testing.assert_allclose(got[2 + step][0], full[0, 9 + step],
                                   atol=2e-5)
        np.testing.assert_allclose(got[2 + step][1], full[1, 6 + step],
                                   atol=2e-5)


# -- an int8 pool's scales: a row and head -----------------------------------------

@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
@pytest.mark.parametrize("heads", [1, 3, 4])
def test_a_wide_row_s_scales_are_its_heads_own(kv_dtype, heads):
    q = quant_lib.resolve_kv_dtype(kv_dtype)
    rows = jax.random.normal(jax.random.key(3), (2, 3, 5, heads, 8)) \
        * jnp.exp(jnp.arange(heads, dtype=jnp.float32))[:, None]
    want, want_scale = quant_lib.quantize(rows, q)
    wide = rows.reshape(2, 3, 5, 1, heads * 8)
    got, scale = quant_lib.quantize(wide, q, heads)
    assert got.shape == wide.shape and scale.shape == (2, 3, 5, 1, heads)
    np.testing.assert_array_equal(got.reshape(rows.shape), want)
    np.testing.assert_array_equal(scale.reshape(want_scale.shape), want_scale)
    back = quant_lib.dequantize(got, scale)
    np.testing.assert_array_equal(
        back.reshape(rows.shape), quant_lib.dequantize(want, want_scale))
    # the round trip loses nothing more (an int8 payload is bit-stable; an
    # 8-bit float's may come back under half the scale, the same numbers)
    again, scale_again = quant_lib.quantize(back, q, heads)
    np.testing.assert_array_equal(quant_lib.dequantize(again, scale_again),
                                  back)
    if kv_dtype == "int8":
        np.testing.assert_array_equal(again, got)
        np.testing.assert_array_equal(scale_again, scale)


def test_an_int8_pool_keeps_a_scale_a_row_and_head():
    cfg, _ = model("gqa-rope")
    pool = quant_lib.init_quant_cache(cfg, 2, quant_lib.resolve_kv_dtype("int8"))
    assert pool["k"].shape == (2, 2, 32, 1, 128)
    assert pool["k_scale"].shape == (2, 2, 32, 1, 4)
    assert sum(int(a.nbytes) for n, a in pool.items() if n.endswith("_scale")) \
        == quant_lib.scale_bytes(cfg, 2)


# -- tp: whole heads a shard, or none ----------------------------------------------

@pytest.mark.parametrize("form, over, tp, axis, shards", [
    ("mha", {}, 2, 4, 2),
    ("gqa-rope", {}, 2, 4, 2),
    ("gqa-rope", {}, 4, 4, 4),
    ("gqa-rope", dict(n_head=4, n_kv_head=2), 4, None, 1),
    ("mqa-rope", {}, 2, None, 1),
    ("narrow", {}, 2, 3, 2),
    ("heads-of-128", {}, 2, 3, 2),
], ids=["4-heads", "4-kv-heads", "4-kv-heads-tp4", "2-kv-heads-of-64-tp4",
        "1-kv-head", "narrow", "heads-of-128"])
@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["fp32", "int8"])
def test_tp_shards_the_axis_that_holds_the_heads(form, over, tp, axis, shards,
                                                 kv_dtype):
    """Equal parts of a wide row are whole heads where ``kv_heads % tp ==
    0``: the rule is held to the head count, so two heads of 64 (a row of
    128, which four does divide) stay whole on every chip of four."""
    cfg, params = model(form, **over)
    mesh = mesh_lib.make_mesh(MeshConfig(dp=1, tp=tp),
                              devices=jax.devices()[:tp])
    eng = DecodeEngine(params, cfg, n_slots=2, mesh=mesh, kv_dtype=kv_dtype)
    assert eng.kv_shard_count == shards
    spec = engine_mod.kv_pool_spec(cfg)
    assert len(spec) == (5 if gen.row_heads(cfg) > 1 else 4)
    for name, leaf in eng.pool.cache.items():
        if leaf.ndim != 5:
            continue
        shard = leaf.sharding.shard_shape(leaf.shape)
        want = list(leaf.shape)
        if axis is not None:
            want[axis] //= tp
        assert list(shard) == want, name


# -- the models this PR passes by trace to the parent's programs -------------------

#: sha256 of ``digest``'s jaxprs. The prefill programs: on the commit before
#: PR 45 (182a0b7), made by this function there and equal on this one, for
#: every form: since PR 39 only the decode step has changed. The decode
#: program: made again by PR 45, which changed it on purpose for every form.
#: A pool of three tiny slots is read in one pass (``attention.step_block``),
#: the parent's read with each lane's reach where the scalar frontier was, a
#: dead equation there; a hybrid stack loses the frontier's two. The walk
#: over lanes and blocks itself is WALKED_DECODE_DIGESTS: the same programs
#: under ``walk_in_blocks(8)``, where a leaf that lies positions minor on the
#: chip (five heads of 64, GPT-2 XL's 25) is walked too. The forward's:
#: ``gpt.forward`` alone; a ``form/train`` key is the training-mode forward
#: (``forward_digest_of``). PR 46's rows (every ``capacity``; the forward's
#: ``window-softcap``, ``latent`` and ``/train``) were made on its parent
#: (f3c5a18), before it moved a line of either body. PR 49 made the decode
#: rows again, walked or not, of the forms whose projections are turned per
#: head (``capacity``, ``latent``, ``hybrid``, ``looped-heads-of-128``,
#: ``mqa-rope``: ``gpt.head_projection``'s boundary, which
#: tests/test_cast_once.py holds to be all that moved); the GPT-2 forms', every
#: prefill program's and every forward's are untouched. PR 61 made the four
#: ``latent`` rows again (prefill, decode, walked decode and the forward): the
#: dropless route's counts are one entry longer, the experts that held a row
#: (``moe_expert_runs``: a comparison with 0, its sum, a conversion and a
#: broadcast an expert layer; tests/test_smallthinker.py holds that this is
#: all the two accepted routed cells' decode programs gained); no other row
#: of these tables moved. A PR that changes one of
#: these programs on purpose makes them again: tests/program_digests.py, run,
#: prints every table.
PARENT_PREFILL_DIGESTS = {
    "capacity": "9ca244167a1bf8c5",
    "five-heads-of-64": "e48b504c543f2155",
    "gqa-rope": "996c36df05f920bc",
    "heads-of-128": "c695671b5cdd4b04",
    "hybrid": "dd762e1e02072ad3",
    "latent": "85e5e963df757fc4",
    "looped": "7a71c6b7f8d1cf85",
    "looped-heads-of-128": "c59d0a1539fd91b5",
    "mha": "7896012cc8a4b9ed",
    "mqa-rope": "9cfc64d4c0a56333",
    "narrow": "384a636bce36f32a",
    "two-tiles": "4a1b36b0e7d9a8ca",
    "window-softcap": "034f2065e8be1a07",
}
DECODE_DIGESTS = {
    "capacity": "42748f164b0c9fbf",
    "latent": "4a1d4e62c5329b1d",
    "hybrid": "daedec3b4381ea97",
    "looped-heads-of-128": "21a214eace867bfb",
    "heads-of-128": "3be9b1ee5821a527",
    "five-heads-of-64": "023e4278f7c3f7b7",
    "narrow": "dceeb701a0e6a7a2",
    "mqa-rope": "dd26b9ebcedf0137",
}
WALKED_DECODE_DIGESTS = {
    "capacity": "1d0fc943042274a3",
    "latent": "012b1a7c2b13d0d0",
    "looped-heads-of-128": "5db60c232ed1b1aa",
    "heads-of-128": "934bd3b284cf760c",
    "mha": "01a10a357520b3da",
    "mqa-rope": "299f6b4c03089557",
}
PARENT_FORWARD_DIGESTS = {
    "mha": "4df8e026ac68301c",
    "gqa-rope": "c6eb2e0998a3e106",
    "looped": "2862654be5e34702",
    "window-softcap": "9ad431b0213dca45",
    "latent": "743082716282be8f",
    "capacity": "0126daae348e6d6f",
    "mha/train": "8559ec7fca433251",
    "gqa-rope/train": "bfbed589e21292c8",
    "capacity/train": "de10ad6f58960ab5",
    # the loss-only forward under value_and_grad, pinned first by PR 56,
    # whose parent read "bbe5700240ef2a21" and "8d446719bbc144e0": the
    # chunked loss took its gradient under jax.checkpoint there (a fourth
    # head matmul a chunk, in a backward scan), and takes it in the forward
    # sweep now. No other digest of this file moved with it.
    "mha/loss": "89e3d616e8d18e40",
    "latent/loss": "6a554607055800a2",
}


def forward_digest_of(key: str) -> str:
    """A PARENT_FORWARD_DIGESTS key's digest: ``form`` is the deterministic
    forward, ``form/train`` the training-mode one, with a dropout key and
    both dropouts on, ``form/loss`` the loss-only forward differentiated."""
    form, _, mode = key.partition("/")
    over = dict(resid_pdrop=0.1, attn_pdrop=0.1) if mode == "train" else {}
    return forward_digest(GPTConfig.make(**{**FORMS[form], **over}),
                          train=mode == "train", loss=mode == "loss")


@pytest.mark.parametrize("form", sorted(PARENT_PREFILL_DIGESTS))
def test_every_prefill_program_is_the_parent_s(form):
    cfg, _ = model(form)
    assert digest(cfg, decode=False) == PARENT_PREFILL_DIGESTS[form]


@pytest.mark.parametrize("form", sorted(DECODE_DIGESTS))
def test_a_model_whose_rows_were_wide_or_whole_runs_the_parent_s_programs(form):
    """The decode programs of the forms PR 39 passed by, as PR 45 made
    them again."""
    cfg, _ = model(form)
    assert digest(cfg, decode=True) == DECODE_DIGESTS[form]


@pytest.mark.parametrize("form", sorted(WALKED_DECODE_DIGESTS))
def test_the_walk_s_own_equations_are_pinned(form, walk_in_blocks):
    """The equations of the two loops and their plan, at a block of 8 rows
    a tiny slice (``walk_in_blocks``: the chip's programs walk 512 and 1,024
    by the same code, and tiny heads not at all). Equal, under a rule that
    gives both the same walk, to those of the tree PERF.md's numbers for
    PR 45 were measured on."""
    walk_in_blocks(8)
    cfg, _ = model(form)
    assert digest(cfg, decode=True) == WALKED_DECODE_DIGESTS[form]


@pytest.mark.parametrize("form", sorted(PARENT_FORWARD_DIGESTS))
def test_training_s_forward_is_the_parent_s(form):
    assert forward_digest_of(form) == PARENT_FORWARD_DIGESTS[form]


def test_the_per_head_rule_traces_to_the_parent_s_gpt2_programs(monkeypatch):
    """One algorithm: with the rule as it was, the code of PR 39 traced to
    its parent's programs for the models it moved. Their prefill programs
    still do; the decode programs are PR 45's (a per-head leaf of heads
    under a lane tile is read in one pass, the parent's read less the dead
    frontier), ``gqa-rope``'s with PR 49's boundary on its rotated
    projections."""
    per_head(monkeypatch)
    for form, prefill, decode in (
            ("mha", "e8a706ae5decb1b3", "b2ec58f36ce86c36"),
            ("gqa-rope", "a742b814360f967c", "0cd0e4b368f6d07f")):
        cfg, _ = model(form)
        assert digest(cfg, decode=False) == prefill
        assert digest(cfg, decode=True) == decode


# -- the check holds the rows, whichever way they lie ------------------------------

CELL = "gpt2-124m.serve-decode"
SEED = 3_900_000_001


@pytest.fixture(scope="module")
def gpt2_cell():
    # four heads of 32: the tiny cell's own three make a row of 96
    cell = stacks.tiny_cell(CELL, n_head=4, n_embd=128)
    return cell, spec.load_reference(cell.config), \
        serve_cell.Driver(cell, SEED, traced=False)


def check_prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 384, size=n, dtype=np.int32) for n in (24, 40)]


def test_the_check_passes_the_tiny_gpt2_cell_over_wide_rows(gpt2_cell):
    cell, reference, driver = gpt2_cell
    pool = driver.server.engine.pool
    assert pool.cache["k"].shape[3:] == (1, 128)
    verdict = check.serve_verdict(reference, cell.config, driver.server,
                                  check_prompts(), 4)
    assert verdict["ok"], verdict


class Swapping:
    """The engine, but after every program the first two heads of every
    row of the pool have changed places: the right numbers in the wrong
    order, which a check that forgave an order would pass."""

    def __init__(self, engine, head_dim):
        self._engine, self._hd = engine, head_dim

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def _swap(self):
        pool, hd = self._engine.pool, self._hd
        pool.cache = {
            n: a if a.ndim != 5 else jnp.concatenate(
                [a[..., hd:2 * hd], a[..., :hd], a[..., 2 * hd:]], axis=-1)
            for n, a in pool.cache.items()}

    def prefill_chunk_call(self, *args):
        out = self._engine.prefill_chunk_call(*args)
        self._swap()
        return out

    def decode_step(self, *args):
        out = self._engine.decode_step(*args)
        self._swap()
        return out


def test_the_check_fails_a_pool_whose_heads_are_swapped_inside_the_row(
        gpt2_cell):
    cell, reference, driver = gpt2_cell
    bad = check.serve_verdict(
        reference, cell.config,
        types.SimpleNamespace(engine=Swapping(driver.server.engine, 32)),
        check_prompts(), 4)
    assert bad["ok"] is False
    worst = max(max(c["k_rel"], c["v_rel"]) for c in bad["cases"])
    assert worst > 10 * bad["kv_rel_tol"]


def test_as_rows_reshapes_and_refuses_another_size():
    ref = np.arange(2 * 5 * 3 * 4, dtype=np.float32).reshape(2, 5, 3, 4)
    got = np.zeros((2, 5, 1, 12), np.float32)
    rows = check._as_rows(ref, got)
    assert rows.shape == got.shape
    np.testing.assert_array_equal(rows[1, 2, 0, 4:8], ref[1, 2, 1])
    assert check._as_rows(ref, ref) is ref
    with pytest.raises(RuntimeError, match="another shape"):
        check._as_rows(ref, np.zeros((2, 5, 1, 16), np.float32))


# -- the gauge ---------------------------------------------------------------------

@pytest.mark.parametrize("form, width, tiles", [
    ("mha", 128, 2), ("two-tiles", 256, 4), ("gqa-rope", 128, 2),
    ("looped", 128, 4), ("narrow", 8, 4), ("five-heads-of-64", 64, 40),
    ("heads-of-128", 128, 32), ("latent", 4, 2), ("hybrid", None, None)])
def test_summary_says_which_way_the_pool_keeps_a_row(form, width, tiles):
    cfg, params = model(form)
    options = dict(prefill_buckets=(8, 16)) if form != "hybrid" else {}
    server = InferenceServer(params, cfg, n_slots=2, warmup=False, **options)
    summary = server.metrics.summary()
    shape = server.engine.pool.cache["k"].shape
    if form == "hybrid":
        width = cfg.kv_heads * cfg.head_dim
        tiles = shape[0] * -(-width // 128)
    assert summary["kv_row_width"] == shape[-1] == width
    assert summary["kv_row_tiles"] == tiles
    assert isinstance(summary["kv_row_width"], int)
    facts = server.engine.pool.audit_facts()
    assert (facts["row_width"], facts["row_tiles"]) == (width, tiles)
    page = render_prometheus(server.metrics.registry)
    assert f"mingpt_serve_kv_row_width {width}" in page.replace(".0", "")


def test_the_counters_of_a_run_carry_the_two_gauges(gpt2_cell):
    """``serve_cell.Driver._counters`` takes every numeric field of
    ``summary()``: the gauges reach a run's counters with no edit."""
    _, _, driver = gpt2_cell
    counters = driver._counters()
    assert counters["kv_row_width"] == 128
    assert counters["kv_row_tiles"] == 2 * 1
