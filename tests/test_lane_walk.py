"""The decode step's attention walks only the lanes that hold a request, each
in blocks to its own position, and a block that enough of them need for all
lanes in one step (ISSUE 45): ``attention.step_plan`` is the one rule, for
the program (``_attend_step``'s two loops) and for whoever counts
(``step_rows_read``, ``engine.decode_rows_read``). The walked step against a
plain softmax over each lane's own rows; NaN planted in every row the rule
says is not read; the rows the loops take at run time against the rule;
served tokens against solo ``generate``'s. Where rows lie side by side and
Mosaic compiles, the pairs go through one Pallas kernel a layer (ISSUE 62:
``_rows_attend_pairs``, every lane alone): the kernel in interpret mode
against the XLA walk and one pass, the rows its grid takes against the rule,
its block against the cells' leaves, its served tokens and its gauge. CPU,
tiny, float32."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mingpt_distributed_tpu.config import GPTConfig, MeshConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.ops import attention as attn_ops
from mingpt_distributed_tpu.parallel import mesh as mesh_lib
from mingpt_distributed_tpu.serving import InferenceServer, Request
from mingpt_distributed_tpu.serving import engine as engine_mod
import stacks
from oracles import solo_greedy

BLOCK, ROWS = 8, 32
#: a lane at every place a block's edge can catch: nothing to read, one row,
#: an edge from below, on it and past it, and the slot's last row
REACHES = np.array([0, 1, BLOCK - 1, BLOCK, BLOCK + 1, ROWS - 1], np.int32)
LANES = len(REACHES)
LIVE = {
    "none": np.zeros(LANES, bool),
    "one": np.arange(LANES) == 4,
    "some": np.array([True, False, True, False, True, True]),
    "all": np.ones(LANES, bool),
    "unnamed": None,
}
#: a row's bytes beside a step's cost (``walk_in_blocks``'s ``alone``): a
#: block is shared only where every lane needs it, or wherever two do
COSTS = {"lanes-alone": True, "blocks-shared": False}
HEADS, SIZE = 4, 8


def reach_of(live):
    return REACHES if live is None else REACHES * live


def plan_of(walk, live):
    """(shared, need) of the tests' lanes under ``live``."""
    return attn_ops.step_plan(walk, reach_of(live))


# -- the step against a plain softmax ---------------------------------------------

def per_head_inputs(kv_heads):
    keys = jax.random.split(jax.random.key(4), 5)
    q = jax.random.normal(keys[0], (LANES, 1, HEADS, SIZE))
    k_cache, v_cache = (
        jax.random.normal(k, (2, LANES, ROWS, kv_heads, SIZE))
        for k in keys[1:3])
    k_new, v_new = (jax.random.normal(k, (LANES, 1, kv_heads, SIZE))
                    for k in keys[3:5])
    return q, k_cache, v_cache, k_new, v_new


def plain_per_head(q, k_cache, v_cache, k_new, v_new, rows, window=None,
                   logit_softcap=None):
    """Each lane alone: one softmax over its first ``rows[b]`` cached rows
    of plane 1 and its own new row."""
    out = []
    for b, n in enumerate(rows):
        k = jnp.concatenate([k_cache[1, b, :n], k_new[b]])     # (n + 1, KV, hd)
        v = jnp.concatenate([v_cache[1, b, :n], v_new[b]])
        k, v = (attn_ops.repeat_kv(a[None], HEADS // a.shape[1])[0]
                for a in (k, v))
        z = attn_ops.softcap(
            jnp.einsum("hd,shd->hs", q[b, 0], k) / np.sqrt(SIZE),
            logit_softcap)
        if window is not None:
            z = jnp.where((n - jnp.arange(n + 1) < window)[None], z,
                          attn_ops.NEG_INF)
        out.append(jnp.einsum("hs,shd->hd", jax.nn.softmax(z, -1), v))
    return jnp.stack(out)[:, None]


def side_by_side(a):
    return a.reshape(*a.shape[:-2], 1, -1)


@pytest.mark.parametrize("cost", sorted(COSTS))
@pytest.mark.parametrize("live", sorted(LIVE))
@pytest.mark.parametrize("case", [
    dict(), dict(window=5), dict(logit_softcap=3.0)],
    ids=["plain", "window", "softcap"])
@pytest.mark.parametrize("rows", ["per-head", "gqa", "side-by-side"])
def test_the_walked_step_is_a_plain_softmax_over_each_lane_s_own_rows(
        rows, case, live, cost, walk_in_blocks):
    """A lane that holds a request attends exactly the rows before its
    position and its own; one that does not, its own alone where no block
    is shared (and is nobody's where one is)."""
    walk = walk_in_blocks(BLOCK, COSTS[cost])(ROWS)
    q, k_cache, v_cache, k_new, v_new = per_head_inputs(
        2 if rows == "gqa" else HEADS)
    caches = (k_cache, v_cache, k_new, v_new)
    if rows == "side-by-side":
        caches = tuple(map(side_by_side, caches))
    live = LIVE[live]
    got = jax.jit(lambda q, *c: attn_ops.causal_attend_step(
        q, c[0], c[1], 1, c[2], c[3], jnp.asarray(REACHES), walk,
        frontier=None if live is None else jnp.asarray(REACHES * live),
        **case))(q, *caches)
    want = plain_per_head(q, k_cache, v_cache, k_new, v_new,
                          reach_of(live), **case)
    # a shared block gives a lane that is passed by rows: it is nobody's
    judged = live if live is not None and plan_of(walk, live)[0] > 0 \
        else np.ones(LANES, bool)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got)[judged],
                               np.asarray(want)[judged], rtol=2e-5, atol=2e-6)


def latent_inputs():
    keys = jax.random.split(jax.random.key(6), 6)
    r, e = 16, 4
    return (jax.random.normal(keys[0], (LANES, 1, HEADS, r)),
            jax.random.normal(keys[1], (LANES, 1, HEADS, e)),
            jax.random.normal(keys[2], (2, LANES, ROWS, 1, r)),
            jax.random.normal(keys[3], (2, LANES, ROWS, 1, e)),
            jax.random.normal(keys[4], (LANES, 1, 1, r)),
            jax.random.normal(keys[5], (LANES, 1, 1, e)))


def plain_latent(q_lat, q_pe, latents, pes, new, new_pe, rows, scale):
    out = []
    for b, n in enumerate(rows):
        lat = jnp.concatenate([latents[1, b, :n, 0], new[b, 0]])
        pe = jnp.concatenate([pes[1, b, :n, 0], new_pe[b, 0]])
        z = (q_lat[b, 0] @ lat.T + q_pe[b, 0] @ pe.T) * scale
        out.append(jax.nn.softmax(z, -1) @ lat)
    return jnp.stack(out)[:, None]


@pytest.mark.parametrize("cost", sorted(COSTS))
@pytest.mark.parametrize("live", sorted(LIVE))
def test_the_walked_latent_step_is_a_plain_softmax_over_each_lane_s_own_rows(
        live, cost, walk_in_blocks):
    walk = walk_in_blocks(BLOCK, COSTS[cost])(ROWS)
    inputs = latent_inputs()
    live = LIVE[live]
    got = jax.jit(lambda *a: attn_ops.latent_attend_step(
        *a[:4], 1, *a[4:], jnp.asarray(REACHES), walk,
        frontier=None if live is None else jnp.asarray(REACHES * live),
        scale=0.2))(*inputs)
    want = plain_latent(*inputs, reach_of(live), 0.2)
    judged = live if live is not None and plan_of(walk, live)[0] > 0 \
        else np.ones(LANES, bool)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got)[judged],
                               np.asarray(want)[judged], rtol=2e-5, atol=2e-6)


# -- nothing past the plan is read ------------------------------------------------

def planted(cache, walk, live):
    """NaN in every row the plan does not read: past the blocks shared by
    all lanes and a lane's own."""
    shared, need = plan_of(walk, live)
    read = (int(shared) + np.asarray(need)) * BLOCK       # rows a lane
    dead = np.arange(ROWS)[None, :] >= read[:, None]      # (B, S)
    return jnp.where(dead[None, :, :, None, None], jnp.nan, cache), read


@pytest.mark.parametrize("cost", sorted(COSTS))
@pytest.mark.parametrize("live", sorted(LIVE))
@pytest.mark.parametrize("form", ["per-head", "side-by-side", "latent"])
def test_nan_in_every_row_the_plan_leaves_out_changes_no_bit(
        form, live, cost, walk_in_blocks):
    """The proof that nothing there is read: NaN in every row at or past
    the whole blocks a lane is read to, and in every row of a lane that is
    passed by, leaves every lane's output what it was, bit for bit."""
    walk = walk_in_blocks(BLOCK, COSTS[cost])(ROWS)
    live = LIVE[live]
    frontier = None if live is None else jnp.asarray(REACHES * live)
    if form == "latent":
        inputs, at = latent_inputs(), (2, 3)
        run = jax.jit(lambda *a: attn_ops.latent_attend_step(
            *a[:4], 1, *a[4:], jnp.asarray(REACHES), walk,
            frontier=frontier, scale=0.2))
    else:
        inputs, at = per_head_inputs(HEADS), (1, 2)
        if form == "side-by-side":
            inputs = (inputs[0],) + tuple(map(side_by_side, inputs[1:]))
        run = jax.jit(lambda q, *c: attn_ops.causal_attend_step(
            q, c[0], c[1], 1, c[2], c[3], jnp.asarray(REACHES), walk,
            frontier=frontier))
    clean = run(*inputs)
    dirty = list(inputs)
    for i in at:
        dirty[i], read = planted(inputs[i], walk, live)
    if live is not None and not live.any():
        assert (read == 0).all()        # every row of every slot is NaN
    assert np.isfinite(np.asarray(clean)).all()
    np.testing.assert_array_equal(run(*dirty), clean)


# -- one rule for the program and for whoever counts ---------------------------------

def rows_taken(run, *args):
    """Rows the loops of ``run`` cut out of the first cache buffer, counted
    as they run (eagerly: a loop is then Python's own): lanes x rows of
    every ``_lane_rows`` call."""
    taken = []
    cut = attn_ops._lane_rows

    def counted(buf, layer, lane, lanes, start, size):
        taken.append(lanes * size)
        return cut(buf, layer, lane, lanes, start, size)
    attn_ops._lane_rows = counted
    try:
        with jax.disable_jit():
            run(*args)
    finally:
        attn_ops._lane_rows = cut
    return sum(taken) // 2          # two buffers a step


@pytest.mark.parametrize("cost", sorted(COSTS))
@pytest.mark.parametrize("live", sorted(LIVE))
@pytest.mark.parametrize("form", ["per-head", "latent"])
def test_the_rows_the_loops_take_are_the_rule_s(form, live, cost,
                                                walk_in_blocks):
    """``step_rows_read`` = ``engine.decode_rows_read`` = the two loops'
    trip counts x the lanes and rows a step of each takes, on the same
    vectors."""
    walk = walk_in_blocks(BLOCK, COSTS[cost])(ROWS)
    live = LIVE[live]
    cfg = GPTConfig.make(
        n_layer=2, n_head=HEADS, n_embd=HEADS * SIZE, vocab_size=50,
        block_size=ROWS, embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0,
        dtype="float32", **(dict(
            rope=True, rmsnorm=True, swiglu=True, tie_weights=False,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8) if form == "latent" else {}))
    shared, need = plan_of(walk, live)
    ruled = attn_ops.step_rows_read(walk, reach_of(live))
    assert ruled == (int(shared) * LANES + int(need.sum())) * BLOCK
    # the engine's walk over a pool of these lanes is this one
    assert engine_mod.decode_walk(cfg, gen.init_cache(cfg, LANES)) == walk
    assert engine_mod.decode_rows_read(REACHES, live, walk) == ruled
    assert int(engine_mod.decode_rows_read(
        jnp.asarray(REACHES), None if live is None else jnp.asarray(live),
        walk)) == ruled
    frontier = None if live is None else jnp.asarray(REACHES * live)
    if form == "latent":
        run = lambda *a: attn_ops.latent_attend_step(
            *a[:4], 1, *a[4:], jnp.asarray(REACHES), walk,
            frontier=frontier, scale=0.2)
        inputs = latent_inputs()
    else:
        run = lambda q, *c: attn_ops.causal_attend_step(
            q, c[0], c[1], 1, c[2], c[3], jnp.asarray(REACHES), walk,
            frontier=frontier)
        inputs = per_head_inputs(HEADS)
    assert rows_taken(run, *inputs) == ruled
    # alone: whole blocks up to each reach; shared: never more than all
    alone = int((-(-reach_of(live) // BLOCK)).sum()) * BLOCK
    assert alone <= ruled <= LANES * ROWS
    if COSTS[cost] and not (live is None or live.all()):
        assert ruled == alone


#: the serving cells' pools, as ``generate.cache_leaf_shapes`` lays them and
#: the step sees them: bfloat16, the first leaf's row deciding the layout
CELL_LEAVES = {
    "gpt2-124m": [(12, 64, 1024, 1, 768)] * 2,
    "gpt2-xl": [(48, 4, 1024, 25, 64)] * 2,
    "ouro-2.6b": [(192, 3, 1024, 16, 128)] * 2,
    "ouro-2.6b-8": [(192, 8, 1024, 16, 128)] * 2,     # a house of eight
    "kanana-2-30b-a3b": [(6, 64, 8192, 1, 512), (6, 64, 8192, 1, 64)],
}


@pytest.mark.parametrize("cell, row_bytes, block", [
    ("gpt2-124m", 3072, 512),          # 4 MiB would be the slot: half of it
    ("gpt2-xl", 6400, 0),              # 25 heads of 64 lie positions minor
    ("ouro-2.6b", 8192, 0),            # three slots: 24 MiB a plane, one pass
    ("kanana-2-30b-a3b", 1152, 1024),  # a latent's block, as PR 33 found it
])
def test_the_block_follows_from_the_leaves_shapes(cell, row_bytes, block):
    walk = attn_ops.step_walk(CELL_LEAVES[cell], 2,
                              latent=cell.startswith("kanana"))
    assert walk == (CELL_LEAVES[cell][0][2], row_bytes, block, False)
    if block and not cell.startswith("kanana"):
        assert block * row_bytes <= attn_ops.STEP_COST_BYTES
        assert 2 * block <= walk.s


@pytest.mark.parametrize("s, row_bytes, block", [
    (48, 64, 24),                       # tiny rows: half the slice
    (40, 64, 20),
    (7, 64, 7),                         # no whole number of blocks: one
    (64, 1 << 20, 4),                   # 4 MiB a block: a step's cost
    (64, 8 << 20, 1),                   # a row over a step's cost
    (8192, 1152, 2048),                 # were kanana's rows no latent's
])
def test_a_block_stays_under_a_step_s_cost_and_half_the_slice(
        s, row_bytes, block):
    """A house large enough to be walked at all (``many``): the block is a
    matter of one lane's slice."""
    many = 1 << 20
    assert attn_ops.step_block(s, row_bytes, many) == block
    assert attn_ops.step_block(s, row_bytes, many, latent=True) \
        == (1024 if s % 1024 == 0 else s)


#: 32 MiB: what one pass reads in the time of the steps a walk is given up for
ONE_PASS = attn_ops.ONE_PASS_STEPS * attn_ops.STEP_COST_BYTES


@pytest.mark.parametrize("lanes, s, row_bytes, latent, block", [
    (3, 1024, 8192, False, 0),          # ouro's cell: 24 MiB a plane
    (4, 1024, 8192, False, 0),          # the last house read in one pass
    (5, 1024, 8192, False, 512),
    (10, 1024, 3072, False, 0),         # GPT-2 124M: walked from 11 slots
    (11, 1024, 3072, False, 512),
    (3, 8192, 1152, True, 0),           # a latent likewise: 27 MiB
    (4, 8192, 1152, True, 1024),
    (1, 8192, 4096, False, 0),          # one long slot of 32 MiB
    (2, 8192, 4096, False, 1024),
])
def test_a_house_one_pass_reads_in_a_few_steps_time_is_not_walked(
        lanes, s, row_bytes, latent, block):
    """Slices that, all of them whole, are within ONE_PASS_STEPS steps' cost
    are read in one pass whatever the lanes do (ouro's cell: the walk read a
    plane in 17.9-50 us by the lanes against 43.5 us flat, and the gap
    followed the seed); the counter says so."""
    assert (lanes * s * row_bytes <= ONE_PASS) == (block == 0)
    assert attn_ops.step_block(s, row_bytes, lanes, latent) == block
    walk = attn_ops.StepWalk(s, row_bytes, block)
    reach = np.arange(lanes) == 0           # one lane, a row in
    assert attn_ops.step_rows_read(walk, reach) == (block or lanes * s)


@pytest.mark.parametrize("cell, live_at, shared, alone", [
    # gpt2-124m.serve-decode: two lanes of 64 a quarter into their slots
    ("gpt2-124m", {3: 120, 40: 250}, 0, 2),
    # a full house of it: every slot's first block together, once
    ("gpt2-124m", {b: 200 for b in range(64)}, 1, 0),
    # sixteen lanes, half of them past the first block: each alone
    ("gpt2-124m", {b: 200 + 10 * b for b in range(0, 64, 4)}, 0, 24),
    # twenty-two: the first block together
    ("gpt2-124m", {b: 200 + 3 * b for b in range(0, 64, 3)}, 1, 0),
    ("gpt2-124m", {b: 200 for b in range(0, 64, 8)}, 0, 8),
    # ouro at eight slots: one lane alone; three, each alone still; six,
    # five of them past a block: both blocks together
    ("ouro-2.6b-8", {1: 300}, 0, 1),
    ("ouro-2.6b-8", {0: 300, 2: 700, 5: 80}, 0, 4),
    ("ouro-2.6b-8", {0: 600, 1: 30, 2: 700, 3: 520, 4: 1000, 7: 800}, 2, 0),
    # kanana: twenty of 64 lanes: the blocks fifteen or more stand past
    # together, the rest a lane at a time; eight lanes, each alone
    ("kanana-2-30b-a3b", {3 * b: 600 + 190 * b for b in range(20)}, 2, 19),
    ("kanana-2-30b-a3b", {5 * b: 900 + 500 * b for b in range(8)}, 0, 24),
])
def test_a_block_is_shared_where_that_is_the_cheaper(cell, live_at, shared,
                                                     alone):
    leaves = CELL_LEAVES[cell]
    walk = attn_ops.step_walk(leaves, 2, latent=cell.startswith("kanana"))
    reach = np.zeros(leaves[0][1], np.int64)
    for lane, at in live_at.items():
        reach[lane] = at
    got_shared, need = attn_ops.step_plan(walk, reach)
    assert (int(got_shared), int(need.sum())) == (shared, alone)
    assert attn_ops.step_rows_read(walk, reach) == rows_by_hand(walk, reach) \
        == (shared * len(reach) + alone) * walk.block


def test_slices_that_lie_positions_minor_are_read_whole():
    """GPT-2 XL's 25 heads of 64: one pass over every lane's slice, the
    parent's read, whatever the lanes do; the counter says so."""
    walk = attn_ops.step_walk(CELL_LEAVES["gpt2-xl"], 2)
    for reach in ([0, 0, 0, 0], [650, 0, 0, 12], [640, 650, 660, 670]):
        assert attn_ops.step_rows_read(walk, np.array(reach)) == 4 * 1024
    cfg = GPTConfig.make(model_type="gpt2-xl", dtype="bfloat16")
    assert engine_mod.decode_walk(cfg, pool_of(cfg, 4)) == walk
    assert engine_mod.decode_rows_read(
        np.array([640, 650, 1023, 670]),
        np.array([True, True, False, True]), walk) == 4 * 1024


def rows_by_hand(walk, reach):
    """The rule spelled out a block at a time: block j of the slices costs
    a step and every lane's bytes read together, a step and a block a lane
    that needs it read alone; the cheaper is taken."""
    if not walk.block:
        return len(reach) * walk.s
    cost, one = attn_ops.STEP_COST_BYTES, walk.block * walk.row_bytes
    rows = 0
    for j in range(walk.s // walk.block):
        wanted = sum(int(r) > j * walk.block for r in reach)
        together = wanted * (cost + one) >= cost + len(reach) * one
        rows += (len(reach) if together else wanted) * walk.block
    return rows


def pool_of(cfg, lanes, dtype=None):
    """A ``lanes``-slot pool of ``cfg`` as the engine's is laid, its leaves'
    shapes and dtypes alone."""
    return jax.eval_shape(lambda: gen.init_cache(cfg, lanes, dtype))


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("model_type", [
    "gpt2", "gpt2-xl", "llama-3-8b", "kanana-2-30b-a3b-instruct-2601"])
def test_the_engine_s_count_is_the_rule_s_at_published_shapes(model_type,
                                                              seed):
    """``engine.decode_walk`` of a pool at published shapes is the rule on
    the leaves ``generate.cache_leaf_shapes`` gives it, a latent's block and
    a positions-minor leaf's one pass included, and ``decode_rows_read`` by
    it the rule's count."""
    cfg = GPTConfig.make(model_type=model_type, dtype="bfloat16")
    rng = np.random.default_rng(seed)
    lanes = int(rng.choice([3, 16, 64]))
    live = rng.random(lanes) < rng.choice([0.05, 0.3, 0.9])
    positions = np.where(live, rng.integers(0, cfg.block_size, lanes),
                         cfg.block_size - 1)
    shapes = gen.cache_leaf_shapes(cfg, lanes)
    walk = attn_ops.step_walk([shapes["v"], shapes["k"]], 2,
                              latent=bool(cfg.kv_lora_rank))
    assert engine_mod.decode_walk(cfg, pool_of(cfg, lanes)) == walk
    got = engine_mod.decode_rows_read(positions, live, walk)
    assert got == attn_ops.step_rows_read(walk, positions * live) \
        == rows_by_hand(walk, positions * live)
    assert got <= lanes * cfg.block_size
    if model_type == "gpt2-xl":
        assert walk.block == 0 and got == lanes * cfg.block_size


@pytest.mark.parametrize("lanes, held, row_bytes, block", [
    (10, "bfloat16", 3072, 0),      # 30 MiB a plane: one pass
    (10, "float32", 6144, 512),     # the same pool held wider: 60, walked
    (5, "float32", 6144, 0),
    (64, "float32", 6144, 512),
])
def test_the_walk_follows_the_dtype_the_pool_is_held_in(lanes, held,
                                                        row_bytes, block):
    """``DecodeEngine(cache_dtype=...)`` holds the pool in another dtype
    than ``cfg.dtype``: a row's bytes, and with them the block and whether
    a house is walked at all, are the pool's own leaves', for the program
    and for the counter alike (GPT-2 124M's shapes, ``cfg.dtype``
    bfloat16)."""
    cfg = GPTConfig.make(model_type="gpt2", dtype="bfloat16")
    pool = pool_of(cfg, lanes, jnp.dtype(held))
    walk = engine_mod.decode_walk(cfg, pool)
    assert walk == (1024, row_bytes, block, False) \
        == gen.cache_walk(cfg, pool)
    reach = np.where(np.arange(lanes) == 1, 300, 0)
    assert engine_mod.decode_rows_read(reach, None, walk) \
        == (block or lanes * 1024)


def walks_traced(monkeypatch):
    """The ``walk`` every traced ``causal_attend_step`` was handed."""
    seen, real = [], attn_ops.causal_attend_step

    def recorded(*args, **kwargs):
        seen.append(args[7])
        return real(*args, **kwargs)
    monkeypatch.setattr(attn_ops, "causal_attend_step", recorded)
    return seen


@pytest.mark.parametrize("options, row_bytes", [
    (dict(), 4 * 2 * 128),
    (dict(cache_dtype=jnp.bfloat16), 2 * 2 * 128),
    # a quantized pool is dequantized to ``cfg.dtype`` before the step
    (dict(kv_dtype="int8"), 4 * 2 * 128)],
    ids=["as-cfg", "cache-dtype", "int8"])
def test_an_engine_s_program_and_counter_share_one_walk(options, row_bytes,
                                                        monkeypatch):
    """The engine works its pool's walk out once, from the leaves the step
    reads, and the decode program is traced with that very object: what
    ``decode_rows_read`` counts by is what every layer walked by."""
    cfg, params = model("mha")
    seen = walks_traced(monkeypatch)
    eng = engine_mod.DecodeEngine(params, cfg, n_slots=3,
                                  prefill_buckets=(8, 16, 32), **options)
    assert eng.walk == (ROWS, row_bytes, 0, False)
    s = eng.n_slots
    eng.decode_step(
        np.zeros(s, np.int32), np.array([3, 9, ROWS - 1], np.int32),
        np.ones(s, np.float32), np.zeros(s, np.int32),
        np.ones(s, np.float32), np.zeros(s, bool), np.zeros(s, np.uint32))
    assert len(seen) == cfg.n_layer and all(w is eng.walk for w in seen)


# -- through the model and the server -------------------------------------------

#: forms of ``tests/stacks.py``'s table (a block of ``ROWS`` rows)
MODELS = ("gqa-rope", "latent", "looped", "mha", "window-softcap")
PROMPTS = [[1, 2, 3, 4, 5], list(range(7, 22)), [10, 11, 12, 13],
           list(range(1, 17)) + [40, 41], list(range(1, 10)), [33]]
BUDGETS = [9, 4, 7, 5, 12, 3]


def model(name):
    assert stacks.FORMS[name]["block_size"] == ROWS
    return stacks.form_model(name)


def served(cfg, params, tp=None, **options):
    """Six requests through three slots, admitted while others decode (the
    lanes stand at different positions and the live set changes from round
    to round), the last two into slots that were freed: each request's
    tokens, and the server."""
    if tp:
        options["mesh"] = mesh_lib.make_mesh(
            MeshConfig(dp=1, tp=tp), devices=jax.devices()[:tp])
    server = InferenceServer(params, cfg, n_slots=3,
                             prefill_buckets=(8, 16, 32), warmup=True,
                             **options)
    handles = []
    for prompt, budget in zip(PROMPTS[:4], BUDGETS):
        handles.append(server.submit(
            Request(prompt=prompt, max_new_tokens=budget)))
        server.step()
    server.run_until_drained(max_steps=400)
    for prompt, budget in zip(PROMPTS[4:], BUDGETS[4:]):
        handles.append(server.submit(
            Request(prompt=prompt, max_new_tokens=budget)))
    server.run_until_drained(max_steps=400)
    return [h.tokens for h in handles], server


@pytest.mark.parametrize("cost", sorted(COSTS))
@pytest.mark.parametrize("name", MODELS)
def test_served_tokens_are_solo_generate_s(name, cost, walk_in_blocks):
    """Greedy tokens of every request are solo ``generate``'s (the parent's
    served tokens, by its own tests), staggered admission and a freed and
    refilled slot included; still one decode program, whatever the lanes
    did."""
    walk_in_blocks(BLOCK, COSTS[cost])
    cfg, params = model(name)
    got, server = served(cfg, params)
    for tokens, prompt, budget in zip(got, PROMPTS, BUDGETS):
        assert tokens == solo_greedy(params, cfg, prompt, budget)
    assert server.compile_counts()["decode"] == 1
    assert server.watchdog.recompiles == 0
    summary = server.summary()
    assert 0 < summary["decode_rows_read"] < summary["decode_rows_reserved"]
    assert summary["decode_rows_read"] % BLOCK == 0


@pytest.mark.parametrize("options", [
    dict(kv_dtype="int8"), dict(tp=2), dict(kv_dtype="int8", tp=2),
    dict(prefill_chunk=4), dict(prefix_cache_mb=8.0)],
    ids=["int8", "tp2", "int8-tp2", "chunked", "prefix-store"])
@pytest.mark.parametrize("name", ["mha", "gqa-rope"])
def test_the_walk_under_a_mesh_an_int8_pool_and_the_pool_s_other_users(
        name, options, walk_in_blocks):
    """The walk cuts lanes and rows, never heads (a ``tp`` mesh shards the
    heads' axis), and takes a dequantized pool like any other: tokens are
    those of the same server with every slot one block, and an unquantized
    pool's are solo ``generate``'s."""
    cfg, params = model(name)
    want, _ = served(cfg, params, **options)
    walk_in_blocks(BLOCK)
    got, server = served(cfg, params, **options)
    assert got == want
    if "kv_dtype" not in options:
        for tokens, prompt, budget in zip(got, PROMPTS, BUDGETS):
            assert tokens == solo_greedy(params, cfg, prompt, budget)
    assert server.compile_counts()["decode"] == 1


def test_one_decode_program_while_the_live_set_changes_every_round(
        walk_in_blocks):
    """``compile_counts()["decode"]`` stays 1 through rounds whose live
    lanes, reaches, shared blocks and pairs all differ: the trip counts
    are traced, the plan is arithmetic on the two vectors the program
    already takes."""
    walk = walk_in_blocks(BLOCK, alone=False)(ROWS)
    cfg, params = model("mha")
    server = InferenceServer(params, cfg, n_slots=4,
                             prefill_buckets=(8, 16, 32), warmup=True,
                             recompile_fail=True)
    assert server.engine.walk == walk
    before = server.compile_counts()
    plans = set()
    handles = []
    for i in range(8):
        handles.append(server.submit(Request(
            prompt=list(range(1, 2 + 3 * i)), max_new_tokens=2 + i % 3)))
        server.step()
        st = server.slots
        live = np.isin(np.arange(4), st.decoding_slots())
        shared, need = attn_ops.step_plan(walk, st.positions * live)
        plans.add((int(shared), int(need.sum())))
    server.run_until_drained(max_steps=200)
    assert len(plans) >= 4
    assert server.compile_counts() == before
    assert all(h.tokens for h in handles)


# -- the kernel's walk (ISSUE 62) ----------------------------------------------

#: tiny rows of the three cells the kernel walks, (query heads, KV heads,
#: head size): heads of 64 side by side, twelve a row as GPT-2 124M's, or
#: two KV heads with the grouped queries a KV head of laguna's full layers
#: (6), smallthinker's (7), laguna's rings (8); smallthinker's again with
#: heads of a whole lane tile, as the cells' own are
KERNEL_ROWS = {"mha-12": (12, 12, 64), "gqa-6": (12, 2, 64),
               "gqa-7": (14, 2, 64), "gqa-8": (16, 2, 64),
               "gqa-7-tiles": (14, 2, 128)}
#: a full layer's lanes: nothing to read, one row, exactly a block's edge,
#: just past it, the slot's last row, and a middle one
FULL_AT = np.array([0, 1, BLOCK, BLOCK + 1, ROWS - 1, 2 * BLOCK + 3], np.int32)
#: a ring's, of ROWS rows: position 0, younger than the window, at a block's
#: edge, exactly the window, and twice wrapped (on a block's edge and off it)
RING_AT = np.array([0, 3, BLOCK, ROWS, 2 * ROWS + BLOCK, 3 * ROWS + 5],
                   np.int32)
KERNEL_CASES = {
    "full": ("full", {}),
    "full-window-softcap": ("full", dict(window=5, logit_softcap=3.0)),
    "ring": ("ring", {}),
}


def side_by_side_inputs(heads, kv_heads, hd=64, seed=8):
    """Queries, two planes of cache and the lanes' new rows, a position's
    heads side by side. Every cached row is random: what lies at or past a
    lane's position, or in a ring at an age the request never had, is a
    stale row its slot's last tenant left."""
    keys = jax.random.split(jax.random.key(seed), 5)
    width = kv_heads * hd
    q = jax.random.normal(keys[0], (LANES, 1, heads, hd))
    k_cache, v_cache = (jax.random.normal(k, (2, LANES, ROWS, 1, width))
                        for k in keys[1:3])
    k_new, v_new = (jax.random.normal(k, (LANES, 1, 1, width))
                    for k in keys[3:5])
    return q, k_cache, v_cache, k_new, v_new


def step_of(kind, walk, positions, live, **case):
    """The decode step of a full layer or a ring over plane 1, jitted."""
    positions = jnp.asarray(positions)
    frontier = None if live is None else positions * jnp.asarray(live)
    if kind == "ring":
        return jax.jit(lambda q, *c: attn_ops.ring_attend_step(
            q, c[0], c[1], 1, c[2], c[3], positions, walk,
            frontier=frontier))
    return jax.jit(lambda q, *c: attn_ops.causal_attend_step(
        q, c[0], c[1], 1, c[2], c[3], positions, walk, frontier=frontier,
        **case))


@pytest.mark.parametrize("live", ["all", "some"])
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
@pytest.mark.parametrize("rows", sorted(KERNEL_ROWS))
def test_the_kernel_s_walk_is_the_xla_walk_s_and_one_pass_s(rows, case, live):
    """One softmax three ways: the Pallas kernel (interpret mode), the XLA
    walk in the same blocks, and one pass over every lane's whole slice
    under the same mask. A lane at position 0 attends its own row alone, a
    lane at a block's edge takes that block and no more, a ring that has
    wrapped shows every row but the one it will replace and a younger one
    what it has written; stale rows, the window and the softcap change
    nothing between them. A lane that is not live attends its own new row
    alone in both walks (one pass reads it to its position: not judged)."""
    kind, options = KERNEL_CASES[case]
    at = RING_AT if kind == "ring" else FULL_AT
    inputs = side_by_side_inputs(*KERNEL_ROWS[rows])
    live = LIVE[live]
    got, walked, whole = (
        np.asarray(step_of(kind, walk, at, live, **options)(*inputs))
        for walk in (attn_ops.StepWalk(ROWS, 1 << 40, BLOCK, True),
                     attn_ops.StepWalk(ROWS, 1 << 40, BLOCK),
                     attn_ops.StepWalk(ROWS, 1, 0)))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, walked, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got[live], whole[live], rtol=2e-5, atol=2e-6)
    # a lane passed by, or at position 0, attends its own new row alone
    alone = ~live | (at == 0)
    heads, kv_heads, hd = KERNEL_ROWS[rows]
    own = np.repeat(np.asarray(inputs[4]).reshape(LANES, 1, kv_heads, hd),
                    heads // kv_heads, axis=2)
    np.testing.assert_allclose(got[alone], own[alone], rtol=1e-6)


def blocks_the_step_reads(kind, walk, at, live, inputs):
    """(LANES, blocks) bool, observed: NaN planted in block ``j`` of every
    lane's values turns a lane's output to NaN exactly where the step read
    that block of that lane (a masked row's weight is 0, and 0 x NaN is
    NaN)."""
    run = step_of(kind, walk, at, live)
    assert np.isfinite(np.asarray(run(*inputs))).all()
    read = []
    for j in range(ROWS // BLOCK):
        values = inputs[2].at[1, :, j * BLOCK:(j + 1) * BLOCK].set(jnp.nan)
        out = np.asarray(run(*inputs[:2], values, *inputs[3:]))
        read.append(np.isnan(out).any(axis=(1, 2, 3)))
    return np.stack(read, axis=1)


@pytest.mark.parametrize("live", sorted(LIVE))
@pytest.mark.parametrize("kind", ["full", "ring"])
def test_the_rows_the_kernel_s_grid_takes_are_the_rule_s(kind, live):
    """``step_rows_read`` on the kernel's ``StepWalk`` (and the engine's
    counters over it) = the blocks the kernel's grid is seen to take, a
    lane: whole blocks up to its reach, none of a lane that is not live,
    and no block for all lanes, whatever a row's bytes."""
    at = RING_AT if kind == "ring" else FULL_AT
    live = LIVE[live]
    reach = np.minimum(at if live is None else at * live, ROWS)
    for row_bytes in (1, 1 << 40):    # sharing has nothing to weigh
        walk = attn_ops.StepWalk(ROWS, row_bytes, BLOCK, True)
        shared, need = attn_ops.step_plan(walk, reach)
        assert shared == 0 and (need == -(-reach // BLOCK)).all()
        ruled = attn_ops.step_rows_read(walk, reach)
        assert ruled == int(need.sum()) * BLOCK
        if kind == "ring":
            assert engine_mod.ring_rows(at, live, walk) == (
                ruled, int(np.minimum(reach, ROWS - 1).sum()))
        else:
            assert engine_mod.decode_rows_read(at, live, walk) == ruled
    seen = blocks_the_step_reads(kind, walk, at, live,
                                 side_by_side_inputs(12, 2))
    assert (seen == (np.arange(ROWS // BLOCK)[None, :] < need[:, None])).all()
    assert int(seen.sum()) * BLOCK == ruled
    # the XLA walk over the same lanes shares a block two of them need
    if int((need > 0).sum()) >= 2:
        assert attn_ops.step_rows_read(
            attn_ops.StepWalk(ROWS, 1, BLOCK), reach) > ruled


#: the pools the kernel walks on the chip, and those it leaves
KERNEL_CELL_LEAVES = {
    "gpt2-124m": ([(12, 64, 1024, 1, 768)] * 2, False),
    "laguna-full": ([(2, 64, 8192, 1, 1024)] * 2, False),
    "laguna-rings": ([(3, 64, 512, 1, 1024)] * 2, False),
    "smallthinker-full": ([(2, 64, 16384, 1, 512)] * 2, False),
    "smallthinker-rings": ([(3, 64, 4096, 1, 512)] * 2, False),
    "kanana-2-30b-a3b": (CELL_LEAVES["kanana-2-30b-a3b"], True),
    "gpt2-xl": (CELL_LEAVES["gpt2-xl"], False),
    "ouro-2.6b": (CELL_LEAVES["ouro-2.6b"], False),
    "per-head-128": ([(4, 64, 4096, 8, 128)] * 2, False),
}


@pytest.mark.parametrize("cell, block", [
    ("gpt2-124m", 256), ("laguna-full", 256), ("laguna-rings", 256),
    ("smallthinker-full", 512), ("smallthinker-rings", 512),
    ("kanana-2-30b-a3b", None), ("gpt2-xl", None), ("ouro-2.6b", None),
    ("per-head-128", None)])
def test_the_kernel_walks_rows_side_by_side_where_mosaic_compiles(
        cell, block, monkeypatch):
    """The choice falls to what the code can see: the leaf's shape, whether
    Mosaic compiles, whether the pool lies whole on one device. Rows side
    by side are walked by the kernel in blocks of KERNEL_STEP_BYTES, a
    function of the row's bytes alone; a latent pool, a per-head leaf and
    slices that are not walked at all keep what they had, byte for byte;
    so does everything on the CPU, under a mesh and out of a quantized
    pool."""
    leaves, latent = KERNEL_CELL_LEAVES[cell]
    xla = attn_ops.step_walk(leaves, 2, latent=latent)
    assert not xla.kernel          # the CPU: Mosaic does not compile here
    monkeypatch.setattr(attn_ops, "_mosaic_compiles", lambda: True)
    assert attn_ops.step_walk(leaves, 2, latent=latent, whole=False) == xla
    walk = attn_ops.step_walk(leaves, 2, latent=latent)
    if block is None:
        assert walk == xla
        return
    assert walk == (xla.s, xla.row_bytes, block, True)
    assert block * walk.row_bytes <= attn_ops.KERNEL_STEP_BYTES \
        < 2 * block * walk.row_bytes
    assert attn_ops.kernel_block(walk.s, walk.row_bytes) == block
    # twice the lanes or a step's cost move nothing: the row's bytes alone
    more = [(leaf[0], 2 * leaf[1]) + leaf[2:] for leaf in leaves]
    assert attn_ops.step_walk(more, 2).block == block
    # a slice that is no whole number of blocks keeps the XLA walk
    assert attn_ops.kernel_block(walk.s + 24, walk.row_bytes) == 0
    odd = [leaf[:2] + (walk.s + 24,) + leaf[3:] for leaf in leaves]
    assert not attn_ops.step_walk(odd, 2).kernel


@pytest.mark.parametrize("name, kernel", [
    ("mha", True), ("gqa-rope", True), ("window-softcap", True),
    ("mha", False)])
def test_served_tokens_under_the_kernel_s_walk_and_its_gauge(
        name, kernel, walk_in_blocks):
    """The decode program with the kernel in every layer (interpret mode)
    serves solo ``generate``'s greedy tokens through staggered admission
    and refilled slots, in one program; ``decode_kernel_walk_layers`` says
    how many layers it walks (0 under the XLA walk), and the rows counted
    are the kernel's rule's: whole blocks of the live lanes alone."""
    walk_in_blocks(BLOCK, kernel=kernel)
    cfg, params = model(name)
    got, server = served(cfg, params)
    for tokens, prompt, budget in zip(got, PROMPTS, BUDGETS):
        assert tokens == solo_greedy(params, cfg, prompt, budget)
    assert server.engine.walk.kernel is kernel
    assert server.compile_counts()["decode"] == 1
    assert server.watchdog.recompiles == 0
    summary = server.summary()
    assert summary["decode_kernel_walk_layers"] \
        == server.engine.kernel_walk_layers() == (cfg.n_layer if kernel else 0)
    assert 0 < summary["decode_rows_read"] < summary["decode_rows_reserved"]
    assert summary["decode_rows_read"] % BLOCK == 0


@pytest.mark.parametrize("options, kernel", [
    (dict(), True), (dict(kv_dtype="int8"), False), (dict(tp=2), False)],
    ids=["whole", "int8", "tp2"])
def test_a_sharded_or_quantized_pool_keeps_the_xla_walk(options, kernel,
                                                        monkeypatch):
    """Where Mosaic compiles, an engine whose pool is quantized (the step
    reads a dequantized copy) or sharded over a mesh still builds its
    program with the XLA walk: the kernel reads buffers whole on one
    device, as they lie."""
    monkeypatch.setattr(attn_ops, "_mosaic_compiles", lambda: True)
    monkeypatch.setattr(attn_ops, "ONE_PASS_STEPS", 0)   # tiny slices walked
    monkeypatch.setattr(attn_ops, "KERNEL_STEP_BYTES", 16 << 10)  # 16 rows
    cfg, params = model("mha")
    if "tp" in options:
        options = dict(mesh=mesh_lib.make_mesh(
            MeshConfig(dp=1, tp=2), devices=jax.devices()[:2]))
    engine = InferenceServer(params, cfg, n_slots=3, warmup=False,
                             prefill_buckets=(8, 16, 32), **options).engine
    assert engine.walk.kernel is kernel
    assert engine.walk.block == ROWS // 2
