"""The serving decode step as one batched forward over the pool (PR 27).

``engine._decode_impl`` runs every slot's one token through the cached-block
chain at a ``(S,)`` vector of positions and writes one new row a lane into
the donated pool where it lies. What must hold, on the CPU at tiny sizes:

* structure: nothing in the program has the pool's size but the pool: no
  transpose, no scatter, no select over it; it is read by per-layer slices
  and written by row-sized ``dynamic_update_slice``s only, and the
  executable aliases each cache leaf to its output;
* parity: greedy tokens and cached rows equal solo ``generate``'s forward,
  for every architecture ``GPTConfig`` builds, with lanes at mixed positions
  (0 and ``block_size - 1`` among them) and free lanes between live ones;
* isolation: a lane's token and rows do not depend on what the other lanes
  hold, experts included.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import stacks
from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.ops import attention as attn_ops
from mingpt_distributed_tpu.serving import engine as engine_mod
from mingpt_distributed_tpu.serving.engine import DecodeEngine

BLOCK = 16
BASE = dict(n_layer=2, n_head=4, n_embd=32, vocab_size=50, block_size=BLOCK,
            embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32")
LLAMA = dict(rope=True, swiglu=True, rmsnorm=True, n_kv_head=2)
ARCHS = {
    "gpt2": {},
    "rope-gqa-swiglu": LLAMA,
    "window": dict(LLAMA, attention_window=5),
    "softcap": dict(attn_logit_softcap=5.0, final_logit_softcap=8.0),
    # capacity factor E/k: nothing drops, so a cached decode agrees with a
    # full forward (ops/moe.py's caveat)
    "experts": dict(LLAMA, n_experts=4, moe_top_k=2, moe_capacity_factor=2.0),
}
FREE = None     # a lane no request holds


@functools.lru_cache(maxsize=None)
def model(arch):
    cfg = GPTConfig.make(**BASE, **ARCHS[arch])
    return cfg, gpt.init(jax.random.key(7), cfg)


def history(lane, length):
    """A lane's own tokens: ``length`` of them, different a lane."""
    rng = np.random.default_rng(100 + lane)
    return rng.integers(1, BASE["vocab_size"], size=length).tolist()


def prefilled(arch, lanes):
    """An engine whose lane ``i`` holds ``lanes[i]``: a token history, all of
    it prefilled but the last token (the one a decode step forwards), or
    FREE."""
    cfg, params = model(arch)
    engine = DecodeEngine(params, cfg, n_slots=len(lanes), prefill_len=BLOCK,
                          prefill_buckets=[BLOCK])
    for slot, hist in enumerate(lanes):
        if hist is not FREE and len(hist) > 1:
            engine.prefill_chunk_call(
                slot, hist[:-1], 0, 1.0, None, None, False, 0)
    return engine


def step_lanes(arch, lanes):
    """One decode step over ``prefilled(arch, lanes)``, each live lane
    forwarding its history's last token and each FREE lane parked at
    ``block_size - 1``, as the scheduler parks it. Returns (next tokens
    (S,), the pool after the step)."""
    engine = prefilled(arch, lanes)
    n = len(lanes)
    tokens = np.zeros(n, np.int32)
    positions = np.full(n, BLOCK - 1, np.int32)
    for slot, hist in enumerate(lanes):
        if hist is not FREE:
            tokens[slot], positions[slot] = hist[-1], len(hist) - 1
    nxt = engine.decode_step(
        tokens, positions, np.ones(n, np.float32), np.zeros(n, np.int32),
        np.ones(n, np.float32), np.zeros(n, bool), np.zeros(n, np.uint32))
    return nxt, jax.tree.map(np.asarray, engine.pool.cache)


def solo(arch, hist):
    """Solo generate's forward over the whole history: (greedy next token,
    the (L, len, KV, hd) rows it caches)."""
    cfg, params = model(arch)
    logits, cache = stacks.forward_cached(
        params, np.asarray(hist, np.int32)[None], gen.init_cache(cfg, 1),
        0, cfg)
    rows = {n: np.asarray(cache[n])[:, 0, :len(hist)] for n in ("k", "v")}
    return int(jnp.argmax(logits[0])), rows


# ---------------------------------------------------------------------------
# (b) parity with solo generate
# ---------------------------------------------------------------------------

#: positions 0 (nothing cached), the last row of the window, and two in
#: between, with free lanes between the live ones
MIXED = [1, FREE, BLOCK, 7, FREE, 4]


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_batched_decode_equals_solo_generate(arch):
    lanes = [FREE if n is FREE else history(i, n) for i, n in enumerate(MIXED)]
    nxt, pool = step_lanes(arch, lanes)
    for slot, hist in enumerate(lanes):
        if hist is FREE:
            continue
        tok, rows = solo(arch, hist)
        assert int(nxt[slot]) == tok, (arch, slot)
        for name in ("k", "v"):
            np.testing.assert_allclose(
                pool[name][:, slot, :len(hist)], rows[name],
                rtol=1e-5, atol=1e-6, err_msg=f"{arch} lane {slot} {name}")


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_free_lane_writes_its_own_parked_row_only(arch):
    """A free lane is clipped to ``block_size - 1`` and writes there, in
    its own lane: every other row of the pool is as the prefills left it."""
    lanes = [history(0, 5), FREE, history(2, 9)]
    _, after = step_lanes(arch, lanes)
    before = jax.tree.map(np.asarray, prefilled(arch, lanes).pool.cache)
    written = {0: 4, 1: BLOCK - 1, 2: 8}
    for name in ("k", "v"):
        changed = np.any(after[name] != before[name], axis=(0, 3, 4))  # (S, P)
        for slot, row in written.items():
            assert changed[slot, row], (name, slot)
            changed[slot, row] = False
        assert not changed.any(), (name, np.argwhere(changed))


# ---------------------------------------------------------------------------
# (c) lanes are independent
# ---------------------------------------------------------------------------

NEIGHBOURS = {
    "free": [FREE, FREE, FREE],
    "live-elsewhere": [3, BLOCK, 1],
    "live-at-the-same-position": [6, 6, 6],
}


@pytest.mark.parametrize("neighbours", sorted(NEIGHBOURS))
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_lane_does_not_depend_on_other_lanes(arch, neighbours):
    """Lane 1 of four, at position 5, reads the same token and rows whether
    the other lanes are free, live at other positions or live at its own:
    the routed experts included (each lane routes alone)."""
    mine = history(1, 6)
    alone_tok, alone_rows = solo(arch, mine)
    others = [FREE if n is FREE else history(10 + i, n)
              for i, n in enumerate(NEIGHBOURS[neighbours])]
    lanes = [others[0], mine, others[1], others[2]]
    nxt, pool = step_lanes(arch, lanes)
    ref_nxt, ref_pool = step_lanes(arch, [FREE, mine, FREE, FREE])
    assert int(nxt[1]) == int(ref_nxt[1]) == alone_tok
    for name in ("k", "v"):
        np.testing.assert_array_equal(
            pool[name][:, 1, :6], ref_pool[name][:, 1, :6])
        np.testing.assert_allclose(
            pool[name][:, 1, :6], alone_rows[name], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# (a) structure of the program
# ---------------------------------------------------------------------------


def decode_args(cfg, params, n_slots):
    vec = lambda dtype, fill=0: jnp.full((n_slots,), fill, dtype)
    return (params, gen.init_cache(cfg, n_slots), vec(jnp.int32),
            vec(jnp.int32), vec(jnp.float32, 1), vec(jnp.int32),
            vec(jnp.float32, 1), vec(jnp.bool_), vec(jnp.uint32),
            vec(jnp.int32))


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_nothing_pool_sized_but_the_row_writes(arch):
    # 3 layers and 7 slots: no weight's size, and no layer's slice with its
    # KV heads repeated for grouped queries, is a multiple of the pool's
    cfg = GPTConfig.make(**dict(BASE, n_layer=3), **ARCHS[arch])
    params = gpt.init(jax.random.key(7), cfg)
    n_slots = 7
    jaxpr = jax.make_jaxpr(functools.partial(
        engine_mod._decode_impl, cfg=cfg))(*decode_args(cfg, params, n_slots))
    pool_elems = (cfg.n_layer * n_slots * cfg.block_size * cfg.kv_heads
                  * cfg.head_dim)
    row_elems = cfg.n_layer * cfg.kv_heads * cfg.head_dim
    size = lambda v: math.prod(getattr(v.aval, "shape", ()))
    pool_sized = lambda v: size(v) > 0 and size(v) % pool_elems == 0
    writes = 0
    for eqn in equations(jaxpr.jaxpr):
        name = eqn.primitive.name
        big_in = [v for v in eqn.invars if pool_sized(v)]
        big_out = [v for v in eqn.outvars if pool_sized(v)]
        if list(jax.core.jaxprs_in_params(eqn.params)):
            continue        # a call: its body's equations are judged
        if name == "dynamic_update_slice":
            if big_out:
                # the pool is the operand, the update is one lane's rows
                assert size(eqn.invars[0]) == pool_elems
                assert size(eqn.invars[1]) == row_elems, eqn
                writes += 1
            continue
        assert not big_out, f"{name} makes an array of the pool's size"
        if big_in:
            # read by slices (a layer's slice) only
            assert name in ("slice", "dynamic_slice", "gather"), name
        assert name not in ("transpose", "scatter") or not big_in
    assert writes == 2 * n_slots


@pytest.mark.parametrize("arch", ["gpt2", "experts"])
def test_executable_aliases_each_cache_leaf(arch):
    cfg, params = model(arch)
    engine = DecodeEngine(params, cfg, n_slots=3)
    text = engine._decode_jit.lower(
        *decode_args(cfg, engine.params, 3)).compile().as_text()
    header = text.split("\n", 1)[0]
    assert "input_output_alias" in header
    aliases = header.split("input_output_alias={", 1)[1].split("}, entry")[0]
    assert aliases.count("alias") == engine.audit_contracts()["decode"][
        "donated"] == 2


# ---------------------------------------------------------------------------
# the pieces the batched step leans on
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window,cap", [(None, None), (3, None), (None, 4.0),
                                        (3, 4.0)])
def test_attention_takes_an_offset_a_row(window, cap):
    """``causal_attention`` under a ``(B,)`` offset equals one call a row at
    that row's scalar offset: mask, window and softcap alike."""
    b, s, h, hd = 4, 12, 2, 8
    kq, kk, kv = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(kq, (b, 1, h, hd))
    k = jax.random.normal(kk, (b, s, h, hd))
    v = jax.random.normal(kv, (b, s, h, hd))
    offsets = jnp.asarray([0, 5, 11, 2])
    got = attn_ops.causal_attention(
        q, k, v, kv_offset=offsets, window=window, logit_softcap=cap)
    for row in range(b):
        want = attn_ops.causal_attention(
            q[row:row + 1], k[row:row + 1], v[row:row + 1],
            kv_offset=int(offsets[row]), window=window, logit_softcap=cap)
        np.testing.assert_allclose(got[row], want[0], rtol=1e-6, atol=1e-6)


def test_rope_takes_positions_a_row():
    b, t, h, hd = 3, 2, 2, 8
    x = jax.random.normal(jax.random.key(5), (b, t, h, hd))
    positions = jnp.asarray([[0, 1], [7, 8], [3, 4]])
    got = attn_ops.apply_rope(x, *attn_ops.rope_tables(positions, hd))
    for row in range(b):
        want = attn_ops.apply_rope(
            x[row:row + 1], *attn_ops.rope_tables(positions[row], hd))
        np.testing.assert_array_equal(got[row], want[0])


def test_a_position_a_lane_takes_one_token_a_lane():
    cfg, params = model("gpt2")
    with pytest.raises(ValueError, match="one token a lane"):
        gen._forward_cached(
            params, jnp.zeros((2, 3), jnp.int32), gen.init_cache(cfg, 2),
            jnp.asarray([0, 4]), cfg)


def test_int8_pool_steps_through_the_same_block():
    """A quantized pool is dequantized, stepped by the same batched block
    and requantized whole: rows the step did not write are bit-stable."""
    cfg, params = model("gpt2")
    engine = DecodeEngine(params, cfg, n_slots=3, kv_dtype="int8")
    engine.prefill_chunk_call(0, history(0, 6), 0, 1.0, None, None, False, 0)
    engine.prefill_chunk_call(2, history(2, 9), 0, 1.0, None, None, False, 0)
    before = jax.tree.map(np.asarray, engine.pool.cache)
    n = 3
    engine.decode_step(
        np.asarray([3, 0, 4], np.int32), np.asarray([6, BLOCK - 1, 9], np.int32),
        np.ones(n, np.float32), np.zeros(n, np.int32), np.ones(n, np.float32),
        np.zeros(n, bool), np.zeros(n, np.uint32))
    after = jax.tree.map(np.asarray, engine.pool.cache)
    written = np.zeros((3, BLOCK), bool)
    written[0, 6] = written[1, BLOCK - 1] = written[2, 9] = True
    for name in sorted(before):
        same = np.all(after[name] == before[name], axis=(0, 3, 4))
        assert same[~written].all(), name
