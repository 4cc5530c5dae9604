"""Quantized KV-cache tests (ISSUE 18) — CPU, tiny config, `not slow`
tier, on the conftest 8-virtual-device mesh.

The load-bearing guarantees:
* power-of-two scales make ``dequantize -> quantize`` EXACTLY
  idempotent (payload and scale bit-stable), so whole-lane
  requantize-on-write never drifts untouched rows;
* an int8 server tracks the fp32 server within the tolerance parity
  policy across chunked prefill + prefix reuse + speculative decoding
  composed, with identical compile counts and zero recompiles — the
  dtype is a compile key, not a program-structure change;
* under tp=2 the fp32 scale planes shard over kv_heads exactly like
  the payload (they share the rank-5 layout, head_dim -> 1);
* quantized rows extracted/installed through the migration seam resume
  BIT-identically — same tokens, same final pool leaves;
* ``kv_dtype="fp32"`` is the byte-identical default path: plain
  ``{"k", "v"}`` cache, no scale leaves, no quant descriptor;
* the int8+scales pool at head_dim=64 fits the <= 0.27x fp32 budget
  the acceptance gate (serve.py --selftest-quant) enforces on the
  pool's per-device bytes.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mingpt_distributed_tpu.config import GPTConfig, MeshConfig
from mingpt_distributed_tpu.models import generate as gen
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.parallel import mesh as mesh_lib
from mingpt_distributed_tpu.parallel.zero import per_device_bytes
from mingpt_distributed_tpu.serving import InferenceServer, Request
from mingpt_distributed_tpu.serving import quant as quant_lib
from mingpt_distributed_tpu.serving.engine import DecodeEngine

INT8 = quant_lib.resolve_kv_dtype("int8")


def tree_bytes(tree):
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


@pytest.fixture(scope="module")
def cfg_params():
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    return cfg, gpt.init(jax.random.key(0), cfg)


@pytest.fixture(scope="module")
def tp2_mesh():
    return mesh_lib.make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])


# ---------------------------------------------------------------------------
# roundtrip units
# ---------------------------------------------------------------------------


def test_pow2_roundtrip_is_exactly_idempotent():
    """The design invariant: quantize(dequantize(q)) == q bit-for-bit,
    payload AND scale — this is what lets the decode programs requantize
    the whole lane on every step without drifting untouched rows."""
    x = jax.random.normal(jax.random.key(1), (2, 3, 8, 2, 16)) * 3.7
    p0, s0 = quant_lib.quantize(x, INT8)
    rt = quant_lib.dequantize(p0, s0)
    p1, s1 = quant_lib.quantize(rt, INT8)
    assert np.array_equal(np.asarray(p0), np.asarray(p1))
    assert np.array_equal(np.asarray(s0), np.asarray(s1))
    # and the scales really are powers of two (or exact zero)
    s = np.asarray(s0)
    nz = s[s > 0]
    assert np.array_equal(np.exp2(np.round(np.log2(nz))), nz)
    # second roundtrip reproduces the first's floats exactly too
    rt2 = quant_lib.dequantize(p1, s1)
    assert np.array_equal(np.asarray(rt), np.asarray(rt2))


def test_quantize_error_bounded_by_half_scale():
    x = jax.random.normal(jax.random.key(2), (4, 64))
    p, s = quant_lib.quantize(x, INT8)
    err = np.abs(np.asarray(quant_lib.dequantize(p, s)) - np.asarray(x))
    assert np.all(err <= np.asarray(s) / 2 + 1e-12)


def test_zero_rows_quantize_to_exact_zeros():
    z = jnp.zeros((2, 5, 16))
    p, s = quant_lib.quantize(z, INT8)
    assert not np.any(np.asarray(p))
    assert not np.any(np.asarray(s))
    assert not np.any(np.asarray(quant_lib.dequantize(p, s)))


def test_quantize_weight_per_output_channel():
    w = jax.random.normal(jax.random.key(3), (3, 8, 24)) * 0.1
    p, s = quant_lib.quantize_weight(w, INT8)
    assert p.shape == w.shape and p.dtype == jnp.int8
    assert s.shape == (1, 1, 24)
    err = np.abs(np.asarray(quant_lib.dequantize(p, s)) - np.asarray(w))
    assert np.all(err <= np.asarray(s) / 2 + 1e-12)


def test_resolve_kv_dtype_vocabulary():
    assert quant_lib.resolve_kv_dtype(None) is None
    assert quant_lib.resolve_kv_dtype("fp32") is None
    q = quant_lib.resolve_kv_dtype("int8")
    assert q.name == "int8" and q.qmax == 127.0
    assert quant_lib.resolve_kv_dtype(q) is q  # already-resolved passthrough
    with pytest.raises(ValueError):
        quant_lib.resolve_kv_dtype("int4")
    assert quant_lib.resolve_kv_dtype("fp8").name == "fp8"


# ---------------------------------------------------------------------------
# int8 vs fp32 server parity (tolerance policy) with everything composed
# ---------------------------------------------------------------------------


def test_int8_parity_chunked_prefix_and_speculative(cfg_params):
    """Chunked prefill + prefix reuse + speculative decoding (1-layer
    draft, so rejections genuinely roll back) at kv_dtype=int8: the
    greedy stream must track the fp32 server on a long common prefix
    (tolerance policy — int8 storage MAY flip a late near-tie, exact
    equality is not the contract), with identical compile counts (the
    dtype changes the compile key, never the program inventory), zero
    post-warmup recompiles, and both the prefix and speculative
    machinery actually exercised."""
    cfg, params = cfg_params
    dcfg = dataclasses.replace(cfg, n_layer=1)
    dparams = dict(params)
    dparams["blocks"] = jax.tree.map(lambda a: a[:1], params["blocks"])
    shared = list(range(3, 20))  # 17 tokens: a 16-row storable prefix
    reqs = [
        Request(prompt=shared + [25, 26], max_new_tokens=6),
        Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=8),
        Request(prompt=shared + [27], max_new_tokens=5),
    ]

    def run(kv_dtype):
        server = InferenceServer(
            params, cfg, n_slots=2, prefill_buckets=(4, 8, 16, 32),
            prefill_chunk=8, prefix_cache_mb=8.0, warmup=True,
            draft_params=dparams, draft_cfg=dcfg, spec_k=3,
            kv_dtype=kv_dtype,
        )
        handles = [server.submit(dataclasses.replace(r)) for r in reqs]
        server.run_until_drained(max_steps=200)
        assert all(h.finished for h in handles)
        return server, [h.tokens for h in handles]

    fp32_server, fp32_tokens = run("fp32")
    int8_server, int8_tokens = run("int8")
    matched = total = 0
    for a, b in zip(fp32_tokens, int8_tokens):
        total += len(a)
        for x, y in zip(a, b):
            if x != y:
                break
            matched += 1
    # head_dim=16 here is the worst geometry the repo runs (quant error
    # grows as head_dim shrinks); the measured common prefix is 13/19.
    # The production-geometry (head_dim=64) gate in serve.py
    # --selftest-quant holds the stricter >= 0.9 line.
    assert matched / total >= 0.6, (
        f"int8 greedy stream diverged too early: {matched}/{total} "
        f"({fp32_tokens} vs {int8_tokens})")
    # dtype is a compile key, not a program-structure change
    assert int8_server.compile_counts() == fp32_server.compile_counts()
    assert int8_server.watchdog.recompiles == 0
    assert int8_server.metrics.prefix_hits >= 1
    assert int8_server.metrics.spec_rounds >= 1
    # the int8 pool really is quantized: 4 leaves, int8 payloads
    pool = int8_server.engine.pool.cache
    assert sorted(pool) == ["k", "k_scale", "v", "v_scale"]
    assert pool["k"].dtype == jnp.int8
    assert pool["k_scale"].dtype == jnp.float32
    # and its prefix entries ship payload + scale planes
    entries = int8_server.engine.prefix_store.entries()
    assert entries
    for _, entry in entries:
        assert sorted(entry) == ["k", "k_scale", "v", "v_scale"]
    # the draft pool mirrors the target's kv_dtype
    assert int8_server.spec.draft.engine.kv_dtype == "int8"


# ---------------------------------------------------------------------------
# tp=2: scale planes shard like the data
# ---------------------------------------------------------------------------


def test_tp2_scale_planes_head_sharded(cfg_params, tp2_mesh):
    cfg, params = cfg_params
    eng = DecodeEngine(
        params, cfg, n_slots=2, mesh=tp2_mesh, kv_dtype="int8")
    assert eng.kv_shard_count == 2
    for name, arr in eng.pool.cache.items():
        shard = arr.sharding.shard_shape(arr.shape)
        assert shard[3] * 2 == arr.shape[3], (
            f"{name} not head-sharded: {arr.shape} -> {shard}")
    assert per_device_bytes(eng.pool.cache) * 2 \
        == tree_bytes(eng.pool.cache)


# ---------------------------------------------------------------------------
# migration seam: extracted quantized rows resume bit-identically
# ---------------------------------------------------------------------------


def test_migrated_quantized_rows_resume_bit_identical(cfg_params):
    """Prefill an int8 slot, pull its rows through extract_slot_rows
    (payloads + scale planes), install them into a FRESH engine, then
    decode the same tokens on both engines with the same keys: token
    streams identical and the final pools bit-identical leaf-for-leaf —
    migration is a byte move, not a requantization. This only holds
    because the roundtrip is exactly idempotent (see the unit above);
    with drifting scales the migrated replica would fork."""
    cfg, params = cfg_params
    prompt = list(range(5, 21))  # 16 tokens: a ladder bucket

    def prefill(eng):
        tok, _ = eng.prefill_chunk_call(
            0, prompt, 0, 1.0, None, None, False, 7)
        return int(tok)

    def decode(eng, first_tok):
        toks, tok = [], first_tok
        for i in range(6):
            nxt = eng.decode_step(
                np.asarray([tok], np.int32),
                np.asarray([len(prompt) + i], np.int32),
                np.ones(1, np.float32), np.zeros(1, np.int32),
                np.ones(1, np.float32), np.zeros(1, bool),
                np.asarray([11], np.uint32), np.asarray([i], np.int32),
            )
            tok = int(nxt[0])
            toks.append(tok)
        return toks

    src = DecodeEngine(params, cfg, n_slots=1, prefill_buckets=(8, 16, 32),
                       kv_dtype="int8")
    first = prefill(src)
    entry = src.extract_slot_rows(0, 16)
    assert sorted(entry) == ["k", "k_scale", "v", "v_scale"]
    assert entry["k"].dtype == jnp.int8

    dst = DecodeEngine(params, cfg, n_slots=1, prefill_buckets=(8, 16, 32),
                       kv_dtype="int8")
    assert dst.install_slot_rows(0, entry) == 16

    src_toks = decode(src, first)
    dst_toks = decode(dst, first)
    assert dst_toks == src_toks
    for name in sorted(src.pool.cache):
        assert np.array_equal(
            np.asarray(src.pool.cache[name]),
            np.asarray(dst.pool.cache[name])), f"{name} diverged"


# ---------------------------------------------------------------------------
# fp32 default path + capacity arithmetic
# ---------------------------------------------------------------------------


def test_fp32_default_is_byte_identical_plain_cache(cfg_params):
    cfg, params = cfg_params
    default = DecodeEngine(params, cfg, n_slots=2)
    explicit = DecodeEngine(params, cfg, n_slots=2, kv_dtype="fp32")
    for eng in (default, explicit):
        assert eng.kv_quant is None and eng.kv_dtype == "fp32"
        assert sorted(eng.pool.cache) == ["k", "v"]
    assert tree_bytes(default.pool.cache) == tree_bytes(explicit.pool.cache)
    assert {n: (a.shape, a.dtype) for n, a in default.pool.cache.items()} \
        == {n: (a.shape, a.dtype) for n, a in explicit.pool.cache.items()}


def test_int8_pool_fits_quarter_budget_at_hd64():
    """The acceptance-gate arithmetic without running a model: at
    head_dim=64 (the selftest-quant geometry) int8 payload + fp32 scale
    planes come to (hd+4)/(4*hd) = 0.2656x the fp32 pool bytes —
    under the 0.27 ceiling the selftest-quant gate enforces."""
    cfg = GPTConfig.make(
        n_layer=2, n_head=4, n_embd=256, vocab_size=96, block_size=48,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    fp32 = gen.init_cache(cfg, 2)
    q = quant_lib.init_quant_cache(cfg, 2, INT8)
    data, scales = quant_lib.split_scales(q)
    fp32_bytes = sum(int(a.nbytes) for a in fp32.values())
    q_bytes = sum(int(a.nbytes) for a in q.values())
    assert q_bytes / fp32_bytes <= 0.27
    assert sum(int(a.nbytes) for a in scales.values()) \
        == quant_lib.scale_bytes(cfg, 2)
    assert sorted(data) == ["k", "v"]
