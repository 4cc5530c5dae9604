"""The serving path over rows that lie side by side (ISSUE 39), mechanism by
mechanism: everything that touches the pool (chunked prefill, the prefix
store, speculation, an int8 pool, ``tp``, migration) gives the per-head
pool's tokens and rows. ``tests/test_wide_rows.py`` holds the rule, the
attention and the pinned programs; this half of it is a file of its own so
that ``--dist loadfile`` can give it a worker (ISSUE 63). CPU, tiny,
float32."""

import jax
import numpy as np
import pytest

from mingpt_distributed_tpu.config import MeshConfig
from mingpt_distributed_tpu.parallel import mesh as mesh_lib
from mingpt_distributed_tpu.serving import InferenceServer, Request
from mingpt_distributed_tpu.serving import quant as quant_lib
from mingpt_distributed_tpu.serving.engine import DecodeEngine
from oracles import solo_greedy
from stacks import form_model as model
from stacks import per_head

# -- through the pool ------------------------------------------------------------

PROMPTS = [[1, 2, 3, 4, 5], list(range(7, 22)), [10, 11, 12, 13],
           list(range(1, 17)) + [40, 41], list(range(1, 17)) + [20, 21, 22],
           list(range(1, 17)) + [33]]
BUDGETS = [9, 4, 7, 5, 6, 3]
MECHANISMS = {
    "plain": dict(),
    "chunked": dict(prefill_chunk=4),
    "prefix-store": dict(prefix_cache_mb=8.0),
    "speculation": dict(spec_k=3),
    "int8": dict(kv_dtype="int8"),
    "tp2": dict(tp=2),
    "int8-tp2": dict(kv_dtype="int8", tp=2),
    "all-together": dict(prefill_chunk=8, prefix_cache_mb=8.0, spec_k=2, tp=2),
}


def served(cfg, params, tp=None, spec_k=None, **options):
    """The first five prompts through a 3-slot server, admitted while
    others decode (lanes at different positions, prompts in two buckets),
    and once they are done the sixth, whose first 16 tokens two of them
    had: each request's tokens, and the pool's row leaves at the end."""
    if tp:
        options["mesh"] = mesh_lib.make_mesh(
            MeshConfig(dp=1, tp=tp), devices=jax.devices()[:tp])
    if spec_k:
        options.update(spec_k=spec_k, draft_cfg=cfg, draft_params=params)
    server = InferenceServer(params, cfg, n_slots=3,
                             prefill_buckets=(8, 16, 32), **options)
    handles = []
    for prompt, budget in zip(PROMPTS[:5], BUDGETS):
        handles.append(server.submit(
            Request(prompt=prompt, max_new_tokens=budget)))
        server.step()
    server.run_until_drained(max_steps=400)
    handles.append(server.submit(
        Request(prompt=PROMPTS[5], max_new_tokens=BUDGETS[5])))
    server.run_until_drained(max_steps=100)
    pool = {n: np.asarray(a) for n, a in server.engine.pool.cache.items()
            if a.ndim == 5}
    return [h.tokens for h in handles], pool, server


@pytest.mark.parametrize("mechanism", sorted(MECHANISMS))
@pytest.mark.parametrize("form", ["mha", "two-tiles", "gqa-rope"])
def test_the_serving_path_over_wide_rows_is_the_per_head_pool_s(
        form, mechanism, monkeypatch):
    """Every mechanism that touches the pool, over the wide row: the tokens
    are the per-head pool's and its leaves the same to float32 rounding (a
    scale a row and head either way), and an unquantized pool's tokens are
    solo ``generate``'s."""
    options = MECHANISMS[mechanism]
    cfg, params = model(form)
    got, got_pool, server = served(cfg, params, **options)
    width = cfg.kv_heads * cfg.head_dim
    assert got_pool["k"].shape[3:] == (1, width)
    assert server.metrics.summary()["kv_row_width"] == width
    if "prefix_cache_mb" in options:
        assert server.metrics.prefix_hits >= 1
        for _, entry in server.engine.prefix_store.entries():
            assert entry["k"].shape[3:] == (1, width)
    if "spec_k" in options:
        assert server.metrics.spec_accepted > 0
    if options.get("tp"):
        assert server.engine.kv_shard_count == 2
    per_head(monkeypatch)
    want, want_pool, _ = served(cfg, params, **options)
    assert want_pool["k"].shape[3:] == (cfg.kv_heads, cfg.head_dim)
    assert got == want
    assert sorted(got_pool) == sorted(want_pool)
    if "kv_dtype" in options:
        got_pool, want_pool = (
            {n: np.asarray(quant_lib.dequantize(p[n], p[n + "_scale"]))
             for n in ("k", "v")} for p in (got_pool, want_pool))
    for name, rows in want_pool.items():
        # 8 bits a number of |x| <= ~4: a step of 1/32 where a rounding flips
        np.testing.assert_allclose(
            got_pool[name].reshape(rows.shape), rows, rtol=0,
            atol=0.04 if "kv_dtype" in options else 5e-6)
    if "kv_dtype" not in options:
        for tokens, prompt, budget in zip(got, PROMPTS, BUDGETS):
            assert tokens == solo_greedy(params, cfg, prompt, budget)


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["fp32", "int8"])
@pytest.mark.parametrize("form", ["mha", "gqa-rope"])
def test_migrated_wide_rows_resume_bit_identical(form, kv_dtype):
    """A slot's rows out of one engine and into a fresh one, in the pool's
    own row shape (scales beside them): the same decode, the same pools."""
    cfg, params = model(form)
    prompt = list(range(5, 21))

    def engine():
        return DecodeEngine(params, cfg, n_slots=1,
                            prefill_buckets=(8, 16, 32), kv_dtype=kv_dtype)

    def decode(eng, tok):
        out = []
        for i in range(5):
            tok = int(eng.decode_step(
                np.asarray([tok], np.int32),
                np.asarray([len(prompt) + i], np.int32),
                np.ones(1, np.float32), np.zeros(1, np.int32),
                np.ones(1, np.float32), np.zeros(1, bool),
                np.asarray([11], np.uint32), np.asarray([i], np.int32))[0])
            out.append(tok)
        return out

    src, dst = engine(), engine()
    first, _ = src.prefill_chunk_call(0, prompt, 0, 1.0, None, None, False, 7)
    entry = src.extract_slot_rows(0, 16)
    width = cfg.kv_heads * cfg.head_dim
    assert entry["k"].shape == (cfg.n_layer, 1, 16, 1, width)
    if kv_dtype:
        assert entry["k_scale"].shape == (cfg.n_layer, 1, 16, 1, cfg.kv_heads)
    assert dst.install_slot_rows(0, entry) == 16
    assert decode(src, int(first)) == decode(dst, int(first))
    for name in sorted(src.pool.cache):
        np.testing.assert_array_equal(src.pool.cache[name],
                                      dst.pool.cache[name])
