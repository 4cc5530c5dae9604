"""Tensor-parallel sharded serving tests (ISSUE 14) — CPU, tiny config,
`not slow` tier, on the conftest 8-virtual-device mesh.

The load-bearing guarantees:
* a tp=2 DecodeEngine shards the KV pool over heads (per-device pool
  bytes = total/2) and the sharding survives every donated round trip
  through the compiled programs — free/re-admit included;
* greedy output under tp=2 is token-identical to the unsharded solo
  reference AND to a tp=1 server running the same knobs, across chunked
  prefill + prefix reuse + speculative decoding composed;
* the mesh is compile identity, not a traced input: tp=2 and tp=1
  servers report the SAME compile counts (one executable per family)
  and zero post-warmup recompiles;
* a fleet of sharded replicas survives a mid-decode crash with zero
  duplicate tokens — ownership (fleet) and placement (mesh) never
  interact;
* the bytes each device holds of a sharded pool, read from its shards,
  are exactly total/tp — on a bare engine and on a whole server.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from mingpt_distributed_tpu.config import GPTConfig, MeshConfig
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.parallel import mesh as mesh_lib
from mingpt_distributed_tpu.parallel.zero import per_device_bytes
from mingpt_distributed_tpu.serving import (
    InferenceServer,
    Request,
    ReplicaSupervisor,
    Router,
    VirtualClock,
    default_server_factory,
)
from mingpt_distributed_tpu.serving.engine import DecodeEngine
from mingpt_distributed_tpu.training.faults import ServingFaultInjector
from oracles import solo_greedy


@pytest.fixture(scope="module")
def cfg_params():
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    return cfg, gpt.init(jax.random.key(0), cfg)


def tree_bytes(tree):
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


@pytest.fixture(scope="module")
def tp2_mesh():
    return mesh_lib.make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])


PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [10, 11, 12, 13], [40, 41]]


# ---------------------------------------------------------------------------
# engine placement
# ---------------------------------------------------------------------------


def test_tp2_engine_shards_pool_halving_per_device_bytes(
        cfg_params, tp2_mesh):
    cfg, params = cfg_params
    eng = DecodeEngine(params, cfg, n_slots=2, mesh=tp2_mesh)
    assert eng.kv_shard_count == 2
    # heads axis split in two, every other axis intact
    shape = eng.pool.cache["k"].shape
    shard = eng.pool.sharding.shard_shape(shape)
    assert shard == shape[:3] + (shape[3] // 2,) + shape[4:]
    assert per_device_bytes(eng.pool.cache) * 2 \
        == tree_bytes(eng.pool.cache)
    # an unsharded engine from the same ingredients is the 1x baseline
    solo = DecodeEngine(params, cfg, n_slots=2)
    assert solo.kv_shard_count == 1
    assert tree_bytes(solo.pool.cache) == tree_bytes(eng.pool.cache)


def test_tp2_slot_free_and_readmit_keeps_sharding(cfg_params, tp2_mesh):
    """Queue pressure forces slot free/re-admit cycles; the donated
    cache must come back with the SAME sharding every round (layout
    drift would mean a second executable and gathered KV)."""
    cfg, params = cfg_params
    server = InferenceServer(params, cfg, n_slots=2, mesh=tp2_mesh)
    want = server.engine.pool.sharding
    handles = [server.submit(Request(prompt=p, max_new_tokens=6))
               for p in PROMPTS]  # 4 requests, 2 slots: queue + reuse
    server.step()
    assert len(server.queue) == 2
    server.run_until_drained(max_steps=100)
    for p, h in zip(PROMPTS, handles):
        assert h.finished and h.tokens == solo_greedy(params, cfg, p, 6)
    # late re-admission on a freed slot, still exact, still sharded
    h = server.submit(Request(prompt=PROMPTS[0], max_new_tokens=4))
    server.run_until_drained(max_steps=100)
    assert h.tokens == solo_greedy(params, cfg, PROMPTS[0], 4)
    assert server.engine.pool.sharding == want
    assert server.engine.kv_shard_count == 2
    assert server.compile_counts() == {
        "prefill": 1, "decode": 1, "prefix_load": 0, "prefix_save": 0}


# ---------------------------------------------------------------------------
# tp=2 vs tp=1 parity with everything composed
# ---------------------------------------------------------------------------


def test_tp2_vs_tp1_parity_chunked_prefix_and_speculative(
        cfg_params, tp2_mesh):
    """The acceptance core: chunked prefill + prefix reuse + speculative
    decoding (1-layer draft, so rejections genuinely roll back) running
    under tp=2 — greedy outputs token-identical to the tp=1 server AND
    to solo generate(), compile counts identical between the two meshes
    (one executable per family either way), zero recompiles."""
    cfg, params = cfg_params
    dcfg = dataclasses.replace(cfg, n_layer=1)
    dparams = dict(params)
    dparams["blocks"] = jax.tree.map(lambda a: a[:1], params["blocks"])
    shared = list(range(3, 20))  # 17 tokens: a 16-row storable prefix
    reqs = [
        Request(prompt=shared + [25, 26], max_new_tokens=6),
        Request(prompt=PROMPTS[0], max_new_tokens=8),
        Request(prompt=shared + [27], max_new_tokens=5),
    ]

    def run(mesh):
        server = InferenceServer(
            params, cfg, n_slots=2, prefill_buckets=(4, 8, 16, 32),
            prefill_chunk=8, prefix_cache_mb=8.0, warmup=True,
            draft_params=dparams, draft_cfg=dcfg, spec_k=3, mesh=mesh,
        )
        handles = []
        for r in reqs:
            handles.append(server.submit(dataclasses.replace(r)))
            server.step()  # staggered: each arrival lands mid-flight
        server.run_until_drained(max_steps=200)
        return server, [h.tokens for h in handles]

    tp1_server, tp1_tokens = run(None)
    tp2_server, tp2_tokens = run(tp2_mesh)
    assert tp2_tokens == tp1_tokens
    for r, toks in zip(reqs, tp2_tokens):
        assert toks == solo_greedy(
            params, cfg, list(r.prompt), r.max_new_tokens)
    # mesh is compile identity, not program structure
    assert tp2_server.compile_counts() == tp1_server.compile_counts()
    assert tp2_server.compile_counts()["decode"] == 1
    assert tp2_server.compile_counts()["verify"] == 1
    assert tp2_server.watchdog.recompiles == 0
    assert tp1_server.watchdog.recompiles == 0
    # target pool sharded, draft pool mirrors it
    assert tp2_server.engine.kv_shard_count == 2
    assert tp2_server.spec.draft.engine.kv_shard_count == 2
    assert tp2_server.metrics.prefix_hits >= 1
    # rejections actually happened, so rollback ran under sharding
    assert tp2_server.metrics.spec_accepted \
        < tp2_server.metrics.spec_proposed
    # stored prefix entries keep the head sharding — a hit never
    # gathers the rows to one chip
    entries = tp2_server.engine.prefix_store.entries()
    assert entries
    for _, entry in entries:
        for arr in entry.values():
            shard = arr.sharding.shard_shape(arr.shape)
            assert shard[3] * 2 == arr.shape[3]


# ---------------------------------------------------------------------------
# fleet of sharded replicas
# ---------------------------------------------------------------------------


def prompts_with_affinity(router, index, n, length=3):
    out = []
    for start in range(1, 200):
        p = [start + j for j in range(length)]
        if max(p) < 50 and router._affinity_index(p) == index:
            out.append(p)
            if len(out) == n:
                return out
    raise AssertionError(f"no {n} prompts hash to replica {index}")


def test_fleet_crash_retry_on_sharded_replicas(cfg_params, tp2_mesh):
    """Replica0 (tp=2, like every replica) dies mid-decode; its
    in-flight requests finish on a survivor token-identical with zero
    duplicate tokens. The mesh rides through default_server_factory
    untouched — placement never leaks into ownership or retry logic."""
    cfg, params = cfg_params
    sup = ReplicaSupervisor(
        default_server_factory(params, cfg, n_slots=2, mesh=tp2_mesh),
        n_replicas=2,
        clock=VirtualClock(tick_s=0.001),
        injector=ServingFaultInjector("crash:nth=3:match=replica0"),
        max_restarts=1,
        restart_backoff_s=0.01,
    )
    router = Router(sup, max_retries=3, retry_backoff_s=0.01,
                    breaker_reset_s=0.05)
    for rep in sup.replicas:
        assert rep.server.engine.kv_shard_count == 2
    streamed = {}
    router.on_token = lambda fh, tok: streamed.setdefault(
        fh.request_id, []).append(tok)
    n = 8
    prompts = (prompts_with_affinity(router, 0, 2)
               + prompts_with_affinity(router, 1, 2))
    handles = router.generate_batch(
        [Request(prompt=p, max_new_tokens=n) for p in prompts])
    s = router.summary()
    assert s["replicas"]["replica0"]["crashes"] == 1
    assert s["retries_by_reason"]["crash"] >= 1
    assert [h for h in handles if h.attempts > 1], "crash must force retry"
    for p, h in zip(prompts, handles):
        assert h.finish_reason == "length"
        assert h.tokens == solo_greedy(params, cfg, p, n)
        # the caller-visible stream saw every token exactly once
        assert streamed[h.request_id] == h.tokens


# ---------------------------------------------------------------------------
# accounting units
# ---------------------------------------------------------------------------


def test_per_device_bytes_counts_shards(tp2_mesh):
    single = jnp.zeros((4, 8), jnp.float32)  # single device: full size
    assert per_device_bytes({"a": single}) == single.nbytes
    spec = jax.sharding.NamedSharding(
        tp2_mesh, jax.sharding.PartitionSpec("tp"))
    split = jax.device_put(jnp.zeros((4, 8), jnp.float32), spec)
    assert per_device_bytes({"a": split}) == split.nbytes // 2
    # mixed trees sum leafwise on the busiest device (device 0 holds
    # half of the split leaf and the whole single-device one)
    assert per_device_bytes({"a": split, "b": single}) \
        == split.nbytes // 2 + single.nbytes
    assert tree_bytes({"a": split, "b": single}) \
        == split.nbytes + single.nbytes


def test_tp2_server_pools_hold_total_over_tp_per_device(
        cfg_params, tp2_mesh):
    """A whole tp=2 server — target pool and the speculation draft's
    mirrored pool — holds total/2 of each pool on a device, and sharding
    leaves the totals where a tp=1 server has them."""
    cfg, params = cfg_params
    kw = dict(n_slots=2, draft_params=params, draft_cfg=cfg, spec_k=2)
    solo = InferenceServer(params, cfg, **kw)
    srv = InferenceServer(params, cfg, mesh=tp2_mesh, **kw)
    for pick in (lambda s: s.engine.pool.cache,
                 lambda s: s.spec.draft.engine.pool.cache):
        pool = pick(srv)
        for leaf in pool.values():
            assert [sh.data.nbytes for sh in leaf.addressable_shards] \
                == [leaf.nbytes // 2] * 2
        assert per_device_bytes(pool) * 2 == tree_bytes(pool)
        assert tree_bytes(pool) == tree_bytes(pick(solo))
        assert per_device_bytes(pick(solo)) == tree_bytes(pick(solo))
