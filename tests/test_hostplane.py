"""Cross-host fleet tests (ISSUE 19) — CPU, tiny config, ``not slow``.

Everything runs on the loopback host mesh (LoopbackHostLink — the
multi-host twin of the loopback transport; real sockets are exercised
by ``serve.py --selftest-crosshost``), so the whole suite is sleep-free
and byte-replayable on one shared VirtualClock:

* a full partition drill (host0 cut off, quarantined by the quorate
  ladder, requests failed over cross-host, cable plugged back in)
  produces a BYTE-identical JSON report across two runs, with zero
  duplicate and zero lost stream tokens;
* the emission fence drops stale-placement AND stale-epoch tokens — a
  partitioned-then-healed host can never double-emit;
* a host that cannot see quorum sheds with ``reason="no_quorum"``
  within one heartbeat deadline (never serves both sides of a split);
* the heartbeat ladder degrades on elapsed silence with hysteresis —
  one missed beat never suspects a peer, and quarantined/dead recover
  only after consecutive good beats;
* paced cross-host migration of a quantized tp=2 engine's rows arrives
  bit-identical (head-sharded, no requantization) in a transfer time
  matching the token-bucket budget exactly on the injected clock;
* unsigned / tampered / replayed envelopes are rejected with typed
  errors and distinct ``mingpt_fleet_auth_rejects_total{reason}``
  counts; corrupted chunks NACK under ``reason="frame_digest"``;
* auth is off by default and the token streams with/without a secret
  are byte-identical;
* an exhausted transfer-retry budget degrades to plain re-route —
  ``outcome="failed"``, zero requests lost;
* refused sockets surface as typed TransportUnavailable after bounded
  geometric backoff (injected sleep — the RetryPolicy.sleep idiom).
"""

import copy
import json
import socket

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.parallel.mesh import MeshConfig, make_mesh
from mingpt_distributed_tpu.serving import Request, VirtualClock
from mingpt_distributed_tpu.serving.procfleet import (
    BadSignature,
    FleetAuth,
    PacedChannel,
    PacedTransferError,
    ReplayedNonce,
    SocketTransport,
    TransportUnavailable,
    UnsignedEnvelope,
    build_loopback_fleet,
    canonical_bytes,
    envelope,
    pack_frames,
    unpack_frames,
    validate_envelope,
)
from mingpt_distributed_tpu.serving.requests import ShedError
from mingpt_distributed_tpu.telemetry import (
    parse_prometheus,
    render_prometheus,
)
from mingpt_distributed_tpu.training.faults import (
    LinkPartitioned,
    NetworkFaultInjector,
)
from oracles import solo_greedy


@pytest.fixture(scope="module")
def cfg_params():
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    return cfg, gpt.init(jax.random.key(0), cfg)


def _samples(page_or_registry, family):
    """parse_prometheus samples of one family as {labels-tuple: value}."""
    text = (page_or_registry if isinstance(page_or_registry, str)
            else render_prometheus(page_or_registry))
    got = parse_prometheus(text)
    return {tuple(sorted(labels.items())): value
            for name, labels, value in got["samples"] if name == family}


PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [10, 11, 12, 13]]


# ---------------------------------------------------------------------------
# baseline: the mesh serves byte-identically to solo generate()
# ---------------------------------------------------------------------------


def test_two_host_fleet_matches_solo_and_streams_exactly(cfg_params):
    cfg, params = cfg_params
    streamed = {}
    frontend, agents, _net = build_loopback_fleet(
        params, cfg, n_hosts=2, n_replicas=1,
        server_kwargs=dict(n_slots=2),
        on_token=lambda c, t: streamed.setdefault(
            c.request_id, []).append(t))
    handles = [frontend.submit(Request(prompt=p, max_new_tokens=8))
               for p in PROMPTS]
    frontend.run_until_drained(max_steps=5000)
    for h, p in zip(handles, PROMPTS):
        assert h.finish_reason == "length"
        assert h.tokens == solo_greedy(params, cfg, p, 8)
        # the on_token hook saw every caller-visible token exactly once
        assert streamed[h.request_id] == h.tokens
        assert h.attempts == 1 and h.fenced == 0
    # both hosts see each other alive; nobody was declared failed
    summary = frontend.summary()
    assert summary["declared_failed"] == []
    for host in ("host0", "host1"):
        assert summary["hosts"][host]["admitting"]


# ---------------------------------------------------------------------------
# the partition drill: two runs, byte-identical; zero dup / zero lost
# ---------------------------------------------------------------------------


def _partition_drill(cfg_params):
    """host0 is cut off from the rest of the mesh for 0.2 virtual
    seconds mid-decode: its peers' ladders quarantine it, the frontend
    declares it failed, its requests fail over cross-host, then the
    partition heals on the injected clock and host0 rejoins behind the
    epoch fence. Returns (sorted-key JSON report, streams, frontend,
    agents)."""
    cfg, params = cfg_params
    spec = ";".join(
        f"partition:nth=1:match={a}->{b}:delay=0.2"
        for a, b in [("host0", "host1"), ("host0", "host2"),
                     ("host1", "host0"), ("host2", "host0")])
    streamed = {}
    frontend, agents, net = build_loopback_fleet(
        params, cfg, n_hosts=3, n_replicas=1,
        heartbeat_interval_s=0.01, net_faults=spec,
        server_kwargs=dict(n_slots=2),
        on_token=lambda c, t: streamed.setdefault(
            c.request_id, []).append(t))
    handles = [frontend.submit(Request(prompt=p, max_new_tokens=24))
               for p in PROMPTS]
    frontend.run_until_drained(max_steps=20000)
    # keep the mesh beating past the heal so host0's ladder recovers
    for _ in range(300):
        frontend.step()
    report = json.dumps(frontend.summary(), sort_keys=True)
    return report, streamed, handles, frontend, agents


def test_partition_drill_two_runs_byte_identical(cfg_params):
    cfg, params = cfg_params
    report1, streamed, handles, frontend, agents = _partition_drill(
        cfg_params)
    report2, _, _, _, _ = _partition_drill(cfg_params)
    assert report1 == report2  # the replayability contract

    # zero duplicate, zero lost: every caller stream is exactly the
    # solo greedy stream, delivered once
    for h, p in zip(handles, PROMPTS):
        assert h.finish_reason == "length"
        assert h.tokens == solo_greedy(params, cfg, p, 24)
        assert streamed[h.request_id] == h.tokens

    summary = json.loads(report1)
    # the cut-off host's requests failed over cross-host...
    recovered = [r for r in summary["requests"].values() if r["recovered"]]
    assert recovered, "no request crossed hosts — the drill is vacuous"
    for r in recovered:
        assert r["attempts"] >= 2
        assert len(set(r["hosts"])) >= 2
        # the stale placement kept decoding behind the partition: its
        # emissions were fenced (never double-delivered), and the new
        # placement's re-derive of already-seen tokens was deduped
        assert r["fenced"] > 0 or r["duplicates_suppressed"] > 0
    # ...which bumped the fleet epoch
    assert summary["fleet_epoch"] >= 1
    # after the heal + hysteresis, host0 is back: nobody stays declared
    # failed, every ladder view is alive again
    assert summary["declared_failed"] == []
    for host, info in summary["hosts"].items():
        assert info["admitting"], f"{host} still not admitting after heal"
        assert all(v == "alive" for v in info["peers"].values())

    # the adopting host logged the cross-host recovery tail
    rows = [row for agent in agents.values()
            for row in agent.router.supervisor.recovery_log
            if row.get("path") == "crosshost"]
    assert rows and all(row["recovery_s"] > 0 for row in rows)
    assert any(row["replica"] == "host0" for row in rows)

    # the fence counter on the merged page agrees with the handles
    fenced = _samples(frontend.fleet_metrics_page(),
                      "mingpt_fleet_fenced_emissions_total")
    total_fenced = sum(v for labels, v in fenced.items()
                      if dict(labels).get("host"))
    assert total_fenced == sum(
        r["fenced"] for r in summary["requests"].values())


def test_stale_epoch_and_stale_placement_emissions_are_fenced(cfg_params):
    """The double-emit attempt, surgically: emissions carrying a stale
    epoch or a stale (host, attempt) placement are dropped and counted,
    never appended to the caller stream."""
    cfg, params = cfg_params
    frontend, agents, _net = build_loopback_fleet(
        params, cfg, n_hosts=2, n_replicas=1,
        server_kwargs=dict(n_slots=2))
    h = frontend.submit(Request(prompt=[1, 2, 3], max_new_tokens=6))
    frontend.run_until_drained(max_steps=5000)
    solo = solo_greedy(params, cfg, [1, 2, 3], 6)
    assert h.tokens == solo
    host, local_id = h.local_key

    # a partitioned-then-healed worker replaying its backlog: same
    # placement, but the epoch it computed under is behind the fence
    h.finished = False
    h.fence_epoch = 5
    frontend._local[h.local_key] = (h, object())
    frontend._emissions.append((host, 0, local_id, len(h.tokens), 99))
    frontend._process_emissions()
    assert h.tokens == solo and h.fenced == 1

    # a stale placement: the request moved on, the old host still emits
    h.fence_epoch = 0
    h.local_key = ("host1", "fleet-999")
    frontend._emissions.append((host, 0, local_id, len(h.tokens), 99))
    frontend._process_emissions()
    assert h.tokens == solo and h.fenced == 2

    fenced = _samples(frontend.registry,
                      "mingpt_fleet_fenced_emissions_total")
    assert fenced[(("host", host),)] == 2


def test_no_quorum_host_sheds_typed(cfg_params):
    cfg, params = cfg_params
    frontend, agents, _net = build_loopback_fleet(
        params, cfg, n_hosts=3, n_replicas=1,
        server_kwargs=dict(n_slots=2))
    a0 = agents["host0"]
    assert a0.admitting
    for st in a0.peers.values():
        st["state"] = "quarantined"
    assert not a0.admitting
    with pytest.raises(ShedError) as ei:
        a0.submit(Request(prompt=[1, 2], max_new_tokens=2))
    assert ei.value.reason == "no_quorum"
    # when NO host can see quorum the frontend refuses too — the fleet
    # would rather shed than serve both sides of a partition
    for agent in agents.values():
        for st in agent.peers.values():
            st["state"] = "quarantined"
    with pytest.raises(ShedError) as ei:
        frontend.submit(Request(prompt=[1, 2], max_new_tokens=2))
    assert ei.value.reason == "no_quorum"


def test_heartbeat_ladder_hysteresis(cfg_params):
    cfg, params = cfg_params
    clock = VirtualClock(tick_s=0.001)
    _frontend, agents, _net = build_loopback_fleet(
        params, cfg, n_hosts=2, n_replicas=1, clock=clock,
        heartbeat_interval_s=0.05, server_kwargs=dict(n_slots=2))
    a0 = agents["host0"]
    st = a0.peers["host1"]

    # one missed beat (1.5 intervals of silence) never flaps the peer
    clock.advance(0.075)
    a0.refresh_peer_states()
    assert st["state"] == "alive"
    # the ladder: suspect at 2.5x, quarantined at 5x, dead at 10x
    clock.advance(0.055)  # elapsed 0.13 >= 0.125
    a0.refresh_peer_states()
    assert st["state"] == "suspect"
    clock.advance(0.13)   # elapsed 0.26 >= 0.25
    a0.refresh_peer_states()
    assert st["state"] == "quarantined"
    clock.advance(0.25)   # elapsed 0.51 >= 0.5
    a0.refresh_peer_states()
    assert st["state"] == "dead"

    # recovery out of dead needs recover_beats consecutive good beats:
    # one beat of contact is not enough (hysteresis)...
    a0.record_contact("host1")
    a0.refresh_peer_states()
    assert st["state"] == "dead"
    a0.record_contact("host1")
    a0.refresh_peer_states()
    assert st["state"] == "alive"

    # ...but suspect recovers immediately — it is a worry, not a verdict
    clock.advance(0.13)
    a0.refresh_peer_states()
    assert st["state"] == "suspect"
    a0.record_contact("host1")
    a0.refresh_peer_states()
    assert st["state"] == "alive"


# ---------------------------------------------------------------------------
# paced migration: the token-bucket budget is exact on the virtual clock
# ---------------------------------------------------------------------------


def test_paced_crosshost_migration_budget_exact(cfg_params):
    cfg, params = cfg_params
    streamed = {}
    frontend, agents, _net = build_loopback_fleet(
        params, cfg, n_hosts=2, n_replicas=1,
        secret="drill-secret", paced_bytes_per_s=1_000_000.0,
        net_faults="slow_link:every=1:match=host0->host1:delay=0.05",
        server_kwargs=dict(n_slots=2, prefix_cache_mb=2.0,
                           prefill_buckets=(8, 16, 32)),
        on_token=lambda c, t: streamed.setdefault(
            c.request_id, []).append(t))
    prompt = [5, 6, 7, 8, 9, 10, 11, 12, 13]
    h = frontend.submit(Request(prompt=prompt, max_new_tokens=12))
    for _ in range(4):
        frontend.step()
    assert not h.finished  # migration happens mid-decode

    report = frontend.migrate_crosshost("host0", "host1")
    assert report["outcome"] == "ok" and report["error"] is None
    assert report["requests_moved"] == [h.request_id]
    assert report["entries_installed"] + report["chunks"] >= 1
    # the budget, exactly: B bytes at 1 MB/s plus 0.05s injected link
    # latency per chunk — latency is waited but never becomes bandwidth
    want = report["bytes"] / 1_000_000.0 + 0.05 * report["chunks"]
    assert abs(report["transfer_s"] - want) < 1e-9
    assert report["src_exit_code"] == 75

    frontend.run_until_drained(max_steps=5000)
    assert h.finish_reason == "length"
    assert h.tokens == solo_greedy(params, cfg, prompt, 12)
    assert streamed[h.request_id] == h.tokens  # zero dup / zero lost

    # the transfer counters rendered on the merged page, strict-parsed
    page = frontend.fleet_metrics_page()
    xfer = _samples(page, "mingpt_fleet_xfer_bytes_total")
    assert xfer[(("paced", "true"),)] >= report["bytes"]
    assert xfer[(("paced", "false"),)] == 0


def test_exhausted_transfer_retries_degrade_to_reroute(cfg_params):
    """Every chunk dropped: the paced transfer exhausts its retry budget
    and the migration degrades to plain re-route — outcome="failed",
    zero requests lost (they re-prefill on the destination)."""
    cfg, params = cfg_params
    frontend, agents, _net = build_loopback_fleet(
        params, cfg, n_hosts=2, n_replicas=1,
        net_faults="drop_frame:every=1:match=host0->host1",
        server_kwargs=dict(n_slots=2))
    h = frontend.submit(Request(prompt=[1, 2, 3, 4], max_new_tokens=8))
    for _ in range(3):
        frontend.step()
    report = frontend.migrate_crosshost("host0", "host1")
    assert report["outcome"] == "failed"
    assert report["error"] and "PacedTransferError" in report["error"]
    assert report["to"] is None and report["entries_installed"] == 0
    assert report["requests_moved"] == [h.request_id]
    frontend.run_until_drained(max_steps=5000)
    assert h.finish_reason == "length"
    assert h.tokens == solo_greedy(params, cfg, [1, 2, 3, 4], 8)
    migrations = _samples(
        agents["host0"].router.supervisor.registry,
        "mingpt_fleet_migrations_total")
    assert migrations.get((("outcome", "failed"),), 0) == 1


def test_crosshost_migration_quantized_tp2_bit_identical(cfg_params):
    """The acceptance drill: a quantized (int8 + power-of-two scale
    planes) tp=2 engine's prefix rows cross hosts through the paced
    channel and arrive bit-identical — payloads AND scales byte-equal to
    the source (migration is a byte move, never a requantization) and
    still head-sharded on the destination mesh — in a transfer time
    matching the token-bucket budget exactly."""
    cfg, params = cfg_params
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices (conftest forces 8)")
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    frontend, agents, _net = build_loopback_fleet(
        params, cfg, n_hosts=2, n_replicas=1,
        paced_bytes_per_s=1_000_000.0,
        server_kwargs=dict(n_slots=2, mesh=mesh, kv_dtype="int8",
                           prefix_cache_mb=4.0,
                           prefill_buckets=(8, 16, 32)))
    prompt = [5, 6, 7, 8, 9, 10, 11, 12, 13]
    h = frontend.submit(Request(prompt=prompt, max_new_tokens=4))
    frontend.run_until_drained(max_steps=5000)
    assert h.finish_reason == "length"

    src_host = h.hosts[0]
    dst_host = next(x for x in sorted(agents) if x != src_host)
    src_rep = agents[src_host].router.supervisor.replicas[0]
    src_entries = {
        key: {n: np.asarray(a) for n, a in entry.items()}
        for key, entry in
        src_rep.backend.worker.server.engine.prefix_store.entries()}
    assert src_entries, "no prefix entry stored — nothing to migrate"

    report = frontend.migrate_crosshost(src_host, dst_host)
    assert report["outcome"] == "ok"
    assert report["entries_installed"] >= 1
    # unimpeded link: the budget is purely bytes/rate on the clock
    assert abs(report["transfer_s"]
               - report["bytes"] / 1_000_000.0) < 1e-9

    dst_sup = agents[dst_host].router.supervisor
    entries = (dst_sup.replica_by_name(report["to"])
               .backend.worker.server.engine.prefix_store.entries())
    assert entries
    for key, entry in entries:
        # quantized layout survived: int8 payloads + fp32 scale planes
        assert sorted(entry) == ["k", "k_scale", "v", "v_scale"]
        assert entry["k"].dtype == jnp.int8
        assert entry["k_scale"].dtype == jnp.float32
        for name, arr in entry.items():
            # still head-sharded: the kv_heads axis splits across tp=2
            shard = arr.sharding.shard_shape(arr.shape)
            assert shard[3] * 2 == arr.shape[3], (
                f"migrated {name} not head-sharded: "
                f"{arr.shape} -> {shard}")
            # and bit-identical to the source — no requantization
            assert np.array_equal(np.asarray(arr),
                                  src_entries[key][name]), (
                f"{name} drifted across the host boundary")


# ---------------------------------------------------------------------------
# PacedChannel unit battery
# ---------------------------------------------------------------------------


class _ChunkSink:
    """Fake far side of the transfer channel: validates + acks every
    chunk, remembers what it saw."""

    def __init__(self):
        self.seen = []

    def post_bytes(self, path, blob):
        assert path == "/host/xfer_chunk"
        ((meta, chunk),) = unpack_frames(blob)
        validate_envelope(meta, kind="xfer_chunk")
        self.seen.append((meta["seq"], chunk))
        return envelope("xfer_ack", xfer_id=meta["xfer_id"],
                        seq=meta["seq"], ok=True)


def test_paced_channel_chunking_and_exact_budget():
    clock = VirtualClock(tick_s=0.001)
    sink = _ChunkSink()
    ch = PacedChannel(clock, bytes_per_s=100.0, chunk_bytes=4)
    blob = bytes(range(10))
    report = ch.send(sink, blob, "x0", "a", "b")
    assert report["chunks"] == 3 and report["retries"] == 0
    assert b"".join(c for _s, c in sorted(sink.seen)) == blob
    assert abs(report["transfer_s"] - 10 / 100.0) < 1e-9
    # idle time between transfers never becomes burst credit: the
    # bucket starts EMPTY at each send, so the budget is reproducible
    clock.advance(123.0)
    report2 = ch.send(_ChunkSink(), blob, "x1", "a", "b")
    assert abs(report2["transfer_s"] - 10 / 100.0) < 1e-9


def test_paced_channel_unpaced_is_instant_on_virtual_clock():
    clock = VirtualClock(tick_s=0.001)
    report = PacedChannel(clock, chunk_bytes=4).send(
        _ChunkSink(), b"abcdefgh", "x0", "a", "b")
    assert report["transfer_s"] == 0.0 and report["chunks"] == 2


def test_paced_channel_resumes_from_last_acked_chunk():
    clock = VirtualClock(tick_s=0.001)
    net = NetworkFaultInjector("drop_frame:nth=2:match=a->b", clock=clock)
    sink = _ChunkSink()
    ch = PacedChannel(clock, chunk_bytes=4)
    blob = bytes(range(12))
    report = ch.send(sink, blob, "x0", "a", "b", net=net)
    # chunk 1's first frame dropped in flight: ONE retry, of that chunk
    # alone — never a restart from zero
    assert report["chunks"] == 3 and report["retries"] == 1
    assert [s for s, _c in sink.seen] == [0, 1, 2]
    assert b"".join(c for _s, c in sorted(sink.seen)) == blob


def test_paced_channel_exhausted_retries_raise_typed():
    clock = VirtualClock(tick_s=0.001)
    net = NetworkFaultInjector("drop_frame:every=1:match=a->b",
                               clock=clock)
    ch = PacedChannel(clock, chunk_bytes=4, max_retries=2)
    with pytest.raises(PacedTransferError):
        ch.send(_ChunkSink(), b"abcd", "x0", "a", "b", net=net)


# ---------------------------------------------------------------------------
# auth: typed rejects, distinct counter reasons, off-by-default identity
# ---------------------------------------------------------------------------


def test_auth_battery_unsigned_tampered_replayed(cfg_params):
    cfg, params = cfg_params
    _frontend, agents, _net = build_loopback_fleet(
        params, cfg, n_hosts=2, n_replicas=1, secret="s3cr3t",
        server_kwargs=dict(n_slots=2))
    a0, a1 = agents["host0"], agents["host1"]

    def post(doc):
        raw = a1.handle_host(
            "/host/heartbeat", json.dumps(doc, sort_keys=True).encode())
        return json.loads(raw.decode())

    doc = envelope("heartbeat", host="host0", epoch=0, seq=1)

    # unsigned: typed reject, byte-faithful error envelope
    resp = post(copy.deepcopy(doc))
    assert resp["kind"] == "error"
    assert resp["error"] == "UnsignedEnvelope"

    # tampered: the MAC covers the canonical bytes, so any field flip
    # breaks it
    signed = a0.auth.sign(copy.deepcopy(doc))
    tampered = copy.deepcopy(signed)
    tampered["seq"] = 999
    resp = post(tampered)
    assert resp["kind"] == "error" and resp["error"] == "BadSignature"

    # intact: accepted
    resp = post(signed)
    assert resp["kind"] == "heartbeat_ack"

    # replayed verbatim: the per-sender monotonic nonce refuses it
    resp = post(copy.deepcopy(signed))
    assert resp["kind"] == "error" and resp["error"] == "ReplayedNonce"

    # three DISTINCT counter reasons on the receiving host's registry
    rejects = _samples(a1.registry, "mingpt_fleet_auth_rejects_total")
    assert rejects[(("reason", "unsigned"),)] == 1
    assert rejects[(("reason", "bad_mac"),)] == 1
    assert rejects[(("reason", "replay"),)] == 1
    assert rejects[(("reason", "frame_digest"),)] == 0


def test_auth_typed_errors_and_canonical_bytes():
    auth = FleetAuth("k", sender="x")
    doc = envelope("heartbeat", host="x", epoch=0, seq=1)
    with pytest.raises(UnsignedEnvelope):
        auth.verify(copy.deepcopy(doc))
    assert UnsignedEnvelope.reason == "unsigned"
    assert BadSignature.reason == "bad_mac"
    assert ReplayedNonce.reason == "replay"
    # the signature rides OUTSIDE the canonical bytes: signing changes
    # nothing the MAC covers, which is why auth-off stays byte-identical
    signed = auth.sign(copy.deepcopy(doc))
    assert canonical_bytes(signed) == canonical_bytes(doc)
    assert validate_envelope(copy.deepcopy(signed))["kind"] == "heartbeat"


def test_corrupted_chunk_nacked_under_frame_digest(cfg_params):
    cfg, params = cfg_params
    _frontend, agents, _net = build_loopback_fleet(
        params, cfg, n_hosts=2, n_replicas=1, secret="s3cr3t",
        server_kwargs=dict(n_slots=2))
    a0, a1 = agents["host0"], agents["host1"]
    meta = envelope("xfer_chunk", xfer_id="t0", seq=0, n_chunks=1,
                    digest="0" * 64, total_bytes=3)
    a0.auth.sign(meta)
    raw = a1.handle_host("/host/xfer_chunk", pack_frames([(meta, b"abc")]))
    ack = json.loads(raw.decode())
    assert ack["kind"] == "xfer_ack" and not ack["ok"]
    assert "digest" in ack["message"]
    rejects = _samples(a1.registry, "mingpt_fleet_auth_rejects_total")
    assert rejects[(("reason", "frame_digest"),)] == 1


def test_auth_off_by_default_streams_byte_identical(cfg_params):
    cfg, params = cfg_params

    def run(secret):
        frontend, agents, _ = build_loopback_fleet(
            params, cfg, n_hosts=2, n_replicas=1, secret=secret,
            server_kwargs=dict(n_slots=2))
        hs = [frontend.submit(Request(prompt=p, max_new_tokens=6))
              for p in PROMPTS[:2]]
        frontend.run_until_drained(max_steps=5000)
        return [h.tokens for h in hs], agents

    plain, agents = run(None)
    assert all(a.auth is None for a in agents.values())  # off by default
    signed, _ = run("fleet-secret")
    assert plain == signed


# ---------------------------------------------------------------------------
# the merged fleet page strict-parses with every new family on it
# ---------------------------------------------------------------------------


def test_fleet_metrics_page_strict_parses(cfg_params):
    cfg, params = cfg_params
    frontend, agents, _net = build_loopback_fleet(
        params, cfg, n_hosts=2, n_replicas=1, secret="s3cr3t",
        paced_bytes_per_s=1_000_000.0, server_kwargs=dict(n_slots=2))
    h = frontend.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
    frontend.run_until_drained(max_steps=5000)
    assert h.finish_reason == "length"
    page = frontend.fleet_metrics_page()
    got = parse_prometheus(page)  # raises on any malformed line
    assert got["types"]["mingpt_fleet_hosts"] == "gauge"
    assert got["types"]["mingpt_fleet_auth_rejects_total"] == "counter"
    assert got["types"]["mingpt_fleet_xfer_seconds"] == "histogram"

    hosts = _samples(page, "mingpt_fleet_hosts")
    for host in ("host0", "host1"):
        # each host's view: itself + the peer, both alive
        assert hosts[(("host", host), ("state", "alive"))] == 2
        assert hosts[(("host", host), ("state", "dead"))] == 0
    outcomes = _samples(page, "mingpt_fleet_cross_requests_total")
    assert outcomes[(("outcome", "completed"),)] == 1
    xfer = _samples(page, "mingpt_fleet_xfer_bytes_total")
    assert (("paced", "true"),) in xfer and (("paced", "false"),) in xfer


# ---------------------------------------------------------------------------
# SocketTransport: refused connections retry bounded, then surface typed
# ---------------------------------------------------------------------------


def test_socket_transport_unavailable_after_bounded_backoff():
    # a port that *refuses*: bind-then-close guarantees nothing listens
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    delays = []
    t = SocketTransport("127.0.0.1", port, timeout_s=1.0,
                        connect_retries=2, retry_backoff_s=0.01,
                        sleep=delays.append)
    with pytest.raises(TransportUnavailable) as ei:
        t.fetch_text("/metrics")
    assert "after 3 attempts" in str(ei.value)
    # geometric backoff between the 3 attempts, via the injected sleep
    assert delays == [0.01, 0.02]


# ---------------------------------------------------------------------------
# NetworkFaultInjector: grammar + verdicts
# ---------------------------------------------------------------------------


def test_network_injector_rejects_foreign_ops():
    with pytest.raises(ValueError):
        NetworkFaultInjector("kill:nth=1")


def test_network_injector_partition_until_heal():
    clock = VirtualClock(tick_s=0.001)
    net = NetworkFaultInjector("partition:nth=1:match=a->b", clock=clock)
    with pytest.raises(LinkPartitioned):
        net.link_verdict("a", "b")
    with pytest.raises(LinkPartitioned):  # stays open: no delay given
        net.link_verdict("a", "b")
    assert net.link_verdict("b", "a") == 0.0  # the other direction is up
    net.heal()
    assert net.link_verdict("a", "b") == 0.0
    assert net.fired[0] == "partition:a->b"


def test_network_injector_timed_partition_heals_on_clock():
    clock = VirtualClock(tick_s=0.001)
    net = NetworkFaultInjector("partition:nth=1:match=a->b:delay=0.5",
                               clock=clock)
    with pytest.raises(LinkPartitioned):
        net.link_verdict("a", "b")
    clock.advance(0.4)
    with pytest.raises(LinkPartitioned):
        net.link_verdict("a", "b")
    clock.advance(0.2)  # past the deadline: the cable is back in
    assert net.link_verdict("a", "b") == 0.0


def test_network_injector_slow_drop_and_host_kill():
    clock = VirtualClock(tick_s=0.001)
    net = NetworkFaultInjector(
        "slow_link:every=1:delay=0.2:match=a->b;"
        "drop_frame:nth=2:match=a->b;"
        "host_kill:nth=1:match=hostX", clock=clock)
    assert net.link_verdict("a", "b") == 0.2
    assert net.link_verdict("a", "c") == 0.0  # match filters the link
    assert net.frame_verdict("a", "b") is False
    assert net.frame_verdict("a", "b") is True
    assert net.frame_verdict("a", "b") is False
    assert net.host_verdict("hostY") is False
    assert net.host_verdict("hostX") is True
    assert net.host_verdict("hostX") is False  # nth fires once
