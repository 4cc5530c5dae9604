"""procfleet tests (ISSUE 16) — CPU, tiny config, ``not slow`` tier.

Everything here runs on the deterministic loopback transport (the
byte-faithful in-process twin of the socket; real subprocesses are
exercised by ``serve.py --selftest-procfleet``), so the whole suite is
sleep-free and replayable on a virtual clock:

* a chaos run (kill -9 + slow socket + live migration) produces a
  BYTE-identical JSON report across two runs;
* the ``mingpt-rpc/1`` envelope validator and the size-framed transfer
  channel reject every tampered shape loudly;
* respawn-budget exhaustion fails requests with ``finish_reason=error``
  (never spins), with every crash reaped as exit -9;
* migrating a mid-prefill request resumes its chunks on the peer,
  token-identical to solo generate(), with a prefix hit from the
  shipped rows;
* migrated prefix entries stay head-sharded under tp=2 — adoption is a
  ``device_put`` under the destination pool's sharding, never a gather;
* warm-standby failover (ISSUE 17): adopting a pre-warmed spare records
  a strictly smaller recovery than a cold respawn of the same kill -9,
  stamps a ``failover`` trace event, and backfills the pool;
* the liveness ladder escalates a wedged worker SIGTERM -> SIGKILL
  (the wedge refuses SIGTERM; only the kill rung clears it);
* an exhausted pool falls back to a cold respawn LOUDLY;
* a migrated speculative request resumes proposing from the shipped
  draft-pool rows — zero draft prefill for a bucket-aligned prompt —
  and adopted draft rows stay head-sharded under tp=2.
"""

import json

import jax
import pytest

from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models import gpt
from mingpt_distributed_tpu.parallel.mesh import MeshConfig, make_mesh
from mingpt_distributed_tpu.serving import Request, VirtualClock
from mingpt_distributed_tpu.serving.procfleet import (
    EnvelopeError,
    FRAME_MAGIC,
    ProcRouter,
    ProcessSupervisor,
    envelope,
    loopback_backend_factory,
    pack_frames,
    unpack_frames,
    validate_envelope,
)
from mingpt_distributed_tpu.telemetry import parse_prometheus
from mingpt_distributed_tpu.telemetry.tracing import TraceRecorder
from mingpt_distributed_tpu.training.faults import ProcessFaultInjector
from oracles import solo_greedy


@pytest.fixture(scope="module")
def cfg_params():
    cfg = GPTConfig.make(
        n_layer=2, n_head=2, n_embd=32, vocab_size=50, block_size=32,
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0, dtype="float32",
    )
    return cfg, gpt.init(jax.random.key(0), cfg)


def make_procfleet(cfg_params, n_replicas=2, pspec=None, server_kwargs=None,
                   sup_kwargs=None, **router_kw):
    """A loopback-transport process fleet on a virtual clock with fast
    backoffs — shape-identical to the real-socket fleet (same RPC bytes,
    same exit-code conventions) but fully deterministic."""
    cfg, params = cfg_params
    pinj = ProcessFaultInjector(pspec) if pspec is not None else None
    sup = ProcessSupervisor(
        loopback_backend_factory(params, cfg, n_slots=2,
                                 **(server_kwargs or {})),
        n_replicas=n_replicas,
        clock=VirtualClock(tick_s=0.001),
        process_injector=pinj,
        max_restarts=router_kw.pop("max_restarts", 1),
        restart_backoff_s=0.01,
        **(sup_kwargs or {}),
    )
    streamed = {}
    router = ProcRouter(
        sup,
        on_token=lambda fh, t: streamed.setdefault(
            fh.request_id, []).append(t),
        max_retries=router_kw.pop("max_retries", 3),
        retry_backoff_s=0.01, breaker_reset_s=0.05, **router_kw)
    return router, sup, streamed


PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [10, 11, 12, 13], [40, 41]]


# ---------------------------------------------------------------------------
# loopback determinism: two chaos runs, byte-identical report
# ---------------------------------------------------------------------------


def _chaos_report(cfg_params) -> str:
    """One full chaos story — a kill -9 on replica0's third step RPC, a
    slow socket on replica1 (landing as clock skew, never a sleep), then
    a drain-with-migration — rendered as sorted-key JSON."""
    router, sup, streamed = make_procfleet(
        cfg_params,
        pspec="kill:nth=3:match=replica0;"
              "slow_socket:every=2:delay=0.01:match=replica1",
        server_kwargs=dict(prefix_cache_mb=2.0))
    handles = [router.submit(Request(prompt=p, max_new_tokens=6))
               for p in PROMPTS]
    router.run_until_drained(max_steps=10000)
    src = next(rep.name for rep in sup.replicas if rep.state == "ready")
    migration = router.migrate_and_drain(src)
    doc = {
        "tokens": {h.request_id: h.tokens for h in handles},
        "reasons": {h.request_id: h.finish_reason for h in handles},
        "attempts": {h.request_id: h.attempts for h in handles},
        "streams": streamed,
        "fired": sup.process_injector.fired,
        "summary": router.summary(),
        "migration": migration,
        "exits": sup.shutdown_all(),
    }
    return json.dumps(doc, sort_keys=True)


def test_chaos_report_byte_identical_across_runs(cfg_params):
    a = _chaos_report(cfg_params)
    b = _chaos_report(cfg_params)
    assert a == b
    doc = json.loads(a)
    # the report must also describe a *successful* chaos story, or two
    # identically-broken runs would pass
    assert set(doc["reasons"].values()) == {"length"}
    assert "kill:replica0" in doc["fired"]
    assert "slow_socket:replica1" in doc["fired"]
    assert doc["migration"]["outcome"] == "ok"
    assert doc["migration"]["src_exit_code"] == 75


def test_chaos_tokens_match_solo_and_streams_dedup(cfg_params):
    cfg, params = cfg_params
    doc = json.loads(_chaos_report(cfg_params))
    by_id = doc["tokens"]
    # submission order is deterministic: fleet-0.. maps to PROMPTS order
    for i, p in enumerate(PROMPTS):
        rid = f"fleet-{i}"
        assert by_id[rid] == solo_greedy(params, cfg, p, 6)
        # the caller-visible stream saw each token exactly once, even for
        # the requests whose first attempt died with replica0
        assert doc["streams"][rid] == by_id[rid]
    assert doc["summary"]["duplicates_suppressed"] >= 1


# ---------------------------------------------------------------------------
# mingpt-rpc/1 envelope validator + transfer channel tamper battery
# ---------------------------------------------------------------------------


def test_envelope_validator_tamper_battery():
    good = envelope("submit_result", request_id="r1", queue_depth=0)
    validate_envelope(good)
    validate_envelope(good, kind="submit_result")
    # kind pinning: a valid envelope of the WRONG kind is a protocol
    # error, not a fallthrough
    with pytest.raises(EnvelopeError):
        validate_envelope(good, kind="step_result")

    tampers = [
        lambda d: d.pop("schema"),
        lambda d: d.update(schema="mingpt-rpc/2"),
        lambda d: d.pop("kind"),
        lambda d: d.update(kind="gossip"),
        lambda d: d.pop("request_id"),
        lambda d: d.update(request_id=7),          # wrong type
        lambda d: d.update(queue_depth="3"),       # wrong type
        lambda d: d.update(queue_depth=True),      # bool is not an int
    ]
    for tamper in tampers:
        doc = dict(good)
        tamper(doc)
        with pytest.raises(EnvelopeError):
            validate_envelope(doc)


def test_step_result_event_validation():
    ok = envelope("step_result", events=[
        {"type": "emit", "request_id": "r", "token": 3, "token_index": 0},
        {"type": "finish", "request_id": "r", "finish_reason": "length",
         "n_tokens": 1},
    ], queue_depth=0, occupied=0, recompiles=0, busy=False)
    validate_envelope(ok, kind="step_result")
    # events are validated at mint time too — a worker can't emit drift
    for bad_ev in (
        {"type": "emit", "request_id": "r", "token": 3},   # missing index
        {"type": "emit", "request_id": "r", "token": 3.5,  # wrong type
         "token_index": 0},
        {"type": "levitate", "request_id": "r"},           # unknown type
    ):
        with pytest.raises(EnvelopeError):
            envelope("step_result", events=[bad_ev], queue_depth=0,
                     occupied=0, recompiles=0, busy=False)


def test_transfer_channel_tamper_battery():
    frames = [
        ({"type": "manifest", "replica": "replica0", "unfinished": [],
          "n_frames": 1}, b""),
        ({"type": "prefix_entry", "key": [1, 2, 3]}, b"\x01\x02\x03\x04"),
    ]
    blob = pack_frames(frames)
    assert unpack_frames(blob) == frames
    # pack is canonical: same frames -> same bytes
    assert pack_frames(frames) == blob

    with pytest.raises(EnvelopeError):
        unpack_frames(b"NOTMAGIC" + blob[len(FRAME_MAGIC):])
    with pytest.raises(EnvelopeError):
        unpack_frames(blob[:-1])               # truncated payload
    with pytest.raises(EnvelopeError):
        unpack_frames(blob[: len(FRAME_MAGIC) + 4])  # truncated header
    with pytest.raises(EnvelopeError):
        unpack_frames(blob + b"\x00")          # trailing garbage


# ---------------------------------------------------------------------------
# respawn-budget exhaustion
# ---------------------------------------------------------------------------


def test_respawn_budget_exhaustion_fails_loudly(cfg_params):
    """Every step RPC SIGKILLs its worker and the restart budget runs
    out: accepted requests terminate with finish_reason=error instead of
    the router spinning forever, and every crash is reaped as exit -9
    with its spill collected."""
    router, sup, _ = make_procfleet(cfg_params, pspec="kill:every=1",
                                    max_retries=2)
    handles = [router.submit(Request(prompt=p, max_new_tokens=4))
               for p in PROMPTS[:2]]
    router.run_until_drained(max_steps=5000)
    assert all(h.finished for h in handles)
    assert all(h.finish_reason == "error" for h in handles)
    s = router.summary()
    assert s["pending"] == 0 and s["in_flight"] == 0
    assert s["requests_by_outcome"]["error"] == 2
    assert sup.crash_reports
    assert all(c["exit_code"] == -9 for c in sup.crash_reports)
    # budget of 1 respawn per replica, then the supervisor stops trying
    assert all(rep.state == "crashed" for rep in sup.replicas)


# ---------------------------------------------------------------------------
# live migration
# ---------------------------------------------------------------------------


def test_migration_mid_prefill_resumes_on_peer(cfg_params):
    """Migrating a request whose prefill is mid-flight (chunked, several
    chunks to go): the shipped bucket-quantized leading rows become a
    prefix entry on the peer, the re-submitted request hits it, and the
    final tokens are bit-identical to an undisturbed run."""
    cfg, params = cfg_params
    router, sup, streamed = make_procfleet(
        cfg_params,
        server_kwargs=dict(prefill_chunk=4, prefix_cache_mb=4.0))
    long_prompt = list(range(1, 25))  # 24 tokens = 6 chunks of 4
    h = router.submit(Request(prompt=long_prompt, max_new_tokens=6))

    src = None
    for _ in range(200):
        router.step()
        for rep in sup.replicas:
            for wh in rep.backend.worker.server.unfinished():
                if wh.prefilling and wh.prefill_pos > 0:
                    src = rep
        if src is not None:
            break
    assert src is not None, "request never observed mid-prefill"

    report = router.migrate_and_drain(src.name)
    assert report["outcome"] == "ok"
    assert h.request_id in report["requests_moved"]
    assert report["entries_installed"] >= 1
    assert report["src_exit_code"] == 75

    router.run_until_drained(max_steps=5000)
    assert h.finish_reason == "length"
    assert h.tokens == solo_greedy(params, cfg, long_prompt, 6)
    assert streamed[h.request_id] == h.tokens  # zero dup/lost emissions
    dst = sup.replica_by_name(report["to"])
    # the peer resumed from the shipped rows rather than re-prefilling
    # from scratch
    assert dst.backend.worker.server.metrics.prefix_hits >= 1
    # migration re-routing consumes no retry budget
    assert all(v == 0
               for v in router.summary()["retries_by_reason"].values())


def test_migrated_prefix_entries_stay_head_sharded_tp2(cfg_params):
    """Under tp=2, adopting a migrated prefix entry is a device_put under
    the destination pool's kv_sharding: entries land head-sharded (the
    heads axis split across the mesh), never gathered to one device."""
    cfg, params = cfg_params
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices (conftest forces 8)")
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    # the default ladder at block_size=32 is a single 32-bucket (nothing
    # short ever stores); give it small buckets so a 9-token prompt
    # quantizes to a storable 8-row entry
    router, sup, _ = make_procfleet(
        cfg_params,
        server_kwargs=dict(mesh=mesh, prefix_cache_mb=4.0,
                           prefill_buckets=(8, 16, 32)))
    h = router.submit(Request(prompt=[5, 6, 7, 8, 9, 10, 11, 12, 13],
                              max_new_tokens=4))
    router.run_until_drained(max_steps=2000)
    assert h.finish_reason == "length"

    src = sup.replica_by_name(h.replica)
    report = router.migrate_and_drain(src.name)
    assert report["outcome"] == "ok"
    assert report["entries_installed"] >= 1

    dst = sup.replica_by_name(report["to"])
    entries = dst.backend.worker.server.engine.prefix_store.entries()
    assert entries
    for key, entry in entries:
        for arr in entry.values():
            shard = arr.sharding.shard_shape(arr.shape)
            assert shard[3] * 2 == arr.shape[3], (
                f"migrated entry (rows={len(key)}) not head-sharded: "
                f"{arr.shape} -> {shard}")


# ---------------------------------------------------------------------------
# warm-standby failover (ISSUE 17)
# ---------------------------------------------------------------------------


class _EventSink:
    """Trace sink collecting mirrored (kind, record) pairs in order."""

    def __init__(self):
        self.records = []

    def write(self, kind, rec):
        self.records.append((kind, rec))

    def close(self):
        pass


def _kill_run(cfg_params, standby):
    """One kill -9 on replica0's third step, drained to completion and
    stepped until the victim respawned; the standby axis is the only
    difference between runs, so the recorded recoveries compare the two
    paths on the SAME fault trace."""
    sink = _EventSink()
    recorder = TraceRecorder(sink=sink)
    router, sup, streamed = make_procfleet(
        cfg_params, pspec="kill:nth=3:match=replica0",
        sup_kwargs=dict(standby=standby), trace_recorder=recorder)
    handles = [router.submit(Request(prompt=p, max_new_tokens=6))
               for p in PROMPTS]
    router.run_until_drained(max_steps=10000)
    for _ in range(500):
        if sup.recovery_log:
            break
        router.step()
    return router, sup, handles, streamed, sink


def test_standby_adoption_beats_cold_respawn(cfg_params):
    cfg, params = cfg_params
    runs = {path: _kill_run(cfg_params, standby)
            for path, standby in (("cold", 0), ("standby", 1))}
    for router, sup, handles, streamed, _ in runs.values():
        for p, h in zip(PROMPTS, handles):
            assert h.finish_reason == "length"
            assert h.tokens == solo_greedy(params, cfg, p, 6)
            # zero duplicate or lost tokens across the failover
            assert streamed[h.request_id] == h.tokens
    rec_cold = runs["cold"][1].recovery_log[0]
    rec_stby = runs["standby"][1].recovery_log[0]
    assert rec_cold["path"] == "cold" and rec_cold["adopted"] is None
    assert rec_stby["path"] == "standby"
    assert rec_stby["adopted"] == "standby0"
    # adoption skips the cold-spawn backoff entirely: strictly faster
    # on the same fault, never merely equal
    assert rec_stby["recovery_s"] < rec_cold["recovery_s"]
    # the pool was backfilled AFTER the adoption (spawn cost lands off
    # the recovery window just recorded)
    assert runs["standby"][1].standby_pool.available() == 1
    events = [rec for kind, rec in runs["standby"][4].records
              if kind == "event" and rec["name"] == "failover"]
    assert events, "no failover trace event stamped"
    for e in events:
        assert e["from_replica"] == "replica0"
        assert e["to_replica"] == "standby0"
        assert e["path"] == "standby"
    page = parse_prometheus(runs["standby"][0].fleet_metrics_page())
    got = {(n, tuple(sorted(l.items()))): v for n, l, v in page["samples"]}
    assert got[("mingpt_fleet_standby_adoptions_total", ())] == 1
    assert got[("mingpt_fleet_standby_pool_size", ())] == 1


def test_hang_escalation_sigterm_then_sigkill(cfg_params):
    """A stuck_step wedge freezes replica0's step progress while its
    mirrored load stays nonzero: the ladder must fire SIGTERM first
    (refused — the wedged worker's handler can never run), SIGKILL
    after the grace, and the crash path recovers through adoption."""
    cfg, params = cfg_params
    router, sup, streamed = make_procfleet(
        cfg_params, pspec="stuck_step:nth=3:match=replica0",
        sup_kwargs=dict(standby=1, hang_deadline_s=0.01,
                        hang_kill_grace_s=0.005))
    ladder = []
    orig = sup.poll_liveness

    def spy():
        out = orig()
        ladder.extend(out)
        return out

    sup.poll_liveness = spy
    handles = [router.submit(Request(prompt=p, max_new_tokens=6))
               for p in PROMPTS]
    router.run_until_drained(max_steps=10000)
    for p, h in zip(PROMPTS, handles):
        assert h.finish_reason == "length"
        assert h.tokens == solo_greedy(params, cfg, p, 6)
        assert streamed[h.request_id] == h.tokens
    assert ladder == [("replica0", "term"), ("replica0", "kill")]
    crash = next(c for c in sup.crash_reports
                 if c["replica"] == "replica0")
    assert crash["exit_code"] == -9  # SIGTERM did NOT produce exit 75
    rec = sup.recovery_info("replica0")
    assert rec is not None and rec["path"] == "standby"
    assert sup.replica_by_name("replica0").state == "ready"
    page = parse_prometheus(router.fleet_metrics_page())
    esc = {l.get("signal"): v for n, l, v in page["samples"]
           if n == "mingpt_fleet_hang_escalations_total"}
    assert esc == {"term": 1, "kill": 1}


def test_hang_deadline_none_never_escalates(cfg_params):
    """Without a deadline the ladder is inert — a wedged replica is the
    restart budget's problem, and an idle fleet is never judged."""
    router, sup, _ = make_procfleet(cfg_params, sup_kwargs=dict(standby=0))
    assert sup.poll_liveness() == []
    h = router.submit(Request(prompt=PROMPTS[0], max_new_tokens=4))
    router.run_until_drained(max_steps=2000)
    assert h.finish_reason == "length"
    assert sup.poll_liveness() == []


def test_standby_pool_exhausted_falls_back_cold_loudly(cfg_params, capsys):
    """Both replicas die in the same round against a 1-deep pool: the
    first respawn adopts the spare, the second must cold-spawn and SAY
    SO on stderr — a silent fallback would hide that the fleet is
    running without its recovery-latency insurance."""
    cfg, params = cfg_params
    router, sup, streamed = make_procfleet(
        cfg_params,
        pspec="kill:nth=3:match=replica0;kill:nth=3:match=replica1",
        sup_kwargs=dict(standby=1))
    handles = [router.submit(Request(prompt=p, max_new_tokens=6))
               for p in PROMPTS]
    router.run_until_drained(max_steps=10000)
    for _ in range(500):
        if len(sup.recovery_log) >= 2:
            break
        router.step()
    for p, h in zip(PROMPTS, handles):
        assert h.finish_reason == "length"
        assert h.tokens == solo_greedy(params, cfg, p, 6)
        assert streamed[h.request_id] == h.tokens
    paths = {r["replica"]: r["path"] for r in sup.recovery_log}
    assert paths == {"replica0": "standby", "replica1": "cold"}
    assert "standby pool exhausted" in capsys.readouterr().err
    assert sup.replica_by_name("replica1").last_spawn_path == "cold"
    # the post-crash backfill restocked the pool for the NEXT fault
    assert sup.standby_pool.available() == 1


def _decode_src(router, sup):
    """Step until some worker holds a request past prefill (the draft
    lane is primed only then — that's the state worth migrating)."""
    for _ in range(500):
        router.step()
        for rep in sup.replicas:
            for wh in rep.backend.worker.server.unfinished():
                if not wh.prefilling:
                    return rep
    return None


def test_migrated_spec_request_resumes_without_draft_prefill(cfg_params):
    """Speculative-state-complete migration: the draft-pool rows ride
    the transfer channel next to the target rows, and a bucket-aligned
    prompt re-primes on the peer with ZERO draft prefill calls — the
    whole primed cache shipped (the draft ladder has no ``-1``: drafts
    never regenerate prompt logits)."""
    cfg, params = cfg_params
    router, sup, streamed = make_procfleet(
        cfg_params,
        server_kwargs=dict(draft_params=params, draft_cfg=cfg, spec_k=3,
                           prefill_chunk=4, prefill_buckets=(8, 16, 32)))
    prompt = list(range(1, 9))  # 8 tokens: exactly a ladder bucket
    h = router.submit(Request(prompt=prompt, max_new_tokens=6))
    src = _decode_src(router, sup)
    assert src is not None, "request never observed mid-decode"
    report = router.migrate_and_drain(src.name)
    assert report["outcome"] == "ok"
    assert report["draft_rows_installed"] >= 1
    dst = sup.replica_by_name(report["to"])
    spec_dec = dst.backend.worker.server.spec
    assert spec_dec.pending_draft  # parked until the re-prime
    prefills = []
    orig = spec_dec.draft.engine.prefill_chunk_call
    spec_dec.draft.engine.prefill_chunk_call = (
        lambda *a, **kw: prefills.append(a) or orig(*a, **kw))
    router.run_until_drained(max_steps=5000)
    assert h.finish_reason == "length"
    assert h.tokens == solo_greedy(params, cfg, prompt, 6)
    assert streamed[h.request_id] == h.tokens
    assert spec_dec.prime_adopted == 1
    assert prefills == [], "peer re-prefilled the draft lane"
    assert not spec_dec.pending_draft  # consumed by the prime


def test_migrated_draft_rows_stay_head_sharded_tp2(cfg_params):
    """Under tp=2 the parked draft rows are re-placed under the draft
    pool's kv_sharding at adoption — heads split across the mesh, never
    gathered — and the adopted prime still decodes token-exact."""
    cfg, params = cfg_params
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices (conftest forces 8)")
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    router, sup, _ = make_procfleet(
        cfg_params,
        server_kwargs=dict(mesh=mesh, draft_params=params, draft_cfg=cfg,
                           spec_k=3, prefill_chunk=4,
                           prefill_buckets=(8, 16, 32)))
    # max_new leaves a decode round AFTER the prefill-completion round
    # (a k=3 spec round can retire 4 tokens at once), so a mid-decode
    # migration window is observable
    prompt = list(range(1, 9))
    h = router.submit(Request(prompt=prompt, max_new_tokens=6))
    src = _decode_src(router, sup)
    assert src is not None, "request never observed mid-decode"
    report = router.migrate_and_drain(src.name)
    assert report["outcome"] == "ok"
    assert report["draft_rows_installed"] >= 1
    spec_dec = sup.replica_by_name(
        report["to"]).backend.worker.server.spec
    assert spec_dec.pending_draft
    for key, entry in spec_dec.pending_draft.items():
        assert list(key) == prompt[:len(key)]
        for arr in entry.values():
            shard = arr.sharding.shard_shape(arr.shape)
            assert shard[3] * 2 == arr.shape[3], (
                f"parked draft rows not head-sharded: "
                f"{arr.shape} -> {shard}")
    router.run_until_drained(max_steps=5000)
    assert h.finish_reason == "length"
    assert h.tokens == solo_greedy(params, cfg, prompt, 6)
    assert spec_dec.prime_adopted >= 1
