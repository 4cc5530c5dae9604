"""Continuous-batching inference serving (ROADMAP north star: serve heavy
traffic, not one prompt batch at a time).

The substrate is models/generate.py's compiled prefill/decode split: a
static-shape, slot-addressable KV cache updated in place. This package adds
what a server needs on top of it:

* ``SlotKVPool`` (kv_pool.py) — a fixed (L, S_slots, block_size, heads, size)
  cache where each slot holds one in-flight request, with a deterministic
  host-side allocate/free free-list; ``PrefixKVStore`` is the byte-bounded
  LRU of shared-prefix KV entries behind prefix reuse.
* ``DecodeEngine`` (engine.py) — a bounded compiled-program family shared
  by every request for the server's lifetime: bucket-laddered
  prefill-at-offset (O(log block_size) executables; prefill FLOPs track
  prompt length), a one-token-per-step decode over all slots (per-slot
  positions, masked inactive slots, per-slot sampling params as traced
  arrays — admission never recompiles), and device-side prefix row copies.
* ``InferenceServer`` (scheduler.py) — the continuous-batching scheduler:
  a policy-ordered request queue (``AdmissionPolicy`` in admission.py,
  FIFO default) with per-request sampling params, admission into
  free slots at decode-step boundaries (prefix hit → chunked prefill
  interleaved with decode → first token), retirement on per-request stop
  conditions, token streaming via callbacks / request handles.
* ``ServingMetrics`` (metrics.py) — tokens/sec, queue depth, slot
  utilization, per-request TTFT and inter-token latency; periodic log line
  plus a JSON summary, sharing the RateWindow plumbing of
  training/metrics.py.
* ``SpeculativeDecoder`` / ``DraftEngine`` (speculative.py) — draft/verify
  speculative decoding: a small-config draft model (slot pool mirrored
  1:1 with the target's) proposes k tokens, ONE lifetime-compiled verify
  program scores all k+1 rows in a single batched target forward, and the
  scheduler emits the longest matching prefix plus a bonus token —
  multiple tokens per round, token-exact with the plain greedy path.
* ``Router`` / ``ReplicaSupervisor`` (fleet.py) — the resilient
  multi-replica layer: supervised in-process replicas with health-gated
  prefix-affinity routing, per-replica circuit breakers, bounded
  idempotent retry, deadline-aware load shedding and graceful drain;
  request state (requests.py) split from slot state so a request can
  outlive the replica serving it.
* ``ProcessSupervisor`` / ``ProcRouter`` (procfleet/) — the same fleet
  machinery with the failure domain moved to an OS process: replicas
  are spawned subprocesses behind a versioned ``mingpt-rpc/1`` HTTP
  surface (with a deterministic in-process loopback twin for chaos
  tests), SIGKILL-able crash detection via the socket + waitpid, and
  live KV/prefix migration so a drain loses zero admitted requests.

Everything is CPU-testable with a tiny config (tests/test_serving.py,
tests/test_fleet.py) and driven end-to-end by ``serve.py`` at the repo
root.
"""

from mingpt_distributed_tpu.serving import quant
from mingpt_distributed_tpu.serving.admission import AdmissionPolicy, FifoPolicy
from mingpt_distributed_tpu.serving.engine import DecodeEngine
from mingpt_distributed_tpu.serving.fleet import (
    CircuitBreaker,
    FleetHandle,
    Replica,
    ReplicaSupervisor,
    Router,
    VirtualClock,
    WallClock,
    default_server_factory,
)
from mingpt_distributed_tpu.serving.kv_pool import PrefixKVStore, SlotKVPool
from mingpt_distributed_tpu.serving.procfleet import (
    ProcRouter,
    ProcessSupervisor,
    loopback_backend_factory,
    process_backend_factory,
)
from mingpt_distributed_tpu.serving.metrics import ServingMetrics
from mingpt_distributed_tpu.serving.requests import (
    QueueFullError,
    Request,
    RequestHandle,
    ShedError,
)
from mingpt_distributed_tpu.serving.scheduler import InferenceServer, SlotTable
from mingpt_distributed_tpu.serving.speculative import (
    DraftEngine,
    SpeculativeDecoder,
)

__all__ = [
    "AdmissionPolicy",
    "CircuitBreaker",
    "DecodeEngine",
    "DraftEngine",
    "FifoPolicy",
    "FleetHandle",
    "InferenceServer",
    "PrefixKVStore",
    "ProcRouter",
    "ProcessSupervisor",
    "QueueFullError",
    "Replica",
    "ReplicaSupervisor",
    "Request",
    "RequestHandle",
    "Router",
    "ServingMetrics",
    "ShedError",
    "SlotKVPool",
    "SlotTable",
    "SpeculativeDecoder",
    "VirtualClock",
    "WallClock",
    "default_server_factory",
    "loopback_backend_factory",
    "process_backend_factory",
    "quant",
]
