"""Multi-host runtime lifecycle — the ``init_process_group`` analogue.

The reference boots its distributed runtime with
``init_process_group(backend="nccl")`` / ``destroy_process_group``
(/root/reference/mingpt/train.py:34,58), fed by env vars torchrun sets
(RANK / WORLD_SIZE / MASTER_ADDR — slurm_run.sh:17-23). TPU-natively the
same contract is ``jax.distributed.initialize()``: the launcher (launch/)
starts one identical process per TPU host; the coordinator address is the
rendezvous endpoint; there is no backend string because XLA owns the
transport (ICI within a slice, DCN across slices — SURVEY §2.3).

On single-host (or under test) this is a no-op, so the same train.py runs
unchanged from a laptop CPU to a pod slice — the debuggability the reference
lacked by hard-coding NCCL (SURVEY §5.8). "Single-host" is decided by the
absence of a coordinator address and by nothing else: a one-host TPU VM
exports ``TPU_WORKER_HOSTNAMES=localhost`` and ``TPU_WORKER_ID=0`` like a
pod worker does, and ``jax.distributed.initialize()`` with no arguments
would go looking for a cluster from them, on a machine that may have no
network.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

_initialized = False


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join the multi-host job if one is configured; otherwise no-op.

    Resolution order: explicit args > env (COORDINATOR_ADDRESS / NUM_PROCESSES
    / PROCESS_ID — set by launch/tpu_pod_run.sh). Single-process, and no
    network touched, when no coordinator address is given.
    """
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    num_processes = num_processes or _int_env("NUM_PROCESSES")
    process_id = process_id if process_id is not None else _int_env("PROCESS_ID")

    if coordinator_address is not None:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        _initialized = True


def shutdown() -> None:
    """destroy_process_group analogue (reference train.py:58)."""
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False


def _int_env(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v is not None and v != "" else None
