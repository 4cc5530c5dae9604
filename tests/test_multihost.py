"""2-process multi-host integration test on CPU (SURVEY §4's
distributed-without-a-pod strategy, taken to real process boundaries).

Spawns two OS processes joined via jax.distributed over a localhost
coordinator — each contributes ONE CPU device to a dp=2 mesh, feeds its own
half of every global batch, and participates in the snapshot gather. This is
the exact topology of a 2-worker pod slice, minus the chips — something the
reference could never test without standing up a real cluster (SURVEY §5.8).
"""

import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_pair(snapshot: str, max_steps: int, timeout=600, mesh="dp2",
              local_devices=1):
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update(
            PYTHONPATH=str(REPO),  # repo importable from the worker script
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=(
                f"--xla_force_host_platform_device_count={local_devices}"
                if local_devices > 1 else ""
            ),
            COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            NUM_PROCESSES="2",
            PROCESS_ID=str(pid),
        )
        procs.append(subprocess.Popen(
            [sys.executable, "tests/multihost_worker.py", snapshot,
             str(max_steps), mesh],
            cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        ))
    results = {}
    logs = []
    try:
        for p in procs:
            try:
                out, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                # kill BOTH, drain their output, and surface it — a bare
                # TimeoutExpired with no worker logs is undiagnosable
                drained = []
                for q in procs:
                    if q.poll() is None:
                        q.kill()
                    o, _ = q.communicate()
                    drained.append(o or "")
                raise AssertionError(
                    "worker deadlock/timeout; captured logs:\n"
                    + "\n=== next worker ===\n".join(drained)
                ) from None
            logs.append(out)
            assert p.returncode == 0, f"worker failed:\n{out}"
            for line in out.splitlines():
                if line.startswith("MULTIHOST_RESULT "):
                    r = json.loads(line[len("MULTIHOST_RESULT "):])
                    results[r["process"]] = r
    finally:
        # a failed/deadlocked worker must not leak past the test: the peer
        # blocks forever in a collective holding the coordinator socket
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    assert set(results) == {0, 1}, f"missing results:\n{''.join(logs)}"
    return results, logs


@pytest.mark.slow
def test_two_process_training_and_resume(tmp_path):
    snap = str(tmp_path / "mh_snap.msgpack")

    # fresh 2-process run: both processes see the same (global) loss
    results, logs = _run_pair(snap, max_steps=4)
    assert results[0]["start_step"] == 0
    assert results[0]["end_step"] == 4 and results[1]["end_step"] == 4
    assert abs(results[0]["eval_loss"] - results[1]["eval_loss"]) < 1e-6
    assert os.path.exists(snap)

    # resume: both processes pick up at step 4 and continue
    results2, logs2 = _run_pair(snap, max_steps=8)
    assert results2[0]["start_step"] == 4 and results2[1]["start_step"] == 4
    assert results2[0]["end_step"] == 8
    assert results2[0]["eval_loss"] < results[0]["eval_loss"]
    # single-writer: only process 0 printed the snapshot-saved notice
    saved_notices = [
        ("Snapshot saved" in log) for log in logs2
    ]
    assert sum(saved_notices) == 1


@pytest.mark.slow
def test_hybrid_mesh_two_hosts(tmp_path):
    """2 processes x 4 local devices: dp crosses the process (DCN) boundary,
    fsdp/tp ride the intra-process axes — cross-host param gathers, tp
    collectives and the snapshot process_allgather all on one mesh."""
    snap = str(tmp_path / "mh_hybrid.msgpack")
    results, logs = _run_pair(snap, max_steps=3, mesh="hybrid",
                              local_devices=4)
    assert results[0]["end_step"] == 3 and results[1]["end_step"] == 3
    # the eval loss is a global mean — identical on every host
    assert abs(results[0]["eval_loss"] - results[1]["eval_loss"]) < 1e-6
    assert os.path.exists(snap)
    saved_notices = [("Snapshot saved" in log) for log in logs]
    assert sum(saved_notices) == 1


@pytest.mark.slow
def test_ring_attention_across_process_boundary(tmp_path):
    """2 processes x 2 local devices with sp=4: the zigzag ring's ppermute
    hops (and its entry/exit redistribution) cross the process (DCN)
    boundary — long-context sequence parallelism the way a real pod would
    run it, not just virtual devices in one process."""
    snap = str(tmp_path / "mh_ring.msgpack")
    results, logs = _run_pair(snap, max_steps=3, mesh="sp_ring",
                              local_devices=2)
    assert results[0]["end_step"] == 3 and results[1]["end_step"] == 3
    assert abs(results[0]["eval_loss"] - results[1]["eval_loss"]) < 1e-6
    assert os.path.exists(snap)
    saved_notices = [("Snapshot saved" in log) for log in logs]
    assert sum(saved_notices) == 1
