"""Checkpoint / resume: step-granular snapshots to local disk, S3 or GCS.

Re-design of the reference's snapshot machinery (SURVEY §3.4/§5.4):
ModelSnapshot (/root/reference/mingpt/trainer.py:33-37), torch.save to disk or
BytesIO->boto3 S3 (trainer.py:83-95,149-167), fsspec read + try-load-else-fresh
(trainer.py:97-116). Kept: the same public semantics — a single snapshot path
(any fsspec URL: local, ``s3://``, ``gs://``), "missing snapshot = train from
scratch", the wrapper-agnostic schema. Fixed / upgraded:

* **single writer** — only process 0 writes (the reference gated on
  *local* rank 0, so every node raced on one S3 key — bug B9);
* **step-granular resume** — snapshot carries step, epoch, PRNG key and the
  data-iterator state, not just an epoch counter (the reference loses
  mid-epoch progress, sampler position and RNG — SURVEY §5.4 "not saved");
* **no pickle** — arrays go through flax.serialization msgpack (the
  reference's torch.load of an untrusted path executes pickle);
* atomic local writes (tmp + rename) so a killed job can't leave a torn
  snapshot behind;
* **durable, crash-consistent saves** (training/durability.py): every
  save writes a step-suffixed data object and then commits it via a small
  JSON manifest (``<path>.manifest.json``: ``latest`` pointer +
  per-checkpoint SHA-256 digest + step), keeping the last K checkpoints.
  All fsspec I/O retries transient errors with exponential backoff, and
  restore verifies the digest — falling back to the previous good
  checkpoint on a torn/truncated/bit-flipped blob instead of crashing
  (or loading garbage).

The serialised schema is the public contract (ModelSnapshot analogue):
``{version, step, epoch, prng, data_state, config, state: {params, opt_state}}``.
A legacy single blob at the bare ``path`` (the pre-manifest layout) still
restores; new saves always go through the manifest.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import numpy as np
from flax import serialization

from mingpt_distributed_tpu.training import durability
from mingpt_distributed_tpu.training.durability import (
    RetryPolicy,
    SnapshotIntegrityError,
)

SNAPSHOT_VERSION = 1
#: payload version of one shard of a sharded snapshot (ISSUE 9): same
#: schema as v1 plus ``shard``/``n_shards`` framing; every leaf is
#: flattened and split into n_shards contiguous chunks, meta fields
#: (prng/data_state/config) ride in shard 0 only.
SHARDED_SNAPSHOT_VERSION = 2
DEFAULT_SNAPSHOT_PATH = "gpt_snapshot.msgpack"  # reference default: gpt_snapshot.pt
DEFAULT_KEEP = 3  # checkpoints retained in the manifest (keep-last-K)


@dataclass
class Snapshot:
    """In-memory snapshot (the reference's ModelSnapshot, trainer.py:33-37,
    extended to step granularity)."""

    params: Any
    opt_state: Any
    step: int = 0
    epoch: int = 0
    prng: Optional[np.ndarray] = None
    data_state: dict = field(default_factory=dict)
    config: dict = field(default_factory=dict)


def _to_host(tree: Any) -> Any:
    """Fully-addressable host copy of a (possibly sharded) pytree."""
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)


def _chunk_state(sd: Any, i: int, n: int) -> Any:
    """Shard ``i``'s slice of a state dict: every leaf flattened and
    contiguously split into ``n`` near-equal chunks (0-d leaves land
    wholly in shard 0; np.array_split pads nothing)."""
    if isinstance(sd, dict):
        return {k: _chunk_state(v, i, n) for k, v in sd.items()}
    return np.array_split(np.asarray(sd).reshape(-1), n)[i]


def _assemble_state(skel_sd: Any, shard_sds: list, label: str) -> Any:
    """Inverse of ``_chunk_state``: concatenate every leaf's chunks across
    the shard payloads and reshape against the skeleton state dict."""
    if isinstance(skel_sd, dict):
        try:
            return {
                k: _assemble_state(skel_sd[k], [s[k] for s in shard_sds], label)
                for k in skel_sd
            }
        except KeyError as e:
            raise ValueError(
                f"sharded snapshot {label} is missing key {e} expected by "
                f"the current config — refusing to restore"
            ) from None
    flat = np.concatenate([np.asarray(s).reshape(-1) for s in shard_sds])
    return flat.reshape(tuple(np.shape(skel_sd)))


def save_snapshot(
    path: str,
    snap: Snapshot,
    keep: int = DEFAULT_KEEP,
    retry: Optional[RetryPolicy] = None,
    shards: int = 1,
) -> None:
    """Serialise and durably commit. Call only from the single writer
    (process 0).

    The write protocol (durability.commit_blob/commit_shards): the data
    objects land at step-suffixed keys nothing references yet (local keys
    additionally use tmp+rename, the reference's atomicity, now with a
    digest), then the manifest PUT commits them as a unit. A crash or
    injected fault anywhere in between leaves the previous manifest — and
    every checkpoint it points at — fully intact. Transient fsspec errors
    retry with backoff + jitter.

    ``shards > 1`` (manifest schema v2) splits the state into that many
    data objects with per-shard digests — ZeRO runs pass their dp extent
    so write amplification tracks per-host state. The *contents* are
    layout-independent (each leaf contiguously chunked), so any shard
    count restores against any other; the shard count is a property of
    the write, not of the checkpoint. A state too large for one data
    object (``durability.max_object_bytes``) is split further, whatever
    ``shards`` says.
    """
    state = {
        "params": _to_host(snap.params),
        "opt_state": _to_host(snap.opt_state),
    }
    nbytes = sum(x.nbytes for x in jax.tree.leaves(state))
    shards = max(shards, -(-nbytes // durability.max_object_bytes()))
    if shards <= 1:
        payload = {
            "version": SNAPSHOT_VERSION,
            "step": snap.step,
            "epoch": snap.epoch,
            "prng": None if snap.prng is None else np.asarray(snap.prng),
            "data_state": json.dumps(snap.data_state),
            "config": json.dumps(snap.config),
            "state": state,
        }
        blob = serialization.to_bytes(payload)
        durability.commit_blob(
            path, blob, step=snap.step, epoch=snap.epoch, keep=keep,
            policy=retry,
        )
        return
    state_sd = serialization.to_state_dict(state)
    blobs = []
    for i in range(shards):
        payload = {
            "version": SHARDED_SNAPSHOT_VERSION,
            "shard": i,
            "n_shards": shards,
            "step": snap.step,
            "epoch": snap.epoch,
            # meta rides in shard 0 only — it is tiny and restoring it
            # twice would be ambiguity, not redundancy
            "prng": (
                np.asarray(snap.prng)
                if i == 0 and snap.prng is not None else None
            ),
            "data_state": json.dumps(snap.data_state) if i == 0 else "",
            "config": json.dumps(snap.config) if i == 0 else "",
            "state": _chunk_state(state_sd, i, shards),
        }
        blobs.append(serialization.to_bytes(payload))
    durability.commit_shards(
        path, blobs, step=snap.step, epoch=snap.epoch, keep=keep, policy=retry
    )


def load_snapshot(
    path: str,
    params_like: Any,
    opt_state_like: Any = None,
    retry: Optional[RetryPolicy] = None,
) -> Optional[Snapshot]:
    """Try to load; None = no snapshot, train from scratch (the reference's
    FileNotFoundError branch, trainer.py:103-107).

    Restore path: read the manifest, walk newest → oldest, return the
    first checkpoint whose SHA-256 matches its committed digest and whose
    payload deserialises — a torn/truncated latest falls back to the
    previous good checkpoint. No manifest falls back to the legacy single
    blob at the bare ``path``. Only *missing* (durability.classify_io_error
    — FileNotFoundError or any ENOENT-carrying OSError, regardless of
    fsspec backend) means fresh start; transient I/O retries then raises,
    so a blip can never be mistaken for "no snapshot" and let a later save
    overwrite the only good state.

    ``params_like`` / ``opt_state_like`` supply the target pytree structure
    (fresh init or eval_shape) the serialised arrays are poured into —
    shape/dtype mismatch raises rather than silently mistraining.
    ``opt_state_like=None`` skips optimizer state (inference-only restore);
    the returned Snapshot then has ``opt_state=None``.
    """
    manifest = durability.load_manifest(path, retry)
    if manifest is not None and manifest.entries:
        blobs, entry = durability.read_verified_shards(path, manifest, retry)
        if entry.shards is None:
            payload = _restore_payload(blobs[0], source=entry.key)
        else:
            payload = _restore_sharded(
                blobs, entry, params_like, opt_state_like
            )
    else:
        # legacy pre-manifest layout: one blob at the bare path
        try:
            blob = durability.read_bytes(path, retry)
        except BaseException as e:  # noqa: BLE001 — classified, not blanket
            if durability.is_missing_error(e):
                return None
            raise
        payload = _restore_payload(blob, source=path)
    params = _owned(serialization.from_state_dict(
        _abstract_to_zeros(params_like), payload["state"]["params"]
    ))
    _check_shapes(params_like, params, "params")
    opt_state = None
    if opt_state_like is not None:
        opt_state = _owned(serialization.from_state_dict(
            _abstract_to_zeros(opt_state_like), payload["state"]["opt_state"]
        ))
        _check_shapes(opt_state_like, opt_state, "opt_state")
    prng = payload["prng"]
    if prng is not None:
        prng = np.array(prng)
    return Snapshot(
        params=params,
        opt_state=opt_state,
        step=int(payload["step"]),
        epoch=int(payload["epoch"]),
        prng=None if prng is None or np.ndim(prng) == 0 else np.asarray(prng),
        data_state=json.loads(payload["data_state"]) if payload["data_state"] else {},
        config=json.loads(payload["config"]) if payload["config"] else {},
    )


def _owned(tree: Any) -> Any:
    """Deep-copy restored leaves into memory the caller owns.

    msgpack_restore hands back READ-ONLY numpy views into the serialised
    blob. jax's CPU backend zero-copy-adopts immutable aligned numpy
    arrays on device_put — and the trainer then DONATES the restored
    state to the compiled step, so XLA would write into (and recycle)
    heap memory owned by the blob's bytes object: nondeterministic
    corruption/segfaults on resume. Owned writable copies force a real
    device buffer and also let the (much larger) blob be GC'd instead of
    being pinned by views."""
    return jax.tree.map(np.array, tree)


def _restore_payload(
    blob: bytes, source: str, expected: int = SNAPSHOT_VERSION
) -> dict:
    """msgpack bytes -> payload dict, with version gate and a corruption
    error that names the offending object."""
    try:
        payload = serialization.msgpack_restore(blob)
    except Exception as e:
        raise SnapshotIntegrityError(
            f"snapshot blob {source} is corrupt (msgpack decode failed): {e}"
        ) from e
    if payload["version"] != expected:
        raise ValueError(
            f"snapshot version {payload['version']} != {expected}"
        )
    return payload


def _restore_sharded(
    blobs: list, entry, params_like: Any, opt_state_like: Any
) -> dict:
    """Shard payloads (already digest-verified) -> one v1-shaped payload
    with fully assembled state sections. Works for ANY saved shard count:
    the chunking is layout-independent, so this is where a dp=4 checkpoint
    reshards onto a dp=2 or dp=1 run."""
    payloads = [
        _restore_payload(
            blob, source=entry.shards[i].key,
            expected=SHARDED_SNAPSHOT_VERSION,
        )
        for i, blob in enumerate(blobs)
    ]
    payloads.sort(key=lambda p: int(p["shard"]))
    n = len(payloads)
    if [int(p["shard"]) for p in payloads] != list(range(n)) or any(
        int(p["n_shards"]) != n for p in payloads
    ):
        raise SnapshotIntegrityError(
            f"sharded snapshot at step {entry.step} has inconsistent shard "
            f"framing: got shards "
            f"{[(int(p['shard']), int(p['n_shards'])) for p in payloads]}"
        )
    head = payloads[0]
    state_sds = [p["state"] for p in payloads]
    params_skel = serialization.to_state_dict(_abstract_to_zeros(params_like))
    state = {
        "params": _assemble_state(
            params_skel, [s["params"] for s in state_sds], "params"
        ),
        "opt_state": None,
    }
    if opt_state_like is not None:
        opt_skel = serialization.to_state_dict(
            _abstract_to_zeros(opt_state_like)
        )
        state["opt_state"] = _assemble_state(
            opt_skel, [s["opt_state"] for s in state_sds], "opt_state"
        )
    return {
        "version": SNAPSHOT_VERSION,
        "step": head["step"],
        "epoch": head["epoch"],
        "prng": head["prng"],
        "data_state": head["data_state"],
        "config": head["config"],
        "state": state,
    }


def _check_shapes(expected: Any, restored: Any, label: str) -> None:
    """Refuse shape/dtype drift between the current config's state and the
    snapshot — e.g. a vocab change with a stale snapshot_path would otherwise
    silently mistrain (flax from_bytes does not validate leaf shapes)."""

    def check(path, exp, got):
        eshape = tuple(getattr(exp, "shape", ()) or ())
        gshape = tuple(np.shape(got))
        if eshape != gshape:
            raise ValueError(
                f"snapshot {label} leaf {jax.tree_util.keystr(path)} has "
                f"shape {gshape}, but the current config expects {eshape} — "
                f"refusing to restore (did the dataset/model config change "
                f"under an old snapshot_path?)"
            )

    jax.tree_util.tree_map_with_path(check, expected, restored)


def _abstract_to_zeros(tree: Any) -> Any:
    """Accept concrete arrays or ShapeDtypeStructs as the target skeleton."""

    def conv(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return np.zeros(x.shape, x.dtype)
        return x

    return jax.tree.map(conv, tree)


def restore_inference_params(path: str, gpt_cfg) -> Optional[Snapshot]:
    """Restore a train.py snapshot for inference (params only, no optimizer
    state): the backend dispatch sample.py and serve.py share. ``.msgpack``
    = single blob (this module); anything else = Orbax directory (a sharded
    checkpoint is not an openable file). Returns None when no snapshot
    exists at ``path``."""
    from mingpt_distributed_tpu.models import gpt

    params_shape = jax.eval_shape(
        lambda k: gpt.init(k, gpt_cfg), jax.random.key(0)
    )
    if path.endswith(".msgpack"):
        return load_snapshot(path, params_shape)
    from mingpt_distributed_tpu.training import checkpoint_orbax

    return checkpoint_orbax.load_snapshot(path, params_shape)
