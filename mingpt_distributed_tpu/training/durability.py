"""Durable I/O primitives for checkpointing: error classification, retry
with exponential backoff + jitter, SHA-256 digests, and the commit
manifest that turns a set of snapshot objects into a crash-consistent
checkpoint history.

Production TPU training treats preemption and storage flakiness as the
steady state (PAPERS.md: "Scalable Training of Language Models using JAX
pjit and TPUv4"). The failure modes this module is built around:

* **transient I/O** — an object-store PUT/GET times out or resets; the
  only correct reaction is backoff + retry, not killing a multi-hour run;
* **missing object** — fsspec backends surface "no such key" as
  ``FileNotFoundError`` *or* other ``OSError`` subclasses depending on
  backend; missing must be classified in ONE place so "fresh start" and
  "retry" never get confused (a transient error mistaken for missing
  would let a later save overwrite the only good state);
* **torn / corrupt blobs** — a writer killed mid-PUT, or a store that
  returns truncated bytes. Every committed object carries a SHA-256
  digest in the manifest; restore verifies before trusting.

The commit protocol (``Manifest``): data objects are written under
step-suffixed keys that nothing references yet, then a small JSON
manifest — ``latest`` pointer + per-checkpoint digest/step — is written
last as the single commit point. A crash between the two leaves the
previous manifest (and every object it references) fully intact.

Manifest schema v2 (ISSUE 9) extends an entry with an optional
``shards`` list: a checkpoint may be committed as N data objects
(``.shard-iiii-of-nnnn`` keys), each with its own SHA-256/size, written
before the single manifest PUT — the commit stays atomic while
save/restore I/O scales with per-host (1/dp) state for ZeRO-sharded
runs. Single-blob entries serialise exactly as in v1, and v1 manifests
still load (restore treats a blob entry as a 1-shard checkpoint).
"""

from __future__ import annotations

import dataclasses
import errno
import hashlib
import json
import os
import random
import resource
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Tuple

import fsspec

from mingpt_distributed_tpu.telemetry import log_event

MANIFEST_VERSION = 2
#: versions ``from_json`` accepts — v1 manifests (single-blob entries
#: only) predate shard support and must keep restoring
SUPPORTED_MANIFEST_VERSIONS = (1, 2)
MANIFEST_SUFFIX = ".manifest.json"

# -- error classification ---------------------------------------------------

MISSING = "missing"
TRANSIENT = "transient"
PERMANENT = "permanent"

# errno values that mean "the object is not there" rather than "the store
# hiccuped" — ENOENT is the POSIX spelling; some fsspec backends raise a
# bare OSError carrying it instead of FileNotFoundError.
_MISSING_ERRNOS = {errno.ENOENT}
# errors that retrying cannot fix: bad credentials, a directory where a
# file was expected, read-only stores.
_PERMANENT_OSERRORS = (
    PermissionError,
    IsADirectoryError,
    NotADirectoryError,
)
# EFBIG: the object is larger than the machine lets one file be; the same
# bytes fail the same way on every attempt.
_PERMANENT_ERRNOS = {errno.EFBIG}


def classify_io_error(exc: BaseException) -> str:
    """One shared verdict for every fsspec/OS error the checkpoint layer
    sees: ``missing`` | ``transient`` | ``permanent``.

    Used by both the retry loop (retry only ``transient``) and
    ``load_snapshot`` ("fresh start" only on ``missing``) so the two can
    never disagree about what a given exception means.
    """
    if isinstance(exc, _PERMANENT_OSERRORS):
        return PERMANENT
    if isinstance(exc, FileNotFoundError):
        return MISSING
    if isinstance(exc, OSError):
        if exc.errno in _MISSING_ERRNOS:
            return MISSING
        if exc.errno in _PERMANENT_ERRNOS:
            return PERMANENT
        # covers TimeoutError, ConnectionError, BlockingIOError, and the
        # anonymous OSErrors object-store backends raise on flaky transport
        return TRANSIENT
    return PERMANENT


def is_missing_error(exc: BaseException) -> bool:
    return classify_io_error(exc) == MISSING


class SnapshotIntegrityError(RuntimeError):
    """Every checkpoint referenced by the manifest failed digest or
    deserialisation checks — restoring would load corrupt state, and
    fresh-starting would let the next save overwrite the evidence."""


# -- retry ------------------------------------------------------------------


@dataclass
class RetryPolicy:
    """Exponential backoff with deterministic-seedable jitter.

    ``sleep`` is injectable so tests (and the fault harness) run with zero
    wall-clock delay; ``seed`` pins the jitter sequence.
    """

    attempts: int = 4
    base_delay_s: float = 0.5
    max_delay_s: float = 8.0
    multiplier: float = 2.0
    jitter: float = 0.25          # fraction of the delay randomised away
    seed: Optional[int] = None
    sleep: Callable[[float], None] = time.sleep

    def delays(self):
        rng = random.Random(self.seed)
        d = self.base_delay_s
        for _ in range(max(self.attempts - 1, 0)):
            yield d * (1.0 - self.jitter * rng.random())
            d = min(d * self.multiplier, self.max_delay_s)


#: zero-sleep policy for tests and the --selftest-faults smoke
NO_WAIT = RetryPolicy(attempts=4, base_delay_s=0.0, seed=0, sleep=lambda _: None)


def with_retries(
    fn: Callable[[], Any],
    policy: Optional[RetryPolicy] = None,
    op: str = "io",
) -> Any:
    """Run ``fn``; retry transient failures per ``policy``.

    ``missing`` and ``permanent`` errors raise immediately (retrying a 404
    or a permission error only delays the inevitable); the last transient
    error raises once attempts are exhausted.
    """
    policy = policy or RetryPolicy()
    delays = policy.delays()
    attempt = 1
    while True:
        try:
            return fn()
        except BaseException as e:  # noqa: BLE001 — classified below
            verdict = classify_io_error(e)
            if verdict != TRANSIENT:
                raise
            try:
                delay = next(delays)
            except StopIteration:
                raise e
            log_event(
                f"[durability] transient {op} error "
                f"(attempt {attempt}/{policy.attempts}): {e!r}; "
                f"retrying in {delay:.2f}s",
                op=op, attempt=attempt,
            )
            policy.sleep(delay)
            attempt += 1


# -- digests ----------------------------------------------------------------


def sha256_hex(blob: bytes) -> str:
    return hashlib.sha256(blob).hexdigest()


# -- byte transport (retry-wrapped, atomic where the backend allows) --------

#: the size one data object of a checkpoint is kept to. A state larger than
#: this is committed as several shard objects (schema v2) instead of one
#: blob of any size: GPT-2 124M with its Adam moments is 1.96 GB, and a
#: machine that limits file size refuses that as one file (EFBIG).
MAX_OBJECT_BYTES = 64 * 2**20


def max_object_bytes() -> int:
    """Largest data object a save should write: ``MAX_OBJECT_BYTES``, or
    half of this process's file-size limit (``RLIMIT_FSIZE``) where the
    machine sets a smaller one — half, because an object carries msgpack
    framing on top of its arrays."""
    soft, _ = resource.getrlimit(resource.RLIMIT_FSIZE)
    if soft == resource.RLIM_INFINITY:
        return MAX_OBJECT_BYTES
    return max(1, min(MAX_OBJECT_BYTES, soft // 2))


def _is_local(path: str) -> bool:
    return "://" not in path


def write_bytes(
    path: str, blob: bytes, policy: Optional[RetryPolicy] = None
) -> None:
    """Write ``blob`` to ``path`` with retries.

    Local paths write tmp+rename so a killed writer can never leave a torn
    file at the final name. Remote (``://``) paths write the key directly —
    the manifest commit protocol is what makes that safe: an uncommitted
    key is invisible to readers.
    """
    if _is_local(path):
        def put():
            tmp = path + ".tmp"
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
    else:
        def put():
            fs, p = fsspec.core.url_to_fs(path)
            with fs.open(p, "wb") as f:
                f.write(blob)
    with_retries(put, policy, op=f"write {path}")


def read_bytes(path: str, policy: Optional[RetryPolicy] = None) -> bytes:
    """Read ``path`` fully, with retries on transient errors. ``missing``
    raises FileNotFoundError-family immediately (callers map it to their
    own semantics — fresh start, or fall back to a previous checkpoint)."""
    def get():
        fs, p = fsspec.core.url_to_fs(path)
        with fs.open(p, "rb") as f:
            return f.read()
    return with_retries(get, policy, op=f"read {path}")


def delete_quiet(path: str) -> None:
    """Best-effort delete (checkpoint rotation): never raises — a
    leftover rotated-out object is garbage, not a correctness problem."""
    try:
        fs, p = fsspec.core.url_to_fs(path)
        fs.rm(p)
    except BaseException:  # noqa: BLE001
        pass


# -- the commit manifest ----------------------------------------------------


@dataclass
class ShardRef:
    """One data object of a sharded checkpoint entry (schema v2)."""

    key: str          # object key, relative to the manifest's directory
    sha256: str
    size: int


@dataclass
class ManifestEntry:
    key: str          # object key, relative to the manifest's directory
    step: int
    epoch: int
    sha256: str       # blob digest; for sharded entries, digest-of-digests
    size: int         # blob size; for sharded entries, total bytes
    #: schema v2: present when the checkpoint was committed as N shard
    #: objects. ``key`` then names shard 0 (so the ``latest`` pointer
    #: stays meaningful) and ``sha256``/``size`` summarise the set.
    shards: Optional[List[ShardRef]] = None

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if self.shards is None:
            # single-blob entries serialise exactly as schema v1 wrote them
            del d["shards"]
        return d

    @classmethod
    def from_dict(cls, raw: dict) -> "ManifestEntry":
        raw = dict(raw)
        shards = raw.pop("shards", None)
        if shards is not None:
            shards = [ShardRef(**s) for s in shards]
        return cls(shards=shards, **raw)

    def shard_refs(self) -> List[ShardRef]:
        """The entry as a uniform shard list — a v1/single-blob entry is
        its own 1-shard checkpoint."""
        if self.shards is not None:
            return list(self.shards)
        return [ShardRef(key=self.key, sha256=self.sha256, size=self.size)]


@dataclass
class Manifest:
    """``latest`` pointer + ordered checkpoint history, committed as one
    small JSON PUT. Entries are oldest → newest; restore walks newest →
    oldest until a digest-verified checkpoint loads."""

    entries: List[ManifestEntry] = field(default_factory=list)

    @property
    def latest(self) -> Optional[ManifestEntry]:
        return self.entries[-1] if self.entries else None

    def to_json(self) -> str:
        return json.dumps(
            {
                "version": MANIFEST_VERSION,
                "latest": self.latest.key if self.latest else None,
                "checkpoints": [e.to_dict() for e in self.entries],
            },
            indent=2,
        )

    @classmethod
    def from_json(cls, text: str) -> "Manifest":
        raw = json.loads(text)
        if raw.get("version") not in SUPPORTED_MANIFEST_VERSIONS:
            raise ValueError(
                f"manifest version {raw.get('version')} not in "
                f"{SUPPORTED_MANIFEST_VERSIONS}"
            )
        return cls(
            entries=[
                ManifestEntry.from_dict(e) for e in raw.get("checkpoints", [])
            ]
        )


def manifest_path(snapshot_path: str) -> str:
    return snapshot_path + MANIFEST_SUFFIX


def object_key(snapshot_path: str, step: int) -> str:
    """Step-suffixed data key next to ``snapshot_path`` (never the bare
    path itself — the bare path is reserved for legacy single-blob
    snapshots, which restore still reads)."""
    return f"{snapshot_path}.step-{step:08d}"


def shard_key(snapshot_path: str, step: int, i: int, n: int) -> str:
    """Data key for shard ``i`` of an ``n``-shard checkpoint (schema v2)."""
    return f"{object_key(snapshot_path, step)}.shard-{i:04d}-of-{n:04d}"


def _sibling(snapshot_path: str, key: str) -> str:
    """Resolve a manifest-relative key next to the snapshot path."""
    head = snapshot_path.rsplit("/", 1)[0] if "/" in snapshot_path else "."
    return f"{head}/{key}"


def load_manifest(
    snapshot_path: str, policy: Optional[RetryPolicy] = None
) -> Optional[Manifest]:
    """None = no manifest (legacy layout or fresh run); transient errors
    retry then raise — they must never be mistaken for 'fresh start'."""
    try:
        text = read_bytes(manifest_path(snapshot_path), policy)
    except BaseException as e:  # noqa: BLE001
        if is_missing_error(e):
            return None
        raise
    return Manifest.from_json(text.decode("utf-8"))


def _commit_entry(
    snapshot_path: str,
    entry: ManifestEntry,
    keep: int,
    policy: Optional[RetryPolicy],
) -> ManifestEntry:
    """Manifest update shared by blob and sharded commits: replace any
    same-step entry, append, rotate, ONE manifest PUT (the commit point),
    then best-effort delete of the rotated-out data objects — only after
    the new manifest no longer references them, so no reader can race
    into a dangling pointer."""
    if keep < 1:
        raise ValueError(f"keep must be >= 1, got {keep}")
    manifest = load_manifest(snapshot_path, policy) or Manifest()
    # re-saving the same step replaces that entry (e.g. a retried run that
    # stopped at the same boundary) instead of growing duplicate keys
    manifest.entries = [e for e in manifest.entries if e.step != entry.step]
    manifest.entries.append(entry)
    dropped = manifest.entries[:-keep]
    manifest.entries = manifest.entries[-keep:]
    write_bytes(
        manifest_path(snapshot_path), manifest.to_json().encode(), policy
    )
    for old in dropped:
        for ref in old.shard_refs():
            delete_quiet(_sibling(snapshot_path, ref.key))
    return entry


def commit_blob(
    snapshot_path: str,
    blob: bytes,
    step: int,
    epoch: int,
    keep: int = 3,
    policy: Optional[RetryPolicy] = None,
) -> ManifestEntry:
    """The durable-write protocol: data object first (uncommitted key),
    manifest second (the commit point), rotation last (best effort).
    Returns the committed entry."""
    if keep < 1:
        raise ValueError(f"keep must be >= 1, got {keep}")
    key_path = object_key(snapshot_path, step)
    write_bytes(key_path, blob, policy)
    entry = ManifestEntry(
        key=key_path.rsplit("/", 1)[-1],
        step=int(step),
        epoch=int(epoch),
        sha256=sha256_hex(blob),
        size=len(blob),
    )
    return _commit_entry(snapshot_path, entry, keep, policy)


def commit_shards(
    snapshot_path: str,
    blobs: List[bytes],
    step: int,
    epoch: int,
    keep: int = 3,
    policy: Optional[RetryPolicy] = None,
) -> ManifestEntry:
    """Commit one checkpoint as N data objects (schema v2).

    Every shard is written (each under its own uncommitted key, each
    write individually retried) BEFORE the single manifest PUT commits
    them as a unit — a crash or exhausted retry mid-way leaves the
    previous checkpoint fully intact, exactly like ``commit_blob``. The
    entry-level digest is a digest-of-digests so a whole entry can be
    compared cheaply without re-reading every shard."""
    if keep < 1:
        raise ValueError(f"keep must be >= 1, got {keep}")
    if not blobs:
        raise ValueError("commit_shards needs at least one shard")
    if len(blobs) == 1:
        return commit_blob(
            snapshot_path, blobs[0], step, epoch, keep=keep, policy=policy
        )
    n = len(blobs)
    refs = []
    for i, blob in enumerate(blobs):
        key_path = shard_key(snapshot_path, step, i, n)
        write_bytes(key_path, blob, policy)
        refs.append(
            ShardRef(
                key=key_path.rsplit("/", 1)[-1],
                sha256=sha256_hex(blob),
                size=len(blob),
            )
        )
    entry = ManifestEntry(
        key=refs[0].key,
        step=int(step),
        epoch=int(epoch),
        sha256=sha256_hex("".join(r.sha256 for r in refs).encode()),
        size=sum(r.size for r in refs),
        shards=refs,
    )
    return _commit_entry(snapshot_path, entry, keep, policy)


def read_verified_shards(
    snapshot_path: str,
    manifest: Manifest,
    policy: Optional[RetryPolicy] = None,
) -> Tuple[List[bytes], ManifestEntry]:
    """Walk the manifest newest → oldest; return the first checkpoint
    whose every shard reads back with a matching SHA-256. A single-blob
    (v1) entry is treated as a 1-shard checkpoint. Any unreadable or
    digest-mismatched (torn, truncated, bit-flipped) shard fails the
    WHOLE entry — restore falls back to the previous good checkpoint
    instead of crashing or, worse, loading garbage into the optimizer."""
    failures = []
    for entry in reversed(manifest.entries):
        blobs = []
        ok = True
        for ref in entry.shard_refs():
            path = _sibling(snapshot_path, ref.key)
            try:
                blob = read_bytes(path, policy)
            except BaseException as e:  # noqa: BLE001
                if classify_io_error(e) == PERMANENT:
                    raise
                failures.append(f"{ref.key}: unreadable ({e!r})")
                ok = False
                break
            digest = sha256_hex(blob)
            if digest != ref.sha256:
                failures.append(
                    f"{ref.key}: digest mismatch "
                    f"(manifest {ref.sha256[:12]}…, got {digest[:12]}…, "
                    f"{len(blob)}/{ref.size} bytes)"
                )
                ok = False
                break
            blobs.append(blob)
        if not ok:
            continue
        if failures:
            log_event(
                "[durability] fell back to checkpoint "
                f"step {entry.step} after: " + "; ".join(failures),
                step=entry.step,
            )
        return blobs, entry
    raise SnapshotIntegrityError(
        f"no checkpoint in {manifest_path(snapshot_path)} passed "
        f"verification: " + "; ".join(failures)
    )


def read_verified(
    snapshot_path: str,
    manifest: Manifest,
    policy: Optional[RetryPolicy] = None,
) -> Tuple[bytes, ManifestEntry]:
    """Single-payload wrapper over ``read_verified_shards`` (shards of a
    v2 entry are concatenated — only meaningful when the writer's shard
    framing says so; the checkpoint layer uses the shard API directly)."""
    blobs, entry = read_verified_shards(snapshot_path, manifest, policy)
    return b"".join(blobs), entry
