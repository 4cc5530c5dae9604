"""Speculative decoding: draft/verify with a single batched verify program.

The plain decode round emits exactly one token per compiled step per
slot, so tokens/sec is bounded by per-step latency. Speculation breaks
that bound without changing a single emitted token:

* a **DraftEngine** — a small-config GPT with its own ``SlotKVPool``
  whose slot indices mirror the target's 1:1 — proposes ``k`` tokens
  autoregressively (k batched draft decode steps over every speculating
  lane at once), and
* ONE lifetime-compiled **verify program** on the target model scores
  all ``k+1`` positions in a single batched forward against the slot's
  cache lane. The program is the prefill-at-offset body from
  ``engine.py`` with a fixed ``k+1``-row chunk and logits read at every
  row instead of just the last — offset/slot are traced scalars and the
  row count is static, so the verify family is exactly one executable
  per (k, engine) for the server's lifetime (asserted through
  ``compile_counts()``).

Acceptance is greedy longest-matching-prefix: feeding
``[cur, d_1..d_k]`` at positions ``pos..pos+k`` yields the target's own
next-token choice ``g_j`` at every row; proposals are accepted while
``d_{j+1} == g_j``, and ``g_{n_acc-1}`` rides along as the bonus token,
so every emitted token is the target's own greedy choice — token-exact
parity with the non-speculative path by construction, and at least one
token per verify even when the draft is useless.

**Rollback is free.** Rejected rows on both engines are simply left in
place: the stale-row invariant (a cache row is visible only once a
query position reaches it, and every writer fills a row before its
first reader) means the next verify/decode at ``pos+n_acc`` rewrites
them before anything attends that far. The only write speculation adds
is the draft **backfill** step on full acceptance — one extra batched
draft decode feeding ``d_k`` at ``pos+k`` so the draft row the *next*
propose round's queries attend is real, not stale.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..config import GPTConfig
from ..models import generate as gen
from ..telemetry import programs as program_lib
from .engine import (
    DecodeEngine,
    _forward_slot_lane,
    _pin_kv,
    _select_next_slots,
    bind_static,
    lane_keys,
    request_seeds,
)

__all__ = ["DraftEngine", "SpeculativeDecoder"]


def _verify_impl(
    params, cache, tokens, offset, slot, temp, top_k, top_p,
    seed, token_index,
    *, cfg: GPTConfig, kv_sharding=None, kv_quant=None,
):
    """Score ``tokens`` (rows = k+1, static) at absolute positions
    ``offset..offset+rows-1`` against one slot lane and return the
    target's next-token choice at EVERY row. The sampler is
    ``_select_next_slots`` with the slot's own (greedy) parameters — not
    a raw argmax — so fp tie-breaking is bit-identical to the plain
    decode path and parity holds even on tied logits. The rows' keys are
    split from the slot's ``fold_in(key(seed), token_index)``, derived
    here like every other program's (``engine.lane_keys``). A quantized
    pool dequantizes the lane before the forward and requantizes the
    whole lane on the way back in, same as the prefill/decode bodies."""
    rows = tokens.shape[0]
    x, cache = _forward_slot_lane(
        params, cache, tokens, offset, slot, cfg=cfg, kv_quant=kv_quant)
    logits = gen._head_logits(params, x, cfg)[0]  # (rows, V) fp32
    keys = jax.random.split(
        lane_keys(seed[None], token_index[None])[0], rows)
    nxt = _select_next_slots(
        logits, keys,
        jnp.full((rows,), temp, jnp.float32),
        jnp.full((rows,), top_k, jnp.int32),
        jnp.full((rows,), top_p, jnp.float32),
        jnp.zeros((rows,), bool),
    )
    return nxt, _pin_kv(cache, kv_sharding)


class DraftEngine:
    """The proposal model: a ``DecodeEngine`` over the draft params whose
    slot pool mirrors the target's slot indices 1:1.

    Mirroring works because both pools allocate lowest-free-index and
    this wrapper binds/frees in lockstep with the target — ``bind``
    asserts the indices actually coincide, so a drifted mirror fails
    loudly instead of silently attending the wrong lane. Draft state is
    advisory (it only shapes proposal quality, never emitted tokens), so
    the draft prefill is one un-chunked shot with no prefix store."""

    def __init__(
        self,
        params,
        cfg: GPTConfig,
        target: DecodeEngine,
    ):
        if cfg.vocab_size != target.cfg.vocab_size:
            raise ValueError(
                f"draft vocab {cfg.vocab_size} != target "
                f"{target.cfg.vocab_size}")
        if cfg.block_size < target.cfg.block_size:
            raise ValueError(
                f"draft block_size {cfg.block_size} < target "
                f"{target.cfg.block_size}: draft must cover the window")
        self.engine = DecodeEngine(
            params, cfg, target.n_slots,
            prefill_len=target.prefill_len,
            prefill_buckets=target.buckets,
            mesh=target.mesh,
            tp_axis=target.tp_axis,
            # mirror the target's KV storage dtype (ISSUE 18): smaller
            # draft + target caches compose into more concurrent lanes
            kv_dtype=target.kv_dtype,
        )

    def bind(self, slot: int) -> None:
        got = self.engine.pool.allocate()
        if got != slot:
            self.engine.pool.free(got)
            raise RuntimeError(
                f"draft/target slot mirror broken: target gave {slot}, "
                f"draft gave {got}")

    def release(self, slot: int) -> None:
        self.engine.pool.free(slot)

    def prime(self, slot: int, prompt_ids: Sequence[int], seed) -> None:
        """Prefill the draft lane with the full prompt in one call (the
        ladder always covers prefill_len, so one bucket suffices)."""
        self.engine.prefill_chunk_call(
            slot, list(prompt_ids), 0, 1.0, None, None, False, seed)


class SpeculativeDecoder:
    """propose -> verify -> accept-n for the scheduler's decode round.

    Owns the draft engine and the single verify jit. The scheduler calls
    ``bind``/``release`` in lockstep with the target pool, ``prime`` at
    end-of-prefill, and per round: ``propose`` (k batched draft steps),
    ``verify`` per speculating slot, ``accept`` for the matching-prefix
    length, then ``backfill`` for fully-accepted slots."""

    def __init__(
        self,
        target: DecodeEngine,
        draft_params,
        draft_cfg: GPTConfig,
        k: int,
    ):
        k = int(k)
        if k < 1:
            raise ValueError(f"spec_k must be >= 1, got {k}")
        if k + 1 > target.cfg.block_size:
            raise ValueError(
                f"spec_k {k} leaves no room for the bonus row in a "
                f"{target.cfg.block_size}-position window")
        self.target = target
        self.k = k
        self.rows = k + 1
        self.draft = DraftEngine(draft_params, draft_cfg, target)
        self._parked = target.cfg.block_size - 1
        self._verify_jit = jax.jit(
            bind_static(_verify_impl, cfg=target.cfg,
                        kv_sharding=target.kv_sharding,
                        kv_quant=target.kv_quant),
            donate_argnums=(1,))
        # migrated draft state parked until the owning request re-primes
        # (ISSUE 17): prompt-prefix key -> lane-dict rows, device-side
        # under the draft pool's sharding. Bounded FIFO — advisory state
        # only.
        self.pending_draft: Dict[tuple, dict] = {}
        self.pending_draft_cap = 32
        self.prime_full = 0     # primes that paid a full draft prefill
        self.prime_adopted = 0  # primes served from migrated rows

    # -- slot lifecycle (mirrors the target pool) ----------------------
    def bind(self, slot: int) -> None:
        self.draft.bind(slot)

    def release(self, slot: int) -> None:
        self.draft.release(slot)

    def prime(self, slot: int, prompt_ids: Sequence[int], seed) -> str:
        """Fill the draft lane for a freshly-prefilled request. Normally
        one full un-chunked draft prefill; when migration parked draft
        rows for this prompt (``adopt_draft_rows``), install them
        device-side through the compiled row-copy program and prefill
        only the uncovered tail — a bucket-aligned prompt resumes
        proposing with ZERO draft prefill calls. Returns the path taken
        (``"full"`` | ``"adopted"``) so the scheduler can count it."""
        prompt = [int(t) for t in prompt_ids]
        best = None
        for pkey in self.pending_draft:
            if len(pkey) <= len(prompt) and list(pkey) == \
                    prompt[:len(pkey)]:
                if best is None or len(pkey) > len(best):
                    best = pkey
        if best is not None:
            # one-shot: the rows now live in the slot's cache; keeping
            # the parked copy would pin device memory for a request
            # that already resumed
            entry = self.pending_draft.pop(best)
            rows = self.draft.engine.install_slot_rows(slot, entry)
            if rows < len(prompt):
                self.draft.engine.prefill_chunk_call(
                    slot, prompt[rows:], rows, 1.0, None, None, False,
                    seed)
            self.prime_adopted += 1
            return "adopted"
        self.draft.prime(slot, prompt, seed)
        self.prime_full += 1
        return "full"

    # -- draft-state migration (ISSUE 17) ------------------------------
    def migratable_draft_rows(self, prompt_len: int) -> int:
        """Rows worth shipping from a primed draft lane: the largest
        ladder bucket <= prompt_len. Unlike the target's
        ``migratable_rows`` there is no ``- 1`` — the draft never
        regenerates prompt logits, so a bucket-aligned prompt ships its
        WHOLE primed cache and the peer's re-prime prefills nothing."""
        best = 0
        for b in self.draft.engine.buckets:
            if b <= prompt_len:
                best = b
        return best

    def extract_draft_rows(self, slot: int, rows: int):
        """The extract half of draft migration — same compiled row-copy
        family as the target's, on the draft pool."""
        return self.draft.engine.extract_slot_rows(slot, rows)

    def adopt_draft_rows(self, key: Sequence[int], entry: dict) -> bool:
        """Park a migrated draft row entry (host-array lane dict off the
        transfer channel — quantized lanes carry their scale planes)
        until the re-routed request's ``prime``, re-placed under the
        draft pool's sharding so adopted rows stay head-sharded under tp
        exactly like locally-primed ones. Bounded FIFO; returns False
        when already present."""
        key = tuple(int(t) for t in key)
        if key in self.pending_draft:
            return False
        entry = self.draft.engine._place_entry(entry)
        while len(self.pending_draft) >= self.pending_draft_cap:
            self.pending_draft.pop(next(iter(self.pending_draft)))
        self.pending_draft[key] = entry
        return True

    # -- eligibility ---------------------------------------------------
    def eligible(self, do_sample: bool, position: int) -> bool:
        """A lane speculates only when greedy (sampled lanes keep the
        plain path's per-token key-folding semantics) and when all k+1
        verify rows fit inside the cache window; near-window tails fall
        back to the plain decode step, preserving parity."""
        return (not do_sample) and position + self.rows <= \
            self.target.cfg.block_size

    # -- the round -----------------------------------------------------
    def propose(
        self,
        tokens: np.ndarray,      # (S,) last emitted token per slot
        positions: np.ndarray,   # (S,) its absolute position
        spec_mask: np.ndarray,   # (S,) bool, lanes speculating this round
        seeds: np.ndarray,       # (S,) request seeds and the round's
        token_index: np.ndarray,  # (S,) token indices (unused: greedy draft)
    ) -> np.ndarray:
        """k greedy draft decode steps over every speculating lane at
        once; non-speculating lanes ride along parked (their draft rows
        at block_size-1 go stale, never read). Returns (S, k) proposals;
        rows where ``spec_mask`` is False are meaningless."""
        s = len(tokens)
        toks = np.where(spec_mask, tokens, 0).astype(np.int32)
        pos = np.where(spec_mask, positions, self._parked).astype(np.int32)
        ones_f = np.ones(s, np.float32)
        zeros_i = np.zeros(s, np.int32)
        greedy = np.zeros(s, bool)
        out = np.zeros((s, self.k), np.int32)
        for j in range(self.k):
            nxt = self.draft.engine.decode_step(
                toks, pos, ones_f, zeros_i, ones_f, greedy, seeds,
                token_index, spec_mask)
            out[:, j] = nxt
            toks = np.where(spec_mask, nxt, 0).astype(np.int32)
            pos = np.where(spec_mask, pos + 1, self._parked).astype(np.int32)
        return out

    def verify(
        self,
        slot: int,
        row_tokens: Sequence[int],   # [cur, d_1..d_k] — exactly k+1 rows
        offset: int,
        temperature: float,
        top_k: Optional[int],
        top_p: Optional[float],
        seed,
        token_index: int,
    ) -> np.ndarray:
        """One batched target forward over the k+1 rows at
        ``offset..offset+k``; returns the target's greedy choice at every
        row (the cache lane keeps all k+1 written rows — rejected ones
        become stale)."""
        if len(row_tokens) != self.rows:
            raise ValueError(
                f"verify expects {self.rows} rows, got {len(row_tokens)}")
        if offset + self.rows > self.target.cfg.block_size:
            raise ValueError(
                f"verify rows at offset {offset} overrun the "
                f"{self.target.cfg.block_size} cache window (the scheduler "
                "gates eligibility on window headroom)")
        nxt, cache = self._verify_jit(
            self.target.program_params, self.target.pool.cache,
            np.asarray(row_tokens, np.int32),
            np.int32(offset), np.int32(slot),
            np.float32(temperature),
            np.int32(0 if top_k is None else top_k),
            np.float32(1.0 if top_p is None else top_p),
            request_seeds(seed)[()], np.int32(token_index),
        )
        self.target.pool.cache = cache
        return np.asarray(jax.device_get(nxt))

    def accept_len(self, proposals: np.ndarray, greedy: np.ndarray) -> int:
        """Longest matching prefix + 1: tokens emitted this round are
        ``greedy[:n_acc]`` — always >= 1 (the bonus token) and all the
        target's own choices."""
        n_acc = 1
        while n_acc <= self.k and int(proposals[n_acc - 1]) == \
                int(greedy[n_acc - 1]):
            n_acc += 1
        return n_acc

    def backfill(
        self,
        tokens: np.ndarray,      # (S,) d_k per fully-accepted slot
        positions: np.ndarray,   # (S,) pos + k for those slots
        fill_mask: np.ndarray,   # (S,) bool, fully-accepted lanes
        seeds: np.ndarray,
        token_index: np.ndarray,
    ) -> None:
        """On full acceptance the draft cache's row ``pos+k`` was never
        written (the k-th draft step read it as a query input, not a
        write target), but the next propose round's queries will attend
        it — run one extra batched draft step feeding ``d_k`` there so
        the row is real. Skipped entirely when no lane fully accepted."""
        if not fill_mask.any():
            return
        s = len(tokens)
        toks = np.where(fill_mask, tokens, 0).astype(np.int32)
        pos = np.where(fill_mask, positions, self._parked).astype(np.int32)
        self.draft.engine.decode_step(
            toks, pos, np.ones(s, np.float32), np.zeros(s, np.int32),
            np.ones(s, np.float32), np.zeros(s, bool), seeds, token_index,
            fill_mask)

    # -- warmup / accounting -------------------------------------------
    def warmup(self) -> None:
        """Trace the draft family (ladder + decode) and the verify
        program. Scribbles slot 0 rows on both engines — harmless under
        the stale-row invariant, but both pools must be empty."""
        assert self.target.pool.used_count == 0, \
            "spec warmup requires an empty target pool"
        self.draft.engine.warmup()
        self.verify(0, [0] * self.rows, 0, 1.0, None, None, 0, 0)

    def compile_counts(self) -> Dict[str, int]:
        """Speculation's program families: verify stays at 1 for the
        server's lifetime (fixed row count, traced offset/slot); draft
        prefill <= len(ladder), draft decode 1."""
        draft = self.draft.engine.compile_counts()
        return {
            "verify": self._verify_jit._cache_size(),
            "draft_prefill": draft["prefill"],
            "draft_decode": draft["decode"],
        }

    def programs(self, family_prefix: str = ""):
        """The verify program plus the draft engine's programs under the
        ``draft_`` prefix, as ``DecodeEngine.programs`` yields them —
        matching the ``compile_counts()`` family names.
        ``family_prefix`` prefixes every family (graftaudit audits a
        quantized decoder beside the fp32 one as ``q8_*``)."""
        yield (f"{family_prefix}verify", f"k{self.k}", self._verify_jit,
               (program_lib.abstract(self.target.program_params),
                program_lib.abstract(self.target.pool.cache),
                np.zeros(self.rows, np.int32),
                np.int32(0), np.int32(0),
                np.float32(1.0), np.int32(0), np.float32(1.0),
                np.uint32(0), np.int32(0)), {})
        yield from self.draft.engine.programs(
            family_prefix=f"{family_prefix}draft_")

    def audit_contracts(self, family_prefix: str = "") -> Dict[str, dict]:
        """Audit contracts (ISSUE 15) for the families ``programs``
        yields: verify is a model-forwarding
        family on the target engine — same collectives/donation/sharding
        contract as the target's prefill — and the draft families are
        the draft engine's own contracts under the ``draft_`` prefix."""
        prefill = f"{family_prefix}prefill"
        verify = dict(
            self.target.audit_contracts(family_prefix=family_prefix)[prefill])
        return {
            f"{family_prefix}verify": verify,
            **self.draft.engine.audit_contracts(
                family_prefix=f"{family_prefix}draft_"),
        }
