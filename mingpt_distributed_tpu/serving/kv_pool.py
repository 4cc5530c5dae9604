"""Slot-based KV-cache pool + shared-prefix KV store.

One fixed pair of ``(planes, n_slots, block_size, heads, size)`` buffers —
``models/generate.init_cache`` with the batch axis reinterpreted as a
*slot* axis; a plane a layer, or a pass and layer of a looped stack
(``GPTConfig.cache_planes``). ``heads`` and ``size`` are each leaf's own
(``generate.cache_leaf_shapes``): per-head rows keep ``kv_heads`` keys and
values of ``head_dim``, as a rule a head an axis entry of its own; where a
head fills no lane tile (``head_dim`` under 128) and a position's heads
together fill whole ones (GPT-2 124M's 12 x 64 = 768), they lie side by
side, ``(1, kv_heads x head_dim)``: the same numbers in the same order,
and a lane's row write touches ``width / 128`` tiles a plane where the
per-head buffer, which the device keeps with positions minor, makes it
``kv_heads x head_dim / 16`` (``row_width``, ``row_tiles``); a latent
(MLA) model keeps one rotated rope key
(``"k"``) and one normed latent (``"v"``) a token, which differ in size.
Everything here and in the engine's programs works leaf by leaf and asks
no leaf for another's shape. A hybrid stack (``GPTConfig.mixer_types``)
keeps two kinds of thing a slot: rows of its sparse layers alone (``"k"``,
``"v"`` and their pooled keys, ``generate.POOLED``) and a float32 state of
its linear layers (``generate.STATE``: ``(layers, n_slots, heads, size,
size)``, no position axis). No mask hides a stale state as position hides a
stale row, so a slot's state is started from zero by the program that
prefills a sequence's first chunk (``generate._cached_hybrid_block``):
allocate and free stay host-side and clear nothing. A model that counts on
the device (``generate.COUNTERS``: routed rows, the sparse layers' attended
rows, a looped stack's passes) carries the counter in the same donated tree;
it is no buffer of rows and the row programs pass it through.
Each slot holds one in-flight request's
cache; a request is admitted by prefilling its prompt into a free slot
and retired by returning the slot to the free list. Stale K/V from a
previous tenant never leaks into attention because masking is positional
and every writer fills a row with real data before the first query that
could see it (the stale-row invariant, serving/engine.py). The buffers
themselves never change shape or owner-visible identity, which is what
lets the decode program stay compiled once for the server's lifetime.
The decode step reads the buffers as they lie: a layer's slice is never
selected over, copied or converted on its way into the attention (each
lane's new row is attended beside the cached ones and written after the
last layer), and only the slots that hold a request are read, each in
blocks as far as its own position, by one of two walks
(``attention.step_walk`` chooses by what it can see). Rows that hold a
position's heads side by side, whole on one chip where Mosaic compiles,
go through a Pallas kernel in which a (slot, block) pair costs its bytes:
small blocks by the row's bytes alone (``attention.kernel_block``), every
slot read alone. Everything else (a latent pool, a per-head leaf, a
quantized or sharded pool, any pool on the CPU) keeps the XLA walk, where
a step is dear: large blocks by ``attention.STEP_COST_BYTES``
(``step_block``), a block that enough slots need read for all slots at
once (``step_plan``). Either way a pool one pass reads in a few steps'
time is read whole (``step_block``); ``engine.decode_rows_read`` counts by
the rule the program runs, and ``ServingMetrics`` holds
``decode_rows_read`` against ``decode_rows_reserved`` and how many layers
the kernel walks (``decode_kernel_walk_layers``).

Allocation is deterministic (lowest free index first) so a given arrival
order always produces the same slot placement — the scheduler tests rely
on replayability.

Tensor-parallel serving (ISSUE 14): the pool optionally carries a
``NamedSharding`` that splits the axis holding the KV heads (the last, of
a row of heads side by side: ``engine.kv_pool_spec``) over the mesh's tp
axis,
so each device holds ``total / tp`` cache bytes. The sharding is decided
once at construction (it is part of the engine's program identity, see
serving/engine.py) and never changes — the buffers keep the same global
shape, owner-visible identity and host-side free-list semantics whether
they live on one chip or many. Ownership (which slot belongs to which
request) stays a host concept; placement (which chip holds which heads)
is the sharding's concern — the two never interact.

``PrefixKVStore`` is the byte-bounded LRU behind shared-prefix reuse
(the system-prompt case): entries are device-resident ``(L, 1, P, heads,
size)`` K/V row blocks (the pool's own row shape) keyed by the exact
token tuple they encode, with P
quantized to the engine's bucket ladder so the copy programs stay a
bounded compile family. A request whose prompt extends a stored entry
copies its rows instead of recomputing them and prefills only the tail.
"""

from __future__ import annotations

import math
from collections import OrderedDict
from typing import List, Optional, Tuple

import jax

from mingpt_distributed_tpu.config import GPTConfig
from mingpt_distributed_tpu.models.generate import (
    COUNTERS, LOOP_PASSES, MOE_ROWS, SPARSE_ROWS, STATE, Cache, init_cache,
    init_loop_passes, init_moe_rows, init_sparse_rows, row_tiles)
from mingpt_distributed_tpu.serving import quant as quant_lib


class SlotKVPool:
    """Fixed-slot KV cache + host-side free-list.

    The device arrays live in ``.cache`` and are *replaced* (never resized)
    by the engine after each compiled call — jit donation makes the update
    in place at the buffer level while this object keeps a stable handle.
    """

    def __init__(self, cfg: GPTConfig, n_slots: int, dtype=None,
                 sharding=None, quant=None):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.cfg = cfg
        self.n_slots = n_slots
        self.quant = quant
        if quant is None:
            cache: Cache = init_cache(cfg, n_slots, dtype)
        else:
            # quantized payload buffers + fp32 scale planes (ISSUE 18);
            # the scale leaves are rank-5 with head_dim -> 1, so the
            # head-sharding spec below applies to them unchanged
            cache = quant_lib.init_quant_cache(cfg, n_slots, quant)
        if sharding is not None:
            cache = jax.device_put(
                cache, {name: sharding for name in cache})
            # adopt the runtime's normalized sharding (trailing-None
            # PartitionSpec entries stripped): compiled-program outputs
            # carry the normalized form, and the engine keys executables
            # on sharding equality — an unnormalized spec here would make
            # the first serving call on a warmed bucket look novel
            sharding = cache["k"].sharding
        for name, counter in ((MOE_ROWS, init_moe_rows(cfg)),
                              (SPARSE_ROWS, init_sparse_rows(cfg)),
                              (LOOP_PASSES, init_loop_passes(cfg))):
            if counter is not None:
                if sharding is not None:
                    # whole on every device of the mesh, and committed as
                    # the programs hand it back: an uncommitted leaf would
                    # be another jit entry on the first call after it
                    counter = jax.device_put(counter, jax.sharding.NamedSharding(
                        sharding.mesh, jax.sharding.PartitionSpec()))
                cache[name] = counter
        self.sharding = sharding
        self.cache = cache
        self._free: List[int] = list(range(n_slots))  # kept sorted

    @property
    def shard_count(self) -> int:
        """How many devices one cache buffer is physically split over
        (1 = single-device or replicated — e.g. a kv_heads count the tp
        extent doesn't divide, which shard_by_rule downgrades)."""
        if self.sharding is None:
            return 1
        shape = tuple(self.cache["k"].shape)
        shard = self.sharding.shard_shape(shape)
        return math.prod(shape) // math.prod(shard)

    @property
    def row_width(self) -> int:
        """The last axis of the ``"k"`` leaf: a head's size where each head
        has an axis entry of its own, all of a position's heads where they
        lie side by side (``generate.cache_leaf_shapes``); 0 where no leaf
        holds rows."""
        return self.cache["k"].shape[-1] if "k" in self.cache else 0

    @property
    def row_tiles(self) -> int:
        """Lane tiles one lane's row write touches in the ``"k"`` leaf, all
        planes (``generate.row_tiles``)."""
        return row_tiles(self.cfg) if "k" in self.cache else 0

    def audit_facts(self) -> dict:
        """Static facts graftaudit checks pool-touching programs against
        (plain dict so serving never imports the analysis layer):
        ``cache_leaf_shapes`` is each row buffer's shape, by name (a
        hybrid stack's pooled keys are rows of a coarser grid);
        ``state_leaf_shapes`` the leaves that hold a state a slot and have
        no position axis (a hybrid stack's linear layers), by name;
        ``cache_leaf_elems`` the element count of the smallest row buffer —
        any collective whose result is at least that large is moving the
        pool itself, not a per-token activation; ``cache_sharding`` is the
        runtime-normalized NamedSharding every compiled program must
        return the cache under (None on a single device)."""
        shapes = {n: tuple(a.shape) for n, a in self.cache.items()
                  if n not in COUNTERS and n != STATE}
        return {
            "cache_leaf_shapes": shapes,
            "state_leaf_shapes": {n: tuple(a.shape)
                                  for n, a in self.cache.items() if n == STATE},
            "cache_leaf_elems": min(map(math.prod, shapes.values())),
            "cache_sharding": self.sharding,
            "shard_count": self.shard_count,
            "row_width": self.row_width,
            "row_tiles": self.row_tiles,
        }

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.n_slots - len(self._free)

    def allocate(self) -> Optional[int]:
        """Claim the lowest free slot index, or None when exhausted."""
        if not self._free:
            return None
        return self._free.pop(0)

    def free(self, slot: int) -> None:
        """Return a slot to the pool (idempotence is a bug: double-free
        means two requests would share a cache slot, so it raises)."""
        if not (0 <= slot < self.n_slots):
            raise ValueError(f"slot {slot} outside [0, {self.n_slots})")
        if slot in self._free:
            raise ValueError(f"slot {slot} is already free (double free)")
        self._free.append(slot)
        self._free.sort()


class PrefixKVStore:
    """Bounded LRU of shared-prefix KV entries.

    Keys are exact token tuples (the prefix the rows encode — hashing the
    tokens themselves, so a hit can never alias two different prefixes);
    values are device-array lane dicts (``{"k", "v"}``, plus
    ``{"k_scale", "v_scale"}`` planes when the pool is quantized) of
    shape (L, 1, P, heads, size), each leaf's own, with P = len(key). ``capacity_bytes`` bounds
    the sum of entry sizes across every leaf — a quantized store fits
    ~4x the prefixes in the same budget, which is the ISSUE 18 point;
    inserting past it evicts least-recently-used entries first. An entry
    larger than the whole budget is refused rather than thrashing the
    store empty.
    """

    def __init__(self, capacity_bytes: int):
        if capacity_bytes < 1:
            raise ValueError(
                f"capacity_bytes must be >= 1, got {capacity_bytes}")
        self.capacity_bytes = capacity_bytes
        self.used_bytes = 0
        self._entries: "OrderedDict[Tuple[int, ...], tuple]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._entries)

    def contains(self, key: Tuple[int, ...]) -> bool:
        return key in self._entries

    def entries(self):
        """(key, lane-dict) pairs in LRU order — read-only introspection
        for accounting and the sharded-serving selftest (which asserts
        stored entries keep the pool's head-sharding instead of
        gathering)."""
        return list(self._entries.items())

    @staticmethod
    def _nbytes(kv) -> int:
        return sum(int(a.nbytes) for a in kv.values())

    def lookup(self, tokens: Tuple[int, ...]):
        """Longest stored entry that is a *proper* prefix of ``tokens``
        (P < len(tokens): the tail must keep >= 1 token to prefill, since
        the first sampled token needs the last prompt position's logits).
        Returns (rows, lane-dict) or None; a hit refreshes LRU order."""
        best_key = None
        for key in self._entries:
            p = len(key)
            if p < len(tokens) and tokens[:p] == key:
                if best_key is None or p > len(best_key):
                    best_key = key
        if best_key is None:
            return None
        self._entries.move_to_end(best_key)
        return len(best_key), self._entries[best_key]

    def insert(self, key: Tuple[int, ...], kv) -> bool:
        """Store rows for ``key``; evict LRU entries until it fits.
        Returns False when the entry alone exceeds the byte budget or the
        key is already present (refreshed, not replaced — the rows are
        deterministic functions of the tokens, so old is as good as new).
        """
        if key in self._entries:
            self._entries.move_to_end(key)
            return False
        need = self._nbytes(kv)
        if need > self.capacity_bytes:
            return False
        while self.used_bytes + need > self.capacity_bytes:
            _, old = self._entries.popitem(last=False)
            self.used_bytes -= self._nbytes(old)
        self._entries[key] = kv
        self.used_bytes += need
        return True
