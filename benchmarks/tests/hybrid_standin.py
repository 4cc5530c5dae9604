"""A stand-in for ``server.engine`` over the fixture ``hybrid-experts``: what
``check.serve_verdict`` reads of an engine (``prefill_chunk_call``,
``decode_step``, ``params``, ``n_slots``, ``pool`` with ``cache``,
``allocate``, ``free``, ``used_count``, and ``cfg`` with ``dtype``,
``n_experts``, ``moe_top_k``, ``block_size``), and nothing of the program. No
``GPTConfig`` builds a routed hybrid stack yet, so the verdict's path over
one (the layer map, the pool's planes, the table's shapes) is held by this:
the "program" is the fixture's reference run in the stated dtype **under its
own routes** (a near-tied router then goes another way than float32 does,
as a real program's does), and its pool holds a plane a ``rows`` layer.

It keeps no state between positions: a decode step runs the whole sequence
again and writes the new position's rows, which is what a cache gives by
causality. ``fault`` plants what the verdict may not forgive:

  ``"outside-the-margin"``  the router a tenth off the reference's
  ``"a-dropped-route"``     every third token's last expert computes nothing
                            in one layer, the other gates as they were
  ``"gates-not-renormalised"``  ``norm_topk_prob`` false on this side
  ``"an-int8-row"``         rows through int8 with a scale a row
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.tests import ref_hybrid_experts as reference

PREFILL_BUCKET = 64


class Pool:
    def __init__(self, planes, n_slots, block_size, n_head, hd, dtype):
        shape = (planes, n_slots, block_size, n_head, hd)
        self.cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        self._used = set()
        self._n_slots = n_slots

    @property
    def used_count(self) -> int:
        return len(self._used)

    def allocate(self) -> int:
        slot = min(set(range(self._n_slots)) - self._used)
        self._used.add(slot)
        return slot

    def free(self, slot: int) -> None:
        self._used.remove(slot)


class Engine:
    def __init__(self, sizes, seed: int, dtype: str = "bfloat16",
                 n_slots: int = 2, fault: str = None, fault_layer: int = 1):
        self.sizes = dict(sizes)
        self.params = reference.init_weights(jax.random.key(seed), sizes)
        self.n_slots = n_slots
        self.cfg = types.SimpleNamespace(
            dtype=dtype, n_experts=sizes["num_experts"],
            moe_top_k=sizes["num_experts_per_tok"],
            block_size=sizes["max_position_embeddings"])
        self.pool = Pool(len(reference.cached_layers(sizes)), n_slots,
                         self.cfg.block_size, sizes["num_attention_heads"],
                         sizes["head_dim"], jnp.dtype(dtype))
        self._seq = {}
        weights, own = self.params, dict(sizes)
        n_layer, top_k = len(sizes["layer_types"]), self.cfg.moe_top_k
        mask = None
        if fault == "outside-the-margin":
            router = weights["blocks"]["w_router"]
            weights = dict(weights, blocks=dict(
                weights["blocks"], w_router=router + 0.1 * 0.02
                * jax.random.normal(jax.random.key(5), router.shape)))
        elif fault == "a-dropped-route":
            mask = np.ones((n_layer, 1, self.cfg.block_size, top_k),
                           np.float32)
            mask[fault_layer, 0, ::3, -1] = 0.0
        elif fault == "gates-not-renormalised":
            own["norm_topk_prob"] = False
        elif fault not in (None, "an-int8-row"):
            raise ValueError(f"unknown fault {fault!r}")
        self._int8 = fault == "an-int8-row"
        act = None if dtype == "float32" else jnp.dtype(dtype)

        @jax.jit
        def run(seq):
            x, ks, vs, _ = reference.hidden(
                weights, seq[None], own, act_dtype=act, gate_mask=mask)
            return reference.logits(weights, x[0]), ks[:, 0], vs[:, 0]

        self._run = run

    def _rows(self, a):
        if self._int8:      # a scale a row, the finest 8 bits can do
            scale = jnp.abs(a).max((-2, -1), keepdims=True) / 127.0
            a = jnp.round(a / scale) * scale
        return a.astype(self.pool.cache["k"].dtype)

    def _forward(self, slot: int, first: int, last: int) -> int:
        """Run the slot's sequence, write rows [first, last), and return
        the greedy token after position ``last - 1``."""
        seq = np.zeros(self.cfg.block_size, np.int32)
        seq[:len(self._seq[slot])] = self._seq[slot]
        logits, ks, vs = self._run(seq)
        for name, rows in (("k", ks), ("v", vs)):
            self.pool.cache[name] = self.pool.cache[name].at[
                :, slot, first:last].set(self._rows(rows[:, first:last]))
        return int(jnp.argmax(logits[last - 1]))

    def prefill_chunk_call(self, slot, prompt, *_):
        self._seq[slot] = list(prompt)
        return self._forward(slot, 0, len(prompt)), PREFILL_BUCKET

    def decode_step(self, tokens, positions, *_):
        out = np.zeros(self.n_slots, np.int32)
        for slot in range(self.n_slots):
            at = int(positions[slot])
            if at == self.cfg.block_size - 1:       # parked: no request
                continue
            assert at == len(self._seq[slot])
            self._seq[slot].append(int(tokens[slot]))
            out[slot] = self._forward(slot, at, at + 1)
        return out


def server(sizes, seed: int, **options):
    """What ``serve_verdict`` takes for a server: an object with ``engine``."""
    return types.SimpleNamespace(engine=Engine(sizes, seed, **options))
