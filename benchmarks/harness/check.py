"""``correct``: the program against the plain reference, on the chip, at the
cell's published widths, outside the measured window. ``reference`` is the
module the configuration names (``spec.load_reference``): it brings the
equations (``hidden``, ``logits``, ``loss``) and ``weights_from_program``,
which reads the program's parameters under the reference's own names, so
nothing here knows an architecture's parameter names.

What a reference owes the check (``sizes`` is the configuration file as
loaded, so a reference reads its published keys under their own names):

  ``hidden(weights, tokens (B, T) int32, sizes)`` -> ``(x, ks, vs)``: ``x``
      (B, T, d) the hidden states after the final norm, float32; ``ks`` and
      ``vs`` (L, B, T, KV, hd) every layer's keys and values *as the
      architecture caches them*: after any norm of the projected keys and
      after any rotation by position (a rotary model's keys are cached
      rotated, so the reference returns them rotated), with the KV heads
      the architecture keeps (not repeated up to the query heads). The
      serving check holds the rows the engine's programs left in a slot to
      exactly these; a cache that is not ``{"k", "v"}`` of this layout (a
      latent, a state, a window ring) needs a ``benchmark`` PR here first.
      Where the block routes each token to k of E experts (the program's
      ``GPTConfig.n_experts`` is set), ``hidden`` owes two things more, and
      a reference that lacks them is an error there, not a dense check: it
      takes ``experts=`` (L, B, T, k) int32, the experts every token takes
      in every layer (None: the router's own k best), and returns a fourth
      array, the router's float32 logits (L, B, T, E). Nothing else: no
      tolerance, no margin, no law. ``serve_verdict`` then has the reference
      route as the program routed (``follow_routes``) before it holds every
      layer to the dense law. A dense reference owes neither.
  ``logits(weights, x (..., d))`` -> float32 (..., V), any final softcap in.
  ``loss(weights, tokens, targets, sizes)`` -> mean cross-entropy over the
      targets that are not -1 (training cells).
  ``weights_from_program(params)`` -> the reference's weights: renames of
      the program's arrays, nothing copied or cast.

Every tolerance stands beside its check with the reason for it. They are
set from what bf16 arithmetic must give and what the chip measured
(PERF.md, Findings), tight enough that a lower precision than the
configuration states (an int8 or fp8 cache or matmul) would fail.
"""

from __future__ import annotations

import functools
import inspect
import itertools
import math
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

# -- training ---------------------------------------------------------------
#: The program's forward (bf16 activations over float32 parameters, the flash
#: kernel, the chunked head) against the float32 reference on the same rows
#: of the first batch with dropout off. bf16 keeps 8 bits: per-token
#: cross-entropies near ln(50257) = 10.8 differ by a few 1e-2 and average
#: out over thousands of tokens. Measured on the chip: -0.0002 to -0.0004 on
#: GPT-2 124M (my chip runs, PR 22); the tolerance is ten times that.
TRAIN_EVAL_LOSS_TOL = 3e-3
#: The first optimizer step's own loss has embedding and residual dropout on
#: and covers all rows, the reference none and a few rows: at seeded init
#: both are ln(vocabulary) to within the spread between rows. A head that is
#: not in play, or a forward that is broken, lands far outside.
TRAIN_FIRST_LOSS_TOL = 0.1

# -- serving ----------------------------------------------------------------
#: Keys and values the engine's own prefill and decode programs left in a
#: slot, against the reference's, as relative Frobenius error per tensor over
#: all layers and positions. The cache is bf16 over bf16 matmuls and the
#: rounding of the residual stream adds up with depth: measured 0.84-0.89% at
#: 12 layers and 1.32-1.34% at 48, the same to a few percent for every prompt
#: and seed (my chip runs, PR 22). The tolerance is a quarter above a power
#: law through both; an int8 or fp8 cache adds about 1% of its own and fails.
#: The law is a dense block's, and it is the yardstick's: no reference sets
#: a tolerance for itself. A block that makes a discrete choice (routed
#: experts) is held to the same law once the reference has made the
#: program's choices (``follow_routes``, below).
SERVE_KV_REL_TOL_12_LAYERS = 1.1e-2
SERVE_KV_DEPTH_POWER = 0.3
#: Routed experts: how far from the reference's own choice the program's
#: choice of experts may lie and still be followed. The router's logit of an
#: expert is z = h . w, h the normed residual stream. Where the program's h
#: is off by a relative error eps in no particular direction, the difference
#: of two experts' logits moves by eps * sqrt(2) * s (one standard
#: deviation), s being the scale of one logit, |h| |w| / sqrt(d): read here
#: as the spread of a layer's logits about their mean over the experts. The
#: law above lets a layer's input be off by ``kv_rel_tol``, and behind an
#: expert layer the chip's rows are off by just that (0.72-0.90% a layer
#: against 0.79%, every position alike: PERF.md, PR 26). A token is followed
#: to another set of k experts only where every pair of logits the swap
#: turns over lies within SERVE_ROUTE_MARGIN * kv_rel_tol * s:
#: SERVE_ROUTE_MARGIN / sqrt(2) = 5.7 standard deviations of the rounding the
#: law allows. Set from that arithmetic and from the chip: of 549 flips
#: followed on the probe the widest lay 4.08 of these units apart, and a
#: margin of 16 found the same 549 (PERF.md, PR 26). The margin fences which
#: routes may be tried and forgives nothing: a program that routes outside
#: it, drops a route, weighs a gate otherwise or caches in a lower precision
#: matches no admissible set and fails by the dense law.
SERVE_ROUTE_MARGIN = 8.0
#: The sets of experts a token may try in one layer beside the reference's
#: own, nearest first; each is one more forward of the reference for all
#: tokens at once (40 ms on the chip at the probe's size). Sets beyond these
#: are counted in the notes: a token has more only with three logits on one
#: side of the boundary inside the margin and two on the other.
SERVE_ROUTE_TRIES = 8
#: Each token the engine emitted greedily must be the reference's best or
#: within this of it, in the reference's own logits. With seeded weights the
#: best two logits are often closer than bf16 resolves, so tokens are never
#: compared for equality; a wrong position, mask or cache row puts the
#: engine's token several units down. Measured: 0 to 0.003.
SERVE_LOGIT_GAP_TOL = 0.05


def train_reference_loss(reference, sizes: Dict, params, x: np.ndarray,
                         y: np.ndarray) -> float:
    """The reference's loss on host rows ``x``/``y`` under the parameters as
    they lie on the mesh (XLA partitions the plain program itself)."""
    import jax

    fn = jax.jit(functools.partial(reference.loss, sizes=sizes))
    return float(fn(reference.weights_from_program(params), x, y))


def train_verdict(eval_loss: float, ref_loss: float,
                  losses: Sequence[float]) -> Dict:
    finite = all(math.isfinite(v) for v in losses)
    out = {
        "eval_loss": eval_loss, "reference_loss": ref_loss,
        "eval_minus_reference": eval_loss - ref_loss,
        "first_loss": losses[0], "last_loss": losses[-1],
        "losses_finite": finite, "steps": len(losses),
    }
    out["ok"] = bool(
        finite
        and abs(eval_loss - ref_loss) <= TRAIN_EVAL_LOSS_TOL
        and abs(losses[0] - ref_loss) <= TRAIN_FIRST_LOSS_TOL
        and losses[-1] < losses[0])
    return out


def route_alternatives(logits: np.ndarray, top_k: int,
                       margin: float) -> List[Tuple[float, np.ndarray]]:
    """The other sets of ``top_k`` experts one token may have been sent to:
    ``[(gap, experts), ...]``, smallest gap first. A set is admissible when
    every expert it drops from the router's own k best lies within
    ``margin`` (in logits) of every expert it takes in their place; its gap
    is the largest such difference, the inversion the program must have
    made."""
    order = np.argsort(-logits, kind="stable")
    own, rest = order[:top_k], order[top_k:]
    if not len(rest):
        return []
    drop = [e for e in own[::-1] if logits[e] - logits[rest[0]] <= margin]
    take = [e for e in rest if logits[own[-1]] - logits[e] <= margin]
    found = []
    for n in range(1, min(len(drop), len(take)) + 1):
        for out in itertools.combinations(drop, n):
            for into in itertools.combinations(take, n):
                gap = float(max(logits[list(out)]) - min(logits[list(into)]))
                if gap <= margin:
                    kept = [e for e in own if e not in out]
                    found.append((gap, np.asarray(kept + list(into),
                                                  own.dtype)))
    return sorted(found, key=lambda f: f[0])


def follow_routes(forward: Callable, row_distance: Callable, n_layer: int,
                  n_positions: int, top_k: int, n_prompt: int,
                  emitted: Sequence[int], kv_tol: float):
    """The table of experts (L, T, k) under which the reference goes the way
    the program went, and the notes of how it was found.

    ``forward(table)`` is one forward of the reference over the checked
    sequence: ``(logits of the rows that emitted a token, ks, vs, router
    logits (L, T, E))``. ``row_distance(ks, vs, layer)`` is every position's
    squared relative distance between the reference's keys and values of
    that layer and the rows the program cached.

    Layer by layer, the layers below fixed. Row t of layer l+1's keys and
    values is made of x_{l+1}(t) alone, and with everything before layer l
    fixed that depends on token t's own experts at layer l alone: one
    expert swapped moves the row by tens of percent, bf16 rounding by about
    one. So each token whose logits leave a choice inside the margin takes,
    of its admissible sets, the one whose layer-l+1 rows lie nearest the
    program's; all such tokens try their n-th alternative in one forward.
    Rows written by prefill and by decode are treated alike. The last
    layer's experts feed nothing cached: there the rows that emitted a
    token take the set under which that token's logit gap is least."""
    n_rows = n_prompt + len(emitted) - 1          # rows the program cached
    emitting = np.arange(n_prompt - 1, n_rows)
    table = np.broadcast_to(np.arange(top_k, dtype=np.int32),
                            (n_layer, n_positions, top_k)).copy()
    notes: Dict[str, list] = {}
    for layer in range(n_layer):
        # the table's rows from this layer on are not yet the reference's
        # choice, and this layer's logits do not depend on them
        router = np.asarray(forward(table)[3][layer])
        live = router[:n_rows]
        spread = float(np.sqrt(np.mean(
            (live - live.mean(-1, keepdims=True)) ** 2)))
        margin = SERVE_ROUTE_MARGIN * kv_tol * spread
        table[layer] = np.argsort(-router, axis=-1, kind="stable")[:, :top_k]
        last = layer == n_layer - 1
        # position -> the sets it may take, [(gap, experts), ...], its own
        # first: only positions with a choice
        choices, cut = {}, 0
        for t in (emitting if last else range(n_rows)):
            others = route_alternatives(router[t], top_k, margin)
            if others:
                choices[int(t)] = [(0.0, table[layer, t].copy()),
                                   *others[:SERVE_ROUTE_TRIES]]
                cut += max(0, len(others) - SERVE_ROUTE_TRIES)
        scores = np.full((max(map(len, choices.values()), default=0),
                          n_positions), np.inf)
        for c in range(len(scores)):
            trying = [t for t, sets in choices.items() if c < len(sets)]
            trial = table.copy()
            for t in trying:
                trial[layer, t] = choices[t][c][1]
            out = forward(trial)
            if last:
                rows = np.asarray(out[0])
                score = np.full(n_positions, np.inf)
                score[emitting] = rows.max(-1) - rows[
                    np.arange(len(emitted)), list(emitted)]
            else:
                score = np.asarray(row_distance(out[1], out[2], layer + 1))
            scores[c, trying] = score[trying]
        best = {t: int(np.argmin(scores[:, t])) for t in choices}
        for t, c in best.items():
            table[layer, t] = choices[t][c][1]
        gaps = [choices[t][c][0] for t, c in best.items() if c]
        for key, value in (("route_margin_layers", margin),
                           ("route_banded_layers", len(choices)),
                           ("route_followed_layers", len(gaps)),
                           ("route_gap_max_layers", max(gaps, default=0.0)),
                           ("route_cut_layers", cut)):
            notes.setdefault(key, []).append(value)
    return table, notes


def serve_verdict(reference, sizes: Dict, server, prompts: List[np.ndarray],
                  decode_steps: int) -> Dict:
    """Prefill each prompt into slot 0 and decode ``decode_steps`` tokens with
    the engine's own compiled programs, then hold the slot's cache rows and
    the emitted tokens to the reference's full forward over the same
    sequence; where the reference brings the routed contract, to its
    forward under the program's routes (``follow_routes``), whose notes then
    stand in each case. The pool must be empty: the check takes a slot as a
    request would and gives it back."""
    import jax
    import jax.numpy as jnp

    eng = server.engine
    cfg = eng.cfg
    if eng.pool.used_count:
        raise RuntimeError("the correctness check needs an empty pool")
    routed = "experts" in inspect.signature(reference.hidden).parameters
    if cfg.n_experts and not routed:
        raise RuntimeError(
            "the program routes experts (n_experts is set) and the "
            "reference's hidden() takes no experts= table: it owes the "
            "routed contract (harness/check.py)")
    kv_tol = SERVE_KV_REL_TOL_12_LAYERS * (
        cfg.n_layer / 12.0) ** SERVE_KV_DEPTH_POWER
    weights = reference.weights_from_program(eng.params)
    n_slots, parked = eng.n_slots, cfg.block_size - 1
    # greedy lanes: the seed is never used, every request's is 0
    seeds = np.zeros(n_slots, np.uint32)
    token_index = np.zeros(n_slots, np.int32)

    @jax.jit
    def ref_forward(w, seq, n_prompt, experts=None):
        """Dense: (logits, ks, vs). Routed: and the router's logits."""
        routes = {} if experts is None else {"experts": experts[:, None]}
        x, ks, vs, *router = reference.hidden(w, seq[None], sizes, **routes)
        rows = jax.lax.dynamic_slice_in_dim(
            x[0], n_prompt - 1, decode_steps + 1, axis=0)
        return (reference.logits(w, rows),
                *(a[:, 0] for a in (ks, vs, *router)))

    @jax.jit
    def row_distance(cache, ref_k, ref_v, slot, layer):
        total = 0.0
        for name, ref in (("k", ref_k), ("v", ref_v)):
            ref = jax.lax.dynamic_index_in_dim(ref, layer, keepdims=False)
            got = jax.lax.dynamic_index_in_dim(
                jax.lax.dynamic_index_in_dim(cache[name], layer,
                                             keepdims=False),
                slot, keepdims=False)[:ref.shape[0]]
            total += jnp.sum((got.astype(jnp.float32) - ref) ** 2, (1, 2)) \
                / jnp.sum(ref ** 2, (1, 2))
        return total

    @jax.jit
    def kv_errors(cache, ref_k, ref_v, slot, n_rows):
        t = ref_k.shape[1]
        live = (jnp.arange(t) < n_rows)[None, :, None, None]
        out = {}
        for name, ref in (("k", ref_k), ("v", ref_v)):
            got = jax.lax.dynamic_index_in_dim(
                cache[name], slot, axis=1, keepdims=False)[:, :t]
            diff = jnp.where(live, got.astype(jnp.float32) - ref, 0.0)
            ref = jnp.where(live, ref, 0.0)
            out[name + "_rel"] = jnp.sqrt(jnp.sum(diff ** 2)
                                          / jnp.sum(ref ** 2))
            out[name + "_max_abs"] = jnp.max(jnp.abs(diff))
            # the same error a layer: a verdict that fails says where
            out[name + "_rel_layers"] = jnp.sqrt(
                jnp.sum(diff ** 2, axis=(1, 2, 3))
                / jnp.sum(ref ** 2, axis=(1, 2, 3)))
            # and a layer's median over the live positions of a position's
            # own error: the bulk of the tokens, whatever a few of them do
            by_position = jnp.sqrt(jnp.sum(diff ** 2, axis=(2, 3))
                                   / jnp.sum(ref ** 2, axis=(2, 3)))
            out[name + "_rel_p50_layers"] = jnp.nanmedian(
                jnp.where(live[:, :, 0, 0], by_position, jnp.nan), axis=1)
        return out

    cases = []
    for prompt in prompts:
        slot = eng.pool.allocate()
        n = len(prompt)
        tok, bucket = eng.prefill_chunk_call(
            slot, prompt.tolist(), 0, 1.0, None, None, False, 0)
        emitted = [tok]
        for i in range(decode_steps):
            tokens = np.zeros(n_slots, np.int32)
            positions = np.full(n_slots, parked, np.int32)
            tokens[slot], positions[slot] = emitted[-1], n + i
            nxt = eng.decode_step(
                tokens, positions, np.ones(n_slots, np.float32),
                np.zeros(n_slots, np.int32), np.ones(n_slots, np.float32),
                np.zeros(n_slots, bool), seeds, token_index)
            emitted.append(int(nxt[slot]))
        # the reference runs the prompt and the tokens that were fed back,
        # padded to one length per prefill bucket (causal: the padding after
        # the last real position changes nothing before it)
        t_pad = min(bucket + 128, cfg.block_size)
        if n + decode_steps > t_pad:
            raise RuntimeError(f"prompt of {n} leaves no room to decode")
        seq = np.zeros(t_pad, np.int32)
        seq[:n] = prompt
        seq[n:n + decode_steps] = emitted[:-1]
        routes = {}
        if routed:
            table, routes = follow_routes(
                lambda experts: ref_forward(weights, seq, np.int32(n), experts),
                lambda ks, vs, layer: row_distance(
                    eng.pool.cache, ks, vs, np.int32(slot), np.int32(layer)),
                cfg.n_layer, t_pad, cfg.moe_top_k, n, emitted, kv_tol)
            ref_logits, ref_k, ref_v, _ = ref_forward(
                weights, seq, np.int32(n), table)
        else:
            ref_logits, ref_k, ref_v = ref_forward(weights, seq, np.int32(n))
        errs = jax.device_get(kv_errors(
            eng.pool.cache, ref_k, ref_v, np.int32(slot),
            np.int32(n + decode_steps)))
        ref_logits = np.asarray(ref_logits)
        gaps = [float(ref_logits[i].max() - ref_logits[i, t])
                for i, t in enumerate(emitted)]
        eng.pool.free(slot)
        cases.append({
            "prompt_len": n, "bucket": bucket,
            **{k: float(v) if v.ndim == 0 else [float(e) for e in v]
               for k, v in errs.items()},
            "max_logit_gap": max(gaps),
            "tokens_equal_argmax": sum(g == 0.0 for g in gaps),
            **routes,
        })
    ok = all(c["k_rel"] <= kv_tol and c["v_rel"] <= kv_tol
             and c["max_logit_gap"] <= SERVE_LOGIT_GAP_TOL for c in cases)
    return {"ok": bool(ok and cases), "kv_rel_tol": kv_tol, "cases": cases}


def pick_prompts(reqs, buckets: Sequence[int], n: int) -> List[np.ndarray]:
    """Up to ``n`` prompts of the generated traffic, taken from the prefill
    buckets in turn so that every compiled prefill program is checked."""
    by_bucket: Dict[int, List[np.ndarray]] = {}
    for r in reqs:
        b = next(b for b in buckets if b >= len(r.prompt))
        by_bucket.setdefault(b, []).append(r.prompt)
    chosen: List[np.ndarray] = []
    depth = 0
    while len(chosen) < n and any(len(v) > depth for v in by_bucket.values()):
        for b in sorted(by_bucket):
            if len(by_bucket[b]) > depth and len(chosen) < n:
                chosen.append(by_bucket[b][depth])
        depth += 1
    return chosen
