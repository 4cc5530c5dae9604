"""``correct``: the program against the plain reference, on the chip, at the
cell's published widths, outside the measured window. ``reference`` is the
module the configuration names (``spec.load_reference``): it brings the
equations (``hidden``, ``logits``, ``loss``) and ``weights_from_program``,
which reads the program's parameters under the reference's own names, so
nothing here knows an architecture's parameter names.

What a reference owes the check (``sizes`` is the configuration file as
loaded, so a reference reads its published keys under their own names):

  ``hidden(weights, tokens (B, T) int32, sizes, act_dtype=None)`` -> ``(x,
      ks, vs)``: ``x`` (B, T, d) the hidden states after the final norm,
      float32; ``ks`` and ``vs`` (L, B, T, KV, hd) every cached layer's keys
      and values *as the architecture caches them*: after any norm of the
      projected keys and after any rotation by position (a rotary model's
      keys are cached rotated, so the reference returns them rotated), with
      the KV heads the architecture keeps (not repeated up to the query
      heads). The serving check holds the rows the engine's programs left
      in a slot's ``"k"`` and ``"v"`` leaves to exactly these, a row against
      a row: the reference's ``(KV, hd)`` is reshaped to the pool's row
      shape where the sizes agree (a pool that keeps a row's heads side by
      side, ``(1, KV x hd)``, is the same numbers). A latent's two parts
      (PR 30) and a hybrid stack's sparse rows beside a state (PR 32) come
      as ``ks``, ``vs`` too; what the pool holds under other names (a
      recurrent state, pooled keys) is held by the rows above it and by the
      logits.
      ``act_dtype`` is **the twin**: with a dtype the same plain code rounds
      to it where the published model holds that type (the embedding's
      output, every matmul's output, every residual sum, every norm's
      output) and keeps float32 inside what the configuration's
      ``departures`` say the program keeps there (norms, softmax, gates, a
      recurrent state, a router's scores). At ``None`` nothing is rounded.
      The reference writes the twin and the check reads it: how far the
      twin's rows lie from the unrounded run's is the error the stated
      precision must make in this architecture at this depth with these
      weights, and the program is held to ``SERVE_TWIN_FACTOR`` times it,
      a layer and (times the cell's ``twin_ratio``) over the stack. A
      reference rounds with ``references/rounding.py`` and nothing else.
      Where the block routes each token to k of E experts (the program's
      ``GPTConfig.n_experts`` is set), ``hidden`` owes two things more, and
      a reference that lacks them is an error there, not a dense check: it
      takes ``experts=`` (L, B, T, k) int32, the experts every token takes
      in every layer (None: the router's own k best; **a token's row with
      an entry under 0: the router's own k best for that token in that
      layer**, which is how a layer not yet followed runs), and returns a
      fourth array, the router's float32 logits (L, B, T, E), L counting
      every model layer. Nothing else: no tolerance, no margin, no law.
      ``serve_verdict`` then has the reference route as the program routed
      (``follow_routes``) before it holds the rows to the twin, which takes
      the same table. A dense reference owes neither.
  ``cached_layers(sizes)`` -> the model layers whose rows are ``ks[i]``,
      ``vs[i]``, in order: owed only where some layer caches no rows (a
      state layer of a hybrid stack). A reference that brings none caches a
      plane a layer. A dense check never reads it (``ks`` against the
      pool's planes, one for one); a routed one scores a layer's routes by
      the first cached layer above it (``follow_routes``).
  ``logits(weights, x (..., d))`` -> float32 (..., V), any final softcap in.
  ``loss(weights, tokens, targets, sizes)`` -> mean cross-entropy over the
      targets that are not -1 (training cells).
  ``weights_from_program(params)`` -> the reference's weights: renames of
      the program's arrays, nothing copied or cast.

Every tolerance stands beside its check with the reason for it. They are
set from what the stated precision must give (the twin) and what the chip
measured (PERF.md, Findings), tight enough that a lower precision than the
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
#: the live positions: **in every cached layer**, and over all of them. The
#: tolerance is this factor times the twin's own error over the same rows
#: (module docstring): what the stated precision must give in this
#: architecture, at this depth, under these weights, so no line here reads a
#: depth. (Until PR 36 it was a power law in ``n_layer`` through two GPT-2
#: depths, which a stack with four norms a layer, or one that runs its
#: layers several times, outruns while computing exactly what its
#: configuration states.)
#: A layer is what a pool in fewer bits fails by: rounding adds up with
#: depth, a row's own rounding does not, so it stands out in the first
#: layer, whose twin is the smallest. On the chip (PR 36; PERF.md has the
#: tables) the program reads 0.88 of the first layer's twin in the GPT-2
#: cells, 1.00 in kanana-2-30b-a3b, 0.84 in minicpm-sala, and at most 1.00 of
#: any layer's in any cell: the factor is a quarter above that. gpt2-xl's
#: rows through int8 with a scale a row (0.6% a row, the finest 8 bits can
#: do) passed the whole stack (0.87 of its limit) and the first quarter of it
#: (0.96), which this rule's first draft held, and fail the first layer on
#: all 6 prompts read (0.705% against 0.472: 1.49 times); rows through
#: 8-bit floats read twice the last layer's limit in kanana and more in
#: every layer before it.
#: Over the whole stack the program is held to its cell's ``twin_ratio``
#: (``cells/<cell>.json``, found once on the chip like its rate) of the
#: twin, times the same factor. The program is not the twin: its matmuls sum
#: in another order, its attention is a kernel or a two-part softmax, and
#: inside a fusion it keeps float32 where the twin rounds after each
#: operation, so past the first layer it reads *under* the twin, by a share
#: that is the architecture's: the largest whole-stack program over twin,
#: k or v, of the builder's runs (gpt2-124m 0.926-0.988, gpt2-xl
#: 0.990-0.997, kanana 0.864-0.896 on 96 prompts, minicpm 0.745-0.751),
#: rounded up to two places. Lower-precision arithmetic in the trunk adds up
#: with depth and shows there. A cell that states none is held to 1, and
#: none may state more: the twin rounds wherever the program may, so no
#: cell buys room above the factor, and a reference that rounds more than
#: it should gets its cell a smaller ratio, not a wider limit.
SERVE_TWIN_FACTOR = 1.25
#: A twin that reads above this makes the verdict blind: seeded weights
#: under which every branch has unit size amplify rounding (10% at 192
#: blocks, sized on the CPU: ISSUE 36), and a lower precision's 1% is then
#: lost in it. That is an error that names the configuration's ``assumed``
#: weights, never a pass. Twice the twin of the deepest cell, gpt2-xl's 48
#: layers (1.330-1.356% on the chip, PR 36); the largest twin of a cell is
#: minicpm-sala's 1.234%.
SERVE_TWIN_CEILING = 0.027
#: Where the stated precision is float32 the twin is the reference and
#: reads 0: two float32 programs still sum in two orders (measured on the
#: CPU: under 1e-5 at every tiny size of the tests).
SERVE_KV_REL_FLOOR = 1e-4
#: Routed experts: how far from the reference's own choice the program's
#: choice of experts may lie and still be followed. The router's logit of an
#: expert is z = h . w, h the normed residual stream. Where the program's h
#: is off by a relative error eps in no particular direction, the difference
#: of two experts' logits moves by eps * sqrt(2) * s (one standard
#: deviation), s being the scale of one logit, |h| |w| / sqrt(d): read here
#: as the spread of a layer's logits about their mean over the experts. The
#: twin's tolerance lets a layer's input be off by ``kv_tol`` (the whole
#: stack's, under the reference's own routes: the followed ones do not exist
#: yet), and behind an expert layer the chip's rows are off by just that
#: (0.72-0.90% a layer, every position alike: PERF.md, PR 26). A token is
#: followed to another set of k experts only where every pair of logits the
#: swap turns over lies within SERVE_ROUTE_MARGIN * kv_tol * s:
#: SERVE_ROUTE_MARGIN / sqrt(2) = 5.7 standard deviations of the rounding the
#: tolerance allows. Set from that arithmetic and from the chip: of 549 flips
#: followed on the probe the widest lay 4.08 of these units apart, and a
#: margin of 16 found the same 549 (PERF.md, PR 26). The margin fences which
#: routes may be tried and forgives nothing: a program that routes outside
#: it, drops a route, weighs a gate otherwise or caches in a lower precision
#: matches no admissible set and fails by the twin's tolerance.
SERVE_ROUTE_MARGIN = 8.0
#: The sets of experts a token may try in one layer beside the reference's
#: own, nearest first; each is one more forward of the reference for all
#: tokens at once (40 ms on the chip at the probe's size). Sets beyond these
#: are counted in the notes: a token has more only with three logits on one
#: side of the boundary inside the margin and two on the other.
SERVE_ROUTE_TRIES = 8
#: Where a stack's rows skip layers (``follow_routes``), a token is settled
#: once its rows lie near the program's: within this many times the square
#: of the rows' tolerance, in the squared distance ``row_distance`` gives
#: (keys and values added: 32 is both four times the tolerance away). On the
#: fixture a token under the program's own sets reads 0.9-1.3 at the median,
#: 5 at the most, and 10 while a neighbour a few positions before it still
#: stands elsewhere than the program had it; one with a single expert
#: swapped reads 900-3,000 (one expert moves a row by tens of percent,
#: rounding by about one): the level lies between, nearer the settled side.
#: A rule of the search and not of the verdict: it says which tokens go
#: round again and when a set is taken for good; every row is held to the
#: twin in the end, whatever the search took.
SERVE_ROUTE_NEAR = 32.0
#: The rounds a group of layers that share their scoring layer may take
#: (``follow_routes``): a sweep of each of its layers a round, each sweep one
#: forward for the router's logits, at most 1 + SERVE_ROUTE_TRIES for the
#: sets and one to confirm, and one forward a round to see what is settled;
#: so a case costs at most layers x (3 + SERVE_ROUTE_TRIES) x
#: SERVE_ROUTE_SWEEPS forwards of the reference and one a round. A stack that
#: caches a plane a layer takes one sweep a layer, the parent's. Where rows
#: skip layers, the first round settles the tokens that moved in one layer
#: at most and have no such neighbour just before them (on the chip one
#: token in twenty moves in a layer: ledger, kanana), a second their
#: neighbours, a third finds nothing more, and the rounds after it take the
#: tokens that moved in two layers, one rule a round (``follow_routes``): 8
#: holds three rounds and a rule for each of four layers and the one before
#: them. What is still unsettled then is noted (``route_unsettled_layers``)
#: and keeps the nearest sets it found, which the rows then pass or fail by.
SERVE_ROUTE_SWEEPS = 8
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
    out["compared"] = {
        "eval_minus_reference": [abs(eval_loss - ref_loss),
                                 TRAIN_EVAL_LOSS_TOL],
        "first_minus_reference": [abs(losses[0] - ref_loss),
                                  TRAIN_FIRST_LOSS_TOL],
        "last_minus_first": [losses[-1] - losses[0], 0.0]}
    return out


def compared(verdict: Dict) -> Dict:
    """Every number a verdict compared, beside its limit, under short plain
    names: ``{name: [number, limit]}``; a serving case's under ``<case>.``.
    What ``run.py`` prints last, so that a run that is not correct says by
    which number."""
    out = dict(verdict.get("compared", {}))
    for i, case in enumerate(verdict.get("cases", [])):
        out.update({f"{i}.{name}": pair
                    for name, pair in case["compared"].items()})
    if "compiled_in_window" in verdict:
        out["compiled_in_window"] = [verdict["compiled_in_window"], 0]
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
                  emitted: Sequence[int], kv_tol: float,
                  cached: Sequence[int] = None):
    """The table of experts (L, T, k) under which the reference goes the way
    the program went, and the notes of how it was found.

    ``forward(table)`` is one forward of the reference over the checked
    sequence: ``(logits of the rows that emitted a token, ks, vs, router
    logits (L, T, E))``; a row of the table with an entry under 0 runs under
    the router's own choice. ``row_distance(ks, vs, plane)`` is every
    position's squared relative distance between the reference's keys and
    values of that cached plane and the rows the program cached. ``cached``
    names the model layers whose rows the planes are, in order (None: a
    plane a layer).

    A layer's routes are scored by the first cached layer above it, and the
    layers above the last cached one by the tokens the program emitted.
    Each token whose logits leave a choice inside the margin takes, of its
    admissible sets, the one whose rows there lie nearest the program's (of
    the rows that emitted a token: under which that token's logit gap is
    least); all such tokens try their n-th set in one forward, a sweep. One
    expert swapped moves a row by tens of percent, bf16 rounding by about
    one. Rows written by prefill and by decode are treated alike.

    Where the scoring layer is the next one (every layer of a stack that
    caches a plane a layer), row t there is made of x_{l+1}(t) alone and
    depends on token t's own experts at layer l alone, whatever the layers
    above do: layer by layer, the layers below fixed, one sweep each. That
    is the whole of it there.

    Where rows skip layers (a hybrid stack's state layers cache none), the
    layers that share a scoring layer are followed together, in rounds, the
    lowest layer first. A token's rows at the scoring layer move with its
    sets in every layer of the group, and a layer not yet followed runs
    under the reference's own choice (entries of -1): the program's for all
    but the one token in twenty the program sent elsewhere there. For such a
    token the right set below does not bring the rows near, and a wrong one
    can read nearer than it (the layer above, chosen anew under another
    input, may fall the program's way by chance), so nearest alone is no
    rule. What is sure is the level: under the program's sets in every
    layer a token's rows lie within rounding of the program's
    (``SERVE_ROUTE_NEAR``), under any other at a swap's distance. So a token
    takes another set only where that brings its rows near (read in a
    forward of its own, in which only the tokens that found a nearer set
    stand elsewhere than the reference: ``sweep``), which settles
    every token that moved in one layer at most; the tokens still far after
    a round go round again from the reference's own choice. The sets of the
    tokens before it reach a token's rows too, through a convolution's taps
    or a state (on the fixture a neighbour's swap reads a quarter of a
    token's own), so a round that settled some tokens is followed by
    another under the same rule: their neighbours' rows are clear now, and
    rows before a token never depend on it. A round that settled none hands
    the rest to the next rule: each layer takes its nearest set (a token
    that moved in two layers: the lower one's right set reads a swap's
    distance like all the others, and is the nearest more often than not),
    then the token is held off its own set in the first, the second, ... of
    the layers it has a choice in. Under those a token keeps what a round
    gave it only if its rows are near at the round's end, and the nearest it
    ever stood otherwise."""
    cached = list(range(n_layer)) if cached is None else [int(c) for c in cached]
    n_rows = n_prompt + len(emitted) - 1          # rows the program cached
    emitting = np.arange(n_prompt - 1, n_rows)
    table = np.full((n_layer, n_positions, top_k), -1, np.int32)
    # plane -> the layers it scores, lowest first; None: the emitted tokens
    groups: Dict = {}
    for layer in range(n_layer):
        groups.setdefault(next(
            (i for i, c in enumerate(cached) if c > layer), None), []
        ).append(layer)
    # a layer's tokens with a choice: position -> the sets it may take,
    # [(gap, experts), ...], its own first, and the sets cut beyond the tries
    state = [{"sweeps": 0, "unsettled": 0, "choices": {}, "cut": {}}
             for _ in range(n_layer)]
    forwards = 0

    def run(trial):
        nonlocal forwards
        forwards += 1
        return forward(trial)

    def scored(out, plane) -> np.ndarray:
        if plane is not None:
            return np.asarray(row_distance(out[1], out[2], plane))
        rows = np.asarray(out[0])
        score = np.full(n_positions, np.inf)
        score[emitting] = rows.max(-1) - rows[
            np.arange(len(emitted)), list(emitted)]
        return score

    def sweep(layer: int, plane, trying, take: Callable, near=None) -> None:
        """The tokens of ``trying`` (None: all) with a choice in this layer
        try their sets at once, each from the reference's own;
        ``take(t, scores)`` says which of them token t keeps, and with a
        level ``near`` a token keeps another set than its own only if its
        rows lie that near under it."""
        at = state[layer]
        # this layer's logits depend on the layers below alone
        router = np.asarray(run(table)[3][layer])
        live = router[:n_rows]
        spread = float(np.sqrt(np.mean(
            (live - live.mean(-1, keepdims=True)) ** 2)))
        margin = SERVE_ROUTE_MARGIN * kv_tol * spread
        own = np.argsort(-router, axis=-1, kind="stable")[:, :top_k]
        if trying is None:
            table[layer] = own
        else:
            rows = sorted(trying)
            table[layer, rows] = own[rows]
        # a later sweep reads anew only the tokens it tries: the layers
        # below moved under them alone
        choices, cut = at["choices"], at["cut"]
        for t in map(int, (emitting if plane is None else range(n_rows))
                     if trying is None else trying):
            others = route_alternatives(router[t], top_k, margin)
            choices.pop(t, None)
            if others:
                choices[t] = [(0.0, own[t].copy()),
                              *others[:SERVE_ROUTE_TRIES]]
                cut[t] = max(0, len(others) - SERVE_ROUTE_TRIES)
        at.update(margin=margin, own=own, router=router,
                  sweeps=at["sweeps"] + 1)
        members = sorted(choices if trying is None else trying & set(choices))
        if not members:
            return
        scores = np.full((max(len(choices[t]) for t in members), n_positions),
                         np.inf)
        for c in range(len(scores)):
            taking = [t for t in members if c < len(choices[t])]
            trial = table.copy()
            for t in taking:
                trial[layer, t] = choices[t][c][1]
            scores[c, taking] = scored(run(trial), plane)[taking]
        taken = {t: take(t, scores[:, t]) for t in members}
        if near is not None:
            # the forwards above held most neighbours at sets they did not
            # take, and what that carries into a token's rows can hide that
            # a set is the right one: every token that found a nearer set
            # than its own takes it in one more forward, the others at
            # their own, and keeps it only if its rows lie near there
            moving = {t: c for t, c in taken.items() if c}
            trial = table.copy()
            for t, c in moving.items():
                trial[layer, t] = choices[t][c][1]
            score = scored(run(trial), plane) if moving else None
            taken = {t: c if c and score[t] <= near else 0
                     for t, c in taken.items()}
        for t, c in taken.items():
            table[layer, t] = choices[t][c][1]

    # 0: only what brings a token's rows near (a layer alone: what lies
    # nearest); 1: nearest; 1 + j: held off its own set in the j-th layer
    # the token has a choice in, nearest in the others
    rule, turn = 0, {}

    def take(t, scores):
        if rule:
            turn[t] += 1
            if turn[t] == rule - 1:
                return 1 + int(np.argmin(scores[1:]))
        return int(np.argmin(scores))

    for plane, layers in groups.items():
        if len(layers) == 1:            # the next layer scores it
            sweep(layers[0], plane, None, take)
            continue
        near = SERVE_LOGIT_GAP_TOL if plane is None \
            else SERVE_ROUTE_NEAR * kv_tol ** 2
        far, kept = None, {}    # token -> (its distance, its sets) at its best
        tried = 0               # the last rule a round ran under
        for _ in range(SERVE_ROUTE_SWEEPS):
            if far is not None:
                table[np.ix_(layers, sorted(far))] = -1
            turn = {t: 0 for t in far or ()}
            for layer in layers:
                sweep(layer, plane, far, take, None if rule else near)
            score = scored(run(table), plane)
            if far is None:
                far = set().union(*(state[layer]["choices"]
                                    for layer in layers))
            for t in far:
                if t not in kept or score[t] < kept[t][0]:
                    kept[t] = (float(score[t]), table[layers, t].copy())
            still = {t for t in far if not kept[t][0] <= near}
            for t in still:             # the nearest it ever stood
                table[layers, t] = kept[t][1]
            # a round that settled some clears their neighbours' rows: the
            # rest try once more for what brings them near; one that settled
            # none hands them to the next rule
            if len(still) == len(far):
                rule = tried = tried + 1
            else:
                rule = 0
            far = still
            if not far or rule > 1 + len(layers):
                break
        rule = 0
        for layer in layers:
            state[layer]["unsettled"] = len(far & set(state[layer]["choices"]))
    notes: Dict[str, list] = {}
    for layer, at in enumerate(state):
        moved = [t for t in range(n_rows)
                 if set(table[layer, t]) != set(at["own"][t])]
        gaps = [float(max(at["router"][t][[e for e in at["own"][t]
                                           if e not in table[layer, t]]])
                      - min(at["router"][t][[e for e in table[layer, t]
                                             if e not in at["own"][t]]]))
                for t in moved]
        for key, value in (("route_margin_layers", at["margin"]),
                           ("route_banded_layers", len(at["choices"])),
                           ("route_followed_layers", len(gaps)),
                           ("route_gap_max_layers", max(gaps, default=0.0)),
                           ("route_cut_layers", sum(
                               at["cut"][t] for t in at["choices"])),
                           ("route_sweeps_layers", at["sweeps"]),
                           ("route_unsettled_layers", at["unsettled"])):
            notes.setdefault(key, []).append(value)
    notes["route_forwards"] = forwards
    return table, notes


def _as_rows(ref, got):
    """The reference's rows ``(..., KV, hd)`` in the pool's row shape: the
    same numbers where the pool keeps a row's heads side by side."""
    if ref.shape[-2:] == got.shape[-2:]:
        return ref
    if math.prod(ref.shape[-2:]) != math.prod(got.shape[-2:]):
        raise RuntimeError(
            f"the pool's rows {got.shape[-2:]} are not the reference's "
            f"{ref.shape[-2:]} in another shape")
    return ref.reshape(*ref.shape[:-2], *got.shape[-2:])


def row_errors(got: Dict, ref_k, ref_v, n_rows) -> Dict:
    """``got["k"]``, ``got["v"]`` (L, T' >= T, row) against the reference's
    (L, T, row) over the first ``n_rows`` positions: the relative Frobenius
    error of each over the whole stack, a layer, and a layer's median over
    the positions of a position's own error."""
    import jax.numpy as jnp

    t = ref_k.shape[1]
    live = (jnp.arange(t) < n_rows)[None, :, None, None]
    out = {}
    for name, ref in (("k", ref_k), ("v", ref_v)):
        rows = got[name][:, :t]
        ref = _as_rows(ref, rows)
        diff = jnp.where(live, rows.astype(jnp.float32) - ref, 0.0)
        ref = jnp.where(live, ref, 0.0)
        num = jnp.sum(diff ** 2, axis=(1, 2, 3))
        den = jnp.sum(ref ** 2, axis=(1, 2, 3))
        out[name + "_rel"] = jnp.sqrt(num.sum() / den.sum())
        out[name + "_max_abs"] = jnp.max(jnp.abs(diff))
        # the same error a layer: what a row kept in fewer bits fails by
        out[name + "_rel_layers"] = jnp.sqrt(num / den)
        # and a layer's median over the live positions of a position's
        # own error: the bulk of the tokens, whatever a few of them do
        by_position = jnp.sqrt(jnp.sum(diff ** 2, axis=(2, 3))
                               / jnp.sum(ref ** 2, axis=(2, 3)))
        out[name + "_rel_p50_layers"] = jnp.nanmedian(
            jnp.where(live[:, :, 0, 0], by_position, jnp.nan), axis=1)
    return out


def pool_errors(cache, ref_k, ref_v, slot, n_rows) -> Dict:
    """``row_errors`` of one slot of the pool's ``"k"`` and ``"v"``."""
    import jax

    return row_errors(
        {name: jax.lax.dynamic_index_in_dim(cache[name], slot, axis=1,
                                            keepdims=False)
         for name in ("k", "v")}, ref_k, ref_v, n_rows)


def row_distance(cache, ref_k, ref_v, slot, layer):
    """Every position's squared relative distance between one cached plane's
    rows of a slot and the reference's, keys and values added. ``layer``
    counts the planes the pool holds, which are the model's layers only
    where every layer caches rows."""
    import jax
    import jax.numpy as jnp

    total = 0.0
    for name, ref in (("k", ref_k), ("v", ref_v)):
        ref = jax.lax.dynamic_index_in_dim(ref, layer, keepdims=False)
        got = jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(cache[name], layer, keepdims=False),
            slot, keepdims=False)[:ref.shape[0]]
        ref = _as_rows(ref, got)
        total += jnp.sum((got.astype(jnp.float32) - ref) ** 2, (1, 2)) \
            / jnp.sum(ref ** 2, (1, 2))
    return total


def twin_tolerance(twin_rel, twin_ratio: float = 1.0) -> float:
    """What the program's error may be where the twin's is ``twin_rel`` and
    the cell's program reads ``twin_ratio`` of its twin."""
    return max(SERVE_TWIN_FACTOR * twin_ratio * float(twin_rel),
               SERVE_KV_REL_FLOOR)


def _listed(errors: Dict, prefix: str = "") -> Dict:
    """Fetched arrays as plain numbers and lists, for the notes."""
    return {prefix + k: float(v) if v.ndim == 0 else [float(e) for e in v]
            for k, v in errors.items()}


def held_to_twin(errs: Dict, twin: Dict, twin_ratio: float = 1.0) -> Dict:
    """One case's numbers beside their limits: ``{name: [the program's
    error, the tolerance]}`` for keys and values, over the whole stack
    (``k_rel``, held to the cell's ``twin_ratio`` of the twin) and in the
    layer that stands nearest its own twin's tolerance or furthest past it
    (``k_rel_layer``; ``k_worst_layer`` says which). ``kv_ratio`` is the
    program over the twin across the whole stack, the larger of keys and
    values: what a cell's ``twin_ratio`` records; ``kv_ratio_layers`` the
    largest of any layer."""
    out = {"compared": {}, "kv_ratio": 0.0, "kv_ratio_layers": 0.0}
    for name in ("k_rel", "v_rel"):
        out["compared"][name] = [
            float(errs[name]), twin_tolerance(twin[name], twin_ratio)]
        layers = [(float(e), float(t)) for e, t in
                  zip(errs[name + "_layers"], twin[name + "_layers"])]
        worst = max(range(len(layers)), key=lambda i: layers[i][0]
                    / twin_tolerance(layers[i][1]))
        out["compared"][name + "_layer"] = [
            layers[worst][0], twin_tolerance(layers[worst][1])]
        out[name[0] + "_worst_layer"] = worst
        if float(twin[name]) > 0.0:
            out["kv_ratio"] = max(out["kv_ratio"],
                                  float(errs[name]) / float(twin[name]))
        out["kv_ratio_layers"] = max(
            out["kv_ratio_layers"], *(e / t for e, t in layers if t > 0.0), 0.0)
    return out


def serve_verdict(reference, sizes: Dict, server, prompts: List[np.ndarray],
                  decode_steps: int, twin_ratio: float = 1.0) -> Dict:
    """Prefill each prompt into slot 0 and decode ``decode_steps`` tokens with
    the engine's own compiled programs, then hold the slot's cache rows and
    the emitted tokens to the reference's full forward over the same
    sequence; where the reference brings the routed contract, to its
    forward under the program's routes (``follow_routes``), whose notes then
    stand in each case. The rows' tolerance is the twin's: the reference
    once more over the same sequence (a routed one under its own routes:
    rounding does not read which experts a few tokens took, and the margin
    that fences the following needs the tolerance first), rounded to the
    program's compute dtype, which the configuration states, times the
    cell's ``twin_ratio``. The pool must be empty: the check takes a slot as
    a request would and gives it back."""
    import jax
    import jax.numpy as jnp

    if not 0.0 < twin_ratio <= 1.0:
        raise ValueError(
            f"twin_ratio {twin_ratio}: the share of its twin's error a "
            "cell's program reads lies in (0, 1]; the twin rounds wherever "
            "the program may, so no cell buys room above SERVE_TWIN_FACTOR")

    eng = server.engine
    cfg = eng.cfg
    if eng.pool.used_count:
        raise RuntimeError("the correctness check needs an empty pool")
    parameters = inspect.signature(reference.hidden).parameters
    routed = "experts" in parameters
    if cfg.n_experts and not routed:
        raise RuntimeError(
            "the program routes experts (n_experts is set) and the "
            "reference's hidden() takes no experts= table: it owes the "
            "routed contract (harness/check.py)")
    if "act_dtype" not in parameters:
        raise RuntimeError(
            "the reference's hidden() takes no act_dtype=: it owes the twin "
            "the rows' tolerance is read from (harness/check.py)")
    # the model layers whose rows the pool's planes are: the reference's
    # word, and a plane a layer where it says nothing
    cached = reference.cached_layers(sizes) \
        if hasattr(reference, "cached_layers") else None
    act_dtype = jnp.dtype(cfg.dtype)
    weights = reference.weights_from_program(eng.params)
    n_slots, parked = eng.n_slots, cfg.block_size - 1
    # greedy lanes: the seed is never used, every request's is 0
    seeds = np.zeros(n_slots, np.uint32)
    token_index = np.zeros(n_slots, np.int32)

    def hidden(w, seq, experts, **twin):
        routes = {} if experts is None else {"experts": experts[:, None]}
        return reference.hidden(w, seq[None], sizes, **routes, **twin)

    @jax.jit
    def ref_forward(w, seq, n_prompt, experts=None):
        """Dense: (logits, ks, vs). Routed: and the router's logits."""
        x, ks, vs, *router = hidden(w, seq, experts)
        rows = jax.lax.dynamic_slice_in_dim(
            x[0], n_prompt - 1, decode_steps + 1, axis=0)
        return (reference.logits(w, rows),
                *(a[:, 0] for a in (ks, vs, *router)))

    @jax.jit
    def twin_errors(w, seq, ref_k, ref_v, n_rows, experts=None):
        """The twin's rows against the unrounded run's: ``row_errors``."""
        _, ks, vs, *_ = hidden(w, seq, experts, act_dtype=act_dtype)
        return row_errors({"k": ks[:, 0], "v": vs[:, 0]}, ref_k, ref_v,
                          n_rows)

    def read_twin(seq, ref_k, ref_v, n_rows, table=None) -> Dict:
        twin = jax.device_get(twin_errors(weights, seq, ref_k, ref_v,
                                          np.int32(n_rows), table))
        worst = float(max(twin["k_rel"], twin["v_rel"]))
        if not worst <= SERVE_TWIN_CEILING:
            raise RuntimeError(
                f"the reference rounded to {act_dtype.name} lies {worst:.4f} "
                f"from itself unrounded, over SERVE_TWIN_CEILING = "
                f"{SERVE_TWIN_CEILING}: these weights amplify rounding, and "
                "no verdict under them could see a lower precision. The "
                "configuration's assumed.weights have to change (smaller "
                "norm weights on a deep or looped stack: "
                "benchmarks/README.md)")
        return twin

    errors_of_slot = jax.jit(pool_errors)
    distance_of_layer = jax.jit(row_distance)

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
        n_rows = n + decode_steps
        ref_logits, ref_k, ref_v, *router = ref_forward(
            weights, seq, np.int32(n))
        routes = {}
        if routed:
            # both runs under the reference's own routes: the twin must take
            # the table, or it would round its way to routes of its own
            own = np.argsort(-np.asarray(router[0]), axis=-1,
                             kind="stable")[..., :cfg.moe_top_k]
            twin = read_twin(seq, ref_k, ref_v, n_rows, own.astype(np.int32))
            table, routes = follow_routes(
                lambda experts: ref_forward(weights, seq, np.int32(n), experts),
                lambda ks, vs, plane: distance_of_layer(
                    eng.pool.cache, ks, vs, np.int32(slot), np.int32(plane)),
                len(own), t_pad, cfg.moe_top_k, n, emitted,
                twin_tolerance(max(twin["k_rel"], twin["v_rel"]), twin_ratio),
                cached)
            ref_logits, ref_k, ref_v, _ = ref_forward(
                weights, seq, np.int32(n), table)
        else:
            twin = read_twin(seq, ref_k, ref_v, n_rows)
        errs = jax.device_get(errors_of_slot(
            eng.pool.cache, ref_k, ref_v, np.int32(slot), np.int32(n_rows)))
        ref_logits = np.asarray(ref_logits)
        gaps = [float(ref_logits[i].max() - ref_logits[i, t])
                for i, t in enumerate(emitted)]
        eng.pool.free(slot)
        held = held_to_twin(errs, twin, twin_ratio)
        held["compared"]["logit_gap"] = [max(gaps), SERVE_LOGIT_GAP_TOL]
        cases.append({
            "prompt_len": n, "bucket": bucket,
            **_listed(errs),
            # the twin's own error: a case that fails says which side moved
            **_listed({k: v for k, v in twin.items()
                       if k.endswith(("_rel", "_rel_layers"))}, "twin_"),
            **held,
            "max_logit_gap": max(gaps),
            "tokens_equal_argmax": sum(g == 0.0 for g in gaps),
            **routes,
        })
    ok = all(value <= limit for c in cases
             for value, limit in c["compared"].values())
    # the widest tolerance any part of any case's rows was held to
    widest = max((limit for c in cases for name, (_, limit)
                  in c["compared"].items() if name != "logit_gap"),
                 default=0.0)
    return {"ok": bool(ok and cases), "kv_rel_tol": widest, "cases": cases}


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
