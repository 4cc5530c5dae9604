"""Serving traffic from a seed and a mix file: one general generator.

A mix file (``benchmarks/mixes/<name>.json``, kind ``serve``) holds only
parameters: the loop (open or closed), the arrival process, the length
distributions. This module turns them into a list of requests; the same
seed gives the same list, byte for byte. The arithmetic of the arrival
processes is ``trafficlab/arrivals.py``'s (Lewis–Shedler thinning against
the peak rate), copied so that the program's copy can change or go.

A length distribution with a ``stratum`` is drawn by strata: every block of
``stratum`` requests takes the ``stratum`` quantile midpoints of the
distribution once each, in seeded order. The multiset of lengths in a block
is then the same for every seed, so two runs differ in order and in token
ids, not in how much work they were dealt; the price is that nothing beyond
the outermost midpoints is ever drawn. Without a ``stratum`` lengths are
independent draws. With ``"order": "cycle"`` the strata are dealt in one
order, drawn from the spec's own ``order_seed`` and not from ``--seed``, and
every block repeats it: the traffic is then periodic, every run and every
block of it is dealt the same lengths in the same order (token ids and
weights still differ by seed), and a closed loop settles into a cycle whose
blocks can be timed one by one (``cycle_length``, ``serve_cell``).
Arrivals likewise: ``poisson`` is a Poisson process, and
``stratified_poisson`` deals the quantile midpoints of the exponential gap
in seeded order, so every block of ``stratum`` requests takes the same total
time and a window holds the same number of requests to within one: a
measurement's variance reduction, with the bursts of a real Poisson stream
removed. A mix that uses either says so in its ``what``. With ``"order":
"cycle"`` and an ``order_seed`` the gaps come as a length spec's strata do:
one order, drawn from ``order_seed``, that every block repeats and no
``--seed`` changes, so every run's requests are due at the same moments.

An open-loop mix can start warm (``warm_start``): at time 0 the generator
sends as many requests as the cell's file says are in flight in steady state
(``warm_inflight``), each with what a request caught at a random moment of
its life has left to generate (a length drawn in proportion to its size,
times a uniform share). Occupancy then starts where it would settle, and
the ramp need not last a request's life. ``warm_start`` may be an object
``{"order": "cycle", "order_seed": n}``: the pairing of the residual lengths
with their shares is then drawn from ``order_seed`` and not from ``--seed``
(``true`` pairs them by ``--seed``, as before). A mix that deals its gaps,
both lengths and its warm start in cycles gives every seed the same due
times and the same lengths in the same order: two seeds differ in token ids
and weights alone. Give each spec of a mix an ``order_seed`` of its own: two
specs of one stratum under one seed are dealt in the same order, and long
prompts then always meet long answers.
"""

from __future__ import annotations

import dataclasses
import zlib
from statistics import NormalDist
from typing import List, Mapping, Optional

import numpy as np


def _rng(seed: int, purpose: str) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([seed, zlib.crc32(purpose.encode())])))


def quantiles(spec: Mapping, k: int) -> np.ndarray:
    """The ``k`` quantile midpoints of a length distribution, as integers
    clipped to [min, max]. Kinds: ``lognormal`` (median, sigma), ``uniform``
    and ``fixed`` (value)."""
    u = (np.arange(k) + 0.5) / k
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        vals = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "fixed":
        return np.full(k, int(spec["value"]), dtype=np.int64)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def _dealt(grid: np.ndarray, n_blocks: int, spec: Mapping,
           rng: np.random.Generator) -> List[np.ndarray]:
    """``n_blocks`` blocks of a stratum's quantile midpoints: each in an
    order of its own drawn from ``rng`` (the run's seed), or, under
    ``"order": "cycle"``, all in the one order the spec's ``order_seed``
    draws."""
    if spec.get("order") == "cycle":
        return [_rng(int(spec["order_seed"]), "cycle").permutation(grid)] \
            * n_blocks
    return [rng.permutation(grid) for _ in range(n_blocks)]


def lengths(spec: Mapping, n: int, rng: np.random.Generator) -> np.ndarray:
    if "stratum" not in spec:       # independent draws, clipped
        dist = spec["dist"]
        if dist == "lognormal":
            vals = spec["median"] * np.exp(
                spec["sigma"] * rng.standard_normal(n))
        elif dist == "uniform":
            vals = rng.uniform(spec["min"], spec["max"], n)
        elif dist == "fixed":
            return np.full(n, int(spec["value"]), dtype=np.int64)
        else:
            raise ValueError(f"unknown length distribution {dist!r}")
        return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)
    k = int(spec["stratum"])
    grid = quantiles(spec, k)
    blocks = _dealt(grid, -(-n // k), spec, rng)
    return np.concatenate(blocks)[:n] if blocks else np.zeros(0, np.int64)


def cycle_length(mix: Mapping) -> int:
    """Requests in a block of a closed-loop mix that deals both its lengths
    in cycles of one length; 0 for any other mix."""
    specs = (mix["prompt_len"], mix["output_len"])
    if mix["loop"] != "closed" or any(s.get("order") != "cycle" for s in specs) \
            or specs[0]["stratum"] != specs[1]["stratum"]:
        return 0
    return int(specs[0]["stratum"])


def arrival_times(spec: Mapping, rate: float, horizon_s: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Times in [0, horizon) of an arrival process with mean ``rate`` a
    second. ``poisson``: homogeneous. ``stratified_poisson``: exponential
    gaps dealt by strata of ``stratum``, in seeded order or, under ``"order":
    "cycle"``, in the one order of the spec's ``order_seed``. ``bursty``:
    on/off, each ``period`` seconds spends ``duty`` of its length at
    ``peak_to_mean`` times the mean and the rest at whatever keeps the
    mean."""
    process = spec["process"]
    if process == "stratified_poisson":
        k = int(spec["stratum"])
        grid = -np.log1p(-(np.arange(k) + 0.5) / k)
        grid *= 1.0 / (rate * grid.mean())          # mean gap exactly 1/rate
        n_blocks = int(horizon_s * rate / k) + 2
        gaps = np.concatenate(_dealt(grid, n_blocks, spec, rng))
        t = np.cumsum(gaps)
        return t[t < horizon_s]
    if process == "poisson":
        peak = rate
        rate_at = lambda t: rate
    elif process == "bursty":
        duty, period = float(spec["duty"]), float(spec["period"])
        on = rate * float(spec["peak_to_mean"])
        off = (rate - on * duty) / (1.0 - duty)
        if off < 0.0:
            raise ValueError("bursty: peak_to_mean * duty must not pass 1")
        peak = on
        rate_at = lambda t: on if (t % period) / period < duty else off
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    out, t = [], 0.0
    while True:
        t += float(rng.exponential(1.0 / peak))
        if t >= horizon_s:
            return np.asarray(out, dtype=np.float64)
        if rng.uniform() * peak <= rate_at(t):
            out.append(t)


@dataclasses.dataclass(frozen=True)
class Req:
    index: int
    due_s: Optional[float]      # open loop: seconds after the origin
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int


def residual_lengths(spec: Mapping, n: int,
                     rng: np.random.Generator) -> np.ndarray:
    """What ``n`` requests caught mid-life have left to generate: a length
    from the size-biased distribution (a long request is in flight for
    longer) times a uniform share, at least 1. Both are quantile midpoints
    paired in the order ``rng`` draws (the run's seed, or a warm start's own
    ``order_seed``), so every seed gets the same two multisets."""
    if n == 0:
        return np.zeros(0, np.int64)
    grid = np.sort(quantiles(spec, int(spec.get("stratum", 64))))
    cdf = np.cumsum(grid) / grid.sum()
    mid = (np.arange(n) + 0.5) / n
    biased = grid[np.searchsorted(cdf, mid)]
    share = rng.permutation(mid)
    return np.maximum(1, np.ceil(biased * share)).astype(np.int64)


def requests(mix: Mapping, vocab: int, seed: int, *, rate: Optional[float],
             horizon_s: float, warm_inflight: int = 0) -> List[Req]:
    """The whole run's requests. Open loop: one per arrival in
    [0, horizon), after ``warm_inflight`` requests due at 0 if the mix starts
    warm. Closed loop: a pool of ``mix['pool']`` that the clients take in
    order, each its next when its last has completed."""
    warm = mix.get("warm_start")
    n_warm = int(warm_inflight) if warm else 0
    warm_rng = _rng(int(warm["order_seed"]), "cycle") \
        if isinstance(warm, Mapping) and warm.get("order") == "cycle" \
        else _rng(seed, "warm")
    if mix["loop"] == "open":
        due = np.concatenate([np.zeros(n_warm), arrival_times(
            mix["arrivals"], float(rate), horizon_s, _rng(seed, "arrivals"))])
        n = len(due)
    elif mix["loop"] == "closed":
        due, n = None, int(mix["pool"])
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    n_prompt = lengths(mix["prompt_len"], n, _rng(seed, "prompt_len"))
    n_out = np.concatenate([
        residual_lengths(mix["output_len"], n_warm, warm_rng),
        lengths(mix["output_len"], n - n_warm, _rng(seed, "output_len"))])
    tok = _rng(seed, "tokens")
    return [
        Req(index=i, due_s=None if due is None else float(due[i]),
            prompt=tok.integers(0, vocab, size=int(n_prompt[i]),
                                dtype=np.int32),
            max_new_tokens=int(n_out[i]))
        for i in range(n)
    ]


def digest(reqs: List[Req]) -> str:
    """A fingerprint of the generated traffic, printed on an earlier line so
    that two runs of one seed can be seen to have had the same inputs."""
    h = zlib.crc32(b"")
    for r in reqs:
        h = zlib.crc32(np.float64(-1.0 if r.due_s is None else r.due_s)
                       .tobytes(), h)
        h = zlib.crc32(r.prompt.tobytes(), h)
        h = zlib.crc32(np.int64(r.max_new_tokens).tobytes(), h)
    return f"{h:08x}"
