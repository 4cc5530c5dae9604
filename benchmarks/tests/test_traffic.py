"""The traffic generator: the same seed gives the same bytes, another seed
other bytes, and every seed the same amount of work."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import spec, tokens, traffic

MIXES = os.path.join(spec.BENCH, "mixes")


def load(name):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix, rate", [("serve-decode", 20.0),
                                       ("serve-prefill", None)])
def test_one_seed_one_trace(mix, rate):
    m = load(mix)
    a = traffic.requests(m, 50257, 3, rate=rate, horizon_s=20.0)
    b = traffic.requests(m, 50257, 3, rate=rate, horizon_s=20.0)
    c = traffic.requests(m, 50257, 4, rate=rate, horizon_s=20.0)
    assert traffic.digest(a) == traffic.digest(b)
    assert traffic.digest(a) != traffic.digest(c)
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_open_loop_arrivals_follow_the_rate_and_the_horizon():
    reqs = traffic.requests(load("serve-decode"), 50257, 1, rate=20.0,
                            horizon_s=50.0)
    due = np.array([r.due_s for r in reqs])
    assert (np.diff(due) > 0).all() and due[-1] < 50.0
    assert abs(len(reqs) - 1000) < 4 * np.sqrt(1000)


def test_bursty_keeps_the_mean_rate():
    spec_ = {"process": "bursty", "period": 2.0, "duty": 0.25,
             "peak_to_mean": 3.0}
    t = traffic.arrival_times(spec_, 40.0, 200.0, traffic._rng(1, "a"))
    assert abs(len(t) - 8000) < 4 * np.sqrt(8000 * 3)
    on = ((t % 2.0) / 2.0 < 0.25).mean()
    assert on == pytest.approx(0.75, abs=0.03)


@pytest.mark.parametrize("mix", ["serve-decode", "serve-prefill"])
def test_lengths_stay_in_their_clip_and_weigh_the_same_for_every_seed(mix):
    m = load(mix)
    k = m["prompt_len"]["stratum"]
    per_seed = []
    for seed in (1, 2):
        reqs = traffic.requests(m, 50257, seed, rate=20.0, horizon_s=20.0)[:2 * k]
        n_in = np.array([len(r.prompt) for r in reqs])
        n_out = np.array([r.max_new_tokens for r in reqs])
        assert n_in.min() >= m["prompt_len"]["min"]
        assert n_in.max() <= m["prompt_len"]["max"]
        assert n_out.min() >= m["output_len"]["min"]
        assert n_out.max() <= m["output_len"]["max"]
        assert (n_in + n_out).max() <= 1024
        per_seed.append((sorted(n_in), sorted(n_out)))
    assert per_seed[0] == per_seed[1]


def test_lognormal_quantiles_have_the_stated_median():
    q = traffic.quantiles({"dist": "lognormal", "median": 192, "sigma": 0.5,
                           "min": 1, "max": 10**6}, 64)
    assert abs(np.median(q) - 192) <= 2
    assert (np.diff(q) >= 0).all()


def test_token_stream_is_seeded_and_holds_every_symbol():
    a = tokens.token_stream(5, 60000, 50257)
    assert (a == tokens.token_stream(5, 60000, 50257)).all()
    assert (a != tokens.token_stream(6, 60000, 50257)).any()
    assert len(np.unique(a)) == 50257 and a.dtype == np.int32


def test_without_strata_gaps_and_lengths_are_independent_draws():
    m = load("serve-decode")
    m = dict(m, arrivals={"process": "poisson"},
             prompt_len={k: v for k, v in m["prompt_len"].items()
                         if k != "stratum"},
             output_len={k: v for k, v in m["output_len"].items()
                         if k != "stratum"})
    counts, longest = [], []
    for seed in range(8):
        reqs = traffic.requests(m, 50257, seed, rate=1.75, horizon_s=30.0)
        counts.append(len(reqs))
        longest.append(max(r.max_new_tokens for r in reqs))
        assert all(16 <= len(r.prompt) <= 256 for r in reqs)
        assert all(64 <= r.max_new_tokens <= 512 for r in reqs)
    # a Poisson window of 52.5 requests spreads by about 7; strata by 1
    assert max(counts) - min(counts) > 4 and max(longest) == 512
    assert abs(np.mean(counts) - 52.5) < 3 * np.sqrt(52.5 / 8)


def test_a_cycle_deals_every_block_and_every_seed_the_same_lengths():
    m = load("serve-prefill")
    k = traffic.cycle_length(m)
    assert k == m["prompt_len"]["stratum"] == m["output_len"]["stratum"] == 16
    assert traffic.cycle_length(load("serve-decode")) == 0
    shapes = []
    for seed in (1, 2):
        reqs = traffic.requests(m, 50257, seed, rate=None, horizon_s=0.0)
        shapes.append([(len(r.prompt), r.max_new_tokens) for r in reqs[:3 * k]])
        assert shapes[-1][:k] == shapes[-1][k:2 * k] == shapes[-1][2 * k:]
    assert shapes[0] == shapes[1]
    n_in, n_out = map(np.array, zip(*shapes[0][:k]))
    # the two orders differ, or long prompts would always meet long answers
    assert abs(np.corrcoef(n_in, n_out)[0, 1]) < 0.5
    # every request's first token comes from its prefill, so a block needs
    # sum(n - 1) lane-rounds of decoding: a whole number of rounds of the
    # cell's 4 lanes, or the loop's cycle would be four blocks long, not one
    assert (n_out - 1).sum() % 4 == 0


# -- every seed the same arrivals (PR 51) --------------------------------------

#: ``traffic.digest`` of every mix on seeds 0-2 as the parent of PR 51
#: generated it (vocabulary 50,257, a 36 s horizon, the rate and the warm
#: start its cell had then). The two mixes PR 51 put into cycles are held
#: with their cycle keys taken out again: the generator, not the file.
PARENT_DIGESTS = {
    "serve-decode": (1.75, 44, ["716dec64", "4808158d", "60e129b5"]),
    "serve-long-decode": (3.08, 21, ["ba1a83b8", "2a402185", "8c73a1b6"]),
    "serve-long-context": (0.28, 5, ["30624979", "f169d135", "cf7bcfc6"]),
    "serve-looped-decode": (0.126, 2, ["1940c1d9", "9e7b7bd7", "52a8b6f8"]),
    "serve-prefill": (None, 0, ["b119a338", "af453eb0", "05248c4a"]),
}
CYCLED = ("serve-decode", "serve-long-decode")


def without_cycles(mix):
    """The mix as it was before its gaps and warm start came in cycles."""
    plain = lambda spec_: {k: v for k, v in spec_.items()
                           if k not in ("order", "order_seed")}
    if mix["loop"] != "open" or "order" not in mix["arrivals"]:
        return mix
    return dict(mix, arrivals=plain(mix["arrivals"]),
                prompt_len=plain(mix["prompt_len"]),
                output_len=plain(mix["output_len"]), warm_start=True)


@pytest.mark.parametrize("name", sorted(PARENT_DIGESTS))
def test_a_mix_that_sets_no_cycle_generates_its_parent_s_requests(name):
    rate, warm, want = PARENT_DIGESTS[name]
    mix = without_cycles(load(name))
    assert (mix != load(name)) == (name in CYCLED)
    got = [traffic.digest(traffic.requests(
        mix, 50257, seed, rate=rate, horizon_s=36.0, warm_inflight=warm))
        for seed in (0, 1, 2)]
    assert got == want


@pytest.mark.parametrize("name", CYCLED)
def test_under_cycles_two_seeds_differ_in_token_ids_alone(name):
    mix = load(name)
    assert mix["warm_start"]["order"] == mix["arrivals"]["order"] == "cycle"
    seeds = {mix[k]["order_seed"] for k in
             ("arrivals", "prompt_len", "output_len", "warm_start")}
    assert len(seeds) == 4      # one order each, or long would meet long
    a, b = (traffic.requests(mix, 50257, seed, rate=7.0, horizon_s=36.0,
                             warm_inflight=21) for seed in (1, 2_500_000_007))
    assert len(a) == len(b) > 21 + 200
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    assert traffic.digest(a) != traffic.digest(b)
    # the warm start's residual lengths too, and they are not all alike
    assert len({r.max_new_tokens for r in a[:21]}) > 10
    assert all(r.due_s == 0.0 for r in a[:21]) and a[21].due_s > 0.0


@pytest.mark.parametrize("name", CYCLED)
def test_every_block_of_a_cycle_repeats_its_gaps_and_lengths(name):
    mix = load(name)
    k = mix["arrivals"]["stratum"]
    reqs = traffic.requests(mix, 50257, 3, rate=5.0, horizon_s=40.0)
    assert len(reqs) >= 4 * k
    gaps = np.diff([0.0] + [r.due_s for r in reqs])
    for first in range(k, 3 * k + 1, k):
        np.testing.assert_allclose(gaps[first:first + k], gaps[:k], rtol=1e-9)
        assert [len(r.prompt) for r in reqs[first:first + k]] \
            == [len(r.prompt) for r in reqs[:k]]
        assert [r.max_new_tokens for r in reqs[first:first + k]] \
            == [r.max_new_tokens for r in reqs[:k]]
    # a block is the stratum's gaps once each and takes k / rate seconds
    assert gaps[:k].sum() == pytest.approx(k / 5.0)
    assert len(set(np.round(gaps[:k], 9))) == k
    # no order is another's: short gaps do not always bring long prompts
    n_in = np.array([len(r.prompt) for r in reqs[:k]])
    n_out = np.array([r.max_new_tokens for r in reqs[:k]])
    for x, y in ((gaps[:k], n_in), (gaps[:k], n_out), (n_in, n_out)):
        assert abs(np.corrcoef(x, y)[0, 1]) < 0.5


def test_a_warm_start_that_is_true_pairs_by_the_run_s_seed():
    mix = dict(load("serve-long-decode"), warm_start=True)
    a, b = (traffic.requests(mix, 50257, seed, rate=3.0, horizon_s=10.0,
                             warm_inflight=21) for seed in (1, 2))
    left = [[r.max_new_tokens for r in reqs[:21]] for reqs in (a, b)]
    assert left[0] != left[1] and sum(left[0]) != sum(left[1])
    assert [r.due_s for r in a] == [r.due_s for r in b]     # gaps in cycles
