"""The operation counts against hand counts for both configurations."""

import json
import os

import pytest

from benchmarks.harness import flops, spec


def config(name):
    with open(os.path.join(spec.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name, want", [
    # 6 * (12 * (4*768^2 + 2*768*3072) + 768*50257) + 12*12*768*1024
    ("gpt2-124m", 854_438_400),
    # 6 * (48 * (4*1600^2 + 2*1600*6400) + 1600*50257) + 12*48*1600*1024
    ("gpt2-xl", 10_273_545_600),
])
def test_train_flops_per_token(name, want):
    assert flops.train_flops_per_token(config(name), 1024) == want


@pytest.mark.parametrize("name, want", [
    ("gpt2-124m", 7 * 12 * 32 * 768 * 1024 ** 2),
    ("gpt2-xl", 7 * 48 * 32 * 1600 * 1024 ** 2),
])
def test_flash_flops_are_seven_causal_matmuls_a_layer(name, want):
    cfg = config(name)
    assert flops.flash_train_flops(cfg, 32, 1024) == want
    # the attention term of the 6N model is the full square, 12/7 of it
    assert flops.flash_train_flops(cfg, 1, 1024) / 1024 * 12 / 7 == \
        12 * cfg["n_layer"] * cfg["n_embd"] * 1024


def test_a_cached_row_of_gpt2_is_36_864_bytes():
    assert flops.kv_row_bytes(config("gpt2-124m")) == 36_864
    assert flops.kv_row_bytes(config("gpt2-xl")) * 1024 == 314_572_800
