"""``serve_tok_s`` over whole blocks: the median of the blocks' rates, which a
stall inside one block does not move."""

import types

import numpy as np

from benchmarks.harness import serve_cell, traffic


def sent(index, t_ref, n_prompt=10, n_out=5, reason="length"):
    handle = types.SimpleNamespace(
        finish_reason=reason, prompt_used=[0] * n_prompt, tokens=[0] * n_out,
        request_id=f"r{index}")
    req = traffic.Req(index=index, due_s=None,
                      prompt=np.zeros(n_prompt, np.int32),
                      max_new_tokens=n_out)
    return serve_cell.Sent(req, handle, t_ref, 0.0)


def play_of(times, k=4, **kw):
    play = serve_cell.Play(n_slots=4, block_size=64, block_requests=k)
    play.w0, play.w1 = 10.0, 40.0
    play.sent = [sent(i, t, **kw) for i, t in enumerate(times)]
    return play


def test_block_rates_are_tokens_over_the_time_between_block_starts():
    # blocks of 4 requests of 15 tokens, one a second: 60 tokens in 4 s
    play = play_of([8.0 + i for i in range(22)])
    # request 0 (t=8) starts before the window; 4, 8, 12, 16 start whole
    # blocks inside it; the block of 20 has no successor
    assert play.block_rates().tolist() == [15.0] * 4


def test_a_stall_in_one_block_moves_the_mean_and_not_the_median():
    times = [10.0 + i for i in range(30)]
    times[13:] = [t + 2.0 for t in times[13:]]      # 2 s held inside block 3
    rates = play_of(times).block_rates()
    assert sorted(rates.tolist())[0] == 10.0 and np.median(rates) == 15.0


def test_a_block_with_an_unfinished_request_is_left_out():
    play = play_of([10.0 + i for i in range(13)])
    play.sent[5].handle.finish_reason = "cancelled"
    assert play.block_rates().tolist() == [15.0, 15.0]


def test_without_blocks_there_are_no_rates():
    assert len(play_of([10.0, 11.0], k=0).block_rates()) == 0
