"""The suite's oracles for greedy decoding, in one place (ISSUE 63).

``dense_greedy`` is the reference's loop (model.py:322-356): a full
re-forward of the last ``block_size`` tokens a step through ``gpt.forward``,
argmax, append. ``solo_greedy`` is what a server's greedy stream of one
request is held to: the new tokens of that loop on the prompt alone.

One compiled program a stack, not one a (prompt length, budget): the window
is always ``(B, block_size)``, left-aligned and padded with zeros, and the
position read is an argument. A causal stack's logits at a position read
nothing that stands after it, so the padding is invisible
(``tests/test_generate.py::test_padding_after_a_position_is_not_read`` holds
that for a stack of every family the suite serves). The capacity route is the
exception: a token's room in an expert depends on how many tokens the call
holds, so ``padded=False`` forwards the exact length, a program a length.

Neither reads an engine, a pool or a server, nor ``generate``'s cached
forward: ``generate`` is called where it is the subject (``tests/
test_generate.py``, and the stacks whose cached path a test holds to
``dense_greedy``), never as an oracle.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from mingpt_distributed_tpu.models import gpt


#: What the session's shared programs are keyed by beside their arguments.
#: A patch of what a program traces does not show in its static config, so
#: under one key a test that patches would be served the unpatched trace (and
#: pass vacuously) and leave its own to the next test with an equal config.
#: ``tests/conftest.py`` gives every test that asks for ``monkeypatch`` a
#: value of its own, and what such a test computes is kept for it alone.
EPOCH = [0]


def shared_jit(fn, **static):
    """``jax.jit(fn, **static)`` for a program many tests call: one trace a
    (static arguments, shapes, epoch)."""
    names = (*static.pop("static_argnames", ()), "_epoch")
    program = jax.jit(lambda *args, _epoch, **kwargs: fn(*args, **kwargs),
                      static_argnames=names, **static)
    return lambda *args, **kwargs: program(*args, _epoch=EPOCH[0], **kwargs)


@partial(shared_jit, static_argnames=("cfg",))
def _next_tokens(params, window, length, *, cfg):
    """argmax of the logits at position ``length - 1`` of every row."""
    logits = gpt.forward(params, window, cfg)[0]
    return jnp.argmax(
        jax.lax.dynamic_index_in_dim(logits, length - 1, 1, keepdims=False),
        axis=-1)


def dense_greedy(params, cfg, idx, n, padded=True):
    """``idx`` (B, T0) and ``n`` greedy tokens after it, (B, T0 + n), by a
    full re-forward a step; past ``block_size`` the window slides, as the
    reference's does."""
    seq = np.asarray(idx, np.int32)
    if seq.ndim == 1:
        seq = seq[None]
    for _ in range(n):
        tail = seq[:, -cfg.block_size:]
        length = tail.shape[1]
        if padded:
            window = np.zeros((seq.shape[0], cfg.block_size), np.int32)
            window[:, :length] = tail
        else:
            window = tail
        nxt = _next_tokens(params, window, length, cfg=cfg)
        seq = np.concatenate([seq, np.asarray(nxt, np.int32)[:, None]], 1)
    return seq


#: (epoch, cfg, id of the first leaf, prompt) -> (params, the longest
#: continuation asked so far). ``params`` is kept so that the id stays its own.
_SOLO = {}


def solo_greedy(params, cfg, prompt, n):
    """The ``n`` tokens the dense loop emits after ``prompt`` alone, as a
    list. A (stack, prompt) is computed once a session at the longest budget
    asked: under greedy choice a shorter budget is its prefix."""
    prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
    key = (EPOCH[0], cfg, id(jax.tree.leaves(params)[0]), tuple(prompt))
    _, known = _SOLO.get(key, (None, []))
    if len(known) < n:
        # the capacity route alone reads its padding (a token's room in an
        # expert is a share of the call's tokens)
        padded = not cfg.n_experts or cfg.dropless
        known = dense_greedy(params, cfg, [prompt + known], n - len(known),
                             padded)[0, len(prompt):].tolist()
        _SOLO[key] = (params, known)
    return known[:n]
