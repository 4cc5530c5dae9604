"""GPT-2 as published, in plain ``jax.numpy`` and float32: the reference the
benchmark holds the program to. No kernel, no cache, no batching tricks, no
lower precision: every matmul runs under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul is
otherwise done in bf16 passes).

The equations are those of Radford et al. 2019 as released in
``openai-community/gpt2``: token plus learned position embedding; ``n_layer``
pre-LayerNorm blocks of causal multi-head attention and a 4x MLP with the
tanh GELU (``gelu_new``); a final LayerNorm; a linear head over the
vocabulary.

Departures from the release, both the program's and so the benchmark's:
the head is a matrix of its own (``head``), not the transposed token
embedding; weights are seeded random, not the trained ones.

``weights`` is a dict of float32 arrays, the per-layer ones stacked on a
leading layer axis (that is how the program keeps them, so no copy is made):

  wte (V, d)  wpe (P, d)  lnf_g, lnf_b (d,)  head (d, V)
  blocks: ln1_g, ln1_b, ln2_g, ln2_b (L, d); wq, wk, wv, wo (L, d, d);
          bq, bk, bv, bo (L, d); w_fc (L, d, 4d); b_fc (L, 4d);
          w_proj (L, 4d, d); b_proj (L, d)

``sizes`` holds the published keys ``n_head`` and ``layer_norm_epsilon``.

The twin (``harness/check.py``). ``hidden(..., act_dtype=jnp.bfloat16)`` is
the same code with every value rounded to that type where a model served in
it holds that type: the matmuls' weights, the embedding's output, every
matmul's output (its bias added), the GELU's, every residual sum, every
LayerNorm's output. LayerNorm and softmax stay float32 inside (the
configurations' ``departures``), and every sum of a matmul is still float32
at ``highest``: a bf16 matmul's products are exact and its sums float32, so
the twin errs as bf16 arithmetic must and no more. At ``None`` nothing is
rounded: the function of before, bit for bit. The check measures how far
the twin's cached rows lie from the unrounded run's and holds the program
to a multiple of that; this file states no tolerance.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.references.rounding import rounder


def weights_from_program(params) -> dict:
    """The program's parameter pytree (``models/gpt.py``) under the names
    above. Renames only: the arrays are shared, nothing is copied or cast."""
    blk = params["blocks"]
    rename = {"ln1_g": "ln1_scale", "ln1_b": "ln1_bias", "ln2_g": "ln2_scale",
              "ln2_b": "ln2_bias"}
    same = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo",
            "w_fc", "b_fc", "w_proj", "b_proj")
    return {
        "wte": params["wte"], "wpe": params["wpe"], "head": params["head"],
        "lnf_g": params["lnf_scale"], "lnf_b": params["lnf_bias"],
        "blocks": {**{k: blk[v] for k, v in rename.items()},
                   **{k: blk[k] for k in same}},
    }


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def hidden(weights, tokens, sizes, act_dtype=None):
    """tokens (B, T) int32 -> (final-LayerNorm hidden (B, T, d), keys and
    values of every layer, each (L, B, T, H, hd)). ``act_dtype``: the twin
    (module docstring); None: float32 throughout."""
    n_head, eps = sizes["n_head"], sizes["layer_norm_epsilon"]
    r = rounder(act_dtype)
    b, t = tokens.shape
    x = r(r(weights["wte"][tokens]) + r(weights["wpe"][:t]))
    d = x.shape[-1]
    hd = d // n_head
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, w):
        h = r(_layer_norm(x, w["ln1_g"], w["ln1_b"], eps))
        q = r(h @ r(w["wq"]) + w["bq"]).reshape(b, t, n_head, hd)
        k = r(h @ r(w["wk"]) + w["bk"]).reshape(b, t, n_head, hd)
        v = r(h @ r(w["wv"]) + w["bv"]).reshape(b, t, n_head, hd)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        scores = jnp.where(causal, scores, -jnp.inf)
        att = r(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v))
        x = r(x + r(att.reshape(b, t, d) @ r(w["wo"])) + w["bo"])
        h = r(_layer_norm(x, w["ln2_g"], w["ln2_b"], eps))
        inner = r(_gelu_new(r(h @ r(w["w_fc"]) + w["b_fc"])))
        x = r(x + r(inner @ r(w["w_proj"])) + w["b_proj"])
        return x, (k, v)

    with jax.default_matmul_precision("highest"):
        x, (ks, vs) = jax.lax.scan(block, x.astype(jnp.float32),
                                   weights["blocks"])
        x = r(_layer_norm(x, weights["lnf_g"], weights["lnf_b"], eps))
    return x, ks, vs


def logits(weights, x):
    """Hidden states (..., d) -> float32 logits (..., V)."""
    with jax.default_matmul_precision("highest"):
        return x @ weights["head"]


def loss(weights, tokens, targets, sizes):
    """Mean cross-entropy over the positions whose target is not -1."""
    x, _, _ = hidden(weights, tokens, sizes)
    logp = jax.nn.log_softmax(logits(weights, x), -1)
    valid = targets != -1
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, targets, 0)[..., None], -1)[..., 0]
    return -(picked * valid).sum() / valid.sum()
