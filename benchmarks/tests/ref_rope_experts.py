"""A rotary, RMSNorm, routed-experts decoder in plain ``jax.numpy`` and
float32: the reference of the fixture ``fixtures/rope-experts.json``, which
stands for no published model. It is the proof that an architecture the
program builds from ``GPTConfig`` enters a serving cell as files, and the
worked example of a reference that is not GPT-2's (``benchmarks/README.md``,
"Adding a configuration"). Every matmul runs under
``jax.default_matmul_precision("highest")``.

The equations (the block ``GPTConfig(rope, rmsnorm, swiglu, n_experts)``
builds, written from ``models/gpt.py`` and ``ops/moe.py`` and sharing no
code with them):

  x = wte[tokens]                                 no position table
  per layer:
    h = rms(x) * ln1_g
    q, k, v = h wq, h wk, h wv                    no biases; KV heads <= heads
    q, k = rotate(q), rotate(k)                   split-half rotary, base theta
    x = x + softmax(causal(q k^T / sqrt(hd))) v wo
    h = rms(x) * ln2_g
    z = h w_router                                E router logits, float32
    p = softmax(z)                                over all E experts
    the chosen k: those of the table the caller hands over, or, with no
    table or where a token's row of it has an entry under 0, the k largest
    p; their p renormalised to sum to 1 (k = 1, or
    ``norm_topk_prob`` false: the raw p)
    x = x + sum over the chosen e of
            gate_e * (silu(h w_eg[e]) * (h w_e1[e])) w_e2[e]
  x = rms(x) * lnf_g;  logits = x head

Every chosen expert computes its token: there is no capacity and nothing
drops, which the program matches only at ``moe_capacity_factor >= E / k``.
Keys are returned as they are cached: rotated.

The routed contract (``harness/check.py``). ``hidden`` takes an optional
table of experts ``(L, B, T, k)`` and also returns the router's logits
``(L, B, T, E)``: with bf16 activations a token whose k-th and (k+1)-th
logits lie closer than the rounding of the residual stream moves them goes
to another expert in the program than float32 sends it to, and the check
reads off the next layer's cached rows which way the program went and has
this file go the same way. The tolerances and the margin inside which a
route may be followed are the yardstick's; this file states none. In
float32 the program agrees with this file to 2e-5 (``tests/test_arch.py``),
with a table that holds its own choice as without one.

The twin: ``hidden(..., act_dtype=jnp.bfloat16)`` rounds to that type the
matmuls' weights, the embedding's output, every matmul's output, the rotated
queries and keys, the SwiGLU's inner product, every residual sum and every
norm's output; the norms, the softmax, the router and the gates stay
float32 inside. At ``None`` it is the function of before, bit for bit.

``weights``: wte (V, d), lnf_g (d,), head (d, V); blocks: ln1_g, ln2_g
(L, d); wq (L, d, H hd); wk, wv (L, d, KV hd); wo (L, H hd, d); w_router
(L, d, E); w_eg, w_e1 (L, E, d, f); w_e2 (L, E, f, d).

``sizes`` holds the published keys ``num_attention_heads``,
``num_key_value_heads``, ``num_experts_per_tok``, ``rms_norm_eps``,
``rope_theta`` and, optionally, ``norm_topk_prob`` (absent: true).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.references.rounding import rounder
from benchmarks.references.routes import chosen_experts


def weights_from_program(params) -> dict:
    """The program's parameter pytree (``models/gpt.py``) under the names
    above. Renames only: the arrays are shared, nothing is copied or cast."""
    blk = params["blocks"]
    same = ("wq", "wk", "wv", "wo", "w_router", "w_eg", "w_e1", "w_e2")
    return {
        "wte": params["wte"], "head": params["head"],
        "lnf_g": params["lnf_scale"],
        "blocks": {"ln1_g": blk["ln1_scale"], "ln2_g": blk["ln2_scale"],
                   **{k: blk[k] for k in same}},
    }


def _rms(x, g, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * g


def _rotate(x, theta):
    """(B, T, H, hd) turned by position: the pair (x[i], x[i + hd/2]) by the
    angle t * theta^(-2i / hd)."""
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _experts(h, w, top_k, renormalise, r, chosen=None):
    """(B, T, d) -> ((B, T, d), router logits (B, T, E)): every expert on
    every token, weighted by its gate, which is zero where the expert was
    not chosen. ``chosen`` (B, T, k): the experts to take; None, or a token's
    row with an entry under 0: the k of the largest probability."""
    z = h @ w["w_router"]                                       # (B, T, E)
    p = jax.nn.softmax(z, -1)
    chosen = chosen_experts(p, top_k, chosen)
    top = jnp.take_along_axis(p, chosen, -1)
    if renormalise:
        top = top / top.sum(-1, keepdims=True)
    gates = (jax.nn.one_hot(chosen, p.shape[-1]) * top[..., None]).sum(-2)
    inner = r(jax.nn.silu(r(jnp.einsum("btd,edf->btef", h, r(w["w_eg"]))))
              * r(jnp.einsum("btd,edf->btef", h, r(w["w_e1"]))))
    return r(jnp.einsum("btef,efd,bte->btd", inner, r(w["w_e2"]), gates)), z


def hidden(weights, tokens, sizes, experts=None, act_dtype=None):
    """tokens (B, T) int32 -> (final-RMSNorm hidden (B, T, d), keys (rotated)
    and values of every layer, each (L, B, T, KV, hd), the router's logits
    (L, B, T, E)). ``experts`` (L, B, T, k) int32: the experts every token
    takes in every layer (a row with an entry under 0: the router's own k
    best for that token there); None: the router's own k best. ``act_dtype``: the
    twin (module docstring); None: float32 throughout."""
    n_head, n_kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    top_k, eps = sizes["num_experts_per_tok"], sizes["rms_norm_eps"]
    theta = float(sizes["rope_theta"])
    renormalise = top_k > 1 and bool(sizes.get("norm_topk_prob", True))
    r = rounder(act_dtype)
    b, t = tokens.shape
    x = r(weights["wte"][tokens])
    d = x.shape[-1]
    hd = d // n_head
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, layer):
        w, chosen = layer
        h = r(_rms(x, w["ln1_g"], eps))
        q = r(_rotate(r(h @ r(w["wq"])).reshape(b, t, n_head, hd), theta))
        k = r(_rotate(r(h @ r(w["wk"])).reshape(b, t, n_kv, hd), theta))
        v = r(h @ r(w["wv"])).reshape(b, t, n_kv, hd)
        # a query head reads the KV head of its group
        kq, vq = (jnp.repeat(a, n_head // n_kv, axis=2) for a in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kq) / math.sqrt(hd)
        scores = jnp.where(causal, scores, -jnp.inf)
        att = r(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                           vq))
        x = r(x + r(att.reshape(b, t, d) @ r(w["wo"])))
        out, z = _experts(r(_rms(x, w["ln2_g"], eps)), w, top_k, renormalise,
                          r, chosen)
        return r(x + out), (k, v, z)

    with jax.default_matmul_precision("highest"):
        x, (ks, vs, router) = jax.lax.scan(
            block, x.astype(jnp.float32), (weights["blocks"], experts))
        x = r(_rms(x, weights["lnf_g"], eps))
    return x, ks, vs, router


def logits(weights, x):
    """Hidden states (..., d) -> float32 logits (..., V)."""
    with jax.default_matmul_precision("highest"):
        return x @ weights["head"]


def loss(weights, tokens, targets, sizes):
    """Mean cross-entropy over the positions whose target is not -1."""
    x = hidden(weights, tokens, sizes)[0]
    logp = jax.nn.log_softmax(logits(weights, x), -1)
    valid = targets != -1
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, targets, 0)[..., None], -1)[..., 0]
    return -(picked * valid).sum() / valid.sum()
