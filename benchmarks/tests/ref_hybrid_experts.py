"""A routed hybrid stack in plain ``jax.numpy`` and float32: the reference of
the fixture ``fixtures/hybrid-experts.json``, which stands for no published
model and which no ``GPTConfig`` builds (``config._validate_hybrid`` refuses
experts in a hybrid stack). It is the worked example of a reference whose
rows skip layers (``cached_layers``), and with ``hybrid_standin.py`` the
proof that ``check.serve_verdict`` follows a routed model's routes across
layers that cache no rows before any program has them
(``benchmarks/README.md``, "Routed experts and the verdict"). Every matmul
runs under ``jax.default_matmul_precision("highest")``.

The equations, ``rms(x) = x / sqrt(mean(x^2) + eps)``, a layer of either
kind (``layer_types``: ``rows`` or ``state``) with the same expert MLP:

  x = wte[tokens]                                 no position table
  per layer:
    h = rms(x) * ln1_g
    a ``rows`` layer (softmax attention, no rotation):
      q, k, v = h wq, h wk, h wv                  H heads of hd
      o = softmax(causal(q k^T / sqrt(hd))) v
    a ``state`` layer (a gated linear state behind a convolution):
      q, k, v = silu(conv(h wq)), silu(conv(h wk)), silu(conv(h wv))
          conv: causal, depthwise, ``conv_kernel`` taps (w_conv: one set of
          taps a channel for each of q, k, v): tap j weighs position t - j
      q, k = q / |q|, k / |k|                     a head
      a_t = exp(-softplus(h w_a))                 one decay a head, in (0, 1)
      S_t = a_t S_{t-1} + k_t v_t^T               float32, hd x hd a head
      o_t = S_t^T q_t / sqrt(hd);  o = rms(o) * o_norm_g       a head
    x = x + (o * sigmoid(h wg)) wo                an output gate, both kinds
    h = rms(x) * ln2_g
    s = sigmoid(h w_router)                       E scores, float32
    the chosen k: those of the table the caller hands over, or, with no
    table or where a token's row of it has an entry under 0, the k largest s
    g = s[chosen], over their sum under ``norm_topk_prob``
    x = x + sum over the chosen e of g_e * (silu(h w_eg[e]) * (h w_e1[e])) w_e2[e]
  x = rms(x) * lnf_g;  logits = x head

What is cached: a ``rows`` layer's keys and values; a ``state`` layer keeps
its state and its convolution's last inputs, which are no rows, so ``ks`` and
``vs`` have a plane a ``rows`` layer, in order, and ``cached_layers`` says
which model layers those are. The router's scores come for every layer.

The routed contract and the twin are ``ref_rope_experts.py``'s, word for
word: ``experts=`` (L, B, T, k) with -1 for "the router's own choice here",
the scores (L, B, T, E) as the fourth array, ``act_dtype`` rounding the
embedding's output, every matmul's and convolution's output, the normed
queries and keys, the SwiGLU's inner product, every residual sum and every
norm's output, and keeping float32 inside the norms, the softmax, the
state, the router and the gates.

``gate_mask`` (L, B, T, k) is no part of any contract: the stand-in engine
plants a dropped route with it (a chosen expert whose gate is 0, the others
as they were), the fault a capacity-bound dispatch makes.

``weights``: wte (V, d), lnf_g (d,), head (d, V); blocks, each (L, ...):
ln1_g, ln2_g (d,); wq, wk, wv, wg (d, H hd); wo (H hd, d); w_conv (3, K,
H hd); w_a (d, H); o_norm_g (hd,); w_router (d, E); w_eg, w_e1 (E, d, f);
w_e2 (E, f, d). A ``rows`` layer reads no w_conv, w_a or o_norm_g.

``sizes`` holds ``layer_types``, ``num_attention_heads``, ``head_dim``,
``num_experts_per_tok``, ``norm_topk_prob``, ``rms_norm_eps``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.references.rounding import rounder
from benchmarks.references.routes import chosen_experts


def cached_layers(sizes) -> tuple:
    """The model layers whose rows are ``ks[i]``, ``vs[i]``, in order."""
    return tuple(i for i, kind in enumerate(sizes["layer_types"])
                 if kind == "rows")


def weights_from_program(params) -> dict:
    """The stand-in engine keeps its weights under the names above."""
    return params


def init_weights(key, sizes) -> dict:
    """Seeded weights at the fixture's sizes: N(0, 0.02) projections, the
    residual ones over sqrt(2L), norms' scales near 1, taps near a quarter,
    and experts' weights of a size at which the MLP carries the stream."""
    d, n_head, hd = sizes["hidden_size"], sizes["num_attention_heads"], \
        sizes["head_dim"]
    n_layer, e = len(sizes["layer_types"]), sizes["num_experts"]
    f, v, taps = sizes["moe_intermediate_size"], sizes["vocab_size"], \
        sizes["conv_kernel"]
    keys = iter(jax.random.split(key, 32))
    normal = lambda shape, std: std * jax.random.normal(
        next(keys), shape, jnp.float32)
    scale = lambda shape: 1.0 + 0.1 * jax.random.normal(
        next(keys), shape, jnp.float32)
    out = 0.02 / math.sqrt(2 * n_layer)
    return {
        "wte": normal((v, d), 0.15), "lnf_g": scale((d,)),
        "head": normal((d, v), 0.02),
        "blocks": {
            "ln1_g": scale((n_layer, d)), "ln2_g": scale((n_layer, d)),
            **{k: normal((n_layer, d, n_head * hd), 0.05)
               for k in ("wq", "wk", "wv", "wg")},
            "wo": normal((n_layer, n_head * hd, d), out),
            "w_conv": 0.25 + normal((n_layer, 3, taps, n_head * hd), 0.1),
            "w_a": normal((n_layer, d, n_head), 0.5),
            "o_norm_g": scale((n_layer, hd)),
            "w_router": normal((n_layer, d, e), 0.02),
            "w_eg": normal((n_layer, e, d, f), 0.05),
            "w_e1": normal((n_layer, e, d, f), 0.05),
            "w_e2": normal((n_layer, e, f, d), 0.05),
        },
    }


def _rms(x, g, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * g


def _conv(x, taps):
    """(B, T, C) under (K, C) taps: position t takes tap j of position
    t - j, nothing of what comes after it."""
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    return sum(padded[:, taps.shape[0] - 1 - j:taps.shape[0] - 1 - j + t]
               * taps[j] for j in range(taps.shape[0]))


def _rows_mixer(h, w, n_head, hd, r):
    b, t, _ = h.shape
    q, k, v = (r(h @ r(w[name])).reshape(b, t, n_head, hd)
               for name in ("wq", "wk", "wv"))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    out = r(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v))
    return out, k, v


def _state_mixer(h, w, n_head, hd, eps, r):
    b, t, _ = h.shape
    q, k, v = (r(jax.nn.silu(r(_conv(r(h @ r(w[name])), w["w_conv"][i]))))
               .reshape(b, t, n_head, hd)
               for i, name in enumerate(("wq", "wk", "wv")))
    q, k = (r(a / jnp.sqrt((a ** 2).sum(-1, keepdims=True) + eps))
            for a in (q, k))
    decay = jnp.exp(-jax.nn.softplus(h @ w["w_a"]))             # (B, T, H)

    def step(state, at):                    # state (B, H, hd, hd), float32
        q_t, k_t, v_t, a_t = at
        state = a_t[..., None, None] * state \
            + k_t[..., :, None] * v_t[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _, out = jax.lax.scan(
        step, jnp.zeros((b, n_head, hd, hd), jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, decay)))
    out = jnp.moveaxis(out, 0, 1) / math.sqrt(hd)               # (B, T, H, hd)
    return r(_rms(out, w["o_norm_g"], eps))


def _experts(h, w, top_k, renormalise, r, chosen, gate_mask):
    """(B, T, d) -> ((B, T, d), the router's scores (B, T, E)): every
    expert on every token under a gate that is zero where it was not
    chosen."""
    s = jax.nn.sigmoid(h @ w["w_router"])
    chosen = chosen_experts(s, top_k, chosen)
    g = jnp.take_along_axis(s, chosen, -1)
    if renormalise:
        g = g / g.sum(-1, keepdims=True)
    if gate_mask is not None:
        g = g * gate_mask
    gates = (jax.nn.one_hot(chosen, s.shape[-1]) * g[..., None]).sum(-2)
    inner = r(jax.nn.silu(r(jnp.einsum("btd,edf->btef", h, r(w["w_eg"]))))
              * r(jnp.einsum("btd,edf->btef", h, r(w["w_e1"]))))
    return r(jnp.einsum("btef,efd,bte->btd", inner, r(w["w_e2"]), gates)), s


def hidden(weights, tokens, sizes, experts=None, act_dtype=None,
           gate_mask=None):
    """tokens (B, T) int32 -> (final-RMSNorm hidden (B, T, d); the keys and
    the values of the ``rows`` layers, each (planes, B, T, H, hd); the
    router's scores of every layer (L, B, T, E)). ``experts`` (L, B, T, k)
    int32: the experts every token takes in every layer (a row with an entry
    under 0: the router's own k best for that token there); None: the
    router's own k best. ``act_dtype``: the twin; None: float32 throughout.
    ``gate_mask``: module docstring."""
    kinds = sizes["layer_types"]
    n_head, hd = sizes["num_attention_heads"], sizes["head_dim"]
    top_k, eps = sizes["num_experts_per_tok"], sizes["rms_norm_eps"]
    renormalise = top_k > 1 and bool(sizes.get("norm_topk_prob", True))
    r = rounder(act_dtype)
    b, t = tokens.shape
    ks, vs, router = [], [], []
    with jax.default_matmul_precision("highest"):
        x = r(weights["wte"][tokens])
        for layer, kind in enumerate(kinds):
            w = {name: a[layer] for name, a in weights["blocks"].items()}
            h = r(_rms(x, w["ln1_g"], eps))
            if kind == "rows":
                out, k, v = _rows_mixer(h, w, n_head, hd, r)
                ks.append(k)
                vs.append(v)
            elif kind == "state":
                out = _state_mixer(h, w, n_head, hd, eps, r)
            else:
                raise ValueError(f"unknown kind of layer {kind!r}")
            gated = r(out.reshape(b, t, n_head * hd)
                      * jax.nn.sigmoid(h @ w["wg"]))
            x = r(x + r(gated @ r(w["wo"])))
            out, s = _experts(
                r(_rms(x, w["ln2_g"], eps)), w, top_k, renormalise, r,
                None if experts is None else experts[layer],
                None if gate_mask is None else gate_mask[layer])
            x = r(x + out)
            router.append(s)
        x = r(_rms(x, weights["lnf_g"], eps))
    return x, jnp.stack(ks), jnp.stack(vs), jnp.stack(router)


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
