"""SmallThinker's decoder (PowerInfer's SmallThinker-21BA3B-Instruct and its
4B sibling) in plain ``jax.numpy`` and float32: the reference of
``configs/smallthinker-21b-a3b.json``. Every matmul runs under
``jax.default_matmul_precision("highest")``. It shares no code with the
program, keeps no cache and no ring: every layer attends the whole sequence
under its own mask, so the program's ring (a window layer's last 4,096 rows a
slot, addressed modulo the window) agrees with it only if the ring holds the
right rows at the right ages, for a lane under the window, one that crosses
it and one past it alike.

The equations, ``rms(x) = x / sqrt(mean(x^2) + eps)``, for layer ``l`` with
``H = num_attention_heads`` query heads over ``KV = num_key_value_heads``
heads of ``head_dim``, no bias anywhere:

  x = embed[tokens]                                 no position table
  per layer:
    h = rms(x) * input_norm
    z = h router                                    E logits, float32: the
      router reads the attention's input, so a token's experts are known
      before its attention has run
    the chosen k = ``moe_num_active_primary_experts``: those of the table
      the caller hands over, or, with no table or where a token's row of it
      has an entry under 0 (a layer the check has not followed yet), the k
      largest of z, ties to the lowest index
      (``references/routes.chosen_experts``)
    g = softmax(z)[chosen] / sum(softmax(z)[chosen])
      (``moe_primary_router_apply_softmax``, ``norm_topk_prob``: the softmax
      over the chosen alone)
    q = h q_proj -> (H, hd);  k = h k_proj, v = h v_proj -> (KV, hd)
    ``rope_layout[l]`` 1: q, k turned by position, all ``head_dim``
      dimensions, the halves ``(x[i], x[i + hd/2])`` by the angle
      ``t * rope_theta^(-2i / hd)``; 0: as projected (the layer carries no
      position)
    score = q . k / sqrt(hd) where position_k <= position_q and, where
      ``sliding_window_layout[l]`` is 1, position_q - position_k <
      ``sliding_window_size`` (the query's own position included); query
      head j reads KV head j // (H / KV)
    x = x + (softmax(score) v) o_proj
    h2 = rms(x) * post_norm
    x = x + sum over the chosen e of g_e * expert_e(h2)
    expert_e(h2) = (relu(h2 eg[e]) * (h2 eu[e])) ed[e]       ReGLU, width
      ``moe_ffn_hidden_size``; no shared expert, no scale
  x = rms(x) * norm;  logits = x head               untied, no softcap

Every layer is sparse and every chosen expert computes its token: there is
no capacity and nothing drops. The frequencies are worked out in float64 and
rounded once to float32.

What is returned as cached (``harness/check.py``): ``ks``, ``vs`` the keys
(rotated where the layer rotates: in the published layouts the layers that
attend every position rotate nothing) and the values **of the layers without
a window only**, in order, (L_full, B, T, KV, hd) each, and
``cached_layers(sizes)`` says which model layers those are. A window layer's
rows live in the program's pool under other names (its rings) and are held,
as a recurrent state is, by the plane above them and by the logits.

The routed contract. ``hidden`` takes an optional table of experts (L, B, T,
k) and returns a fourth array (L, B, T, E): every layer's router logits
``z``, which is what the architecture takes its k largest of. No
``route_rule``: the choice has no group limit. The tolerances and the margin
are the yardstick's; this file states none.

Weights arrive in the program's dtype (bfloat16) and are cast up where they
are used. The experts run as a loop over blocks of ``EXPERT_ROWS``
token-expert pairs sorted by expert, a block through its expert's three
matrices read out of the stacked leaves (``_chosen_experts``), so no copy of
a layer's 377M expert weights is ever whole and a token pays for its 6
experts and not for 64 (the form ``references/laguna.py`` took when every
token through every expert cost its check 281 s: PERF.md, PR 59). The
attention runs over blocks of ``QUERY_BLOCK`` queries against the keys their
mask can reach, so the (H, T, T) scores of a 6k prompt never are whole. All
are the same sums in another order.

``sizes`` holds the published keys ``head_dim``, ``num_attention_heads``,
``num_key_value_heads``, ``rope_layout``, ``sliding_window_layout``,
``sliding_window_size``, ``rope_theta``, ``rms_norm_eps``,
``moe_num_active_primary_experts``, ``moe_primary_router_apply_softmax`` and
``norm_topk_prob``.

The twin (``harness/check.py``). ``hidden(..., act_dtype=jnp.bfloat16)`` is
the same code with every value rounded to that type where the published
model holds that type: the embedding's output, every matmul's output, the
rotated keys and queries, the attention's output, the ReGLU's inner product,
every residual sum, every RMSNorm's output. The norms, the softmax, the
router's logits and probabilities, the choice and the experts' gates stay
float32 inside, as the configuration's ``departures`` say the program keeps
them, and every sum of a matmul is still float32 at ``highest``. At ``None``
nothing is rounded. The twin takes the same table of experts as the
unrounded run, so the two differ by rounding alone and never by a route.

Departures from the published description: ReLU gating, the router's place
and its input (the normed stream the attention reads) are the family's
description and not keys of this configuration (``assumed`` in its file);
none other intended.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references.rounding import rounder
from benchmarks.references.routes import chosen_experts

#: token-expert pairs one step of the experts' loop takes through an expert
EXPERT_ROWS = 128
#: queries whose scores against their keys exist at one time
QUERY_BLOCK = 512


def cached_layers(sizes):
    """The model layers whose rows are ``ks[i]``, ``vs[i]``: the layers
    that attend every position (no window), in order."""
    return tuple(i for i, windowed in enumerate(
        sizes["sliding_window_layout"]) if not windowed)


def weights_from_program(params) -> dict:
    """The program's parameter pytree (``models/gpt.py``) under the names
    above. Renames only: the arrays are shared, nothing is copied or cast.
    ``full`` and ``window`` stack the attention of the layers without and
    with a window, ``moe`` the routed MLPs, each in layer order."""
    def attention(blk):
        return {"input_norm": blk["ln1_scale"], "q_proj": blk["wq"],
                "k_proj": blk["wk"], "v_proj": blk["wv"], "o_proj": blk["wo"]}

    moe = params["blocks"]
    out = {
        "embed": params["wte"], "head": params["head"],
        "norm": params["lnf_scale"],
        "full": attention(params["full_attn_blocks"]),
        "moe": {"post_norm": moe["ln2_scale"], "router": moe["w_router"],
                "eg": moe["w_eg"], "eu": moe["w_e1"], "ed": moe["w_e2"]},
    }
    if "window_attn_blocks" in params:
        out["window"] = attention(params["window_attn_blocks"])
    return out


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * _f32(g)


def _rotate(x, theta: float):
    """(B, T, H, hd) turned by position: every dimension of a head, the
    halves ``(i, i + hd/2)``."""
    t, hd = x.shape[1], x.shape[-1]
    i = np.arange(hd // 2, dtype=np.float64)
    freq = jnp.asarray(float(theta) ** (-2.0 * i / hd), jnp.float32)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attend(q, k, v, window):
    """Causal softmax attention, q (B, T, H, hd) over k, v (B, T, KV, hd),
    query head j reading KV head ``j // (H / KV)``: a block of queries
    against the keys from its window's start (``window`` None: from the
    first) to the block's end."""
    b, t, h, hd = q.shape
    kv = k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(b, t, kv, h // kv, hd)
    out = []
    for a in range(0, t, QUERY_BLOCK):
        e = min(a + QUERY_BLOCK, t)
        s = 0 if window is None else max(0, a - window + 1)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg[:, a:e], k[:, s:e]) * scale
        behind = jnp.arange(a, e)[:, None] - jnp.arange(s, e)[None, :]
        seen = behind >= 0
        if window is not None:
            seen = seen & (behind < window)
        scores = jnp.where(seen, scores, -jnp.inf)
        out.append(jnp.einsum("bkgqs,bskd->bqkgd",
                              jax.nn.softmax(scores, -1), v[:, s:e]))
    return jnp.concatenate(out, axis=1).reshape(b, t, h, hd)


def _chosen_experts(h, stack, at, chosen, g, r):
    """``sum over a token's chosen e of g_e * expert_e(h)``, (B, T, d), an
    expert at a time over the tokens that chose it. The token-expert pairs
    are sorted by expert and laid out with each expert's pairs padded to
    whole blocks of ``EXPERT_ROWS``; a loop takes the blocks that hold a
    pair through their expert's three matrices, read out of ``stack`` (all
    layers' (L, E, ...)) at layer ``at``; each pair's output is then read
    back from where it lies and weighed by its gate. The same sums as every
    token through every expert under a gate that is zero where the expert
    was not chosen, less the zeros."""
    b, t, d = h.shape
    k, rows = chosen.shape[-1], EXPERT_ROWS
    n = b * t * k
    leaves = {name: stack[name].reshape(-1, *stack[name].shape[2:])
              for name in ("eg", "eu", "ed")}
    e = stack["eg"].shape[1]
    tokens = h.reshape(b * t, d)
    flat = chosen.reshape(n)
    order = jnp.argsort(flat, stable=True)        # the pairs, by expert
    sizes = jnp.bincount(flat, length=e)
    first_pair = jnp.cumsum(sizes) - sizes        # an expert's first in `order`
    blocks = -(-sizes // rows)
    first_block = jnp.cumsum(blocks) - blocks

    def one_block(j, laid):
        ex = jnp.searchsorted(first_block + blocks, j, side="right")
        within = (j - first_block[ex]) * rows + jnp.arange(rows)
        pair = order[jnp.minimum(first_pair[ex] + within, n - 1)]
        x = jnp.where((within < sizes[ex])[:, None], tokens[pair // k], 0.0)
        eg, eu, ed = (_f32(leaves[name][at * e + ex])
                      for name in ("eg", "eu", "ed"))
        out = r(r(jnp.maximum(r(x @ eg), 0.0) * r(x @ eu)) @ ed)
        return jax.lax.dynamic_update_slice_in_dim(laid, out, j * rows, 0)

    laid = jax.lax.fori_loop(
        0, blocks.sum(), one_block,
        jnp.zeros(((-(-n // rows) + e) * rows, d), jnp.float32))
    # where each pair lies: its expert's first block, then its place among
    # that expert's pairs
    of_sorted = flat[order]
    lies = first_block[of_sorted] * rows + jnp.arange(n) - first_pair[of_sorted]
    out = laid[lies[jnp.argsort(order)]].reshape(b, t, k, d)
    return (out * g[..., None]).sum(-2)


def _route(h, router, sizes, table):
    """(B, T, d) normed activations -> (the chosen experts (B, T, k), their
    gates (B, T, k), the router's logits (B, T, E)), all float32 inside."""
    if not sizes["moe_primary_router_apply_softmax"]:
        raise ValueError(
            "a router without a softmax is not written here: the "
            "configuration states moe_primary_router_apply_softmax true")
    z = h @ _f32(router)
    chosen = chosen_experts(z, sizes["moe_num_active_primary_experts"], table)
    g = jnp.take_along_axis(jax.nn.softmax(z, -1), chosen, -1)
    if sizes["norm_topk_prob"]:
        g = g / g.sum(-1, keepdims=True)
    return chosen, g, z


def hidden(weights, tokens, sizes, experts=None, act_dtype=None):
    """tokens (B, T) int32 -> (final-RMSNorm hidden (B, T, d); the keys and
    the values of the layers without a window (L_full, B, T, KV, hd) each;
    the router's logits of every layer (L, B, T, E)). ``experts`` (L, B, T,
    k) int32: the experts every token takes in every layer (a row with an
    entry under 0: that token's k largest logits there); None: the k
    largest. ``act_dtype``: the twin (module docstring); None: float32
    throughout."""
    hd, kv = sizes["head_dim"], sizes["num_key_value_heads"]
    n_head, eps = sizes["num_attention_heads"], sizes["rms_norm_eps"]
    windowed, turned = sizes["sliding_window_layout"], sizes["rope_layout"]
    r = rounder(act_dtype)
    b, t = tokens.shape

    ks, vs, router = [], [], []
    seen = {"full": 0, "window": 0}
    moe = weights["moe"]
    with jax.default_matmul_precision("highest"):
        x = r(_f32(weights["embed"][tokens]))
        for layer, (has_window, rotates) in enumerate(zip(windowed, turned)):
            kind = "window" if has_window else "full"
            a = {n: leaf[seen[kind]] for n, leaf in weights[kind].items()}
            seen[kind] += 1
            h = r(_rms(x, a["input_norm"], eps))
            # the route, from the attention's input
            chosen, g, z = _route(
                h, moe["router"][layer], sizes,
                None if experts is None else experts[layer])
            router.append(z)
            q = r(h @ _f32(a["q_proj"])).reshape(b, t, n_head, hd)
            k = r(h @ _f32(a["k_proj"])).reshape(b, t, kv, hd)
            v = r(h @ _f32(a["v_proj"])).reshape(b, t, kv, hd)
            if rotates:
                q = r(_rotate(q, sizes["rope_theta"]))
                k = r(_rotate(k, sizes["rope_theta"]))
            o = r(_attend(q, k, v, sizes["sliding_window_size"]
                          if has_window else None))
            x = r(x + r(o.reshape(b, t, n_head * hd) @ _f32(a["o_proj"])))
            if not has_window:
                ks.append(k)
                vs.append(v)
            # the experts, on the MLP's input
            h2 = r(_rms(x, moe["post_norm"][layer], eps))
            x = r(x + r(_chosen_experts(h2, moe, layer, chosen, g, r)))
        x = r(_rms(x, weights["norm"], eps))
    return x, jnp.stack(ks), jnp.stack(vs), jnp.stack(router)


def logits(weights, x):
    """Hidden states (..., d) -> float32 logits (..., V)."""
    with jax.default_matmul_precision("highest"):
        return x @ _f32(weights["head"])


def loss(weights, tokens, targets, sizes):
    """Mean cross-entropy over the positions whose target is not -1."""
    x = hidden(weights, tokens, sizes)[0]
    logp = jax.nn.log_softmax(logits(weights, x), -1)
    valid = targets != -1
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, targets, 0)[..., None], -1)[..., 0]
    return -(picked * valid).sum() / valid.sum()
