"""Laguna's decoder (``model_type`` ``laguna``: poolside's Laguna-XS.2 and its
siblings) in plain ``jax.numpy`` and float32: the reference of
``configs/laguna-xs.2.json``. Every matmul runs under
``jax.default_matmul_precision("highest")``. It shares no code with the
program, keeps no cache and no ring: every layer attends the whole sequence
under its kind's mask, so the program's ring (a window layer's last 512 rows
a slot, addressed modulo the window) agrees with it only if the ring holds
the right rows at the right ages.

The equations, ``rms(x) = x / sqrt(mean(x^2) + eps)``, for layer ``l`` of
``kind = layer_types[l]`` with ``H = num_attention_heads_per_layer[l]`` query
heads over ``KV = num_key_value_heads`` heads of ``head_dim``:

  x = embed[tokens]                                 no position table
  per layer:
    h = rms(x) * input_norm
    q = h q_proj -> (H, hd);  k = h k_proj, v = h v_proj -> (KV, hd)
    q, k = rotate_kind(q, k, position)
    score = q . k / sqrt(hd) where position_k <= position_q and, in a
      ``sliding_attention`` layer, position_q - position_k < sliding_window
      (the query's own position included); query head j reads KV head
      j // (H / KV)
    o = softmax(score) v
    g = sigmoid(h gate) -> (H,)                     one gate a head and token
    x = x + concat_j(g_j * o_j) o_proj
    h = rms(x) * post_norm
    ``mlp_layer_types[l]`` "dense":
      x = x + (silu(h gate_proj) * (h up_proj)) down_proj
    "sparse":
      z = h router                                  E logits, float32
      p = softmax(z)
      the chosen k: those of the table the caller hands over, or, with no
      table or where a token's row of it has an entry under 0 (a layer the
      check has not followed yet), the k largest of z, ties to the lowest
      index (``references/routes.chosen_experts``)
      w = p[chosen] / sum(p[chosen])                (``norm_topk_prob``)
      x = x + moe_routed_scaling_factor * sum over the chosen e of
              w_e * expert_e(h) + shared(h)
      expert_e(h) = (silu(h eg[e]) * (h eu[e])) ed[e]; shared the same MLP
      of ``shared_expert_intermediate_size``
  x = rms(x) * norm;  logits = x head               untied, no softcap

``rotate_kind`` turns the first ``rot = head_dim * partial_rotary_factor``
dimensions of a head, the halves ``(x[i], x[i + rot/2])`` by the angle
``t * inv_freq_i``, and passes the others through. ``sliding_attention``:
``partial_rotary_factor`` 1, ``inv_freq_i = theta^(-2i / rot)``, theta
10,000. ``full_attention``: ``partial_rotary_factor`` 0.5 and YaRN as the
published ``rope_parameters`` state it: ``extra_i = theta^(-2i / rot)``,
``inter_i = extra_i / factor``; ``low``, ``high`` the floor and the ceiling of
``rot * ln(original_max_position_embeddings / (beta * 2 pi)) / (2 ln
theta)`` for ``beta_fast`` and ``beta_slow``, clipped to ``[0, rot - 1]``;
``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i = inter_i *
ramp_i + extra_i * (1 - ramp_i)``; the cosine and the sine both times
``attention_factor``. The frequencies are worked out in float64 and rounded
once to float32.

Every chosen expert computes its token: there is no capacity and nothing
drops.

What is returned as cached (``harness/check.py``): ``ks``, ``vs`` the
rotated keys and the values **of the full-attention layers only**, in
order, (L_full, B, T, KV, hd) each, and ``cached_layers(sizes)`` says which
model layers those are. A window layer's rows live in the program's pool
under other names (its rings) and are held, as a recurrent state is, by the
plane above them and by the logits.

The routed contract. ``hidden`` takes an optional table of experts (L, B, T,
k), ``L`` counting every model layer, and returns a fourth array (L, B, T,
E): for a sparse layer the router's logits ``z``, which is what the
architecture takes its k largest of. For a dense layer the table is ignored
and the row returned has its first k entries +1 and the rest -1: a gap of 2,
so the check finds no near-tie where there is no choice. No ``route_rule``:
the choice has no group limit. The tolerances and the margin are the
yardstick's; this file states none.

Weights arrive in the program's dtype (bfloat16) and are cast up where they
are used. The experts run as a loop over blocks of ``EXPERT_ROWS``
token-expert pairs sorted by expert, a block through its expert's three
matrices read out of the stacked leaves (``_chosen_experts``), so no copy of
a layer's 805M expert weights is ever whole and a token pays for its 8
experts and not for 256: the check runs some 270 forwards a case, and with
every token through every expert under a zero gate, as kanana's reference
runs its 128, a forward at 4k positions is 27 TFLOP in float32 and three
cases do not fit a run's 360 s (my chip runs, PR 59). The attention runs over
blocks of ``QUERY_BLOCK`` queries against the keys their mask can reach, so
the (H, T, T) scores of a 4k prompt never are either. All are the same sums
in another order.

``sizes`` holds the published keys ``head_dim``, ``num_key_value_heads``,
``num_attention_heads_per_layer``, ``layer_types``, ``mlp_layer_types``,
``sliding_window``, ``rope_parameters``, ``rms_norm_eps``,
``num_experts_per_tok``, ``moe_routed_scaling_factor`` and, where present,
``norm_topk_prob`` (the sibling configuration's key; true where absent,
which the configuration lists under ``assumed``) and ``gating``.

The twin (``harness/check.py``). ``hidden(..., act_dtype=jnp.bfloat16)`` is
the same code with every value rounded to that type where the published
model holds that type: the embedding's output, every matmul's output, the
rotated keys and queries, the attention's output and its gated form, the
SwiGLU's inner product, every residual sum, every RMSNorm's output. The
norms, the softmax, the heads' gates, the router's logits and
probabilities, the choice and the experts' gates stay float32 inside, as
the configuration's ``departures`` say the program keeps them, and every
sum of a matmul is still float32 at ``highest``. At ``None`` nothing is
rounded. The twin takes the same table of experts as the unrounded run, so
the two differ by rounding alone and never by a route.

Departures from the published description: the router's softmax with the
chosen probabilities renormalised, and a gate a head, are the family's
convention and not keys of this configuration (``assumed`` in its file);
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

FULL, WINDOW = "full_attention", "sliding_attention"


def cached_layers(sizes):
    """The model layers whose rows are ``ks[i]``, ``vs[i]``: the
    full-attention layers, in order."""
    return tuple(i for i, kind in enumerate(sizes["layer_types"])
                 if kind == FULL)


def weights_from_program(params) -> dict:
    """The program's parameter pytree (``models/gpt.py``) under the names
    above. Renames only: the arrays are shared, nothing is copied or cast.
    ``full`` and ``window`` stack the attention of the layers of each kind,
    ``dense`` and ``moe`` the MLPs of each kind, each in layer order."""
    def attention(blk):
        out = {"input_norm": blk["ln1_scale"], "q_proj": blk["wq"],
               "k_proj": blk["wk"], "v_proj": blk["wv"], "o_proj": blk["wo"]}
        if "w_hg" in blk:
            out["gate"] = blk["w_hg"]
        return out

    moe = params["blocks"]
    out = {
        "embed": params["wte"], "head": params["head"],
        "norm": params["lnf_scale"],
        "full": attention(params["full_attn_blocks"]),
        "moe": {"post_norm": moe["ln2_scale"], "router": moe["w_router"],
                "eg": moe["w_eg"], "eu": moe["w_e1"], "ed": moe["w_e2"],
                "shared_gate": moe["w_sg"], "shared_up": moe["w_su"],
                "shared_down": moe["w_sd"]},
    }
    if "window_attn_blocks" in params:
        out["window"] = attention(params["window_attn_blocks"])
    if "dense_blocks" in params:
        dense = params["dense_blocks"]
        out["dense"] = {"post_norm": dense["ln2_scale"],
                        "gate_proj": dense["w_gate"],
                        "up_proj": dense["w_up"],
                        "down_proj": dense["w_down"]}
    return out


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * _f32(g)


def inv_freq(rope: dict, head_dim: int) -> np.ndarray:
    """(rot / 2,) float64: the frequencies one kind of layer rotates by,
    from its entry of the published ``rope_parameters``."""
    rot = int(head_dim * rope["partial_rotary_factor"])
    i = np.arange(rot // 2, dtype=np.float64)
    extra = float(rope["rope_theta"]) ** (-2.0 * i / rot)
    if rope["rope_type"] == "default":
        return extra
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not written here")
    inter = extra / rope["factor"]

    def pair_of(beta):
        return rot * math.log(rope["original_max_position_embeddings"]
                              / (beta * 2 * math.pi)) \
            / (2 * math.log(rope["rope_theta"]))

    low = max(math.floor(pair_of(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rope["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001       # the published code's guard
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def _rotate(x, rope: dict):
    """(B, T, H, hd) turned by position: the first ``rot`` dimensions of a
    head, halves ``(i, i + rot/2)``, the others passed through."""
    t, hd = x.shape[1], x.shape[-1]
    freq = jnp.asarray(inv_freq(rope, hd), jnp.float32)
    rot = 2 * freq.shape[0]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    factor = rope.get("attention_factor", 1.0) \
        if rope["rope_type"] == "yarn" else 1.0
    cos = (jnp.cos(angle) * factor)[:, None, :]
    sin = (jnp.sin(angle) * factor)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rot:]], -1)


def _swiglu(h, gate, up, down, r):
    return r(r(jax.nn.silu(r(h @ _f32(gate))) * r(h @ _f32(up)))
             @ _f32(down))


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
    sparse layers' (L, E, ...)) at layer ``at``; each pair's output is then
    read back from where it lies and weighed by its gate. The same sums as
    every token through every expert under a gate that is zero where the
    expert was not chosen, less the zeros: through 256 experts of which a
    token takes 8 that is a thirtieth of the products."""
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
        out = r(r(jax.nn.silu(r(x @ eg)) * r(x @ eu)) @ ed)
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


def _routed(h, w, stack, at, sizes, chosen, r):
    """(B, T, d) -> (the routed experts' scaled sum (B, T, d), the router's
    logits (B, T, E)). ``w`` is the layer's router; the experts are read a
    block at a time out of ``stack``, all sparse layers' (L, E, ...), at
    layer ``at``."""
    top_k = sizes["num_experts_per_tok"]
    z = h @ _f32(w["router"])
    p = jax.nn.softmax(z, -1)
    chosen = chosen_experts(z, top_k, chosen)
    g = jnp.take_along_axis(p, chosen, -1)
    if sizes.get("norm_topk_prob", True):
        g = g / g.sum(-1, keepdims=True)
    g = g * sizes["moe_routed_scaling_factor"]
    total = _chosen_experts(h, stack, at, chosen, g, r)
    return r(total), z


def hidden(weights, tokens, sizes, experts=None, act_dtype=None):
    """tokens (B, T) int32 -> (final-RMSNorm hidden (B, T, d); the rotated
    keys and the values of the full-attention layers (L_full, B, T, KV, hd)
    each; the router's logits of every model layer (L, B, T, E)).
    ``experts`` (L, B, T, k) int32: the experts every token takes in every
    layer (a dense layer's row is ignored; a row with an entry under 0: that
    token's k largest logits there); None: the k largest. ``act_dtype``: the
    twin (module docstring); None: float32 throughout."""
    hd, kv = sizes["head_dim"], sizes["num_key_value_heads"]
    heads = sizes["num_attention_heads_per_layer"]
    kinds, mlps = sizes["layer_types"], sizes["mlp_layer_types"]
    eps, top_k = sizes["rms_norm_eps"], sizes["num_experts_per_tok"]
    ropes = sizes["rope_parameters"]
    r = rounder(act_dtype)
    b, t = tokens.shape
    n_experts = weights["moe"]["router"].shape[-1]
    no_choice = jnp.broadcast_to(
        jnp.where(jnp.arange(n_experts) < top_k, 1.0, -1.0),
        (b, t, n_experts))

    ks, vs, router = [], [], []
    seen = {FULL: 0, WINDOW: 0, "dense": 0, "sparse": 0}
    with jax.default_matmul_precision("highest"):
        x = r(_f32(weights["embed"][tokens]))
        for layer, (kind, mlp) in enumerate(zip(kinds, mlps)):
            a = {n: leaf[seen[kind]] for n, leaf in weights[
                "full" if kind == FULL else "window"].items()}
            seen[kind] += 1
            n_head = heads[layer]
            h = r(_rms(x, a["input_norm"], eps))
            q = r(h @ _f32(a["q_proj"])).reshape(b, t, n_head, hd)
            k = r(h @ _f32(a["k_proj"])).reshape(b, t, kv, hd)
            v = r(h @ _f32(a["v_proj"])).reshape(b, t, kv, hd)
            q, k = r(_rotate(q, ropes[kind])), r(_rotate(k, ropes[kind]))
            o = r(_attend(q, k, v, sizes["sliding_window"]
                          if kind == WINDOW else None))
            if "gate" in a:
                # one gate a head, float32 inside
                o = r(jax.nn.sigmoid(h @ _f32(a["gate"]))[..., None] * o)
            x = r(x + r(o.reshape(b, t, n_head * hd) @ _f32(a["o_proj"])))
            if kind == FULL:
                ks.append(k)
                vs.append(v)

            stack, at = weights["dense" if mlp == "dense" else "moe"], seen[mlp]
            seen[mlp] += 1
            # this layer's leaves, but for the experts': those stay stacked
            # and are read a block at a time (_routed)
            w = {n: leaf[at] for n, leaf in stack.items()
                 if n not in ("eg", "eu", "ed")}
            h = r(_rms(x, w["post_norm"], eps))
            if mlp == "dense":
                x = r(x + _swiglu(h, w["gate_proj"], w["up_proj"],
                                  w["down_proj"], r))
                router.append(no_choice)
            else:
                out, z = _routed(
                    h, w, stack, at, sizes,
                    None if experts is None else experts[layer], r)
                x = r(x + out + _swiglu(h, w["shared_gate"], w["shared_up"],
                                        w["shared_down"], r))
                router.append(z)
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
