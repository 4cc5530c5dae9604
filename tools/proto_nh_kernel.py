#!/usr/bin/env python
"""Prototype 2: flash forward over the native (B, T, D) activation layout.

Instead of transposing activations to (B*H, T, hd) (28 ms/step of
standalone transposes on the r4 batch-16 trace), keep q/k/v as (B, T, D)
and make the HEAD a grid dimension: grid (B, H, nq, nk) with per-head
block specs — block (1, block, hd) whose index map selects head h's lane
window of the D axis. The kernel body is the existing 2D online-softmax
cell, re-indexed for the 4D grid. GQA indexes the KV head directly in the
index map (no repeat_kv materialisation).
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mingpt_distributed_tpu.ops import attention as attn_ops
from mingpt_distributed_tpu.ops import flash_attention as fa

NEG_INF = -1e30


def _fwd_kernel4(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                 acc_scr, *, scale, block, hd, window=None, softcap=None):
    """Two heads per grid step: q_ref block is (1, block, 2*hd) — the pair
    of 64-lane sub-heads keeps the lane dim at 128 (Mosaic's minimum)."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if window is not None:
        active = (kj <= fa._kv_hi(qi, block, 0, nk)) & (
            kj >= fa._kv_lo(qi, block, window, 0))
    else:
        active = kj <= fa._kv_hi(qi, block, 0, nk)

    @pl.when(active)
    def _compute():
        q2 = q_ref[0]  # (block, 2*hd)
        k2 = k_ref[0]
        v2 = v_ref[0]
        # causal mask shared by both sub-heads: built once per cell
        q_pos = qi * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 0)
        k_pos = kj * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 1)
        ok = q_pos >= k_pos
        if window is not None:
            ok = ok & (q_pos - k_pos < window)
        for sh in range(2):
            lo, hi = sh * hd, (sh + 1) * hd
            q = q2[:, lo:hi]
            kblk = k2[:, lo:hi]
            vblk = v2[:, lo:hi]
            s = jax.lax.dot_general(
                q, kblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(ok, s, NEG_INF)
            m = m_scr[sh]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            m_scr[sh] = m_new
            l_scr[sh] = l_scr[sh] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[sh] = acc_scr[sh] * alpha + jax.lax.dot_general(
                p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(kj == nk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-30)  # (2, block, 1)
        o_pair = (acc_scr[...] / l_safe)  # (2, block, hd)
        o_ref[0] = jnp.concatenate(
            [o_pair[0], o_pair[1]], axis=1).astype(o_ref.dtype)
        lse = m_scr[...] + jnp.log(l_safe)  # (2, block, 1)
        lse_ref[0, 0] = lse[0]
        lse_ref[0, 1] = lse[1]


def flash_fwd_btd(q, k, v, h, scale, block, window=None, softcap=None):
    """q/k/v (B, T, H*hd) -> out (B, T, H*hd), lse (B, H, T, 1)."""
    b, t, d = q.shape
    hd = d // h
    assert h % 2 == 0 and k.shape[2] == d, "pair-packed variant: KV == H, even H"
    nb = t // block
    grid = (b, h // 2, nb, nb)

    def kv_idx(bb, hh, i, j):
        return (bb, jnp.minimum(j, fa._kv_hi(i, block, 0, nb)), hh)

    if window is not None:
        def kv_idx(bb, hh, i, j):  # noqa: F811
            return (bb, jnp.clip(j, fa._kv_lo(i, block, window, 0),
                                 fa._kv_hi(i, block, 0, nb)), hh)

    q_spec = pl.BlockSpec((1, block, 2 * hd), lambda bb, hh, i, j: (bb, i, hh))
    kv_spec = pl.BlockSpec((1, block, 2 * hd), kv_idx)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel4, scale=scale, block=block, hd=hd,
                          window=window, softcap=softcap),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            q_spec,
            pl.BlockSpec((1, 2, block, 1),
                         lambda bb, hh, i, j: (bb, hh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, block, 1), jnp.float32),
            pltpu.VMEM((2, block, 1), jnp.float32),
            pltpu.VMEM((2, block, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")
        ),
        interpret=fa._interpret(),
    )(q, k, v)
    return out, lse


def main():
    B, T, H, HD = 16, 1024, 12, 64
    D = H * HD
    block = 512
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, T, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, T, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, T, D), jnp.bfloat16)
    scale = 1.0 / (HD ** 0.5)

    out, lse = jax.jit(
        lambda q, k, v: flash_fwd_btd(q, k, v, H, scale, block))(q, k, v)
    want = attn_ops.causal_attention(
        q.reshape(B, T, H, HD), k.reshape(B, T, H, HD),
        v.reshape(B, T, H, HD)).reshape(B, T, D)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    print(json.dumps({"what": "parity max_err", "err": err}), flush=True)
    assert err < 0.03, err

    INNER = 10

    def timed(jfn, *args, n=5, warm=2):
        for _ in range(warm):
            o = jfn(*args)
        float(jnp.sum(jax.tree.leaves(o)[0]))
        t0 = time.perf_counter()
        for _ in range(n):
            o = jfn(*args)
        s = float(jnp.sum(jax.tree.leaves(o)[0]))
        assert s == s
        return (time.perf_counter() - t0) / (n * INNER) * 1e3

    @jax.jit
    def new_loop(q, k, v):
        def body(i, qc):
            o, _ = flash_fwd_btd(qc, k, v, H, scale, block)
            return (qc + o * jnp.bfloat16(1e-6)).astype(qc.dtype)
        return jax.lax.fori_loop(0, INNER, body, q)

    @jax.jit
    def old_loop(q, k, v):
        kb = k.reshape(B, T, H, HD).transpose(0, 2, 1, 3).reshape(B * H, T, HD)
        vb = v.reshape(B, T, H, HD).transpose(0, 2, 1, 3).reshape(B * H, T, HD)

        def body(i, qc):
            qb = qc.reshape(B, T, H, HD).transpose(0, 2, 1, 3).reshape(
                B * H, T, HD)
            o = fa._flash(qb, kb, vb, scale, block, None, None)
            o3 = o.reshape(B, H, T, HD).transpose(0, 2, 1, 3).reshape(B, T, D)
            return (qc + o3 * jnp.bfloat16(1e-6)).astype(qc.dtype)
        return jax.lax.fori_loop(0, INNER, body, q)

    print(json.dumps({"what": "btd fwd ms",
                      "ms": round(timed(new_loop, q, k, v), 3)}), flush=True)
    print(json.dumps({"what": "old fwd+transpose ms",
                      "ms": round(timed(old_loop, q, k, v), 3)}), flush=True)




# --- pair-packed backward kernels (round-5 candidate: kill ALL transposes) --


def _dq_kernel4(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                dq_scr, *, scale, block, hd):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    active = kj <= fa._kv_hi(qi, block, 0, nk)

    @pl.when(active)
    def _compute():
        q_pos = qi * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 0)
        k_pos = kj * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 1)
        ok = q_pos >= k_pos
        for sh in range(2):
            lo, hi = sh * hd, (sh + 1) * hd
            q = q_ref[0][:, lo:hi]
            kblk = k_ref[0][:, lo:hi]
            vblk = v_ref[0][:, lo:hi]
            do = do_ref[0][:, lo:hi]
            lse = lse_ref[0, sh]
            delta = delta_ref[0, sh]
            s = jax.lax.dot_general(
                q, kblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            s = jnp.where(ok, s, NEG_INF)
            p = jnp.where(ok, jnp.exp(s - lse), 0.0)
            dp = jax.lax.dot_general(
                do, vblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta.astype(jnp.float32)) * scale
            dq_scr[sh] += jax.lax.dot_general(
                ds.astype(kblk.dtype), kblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = jnp.concatenate(
            [dq_scr[0], dq_scr[1]], axis=1).astype(dq_ref.dtype)


def _dkv_kernel4(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_scr, dv_scr, *, scale, block, hd):
    kj = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    active = qi >= fa._q_lo(kj, block, 0)

    @pl.when(active)
    def _compute():
        q_pos = qi * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 0)
        k_pos = kj * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 1)
        ok = q_pos >= k_pos
        for sh in range(2):
            lo, hi = sh * hd, (sh + 1) * hd
            q = q_ref[0][:, lo:hi]
            kblk = k_ref[0][:, lo:hi]
            vblk = v_ref[0][:, lo:hi]
            do = do_ref[0][:, lo:hi]
            lse = lse_ref[0, sh]
            delta = delta_ref[0, sh]
            s = jax.lax.dot_general(
                q, kblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            s = jnp.where(ok, s, NEG_INF)
            p = jnp.where(ok, jnp.exp(s - lse), 0.0)
            dv_scr[sh] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                do, vblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta.astype(jnp.float32)) * scale
            dk_scr[sh] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = jnp.concatenate(
            [dk_scr[0], dk_scr[1]], axis=1).astype(dk_ref.dtype)
        dv_ref[0] = jnp.concatenate(
            [dv_scr[0], dv_scr[1]], axis=1).astype(dv_ref.dtype)


def flash_bwd_btd(q, k, v, do, lse, delta, h, scale, block):
    """Inputs (B, T, H*hd) + lse/delta (B, H, T, 1) -> dq, dk, dv."""
    b, t, d = q.shape
    hd = d // h
    nb = t // block
    grid = (b, h // 2, nb, nb)
    io_q = pl.BlockSpec((1, block, 2 * hd), lambda bb, hh, i, j: (bb, i, hh))
    kv_stream = pl.BlockSpec(
        (1, block, 2 * hd),
        lambda bb, hh, i, j: (bb, jnp.minimum(j, fa._kv_hi(i, block, 0, nb)),
                              hh))
    vec_q = pl.BlockSpec((1, 2, block, 1), lambda bb, hh, i, j: (bb, hh, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel4, scale=scale, block=block, hd=hd),
        grid=grid,
        in_specs=[io_q, kv_stream, kv_stream, io_q, vec_q, vec_q],
        out_specs=[io_q],
        out_shape=[jax.ShapeDtypeStruct((b, t, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((2, block, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=fa._interpret(),
    )(q, k, v, do, lse, delta)[0]

    grid2 = (b, h // 2, nb, nb)
    kv_fixed = pl.BlockSpec((1, block, 2 * hd),
                            lambda bb, hh, j, i: (bb, j, hh))
    q_stream = pl.BlockSpec(
        (1, block, 2 * hd),
        lambda bb, hh, j, i: (bb, jnp.maximum(i, fa._q_lo(j, block, 0)), hh))
    vec_stream = pl.BlockSpec(
        (1, 2, block, 1),
        lambda bb, hh, j, i: (bb, hh, jnp.maximum(i, fa._q_lo(j, block, 0)),
                              0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel4, scale=scale, block=block, hd=hd),
        grid=grid2,
        in_specs=[q_stream, kv_fixed, kv_fixed, q_stream, vec_stream,
                  vec_stream],
        out_specs=[kv_fixed, kv_fixed],
        out_shape=[jax.ShapeDtypeStruct((b, t, d), k.dtype),
                   jax.ShapeDtypeStruct((b, t, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((2, block, hd), jnp.float32),
                        pltpu.VMEM((2, block, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=fa._interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def main_bwd():
    B, T, H, HD = 16, 1024, 12, 64
    D = H * HD
    block = 512
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, T, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, T, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, T, D), jnp.bfloat16)
    do = jax.random.normal(ks[3], (B, T, D), jnp.bfloat16)
    scale = 1.0 / (HD ** 0.5)

    # parity vs autodiff through the oracle
    def oracle_loss(q, k, v):
        o = attn_ops.causal_attention(
            q.reshape(B, T, H, HD), k.reshape(B, T, H, HD),
            v.reshape(B, T, H, HD)).reshape(B, T, D)
        return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32))

    gw = jax.jit(jax.grad(oracle_loss, argnums=(0, 1, 2)))(q, k, v)

    @jax.jit
    def new_bwd(q, k, v, do):
        out, lse = flash_fwd_btd(q, k, v, H, scale, block)
        o4 = out.reshape(B, T, H, HD)
        do4 = do.reshape(B, T, H, HD)
        delta = jnp.sum(o4.astype(jnp.float32) * do4.astype(jnp.float32),
                        axis=-1)  # (B, T, H)
        delta = delta.transpose(0, 2, 1)[..., None]  # (B, H, T, 1) tiny
        return flash_bwd_btd(q, k, v, do, lse, delta, H, scale, block)

    gn = new_bwd(q, k, v, do)
    for a, b2, nm in zip(gw, gn, ("dq", "dk", "dv")):
        sc = float(jnp.max(jnp.abs(a.astype(jnp.float32)))) or 1.0
        err = float(jnp.max(jnp.abs(
            b2.astype(jnp.float32) - a.astype(jnp.float32)))) / sc
        print(json.dumps({"what": f"bwd parity {nm}", "rel_err": round(err, 5)}),
              flush=True)
        assert err < 0.03, (nm, err)

    INNER = 10

    def timed(jfn, *args, n=5, warm=2):
        for _ in range(warm):
            o = jfn(*args)
        float(jnp.sum(jax.tree.leaves(o)[0]))
        t0 = time.perf_counter()
        for _ in range(n):
            o = jfn(*args)
        s = float(jnp.sum(jax.tree.leaves(o)[0]))
        assert s == s
        return (time.perf_counter() - t0) / (n * INNER) * 1e3

    @jax.jit
    def new_loop(q, k, v, do):
        def body(i, qc):
            dq, dk, dv = new_bwd(qc, k, v, do)
            return (qc + dq * jnp.bfloat16(1e-6)).astype(qc.dtype)
        return jax.lax.fori_loop(0, INNER, body, q)

    @jax.jit
    def old_loop(q, k, v, do):
        def body(i, qc):
            def f(q3, k3, v3):
                o = fa.causal_attention(
                    q3.reshape(B, T, H, HD), k3.reshape(B, T, H, HD),
                    v3.reshape(B, T, H, HD)).reshape(B, T, D)
                return jnp.sum(o.astype(jnp.float32)
                               * do.astype(jnp.float32))
            dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(qc, k, v)
            return (qc + dq * jnp.bfloat16(1e-6)).astype(qc.dtype)
        return jax.lax.fori_loop(0, INNER, body, q)

    print(json.dumps({"what": "new fwd+bwd btd ms",
                      "ms": round(timed(new_loop, q, k, v, do), 3)}),
          flush=True)
    print(json.dumps({"what": "old fwd+bwd (kernels+transposes) ms",
                      "ms": round(timed(old_loop, q, k, v, do), 3)}),
          flush=True)


if __name__ == "__main__":
    main()
    main_bwd()
