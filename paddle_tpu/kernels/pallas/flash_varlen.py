"""Varlen (packed-sequence) Pallas flash attention with segment pruning.

Replaces the O(total²) masked-softmax fallback for
`flash_attn_unpadded` (reference python/paddle/nn/functional/
flash_attention.py:455 dispatches varlen into libflashattn): packed
[total, H, D] tokens with cu_seqlens boundaries run through streaming
flash kernels that (a) mask cross-segment pairs elementwise and (b) SKIP
whole (q-block, kv-block) pairs whose segment ranges cannot overlap —
for B packed sequences of length L each, compute drops from (BL)² to
~B·L², the same asymptotic win the reference gets from its varlen CUDA
kernels.

Causality is evaluated on LOCAL (within-segment) positions, so unequal
q/k packings (cross attention) stay correct; the extra global-index
block prune is applied only when the caller certifies both packs share
one layout (`same_pack`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._x64 import i32_trace
from .flash_attention import NEG_INF, _interpret, _largest_dividing

__all__ = ["flash_varlen_attention", "segments_from_cu"]


def segments_from_cu(cu, total):
    """cu_seqlens [B+1] -> (seg [total] int32, local_pos [total] int32)."""
    cu = cu.astype(jnp.int32)
    seg = jnp.cumsum(jnp.zeros(total, jnp.int32).at[cu[1:-1]].add(1))
    starts = cu[:-1][seg]
    pos = jnp.arange(total, dtype=jnp.int32) - starts
    return seg, pos


def _blk(total):
    bq = _largest_dividing(total, min(512, total))
    bk = _largest_dividing(total, min(512, total))
    return bq, bk


def _mask_st(st, sq, pq, sk, pk, causal, bq, bk):
    # sq/pq [bq, 1]; sk/pk [bk, 1]
    same = sq == sk.reshape(1, bk)
    if causal:
        same = same & (pq >= pk.reshape(1, bk))
    return jnp.where(same, st, NEG_INF)


def _fwd_kernel(smin_q, smax_q, smin_k, smax_k,
                q_ref, k_ref, v_ref, sq_ref, pq_ref, sk_ref, pk_ref,
                o_ref, lse_ref, m_sc, l_sc, acc_sc,
                *, scale, causal, same_pack, bq, bk):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    # segment-range overlap prune: whole block pairs with disjoint
    # segments never touch the MXU
    live = (smin_q[qi, 0] <= smax_k[j, 0]) & (smax_q[qi, 0] >= smin_k[j, 0])
    if causal and same_pack:
        live = live & (j * bk <= qi * bq + bq - 1)

    @pl.when(live)
    def _step():
        q = q_ref[:].astype(jnp.float32) * scale
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        st = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        st = _mask_st(st, sq_ref[:], pq_ref[:], sk_ref[:], pk_ref[:],
                      causal, bq, bk)
        m = m_sc[:]
        m_new = jnp.maximum(m, st.max(axis=-1, keepdims=True))
        # rows with no visible keys in any block (possible for unequal
        # q/k packs) must not collapse to uniform attention
        p = jnp.where(st > 0.5 * NEG_INF, jnp.exp(st - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_sc[:] = l_sc[:] * alpha + p.sum(axis=-1, keepdims=True)
        acc_sc[:] = acc_sc[:] * alpha + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[:] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_sc[:], 1e-30)  # keyless rows emit zeros
        o_ref[:] = (acc_sc[:] / l).astype(o_ref.dtype)
        lse_ref[0, :] = m_sc[:, 0] + jnp.log(l[:, 0])


def _dq_kernel(smin_q, smax_q, smin_k, smax_k,
               q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               sq_ref, pq_ref, sk_ref, pk_ref, dq_ref, dq_sc,
               *, scale, causal, same_pack, bq, bk):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    live = (smin_q[qi, 0] <= smax_k[j, 0]) & (smax_q[qi, 0] >= smin_k[j, 0])
    if causal and same_pack:
        live = live & (j * bk <= qi * bq + bq - 1)

    @pl.when(live)
    def _step():
        q = q_ref[:].astype(jnp.float32) * scale
        do = do_ref[:].astype(jnp.float32)
        lse = lse_ref[0, :][:, None]
        delta = delta_ref[0, :][:, None]
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        st = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        st = _mask_st(st, sq_ref[:], pq_ref[:], sk_ref[:], pk_ref[:],
                      causal, bq, bk)
        p = jnp.where(st > 0.5 * NEG_INF, jnp.exp(st - lse), 0.0)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_sc[:] = dq_sc[:] + lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[:] = dq_sc[:].astype(dq_ref.dtype)


def _dkv_kernel(smin_q, smax_q, smin_k, smax_k,
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                sq_ref, pq_ref, sk_ref, pk_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale, causal, same_pack, bq, bk):
    ki = pl.program_id(1)
    i = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    live = (smin_q[i, 0] <= smax_k[ki, 0]) & (smax_q[i, 0] >= smin_k[ki, 0])
    if causal and same_pack:
        live = live & (i * bq + bq - 1 >= ki * bk)

    @pl.when(live)
    def _step():
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        q = q_ref[:].astype(jnp.float32) * scale
        do = do_ref[:].astype(jnp.float32)
        lse = lse_ref[0, :][:, None]
        delta = delta_ref[0, :][:, None]
        st = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        st = _mask_st(st, sq_ref[:], pq_ref[:], sk_ref[:], pk_ref[:],
                      causal, bq, bk)
        p = jnp.where(st > 0.5 * NEG_INF, jnp.exp(st - lse), 0.0)
        dv_sc[:] = dv_sc[:] + lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_sc[:] = dk_sc[:] + lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[:] = (dk_sc[:] / scale).astype(dk_ref.dtype)
        dv_ref[:] = dv_sc[:].astype(dv_ref.dtype)


def _block_extremes(seg, blk):
    n = seg.shape[0] // blk
    s2 = seg.reshape(n, blk)
    return (s2.min(axis=1, keepdims=True).astype(jnp.int32),
            s2.max(axis=1, keepdims=True).astype(jnp.int32))


def _seg_inputs(seg, pos, blk):
    # per-token arrays as [total, 1] so the kernel reads [blk, 1] tiles
    return seg.reshape(-1, 1).astype(jnp.int32), \
        pos.reshape(-1, 1).astype(jnp.int32)


@i32_trace
def _varlen_fwd(q, k, v, seg_q, pos_q, seg_k, pos_k, causal, scale,
                same_pack):
    # q: [h, tq, d]; k/v: [h, tk, d]
    h, tq, d = q.shape
    tk = k.shape[1]
    bq, bk = _blk(tq)
    bk = _largest_dividing(tk, bk)
    sminq, smaxq = _block_extremes(seg_q, bq)
    smink, smaxk = _block_extremes(seg_k, bk)
    sq2, pq2 = _seg_inputs(seg_q, pos_q, bq)
    sk2, pk2 = _seg_inputs(seg_k, pos_k, bk)

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          same_pack=same_pack, bq=bq, bk=bk),
        grid=(h, tq // bq, tk // bk),
        in_specs=[
            pl.BlockSpec((tq // bq, 1), lambda b, i, j: (0, 0)),
            pl.BlockSpec((tq // bq, 1), lambda b, i, j: (0, 0)),
            pl.BlockSpec((tk // bk, 1), lambda b, i, j: (0, 0)),
            pl.BlockSpec((tk // bk, 1), lambda b, i, j: (0, 0)),
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((bq, 1), lambda b, i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda b, i, j: (i, 0)),
            pl.BlockSpec((bk, 1), lambda b, i, j: (j, 0)),
            pl.BlockSpec((bk, 1), lambda b, i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, tq, d), q.dtype),
            jax.ShapeDtypeStruct((h, 1, tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(sminq, smaxq, smink, smaxk, q, k, v, sq2, pq2, sk2, pk2)
    return o, lse.reshape(h, tq)


@i32_trace
def _varlen_bwd(q, k, v, o, lse, do, seg_q, pos_q, seg_k, pos_k, causal,
                scale, same_pack):
    h, tq, d = q.shape
    tk = k.shape[1]
    bq, bk = _blk(tq)
    bk = _largest_dividing(tk, bk)
    sminq, smaxq = _block_extremes(seg_q, bq)
    smink, smaxk = _block_extremes(seg_k, bk)
    sq2, pq2 = _seg_inputs(seg_q, pos_q, bq)
    sk2, pk2 = _seg_inputs(seg_k, pos_k, bk)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(h, 1, tq)
    lse3 = lse.reshape(h, 1, tq)
    interp = _interpret()

    seg_specs_q = [pl.BlockSpec((bq, 1), lambda b, i, j: (i, 0)),
                   pl.BlockSpec((bq, 1), lambda b, i, j: (i, 0))]
    seg_specs_k = [pl.BlockSpec((bk, 1), lambda b, i, j: (j, 0)),
                   pl.BlockSpec((bk, 1), lambda b, i, j: (j, 0))]
    ext_specs = [
        pl.BlockSpec((tq // bq, 1), lambda b, i, j: (0, 0)),
        pl.BlockSpec((tq // bq, 1), lambda b, i, j: (0, 0)),
        pl.BlockSpec((tk // bk, 1), lambda b, i, j: (0, 0)),
        pl.BlockSpec((tk // bk, 1), lambda b, i, j: (0, 0)),
    ]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          same_pack=same_pack, bq=bq, bk=bk),
        grid=(h, tq // bq, tk // bk),
        in_specs=ext_specs + [
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)),
        ] + seg_specs_q + seg_specs_k,
        out_specs=pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((h, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interp,
    )(sminq, smaxq, smink, smaxk, q, k, v, do, lse3, delta,
      sq2, pq2, sk2, pk2)

    dkv_seg_q = [pl.BlockSpec((bq, 1), lambda b, ki, i: (i, 0)),
                 pl.BlockSpec((bq, 1), lambda b, ki, i: (i, 0))]
    dkv_seg_k = [pl.BlockSpec((bk, 1), lambda b, ki, i: (ki, 0)),
                 pl.BlockSpec((bk, 1), lambda b, ki, i: (ki, 0))]
    dkv_ext = [
        pl.BlockSpec((tq // bq, 1), lambda b, ki, i: (0, 0)),
        pl.BlockSpec((tq // bq, 1), lambda b, ki, i: (0, 0)),
        pl.BlockSpec((tk // bk, 1), lambda b, ki, i: (0, 0)),
        pl.BlockSpec((tk // bk, 1), lambda b, ki, i: (0, 0)),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          same_pack=same_pack, bq=bq, bk=bk),
        grid=(h, tk // bk, tq // bq),
        in_specs=dkv_ext + [
            pl.BlockSpec((None, bq, d), lambda b, ki, i: (b, i, 0)),
            pl.BlockSpec((None, bk, d), lambda b, ki, i: (b, ki, 0)),
            pl.BlockSpec((None, bk, d), lambda b, ki, i: (b, ki, 0)),
            pl.BlockSpec((None, bq, d), lambda b, ki, i: (b, i, 0)),
            pl.BlockSpec((None, 1, bq), lambda b, ki, i: (b, 0, i)),
            pl.BlockSpec((None, 1, bq), lambda b, ki, i: (b, 0, i)),
        ] + dkv_seg_q + dkv_seg_k,
        out_specs=[
            pl.BlockSpec((None, bk, d), lambda b, ki, i: (b, ki, 0)),
            pl.BlockSpec((None, bk, d), lambda b, ki, i: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((h, tk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interp,
    )(sminq, smaxq, smink, smaxk, q, k, v, do, lse3, delta,
      sq2, pq2, sk2, pk2)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _flash_varlen(q, k, v, seg_q, pos_q, seg_k, pos_k, causal, scale,
                  same_pack):
    return _varlen_fwd(q, k, v, seg_q, pos_q, seg_k, pos_k, causal, scale,
                       same_pack)[0]


def _flash_varlen_fwd_rule(q, k, v, seg_q, pos_q, seg_k, pos_k, causal,
                           scale, same_pack):
    o, lse = _varlen_fwd(q, k, v, seg_q, pos_q, seg_k, pos_k, causal,
                         scale, same_pack)
    return o, (q, k, v, o, lse, seg_q, pos_q, seg_k, pos_k)


def _flash_varlen_bwd_rule(causal, scale, same_pack, res, do):
    q, k, v, o, lse, seg_q, pos_q, seg_k, pos_k = res
    dq, dk, dv = _varlen_bwd(q, k, v, o, lse, do, seg_q, pos_q, seg_k,
                             pos_k, causal, scale, same_pack)
    import numpy as np
    f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (dq, dk, dv, f0(seg_q), f0(pos_q), f0(seg_k), f0(pos_k))


_flash_varlen.defvjp(_flash_varlen_fwd_rule, _flash_varlen_bwd_rule)


def flash_varlen_attention(q, k, v, cu_seqlens_q, cu_seqlens_k, scale=None,
                           causal=False, same_pack=None):
    """Packed varlen flash attention. q/k/v: [total, H, D] jax arrays;
    cu_seqlens: [B+1]. Returns [total_q, H, D]."""
    tq, h, d = q.shape
    tk = k.shape[0]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    seg_q, pos_q = segments_from_cu(jnp.asarray(cu_seqlens_q), tq)
    seg_k, pos_k = segments_from_cu(jnp.asarray(cu_seqlens_k), tk)
    if same_pack is None:
        same_pack = tq == tk and cu_seqlens_q is cu_seqlens_k
    qh = jnp.swapaxes(q, 0, 1)
    kh = jnp.swapaxes(k, 0, 1)
    vh = jnp.swapaxes(v, 0, 1)
    o = _flash_varlen(qh, kh, vh, seg_q, pos_q, seg_k, pos_k,
                      bool(causal), float(scale), bool(same_pack))
    return jnp.swapaxes(o, 0, 1)


def varlen_supported(total_q, total_k, d):
    """Mirror of the dense-path pallas guard: 128-divisible totals and a
    kernel-tileable head dim."""
    return (d in (64, 128, 256) and total_q % 128 == 0
            and total_k % 128 == 0 and total_q >= 128 and total_k >= 128)
