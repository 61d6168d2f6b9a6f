"""FlashMask-style sparse-mask Pallas flash attention.

Reference: nn/functional/flash_attention.py
flash_attention_with_sparse_mask — attention where query rows >=
start_row_indices[col] are masked per column (plus causal), the compact
encoding PaddleNLP's FlashMask uses for document/causal hybrid masks.
Instead of materializing the O(S²) additive bias, these streaming kernels
evaluate the mask inside the tile and SKIP (q-block, kv-block) pairs that
are provably fully masked: causal-dead blocks and blocks where every
column's start row precedes the block's first query row.
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

__all__ = ["flash_sparse_mask_attention", "sparse_mask_supported"]


def _mask_st(st, start_ref, qi, j, causal, bq, bk):
    row = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    allowed = row < start_ref[:].reshape(1, bk)
    if causal:
        allowed = allowed & (row >= col)
    return jnp.where(allowed, st, NEG_INF)


def _fwd_kernel(maxs_ref, q_ref, k_ref, v_ref, start_ref, o_ref, lse_ref,
                m_sc, l_sc, acc_sc, *, scale, causal, bq, bk):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    # block prune: dead if every column's start row precedes the block's
    # first query row (no row in this block can see any column), or the
    # whole block is above the causal diagonal
    live = qi * bq < maxs_ref[j, 0]
    if causal:
        live = live & (j * bk <= qi * bq + bq - 1)

    @pl.when(live)
    def _step():
        q = q_ref[:].astype(jnp.float32) * scale
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        st = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        st = _mask_st(st, start_ref, qi, j, causal, bq, bk)
        m = m_sc[:]
        m_new = jnp.maximum(m, st.max(axis=-1, keepdims=True))
        # rows the mask kills entirely have m_new == NEG_INF; exp(0)=1
        # would give them uniform attention — zero them instead
        p = jnp.where(st > 0.5 * NEG_INF, jnp.exp(st - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_sc[:] = l_sc[:] * alpha + p.sum(axis=-1, keepdims=True)
        acc_sc[:] = acc_sc[:] * alpha + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[:] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_sc[:], 1e-30)  # fully-masked rows emit zeros
        o_ref[:] = (acc_sc[:] / l).astype(o_ref.dtype)
        lse_ref[0, :] = m_sc[:, 0] + jnp.log(l[:, 0])


def _dq_kernel(maxs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               start_ref, dq_ref, dq_sc, *, scale, causal, bq, bk):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    live = qi * bq < maxs_ref[j, 0]
    if causal:
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
        st = _mask_st(st, start_ref, qi, j, causal, bq, bk)
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


def _dkv_kernel(maxs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                start_ref, dk_ref, dv_ref, dk_sc, dv_sc,
                *, scale, causal, bq, bk):
    ki = pl.program_id(1)
    i = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    live = i * bq < maxs_ref[ki, 0]
    if causal:
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
        st = _mask_st(st, start_ref, i, ki, causal, bq, bk)
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


def _prep(start, bk):
    # start: [bh, s] -> per-block column maxima [bh? no] ...
    # maxima must be per (bh, block): [bh, nk, 1]; per-token [bh, s, 1]
    bh, s = start.shape
    nk = s // bk
    maxs = start.reshape(bh, nk, bk).max(axis=2, keepdims=True)
    return start.reshape(bh, s, 1).astype(jnp.int32), \
        maxs.astype(jnp.int32)


@i32_trace
def _sm_fwd(q, k, v, start, causal, scale):
    bh, s, d = q.shape
    bq = _largest_dividing(s, min(512, s))
    bk = _largest_dividing(s, min(512, s))
    start2, maxs = _prep(start, bk)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=(bh, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((None, s // bk, 1), lambda b, i, j: (b, 0, 0)),
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, 1), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(maxs, q, k, v, start2)
    return o, lse.reshape(bh, s)


@i32_trace
def _sm_bwd(q, k, v, o, lse, do, start, causal, scale):
    bh, s, d = q.shape
    bq = _largest_dividing(s, min(512, s))
    bk = _largest_dividing(s, min(512, s))
    start2, maxs = _prep(start, bk)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, s)
    lse3 = lse.reshape(bh, 1, s)
    interp = _interpret()

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=(bh, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((None, s // bk, 1), lambda b, i, j: (b, 0, 0)),
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((None, bk, 1), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interp,
    )(maxs, q, k, v, do, lse3, delta, start2)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=(bh, s // bk, s // bq),
        in_specs=[
            pl.BlockSpec((None, s // bk, 1), lambda b, ki, i: (b, 0, 0)),
            pl.BlockSpec((None, bq, d), lambda b, ki, i: (b, i, 0)),
            pl.BlockSpec((None, bk, d), lambda b, ki, i: (b, ki, 0)),
            pl.BlockSpec((None, bk, d), lambda b, ki, i: (b, ki, 0)),
            pl.BlockSpec((None, bq, d), lambda b, ki, i: (b, i, 0)),
            pl.BlockSpec((None, 1, bq), lambda b, ki, i: (b, 0, i)),
            pl.BlockSpec((None, 1, bq), lambda b, ki, i: (b, 0, i)),
            pl.BlockSpec((None, bk, 1), lambda b, ki, i: (b, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, d), lambda b, ki, i: (b, ki, 0)),
            pl.BlockSpec((None, bk, d), lambda b, ki, i: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interp,
    )(maxs, q, k, v, do, lse3, delta, start2)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_sm(q, k, v, start, causal, scale):
    return _sm_fwd(q, k, v, start, causal, scale)[0]


def _flash_sm_fwd_rule(q, k, v, start, causal, scale):
    o, lse = _sm_fwd(q, k, v, start, causal, scale)
    return o, (q, k, v, o, lse, start)


def _flash_sm_bwd_rule(causal, scale, res, do):
    q, k, v, o, lse, start = res
    dq, dk, dv = _sm_bwd(q, k, v, o, lse, do, start, causal, scale)
    import numpy as np
    return dq, dk, dv, np.zeros(start.shape, jax.dtypes.float0)


_flash_sm.defvjp(_flash_sm_fwd_rule, _flash_sm_bwd_rule)


def flash_sparse_mask_attention(q, k, v, start_rows, causal=True,
                                scale=None):
    """q/k/v: [B, S, H, D]; start_rows: [B, H, S] int (rows >= start are
    masked for that column). Returns [B, S, H, D]."""
    b, s, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    def to_bh(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)

    start = jnp.broadcast_to(start_rows, (b, h, s)).reshape(b * h, s)
    o = _flash_sm(to_bh(q), to_bh(k), to_bh(v), start.astype(jnp.int32),
                  bool(causal), float(scale))
    return jnp.swapaxes(o.reshape(b, h, s, d), 1, 2)


def sparse_mask_supported(s, d):
    return d in (64, 128, 256) and s % 128 == 0 and s >= 128
