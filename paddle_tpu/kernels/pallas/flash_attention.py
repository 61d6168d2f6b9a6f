"""Pallas TPU flash attention (fwd + bwd), online-softmax tiled.

TPU-native replacement for the reference's dynloaded FlashAttention-v2
(paddle/phi/kernels/gpu/flash_attn_kernel.cu + third_party/flashattn) and
the fused attention kernels in phi/kernels/fusion/gpu. Layout contract
matches paddle's flash_attention python API: [batch, seq, heads, head_dim].

Every product takes its operands in the dtype they are stored in and
accumulates in float32 (`_dot`): bf16 q, k, v, do go to the MXU as bf16,
one pass, and `p` / `ds` are rounded to that dtype for the four products
that take them (`P V`, `P^T dO`, `dS K`, `dS^T Q`), as the outputs are
when stored; float32 inputs keep float32 products. All else is float32
whatever the inputs: the scores (`scale` multiplies them, never an
operand), the mask, the running (max, sum) per row, `exp`, the
accumulators, `delta`, and the log-sum-exp saved for the backward. The
backward is the standard two-pass flash backward: one kernel accumulates
dq over kv blocks, one accumulates (dk, dv) over q blocks; both recompute
p from the saved lse. Causal scheduling prunes fully-masked blocks via
dynamic fori_loop bounds.

On non-TPU backends the kernels run in interpret mode so CPU CI exercises
the exact kernel code (SURVEY.md §4's custom_cpu-plugin pattern).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import numpy as np

from ._x64 import i32_trace

__all__ = ["flash_attention_jax", "flash_attention_fwd"]

# np.float32, not a python float: the kernel body is re-traced at
# interpret-mode lowering time OUTSIDE the i32_trace context, where a
# weak float constant would promote to f64 under the global x64 mode
NEG_INF = np.float32(-1e30)


def _interpret():
    return jax.default_backend() != "tpu"


# explicit override used by the autotuner while timing candidates
_BLOCK_OVERRIDE = {}


def _largest_dividing(s, cap):
    """Largest block size <= cap that divides s (s % 128 == 0 guaranteed
    by the entry guard, so 128 always qualifies)."""
    for b in (cap, 256, 128):
        if b <= cap and s % b == 0:
            return b
    return 128


def _block_sizes(s, d, dtype=None):
    if "flash" in _BLOCK_OVERRIDE:
        return _BLOCK_OVERRIDE["flash"]
    # autotuned winner for this exact signature, when recorded
    # (kernels/autotune.py tune_flash_blocks)
    if dtype is not None:
        try:
            from ..autotune import AutoTuneCache
            hit = AutoTuneCache.instance()._store.get(
                ("flash_blocks", (s, d, str(dtype))))
            if hit is not None:
                return hit
        except ImportError:  # pragma: no cover
            pass
    # blocks must DIVIDE the sequence: the grid truncates otherwise and
    # rows/columns beyond grid*block would silently be dropped
    bq = _largest_dividing(s, min(512, s))
    bk = _largest_dividing(s, min(512, s))
    return bq, bk


def _dot(a, b, contract):
    """`a . b` over the dimensions `contract` = ((a's,), (b's,)), float32
    accumulated. Operands go to the MXU in the dtype they are stored in:
    a bf16 x bf16 product is exact in its float32 accumulator, so an
    upcast would add five passes and no information. float32 operands
    keep the process-wide precision (`highest`: six passes); narrower
    ones name the one-pass product themselves, because Mosaic refuses a
    float32 contract precision on them."""
    precision = None if a.dtype == jnp.float32 else lax.Precision.DEFAULT
    return lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


_NT = ((1,), (1,))  # a b^T
_NN = ((1,), (0,))  # a b
_TN = ((0,), (0,))  # a^T b


def _scores(q, k, scale, causal_from=None):
    """float32 scores `scale * q k^T` of one [bq, bk] block pair. `scale`
    is applied to the float32 scores, never to an operand that is then
    rounded. `causal_from` = (first row, first column) of the pair masks
    what lies above the diagonal."""
    st = _dot(q, k, _NT) * scale
    if causal_from is not None:
        row0, col0 = causal_from
        row = row0 + lax.broadcasted_iota(jnp.int32, st.shape, 0)
        col = col0 + lax.broadcasted_iota(jnp.int32, st.shape, 1)
        st = jnp.where(row >= col, st, NEG_INF)
    return st


# -- forward -----------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, bq, bk):
    # q_ref [bq, d]; k_ref/v_ref [s, d]; o_ref [bq, d]; lse_ref [1, bq]
    qi = pl.program_id(1)
    d = q_ref.shape[-1]
    s = k_ref.shape[0]
    q = q_ref[:]

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * bk, bk), :]
        v = v_ref[pl.ds(j * bk, bk), :]
        st = _scores(q, k, scale, (qi * bq, j * bk) if causal else None)
        m_new = jnp.maximum(m, st.max(axis=-1, keepdims=True))
        p = jnp.exp(st - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + _dot(p.astype(v.dtype), v, _NN)
        return m_new, l, acc

    nk = s // bk
    hi = jnp.minimum(nk, (qi * bq + bq + bk - 1) // jnp.int32(bk)) if causal else nk
    # explicit i32 bounds: the kernel is re-traced at interpret-mode
    # lowering time OUTSIDE the i32_trace context, where a weak python
    # int bound would promote to i64 and break the while-loop compare
    m, l, acc = lax.fori_loop(jnp.int32(0), jnp.int32(hi),
                              body, (m0, l0, acc0))
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, :] = (m[:, 0] + jnp.log(l[:, 0]))


@i32_trace
def _mha_fwd(q, k, v, causal, scale):
    # q,k,v: [bh, s, d]
    bh, s, d = q.shape
    if _use_streaming(s, d):
        return _mha_fwd_stream(q, k, v, causal, scale)
    bq, bk = _block_sizes(s, d, q.dtype)
    grid = (bh, s // bq)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, s, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, 1, bq), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v)
    return o, lse.reshape(bh, s)


# -- streaming variants (long sequence) --------------------------------------
# The resident kernels above stage the FULL [s, d] K/V (or Q) block in
# VMEM — fastest while it fits (~8k tokens at d=128), but a VMEM OOM
# beyond. The streaming kernels drive the kv/q axis through the grid with
# running (m, l, acc) state in VMEM scratch; causal-skipped blocks cost
# one predicated branch (pl.when).

_RESIDENT_LIMIT = 8192 * 128  # s * d elements of one K or V block


def _stream_blocks(s, d):
    if "flash" in _BLOCK_OVERRIDE:
        return _BLOCK_OVERRIDE["flash"]
    bq = _largest_dividing(s, min(512, s))
    bk = _largest_dividing(s, min(512, s))
    return bq, bk


def _fwd_kernel_stream(q_ref, k_ref, v_ref, o_ref, lse_ref,
                       m_sc, l_sc, acc_sc, *, scale, causal, bq, bk):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    d = q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    live = (j * bk <= qi * bq + bq - 1) if causal else True

    @pl.when(live)
    def _step():
        v = v_ref[:]
        st = _scores(q_ref[:], k_ref[:], scale,
                     (qi * bq, j * bk) if causal else None)
        m = m_sc[:]
        m_new = jnp.maximum(m, st.max(axis=-1, keepdims=True))
        p = jnp.exp(st - m_new)
        alpha = jnp.exp(m - m_new)
        l_sc[:] = l_sc[:] * alpha + p.sum(axis=-1, keepdims=True)
        acc_sc[:] = acc_sc[:] * alpha + _dot(p.astype(v.dtype), v, _NN)
        m_sc[:] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[:] = (acc_sc[:] / l_sc[:]).astype(o_ref.dtype)
        lse_ref[0, :] = m_sc[:, 0] + jnp.log(l_sc[:, 0])


@i32_trace
def _mha_fwd_stream(q, k, v, causal, scale):
    bh, s, d = q.shape
    bq, bk = _stream_blocks(s, d)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_stream, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=(bh, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0)),
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
    )(q, k, v)
    return o, lse.reshape(bh, s)


def _dq_kernel_stream(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_sc, *, scale, causal, bq, bk):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    live = (j * bk <= qi * bq + bq - 1) if causal else True

    @pl.when(live)
    def _step():
        lse = lse_ref[0, :][:, None]
        delta = delta_ref[0, :][:, None]
        k = k_ref[:]
        st = _scores(q_ref[:], k, scale,
                     (qi * bq, j * bk) if causal else None)
        p = jnp.exp(st - lse)
        dp = _dot(do_ref[:], v_ref[:], _NT)
        ds = p * (dp - delta) * scale
        dq_sc[:] = dq_sc[:] + _dot(ds.astype(k.dtype), k, _NN)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[:] = dq_sc[:].astype(dq_ref.dtype)


def _dkv_kernel_stream(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_sc, dv_sc, *, scale, causal,
                       bq, bk):
    ki = pl.program_id(1)
    i = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    live = (i * bq + bq - 1 >= ki * bk) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[:]
        do = do_ref[:]
        lse = lse_ref[0, :][:, None]
        delta = delta_ref[0, :][:, None]
        st = _scores(q, k_ref[:], scale,
                     (i * bq, ki * bk) if causal else None)
        p = jnp.exp(st - lse)
        dv_sc[:] = dv_sc[:] + _dot(p.astype(do.dtype), do, _TN)
        dp = _dot(do, v_ref[:], _NT)
        ds = p * (dp - delta) * scale
        dk_sc[:] = dk_sc[:] + _dot(ds.astype(q.dtype), q, _TN)

    @pl.when(i == nq - 1)
    def _finish():
        # q entered unscaled and ds carries `scale` once: nothing to undo
        dk_ref[:] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_sc[:].astype(dv_ref.dtype)


@i32_trace
def _mha_bwd_stream(q, k, v, o, lse, do, causal, scale):
    bh, s, d = q.shape
    bq, bk = _stream_blocks(s, d)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, s)
    lse3 = lse.reshape(bh, 1, s)
    interp = _interpret()

    dq = pl.pallas_call(
        functools.partial(_dq_kernel_stream, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=(bh, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((None, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interp,
    )(q, k, v, do, lse3, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel_stream, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=(bh, s // bk, s // bq),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda b, jj, i: (b, i, 0)),
            pl.BlockSpec((None, bk, d), lambda b, jj, i: (b, jj, 0)),
            pl.BlockSpec((None, bk, d), lambda b, jj, i: (b, jj, 0)),
            pl.BlockSpec((None, bq, d), lambda b, jj, i: (b, i, 0)),
            pl.BlockSpec((None, 1, bq), lambda b, jj, i: (b, 0, i)),
            pl.BlockSpec((None, 1, bq), lambda b, jj, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, d), lambda b, jj, i: (b, jj, 0)),
            pl.BlockSpec((None, bk, d), lambda b, jj, i: (b, jj, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interp,
    )(q, k, v, do, lse3, delta)
    return dq, dk, dv


def _use_streaming(s, d):
    return s * d > _RESIDENT_LIMIT


# -- backward ----------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               scale, causal, bq, bk):
    # q/do/dq [bq, d]; k/v [s, d]; lse/delta [1, bq]
    qi = pl.program_id(1)
    d = q_ref.shape[-1]
    s = k_ref.shape[0]
    q = q_ref[:]
    do = do_ref[:]
    lse = lse_ref[0, :][:, None]
    delta = delta_ref[0, :][:, None]

    def body(j, dq):
        k = k_ref[pl.ds(j * bk, bk), :]
        v = v_ref[pl.ds(j * bk, bk), :]
        st = _scores(q, k, scale, (qi * bq, j * bk) if causal else None)
        p = jnp.exp(st - lse)
        dp = _dot(do, v, _NT)
        ds = p * (dp - delta) * scale
        return dq + _dot(ds.astype(k.dtype), k, _NN)

    nk = s // bk
    hi = jnp.minimum(nk, (qi * bq + bq + bk - 1) // jnp.int32(bk)) if causal else nk
    dq = lax.fori_loop(jnp.int32(0), jnp.int32(hi), body,
                       jnp.zeros((bq, d), jnp.float32))
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale, causal, bq, bk):
    # k/v/dk/dv [bk, d]; q/do [s, d]; lse/delta [1, s]
    ki = pl.program_id(1)
    d = k_ref.shape[-1]
    s = q_ref.shape[0]
    k = k_ref[:]
    v = v_ref[:]

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.ds(i * bq, bq), :]
        do = do_ref[pl.ds(i * bq, bq), :]
        lse = lse_ref[0, pl.ds(i * bq, bq)][:, None]
        delta = delta_ref[0, pl.ds(i * bq, bq)][:, None]
        st = _scores(q, k, scale, (i * bq, ki * bk) if causal else None)
        p = jnp.exp(st - lse)
        dv = dv + _dot(p.astype(do.dtype), do, _TN)
        dp = _dot(do, v, _NT)
        ds = p * (dp - delta) * scale
        dk = dk + _dot(ds.astype(q.dtype), q, _TN)
        return dk, dv

    nq = s // bq
    lo = (ki * bk) // jnp.int32(bq) if causal else 0
    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    dk, dv = lax.fori_loop(jnp.int32(lo), jnp.int32(nq), body, (dk0, dv0))
    # the scores are `scale * q k^T` with q and k as stored, so ds carries
    # `scale` once (d st / d (q k^T)) and dq = ds k, dk = ds^T q need no
    # correction
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


@i32_trace
def _mha_bwd(q, k, v, o, lse, do, causal, scale):
    bh, s, d = q.shape
    if _use_streaming(s, d):
        return _mha_bwd_stream(q, k, v, o, lse, do, causal, scale)
    bq, bk = _block_sizes(s, d, q.dtype)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, s)
    lse3 = lse.reshape(bh, 1, s)
    interp = _interpret()

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=(bh, s // bq),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, 1, bq), lambda b, i: (b, 0, i)),
            pl.BlockSpec((None, 1, bq), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, bq, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=interp,
    )(q, k, v, do, lse3, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=(bh, s // bk),
        in_specs=[
            pl.BlockSpec((None, s, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, bk, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, bk, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, s, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, 1, s), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, 1, s), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, bk, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        interpret=interp,
    )(q, k, v, do, lse3, delta)
    return dq, dk, dv


# -- custom-vjp JAX-level op --------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_bhsd(q, k, v, causal, scale):
    return _mha_fwd(q, k, v, causal, scale)[0]


def _flash_fwd_rule(q, k, v, causal, scale):
    o, lse = _mha_fwd(q, k, v, causal, scale)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, scale, res, do):
    q, k, v, o, lse = res
    return _mha_bwd(q, k, v, o, lse, do, causal, scale)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_bhsd_lse(q, k, v, causal, scale):
    """Variant returning (o, lse) — used by the framework op so the lse
    residual is a real output (saved by the tape) while jit-mode AD still
    gets the flash backward."""
    return _mha_fwd(q, k, v, causal, scale)


def _flash_lse_fwd_rule(q, k, v, causal, scale):
    o, lse = _mha_fwd(q, k, v, causal, scale)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd_rule(causal, scale, res, gs):
    q, k, v, o, lse = res
    do, _dlse = gs  # lse is a residual output; its cotangent is ignored
    return _mha_bwd(q, k, v, o, lse, do, causal, scale)


_flash_bhsd_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def flash_attention_jax(q, k, v, causal=True, scale=None):
    """Pure-JAX flash attention on [B, S, H, D] arrays (paddle layout).
    Differentiable via jax AD (custom VJP -> pallas backward kernels)."""
    b, s, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    def to_bh(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)

    o = _flash_bhsd(to_bh(q), to_bh(k), to_bh(v), bool(causal), float(scale))
    return jnp.swapaxes(o.reshape(b, h, s, d), 1, 2)


# -- framework primitive -----------------------------------------------------
# The op returns (out, lse) with save_outputs=True so the eager-tape
# backward reuses the forward's residuals and calls _mha_bwd directly —
# no forward recompute (same as the custom-vjp path under jit).

def _fa_bwd(out_grads, saved, *, causal, scale):
    q, k, v = saved.inputs
    o, lse = saved.outputs
    b, s, h, d = q.shape

    def to_bh(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)

    dq, dk, dv = _mha_bwd(to_bh(q), to_bh(k), to_bh(v), to_bh(o),
                          lse.reshape(b * h, s), to_bh(out_grads[0]),
                          causal, scale)

    def from_bh(x):
        return jnp.swapaxes(x.reshape(b, h, s, d), 1, 2)

    return from_bh(dq), from_bh(dk), from_bh(dv)


from ...framework.op_registry import primitive  # noqa: E402


@primitive("flash_attn_pallas", bwd=_fa_bwd, save_outputs=True)
def _fa_op(q, k, v, *, causal, scale):
    b, s, h, d = q.shape

    def to_bh(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)

    o, lse = _flash_bhsd_lse(to_bh(q), to_bh(k), to_bh(v), causal, scale)
    from jax.ad_checkpoint import checkpoint_name
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return jnp.swapaxes(o.reshape(b, h, s, d), 1, 2), lse.reshape(b, h, s)


def flash_attention_fwd(query, key, value, causal=True, scale=None):
    """Tensor-level entry used by nn.functional.flash_attention."""
    if scale is None:
        scale = 1.0 / math.sqrt(query.shape[-1])
    s = query.shape[1]
    if s % 128 != 0 and s > 128:
        raise ValueError(
            f"flash_attention pallas kernel needs seq_len % 128 == 0, "
            f"got {s}; use the XLA sdpa fallback for ragged lengths")
    out, _lse = _fa_op(query, key, value, causal=bool(causal),
                       scale=float(scale))
    return out


def flash_bhsd_sharded(q, k, v, causal, scale, mesh, batch_axes=("dp",),
                       head_axis="mp"):
    """Flash attention on a MULTI-DEVICE mesh: Mosaic kernels cannot be
    auto-partitioned by GSPMD (the v5e-256 overlap probe hits exactly
    this), so the kernel runs per-shard under shard_map — batch dims
    over `batch_axes`, heads over `head_axis` (the TP layout: attention
    is head-local, so no communication happens inside the map).

    q,k,v: GLOBAL [N, S, H, D] (kv already GQA-repeated to H). Heads
    must divide the head_axis degree; seq stays unsharded (sequence
    parallelism uses ring/Ulysses attention instead)."""
    from jax import shard_map

    from ...distributed.shard_util import axes_spec

    spec = axes_spec(mesh, batch_axes, None, head_axis, None)

    def body(ql, kl, vl):
        n, s, h, d = ql.shape

        def fold(a):
            return jnp.swapaxes(a, 1, 2).reshape(n * h, s, d)

        o = _flash_bhsd(fold(ql), fold(kl), fold(vl), causal, scale)
        return jnp.swapaxes(o.reshape(n, h, s, d), 1, 2)

    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)


def flash_bhsd_dispatch(q, k, v, causal, scale, mesh, batch_axes=("dp",),
                        head_axis="mp"):
    """One entry for model code: q,k,v [N, S, H, D] (kv GQA-repeated).
    Multi-device meshes route per-shard through flash_bhsd_sharded;
    single-device folds to [N*H, S, D] and calls the kernel directly.
    Returns [N, S, H, D]."""
    axes = tuple(batch_axes) + ((head_axis,) if head_axis else ())
    if mesh is not None and any(mesh.shape.get(a, 1) > 1 for a in axes):
        return flash_bhsd_sharded(q, k, v, causal, scale, mesh,
                                  batch_axes=batch_axes,
                                  head_axis=head_axis)
    n, s, h, d = q.shape

    def fold(a):
        return jnp.swapaxes(a, 1, 2).reshape(n * h, s, d)

    o = _flash_bhsd(fold(q), fold(k), fold(v), causal, scale)
    return jnp.swapaxes(o.reshape(n, h, s, d), 1, 2)
