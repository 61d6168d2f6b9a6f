"""Pallas TPU flash attention for a prefill chunk against keys that lie
before and inside it (forward only).

What the training kernel (`flash_attention.py`) does not take and a
served prompt needs, in one streaming kernel:

- the queries are a chunk of a longer sequence: row i sits at key
  position `q_start + i` (a scalar the program computes, not a shape),
  so one compiled program attends any chunk of any prompt against the
  keys gathered from the cache, and never forms more than a
  `[bq, bk]` tile of scores;
- grouped KV heads without repeating K or V: query head h reads KV head
  `h // nrep` through the block index map;
- K rows and V rows of different widths (`o` is as wide as V's);
- a sliding window: row i attends keys `max(kv_first, p - window + 1) ..
  p` at `p = q_start + i`. The grid's key axis then covers only the
  band (the blocks a query block's window can touch), not the causal
  triangle, and a block index past the band repeats the last live one,
  which costs no copy;
- a learned sink: a bias a head that joins the softmax's denominator and
  carries no value. The running (max, sum) start at (sink, 1).

Arithmetic as `flash_attention.py`'s: operands to the MXU in the dtype
they are stored in, float32 accumulated; scores, mask, running max and
sum, `exp` and the accumulator in float32; `p` rounded to V's dtype for
the second product. On other backends than the TPU the kernel runs
interpreted.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._x64 import i32_trace
from .flash_attention import _NN, _NT, NEG_INF, _dot

__all__ = ["flash_prefill_attention"]


def _interpret():
    return jax.default_backend() != "tpu"


def _block(n, cap):
    """The largest of cap, cap/2, .. 8 that divides n, else n itself."""
    b = cap
    while b >= 8:
        if n % b == 0:
            return b
        b //= 2
    return n


def _kernel(at_ref, q_ref, k_ref, v_ref, *rest, scale, bq, bk, window,
            sunk):
    """One (head, query block, key step) of the grid. at_ref (SMEM)
    [q_start, kv_first]; q_ref [bq, dk]; k_ref [bk, dk]; v_ref [bk, dv];
    with `sunk` sink_ref (SMEM) [nh] float32; o_ref [bq, dv]."""
    if sunk:
        sink_ref, *rest = rest
    o_ref, m_sc, l_sc, acc_sc = rest
    h, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    row0 = at_ref[0] + i * np.int32(bq)
    first = _first_block(row0, bk, window)
    blk = first + j
    last = (row0 + np.int32(bq - 1)) // np.int32(bk)

    @pl.when(j == 0)
    def _init():
        if sunk:
            m_sc[:] = jnp.full_like(m_sc, sink_ref[h])
            l_sc[:] = jnp.ones_like(l_sc)
        else:
            m_sc[:] = jnp.full_like(m_sc, NEG_INF)
            l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    @pl.when(blk <= last)
    def _step():
        v = v_ref[:]
        st = _dot(q_ref[:], k_ref[:], _NT) * scale
        row = row0 + lax.broadcasted_iota(jnp.int32, st.shape, 0)
        col = blk * np.int32(bk) + lax.broadcasted_iota(jnp.int32,
                                                         st.shape, 1)
        sees = jnp.logical_and(col <= row, col >= at_ref[1])
        if window is not None:
            sees = jnp.logical_and(sees, col > row - np.int32(window))
        st = jnp.where(sees, st, NEG_INF)
        m = m_sc[:]
        m_new = jnp.maximum(m, st.max(axis=-1, keepdims=True))
        p = jnp.exp(st - m_new)
        alpha = jnp.exp(m - m_new)
        l_sc[:] = l_sc[:] * alpha + p.sum(axis=-1, keepdims=True)
        acc_sc[:] = acc_sc[:] * alpha + _dot(p.astype(v.dtype), v, _NN)
        m_sc[:] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[:] = (acc_sc[:] / l_sc[:]).astype(o_ref.dtype)


def _first_block(row0, bk, window):
    """The first key block a query block starting at position `row0`
    attends."""
    if window is None:
        return np.int32(0)
    return jnp.maximum(row0 - np.int32(window - 1), np.int32(0)) \
        // np.int32(bk)


@i32_trace
def _launch(q, k, v, at, scale, window, sinks):
    nh, tq, dk = q.shape
    nkv, tk, dv = v.shape
    nrep = nh // nkv
    bq = _block(tq, 512)
    bk = _block(tk, 512 if window is None else 128)
    if window is None:
        steps = tk // bk
    else:
        # the blocks a query block's band can touch: its own rows and
        # the window before them, from wherever the first one starts
        steps = min(tk // bk, -(-(bq + window - 1) // bk) + 1)

    def kv_block(h, i, j, at_ref, *_):
        row0 = at_ref[0] + i * np.int32(bq)
        last = (row0 + np.int32(bq - 1)) // np.int32(bk)
        return (h // np.int32(nrep),
                jnp.minimum(_first_block(row0, bk, window) + j, last), 0)

    in_specs = [pl.BlockSpec((None, bq, dk), lambda h, i, j, *_: (h, i, 0)),
                pl.BlockSpec((None, bk, dk), kv_block),
                pl.BlockSpec((None, bk, dv), kv_block)]
    operands = [q, k, v]
    if sinks is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(sinks.astype(jnp.float32))
    return pl.pallas_call(
        functools.partial(_kernel, scale=np.float32(scale), bq=bq, bk=bk,
                          window=window, sunk=sinks is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nh, tq // bq, steps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, bq, dv),
                                   lambda h, i, j, *_: (h, i, 0)),
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((nh, tq, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(at, *operands)


def flash_prefill_attention(q, k, v, q_start, kv_first=0, window=None,
                            sinks=None, scale=None):
    """Causal attention of a chunk of queries against keys that start
    before it.

    q [Tq, nh, dk]; k [Tk, nkv, dk]; v [Tk, nkv, dv]; query row i lies at
    key position `q_start + i` (int32 scalar, traced) and attends keys
    `max(kv_first, p - window + 1) .. p` (`window` None: from
    `kv_first`). `sinks` [nh] float32 joins each head's denominator.
    Tq and Tk are whole numbers of blocks (the largest of 512, 256, ..
    that divides them; 128 for the keys of a window). A key past
    `q_start + Tq - 1` is never read.
    Returns [Tq, nh, dv] in q's dtype."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    at = jnp.stack([jnp.asarray(q_start, jnp.int32),
                    jnp.asarray(kv_first, jnp.int32)])
    o = _launch(jnp.swapaxes(q, 0, 1), jnp.swapaxes(k, 0, 1),
                jnp.swapaxes(v, 0, 1), at, float(scale),
                None if window is None else int(window), sinks)
    return jnp.swapaxes(o, 0, 1)
