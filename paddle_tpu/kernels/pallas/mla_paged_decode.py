"""Pallas TPU kernel: multi-head latent attention of decode rows in the
absorbed form over EVERY cached latent row of their slot, read from the
paged pool through the slot's block table (forward only).

For slot s, its query rows r < R (row r at its own position, seeing the
keys j < lens[s, r]) and every head h, with the latent rows
c_j = [c | k_pe]:

    s_rh(j) = (qc_rh . c_j[:kvr] + q_pe_rh . k_pe_j) * scale
    o_rh = sum_{j < lens[s, r]} softmax_j(s_rh) c_j[:kvr]      [kv_lora_rank]

where qc_rh = q_nope_rh Wuk_h^T folds the key up-projection into the
query (the caller takes o_rh through Wuv_h). R is the number of rows a
slot asks at once: 1 for a plain decode step, k + 1 for a pass that
verifies k drafted tokens, each row one position further.

The rows are never gathered: the grid walks a slot's blocks in steps of
`group` blocks, and each block's index map reads its pool block from the
scalar-prefetched table, so the pipeline copies it HBM -> VMEM while the
step before it computes. A slot's blocks past the one that holds its
last live row map to that block again, which the pipeline does not copy
a second time, and their steps compute nothing. Each block is read once
for all R rows and heads; the scores of a block are [R x heads, bs] and
an online softmax runs over the blocks, float32. Operands to the MXU in
their stored dtype (bf16 in a cell); on other backends than the TPU the
kernel runs interpreted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._x64 import i32_trace
from .flash_attention import _NN, _NT, NEG_INF, _dot

__all__ = ["mla_paged_decode_attention"]

# bytes of latent blocks one grid step copies: a step costs about a third
# of a microsecond whatever it reads, so it reads about a megabyte
_STEP_BYTES = 1 << 20


def _interpret():
    return jax.default_backend() != "tpu"


def _last_block(lens_ref, s, rows, bs):
    """The index in slot s's table of its block that holds the last key
    any of its rows sees."""
    top = lens_ref[s, 0]
    for r in range(1, rows):
        top = jnp.maximum(top, lens_ref[s, r])
    return (top - np.int32(1)) // np.int32(bs)


def _kernel(tabs_ref, lens_ref, base_ref, qc_ref, qp_ref, *refs, scale, kvr,
            bs, group, rows, heads):
    """One (slot, step of `group` blocks). tabs_ref [S, MB], lens_ref [S,
    R], base_ref [1] in SMEM; qc_ref [R x heads, kvr]; qp_ref [R x heads,
    W - kvr]; refs: `group` blocks [bs, W], then o_ref [R x heads, kvr]
    and the scratch m, l [R x heads, 1], acc [R x heads, kvr] float32."""
    blocks, (o_ref, m_sc, l_sc, acc_sc) = refs[:group], refs[group:]
    s, j = pl.program_id(0), pl.program_id(1)
    last = _last_block(lens_ref, s, rows, bs)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    @pl.when(j * np.int32(group) <= last)
    def _step():
        qc, qp = qc_ref[...], qp_ref[...]
        shape = (rows * heads, bs)
        row = lax.broadcasted_iota(jnp.int32, shape, 0) // np.int32(heads)
        sees = jnp.zeros(shape, jnp.int32)
        for r in range(rows):
            sees = jnp.where(row == r, lens_ref[s, r], sees)
        col = lax.broadcasted_iota(jnp.int32, shape, 1)
        for g in range(group):
            lat = blocks[g][...]
            c, pe = lat[:, :kvr], lat[:, kvr:]
            st = (_dot(qc, c, _NT) + _dot(qp, pe, _NT)) * scale
            first = (j * np.int32(group) + np.int32(g)) * np.int32(bs)
            st = jnp.where(first + col < sees, st, NEG_INF)
            m = m_sc[:]
            m_new = jnp.maximum(m, st.max(axis=-1, keepdims=True))
            p = jnp.exp(st - m_new)
            alpha = jnp.exp(m - m_new)
            l_sc[:] = l_sc[:] * alpha + p.sum(axis=-1, keepdims=True)
            acc_sc[:] = acc_sc[:] * alpha + _dot(p.astype(c.dtype), c, _NN)
            m_sc[:] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_sc[:] / l_sc[:]).astype(o_ref.dtype)


@i32_trace
def _launch(qc, qp, pool, tables, lens, base, scale, kvr):
    slots, n, _ = qc.shape
    rows = lens.shape[1]
    heads = n // rows
    bs, width = pool.shape[1:]
    mb = tables.shape[1]
    block_bytes = bs * width * pool.dtype.itemsize
    group = max(1, min(mb, _STEP_BYTES // block_bytes))
    steps = -(-mb // group)

    def block_of(g):
        def index(s, j, tabs, lens_ref, base_ref):
            at = jnp.minimum(j * np.int32(group) + np.int32(g),
                             _last_block(lens_ref, s, rows, bs))
            return tabs[s, at] + base_ref[0], 0, 0
        return pl.BlockSpec((None, bs, width), index)

    def slot(*shape):
        return pl.BlockSpec((None,) + shape,
                            lambda s, j, *_: (s,) + (0,) * len(shape))

    return pl.pallas_call(
        functools.partial(_kernel, scale=np.float32(scale), kvr=kvr, bs=bs,
                          group=group, rows=rows, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots, steps),
            in_specs=[slot(n, kvr), slot(n, qp.shape[-1])]
            + [block_of(g) for g in range(group)],
            out_specs=slot(n, kvr),
            scratch_shapes=[pltpu.VMEM((n, 1), jnp.float32),
                            pltpu.VMEM((n, 1), jnp.float32),
                            pltpu.VMEM((n, kvr), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((slots, n, kvr), qc.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=2 * group * block_bytes + 32 * 2**20),
        interpret=_interpret(),
    )(tables, lens, base, qc, qp, *([pool] * group))


def mla_paged_decode_attention(qc, q_pe, pool, tables, lens, base,
                               kv_lora_rank, scale):
    """Absorbed MLA of R query rows a slot over the slot's latent rows,
    read from the paged pool through its block table.

    qc [S, R, heads, kv_lora_rank] (q_nope with the key up-projection
    folded in); q_pe [S, R, heads, dr] (the rotary term applied); pool
    [blocks, bs, W] latent rows [c | k_pe | zeros] of every layer;
    tables [S, MB] int32, slot s's position j lies in pool block
    `tables[s, j // bs] + base` (`base`, an int32 scalar: the layer's
    first block), row j % bs; lens [S, R] int32 >= 1, the keys row r of
    slot s sees (positions 0 .. lens - 1). Only the blocks up to the one
    that holds a slot's last seen key are read. Returns the latent output
    [S, R, heads, kv_lora_rank] in qc's dtype."""
    S, R, heads, kvr = qc.shape
    width = pool.shape[-1] - kv_lora_rank
    qp = jnp.pad(q_pe, ((0, 0),) * 3 + ((0, width - q_pe.shape[-1]),))
    out = _launch(qc.reshape(S, R * heads, kvr),
                  qp.astype(qc.dtype).reshape(S, R * heads, width), pool,
                  tables.astype(jnp.int32), lens.astype(jnp.int32),
                  jnp.asarray(base, jnp.int32).reshape(1), float(scale),
                  kv_lora_rank)
    return out.reshape(S, R, heads, kvr)
