"""Pallas TPU kernel: a decode step's multi-head latent attention in the
absorbed form, over the latent rows its indexer chose (forward only).

For a slot s with chosen latent rows c_i = [c | k_pe] (i < count[s]),
every head h:

    s_h(i) = (qc_h . c_i[:kvr] + q_pe_h . k_pe_i) * scale
    o_h = sum_i softmax_i(s_h) c_i[:kvr]                 [kv_lora_rank]

where qc_h = q_nope_h Wuk_h^T folds the key up-projection into the query
(and the caller takes o_h through Wuv_h). The rows come gathered from
the paged pool by XLA, [k, W] a slot with W whole lanes (k_pe behind c,
zeros past its dims; q_pe padded to the same width): the kernel cannot
copy a chosen row out of the pool itself, because the pool's HBM tiles
hold 8 rows and Mosaic refuses a copy of fewer than a tile's rows
("Slice shape along dimension 0 must be aligned to tiling (8)"), and a
tile a chosen row is 8 x its bytes. A 32-bit view whose tile is one row
([rows, 1, 384], 1.2 x the pool's bytes) can be copied a row at a time,
but 48 x 2,048 such copies took 3.0 ms a layer on a TPU v5e, where XLA's
gather takes 1.6. Places from `count[s]` on are
masked. One grid step a slot: its rows, its heads' scores [heads, k]
and their softmax stay in VMEM. Operands to the MXU in their stored
dtype, float32 accumulated and softmaxed; on other backends than the TPU
the kernel runs interpreted.
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

__all__ = ["mla_decode_attention"]


def _interpret():
    return jax.default_backend() != "tpu"


def _kernel(count_ref, qc_ref, qp_ref, rows_ref, o_ref, *, scale, kvr):
    """One slot. count_ref (SMEM) [S]; qc_ref [heads, kvr]; qp_ref
    [heads, W - kvr]; rows_ref [k, W]; o_ref [heads, kvr]."""
    rows = rows_ref[...]
    c, pe = rows[:, :kvr], rows[:, kvr:]
    st = (_dot(qc_ref[...], c, _NT) + _dot(qp_ref[...], pe, _NT)) * scale
    at = lax.broadcasted_iota(jnp.int32, st.shape, 1)
    st = jnp.where(at < count_ref[pl.program_id(0)], st, NEG_INF)
    p = jnp.exp(st - st.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    o_ref[...] = _dot(p.astype(c.dtype), c, _NN).astype(o_ref.dtype)


@i32_trace
def _launch(qc, qp, rows, count, scale, kvr):
    slots, heads, _ = qc.shape
    k, width = rows.shape[1:]
    return pl.pallas_call(
        functools.partial(_kernel, scale=np.float32(scale), kvr=kvr),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots,),
            in_specs=[pl.BlockSpec((None, heads, kvr),
                                   lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec((None, heads, qp.shape[-1]),
                                   lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec((None, k, width), lambda s, *_: (s, 0, 0))],
            out_specs=pl.BlockSpec((None, heads, kvr),
                                   lambda s, *_: (s, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((slots, heads, kvr), qc.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=64 * 2**20),
        interpret=_interpret(),
    )(count, qc, qp, rows)


def mla_decode_attention(qc, q_pe, rows, count, kv_lora_rank, scale):
    """Absorbed MLA of one decode row a slot over its chosen latent rows.

    qc [S, heads, kv_lora_rank] (q_nope with the key up-projection folded
    in); q_pe [S, heads, dr] (the rotary term applied); rows [S, k, W]
    latent rows [c | k_pe | zeros]; count [S] int32, how many of a slot's
    rows are chosen (at least one). Returns the latent output [S, heads,
    kv_lora_rank] in qc's dtype."""
    width = rows.shape[-1] - kv_lora_rank
    qp = jnp.pad(q_pe, ((0, 0), (0, 0), (0, width - q_pe.shape[-1])))
    return _launch(qc, qp.astype(qc.dtype), rows, count.astype(jnp.int32),
                   float(scale), kv_lora_rank)
