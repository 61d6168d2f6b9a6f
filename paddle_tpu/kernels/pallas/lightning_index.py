"""Pallas TPU kernels: the lightning indexer's scores.

    I(t, j) = sum_h w[t, h] * relu(q[t, h] . k[j])

`lightning_index_scores`: query rows t of a prefill chunk (row t at key
position `q_start + t`) against every key j of their sequence (keys past
the chunk's last row are not computed; their scores read 0 and the
selection masks them). Written as XLA the product [rows, heads, keys] in
float32 would be materialised before the sum over heads (4.3 GB for
1,024 rows, 64 heads and 16,384 keys); here a tile of rows and keys
keeps its heads' products in VMEM and only the [rows, keys] sum goes
back.

`lightning_index_decode`: one query row a slot against that slot's own
keys (a decode step), key tiles past the slot's position skipped.

Operands go to the MXU in their stored dtype (bf16 in a cell), float32
accumulated; `relu`, the weights and the sum over heads in float32. On
other backends than the TPU the kernel runs interpreted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._x64 import i32_trace
from .flash_attention import _NT, _dot
from .flash_prefill import _block

__all__ = ["lightning_index_decode", "lightning_index_scores"]

# heads whose products one MXU call forms: [group x rows, keys] float32
# stays at 2 MB for 128 rows and 512 keys
_GROUP = 8


def _interpret():
    return jax.default_backend() != "tpu"


def _kernel(at_ref, q_ref, w_ref, k_ref, o_ref, *, rt, bk, heads, group):
    """One (row tile, key tile). at_ref (SMEM) [q_start]; q_ref [heads,
    rt, d]; w_ref [rt, heads] float32; k_ref [bk, d]; o_ref [rt, bk]."""
    i, j = pl.program_id(0), pl.program_id(1)
    last = (at_ref[0] + (i + 1) * np.int32(rt) - 1) // np.int32(bk)

    @pl.when(j <= last)
    def _scores():
        k, w = k_ref[...], w_ref[...]
        acc = jnp.zeros((rt, bk), jnp.float32)
        for g in range(heads // group):
            q = q_ref[g * group:(g + 1) * group].reshape(group * rt, -1)
            s = jnp.maximum(_dot(q, k, _NT), 0.0).reshape(group, rt, bk)
            for hh in range(group):
                h = g * group + hh
                acc = acc + w[:, h:h + 1] * s[hh]
        o_ref[...] = acc

    @pl.when(j > last)
    def _past():
        o_ref[...] = jnp.zeros_like(o_ref)


@i32_trace
def _launch(q, w, k, at):
    heads, tq, d = q.shape
    tk = k.shape[0]
    rt, bk = _block(tq, 128), _block(tk, 512)
    group = _GROUP if heads % _GROUP == 0 else 1

    def k_block(i, j, at_ref):
        last = (at_ref[0] + (i + 1) * np.int32(rt) - 1) // np.int32(bk)
        return jnp.minimum(j, last), 0

    return pl.pallas_call(
        functools.partial(_kernel, rt=rt, bk=bk, heads=heads, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tq // rt, tk // bk),
            in_specs=[pl.BlockSpec((heads, rt, d),
                                   lambda i, j, *_: (0, i, 0)),
                      pl.BlockSpec((rt, heads), lambda i, j, *_: (i, 0)),
                      pl.BlockSpec((bk, d), k_block)],
            out_specs=pl.BlockSpec((rt, bk), lambda i, j, *_: (i, j))),
        out_shape=jax.ShapeDtypeStruct((tq, tk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(at, q, w, k)


def lightning_index_scores(q, w, k, q_start):
    """Index scores of a chunk of queries against one sequence's keys.

    q [Tq, heads, d] (the query row t lies at key position `q_start + t`,
    an int32 scalar, traced); w [Tq, heads] (the per-head weights, float32
    or cast to it); k [Tk, d]. Tq and Tk are whole tiles (the largest of
    128 .. 8 rows and 512 .. 8 keys that divides them). Returns [Tq, Tk]
    float32: `sum_h w[t, h] relu(q[t, h] . k[j])` for keys up to the
    tile that holds the chunk's last row, 0 behind it."""
    at = jnp.asarray(q_start, jnp.int32).reshape(1)
    return _launch(jnp.swapaxes(q, 0, 1), w.astype(jnp.float32), k, at)


def _slots_kernel(pos_ref, q_ref, w_ref, k_ref, o_ref, *, bk):
    """One (slot, key tile). pos_ref (SMEM) [S]; q_ref [heads, d]; w_ref
    [heads, 1] float32; k_ref [bk, d]; o_ref [1, bk]."""
    s, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j <= pos_ref[s] // np.int32(bk))
    def _scores():
        prod = jnp.maximum(_dot(q_ref[...], k_ref[...], _NT), 0.0)
        o_ref[...] = jnp.sum(w_ref[...] * prod, axis=0, keepdims=True)

    @pl.when(j > pos_ref[s] // np.int32(bk))
    def _past():
        o_ref[...] = jnp.zeros_like(o_ref)


@i32_trace
def _launch_slots(q, w, k, pos):
    slots, heads, d = q.shape
    n = k.shape[1]
    bk = _block(n, 2048)

    def k_block(s, j, pos_ref):
        return s, jnp.minimum(j, pos_ref[s] // np.int32(bk)), 0

    return pl.pallas_call(
        functools.partial(_slots_kernel, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots, n // bk),
            in_specs=[pl.BlockSpec((None, heads, d),
                                   lambda s, j, *_: (s, 0, 0)),
                      pl.BlockSpec((None, heads, 1),
                                   lambda s, j, *_: (s, 0, 0)),
                      pl.BlockSpec((None, bk, d), k_block)],
            out_specs=pl.BlockSpec((None, 1, bk), lambda s, j, *_: (s, 0, j))),
        out_shape=jax.ShapeDtypeStruct((slots, 1, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(pos, q, w, k)


def lightning_index_decode(q, w, k, pos):
    """Index scores of one query row a slot against the slot's own keys.

    q [S, heads, d]; w [S, heads] (float32 or cast to it); k [S, N, d],
    key j of slot s at position j; pos [S] int32, the slot's position
    (keys past it are masked by the selection). N is whole tiles (the
    largest of 2048 .. 8 that divides it). Returns [S, N] float32:
    `sum_h w[s, h] relu(q[s, h] . k[s, j])` for the tiles up to the one
    that holds `pos`, 0 behind."""
    out = _launch_slots(q, w.astype(jnp.float32)[..., None], k,
                        pos.astype(jnp.int32))
    return out[:, 0]
