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
keys (a decode step). The keys stay in the paged pool: the kernel copies
a slot's live blocks itself, through its block table, the way
`ragged_paged_attention` does (no gathered copy of the keys in HBM), and
none past the slot's position.

Operands go to the MXU in their stored dtype (bf16 in a cell), float32
accumulated; `relu`, the weights and the sum over heads in float32. On
other backends than the TPU the kernel runs interpreted.
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
from .flash_attention import _NT, _dot
from .flash_prefill import _block
from .ragged_paged_attention import _blocks_per_step

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


def _slots_kernel(tabs_ref, pos_ref, base_ref, q_ref, w_ref, k_hbm, o_ref,
                  kbuf, sem, turn, *, bs, group, chunk, products):
    """One slot: its live pool blocks (those that hold positions 0 ..
    pos[s]) stream HBM -> VMEM through its block table in groups of
    `group`, double-buffered, the next group (this slot's, or the next
    slot's first) in flight while this one is scored, `chunk` blocks a
    product; `ragged_paged_attention._step_kernel`'s pipeline, K alone.

    tabs_ref [S, MB], pos_ref [S], base_ref [1] (the layer's first block
    in the pool) in SMEM; q_ref [heads, d]; w_ref [heads, 1] float32;
    k_hbm the pool [blocks, bs, d], left in HBM; o_ref [1, products *
    chunk * bs]: product p's columns are its blocks' positions, 0 from
    the slot's last live block on."""
    s = pl.program_id(0)
    nslots = pl.num_programs(0)
    zero, one = np.int32(0), np.int32(1)
    bs_i, grp = np.int32(bs), np.int32(group)
    cols = chunk * bs
    base = base_ref[0]

    def blocks_of(slot):
        return pos_ref[slot] // bs_i + one

    def copy(slot, j, buf, i):
        """The copy of member i of `slot`'s group j."""
        blk = tabs_ref[slot, j * grp + i] + base
        at = pl.ds(pl.multiple_of(i * bs_i, bs), bs)
        return pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[buf, at],
                                     sem.at[buf])

    def start(slot, j, buf):
        def member(i, _):
            copy(slot, j, buf, i).start()
            return _
        lax.fori_loop(zero, jnp.minimum(grp, blocks_of(slot) - j * grp),
                      member, zero)

    # a slot's first group is started by the slot before it, behind its
    # own last group
    @pl.when(s == 0)
    def _first():
        turn[0] = zero
        start(zero, zero, zero)

    nblk = blocks_of(s)
    first = turn[0]                      # groups scored before this slot
    q, w = q_ref[...], w_ref[...]
    for p in range(products):
        j, c = divmod(p, group // chunk)
        buf = (first + j) % 2
        live = nblk - p * chunk          # the slot's blocks from p's first
        out = slice(p * cols, (p + 1) * cols)

        @pl.when(live > 0)
        def _scores():
            if c == 0:
                mine = (j + 1) * group < nblk

                @pl.when(jnp.logical_or(mine, s + one < nslots))
                def _prefetch():
                    start(jnp.where(mine, s, s + one),
                          jnp.where(mine, np.int32(j + 1), zero), one - buf)

            here = jnp.minimum(np.int32(chunk), live)
            keys = kbuf.at[buf, c * cols:(c + 1) * cols]

            # a semaphore counts bytes: one wait the size of the product's
            # blocks covers all of their copies
            @pl.when(here == chunk)
            def _whole():
                pltpu.make_async_copy(keys, keys, sem.at[buf]).wait()

            @pl.when(here < chunk)
            def _part():
                def arrived(i, _):
                    copy(s, j, buf, c * chunk + i).wait()
                    return _
                lax.fori_loop(zero, here, arrived, zero)

            prod = jnp.maximum(_dot(q, keys[...], _NT), 0.0)
            sc = jnp.sum(w * prod, axis=0, keepdims=True)
            # what the buffer holds behind the live blocks is another
            # group's keys, or nothing ever written
            col = lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            o_ref[:, out] = jnp.where(col < here * bs_i, sc, 0.0)

        @pl.when(live <= 0)
        def _past():
            o_ref[:, out] = jnp.zeros((1, cols), jnp.float32)

    turn[0] = first + (nblk + grp - one) // grp


@i32_trace
def _launch_slots(q, w, pool, tables, pos, base):
    slots, heads, d = q.shape
    bs, mb = pool.shape[1], tables.shape[1]
    block_bytes = bs * d * pool.dtype.itemsize
    # K alone: two buffers where the attention kernel has four
    group, chunk = _blocks_per_step(block_bytes // 2, bs, mb)
    products = -(-mb // chunk)

    def slot(*shape):
        return pl.BlockSpec((None,) + shape,
                            lambda s, *_: (s,) + (0,) * len(shape))

    return pl.pallas_call(
        functools.partial(_slots_kernel, bs=bs, group=group, chunk=chunk,
                          products=products),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots,),
            in_specs=[slot(heads, d), slot(heads, 1),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=slot(1, products * chunk * bs),
            scratch_shapes=[pltpu.VMEM((2, group * bs, d), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((slots, 1, products * chunk * bs),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * group * block_bytes + 32 * 2**20),
        interpret=_interpret(),
    )(tables, pos, base, q, w, pool)


def lightning_index_decode(q, w, pool, tables, pos, base=0):
    """Index scores of one query row a slot against the slot's own keys,
    read from the paged pool through its block table.

    q [S, heads, d]; w [S, heads] (float32 or cast to it); pool [blocks,
    bs, d], the indexer keys of every layer; tables [S, MB] int32, slot
    s's key at position j lies in pool block `tables[s, j // bs] + base`
    (`base`, an int32 scalar: the layer's first block), row j % bs; pos
    [S] int32, the slot's position. Only the blocks up to the one that
    holds `pos` are copied. Returns [S, MB * bs] float32: `sum_h w[s, h]
    relu(q[s, h] . k[s, j])` for the positions of those blocks (the ones
    past `pos` score whatever the pool holds there: the selection masks
    them), 0 behind."""
    mb, bs = tables.shape[1], pool.shape[1]
    out = _launch_slots(q, w.astype(jnp.float32)[..., None], pool,
                        tables.astype(jnp.int32), pos.astype(jnp.int32),
                        jnp.asarray(base, jnp.int32).reshape(1))
    return out[:, 0, :mb * bs]
