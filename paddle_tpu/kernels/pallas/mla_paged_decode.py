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

The rows are never gathered: one grid step a slot, the kernel copies the
slot's live pool blocks (up to the one that holds its last seen key, no
further) HBM -> VMEM itself through the block table, `group` blocks a
copy group into a double buffer, the next group (the slot's, or the next
slot's first) in flight while this one is attended, the way
`ragged_paged_attention` streams its pools. A product covers `chunk`
blocks: one score product [R x heads, W] x [W, chunk x bs] of the query
[qc | q_pe | 0] against the latent rows [c | k_pe | 0] (the zero lanes
add exactly 0), one online-softmax update and one rescale of the float32
accumulator, one [R x heads, chunk x bs] x [chunk x bs, kvr] product;
only a product that reaches past a row's last key is masked. Each block
is read once for all R rows and heads. Operands to the MXU in their
stored dtype (bf16 in a cell); on other backends than the TPU the kernel
runs interpreted.
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

# keys one product scores, and the bytes of the two copy buffers
# together: at the MTP cell's widths (blocks of 64 rows x 640 lanes, 80 KB)
# 16 blocks a product and 48 a group, the fastest of 512, 1,024 and 2,048
# keys a product on a v5e (0.73 ms a call of 48 slots and two rows at 4k
# to 13k keys, 116 ns a block against the 100 ns of its copy)
_PRODUCT_KEYS = 1024
_BUFFER_BYTES = 8 * 2**20


def _interpret():
    return jax.default_backend() != "tpu"


def _blocks_per_step(block_bytes, bs, mb):
    """(blocks one copy group carries, blocks one product covers), from
    the block the kernel is handed: a product `_PRODUCT_KEYS` keys, a
    group as many whole products as two buffers hold in `_BUFFER_BYTES`;
    neither more than a table."""
    chunk = max(1, min(mb, _PRODUCT_KEYS // bs))
    chunks = max(1, min(-(-mb // chunk),
                        _BUFFER_BYTES // (2 * block_bytes * chunk)))
    return chunk * chunks, chunk


def _kernel(tabs_ref, lens_ref, base_ref, q_ref, pool, o_ref, lat_buf, sem,
            turn, m_sc, l_sc, acc_sc, *, scale, kvr, bs, group, chunk, rows,
            heads):
    """One slot. tabs_ref [S, MB], lens_ref [S, R], base_ref [1] (the
    layer's first block) in SMEM; q_ref [R x heads, W], the query [qc |
    q_pe | 0]; pool [blocks, bs, W], left in HBM; o_ref [R x heads, kvr];
    scratch: lat_buf [2, group x bs, W] the double buffer, a DMA
    semaphore for each product of each buffer (a wait counts bytes, so a
    product waits on its own copies alone), turn (SMEM [1]: groups copied
    before this slot), and m, l [R x heads, 1], acc [R x heads, kvr]
    float32."""
    s = pl.program_id(0)
    nslots = pl.num_programs(0)
    zero, one = np.int32(0), np.int32(1)
    bs_i, grp, chk = np.int32(bs), np.int32(group), np.int32(chunk)
    cols = chunk * bs
    base = base_ref[0]

    def blocks_of(slot):
        """The slot's live blocks: up to the one that holds the last key
        any of its rows sees."""
        top = lens_ref[slot, 0]
        for r in range(1, rows):
            top = jnp.maximum(top, lens_ref[slot, r])
        return (top - one) // bs_i + one

    def copy(slot, j, buf, i):
        """The copy of member i of `slot`'s group j."""
        blk = tabs_ref[slot, j * grp + i] + base
        at = pl.ds(pl.multiple_of(i * bs_i, bs), bs)
        return pltpu.make_async_copy(pool.at[blk], lat_buf.at[buf, at],
                                     sem.at[buf, i // chk])

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
        # a product reaching past a slot's last live block reads what the
        # buffer holds there: an earlier block or zero, never what the
        # chip left in VMEM (p is 0 there, and 0 x NaN is NaN)
        lat_buf[...] = jnp.zeros_like(lat_buf)
        start(zero, zero, zero)

    nblk = blocks_of(s)
    ngroups = (nblk + grp - one) // grp
    first = turn[0]                      # groups copied before this slot
    low = lens_ref[s, 0]                 # the fewest keys a row sees
    for r in range(1, rows):
        low = jnp.minimum(low, lens_ref[s, r])
    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)
    q = q_ref[...]

    def attend(buf, c, b0, masked):
        """Product c of buffer `buf`: the slot's blocks b0 .. b0 + chunk."""
        at = pl.ds(pl.multiple_of(c * np.int32(cols), cols), cols)
        st = _dot(q, lat_buf[buf, at, :], _NT) * scale
        if masked:
            shape = st.shape
            row = lax.broadcasted_iota(jnp.int32, shape, 0) // np.int32(
                heads)
            sees = jnp.zeros(shape, jnp.int32)
            for r in range(rows):
                sees = jnp.where(row == r, lens_ref[s, r], sees)
            col = lax.broadcasted_iota(jnp.int32, shape, 1) + b0 * bs_i
            st = jnp.where(col < sees, st, NEG_INF)
        m = m_sc[...]
        m_new = jnp.maximum(m, st.max(axis=-1, keepdims=True))
        p = jnp.exp(st - m_new)
        alpha = jnp.exp(m - m_new)
        l_sc[...] = l_sc[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + _dot(
            p.astype(lat_buf.dtype), lat_buf[buf, at, :kvr], _NN)
        m_sc[...] = m_new

    def one_group(j, _):
        buf = (first + j) % 2
        mine = j + one < ngroups

        @pl.when(jnp.logical_or(mine, s + one < nslots))
        def _prefetch():
            start(jnp.where(mine, s, s + one), jnp.where(mine, j + one, zero),
                  one - buf)

        here = jnp.minimum(grp, nblk - j * grp)          # live blocks

        # a block past the slot's last is never copied and starts no
        # product: the loop ends with the live ones
        def one_product(c, _):
            b0 = j * grp + c * chk
            live = jnp.minimum(chk, here - c * chk)
            keys = lat_buf.at[buf, pl.ds(pl.multiple_of(c * np.int32(cols),
                                                        cols), cols)]

            # a semaphore counts bytes: one wait the size of the product's
            # blocks covers all of their copies
            @pl.when(live == chk)
            def _whole():
                pltpu.make_async_copy(keys, keys, sem.at[buf, c]).wait()

            @pl.when(live < chk)
            def _part():
                def arrived(i, _):
                    copy(s, j, buf, c * chk + i).wait()
                    return _
                lax.fori_loop(zero, live, arrived, zero)

            # only a product past a row's last key is masked
            past = (b0 + chk) * bs_i > low

            @pl.when(past)
            def _edge():
                attend(buf, c, b0, True)

            @pl.when(jnp.logical_not(past))
            def _inside():
                attend(buf, c, b0, False)
            return _
        lax.fori_loop(zero, (here + chk - one) // chk, one_product, zero)
        return _

    lax.fori_loop(zero, ngroups, one_group, zero)
    turn[0] = first + ngroups
    o_ref[...] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)


@i32_trace
def _launch(q, pool, tables, lens, base, scale, kvr):
    slots, n, width = q.shape
    rows = lens.shape[1]
    heads = n // rows
    bs = pool.shape[1]
    mb = tables.shape[1]
    block_bytes = bs * width * pool.dtype.itemsize
    group, chunk = _blocks_per_step(block_bytes, bs, mb)

    def slot(*shape):
        return pl.BlockSpec((None,) + shape,
                            lambda s, *_: (s,) + (0,) * len(shape))

    return pl.pallas_call(
        functools.partial(_kernel, scale=np.float32(scale), kvr=kvr, bs=bs,
                          group=group, chunk=chunk, rows=rows, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots,),
            in_specs=[slot(n, width), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=slot(n, kvr),
            scratch_shapes=[pltpu.VMEM((2, group * bs, width), pool.dtype),
                            pltpu.SemaphoreType.DMA((2, group // chunk)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((n, 1), jnp.float32),
                            pltpu.VMEM((n, 1), jnp.float32),
                            pltpu.VMEM((n, kvr), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((slots, n, kvr), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * group * block_bytes + 32 * 2**20),
        interpret=_interpret(),
    )(tables, lens, base, q, pool)


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
    width = pool.shape[-1]
    q = jnp.concatenate([qc, q_pe.astype(qc.dtype)], axis=-1)
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, width - q.shape[-1]),))
    out = _launch(q.reshape(S, R * heads, width), pool,
                  tables.astype(jnp.int32), lens.astype(jnp.int32),
                  jnp.asarray(base, jnp.int32).reshape(1), float(scale),
                  kv_lora_rank)
    return out.reshape(S, R, heads, kvr)
