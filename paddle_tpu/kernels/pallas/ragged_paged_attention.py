"""Pallas TPU ragged paged attention for the serving decode path.

Reference capability: the block-table decode attention of
phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu, fused the
way "Ragged Paged Attention" (arxiv 2604.15464) does it on TPU: the
kernel reads K/V blocks DIRECTLY from the paged pool through the block
table and stops at each sequence's true length.

Why this exists: models/paged_decode.py's dense path materializes a
gathered window `[S, W, Hkv, D]` (W = blocks_per_seq * block_size) in
HBM before attending — every slot READS the full window twice (pool
gather read, then attention read of the gathered copy) and writes it
once, regardless of its actual length. Here the pool blocks stream
HBM -> VMEM exactly once, and whole blocks past `seq_lens[s]` are never
copied at all (the ragged early-exit), so a slot at position p costs
`(p // bs + 1) * bs` tokens of read traffic instead of `2 * W` reads
plus a `W` write.

Mechanics:

- grid = (S,), one step a slot. The pools stay in HBM
  (`memory_space=ANY`), seen as `[blocks, bs * Hkv, D]`: tokens and KV
  heads merged into one axis of rows, which under the chip's tiled
  layouts is the same bytes (XLA makes the reshape a bitcast). The body
  copies the slot's LIVE blocks itself, through the scalar-prefetched
  block table, into two VMEM buffers of `group` blocks for K and two
  for V: while one group is attended the next is in flight, this slot's
  or the next slot's first, so the DMA engine never waits for a grid
  step. A block past `seq_lens[s] // bs` gets no copy, and the loops
  over blocks end with the live ones: the early exit costs nothing, and
  a table entry behind the live ones is never read.
- one product `q [nh, D] x K^T [D, chunk * bs * Hkv]` gives the scores
  of every query head against every (token, KV head) row of `chunk`
  blocks; a query head keeps the columns of its own KV head (the rest
  are masked before the softmax and are 0 in `p`), so `p x V` lands in
  the accumulator's layout with no head ever sliced out of a block. The
  masked share is arithmetic the MXU has to spare; the kernel's time is
  that of its bytes. K, V and q go to the MXU in the dtype they are
  stored in, float32 accumulated (`flash_attention._dot`); `p` is
  rounded to that dtype before the second product.
- `_blocks_per_step` sizes `group` and `chunk` from the block the kernel
  is handed (its bytes against the buffers, its rows against a product's
  columns): 4 and 1 at 32 KV heads, 32 and 16 at 2.
- online softmax (running m / l / acc in VMEM scratch across the slot's
  products) keeps the whole reduction in one pass. The plain, partials
  (sharded) and int8 entry points share the one step body: int8 codes
  are exact in q's dtype and their row scales multiply score and
  probability columns; the partials finish with (o, lse).

On non-TPU backends the kernel runs in interpret mode so tier-1 CI
exercises the exact kernel code (flash_attention.py's pattern).
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
from .flash_attention import _NN, _NT, _dot

__all__ = ["ragged_paged_attention", "ragged_paged_attention_sharded",
           "ragged_paged_attention_quant",
           "kv_quantize_rows", "kv_dequantize_rows", "kv_row_error_bound",
           "ragged_hbm_bytes", "dense_gather_hbm_bytes",
           "record_ragged_step"]

import numpy as np

# the kernel body is re-traced at pallas lowering time,
# OUTSIDE the i32_trace context — every scalar constant must carry an
# explicit 32-bit dtype or global x64 mode promotes it to f64/i64, which
# Mosaic (and the interpret-mode verifier) reject
NEG_INF = np.float32(-1e30)


def _interpret():
    return jax.default_backend() != "tpu"


# both pools' double buffers together; what the scores, the mask and the
# pipeline's own q / output / scale blocks need fits beside them under
# the limit _launch asks for (the chip's default scoped limit is 16 MiB
# of its 128)
_BUFFER_BYTES = 8 * 2**20
# score columns one product covers: a 64-token block of 32 KV heads
# alone, 16 blocks of 2 KV heads together
_PRODUCT_COLS = 2048


def _blocks_per_step(block_bytes, block_rows, blocks_per_seq):
    """(blocks one DMA group carries, blocks one product covers), from
    what the kernel can see: a group is as many blocks as the buffers
    hold (K and V, two buffers each), whole products and at most a
    sequence. Blocks of 2048 rows (32 KV heads, 0.5 MB of K and as much
    of V) come 4 a group and 1 a product; blocks of 128 rows (2 KV
    heads, 32 KB) 32 and 16."""
    chunk = max(1, min(blocks_per_seq, _PRODUCT_COLS // block_rows))
    chunks = max(1, min(-(-blocks_per_seq // chunk),
                        _BUFFER_BYTES // (4 * block_bytes * chunk)))
    return chunk * chunks, chunk


def _step_kernel(tabs_ref, lens_ref, *rest, bs, nkv, nrep, scale, group,
                 chunk, quant, partials, bounded=False, sunk=False):
    """One slot: its live pool blocks stream HBM -> VMEM in groups of
    `group`, double-buffered, the next group (this slot's, or the next
    slot's first) in flight while this one is attended, `chunk` blocks
    a product.

    q_ref [nh, hd]; k_hbm / v_hbm the whole pools [blocks, bs * nkv,
    hd], left in HBM: row t * nkv + g of a block is token t, KV head g;
    with `quant` two more inputs ks_ref / vs_ref [1, columns] f32, the
    slot's per-row scales laid out along the score columns of its
    blocks; o_ref [nh, hd] (with `partials` float32, and lse_ref
    [nh, 1] beside it). lens[s] is the position of the token just
    written: the live window is positions 0..lens[s], -1 an empty
    shard. K rows and V rows may differ in width (o_ref is as wide as
    V's). `bounded`: a third prefetched scalar a slot, lows[s], the
    first position of the table's first block that is attended (a
    window's lower edge; what lies before it in that block is masked).
    `sunk`: one more input sink_ref [nh, 1] f32, a bias a head that
    joins the softmax's denominator and carries no value: the running
    (max, sum) start at (sink, 1) instead of (-inf, 0).
    """
    if bounded:
        lows_ref, *rest = rest
    q_ref, k_hbm, v_hbm, *rest = rest
    if quant:
        ks_ref, vs_ref, *rest = rest
    if sunk:
        sink_ref, *rest = rest
    if partials:
        o_ref, lse_ref, *rest = rest
    else:
        o_ref, *rest = rest
    kbuf, vbuf, sem, turn, m_sc, l_sc, acc_sc = rest
    s = pl.program_id(0)
    nslots = pl.num_programs(0)
    nh = nkv * nrep
    rows = bs * nkv                     # of one block
    cols = chunk * rows                 # of one product
    one, zero = np.int32(1), np.int32(0)
    bs_i, grp, chk = np.int32(bs), np.int32(group), np.int32(chunk)
    # int8 codes are exact in q's dtype; a pool stored in another float
    # type than q keeps the float32 contract
    cdtype = q_ref.dtype if quant or k_hbm.dtype == q_ref.dtype \
        else jnp.float32

    def blocks_of(slot):
        return (lens_ref[slot] + bs_i) // bs_i          # 0 when empty

    def copies(slot, j, buf, i):
        """The K and V copies of member i of `slot`'s group j."""
        blk = tabs_ref[slot, j * grp + i]
        at = pl.ds(pl.multiple_of(i * np.int32(rows), rows), rows)
        return [pltpu.make_async_copy(pool.at[blk], dst.at[buf, at],
                                      sem.at[buf, lane])
                for pool, dst, lane in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1))]

    def start(slot, j, buf):
        def member(i, _):
            for dma in copies(slot, j, buf, i):
                dma.start()
            return _
        lax.fori_loop(zero, jnp.minimum(grp, blocks_of(slot) - j * grp),
                      member, zero)

    @pl.when(s == 0)
    def _first():
        turn[0] = zero
        if chunk > 1:
            # a product covers `chunk` blocks, the last of a slot maybe
            # fewer live ones: what it reads behind them is zero or an
            # earlier live block, never what the chip left in VMEM
            # (p is 0 there, and 0 x NaN is NaN)
            vbuf[:] = jnp.zeros_like(vbuf)

    nblk = blocks_of(s)
    ngroups = (nblk + grp - one) // grp
    first = turn[0]                      # groups attended before this slot
    prev_blocks = blocks_of(jnp.maximum(s - one, zero))

    # a slot's first group is started by the slot before it, behind its
    # own last group; the first slot, and one behind an empty shard,
    # starts its own
    @pl.when(jnp.logical_or(s == 0, prev_blocks == 0))
    def _own():
        start(s, zero, first % 2)

    if sunk:
        m_sc[:] = sink_ref[:]
        l_sc[:] = jnp.ones_like(l_sc)
    else:
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
    acc_sc[:] = jnp.zeros_like(acc_sc)

    pos = lens_ref[s]
    q = q_ref[:].astype(cdtype)                          # [nh, hd]
    # score column c is (token c // nkv, kv head c % nkv): a query head
    # keeps the columns of its own kv head
    col = lax.broadcasted_iota(jnp.int32, (nh, cols), 1)
    head = lax.broadcasted_iota(jnp.int32, (nh, cols), 0) // np.int32(nrep)
    own = lax.rem(col, np.int32(nkv)) == head

    def attend(buf, c, b0):
        """Product c of buffer `buf`: the slot's blocks b0 .. b0 + chunk."""
        at = pl.ds(pl.multiple_of(c * np.int32(cols), cols), cols)
        st = _dot(q, kbuf[buf, at, :].astype(cdtype), _NT) * scale
        live = jnp.logical_and(
            own, col < (pos - b0 * bs_i + one) * np.int32(nkv))
        if bounded:
            live = jnp.logical_and(
                live, col >= (lows_ref[s] - b0 * bs_i) * np.int32(nkv))
        if quant:
            sc_at = pl.ds(pl.multiple_of(b0 * np.int32(rows), rows), cols)
            st = st * ks_ref[:, sc_at]
        st = jnp.where(live, st, NEG_INF)                # [nh, cols]
        m = m_sc[:]
        m_new = jnp.maximum(m, st.max(axis=-1, keepdims=True))
        p = jnp.exp(st - m_new)
        alpha = jnp.exp(m - m_new)
        l_sc[:] = l_sc[:] * alpha + p.sum(axis=-1, keepdims=True)
        if quant:
            # a dead column's p is 0, its scale may be anything
            p = jnp.where(live, p * vs_ref[:, sc_at], np.float32(0))
        o = _dot(p.astype(cdtype), vbuf[buf, at, :].astype(cdtype), _NN)
        acc_sc[:] = acc_sc[:] * alpha + o
        m_sc[:] = m_new

    def one_group(j, _):
        buf = (first + j) % 2
        mine = j + one < ngroups
        nxt_slot = jnp.where(mine, s, s + one)

        @pl.when(jnp.logical_or(mine, nxt_slot < nslots))
        def _prefetch():
            start(nxt_slot, jnp.where(mine, j + one, zero), one - buf)

        here = jnp.minimum(grp, nblk - j * grp)          # live blocks

        # a block past the slot's last is never copied and starts no
        # product: the loops end with the live ones
        def one_product(c, _):
            def arrived(i, _):
                for dma in copies(s, j, buf, c * chk + i):
                    dma.wait()
                return _
            lax.fori_loop(zero, jnp.minimum(chk, here - c * chk),
                          arrived, zero)
            attend(buf, c, j * grp + c * chk)
            return _
        lax.fori_loop(zero, (here + chk - one) // chk, one_product, zero)
        return _

    lax.fori_loop(zero, ngroups, one_group, zero)
    turn[0] = first + ngroups

    if partials:
        l_safe = jnp.maximum(l_sc[:], np.float32(1e-30))  # [nh, 1]
        o_ref[:] = acc_sc[:] / l_safe
        lse_ref[:] = m_sc[:] + jnp.log(l_safe)
    else:
        o_ref[:] = (acc_sc[:] / l_sc[:]).astype(o_ref.dtype)


@i32_trace
def _launch(q, kpool, vpool, tables, seq_lens, scale, scales=None,
            partials=False, lows=None, sinks=None, kv_heads=None):
    """The one pallas launch behind the entry points. `scales` =
    (kscale, vscale) [num_blocks, bs] f32 marks int8 pools; `partials`
    returns (o [S, nh, hd] f32 normalized within the launch, lse
    [S, nh, 1] f32) for the sharded merge, where seq_lens may be -1.
    V's rows may be narrower or wider than K's (o is as wide as V's);
    `lows` [S] int32 bounds each slot's window from below and `sinks`
    [nh] f32 joins each head's denominator (`_step_kernel`)."""
    S, nh, hd = q.shape
    if kpool.ndim == 3:
        # the pools already as the kernel sees them, [blocks, bs * nkv,
        # width]
        nblocks, rows, _ = kpool.shape
        nkv, bs = int(kv_heads), rows // int(kv_heads)
    else:
        nblocks, bs, nkv, _ = kpool.shape
    hdv = vpool.shape[-1]
    mb = tables.shape[1]
    rows = bs * nkv
    tables = tables.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)
    block_bytes = rows * hd * kpool.dtype.itemsize
    vblock_bytes = rows * hdv * vpool.dtype.itemsize
    # K's and V's blocks together, as two of K's when they are alike
    group, chunk = _blocks_per_step((block_bytes + vblock_bytes) // 2, rows,
                                    mb)
    # tokens and KV heads merged into one axis of rows: the same bytes
    # under the chip's tiled layouts (XLA makes it a bitcast, not a
    # copy), and no head is ever sliced out of a block
    kpool = kpool.reshape(nblocks, rows, hd)
    vpool = vpool.reshape(nblocks, rows, hdv)
    prefetch = [tables, seq_lens]
    if lows is not None:
        prefetch.append(lows.astype(jnp.int32))

    def slot(*shape):
        return pl.BlockSpec((None,) + shape,
                            lambda s, *_: (s,) + (0,) * len(shape))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs, operands = [slot(nh, hd), hbm, hbm], [q, kpool, vpool]
    if scales is not None:
        # the slot's scale rows, gathered through its table by XLA and
        # laid along the score columns (each token's nkv times over),
        # to a whole number of products
        pad = -mb % chunk
        for sc in scales:
            sc = jnp.take(sc.astype(jnp.float32), tables, axis=0)
            sc = jnp.pad(jnp.repeat(sc, nkv, axis=-1),
                         ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
            in_specs.append(slot(1, (mb + pad) * rows))
            operands.append(sc.reshape(S, 1, -1))
    if sinks is not None:
        in_specs.append(pl.BlockSpec((nh, 1), lambda s, *_: (0, 0)))
        operands.append(sinks.astype(jnp.float32).reshape(nh, 1))
    out_specs, out_shape = slot(nh, hdv), jax.ShapeDtypeStruct(
        (S, nh, hdv), jnp.float32 if partials else q.dtype)
    if partials:
        out_specs = [out_specs, slot(nh, 1)]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((S, nh, 1), jnp.float32)]
    buffers = [pltpu.VMEM((2, group * rows, pool.shape[-1]), pool.dtype)
               for pool in (kpool, vpool)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(S,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=buffers + [
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, hdv), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _step_kernel, bs=bs, nkv=nkv, nrep=nh // nkv,
        scale=np.float32(scale), group=group, chunk=chunk,
        quant=scales is not None, partials=partials,
        bounded=lows is not None, sunk=sinks is not None)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * group * (block_bytes + vblock_bytes)
            + 32 * 2**20),
        interpret=_interpret(),
    )(*prefetch, *operands)


def ragged_paged_attention(q, kpool, vpool, tables, seq_lens, scale=None,
                           lows=None, sinks=None, kv_heads=None):
    """Grouped causal decode attention straight off the paged KV pool.

    q [S, nh, hd]; kpool [num_blocks, block_size, nkv, hd]; vpool the
    same with its own last axis (V's rows may be narrower than K's);
    tables [S, blocks_per_seq] int32 pool-block ids; seq_lens [S] int32
    position of the token just written (the window is positions
    0..seq_lens[s] inclusive, matching the dense path's
    `arange(W) <= pos` mask). Returns [S, nh, V's width] in q.dtype.

    Rows whose table entries past `seq_lens[s] // block_size` are
    unallocated (zeros) are safe: no copy is issued for them.

    A sliding window hands in the table of its live blocks alone, first
    live block first, positions counted from that block's start, and
    `lows` [S] int32: the first position attended (the window is
    lows[s]..seq_lens[s]; no block before it is in the table, so none is
    copied). `sinks` [nh] float32: a learned bias a head that joins the
    softmax's denominator and carries no value.

    Pools may come as the kernel sees them, [num_blocks, block_size *
    nkv, width] with `kv_heads` = nkv (row t * nkv + g of a block is
    token t, KV head g): few KV heads of wide rows are not the same bytes
    in the two shapes under the chip's tiled layouts, and the reshape
    would copy the pool.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _launch(q, kpool, vpool, tables, seq_lens, float(scale),
                   lows=lows, sinks=sinks, kv_heads=kv_heads)


# -- context-length-sharded decode attention (ISSUE 19 tentpole a) ------------
# When one slot's KV span exceeds a per-chip block budget, its block
# table is split into contiguous sub-tables ("shards") and the ragged
# kernel runs once per shard, emitting ONLINE-SOFTMAX PARTIALS instead
# of a finished output: (o_k normalized within the shard, lse_k =
# m + log l). The partials combine exactly like the ring-attention
# m/l rescale merge (_ring_flash_fwd_core): with M = max_k lse_k and
# w_k = exp(lse_k - M), out = sum_k w_k * o_k / sum_k w_k. Each shard
# call is an independent pallas launch over its sub-table, so the same
# code path serves blockwise execution on one chip (bounding VMEM-
# resident table span and per-launch KV traffic) and ring-style
# placement of shards over the mp axis (each chip runs its shard, the
# merge is a tiny [S, nh] reduction on the combining chip). A shard with
# no live tokens (shard-local seq_lens -1) copies and computes nothing
# and lands at o = 0, lse ~ -inf, so its merge weight exp(lse - M)
# underflows to exactly 0.

def ragged_paged_attention_sharded(q, kpool, vpool, tables, seq_lens,
                                   num_shards, scale=None):
    """Context-length-sharded ragged paged attention.

    Same contract as :func:`ragged_paged_attention` (q [S, nh, hd],
    pools [NB, bs, nkv, hd], tables [S, MB] i32, seq_lens [S] i32 =
    position of the token just written), but the block table is split
    into ``num_shards`` contiguous sub-tables of ceil(MB/num_shards)
    blocks, each run as an independent partials launch, and the
    per-shard online-softmax partials merged via the lse rescale
    (max/exp-weighted sum — the ring-attention combine). num_shards=1
    degenerates to the plain kernel's math exactly (one launch, unit
    merge weight).

    All shard index math is pinned i32 (the 128k-position s64 trap:
    satellite 1 of ISSUE 19)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    num_shards = int(num_shards)
    mb = tables.shape[1]
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if num_shards > mb:
        raise ValueError(f"num_shards {num_shards} exceeds "
                         f"blocks_per_seq {mb}")
    bs = kpool.shape[1]
    spb = -(-mb // num_shards)            # shard width in blocks
    lens = seq_lens.astype(jnp.int32)
    outs, lses = [], []
    for k in range(num_shards):
        lo = k * spb
        hi = min((k + 1) * spb, mb)
        if lo >= mb:
            break
        sub = tables[:, lo:hi]
        # shard-local position of the last live token: global window is
        # 0..lens inclusive => this shard holds
        # clip(lens + 1 - lo*bs, 0, width*bs) live tokens; -1 == empty
        lens_k = jnp.clip(lens + np.int32(1) - np.int32(lo * bs),
                          np.int32(0),
                          np.int32((hi - lo) * bs)) - np.int32(1)
        o_k, lse_k = _launch(q, kpool, vpool, sub, lens_k, float(scale),
                             partials=True)
        outs.append(o_k)
        lses.append(lse_k[..., 0])        # [S, nh]
    lse = jnp.stack(lses, axis=0)         # [K, S, nh] f32
    m = jnp.max(lse, axis=0)              # [S, nh]
    w = jnp.exp(lse - m[None])            # [K, S, nh]; empty shards -> 0
    num = jnp.einsum("ksh,kshd->shd", w, jnp.stack(outs, axis=0))
    den = jnp.maximum(jnp.sum(w, axis=0), np.float32(1e-30))
    return (num / den[..., None]).astype(q.dtype)


# -- int8 paged KV: per-row codec + in-kernel dequant variant -----------------
# EQuARX-style per-block scale codec (distributed/collective.py's
# quantize_blockwise_int8, PR 4) applied to the paged-KV pool: the quant
# group ("block") is one pool token row — the [nkv, hd] K (or V) vector
# a single token writes — so appending a token touches exactly its own
# codes + one f32 scale and never requantizes neighbors. The wire win is
# what the ragged kernel fetches: codes int8 + one f32/row instead of
# bf16/f32 values, dequantized AFTER the HBM -> VMEM fetch so HBM moves
# (nkv*hd + 4) bytes/token instead of 2*nkv*hd (bf16).
#
# Error model (documented contract, asserted in tests/test_kv_quant_spec
# .py): with a = max|x| over the row, scale = a/127 and round-to-nearest
# gives |dequant(x) - x| <= a/254 per element. A row of zeros stores
# scale 1 and codes 0 (exact).

def kv_quantize_rows(x):
    """x [..., nkv, hd] -> (codes int8 [..., nkv, hd], scales f32
    [...]). One symmetric scale per token row; every constant pinned
    f32 so the codec traces x64-clean (PR 4 discipline)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = jnp.where(amax > 0, amax / np.float32(127.0),
                      jnp.float32(1.0))
    q = jnp.clip(jnp.round(xf / scale[..., None, None]),
                 np.float32(-127.0), np.float32(127.0))
    return q.astype(jnp.int8), scale


def kv_dequantize_rows(codes, scales):
    """Inverse of kv_quantize_rows; returns f32."""
    return codes.astype(jnp.float32) * scales[..., None, None]


def kv_row_error_bound(x):
    """Per-element |dequant - x| bound for each row of x [..., nkv, hd]:
    amax_row / 254 (half an int8 step at scale amax/127)."""
    amax = np.max(np.abs(np.asarray(x, np.float32)), axis=(-2, -1))
    return amax / 254.0


def ragged_paged_attention_quant(q, kpool, kscale, vpool, vscale, tables,
                                 seq_lens, scale=None):
    """ragged_paged_attention over an int8 pool: kpool/vpool
    [num_blocks, block_size, nkv, hd] int8 codes, kscale/vscale
    [num_blocks, block_size] f32 per-row scales (kv_quantize_rows
    layout). The codes reach the MXU as they are (exact in q's dtype)
    and the row scales multiply the scores and the probabilities, so
    the wire moves codes + scales, never the widened values. Same
    early-exit contract as the unquantized kernel: no code block past
    seq_lens is copied; the scale rows a table names are gathered
    beforehand, and those of its dead entries never meet a product."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _launch(q, kpool, vpool, tables, seq_lens, float(scale),
                   scales=(kscale, vscale))


# op-registry faces (lazily registered at module import, the flash /
# fused-kernel pattern): each carries a SKIP-map entry in
# tests/test_op_golden_sweep.py pointing at its dedicated parity suite
def _register_ops():
    from ...framework.op_registry import register_op
    register_op("kv_block_quant_int8",
                lambda x: kv_quantize_rows(x))
    register_op(
        "ragged_paged_attn_quant_pallas",
        lambda q, kc, ks, vc, vs, tables, lens, *, scale=None:
        ragged_paged_attention_quant(q, kc, ks, vc, vs, tables, lens,
                                     scale=scale))


try:
    _register_ops()
except Exception:  # pragma: no cover - registry optional in slim builds
    pass


# -- traffic accounting -------------------------------------------------------
# The win this kernel buys is HBM traffic; these helpers price one decode
# step's attention KV reads for both paths so benchmarks/observability
# can report the gap without a hardware profiler. K+V both stream, hence
# the factor 2.

def ragged_hbm_bytes(seq_lens, block_size, nkv, hd, itemsize, live=None,
                     scale_bytes=0):
    """KV bytes one ragged-kernel step reads: only blocks up to each live
    slot's position. seq_lens: array-like [S] of just-written positions.
    scale_bytes: per-token codec-scale bytes riding along with an int8
    pool (4 for the f32 per-row scales; 0 for an unquantized pool)."""
    import numpy as np
    lens = np.asarray(seq_lens)
    needed = lens // block_size + 1
    if live is not None:
        needed = np.where(np.asarray(live), needed, 1)  # trash block only
    per_block = 2 * block_size * (nkv * hd * itemsize + scale_bytes)
    return int(needed.sum()) * per_block


def dense_gather_hbm_bytes(n_slots, blocks_per_seq, block_size, nkv, hd,
                           itemsize, scale_bytes=0):
    """KV bytes one dense-gather step READS: the full [S, W] window is
    read from the pool by the gather, then the gathered copy is read
    again by attention — 2x the window, for every slot, every step.
    (The gather also WRITES a window-sized copy; reads alone are billed
    so the number matches the ragged kernel's read-only accounting.)"""
    window = n_slots * blocks_per_seq * block_size \
        * (nkv * hd * itemsize + scale_bytes)
    return 2 * 2 * window


def record_ragged_step(seq_lens, blocks_per_seq, block_size, nkv, hd,
                       itemsize, layers=1, steps=1, live=None,
                       budgets=None, scale_bytes=0, launches=None):
    """Host-side telemetry for `steps` fused decode steps through the
    ragged kernel: kernel calls, blocks attended vs skipped (the ragged
    early-exit), and HBM KV bytes actually read vs what the dense-gather
    path would have read. seq_lens are the positions at the START of the
    chunk; a live slot advances one position per step until its budget
    (if given) runs out — after that its length FREEZES but the kernel
    still streams its blocks at the frozen position every remaining
    step, which is exactly what gets billed. Retired slots (live False)
    read only the trash block. `launches` overrides the kernel-launch
    count when it differs from `steps`: a batched spec-decode verify is
    ONE launch per layer covering k+1 positions' worth of traffic —
    bytes bill at steps=k+1, calls at launches=1.

    How much of the grid works: a launch makes one grid step a slot, and
    a step waits once for each DMA group of its live blocks (the group
    size `_blocks_per_step` gives this block). `grid_steps` counts the
    former, `grid_steps_live` those that attended a block (a step a
    slot leaves none dead), `dma_groups` the latter."""
    from ... import observability as obs
    if not obs.enabled():
        return
    import numpy as np
    reg = obs.registry()
    lens = np.asarray(seq_lens, np.int64)
    alive = np.ones(lens.shape, bool) if live is None \
        else np.asarray(live, bool)
    attended = skipped = ragged_bytes = bf16eq_bytes = 0
    grid_steps = grid_live = dma_groups = 0
    per_block = 2 * block_size * (nkv * hd * itemsize + scale_bytes)
    bf16_block = 2 * block_size * nkv * hd * 2
    group, _ = _blocks_per_step(block_size * nkv * hd * itemsize,
                                block_size * nkv, blocks_per_seq)
    for i in range(steps):
        adv = i if budgets is None else np.minimum(i, np.asarray(budgets))
        pos = lens + adv * alive
        needed = np.where(alive, pos // block_size + 1, 1)
        attended += int(needed.sum())
        skipped += int((blocks_per_seq - needed).sum())
        grid_steps += len(needed)
        grid_live += int((needed > 0).sum())
        dma_groups += int((-(-needed // group)).sum())
        ragged_bytes += int(needed.sum()) * per_block
        bf16eq_bytes += int(needed.sum()) * bf16_block
    dense_bytes = steps * dense_gather_hbm_bytes(
        len(lens), blocks_per_seq, block_size, nkv, hd, itemsize,
        scale_bytes=scale_bytes)
    reg.counter("paddle_tpu_ragged_attn_calls_total",
                "ragged paged-attention kernel launches").inc(
                    layers * (steps if launches is None else launches))
    reg.counter("paddle_tpu_ragged_attn_blocks_attended_total",
                "KV pool blocks streamed through the ragged kernel").inc(
                    layers * attended)
    reg.counter("paddle_tpu_ragged_attn_blocks_skipped_total",
                "KV pool blocks skipped by the ragged early-exit").inc(
                    layers * skipped)
    reg.counter("paddle_tpu_ragged_attn_grid_steps_total",
                "grid steps of the ragged kernel's launches").inc(
                    layers * grid_steps)
    reg.counter("paddle_tpu_ragged_attn_grid_steps_live_total",
                "ragged kernel grid steps that attended a block").inc(
                    layers * grid_live)
    reg.counter("paddle_tpu_ragged_attn_dma_groups_total",
                "groups of pool blocks the ragged kernel waited for").inc(
                    layers * dma_groups)
    reg.counter("paddle_tpu_ragged_attn_hbm_bytes_total",
                "attention KV bytes read by the ragged kernel").inc(
                    layers * ragged_bytes)
    reg.counter("paddle_tpu_ragged_attn_dense_hbm_bytes_total",
                "attention KV bytes the dense-gather path would move").inc(
                    layers * dense_bytes)
    # priced against a constant yardstick so the int8 pool's wire win is
    # a counter ratio (kv_hbm_bytes_ratio gate in bench_smoke): what the
    # SAME block fetches would have cost at bf16, no codec
    reg.counter("paddle_tpu_ragged_attn_hbm_bytes_bf16eq_total",
                "bf16-equivalent bytes for the same ragged KV fetches"
                ).inc(layers * bf16eq_bytes)
