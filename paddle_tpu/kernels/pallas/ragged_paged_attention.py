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
fetched at all (the ragged early-exit), so a slot at position p costs
`(p // bs + 1) * bs` tokens of read traffic instead of `2 * W` reads
plus a `W` write.

Mechanics:

- grid = (S, blocks_per_seq); scalar-prefetched block tables + seq_lens
  drive the K/V BlockSpec index maps, so the pipeline fetches pool
  block `tables[s, j]` for grid step (s, j) — the gather IS the fetch
  (pltpu.PrefetchScalarGridSpec, the T3-style fusion of gather and
  attention into one pipeline).
- blocks past the sequence's last block CLAMP their index map to the
  last live block: Mosaic skips the re-fetch when consecutive grid
  steps map to the same block, and `pl.when` skips the compute — the
  early-exit costs no HBM and (nearly) no cycles.
- online softmax (running m / l / acc in VMEM scratch across the j
  axis, exactly like flash_attention.py's streaming kernels) keeps the
  whole reduction in one pass; grouped (GQA) heads attend against the
  unrepeated K/V block via a per-group MXU dot.

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

__all__ = ["ragged_paged_attention", "ragged_paged_attention_sharded",
           "ragged_paged_attention_quant",
           "kv_quantize_rows", "kv_dequantize_rows", "kv_row_error_bound",
           "ragged_hbm_bytes", "dense_gather_hbm_bytes",
           "record_ragged_step"]

import numpy as np

# the kernel body and index maps are re-traced at pallas lowering time,
# OUTSIDE the i32_trace context — every scalar constant must carry an
# explicit 32-bit dtype or global x64 mode promotes it to f64/i64, which
# Mosaic (and the interpret-mode verifier) reject
NEG_INF = np.float32(-1e30)


def _interpret():
    return jax.default_backend() != "tpu"


def _kernel(tabs_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
            m_sc, l_sc, acc_sc, *, bs, nkv, nrep, scale):
    """One (slot, kv-block) grid step.

    q_ref [nh, hd]; k_ref/v_ref [bs, nkv, hd] = pool block tables[s, j];
    o_ref [nh, hd]; scratch m/l [nh, 1] f32, acc [nh, hd] f32 carried
    across the j axis. lens[s] is the position of the token just
    written, so the live window is positions 0..lens[s] inclusive.
    """
    s = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    pos = lens_ref[s]

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    # ragged early-exit: block j holds positions [j*bs, (j+1)*bs) — past
    # the last live block nothing is fetched (index map clamps) and
    # nothing is computed
    @pl.when(j * bs <= pos)
    def _step():
        q = q_ref[:].astype(jnp.float32) * scale        # [nh, hd]
        col = j * bs + lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        live = col <= pos                               # [1, bs]
        # grouped scores against the UNREPEATED block: one [nrep, hd] x
        # [hd, bs] MXU dot per kv group
        st_groups = []
        for g in range(nkv):
            qg = q[g * nrep:(g + 1) * nrep, :]          # [nrep, hd]
            kg = k_ref[:, g, :].astype(jnp.float32)     # [bs, hd]
            st_groups.append(lax.dot_general(
                qg, kg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))    # [nrep, bs]
        st = jnp.concatenate(st_groups, axis=0) if nkv > 1 \
            else st_groups[0]                           # [nh, bs]
        st = jnp.where(live, st, NEG_INF)
        m = m_sc[:]
        m_new = jnp.maximum(m, st.max(axis=-1, keepdims=True))
        p = jnp.exp(st - m_new)
        alpha = jnp.exp(m - m_new)
        l_sc[:] = l_sc[:] * alpha + p.sum(axis=-1, keepdims=True)
        o_groups = []
        for g in range(nkv):
            pg = p[g * nrep:(g + 1) * nrep, :]          # [nrep, bs]
            vg = v_ref[:, g, :].astype(jnp.float32)     # [bs, hd]
            o_groups.append(lax.dot_general(
                pg, vg, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))    # [nrep, hd]
        o = jnp.concatenate(o_groups, axis=0) if nkv > 1 \
            else o_groups[0]                            # [nh, hd]
        acc_sc[:] = acc_sc[:] * alpha + o
        m_sc[:] = m_new

    @pl.when(j == nb - 1)
    def _finish():
        o_ref[:] = (acc_sc[:] / l_sc[:]).astype(o_ref.dtype)


@i32_trace
def _ragged_call(q, kpool, vpool, tables, seq_lens, scale):
    S, nh, hd = q.shape
    nb_pool, bs, nkv, _ = kpool.shape
    mb = tables.shape[1]
    nrep = nh // nkv
    tables = tables.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)

    # numpy scalar: index maps must not capture traced constants
    bs_i = np.int32(bs)

    def kv_map(s, j, tabs, lens):
        # clamp past-the-end j to the last live block: same index as the
        # previous grid step => the pipeline skips the HBM fetch
        return (tabs[s, jnp.minimum(j, lens[s] // bs_i)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, mb),
        in_specs=[
            pl.BlockSpec((None, nh, hd), lambda s, j, tabs, lens: (s, 0, 0)),
            pl.BlockSpec((None, bs, nkv, hd), kv_map),
            pl.BlockSpec((None, bs, nkv, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((None, nh, hd),
                               lambda s, j, tabs, lens: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, bs=bs, nkv=nkv, nrep=nrep,
                               scale=np.float32(scale))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nh, hd), q.dtype),
        interpret=_interpret(),
    )(tables, seq_lens, q, kpool, vpool)


def ragged_paged_attention(q, kpool, vpool, tables, seq_lens, scale=None):
    """Grouped causal decode attention straight off the paged KV pool.

    q [S, nh, hd]; kpool/vpool [num_blocks, block_size, nkv, hd];
    tables [S, blocks_per_seq] int32 pool-block ids; seq_lens [S] int32
    position of the token just written (the window is positions
    0..seq_lens[s] inclusive, matching the dense path's
    `arange(W) <= pos` mask). Returns [S, nh, hd] in q.dtype.

    Rows whose table entries past `seq_lens[s] // block_size` are
    unallocated (zeros) are safe: the index map never reads them.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _ragged_call(q, kpool, vpool, tables, seq_lens, float(scale))


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
# merge is a tiny [S, nh] reduction on the combining chip).

def _pkernel(tabs_ref, lens_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
             m_sc, l_sc, acc_sc, *, bs, nkv, nrep, scale):
    """Partials grid step: the _kernel online-softmax body, finishing
    with (o = acc / max(l, tiny) in f32, lse = m + log(max(l, tiny)))
    instead of a cast final output. A shard with no live tokens
    (lens[s] < 0) computes nothing and lands at o = 0, lse ~ -inf, so
    its merge weight exp(lse - M) underflows to exactly 0."""
    s = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    pos = lens_ref[s]

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    @pl.when(j * bs <= pos)
    def _step():
        q = q_ref[:].astype(jnp.float32) * scale        # [nh, hd]
        col = j * bs + lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        live = col <= pos                               # [1, bs]
        st_groups = []
        for g in range(nkv):
            qg = q[g * nrep:(g + 1) * nrep, :]          # [nrep, hd]
            kg = k_ref[:, g, :].astype(jnp.float32)     # [bs, hd]
            st_groups.append(lax.dot_general(
                qg, kg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))    # [nrep, bs]
        st = jnp.concatenate(st_groups, axis=0) if nkv > 1 \
            else st_groups[0]                           # [nh, bs]
        st = jnp.where(live, st, NEG_INF)
        m = m_sc[:]
        m_new = jnp.maximum(m, st.max(axis=-1, keepdims=True))
        p = jnp.exp(st - m_new)
        alpha = jnp.exp(m - m_new)
        l_sc[:] = l_sc[:] * alpha + p.sum(axis=-1, keepdims=True)
        o_groups = []
        for g in range(nkv):
            pg = p[g * nrep:(g + 1) * nrep, :]          # [nrep, bs]
            vg = v_ref[:, g, :].astype(jnp.float32)     # [bs, hd]
            o_groups.append(lax.dot_general(
                pg, vg, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))    # [nrep, hd]
        o = jnp.concatenate(o_groups, axis=0) if nkv > 1 \
            else o_groups[0]                            # [nh, hd]
        acc_sc[:] = acc_sc[:] * alpha + o
        m_sc[:] = m_new

    @pl.when(j == nb - 1)
    def _finish():
        l_safe = jnp.maximum(l_sc[:], np.float32(1e-30))  # [nh, 1]
        o_ref[:] = acc_sc[:] / l_safe
        lse_ref[:] = m_sc[:] + jnp.log(l_safe)


@i32_trace
def _ragged_partials_call(q, kpool, vpool, tables, seq_lens, scale):
    """One shard's pallas launch: like _ragged_call but returns
    (o [S, nh, hd] f32 normalized-within-shard, lse [S, nh, 1] f32).
    seq_lens here are SHARD-LOCAL positions (may be -1: empty shard;
    the index map clamps so nothing out-of-range is ever fetched)."""
    S, nh, hd = q.shape
    nb_pool, bs, nkv, _ = kpool.shape
    mb = tables.shape[1]
    nrep = nh // nkv
    tables = tables.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)
    bs_i = np.int32(bs)
    zero_i = np.int32(0)

    def kv_map(s, j, tabs, lens):
        # clamp empty (-1) AND past-the-end positions into the
        # sub-table: repeated indices skip the HBM re-fetch, and the
        # pl.when gate skips the compute either way
        return (tabs[s, jnp.minimum(
            j, jnp.maximum(lens[s], zero_i) // bs_i)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, mb),
        in_specs=[
            pl.BlockSpec((None, nh, hd), lambda s, j, tabs, lens: (s, 0, 0)),
            pl.BlockSpec((None, bs, nkv, hd), kv_map),
            pl.BlockSpec((None, bs, nkv, hd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, nh, hd),
                         lambda s, j, tabs, lens: (s, 0, 0)),
            pl.BlockSpec((None, nh, 1),
                         lambda s, j, tabs, lens: (s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_pkernel, bs=bs, nkv=nkv, nrep=nrep,
                               scale=np.float32(scale))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, nh, hd), jnp.float32),
                   jax.ShapeDtypeStruct((S, nh, 1), jnp.float32)],
        interpret=_interpret(),
    )(tables, seq_lens, q, kpool, vpool)


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
        o_k, lse_k = _ragged_partials_call(q, kpool, vpool, sub, lens_k,
                                           float(scale))
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


def _qkernel(tabs_ref, lens_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
             o_ref, m_sc, l_sc, acc_sc, *, bs, nkv, nrep, scale):
    """Quantized-pool grid step: identical online-softmax body to
    _kernel, but k_ref/v_ref are int8 codes and ks_ref/vs_ref [bs] the
    per-row f32 scales — dequantized here, in VMEM, after the fetch."""
    s = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    pos = lens_ref[s]

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    @pl.when(j * bs <= pos)
    def _step():
        q = q_ref[:].astype(jnp.float32) * scale        # [nh, hd]
        col = j * bs + lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        live = col <= pos                               # [1, bs]
        ks = ks_ref[:].astype(jnp.float32)[:, None]     # [bs, 1]
        vs = vs_ref[:].astype(jnp.float32)[:, None]
        st_groups = []
        for g in range(nkv):
            qg = q[g * nrep:(g + 1) * nrep, :]          # [nrep, hd]
            kg = k_ref[:, g, :].astype(jnp.float32) * ks  # dequant [bs, hd]
            st_groups.append(lax.dot_general(
                qg, kg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))    # [nrep, bs]
        st = jnp.concatenate(st_groups, axis=0) if nkv > 1 \
            else st_groups[0]                           # [nh, bs]
        st = jnp.where(live, st, NEG_INF)
        m = m_sc[:]
        m_new = jnp.maximum(m, st.max(axis=-1, keepdims=True))
        p = jnp.exp(st - m_new)
        alpha = jnp.exp(m - m_new)
        l_sc[:] = l_sc[:] * alpha + p.sum(axis=-1, keepdims=True)
        o_groups = []
        for g in range(nkv):
            pg = p[g * nrep:(g + 1) * nrep, :]          # [nrep, bs]
            vg = v_ref[:, g, :].astype(jnp.float32) * vs  # dequant [bs, hd]
            o_groups.append(lax.dot_general(
                pg, vg, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))    # [nrep, hd]
        o = jnp.concatenate(o_groups, axis=0) if nkv > 1 \
            else o_groups[0]                            # [nh, hd]
        acc_sc[:] = acc_sc[:] * alpha + o
        m_sc[:] = m_new

    @pl.when(j == nb - 1)
    def _finish():
        o_ref[:] = (acc_sc[:] / l_sc[:]).astype(o_ref.dtype)


@i32_trace
def _ragged_quant_call(q, kpool, kscale, vpool, vscale, tables, seq_lens,
                       scale):
    S, nh, hd = q.shape
    nb_pool, bs, nkv, _ = kpool.shape
    mb = tables.shape[1]
    nrep = nh // nkv
    tables = tables.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)
    bs_i = np.int32(bs)

    def kv_map(s, j, tabs, lens):
        # same past-the-end clamp as the unquantized kernel: repeated
        # indices skip the re-fetch
        return (tabs[s, jnp.minimum(j, lens[s] // bs_i)], 0, 0, 0)

    def sc_map(s, j, tabs, lens):
        return (tabs[s, jnp.minimum(j, lens[s] // bs_i)], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, mb),
        in_specs=[
            pl.BlockSpec((None, nh, hd), lambda s, j, tabs, lens: (s, 0, 0)),
            pl.BlockSpec((None, bs, nkv, hd), kv_map),
            pl.BlockSpec((None, bs), sc_map),
            pl.BlockSpec((None, bs, nkv, hd), kv_map),
            pl.BlockSpec((None, bs), sc_map),
        ],
        out_specs=pl.BlockSpec((None, nh, hd),
                               lambda s, j, tabs, lens: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_qkernel, bs=bs, nkv=nkv, nrep=nrep,
                               scale=np.float32(scale))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nh, hd), q.dtype),
        interpret=_interpret(),
    )(tables, seq_lens, q, kpool, kscale, vpool, vscale)


def ragged_paged_attention_quant(q, kpool, kscale, vpool, vscale, tables,
                                 seq_lens, scale=None):
    """ragged_paged_attention over an int8 pool: kpool/vpool
    [num_blocks, block_size, nkv, hd] int8 codes, kscale/vscale
    [num_blocks, block_size] f32 per-row scales (kv_quantize_rows
    layout). Dequantization happens inside the kernel after the
    HBM -> VMEM fetch, so the wire moves codes + scales, never the
    widened values. Same clamp/early-exit contract as the unquantized
    kernel: blocks (and their scale rows) past seq_lens are never
    fetched."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _ragged_quant_call(q, kpool, kscale, vpool, vscale, tables,
                              seq_lens, float(scale))


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
    bytes bill at steps=k+1, calls at launches=1."""
    from ... import observability as obs
    if not obs.enabled():
        return
    import numpy as np
    reg = obs.registry()
    lens = np.asarray(seq_lens, np.int64)
    alive = np.ones(lens.shape, bool) if live is None \
        else np.asarray(live, bool)
    attended = skipped = ragged_bytes = bf16eq_bytes = 0
    per_block = 2 * block_size * (nkv * hd * itemsize + scale_bytes)
    bf16_block = 2 * block_size * nkv * hd * 2
    for i in range(steps):
        adv = i if budgets is None else np.minimum(i, np.asarray(budgets))
        pos = lens + adv * alive
        needed = np.where(alive, pos // block_size + 1, 1)
        attended += int(needed.sum())
        skipped += int((blocks_per_seq - needed).sum())
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
