"""Pallas TPU flash attention of a prefill chunk in multi-head latent
attention's expanded form, each head's keys and values formed from the
latent rows inside the kernel, causal and, where a selection is given,
masked to it (forward only).

For query rows t of a chunk (row t at key position `q_start + t`) and
every key j of the sequence, head h:

    k_nope_h(j), v_h(j) = c(j) Wkvb_h          (c: the latent row's first
                                               kv_lora_rank dims)
    s_h(t, j) = (q_nope_h(t) . k_nope_h(j) + q_pe_h(t) . k_pe(j)) * scale
    o_h(t) = softmax over the j <= q_start + t (with mask[t, j] != 0
             where a mask is given: a sparse configuration's choice)

A key tile's latent rows [bk, W] and the heads' [kv_lora_rank, dn + dv]
slices of Wkvb are all the kernel reads besides q and the mask: no [keys,
heads, dims] array of expanded keys or values exists in HBM (at 16,384
keys and 128 heads those are 1.34 GB a layer), and keys past the chunk's
last row are neither expanded nor read. A query block holds the whole
chunk (up to 1,024 rows), so a tile's keys are expanded once a head.

A grid step takes a group of G heads against one key tile (G from the
widths and the VMEM limit, `_heads_per_step`): the latent and mask tiles
are fetched once for the group, and the tile's visibility is built once
for it, as a float32 tile of 0 and NEG_INF added to each head's scores;
the causal compare is made only on a tile that crosses the block's
diagonal, and a causal tile below it adds nothing. The steps past the
chunk's last tile do nothing, G heads at a time.

The rotary key k_pe sits in the latent row behind c, in whole lanes
(zeros past its dr dims); q_pe comes padded to the same width. Operands
to the MXU in their stored dtype (bf16 in a cell), float32 accumulated;
the expanded k_nope and v rounded back to that dtype, as an XLA
expansion would store them. On other backends than the TPU the kernel
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
from .flash_prefill import _block

__all__ = ["mla_prefill_attention"]


def _interpret():
    return jax.default_backend() != "tpu"


# keys a grid step, the most (a divisor of Tk): a 1,024-key tile takes half
# the row reductions and rescales a key that 512-key tiles take
_KEY_TILE = 1024
_VMEM_LIMIT = 64 * 2**20


def _heads_per_step(nh, bq, bk, dn, dv, qp_width, width, kvr, itemsize,
                    masked):
    """G, the largest divisor of nh whose step fits `_VMEM_LIMIT`: its
    blocks twice (q_nope, q_pe, Wkvb, out for G heads; the latent and
    mask tiles), the scratch (m and l padded to a lane tile a row, acc,
    the visibility tile) and a head's float32 scores and probabilities
    with their temporaries, four [bq, bk] tiles."""
    def held(g):
        blocks = itemsize * (g * bq * (dn + qp_width + dv) + bk * width
                             + g * kvr * (dn + dv))
        if masked:
            blocks += bq * bk
        scratch = 4 * (g * bq * (2 * 128 + dv) + bq * bk)
        return 2 * blocks + scratch + 4 * 4 * bq * bk
    if held(1) > _VMEM_LIMIT:
        raise ValueError(f"one head's grid step holds {held(1):,} B of VMEM,"
                         f" over the kernel's limit of {_VMEM_LIMIT:,} B")
    return max(g for g in range(1, nh + 1)
               if nh % g == 0 and held(g) <= _VMEM_LIMIT)


def _kernel(at_ref, qn_ref, qp_ref, lat_ref, w_ref, *refs, scale, bq, bk,
            kvr, dn, heads, masked):
    """One (group of `heads` heads, query block, key step). at_ref (SMEM)
    [q_start]; qn_ref [G, bq, dn]; qp_ref [G, bq, W - kvr]; lat_ref [bk,
    W]; w_ref [G, kvr, dn + dv]; then, where `masked`, mask_ref [bq, bk]
    int8; o_ref [G, bq, dv], the scratch m, l [G, bq, 1], acc [G, bq,
    dv] and the tile's visibility [bq, bk] (0 or NEG_INF, added to each
    head's scores)."""
    mask_ref = refs[0] if masked else None
    o_ref, m_sc, l_sc, acc_sc, vis_sc = refs[1:] if masked else refs
    i, j = pl.program_id(1), pl.program_id(2)
    row0 = at_ref[0] + i * np.int32(bq)
    last = (row0 + np.int32(bq - 1)) // np.int32(bk)
    working = j <= last
    # a key of the tile lies past the block's first row: only there is
    # the causal compare made
    crosses = j * np.int32(bk) + np.int32(bk - 1) > row0

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    def attend(visible):
        """Each head of the group against the tile, its scores plus the
        visibility tile where `visible`. The heads are a loop on the
        chip, not unrolled: unrolled, eight heads' code ran slower than
        one head's."""
        lat = lat_ref[...]
        c, pe = lat[:, :kvr], lat[:, kvr:]

        def head(g, carry):
            w = w_ref[g]
            kn = _dot(c, w[:, :dn], _NN).astype(c.dtype)      # [bk, dn]
            v = _dot(c, w[:, dn:], _NN).astype(c.dtype)       # [bk, dv]
            st = (_dot(qn_ref[g], kn, _NT) + _dot(qp_ref[g], pe, _NT)) \
                * scale
            if visible:
                st = st + vis_sc[...]
            m = m_sc[g]
            m_new = jnp.maximum(m, st.max(axis=-1, keepdims=True))
            p = jnp.exp(st - m_new)
            alpha = jnp.exp(m - m_new)
            l_sc[g] = l_sc[g] * alpha + p.sum(axis=-1, keepdims=True)
            acc_sc[g] = acc_sc[g] * alpha + _dot(p.astype(v.dtype), v, _NN)
            m_sc[g] = m_new
            return carry

        lax.fori_loop(0, heads, head, 0)

    def hide(seen):
        """The visibility tile from int32 0 / 1: 0 where seen, NEG_INF
        where not (Mosaic takes no select between two constants)."""
        vis_sc[...] = (1 - seen).astype(jnp.float32) * NEG_INF

    @pl.when(jnp.logical_and(working, crosses))
    def _diagonal():
        shape = vis_sc.shape
        row = row0 + lax.broadcasted_iota(jnp.int32, shape, 0)
        col = j * np.int32(bk) + lax.broadcasted_iota(jnp.int32, shape, 1)
        seen = jnp.clip(row - col + 1, 0, 1)
        hide(seen * mask_ref[...].astype(jnp.int32) if masked else seen)
        attend(True)

    @pl.when(jnp.logical_and(working, jnp.logical_not(crosses)))
    def _below():
        if masked:
            hide(mask_ref[...].astype(jnp.int32))
        attend(masked)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)


@i32_trace
def _launch(qn, qp, lat, w, mask, at, scale, kvr):
    nh, tq, dn = qn.shape
    tk, width = lat.shape
    dv = w.shape[-1] - dn
    bq, bk = _block(tq, 1024), _block(tk, _KEY_TILE)
    G = _heads_per_step(nh, bq, bk, dn, dv, qp.shape[-1], width, kvr,
                        qn.dtype.itemsize, mask is not None)

    def key_tile(i, j, at_ref):
        last = (at_ref[0] + (i + 1) * np.int32(bq) - 1) // np.int32(bk)
        return jnp.minimum(j, last)

    masks = [] if mask is None else [
        pl.BlockSpec((bq, bk), lambda h, i, j, at: (i, key_tile(i, j, at)))]
    return pl.pallas_call(
        functools.partial(_kernel, scale=np.float32(scale), bq=bq, bk=bk,
                          kvr=kvr, dn=dn, heads=G, masked=mask is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nh // G, tq // bq, tk // bk),
            in_specs=[
                pl.BlockSpec((G, bq, dn), lambda h, i, j, *_: (h, i, 0)),
                pl.BlockSpec((G, bq, qp.shape[-1]),
                             lambda h, i, j, *_: (h, i, 0)),
                pl.BlockSpec((bk, width),
                             lambda h, i, j, at: (key_tile(i, j, at), 0)),
                pl.BlockSpec((G, kvr, dn + dv),
                             lambda h, i, j, *_: (h, 0, 0))]
            + masks,
            out_specs=pl.BlockSpec((G, bq, dv),
                                   lambda h, i, j, *_: (h, i, 0)),
            scratch_shapes=[pltpu.VMEM((G, bq, 1), jnp.float32),
                            pltpu.VMEM((G, bq, 1), jnp.float32),
                            pltpu.VMEM((G, bq, dv), jnp.float32),
                            pltpu.VMEM((bq, bk), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((nh, tq, dv), qn.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
    )(at, qn, qp, lat, w, *([] if mask is None else [mask]))


def mla_prefill_attention(q, latent, wkv_b, mask, q_start, kv_lora_rank,
                          rope_dims, scale):
    """Causal MLA of a chunk of queries against latent rows, masked to a
    selection where one is given.

    q [Tq, nh, dn + dr] ([q_nope | q_pe], the rotary term applied, dr =
    `rope_dims`); latent [Tk, W] rows [c | k_pe | zeros] with c
    `kv_lora_rank` wide, for key positions 0 .. Tk - 1; wkv_b
    [kv_lora_rank, nh * (dn + dv)] (head h's k_nope and v columns side
    by side); mask [Tq, Tk], nonzero where a query may attend a key, or
    None where every causal key is attended;
    query row i lies at key position `q_start + i` (int32 scalar, traced)
    and attends no key past it. Tq and Tk are whole tiles (the largest of
    1024 .. 8 rows and 1024 .. 8 keys that divides them). Returns [Tq, nh,
    dv] in q's dtype."""
    dn = q.shape[-1] - rope_dims
    pe_width = latent.shape[1] - kv_lora_rank
    qn = jnp.swapaxes(q[..., :dn], 0, 1)
    qp = jnp.swapaxes(jnp.pad(q[..., dn:], ((0, 0), (0, 0),
                                            (0, pe_width - rope_dims))), 0, 1)
    at = jnp.asarray(q_start, jnp.int32).reshape(1)
    nh = q.shape[1]
    # each head's slice of Wkvb an array of its own, [nh, kv_lora_rank,
    # dn + dv]: a step's G heads are one block, and a head's columns need
    # not be whole lanes
    wkv_b = jnp.swapaxes(wkv_b.reshape(kv_lora_rank, nh, -1), 0, 1)
    o = _launch(qn, qp, latent, wkv_b,
                None if mask is None else (mask != 0).astype(jnp.int8), at,
                float(scale), kv_lora_rank)
    return jnp.swapaxes(o, 0, 1)
