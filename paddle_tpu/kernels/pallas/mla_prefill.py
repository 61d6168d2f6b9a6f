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

A key tile's latent rows [bk, W] and the head's [kv_lora_rank, dn + dv]
slice of Wkvb are all the kernel reads besides q and the mask: no [keys,
heads, dims] array of expanded keys or values exists in HBM (at 16,384
keys and 128 heads those are 1.34 GB a layer), and keys past the chunk's
last row are neither expanded nor read. A query block holds the whole
chunk (up to 1,024 rows), so a tile's keys are expanded once a head.

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


def _kernel(at_ref, qn_ref, qp_ref, lat_ref, w_ref, *refs, scale, bq, bk,
            kvr, dn, masked):
    """One (head, query block, key step). at_ref (SMEM) [q_start];
    qn_ref [bq, dn]; qp_ref [bq, W - kvr]; lat_ref [bk, W]; w_ref [kvr,
    dn + dv]; then, where `masked`, mask_ref [bq, bk] int8; o_ref [bq,
    dv] and the scratch."""
    mask_ref = refs[0] if masked else None
    o_ref, m_sc, l_sc, acc_sc = refs[1:] if masked else refs
    i, j = pl.program_id(1), pl.program_id(2)
    row0 = at_ref[0] + i * np.int32(bq)
    last = (row0 + np.int32(bq - 1)) // np.int32(bk)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    @pl.when(j <= last)
    def _step():
        lat, w = lat_ref[...], w_ref[...]
        c, pe = lat[:, :kvr], lat[:, kvr:]
        kn = _dot(c, w[:, :dn], _NN).astype(c.dtype)          # [bk, dn]
        v = _dot(c, w[:, dn:], _NN).astype(c.dtype)           # [bk, dv]
        st = (_dot(qn_ref[...], kn, _NT) + _dot(qp_ref[...], pe, _NT)) \
            * scale
        row = row0 + lax.broadcasted_iota(jnp.int32, st.shape, 0)
        col = j * np.int32(bk) + lax.broadcasted_iota(jnp.int32, st.shape, 1)
        sees = col <= row
        if masked:
            sees = jnp.logical_and(sees, mask_ref[...] != 0)
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


@i32_trace
def _launch(qn, qp, lat, w, mask, at, scale, kvr):
    nh, tq, dn = qn.shape
    tk = lat.shape[0]
    dv = w.shape[-1] // (1 if w.ndim == 3 else nh) - dn
    bq, bk = _block(tq, 1024), _block(tk, 512)

    def key_tile(i, j, at_ref):
        last = (at_ref[0] + (i + 1) * np.int32(bq) - 1) // np.int32(bk)
        return jnp.minimum(j, last)

    masks = [] if mask is None else [
        pl.BlockSpec((bq, bk), lambda h, i, j, at: (i, key_tile(i, j, at)))]
    return pl.pallas_call(
        functools.partial(_kernel, scale=np.float32(scale), bq=bq, bk=bk,
                          kvr=kvr, dn=dn, masked=mask is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nh, tq // bq, tk // bk),
            in_specs=[
                pl.BlockSpec((None, bq, dn), lambda h, i, j, *_: (h, i, 0)),
                pl.BlockSpec((None, bq, qp.shape[-1]),
                             lambda h, i, j, *_: (h, i, 0)),
                pl.BlockSpec((bk, lat.shape[1]),
                             lambda h, i, j, at: (key_tile(i, j, at), 0)),
                pl.BlockSpec((kvr, dn + dv), lambda h, i, j, *_: (0, h))
                if w.ndim == 2 else
                pl.BlockSpec((None, kvr, dn + dv),
                             lambda h, i, j, *_: (h, 0, 0))]
            + masks,
            out_specs=pl.BlockSpec((None, bq, dv),
                                   lambda h, i, j, *_: (h, i, 0)),
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((nh, tq, dv), qn.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2**20),
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
    1024 .. 8 rows and 512 .. 8 keys that divides them). Returns [Tq, nh,
    dv] in q's dtype."""
    dn = q.shape[-1] - rope_dims
    pe_width = latent.shape[1] - kv_lora_rank
    qn = jnp.swapaxes(q[..., :dn], 0, 1)
    qp = jnp.swapaxes(jnp.pad(q[..., dn:], ((0, 0), (0, 0),
                                            (0, pe_width - rope_dims))), 0, 1)
    at = jnp.asarray(q_start, jnp.int32).reshape(1)
    nh, per_head = q.shape[1], wkv_b.shape[1] // q.shape[1]
    if per_head % 128:
        # a head's columns are no whole lanes: Mosaic copies a block of
        # whole lanes or the whole dimension, so a head's slice of Wkvb
        # becomes a [kv_lora_rank, dn + dv] array of its own
        wkv_b = jnp.swapaxes(wkv_b.reshape(kv_lora_rank, nh, per_head), 0, 1)
    o = _launch(qn, qp, latent, wkv_b,
                None if mask is None else mask.astype(jnp.int8), at,
                float(scale), kv_lora_rank)
    return jnp.swapaxes(o, 0, 1)
