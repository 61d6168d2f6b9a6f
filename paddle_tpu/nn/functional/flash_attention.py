"""Attention functionals.

Reference: python/paddle/nn/functional/flash_attention.py:147 (flash_attention),
:722 (scaled_dot_product_attention). The XLA path below is the fallback;
paddle_tpu.kernels.pallas.flash_attention provides the fused TPU kernel and
is selected automatically for supported shapes/dtypes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...framework.op_registry import primitive
from ...framework.tensor import Tensor

__all__ = ["scaled_dot_product_attention", "flash_attention",
           "flash_attn_unpadded", "sdp_kernel"]


@primitive("sdpa_xla")
def _sdpa_xla(q, k, v, *, causal, scale):
    # [B, S, H, D] (paddle flash_attention layout)
    qh = jnp.swapaxes(q, 1, 2)  # [B, H, S, D]
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    scores = jnp.einsum("bhsd,bhtd->bhst", qh, kh) * scale
    if causal:
        s, t = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), bool), k=t - s)
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


@primitive("sdpa_mask_xla")
def _sdpa_mask_xla(q, k, v, mask, *, scale):
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    scores = jnp.einsum("bhsd,bhtd->bhst", qh, kh) * scale
    if mask.dtype == jnp.bool_:
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    else:
        scores = scores + mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


def _use_pallas(q):
    """The Pallas kernel serves these shapes on a TPU. Whether it imports
    and lowers is not asked here: on a TPU that failure raises at the call
    rather than giving way to the O(s^2) reference in silence."""
    d, s = q.shape[-1], q.shape[1]
    # kernel blocks are 128-wide: seq must divide evenly or rows of the
    # output block would be undefined
    return (_use_pallas_backend() and d in (64, 128, 256)
            and s >= 128 and s % 128 == 0)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """Inputs [batch, seq, heads, head_dim] (reference layout at
    flash_attention.py:147). Returns (out, softmax) tuple like the reference."""
    scale = 1.0 / math.sqrt(query.shape[-1])
    if _use_pallas(query):
        from ...kernels.pallas.flash_attention import flash_attention_fwd
        out = flash_attention_fwd(query, key, value, causal=causal, scale=scale)
    else:
        out = _sdpa_xla(query, key, value, causal=bool(causal), scale=scale)
    if dropout > 0.0 and training:
        from .common import dropout as _dropout
        out = _dropout(out, p=dropout)
    if return_softmax:
        return out, None
    return out, None


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None):
    """Reference: flash_attention.py:722 — same [B, S, H, D] layout."""
    scale = 1.0 / math.sqrt(query.shape[-1])
    if attn_mask is None:
        if _use_pallas(query):
            from ...kernels.pallas.flash_attention import flash_attention_fwd
            out = flash_attention_fwd(query, key, value, causal=is_causal,
                                      scale=scale)
        else:
            out = _sdpa_xla(query, key, value, causal=bool(is_causal), scale=scale)
    else:
        out = _sdpa_mask_xla(query, key, value, attn_mask, scale=scale)
    if dropout_p > 0.0 and training:
        from .common import dropout as _dropout
        out = _dropout(out, p=dropout_p)
    return out


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen attention (reference flash_attention.py:455): total-token packed
    layout [total, H, D] with cu_seqlens boundaries. On TPU this runs the
    segment-pruning Pallas kernels (kernels/pallas/flash_varlen.py) — the
    O(total²) masked-softmax XLA path remains only as the ragged-shape
    fallback.

    Deviation (documented, PARITY.md): dropout>0 is applied to the
    attention OUTPUT, not to the attention probabilities as the reference
    varlen CUDA kernel does — a different (but standard) regularization
    distribution, consistent with this repo's sdpa approximation. Thread
    prob-dropout through the Pallas kernel if bit-parity is ever needed."""
    import numpy as np
    total, h, d = query.shape
    total_k = key.shape[0]
    cu_q = cu_seqlens_q._data if isinstance(cu_seqlens_q, Tensor) else cu_seqlens_q
    cu_k = cu_seqlens_k._data if isinstance(cu_seqlens_k, Tensor) else cu_seqlens_k
    from ...kernels.pallas.flash_varlen import varlen_supported
    if _use_pallas_backend() and varlen_supported(total, total_k, d):
        same_pack = False
        if not isinstance(cu_q, jax.core.Tracer) and \
                not isinstance(cu_k, jax.core.Tracer):
            same_pack = bool(np.array_equal(np.asarray(cu_q),
                                            np.asarray(cu_k)))
        out = _varlen_pallas(query, key, value, Tensor(cu_q), Tensor(cu_k),
                             scale=float(scale), causal=bool(causal),
                             same_pack=same_pack)
    else:
        seg_q = jnp.cumsum(jnp.zeros(total, jnp.int32).at[cu_q[1:-1]].add(1))
        seg_k = jnp.cumsum(
            jnp.zeros(total_k, jnp.int32).at[cu_k[1:-1]].add(1))
        out = _varlen_attn(query, key, value, Tensor(seg_q), Tensor(seg_k),
                           scale=float(scale), causal=bool(causal))
    if dropout > 0.0 and training:
        from .common import dropout as _dropout
        out = _dropout(out, p=dropout)
    return out


def _use_pallas_backend():
    return jax.default_backend() == "tpu"


@primitive("flash_varlen_pallas")
def _varlen_pallas(q, k, v, cu_q, cu_k, *, scale, causal, same_pack):
    from ...kernels.pallas.flash_varlen import flash_varlen_attention
    return flash_varlen_attention(q, k, v, cu_q, cu_k, scale=scale,
                                  causal=causal, same_pack=same_pack)


@primitive("varlen_attn_xla")
def _varlen_attn(q, k, v, seg_q, seg_k, *, scale, causal):
    scores = jnp.einsum("shd,thd->hst", q, k) * scale
    mask = seg_q[:, None] == seg_k[None, :]
    if causal:
        mask = mask & (jnp.arange(q.shape[0], dtype=jnp.int32)[:, None]
                       >= jnp.arange(k.shape[0], dtype=jnp.int32)[None, :])
    scores = jnp.where(mask[None], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(q.dtype)
    out = jnp.einsum("hst,thd->shd", probs, v)
    return out


class sdp_kernel:
    """Context selecting attention backends (API parity with paddle incubate)."""

    def __init__(self, enable_flash=True, enable_math=True,
                 enable_mem_efficient=True):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
