"""Fused RMSNorm Pallas kernel (+ residual-add variant).

Counterpart of the reference's fused_rms_norm CUDA kernels
(paddle/phi/kernels/fusion/gpu/fused_layernorm_kernel.cu rms path,
fused_bias_dropout_residual_layer_norm_kernel.cu family): one pass over
HBM computing x*rsqrt(mean(x^2)+eps)*w in fp32, optionally fusing the
residual add. Backward is a custom VJP with a row-blocked kernel for dx
and an fp32 psum for dw.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ._x64 import i32_trace

__all__ = ["rms_norm_jax", "rms_norm_residual_jax"]


def _interpret():
    return jax.default_backend() != "tpu"


# Mosaic gives one kernel 16 MiB of scoped VMEM on the v5e (the limit its
# compiler names when it refuses). The double-buffered row blocks may take
# half; the kernel's f32 temporaries need the rest.
_VMEM_BLOCK_BUDGET = 8 << 20


def _row_block(n_rows, width, itemsize):
    """Rows per grid step: the largest power of two <= 256 that divides
    n_rows and keeps the backward's three [rows, width] blocks (x, g, dx),
    each double buffered, inside the block budget. Forward and backward
    share it."""
    fit = max(_VMEM_BLOCK_BUDGET // (6 * width * itemsize), 1)
    for b in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if b <= fit and n_rows % b == 0:
            return b
    return 1


def _fwd_kernel(x_ref, w_ref, o_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = lax.rsqrt(ms + eps)
    o_ref[:] = (x * rstd * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    rstd_ref[:, 0] = rstd[:, 0]


def _bwd_kernel(x_ref, w_ref, rstd_ref, g_ref, dx_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:, 0][:, None]
    xhat = x * rstd
    wg = g * w
    # dx = rstd * (wg - xhat * mean(wg * xhat))
    dx = rstd * (wg - xhat * jnp.mean(wg * xhat, axis=-1, keepdims=True))
    dx_ref[:] = dx.astype(dx_ref.dtype)


@i32_trace
def _rms_fwd(x2d, w, eps):
    n, h = x2d.shape
    br = _row_block(n, h, x2d.dtype.itemsize)
    out, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(n // br,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2d.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(x2d, w)
    return out, rstd


@i32_trace
def _rms_bwd(x2d, w, rstd, g2d, eps):
    n, h = x2d.shape
    br = _row_block(n, h, x2d.dtype.itemsize)
    nb = n // br
    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, h), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h), x2d.dtype),
        interpret=_interpret(),
    )(x2d, w, rstd, g2d)
    # dw = sum_n g * xhat — a single fused XLA reduction pass (a (1, h)
    # per-block partial output would violate Mosaic's (8, 128) store
    # tiling, so the kernel only produces dx)
    dw = jnp.einsum("nh,nh,n->h", g2d.astype(jnp.float32),
                    x2d.astype(jnp.float32), rstd[:, 0])
    return dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms2d(x2d, w, eps):
    return _rms_fwd(x2d, w, eps)[0]


def _rms2d_fwd(x2d, w, eps):
    out, rstd = _rms_fwd(x2d, w, eps)
    return out, (x2d, w, rstd)


def _rms2d_bwd(eps, res, g):
    x2d, w, rstd = res
    dx, dw = _rms_bwd(x2d, w, rstd, g, eps)
    return dx, dw.astype(w.dtype)


_rms2d.defvjp(_rms2d_fwd, _rms2d_bwd)


def rms_norm_jax(x, w, eps=1e-6):
    """RMSNorm over the last dim; x any rank, w [hidden]."""
    shape = x.shape
    out = _rms2d(x.reshape(-1, shape[-1]), w, float(eps))
    return out.reshape(shape)


def rms_norm_residual_jax(x, residual, w, eps=1e-6):
    """(x + residual) -> rms_norm; returns (normed, x+residual) like the
    reference's fused residual+norm kernels."""
    s = x + residual
    return rms_norm_jax(s, w, eps), s
