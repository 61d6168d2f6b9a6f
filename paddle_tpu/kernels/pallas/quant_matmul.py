"""Per-block-scaled int8/fp8 matmul Pallas kernels — quantized COMPUTE.

Every quantization win so far is wire-only: grad sync (PR 4), mp
activations (PR 6), ep dispatch (PR 5), the KV cache (PR 12). The MXU
still runs everything in bf16 and decode still streams full-width
weights from HBM. This module moves the EQuARX-style per-block scale
codec (PAPERS.md) from the wire into the compute path:

- codec: weights [.., K, N] are quantized per (K-block, output column)
  — `scales[kb, n] = amax(|w[kb*B:(kb+1)*B, n]|) / QMAX` — the PR-4
  blockwise recipe turned column-major so the N (lane) dim stays dense
  and a K-block's scale row broadcasts across the MXU contraction.
- dense kernel: grid (MT, NT); the x tile [bm, K] streams full-width
  activations, the weight tile streams CODES [K, bn] (1 byte/elem) plus
  SCALES [KB, bn] (f32, K/B smaller) and dequantizes in VMEM right
  before the dot — quantized operands are the only weight HBM stream,
  ~0.52x the bf16 bytes at B=128.
- grouped kernel: grouped_matmul's scalar-prefetch machinery (tile
  offsets/counts, index-map clamp, pl.when ragged early-exit) with the
  expert weight tile swapped for codes+scales — the dropless MoE expert
  path at quantized weight traffic.
- training front doors `quantized_linear` / `quantized_grouped_linear`:
  custom_vjp whose FORWARD runs the quantized matmul (fp8 additionally
  fake-quantizes activations per-tensor, delayed scaling via
  `DelayedScaleState` outside the step) and whose BACKWARD stays in
  full precision against the original weights — the straight-through
  estimator every production fp8 recipe (transformer-engine) uses.

`impl` follows grouped_matmul: "auto" = kernel on TPU / XLA reference
(dequant-then-dot, numerically identical) off-TPU; "kernel" forces the
Pallas code in interpret mode so tier-1 CI executes it on CPU.

Process-global `configure_matmul_quant` is the knob fleet.init plumbs
from DistributedStrategy.matmul_quant (the mp_overlap/dispatch_compress
pattern); mp_layers and MoELayer consult it at trace time.
"""
from __future__ import annotations

import collections
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._x64 import i32_trace
from .grouped_matmul import (DEFAULT_BM, _interpret, _pick_tile,
                             _ref_dx, _ref_dw, _ref_fwd, _row_experts,
                             _tile_experts, _use_kernel, default_block_m)

__all__ = [
    "QK_BLOCK", "FP8_MAX", "INT8_MAX",
    "quantize_weight_blockwise", "dequantize_weight_blockwise",
    "quant_error_bound", "blockwise_weight_bytes",
    "quant_matmul", "quant_grouped_matmul",
    "quantized_linear", "quantized_grouped_linear",
    "DelayedScaleState",
    "configure_matmul_quant", "get_matmul_quant", "active_matmul_dtype",
    "record_weight_stream",
]

# default K-block: one scale row per 128 contraction rows — the MXU
# sublane tile, and the PR-4 wire codec's error regime (block amax /
# QMAX half-step) at 1/128 the scale overhead of per-element storage
QK_BLOCK = 128

INT8_MAX = np.float32(127.0)
FP8_MAX = np.float32(448.0)      # float8_e4m3fn finite max

_QDTYPES = ("int8", "fp8")


def _code_dtype(qdtype):
    return jnp.int8 if qdtype == "int8" else jnp.float8_e4m3fn


def _qmax(qdtype):
    return INT8_MAX if qdtype == "int8" else FP8_MAX


# -- codec -------------------------------------------------------------------

def _block_of(k, block_k):
    if block_k in (None, 0):
        return _pick_tile(k, QK_BLOCK)
    block_k = int(block_k)
    assert k % block_k == 0, \
        f"block_k={block_k} must divide the contraction dim K={k}"
    return block_k


def quantize_weight_blockwise(w, block_k=None, qdtype="int8"):
    """w [.., K, N] -> (codes [.., K, N] int8/f8e4m3, scales [.., KB, N]
    f32) with one scale per (K-block, output column). Zero blocks get
    scale 1.0 so dequant is exact there (the PR-4 convention)."""
    assert qdtype in _QDTYPES, qdtype
    k, n = w.shape[-2:]
    block = _block_of(k, block_k)
    kb = k // block
    wf = w.astype(jnp.float32).reshape(w.shape[:-2] + (kb, block, n))
    amax = jnp.max(jnp.abs(wf), axis=-2)                     # [.., kb, n]
    qmax = _qmax(qdtype)
    scale = jnp.where(amax > 0, amax / jnp.float32(qmax),
                      jnp.float32(1.0)).astype(jnp.float32)
    xb = wf / scale[..., :, None, :]
    if qdtype == "int8":
        q = jnp.clip(jnp.round(xb), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    else:
        q = xb.astype(jnp.float8_e4m3fn)
    return q.reshape(w.shape), scale


def dequantize_weight_blockwise(codes, scales):
    """Inverse of the codec: codes [.., K, N] * scales [.., KB, N]
    broadcast over each K-block -> f32 [.., K, N]."""
    k, n = codes.shape[-2:]
    kb = scales.shape[-2]
    block = k // kb
    q = codes.astype(jnp.float32).reshape(
        codes.shape[:-2] + (kb, block, n))
    return (q * scales[..., :, None, :].astype(jnp.float32)) \
        .reshape(codes.shape)


def quant_error_bound(w, scales, qdtype="int8"):
    """Elementwise worst-case round-trip error of the codec (the PR-4
    bound style): int8 rounds to the nearest scale step (half-step
    bound); fp8 e4m3 has 3 mantissa bits (relative half-ulp 2^-4) and
    bottoms out at the subnormal step scale * 2^-9."""
    k = w.shape[-2]
    block = k // scales.shape[-2]
    sb = jnp.repeat(scales.astype(jnp.float32), block, axis=-2)
    if qdtype == "int8":
        return sb * jnp.float32(0.5)
    return jnp.maximum(jnp.abs(w.astype(jnp.float32)) * jnp.float32(2.0 ** -4),
                       sb * jnp.float32(2.0 ** -9))


def blockwise_weight_bytes(k, n, block_k=None, qdtype="int8"):
    """(quantized_bytes, bf16_equivalent_bytes) one [K, N] weight costs
    per full fetch: codes at 1 byte/elem + f32 scales every block_k
    rows, vs 2 bytes/elem full-width. ~0.516x at block_k=128."""
    k, n = int(k), int(n)
    block = _block_of(k, block_k)
    return k * n * 1 + (k // block) * n * 4, k * n * 2


# -- dense kernel ------------------------------------------------------------

def _qmm_kernel(x_ref, q_ref, s_ref, o_ref, *, block_k):
    # dequantize IN VMEM: codes arrive 1 byte/elem, the scale row
    # broadcasts over its K-block, and the full-width weight tile never
    # exists outside the register file
    q = q_ref[:].astype(jnp.float32)                    # [K, bn]
    s = s_ref[:].astype(jnp.float32)                    # [KB, bn]
    k, bn = q.shape
    w = (q.reshape(k // block_k, block_k, bn) * s[:, None, :]) \
        .reshape(k, bn)
    acc = lax.dot_general(x_ref[:].astype(jnp.float32), w,
                          (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    o_ref[:] = acc.astype(o_ref.dtype)


@i32_trace
def _qmm_call(x, codes, scales, bm, bn, block_k, out_dtype):
    m, k = x.shape
    n = codes.shape[1]
    kb = k // block_k
    return pl.pallas_call(
        functools.partial(_qmm_kernel, block_k=block_k),
        grid=(m // bm, n // bn),
        in_specs=[pl.BlockSpec((bm, k), lambda mi, ni: (mi, 0)),
                  pl.BlockSpec((k, bn), lambda mi, ni: (0, ni)),
                  pl.BlockSpec((kb, bn), lambda mi, ni: (0, ni))],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=_interpret(),
    )(x, codes, scales)


def quant_matmul(x, codes, scales, *, bm=None, bn=128, impl="auto"):
    """x [.., K] @ dequant(codes [K, N], scales [KB, N]) -> [.., N] in
    x.dtype; the weight HBM stream is codes+scales only. impl follows
    grouped_matmul ("auto"/"kernel"/"reference")."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = codes.shape[-1]
    assert codes.shape[-2] == k, (x.shape, codes.shape)
    x2 = x.reshape(-1, k)
    out_dtype = x.dtype
    if not _use_kernel(impl):
        w = dequantize_weight_blockwise(codes, scales)
        out = jnp.matmul(x2.astype(jnp.float32), w,
                         preferred_element_type=jnp.float32) \
            .astype(out_dtype)
    else:
        block_k = k // scales.shape[-2]
        bm_eff = _pick_tile(x2.shape[0], bm or default_block_m())
        bn_eff = _pick_tile(n, bn)
        out = _qmm_call(x2, codes, scales, bm_eff, bn_eff, block_k,
                        out_dtype)
    return out.reshape(lead + (n,))


# -- grouped kernel (expert-sorted tokens, grouped_matmul layout) ------------

def _gq_kernel(toffs, tcnt, x_ref, q_ref, s_ref, o_ref, *, block_k):
    e = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t < tcnt[e])
    def _step():
        q = q_ref[:].astype(jnp.float32)                # [K, bn]
        s = s_ref[:].astype(jnp.float32)                # [KB, bn]
        k, bn = q.shape
        w = (q.reshape(k // block_k, block_k, bn) * s[:, None, :]) \
            .reshape(k, bn)
        acc = lax.dot_general(x_ref[:].astype(jnp.float32), w,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
        o_ref[:] = acc.astype(o_ref.dtype)


@i32_trace
def _gq_call(x, codes, scales, toffs, tcnt, bm, bn, block_k, out_dtype):
    t_rows, k = x.shape
    e, _, n = codes.shape
    kb = k // block_k
    mt = t_rows // bm
    nt = n // bn

    def row(ei, ti, toffs, tcnt):
        return toffs[ei] + jnp.minimum(ti, jnp.maximum(tcnt[ei] - 1, 0))

    def x_map(ei, ti, ni, toffs, tcnt):
        return (row(ei, ti, toffs, tcnt), 0)

    def q_map(ei, ti, ni, toffs, tcnt):
        return (ei, 0, ni)

    def s_map(ei, ti, ni, toffs, tcnt):
        return (ei, 0, ni)

    def o_map(ei, ti, ni, toffs, tcnt):
        return (row(ei, ti, toffs, tcnt), ni)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(e, mt, nt),
        in_specs=[pl.BlockSpec((bm, k), x_map),
                  pl.BlockSpec((None, k, bn), q_map),
                  pl.BlockSpec((None, kb, bn), s_map)],
        out_specs=pl.BlockSpec((bm, bn), o_map),
    )
    return pl.pallas_call(
        functools.partial(_gq_kernel, block_k=block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_rows, n), out_dtype),
        interpret=_interpret(),
    )(toffs, tcnt, x, codes, scales)


def quant_grouped_matmul(x, codes, scales, *, group_offsets, group_counts,
                         bm=DEFAULT_BM, bn=128, impl="auto"):
    """grouped_matmul over quantized expert weights: out[r] = x[r] @
    dequant(codes[e(r)], scales[e(r)]). Same tile-aligned sorted-token
    layout and ragged early-exit; codes [E, K, N], scales [E, KB, N]."""
    t_rows, k = x.shape
    e, k2, n = codes.shape
    assert k == k2, (x.shape, codes.shape)
    assert t_rows % bm == 0, \
        f"token buffer rows {t_rows} must be a multiple of bm={bm}"
    offsets = group_offsets.astype(jnp.int32)
    counts = group_counts.astype(jnp.int32)
    out_dtype = x.dtype
    if not _use_kernel(impl):
        w = dequantize_weight_blockwise(codes, scales)
        return _ref_fwd(x, w, None, offsets, counts, bm, out_dtype)
    block_k = k // scales.shape[-2]
    toffs = offsets // jnp.int32(bm)
    tcnt = -(-counts // jnp.int32(bm))
    bn_eff = _pick_tile(n, bn)
    return _gq_call(x, codes, scales, toffs, tcnt, bm, bn_eff, block_k,
                    out_dtype)


# -- training front doors (custom_vjp, full-precision backward) --------------

@functools.lru_cache(maxsize=None)
def _qlin_vjp(qdtype, block_k, impl, has_xscale):
    """One custom_vjp per static config (the grouped_matmul._gmm_vjp
    pattern — stable primitives across traces). Forward quantizes the
    weight per-block (fp8 additionally fake-quantizes activations
    per-tensor, scale either delayed via has_xscale or in-trace amax);
    backward is the straight-through estimator: plain bf16/f32 matmuls
    against the ORIGINAL weight and activations."""

    def run(x, w, x_scale):
        codes, scales = quantize_weight_blockwise(w, block_k, qdtype)
        x2 = x.reshape(-1, x.shape[-1])
        if qdtype == "fp8":
            xs = x_scale if has_xscale else jnp.maximum(
                jnp.max(jnp.abs(x2.astype(jnp.float32))),
                jnp.float32(1e-12)) / jnp.float32(FP8_MAX)
            xq = (x2.astype(jnp.float32) / xs).astype(jnp.float8_e4m3fn)
            x2 = (xq.astype(jnp.float32) * xs).astype(x.dtype)
        out = quant_matmul(x2, codes, scales, impl=impl)
        return out.reshape(x.shape[:-1] + (w.shape[-1],))

    @jax.custom_vjp
    def qlin(x, w, x_scale):
        return run(x, w, x_scale)

    def fwd(x, w, x_scale):
        return run(x, w, x_scale), (x, w, x_scale)

    def bwd(res, dy):
        x, w, x_scale = res
        k, n = w.shape
        dy2 = dy.reshape(-1, n).astype(jnp.float32)
        x2 = x.reshape(-1, k).astype(jnp.float32)
        dx = jnp.matmul(dy2, w.astype(jnp.float32).T,
                        preferred_element_type=jnp.float32) \
            .astype(x.dtype).reshape(x.shape)
        dw = jnp.matmul(x2.T, dy2,
                        preferred_element_type=jnp.float32).astype(w.dtype)
        return dx, dw, jnp.zeros_like(x_scale)

    qlin.defvjp(fwd, bwd)
    return qlin


def quantized_linear(x, w, *, qdtype="int8", block_k=None, x_scale=None,
                     impl="auto"):
    """x [.., K] @ w [K, N] with the weight quantized per-block at trace
    time and the matmul run through quant_matmul; gradients are full
    precision (STE). qdtype "int8" is weight-only; "fp8" also
    fake-quantizes activations per-tensor — pass x_scale (a
    DelayedScaleState.scale) for delayed scaling, else the amax is
    taken in-trace."""
    assert qdtype in _QDTYPES, qdtype
    fn = _qlin_vjp(str(qdtype), int(block_k or 0), str(impl),
                   x_scale is not None)
    xs = jnp.float32(x_scale if x_scale is not None else 1.0)
    return fn(x, w, xs)


@functools.lru_cache(maxsize=None)
def _qgmm_vjp(qdtype, block_k, bm, bn, impl, b_dtype):
    from .grouped_matmul import _dw_call, _gmm_raw
    has_bias = b_dtype is not None

    def run(x, w, b, offsets, counts):
        codes, scales = quantize_weight_blockwise(w, block_k, qdtype)
        y = quant_grouped_matmul(x, codes, scales, group_offsets=offsets,
                                 group_counts=counts, bm=bm, bn=bn,
                                 impl=impl)
        if has_bias:
            e_of_row, _ = _row_experts(offsets.astype(jnp.int32),
                                       counts.astype(jnp.int32),
                                       x.shape[0], w.shape[0])
            y = (y.astype(jnp.float32)
                 + b[e_of_row].astype(jnp.float32)).astype(y.dtype)
        return y

    @jax.custom_vjp
    def qgmm(x, w, b, offsets, counts):
        return run(x, w, b, offsets, counts)

    def fwd(x, w, b, offsets, counts):
        return run(x, w, b, offsets, counts), (x, w, offsets, counts)

    def bwd(res, dy):
        # grouped_matmul's backward rules verbatim, but ALWAYS against
        # the original full-precision weights (STE) — quantization never
        # touches the gradient path
        x, w, offsets, counts = res
        offsets = offsets.astype(jnp.int32)
        counts = counts.astype(jnp.int32)
        e, k, n = w.shape
        if _use_kernel(impl):
            dx = _gmm_raw(dy, jnp.swapaxes(w, 1, 2), None, offsets,
                          counts, bm, bn, impl).astype(x.dtype)
            toffs = offsets // jnp.int32(bm)
            tcnt = -(-counts // jnp.int32(bm))
            bk = _pick_tile(k, bn)
            bn_eff = _pick_tile(n, bn)
            dw = _dw_call(x, dy, toffs, tcnt, counts, bm, bk, bn_eff)
        else:
            wg = w[_tile_experts(offsets, x.shape[0], bm, e)]
            dx = _ref_dx(dy, wg, bm).astype(x.dtype)
            dw = _ref_dw(x, dy, offsets, counts, bm, e)
        dw = dw.astype(w.dtype)
        if has_bias:
            e_of_row, valid = _row_experts(offsets, counts, x.shape[0], e)
            oh = (e_of_row[:, None]
                  == jnp.arange(e, dtype=jnp.int32)[None, :])
            mask = (oh & valid[:, None]).astype(jnp.float32)
            db = jnp.einsum("te,tn->en", mask,
                            dy.astype(jnp.float32)).astype(b_dtype)
        else:
            db = None
        return dx, dw, db, None, None

    qgmm.defvjp(fwd, bwd)
    return qgmm


def quantized_grouped_linear(x, w, b=None, *, group_offsets, group_counts,
                             qdtype="int8", block_k=None, bm=DEFAULT_BM,
                             bn=128, impl="auto"):
    """grouped_matmul with per-block weight quantization on the forward
    and full-precision (STE) gradients — the MoE expert GEMMs'
    quantized path. Same layout contract as grouped_matmul."""
    assert qdtype in _QDTYPES, qdtype
    if b is not None and b.ndim == 3:        # [E, 1, N] layer bias form
        b = b.reshape(b.shape[0], b.shape[2])
    fn = _qgmm_vjp(str(qdtype), int(block_k or 0), int(bm), int(bn),
                   str(impl), None if b is None else str(b.dtype))
    return fn(x, w, b, group_offsets, group_counts)


# -- delayed scaling (fp8) ---------------------------------------------------

class DelayedScaleState:
    """Host-side amax history for fp8 delayed scaling (the
    transformer-engine recipe): observe the activation amax OUTSIDE the
    jitted step, feed `.scale` into the next step's x_scale — the scale
    is a step argument, never a traced recomputation."""

    def __init__(self, history_len=16, qmax=FP8_MAX):
        self._hist = collections.deque(maxlen=int(history_len))
        self._qmax = float(qmax)

    def observe(self, amax):
        self._hist.append(float(amax))
        return self.scale

    @property
    def scale(self):
        if not self._hist:
            return 1.0
        m = max(self._hist)
        return m / self._qmax if m > 0 else 1.0


# -- process-global knob (fleet.init plumbs DistributedStrategy here) --------

def _env_default():
    d = os.environ.get("PT_MATMUL_QUANT", "").strip().lower()
    return d if d in _QDTYPES else None


_MATMUL_QUANT = {"dtype": _env_default()}
_UNCHANGED = "__unchanged__"


def configure_matmul_quant(dtype=_UNCHANGED):
    """Set the process-global quantized-matmul dtype (None | "int8" |
    "fp8"); mp_layers and MoELayer consult it at trace time. Call with
    no args to read without changing."""
    if dtype is not _UNCHANGED:
        if dtype in ("none", "", False):
            dtype = None
        if dtype is not None and dtype not in _QDTYPES:
            raise ValueError(
                f"matmul_quant must be one of {(None,) + _QDTYPES}, "
                f"got {dtype!r}")
        _MATMUL_QUANT["dtype"] = dtype
    return dict(_MATMUL_QUANT)


def get_matmul_quant():
    return _MATMUL_QUANT["dtype"]


def active_matmul_dtype(default="bfloat16"):
    """The dtype the training matmuls actually run at — the bench
    telemetry's `matmul_dtype` field."""
    return _MATMUL_QUANT["dtype"] or str(default)


# -- host-side telemetry -----------------------------------------------------

def record_weight_stream(*, quant_bytes, bf16_bytes, fetches=1):
    """Counters for the quantized weight HBM stream (concrete host
    values only — decode records once per step outside the trace,
    mirroring record_moe_dispatch):

      paddle_tpu_quant_weight_bytes_total   codes+scales bytes fetched
      paddle_tpu_quant_weight_bf16eq_total  what the same fetches would
                                            have cost at bf16 — the
                                            yardstick the <0.6x traffic
                                            gate divides by
    """
    from ... import observability as obs
    if not obs.enabled():
        return
    reg = obs.registry()
    reg.counter("paddle_tpu_quant_weight_bytes_total",
                "Quantized weight bytes streamed from HBM").inc(
                    int(fetches) * int(quant_bytes))
    reg.counter("paddle_tpu_quant_weight_bf16eq_total",
                "bf16-equivalent bytes for the same weight "
                "fetches").inc(int(fetches) * int(bf16_bytes))
