"""Pallas TPU grouped matmul for the dropless MoE expert path.

Reference capability: the grouped NCCL dispatch + per-expert FFNs of
incubate/distributed/models/moe (global_scatter -> expert MLPs ->
global_gather), computed the way MegaBlocks-style dropless MoE does it
on TPU: tokens are SORTED by expert id into contiguous groups and each
expert's matmul runs over exactly its tokens — no `[E, C, H]` capacity
buffer, no dropped routes, no dead capacity-padding flops.

Why this exists: moe_layer.py's capacity formulation pads every expert
to a static capacity `C = ceil(cf * N * K / E)` and pushes `[E, C, H]`
buffers through dense einsums, so compute and HBM traffic scale with
the WORST-CASE capacity rather than the actual routed tokens, and
imbalanced gates silently drop routes past C. Here the sorted token
buffer holds each group at a tile-ALIGNED offset, and the kernel's grid
visits only tiles the scalar-prefetched group metadata marks live — a
group with `c` tokens costs `ceil(c/bm)` tile-matmuls, and tiles past a
group's token count are never fetched or computed (the same ragged
early-exit ragged_paged_attention.py proved for paged KV blocks).

Mechanics (the PR-2 pattern applied to expert groups):

- grid = (E, MT, NT), MT = T // bm worst-case row tiles, NT output
  column tiles; scalar-prefetched per-group TILE offsets and live-tile
  counts drive every BlockSpec index map, so grid step (e, t, n)
  fetches x tile `toffs[e] + t` and writes the matching out tile — the
  group layout IS the fetch schedule.
- steps with `t >= tcnt[e]` CLAMP their index maps to the group's last
  live tile (Mosaic skips the re-fetch when consecutive steps map to
  the same block) and `pl.when` skips the compute: the ragged
  early-exit costs no HBM and (nearly) no cycles.
- the MXU dot accumulates in f32 (`preferred_element_type`) and casts
  to the output dtype once — bf16 activations stay bf16 end to end.

The backward runs through a `jax.custom_vjp`: dx is the SAME kernel
against the transposed expert weights, dw is a second grouped kernel
accumulating `x_tile^T @ dy_tile` per expert across its live tiles
(rows past each group's token count are masked, so callers with
garbage padding rows still get exact weight grads).

On non-TPU backends `impl="kernel"` runs the exact kernel code in
interpret mode so tier-1 CI exercises it (flash_attention.py's
pattern); `impl="auto"` uses a mathematically-identical gathered-weight
XLA reference off-TPU, which is what CPU benchmarks and the MoE layer's
jitted path execute (interpret-mode grid loops are host-speed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import numpy as np

from ._x64 import i32_trace

__all__ = ["grouped_matmul", "grouped_matmul_sorted", "grouped_metadata",
           "aligned_group_size", "record_moe_dispatch", "DEFAULT_BM",
           "default_block_m"]


def _interpret():
    return jax.default_backend() != "tpu"


# default row-tile: MXU-sized on TPU; 32 on CPU — the reference path's
# per-tile weight gather is [MT, K, N] and MT shrinks with bm, so small
# tiles pay a gather far bigger than the weights themselves (bm=8 is
# 1.7-2.2x slower than bm=32 across 64-512 routes, measured jitted
# fwd+bwd at the bench geometry; alignment padding at bm=32 stays < E
# tiles and is dwarfed by the gather saving)
DEFAULT_BM = 128


def default_block_m():
    return DEFAULT_BM if jax.default_backend() == "tpu" else 32


def aligned_group_size(n_routes, num_expert, bm):
    """Static row count of the tile-aligned sorted token buffer: every
    group padded up to a multiple of bm can add at most bm-1 rows, plus
    one spare tile so the empty-group index-map clamp stays in range."""
    import math
    return (math.ceil(max(int(n_routes), 1) / bm) + int(num_expert)) * bm


def _onehot_ranks(expert_ids, num_expert):
    """(counts [E], rank [T]) of each route within its expert group via
    one-hot cumsums: rank = the route's position among all routes to
    its expert in route-major order, which IS the stable expert-sort
    order — no argsort runs (a comparison sort per dispatch, and itself
    an s64 trap under x64). The SINGLE copy of the routing idiom shared
    by grouped_metadata, moe_layer._route and dispatch._ep_body — the
    receiver-side regroup in _ep_body depends on all callers producing
    byte-identical ordering, and every output is pinned i32 (under x64
    cumsum/take promote to s64 and s64-indexed dynamic slices on
    sharded dims fail after spmd-partitioning on this container)."""
    e = expert_ids.reshape(-1).astype(jnp.int32)
    oh = (e[:, None] == jnp.arange(num_expert,
                                   dtype=jnp.int32)[None, :]) \
        .astype(jnp.int32)                                  # [T, E]
    counts = jnp.sum(oh, axis=0, dtype=jnp.int32)           # [E]
    # flat i32 gather, not take_along_axis — its internal bounds-check
    # math is default-int and plants s64 index vectors under x64 (the
    # lowering-lint registry gates this module on no-s64)
    csum = jnp.cumsum(oh, axis=0, dtype=jnp.int32) - 1      # [T, E]
    t_idx = jnp.arange(e.shape[0], dtype=jnp.int32)
    rank = csum.reshape(-1)[t_idx * jnp.int32(num_expert) + e]  # [T]
    return counts, rank


def grouped_metadata(expert_ids, num_expert, bm, total_rows=None):
    """Routing metadata for the sorted-token grouped layout.

    No actual sort runs: a route's rank within its group is the
    one-hot CUMSUM at its position (`_onehot_ranks`), which reproduces
    the stable expert-sort order directly.

    expert_ids: [T] int route -> expert. Returns a dict of i32 arrays
    (every index pinned i32 — the known partitioner trap, see
    `_onehot_ranks`):

      counts     [E]  tokens routed to each expert
      offsets    [E]  tile-ALIGNED row offset of each group (mult of bm)
      dest       [T]  aligned buffer row of route i (groups contiguous,
                      route order preserved within each group)
      row_src    [Tp] buffer row -> route id (-1 = padding row)
      row_valid  [Tp] 1.0 where the row holds a real route

    Tp = total_rows or aligned_group_size(T, E, bm).
    """
    e = expert_ids.reshape(-1).astype(jnp.int32)
    t = e.shape[0]
    tp = int(total_rows) if total_rows is not None \
        else aligned_group_size(t, num_expert, bm)
    counts, rank = _onehot_ranks(e, num_expert)
    tiles = -(-counts // jnp.int32(bm))                     # ceil
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(tiles, dtype=jnp.int32)[:-1]]) * jnp.int32(bm)
    dest = offsets[e] + rank                                # [T]
    row_src = jnp.full((tp,), -1, jnp.int32).at[dest].set(
        jnp.arange(t, dtype=jnp.int32), mode="drop")
    return {"counts": counts, "offsets": offsets,
            "dest": dest, "row_src": row_src,
            "row_valid": (row_src >= 0)}


def _pick_tile(n, pref):
    """Largest divisor of n that is <= pref (tile sizes must tile the
    array exactly; shapes here are layer dims, usually 2^k multiples)."""
    n, pref = int(n), int(pref)
    if n <= pref:
        return n
    for c in range(pref, 0, -1):
        if n % c == 0:
            return c
    return n


# -- forward kernel ----------------------------------------------------------

def _fwd_kernel(toffs, tcnt, x_ref, w_ref, b_ref, o_ref, *, has_bias):
    e = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t < tcnt[e])
    def _step():
        acc = lax.dot_general(x_ref[:], w_ref[:],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
        if has_bias:
            acc = acc + b_ref[:].astype(jnp.float32)
        o_ref[:] = acc.astype(o_ref.dtype)


@i32_trace
def _fwd_call(x, w, b, toffs, tcnt, bm, bn, out_dtype):
    t_rows, k = x.shape
    e, _, n = w.shape
    mt = t_rows // bm
    nt = n // bn

    # index maps are re-traced at pallas lowering time in TILE units;
    # toffs/tcnt arrive as i32 scalar-prefetch refs, so all arithmetic
    # here stays 32-bit (the _x64 guard covers the call itself)
    def row(ei, ti, toffs, tcnt):
        return toffs[ei] + jnp.minimum(ti, jnp.maximum(tcnt[ei] - 1, 0))

    def x_map(ei, ti, ni, toffs, tcnt):
        return (row(ei, ti, toffs, tcnt), 0)

    def w_map(ei, ti, ni, toffs, tcnt):
        return (ei, 0, ni)

    def b_map(ei, ti, ni, toffs, tcnt):
        return (ei, ni)

    def o_map(ei, ti, ni, toffs, tcnt):
        return (row(ei, ti, toffs, tcnt), ni)

    has_bias = b is not None
    in_specs = [pl.BlockSpec((bm, k), x_map),
                pl.BlockSpec((None, k, bn), w_map)]
    args = [toffs, tcnt, x, w]
    if has_bias:
        in_specs.append(pl.BlockSpec((None, bn), b_map))
        args.append(b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(e, mt, nt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), o_map),
    )
    kernel = functools.partial(_fwd_kernel, has_bias=has_bias)
    if not has_bias:
        def kernel(toffs, tcnt, x_ref, w_ref, o_ref):  # noqa: F811
            return _fwd_kernel(toffs, tcnt, x_ref, w_ref, None, o_ref,
                               has_bias=False)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_rows, n), out_dtype),
        interpret=_interpret(),
    )(*args)


# -- backward dw kernel ------------------------------------------------------

def _dw_kernel(toffs, tcnt, rowcnt, x_ref, dy_ref, o_ref, *, bm):
    e = pl.program_id(0)
    t = pl.program_id(3)

    @pl.when(t == 0)
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    @pl.when(t < tcnt[e])
    def _step():
        # mask rows past the group's token count inside its last live
        # tile: garbage padding rows must not pollute the weight grad
        live = (t * bm + lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
                < rowcnt[e])
        # literal pinned f32: a bare 0.0 lowers as weak f64 under the
        # outer x64 jit and the cond-branch func verifier rejects it
        xm = jnp.where(live, x_ref[:].astype(jnp.float32),
                       jnp.float32(0.0))
        o_ref[:] += lax.dot_general(
            xm, dy_ref[:].astype(jnp.float32),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


@i32_trace
def _dw_call(x, dy, toffs, tcnt, counts, bm, bk, bn):
    t_rows, k = x.shape
    _, n = dy.shape
    e = counts.shape[0]
    mt = t_rows // bm
    kt = k // bk
    nt = n // bn

    def row(ei, ti, toffs, tcnt):
        return toffs[ei] + jnp.minimum(ti, jnp.maximum(tcnt[ei] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(e, kt, nt, mt),          # t innermost: o_ref accumulates
        in_specs=[
            pl.BlockSpec((bm, bk),
                         lambda ei, ki, ni, ti, toffs, tcnt, rc:
                         (row(ei, ti, toffs, tcnt), ki)),
            pl.BlockSpec((bm, bn),
                         lambda ei, ki, ni, ti, toffs, tcnt, rc:
                         (row(ei, ti, toffs, tcnt), ni)),
        ],
        out_specs=pl.BlockSpec((None, bk, bn),
                               lambda ei, ki, ni, ti, toffs, tcnt, rc:
                               (ei, ki, ni)),
    )
    return pl.pallas_call(
        functools.partial(_dw_kernel, bm=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, k, n), jnp.float32),
        interpret=_interpret(),
    )(toffs, tcnt, counts, x, dy)


# -- XLA reference (CPU/benchmark path; numerically the same contract) -------
#
# The reference exploits the SAME structural fact as the kernel: tile
# alignment means every bm-row tile belongs to exactly one expert, so
# the whole grouped matmul is ONE batched GEMM over tiles with a
# per-tile weight gather ([MT, K, N] — tiles, not rows, so the gather
# is tiny). A per-row formulation (einsum 'tk,tkn->tn') degenerates to
# matvecs and loses to the capacity einsum on CPU.

def _row_experts(offsets, counts, t_rows, num_expert):
    """Buffer row -> (expert id, valid) from the aligned group layout."""
    rows = jnp.arange(t_rows, dtype=jnp.int32)
    ge = rows[:, None] >= offsets[None, :]
    exp = jnp.sum(ge.astype(jnp.int32), axis=1, dtype=jnp.int32) - 1
    exp = jnp.clip(exp, 0, num_expert - 1)
    valid = rows < offsets[exp] + counts[exp]
    return exp, valid


def _tile_experts(offsets, t_rows, bm, num_expert):
    """Tile index -> expert id (alignment guarantees uniqueness)."""
    toffs = offsets // jnp.int32(bm)
    tiles = jnp.arange(t_rows // bm, dtype=jnp.int32)
    ge = tiles[:, None] >= toffs[None, :]
    exp = jnp.sum(ge.astype(jnp.int32), axis=1, dtype=jnp.int32) - 1
    return jnp.clip(exp, 0, num_expert - 1)


def _ref_fwd(x, w, b, offsets, counts, bm, out_dtype, wg=None):
    t_rows, k = x.shape
    texp = _tile_experts(offsets, t_rows, bm, w.shape[0])
    if wg is None:
        wg = w[texp]
    out = jnp.einsum("mbk,mkn->mbn", x.reshape(-1, bm, k), wg,
                     preferred_element_type=jnp.float32)
    if b is not None:
        out = out + b[texp][:, None, :].astype(jnp.float32)
    return out.reshape(t_rows, -1).astype(out_dtype)


def _ref_dx(dy, wg, bm):
    """dx tiles = dy tiles @ wg^T, contracted directly against the
    UNTRANSPOSED per-tile weights GATHERED ONCE in the forward (the
    residual wg): re-gathering w[texp] — or transposing w for a
    _ref_fwd(dy, w^T) call — costs an [MT, K, N] materialization per
    backward, which at bench shapes is the reference's dominant HBM
    traffic."""
    t_rows, n = dy.shape
    return jnp.einsum("mbn,mkn->mbk", dy.reshape(-1, bm, n), wg,
                      preferred_element_type=jnp.float32) \
        .reshape(t_rows, -1)


def _ref_dw(x, dy, offsets, counts, bm, num_expert):
    t_rows, k = x.shape
    _, valid = _row_experts(offsets, counts, t_rows, num_expert)
    texp = _tile_experts(offsets, t_rows, bm, num_expert)
    xm = jnp.where(valid[:, None], x.astype(jnp.float32),
                   jnp.float32(0.0))
    dwt = jnp.einsum("mbk,mbn->mkn", xm.reshape(-1, bm, k),
                     dy.astype(jnp.float32).reshape(-1, bm, dy.shape[1]),
                     preferred_element_type=jnp.float32)
    # reduce tiles into experts with a tile-level one-hot GEMM: an
    # [MT, E] contraction costs MT*E*K*N fma, where .at[texp].add is a
    # serialized scatter (~2x slower on XLA CPU) and a row-level
    # one-hot ('te,tk,tn->ekn') pays the full E* flop blowup
    oh = (texp[:, None]
          == jnp.arange(num_expert, dtype=jnp.int32)[None, :])
    return jnp.einsum("me,mkn->ekn", oh.astype(jnp.float32), dwt,
                      preferred_element_type=jnp.float32)


def _use_kernel(impl):
    if impl == "kernel":
        return True
    if impl == "reference":
        return False
    return jax.default_backend() == "tpu"


def _gmm_raw(x, w, b, offsets, counts, bm, bn, impl):
    t_rows, k = x.shape
    e, k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert t_rows % bm == 0, \
        f"token buffer rows {t_rows} must be a multiple of bm={bm}"
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    offsets = offsets.astype(jnp.int32)
    counts = counts.astype(jnp.int32)
    if not _use_kernel(impl):
        return _ref_fwd(x, w, b, offsets, counts, bm, out_dtype)
    toffs = offsets // jnp.int32(bm)
    tcnt = -(-counts // jnp.int32(bm))
    bn_eff = _pick_tile(n, bn)
    return _fwd_call(x, w, b, toffs, tcnt, bm, bn_eff, out_dtype)


@functools.lru_cache(maxsize=None)
def _gmm_vjp(bm, bn, impl, b_dtype):
    """One custom_vjp per (tile config, impl, bias dtype — None for no
    bias): stable primitives across traces (the grad_buckets._bucket_tag
    pattern). pallas_call has no transpose rule, so the kernel path
    NEEDS the explicit VJP; the reference path uses the identical rules
    so grads cannot drift between impls. The bias dtype rides the cache
    key so bwd can cast db back to it — custom_vjp cotangents must match
    the primal dtype (bf16 biases got f32 grads otherwise)."""
    has_bias = b_dtype is not None

    @jax.custom_vjp
    def gmm(x, w, b, offsets, counts):
        return _gmm_raw(x, w, b, offsets, counts, bm, bn, impl)

    def fwd(x, w, b, offsets, counts):
        if _use_kernel(impl):
            out = _gmm_raw(x, w, b, offsets, counts, bm, bn, impl)
            return out, (x, w, None, offsets, counts)
        # reference path: gather the per-tile weights ONCE and carry
        # them as a residual — _ref_dx contracts against wg directly,
        # and a second w[texp] gather per backward would be the
        # reference's dominant HBM traffic at bench shapes
        off32 = offsets.astype(jnp.int32)
        cnt32 = counts.astype(jnp.int32)
        out_dtype = jnp.result_type(x.dtype, w.dtype)
        wg = w[_tile_experts(off32, x.shape[0], bm, w.shape[0])]
        out = _ref_fwd(x, w, b, off32, cnt32, bm, out_dtype, wg=wg)
        return out, (x, w, wg, offsets, counts)

    def bwd(res, dy):
        x, w, wg, offsets, counts = res
        offsets = offsets.astype(jnp.int32)
        counts = counts.astype(jnp.int32)
        e, k, n = w.shape
        if _use_kernel(impl):
            # dx: the SAME grouped kernel against w^T (dy stays grouped)
            dx = _gmm_raw(dy, jnp.swapaxes(w, 1, 2), None, offsets,
                          counts, bm, bn, impl).astype(x.dtype)
            toffs = offsets // jnp.int32(bm)
            tcnt = -(-counts // jnp.int32(bm))
            bk = _pick_tile(k, bn)
            bn_eff = _pick_tile(n, bn)
            dw = _dw_call(x, dy, toffs, tcnt, counts, bm, bk, bn_eff)
        else:
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

    gmm.defvjp(fwd, bwd)
    return gmm


def grouped_matmul(x, w, b=None, *, group_offsets, group_counts,
                   bm=DEFAULT_BM, bn=128, impl="auto"):
    """Per-expert matmul over expert-sorted tokens: out[r] = x[r] @
    w[e(r)] (+ b[e(r)]) where e(r) is the group row r belongs to.

    x [T, K] with each group at tile-aligned `group_offsets[e]` (a
    multiple of bm; `grouped_metadata` builds the layout), w [E, K, N],
    b [E, N] or None, group_counts [E] actual tokens per group. T must
    be a multiple of bm. Rows between groups (padding) produce
    unspecified output values and never contribute to gradients.

    impl: "auto" (kernel on TPU, XLA reference elsewhere), "kernel"
    (Pallas, interpret-mode off-TPU — what the tier-1 tests force), or
    "reference". Differentiable via custom_vjp on either impl; grads
    accumulate in f32 and cast back (activation dtype preserved).
    """
    if b is not None and b.ndim == 3:        # [E, 1, N] layer bias form
        b = b.reshape(b.shape[0], b.shape[2])
    fn = _gmm_vjp(int(bm), int(bn), str(impl),
                  None if b is None else str(b.dtype))
    return fn(x, w, b, group_offsets, group_counts)


# -- rows sorted by group with no padding (serving, and training since PR 35) ---

def _sorted_tile(n, cap):
    """Largest multiple of 128 that divides n and is <= cap; n itself
    when it has none (a toy width)."""
    for c in range(min(cap, n) // 128 * 128, 0, -128):
        if n % c == 0:
            return c
    return n


def _sorted_reference(x, w, group_sizes):
    """out[r] = x[r] @ w[group of r]; rows past the groups give 0."""
    ends = jnp.cumsum(group_sizes, dtype=jnp.int32)
    rows = jnp.arange(x.shape[0], dtype=jnp.int32)
    group = jnp.sum(rows[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    inside = group < w.shape[0]
    wg = w[jnp.minimum(group, w.shape[0] - 1)]
    out = jnp.einsum("mk,mkn->mn", x, wg,
                     preferred_element_type=jnp.float32)
    return jnp.where(inside[:, None], out, 0.0)


def _megablox(name):
    """JAX's grouped kernel `name` ("gmm", "tgmm") as a plain function.
    The package's attribute of that name is its custom_vjp or jit
    wrapper; the module `gmm` holds the kernels themselves, and the
    plain function under JAX's own jit wrapper lets the kernel's
    instruction carry the caller's `jax.named_scope`."""
    import importlib
    fn = getattr(importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm"), name)
    return getattr(fn, "__wrapped__", fn)


def _megablox_call(name, lhs, rhs, group_sizes, out_dtype, tiling, **kw):
    """Traced with x64 off: the kernel's tile counts and index maps must
    stay 32-bit (`_x64.i32_trace`). And at the default matmul precision:
    the package sets "highest" for the whole process, which Mosaic
    refuses for a kernel's bf16 operands ("Bad lhs type")."""
    with jax.default_matmul_precision("default"):
        return i32_trace(_megablox(name))(
            lhs, rhs, group_sizes.astype(jnp.int32),
            preferred_element_type=out_dtype, tiling=tiling,
            interpret=_interpret(), **kw)


def _pad_rows(x, tm):
    pad = (-x.shape[0]) % tm
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _sorted_kernel_fwd(x, w, group_sizes, tm, out_dtype):
    m, k = x.shape
    n = w.shape[2]
    out = _megablox_call(
        "gmm", _pad_rows(x, tm), w, group_sizes, out_dtype,
        (tm, _sorted_tile(k, 1024), _sorted_tile(n, 1024)))
    return out if out.shape[0] == m else out[:m]


def _sorted_kernel_bwd(tm, res, dy):
    """dx = dy @ w[group]^T through the same grouped kernel with the
    weights read transposed; dw[e] = x[rows of e]^T @ dy[rows of e]
    through the kernel that accumulates over a group's row tiles
    (`tgmm`: an empty group's block is written as zeros). Rows past the
    groups' sum are visited by neither: their dx is selected to zero."""
    x, w, group_sizes = res
    m, k = x.shape
    n = w.shape[2]
    dy = _pad_rows(dy.astype(x.dtype), tm)
    dx = _megablox_call(
        "gmm", dy, w, group_sizes, x.dtype,
        (tm, _sorted_tile(n, 1024), _sorted_tile(k, 1024)),
        transpose_rhs=True)[:m]
    inside = jnp.arange(m, dtype=jnp.int32) < jnp.sum(
        group_sizes, dtype=jnp.int32)
    dx = jnp.where(inside[:, None], dx, jnp.zeros((), dx.dtype))
    dw = _megablox_call(
        "tgmm", _pad_rows(x, tm).swapaxes(0, 1), dy, group_sizes, w.dtype,
        (tm, _sorted_tile(k, 1024), _sorted_tile(n, 1024)))
    return dx, dw, None


@functools.lru_cache(maxsize=None)
def _sorted_kernel(tm, out_dtype):
    fn = jax.custom_vjp(functools.partial(
        _sorted_kernel_fwd, tm=tm, out_dtype=out_dtype))
    fn.defvjp(lambda x, w, sizes: (
        _sorted_kernel_fwd(x, w, sizes, tm, out_dtype), (x, w, sizes)),
        functools.partial(_sorted_kernel_bwd, tm))
    return fn


def grouped_matmul_sorted(x, w, group_sizes, *, impl="auto", row_tile=128,
                          out_dtype=jnp.float32):
    """Per-group matmul over rows SORTED by group with no padding
    between groups: rows `sum(sizes[:e]) .. sum(sizes[:e+1])` of x [M, K]
    meet w[e] of w [E, K, N]; `group_sizes` [E] int32 may sum to less
    than M, and rows past that sum are not computed (their output is
    unspecified: select them away, never scale them). Returns [M, N] in
    `out_dtype` (float32 accumulated whatever it is). The layout of an
    expert layer that brings each expert exactly its rows, where the
    tile-aligned layout of `grouped_matmul` (a grid over every expert's
    worst-case tiles) would spend the step skipping.

    Differentiable in x and w: dx through the same kernel against the
    transposed weights, dw through the kernel that accumulates over a
    group's rows. Rows past the groups' sum get zero cotangent into dx
    and take no part in dw; an expert without a row gets a zero dw. The
    caller selects the unwritten rows of the OUTPUT away before anything
    multiplies them: a backward pass would otherwise multiply their zero
    cotangent by whatever the memory held (0 x NaN).

    impl "auto": on a TPU the grouped kernels that ship with JAX
    (`jax.experimental.pallas.ops.tpu.megablox`: `gmm`, a grid over the
    row tiles that groups touch, the group's weights found through
    scalar-prefetched ids, an expert's weights streamed once per
    visit; `tgmm` for dw), elsewhere a gathered-weight XLA product.
    "kernel" forces the kernels (interpreted off the TPU), "reference"
    the XLA product. `row_tile`: rows of a kernel tile; 128 suits a
    serving step's handful of rows an expert, a training step's
    thousands want more rows against each weight tile it streams.
    """
    if impl == "reference" or (impl == "auto" and _interpret()):
        return _sorted_reference(x, w, group_sizes).astype(out_dtype)
    return _sorted_kernel(int(row_tile), jnp.dtype(out_dtype))(
        x, w, group_sizes)


# -- host-side telemetry -----------------------------------------------------

def record_moe_dispatch(counts, *, bm, n_routes, n_dropped=0,
                        dispatch_bytes=0, n_tiles_col=1, gemms=1,
                        layers=1):
    """Host-side counters for one MoE dispatch (concrete values only —
    the layer calls this on the eager path, benchmarks call it with
    routing stats probed outside the jitted step, mirroring
    ragged_paged_attention.record_ragged_step):

      paddle_tpu_moe_tokens_routed_total    routes carried to experts
      paddle_tpu_moe_tokens_dropped_total   routes lost to capacity (0
                                            by construction in grouped
                                            dispatch mode)
      paddle_tpu_moe_group_gemm_tiles_total grouped-GEMM tiles computed
      paddle_tpu_moe_tiles_skipped_total    grid steps the ragged
                                            early-exit skipped
      paddle_tpu_moe_dispatch_bytes_total   token bytes THIS rank moves
                                            through the dispatch seam
                                            (buffer or wire), both
                                            directions summed — one
                                            convention across dispatch
                                            modes so lanes compare

    counts: array-like [E] tokens per expert; n_tiles_col = output
    column tiles per GEMM; gemms = grouped matmuls per dispatch (2 for
    gate->up->down MLP fwd; backward doubles it on the trained path).
    """
    from ... import observability as obs
    if not obs.enabled():
        return
    c = np.asarray(counts, np.int64)
    bm = int(bm)
    live = int((-(-c // bm)).sum()) * int(n_tiles_col) * int(gemms)
    total_rows = aligned_group_size(int(n_routes), len(c), bm) // bm
    grid = total_rows * len(c) * int(n_tiles_col) * int(gemms)
    reg = obs.registry()
    reg.counter("paddle_tpu_moe_tokens_routed_total",
                "MoE routes carried to experts").inc(
                    int(layers) * int(n_routes))
    reg.counter("paddle_tpu_moe_tokens_dropped_total",
                "MoE routes dropped at capacity").inc(
                    int(layers) * int(n_dropped))
    reg.counter("paddle_tpu_moe_group_gemm_tiles_total",
                "Grouped-GEMM tiles computed").inc(int(layers) * live)
    reg.counter("paddle_tpu_moe_tiles_skipped_total",
                "Grouped-GEMM grid steps skipped by the ragged "
                "early-exit").inc(int(layers) * max(grid - live, 0))
    reg.counter("paddle_tpu_moe_dispatch_bytes_total",
                "Per-rank MoE dispatch bytes, both directions "
                "summed").inc(
                    int(layers) * int(dispatch_bytes))
