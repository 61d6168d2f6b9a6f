"""Pallas TPU kernel for one decode step of a Mamba-2 block's recurrence
over a pool of per-slot states.

`S_t = exp(dt A) S + dt x (x) B` and `y = S_t C` for every slot of one
block of the pool `[blocks, slots, heads, hd, N]` float32. XLA's form of
the step writes `S_t` into the pool in place and then reads it a second
time to form `S_t C` (it will not fuse a reduction into an in-place
write); here a slot's tile is read once, updated, contracted with `C`
while it is in VMEM and written back to where it came from.

Mechanics:

- the launch takes the WHOLE pool and returns it under
  `input_output_aliases`, the block's index a prefetched scalar that the
  index map reads: a kernel over the slice `pool[m]` would make XLA copy
  the block out and back.
- grid `(head blocks, slots)`, a tile of `hb` heads `[hb, hd, N]` a
  step; `hb` is whole groups (B and C are a group's) and the largest
  that keeps a tile under `TILE_BYTES`: all heads at the published
  widths, so one step a slot.
- a slot that is not active costs no DMA: step r of the slot axis works
  on the r-th ACTIVE slot (found through a scalar-prefetched list), so
  that the active tiles follow one another and each is fetched while
  the one before is computed; the steps behind the last one name its
  tile again, which the pipeline neither fetches nor writes twice, and
  do nothing. An inactive slot's `y` is zeroed outside.
- the small per-row operands come transposed by XLA (a few MB a step
  against the pool's GB): `dt x` as `[hd, lanes]`, head h in lane h, so
  that a head's values are a column that broadcasts along the lanes
  against the row `B_g`; `y` leaves the same way, each head's row sums
  selected into its lane.
- the sum over N rides the MXU: a head's new tile `[hd, N]` times the
  transpose of `[lanes, N]` (every row the C of the head's group) has
  the head's `S_t C` in every column, at `HIGHEST` (six bf16 passes:
  float32 accurate). A lane reduction a vreg on top of the column
  broadcasts made the kernel wait for its cross-lane unit: 1.95 ms a
  launch at `[5, 128, 128, 64, 128]` against 1.63 for a kernel that
  only copies the tiles; this form 1.65.
- the heads of a group are unrolled and the groups are a loop, the
  launches of a step's blocks one jitted function: the body's way from
  Python to Mosaic is paid at every start of a process, compile cache
  or not (unrolled over all heads and traced a block, it added 4 s to
  the serving cell's 43 s of set-up).
- float32 throughout: state, decay, the outer product on the VPU, the
  product with C on the MXU.

On other backends than the TPU the kernel runs interpreted.
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
from .flash_attention import _NT

__all__ = ["ssm_update", "SCOPE"]

# what a device trace calls the launch: it is jitted on its own
# (`_launch`), and a jitted function names the instructions inside it,
# whatever scope its caller has open
SCOPE = "decode.ssm_update"

F32 = jnp.float32
# a tile in and out, each double-buffered: four of these in VMEM
TILE_BYTES = 4 * 2**20


def _interpret():
    return jax.default_backend() != "tpu"


def _groups_per_step(groups, group_bytes):
    """The most whole groups (a divisor of `groups`) whose heads' state
    fits a tile; one where a single group is over."""
    return max([g for g in range(1, groups + 1)
                if groups % g == 0 and g * group_bytes <= TILE_BYTES],
               default=1)


def _kernel(slot_ref, at_ref, s_ref, xt_ref, dec_ref, b_ref, c_ref, y_ref,
            o_ref):
    """One (head block, active slot). slot_ref (SMEM) [slots]: the slot
    each step works on; at_ref (SMEM) [2]: the pool's block, how many
    slots are active. s_ref, o_ref [hb, hd, N]: the tile, read and
    written. xt_ref [hd, lanes]: dt x, head h in lane h; dec_ref [1,
    lanes]: exp(dt A); b_ref, c_ref [groups here, N]; y_ref [hd,
    lanes]."""
    r = pl.program_id(1)
    hb, hd, n = s_ref.shape
    lanes = xt_ref.shape[1]
    groups = b_ref.shape[0]
    rep = hb // groups

    @pl.when(r < at_ref[1])
    def _update():
        lane = lax.broadcasted_iota(jnp.int32, (hd, lanes), 1)

        def group(g, y):
            """The heads of one group, unrolled. A lane index has to be
            static, so the group's columns of `dt x` and of the decay
            are rotated to lanes 0 .. rep - 1 and its `y` back."""
            first = g * np.int32(rep)
            back = (np.int32(lanes) - first) % np.int32(lanes)
            xg = pltpu.roll(xt_ref[...], back, 1)
            dg = pltpu.roll(jnp.broadcast_to(dec_ref[...], (hd, lanes)),
                            back, 1)
            b = b_ref[pl.ds(g, 1), :]
            # every row the group's C: a head's tile times its transpose
            # sums the tile's rows over N, on the MXU
            c = jnp.broadcast_to(c_ref[pl.ds(g, 1), :], (lanes, n))
            yg = jnp.zeros((hd, lanes), F32)
            for i in range(rep):
                new = s_ref[first + np.int32(i)] * dg[:, i:i + 1] \
                    + xg[:, i:i + 1] * b
                o_ref[first + np.int32(i)] = new
                sums = lax.dot_general(new, c, (_NT, ((), ())),
                                       precision=lax.Precision.HIGHEST,
                                       preferred_element_type=F32)
                yg = jnp.where(lane == np.int32(i), sums, yg)
            return y + pltpu.roll(yg, first, 1)

        y_ref[...] = lax.fori_loop(np.int32(0), np.int32(groups), group,
                                   jnp.zeros((hd, lanes), F32))

    # no slot is active: every step names slot 0's tile and none wrote it
    @pl.when(jnp.logical_and(r == 0, at_ref[1] == 0))
    def _keep():
        o_ref[...] = s_ref[...]


@i32_trace
@functools.partial(jax.jit, static_argnames="interpret")
def _launch(pool, xt, dec, b, c, slot, at, interpret):
    """Jitted on its own, the block's index an operand: the launches of
    a step's Mamba blocks are then one traced and lowered function,
    called once a block."""
    _, slots, heads, hd, n = pool.shape
    blocks, gb = b.shape[1], b.shape[2]
    hb, lanes = heads // blocks, xt.shape[3]
    tile = pl.BlockSpec((None, None, hb, hd, n),
                        lambda j, r, slot, at: (at[0], slot[r], j, 0, 0))

    def row(*shape):
        return pl.BlockSpec((None, None) + shape,
                            lambda j, r, slot, at: (slot[r], j, 0, 0))

    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            _kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(blocks, slots),
                in_specs=[tile, row(hd, lanes), row(1, lanes), row(gb, n),
                          row(gb, n)],
                out_specs=[row(hd, lanes), tile]),
            out_shape=[jax.ShapeDtypeStruct((slots, blocks, hd, lanes), F32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            input_output_aliases={2: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=int(4 * hb * hd * n * 4 + 16 * 2**20)),
            interpret=interpret,
        )(slot, at, pool, xt, dec, b, c)


def ssm_update(pool, m, x, b, c, dt, a, active=None):
    """One step of the recurrence for every slot of block `m` of `pool`
    [blocks, S, heads, hd, N] float32, in place.

    x [S, heads, hd]; b, c [S, G, N]; dt [S, heads] float32 (after
    softplus); a [heads]; `active` [S] bool or None (all). Returns
    (`S_t C` [S, heads, hd] float32, zero for a slot that is not active;
    the pool, block `m`'s active slots moved on to `S_t = exp(dt A) S +
    dt x (x) B` and nothing else touched)."""
    _, slots, heads, hd, n = pool.shape
    groups = b.shape[1]
    rep = heads // groups
    gb = _groups_per_step(groups, rep * hd * n * 4)
    blocks, hb = groups // gb, gb * rep
    # whole lane tiles: the kernel rotates these along the lanes
    pad = ((0, 0),) * 3 + ((0, -hb % 128),)
    dtx = dt.astype(F32)[..., None] * x.astype(F32)
    xt = jnp.pad(dtx.reshape(slots, blocks, hb, hd).swapaxes(2, 3), pad)
    dec = jnp.pad(jnp.exp(dt.astype(F32) * a.astype(F32)[None, :])
                  .reshape(slots, blocks, 1, hb), pad)
    act = jnp.ones(slots, bool) if active is None else active
    # step r works on the r-th active slot; the steps behind the last
    # one name its tile again
    seen = jnp.cumsum(act, dtype=jnp.int32)
    count = seen[-1]
    rank = jnp.minimum(jnp.arange(slots, dtype=jnp.int32),
                       jnp.maximum(count - 1, 0))
    slot = jnp.sum(seen[None, :] <= rank[:, None], axis=1, dtype=jnp.int32)
    y, pool = _launch(pool, xt, dec,
                      b.astype(F32).reshape(slots, blocks, gb, n),
                      c.astype(F32).reshape(slots, blocks, gb, n),
                      jnp.where(count > 0, slot, 0),
                      jnp.stack([jnp.asarray(m, jnp.int32), count]),
                      interpret=_interpret())
    y = jnp.where(act[:, None, None, None], y[..., :hb], 0.0)
    return y.swapaxes(2, 3).reshape(slots, heads, hd), pool
