"""The decode step's state-update kernel (`kernels/pallas/ssm_update.py`,
interpreted here) against the XLA form of the step it replaced, which
lives on below as the oracle, and against the reference's sequential
recurrence: a pool of three blocks stepped at block 1, at toy widths and
at one tile-aligned shape; what a slot that is not active and the other
blocks keep; the head-blocked grid; `ssm_step`'s two forms of state; a
served request's tokens against the parent commit's.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import nemotron_h as ref
from paddle_tpu.kernels.pallas import ssm_update as kernel
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.models.paged_decode import PagedDecoder

F32 = jnp.float32
# (heads, hd, N, groups): `nemotron_h_tiny`'s, and whole (8, 128) tiles
SHAPES = {"tiny": (8, 8, 16, 2), "aligned": (16, 64, 128, 2)}
SLOTS, STEPS, BLOCKS, M = 5, 6, 3, 1
ACTIVE = {"all": None,
          "mixed": [True, False, True, True, False],
          "leading": [False, False, True, False, True],
          "one": [False, False, False, True, False],
          "none": [False] * SLOTS}


def xla_step(state, x, b, c, dt, a, d):
    """The step as XLA ran it before the kernel: state [S, heads, hd, N]
    float32; x [S, heads, hd]; b, c [S, G, N]; dt [S, heads]; a, d
    [heads]. Returns (y [S, heads, hd], S_t)."""
    s, heads, hd, n = state.shape
    g = b.shape[1]
    r = heads // g
    st = state.reshape(s, g, r, hd, n)
    xf = x.astype(F32).reshape(s, g, r, hd)
    decay = jnp.exp(dt * a[None, :]).reshape(s, g, r, 1, 1)
    dtx = dt.reshape(s, g, r, 1) * xf
    new = st * decay + dtx[..., None] * b.astype(F32)[:, :, None, None, :]
    y = jnp.sum(new * c.astype(F32)[:, :, None, None, :], axis=-1) \
        + d.astype(F32).reshape(1, g, r, 1) * xf
    return y.reshape(s, heads, hd), new.reshape(s, heads, hd, n)


def _inputs(shape, seed=3):
    """STEPS steps of inputs for SLOTS slots, and a pool of noise."""
    heads, hd, n, g = SHAPES[shape]
    rng = np.random.default_rng(seed)
    draw = lambda *s: jnp.asarray(rng.normal(size=s), F32)
    return (draw(BLOCKS, SLOTS, heads, hd, n),
            draw(STEPS, SLOTS, heads, hd), draw(STEPS, SLOTS, g, n),
            draw(STEPS, SLOTS, g, n),
            jnp.asarray(rng.uniform(0.001, 0.3, (STEPS, SLOTS, heads)), F32),
            -jnp.asarray(rng.uniform(1.0, 16.0, heads), F32),
            draw(heads))


def _mask(active):
    return np.ones(SLOTS, bool) if ACTIVE[active] is None \
        else np.asarray(ACTIVE[active])


def _run(shape, active, steps=STEPS):
    """The kernel and the oracle over `steps` steps from the same pool.
    Returns (ys, pool) of each, the oracle's state selected by hand."""
    pool, x, b, c, dt, a, d = _inputs(shape)
    on = _mask(active)
    act = None if ACTIVE[active] is None else jnp.asarray(on)
    # a function of its own a run: no trace of another run's tiling
    step = jax.jit(lambda *args: nh.ssm_step(*args[:7], M, *args[7:]))
    got_pool, want = pool, pool[M]
    got_y, want_y = [], []
    for t in range(steps):
        y, got_pool = step(got_pool, x[t], b[t], c[t], dt[t], a, d, act)
        got_y.append(y)
        y, new = xla_step(want, x[t], b[t], c[t], dt[t], a, d)
        want = jnp.where(on[:, None, None, None], new, want)
        want_y.append(y)
    return (np.asarray(jnp.stack(got_y)), np.asarray(got_pool),
            np.asarray(jnp.stack(want_y)), np.asarray(want), pool)


def _rounding(got, want, ulps=32):
    """Float32 rounding of sums taken in another order."""
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= ulps * 2.0**-24 * scale


@pytest.mark.parametrize("active", ["all", "mixed"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_is_the_xla_form_of_the_step(shape, active):
    got_y, got_pool, want_y, want, _ = _run(shape, active)
    on = _mask(active)
    _rounding(got_y[:, on], want_y[:, on])
    _rounding(got_pool[M], want)


@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_is_the_sequential_recurrence(shape):
    """Six steps from a zero state, every slot a sequence of its own."""
    pool, x, b, c, dt, a, d = _inputs(shape)
    state = jnp.zeros_like(pool)
    ys = []
    for t in range(STEPS):
        y, state = nh.ssm_step(state, x[t], b[t], c[t], dt[t], a, d, M)
        ys.append(y)
    ys = np.asarray(jnp.stack(ys))
    for s in range(SLOTS):
        y_ref, last = ref.ssm_sequential(x[:, s], b[:, s], c[:, s],
                                         dt[:, s], a, d)
        _rounding(ys[:, s], np.asarray(y_ref))
        _rounding(np.asarray(state[M, s]), np.asarray(last))


@pytest.mark.parametrize("active", ["mixed", "leading", "one", "none"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_inactive_slots_and_other_blocks_keep_their_bits(shape, active):
    _, got_pool, _, want, pool = _run(shape, active, steps=2)
    pool, off = np.asarray(pool), ~_mask(active)
    assert (got_pool[M][off] == pool[M][off]).all()
    assert (got_pool[0] == pool[0]).all() and (got_pool[2] == pool[2]).all()
    _rounding(got_pool[M], want)


@pytest.mark.parametrize("active", ["all", "leading", "none"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_head_blocked_grid_is_the_same_step(shape, active, monkeypatch):
    """A tile of one group's heads a grid step (two head blocks), as a
    configuration whose heads do not fit one tile gets."""
    heads, hd, n, g = SHAPES[shape]
    whole = _run(shape, active, steps=2)
    monkeypatch.setattr(kernel, "TILE_BYTES", heads // g * hd * n * 4)
    assert kernel._groups_per_step(g, heads // g * hd * n * 4) == 1
    blocked = _run(shape, active, steps=2)
    # the CPU contracts `a * b + c * d` as it sees fit a loop: a last bit
    _rounding(blocked[1][M], whole[1][M], ulps=4)
    np.testing.assert_array_equal(blocked[1][[0, 2]], whole[1][[0, 2]])
    _rounding(blocked[0], whole[0], ulps=4)


def test_a_slot_that_is_not_active_gives_d_x():
    pool, x, b, c, dt, a, d = _inputs("tiny")
    on = _mask("mixed")
    y, _ = nh.ssm_step(pool, x[0], b[0], c[0], dt[0], a, d, M,
                       jnp.asarray(on))
    np.testing.assert_array_equal(
        np.asarray(y)[~on], np.asarray(d[None, :, None] * x[0])[~on])


@pytest.mark.parametrize("shape", list(SHAPES))
def test_one_blocks_state_is_a_pool_of_one(shape):
    """`ssm_step` hands the state back in the form it came in."""
    pool, x, b, c, dt, a, d = _inputs(shape)
    y4, s4 = nh.ssm_step(pool[M], x[0], b[0], c[0], dt[0], a, d)
    y5, s5 = nh.ssm_step(pool, x[0], b[0], c[0], dt[0], a, d, M)
    assert s4.shape == pool.shape[1:] and s5.shape == pool.shape
    np.testing.assert_array_equal(np.asarray(y4), np.asarray(y5))
    np.testing.assert_array_equal(np.asarray(s4), np.asarray(s5[M]))


# what `PagedDecoder(nemotron_h_tiny).serve` gave at the parent commit
# (f471b2b, the two-fusion XLA step): four requests over three slots
PARENT_TOKENS = {
    0: [69, 175, 8, 70, 148, 102, 35, 162, 207, 137, 125, 131],
    1: [126, 172, 47, 110, 139],
    2: [187, 113, 102, 227, 18, 119, 11, 139, 230, 246, 195, 65, 70, 148,
        102, 157, 127, 83, 16, 90, 242],
    3: [202, 69, 81, 71, 142, 90, 139, 125, 49],
}


@pytest.fixture(scope="module")
def served():
    model = nh.NemotronHForCausalLM(nh.nemotron_h_tiny())
    dec = PagedDecoder(model, max_len=64, block_size=8, num_blocks=33,
                       max_slots=3)
    rng = np.random.default_rng(38)
    reqs = [(rid, rng.integers(0, 256, n).tolist(), budget)
            for rid, (n, budget) in enumerate(
                [(7, 12), (19, 5), (4, 21), (11, 9)])]
    return dec.serve(reqs, max_new_tokens=25, chunk=4)


@pytest.mark.parametrize("rid", sorted(PARENT_TOKENS))
def test_served_request_gives_the_parents_tokens(served, rid):
    assert served[rid] == PARENT_TOKENS[rid]
