"""The main path's Pallas kernels, compiled at real widths for a TPU v5e that
is described, not attached (section 2 of the on-chip-measurement guide).

This is the only file that describes the chip: only one process may load
the TPU's library, so the description happens inside a fixture, in the
worker that is given this file, and nowhere at import time. A compile that
passes is not a chip run; it says the chip's compiler accepts the kernel
(tiling, fast memory) and that the kernel is in the program.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def for_the_chip(monkeypatch):
    """Kernels lower for Mosaic, not for the interpreter (they ask
    jax.default_backend(), which is the CPU here), and nothing these
    compiles make goes to or comes from the persistent cache: an entry
    written for a described device cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.kernels.pallas import (flash_attention,
                                           fused_elementwise,
                                           ragged_paged_attention, rms_norm)
    for mod in (flash_attention, fused_elementwise, ragged_paged_attention,
                rms_norm):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("rows,width", [(12288, 4096), (4096, 8192)])
def test_rms_norm_fwd_bwd(one_chip, for_the_chip, rows, width):
    """[12288, 4096] is bench.py's batch 6 x seq 2048 at 7B width; the
    backward's three row blocks must fit the chip's scoped VMEM."""
    from paddle_tpu.kernels.pallas.rms_norm import rms_norm_jax

    def fwd_bwd(x, w):
        return jax.grad(lambda x, w: rms_norm_jax(x, w).astype(
            jnp.float32).sum(), argnums=(0, 1))(x, w)

    text = _compiled_text(fwd_bwd, one_chip, ((rows, width), BF16),
                          ((width,), BF16))
    assert text.count("tpu_custom_call") >= 2       # forward and backward


def test_flash_attention_fwd_bwd(one_chip, for_the_chip):
    """[batch 6 x 32 heads, seq 2048, head_dim 128]: the train step's."""
    from paddle_tpu.kernels.pallas.flash_attention import _flash_bhsd

    def fwd_bwd(q, k, v):
        return jax.grad(lambda q, k, v: _flash_bhsd(
            q, k, v, True, 128 ** -0.5).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    qkv = ((192, 2048, 128), BF16)
    text = _compiled_text(fwd_bwd, one_chip, qkv, qkv, qkv)
    assert text.count("tpu_custom_call") >= 3       # fwd, dq, dk/dv


@pytest.mark.parametrize("nkv", [32, 8])
def test_ragged_paged_attention(one_chip, for_the_chip, nkv):
    """16 slots x 32 heads x 128 against a paged pool, MHA and GQA."""
    from paddle_tpu.kernels.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    slots, blocks, block_size, blocks_per_seq = 16, 257, 64, 32
    pool = ((blocks, block_size, nkv, 128), BF16)
    text = _compiled_text(
        ragged_paged_attention, one_chip, ((slots, 32, 128), BF16), pool,
        pool, ((slots, blocks_per_seq), jnp.int32), ((slots,), jnp.int32))
    assert "tpu_custom_call" in text


def test_rope(one_chip, for_the_chip):
    from paddle_tpu.kernels.pallas.fused_elementwise import rope_pallas
    text = _compiled_text(rope_pallas, one_chip,
                          ((6, 2048, 32, 128), BF16),
                          ((2048, 128), jnp.float32),
                          ((2048, 128), jnp.float32))
    assert "tpu_custom_call" in text
