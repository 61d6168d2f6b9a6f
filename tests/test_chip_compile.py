"""The main path's Pallas kernels, compiled at real widths for a TPU v5e that
is described, not attached (section 2 of the on-chip-measurement guide).

This is the only file that describes the chip: only one process may load
the TPU's library, so the description happens inside a fixture, in the
worker that is given this file, and nowhere at import time. A compile that
passes is not a chip run; it says the chip's compiler accepts the kernel
(tiling, fast memory) and that the kernel is in the program.
"""
import re

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
    from paddle_tpu.kernels.pallas import (flash_attention, flash_prefill,
                                           fused_elementwise,
                                           grouped_matmul, lightning_index,
                                           mla_decode, mla_paged_decode,
                                           mla_prefill,
                                           ragged_paged_attention, rms_norm,
                                           ssm_update)
    for mod in (flash_attention, flash_prefill, fused_elementwise,
                grouped_matmul, lightning_index, mla_decode,
                mla_paged_decode, mla_prefill, ragged_paged_attention,
                rms_norm, ssm_update):
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


@pytest.mark.parametrize("bh,seq,dtype", [
    (192, 2048, BF16),          # batch 6 x 32 heads: bench.py's train step
    (96, 4096, BF16),           # batch 3 x 32 heads: mistral_7b_train_seq4k
    (8, 16384, BF16),           # past the resident limit: the streaming kernels
    (8, 2048, jnp.float32),     # float32 operands keep their six-pass products
])
def test_flash_attention_fwd_bwd(one_chip, for_the_chip, bh, seq, dtype):
    """Mosaic takes the products as the kernels name them: bf16 operands with
    the one-pass precision (it refuses the package's process-wide `highest`
    on them), also where `p` and `ds` are contracted over their rows."""
    from paddle_tpu.kernels.pallas.flash_attention import _flash_bhsd

    def fwd_bwd(q, k, v):
        return jax.grad(lambda q, k, v: _flash_bhsd(
            q, k, v, True, 128 ** -0.5).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    qkv = ((bh, seq, 128), dtype)
    text = _compiled_text(fwd_bwd, one_chip, qkv, qkv, qkv)
    assert text.count("tpu_custom_call") >= 3       # fwd, dq, dk/dv


@pytest.mark.parametrize("slots,nkv,blocks", [
    (16, 32, 257), (16, 8, 257), (16, 2, 257),     # MHA, GQA, 2 KV heads of 16
    (32, 32, 8 * 513),      # deepseek_llm_7b_serve_backlog: 8 layers' pool
    (128, 2, 4097),         # nemotron3_super_serve_backlog
])
def test_ragged_paged_attention(one_chip, for_the_chip, slots, nkv, blocks):
    """32 query heads x 128 against a paged pool of 64-token blocks, 32
    blocks a sequence: the pools stay in HBM and the body copies the live
    blocks itself, so Mosaic has to take the DMAs, the merged (token, KV
    head) rows and the buffers' share of VMEM."""
    from paddle_tpu.kernels.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    block_size, blocks_per_seq = 64, 32
    pool = ((blocks, block_size, nkv, 128), BF16)
    text = _compiled_text(
        ragged_paged_attention, one_chip, ((slots, 32, 128), BF16), pool,
        pool, ((slots, blocks_per_seq), jnp.int32), ((slots,), jnp.int32))
    assert "tpu_custom_call" in text
    # the pools' (token, KV head) axes merged: the same bytes, no copy
    merged = rf"bf16\[{blocks},{block_size * nkv},128\]\S* "
    assert len(re.findall(merged + r"bitcast\(", text)) == 2
    assert not re.search(merged + r"copy\(", text)


@pytest.mark.parametrize("nkv", [32, 8])
def test_ragged_paged_attention_quant(one_chip, for_the_chip, nkv):
    """int8 pools with float32 row scales: the codes go to the MXU in q's
    dtype, the scales ride the score columns as a `[1, columns]` block a
    slot (Mosaic refuses a `[bs]` row of the `[blocks, bs]` scale pool)."""
    from paddle_tpu.kernels.pallas.ragged_paged_attention import (
        ragged_paged_attention_quant)
    slots, blocks = 32, 513
    pool = ((blocks, 64, nkv, 128), jnp.int8)
    scales = ((blocks, 64), jnp.float32)
    text = _compiled_text(
        ragged_paged_attention_quant, one_chip, ((slots, 32, 128), BF16),
        pool, scales, pool, scales, ((slots, 32), jnp.int32),
        ((slots,), jnp.int32))
    assert "tpu_custom_call" in text


def test_ragged_paged_attention_sharded(one_chip, for_the_chip):
    """Two shards of 16 blocks: two partials launches and the merge."""
    from paddle_tpu.kernels.pallas.ragged_paged_attention import (
        ragged_paged_attention_sharded)
    slots, blocks = 32, 513
    pool = ((blocks, 64, 32, 128), BF16)
    text = _compiled_text(
        lambda *a: ragged_paged_attention_sharded(*a, 2), one_chip,
        ((slots, 32, 128), BF16), pool, pool, ((slots, 32), jnp.int32),
        ((slots,), jnp.int32))
    assert text.count("tpu_custom_call") >= 2


def test_rope(one_chip, for_the_chip):
    from paddle_tpu.kernels.pallas.fused_elementwise import rope_pallas
    text = _compiled_text(rope_pallas, one_chip,
                          ((6, 2048, 32, 128), BF16),
                          ((2048, 128), jnp.float32),
                          ((2048, 128), jnp.float32))
    assert "tpu_custom_call" in text


# -- the hybrid (Mamba-2 + attention + LatentMoE) at its cell's widths -----------

@pytest.mark.parametrize("m,k,n", [(2816, 1024, 2688), (2816, 2688, 1024)])
def test_grouped_matmul_sorted(one_chip, for_the_chip, m, k, n):
    """A decode step's 128 rows x top-22 pairs over 128 held experts,
    both products of an expert."""
    from paddle_tpu.kernels.pallas.grouped_matmul import grouped_matmul_sorted
    text = _compiled_text(grouped_matmul_sorted, one_chip, ((m, k), BF16),
                          ((128, k, n), BF16), ((128,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("blocks,slots,heads,hd,n,groups", [
    (5, 128, 128, 64, 128, 8),      # nemotron3_super_serve_backlog's pool
    (2, 4, 8, 8, 16, 2),            # nemotron_h_tiny's
])
def test_ssm_update(one_chip, for_the_chip, blocks, slots, heads, hd, n,
                    groups):
    """One Mamba block's decode step over the whole stacked state pool:
    a 4 MB tile a slot in and out (over the default VMEM limit, which the
    launch raises), the pool aliased, so the program holds no second
    copy of a block's state."""
    from paddle_tpu.kernels.pallas.ssm_update import ssm_update
    f32 = jnp.float32
    pool = jax.ShapeDtypeStruct((blocks, slots, heads, hd, n), f32,
                                sharding=one_chip)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (
                ((slots, heads, hd), BF16), ((slots, groups, n), BF16),
                ((slots, groups, n), BF16), ((slots, heads), f32),
                ((heads,), f32), ((slots,), jnp.bool_))]
    compiled = jax.jit(
        lambda pool, *a: ssm_update(pool, blocks - 1, *a),
        donate_argnums=0).lower(pool, *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes \
        < slots * heads * hd * n * 4 / 8


@pytest.fixture(scope="module")
def hybrid(one_chip):
    """The serving engine of `nemotron3_super_120b_ep4_l11` with its
    4.65 B parameters described, not made: (decoder, pools, one_chip)."""
    from paddle_tpu.models.nemotron_h import (HybridPagedDecoder,
                                              NemotronHConfig)
    cfg = NemotronHConfig(
        vocab_size=32768, hybrid_override_pattern="MEMEMEMEM*E",
        n_routed_experts=512, experts_held=(0, 128), dtype="bfloat16",
        max_position_embeddings=262144)

    def described(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    class Described:
        config = cfg

        def param_tree(self):
            tree = {"layers": [{} for _ in cfg.hybrid_override_pattern]}
            for name, (shape, f32) in cfg.param_shapes().items():
                leaf = described(shape, jnp.float32 if f32 else BF16)
                if name.startswith("layers."):
                    _, i, key = name.split(".")
                    tree["layers"][int(i)][key] = leaf
                else:
                    tree[name] = leaf
            return tree

    dec = HybridPagedDecoder(Described(), max_len=2048, block_size=64,
                             num_blocks=4097, max_slots=128,
                             ragged_kernel=True)
    pools = tuple(described(p.shape, p.dtype)
                  for p in jax.eval_shape(dec.new_pools))
    return dec, pools, described


def _kernels_and_spare(compiled, dec):
    """The compiled program's text, and its temporaries against the
    smallest thing it must never hold twice."""
    spare = compiled.memory_analysis().temp_size_in_bytes
    cfg = dec.cfg
    one_state_layer = (dec.max_slots * cfg.mamba_inner
                       * cfg.ssm_state_size * 4)
    one_expert_stack = (cfg.experts_held[1] * cfg.moe_latent_size
                        * cfg.moe_intermediate_size * 2)
    assert spare < min(one_state_layer, one_expert_stack), \
        f"{spare} bytes of temporaries: a second copy of a layer of " \
        f"state ({one_state_layer}) or of an expert stack " \
        f"({one_expert_stack}) would fit in them"
    return compiled.as_text()


def test_hybrid_chunk_program(hybrid, for_the_chip):
    """8 decode steps for 128 slots: the grouped expert kernel, the
    ragged paged kernel and the state-update kernel (one launch a Mamba
    block, named by its scope) are in the program, its 12.3 GB of
    arguments fit the chip, and its temporaries are too small for a
    second copy of a layer of state or of an expert stack. No XLA fusion
    writes the state pool any more."""
    dec, pools, described = hybrid
    S, MB = dec.max_slots, dec.blocks_per_seq
    i32, flag = jnp.int32, jnp.bool_
    compiled = dec._paged_chunk_state_jit.lower(
        dec._params, described((S,), i32), described((S,), i32),
        described((S, MB), i32), described((S,), flag),
        described((S,), i32), described((S,), flag), *pools, 8, -1).compile()
    text = _kernels_and_spare(compiled, dec)
    assert "%moe.experts" in text and "%decode.attend" in text
    assert "%decode.ssm_update" in text
    # 5 x 2 expert products + attend + 5 state updates
    assert text.count("tpu_custom_call") >= 16
    state_pool = "f32[%d,%d,%d,%d,%d]" % pools[2].shape
    assert state_pool == "f32[5,128,128,64,128]"
    assert not [line for line in text.splitlines()
                if "dynamic-update-slice_fusion" in line
                and state_pool in line]
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2**30


def _hybrid_prefill(dec, pools, described, bucket):
    """The packed prefill program of `bucket` rows, compiled."""
    k = -(-bucket // dec.cfg.chunk_size)
    i32 = jnp.int32
    return dec._prefill_exec(bucket).lower(
        dec._params, described((bucket,), i32), described((k,), i32),
        described((k,), i32), described((k, dec.blocks_per_seq), i32),
        *pools, described((k,), i32)).compile()


def test_hybrid_prefill_bucket(hybrid, for_the_chip):
    """The 128-row prefill bucket (a pack of one): it writes one slot's
    state in place."""
    dec, pools, described = hybrid
    text = _kernels_and_spare(_hybrid_prefill(dec, pools, described, 128),
                              dec)
    assert "%moe.experts" in text


def test_hybrid_prefill_pack_of_sixteen(hybrid, for_the_chip):
    """The 2048-row bucket, up to 16 prompts a pack: with the arguments
    it fits the chip, and the 16 segments' states go into the pools
    without a second copy of a pool."""
    dec, pools, described = hybrid
    compiled = _hybrid_prefill(dec, pools, described, 2048)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2**30
    ssm = pools[2]
    assert memory.temp_size_in_bytes < ssm.size * 4


# -- the sparse-expert train step (PR 35): head size 64, the grouped backward ----

def test_flash_attention_head64_fwd_bwd(one_chip, for_the_chip):
    """32 query heads of 64 over one 8192-token sequence (K and V already
    repeated to the query heads): half a lane tile a head, forward, dQ
    and dK/dV, resident kernels (8192 x 64 is under the resident limit)."""
    from paddle_tpu.kernels.pallas.flash_attention import _flash_bhsd

    def fwd_bwd(q, k, v):
        return jax.grad(lambda q, k, v: _flash_bhsd(
            q, k, v, True, 64 ** -0.5).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    qkv = ((32, 8192, 64), BF16)
    text = _compiled_text(fwd_bwd, one_chip, qkv, qkv, qkv)
    assert text.count("tpu_custom_call") >= 3       # fwd, dq, dk/dv


@pytest.mark.parametrize("rows", [8192, 12288])
@pytest.mark.parametrize("k,n", [(2048, 1792), (1792, 2048)])
def test_grouped_matmul_sorted_fwd_bwd(one_chip, for_the_chip, rows, k, n):
    """One chip's 8 of 32 experts of width 1792 under hidden 2048, the
    sorted buffer of a step of 8192 tokens x top-4 (a quarter lands
    here: 8192 rows; the static bound: 12288), 512 rows a tile: the
    forward, dx (`gmm` with the weights read transposed) and dw (`tgmm`)
    for gate / up and for down."""
    from paddle_tpu.kernels.pallas.grouped_matmul import grouped_matmul_sorted

    def fwd_bwd(x, w, sizes):
        return jax.value_and_grad(lambda x, w: grouped_matmul_sorted(
            x, w, sizes, row_tile=512, out_dtype=BF16).astype(
                jnp.float32).sum(), argnums=(0, 1))(x, w)

    text = _compiled_text(fwd_bwd, one_chip, ((rows, k), BF16),
                          ((8, k, n), BF16), ((8,), jnp.int32))
    assert text.count("tpu_custom_call") >= 3       # forward, dx, dw


@pytest.mark.parametrize("kind,nkv,blocks,blocks_per_seq", [
    ("full", 4, 2 * 15361, 160),        # two full layers' paged pools
    ("window", 8, 5 * 385, 3),          # five window layers' rings
])
def test_ragged_paged_attention_by_layer_kind(one_chip, for_the_chip, kind,
                                              nkv, blocks, blocks_per_seq):
    """mimo_v2_flash_serve_backlog_8k's decode attention: 64 query heads,
    K rows of 192 stored in two lanes (256) and V rows of 128, pools as
    the kernel sees them (`[blocks, 64 * nkv, width]`: with 4 or 8 KV
    heads of 256 the merge out of `[64, nkv, 256]` is a copy of the pool,
    not a bitcast); a window layer with its lower bound and its sink."""
    from paddle_tpu.kernels.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    slots, i32 = 128, jnp.int32
    shapes = [((slots, 64, 256), BF16), ((blocks, 64 * nkv, 256), BF16),
              ((blocks, 64 * nkv, 128), BF16),
              ((slots, blocks_per_seq), i32), ((slots,), i32)]
    if kind == "window":
        shapes += [((slots,), i32), ((64,), jnp.float32)]
        fn = lambda q, k, v, t, n, lo, sk: ragged_paged_attention(
            q, k, v, t, n, lows=lo, sinks=sk, kv_heads=nkv)
    else:
        fn = lambda q, k, v, t, n: ragged_paged_attention(
            q, k, v, t, n, kv_heads=nkv)
    text = _compiled_text(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text
    assert not re.search(rf"bf16\[{blocks},{64 * nkv},\d+\]\S* copy\(", text)


@pytest.mark.parametrize("kind,nkv,keys", [("full", 4, 10240),
                                           ("window", 8, 128 + 1024)])
def test_flash_prefill_chunk(one_chip, for_the_chip, kind, nkv, keys):
    """The same cell's prefill: a chunk of 1,024 queries x 64 heads x 192
    against the sequence's keys gathered from the cache (a full layer) or
    the window before the chunk and the chunk's own (a window layer, with
    the band's lower bound and the sink); V rows of 128."""
    from paddle_tpu.kernels.pallas.flash_prefill import (
        flash_prefill_attention)
    shapes = [((1024, 64, 192), BF16), ((keys, nkv, 192), BF16),
              ((keys, nkv, 128), BF16), ((), jnp.int32)]
    if kind == "window":
        shapes += [((64,), jnp.float32)]
        fn = lambda q, k, v, at, sk: flash_prefill_attention(
            q, k, v, at, 0, window=128, sinks=sk)
    else:
        fn = lambda q, k, v, at: flash_prefill_attention(q, k, v, at)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


# -- the latent engine (deepseek_v32_serve_backlog_16k) ---------------------------

@pytest.fixture(scope="module")
def latent(one_chip):
    """The serving engine of `deepseek_v32_ep16_l5` with its 4.64 B
    parameters described, not made: (decoder, pools, described)."""
    from paddle_tpu.models.deepseek_v32 import (DeepseekV32Config,
                                                LatentPagedDecoder)
    cfg = DeepseekV32Config(
        num_hidden_layers=5, first_k_dense_replace=1, vocab_size=16160,
        experts_held=(0, 16), dtype="bfloat16",
        rope_scaling=dict(type="yarn", factor=40,
                          original_max_position_embeddings=4096,
                          beta_fast=32, beta_slow=1, mscale=1,
                          mscale_all_dim=1))

    def described(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    class Described:
        config = cfg

        def param_tree(self):
            tree = {"layers": [{} for _ in range(cfg.num_hidden_layers)]}
            for name, (shape, f32) in cfg.param_shapes().items():
                leaf = described(shape, jnp.float32 if f32 else BF16)
                if name.startswith("layers."):
                    _, i, key = name.split(".")
                    tree["layers"][int(i)][key] = leaf
                else:
                    tree[name] = leaf
            return tree

    dec = LatentPagedDecoder(Described(), max_len=16384, block_size=64,
                             num_blocks=9216, max_slots=48,
                             prefill_chunk=1024)
    pools = tuple(described(p.shape, p.dtype)
                  for p in jax.eval_shape(dec.new_pools))
    return dec, pools, described


def _fits_beside_its_pools(compiled, pools):
    """The program's arguments and temporaries fit the chip, and its
    temporaries are too small for a second copy of one layer's latent
    pool."""
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75 * 2**30
    one_layer = pools[0].size * 2 // pools[0].shape[0]
    assert memory.temp_size_in_bytes < one_layer
    return compiled.as_text()


def test_latent_chunk_program(latent, for_the_chip):
    """8 decode steps for 48 slots at 16,384 positions: a slot's indexer
    kernel scores its cached keys, read from the pool through the block
    table (no copy of a slot's keys, `[48, 16384, 128]` or by blocks, in
    HBM), the chosen rows' pool addresses need no gather of table
    entries and their copy no select, the chosen rows' attention is a
    kernel of its own, the grouped expert products run, and the 13.8 GB
    of arguments fit the chip beside the temporaries: 325,735,936 bytes
    of them, against 596,450,304 while XLA gathered every slot's keys
    and selected over the rows' copy (jax 0.9.0, libtpu 0.0.34)."""
    dec, pools, described = latent
    S, MB = dec.max_slots, dec.blocks_per_seq
    i32, flag = jnp.int32, jnp.bool_
    compiled = dec._paged_chunk_state_jit.lower(
        dec._params, described((S,), i32), described((S,), i32),
        described((S, MB), i32), described((S,), flag),
        described((S,), i32), described((S,), flag), *pools, 8, -1).compile()
    text = _fits_beside_its_pools(compiled, pools)
    assert "%decode.index" in text and "%decode.attend.sparse" in text
    assert "%moe.experts" in text
    for keys in ("bf16[48,16384,128]", "bf16[48,256,64,128]",
                 "bf16[12288,64,128]"):
        assert keys not in text
    assert not re.search(r"= s32\[48,2048\]\S* gather\(", text)
    assert not re.search(r"= bf16\[48,2048,640\]\S* select\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 596_450_304


def test_latent_prefill_program(latent, for_the_chip):
    """A 1,024-row chunk against 16,384 positions: the indexer's kernel
    and the attention kernel that forms the heads' keys and values from
    the latent rows, no [keys, heads, dims] array in HBM."""
    dec, pools, described = latent
    i32 = jnp.int32
    compiled = dec._prefill_exec(1024).lower(
        dec._params, described((1024,), i32), described((), i32),
        described((), i32), described((dec.blocks_per_seq,), i32),
        *pools).compile()
    text = _fits_beside_its_pools(compiled, pools)
    assert "%prefill.index" in text and "%prefill.attend" in text
    assert not re.search(r"bf16\[16384,128,\d+\]", text)


@pytest.mark.parametrize("heads,dn,dv,keys,masked,group", [
    (128, 128, 128, 16384, True, 8),   # deepseek_v32_serve_backlog_16k
    (20, 192, 256, 13312, False, 5),   # glm47_flash_serve_mtp_13k, causal
])
def test_mla_prefill(one_chip, for_the_chip, heads, dn, dv, keys, masked,
                     group):
    """The latent prefill kernel alone at the cells' widths (1,024 rows,
    latent 512 + 64 in 640 lanes, key tiles of 1,024): the group of heads
    a grid step that `_heads_per_step` derives, its blocks, scratch and a
    head's tiles, fit the chip's VMEM."""
    from paddle_tpu.kernels.pallas import mla_prefill as kernel
    assert kernel._heads_per_step(heads, 1024, 1024, dn, dv, 128, 640, 512,
                                  2, masked) == group
    shapes = [((1024, heads, dn + 64), BF16), ((keys, 640), BF16),
              ((512, heads * (dn + dv)), BF16)]
    if masked:
        shapes.append(((1024, keys), jnp.bool_))
    text = _compiled_text(
        lambda q, lat, w, *mask: kernel.mla_prefill_attention(
            q, lat, w, mask[0] if mask else None, 7168, 512, 64, 0.1),
        one_chip, *shapes)
    assert "tpu_custom_call" in text


# -- the dense latent engine with its MTP draft (glm47_flash_serve_mtp_13k) ------

@pytest.mark.parametrize("rows", [1, 2])
def test_mla_paged_decode(one_chip, for_the_chip, rows):
    """The dense absorbed decode kernel at the cell's widths (20 heads,
    latent 512 + 64 in 640 lanes, 48 slots, 208 blocks of 64 a slot,
    7 layers of 7,680 blocks), one row a slot and the verify pass's two:
    it reads the pool through the block table, no copy of a slot's rows.
    Its scoped VMEM: a product of 16 blocks (1,024 keys), a copy group of
    48, so the double buffer is 2 x 48 x 80 KB = 7.5 MiB, and the launch
    declares 39.5 MiB (41,418,752 B) of the v5e's 128 MiB; the query and
    output blocks, m, l, acc and a product's float32 scores and
    probabilities take under 1 MiB of the 32 MiB beside the buffer."""
    from paddle_tpu.kernels.pallas import mla_paged_decode as kernel
    S, MB, NB, n = 48, 208, 7 * 7680, rows * 20
    text = _compiled_text(
        lambda qc, qp, pool, tab, lens: kernel.mla_paged_decode_attention(
            qc, qp, pool, tab, lens, 7680, 512, 256 ** -0.5),
        one_chip, ((S, rows, 20, 512), BF16), ((S, rows, 20, 64), BF16),
        ((NB, 64, 640), BF16), ((S, MB), jnp.int32), ((S, rows), jnp.int32))
    assert "tpu_custom_call" in text
    assert not re.search(r"bf16\[48,\d+,64,640\]", text)
    block_bytes = 64 * 640 * 2
    group, chunk = kernel._blocks_per_step(block_bytes, 64, MB)
    assert (group, chunk) == (48, 16)
    declared = [int(size) for line in text.splitlines()
                if "tpu_custom_call" in line
                for size in re.findall(
                    r'"scoped_memory_configs":\[\{"memory_space":"1",'
                    r'"offset":"0","size":"(\d+)"', line)]
    assert declared == [2 * group * block_bytes + 32 * 2**20] == [41_418_752]
    held = (2 * group * block_bytes                    # the double buffer
            + 2 * 2 * n * (640 + 512) * 2              # q, o: two each
            + 4 * n * (128 + 128 + 512)                # m, l, acc
            + 3 * 4 * n * chunk * 64)                  # scores, p, mask
    assert held < declared[0] <= 128 * 2**20


@pytest.fixture(scope="module")
def dense_latent(one_chip):
    """The serving engine of `glm47_flash_l6_mtp1` with its 4.54 B
    parameters described, not made: (decoder, pools, described)."""
    from paddle_tpu.models.deepseek_v32 import LatentPagedDecoder
    from paddle_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig
    cfg = Glm4MoeLiteConfig(num_hidden_layers=6, dtype="bfloat16")

    def described(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    class Described:
        config = cfg

        def param_tree(self):
            tree = {"layers": [{} for _ in range(cfg.num_hidden_layers)],
                    "mtp": {}}
            for name, (shape, f32) in cfg.param_shapes().items():
                leaf = described(shape, jnp.float32 if f32 else BF16)
                if name.startswith("layers."):
                    _, i, key = name.split(".")
                    tree["layers"][int(i)][key] = leaf
                elif name.startswith("mtp."):
                    tree["mtp"][name[4:]] = leaf
                else:
                    tree[name] = leaf
            return tree

    dec = LatentPagedDecoder(Described(), max_len=13312, block_size=64,
                             num_blocks=7680, max_slots=48,
                             prefill_chunk=1024)
    pools = tuple(described(p.shape, p.dtype)
                  for p in jax.eval_shape(dec.new_pools))
    return dec, pools, described


@pytest.mark.parametrize("drafting", [True, False])
def test_dense_latent_chunk_program(dense_latent, for_the_chip, drafting):
    """8 decode steps for 48 slots at 13,312 positions, verify passes of
    two rows a slot with the MTP draft (tokens [48, 2]) or plain steps:
    the dense kernel reads the latent rows through the block table (no
    gathered copy of a slot's rows), the MTP block runs, the grouped
    expert products run, and the 13.5 GB of arguments fit the chip beside
    the temporaries."""
    dec, pools, described = dense_latent
    S, MB = dec.max_slots, dec.blocks_per_seq
    i32, flag = jnp.int32, jnp.bool_
    tok = (S, 2) if drafting else (S,)
    compiled = dec._paged_chunk_state_jit.lower(
        dec._params, described(tok, i32), described((S,), i32),
        described((S, MB), i32), described((S,), flag),
        described((S,), i32), described((S,), flag), *pools, 8,
        -1).compile()
    text = _fits_beside_its_pools(compiled, pools)
    assert "%decode.attend.dense" in text and "%moe.experts" in text
    assert ("%decode.mtp" in text) == drafting
    assert not re.search(r"bf16\[48,(13312|208,64),640\]", text)


def test_dense_latent_prefill_program(dense_latent, for_the_chip):
    """A 1,024-row chunk against 13,312 positions, causal, with the MTP
    layer's rows: the attention kernel forms the heads' keys and values
    from the latent rows, no [keys, heads, dims] array in HBM."""
    dec, pools, described = dense_latent
    i32 = jnp.int32
    compiled = dec._prefill_exec(1024).lower(
        dec._params, described((1024,), i32), described((), i32),
        described((), i32), described((dec.blocks_per_seq,), i32),
        *pools, described((), i32)).compile()
    text = _fits_beside_its_pools(compiled, pools)
    assert "%prefill.attend" in text and "%prefill.mtp" in text
    assert not re.search(r"bf16\[13312,20,\d+\]", text)
