"""The KV pools ride the layer loop's carry and are updated in place.

Two properties of `PagedDecoder`'s jitted programs, on the CPU at tiny sizes:

- no program moves a whole pool, or a whole layer of one, to write a few
  rows: the compiled text holds no copy, broadcast, dynamic-slice or
  dynamic-update-slice of that shape (pools that go through the layer scan
  as xs and come back as ys give six of them);
- with the pools flat over layers inside the programs, a layer's rows still
  land in that layer's blocks and nowhere else.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.analysis.hlo_lint import compiled_text, shape_str
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.decode import CachedDecoder
from paddle_tpu.models.paged_decode import PagedDecoder
from paddle_tpu.utils.hlo_analysis import _parse_instr

LAYERS, BLOCK, BLOCKS, SLOTS, MAX_LEN, BUCKET = 3, 8, 23, 4, 64, 16


def _model(dtype="float32"):
    pt.seed(5)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=97, hidden_size=64, intermediate_size=128,
        num_hidden_layers=LAYERS, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=128,
        use_flash_attention=False, dtype=dtype))
    model.eval()
    return model


def _decoder(model, **kw):
    return PagedDecoder(model, max_len=MAX_LEN, block_size=BLOCK,
                        max_slots=SLOTS, num_blocks=BLOCKS, **kw)


def _cold_prefill(dec):
    return jax.jit(dec._prefill_paged, donate_argnums=(4, 5))


# -- the compiled programs move no pool ----------------------------------------

def _programs(dec):
    """name -> (jitted program, example arguments), the five programs
    that write the pools."""
    S, MB = SLOTS, dec.blocks_per_seq
    kp, vp = dec.new_pools()
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    flag = lambda *shape: jnp.zeros(shape, bool)
    state = (dec._params, i32(S), i32(S), i32(S, MB), flag(S), i32(S),
             flag(S), kp, vp)
    return {
        "state_chunk": (dec._paged_chunk_state_jit, state + (2, -1)),
        "state_chunk_eos": (dec._paged_chunk_state_jit, state + (2, 0)),
        "spec_verify": (dec._spec_verify_jit,
                        (dec._params, i32(S, 3)) + state[2:]),
        "warm_prefill": (jax.jit(dec._prefill_warm_impl,
                                 donate_argnums=(5, 6)),
                         (dec._params, i32(BUCKET), i32(), i32(), i32(MB),
                          kp, vp)),
        "cold_prefill": (_cold_prefill(dec),
                         (dec._params, i32(BUCKET), i32(), i32(MB), kp, vp)),
    }


_MOVES = {"copy", "broadcast", "dynamic-slice", "dynamic-update-slice"}


def pool_moves(text, pool_shape):
    """Instructions of `text` (every computation, fused ones too) that
    produce a whole pool or a whole layer of one by moving it."""
    L, NB, *row = pool_shape
    whole = {shape_str("", dims) for dims in (
        (L, NB, *row), (L * NB, *row), (L * NB * row[0], *row[1:]),
        (1, NB, *row), (NB, *row), (NB * row[0], *row[1:]))}
    hits = []
    for line in text.splitlines():
        instr = _parse_instr(line)
        if instr and instr["op"] in _MOVES and \
                instr["shape"][instr["shape"].find("["):] in whole:
            hits.append(line.strip()[:160])
    return hits


@pytest.mark.parametrize("program", ["state_chunk", "state_chunk_eos",
                                     "spec_verify", "warm_prefill",
                                     "cold_prefill"])
def test_no_program_moves_a_pool(program):
    dec = _decoder(_model())
    fn, args = _programs(dec)[program]
    text = compiled_text(fn, *args)
    assert "scatter" in text        # the write itself is there
    shape = (LAYERS, BLOCKS, BLOCK, dec.nkv, dec.hd)
    assert pool_moves(text, shape) == []


def test_pool_moves_sees_the_restacking_loop():
    """The check above is not blind: pools that go through a layer scan
    as xs and come back as ys are found."""
    shape = (LAYERS, BLOCKS, BLOCK, 2, 16)

    def restack(kpool, rows, widx):
        def layer(x, kc):
            flat = kc.reshape(-1, *shape[3:])
            return x, flat.at[widx].set(rows).reshape(kc.shape)
        return jax.lax.scan(layer, 0.0, kpool)[1]

    text = compiled_text(jax.jit(restack, donate_argnums=0),
                         jnp.zeros(shape), jnp.ones((4, 2, 16)),
                         jnp.arange(4, dtype=jnp.int32))
    assert pool_moves(text, shape)


def test_row_index_past_int32_is_refused():
    with pytest.raises(ValueError, match="token rows"):
        PagedDecoder(_model(), max_len=MAX_LEN, block_size=BLOCK,
                     max_slots=SLOTS, num_blocks=2**31 // (LAYERS * BLOCK) + 1)


# -- a layer's rows land in that layer ------------------------------------------

VARIANTS = {
    "bf16": (dict(ragged_kernel=True), "bfloat16", 6e-2),
    "kv_quant": (dict(ragged_kernel=True, kv_quant="int8"), "float32", 6e-2),
    "attn_shards2": (dict(ragged_kernel=True, attn_shards=2), "float32",
                     2e-4),
    "dense_oracle": (dict(ragged_kernel=False), "float32", 2e-4),
}


def _dequantized(pool):
    """Exported pool leaves as float32 [L, n, bs, Hkv, D]."""
    if isinstance(pool, tuple):
        codes, scales = pool
        return codes.astype(np.float32) * scales[..., None, None]
    return np.asarray(pool, np.float32)


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_layer_rows_land_in_their_layer(variant):
    kw, dtype, tol = VARIANTS[variant]
    model = _model(dtype)
    dec = _decoder(model, **kw)
    rng = np.random.default_rng(11)
    MB, steps = dec.blocks_per_seq, 4
    # three live slots with prompts of different lengths, one retired slot;
    # block ids out of order and interleaved between the slots
    prompts = [rng.integers(1, 97, n).tolist() for n in (5, 11, 9)]
    order = rng.permutation(np.arange(1, BLOCKS))
    blocks = [order[0:3], order[3:6], order[6:9], order[9:12]]
    tables = np.zeros((SLOTS, MB), np.int32)
    for s, ids in enumerate(blocks):
        tables[s, :len(ids)] = ids
    budgets = np.array([steps, 2, steps, steps], np.int32)
    live = np.array([True, True, True, False])

    kp, vp = dec.new_pools()
    prefill = _cold_prefill(dec)
    first = []
    for s, prompt in enumerate(prompts):
        ids = np.zeros(BUCKET, np.int32)
        ids[:len(prompt)] = prompt
        enc, kp, vp = prefill(dec._params, jnp.asarray(ids),
                              jnp.int32(len(prompt)),
                              jnp.asarray(tables[s]), kp, vp)
        first.append(dec.decode_first_token([enc])[0])
    lens0 = np.array([len(p) for p in prompts] + [7], np.int32)
    toks, bad, *_, kp, vp = dec._paged_chunk_state_jit(
        dec._params, jnp.asarray(first + [3], jnp.int32),
        jnp.asarray(lens0), jnp.asarray(tables), jnp.asarray(live),
        jnp.asarray(budgets), jnp.zeros(SLOTS, bool), kp, vp, steps, -1)
    toks = np.asarray(toks)
    assert not np.asarray(bad).any()

    # the dense K and V of every layer: the fixed engine's prefill over the
    # tokens whose rows the paged programs wrote
    fixed = CachedDecoder(model, max_len=MAX_LEN)
    for s, prompt in enumerate(prompts):
        took = int(min(steps, budgets[s]))
        seq = prompt + [first[s]] + toks[s, :took - 1].tolist()
        _, dk, dv = fixed._prefill(jnp.asarray([seq], jnp.int32),
                                   *fixed.new_caches(1))
        ek, ev = dec.export_blocks(kp, vp, blocks[s])
        for name, got, want in (("K", ek, dk), ("V", ev, dv)):
            got = _dequantized(got).reshape(LAYERS, -1, dec.nkv, dec.hd)
            want = np.asarray(want, np.float32)[:, 0]
            for l in range(LAYERS):
                scale = np.abs(want[l, :len(seq)]).max()
                np.testing.assert_allclose(
                    got[l, :len(seq)], want[l, :len(seq)],
                    atol=tol * scale, rtol=0,
                    err_msg=f"{name} slot {s} layer {l}")
                # and the layers are told apart by more than the tolerance
                other = want[(l + 1) % LAYERS, :len(seq)]
                assert np.abs(got[l, :len(seq)] - other).max() > 10 * tol * scale
            # the slot's blocks hold nothing past its length
            assert not got[:, len(seq):].any(), f"{name} slot {s}"

    # every block no live slot owns is untouched in every layer: the retired
    # slot's own blocks too, since its writes went to the trash block
    untouched = np.concatenate([blocks[3], order[12:]])
    for pool in dec.export_blocks(kp, vp, untouched):
        assert not _dequantized(pool).any()
    # the trash block took them (and the prefill's pad rows), layer by layer
    for pool in dec.export_blocks(kp, vp, [0]):
        assert _dequantized(pool).reshape(LAYERS, -1).any(axis=1).all()


# -- the hybrid engine's second cache and its expert stacks ----------------------

def _hybrid_programs():
    from paddle_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                              nemotron_h_tiny)
    model = NemotronHForCausalLM(nemotron_h_tiny(
        experts_held=(4, 8)))
    dec = PagedDecoder(model, max_len=MAX_LEN, block_size=BLOCK,
                       max_slots=SLOTS, num_blocks=BLOCKS)
    S, MB = SLOTS, dec.blocks_per_seq
    pools = dec.new_pools()
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    flag = lambda *shape: jnp.zeros(shape, bool)
    # a pack of two prompts, from rows 0 and 8 of the bucket
    head, tail = dec._prefill_inputs(
        BUCKET, [(1, [3, 4, 5], 0), (2, [6, 7], 8)],
        np.zeros((S, MB), np.int32), 0)
    return dec, pools, {
        "state_chunk": (dec._paged_chunk_state_jit,
                        (dec._params, i32(S), i32(S), i32(S, MB), flag(S),
                         i32(S), flag(S)) + pools + (2, -1)),
        "cold_prefill": (dec._prefill_exec(BUCKET),
                         (dec._params,) + head + pools + tail),
    }


@pytest.mark.parametrize("program", ["state_chunk", "cold_prefill"])
def test_hybrid_program_copies_no_state_pool_and_no_expert_stack(program):
    """The recurrent state rides the step loop's carry as the KV pools
    do, and every block's weights are the model's own arrays: no program
    copies or rebuilds the SSM state pool, a layer of it, or an expert
    stack (an update in place, a `dynamic-update-slice` of the carry, is
    the write itself). The CPU backend copies a step loop's carried
    state once around the loop, so the chunk is held here to its expert
    stacks and its KV pool; that the chip's compiler leaves no room for a
    second copy of a layer of state, at the cell's widths, is
    `tests/test_chip_compile.py::test_hybrid_chunk_program`'s."""
    dec, pools, programs = _hybrid_programs()
    fn, args = programs[program]
    text = compiled_text(fn, *args)
    ssm = pools[2]
    w1 = dec._params["layers"][1]["w1"]
    w2 = dec._params["layers"][1]["w2"]
    state = (ssm.shape, ssm.shape[1:], (1,) + ssm.shape[1:]) \
        if program == "cold_prefill" else ()
    whole = {shape_str("f32", dims)
             for dims in state + (w1.shape, w2.shape)}
    hits = []
    for line in text.splitlines():
        instr = _parse_instr(line)
        if instr and instr["op"] in ("copy", "broadcast") and \
                instr["shape"].split("{")[0] in whole:
            hits.append(line.strip()[:160])
    assert hits == []
    # the KV pool holds the attention block only, and is not moved either
    assert pool_moves(text, (1, BLOCKS, BLOCK, dec.nkv, dec.hd)) == []
