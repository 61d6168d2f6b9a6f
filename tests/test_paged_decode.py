"""Paged KV cache + continuous batching (VERDICT r4 #2).

Reference capability:
phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu:609
`BlockMultiheadAttentionKernel` — per-sequence block tables, in-batch
admission, per-slot lengths. Oracles here are the full-forward
generate() and the fixed-shape CachedDecoder (exact greedy equality).
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.decode import CachedDecoder
from paddle_tpu.models.paged_decode import BlockAllocator, PagedDecoder

RNG = np.random.default_rng(23)


def _tiny(dtype="float32", **kw):
    cfg = dict(vocab_size=97, hidden_size=64, intermediate_size=128,
               num_hidden_layers=3, num_attention_heads=4,
               num_key_value_heads=2, max_position_embeddings=128,
               use_flash_attention=False, dtype=dtype)
    cfg.update(kw)
    pt.seed(5)
    return LlamaForCausalLM(LlamaConfig(**cfg))


def _oracle(model, prompt, n):
    ids = pt.to_tensor(np.asarray(prompt)[None])
    out = model.generate(ids, max_new_tokens=n)
    return [int(t) for t in out.numpy()[0, len(prompt):]]


class TestBlockAllocator:
    def test_alloc_free_reclaim(self):
        a = BlockAllocator(8)            # blocks 1..7 usable
        got = a.alloc(7)
        assert sorted(got) == list(range(1, 8))
        with pytest.raises(MemoryError):
            a.alloc(1)
        a.free(got[:3])
        assert a.free_count == 3
        assert a.peak_in_use == 7

    def test_trash_block_reserved(self):
        a = BlockAllocator(4)
        assert 0 not in a.alloc(3)
        with pytest.raises(ValueError):
            a.free([0])


class TestPagedParity:
    def test_single_request_matches_full_forward(self):
        model = _tiny()
        model.eval()
        dec = PagedDecoder(model, max_len=64, block_size=16, max_slots=2,
                           num_blocks=9)
        prompt = [int(t) for t in RNG.integers(0, 97, 7)]
        out = dec.serve([("a", prompt)], max_new_tokens=12)
        assert out["a"] == _oracle(model, prompt, 12)
        # all blocks reclaimed after the run
        assert dec.allocator.in_use == 0

    def test_concurrent_variable_length_streams(self):
        """Slots decode together at DIFFERENT positions (ragged seqlens
        inside one executable) — every stream must match its own
        single-stream oracle exactly."""
        model = _tiny()
        model.eval()
        dec = PagedDecoder(model, max_len=64, block_size=16, max_slots=4,
                           num_blocks=17)
        prompts = {f"r{i}": [int(t) for t in RNG.integers(0, 97, ln)]
                   for i, ln in enumerate((3, 9, 14, 6))}
        out = dec.serve(list(prompts.items()), max_new_tokens=10)
        for rid, prompt in prompts.items():
            assert out[rid] == _oracle(model, prompt, 10), rid

    def test_matches_fixed_engine(self):
        model = _tiny()
        model.eval()
        fixed = CachedDecoder(model, max_len=64)
        paged = PagedDecoder(model, max_len=64, block_size=16,
                             max_slots=2, num_blocks=9)
        prompt = [int(t) for t in RNG.integers(0, 97, 8)]
        ref = fixed.generate(pt.to_tensor(np.asarray(prompt)[None]),
                             max_new_tokens=9).numpy()[0, 8:]
        out = paged.serve([("x", prompt)], max_new_tokens=9)
        assert out["x"] == [int(t) for t in ref]


class TestContinuousBatching:
    def test_admission_between_chunks(self):
        """More requests than slots: latecomers are admitted as slots
        retire, inside ONE serve() call; everyone matches their oracle."""
        model = _tiny()
        model.eval()
        dec = PagedDecoder(model, max_len=64, block_size=16, max_slots=2,
                           num_blocks=9)
        prompts = {f"r{i}": [int(t) for t in RNG.integers(0, 97, ln)]
                   for i, ln in enumerate((5, 11, 4, 8, 13))}
        out = dec.serve(list(prompts.items()), max_new_tokens=7, chunk=4)
        assert set(out) == set(prompts)
        for rid, prompt in prompts.items():
            assert out[rid] == _oracle(model, prompt, 7), rid
        assert dec.allocator.in_use == 0

    def test_hbm_bounded_by_pool_not_batch(self):
        """The whole point: peak HBM is the block pool, not
        slots x max_len. 5 streams through 2 slots with a pool HALF the
        fixed engine's 2-slot bill."""
        model = _tiny()
        model.eval()
        max_len, slots = 64, 2
        fixed_tokens = slots * max_len
        dec = PagedDecoder(model, max_len=max_len, block_size=16,
                           max_slots=slots,
                           num_blocks=fixed_tokens // 16 // 2 + 1)
        prompts = [(f"r{i}", [int(t) for t in RNG.integers(0, 97, 6)])
                   for i in range(5)]
        out = dec.serve(prompts, max_new_tokens=8, chunk=4)
        assert len(out) == 5
        peak_tokens = dec.allocator.peak_in_use * dec.block_size
        assert peak_tokens < fixed_tokens
        # pool bytes really are the smaller bill
        kc, vc = CachedDecoder(model, max_len=max_len).new_caches(slots)
        assert dec.pool_bytes() < 2 * kc.nbytes

    def test_backpressure_queues_when_pool_tight(self):
        """A pool that fits only one stream at a time still completes
        every request (admission waits for blocks)."""
        model = _tiny()
        model.eval()
        dec = PagedDecoder(model, max_len=64, block_size=16, max_slots=3,
                           num_blocks=2)      # 1 usable block = 16 tokens
        prompts = [(i, [int(t) for t in RNG.integers(0, 97, 4)])
                   for i in range(3)]
        out = dec.serve(prompts, max_new_tokens=6, chunk=4)
        for rid, prompt in prompts:
            assert out[rid] == _oracle(model, prompt, 6)

    def test_pool_too_small_raises(self):
        model = _tiny()
        model.eval()
        dec = PagedDecoder(model, max_len=64, block_size=16, max_slots=1,
                           num_blocks=2)
        with pytest.raises(MemoryError):
            dec.serve([("big", list(range(40)))], max_new_tokens=8)

    def test_per_slot_eos(self):
        """One stream hits eos early; its tail is pad, its blocks free
        while the other stream keeps decoding."""
        model = _tiny()
        model.eval()
        probe = PagedDecoder(model, max_len=64, block_size=16,
                             max_slots=2, num_blocks=9)
        p0 = [int(t) for t in RNG.integers(0, 97, 5)]
        p1 = [int(t) for t in RNG.integers(0, 97, 9)]
        free_run = probe.serve([("a", p0), ("b", p1)], max_new_tokens=10)
        eos = free_run["a"][3]           # force this value to be eos
        cut = free_run["a"].index(eos)   # first occurrence retires slot a
        dec = PagedDecoder(model, max_len=64, block_size=16, max_slots=2,
                           num_blocks=9)
        out = dec.serve([("a", p0), ("b", p1)], max_new_tokens=10,
                        eos_token_id=eos, pad_token_id=0, chunk=4)
        assert out["a"][:cut + 1] == free_run["a"][:cut + 1]
        assert all(t == 0 for t in out["a"][cut + 1:])
        if eos not in free_run["b"]:
            assert out["b"] == free_run["b"]

    def test_heterogeneous_budgets_cannot_clobber_pool(self):
        """Regression (ADVICE r5): a chunk is sized by the LARGEST
        remaining budget, so a smaller-budget slot used to keep stepping
        past its allocation — the clamped out-of-range gather let it
        write into valid pool KV. Steps are now gated per slot on
        device; with per-request budgets differing inside one chunk,
        every stream must still match its own oracle exactly."""
        model = _tiny()
        model.eval()
        dec = PagedDecoder(model, max_len=64, block_size=16, max_slots=4,
                           num_blocks=17)
        prompts = {f"r{i}": [int(t) for t in RNG.integers(0, 97, ln)]
                   for i, ln in enumerate((4, 11, 7, 14))}
        budgets = {"r0": 2, "r1": 13, "r2": 5, "r3": 9}
        reqs = [(rid, p, budgets[rid]) for rid, p in prompts.items()]
        # chunk far larger than the smallest budget: r0 exhausts at
        # step 2 while r1 keeps decoding the same chunk
        out = dec.serve(reqs, chunk=8)
        for rid, prompt in prompts.items():
            assert len(out[rid]) == budgets[rid], rid
            assert out[rid] == _oracle(model, prompt, budgets[rid]), rid
        assert dec.allocator.in_use == 0

    def test_exhausted_slot_stops_advancing_on_device(self):
        """The budget gate itself: an exhausted slot's length must not
        advance past prompt+budget inside an oversized chunk (before the
        fix it advanced with the chunk and wrote through the clamped
        gather)."""
        import jax.numpy as jnp
        model = _tiny()
        model.eval()
        dec = PagedDecoder(model, max_len=64, block_size=16, max_slots=2,
                           num_blocks=9)
        kpool, vpool = dec.new_pools()
        tables = np.zeros((2, dec.blocks_per_seq), np.int32)
        for i in range(2):
            blocks = dec.allocator.alloc(2)
            tables[i, :2] = blocks
        toks = jnp.asarray(np.array([5, 7], np.int32))
        lens0 = np.array([10, 10], np.int32)
        live = jnp.asarray(np.ones(2, bool))
        budgets = jnp.asarray(np.array([3, 8], np.int32))
        n = 8
        poison = jnp.asarray(np.zeros(2, bool))
        *_, kpool, vpool = dec._paged_chunk_state_jit(
            dec._params, toks, jnp.asarray(lens0), jnp.asarray(tables),
            live, budgets, poison, kpool, vpool, n, -1)
        # step i writes position lens0+i for slots with i < budget:
        # slot 0 (budget 3) writes lanes 10..12 of its first block and
        # FREEZES — lanes 13..15 stay zero; slot 1 (budget 8) fills
        # lanes 10..15 and spills into its second block
        k0 = np.asarray(kpool)[0]          # layer 0 pool [NB, bs, H, D]
        b00 = tables[0, 0]
        assert (np.abs(k0[b00, 10:13]).max(axis=(1, 2)) > 0).all()
        assert np.abs(k0[b00, 13:16]).max() == 0
        b10, b11 = tables[1, 0], tables[1, 1]
        assert (np.abs(k0[b10, 10:16]).max(axis=(1, 2)) > 0).all()
        assert (np.abs(k0[b11, 0:2]).max(axis=(1, 2)) > 0).all()

    def test_compiled_set_stays_bounded(self):
        """Serving again (same chunk/maxima, different prompts/lengths)
        must not add executables — block tables and seqlens are DATA.
        The CPU twin of the cells' `programs.compiles_in_window.serve`
        = 0, read off the programs the loop runs."""
        model = _tiny()
        model.eval()
        dec = PagedDecoder(model, max_len=64, block_size=16, max_slots=2,
                           num_blocks=9)

        def programs():
            return (dec._paged_chunk_state_jit._cache_size(),
                    {b: fn._cache_size()
                     for b, fn in dec._prefill_cache.items()},
                    {b: fn._cache_size()
                     for b, fn in dec._warm_cache.items()})

        dec.serve([("a", [1, 2, 3]), ("b", [4, 5, 6, 7, 8])],
                  max_new_tokens=9, chunk=4)
        first = programs()
        # a budget of 9 is the prefill's token and two chunks of 4: one
        # chunk length; both prompts fall in the 16 bucket
        assert first == (1, {16: 1}, {})
        dec.serve([("c", [9, 8, 7, 6]), ("d", [5])],
                  max_new_tokens=9, chunk=4)
        assert programs() == first
        # and it can fail: a prompt past the bucket adds a program
        dec.serve([("e", list(range(1, 20)))], max_new_tokens=9, chunk=4)
        assert programs() != first
