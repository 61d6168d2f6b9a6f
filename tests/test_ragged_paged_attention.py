"""Ragged paged-attention Pallas kernel (ISSUE 2 tentpole).

Oracles: an independent numpy dense-gather reference (the exact math of
PagedDecoder._attend), the full-forward generate() for end-to-end serve
parity, and NaN-poisoned pool blocks for the never-reads-past-seq_lens
property. All kernel runs here are interpret mode (CPU tier-1)."""
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.observability as obs
from test_flash_bf16 import _eqns
from paddle_tpu.kernels.pallas import ragged_paged_attention as rpa
from paddle_tpu.kernels.pallas.ragged_paged_attention import (
    dense_gather_hbm_bytes, kv_dequantize_rows, kv_quantize_rows,
    ragged_hbm_bytes, ragged_paged_attention, ragged_paged_attention_quant,
    ragged_paged_attention_sharded, record_ragged_step)

RNG = np.random.default_rng(31)


def _dense_reference(q, kpool, vpool, tables, lens, nh, nkv):
    """The dense-gather path's math in plain numpy/f32: gather the full
    [S, W] window, mask arange(W) <= pos, softmax, weighted sum."""
    S, _, hd = q.shape
    bs = kpool.shape[1]
    W = tables.shape[1] * bs
    kw = np.asarray(kpool, np.float32)[np.asarray(tables)]
    vw = np.asarray(vpool, np.float32)[np.asarray(tables)]
    kw = kw.reshape(S, W, nkv, hd)
    vw = vw.reshape(S, W, nkv, hd)
    nrep = nh // nkv
    scale = 1.0 / np.sqrt(hd)
    qg = np.asarray(q, np.float32).reshape(S, nkv, nrep, hd)
    att = np.einsum("bgnd,bwgd->bgnw", qg, kw) * scale
    mask = np.arange(W)[None] <= np.asarray(lens)[:, None]
    att = np.where(mask[:, None, None, :], att, -1e30)
    p = np.exp(att - att.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    o = np.einsum("bgnw,bwgd->bgnd", p, vw)
    return o.reshape(S, nh, hd)


def _random_case(nh, nkv, hd, bs, mb, S, dtype, lens=None):
    import jax.numpy as jnp
    nb = S * mb + 1
    kp = jnp.asarray(RNG.standard_normal((nb, bs, nkv, hd)), dtype)
    vp = jnp.asarray(RNG.standard_normal((nb, bs, nkv, hd)), dtype)
    q = jnp.asarray(RNG.standard_normal((S, nh, hd)), dtype)
    perm = RNG.permutation(nb - 1)[:S * mb] + 1    # distinct, no trash
    tables = jnp.asarray(perm.reshape(S, mb), jnp.int32)
    if lens is None:
        lens = RNG.integers(0, mb * bs, S)
    lens = jnp.asarray(np.asarray(lens), jnp.int32)
    return q, kp, vp, tables, lens


def _entry_point(entry, q, kp, vp, tables, lens):
    """Run one of the three public kernels; returns (output, the pools
    its dense reference attends over)."""
    import jax
    import jax.numpy as jnp
    if entry == "plain":
        return jax.jit(ragged_paged_attention)(q, kp, vp, tables, lens), \
            kp, vp
    if entry == "partials":
        shards = min(3, tables.shape[1])
        out = jax.jit(lambda *a: ragged_paged_attention_sharded(
            *a, shards))(q, kp, vp, tables, lens)
        return out, kp, vp
    kc, ks = kv_quantize_rows(jnp.asarray(kp, jnp.float32))
    vc, vs = kv_quantize_rows(jnp.asarray(vp, jnp.float32))
    out = jax.jit(ragged_paged_attention_quant)(q, kc, ks, vc, vs, tables,
                                                lens)
    return out, kv_dequantize_rows(kc, ks), kv_dequantize_rows(vc, vs)


def _tolerance(entry, dtype):
    """float32 keeps its tolerance. bf16 rounds the output as it is
    stored, 2**-9 of values up to about 4; over int8 pools the
    dequantized V rows are no bf16 numbers, so even a window of one
    token is rounded, by up to 2**-8 of it."""
    if dtype == "float32":
        return 1e-5
    return 2e-2 if entry == "int8" else 1e-2


def _steer_step(monkeypatch, group, chunk, bs, nkv, hd, itemsize):
    """Make the kernel carry `group` blocks a DMA group and `chunk` a
    product at this (tiny) block size: the rule is the kernel's own, the
    test moves the two sizes it reads."""
    block = bs * nkv * hd * itemsize
    monkeypatch.setattr(rpa, "_PRODUCT_COLS", chunk * bs * nkv)
    monkeypatch.setattr(rpa, "_BUFFER_BYTES", 4 * block * group)


ENTRIES = ["plain", "partials", "int8"]


class TestGroupedStep:
    """Several pool blocks a DMA group, one masked product over tokens x
    KV heads. The three entry points share the step."""

    # nrep 1 at three widths, nrep 4, and the hybrid's nrep 16 twice
    @pytest.mark.parametrize("entry", ENTRIES)
    @pytest.mark.parametrize("nh,nkv", [(8, 8), (16, 16), (32, 32), (8, 2),
                                        (16, 1), (32, 2)])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_matches_dense_gather(self, entry, nh, nkv, dtype, monkeypatch):
        """blocks_per_seq 5 in groups of 2: lengths of 0, a block's last
        and first position, one whose live blocks end inside a group
        (3 of 4) and inside the table's odd last group, and full."""
        bs, mb, hd = 8, 5, 16
        itemsize = 1 if entry == "int8" else (4 if dtype == "float32" else 2)
        _steer_step(monkeypatch, 2, 1, bs, nkv, hd, itemsize)
        assert rpa._blocks_per_step(bs * nkv * hd * itemsize, bs * nkv,
                                    mb) == (2, 1)
        lens = [0, bs - 1, bs, 2 * bs + 3, 4 * bs - 1, 4 * bs, mb * bs - 1]
        q, kp, vp, tables, lens = _random_case(
            nh, nkv, hd, bs, mb, len(lens), dtype, lens=lens)
        out, kref, vref = _entry_point(entry, q, kp, vp, tables, lens)
        ref = _dense_reference(q, kref, vref, tables, lens, nh, nkv)
        assert np.abs(np.asarray(out, np.float32) - ref).max() \
            < _tolerance(entry, dtype)

    @pytest.mark.parametrize("entry", ENTRIES)
    @pytest.mark.parametrize("group,chunk,carried", [
        (4, 2, (4, 2)), (6, 3, (6, 3)), (8, 8, (7, 7))])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_several_blocks_a_product(self, entry, group, chunk, carried,
                                      dtype, monkeypatch):
        """Small blocks (2 KV heads) share a product: 7 blocks a sequence
        in products of 2, 3 and 8 blocks, so the last product of a slot
        holds dead blocks behind the live ones; the last case is the
        whole table in one product."""
        nh, nkv, bs, mb, hd = 8, 2, 8, 7, 16
        itemsize = 1 if entry == "int8" else (4 if dtype == "float32" else 2)
        _steer_step(monkeypatch, group, chunk, bs, nkv, hd, itemsize)
        assert rpa._blocks_per_step(bs * nkv * hd * itemsize, bs * nkv,
                                    mb) == carried
        lens = [0, bs - 1, bs, 3 * bs + 2, 4 * bs - 1, 6 * bs, mb * bs - 1]
        q, kp, vp, tables, lens = _random_case(
            nh, nkv, hd, bs, mb, len(lens), dtype, lens=lens)
        out, kref, vref = _entry_point(entry, q, kp, vp, tables, lens)
        ref = _dense_reference(q, kref, vref, tables, lens, nh, nkv)
        assert np.abs(np.asarray(out, np.float32) - ref).max() \
            < _tolerance(entry, dtype)

    @pytest.mark.parametrize("block_bytes,rows,mb,want", [
        (2**19, 2048, 32, (4, 1)),      # the dense cell: 32 KV heads, bf16
        (2**15, 128, 32, (32, 16)),     # the hybrid cell: 2 KV heads
        (2**17, 512, 32, (16, 4)),      # 8 KV heads
        (2**20, 2048, 32, (2, 1)),      # the dense cell's block in float32
        (2**15, 128, 5, (5, 5)),        # a table shorter than a product
        (2**25, 8192, 4, (1, 1)),       # a block larger than the buffers
    ])
    def test_step_sizes_follow_the_block(self, block_bytes, rows, mb, want):
        assert rpa._blocks_per_step(block_bytes, rows, mb) == want

    @pytest.mark.parametrize("entry", ENTRIES)
    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    def test_operands_reach_the_mxu_as_stored(self, entry, dtype):
        """Both products of every kernel take their operands in q's dtype
        (the pool's own; int8 codes are exact in it) and accumulate in
        float32; bf16 names the one-pass product, float32 keeps the
        process-wide `highest`."""
        import jax
        from jax import lax
        q, kp, vp, tables, lens = _random_case(4, 2, 16, 8, 3, 2, dtype)
        jaxpr = jax.make_jaxpr(
            lambda *a: _entry_point(entry, *a)[0])(q, kp, vp, tables, lens)
        kernels = list(_eqns(jaxpr.jaxpr, "pallas_call"))
        assert kernels
        want = lax.Precision.DEFAULT if dtype == "bfloat16" \
            else lax.Precision.HIGHEST
        for kernel in kernels:
            dots = list(_eqns(kernel.params["jaxpr"], "dot_general"))
            assert len(dots) == 2       # scores, weighted values: no more
            for dot in dots:
                lhs, rhs = (v.aval.dtype for v in dot.invars)
                assert lhs == rhs == q.dtype
                assert dot.outvars[0].aval.dtype == np.float32
                assert set(dot.params["precision"]) == {want}


class TestKernelEquivalence:
    @pytest.mark.parametrize("nh,nkv", [(4, 4), (4, 2), (8, 1)])
    @pytest.mark.parametrize("bs", [8, 16])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_matches_dense_gather(self, nh, nkv, bs, dtype):
        import jax
        q, kp, vp, tables, lens = _random_case(
            nh, nkv, 16, bs, 4, 5, dtype)
        out = jax.jit(ragged_paged_attention)(q, kp, vp, tables, lens)
        ref = _dense_reference(q, kp, vp, tables, lens, nh, nkv)
        tol = 1e-2 if dtype == "bfloat16" else 1e-5
        assert np.abs(np.asarray(out, np.float32) - ref).max() < tol

    def test_raggedness_extremes(self):
        """Every boundary position: empty context (pos 0), last lane of
        a block, first lane of a block, full window."""
        import jax
        bs, mb = 8, 4
        lens = [0, bs - 1, bs, 2 * bs + 3, mb * bs - 1]
        q, kp, vp, tables, lens = _random_case(
            4, 2, 16, bs, mb, len(lens), "float32", lens=lens)
        out = jax.jit(ragged_paged_attention)(q, kp, vp, tables, lens)
        ref = _dense_reference(q, kp, vp, tables, lens, 4, 2)
        assert np.abs(np.asarray(out) - ref).max() < 1e-5

    def test_inside_jit_scan(self):
        """The serving engine calls the kernel inside lax.scan (layer
        loop) inside jit — the scalar-prefetch machinery must survive
        that nesting."""
        import jax
        import jax.numpy as jnp
        q, kp, vp, tables, lens = _random_case(4, 2, 16, 8, 3, 4,
                                               "float32")

        @jax.jit
        def stacked(q, kp, vp):
            def body(c, _):
                return c + ragged_paged_attention(q, kp, vp, tables,
                                                  lens), None
            out, _ = jax.lax.scan(body, jnp.zeros_like(q), None, length=3)
            return out

        out = stacked(q, kp, vp)
        ref = 3 * _dense_reference(q, kp, vp, tables, lens, 4, 2)
        assert np.abs(np.asarray(out) - ref).max() < 1e-4


class TestNeverReadsPastSeqLens:
    @pytest.mark.parametrize("entry", ["plain", "partials"])
    @pytest.mark.parametrize("group,chunk", [(2, 1), (4, 2), (4, 4)])
    def test_poisoned_blocks_never_influence_output(self, entry, group,
                                                    chunk, monkeypatch):
        """Property: every pool block not reachable through (tables,
        seq_lens) is NaN-poisoned; a single out-of-window fetch that
        fed compute would propagate NaN into the output. The poisoned
        ones include the dead members of a live DMA group (3 live
        blocks in groups of 2 and 4), the dead blocks a product of 2 or
        4 covers behind its live ones, and block 0, which every zero
        table entry behind them names."""
        import jax.numpy as jnp
        nh, nkv, hd, bs, mb, S = 4, 2, 16, 8, 4, 3
        _steer_step(monkeypatch, group, chunk, bs, nkv, hd, 4)
        assert rpa._blocks_per_step(bs * nkv * hd * 4, bs * nkv,
                                    mb) == (group, chunk)
        nb = S * mb + 1
        kp = RNG.standard_normal((nb, bs, nkv, hd)).astype(np.float32)
        vp = RNG.standard_normal((nb, bs, nkv, hd)).astype(np.float32)
        q = jnp.asarray(RNG.standard_normal((S, nh, hd)), jnp.float32)
        lens = np.asarray([3, 17, 20], np.int32)
        tables = np.zeros((S, mb), np.int32)
        needed = lens // bs + 1
        used, nxt = set(), 1
        for s in range(S):
            for j in range(needed[s]):
                tables[s, j] = nxt
                used.add(nxt)
                nxt += 1
        for b in range(nb):
            if b not in used:          # includes the trash block 0 and
                kp[b] = np.nan         # every block past each seq_len
                vp[b] = np.nan
        out, _, _ = _entry_point(
            entry, q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
            jnp.asarray(lens))
        out = np.asarray(out)
        assert np.isfinite(out).all(), "out-of-window block was read"
        # and the result is still the correct attention over the live
        # prefix (poison the reference identically: it only gathers
        # allocated entries when we slice to the live window)
        clean_k = np.nan_to_num(kp)
        clean_v = np.nan_to_num(vp)
        ref = _dense_reference(q, clean_k, clean_v, tables, lens, nh, nkv)
        assert np.abs(out - ref).max() < 1e-5

    def test_skipped_block_counter_accounts_for_early_exit(self):
        obs.registry().reset()
        obs.enable()
        try:
            bs, mb, nkv, hd = 8, 4, 2, 16
            lens = np.asarray([0, 9, 31])      # needed = 1, 2, 4 blocks
            record_ragged_step(lens, mb, bs, nkv, hd, itemsize=4,
                               layers=2, steps=1)
            reg = obs.registry()
            att = reg.counter(
                "paddle_tpu_ragged_attn_blocks_attended_total").value()
            skp = reg.counter(
                "paddle_tpu_ragged_attn_blocks_skipped_total").value()
            assert att == 2 * (1 + 2 + 4)
            assert skp == 2 * (3 * mb - (1 + 2 + 4))
            rb = reg.counter(
                "paddle_tpu_ragged_attn_hbm_bytes_total").value()
            db = reg.counter(
                "paddle_tpu_ragged_attn_dense_hbm_bytes_total").value()
            assert rb == 2 * ragged_hbm_bytes(lens, bs, nkv, hd, 4)
            assert db == 2 * dense_gather_hbm_bytes(3, mb, bs, nkv, hd, 4)
            assert rb < db
        finally:
            obs.disable()
            obs.registry().reset()


    def test_grid_step_counters_at_the_dense_cells_shapes(self):
        """32 slots at 128-1,100 tokens of 32 blocks a sequence: about
        one table entry in three names a live block. A launch is one
        grid step a slot, all live, and waits for a DMA group every 4
        blocks."""
        obs.registry().reset()
        obs.enable()
        try:
            bs, mb, nkv, hd = 64, 32, 32, 128
            lens = np.random.default_rng(3).integers(128, 1100, 32)
            needed = lens // bs + 1
            assert 0.25 < needed.sum() / (32 * mb) < 0.4
            record_ragged_step(lens, mb, bs, nkv, hd, itemsize=2,
                               layers=8, steps=1)
            reg = obs.registry()

            def read(name):
                return reg.counter(f"paddle_tpu_ragged_attn_{name}").value()
            assert read("grid_steps_total") == 8 * 32
            assert read("grid_steps_live_total") == 8 * 32
            assert read("dma_groups_total") == 8 * int(
                (-(-needed // 4)).sum())
            assert read("blocks_attended_total") == 8 * int(needed.sum())
            # 2 KV heads: a sequence's 32 blocks are one group
            obs.registry().reset()
            record_ragged_step(lens, mb, bs, 2, hd, itemsize=2, steps=2)
            assert read("grid_steps_total") == 2 * 32
            assert read("dma_groups_total") == 2 * 32
        finally:
            obs.disable()
            obs.registry().reset()


class TestServeParity:
    def _model(self):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        pt.seed(5)
        m = LlamaForCausalLM(LlamaConfig(
            vocab_size=97, hidden_size=64, intermediate_size=128,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            use_flash_attention=False, dtype="float32"))
        m.eval()
        return m

    def test_serve_matches_oracle_with_ragged_kernel(self):
        """End-to-end continuous batching through the fused kernel:
        every mixed-length stream matches its full-forward oracle
        exactly (greedy argmax survives the kernel's block-wise online
        softmax)."""
        from paddle_tpu.models.paged_decode import PagedDecoder
        model = self._model()
        dec = PagedDecoder(model, max_len=64, block_size=16, max_slots=4,
                           num_blocks=17, ragged_kernel=True)
        assert dec.use_ragged_kernel
        prompts = {f"r{i}": [int(t) for t in RNG.integers(0, 97, ln)]
                   for i, ln in enumerate((3, 9, 14, 6))}
        out = dec.serve(list(prompts.items()), max_new_tokens=10)
        for rid, prompt in prompts.items():
            ids = pt.to_tensor(np.asarray(prompt)[None])
            ref = model.generate(ids, max_new_tokens=10)
            ref = [int(t) for t in ref.numpy()[0, len(prompt):]]
            assert out[rid] == ref, rid

    def test_serve_records_ragged_telemetry(self):
        from paddle_tpu.models.paged_decode import PagedDecoder
        model = self._model()
        obs.registry().reset()
        obs.enable()
        try:
            dec = PagedDecoder(model, max_len=64, block_size=16,
                               max_slots=2, num_blocks=9,
                               ragged_kernel=True)
            dec.serve([("a", [1, 2, 3])], max_new_tokens=6, chunk=4)
            reg = obs.registry()
            calls = reg.counter(
                "paddle_tpu_ragged_attn_calls_total").value()
            assert calls > 0
            rb = reg.counter(
                "paddle_tpu_ragged_attn_hbm_bytes_total").value()
            db = reg.counter(
                "paddle_tpu_ragged_attn_dense_hbm_bytes_total").value()
            assert 0 < rb < db
        finally:
            obs.disable()
            obs.registry().reset()


class TestAutotune:
    def test_tune_ragged_blocks_caches_winner(self):
        from paddle_tpu.kernels.autotune import (
            AutoTuneCache, lookup_ragged_blocks, tune_ragged_blocks)
        cache = AutoTuneCache.instance()
        key_args = (4, 2, 16, "float32")
        cache._store.pop(("ragged_blocks",
                          (4, 2, 16, "float32")), None)
        best = tune_ragged_blocks(4, 2, 16, dtype="float32", max_len=64,
                                  slots=2, candidates=(16, 32))
        assert best in (16, 32)
        assert lookup_ragged_blocks(*key_args) == best
        # the decoder consults the cached winner for block_size="auto"
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.models.paged_decode import PagedDecoder
        pt.seed(5)
        m = LlamaForCausalLM(LlamaConfig(
            vocab_size=97, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            use_flash_attention=False, dtype="float32"))
        m.eval()
        dec = PagedDecoder(m, max_len=64, block_size="auto", max_slots=2)
        assert dec.block_size == best
