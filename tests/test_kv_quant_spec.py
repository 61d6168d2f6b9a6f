"""int8 paged KV cache + speculative decoding (ISSUE 13 tentpole).

Oracles, in strength order:

- the per-row codec's DOCUMENTED error bound (|dequant - x| <= amax/254
  per element — half an int8 step at scale amax/127),
- the dense-gather reference computed over the DEQUANTIZED pool: the
  quantized kernel must match it to fp tolerance (identical math, so a
  wrong scale row or block read shows up as a gross diff, not noise),
- NaN-poisoned codec scales for the never-reads-past-seq_lens property
  (int8 codes cannot hold NaN; the f32 scales can, and one out-of-window
  dequant would poison the output),
- plain greedy decode for speculative decoding: greedy verification
  must be exactly token-identical — the draft changes speed, never
  tokens.
"""
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.observability as obs
from paddle_tpu.kernels.pallas.ragged_paged_attention import (
    kv_dequantize_rows, kv_quantize_rows, kv_row_error_bound,
    ragged_paged_attention_quant)

RNG = np.random.default_rng(41)


def _dense_reference(q, kw, vw, lens, nh, nkv):
    """Dense-gather attention math in numpy/f32 over ALREADY-GATHERED
    (and, for quantized pools, already-dequantized) windows
    kw/vw [S, W, nkv, hd]."""
    S, W = kw.shape[0], kw.shape[1]
    hd = q.shape[-1]
    nrep = nh // nkv
    scale = 1.0 / np.sqrt(hd)
    qg = np.asarray(q, np.float32).reshape(S, nkv, nrep, hd)
    att = np.einsum("bgnd,bwgd->bgnw", qg, np.asarray(kw, np.float32))
    att *= scale
    mask = np.arange(W)[None] <= np.asarray(lens)[:, None]
    att = np.where(mask[:, None, None, :], att, -1e30)
    p = np.exp(att - att.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    o = np.einsum("bgnw,bwgd->bgnd", p, np.asarray(vw, np.float32))
    return o.reshape(S, nh, hd)


def _quant_case(nh, nkv, hd, bs, mb, S, dtype="float32", lens=None):
    import jax.numpy as jnp
    nb = S * mb + 1
    kf = RNG.standard_normal((nb, bs, nkv, hd)).astype(np.float32)
    vf = RNG.standard_normal((nb, bs, nkv, hd)).astype(np.float32)
    q = jnp.asarray(RNG.standard_normal((S, nh, hd)), dtype)
    kc, ks = kv_quantize_rows(jnp.asarray(kf))
    vc, vs = kv_quantize_rows(jnp.asarray(vf))
    perm = RNG.permutation(nb - 1)[:S * mb] + 1
    tables = jnp.asarray(perm.reshape(S, mb), jnp.int32)
    if lens is None:
        lens = RNG.integers(0, mb * bs, S)
    lens = jnp.asarray(np.asarray(lens), jnp.int32)
    return q, kf, vf, kc, ks, vc, vs, tables, lens


def _tiny(dtype="float32", **kw):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    cfg = dict(vocab_size=97, hidden_size=64, intermediate_size=128,
               num_hidden_layers=3, num_attention_heads=4,
               num_key_value_heads=2, max_position_embeddings=128,
               use_flash_attention=False, dtype=dtype)
    cfg.update(kw)
    pt.seed(5)
    m = LlamaForCausalLM(LlamaConfig(**cfg))
    m.eval()
    return m


class TestCodec:
    def test_round_trip_within_documented_bound(self):
        """The contract the README documents: per-element reconstruction
        error <= amax_row / 254, rows of zeros exact."""
        x = RNG.standard_normal((7, 5, 2, 16)).astype(np.float32) * 3
        x[2, 1] = 0.0                       # a zero row stays exact
        codes, scales = kv_quantize_rows(np.asarray(x))
        back = np.asarray(kv_dequantize_rows(codes, scales))
        bound = kv_row_error_bound(x)
        err = np.abs(back - x).max(axis=(-2, -1))
        assert (err <= bound + 1e-7).all(), (err, bound)
        assert np.abs(back[2, 1]).max() == 0
        assert np.asarray(codes).dtype == np.int8
        assert np.asarray(scales).dtype == np.float32

    def test_wire_bytes_accounting(self):
        """ragged_hbm_bytes with codes+scales vs the bf16 pool: the
        quantized wire must bill (nkv*hd + 4) per token against bf16's
        2*nkv*hd — under the 0.6 gate for every real head_dim."""
        from paddle_tpu.kernels.pallas.ragged_paged_attention import (
            ragged_hbm_bytes)
        lens = np.asarray([0, 9, 31])
        for nkv, hd in ((2, 16), (8, 128), (1, 64)):
            qb = ragged_hbm_bytes(lens, 8, nkv, hd, 1, scale_bytes=4)
            bf = ragged_hbm_bytes(lens, 8, nkv, hd, 2)
            assert qb / bf == (nkv * hd + 4) / (2 * nkv * hd)
            assert qb / bf < 0.6


class TestQuantKernelEquivalence:
    @pytest.mark.parametrize("nh,nkv", [(4, 4), (4, 2), (8, 1)])
    @pytest.mark.parametrize("bs", [8, 16])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_matches_dequantized_dense_reference(self, nh, nkv, bs,
                                                 dtype):
        """The quantized kernel computes EXACTLY dense attention over
        the dequantized pool — in-kernel dequant after the fetch is a
        layout choice, not a numerics change."""
        import jax
        q, kf, vf, kc, ks, vc, vs, tables, lens = _quant_case(
            nh, nkv, 16, bs, 4, 5, dtype)
        out = jax.jit(ragged_paged_attention_quant)(
            q, kc, ks, vc, vs, tables, lens)
        kw = np.asarray(kv_dequantize_rows(kc, ks))[np.asarray(tables)]
        vw = np.asarray(kv_dequantize_rows(vc, vs))[np.asarray(tables)]
        S = q.shape[0]
        kw = kw.reshape(S, -1, nkv, 16)
        vw = vw.reshape(S, -1, nkv, 16)
        ref = _dense_reference(q, kw, vw, lens, nh, nkv)
        tol = 1e-2 if dtype == "bfloat16" else 1e-5
        assert np.abs(np.asarray(out, np.float32) - ref).max() < tol

    def test_close_to_full_precision_within_codec_envelope(self):
        """vs the UNQUANTIZED reference the error is the codec's, and it
        stays inside an envelope derived from the documented per-row
        bound (values bounded by softmax-convexity: the output is a
        convex combination of V rows, each off by <= its row bound, plus
        a score-perturbation term)."""
        q, kf, vf, kc, ks, vc, vs, tables, lens = _quant_case(
            4, 2, 16, 8, 4, 5)
        import jax
        out = np.asarray(jax.jit(ragged_paged_attention_quant)(
            q, kc, ks, vc, vs, tables, lens), np.float32)
        kw = kf[np.asarray(tables)].reshape(5, -1, 2, 16)
        vw = vf[np.asarray(tables)].reshape(5, -1, 2, 16)
        ref = _dense_reference(q, kw, vw, lens, 4, 2)
        v_bound = kv_row_error_bound(vf).max()
        # convex-combination term + a generous score-shift term (scores
        # move by <= |q| * k_bound / sqrt(hd) per lane, reweighting
        # within the V range); standard-normal inputs keep both small
        envelope = v_bound + 8.0 * kv_row_error_bound(kf).max()
        assert np.abs(out - ref).max() < envelope, (
            np.abs(out - ref).max(), envelope)

    def test_raggedness_extremes(self):
        import jax
        bs, mb = 8, 4
        lens = [0, bs - 1, bs, 2 * bs + 3, mb * bs - 1]
        q, kf, vf, kc, ks, vc, vs, tables, lens = _quant_case(
            4, 2, 16, bs, mb, len(lens), lens=lens)
        out = jax.jit(ragged_paged_attention_quant)(
            q, kc, ks, vc, vs, tables, lens)
        kw = np.asarray(kv_dequantize_rows(kc, ks))[np.asarray(tables)]
        vw = np.asarray(kv_dequantize_rows(vc, vs))[np.asarray(tables)]
        S = q.shape[0]
        ref = _dense_reference(q, kw.reshape(S, -1, 2, 16),
                               vw.reshape(S, -1, 2, 16), lens, 4, 2)
        assert np.abs(np.asarray(out) - ref).max() < 1e-5


class TestNeverReadsPastSeqLens:
    def test_poisoned_scales_never_influence_output(self):
        """int8 codes can't carry NaN — the f32 SCALES can. Every pool
        block not reachable through (tables, seq_lens) gets NaN scales
        and saturated codes; one out-of-window fetch that fed the
        dequant would poison the output."""
        import jax
        import jax.numpy as jnp
        nh, nkv, hd, bs, mb, S = 4, 2, 16, 8, 4, 3
        nb = S * mb + 1
        kf = RNG.standard_normal((nb, bs, nkv, hd)).astype(np.float32)
        vf = RNG.standard_normal((nb, bs, nkv, hd)).astype(np.float32)
        kc, ks = (np.asarray(a) for a in kv_quantize_rows(jnp.asarray(kf)))
        vc, vs = (np.asarray(a) for a in kv_quantize_rows(jnp.asarray(vf)))
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
        ks, vs = ks.copy(), vs.copy()
        kc, vc = kc.copy(), vc.copy()
        for b in range(nb):
            if b not in used:          # the trash block and every block
                ks[b] = np.nan         # past each seq_len
                vs[b] = np.nan
                kc[b] = 127
                vc[b] = 127
        out = np.asarray(jax.jit(ragged_paged_attention_quant)(
            q, jnp.asarray(kc), jnp.asarray(ks), jnp.asarray(vc),
            jnp.asarray(vs), jnp.asarray(tables), jnp.asarray(lens)))
        assert np.isfinite(out).all(), "out-of-window block was read"
        # and still the correct attention over the live prefix
        clean_ks = np.nan_to_num(ks, nan=1.0)
        clean_vs = np.nan_to_num(vs, nan=1.0)
        kw = (kc.astype(np.float32)
              * clean_ks[..., None, None])[tables].reshape(S, -1, nkv, hd)
        vw = (vc.astype(np.float32)
              * clean_vs[..., None, None])[tables].reshape(S, -1, nkv, hd)
        ref = _dense_reference(q, kw, vw, lens, nh, nkv)
        assert np.abs(out - ref).max() < 1e-5


class TestQuantServe:
    def test_quant_ragged_serve_matches_quant_dense_serve(self):
        """End-to-end parity of the two quantized paths: the in-kernel
        dequant Pallas path and the dense dequantized-gather reference
        must emit identical greedy streams from identical state — a
        wrong scale-row fetch would diverge the argmax."""
        from paddle_tpu.models.paged_decode import PagedDecoder
        model = _tiny()
        prompts = {f"r{i}": [int(t) for t in RNG.integers(0, 97, ln)]
                   for i, ln in enumerate((3, 9, 14, 6))}
        outs = {}
        for ragged in (False, True):
            dec = PagedDecoder(model, max_len=64, block_size=16,
                               max_slots=4, num_blocks=17,
                               kv_quant="int8", ragged_kernel=ragged)
            outs[ragged] = dec.serve(list(prompts.items()),
                                     max_new_tokens=10, chunk=4)
        assert outs[True] == outs[False]
        # quantization is an approximation of the fp serve, not a
        # repaint: streams must still be near the fp oracle (tiny model,
        # short horizon — argmax flips stay rare)
        dec = PagedDecoder(model, max_len=64, block_size=16,
                           max_slots=4, num_blocks=17)
        fp = dec.serve(list(prompts.items()), max_new_tokens=10, chunk=4)
        agree = sum(a == b for r in fp
                    for a, b in zip(fp[r], outs[True][r]))
        total = sum(len(v) for v in fp.values())
        assert agree / total > 0.8, (agree, total)

    def test_pool_and_guard_accounting_uses_quantized_bytes(self):
        """Satellite gate: pool sizing / guard admission must price the
        int8 footprint — same guard limit, proportionally more blocks."""
        from paddle_tpu.models.paged_decode import PagedDecoder
        model = _tiny()
        dec_fp = PagedDecoder(model, max_len=64, block_size=16,
                              max_slots=2, num_blocks=9)
        dec_q = PagedDecoder(model, max_len=64, block_size=16,
                             max_slots=2, num_blocks=9, kv_quant="int8")
        nkv, hd = dec_q.nkv, dec_q.hd
        want_tok = nkv * hd + 4            # int8 codes + one f32 scale
        L, bs = model.config.num_hidden_layers, 16
        assert dec_q.bytes_per_block() == 2 * L * bs * want_tok
        assert dec_q.pool_bytes() == 2 * L * 9 * bs * want_tok
        # vs a bf16 pool of the same geometry: strictly under the 0.6
        # wire gate (f32's ratio is half that again)
        bf16_tok = nkv * hd * 2
        assert want_tok / bf16_tok < 0.6
        assert dec_q.pool_bytes() < dec_fp.pool_bytes()

    def test_hbm_telemetry_prices_quantized_wire(self):
        """The bench_smoke kv_hbm_bytes_ratio gate's substrate: the
        ragged counters bill codes+scales for an int8 pool, and the
        bf16-equivalent counter prices the same fetches at bf16 — the
        ratio is exact arithmetic, (nkv*hd + 4) / (2*nkv*hd)."""
        from paddle_tpu.models.paged_decode import PagedDecoder
        model = _tiny()
        obs.registry().reset()
        obs.enable()
        try:
            dec = PagedDecoder(model, max_len=64, block_size=16,
                               max_slots=2, num_blocks=9,
                               kv_quant="int8", ragged_kernel=True)
            dec.serve([("a", [1, 2, 3])], max_new_tokens=6, chunk=4)
            reg = obs.registry()
            rb = reg.counter(
                "paddle_tpu_ragged_attn_hbm_bytes_total").value()
            bf = reg.counter(
                "paddle_tpu_ragged_attn_hbm_bytes_bf16eq_total").value()
            assert rb > 0 and bf > 0
            want = (dec.nkv * dec.hd + 4) / (2 * dec.nkv * dec.hd)
            assert abs(rb / bf - want) < 1e-9
            assert rb / bf < 0.6
        finally:
            obs.disable()
            obs.registry().reset()


class TestSpeculativeDecode:
    def test_greedy_spec_is_token_identical_to_plain_decode(self):
        """THE spec-decode contract (tier-1 acceptance gate): greedy
        verification emits exactly the plain-decode stream across
        mixed-length prompts, heterogeneous budgets and continuous
        batching — for both the n-gram self-draft and a draft length
        that overshoots some budgets."""
        from paddle_tpu.models.paged_decode import PagedDecoder
        model = _tiny()
        prompts = {f"r{i}": [int(t) for t in RNG.integers(0, 97, ln)]
                   for i, ln in enumerate((4, 11, 7, 14, 5))}
        budgets = {"r0": 2, "r1": 13, "r2": 5, "r3": 9, "r4": 7}
        reqs = [(rid, p, budgets[rid]) for rid, p in prompts.items()]
        dec = PagedDecoder(model, max_len=64, block_size=16, max_slots=2,
                           num_blocks=9)
        plain = dec.serve(list(reqs), chunk=8)
        for k in (1, 4):
            dec_s = PagedDecoder(model, max_len=64, block_size=16,
                                 max_slots=2, num_blocks=9)
            spec = dec_s.serve(list(reqs), spec_decode=k)
            assert spec == plain, f"k={k}"
            st = dec_s.spec_stats
            assert st["verify_calls"] > 0
            assert 0 <= st["accepted"] <= st["proposed"]
            # each request's FIRST token comes from prefill, the rest
            # from verify passes
            assert st["emitted"] == sum(len(v) for v in spec.values()) \
                - len(reqs)
            # one verify executable per draft length
            assert dec_s._spec_verify_jit._cache_size() == 1

    def test_spec_identity_with_eos_and_quant(self):
        """Spec + eos masking + int8 pool compose: identical output to
        the plain quantized serve, including the post-eos pad tail."""
        from paddle_tpu.models.paged_decode import PagedDecoder
        model = _tiny()
        p0 = [int(t) for t in RNG.integers(0, 97, 5)]
        p1 = [int(t) for t in RNG.integers(0, 97, 9)]
        probe = PagedDecoder(model, max_len=64, block_size=16,
                             max_slots=2, num_blocks=9, kv_quant="int8")
        free_run = probe.serve([("a", p0), ("b", p1)], max_new_tokens=10)
        eos = free_run["a"][3]
        plain = PagedDecoder(model, max_len=64, block_size=16,
                             max_slots=2, num_blocks=9,
                             kv_quant="int8").serve(
            [("a", p0), ("b", p1)], max_new_tokens=10,
            eos_token_id=eos, pad_token_id=0, chunk=4)
        spec = PagedDecoder(model, max_len=64, block_size=16,
                            max_slots=2, num_blocks=9,
                            kv_quant="int8").serve(
            [("a", p0), ("b", p1)], max_new_tokens=10,
            eos_token_id=eos, pad_token_id=0, spec_decode=3)

        # the VISIBLE stream (tokens through the first eos, pad after)
        # must agree exactly; raw lengths may differ because the plain
        # chunk overshoots eos to its chunk boundary while a verify
        # pass retires at the eos it just emitted — both tails are pad
        def canon(toks):
            return toks[:toks.index(eos) + 1] if eos in toks else toks

        for rid in plain:
            assert canon(spec[rid]) == canon(plain[rid]), rid
            cut = len(canon(spec[rid]))
            assert all(t == 0 for t in spec[rid][cut:])
            assert all(t == 0 for t in plain[rid][cut:])

    def test_model_draft_hook_accepts_its_own_predictions(self):
        """The small-draft-model hook behind the same interface: using
        the TARGET as its own draft makes every proposal the target's
        own argmax — near-total acceptance, identical stream."""
        from paddle_tpu.models.paged_decode import PagedDecoder
        from paddle_tpu.models.spec_decode import ModelDraft, SpecConfig
        model = _tiny()
        prompt = [int(t) for t in RNG.integers(0, 97, 6)]
        dec = PagedDecoder(model, max_len=64, block_size=16, max_slots=2,
                           num_blocks=9)
        plain = dec.serve([("a", prompt)], max_new_tokens=12)
        dec_s = PagedDecoder(model, max_len=64, block_size=16,
                             max_slots=2, num_blocks=9)
        spec = dec_s.serve(
            [("a", prompt)], max_new_tokens=12,
            spec_decode=SpecConfig(k=3, draft=ModelDraft(model)))
        assert spec == plain
        st = dec_s.spec_stats
        assert st["accepted"] / st["proposed"] > 0.5, st

    def test_accept_rate_counters_live_in_registry(self):
        from paddle_tpu.models.paged_decode import PagedDecoder
        model = _tiny()
        obs.registry().reset()
        obs.enable()
        try:
            dec = PagedDecoder(model, max_len=64, block_size=16,
                               max_slots=2, num_blocks=9)
            dec.serve([("a", [1, 2, 3, 4])], max_new_tokens=8,
                      spec_decode=2)
            reg = obs.registry()
            calls = reg.counter(
                "paddle_tpu_spec_decode_verify_calls_total").value()
            prop = reg.counter(
                "paddle_tpu_spec_decode_proposed_total").value()
            acc = reg.counter(
                "paddle_tpu_spec_decode_accepted_total").value()
            assert calls > 0
            assert prop == 2 * calls        # k per live slot per call
            assert 0 <= acc <= prop
        finally:
            obs.disable()
            obs.registry().reset()

    def test_ngram_draft_prompt_lookup(self):
        from paddle_tpu.models.spec_decode import NGramDraft
        d = NGramDraft(max_ngram=3)
        # trailing bigram (7, 8) occurred earlier, followed by 9, 10
        assert d.propose([7, 8, 9, 10, 5, 7, 8], 2) == [9, 10]
        # no match: repeat the last token
        assert d.propose([1, 2, 3], 2) == [3, 3]
        assert d.propose([], 3) == [0, 0, 0]
        # continuation shorter than k pads with the last history token
        assert d.propose([4, 6, 4], 3) == [6, 4, 4]


class TestAutotune:
    def test_tune_kv_quant_blocks_caches_winner(self):
        from paddle_tpu.kernels.autotune import (
            AutoTuneCache, lookup_kv_quant_blocks, tune_kv_quant_blocks)
        cache = AutoTuneCache.instance()
        cache._store.pop(("kv_quant_blocks", (4, 2, 16, "float32")), None)
        best = tune_kv_quant_blocks(4, 2, 16, dtype="float32",
                                    max_len=64, slots=2,
                                    candidates=(16, 32))
        assert best in (16, 32)
        assert lookup_kv_quant_blocks(4, 2, 16, "float32") == best
        # block_size="auto" on a QUANTIZED decoder consults this cache,
        # not the unquantized kernel's
        from paddle_tpu.models.paged_decode import PagedDecoder
        model = _tiny(num_hidden_layers=2)
        dec = PagedDecoder(model, max_len=64, block_size="auto",
                           max_slots=2, kv_quant="int8")
        assert dec.block_size == best

    def test_tune_spec_decode_caches_winner(self):
        from paddle_tpu.kernels.autotune import (
            AutoTuneCache, lookup_spec_decode, tune_spec_decode)
        model = _tiny(num_hidden_layers=2)
        cfg = model.config
        key_args = (cfg.hidden_size, cfg.num_hidden_layers, 4, 2, 16,
                    cfg.vocab_size, cfg.dtype)
        AutoTuneCache.instance()._store.pop(
            ("spec_decode", (*key_args, 0.6)), None)
        best = tune_spec_decode(model, accept_prob=0.6,
                                candidates=(2, 3), max_len=64,
                                block_size=16, slots=2, iters=1)
        assert best in (2, 3)
        assert lookup_spec_decode(*key_args) == best
        # serve(spec_decode="auto") consults the cached winner
        from paddle_tpu.models.paged_decode import PagedDecoder
        from paddle_tpu.models.spec_decode import resolve_spec
        dec = PagedDecoder(model, max_len=64, block_size=16, max_slots=2,
                           num_blocks=9)
        spec_cfg, _ = resolve_spec("auto", dec)
        assert spec_cfg.k == best
