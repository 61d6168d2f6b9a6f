"""The `mimo_v2_flash` family (window and full attention mixed, each kind
with its own KV head count, K rows wider than V rows, a learned sink,
sparse SwiGLU experts) against its plain reference, on the CPU at toy
widths with seeded weights: the whole forward, a prompt prefilled in
chunks and then decoded through the two kinds of cache past the window
and across blocks, the serve loop, slot reuse over a poisoned ring, the
sixteen shares of one expert layer, the kernels in interpret mode, the
options that refuse, and the other engines' programs, which must lower
to the text they lowered to at the parent commit.

The reference (`chipbench/reference/mimo_v2.py`) is float32 `highest`,
one sequence, one head and one expert at a time, and imports nothing of
the program.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.adapters import mimo_v2 as adapter
from chipbench.reference import mimo_v2 as ref
from paddle_tpu.models import mimo_v2 as mm
from paddle_tpu.models.paged_decode import PagedDecoder

F32 = jnp.float32
CFG = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
           moe_intermediate_size=48, num_hidden_layers=5,
           hybrid_layer_pattern=[0, 1, 1, 0, 1],
           moe_layer_freq=[0, 1, 1, 1, 1], num_attention_heads=8,
           num_key_value_heads=2, head_dim=24, v_head_dim=16,
           swa_num_attention_heads=8, swa_num_key_value_heads=4,
           swa_head_dim=24, swa_v_head_dim=16, sliding_window=8,
           partial_rotary_factor=0.334, rope_theta=5000000,
           swa_rope_theta=10000, attention_value_scale=0.707,
           add_swa_attention_sink_bias=True,
           add_full_attention_sink_bias=False, n_routed_experts=8,
           experts_first=4, published={"n_routed_experts": 16},
           num_experts_per_tok=4, norm_topk_prob=True,
           routed_scaling_factor=None, layernorm_epsilon=1e-5,
           max_position_embeddings=256, initializer_range=0.16,
           torch_dtype="float32")
SEED = 2**31 + 29
TOL = 2e-5          # float32 against float32 `highest`, sums reordered
BLOCK, CHUNK = 4, 16


@pytest.fixture(scope="module")
def weights():
    """The reference's seeded leaves as `CFG` stores them, float32:
    program and reference compute on the same numbers."""
    return ref.make_weights(CFG, SEED)


@pytest.fixture(scope="module")
def model(weights):
    return adapter.build_model(CFG, weights)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], n).astype(np.int32)


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())


def _decoder(model, slots=2, **kw):
    kw.setdefault("prefill_chunk", CHUNK)
    return PagedDecoder(model, max_len=64, block_size=BLOCK, num_blocks=49,
                        max_slots=slots, **kw)


# -- the whole forward ----------------------------------------------------------------

@pytest.mark.parametrize("length", [1, 8, 9, 37])
def test_forward_logits_match_the_reference(model, weights, length):
    ids = _ids(length)
    got = model.forward(ids[None])._data[0]
    want = ref.logits_at(CFG, weights, jnp.asarray(ids), jnp.arange(length))
    _close(got, want)


def test_rotary_term_turns_the_first_dims_only():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(5, 2, 24)), F32)
    pos = jnp.asarray([0, 1, 7, 100, 4000], jnp.int32)
    got = mm.rope(x, pos, 8, 1e4)
    _close(got[..., 8:], x[..., 8:], 0)
    _close(got[0], x[0], 0)                       # position 0 turns nothing
    _close(jnp.sum(got * got, -1), jnp.sum(x * x, -1))
    want = ref.rotary(jnp.tile(x[:1], (8, 1, 1)), 8, 1e4)
    _close(mm.rope(jnp.tile(x[:1], (8, 1, 1)), jnp.arange(8), 8, 1e4), want)


# -- the engine and its two kinds of cache -----------------------------------------

def test_paged_decoder_builds_the_window_engine(model):
    dec = _decoder(model)
    assert isinstance(dec, mm.WindowPagedDecoder)
    kf, vf, kw, vw = dec.new_pools()
    # the paged pools hold the two full layers, 2 KV heads each
    # (a block's rows are its tokens and KV heads merged)
    assert kf.shape == (2, 49, BLOCK * 2, 24) and vf.shape == (2, 49,
                                                               BLOCK * 2, 16)
    # the rings: window 8 = 2 blocks, and one of slack; a trash block
    assert dec.ring_blocks == 3 and dec.ring_tokens == 12
    assert kw.shape == (3, 1 + 2 * 3, BLOCK * 4, 24)
    assert vw.shape == (3, 1 + 2 * 3, BLOCK * 4, 16)
    assert dec.full_token_bytes == 2 * 2 * (24 + 16) * 4
    assert dec.slot_window_bytes == 3 * 12 * 4 * (24 + 16) * 4
    assert dec.bytes_per_block() == BLOCK * dec.full_token_bytes
    assert dec.pool_bytes() == sum(p.size * 4 for p in (kf, vf, kw, vw))
    assert 2 * dec.kv_layers * dec.num_blocks * BLOCK \
        * dec.kv_token_bytes() == (kf.size + vf.size) * 4


def test_k_rows_wider_than_a_lane_are_stored_in_whole_lanes(model,
                                                            monkeypatch):
    """192 is a lane and a half: the pools keep such rows 256 wide, zeros
    behind (here 24 against lanes of 16: 32)."""
    monkeypatch.setattr(mm.WindowPagedDecoder, "LANES", 16)
    dec = _decoder(model)
    kf, vf, kw, vw = dec.new_pools()
    assert kf.shape[-1] == kw.shape[-1] == 32 and vf.shape[-1] == 16
    assert dec.full_token_bytes == 2 * 2 * (32 + 16) * 4


@pytest.mark.parametrize("engine", ["llama", "nemotron_h", "mimo_v2"])
def test_engine_is_picked_by_the_patterns_cache_kinds(model, engine):
    if engine == "mimo_v2":
        assert model.config.cache_kinds == ("full", "window", "window",
                                            "full", "window")
        assert type(_decoder(model)) is mm.WindowPagedDecoder
    elif engine == "nemotron_h":
        from paddle_tpu.models import nemotron_h as nh
        cfg = nh.nemotron_h_tiny(hybrid_override_pattern="M*E")
        assert cfg.cache_kinds == ("state", "kv", None)
        dec = PagedDecoder(nh.NemotronHForCausalLM(cfg), max_len=32,
                           block_size=8, max_slots=2)
        assert type(dec) is nh.HybridPagedDecoder
    else:
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny
        dec = PagedDecoder(LlamaForCausalLM(llama_tiny()), max_len=32,
                           block_size=8, max_slots=2)
        assert type(dec) is PagedDecoder


def _prefill(dec, pools, slot, prompt, tables):
    encs = []
    for head, tail in dec._prefill_calls(
            dec.prefill_chunk, [(slot, list(prompt), 0)], tables, 0):
        # not the donating program of `_prefill_exec`: a test may hand the
        # same pools to two prefills
        enc, *pools = _jitted(dec, "_prefill_paged")(
            dec._params, *head, *pools, *tail)
        encs.append(enc)
    return encs, pools


def _jitted(dec, name):
    if name not in dec.__dict__.setdefault("_test_jits", {}):
        dec._test_jits[name] = jax.jit(getattr(dec, name))
    return dec._test_jits[name]


def _tables(dec, slot, blocks):
    tables = np.zeros((dec.max_slots, dec.blocks_per_seq), np.int32)
    tables[slot, :len(blocks)] = blocks
    return tables


def _teacher_forced(dec, model, weights, ids, n_prompt, slot=1, pools=None):
    """Prefill ids[:n_prompt] into `slot`, then decode the rest one
    token a step: every step's logits against the reference's one full
    forward."""
    blocks = np.random.default_rng(3).permutation(np.arange(1, 49))[:16]
    tables = _tables(dec, slot, blocks)
    encs, pools = _prefill(dec, pools or dec.new_pools(), slot,
                           ids[:n_prompt], tables)
    want = np.asarray(ref.logits_at(CFG, weights, jnp.asarray(ids),
                                    jnp.arange(n_prompt - 1, len(ids))))
    assert dec.decode_first_token(encs) == (int(want[0].argmax()), False)
    active = jnp.arange(dec.max_slots) == slot
    got = []
    for step, token in enumerate(ids[n_prompt:]):
        tokens = jnp.zeros(dec.max_slots, jnp.int32).at[slot].set(token)
        lens = jnp.zeros(dec.max_slots, jnp.int32).at[slot].set(
            n_prompt + step)
        logits, *pools, counts = _jitted(dec, "_step")(
            dec._params, tokens, lens, jnp.asarray(tables), active, *pools)
        got.append(logits[slot])
        assert int(counts[1]) == 4 * 4        # pairs of the one active row
    return jnp.stack(got), want[1:], pools


@pytest.mark.parametrize("ragged", [False, True])
@pytest.mark.parametrize("n_prompt", [3, 16, 27])
def test_prefill_then_decode_gives_the_reference_logits(model, weights,
                                                        n_prompt, ragged):
    """A prompt shorter than the window, one that fills its chunk, and
    one of two chunks that ends inside a block; then 22 decode steps,
    which cross the window of 8 more than twice and five block
    boundaries, through the pages and the rings."""
    dec = _decoder(model, ragged_kernel=ragged)
    got, want, _ = _teacher_forced(dec, model, weights,
                                   _ids(n_prompt + 22, seed=9), n_prompt)
    _close(got, want)


def test_freed_slots_ring_never_leaks_into_the_next_request(model, weights):
    """A slot's rings and the trash blocks are poisoned with NaN behind
    the request that leaves; the next request in the slot gives what it
    gives alone."""
    dec = _decoder(model, ragged_kernel=True)
    _, _, pools = _teacher_forced(dec, model, weights, _ids(40, seed=2), 27)
    kf, vf, kw, vw = pools
    R = dec.ring_blocks
    ring = slice(1 + 1 * R, 1 + 2 * R)
    kw, vw = (p.at[:, ring].set(jnp.nan).at[:, 0].set(jnp.nan)
              for p in (kw, vw))
    for n_prompt in (5, 19):
        got, want, _ = _teacher_forced(
            dec, model, weights, _ids(n_prompt + 14, seed=11), n_prompt,
            pools=(kf, vf, kw, vw))
        _close(got, want)


def test_ring_view_names_the_live_blocks_and_no_other(model):
    dec = _decoder(model)
    slots = jnp.asarray([0, 1], jnp.int32)
    # position 3: one live block; 17: positions 10..17 lie in blocks 2, 3, 4
    tables, lens, lows = dec._ring_view(slots, jnp.asarray([3, 17]))
    assert tables[0, 0] == 1 + 0 and (lens[0], lows[0]) == (3, 0)
    assert list(tables[1]) == [1 + 3 + 2, 1 + 3 + 0, 1 + 3 + 1]
    assert (lens[1], lows[1]) == (17 - 8, 10 - 8)
    # no position before the window is in a block the kernel is handed
    assert int(lens[1]) // BLOCK + 1 == 3


# -- the serve loop -------------------------------------------------------------------

def _serve_requests():
    rng = np.random.default_rng(4)
    shapes = [(5, 9), (12, 20), (8, 3), (17, 11), (3, 17), (9, 6), (30, 25),
              (33, 14)]
    return [(rid, rng.integers(0, 256, n).tolist(), budget)
            for rid, (n, budget) in enumerate(shapes)]


@pytest.fixture(scope="module")
def served(model):
    dec = _decoder(model, slots=3)
    reqs = _serve_requests()
    return dec, reqs, dec.serve(reqs, max_new_tokens=25, chunk=4)


@pytest.mark.parametrize("rid", range(8))
def test_serve_tokens_are_the_reference_argmax(served, weights, rid):
    _, reqs, out = served
    _, prompt, budget = reqs[rid]
    assert len(out[rid]) == budget
    seq = np.asarray(prompt + out[rid], np.int32)
    logits = np.asarray(ref.logits_at(
        CFG, weights, jnp.asarray(seq),
        jnp.arange(len(prompt) - 1, len(seq) - 1)))
    picked = logits[np.arange(budget), out[rid]]
    assert (logits.max(-1) - picked).max() <= 1e-5


@pytest.mark.parametrize("rid", [3, 6, 7])
def test_reused_slot_gives_what_the_request_gives_alone(served, model, rid):
    _, reqs, out = served
    alone = _decoder(model, slots=3)
    assert alone.serve([reqs[rid]], max_new_tokens=25, chunk=4)[rid] \
        == out[rid]


@pytest.mark.parametrize("how", ["ragged_kernel", "pipelined_admission",
                                 "no_lookahead"])
def test_every_way_through_the_loop_serves_the_same_tokens(served, model,
                                                           how):
    _, reqs, want = served
    if how == "no_lookahead":
        got = _decoder(model, slots=3).serve(reqs, max_new_tokens=25,
                                             chunk=4, pipeline=False)
    else:
        got = _decoder(model, slots=3, **{how: True}).serve(
            reqs, max_new_tokens=25, chunk=4)
    assert got == want


def test_eos_cuts_a_lookahead_chunk_and_the_ring_rewinds(served, model):
    """With an eos the loop still runs look-ahead chunks here: steps
    written past the cut overwrite ring positions a rewound slot no
    longer attends (a chunk is no longer than a block), and every
    request gets what the serial loop gives it."""
    _, reqs, plain = served
    # a token that some request emits in the middle of a chunk
    eos = plain[6][9]
    kw = dict(max_new_tokens=25, chunk=4, eos_token_id=eos)
    a = _decoder(model, slots=3)
    got = a.serve(reqs, **kw)
    assert a.lookahead_dispatches > 0
    assert got == _decoder(model, slots=3).serve(reqs, pipeline=False, **kw)
    assert got[6][9] == eos and set(got[6][10:]) == {0}
    with pytest.raises(NotImplementedError, match="chunk of 8 steps beside"):
        a.serve(reqs, max_new_tokens=25, chunk=8, eos_token_id=eos)


def test_counters_ride_the_commit_and_admit_spans(model):
    from paddle_tpu.observability import tracing
    dec = _decoder(model, slots=3)
    reqs = _serve_requests()[:3]
    tracing.enable_tracing()
    try:
        tracing.drain()
        out = dec.serve(reqs, max_new_tokens=25, chunk=4)
        spans = tracing.drain()
    finally:
        tracing.disable_tracing()
    commits = [s["meta"] for s in spans if s["name"] == "serve:commit"]
    assert commits and all(set(dec.COUNTERS) <= set(m) for m in commits)
    decoded = sum(len(v) - 1 for v in out.values())
    assert sum(m["attn_rows"] for m in commits) == decoded
    assert sum(m["moe_pairs_all"] for m in commits) == 4 * 4 * decoded
    here = sum(m["moe_pairs_here"] for m in commits)
    assert 0 < here < 4 * 4 * decoded        # 8 of 16 experts are held
    # a decode step at position p attends p + 1 keys in a full layer and
    # at most the window's 8 in a window layer
    full = sum(len(p) + j + 1 for _, p, b in reqs for j in range(b - 1))
    ring = sum(min(len(p) + j + 1, 8) for _, p, b in reqs
               for j in range(b - 1))
    assert sum(m["attn_tokens_full"] for m in commits) == full
    assert sum(m["attn_tokens_window"] for m in commits) == ring
    # an admission: its full-layer blocks grow with the sequence, its
    # rings' bytes do not
    admits = {s["meta"]["rid"]: s["meta"] for s in spans
              if s["name"] == "serve:admit"}
    assert [admits[r]["kv_blocks_full"] for r, _, _ in reqs] \
        == [-(-(len(p) + b) // BLOCK) for _, p, b in reqs]
    assert {m["kv_bytes_window"] for m in admits.values()} \
        == {dec.slot_window_bytes}
    assert [admits[r]["moe_pairs_all"] for r, _, _ in reqs] \
        == [4 * 4 * len(p) for _, p, _ in reqs]
    assert all(0 < m["moe_pairs_here"] < m["moe_pairs_all"]
               for m in admits.values())


def test_a_prompt_of_several_chunks_is_one_prefill_span(model):
    from paddle_tpu.observability import tracing
    dec = _decoder(model, slots=2)
    tracing.enable_tracing()
    try:
        tracing.drain()
        dec.serve([(0, _ids(37).tolist(), 3)], max_new_tokens=3, chunk=4)
        spans = tracing.drain()
    finally:
        tracing.disable_tracing()
    (prefill,) = [s["meta"] for s in spans if s["name"] == "serve:prefill"]
    assert prefill == {"bucket": CHUNK, "prompts": 1, "rows": 37}
    assert dec.prefill_device_calls == 3 and len(dec._prefill_cache) == 1


# -- one chip's share of an expert layer ------------------------------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer(weights):
    """Each of 16 chips holds one of the 16 experts, routes over all of
    them and computes its own expert's part: the parts add up to what
    the reference gives for the whole layer."""
    whole = dict(CFG, n_routed_experts=16, experts_first=0)
    w = ref.make_weights(whole, SEED)
    rp = ref.layer_params(w, 1)
    u = jnp.asarray(np.random.default_rng(5).normal(size=(19, 64)), F32)
    want = ref.moe(whole, rp, u, "f32")
    total = 0.0
    for chip in range(16):
        cfg = adapter.program_config(dict(
            CFG, n_routed_experts=1, experts_first=chip))
        p = dict(rp, **{k: rp[k][chip:chip + 1] for k in ("w1", "w3", "w2")})
        part, counts = mm.sparse_moe(cfg, p, u)
        total = total + part
        assert int(counts[1]) == 19 * 4 and int(counts[2]) <= 1
        _close(part, ref.moe(whole, p, u, "f32", held=(chip, 1)))
    _close(total, want)


# -- the kernels, interpreted -----------------------------------------------------------

def _plain_attention(q, k, v, rows, lows, sinks, scale):
    """q [S, nh, dk] against k, v [S, W, nkv, d]: keys lows[s] .. rows[s]."""
    nrep = q.shape[1] // k.shape[2]
    k, v = jnp.repeat(k, nrep, 2), jnp.repeat(v, nrep, 2)
    s = jnp.einsum("shd,swhd->shw", q, k) * scale
    at = jnp.arange(k.shape[1])[None, :]
    sees = (at <= rows[:, None]) & (at >= lows[:, None])
    s = jnp.where(sees[:, None], s, -jnp.inf)
    top = s.max(-1, keepdims=True)
    if sinks is not None:
        top = jnp.maximum(top, sinks[None, :, None])
    e = jnp.exp(s - top)
    den = e.sum(-1, keepdims=True)
    if sinks is not None:
        den = den + jnp.exp(sinks[None, :, None] - top)
    return jnp.einsum("shw,swhd->shd", e / den, v)


@pytest.mark.parametrize("window,sink", [(False, False), (True, False),
                                         (True, True), (False, True)])
def test_ragged_kernel_with_window_sink_and_narrow_v(window, sink):
    from paddle_tpu.kernels.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    rng = np.random.default_rng(0)
    S, nh, nkv, dk, dv, bs, nb, mb = 5, 8, 2, 24, 16, 4, 40, 6
    q = jnp.asarray(rng.normal(size=(S, nh, dk)), F32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, nkv, dk)), F32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, nkv, dv)), F32)
    tables = jnp.asarray(rng.permutation(nb - 1)[:S * mb].reshape(S, mb) + 1,
                         jnp.int32)
    lens = jnp.asarray([0, 5, 11, 23, 17], jnp.int32)
    lows = jnp.asarray([0, 2, 4, 16, 10], jnp.int32) if window else None
    sinks = jnp.asarray(rng.normal(size=nh), F32) if sink else None
    got = ragged_paged_attention(q, kp, vp, tables, lens, lows=lows,
                                 sinks=sinks)
    want = _plain_attention(
        q, jnp.take(kp, tables, 0).reshape(S, mb * bs, nkv, dk),
        jnp.take(vp, tables, 0).reshape(S, mb * bs, nkv, dv), lens,
        jnp.zeros_like(lens) if lows is None else lows, sinks,
        1 / np.sqrt(dk))
    assert got.shape == (S, nh, dv)
    _close(got, want, 1e-5)


# the tiles are the kernel's own rule (512 rows, 512 keys or 128 of a
# window): the first four cases span several on both axes, the third and
# fourth at the engine's shape (a ring's 128 keys before a 1,024-row chunk)
@pytest.mark.parametrize("tq,tk,q_start,kv_first,window,sink", [
    (1024, 2048, 1024, 0, None, False), (1024, 1536, 256, 0, None, False),
    (1024, 1152, 128, 0, 128, True), (1024, 1152, 128, 100, 128, True),
    (16, 24, 8, 2, 6, True), (8, 8, 0, 0, 8, False)])
def test_prefill_kernel_attends_a_chunk_against_the_keys_before_it(
        tq, tk, q_start, kv_first, window, sink):
    from paddle_tpu.kernels.pallas.flash_prefill import (
        flash_prefill_attention)
    rng = np.random.default_rng(1)
    nh, nkv, dk, dv = 8, 2, 24, 16
    q = jnp.asarray(rng.normal(size=(tq, nh, dk)), F32)
    k = jnp.asarray(rng.normal(size=(tk, nkv, dk)), F32)
    v = jnp.asarray(rng.normal(size=(tk, nkv, dv)), F32)
    sinks = jnp.asarray(rng.normal(size=nh), F32) if sink else None
    got = flash_prefill_attention(q, k, v, q_start, kv_first, window, sinks)
    rows = q_start + jnp.arange(tq)
    lows = jnp.maximum(kv_first, rows - (window or tk) + 1)
    # every query against the same keys: `_plain_attention` with the
    # keys' axis shared (a copy a query would be gigabytes here)
    s = jnp.einsum("qhd,khd->qhk", q, jnp.repeat(k, nh // nkv, 1),
                   precision="highest") / np.sqrt(dk)
    at = jnp.arange(tk)[None, :]
    sees = (at <= rows[:, None]) & (at >= lows[:, None])
    s = jnp.where(sees[:, None], s, -jnp.inf)
    top = s.max(-1, keepdims=True)
    if sink:
        top = jnp.maximum(top, sinks[None, :, None])
    e = jnp.exp(s - top)
    den = e.sum(-1, keepdims=True)
    if sink:
        den = den + jnp.exp(sinks[None, :, None] - top)
    want = jnp.einsum("qhk,khd->qhd", e / den, jnp.repeat(v, nh // nkv, 1),
                      precision="highest")
    _close(got, want, 1e-5)


# -- what refuses ---------------------------------------------------------------------------

@pytest.mark.parametrize("option,value", [
    ("prefix_cache", True), ("kv_quant", "int8"), ("kv_offload", True),
    ("attn_shards", 2), ("weight_quant", "int8")])
def test_options_that_do_not_compose_refuse_by_name(model, option, value):
    with pytest.raises(NotImplementedError, match=option):
        _decoder(model, **{option: value})


@pytest.mark.parametrize("what", ["spec_decode", "export_blocks",
                                  "import_blocks", "page_out_blocks"])
def test_calls_that_do_not_compose_refuse(model, what):
    dec = _decoder(model)
    with pytest.raises(NotImplementedError, match="window layers"):
        if what == "spec_decode":
            dec.serve(_serve_requests()[:1], spec_decode=2)
        else:
            getattr(dec, what)(None, None, [1])


def test_prefill_chunk_holds_a_ring_and_whole_blocks(model):
    with pytest.raises(ValueError, match="prefill_chunk 8"):
        _decoder(model, prefill_chunk=8)
    with pytest.raises(ValueError, match="prefill_chunk 18"):
        _decoder(model, prefill_chunk=18)
    with pytest.raises(TypeError, match="no_such_option"):
        _decoder(model, no_such_option=1)


# -- the other engines' programs lower to the parent's text -------------------------------------

def _ragged_entry_points():
    from paddle_tpu.kernels.pallas import ragged_paged_attention as rpa
    S, nh, nkv, hd, bs, nb, mb = 4, 8, 2, 128, 64, 9, 4
    sd = jax.ShapeDtypeStruct
    q, kp = sd((S, nh, hd), F32), sd((nb, bs, nkv, hd), F32)
    t, lens = sd((S, mb), jnp.int32), sd((S,), jnp.int32)
    kc, sc = sd((nb, bs, nkv, hd), jnp.int8), sd((nb, bs), F32)
    return {
        "plain": (lambda q, k, v, t, n: rpa.ragged_paged_attention(
            q, k, v, t, n), (q, kp, kp, t, lens)),
        "sharded": (lambda q, k, v, t, n: rpa.ragged_paged_attention_sharded(
            q, k, v, t, n, 2), (q, kp, kp, t, lens)),
        "quant": (lambda q, k, ks, v, vs, t, n:
                  rpa.ragged_paged_attention_quant(q, k, ks, v, vs, t, n),
                  (q, kc, sc, kc, sc, t, lens)),
    }


PARENT_JAXPR = {
    "plain": "19b14341f710ba3722a9b793218849dd6140735f2c8f698972fab44cc692ac04",
    "sharded": "a2f755aec3eeaaca1334299e09d3a1f0e9a41b2c30d1aaa30c6fd304ddd4a40e",
    "quant": "f8a2c07ae98725801883858ed16b12dc7dd8a9167d1333c67dbacc4991634063",
}


@pytest.mark.parametrize("entry", ["plain", "sharded", "quant"])
def test_ragged_kernel_without_window_sink_or_narrow_v_is_the_parents(
        entry, monkeypatch):
    """sha256 of each entry point's jaxpr (the launch as Mosaic gets it:
    grid, scalar prefetch, block specs, scratch, compiler parameters and
    the kernel's body, without source locations) at the parent commit. A
    window's lower bound, a sink and V rows narrower than K's are all
    absent from it unless asked for."""
    from paddle_tpu.kernels.pallas import ragged_paged_attention as rpa
    monkeypatch.setattr(rpa, "_interpret", lambda: False)
    fn, args = _ragged_entry_points()[entry]
    text = str(jax.make_jaxpr(fn)(*args))
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_JAXPR[entry]


PARENT_HYBRID = {
    "chunk":
        "5b1ab2acda8f744d6089c2955830132b7b230b08240e3b4edafa2a50d73512d1",
    "prefill":
        "680390d8f9038aa24f2e73fd8253e93e08370034e19ba2f605ce6e2373677287",
}


@pytest.mark.parametrize("program", ["chunk", "prefill"])
def test_hybrid_engines_programs_lower_to_the_parents_text(program):
    """sha256 of the CPU lowering at the parent commit (the dense
    engine's programs and the LFM2 train step are held the same way by
    tests/test_packed_prefill.py): the rule that picks the engine and the
    loop's prefill calls changed, no program of another engine did.
    PR 38 meant to change "chunk" and no other: its Mamba blocks' state
    update is the kernel of `kernels/pallas/ssm_update.py`. PR 41 meant to
    change both: the expert layer's sorted buffer holds the pairs held
    here, in as many passes of `nemotron_h.buffer_rows` as they take; the
    digests are that commit's."""
    from paddle_tpu.models import nemotron_h as nh
    dec = PagedDecoder(
        nh.NemotronHForCausalLM(nh.nemotron_h_tiny(
            hybrid_override_pattern="ME*ME", experts_held=(4, 8))),
        max_len=64, block_size=8, num_blocks=33, max_slots=4)
    s, mb = dec.max_slots, dec.blocks_per_seq
    pools = dec.new_pools()
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    flag = lambda *shape: jnp.zeros(shape, bool)
    if program == "chunk":
        text = dec._paged_chunk_state_jit.lower(
            dec._params, i32(s), i32(s), i32(s, mb), flag(s), i32(s),
            flag(s), *pools, 2, -1).as_text()
    else:
        head, tail = dec._prefill_inputs(16, [], (), 0)
        text = dec._prefill_exec(16).lower(
            dec._params, *head, *pools, *tail).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PARENT_HYBRID[program]
