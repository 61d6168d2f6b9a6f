"""The `deepseek_v32` family (latent attention whose keys a learned
indexer chooses, YaRN positions, sparse experts with a group-limited
router and a shared expert) against its plain reference, on the CPU at
toy widths with seeded weights: the whole forward; a prompt prefilled in
chunks and then decoded through the latent cache, with an index top-k
far below the contexts so that the choice binds; the absorbed decode
against the expanded form; the exact top-k with its ties; YaRN's
frequencies and scale; the group-limited router against a loop; the
shares of one expert layer; the serve loop; the kernels in interpret
mode; the options that refuse; and the other engines' routers, which
the group limit must leave as they were.

The reference (`chipbench/reference/deepseek_v32.py`) is float32
`highest`, one sequence at a time, every head expanded, the choice by
`lax.top_k`, and imports nothing of the program.
"""
import copy
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.adapters import deepseek_v32 as adapter
from chipbench.reference import deepseek_v32 as ref
from paddle_tpu.models import deepseek_v32 as dm
from paddle_tpu.models.paged_decode import PagedDecoder

F32 = jnp.float32
CFG = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
           moe_intermediate_size=32, num_hidden_layers=3,
           first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=32,
           kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, index_n_heads=4, index_head_dim=16, index_topk=16,
           n_routed_experts=8, experts_first=8,
           published={"n_routed_experts": 16}, num_experts_per_tok=4,
           n_group=4, topk_group=2, n_shared_experts=1,
           routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-6,
           rope_theta=10000,
           rope_scaling=dict(type="yarn", factor=40,
                             original_max_position_embeddings=64,
                             beta_fast=32, beta_slow=1, mscale=1,
                             mscale_all_dim=1),
           max_position_embeddings=256, initializer_range=0.16,
           torch_dtype="float32")
SEED = 2**31 + 29
# float32 against float32 `highest`, sums reordered and the rotary
# angles formed in float32 (the reference's in float64): a few parts in
# 1e6 of the largest logit
TOL = 2e-5
BLOCK, CHUNK, MAX_LEN = 8, 32, 256


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
    return PagedDecoder(model, max_len=MAX_LEN, block_size=BLOCK,
                        num_blocks=97, max_slots=slots, **kw)


# -- the whole forward ----------------------------------------------------------------

@pytest.mark.parametrize("length", [1, 16, 17, 70])
def test_forward_logits_match_the_reference(model, weights, length):
    """Up to 16 positions every key is chosen; at 70 the top-16 binds."""
    ids = _ids(length)
    got = model.forward(ids[None])._data[0]
    want = ref.logits_at(CFG, weights, jnp.asarray(ids), jnp.arange(length))
    _close(got, want)


def test_yarn_frequencies_and_scale_follow_the_formula():
    """The published 64 rotary dims, base 1e4, factor 40 over 4,096: the
    correction dims are floor(10.47) = 10 and ceil(22.50) = 23; below 10
    the frequencies stay, from 23 on they are divided by 40, a linear
    ramp between. The scale is 192^-1/2 (0.1 ln 40 + 1)^2."""
    cfg = dm.DeepseekV32Config(rope_scaling=dict(
        type="yarn", factor=40, original_max_position_embeddings=4096,
        beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1))
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    corr = lambda rot: 64 * math.log(4096 / (rot * 2 * math.pi)) \
        / (2 * math.log(1e4))
    assert (math.floor(corr(32)), math.ceil(corr(1))) == (10, 23)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = base * (1 - ramp) + base / 40 * ramp
    got = dm.yarn_inv_freq(cfg)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(ref.inv_freq(dict(
        qk_rope_head_dim=64, rope_theta=1e4, rope_scaling=cfg.rope_scaling)),
        want, rtol=1e-12)
    m = 0.1 * math.log(40) + 1
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    assert dm.DeepseekV32Config(rope_scaling=None).softmax_scale \
        == pytest.approx(192 ** -0.5)


def test_rotary_pairings():
    """MLA turns pairs (2i, 2i + 1); the indexer pairs (i, i + dr/2) of
    its first dr dims and passes the rest; position 0 turns nothing and
    a turn keeps each pair's length."""
    cfg = dm.deepseek_v32_tiny()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 3, 16)), F32)
    pos = jnp.asarray([0, 1, 7, 100], jnp.int32)
    inv = dm.yarn_inv_freq(cfg)
    got = dm.rope_interleaved(cfg, x[..., :8], pos)
    _close(got[0], x[0, :, :8], 0)
    ang = 7 * inv[1]
    a, b = x[2, :, 2], x[2, :, 3]
    _close(got[2, :, 2], a * np.cos(ang) - b * np.sin(ang), 1e-6)
    _close(got[2, :, 3], b * np.cos(ang) + a * np.sin(ang), 1e-6)
    halves = dm.rope_halves(cfg, x, pos)
    _close(halves[..., 8:], x[..., 8:], 0)
    _close(halves[3, :, 1], x[3, :, 1] * np.cos(100 * inv[1])
           - x[3, :, 5] * np.sin(100 * inv[1]), 1e-6)
    _close(jnp.sum(halves * halves, -1), jnp.sum(x * x, -1), 1e-6)


# -- the choice of keys -------------------------------------------------------------

def _plain_topk(scores, valid, k):
    """Each row's k best valid entries by (score, lower position first):
    a sort, the way the published code's `topk` orders them."""
    out = np.zeros(scores.shape, bool)
    for r in range(scores.shape[0]):
        cand = [j for j in range(scores.shape[1]) if valid[r, j]]
        cand.sort(key=lambda j: (-scores[r, j], j))
        out[r, cand[:k]] = True
    return out


def test_topk_is_exact_with_ties_to_the_lower_position():
    rng = np.random.default_rng(7)
    scores = rng.normal(size=(6, 300)).astype(np.float32)
    # ties: repeated values across the cut, zeros of both signs, a row of
    # one value, a row with fewer valid entries than k
    scores[1, ::3] = 0.25
    scores[2, :150] = 0.0
    scores[2, 150:] = -0.0
    scores[3] = 1.5
    scores[4, 40:] = np.float32(3e-39)          # denormals tie too
    valid = np.ones(scores.shape, bool)
    valid[5, 10:] = False
    valid[0, 250:] = False
    got = np.asarray(dm.topk_mask(jnp.asarray(scores), jnp.asarray(valid), 64))
    want = _plain_topk(np.where(scores == 0, 0.0, scores), valid, 64)
    assert (got == want).all()
    assert got.sum(1).tolist() == [64, 64, 64, 64, 64, 10]
    assert got[3].nonzero()[0].tolist() == list(range(64))
    pos, count = dm.mask_positions(jnp.asarray(got), 64)
    assert count.tolist() == [64] * 5 + [10]
    assert np.asarray(pos)[5].tolist() == list(range(10)) + [0] * 54
    assert (np.asarray(pos)[1] == got[1].nonzero()[0]).all()


def test_topk_against_lax_top_k_on_index_like_scores():
    """ReLU-weighted sums as the indexer makes them (exact zeros where
    every head is negative) against `lax.top_k`, which the reference
    uses: the same keys."""
    rng = np.random.default_rng(3)
    q = rng.normal(size=(40, 4, 16)).astype(np.float32)
    k = rng.normal(size=(200, 16)).astype(np.float32)
    w = rng.normal(size=(40, 4)).astype(np.float32)
    s = dm.index_scores(jnp.asarray(q), jnp.asarray(w), jnp.asarray(k))
    causal = np.arange(200)[None] <= np.arange(100, 140)[:, None]
    got = np.asarray(dm.topk_mask(s, jnp.asarray(causal), 16))
    _, top = jax.lax.top_k(jnp.where(jnp.asarray(causal), s, -jnp.inf), 16)
    want = np.zeros_like(got)
    want[np.arange(40)[:, None], np.asarray(top)] = True
    assert (got == want).all()


# -- the engine and its latent cache -------------------------------------------------

def test_paged_decoder_builds_the_latent_engine(model):
    dec = _decoder(model)
    assert isinstance(dec, dm.LatentPagedDecoder)
    lat, idx = dec.new_pools()
    # a latent row [c | k_pe] = 32 + 8, kept in a whole lane; an indexer
    # key a layer beside it; blocks of 8 tokens
    assert lat.shape == (3, 97, BLOCK, 128) and idx.shape == (3, 97, BLOCK, 16)
    assert dec.kv_token_bytes() == (128 + 16) * 4
    assert dec.bytes_per_block() == 3 * BLOCK * dec.kv_token_bytes()
    assert dec.pool_bytes() == (lat.size + idx.size) * 4
    assert model.config.cache_kinds == ("latent",) * 3


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


def _teacher_forced(dec, weights, ids, n_prompt, slot=1, pools=None):
    """Prefill ids[:n_prompt] into `slot`, then decode the rest one
    token a step: every step's logits against the reference's one full
    forward."""
    blocks = np.random.default_rng(3).permutation(np.arange(1, 97))[:32]
    tables = np.zeros((dec.max_slots, dec.blocks_per_seq), np.int32)
    tables[slot, :32] = blocks
    encs, pools = _prefill(dec, pools or dec.new_pools(), slot,
                           ids[:n_prompt], tables)
    want = np.asarray(ref.logits_at(CFG, weights, jnp.asarray(ids),
                                    jnp.arange(n_prompt - 1, len(ids))))
    assert dec.decode_first_token(encs) == (int(want[0].argmax()), False)
    active = jnp.arange(dec.max_slots) == slot
    got, read = [], []
    for step, token in enumerate(ids[n_prompt:]):
        tokens = jnp.zeros(dec.max_slots, jnp.int32).at[slot].set(token)
        lens = jnp.zeros(dec.max_slots, jnp.int32).at[slot].set(
            n_prompt + step)
        logits, *pools, counts, rows = _jitted(dec, "_step")(
            dec._params, tokens, lens, jnp.asarray(tables), active, *pools)
        got.append(logits[slot])
        read.append(int(rows))
        assert int(counts[1]) == 4 * 2     # one row's 4 pairs, 2 expert layers
    return jnp.stack(got), want[1:], read


@pytest.mark.parametrize("n_prompt", [5, 32, 75])
def test_prefill_then_decode_gives_the_reference_logits(model, weights,
                                                        n_prompt):
    """A prompt shorter than the top-k, one that fills its chunk, and one
    of three chunks that ends inside a block; then decode steps through
    the latent pool, the choice binding from the 16th position on: every
    step reads min(position + 1, 16) latent rows."""
    dec = _decoder(model)
    ids = _ids(n_prompt + 14, seed=9)
    got, want, read = _teacher_forced(dec, weights, ids, n_prompt)
    _close(got, want)
    assert read == [min(n_prompt + s + 1, 16) for s in range(14)]


def test_freed_blocks_never_leak_into_the_next_request(model, weights):
    """Every block of the pools but the ones a request is given (and the
    trash block, which a table's unused entries name) poisoned with NaN:
    it decodes what it decodes alone."""
    dec = _decoder(model)
    lat, idx = dec.new_pools()
    given = np.random.default_rng(3).permutation(np.arange(1, 97))[:32]
    poison = np.setdiff1d(np.arange(1, 97), given)
    lat, idx = (p.at[:, poison].set(jnp.nan) for p in (lat, idx))
    ids = _ids(50, seed=11)
    got, want, _ = _teacher_forced(dec, weights, ids, 40, pools=(lat, idx))
    _close(got, want)


def test_absorbed_decode_is_the_expanded_form(model):
    """Decode folds Wuk into the query and Wuv into the output: the same
    attention as expanding every head's keys and values from the chosen
    latent rows."""
    dec = _decoder(model)
    cfg, p = model.config, dec._params["layers"][1]
    rng = np.random.default_rng(5)
    S, k = 3, 16
    q = jnp.asarray(rng.normal(size=(S, 4, 24)), F32)
    lat = jnp.asarray(rng.normal(size=(200, 128)), F32).at[:, 40:].set(0)
    rows = jnp.asarray(rng.integers(0, 200, (S, k)), jnp.int32)
    count = jnp.asarray([16, 5, 1], jnp.int32)
    got = dec._attend_absorbed(p, q, lat, rows, count)
    want = []
    for s in range(S):
        keys, vals = dm.expand(cfg, p, lat[rows[s, :int(count[s])]])
        sel = jnp.ones((1, int(count[s])), bool)
        want.append(dm.attend_expanded(cfg, q[s:s + 1], keys, vals, sel)[0])
    _close(got, jnp.stack(want), 1e-5)


# -- the serve loop -------------------------------------------------------------------

def _serve_requests():
    rng = np.random.default_rng(4)
    shapes = [(40, 9), (12, 20), (70, 3), (33, 11), (100, 17), (9, 6)]
    return [(rid, rng.integers(0, 256, n).tolist(), budget)
            for rid, (n, budget) in enumerate(shapes)]


@pytest.fixture(scope="module")
def served(model):
    dec = _decoder(model, slots=3)
    reqs = _serve_requests()
    return dec, reqs, dec.serve(reqs, max_new_tokens=20, chunk=4)


@pytest.mark.parametrize("rid", range(6))
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


@pytest.mark.parametrize("how", ["pipelined_admission", "no_lookahead"])
def test_every_way_through_the_loop_serves_the_same_tokens(served, model,
                                                           how):
    _, reqs, want = served
    if how == "no_lookahead":
        got = _decoder(model, slots=3).serve(reqs, max_new_tokens=20,
                                             chunk=4, pipeline=False)
    else:
        got = _decoder(model, slots=3, **{how: True}).serve(
            reqs, max_new_tokens=20, chunk=4)
    assert got == want


def test_counters_ride_the_commit_and_admit_spans(model):
    from paddle_tpu.observability import tracing
    dec = _decoder(model, slots=3)
    reqs = _serve_requests()[:3]
    tracing.enable_tracing()
    try:
        tracing.drain()
        out = dec.serve(reqs, max_new_tokens=20, chunk=4)
        spans = tracing.drain()
    finally:
        tracing.disable_tracing()
    commits = [s["meta"] for s in spans if s["name"] == "serve:commit"]
    assert commits and all(set(dec.COUNTERS) <= set(m) for m in commits)
    decoded = sum(len(v) - 1 for v in out.values())
    assert sum(m["attn_rows"] for m in commits) == decoded
    assert sum(m["moe_pairs_all"] for m in commits) == 2 * 4 * decoded
    here = sum(m["moe_pairs_here"] for m in commits)
    assert 0 < here < 2 * 4 * decoded        # 8 of 16 experts are held
    # a decode step at position p scores p + 1 keys and reads min(p + 1,
    # 16) latent rows
    keys = sum(len(p) + j + 1 for _, p, b in reqs for j in range(b - 1))
    rows = sum(min(len(p) + j + 1, 16) for _, p, b in reqs
               for j in range(b - 1))
    assert sum(m["index_keys"] for m in commits) == keys
    assert sum(m["latent_rows_read"] for m in commits) == rows
    admits = {s["meta"]["rid"]: s["meta"] for s in spans
              if s["name"] == "serve:admit"}
    assert [admits[r]["kv_blocks"] for r, _, _ in reqs] \
        == [-(-(len(p) + b) // BLOCK) for _, p, b in reqs]
    assert [admits[r]["moe_pairs_all"] for r, _, _ in reqs] \
        == [2 * 4 * len(p) for _, p, _ in reqs]


# -- one chip's share of an expert layer and the router ----------------------------------

def test_the_shares_add_up_to_the_uncut_layer(weights):
    """Each of 16 chips holds one of the 16 experts, routes over all of
    them (groups included) and computes its own expert's part; the
    shared expert is whole on every chip. The routed parts of all
    shares, plus the shared expert once, are what the reference gives
    for the whole layer."""
    whole = dict(CFG, n_routed_experts=16, experts_first=0)
    w = ref.make_weights(whole, SEED)
    rp = ref.layer_params(w, 1)
    u = jnp.asarray(np.random.default_rng(5).normal(size=(19, 64)), F32)
    routed = 0.0
    for chip in range(16):
        cfg = adapter.program_config(dict(
            CFG, n_routed_experts=1, experts_first=chip))
        p = dict(rp, **{k: rp[k][chip:chip + 1] for k in ("w1", "w3", "w2")})
        x = jnp.zeros_like(u)
        out, counts = dm.mlp(cfg, 1, dict(p, ln2=jnp.ones(64)), x + u)
        assert int(counts[1]) == 19 * 4 and int(counts[2]) <= 1
        h2 = ref.rms_norm(u, jnp.ones(64), 1e-6)
        _close(out - u, ref.moe(whole, p, h2, "f32", held=(chip, 1)))
        routed = routed + (out - u - ref.swiglu(h2, p["ws_g"], p["ws_u"],
                                                p["ws_d"], "f32"))
    h2 = ref.rms_norm(u, jnp.ones(64), 1e-6)
    _close(routed + ref.swiglu(h2, rp["ws_g"], rp["ws_u"], rp["ws_d"], "f32"),
           ref.moe(whole, rp, h2, "f32"))
    assert np.abs(np.asarray(routed)).max() > 0


def test_group_limited_router_against_a_loop():
    """Eight experts a group, two of four groups kept by the sum of their
    two best choice scores, top-4 among their 16; weights from the
    sigmoid alone, normalised over the four, times 2.5."""
    from paddle_tpu.models.nemotron_h import moe_route
    cfg = dm.deepseek_v32_tiny(n_routed_experts=32, n_group=4, topk_group=2)
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.normal(size=(30, 64)), F32)
    p = {"router": jnp.asarray(rng.normal(size=(64, 32)), F32) * 0.3,
         "b_corr": jnp.asarray(rng.normal(size=32), F32) * 0.05}
    idx, w = moe_route(cfg, p, u)
    s = 1 / (1 + np.exp(-(np.asarray(u, np.float64) @ np.asarray(p["router"]))))
    choice = s + np.asarray(p["b_corr"])
    for t in range(30):
        groups = sorted(range(4), key=lambda g: -np.sort(
            choice[t, 8 * g:8 * g + 8])[-2:].sum())[:2]
        allowed = [e for g in groups for e in range(8 * g, 8 * g + 8)]
        top = sorted(allowed, key=lambda e: -choice[t, e])[:4]
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(top)
        picked = s[t, np.asarray(idx[t])]
        np.testing.assert_allclose(np.asarray(w[t]),
                                   2.5 * picked / picked.sum(), rtol=1e-5)


def test_one_group_routes_as_the_other_families_did():
    """With no `n_group` (the window and hybrid configurations) or one
    group, `moe_route` traces the program it traced before the group
    limit: the same jaxpr as the plain top-k, and the same bits."""
    from paddle_tpu.models import nemotron_h as nh
    from paddle_tpu.models.mimo_v2 import mimo_v2_tiny

    def before(cfg, p, u):
        logits = jnp.dot(u.astype(F32), p["router"].astype(F32),
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s + p["b_corr"].astype(F32)[None, :],
                               cfg.num_experts_per_tok)
        picked = jnp.take_along_axis(s, idx, axis=1)
        if cfg.norm_topk_prob:
            picked = picked / (jnp.sum(picked, -1, keepdims=True)
                               + getattr(cfg, "norm_topk_eps", 1e-20))
        return idx.astype(jnp.int32), picked * cfg.routed_scaling_factor
    rng = np.random.default_rng(8)
    for cfg in (nh.nemotron_h_tiny(), mimo_v2_tiny(),
                dm.deepseek_v32_tiny(n_group=1, topk_group=1)):
        e = cfg.n_routed_experts
        u = jnp.asarray(rng.normal(size=(13, 16)), F32)
        p = {"router": jnp.asarray(rng.normal(size=(16, e)), F32),
             "b_corr": jnp.asarray(rng.normal(size=e), F32) * 0.01}
        a, b = nh.moe_route(cfg, p, u), before(cfg, p, u)
        assert (np.asarray(a[0]) == np.asarray(b[0])).all()
        assert (np.asarray(a[1]) == np.asarray(b[1])).all()
        assert str(jax.make_jaxpr(lambda p, u: nh.moe_route(cfg, p, u))(p, u)) \
            == str(jax.make_jaxpr(lambda p, u: before(cfg, p, u))(p, u))


# -- the kernels, interpreted -----------------------------------------------------------

@pytest.mark.parametrize("tq,tk,q_start", [(32, 256, 96), (32, 1024, 0),
                                           (256, 1024, 512)])
def test_prefill_index_kernel_is_the_plain_scores(tq, tk, q_start):
    from paddle_tpu.kernels.pallas.lightning_index import (
        lightning_index_scores)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(tq, 8, 16)), F32)
    w = jnp.asarray(rng.normal(size=(tq, 8)), F32)
    k = jnp.asarray(rng.normal(size=(tk, 16)), F32)
    got = np.asarray(lightning_index_scores(q, w, k, q_start))
    want = np.asarray(dm.index_scores(q, w, k))
    # tiles past the chunk's last row are not computed
    live = (np.arange(tk) // min(512, tk)) <= (q_start + tq - 1) // min(512, tk)
    _close(got[:, live], want[:, live], 1e-5)
    assert (got[:, ~live] == 0).all()


def test_decode_index_kernel_is_the_plain_scores():
    """Three slots, each the sole owner of a contiguous run of blocks,
    against the plain scores of its own keys."""
    from paddle_tpu.kernels.pallas.lightning_index import (
        lightning_index_decode)
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(3, 8, 16)), F32)
    w = jnp.asarray(rng.normal(size=(3, 8)), F32)
    k = jnp.asarray(rng.normal(size=(3, 256, 16)), F32)
    pos = jnp.asarray([0, 100, 255], jnp.int32)
    tables = jnp.arange(3 * 32, dtype=jnp.int32).reshape(3, 32)
    got = np.asarray(lightning_index_decode(q, w, k.reshape(96, 8, 16),
                                            tables, pos))
    want = np.asarray(dm.index_scores(q, w, k))
    for s, p in enumerate([0, 100, 255]):
        _close(got[s, :p + 1], want[s, :p + 1], 1e-5)


# four slots through one pool of 2 layers x 40 blocks of 8 keys, tables
# of 12 blocks: one key; a position inside its 5th block; all 12 blocks;
# an idle slot whose table names the trash block 0 only
PAGED_POS = [0, 37, 95, 21]


def _paged_index_case(layer):
    """(q, w, pool, tables, pos, base) for `PAGED_POS`: each slot's blocks
    drawn out of order from the layer's 40, none shared; every block no
    table names as live is NaN, those behind a slot's position too."""
    rng = np.random.default_rng(12)
    nb, bs, d = 40, 8, 16
    pool = rng.normal(size=(2 * nb, bs, d)).astype(np.float32)
    owned = rng.permutation(np.arange(1, nb))[:3 * 12].reshape(3, 12)
    tables = np.zeros((4, 12), np.int32)
    tables[:3] = owned
    live = np.zeros(2 * nb, bool)
    for s, p in enumerate(PAGED_POS):
        live[tables[s, :p // bs + 1] + layer * nb] = True
    pool[~live] = np.nan
    q = rng.normal(size=(4, 8, d)).astype(np.float32)
    w = rng.normal(size=(4, 8)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(w), jnp.asarray(pool),
            jnp.asarray(tables), jnp.asarray(PAGED_POS, jnp.int32), layer * nb)


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("sizes", ["one_product", "groups_of_4"])
def test_paged_index_kernel_is_the_gathered_path(monkeypatch, layer, sizes):
    """The kernel reads the slot's live blocks through its table: the
    scores of today's gathered path (every block the table names copied
    out, then scored) on each live block's positions, 0 behind, and no
    NaN from a block it must not read. `groups_of_4` steers the sizing
    rule to products of 2 blocks and groups of 4, so that the one-key
    slot is shorter than a group, the 5-block slot ends inside its
    second group's second product and the full one takes three
    groups."""
    from paddle_tpu.kernels.pallas import ragged_paged_attention as rpa
    from paddle_tpu.kernels.pallas.lightning_index import (
        lightning_index_decode)
    if sizes == "groups_of_4":
        monkeypatch.setattr(rpa, "_PRODUCT_COLS", 16)
        monkeypatch.setattr(rpa, "_BUFFER_BYTES", 4096)
        assert rpa._blocks_per_step(8 * 16 * 4 // 2, 8, 12) == (4, 2)
    q, w, pool, tables, pos, base = _paged_index_case(layer)
    got = np.asarray(lightning_index_decode(q, w, pool, tables, pos, base))
    gathered = jnp.take(pool, tables + base, axis=0).reshape(4, 96, 16)
    want = np.asarray(dm.index_scores(q, w, gathered))
    assert got.shape == (4, 96) and np.isfinite(got).all()
    for s, p in enumerate(PAGED_POS):
        live = (p // 8 + 1) * 8
        _close(got[s, :live], want[s, :live], 1e-5)
        assert (got[s, live:] == 0).all()


@pytest.mark.parametrize("layer", [1, 2])
def test_paged_selection_is_the_gathered_paths(model, layer):
    """`_select` with the kernel reading the pool through the tables
    chooses the positions today's gathered path chooses (every key the
    tables name copied out, scored in plain XLA, the same top-k): a slot
    below `index_topk` (count < k), one mid-block, one idle on the trash
    block, at layers past the first."""
    dec = _decoder(model, slots=4)
    L, nb, bs, d = 3, dec.num_blocks, BLOCK, CFG["index_head_dim"]
    rng = np.random.default_rng(13)
    idx = jnp.asarray(rng.normal(size=(L * nb * bs, d)), F32)
    tables = np.zeros((4, dec.blocks_per_seq), np.int32)
    tables[:3] = rng.permutation(np.arange(1, nb))[:3 * 32].reshape(3, 32)
    tables = jnp.asarray(tables)
    pos = jnp.asarray([9, 100, 255, 30], jnp.int32)
    qi = jnp.asarray(rng.normal(size=(4, 4, d)), F32)
    wi = jnp.asarray(rng.normal(size=(4, 4)), F32)
    got, count = jax.jit(dec._select, static_argnums=3)(qi, wi, idx, layer,
                                                        tables, pos)
    keys = dec._context(idx, layer, tables)
    valid = jnp.arange(keys.shape[1])[None] <= pos[:, None]
    want, want_count = dm.mask_positions(
        dm.topk_mask(dm.index_scores(qi, wi, keys), valid, 16), 16)
    assert count.tolist() == want_count.tolist() == [10, 16, 16, 16]
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("layer", [0, 2])
def test_chosen_rows_are_the_tables_rows(model, layer):
    """Decode's pool rows of the chosen positions, each table entry
    picked by a compare-and-select, are the rows `_rows` gathers: tables
    out of order, positions in every block of a slot and on the trash
    block of an idle one."""
    dec = _decoder(model, slots=4)
    rng = np.random.default_rng(14)
    tables = np.zeros((4, dec.blocks_per_seq), np.int32)
    tables[:3] = rng.permutation(np.arange(1, dec.num_blocks))[:3 * 32] \
        .reshape(3, 32)
    pos = rng.integers(0, MAX_LEN, size=(4, 16)).astype(np.int32)
    pos[3] = 0
    tables, pos = jnp.asarray(tables), jnp.asarray(pos)
    got = jax.jit(dec._chosen_rows, static_argnums=0)(layer, tables, pos)
    assert got.dtype == jnp.int32
    assert (np.asarray(got) == np.asarray(dec._rows(layer, tables, pos))).all()


@pytest.mark.parametrize("heads,group,tk,q_start", [
    pytest.param(4, None, 128, 0, id="0"),
    pytest.param(4, None, 128, 64, id="64"),
    # 8 heads of dn = dv = 64 (a head's Wkvb columns whole lanes, as the
    # cell's are), 2 or 4 a grid step, 1,536 keys in three tiles of 512;
    # 32 rows from 0 (tiles 1 and 2 past the chunk's last row), from 496
    # (the rows cross tiles 0 and 1), from 700 (tile 0 below the
    # diagonal, tile 2 past the last row) and from 1,504 (every tile)
    *[pytest.param(8, g, 1536, s, id=f"8h-G{g}-q{s}")
      for g in (2, 4) for s in (0, 496, 700, 1504)]])
def test_prefill_attention_kernel_is_the_masked_expanded_form(
        model, monkeypatch, heads, group, tk, q_start):
    """The kernel forms k_nope and v from the latent rows a tile at a
    time, a group of heads a grid step; the plain form expands them all
    and masks the scores. Where the rows reach past the first tile, the
    first row's choice misses tile 0 and the second row's tile 1."""
    from paddle_tpu.kernels.pallas import mla_prefill
    cfg, p = model.config, model.param_tree()["layers"][0]
    rng = np.random.default_rng(3)
    if heads != cfg.num_attention_heads:
        cfg = copy.copy(cfg)
        cfg.num_attention_heads = heads
        cfg.qk_nope_head_dim = cfg.v_head_dim = 64
        p = {"wkv_b": jnp.asarray(rng.normal(size=(32, heads * 128)) * 0.2,
                                  F32)}
    if group is not None:
        monkeypatch.setattr(mla_prefill, "_heads_per_step",
                            lambda *_: group)
    tq = 32
    q = jnp.asarray(rng.normal(size=(tq, heads, cfg.qk_head_dim)), F32)
    lat = jnp.asarray(rng.normal(size=(tk, 128)), F32).at[:, 40:].set(0)
    rows = q_start + np.arange(tq)
    cols = np.arange(tk)[None]
    mask = (rng.random((tq, tk)) < 0.4) | (cols == rows[:, None])
    if q_start >= 512:
        mask[0, :512] = False
        mask[1, 512:1024] = False
    mask &= cols <= rows[:, None]
    got = mla_prefill.mla_prefill_attention(
        q, lat, p["wkv_b"], jnp.asarray(mask), q_start, 32, 8,
        cfg.softmax_scale)
    keys, vals = dm.expand(cfg, p, lat)
    want = dm.attend_expanded(cfg, q, keys, vals, jnp.asarray(mask))
    _close(got.reshape(tq, -1), want, 1e-5)


def test_decode_attention_kernel_masks_past_the_count():
    from paddle_tpu.kernels.pallas.mla_decode import mla_decode_attention
    rng = np.random.default_rng(4)
    qc = jnp.asarray(rng.normal(size=(2, 4, 32)), F32)
    qp = jnp.asarray(rng.normal(size=(2, 4, 8)), F32)
    rows = jnp.asarray(rng.normal(size=(2, 16, 128)), F32).at[..., 40:].set(0)
    count = jnp.asarray([16, 3], jnp.int32)
    got = np.asarray(mla_decode_attention(qc, qp, rows, count, 32, 0.3))
    for s, n in enumerate([16, 3]):
        c, pe = rows[s, :n, :32], rows[s, :n, 32:40]
        sc = (qc[s] @ c.T + qp[s] @ pe.T) * 0.3
        want = jax.nn.softmax(sc, -1) @ c
        _close(got[s], want, 1e-5)


# -- what refuses -------------------------------------------------------------------------

@pytest.mark.parametrize("option,value", [
    ("prefix_cache", True), ("kv_quant", "int8"), ("kv_offload", True),
    ("attn_shards", 2), ("weight_quant", "int8"), ("ragged_kernel", True)])
def test_options_that_do_not_compose_refuse_by_name(model, option, value):
    with pytest.raises(NotImplementedError, match=option):
        _decoder(model, **{option: value})


@pytest.mark.parametrize("what", ["spec_decode", "export_blocks",
                                  "import_blocks", "page_out_blocks"])
def test_calls_that_do_not_compose_refuse(model, what):
    dec = _decoder(model)
    with pytest.raises(NotImplementedError, match="latent cache"):
        if what == "spec_decode":
            dec.serve(_serve_requests()[:1], spec_decode=2)
        else:
            getattr(dec, what)(None, None, [1])


def test_sizes_that_cannot_hold_the_choice_refuse(model):
    with pytest.raises(ValueError, match="prefill_chunk 20"):
        _decoder(model, prefill_chunk=20)
    with pytest.raises(ValueError, match="below index_topk"):
        PagedDecoder(model, max_len=8, block_size=8, max_slots=2)
    with pytest.raises(TypeError, match="no_such_option"):
        _decoder(model, no_such_option=1)
