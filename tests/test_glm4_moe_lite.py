"""The `glm4_moe_lite` family (GLM-4.7-Flash: the dense member of the
latent family, every causal latent row attended, experts top-4 with a
shared expert, and a multi-token-prediction layer) against its plain
reference, on the CPU at toy widths with seeded weights: the whole
forward, main and MTP logits; a prompt prefilled in chunks and then
decoded through the latent cache; the prefill's MTP rows and first
draft; a verify pass; the serve loop with `spec_decode="mtp"` against
plain greedy, pipelined, with slots that end on their budget and on an
eos inside an accepted pair; what refuses; the paged dense decode kernel
and the causal prefill kernel against plain JAX; and the sparse
configuration's programs, which must lower to the parent commit's text.

The reference (`chipbench/reference/glm47_flash.py`) is float32
`highest`, one sequence at a time, every head expanded, and imports
nothing of the program.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.adapters import glm47_flash as adapter
from chipbench.reference import glm47_flash as ref
from paddle_tpu.models import deepseek_v32 as dm
from paddle_tpu.models.paged_decode import PagedDecoder

F32 = jnp.float32
CFG = dict(model_type="glm4_moe_lite", vocab_size=256, hidden_size=64,
           intermediate_size=128, moe_intermediate_size=32,
           num_hidden_layers=3, first_k_dense_replace=1,
           num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
           n_routed_experts=8, num_experts_per_tok=4, n_group=1,
           topk_group=1, n_shared_experts=1, routed_scaling_factor=1.8,
           norm_topk_prob=True, rms_norm_eps=1e-5, rope_theta=10000,
           rope_scaling=None, max_position_embeddings=256,
           num_nextn_predict_layers=1, partial_rotary_factor=1,
           initializer_range=0.16, torch_dtype="float32")
SEED = 2**31 + 41
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


def _jitted(dec, name):
    if name not in dec.__dict__.setdefault("_test_jits", {}):
        dec._test_jits[name] = jax.jit(getattr(dec, name))
    return dec._test_jits[name]


# -- the whole forward ----------------------------------------------------------------

@pytest.mark.parametrize("length", [2, 16, 17, 70])
def test_forward_logits_match_the_reference(model, weights, length):
    """Both logit sets of the full forward: the main model's at every
    row, the MTP layer's at every row whose next token is in the
    sequence (the last row's comes from the greedy token after it)."""
    ids = _ids(length, seed=length)
    logits, draft = model(jnp.asarray(ids)[None])
    rows = jnp.arange(length)
    _close(logits._data[0], ref.logits_at(CFG, weights, ids, rows))
    _close(draft._data[0, :-1],
           ref.draft_logits_at(CFG, weights, ids, rows[:-1]))
    # the last row: the greedy token appended
    nxt = int(np.asarray(logits._data[0, -1]).argmax())
    longer = np.concatenate([ids, [nxt]]).astype(np.int32)
    _close(draft._data[0, -1:],
           ref.draft_logits_at(CFG, weights, longer, rows[-1:]))


def test_config_is_the_dense_member_with_an_mtp_layer(model):
    cfg = model.config
    assert not cfg.has_indexer and cfg.num_nextn_predict_layers == 1
    names = cfg.param_shapes()
    assert not any("idx" in n or "k_norm" in n for n in names)
    assert names["mtp.eh_proj"] == ((128, 64), False)
    assert names["mtp.w1"][0] == (8, 64, 32)
    assert cfg.softmax_scale == (16 + 8) ** -0.5
    assert set(model.param_tree()["mtp"]) >= {"enorm", "hnorm", "eh_proj",
                                              "norm", "wkv_b", "router"}


# -- prefill and decode through the latent pool -----------------------------------------

def test_paged_decoder_builds_the_dense_latent_engine(model):
    dec = _decoder(model)
    assert isinstance(dec, dm.LatentPagedDecoder)
    lat, = dec.new_pools()
    # three main layers and the MTP layer; a latent row [c | k_pe] = 32 +
    # 8, kept in a whole lane; no indexer pool
    assert lat.shape == (4, 97, BLOCK, 128)
    assert dec.kv_token_bytes() == 128 * 4
    assert dec.pool_bytes() == lat.size * 4
    assert dec.draft_layers == 1 and dec.COUNTERS == dec.DENSE_COUNTERS


def _prefill(dec, pools, slot, prompt, tables):
    encs = []
    for head, tail in dec._prefill_calls(
            dec.prefill_chunk, [(slot, list(prompt), 0)], tables, 0):
        # not the donating program of `_prefill_exec`: a test may hand the
        # same pools to two prefills
        enc, *pools = _jitted(dec, "_prefill_dense")(
            dec._params, *head, *pools, *tail)
        encs.append(enc)
    return encs, pools


def _tables(dec, slot):
    blocks = np.random.default_rng(3).permutation(np.arange(1, 97))[:32]
    tables = np.zeros((dec.max_slots, dec.blocks_per_seq), np.int32)
    tables[slot, :32] = blocks
    return tables


@pytest.mark.parametrize("n_prompt", [5, 32, 75])
def test_prefill_then_decode_gives_the_reference_logits(model, weights,
                                                        n_prompt):
    """A prompt shorter than a chunk, one that fills its chunk, and one of
    three chunks that ends inside a block; then plain decode steps
    through the latent pool, each reading every row up to its own."""
    dec, slot = _decoder(model), 1
    ids = _ids(n_prompt + 12, seed=9)
    tables = _tables(dec, slot)
    encs, pools = _prefill(dec, dec.new_pools(), slot, ids[:n_prompt],
                           tables)
    want = np.asarray(ref.logits_at(CFG, weights, ids,
                                    jnp.arange(n_prompt - 1, len(ids))))
    assert dec.decode_first_token(encs) == (int(want[0].argmax()), False)
    active = jnp.arange(dec.max_slots) == slot
    for step, token in enumerate(ids[n_prompt:]):
        tokens = jnp.zeros(dec.max_slots, jnp.int32).at[slot].set(token)
        lens = jnp.zeros(dec.max_slots, jnp.int32).at[slot].set(
            n_prompt + step)
        logits, *pools, counts, attn = _jitted(dec, "_dense_step")(
            dec._params, tokens, lens, jnp.asarray(tables), active, *pools)
        _close(logits[slot], want[step + 1])
        # one row, its n_prompt + step + 1 keys, read once
        keys = n_prompt + step + 1
        assert attn.tolist() == [1, keys, keys]
        assert int(counts[1]) == 4 * 2     # one row's 4 pairs, 2 expert layers


@pytest.mark.parametrize("n_prompt", [5, 40])
def test_prefill_writes_the_mtp_rows_and_the_first_draft(model, weights,
                                                         n_prompt):
    """The prompt's MTP rows are written at prefill (the last from the
    first generated token), and the first draft is the reference MTP's
    best at the prompt's last row; a verify pass of the first token and a
    draft then gives the reference's logits at its two rows, and the
    drafts that would follow either are the reference MTP's best there."""
    dec, slot = _decoder(model), 1
    ids = _ids(n_prompt, seed=13)
    tables = _tables(dec, slot)
    encs, pools = _prefill(dec, dec.new_pools(), slot, ids, tables)
    first, _ = dec.decode_first_token(encs)
    draft = dec.first_draft()
    seq = np.concatenate([ids, [first]]).astype(np.int32)
    mtp = np.asarray(ref.draft_logits_at(CFG, weights, seq,
                                         jnp.arange(n_prompt)))
    assert draft == int(mtp[-1].argmax())
    # a verify pass: the first token and a draft (the MTP's own)
    tok = jnp.zeros(dec.max_slots, jnp.int32).at[slot].set(first)
    dr = jnp.zeros(dec.max_slots, jnp.int32).at[slot].set(draft)
    lens = jnp.zeros(dec.max_slots, jnp.int32).at[slot].set(n_prompt)
    active = jnp.arange(dec.max_slots) == slot
    logits, g, cand, pool, counts, attn = _jitted(dec, "_verify_step")(
        dec._params, tok, dr, lens, jnp.asarray(tables), active, *pools)
    seq2 = np.concatenate([seq, [draft]]).astype(np.int32)
    want = np.asarray(ref.logits_at(CFG, weights, seq2,
                                    jnp.arange(n_prompt, n_prompt + 2)))
    _close(logits[slot], want)
    assert np.asarray(g[slot]).tolist() == want.argmax(-1).tolist()
    # the MTP rows at the pass's two positions take the target's tokens
    # (row p + 1's main row saw the draft, accepted or not)
    rows_p = np.asarray(ref.draft_logits_at(
        CFG, weights, np.concatenate([seq, want.argmax(-1)[:1]])
        .astype(np.int32), jnp.arange(n_prompt, n_prompt + 1)))
    assert int(cand[slot, 0]) == int(rows_p[0].argmax())
    rows_q = np.asarray(ref.draft_logits_at(
        CFG, weights, np.concatenate([seq2, want.argmax(-1)[1:]])
        .astype(np.int32), jnp.arange(n_prompt + 1, n_prompt + 2)))
    assert int(cand[slot, 1]) == int(rows_q[0].argmax())
    assert attn.tolist() == [2, 2 * n_prompt + 3, n_prompt + 2]


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
    return reqs, dec.serve(reqs, max_new_tokens=20, chunk=4)


@pytest.mark.parametrize("rid", range(6))
def test_serve_tokens_are_the_reference_argmax(served, weights, rid):
    reqs, out = served
    _, prompt, budget = reqs[rid]
    assert len(out[rid]) == budget
    seq = np.asarray(prompt + out[rid], np.int32)
    logits = np.asarray(ref.logits_at(
        CFG, weights, seq, jnp.arange(len(prompt) - 1, len(seq) - 1)))
    picked = logits[np.arange(budget), out[rid]]
    assert (logits.max(-1) - picked).max() <= 1e-5


@pytest.mark.parametrize("how", [dict(pipelined_admission=True),
                                 dict(pipelined_admission=True,
                                      pipeline=True),
                                 dict(pipeline=False)])
def test_mtp_serves_the_plain_greedy_tokens(served, model, how):
    """`spec_decode="mtp"` through the normal loop, pipelined (look-ahead
    on, pipelined admission) or not: the tokens of plain greedy decode."""
    reqs, want = served
    how = dict(how)
    pipeline = how.pop("pipeline", None)
    dec = _decoder(model, slots=3, **how)
    got = dec.serve(reqs, max_new_tokens=20, chunk=4, spec_decode="mtp",
                    pipeline=pipeline)
    assert got == want
    st = dec.spec_stats
    assert st["emitted"] == sum(len(v) for v in want.values()) - len(want)
    assert st["emitted"] == st["proposed"] + st["accepted"]
    if pipeline is not False:
        assert dec.lookahead_dispatches > 0


def _copycat(model):
    """The model with its blocks' outputs zeroed and the MTP input
    projection passing the next token's embedding: the target's next
    token is a function of the current token alone, and the MTP layer's
    draft is that function applied to the token after it, so every draft
    is accepted."""
    tree = model.param_tree()
    params = jax.tree_util.tree_map(lambda x: x, tree)
    for p in params["layers"] + [params["mtp"]]:
        for leaf in ("wo", "wd", "w2", "ws_d"):
            if leaf in p:
                p[leaf] = jnp.zeros_like(p[leaf])
    h = CFG["hidden_size"]
    params["mtp"]["eh_proj"] = jnp.concatenate(
        [jnp.eye(h, dtype=F32), jnp.zeros((h, h), F32)])
    return params


@pytest.mark.parametrize("budgets", [(7, 8, 5), (2, 9, 4)])
def test_accepted_pairs_end_on_the_budget(model, budgets):
    """Every draft accepted: passes yield two tokens, and a slot whose
    budget ends inside a pair stops there."""
    reqs = [(r, _ids(9 + 5 * r, seed=r).tolist(), b)
            for r, b in enumerate(budgets)]
    plain = _decoder(model, slots=3)
    plain._params = _copycat(model)
    want = plain.serve(reqs, max_new_tokens=9, chunk=3)
    dec = _decoder(model, slots=3, pipelined_admission=True)
    dec._params = _copycat(model)
    got = dec.serve(reqs, max_new_tokens=9, chunk=3, spec_decode="mtp")
    assert got == want
    assert {r: len(v) for r, v in got.items()} == dict(enumerate(budgets))
    st = dec.spec_stats
    assert st["accepted"] > 0 and st["accepted"] >= st["proposed"] - 3
    assert st["emitted"] == st["proposed"] + st["accepted"]


@pytest.mark.parametrize("at", [1, 2, 3])
def test_eos_inside_an_accepted_pair_ends_the_stream(model, at):
    """An eos as the first or the second token of an accepted pair: the
    stream through the eos is plain greedy's, and the slot stops there
    (plain chunks run on to the chunk's end and pad)."""
    reqs = [(0, _ids(13, seed=5).tolist(), 9)]
    plain = _decoder(model, slots=1)
    plain._params = _copycat(model)
    stream = plain.serve(reqs, max_new_tokens=9, chunk=3)[0]
    eos = stream[at]
    cut = stream.index(eos)
    want = plain.serve(reqs, max_new_tokens=9, chunk=3, eos_token_id=eos)[0]
    dec = _decoder(model, slots=1)
    dec._params = _copycat(model)
    got = dec.serve(reqs, max_new_tokens=9, chunk=3, eos_token_id=eos,
                    spec_decode="mtp")[0]
    assert got[:cut + 1] == want[:cut + 1] == stream[:cut + 1]
    assert got[cut + 1:] == [0] * (len(got) - cut - 1)
    assert len(got) <= len(want)


def test_counters_ride_the_commit_spans(model):
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import tracing
    dec = _decoder(model, slots=3)
    reqs = _serve_requests()[:3]
    tracing.enable_tracing()
    obs.enable()
    try:
        dec.serve(reqs, max_new_tokens=20, chunk=4, spec_decode="mtp")
        text = obs.scrape()
    finally:
        obs.disable()
        tracing.disable_tracing()
    commits = [s["meta"] for s in tracing.tail()
               if s["name"] == "serve:commit" and "drafted" in s["meta"]]
    assert commits
    st = dec.spec_stats
    assert sum(m["drafted"] for m in commits) == st["proposed"]
    assert sum(m["accepted"] for m in commits) == st["accepted"]
    # two rows a verify pass: the tokens never pass the rows computed
    assert all(m["steps"] % 2 == 0 and m["tokens"] <= 3 * m["steps"]
               for m in commits)
    assert all(m["attn_rows"] <= 3 * m["steps"] for m in commits)
    for name in ("verify_calls", "proposed", "accepted"):
        assert f"paddle_tpu_spec_decode_{name}_total" in text


# -- what refuses ---------------------------------------------------------------------

@pytest.mark.parametrize("spec,match", [
    (2, "host-side draft"), ({"k": 1, "draft": "ngram"}, "host-side draft"),
    ({"k": 2, "draft": "mtp"}, "k=2"), ({"k": 3, "draft": "ngram"},
                                        "host-side draft")])
def test_host_side_drafts_and_longer_drafts_refuse(model, spec, match):
    dec = _decoder(model)
    with pytest.raises(NotImplementedError, match=match):
        dec.serve(_serve_requests()[:1], spec_decode=spec)


def test_engines_without_an_mtp_draft_refuse_it():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    sparse = dm.DeepseekV32ForCausalLM(dm.deepseek_v32_tiny(), seed=1)
    dec = PagedDecoder(sparse, max_len=64, block_size=8, num_blocks=23,
                       max_slots=2, prefill_chunk=16)
    with pytest.raises(NotImplementedError, match="sparse configuration"):
        dec.serve([(0, [1, 2, 3], 2)], spec_decode="mtp")
    llama = LlamaForCausalLM(LlamaConfig(
        vocab_size=97, hidden_size=64, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, use_flash_attention=False,
        dtype="float32"))
    dec = PagedDecoder(llama, max_len=64, block_size=8, max_slots=2)
    with pytest.raises(NotImplementedError, match="multi-token-prediction"):
        dec.serve([(0, [1, 2, 3], 2)], spec_decode="mtp")


# -- the sparse configuration's programs are the parent's ---------------------------------

PARENT = {
    "latent_chunk":
        "f1fadb677cacc99afbbd9c1a862864b8b3bf19a7397498ddff2394cf87e54b57",
    "latent_prefill":
        "be14ac90dc1ff0969f2861b7fc56694e8f19ba1b35f419574d8533dd4a5fa9fc",
}


def test_sparse_programs_lower_to_the_parents_text():
    """sha256 of the CPU lowering of the V3.2 configuration's decode
    chunk and prefill chunk (the prefill's since its attention kernel
    takes a group of heads a grid step), at toy widths whose
    heads' Wkvb columns are whole lanes (dn + dv = 128), as the cell's
    (256) are: the indexer made optional, the dense engine and the
    causal prefill kernel beside them move neither. A PR that means to
    change them replaces the digests."""
    model = dm.DeepseekV32ForCausalLM(
        dm.deepseek_v32_tiny(qk_nope_head_dim=64, v_head_dim=64), seed=3)
    dec = PagedDecoder(model, max_len=64, block_size=8, num_blocks=23,
                       max_slots=4, prefill_chunk=16)
    s, mb = dec.max_slots, dec.blocks_per_seq
    pools = dec.new_pools()
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    flag = lambda *shape: jnp.zeros(shape, bool)
    texts = {
        "latent_chunk": dec._paged_chunk_state_jit.lower(
            dec._params, i32(s), i32(s), i32(s, mb), flag(s), i32(s),
            flag(s), *pools, 2, -1).as_text(),
        "latent_prefill": dec._prefill_exec(16).lower(
            dec._params, i32(16), i32(), i32(), i32(mb), *pools).as_text()}
    for name, text in texts.items():
        assert hashlib.sha256(text.encode()).hexdigest() == PARENT[name], name


# -- the kernels, interpreted -----------------------------------------------------------

def _paged_reference(qc, qpe, pool, tables, lens, base, kvr, scale):
    S = qc.shape[0]
    ctx = jnp.take(pool, tables + base, axis=0).reshape(S, -1, pool.shape[-1])
    c, pe = ctx[..., :kvr], ctx[..., kvr:kvr + qpe.shape[-1]]
    s = (jnp.einsum("srhc,sjc->srhj", qc, c)
         + jnp.einsum("srhd,sjd->srhj", qpe, pe)) * scale
    j = jnp.arange(ctx.shape[1])
    s = jnp.where(j[None, None, None] < lens[:, :, None, None], s, -jnp.inf)
    return jnp.einsum("srhj,sjc->srhc", jax.nn.softmax(s, -1), c)


@pytest.mark.parametrize("rows", [1, 2])
@pytest.mark.parametrize("product_keys,buffer_bytes", [
    (2048, 8 << 20),          # the defaults: one product a whole table
    (16, 2 * 2 * 2 * 4096),   # two blocks a product, two products a group
    (32, 2 * 4 * 4096),       # a group of four: the long slot's second ends
                              # after two live blocks, inside its product
    (8, 2 * 4096),            # one block a group and a product
])
def test_paged_decode_kernel_is_the_plain_paged_attention(
        monkeypatch, rows, product_keys, buffer_bytes):
    """The dense kernel against gathering every block of a slot's table:
    ragged lengths (a block and one, one key, the whole table right after
    that one-block slot, so the next slot's first group is copied through
    its own table, three blocks less a key), a row a slot and two,
    products of one, two and six blocks, groups cut short by a slot's
    end, tables out of order, a layer's base. Blocks here are 4,096 B (8
    rows of 128 float32 lanes)."""
    from paddle_tpu.kernels.pallas import mla_paged_decode as kernel
    monkeypatch.setattr(kernel, "_PRODUCT_KEYS", product_keys)
    monkeypatch.setattr(kernel, "_BUFFER_BYTES", buffer_bytes)
    rng = np.random.default_rng(rows)
    S, H, kvr, dr, bs, MB, NB, W = 4, 4, 32, 8, 8, 6, 40, 128
    pool = jnp.asarray(rng.normal(size=(2 * NB, bs, W)), F32)
    tables = jnp.asarray(rng.integers(1, NB, (S, MB)), jnp.int32)
    last = np.asarray([9, 1, 48, 23])
    lens = jnp.asarray(np.stack([last - rows + 1 + r for r in range(rows)],
                                1).clip(1), jnp.int32)
    qc = jnp.asarray(rng.normal(size=(S, rows, H, kvr)), F32)
    qpe = jnp.asarray(rng.normal(size=(S, rows, H, dr)), F32)
    got = kernel.mla_paged_decode_attention(qc, qpe, pool, tables, lens, NB,
                                            kvr, 0.3)
    _close(got, _paged_reference(qc, qpe, pool, tables, lens, NB, kvr, 0.3),
           1e-5)


def _causal_reference(q, lat, w, q_start, kvr, dr, dv, scale):
    """Every head's keys and values expanded, [Tq, nh, dv]."""
    tq, nh, _ = q.shape
    kv = (lat[:, :kvr] @ w).reshape(lat.shape[0], nh, -1)
    pe = jnp.broadcast_to(lat[:, None, kvr:kvr + dr], (lat.shape[0], nh, dr))
    k = jnp.concatenate([kv[..., :-dv], pe], -1)
    s = jnp.einsum("thd,jhd->htj", q, k) * scale
    rows = q_start + jnp.arange(tq)
    s = jnp.where(jnp.arange(lat.shape[0])[None] <= rows[:, None], s,
                  -jnp.inf)
    return jnp.einsum("htj,jhd->thd", jax.nn.softmax(s, -1), kv[..., -dv:])


@pytest.mark.parametrize("q_start,nh,dn,dv,tk,group", [
    pytest.param(0, 2, 16, 24, 64, None, id="0"),
    pytest.param(32, 2, 16, 24, 64, None, id="32"),
    # 10 heads, 5 a grid step, dn + dv = 64 (no whole lanes a head),
    # 1,536 keys in three tiles: tile 0 below the rows' diagonal, tile 1
    # across it, tile 2 past the chunk's last row
    pytest.param(700, 10, 24, 40, 1536, 5, id="10h-G5-q700"),
    pytest.param(1504, 10, 24, 40, 1536, 5, id="10h-G5-q1504")])
def test_causal_prefill_kernel_is_the_all_ones_masked_form(
        monkeypatch, q_start, nh, dn, dv, tk, group):
    from paddle_tpu.kernels.pallas import mla_prefill
    if group is not None:
        monkeypatch.setattr(mla_prefill, "_heads_per_step",
                            lambda *_: group)
    rng = np.random.default_rng(q_start)
    tq, dr, kvr, W = 32, 8, 32, 128
    q = jnp.asarray(rng.normal(size=(tq, nh, dn + dr)), F32)
    lat = jnp.asarray(rng.normal(size=(tk, W)), F32)
    w = jnp.asarray(rng.normal(size=(kvr, nh * (dn + dv))) * 0.2, F32)
    causal = mla_prefill.mla_prefill_attention(q, lat, w, None, q_start,
                                               kvr, dr, 0.2)
    masked = mla_prefill.mla_prefill_attention(
        q, lat, w, jnp.ones((tq, tk), bool), q_start, kvr, dr, 0.2)
    _close(causal, masked, 1e-6)
    _close(causal, _causal_reference(q, lat, w, q_start, kvr, dr, dv, 0.2),
           1e-5)
