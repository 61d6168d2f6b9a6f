"""The `nemotron_h` family (Mamba-2 + attention + LatentMoE) against its
plain reference, on the CPU at toy widths with seeded weights: every
mixer, the whole forward, the chunked scan against the step-by-step
recurrence, the state a padded prefill hands to decode, prefill and then
decode through the two caches, slot reuse, the routing rules, the four
shares of one expert layer, and the options that refuse.

The reference (`chipbench/reference/nemotron_h.py`) is float32 `highest`,
sequential, one expert at a time, and imports nothing of the program.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.adapters import nemotron_h as adapter
from chipbench.reference import nemotron_h as ref
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.models.paged_decode import PagedDecoder

F32 = jnp.float32
CFG = dict(vocab_size=256, hidden_size=64, num_hidden_layers=5,
           hybrid_override_pattern="MEM*E", num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
           mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
           chunk_size=8, n_routed_experts=8, experts_first=4,
           published={"n_routed_experts": 16}, num_experts_per_tok=4,
           moe_latent_size=32, moe_intermediate_size=48,
           moe_shared_expert_intermediate_size=96,
           routed_scaling_factor=5.0, norm_topk_prob=True,
           layer_norm_epsilon=1e-5, max_position_embeddings=256,
           time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
           torch_dtype="float32")
SEED = 2**31 + 23
TOL = 2e-5          # float32 against float32 `highest`, sums reordered


@pytest.fixture(scope="module")
def weights():
    """The reference's seeded leaves, bfloat16 values held in float32 so
    that program and reference compute on the same numbers."""
    return {k: v.astype(F32) for k, v in ref.make_weights(CFG, SEED).items()}


@pytest.fixture(scope="module")
def model(weights):
    return adapter.build_model(CFG, weights)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], n).astype(np.int32)


def _hidden(n, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(n, CFG["hidden_size"])), F32)


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())


# -- each mixer, and the whole forward ---------------------------------------------

@pytest.mark.parametrize("kind,layer", [("M", 0), ("E", 1), ("*", 3)])
def test_mixer_matches_the_reference(model, weights, kind, layer):
    cfg, p = model.config, model.param_tree()["layers"][layer]
    rp = ref.layer_params(weights, layer)
    u = _hidden(21)
    if kind == "M":
        got = nh.mamba_sequence(cfg, p, u, *nh.one_segment(21))[0]
        want = ref.mamba_mixer(CFG, rp, u, "f32")[0]
    elif kind == "*":
        got = nh.attention_sequence(cfg, p, u, *nh.one_segment(21))[0]
        want = ref.attention_mixer(CFG, rp, u, "f32")
    else:
        got = nh.latent_moe(cfg, p, u)[0]
        want = ref.moe_mixer(CFG, rp, u, "f32")
    _close(got, want)


@pytest.mark.parametrize("length", [1, 8, 37])
def test_forward_logits_match_the_reference(model, weights, length):
    ids = _ids(length)
    got = model.forward(ids[None])._data[0]
    want = ref.logits_at(CFG, weights, jnp.asarray(ids), jnp.arange(length))
    _close(got, want)


# -- the chunked scan is the recurrence ---------------------------------------------

def _ssm_inputs(n, seed=3):
    rng = np.random.default_rng(seed)
    heads, hd, g, st = 8, 8, 2, 16
    return (jnp.asarray(rng.normal(size=(n, heads, hd)), F32),
            jnp.asarray(rng.normal(size=(n, g, st)), F32),
            jnp.asarray(rng.normal(size=(n, g, st)), F32),
            jnp.asarray(rng.uniform(0.001, 0.3, size=(n, heads)), F32),
            -jnp.asarray(rng.uniform(1.0, 16.0, size=heads), F32),
            jnp.asarray(rng.normal(size=heads), F32))


@pytest.mark.parametrize("length", [3, 8, 13, 29])
def test_chunked_scan_is_the_sequential_recurrence(length):
    x, b, c, dt, a, d = _ssm_inputs(length)
    y, after = nh.ssd_chunked(x, b, c, dt, a, d, 8,
                              nh.one_segment(length)[0])
    y_ref, last_ref = ref.ssm_sequential(x, b, c, dt, a, d)
    _close(y, y_ref)
    _close(after[-1], last_ref)


def test_decode_step_is_one_step_of_the_recurrence():
    x, b, c, dt, a, d = _ssm_inputs(6)
    state = jnp.zeros((1, 8, 8, 16), F32)
    ys = []
    for t in range(6):
        y, state = nh.ssm_step(state, x[t][None], b[t][None], c[t][None],
                               dt[t][None], a, d)
        ys.append(y[0])
    y_ref, last_ref = ref.ssm_sequential(x, b, c, dt, a, d)
    _close(jnp.stack(ys), y_ref)
    _close(state[0], last_ref)


@pytest.mark.parametrize("true_len,bucket", [(1, 8), (2, 8), (5, 16),
                                             (13, 16), (16, 16)])
def test_padded_bucket_leaves_the_state_of_true_len(model, true_len, bucket):
    """Positions behind the prompt contribute nothing: the SSM state and
    the conv's rows a padded prefill hands to decode are those of the
    unpadded prompt, and the real positions' outputs do not move."""
    cfg, p = model.config, model.param_tree()["layers"][0]
    u = _hidden(bucket, seed=true_len)
    one = nh.one_segment(true_len)
    out, after, conv = nh.mamba_sequence(cfg, p, u, *one)
    out1, after1, conv1 = nh.mamba_sequence(cfg, p, u[:true_len], *one)
    state = after[nh.last_chunk(*one, cfg.chunk_size, bucket)[0]]
    state1 = after1[-1]
    _close(state, state1)
    _close(conv, conv1)
    _close(out[:true_len], out1)
    conv = conv[0]
    # and the conv's rows are the last three inputs before true_len
    xbc = nh.mamba_project(cfg, p, u)[1]
    want = np.zeros((3, xbc.shape[1]), np.float32)
    have = np.asarray(xbc[max(true_len - 3, 0):true_len])
    want[3 - len(have):] = have
    _close(conv, want)


# -- prefill, then decode through the caches ---------------------------------------

def _decoder(model, slots=2, **kw):
    return PagedDecoder(model, max_len=64, block_size=8, num_blocks=33,
                        max_slots=slots, **kw)


def test_paged_decoder_builds_the_hybrid_engine(model):
    dec = _decoder(model)
    assert isinstance(dec, nh.HybridPagedDecoder)
    kpool, vpool, ssm, conv = dec.new_pools()
    # the KV pool holds the one attention block only
    assert kpool.shape == vpool.shape == (1, 33, 8, 2, 16)
    assert ssm.shape == (2, 2, 8, 8, 16) and ssm.dtype == F32
    assert conv.shape == (2, 2, 3, 8 * 8 + 2 * 2 * 16)
    assert dec.slot_state_bytes == 2 * (8 * 8 * 16 * 4 + 3 * 128 * 4)


def test_prefill_then_decode_gives_the_reference_logits(model, weights):
    """Prompt of 11 into a bucket of 16, then 9 decode steps through the
    paged K and V and the recurrent state, teacher-forced: the logits of
    every step against the reference's one full forward."""
    dec = _decoder(model)
    ids = _ids(20, seed=9)
    prompt, rest = ids[:11], ids[11:]
    pools = dec.new_pools()
    table = np.zeros(dec.blocks_per_seq, np.int32)
    table[:3] = [5, 2, 7]
    slot = 1
    tables = np.zeros((2, dec.blocks_per_seq), np.int32)
    tables[slot] = table
    head, tail = dec._prefill_inputs(16, [(slot, prompt, 0)], tables, 0)
    enc, *pools = dec._prefill_paged(dec._params, *head, *pools, *tail)
    want = np.asarray(ref.logits_at(CFG, weights, jnp.asarray(ids),
                                    jnp.arange(10, 20)))
    assert dec.decode_first_token([enc]) == (int(want[0].argmax()), False)
    active = jnp.asarray([False, True])
    for step, token in enumerate(rest):
        tokens = jnp.asarray([0, token], jnp.int32)
        lens = jnp.asarray([0, 11 + step], jnp.int32)
        logits, *pools, counts, _ = dec._hybrid_step(
            dec._params, tokens, lens, jnp.asarray(tables), active, *pools)
        _close(logits[slot], want[1 + step])
        assert int(counts[1]) == 2 * 4        # pairs of the one active row
    # the slot that never ran kept its zero state
    assert not np.asarray(pools[2][:, 0]).any()


def _serve_requests():
    rng = np.random.default_rng(4)
    shapes = [(5, 9), (12, 20), (8, 3), (17, 11), (3, 17), (9, 6), (30, 25)]
    return [(rid, rng.integers(0, 256, n).tolist(), budget)
            for rid, (n, budget) in enumerate(shapes)]


@pytest.fixture(scope="module")
def served(model):
    dec = PagedDecoder(model, max_len=64, block_size=8, num_blocks=33,
                       max_slots=3)
    reqs = _serve_requests()
    return dec, reqs, dec.serve(reqs, max_new_tokens=25, chunk=4)


def test_serve_tokens_are_the_reference_argmax(served, weights):
    _, reqs, out = served
    for rid, prompt, budget in reqs:
        assert len(out[rid]) == budget
        seq = np.asarray(prompt + out[rid], np.int32)
        logits = np.asarray(ref.logits_at(
            CFG, weights, jnp.asarray(seq),
            jnp.arange(len(prompt) - 1, len(seq) - 1)))
        picked = logits[np.arange(budget), out[rid]]
        assert (logits.max(-1) - picked).max() <= 1e-5


@pytest.mark.parametrize("rid", [3, 6])
def test_reused_slot_gives_what_the_request_gives_alone(served, model, rid):
    """Seven requests over three slots: a later request runs in a slot
    whose state an earlier one left behind, and must not see it."""
    _, reqs, out = served
    alone = PagedDecoder(model, max_len=64, block_size=8, num_blocks=33,
                         max_slots=3)
    assert alone.serve([reqs[rid]], max_new_tokens=25, chunk=4)[rid] \
        == out[rid]


@pytest.mark.parametrize("eos", [None, 7])
def test_pipelined_admission_serves_the_same_tokens(served, model, eos):
    """`pipelined_admission=True`: a scan dispatches its prompts' one
    packed prefill (three free slots: the first scan takes three
    prompts, 5 + 12 + 8 tokens from rows 0, 8 and 24 of a 32-row
    program) before it reads a first token, and every request gets the
    tokens it gets with one prompt in flight at a time."""
    from paddle_tpu.observability import tracing
    reqs = _serve_requests()
    kw = dict(max_new_tokens=25, chunk=4, eos_token_id=eos)
    want = served[2] if eos is None else \
        _decoder(model, slots=3).serve(reqs, **kw)
    dec = _decoder(model, slots=3, pipelined_admission=True)
    tracing.enable_tracing()
    try:
        tracing.drain()
        got = dec.serve(reqs, **kw)
        spans = tracing.drain()
    finally:
        tracing.disable_tracing()
    assert got == want
    order = [s["name"] for s in sorted(spans, key=lambda s: s["t0_ns"])
             if s["name"] in ("serve:prefill", "serve:wait_first_token")]
    assert order[:4] == ["serve:prefill"] + ["serve:wait_first_token"] * 3
    first = next(s["meta"] for s in spans if s["name"] == "serve:prefill")
    assert first == {"bucket": 32, "prompts": 3, "rows": 5 + 12 + 8}
    ids = {s["id"]: s["name"] for s in spans}
    admits = [s for s in spans if s["name"] == "serve:admit"]
    assert len(admits) == len(reqs)
    assert all("state_bytes" in s["meta"] and "bucket" in s["meta"]
               for s in admits)
    assert {ids[s["parent"]] for s in spans
            if s["name"] == "serve:prefill"} == {"serve:iteration"}
    assert {ids[s["parent"]] for s in spans
            if s["name"] == "serve:wait_first_token"} == {"serve:admit"}


def test_eos_keeps_the_lookahead_out_of_reach(model):
    """With an eos the loop runs no look-ahead chunk for this engine (a
    recurrent state stepped past the cut could not be taken back), and
    gives what the serial loop gives."""
    reqs = _serve_requests()
    eos = 7
    a = _decoder(model, slots=3)
    got = a.serve(reqs, max_new_tokens=25, chunk=4, eos_token_id=eos)
    assert a.lookahead_dispatches == 0
    b = _decoder(model, slots=3)
    assert got == b.serve(reqs, max_new_tokens=25, chunk=4,
                          eos_token_id=eos, pipeline=False)
    c = _decoder(model, slots=3)
    c.serve(reqs, max_new_tokens=25, chunk=4)
    assert c.lookahead_dispatches > 0


def test_chunk_counters_ride_the_commit_span(model):
    from paddle_tpu.observability import tracing
    dec = _decoder(model, slots=3)
    tracing.enable_tracing()
    try:
        tracing.drain()
        out = dec.serve(_serve_requests()[:3], max_new_tokens=25, chunk=4)
        spans = tracing.drain()
    finally:
        tracing.disable_tracing()
    commits = [s["meta"] for s in spans if s["name"] == "serve:commit"]
    assert commits and all(set(dec.COUNTERS) <= set(m) for m in commits)
    decoded = sum(len(v) - 1 for v in out.values())
    assert sum(m["ssm_rows"] for m in commits) == 2 * decoded
    assert sum(m["moe_pairs_all"] for m in commits) == 2 * 4 * decoded
    here = sum(m["moe_pairs_here"] for m in commits)
    assert 0 < here < 2 * 4 * decoded        # 8 of 16 experts are held
    assert all(m["moe_max_load"] <= 3 for m in commits)
    # an admission's span: the state its prefill overwrote, and the
    # prompt's own counts (every prompt row meets both expert blocks)
    admits = [s["meta"] for s in spans if s["name"] == "serve:admit"]
    assert [m["state_bytes"] for m in admits] == [dec.slot_state_bytes] * 3
    assert [m["moe_pairs_all"] for m in admits] \
        == [2 * 4 * m["prompt_tokens"] for m in admits]
    assert all(0 < m["moe_pairs_here"] < m["moe_pairs_all"]
               and 0 < m["moe_experts_touched"] <= 2 * 8
               and "ssm_rows" not in m for m in admits)


# -- routing -----------------------------------------------------------------------------

def _route(model, u, **override):
    cfg, p = model.config, dict(model.param_tree()["layers"][1])
    p.update(override)
    return nh.moe_route(cfg, p, u), p


def test_routing_normalises_over_all_chosen_and_scales(model):
    (idx, w), _ = _route(model, _hidden(19))
    assert idx.shape == w.shape == (19, 4)
    # over all four chosen, held here (ids 4..11) or not
    _close(w.sum(-1), np.full(19, 5.0))
    held = (np.asarray(idx) >= 4) & (np.asarray(idx) < 12)
    assert held.any() and not held.all()


def test_routing_weights_are_the_sigmoid_scores(model):
    u = _hidden(19)
    (idx, w), p = _route(model, u)
    s = np.asarray(jax.nn.sigmoid(u @ p["router"]))
    picked = np.take_along_axis(s, np.asarray(idx), 1)
    _close(w, 5.0 * picked / picked.sum(-1, keepdims=True))


def test_correction_bias_moves_the_choice_not_the_weights(model):
    u = _hidden(19)
    bias = np.zeros(16, np.float32)
    bias[13] = 10.0                                  # always chosen now
    (idx, w), p = _route(model, u, b_corr=jnp.asarray(bias))
    idx, w = np.asarray(idx), np.asarray(w)
    assert (idx == 13).any(axis=1).all()
    s = np.asarray(jax.nn.sigmoid(u @ p["router"]))
    picked = np.take_along_axis(s, idx, 1)          # scores without bias
    _close(w, 5.0 * picked / picked.sum(-1, keepdims=True))
    (idx0, _), _ = _route(model, u)
    assert not (np.asarray(idx0) == 13).any(axis=1).all()


def test_four_shares_of_an_expert_layer_add_up_to_the_whole(weights):
    """Four chips hold four experts each of one layer's 16; each routes
    over all 16 and computes its own experts' part. Those parts, with the
    shared expert counted once, add up to the uncut reference layer."""
    whole_cfg = dict(CFG, n_routed_experts=16, experts_first=0)
    rp = ref.layer_params({k: v.astype(F32) for k, v in ref.make_weights(
        whole_cfg, SEED).items()}, 1)
    u = _hidden(23, seed=5)
    want = ref.moe_mixer(whole_cfg, rp, u, "f32")
    shared = ref.linear(ref.relu2(ref.linear(u, rp["ws1"], "f32")),
                        rp["ws2"], "f32")
    total = jnp.zeros_like(want)
    for first in (0, 4, 8, 12):
        cfg = adapter.program_config(dict(
            CFG, n_routed_experts=4, experts_first=first))
        p = dict(rp, w1=rp["w1"][first:first + 4],
                 w2=rp["w2"][first:first + 4])
        part = nh.latent_moe(cfg, p, u)[0]
        _close(part, ref.moe_mixer(whole_cfg, p, u, "f32",
                                   held=(first, 4)))
        total = total + part - shared
    _close(total + shared, want)


def test_grouped_product_kernel_matches_the_gathered_reference():
    from paddle_tpu.kernels.pallas.grouped_matmul import grouped_matmul_sorted
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(200, 128)), F32)
    w = jnp.asarray(rng.normal(size=(5, 128, 256)), F32)
    sizes = jnp.asarray([10, 0, 100, 30, 7], jnp.int32)
    want = grouped_matmul_sorted(x, w, sizes, impl="reference")
    got = grouped_matmul_sorted(x, w, sizes, impl="kernel")
    _close(got[:147], want[:147], 1e-4)
    assert not np.asarray(want[147:]).any()
    for e, (lo, hi) in [(0, (0, 10)), (2, (10, 110)), (3, (110, 140)),
                        (4, (140, 147))]:           # group 1 is empty
        _close(want[lo:hi], x[lo:hi] @ w[e], 1e-4)


# -- the sorted buffer holds the pairs computed here, in as many passes as they take --------

PASS_CFG = nh.nemotron_h_tiny(experts_held=(4, 4))   # a quarter of 16 held


def _routing_with(n_here, t=256, k=4, seed=0):
    """idx [t, k] with exactly `n_here` pairs on the held experts 4..7
    (at most k a row, the first rows fullest) and routing weights."""
    rng = np.random.default_rng(seed)
    held = np.minimum(k, np.maximum(0, n_here - k * np.arange(t)))
    others = np.r_[0:4, 8:16]
    idx = np.stack([np.r_[rng.choice(np.arange(4, 8), h, replace=False),
                          rng.choice(others, k - h, replace=False)]
                    for h in held])
    idx = np.take_along_axis(idx, rng.permuted(
        np.tile(np.arange(k), (t, 1)), axis=1), 1)
    return (jnp.asarray(idx, jnp.int32),
            jnp.asarray(rng.uniform(0.1, 1.0, (t, k)), F32))


def _full_buffer(cfg, p, v, idx, weights, active):
    """The layer with a row for every pair routed anywhere, gathered
    back to the tokens (what `moe_experts` computed before PR 41)."""
    from paddle_tpu.kernels.pallas.grouped_matmul import grouped_matmul_sorted
    t, k = idx.shape
    order, sizes, rows = nh.sort_pairs(cfg, idx, active)
    h = grouped_matmul_sorted(jnp.take(v, order // k, axis=0), p["w1"], sizes)
    y = grouped_matmul_sorted(nh.relu2(h).astype(v.dtype), p["w2"], sizes)
    n_here = jnp.sum(sizes, dtype=jnp.int32)
    wy = jnp.where((jnp.arange(t * k) < n_here)[:, None],
                   y * jnp.take(weights.reshape(-1), order)[:, None], 0.0)
    back = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    r = jnp.sum(jnp.take(wy, back, axis=0).reshape(t, k, -1), axis=1)
    return r, nh.pair_counts(n_here, sizes, rows, k)


@pytest.mark.parametrize("n_here,active_rows,buffered,kernel", [
    (100, None, 384, False),    # one pass: 1.5 x a quarter of 1,024 pairs
    (500, None, 768, False),    # two
    (900, None, 1152, False),   # three: the pairs past 768
    (900, None, 1152, True),    # the same through the grouped kernel
    (384, None, 384, False),    # exactly a buffer
    (385, None, 768, False),    # one past it
    (1024, None, 1152, False),  # every pair held
    (0, None, 0, False),        # no pair held: no pass
    (900, 80, 384, False),      # 580 held pairs on rows not active
], ids=["one_pass", "two_passes", "three_passes", "three_passes_kernel",
        "at_a_buffer", "one_past_a_buffer", "every_pair_held",
        "no_pair_held", "inactive_rows"])
def test_buffer_passes_give_the_full_buffers_layer(n_here, active_rows,
                                                   buffered, kernel,
                                                   monkeypatch):
    """The passes give the full buffer's `r` and counts. Through the
    grouped kernel (interpreted), the rows a pass leaves unwritten are
    NaN: the select has to keep them out of the sum."""
    from paddle_tpu.kernels.pallas import grouped_matmul as gm
    if kernel:
        plain = gm.grouped_matmul_sorted
        monkeypatch.setattr(gm, "grouped_matmul_sorted", lambda *a, **kw:
                            plain(*a, **dict(kw, impl="kernel")))
        nh._experts_pass.clear_cache()      # traced with the reference
    cfg = PASS_CFG
    assert nh.buffer_rows(cfg, 256 * 4) == 384
    rng = np.random.default_rng(n_here)
    lat, f = cfg.moe_latent_size, cfg.moe_intermediate_size
    p = {"w1": jnp.asarray(rng.normal(0, 0.2, (4, lat, f)), F32),
         "w2": jnp.asarray(rng.normal(0, 0.2, (4, f, lat)), F32)}
    v = jnp.asarray(rng.normal(size=(256, lat)), F32)
    idx, weights = _routing_with(n_here)
    active = None if active_rows is None \
        else jnp.arange(256) < active_rows
    r, counts, rows = nh.moe_experts(cfg, p, v, idx, weights, active)
    if kernel:
        monkeypatch.undo()
        nh._experts_pass.clear_cache()
    want_r, want_counts = _full_buffer(cfg, p, v, idx, weights, active)
    _close(r, want_r, 1e-6)
    assert np.asarray(counts).tolist() == np.asarray(want_counts).tolist()
    assert int(counts[0]) == (n_here if active_rows is None else 320)
    assert int(rows) == buffered


# -- what does not compose with a recurrent state refuses -----------------------------------

@pytest.mark.parametrize("option,value", [
    ("weight_quant", "int8"), ("kv_quant", "int8"), ("prefix_cache", True),
    ("prefix_cache_blocks", 8), ("attn_shards", 2),
    ("shard_block_budget", 4), ("prefill_chunk", 16), ("kv_offload", True),
    ("hbm_budget_gib", 1.0)])
def test_refused_option_raises_and_names_itself(model, option, value):
    with pytest.raises(NotImplementedError, match=option):
        _decoder(model, **{option: value})


@pytest.mark.parametrize("call,what", [
    (lambda d: d.serve([(0, [1, 2, 3])], spec_decode=2), "spec_decode"),
    (lambda d: d.export_blocks(None, None, [1]), "block export"),
    (lambda d: d.import_blocks(None, None, [1], None), "block import"),
    (lambda d: d.page_out_blocks([1]), "page-out"),
    (lambda d: d.page_in_blocks(None), "page-in")])
def test_refused_call_raises_and_names_itself(model, call, what):
    with pytest.raises(NotImplementedError, match=what):
        call(_decoder(model))


def test_unknown_option_is_a_type_error(model):
    with pytest.raises(TypeError, match="no_such_option"):
        _decoder(model, no_such_option=1)


def test_pattern_and_share_are_checked():
    with pytest.raises(ValueError, match="block kind"):
        nh.NemotronHConfig(hybrid_override_pattern="MXE")
    with pytest.raises(ValueError, match="num_hidden_layers"):
        nh.NemotronHConfig(hybrid_override_pattern="M*E", num_hidden_layers=4)
    with pytest.raises(ValueError, match="experts_held"):
        nh.NemotronHConfig(n_routed_experts=16, experts_held=(12, 8))
    with pytest.raises(ValueError, match="the model wants"):
        cfg = nh.nemotron_h_tiny()
        arrays = {k: jnp.zeros(s, F32)
                  for k, (s, _) in cfg.param_shapes().items()}
        arrays["head"] = jnp.zeros((3, 3), F32)
        nh.NemotronHForCausalLM(cfg, arrays=arrays)
