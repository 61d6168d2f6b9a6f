"""The `deepseek_v32` reference, counts and readers as a yardstick: a sound
run at a size the CPU holds is correct (in float32 storage to the last
token); the faults a latent cell can have (the attention reading every
key, the most recent keys read in place of the indexer's choice, the
group limit left out, the shared expert left out, YaRN's scaling left
out, a served token altered) and the float8 control come out as not
correct; `flops_deepseek_v32` against counts by hand at the cell's own
configuration; the readers on hand-made spans and traces.

    PYTHONPATH=. python3 chipbench/tests/test_deepseek_v32.py

prints the readings of the faults and the control at this size."""
import importlib.util
import json
import os
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops_deepseek_v32 as fl
from chipbench import spans
from chipbench.adapters import deepseek_v32 as adapter
from chipbench.kinds import serve as serve_kind
from chipbench.reference import deepseek_v32 as ref
from chipbench.tests import tiny_deepseek_v32 as tiny

HERE = os.path.dirname(os.path.abspath(__file__))
# The sound run and the faults in float32 storage: the program's rounding
# is out of the comparison, so a sound run reads 0 whichever requests the
# window finished and a fault stands clear of it. In the cell's bfloat16
# at these 64-wide rows a top-16 choice of 40-176 keys and a top-4 of 16
# near-tied experts flip on rounding (a quarter of the tokens of seed 7
# differ from the reference's best, by up to 0.66 of a row's spread), so
# no limit at this size separates rounding from a fault
F32CFG = dict(tiny.CFG, torch_dtype="float32")
LIMITS = {"logit_gap": 0.01, "logit_gap_mean": 0.01}
mm = adapter.program


def test_sound_run_is_correct():
    out = tiny.run(tiny.SERVE, LIMITS, cfg=F32CFG)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["logit_gap"]["value"] < 1e-4
    assert out["compared"]["logit_gap_mean"]["value"] < 1e-4
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_cells_storage_type_runs_to_the_end():
    out = tiny.run(tiny.SERVE, LIMITS)
    assert out["failed"] == 0 and out["attempted"] > 0
    assert np.isfinite(out["compared"]["logit_gap"]["value"])
    # the mean of the gaps of which the widest is one
    assert 0 <= out["compared"]["logit_gap_mean"]["value"] \
        <= out["compared"]["logit_gap"]["value"]


def _with_config(monkeypatch, **changed):
    config = adapter.program_config

    def altered(cfg):
        out = config(cfg)
        for key, value in changed.items():
            setattr(out, key, value)
        return out
    monkeypatch.setattr(adapter, "program_config", altered)


def attend_every_key(monkeypatch):
    """The program's choice keeps every key up to the query."""
    _with_config(monkeypatch, index_topk=tiny.SERVE["max_len"])


def most_recent_keys(monkeypatch):
    """The program keeps the last index_topk keys up to each query in
    place of the indexer's choice."""
    def recent(scores, valid, k):
        behind = jnp.cumsum(valid[..., ::-1], axis=-1)[..., ::-1]
        return valid & (behind <= k)
    monkeypatch.setattr(mm, "topk_mask", recent)


def no_group_limit(monkeypatch):
    _with_config(monkeypatch, n_group=1, topk_group=1)


def shared_expert_left_out(monkeypatch):
    build = adapter.build_decoder

    def broken(cfg, traffic, weights):
        dec = build(cfg, traffic, weights)
        for layer in dec._params["layers"]:
            if "ws_d" in layer:
                layer["ws_d"] = layer["ws_d"] * 0
        return dec
    monkeypatch.setattr(adapter, "build_decoder", broken)


def yarn_left_out(monkeypatch):
    _with_config(monkeypatch, rope_scaling={})


def token_altered(monkeypatch):
    build = adapter.build_decoder

    def broken(cfg, traffic, weights):
        dec = build(cfg, traffic, weights)
        chunk = dec._paged_chunk_state_jit

        def altered(*args):
            toks, *rest = chunk(*args)
            toks = toks.at[:, 2].set((toks[:, 2] + 1) % cfg["vocab_size"])
            return (toks, *rest)
        dec._paged_chunk_state_jit = altered
        return dec
    monkeypatch.setattr(adapter, "build_decoder", broken)


FAULTS = [attend_every_key, most_recent_keys, no_group_limit,
          shared_expert_left_out, yarn_left_out, token_altered]


@pytest.mark.parametrize("plant", FAULTS, ids=lambda f: f.__name__)
def test_planted_fault_is_not_correct(plant, monkeypatch):
    plant(monkeypatch)
    out = tiny.run(tiny.SERVE, LIMITS, cfg=F32CFG)
    assert not out["correct"]
    assert out["compared"]["logit_gap"]["value"] > LIMITS["logit_gap"]


def test_float8_control_lies_below_the_reference_best():
    seed = 2**31 + 17
    weights = ref.make_weights(tiny.CFG, seed)
    ids = np.random.default_rng(seed).integers(
        0, tiny.CFG["vocab_size"], 160).astype(np.int32)
    rows = np.arange(40, 160)
    exact = np.asarray(ref.logits_at(tiny.CFG, weights, ids, rows, "f32"))
    low = np.asarray(ref.logits_at(tiny.CFG, weights, ids, rows, "fp8"))
    assert serve_kind.gap_below_best(exact, exact.argmax(-1)).max() == 0.0
    assert serve_kind.gap_below_best(exact, low.argmax(-1)).max() \
        > LIMITS["logit_gap"]


def test_mean_gap_kind_reads_the_widest_and_the_mean():
    """Kind `serve_long` on hand-made served tokens: its widest gap
    is kind `serve`'s, its mean the mean of the same gaps, and the
    control's mean that of the tokens the float8 reference puts first."""
    from chipbench.kinds import serve_long as gap_kind
    seed = 2**31 + 17
    rng = np.random.default_rng(seed)
    ctx = SimpleNamespace(cfg=tiny.CFG, seed=seed, reference=ref,
                          traffic=dict(tiny.SERVE, check_requests=2))
    prompts = {r: [int(t) for t in rng.integers(0, 256, n)]
               for r, n in ((0, 40), (1, 72))}
    budgets = {0: 16, 1: 24}
    served = {r: [int(t) for t in rng.integers(0, 256, b)]
              for r, b in budgets.items()}
    plain, gap = serve_kind.Session(ctx), gap_kind.Session(ctx)
    for sess in (plain, gap):
        sess.prompts, sess.budgets, sess.served = prompts, budgets, served
        sess.finished = [0, 1]
    widest = {n: v for n, v, _, _ in plain.check(control="fp8")}
    rows = {n: (v, k) for n, v, k, _ in gap.check(control="fp8")}
    assert rows["logit_gap"][0] == widest["logit_gap"]
    assert rows["logit_gap_fp8"][0] == widest["logit_gap_fp8"]
    assert rows["logit_gap_mean"][1] == rows["logit_gap_mean_fp8"][1] \
        == "logit_gap_mean"
    plain.weights = ref.make_weights(tiny.CFG, seed)
    own, control = [], []
    for rid in plain.sample():
        exact = plain.reference_rows(rid, "f32")
        low = plain.reference_rows(rid, "fp8")
        own.append(serve_kind.gap_below_best(exact, served[rid]))
        control.append(serve_kind.gap_below_best(exact, low.argmax(-1)))
    assert rows["logit_gap_mean"][0] == pytest.approx(
        float(np.concatenate(own).mean()))
    assert rows["logit_gap_mean_fp8"][0] == pytest.approx(
        float(np.concatenate(control).mean()))
    assert 0 < rows["logit_gap_mean"][0] < rows["logit_gap"][0]


def test_long_kind_serves_every_seed_one_arrangement():
    """Kind `serve_long`'s requests: the sizes, their order and the
    arrivals the same for every seed, each group of four a Latin row,
    every (prompt, budget) pair once a cycle; the token ids the seed's.
    Its run serves that list and leaves `generate` as it found it."""
    from chipbench import generate
    from chipbench.kinds import serve_long as long_kind
    traffic = dict(tiny.SERVE, cycles=2)
    p, b = traffic["prompt_lens"], traffic["budgets"]
    first, other = (long_kind.serve_requests(traffic, 256, s)
                    for s in (2**31 + 3, 2**31 + 4))
    shapes = [(len(ids), budget, due) for _, ids, budget, due in first]
    assert shapes == [(len(ids), budget, due)
                      for _, ids, budget, due in other]
    assert [rid for rid, *_ in first] == list(range(32))
    assert shapes[:16] == shapes[16:]
    assert sorted(s[:2] for s in shapes[:16]) == sorted(
        (n, k) for n in p for k in b)
    for g in range(4):
        assert [s[:2] for s in shapes[4 * g:4 * g + 4]] == [
            (p[(g + i) % 4], b[i]) for i in range(4)]
    assert first[0][1] != other[0][1]
    assert first == long_kind.serve_requests(traffic, 256, 2**31 + 3)
    original = generate.serve_requests
    sess = long_kind.Session(SimpleNamespace(
        cfg=F32CFG, seed=2**31 + 3, reference=ref, adapter=adapter,
        traffic=traffic, seconds=0.5, trace=False, mark=lambda name: None,
        window_open=lambda: 0.0, window_close=lambda: 0.5))
    sess.run()
    assert generate.serve_requests is original
    assert {rid: len(ids) for rid, ids in sess.prompts.items()} == {
        rid: len(ids) for rid, ids, _, _ in first}


# -- counts by hand ------------------------------------------------------------------

@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "..", "configs",
                           "deepseek_v32_ep16_l5.json")) as fh:
        return json.load(fh)


# DeepSeek-V3.2's published `config.json`
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280}


def test_configuration_keeps_every_published_width(cfg):
    assert cfg["source"] == ("https://huggingface.co/deepseek-ai/"
                             "DeepSeek-V3.2/blob/main/config.json")
    changed = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (5, 1, 16, 129280 // 8, 0)


def test_parameters_by_kind(cfg):
    p = fl.matmul_params(cfg)
    # q_a 7168 x 1536, q_b 1536 x 128 x 192, kv_a 7168 x 576, kv_b 512 x
    # 128 x 256, o 16384 x 7168
    assert p["attn"] == 11010048 + 37748736 + 4128768 + 16777216 \
        + 117440512 == 187105280
    # the indexer's wq_b 1536 x 64 x 128, wk 7168 x 128, weights 7168 x 64
    assert p["index"] == 12582912 + 917504 + 458752 == 13959168
    assert p["dense"] == 3 * 7168 * 18432 == 396361728
    assert p["expert"] == p["shared"] == 3 * 7168 * 2048 == 44040192
    assert p["router"] == 7168 * 256 and p["head"] == 7168 * 16160
    # one dense layer 597.4 M, four expert layers of 951.6 M, the tables
    # 231.7 M: 4.64 B parameters, 9.27 GB in bfloat16
    assert fl.parameters(cfg) == 4635518208
    leaves = ref.leaf_shapes(cfg)
    assert sum(int(np.prod(s)) for s in leaves.values()) == 4635518208
    shapes = adapter.program_config(cfg).param_shapes()
    assert {k: s for k, (s, _) in shapes.items()} == leaves


def test_one_token_of_each_phase(cfg):
    """A decoded token at 12,000 cached positions with 1.5 pairs here a
    layer: the matmuls every token meets, the indexer at 12,000 keys,
    2,048 chosen rows absorbed, six pairs' experts, the head. A prompt
    of 8,192: the chosen pairs are the top-2,048 triangle and 2,048 a
    query after it."""
    p = fl.matmul_params(cfg)
    every = 5 * (187105280 + 13959168) + 396361728 \
        + 4 * (1835008 + 44040192)
    got = fl.forward_flops(cfg, 1, 12000, 0, 2048, 1, 6)
    assert got == 2 * every + 2 * 44040192 * 6 \
        + 5 * (16384 * 12000 + 278528 * 2048) + 2 * p["head"]
    # 3.17 G of matmuls, 0.98 G indexer, 2.85 G attention, 0.53 G experts
    assert 7.7e9 < got < 7.8e9
    assert fl.chosen_pairs(cfg, 1, 8192) == 2048 * 2049 // 2 + 6144 * 2048
    assert fl.prefill_pair_flops(cfg) == 81920
    assert fl.decode_pair_flops(cfg) == 278528
    assert fl.latent_row_bytes(cfg) == 1152 and fl.index_key_bytes(cfg) == 256


def test_kernel_counts(cfg):
    work, moved = fl.decode_attention(cfg, rows=48, chosen=48 * 2048)
    assert work == 5 * 278528 * 48 * 2048
    assert moved == 5 * (48 * 2048 * 1152 + 48 * 128 * 1088 * 2)
    work, moved = fl.prefill_attention(cfg, 1024, 500000)
    assert work == 5 * 81920 * 500000
    assert moved == 5 * 1024 * (128 * 320 * 2 + 1152)
    work, moved = fl.indexer(cfg, queries=48, index_pairs=48 * 12000,
                             keys_read=48 * 12000)
    assert work == 5 * 16384 * 48 * 12000
    assert moved == 5 * (48 * 64 * 260 + 48 * 12000 * (256 + 4))


# -- the readers ------------------------------------------------------------------------

def _reader(name):
    path = os.path.join(HERE, "..", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("serve.mfu.deepseek_v32", "lightning_indexer_roofline",
       "sparse_mla_decode_roofline", "sparse_mla_prefill_roofline",
       "dsa.latent_rows_read_per_decode_row")


def _commit(rows, pairs, touched, keys, read):
    return {"name": "serve:commit", "meta": {
        "tokens": rows, "moe_pairs_here": pairs, "moe_pairs_all": 16 * pairs,
        "moe_experts_touched": touched, "moe_max_load": 3,
        "attn_rows": rows, "index_keys": keys, "latent_rows_read": read}}


def test_counter_readers_on_hand_made_spans(cfg, monkeypatch):
    found = [_commit(1000, 1500, 900, 12_000_000, 2_048_000),
             _commit(500, 700, 450, 6_100_000, 1_024_000),
             {"name": "serve:admit", "meta": {
                 "tokens": 1, "prompt_tokens": 8192, "moe_pairs_here": 4000,
                 "moe_pairs_all": 65536, "moe_experts_touched": 64,
                 "moe_max_load": 9, "kv_blocks": 130}}]
    monkeypatch.setattr(spans, "in_window", lambda view: found)
    # the harness counted 1,600 decode rows; the commits cover 1,500
    observed = dict(decode_rows=1600, prefills=1, prefill_tokens=8192,
                    prefill_pairs=8192 * 8193 // 2,
                    decode_context=19_300_000, window_s=2.0, slots=48)
    view = SimpleNamespace(cfg=cfg, observed=observed, peak=PEAK)
    assert _reader("dsa.latent_rows_read_per_decode_row")(view) \
        == pytest.approx(3_072_000 * (1600 / 1500) / 1600)
    work = fl.forward_flops(
        cfg, 8192 + 1600, 8192 * 8193 // 2 + 19_300_000,
        fl.chosen_pairs(cfg, 1, 8192), 3_072_000 * 1600 / 1500, 1 + 1600,
        2200 * 1600 / 1500 + 4000)
    assert _reader("serve.mfu.deepseek_v32")(view) == pytest.approx(
        100 * work / 2.0 / 197e12)
    # prompts are counted by their admissions, never assumed
    found[2:] = [{"name": "serve:admit", "meta": {"tokens": 1}}]
    assert _reader("serve.mfu.deepseek_v32")(view) is None
    # no counters in the window (another engine, the parent): nothing
    monkeypatch.setattr(spans, "in_window", lambda view: found[2:])
    other = SimpleNamespace(cfg={"hidden_size": 8}, observed=observed,
                            peak=PEAK, summary=None)
    for name in ("serve.mfu.deepseek_v32",
                 "dsa.latent_rows_read_per_decode_row"):
        assert _reader(name)(view) is None
        assert _reader(name)(other) is None


def test_roofline_readers_find_their_scopes(cfg, monkeypatch):
    from chipbench import trace
    ops = {
        "%prefill.index.5 = f32[1024,16384]{1,0} custom-call(%q)": 0.5,
        "%decode.index.7 = f32[48,1,16384]{2,1,0} custom-call(%q)": 0.25,
        "%prefill.attend.2 = bf16[128,1024,128]{2,1,0} custom-call(%q)": 3.0,
        "%decode.attend.sparse.3 = bf16[48,128,512]{2,1,0} custom-call(%q)":
            0.75,
        "%fusion.9 = bf16[48,7168]{1,0} fusion(%p), kind=kLoop": 9.0}
    observed = dict(decode_rows=20000, decode_context=240_000_000,
                    prefills=4, prefill_tokens=40960,
                    prefill_pairs=sum(n * (n + 1) // 2 for n in
                                      (8192, 9216, 10240, 13312)),
                    window_s=12.0, slots=48)
    view = SimpleNamespace(
        cfg=cfg, observed=observed, peak=PEAK,
        summary=trace.Summary(window_s=12.0, busy_s=11.9, ops=ops,
                              idle_gaps={}))
    monkeypatch.setattr(spans, "in_window", lambda view: [
        _commit(20000, 30000, 9000, 240_000_000, 20000 * 2048),
        {"name": "serve:admit", "meta": {
            "prompt_tokens": 40960, "moe_pairs_here": 20000,
            "moe_experts_touched": 200}}])
    from chipbench.peaks import least_seconds
    least = least_seconds(*fl.indexer(cfg, 40960, observed["prefill_pairs"],
                                      40960), PEAK) \
        + least_seconds(*fl.indexer(cfg, 20000, 240_000_000, 240_000_000),
                        PEAK)
    assert _reader("lightning_indexer_roofline")(view) \
        == pytest.approx(100 * least / 0.75)
    work, moved = fl.decode_attention(cfg, 20000, 20000 * 2048)
    assert _reader("sparse_mla_decode_roofline")(view) == pytest.approx(
        100 * least_seconds(work, moved, PEAK) / 0.75)
    work, moved = fl.prefill_attention(cfg, 40960,
                                       fl.chosen_pairs(cfg, 4, 40960))
    assert _reader("sparse_mla_prefill_roofline")(view) == pytest.approx(
        100 * least_seconds(work, moved, PEAK) / 3.0)
    for name in NEW[1:4]:
        assert 0 < _reader(name)(view) < 100
    # no such scope in the trace (the parent, another engine): nothing
    view.summary = trace.Summary(window_s=12.0, busy_s=11.0, ops={
        "%fusion.9 = bf16[48,7168]{1,0} fusion(%p), kind=kLoop": 9.0},
        idle_gaps={})
    for name in NEW[1:4]:
        assert _reader(name)(view) is None


def _readings():
    """The logit_gap each fault and the control read at this size."""
    out = {}
    for plant in FAULTS:
        patch = pytest.MonkeyPatch()
        try:
            plant(patch)
            out[plant.__name__] = tiny.run(tiny.SERVE, LIMITS, cfg=F32CFG)[
                "compared"]["logit_gap"]["value"]
        finally:
            patch.undo()
    out["sound"] = tiny.run(tiny.SERVE, LIMITS, cfg=F32CFG)[
        "compared"]["logit_gap"]["value"]
    return out


if __name__ == "__main__":
    print(json.dumps(_readings(), indent=1))
