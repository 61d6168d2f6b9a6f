"""The `mimo_v2` reference, counts and readers as a yardstick: a sound
run at a size the CPU holds is correct (in float32 storage to the last
token, in the cell's bfloat16 within what rounding does at that size);
the faults a cell of window and
full layers can have (a window layer that attends all it is handed, a
window a block short, the sink left out, the rotary term on every dim,
the window layers' base in the full layers, v unscaled, one expert's
output dropped, a served token altered) and the float8 control come out
as not correct; `flops_mimo_v2` against counts by hand at the cell's own
configuration; the readers on hand-made spans."""
import importlib.util
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import flops_mimo_v2 as fl
from chipbench import spans
from chipbench import spans_mimo_v2 as counters
from chipbench.adapters import mimo_v2 as adapter
from chipbench.kinds import serve as serve_kind
from chipbench.reference import mimo_v2 as ref
from chipbench.tests import tiny_mimo_v2 as tiny

HERE = os.path.dirname(os.path.abspath(__file__))
# The sound run and the faults in float32 storage: the program's rounding
# is out of the comparison, so a sound run reads the same few 1e-6
# whichever requests the window finished and a fault stands clear of it.
# In the cell's bfloat16 at these 64-wide rows a top-4 choice among 16
# near-tied scores flips on rounding: seed 7's requests read 0.009 to
# 0.142 one by one, so a limit would hang on the sample the clock drew
F32CFG = dict(tiny.CFG, torch_dtype="float32")
LIMITS = {"logit_gap": 0.01}
# what a run in the cell's storage type has to stay under here: the
# faults read from 0.19 up there, the wrong token 1.9
BF16_LIMITS = {"logit_gap": 0.25}
mm = adapter.program


def test_sound_run_is_correct():
    out = tiny.run(tiny.SERVE, LIMITS, cfg=F32CFG)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_sound_run_in_the_cells_storage_type():
    out = tiny.run(tiny.SERVE, BF16_LIMITS)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0


def _window_attends_all_it_is_handed(monkeypatch):
    view, chunk = mm.WindowPagedDecoder._ring_view, mm.attend_chunk

    def wide_view(self, slots, pos):
        tables, lens, lows = view(self, slots, pos)
        return tables, lens, lows * 0

    def wide_chunk(cfg, kind, p, q, k, v, q_start, kv_first=0):
        from paddle_tpu.kernels.pallas.flash_prefill import (
            flash_prefill_attention)
        return flash_prefill_attention(
            q, k, v, q_start, kv_first, window=None,
            sinks=p.get("sink")).reshape(q.shape[0], -1)
    monkeypatch.setattr(mm.WindowPagedDecoder, "_ring_view", wide_view)
    monkeypatch.setattr(mm, "attend_chunk", wide_chunk)


def _window_a_block_short(monkeypatch):
    view = mm.WindowPagedDecoder._ring_view

    def short(self, slots, pos):
        tables, lens, lows = view(self, slots, pos)
        cut = (lens >= self.block_size) & (lows < self.block_size)
        return tables, lens, lows + cut * (self.block_size - lows)
    monkeypatch.setattr(mm.WindowPagedDecoder, "_ring_view", short)


def _sink_left_out(monkeypatch):
    monkeypatch.setattr(mm.MimoV2Config, "has_sink",
                        lambda self, kind: False)


def _rotary_on_every_dim(monkeypatch):
    monkeypatch.setattr(mm.MimoV2Config, "rotary_dim",
                        property(lambda self: self.head_dim))


def _window_base_in_full_layers(monkeypatch):
    monkeypatch.setattr(mm.MimoV2Config, "theta",
                        lambda self, kind: self.swa_rope_theta)


def _v_unscaled(monkeypatch):
    config = adapter.program_config

    def unscaled(cfg):
        out = config(cfg)
        out.attention_value_scale = 1.0
        return out
    monkeypatch.setattr(adapter, "program_config", unscaled)


def _one_experts_output_dropped(monkeypatch):
    build = adapter.build_decoder

    def broken(cfg, traffic, weights):
        dec = build(cfg, traffic, weights)
        for layer in dec._params["layers"]:
            if "w2" in layer:
                layer["w2"] = layer["w2"].at[1].set(0)
        return dec
    monkeypatch.setattr(adapter, "build_decoder", broken)


def _token_altered(monkeypatch):
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


FAULTS = [_window_attends_all_it_is_handed, _window_a_block_short,
          _sink_left_out, _rotary_on_every_dim, _window_base_in_full_layers,
          _v_unscaled, _one_experts_output_dropped, _token_altered]


@pytest.mark.parametrize("plant", FAULTS, ids=lambda f: f.__name__[1:])
def test_planted_fault_is_not_correct(plant, monkeypatch):
    plant(monkeypatch)
    out = tiny.run(tiny.SERVE, LIMITS, cfg=F32CFG)
    assert not out["correct"]
    assert out["compared"]["logit_gap"]["value"] > LIMITS["logit_gap"]


def test_float8_control_lies_below_the_reference_best():
    seed = 2**31 + 17
    weights = ref.make_weights(tiny.CFG, seed)
    ids = np.random.default_rng(seed).integers(
        0, tiny.CFG["vocab_size"], 128).astype(np.int32)
    rows = np.arange(16, 112)
    exact = np.asarray(ref.logits_at(tiny.CFG, weights, ids, rows, "f32"))
    low = np.asarray(ref.logits_at(tiny.CFG, weights, ids, rows, "fp8"))
    assert serve_kind.gap_below_best(exact, exact.argmax(-1)).max() == 0.0
    assert serve_kind.gap_below_best(exact, low.argmax(-1)).max() \
        > LIMITS["logit_gap"]


# -- counts by hand ------------------------------------------------------------------

@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "..", "configs",
                           "mimo_v2_flash_ep16_l7.json")) as fh:
        return json.load(fh)


# MiMo-V2-Flash's published `config.json`, the keys that are numbers,
# flags or names (the two per-layer lists are given by their rule below)
PUBLISHED = {
    "attention_value_scale": 0.707, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384,
    "max_position_embeddings": 262144, "model_type": "mimo_v2_flash",
    "num_attention_heads": 64, "head_dim": 192, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "layernorm_epsilon": 1e-05,
    "rope_theta": 5000000, "tie_word_embeddings": False,
    "vocab_size": 152576, "partial_rotary_factor": 0.334,
    "sliding_window": 128, "swa_rope_theta": 10000, "attention_bias": False,
    "v_head_dim": 128, "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "sliding_window_size": 128,
    "attention_chunk_size": 128, "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "n_shared_experts": None,
    "num_experts_per_tok": 8, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "routed_scaling_factor": None,
    "swa_num_attention_heads": 64, "swa_num_key_value_heads": 8,
    "swa_head_dim": 192, "swa_v_head_dim": 128}
# full attention in layer 0 and in every sixth from layer 5; every layer
# but the first has experts
PUBLISHED["hybrid_layer_pattern"] = [
    0 if l == 0 or l % 6 == 5 else 1 for l in range(48)]
PUBLISHED["moe_layer_freq"] = [0] + [1] * 47


def test_configuration_keeps_every_published_width(cfg):
    assert cfg["source"] == ("https://huggingface.co/XiaomiMiMo/"
                             "MiMo-V2-Flash/blob/main/config.json")
    changed = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"}
    assert cfg["published"] == {k: PUBLISHED[k] for k in cfg["reduced"]}
    assert cfg["hybrid_layer_pattern"] == PUBLISHED[
        "hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert cfg["moe_layer_freq"] == PUBLISHED["moe_layer_freq"][:7]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (7, 16, 152576 // 8)


def test_parameters_by_kind(cfg):
    p = fl.matmul_params(cfg)
    # q 4096 x 12288, o 8192 x 4096, k + v 4096 x 4 x 320 | 8 x 320
    assert p["full"] == 50331648 + 33554432 + 5242880 == 89128960
    assert p["window"] == 50331648 + 33554432 + 10485760 == 94371840
    assert p["router"] == 4096 * 256 and p["dense"] == 3 * 4096 * 16384
    assert p["expert"] == 3 * 4096 * 2048 == 25165824
    assert p["head"] == 4096 * 19072
    z = fl.sizes(cfg)
    assert (z["n_full"], z["n_window"], z["n_sparse"], z["n_dense"],
            z["held"], z["router_width"]) == (2, 5, 6, 1, 16, 256)
    # layer 0 290.5 M; a window expert layer 498.1 M; the full one 492.9 M;
    # each table 78.1 M: 3.43 B parameters, 6.86 GB in bfloat16
    assert fl.parameters(cfg) == 3429955392
    leaves = ref.leaf_shapes(cfg)
    assert sum(int(np.prod(s)) for s in leaves.values()) == 3429955392


def test_one_decoded_token(cfg):
    """At 5,000 cached positions, 0.5 pairs an expert layer: the matmuls
    every token meets, 3 pairs' experts, 5,000 keys in the 2 full layers
    and 128 in the 5 window layers at 2 x 64 x (192 + 128) a pair, the
    head."""
    every = 2 * 89128960 + 5 * 94371840 + 6 * 1048576 + 201326592
    got = fl.forward_flops(cfg, 1, 5000, 128, 1, 6 * 0.5)
    assert got == 2 * every + 2 * 25165824 * 3 \
        + 40960 * (2 * 5000 + 5 * 128) + 2 * 78118912
    assert 2.44e9 < got < 2.46e9


def test_attention_and_expert_bytes(cfg):
    assert fl.kv_row_bytes(cfg, "full") == 4 * 320 * 2 == 2560
    assert fl.kv_row_bytes(cfg, "window") == 8 * 320 * 2 == 5120
    # a prompt of 1,024: the triangle, and the band 128 wide
    assert fl.window_pairs(cfg, 1, 1024, 0) == 128 * 129 // 2 + 896 * 128
    assert fl.window_pairs(cfg, 0, 0, 7) == 7 * 128
    work, moved = fl.decode_attention(cfg, rows=10, full_keys=50000,
                                      ring_keys=1280)
    assert work == 40960 * (2 * 50000 + 5 * 1280)
    assert moved == 2 * 50000 * 2560 + 5 * 1280 * 5120 \
        + 7 * 10 * 64 * 320 * 2
    work, moved = fl.prefill_attention(cfg, 1024, 1024 * 1025 // 2,
                                       fl.window_pairs(cfg, 1, 1024, 0))
    assert work == 40960 * (2 * 524800 + 5 * 122944)
    assert moved == 1024 * (2 * (40960 + 2560) + 5 * (40960 + 5120))
    work, moved = fl.expert_calls(cfg, pairs=384, touched=94)
    assert work == 6 * 4096 * 2048 * 384
    assert moved == 94 * 50331648 + 384 * (16384 + 16384 + 4096 + 16384)


# -- the readers ------------------------------------------------------------------------

def _reader(name):
    path = os.path.join(HERE, "..", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_counter_readers_on_hand_made_spans(cfg, monkeypatch):
    def commit(rows, pairs, touched, peak, full, ring):
        return {"name": "serve:commit", "meta": {
            "tokens": rows, "moe_pairs_here": pairs,
            "moe_pairs_all": 16 * pairs, "moe_experts_touched": touched,
            "moe_max_load": peak, "attn_rows": rows,
            "attn_tokens_full": full, "attn_tokens_window": ring}}

    def admit(prompt, pairs, touched):
        return {"name": "serve:admit", "meta": {
            "tokens": 1, "prompt_tokens": prompt, "moe_pairs_here": pairs,
            "moe_pairs_all": 16 * pairs, "moe_experts_touched": touched,
            "moe_max_load": 99, "kv_blocks_full": 40,
            "kv_bytes_window": 5898240}}
    found = [commit(1000, 3000, 700, 9, 5_000_000, 128_000),
             commit(500, 1500, 380, 7, 2_600_000, 64_000),
             {"name": "serve:admit", "meta": {"tokens": 1}}]
    monkeypatch.setattr(spans, "in_window", lambda view: found)
    # the harness counted 1600 decode rows; the commits cover 1500
    observed = dict(decode_rows=1600, prefills=0, prefill_tokens=0,
                    prefill_pairs=0, decode_context=8_100_000,
                    window_s=2.0, slots=128)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    view = SimpleNamespace(cfg=cfg, observed=observed, peak=peak)
    got = counters.window_counts(view)
    assert got["pairs_here"] == pytest.approx(4500 * 1600 / 1500)
    assert got["touched"] == pytest.approx(1080 * 1600 / 1500)
    assert got["full_keys"] == pytest.approx(7_600_000 * 1600 / 1500)
    # prompts are counted by their admissions, never assumed
    with_prompts = SimpleNamespace(
        cfg=cfg, peak=peak,
        observed=dict(observed, prefills=2, prefill_tokens=3072,
                      prefill_pairs=1024 * 1025 // 2 + 2048 * 2049 // 2))
    assert counters.window_counts(with_prompts) is None
    found[2:] = [admit(1024, 3100, 96), admit(2048, 6000, 96)]
    both = counters.window_counts(with_prompts)
    assert both["pairs_here"] == pytest.approx(got["pairs_here"] + 9100)
    assert both["touched"] == pytest.approx(got["touched"] + 192)
    work = fl.forward_flops(
        cfg, 3072 + 1600, with_prompts.observed["prefill_pairs"] + 8_100_000,
        fl.window_pairs(cfg, 2, 3072, 1600), 2 + 1600, both["pairs_here"])
    assert _reader("serve.mfu.mimo_v2")(with_prompts) == pytest.approx(
        100 * work / 2.0 / 197e12)
    found[2:] = [{"name": "serve:admit", "meta": {"tokens": 1}}]
    assert _reader("moe.expert_load_max_over_mean.mimo_v2")(view) \
        == pytest.approx((9 * 700 / 3000 + 7 * 380 / 1500) / 2)
    ring = 5 * 192_000 * 5120
    full = 2 * 7_600_000 * 2560
    assert _reader("kv.window_share_of_cache_reads")(view) \
        == pytest.approx(100 * ring / (ring + full))
    # no counters in the window (another engine, the parent): nothing
    monkeypatch.setattr(spans, "in_window", lambda view: found[2:])
    for name in ("serve.mfu.mimo_v2", "kv.window_share_of_cache_reads",
                 "moe.expert_load_max_over_mean.mimo_v2"):
        assert _reader(name)(view) is None
    # another family's configuration: nothing either
    other = SimpleNamespace(cfg={"hidden_size": 8}, observed=observed,
                            peak=peak, summary=None)
    for name in ("serve.mfu.mimo_v2", "kv.window_share_of_cache_reads",
                 "moe.expert_load_max_over_mean.mimo_v2"):
        assert _reader(name)(other) is None


def test_roofline_readers_find_their_scopes(cfg, monkeypatch):
    from chipbench import trace
    ops = {
        "%decode.attend.full.3 = bf16[128,64,128]{2,1,0} custom-call(%q)":
            2.0,
        "%decode.attend.window.7 = bf16[128,64,128]{2,1,0} custom-call(%q)":
            0.5,
        "%prefill.attend.2 = bf16[64,1024,128]{2,1,0} custom-call(%q)": 1.5,
        "%moe.experts.12 = f32[1024,2048]{1,0} custom-call(%a)": 4.0,
        "%fusion.9 = bf16[128,4096]{1,0} fusion(%p), kind=kLoop": 9.0}
    observed = dict(decode_rows=60000, decode_context=330_000_000,
                    prefills=8, prefill_tokens=36864,
                    prefill_pairs=sum(n * 1024 * (n * 1024 + 1) // 2
                                      for n in range(1, 9)),
                    window_s=12.0, slots=128)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    view = SimpleNamespace(
        cfg=cfg, observed=observed, peak=peak,
        summary=trace.Summary(window_s=12.0, busy_s=11.0, ops=ops,
                              idle_gaps={}))
    work, moved = fl.decode_attention(cfg, 60000, 330_000_000, 60000 * 128)
    assert _reader("paged_attention_roofline.mimo_v2")(view) \
        == pytest.approx(100 * max(work / 197e12, moved / 819e9) / 2.5)
    work, moved = fl.prefill_attention(
        cfg, 36864, observed["prefill_pairs"],
        fl.window_pairs(cfg, 8, 36864, 0))
    assert _reader("prefill_attention_roofline.mimo_v2")(view) \
        == pytest.approx(100 * max(work / 197e12, moved / 819e9) / 1.5)
    found = [{"name": "serve:commit", "meta": {
        "moe_pairs_here": 30000, "moe_experts_touched": 9000,
        "moe_max_load": 9, "attn_rows": 60000, "attn_tokens_full": 1,
        "attn_tokens_window": 1}},
        {"name": "serve:admit", "meta": {
            "prompt_tokens": 36864, "moe_pairs_here": 110000,
            "moe_experts_touched": 3400}}]
    monkeypatch.setattr(spans, "in_window", lambda view: found)
    work, moved = fl.expert_calls(cfg, 140000, 12400)
    assert _reader("moe_experts_roofline.mimo_v2")(view) \
        == pytest.approx(100 * max(work / 197e12, moved / 819e9) / 4.0)
    # no such scope in the trace (the parent, another engine): nothing
    view.summary = trace.Summary(window_s=12.0, busy_s=11.0, ops={
        "%fusion.9 = bf16[128,4096]{1,0} fusion(%p), kind=kLoop": 9.0},
        idle_gaps={})
    for name in ("paged_attention_roofline.mimo_v2",
                 "prefill_attention_roofline.mimo_v2",
                 "moe_experts_roofline.mimo_v2"):
        assert _reader(name)(view) is None
