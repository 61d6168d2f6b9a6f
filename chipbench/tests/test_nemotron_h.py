"""The `nemotron_h` reference and counts as a yardstick: a sound run at a
size the CPU holds is correct; the faults a hybrid cell can have (a stale
SSM state, one expert's output dropped, a served token altered) and the
float8 control come out as not correct; `flops_nemotron_h` against counts
by hand at the cell's own configuration."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops_nemotron_h as fl
from chipbench.adapters import nemotron_h as adapter
from chipbench.kinds import serve as serve_kind
from chipbench.reference import nemotron_h as ref
from chipbench.tests import tiny_nemotron_h as tiny

HERE = os.path.dirname(os.path.abspath(__file__))
# the limit of this size: ten times what a sound run reads here (0.002 on
# seed 7); the stale state reads 0.3-0.8, all experts dropped about 1
LIMITS = {"logit_gap": 0.02}


def test_sound_run_is_correct():
    out = tiny.run(tiny.SERVE, LIMITS)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_stale_ssm_state(monkeypatch):
    """Decode reads the state the prefill left and never moves it on."""
    step = adapter.program.ssm_step

    def stale(state, *args):
        y, _ = step(state, *args)
        return y, state
    monkeypatch.setattr(adapter.program, "ssm_step", stale)
    out = tiny.run(tiny.SERVE, LIMITS)
    assert not out["correct"]
    assert out["compared"]["logit_gap"]["value"] > LIMITS["logit_gap"]


def test_one_experts_output_dropped(monkeypatch):
    build = adapter.build_decoder

    def broken(cfg, traffic, weights):
        dec = build(cfg, traffic, weights)
        for layer in dec._params["layers"]:
            if "w2" in layer:
                layer["w2"] = layer["w2"].at[1].set(0)
        return dec
    monkeypatch.setattr(adapter, "build_decoder", broken)
    out = tiny.run(tiny.SERVE, LIMITS)
    assert not out["correct"]


def test_token_altered_where_it_is_produced(monkeypatch):
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
    out = tiny.run(tiny.SERVE, LIMITS)
    assert not out["correct"]
    assert out["compared"]["logit_gap"]["value"] > 0.1


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


def test_reference_is_the_sequential_recurrence():
    """Two halves of a sequence, the second started from the first's
    last state, give the whole: the reference's state really is all a
    position hands on."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(12, 8, 8)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(12, 2, 16)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(12, 2, 16)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(12, 8)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, size=8), jnp.float32)
    d = jnp.ones(8, jnp.float32)
    y, last = ref.ssm_sequential(x, b, c, dt, a, d)
    y1, mid = ref.ssm_sequential(x[:5], b[:5], c[:5], dt[:5], a, d)
    y2, end = ref.ssm_sequential(x[5:], b[5:], c[5:], dt[5:], a, d, mid)
    np.testing.assert_allclose(np.concatenate([y1, y2]), y, rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(end, last, rtol=1e-6, atol=1e-6)


# -- counts by hand ------------------------------------------------------------------

@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "..", "configs",
                           "nemotron3_super_120b_ep4_l11.json")) as fh:
        return json.load(fh)


def test_parameters_by_kind(cfg):
    p = fl.matmul_params(cfg)
    # in_proj 4096 x (8192 + 10240 + 128), out_proj 8192 x 4096
    assert p["M"] == 4096 * 18560 + 8192 * 4096 == 109576192
    # q and o 4096 x 4096, k and v 4096 x 256
    assert p["*"] == 2 * 16777216 + 2 * 1048576 == 35651584
    # router 4096 x 512 (published width), latent down and up, shared
    assert p["E"] == 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 \
        == 54525952
    assert p["expert"] == 2 * 1024 * 2688 == 5505024
    assert p["head"] == 4096 * 32768
    z = fl.sizes(cfg)
    assert (z["n_m"], z["n_e"], z["n_a"], z["held"], z["router_width"]) \
        == (5, 5, 1, 128, 512)
    # all of it: 4.65 B parameters with the embedding table
    total = 5 * (p["M"] + 128 * p["expert"] + p["E"]) + p["*"] \
        + 2 * p["head"]
    assert 4.60e9 < total < 4.66e9


def test_one_decoded_token(cfg):
    """At 1000 cached positions, 5.5 pairs an expert block: 2 x 856 M
    body and 134 M head, the recurrence, the experts, the one attention
    block."""
    body = 5 * 109576192 + 35651584 + 5 * 54525952
    ssm = 6 * 8192 * 128 + 2 * 4 * 10240
    assert fl.ssm_flops_per_token(cfg) == ssm == 6373376
    got = fl.forward_flops(cfg, 1, 1000, 1, 5 * 5.5)
    assert got == 2 * body + 5 * ssm + 2 * 5505024 * 27.5 \
        + 4 * 32 * 128 * 1000 + 2 * 4096 * 32768
    assert 2.30e9 < got < 2.34e9


def test_state_and_expert_bytes(cfg):
    # 128 x 64 x 128 float32 read and written, and the conv's 3 rows
    assert fl.state_bytes_per_row(cfg) == 2 * 4194304 + 2 * 3 * 10240 * 2
    work, moved = fl.expert_calls(cfg, pairs=704, touched=128)
    assert work == 4 * 1024 * 2688 * 704
    assert moved == 128 * 11010048 + 704 * (2048 + 10752 + 5376 + 4096)


# -- the readers of the chunk counters --------------------------------------------------

def test_counter_readers_on_hand_made_commits(cfg, monkeypatch):
    import importlib.util
    from types import SimpleNamespace

    from chipbench import spans

    def reader(name):
        path = os.path.join(HERE, "..", "metrics", name + ".py")
        spec = importlib.util.spec_from_file_location("m", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read

    def commit(pairs, touched, peak, rows):
        return {"name": "serve:commit", "meta": {
            "tokens": rows // 5, "moe_pairs_here": pairs,
            "moe_pairs_all": 4 * pairs, "moe_experts_touched": touched,
            "moe_max_load": peak, "ssm_rows": rows}}
    def admit(prompt, pairs, touched):
        return {"name": "serve:admit", "meta": {
            "tokens": 1, "prompt_tokens": prompt, "moe_pairs_here": pairs,
            "moe_pairs_all": 4 * pairs, "moe_experts_touched": touched,
            "moe_max_load": 99}}
    found = [commit(5600, 1000, 14, 5000), commit(2800, 560, 10, 2500),
             {"name": "serve:admit", "meta": {"tokens": 1}}]
    monkeypatch.setattr(spans, "in_window", lambda view: found)
    # the harness counted 1600 decode rows; the commits cover 1500
    observed = dict(decode_rows=1600, prefills=0, prefill_tokens=0,
                    prefill_pairs=0, decode_context=1600 * 1000,
                    window_s=2.0, slots=128)
    view = SimpleNamespace(cfg=cfg, observed=observed, peak={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    from chipbench import spans_nemotron_h as counters
    got = counters.window_counts(view, 5)
    assert got["pairs_here"] == pytest.approx(8400 * 1600 / 1500)
    assert got["touched"] == pytest.approx(1560 * 1600 / 1500)
    # prompts are counted by their admissions, never assumed: 300 prompt
    # tokens seen by the harness, admissions that cover 200 of them
    with_prompts = SimpleNamespace(
        cfg=cfg, peak=view.peak,
        observed=dict(observed, prefills=3, prefill_tokens=300))
    assert counters.window_counts(with_prompts, 5) is None
    found[2:] = [admit(150, 900, 400), admit(50, 300, 200)]
    both = counters.window_counts(with_prompts, 5)
    assert both["pairs_here"] == pytest.approx(got["pairs_here"] + 1800)
    assert both["touched"] == pytest.approx(got["touched"] + 900)
    assert both["chunks"] == got["chunks"]
    found[2:] = [{"name": "serve:admit", "meta": {"tokens": 1}}]
    # largest load over the mean load of a touched expert, mean of chunks
    assert reader("moe.expert_load_max_over_mean")(view) == pytest.approx(
        (14 * 1000 / 5600 + 10 * 560 / 2800) / 2)
    work = fl.forward_flops(cfg, 1600, 1600 * 1000, 1600, got["pairs_here"])
    assert reader("serve.mfu.nemotron_h")(view) == pytest.approx(
        100 * work / 2.0 / 197e12)
    # no counters in the window (another engine): nothing, not 0
    monkeypatch.setattr(spans, "in_window", lambda view: found[2:])
    assert reader("serve.mfu.nemotron_h")(view) is None
    assert reader("moe.expert_load_max_over_mean")(view) is None
    # a dense configuration's view: nothing either
    dense = SimpleNamespace(cfg={"hidden_size": 8}, observed=observed,
                            peak=view.peak, summary=None)
    assert reader("serve.mfu.nemotron_h")(dense) is None


def test_state_update_roofline_finds_the_pools_by_their_types(cfg):
    import importlib.util
    from types import SimpleNamespace

    from chipbench import trace
    path = os.path.join(HERE, "..", "metrics", "ssm_state_update_roofline.py")
    spec = importlib.util.spec_from_file_location("m", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    ssm, conv = fl.state_pool_shapes(cfg, 128)
    assert (ssm, conv) == ("f32[5,128,128,64,128]", "bf16[5,128,3,10240]")
    assert fl.state_bytes_per_admission(cfg) == 4194304 + 3 * 10240 * 2
    lay = "{4,3,2,1,0:T(8,128)}"
    ops = {
        # the write of the stepped state, in place: result and operand
        f"%select_dynamic-update-slice_fusion.10 = {ssm}{lay} fusion("
        f"{ssm}{lay} %get-tuple-element.7, f32[128,128]{{1,0}} %x), "
        f"kind=kLoop, calls=%fused_computation.3": 2.0,
        # the second read of the state (y = S C): operand only
        f"%multiply_reduce_fusion.4 = f32[128,128,64]{{2,1,0}} fusion("
        f"{ssm}{lay} %select_dynamic-update-slice_fusion.10), kind=kLoop": 1.0,
        f"%fusion.9 = {conv}{{3,2,1,0}} fusion({conv}{{3,2,1,0}} %p), "
        f"kind=kLoop": 0.25,
        # a loop that carries the pools only contains the others
        f"%while.3 = ({ssm}{lay}, {conv}{{3,2,1,0}}) while(%tuple.1), "
        f"body=%body": 9.0,
        # another layer's work
        "%moe.experts.12 = f32[2816,1024]{1,0} custom-call(%a)": 5.0}
    observed = dict(decode_rows=30000, prefills=100, slots=128)
    view = SimpleNamespace(
        cfg=cfg, observed=observed,
        summary=trace.Summary(window_s=12.0, busy_s=11.0, ops=ops,
                              idle_gaps={}),
        peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    moved = 5 * (30000 * 2 * 4255744 + 100 * 4255744)
    assert module.read(view) == pytest.approx(
        100 * moved / 819e9 / 3.25)
    # other slots, so other pools: nothing to read, and nothing, not 0
    view.observed = dict(observed, slots=64)
    assert module.read(view) is None
    view.cfg = {"hidden_size": 8}
    assert module.read(view) is None
