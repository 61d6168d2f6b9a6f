"""The yardstick of the `lfm2_moe` training cell, at a size the CPU
holds: a sound run is `correct`, the float8 control is not, and neither
is a run whose timed path has a fault planted underneath: an expert's
output dropped, the choice-only bias added to the weights, the weights
normalised over the held pairs only, a convolution of two taps or one
that sees the next token, QK-norm left out, half the batch, the state
returned unchanged.

The control and the faults run in float32 storage (`F32CFG`), where a
sound run reads 1e-5 to 1e-3 and every fault stands out; the sound run
also in the cell's bfloat16. Run by hand:
`JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_lfm2.py -q`;
`python3 chipbench/tests/test_lfm2.py` prints the readings the limits
below were set from.
"""
import jax
import jax.numpy as jnp
import pytest

from chipbench.adapters import lfm2 as adapter
from chipbench.tests import tiny_lfm2
from paddle_tpu.models import lfm2 as program

F32CFG = dict(tiny_lfm2.CFG, torch_dtype="float32")
# float32 storage: sound 6.5e-04-7.2e-04 (the bfloat16 first moment's rounding) /
# 7.7e-06-9.0e-06 (seeds 7, 3000000019, 11); the least fault (the bias added
# to the weights) 6.9e-03 / 1.6e-03; the control 0.057-0.071 / 0.017-0.027
F32_LIMITS = {"grad_norm_gap": 2e-3, "change_norm_gap": 2e-4}
# bfloat16 storage at this size: sound 0.0046-0.036 / 0.011-0.029, the control
# 0.044-0.063 / 0.026-0.030: 64-wide rows and top-2 of 16 leave no room
# between them, so this size only shows that the path runs; the cell's
# limits come from the chip (PERF.md section 2)
BF16_LIMITS = {"grad_norm_gap": 0.1, "change_norm_gap": 0.1}


def _run(cfg=F32CFG, limits=F32_LIMITS, seed=7):
    return tiny_lfm2.run(limits=limits, seed=seed, cfg=cfg)


def _readings(out):
    return {k: v["value"] for k, v in out["compared"].items()}


@pytest.mark.parametrize("cfg,limits", [(F32CFG, F32_LIMITS),
                                        (tiny_lfm2.CFG, BF16_LIMITS)])
def test_sound_run_is_correct(cfg, limits):
    out = _run(cfg, limits)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["compared"]) == {"grad_norm_gap", "change_norm_gap"}
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0


def test_float8_control_is_not_correct(cfg=F32CFG, limits=F32_LIMITS):
    from chipbench import generate
    from chipbench import run as harness
    from chipbench.kinds import train as train_kind
    from chipbench.reference import lfm2 as ref
    seed, traffic = 7, tiny_lfm2.TRAIN
    ring = generate.train_ring(traffic, cfg["vocab_size"], seed)
    args = (cfg, seed, ring, traffic["optimizer"], traffic["check_steps"])
    exact = ref.train_steps(*args)
    low = ref.train_steps(*args, precision="fp8")
    correct, compared = harness.decide(train_kind.compare(low, exact), limits)
    assert not correct
    assert any(v["value"] > v["limit"] for v in compared.values())


# -- faults planted in the program ---------------------------------------------------

def _expert_dropped(monkeypatch):
    experts = program.moe_experts
    monkeypatch.setattr(program, "moe_experts", lambda cfg, p, *a: experts(
        cfg, dict(p, w2=p["w2"].at[0].set(0)), *a))


def _rerouted(weights_of):
    """`moe_route` with other weights for the same choice."""
    def route(cfg, p, u):
        s = jax.nn.sigmoid(u.astype(jnp.float32) @ p["router"])
        b = p["b_corr"][None, :]
        _, idx = jax.lax.top_k(s + b, cfg.num_experts_per_tok)
        return idx.astype(jnp.int32), weights_of(cfg, s, b, idx)
    return route


def _bias_in_the_weights(monkeypatch):
    def weights_of(cfg, s, b, idx):
        picked = jnp.take_along_axis(s + b, idx, axis=1)
        return picked / (picked.sum(-1, keepdims=True) + 1e-6)
    monkeypatch.setattr(program, "moe_route", _rerouted(weights_of))


def _normalised_over_held_pairs(monkeypatch):
    def weights_of(cfg, s, b, idx):
        first, count = cfg.experts_held
        here = (idx >= first) & (idx < first + count)
        picked = jnp.take_along_axis(s, idx, axis=1) * here
        return picked / (picked.sum(-1, keepdims=True) + 1e-6)
    monkeypatch.setattr(program, "moe_route", _rerouted(weights_of))


def _two_taps(monkeypatch):
    conv = program.short_conv
    monkeypatch.setattr(program, "short_conv", lambda cfg, p, u: conv(
        cfg, dict(p, conv_w=p["conv_w"].at[:, 0].set(0)), u))


def _conv_sees_the_next_token(monkeypatch):
    def conv(cfg, p, u):
        b, c, z = jnp.split(u @ p["in_proj"].astype(u.dtype), 3, axis=-1)
        s, t = b * z, u.shape[1]
        padded = jnp.pad(s, ((0, 0), (1, 1), (0, 0)))    # t-1, t, t+1
        w = p["conv_w"].astype(jnp.float32)
        mixed = sum(padded[:, j:j + t].astype(jnp.float32) * w[:, j]
                    for j in range(3))
        return (c * mixed.astype(u.dtype)) @ p["out_proj"].astype(u.dtype)
    monkeypatch.setattr(program, "short_conv", conv)


def _no_qk_norm(monkeypatch):
    rms = program._rms
    monkeypatch.setattr(program, "_rms", lambda x, w, eps: x if x.ndim == 4
                        else rms(x, w, eps))


def _half_the_batch(monkeypatch):
    call = adapter.Trainer.__call__
    monkeypatch.setattr(
        adapter.Trainer, "__call__",
        lambda self, ids, labels: call(self, ids[:1], labels[:1]))


def _state_unchanged(monkeypatch):
    built = adapter.Trainer.__init__

    def frozen(self, cfg, traffic, weights):
        built(self, cfg, traffic, weights)
        self.opt._lr = 0.0          # the parameters come back as they went
        self.opt._coeff = 0.0
    monkeypatch.setattr(adapter.Trainer, "__init__", frozen)


FAULTS = {"expert_dropped": _expert_dropped,
          "bias_in_the_weights": _bias_in_the_weights,
          "normalised_over_held_pairs": _normalised_over_held_pairs,
          "two_taps": _two_taps,
          "conv_sees_the_next_token": _conv_sees_the_next_token,
          "no_qk_norm": _no_qk_norm,
          "half_the_batch": _half_the_batch,
          "state_unchanged": _state_unchanged}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    out = _run()
    assert not out["correct"]
    assert any(v["value"] > v["limit"] for v in out["compared"].values())
    if fault == "state_unchanged":
        assert out["compared"]["change_norm_gap"]["value"] == \
            pytest.approx(1.0, abs=1e-3)


# -- the counts the cell's metrics are computed from ---------------------------------

def test_the_cell_counts_what_the_issue_reckoned():
    import numpy as np
    from chipbench import flops_lfm2
    from chipbench import run as harness
    from chipbench.reference import lfm2 as ref
    cfg = harness.load_json("configs", "lfm2_8b_a1b_ep4_l8.json")
    held = sum(int(np.prod(shape)) for shape, _
               in ref.leaf_shapes(cfg).values())
    assert held == pytest.approx(772.2e6, rel=1e-3)
    # a pair a token and sparse layer here: 6 x 309.7 M x 8,192 + attention
    step = flops_lfm2.train_flops_per_step(cfg, 1, 8192, 6 * 8192)
    assert step == pytest.approx(16.87e12, rel=1e-3)
    work, moved = flops_lfm2.expert_train_calls(cfg, 6 * 8192, 6)
    assert work == 6 * 8192 * 6 * flops_lfm2.matmul_params(cfg)["expert"]
    assert work / 197e12 > moved / 819e9        # bound by compute
    assert flops_lfm2.kinds(cfg) == {"conv": 6, "attention": 2, "dense": 2,
                                     "sparse": 6}


def test_ad_wrapped_scope_names_are_read():
    from types import SimpleNamespace
    from chipbench import trace_ad
    ops = {"%jvp_moe.experts_.3 = bf16[8,8] custom-call(%a)": 1.0,
           "%transpose_jvp_moe.experts__.14 = bf16[8,8] custom-call(%a)": 2.0,
           "%moe.experts.2 = bf16[8,8] custom-call(%a)": 4.0,
           "%jvp_moe.route_.1 = f32[8] fusion(%a)": 8.0,
           "%fusion.7 = f32[8] fusion(%a)": 16.0}
    assert trace_ad.scope_seconds(SimpleNamespace(ops=ops),
                                  "moe.experts") == 7.0


if __name__ == "__main__":
    for seed in (7, 3000000019, 11):
        print("f32 sound", seed, _readings(_run(limits={}, seed=seed)))
        print("bf16 sound", seed, _readings(_run(tiny_lfm2.CFG, {}, seed)))
    from _pytest.monkeypatch import MonkeyPatch
    for name, plant in FAULTS.items():
        with MonkeyPatch.context() as mp:
            plant(mp)
            print(name, _readings(_run(limits={})))
