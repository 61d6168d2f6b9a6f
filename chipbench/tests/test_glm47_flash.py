"""The `glm4_moe_lite` reference, kind `serve_mtp`, counts and readers as a
yardstick: a sound run at a size the CPU holds is correct (in float32
storage to the last token and the last draft); the faults a cell can
have (a served token altered, the dense attention reading only the most
recent keys, the shared expert left out, a draft altered, the MTP
layer's two inputs swapped, a verify pass's second row zeroed in the
kernel) and the float8 control come out as not correct, the draft
faults by the drafts' numbers alone, since greedy verification keeps the
served tokens exact, the second row's fault by the passes' second rows; `flops_glm47_flash` against
counts by hand at the cell's own configuration; the readers on hand-made
spans.

    PYTHONPATH=. python3 chipbench/tests/test_glm47_flash.py

prints the readings of the faults and the control at this size."""
import functools
import importlib.util
import json
import os
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops_glm47_flash as fl
from chipbench.adapters import glm47_flash as adapter
from chipbench.kinds import serve as serve_kind
from chipbench.reference import glm47_flash as ref
from chipbench.tests import tiny_glm47_flash as tiny
from paddle_tpu.models import deepseek_v32 as engine

HERE = os.path.dirname(os.path.abspath(__file__))
# float32 storage: the program's rounding is out of the comparison, so a
# sound run reads 0 and a fault stands clear of it
F32CFG = dict(tiny.CFG, torch_dtype="float32")
LIMITS = {"logit_gap": 0.01, "logit_gap_mean": 0.01, "draft_gap": 0.01,
          "draft_gap_mean": 0.01, "verify_gap": 0.01, "verify_gap_mean": 0.01}


def test_sound_run_is_correct():
    out = tiny.run(tiny.SERVE, LIMITS, cfg=F32CFG)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    for name in LIMITS:
        assert out["compared"][name]["value"] < 1e-4, name


def test_the_cells_storage_type_runs_to_the_end():
    out = tiny.run(tiny.SERVE, LIMITS)
    assert out["failed"] == 0 and out["attempted"] > 0
    assert np.isfinite(out["compared"]["draft_gap"]["value"])
    assert 0 <= out["compared"]["draft_gap_mean"]["value"] \
        <= out["compared"]["draft_gap"]["value"]


def _altered_passes(monkeypatch, column):
    """Every chunk's third pass sends home another token in `column` of
    its (g0, g1, emitted, draft) row."""
    build = adapter.build_decoder

    def broken(cfg, traffic, weights):
        dec = build(cfg, traffic, weights)
        chunk = dec._paged_chunk_state_jit

        def altered(*args):
            passes, *rest = chunk(*args)
            passes = passes.at[:, 2, column].set(
                (passes[:, 2, column] + 1) % cfg["vocab_size"])
            return (passes, *rest)
        dec._paged_chunk_state_jit = altered
        return dec
    monkeypatch.setattr(adapter, "build_decoder", broken)


def token_altered(monkeypatch):
    _altered_passes(monkeypatch, 0)


def draft_altered(monkeypatch):
    _altered_passes(monkeypatch, 3)


def recent_keys_only(monkeypatch):
    """The dense decode kernel reads only a row's first 16 keys."""
    from paddle_tpu.kernels.pallas import mla_paged_decode as kernel
    full = kernel.mla_paged_decode_attention

    def recent(qc, q_pe, pool, tables, lens, base, kvr, scale):
        return full(qc, q_pe, pool, tables, jnp.minimum(lens, 16), base, kvr,
                    scale)
    monkeypatch.setattr(kernel, "mla_paged_decode_attention", recent)


def second_row_zeroed(monkeypatch):
    """The dense decode kernel returns zeros for a verify pass's second
    row (the draft's), so the target's token after a draft is wrong
    whether the draft was accepted or not."""
    from paddle_tpu.kernels.pallas import mla_paged_decode as kernel
    full = kernel.mla_paged_decode_attention

    def first_only(qc, q_pe, pool, tables, lens, base, kvr, scale):
        out = full(qc, q_pe, pool, tables, lens, base, kvr, scale)
        return out.at[:, 1:].set(0) if out.shape[1] > 1 else out
    monkeypatch.setattr(kernel, "mla_paged_decode_attention", first_only)


def shared_expert_left_out(monkeypatch):
    build = adapter.build_decoder

    def broken(cfg, traffic, weights):
        dec = build(cfg, traffic, weights)
        for layer in dec._params["layers"]:
            if "ws_d" in layer:
                layer["ws_d"] = layer["ws_d"] * 0
        return dec
    monkeypatch.setattr(adapter, "build_decoder", broken)


def mtp_inputs_swapped(monkeypatch):
    """The MTP layer's input projection takes [hnorm(h) ; enorm(emb)]."""
    def swapped(cfg, params, hn, next_ids):
        e = jnp.take(params["embed"], next_ids, axis=0).astype(hn.dtype)
        m, eps = params["mtp"], cfg.rms_norm_eps
        both = jnp.concatenate([engine._rms(hn, m["hnorm"], eps),
                                engine._rms(e, m["enorm"], eps)], axis=-1)
        return both @ m["eh_proj"].astype(hn.dtype)
    monkeypatch.setattr(engine, "mtp_input", swapped)


SERVED = [token_altered, recent_keys_only, shared_expert_left_out]
DRAFTED = [draft_altered, mtp_inputs_swapped]
SECOND = [second_row_zeroed]


@pytest.mark.parametrize("plant", SERVED + DRAFTED + SECOND,
                         ids=lambda f: f.__name__)
def test_planted_fault_is_not_correct(plant, monkeypatch):
    plant(monkeypatch)
    out = tiny.run(tiny.SERVE, LIMITS, cfg=F32CFG)
    assert not out["correct"]
    if plant in DRAFTED:
        # the served tokens stay exact: only the drafts show it
        assert out["compared"]["logit_gap"]["value"] < 1e-4
        assert out["compared"]["draft_gap"]["value"] > LIMITS["draft_gap"]
    elif plant in SECOND:
        # every pass's second row shows it, accepted or not
        assert out["compared"]["verify_gap"]["value"] > LIMITS["verify_gap"]
        assert out["compared"]["verify_gap_mean"]["value"] \
            > LIMITS["verify_gap_mean"]
    else:
        assert out["compared"]["logit_gap"]["value"] > LIMITS["logit_gap"]


def test_replaced_rows_are_the_full_forward_of_the_replaced_sequence():
    """A row replaced by the sequence's own token is the full forward's
    row; one replaced by another token is the full forward's row of the
    sequence with that token in its place."""
    cfg = F32CFG
    seed = 2**31 + 29
    weights = ref.make_weights(cfg, seed)
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], 96).astype(np.int32)
    rows = np.asarray([1, 40, 41, 95])
    own = np.asarray(ref.replaced_logits_at(cfg, weights, ids, rows,
                                            ids[rows]))
    full = np.asarray(ref.logits_at(cfg, weights, ids, rows))
    np.testing.assert_allclose(own, full, rtol=2e-5, atol=2e-5)
    other = (ids[rows] + 1) % cfg["vocab_size"]
    got = np.asarray(ref.replaced_logits_at(cfg, weights, ids, rows, other))
    for i, (r, tok) in enumerate(zip(rows, other)):
        alt = ids.copy()
        alt[r] = tok
        want = np.asarray(ref.logits_at(cfg, weights, alt, [r]))[0]
        np.testing.assert_allclose(got[i], want, rtol=2e-5, atol=2e-5)
    assert np.abs(got - full).max() > 1e-2


def test_float8_control_lies_below_the_reference_best():
    seed = 2**31 + 17
    weights = ref.make_weights(tiny.CFG, seed)
    ids = np.random.default_rng(seed).integers(
        0, tiny.CFG["vocab_size"], 160).astype(np.int32)
    rows = np.arange(40, 158)
    replaced = functools.partial(ref.replaced_logits_at,
                                 tokens=(ids[rows] + 7) % 256)
    for at in (ref.logits_at, ref.draft_logits_at, replaced):
        exact = np.asarray(at(tiny.CFG, weights, ids, rows,
                              precision="f32"))
        low = np.asarray(at(tiny.CFG, weights, ids, rows, precision="fp8"))
        assert serve_kind.gap_below_best(exact, exact.argmax(-1)).max() == 0
        assert serve_kind.gap_below_best(exact, low.argmax(-1)).max() \
            > LIMITS["draft_gap"]


CELL = os.path.join(HERE, "..", "configs", "glm47_flash_l6_mtp1.json")


def test_counts_at_the_cells_configuration():
    with open(CELL) as fh:
        cfg = json.load(fh)
    p = fl.matmul_params(cfg)
    # q_a 2048 x 768, q_b 768 x 5120, kv_a 2048 x 576, kv_b 512 x 8960,
    # o 5120 x 2048
    assert p["attn"] == 1572864 + 3932160 + 1179648 + 4587520 + 10485760
    assert p["expert"] == p["shared"] == 3 * 2048 * 1536
    assert fl.parameters(cfg) == 4_539_331_712
    assert fl.pair_flops(cfg, True) == 2 * 20 * (512 + 64 + 512)
    assert fl.pair_flops(cfg, False) == 2 * 20 * (192 + 64 + 256)
    # one token, one key of a prefill: every matmul, four experts an
    # expert layer, the pair in six layers, no head
    one = fl.forward_flops(cfg, 1, 1, 0, 0)
    assert one == 2 * (6 * p["attn"] + p["dense"] + 5 * (
        p["router"] + p["shared"] + 4 * p["expert"])) + 6 * 20480
    flops, moved = fl.decode_attention(cfg, 7, 96, 1000, 600)
    assert flops == 7 * 43520 * 1000
    assert moved == 7 * (600 * 1152 + 96 * 20 * 1088 * 2)


def _reader(name):
    path = os.path.join(HERE, "..", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _view(monkeypatch, metas, ops=None, observed=None, cfg=None):
    from chipbench import spans
    found = [{"id": i, "parent": None, "name": "serve:commit", "start": i,
              "end": i + 0.5, "meta": m} for i, m in enumerate(metas)]
    monkeypatch.setattr(spans, "in_window", lambda view: found)
    if cfg is None:
        with open(CELL) as fh:
            cfg = json.load(fh)
    summary = SimpleNamespace(ops=ops or {}, busy_s=1.0)
    return SimpleNamespace(cfg=cfg, observed=observed or {},
                           summary=summary, window=(0.0, 10.0),
                           peak={"bf16_flops_per_s": 197e12,
                                 "hbm_bytes_per_s": 819e9})


def test_readers_on_hand_made_spans(monkeypatch):
    metas = [dict(attn_rows=96, attn_pairs=800_000, latent_rows_read=400_000,
                  drafted=48, accepted=2, steps=16, tokens=50),
             dict(attn_rows=96, attn_pairs=800_100, latent_rows_read=400_048,
                  drafted=48, accepted=0, steps=16, tokens=48)]
    op = "%decode.attend.dense.7 = bf16[48,40,512]{2,1,0} custom-call(%a)"
    mtp_op = "%decode.mtp.3 = bf16[48,40,512]{2,1,0} custom-call(%a)"
    view = _view(monkeypatch, metas, ops={op: 0.25, mtp_op: 0.1},
                 observed=dict(drafted=96, accepted=2))
    roof = _reader("mla_paged_decode_roofline").read(view)
    work, moved = fl.decode_attention(view.cfg, 6, 192, 1_600_100, 800_048)
    least = max(work / 197e12, moved / 819e9)
    assert roof == pytest.approx(100 * least / 0.25)
    assert _reader("mtp.accepted_per_draft").read(view) == pytest.approx(
        100 * 2 / 96)
    assert _reader("mtp.device_share").read(view) == pytest.approx(10.0)
    # another engine's commits, or none: nothing to read
    assert _reader("mla_paged_decode_roofline").read(
        _view(monkeypatch, [dict(steps=8, tokens=8)], ops={op: 0.25})) \
        is None
    assert _reader("mtp.accepted_per_draft").read(
        _view(monkeypatch, [], observed={})) is None


if __name__ == "__main__":
    for plant in SERVED + DRAFTED + SECOND:
        mp = pytest.MonkeyPatch()
        plant(mp)
        out = tiny.run(tiny.SERVE, LIMITS, cfg=F32CFG)
        mp.undo()
        print(plant.__name__, {k: v["value"]
                               for k, v in out["compared"].items()})
