"""The rest of a run (everything after the look for a chip), at a size the
CPU holds, first sound and then with the timed path broken underneath:
`correct` has to come out false for each fault a cell can have."""
import numpy as np
import pytest

from chipbench.adapters import llama_dense as adapter
from chipbench.tests import tiny

# the limits of this size: ten times what sound runs read here
# (grad 8e-4, change 2e-3, loss 9e-6, logit gap 6e-4 on seeds 7, 3000000019)
TRAIN_LIMITS = {"grad_norm_gap": 0.01,
                "change_norm_gap": 0.03}
SERVE_LIMITS = {"logit_gap": 0.01}


def test_sound_train_run_is_correct():
    out = tiny.run(tiny.TRAIN, TRAIN_LIMITS)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["compared"]) == {"grad_norm_gap", "change_norm_gap"}
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "compared"


def test_step_that_returns_its_state_unchanged(monkeypatch):
    built = adapter.Trainer.__init__

    def frozen(self, cfg, traffic, weights):
        built(self, cfg, traffic, weights)
        self.opt._lr = 0.0          # the parameters come back as they went
        self.opt._coeff = 0.0
    monkeypatch.setattr(adapter.Trainer, "__init__", frozen)
    out = tiny.run(tiny.TRAIN, TRAIN_LIMITS)
    assert not out["correct"]
    assert out["compared"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(monkeypatch):
    call = adapter.Trainer.__call__
    monkeypatch.setattr(
        adapter.Trainer, "__call__",
        lambda self, ids, labels: call(self, ids[:1], labels[:1]))
    out = tiny.run(tiny.TRAIN, TRAIN_LIMITS)
    assert not out["correct"]
    bad = out["compared"]["grad_norm_gap"]
    assert bad["value"] > bad["limit"]


def test_sound_serve_run_is_correct():
    out = tiny.run(tiny.SERVE, SERVE_LIMITS)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0


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
    out = tiny.run(tiny.SERVE, SERVE_LIMITS)
    assert not out["correct"]
    assert out["compared"]["logit_gap"]["value"] > 0.1


def test_request_that_stops_before_its_budget(monkeypatch):
    from chipbench.kinds import serve as serve_kind
    snapshot = serve_kind.Probe._snapshot

    def short(self, now):
        snap = snapshot(self, now)
        snap["emitted"] = {rid: max(n - 1, 0)
                           for rid, n in snap["emitted"].items()}
        return snap
    monkeypatch.setattr(serve_kind.Probe, "_snapshot", short)
    out = tiny.run(tiny.SERVE, SERVE_LIMITS)
    assert not out["correct"] and out["failed"] > 0
