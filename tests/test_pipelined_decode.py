"""Zero-sync pipelined decode (ISSUE 20): device-resident batch state,
one-chunk lookahead, fused first-token prefill, and the host_gap
attribution bucket.

Oracle: ``pipeline=False`` — the same state-carrying executable driven
strictly serially (dispatch, wait, consume). The pipelined default must
be token-identical to it across mixed budgets, EOS mid-chunk, eviction
+ replay, quarantine discovered one chunk late, the multi-turn prefix
cache, and spec-decode interop; the h2d upload counters prove the
steady state never uploads batch state; the serve ledger's host_gap
bucket must keep the sums-to-wall invariant; and PT_PIPE_TEETH proves
both gates (zero-upload, parity) have teeth.
"""
import json

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.observability as obs
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.framework.memory import HeadroomGuard
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.paged_decode import PagedDecoder
from paddle_tpu.resilience import faults


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    from paddle_tpu.observability import attribution
    monkeypatch.delenv("PT_PIPE_TEETH", raising=False)
    faults.clear()
    set_flags({"serve_fault_recovery": True,
               "serve_logit_quarantine": True})
    attribution.drain_external()
    yield
    faults.clear()
    set_flags({"serve_fault_recovery": True,
               "serve_logit_quarantine": True})
    obs.set_jsonl_path(None)
    obs.disable()
    attribution.drain_external()


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=97, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128,
                      use_flash_attention=False, dtype="float32")
    pt.seed(5)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _dec(model, **kw):
    args = dict(max_len=64, block_size=8, max_slots=4, num_blocks=48)
    args.update(kw)
    return PagedDecoder(model, **args)


def _prompt(n, seed):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(0, 97, n)]


def _reqs():
    # mixed budgets: the chunk is sized by the largest and the device
    # gate retires the others mid-stream
    return [("a", _prompt(7, 1), 20), ("b", _prompt(5, 2), 9),
            ("c", _prompt(9, 3), 14)]


@pytest.fixture(scope="module")
def serial(model):
    """The serial-loop oracle every pipelined run must reproduce."""
    return _dec(model).serve(_reqs(), chunk=4, pipeline=False)


class TestParityMatrix:
    def test_pipelined_matches_serial_mixed_budgets(self, model,
                                                    serial):
        dec = _dec(model)
        out = dec.serve(_reqs(), chunk=4)
        assert out == serial
        assert dec.lookahead_dispatches >= 1

    def test_eos_mid_chunk(self, model, serial):
        # pick an eos that fires mid-stream: retirement via the
        # device's eos_seen mask, one chunk ahead of the host
        eos = serial["a"][2]
        ref = _dec(model).serve(_reqs(), chunk=4, pipeline=False,
                                eos_token_id=eos)
        out = _dec(model).serve(_reqs(), chunk=4, eos_token_id=eos)
        assert out == ref
        assert any(eos in v for v in ref.values())

    def test_eviction_replay_parity(self, model, serial):
        faults.install_plan({"seed": 7, "sites": {
            "headroom_pressure": {"p": 1.0, "window": [0, 8]}}})
        dec = _dec(model, max_slots=2, num_blocks=12,
                   headroom_guard=HeadroomGuard())
        try:
            out = dec.serve(_reqs(), chunk=4, max_restarts=6)
        finally:
            faults.clear()
        assert out == serial
        assert dec.evictions >= 1
        assert dec.pipeline_drains >= 1

    def test_quarantine_one_chunk_late(self, model, serial):
        # with lookahead on, chunk N's poisoned bad-flag reaches the
        # host AFTER chunk N+1 was dispatched — the quarantine must
        # still recycle the slot and replay to exact parity
        faults.install_plan({"seed": 7, "sites": {
            "logits_poison": {"p": 1.0, "window": [0, 2]}}})
        dec = _dec(model)
        try:
            out = dec.serve(_reqs(), chunk=4, max_restarts=6)
        finally:
            faults.clear()
        assert out == serial
        assert dec.quarantines >= 1
        assert dec.lookahead_dispatches >= 1

    def test_multi_turn_cache_parity(self, model):
        dec = _dec(model, prefix_cache=True)
        off = _dec(model)
        t0 = _prompt(16, 4)
        r0 = dec.serve([("s0", t0, 6)])["s0"]
        assert r0 == off.serve([("x", t0, 6)], pipeline=False)["x"]
        t1 = t0 + r0 + _prompt(5, 6)
        r1 = dec.serve([("s1", t1, 6)])["s1"]
        assert r1 == off.serve([("y", t1, 6)], pipeline=False)["y"]

    def test_spec_decode_default_pipeline_parity(self, model, serial):
        dec = _dec(model)
        out = dec.serve(_reqs(), chunk=4, spec_decode=2)
        assert out == serial
        # the verify pass is host-interactive: no lookahead, but the
        # device-resident mirrors still spare the per-pass re-uploads
        assert dec.lookahead_dispatches == 0

    def test_spec_pipeline_true_refused(self, model):
        with pytest.raises(ValueError, match="spec_decode"):
            _dec(model).serve(_reqs(), chunk=4, spec_decode=2,
                              pipeline=True)


class TestZeroUpload:
    def test_steady_state_uploads_once(self, model, serial):
        dec = _dec(model)
        out = dec.serve(_reqs(), chunk=4)
        assert out == serial
        # one full-state upload (6 arrays) at the first dispatch, then
        # ZERO host->device batch-state traffic for the whole serve
        assert dec.h2d_uploads == 6
        assert dec.chunk_dispatches >= 4
        assert dec.pipeline_drains == 0

    def test_pipeline_false_still_device_resident(self, model, serial):
        dec = _dec(model)
        out = dec.serve(_reqs(), chunk=4, pipeline=False)
        assert out == serial
        assert dec.h2d_uploads == 6
        assert dec.lookahead_dispatches == 0

    def test_admission_drains_and_reuploads(self, model):
        # 5 requests into 4 slots: the queued head joins mid-serve —
        # a composition change the device can't see, so the pipeline
        # drains and re-uploads exactly once more
        reqs = _reqs() + [("d", _prompt(6, 7), 11),
                          ("e", _prompt(8, 8), 13)]
        ref = _dec(model).serve(reqs, chunk=4, pipeline=False)
        dec = _dec(model)
        out = dec.serve(reqs, chunk=4)
        assert out == ref
        assert dec.pipeline_drains >= 1
        assert dec.h2d_uploads == 12

    def test_spec_reuses_device_mirrors(self, model):
        dec = _dec(model)
        dec.serve(_reqs(), chunk=4, spec_decode=2)
        # per verify pass: candidate tokens + positions (2) always;
        # tables/live/budgets/poison only on host-value change — far
        # below the old 6-per-pass re-upload
        assert dec.chunk_dispatches >= 4
        assert dec.h2d_uploads < 6 * dec.chunk_dispatches


class TestLedger:
    def test_host_gap_bucket_telescopes(self, model, tmp_path):
        obs.registry().reset()
        obs.enable()
        path = str(tmp_path / "steps.jsonl")
        obs.set_jsonl_path(path)
        dec = _dec(model)
        dec.serve(_reqs(), chunk=4)
        obs.set_jsonl_path(None)
        recs = [json.loads(l) for l in open(path)]
        recs = [r for r in recs if r.get("event") == "step_attribution"
                and r.get("source") == "serve"]
        assert recs, "pipelined serve emitted no ledger records"
        for r in recs:
            a = r["attribution"]
            assert "host_gap" in a
            assert sum(a.values()) == pytest.approx(
                r["wall_s"], rel=0.02, abs=1e-6)
        led = dec._serve_ledger
        assert "host_gap" in led.totals
        dump = obs.dump()
        ups = dump.get("paddle_tpu_serve_h2d_uploads_total")
        assert ups and sum(ups["values"].values()) == 6
        depth = dump.get("paddle_tpu_serve_pipeline_depth_total")
        assert depth and sum(depth["values"].values()) >= 1


    @pytest.mark.parametrize("pipeline", [False, None],
                             ids=["serial", "lookahead"])
    def test_host_gap_is_the_starved_seconds_that_ended_in_the_iteration(
            self, model, tmp_path, monkeypatch, pipeline):
        """ISSUE 39: the ledger's `host_gap` has one source, the loop's
        `serve:starved` stretches. On a clock that moves a microsecond a
        read, shared by the loop and the tracer, an iteration's bucket
        is the sum of the stretches that ended in it (the ledger holds
        it to the iteration's own unmeasured time), and the registry's
        counter is their sum by `before`."""
        import time
        import types
        from paddle_tpu.observability import tracing
        from paddle_tpu.serving import batcher
        # every program compiled before the clock is swapped: a compile's
        # seconds are the listener's own, on the real clock
        dec = _dec(model)
        dec.serve(_reqs(), chunk=4, pipeline=pipeline)
        now = [100.0]

        def tick():
            now[0] += 1e-6
            return now[0]
        fake = types.SimpleNamespace(
            perf_counter=tick, perf_counter_ns=lambda: round(tick() * 1e9),
            time_ns=time.time_ns, sleep=lambda s: None)
        monkeypatch.setattr(batcher, "time", fake)
        monkeypatch.setattr(tracing, "time", fake)
        obs.registry().reset()
        obs.enable()
        path = str(tmp_path / "steps.jsonl")
        obs.set_jsonl_path(path)
        tracing.clear()
        tracing.enable_tracing()
        try:
            dec.serve(_reqs(), chunk=4, pipeline=pipeline)
        finally:
            tracing.disable_tracing()
            obs.set_jsonl_path(None)
        spans = tracing.tail()
        tracing.clear()
        recs = [json.loads(l) for l in open(path)]
        recs = [r["attribution"] for r in recs
                if r.get("event") == "step_attribution"
                and r.get("source") == "serve"]
        its = [s for s in spans if s["name"] == "serve:iteration"]
        starved = [s for s in spans if s["name"] == "serve:starved"]
        assert len(recs) == len(its) and starved
        end = lambda s: s["t0_ns"] + s["dur_ns"]
        slack = 5e-6        # the loop's reads lie a few ticks off the span's
        ended = []
        for a, it in zip(recs, its):
            mine = [s for s in starved
                    if it["t0_ns"] <= end(s) <= end(it)]
            secs = sum(s["dur_ns"] for s in mine) * 1e-9
            ended.append(len(mine))
            assert a["host_gap"] == pytest.approx(
                min(secs, a["host_gap"] + a["dispatch"]),
                abs=slack * max(len(mine), 1))
        assert sum(ended) == len(starved)
        assert sum(a["host_gap"] for a in recs) > 0
        if pipeline is None:
            # look-ahead: nothing is starved once the chunks follow
            # each other
            assert not any(ended[1:])
            assert all(a["host_gap"] == 0 for a in recs[1:])
        else:
            assert all(ended)
        counted = obs.dump()[
            "paddle_tpu_serve_device_starved_seconds_total"]["values"]
        by_before = {}
        for s in starved:
            k = s["meta"]["before"]
            by_before[k] = by_before.get(k, 0.0) + s["dur_ns"] * 1e-9
        assert set(counted) == set(by_before)
        for k, secs in by_before.items():
            assert counted[k] == pytest.approx(secs,
                                               abs=slack * len(starved))


class TestTeeth:
    def test_force_sync_disables_lookahead(self, model, serial,
                                           monkeypatch):
        monkeypatch.setenv("PT_PIPE_TEETH", "force_sync")
        dec = _dec(model)
        out = dec.serve(_reqs(), chunk=4)
        # tokens stay right (it's a de-optimization, not corruption) —
        # but the upload counter explodes: the gate this env arms in
        # tools/serving_drill.py --verify-teeth must trip on it
        assert out == serial
        assert dec.lookahead_dispatches == 0
        assert dec.h2d_uploads == 6 * dec.chunk_dispatches

    def test_mutate_feedback_breaks_parity(self, model, serial,
                                           monkeypatch):
        monkeypatch.setenv("PT_PIPE_TEETH", "mutate_feedback")
        out = _dec(model).serve(_reqs(), chunk=4)
        assert out != serial


class TestFusedFirstToken:
    def test_decode_roundtrip(self):
        assert PagedDecoder.decode_first_token([np.int32(5)]) == (5, False)
        assert PagedDecoder.decode_first_token([np.int32(0)]) == (0, False)
        # non-finite logits ride the sign bit; the argmax survives
        assert PagedDecoder.decode_first_token([np.int32(-6)]) == (5, True)
        assert PagedDecoder.decode_first_token([np.int32(-1)]) == (0, True)
