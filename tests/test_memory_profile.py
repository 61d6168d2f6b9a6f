"""HBM memory profiler (ISSUE 9): compiled live-buffer ledger,
per-layer attribution, OOM forensics.

Contract style follows PR 7's sums-to-wall:

- ledger buckets sum to memory_analysis totals (<= 2% slack, with the
  measured ~8 B/output-leaf PJRT tuple-metadata floor);
- live.by_scope sums to peak_live_bytes EXACTLY by construction;
- named-scope attribution round-trips through a real 2-layer model
  compile (decoder.0 / decoder.1 / mlp names come back out of the HLO);
- top-K-at-peak is deterministic for a fixed executable;
- HeadroomGuard violations and flight-recorder dumps attach the ledger;
- the report tool (tools/memory_report.py) passes on real lanes and
  exits non-zero under mutation (inflated buffer, un-sharded spec) —
  the trap-linter verification pattern.
"""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.observability as obs
from paddle_tpu.observability import flight_recorder
from paddle_tpu.observability import memory_profile as mp
from paddle_tpu.utils import hlo_analysis as ha

REPO = os.path.join(os.path.dirname(__file__), "..")
ARTIFACT = os.path.join(REPO, "tools", "artifacts", "sweep",
                        "memory_profile_r12.json")


@pytest.fixture
def clean_obs():
    mp.reset()
    obs.reset()
    yield
    obs.disable()
    obs.reset()
    mp.reset()


def _compiled_two_scope():
    """A tiny grad compile with two named scopes — the shared probe."""
    import jax
    import jax.numpy as jnp

    def f(x, w, w2):
        with jax.named_scope("enc.0"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("enc.1"):
            y = jnp.tanh(h @ w2)
        return (y ** 2).sum()

    return jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(
        jnp.ones((32, 64)), jnp.ones((64, 128)),
        jnp.ones((128, 64))).compile()


# -- scope decoding -----------------------------------------------------------
class TestScopeOfOpName:
    def test_plain(self):
        assert ha.scope_of_op_name(
            "jit(f)/jit(main)/jvp(enc.0)/tanh") == "enc.0"

    def test_nested_transforms(self):
        assert ha.scope_of_op_name(
            "jit(f)/jit(main)/transpose(jvp(decoder.0/mlp))/dot_general"
        ) == "decoder.0/mlp"

    def test_no_scope(self):
        assert ha.scope_of_op_name("jit(f)/jit(main)/mul") == ""

    def test_remat_frame(self):
        assert ha.scope_of_op_name(
            "jit(f)/checkpoint(remat(decoder.3))/dot_general") \
            == "decoder.3"


# -- live-range analyzer ------------------------------------------------------
class TestLiveRange:
    def test_report_shape_and_scope_sums(self):
        c = _compiled_two_scope()
        txt = c.runtime_executable().hlo_modules()[0].to_string()
        rep = ha.live_range_report(txt, top_k=6)
        assert rep["instructions"] > 0
        assert rep["peak_live_bytes"] > 0
        # by_scope sums to peak EXACTLY (the "" bucket absorbs
        # unattributed values)
        assert sum(rep["by_scope"].values()) == rep["peak_live_bytes"]
        scopes = set(rep["by_scope"])
        assert any(s.startswith("enc.0") for s in scopes)
        # top-K sorted descending, bytes positive
        tops = rep["top_at_peak"]
        assert tops == sorted(tops, key=lambda t: (-t["bytes"],
                                                   t["name"]))

    def test_io_reconstruction_matches_pjrt(self):
        c = _compiled_two_scope()
        ma = c.memory_analysis()
        txt = c.runtime_executable().hlo_modules()[0].to_string()
        rep = ha.live_range_report(txt)
        assert rep["argument_bytes"] == ma.argument_size_in_bytes
        assert abs(rep["output_bytes"] - ma.output_size_in_bytes) \
            <= max(0.02 * ma.output_size_in_bytes, 256)


# -- the ledger ---------------------------------------------------------------
class TestExecutableLedger:
    def test_buckets_sum_to_total(self):
        led = mp.executable_ledger(_compiled_two_scope())
        assert sum(led["buckets"].values()) == led["total_bytes"]
        assert led["peak_bytes"] > 0
        assert mp.verify_ledger(led) == []

    def test_donated_alias_discounted_once(self):
        import jax
        import jax.numpy as jnp

        def f(x, w):
            return x + 1.0, (x * w).sum()

        c = jax.jit(f, donate_argnums=(0,)).lower(
            jnp.ones((64, 64)), jnp.ones((64, 64))).compile()
        led = mp.executable_ledger(c)
        b = led["buckets"]
        assert b["alias"] > 0          # the donation is booked
        assert led["peak_bytes"] == (b["argument"] + b["output"]
                                     + b["temp"] + b["generated_code"]
                                     - b["alias"])
        assert mp.verify_ledger(led) == []

    def test_top_k_stable(self):
        c = _compiled_two_scope()
        a = mp.executable_ledger(c, top_k=6)
        b = mp.executable_ledger(c, top_k=6)
        assert a["live"]["top_at_peak"] == b["live"]["top_at_peak"]
        assert a["live"]["by_scope"] == b["live"]["by_scope"]

    def test_verify_rejects_broken_scope_sum(self):
        led = mp.executable_ledger(_compiled_two_scope())
        led["live"]["by_scope"][""] += 1
        assert any("by_scope" in e for e in mp.verify_ledger(led))


# -- named-scope round-trip through a real model ------------------------------
class TestModelAttribution:
    def test_two_layer_llama_roundtrip(self, clean_obs):
        from paddle_tpu.models import (LlamaForCausalLM,
                                       LlamaPretrainingCriterion)
        from paddle_tpu.models.llama import llama_tiny

        pt.seed(0)
        cfg = llama_tiny(num_hidden_layers=2)
        model = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion(cfg)
        opt = pt.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
        step = pt.jit.TrainStep(model, lambda lo, la: crit(lo, la), opt)
        rng = np.random.default_rng(0)
        ids = pt.to_tensor(rng.integers(0, cfg.vocab_size, (2, 16)),
                           dtype="int64")
        lab = pt.to_tensor(rng.integers(0, cfg.vocab_size, (2, 16)),
                           dtype="int64")
        obs.enable()
        for _ in range(3):
            step((ids,), (lab,))
        leds = mp.ledgers()
        assert leds and all(k.startswith("train_step:") for k in leds)
        # every recorded executable honors the contracts
        for led in leds.values():
            assert mp.verify_ledger(led) == []
        # attribution round-trip: BOTH layers and block roles survive
        # jvp/transpose wrapping into the optimized module's metadata
        # (by_scope_total is the whole-program per-layer table; the
        # at-peak by_scope only carries whatever is live at the instant)
        scopes = set()
        for led in leds.values():
            scopes |= set((led["live"] or {}).get("by_scope_total", {}))
        assert any(s.startswith("decoder.0") for s in scopes), scopes
        assert any(s.startswith("decoder.1") for s in scopes), scopes
        assert any("mlp" in s for s in scopes), scopes
        assert any("attn" in s for s in scopes), scopes
        # gauges live under the per-executable labels
        dump = obs.dump()
        for g in ("paddle_tpu_hbm_args_bytes",
                  "paddle_tpu_hbm_temps_bytes",
                  "paddle_tpu_hbm_outputs_bytes",
                  "paddle_tpu_hbm_peak_bytes"):
            fam = dump.get(g, {}).get("values", {})
            assert fam, f"{g} not recorded"
        # the bench.py artifact surface
        ms = step.memory_summary()
        assert ms["max_peak_bytes"] > 0
        assert all(v["peak_bytes"] > 0
                   for v in ms["executables"].values())


# -- serve() executables ------------------------------------------------------
class TestServeLedger:
    def test_paged_decoder_records_and_keeps_parity(self, clean_obs):
        from paddle_tpu.models import LlamaForCausalLM
        from paddle_tpu.models.llama import llama_tiny
        from paddle_tpu.models.paged_decode import PagedDecoder

        pt.seed(0)
        cfg = llama_tiny(num_hidden_layers=2,
                         use_flash_attention=False,
                         max_position_embeddings=64)
        model = LlamaForCausalLM(cfg)
        model.eval()
        reqs = [(0, [1, 2, 3], 4), (1, [4, 5], 4)]
        dec = PagedDecoder(model, max_len=32, block_size=8, max_slots=2,
                           num_blocks=9)
        obs.enable()
        out = dec.serve(reqs, chunk=4)
        obs.disable()
        keys = list(mp.ledgers())
        assert any(k.startswith("serve:prefill_b") for k in keys), keys
        # the one decode chunk program (chunkst_n*)
        assert any(k.startswith("serve:chunkst_n") for k in keys), keys
        for led in mp.ledgers().values():
            assert mp.verify_ledger(led) == []
        # an observed serve returns what an unobserved one does
        dec2 = PagedDecoder(model, max_len=32, block_size=8,
                            max_slots=2, num_blocks=9)
        assert dec2.serve(reqs, chunk=4) == out


# -- OOM forensics ------------------------------------------------------------
class TestForensics:
    def test_flight_recorder_memory_section(self, clean_obs, tmp_path):
        mp.record_executable("test", "probe", _compiled_two_scope())
        path = flight_recorder.arm(str(tmp_path / "fr.json"),
                                   install_signals=False)
        try:
            assert flight_recorder.trip("test_memory") == path
        finally:
            flight_recorder.disarm()
        with open(path) as f:
            doc = json.load(f)
        assert flight_recorder.validate(doc) == []
        assert "test:probe" in doc["memory"]["ledgers"]
        entry = doc["memory"]["ledgers"]["test:probe"]
        assert entry["peak_bytes"] > 0
        assert entry["top_at_peak"]          # the named-buffer table

    def test_headroom_violation_attaches_ledgers(self, clean_obs,
                                                 tmp_path):
        from paddle_tpu.framework.memory import HeadroomGuard

        mp.record_executable("test", "probe", _compiled_two_scope())
        path = flight_recorder.arm(str(tmp_path / "hg.json"),
                                   install_signals=False)
        try:
            guard = HeadroomGuard(limit_bytes=1)
            assert not guard.check(10**9)
        finally:
            flight_recorder.disarm()
        with open(path) as f:
            doc = json.load(f)
        assert flight_recorder.validate(doc) == []
        assert doc["reason"] == "headroom_violation"
        assert doc["extra"]["requested_bytes"] == 10**9
        # the forensics ride the dump's own memory section (once)
        assert "test:probe" in doc["memory"]["ledgers"]
        assert "ledgers" not in doc["extra"]

    def test_validate_requires_memory_section(self):
        doc = {"schema": flight_recorder.SCHEMA, "reason": "x",
               "ts": 1.0, "rank": 0, "pid": 1, "spans": [],
               "counters": {}, "counter_deltas": {}, "in_flight": {}}
        assert any("memory" in e for e in flight_recorder.validate(doc))


# -- report tool + mutation verification --------------------------------------
class TestMemoryReport:
    """Driven in-process (the CLI main()) against ONE fast lane so the
    tier-1 budget holds; the full six-lane sweep is the `memory` CI
    tier (tools/run_ci.sh memory)."""

    def _tool(self):
        import importlib
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            return importlib.import_module("memory_report")
        finally:
            sys.path.pop(0)

    def test_lane_passes_and_artifact_exists(self):
        tool = self._tool()
        rc = tool.main(["--lanes", "quantized_grad_sync",
                        "--check", ARTIFACT])
        assert rc == 0
        with open(ARTIFACT) as f:
            base = json.load(f)
        assert base["pass"] and len(base["lanes"]) >= 5

    def test_mutation_inflated_buffer_fails(self, monkeypatch, capsys):
        """The trap-linter pattern: a doubled buffer MUST exit
        non-zero. Simulated at the profiler seam — every measured
        temp/peak doubles, the committed fingerprint doesn't."""
        tool = self._tool()
        real = mp.executable_ledger

        def doubled(compiled, **kw):
            led = real(compiled, **kw)
            led["buckets"]["temp"] *= 2
            led["total_bytes"] = sum(led["buckets"].values())
            led["peak_bytes"] += led["buckets"]["temp"] // 2
            return led

        monkeypatch.setattr(mp, "executable_ledger", doubled)
        rc = tool.main(["--lanes", "quantized_grad_sync",
                        "--check", ARTIFACT])
        assert rc == 1
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert any(v["kind"] == "budget_drift"
                   for v in out["violations"])

    def test_mutation_unsharded_spec_fails(self, monkeypatch, capsys):
        """Un-sharding the save-buffer spec fails the lane's lint entry
        (assert_sharding), which the report tool runs FIRST — rc=1."""
        from paddle_tpu.analysis import registry as reg
        from paddle_tpu.analysis.hlo_lint import LintError
        tool = self._tool()

        def unsharded_entry(prebuilt=None):
            from paddle_tpu.analysis import hlo_lint
            if prebuilt is None:
                g, args, meta = reg.build_lane("pipeline_save_stack")
                text = hlo_lint.compiled_text(g, *args)
            else:
                _, _, meta, text = prebuilt
            sh = dict(meta["sharding"])
            # claim the buffer should also be mp-sharded on the seq
            # dim: the real compile doesn't produce that per-chip
            # shape -> LintError, exactly what a spec regression
            # (an un-sharded or re-laid-out buffer) produces
            sh["spec"] = (None, "pp", "dp", "mp", None)
            hlo_lint.assert_sharding(text, what="mutated", **sh)
            return {}

        monkeypatch.setitem(reg.ENTRIES, "pipeline_save_stack",
                            unsharded_entry)
        with pytest.raises(LintError):
            reg.run_entry("pipeline_save_stack")
        rc = tool.main(["--lanes", "pipeline_save_stack",
                        "--check", ARTIFACT])
        assert rc == 1
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert any("lint entry failed" in str(v.get("detail", ""))
                   for v in out["violations"])

    def test_gate_drift_pure(self):
        tool = self._tool()
        base = {"lanes": {"x": {"temp_bytes": 1000, "peak_bytes": 2000,
                                "total_bytes": 3000,
                                "peak_live_bytes": 1500,
                                "argument_bytes": 64,
                                "output_bytes": 64}}}
        same = json.loads(json.dumps(base["lanes"]))
        assert tool.gate_drift(base, same) == []
        doubled = json.loads(json.dumps(base["lanes"]))
        doubled["x"]["temp_bytes"] *= 2
        vs = tool.gate_drift(base, doubled)
        assert vs and vs[0]["kind"] == "budget_drift"
        # shrinking is drift too: a silently-vanished buffer means the
        # lane no longer exercises what it claims to
        halved = json.loads(json.dumps(base["lanes"]))
        halved["x"]["peak_bytes"] //= 2
        assert tool.gate_drift(base, halved)
