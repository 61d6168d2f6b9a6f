"""Roofline attribution (ISSUE 16): per-op compute/HBM/ICI-bound
pricing, the named-scope MFU-gap waterfall, and the continuous perf
ledger.

Contract style follows PR 7's sums-to-wall / PR 9's sums-to-total:

- class seconds sum to the modeled step wall (exactly by construction;
  verify_record re-checks <= 2%), class fractions sum to 1, the
  by_scope waterfall reconciles to the same wall;
- the recorded rates equal cost_model's chip constants and collective
  rows re-price through the SAME estimate_collective_seconds ring
  model (drift_vs_cost_model);
- named-scope attribution round-trips through real compiles: TrainStep
  executables carry decoder.* scopes, the quantized ragged serve path
  carries decode.attend / decode.kv_pool, spec verification carries
  decode.spec_verify (the ISSUE-16 scope threading);
- the gates have teeth: mutated records trip verify_record /
  drift_vs_cost_model, and the tools (roofline_report, bench_history,
  op_benchmark) fail on planted violations — the trap-linter pattern.
"""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.observability as obs
from paddle_tpu.observability import roofline as rl
from paddle_tpu.utils import hlo_analysis as ha

REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture
def clean_roof():
    rl.reset()
    obs.reset()
    yield
    obs.disable()
    obs.reset()
    rl.reset()


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


def _tiny_decode_model():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    pt.seed(5)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=97, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=128,
        use_flash_attention=False))
    m.eval()
    return m


# -- rates come from the ONE cost model ---------------------------------------
class TestChipRates:
    def test_rates_equal_cost_model_constants(self):
        from paddle_tpu.distributed.auto_tuner import cost_model as cm
        r = rl.chip_rates()
        assert r["mxu_flops_per_sec"] == float(cm.PEAK_FLOPS_TPU)
        assert r["hbm_bytes_per_sec"] == float(cm.HBM_BW)
        assert r["ici_bytes_per_sec"] == float(cm.ICI_BW)
        assert r["host_bytes_per_sec"] == float(cm.OFFLOAD_DMA_BW)
        assert all(v > 0 for v in r.values())

    def test_hbm_bw_exported(self):
        from paddle_tpu.distributed.auto_tuner import cost_model as cm
        assert "HBM_BW" in cm.__all__
        # v5p-class chip: HBM must be slower than MXU per byte-as-flop
        # but faster than the ICI link — or the classifier is nonsense
        assert cm.ICI_BW < cm.HBM_BW < cm.PEAK_FLOPS_TPU


# -- the pricing pass ---------------------------------------------------------
class TestRooflineRecord:
    def test_record_telescopes(self):
        rec = rl.executable_roofline(_compiled_two_scope())
        assert rec is not None and rec["schema"] == rl.SCHEMA
        total = rec["total_modeled_s"]
        assert total > 0
        # class seconds sum to the wall, fractions to 1
        assert sum(rec["class_time_s"][c] for c in rl.CLASSES) == \
            pytest.approx(total, rel=1e-9)
        assert sum(rec["class_time_frac"][c] for c in rl.CLASSES) == \
            pytest.approx(1.0, rel=1e-9)
        # the waterfall reconciles to the same wall
        assert sum(s["seconds"] for s in rec["by_scope"].values()) == \
            pytest.approx(total, rel=1e-9)
        # MFU identity: ideal + gap == wall
        assert rec["ideal_compute_s"] + rec["mfu_gap_s"] == \
            pytest.approx(total, rel=1e-9)
        assert 0.0 <= rec["modeled_mfu"] <= 1.0
        assert 0.0 <= rec["hbm_bound_flops_frac"] <= 1.0
        assert rl.verify_record(rec) == []
        assert rl.drift_vs_cost_model(rec) == []

    def test_scopes_round_trip(self):
        rec = rl.executable_roofline(_compiled_two_scope())
        scopes = set(rec["by_scope"])
        assert any(s.startswith("enc.0") for s in scopes), scopes
        assert any(s.startswith("enc.1") for s in scopes), scopes
        for v in rec["by_scope"].values():
            assert v["bound"] in rl.CLASSES
            assert v["seconds"] >= 0 and v["flops"] >= 0

    def test_top_ops_sorted_by_gap(self):
        rec = rl.executable_roofline(_compiled_two_scope(), top_k=6)
        tops = rec["top_ops"]
        assert tops and len(tops) <= 6
        assert tops == sorted(tops, key=lambda o: (-o["gap_s"],
                                                   o["name"]))
        for o in tops:
            assert o["class"] in rl.CLASSES
            assert o["trips"] >= 1
            # per-op roofline: seconds = max over the bound terms
            assert o["seconds"] >= o["compute_s"] - 1e-30
            assert o["gap_s"] == pytest.approx(
                o["seconds"] - o["compute_s"], abs=1e-18)

    def test_while_trips_weight_the_wall(self):
        """A counted while loop prices its body at trip weight: the
        8-trip compile must model a wall several times the 1-trip
        one."""
        import jax
        import jax.numpy as jnp

        def loop(n):
            def f(x, w):
                return jax.lax.fori_loop(
                    0, n, lambda i, h: jnp.tanh(h @ w), x)
            return jax.jit(f).lower(jnp.ones((64, 64)),
                                    jnp.ones((64, 64))).compile()

        one = rl.executable_roofline(loop(1))
        eight = rl.executable_roofline(loop(8))
        assert eight["total_modeled_s"] > 3 * one["total_modeled_s"]

    def test_record_survives_missing_hlo(self):
        class Dead:
            def runtime_executable(self):
                raise RuntimeError("gone")

        assert rl.executable_roofline(Dead()) is None
        assert rl.record_executable("test", "dead", Dead()) is None


# -- the contract checkers bite -----------------------------------------------
class TestVerifyAndDrift:
    def _rec(self):
        return rl.executable_roofline(_compiled_two_scope())

    def test_dropped_waterfall_bucket_fails(self):
        rec = self._rec()
        big = max(rec["by_scope"],
                  key=lambda s: rec["by_scope"][s]["seconds"])
        rec["by_scope"].pop(big)
        assert any("waterfall" in p for p in rl.verify_record(rec))

    def test_broken_class_fraction_fails(self):
        rec = self._rec()
        rec["class_time_frac"]["hbm"] += 0.1
        assert any("class_time_frac" in p for p in rl.verify_record(rec))

    def test_bad_hbm_frac_fails(self):
        rec = self._rec()
        rec["hbm_bound_flops_frac"] = 1.5
        assert any("hbm_bound_flops_frac" in p
                   for p in rl.verify_record(rec))

    def test_drifted_rate_fails(self):
        rec = self._rec()
        rec["rates"]["hbm_bytes_per_sec"] = 1e12
        assert any("hbm_bytes_per_sec" in p
                   for p in rl.drift_vs_cost_model(rec))

    def test_mispriced_collective_fails(self):
        rec = self._rec()
        rec.setdefault("collectives", []).append(
            {"name": "all-reduce.x", "kind": "all-reduce",
             "bytes": 1 << 20, "group_size": 4, "trips": 1,
             "seconds": 1.0})
        assert any("all-reduce.x" in p
                   for p in rl.drift_vs_cost_model(rec))

    def test_collective_at_ring_price_passes(self):
        rec = self._rec()
        s = ha.estimate_collective_seconds(
            "all-reduce", 1 << 20, 4,
            ici_bytes_per_sec=rl.chip_rates()["ici_bytes_per_sec"])
        rec.setdefault("collectives", []).append(
            {"name": "all-reduce.y", "kind": "all-reduce",
             "bytes": 1 << 20, "group_size": 4, "trips": 1,
             "seconds": s})
        assert rl.drift_vs_cost_model(rec) == []


# -- the bounded store --------------------------------------------------------
class TestRecordStore:
    def test_store_evicts_oldest(self, clean_roof, monkeypatch):
        monkeypatch.setattr(rl, "_MAX_RECORDS", 2)
        c = _compiled_two_scope()
        for i in range(3):
            assert rl.record_executable("test", f"p{i}", c) is not None
        keys = set(rl.records())
        assert keys == {"test:p1", "test:p2"}

    def test_top_hbm_bound_ops_filters_by_source(self, clean_roof):
        c = _compiled_two_scope()
        rl.record_executable("serve", "probe", c)
        rl.record_executable("train_step", "probe", c)
        rows = rl.top_hbm_bound_ops(3, source="serve")
        assert rows and all(r["executable"].startswith("serve:")
                            for r in rows)
        for r in rows:
            assert set(r) == {"executable", "name", "op", "scope",
                              "seconds", "bytes"}
            assert r["seconds"] >= 0


# -- the scope threading (ISSUE 16 satellite) ---------------------------------
class TestScopeOfOpName:
    def test_decode_attend_under_while_nesting(self):
        # the quant ragged kernel call sits inside serve's while loops;
        # the decode.attend scope must survive the body frames
        assert "decode.attend" in ha.scope_of_op_name(
            "jit(_serve_chunk)/jit(main)/while/body/decode.attend/"
            "custom-call")

    def test_spec_verify_scope(self):
        assert "decode.spec_verify" in ha.scope_of_op_name(
            "jit(_spec)/jit(main)/decode.spec_verify/dot_general")

    def test_kv_pool_scope(self):
        assert "decode.kv_pool" in ha.scope_of_op_name(
            "jit(_serve_chunk)/jit(main)/while/body/decode.kv_pool/"
            "dynamic-update-slice")


# -- TrainStep integration ----------------------------------------------------
class TestTrainStepRoofline:
    def test_two_layer_llama_records_and_attributes(self, clean_roof):
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
        recs = rl.records()
        assert recs and all(k.startswith("train_step:") for k in recs)
        scopes = set()
        for rec in recs.values():
            assert rl.verify_record(rec) == []
            assert rl.drift_vs_cost_model(rec) == []
            scopes |= set(rec["by_scope"])
        # both layers and block roles survive jvp/transpose wrapping
        assert any(s.startswith("decoder.0") for s in scopes), scopes
        assert any(s.startswith("decoder.1") for s in scopes), scopes
        assert any("attn" in s for s in scopes), scopes
        assert any("mlp" in s for s in scopes), scopes
        # gauges live under the per-executable labels
        dump = obs.dump()
        for g in ("paddle_tpu_roofline_hbm_bound_flops_frac",
                  "paddle_tpu_roofline_modeled_mfu",
                  "paddle_tpu_roofline_modeled_step_seconds",
                  "paddle_tpu_roofline_mfu_gap_seconds"):
            assert dump.get(g, {}).get("values"), f"{g} not recorded"
        # the bench.py artifact surface
        rs = step.roofline_summary()
        assert rs and rs["executables"]
        for v in rs["executables"].values():
            assert v["total_modeled_s"] > 0
            assert set(v["class_time_frac"]) == set(rl.CLASSES)
            assert len(v["top_ops"]) > 0
            assert v["by_scope"]


# -- serve() executables ------------------------------------------------------
class TestServeRoofline:
    def test_quant_ragged_serve_scopes_and_hbm_bill(self, clean_roof):
        from paddle_tpu.models.paged_decode import PagedDecoder

        model = _tiny_decode_model()
        reqs = [("a", [1, 2, 3], 4), ("b", [4, 5], 4)]
        dec = PagedDecoder(model, max_len=64, block_size=16,
                           max_slots=2, num_blocks=9,
                           kv_quant="int8", ragged_kernel=True)
        obs.enable()
        out = dec.serve(list(reqs), chunk=4)
        obs.disable()
        recs = rl.records()
        assert any(k.startswith("serve:prefill_b") for k in recs), recs
        # the one decode chunk program (chunkst_n*)
        assert any(k.startswith("serve:chunkst_n") for k in recs), recs
        scopes = set()
        for rec in recs.values():
            assert rl.verify_record(rec) == []
            scopes |= set(rec["by_scope"])
        # the ISSUE-16 threading: the quant ragged kernel call and the
        # paged pool writes carry their scopes through the while bodies
        assert any("decode.attend" in s for s in scopes), scopes
        assert any("decode.kv_pool" in s for s in scopes), scopes
        # the per-op bandwidth bill the decode bench attaches
        rows = rl.top_hbm_bound_ops(3, source="serve")
        assert rows
        assert all(np.isfinite(r["seconds"]) and r["seconds"] >= 0
                   for r in rows)
        # telemetry must not repaint the stream
        dec2 = PagedDecoder(model, max_len=64, block_size=16,
                            max_slots=2, num_blocks=9,
                            kv_quant="int8", ragged_kernel=True)
        assert dec2.serve(list(reqs), chunk=4) == out

    def test_spec_decode_carries_verify_scope(self, clean_roof):
        from paddle_tpu.models.paged_decode import PagedDecoder

        model = _tiny_decode_model()
        reqs = [("a", [1, 2, 3, 4], 6), ("b", [5, 6], 6)]
        dec = PagedDecoder(model, max_len=64, block_size=16,
                           max_slots=2, num_blocks=9)
        obs.enable()
        dec.serve(list(reqs), spec_decode=2)
        obs.disable()
        recs = rl.records()
        spec = {k: r for k, r in recs.items()
                if k.startswith("serve:spec_k")}
        assert spec, list(recs)
        scopes = set()
        for rec in spec.values():
            scopes |= set(rec["by_scope"])
        assert any("decode.spec_verify" in s for s in scopes), scopes


# -- GET /roofline ------------------------------------------------------------
class TestExporterEndpoint:
    def test_http_snapshot_and_endpoint(self, clean_roof, tmp_path):
        import urllib.request
        from paddle_tpu.observability import exporter

        rl.record_executable("test", "probe", _compiled_two_scope())
        hist = tmp_path / "bench_history.jsonl"
        hist.write_text(json.dumps(
            {"schema": "paddle_tpu.bench_history/1", "run": "r1",
             "lane": "train", "platform": "tpu",
             "metrics": {"llama_train_tokens_per_sec_per_chip": 1.0}})
            + "\n")
        rl.set_history_path(str(hist))
        port = exporter.start_http_server(port=0, host="127.0.0.1")
        try:
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/roofline", timeout=10).read())
        finally:
            exporter.stop_http_server()
            rl.set_history_path(None)
        assert doc["schema"] == rl.SCHEMA
        snap = doc["executables"]["test:probe"]
        assert snap["total_modeled_s"] > 0
        assert set(snap["class_time_frac"]) == set(rl.CLASSES)
        assert snap["top_ops"] and all(
            set(o) == {"name", "op", "scope", "class", "seconds",
                       "gap_s"} for o in snap["top_ops"])
        tail = doc["bench_history_tail"]
        assert tail and tail[-1]["run"] == "r1"


# -- tools/roofline_report.py -------------------------------------------------
class TestRooflineReportTool:
    """gate_records driven in-process on probe records; the full train
    lane + mutation teeth are the `roofline` CI tier."""

    def _tool(self, name="roofline_report"):
        import importlib
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            return importlib.import_module(name)
        finally:
            sys.path.pop(0)

    def _records(self):
        return {"train_step:probe":
                rl.executable_roofline(_compiled_two_scope())}

    def test_clean_records_pass(self):
        tool = self._tool()
        report, viol = tool.gate_records(self._records())
        assert report["pass"] and not viol
        assert report["top_gap_ops"]
        for o in report["top_gap_ops"]:
            assert o["class"] in rl.CLASSES
        assert report["top_gap_scopes"]
        assert any(s["scope"] for s in report["top_gap_scopes"])

    def test_dropped_bucket_trips_contract(self):
        tool = self._tool()
        recs = self._records()
        rec = recs["train_step:probe"]
        rec["by_scope"].pop(max(
            rec["by_scope"], key=lambda s: rec["by_scope"][s]["seconds"]))
        report, viol = tool.gate_records(recs)
        assert not report["pass"]
        assert any(v["kind"] == "contract" for v in viol)

    def test_scopeless_waterfall_trips(self):
        tool = self._tool()
        recs = self._records()
        rec = recs["train_step:probe"]
        rec["by_scope"] = {"": {"seconds": rec["total_modeled_s"],
                                "gap_s": rec["mfu_gap_s"],
                                "flops": rec["flops_total"],
                                "bytes": rec["bytes_total"],
                                "bound": "hbm"}}
        _, viol = tool.gate_records(recs)
        assert any(v["kind"] == "no_scopes" for v in viol)


# -- tools/bench_history.py ---------------------------------------------------
class TestBenchHistoryTool:
    def _tool(self):
        import importlib
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            return importlib.import_module("bench_history")
        finally:
            sys.path.pop(0)

    def test_flatten_and_directions(self):
        bh = self._tool()
        m = bh.flatten_lines([
            'not json',
            '{"metric": "llama_train_tokens_per_sec_per_chip", '
            '"value": 19232.7}',
            '{"metric": "serving_load_telemetry", "value": 1, '
            '"p99_tpot_s": 0.05, "nested": {"goodput_tokens_per_sec": '
            '7.0}, "rid": "not-a-number"}'])
        assert m["llama_train_tokens_per_sec_per_chip"] == 19232.7
        assert m["serving_load_telemetry.p99_tpot_s"] == 0.05
        assert m["serving_load_telemetry.nested.goodput_tokens_per_sec"] \
            == 7.0
        assert "serving_load_telemetry.rid" not in m
        assert bh.direction_of(
            "llama_train_tokens_per_sec_per_chip") == "higher"
        assert bh.direction_of(
            "serving_load_telemetry.p99_tpot_s") == "lower"
        assert bh.direction_of("serving_load_telemetry.pool_blocks") \
            is None

    def test_gate_direction_and_platform_keying(self):
        bh = self._tool()
        hist = [bh.build_row(
            ['{"metric": "llama_train_tokens_per_sec_per_chip", '
             '"value": 100.0}'], "train", "tpu", "r1")]
        slow = bh.build_row(
            ['{"metric": "llama_train_tokens_per_sec_per_chip", '
             '"value": 80.0}'], "train", "tpu", "r2")
        assert bh.gate_row(hist, slow)          # 20% drop trips
        fast = bh.build_row(
            ['{"metric": "llama_train_tokens_per_sec_per_chip", '
             '"value": 120.0}'], "train", "tpu", "r2")
        assert bh.gate_row(hist, fast) == []
        # cpu-smoke never gates vs tpu history
        cpu = bh.build_row(
            ['{"metric": "llama_train_tokens_per_sec_per_chip", '
             '"value": 1.0}'], "train", "cpu-smoke", "r2")
        assert bh.gate_row(hist, cpu) == []

    def test_append_gate_and_ledger_still_records(self, tmp_path,
                                                  capsys):
        bh = self._tool()
        hist = str(tmp_path / "h.jsonl")
        good = tmp_path / "good.txt"
        good.write_text('{"metric": '
                        '"llama_train_tokens_per_sec_per_chip", '
                        '"value": 100.0}\n')
        rc = bh.main(["--append", str(good), "--lane", "train",
                      "--platform", "tpu", "--gate", "--history", hist])
        assert rc == 0
        bad = tmp_path / "bad.txt"
        bad.write_text('{"metric": '
                       '"llama_train_tokens_per_sec_per_chip", '
                       '"value": 50.0}\n')
        rc = bh.main(["--append", str(bad), "--lane", "train",
                      "--platform", "tpu", "--gate", "--history", hist])
        assert rc == 1
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["violations"]
        # the regressing row is STILL in the ledger (trajectory vs
        # verdict)
        assert len(bh.load_history(hist)) == 2

    def test_import_bench_r_idempotent(self, tmp_path):
        bh = self._tool()
        hist = str(tmp_path / "h.jsonl")
        art = tmp_path / "BENCH_r01.json"
        art.write_text(json.dumps(
            {"n": 1, "cmd": "bench", "rc": 0,
             "tail": '{"metric": "llama_train_tokens_per_sec_per_chip",'
                     ' "value": 16668.3}'}))
        rows = bh.import_bench_r(str(tmp_path / "BENCH_r*.json"), hist)
        assert [r["run"] for r in rows] == ["bench_r01"]
        assert bh.import_bench_r(str(tmp_path / "BENCH_r*.json"),
                                 hist) == []
        assert len(bh.load_history(hist)) == 1

    def test_committed_ledger_seeded_from_rounds(self):
        rows = self._tool().load_history(os.path.join(
            REPO, "tools", "artifacts", "bench_history.jsonl"))
        runs = {r["run"] for r in rows}
        assert {"bench_r01", "bench_r05"} <= runs
        by_run = {r["run"]: r for r in rows}
        tok = "llama_train_tokens_per_sec_per_chip"
        assert by_run["bench_r05"]["metrics"][tok] > \
            by_run["bench_r01"]["metrics"][tok]


# -- tools/op_benchmark.py ----------------------------------------------------
class TestOpBenchmarkGate:
    def _tool(self):
        import importlib
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            return importlib.import_module("op_benchmark")
        finally:
            sys.path.pop(0)

    def test_check_is_pure_and_reads_both_forms(self):
        ob = self._tool()
        results = {"matmul": {"us": 100.0, "spread_frac": 0.1,
                              "repeats": 5},
                   "softmax": {"us": 10.0, "spread_frac": 0.0,
                               "repeats": 5}}
        # dict baseline
        fails, lines = ob.check(results, {"matmul": {"us": 100.0},
                                          "softmax": {"us": 10.0}},
                                tol=1.4)
        assert fails == [] and len(lines) == 2
        # legacy bare-float baseline still gates
        fails, _ = ob.check(results, {"matmul": 50.0}, tol=1.4)
        assert fails == [("matmul", 2.0)]
        # unknown/zero baselines are skipped, not crashed
        fails, _ = ob.check(results, {"other": 1.0, "softmax": 0.0},
                            tol=1.4)
        assert fails == []
