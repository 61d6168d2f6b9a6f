"""Step-time attribution stack: span tracer round-trip + merged chrome
export, goodput-ledger invariants (sums-to-wall, exposed reconcile),
straggler MAD flags, flight-recorder schema + triggers, JSONL rotation,
the live scrape endpoint, and the disabled-path overhead gates.
"""
import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.observability as obs
from paddle_tpu.observability import (attribution, exporter,
                                      flight_recorder, tracing)


@pytest.fixture
def telemetry():
    obs.registry().reset()
    obs.enable()
    yield obs
    obs.disable()
    obs.set_jsonl_path(None)


@pytest.fixture
def traced():
    tracing.clear()
    tracing.enable_tracing()
    yield tracing
    tracing.disable_tracing()
    tracing.clear()


def _tiny_step(in_dim=4, out_dim=3):
    pt.seed(0)
    net = nn.Linear(in_dim, out_dim)
    opt = pt.optimizer.SGD(learning_rate=0.05,
                           parameters=net.parameters())
    return pt.jit.TrainStep(net, lambda o, l: ((o - l) ** 2).mean(), opt)


def _batch(bs, in_dim=4, out_dim=3, seed=0):
    rng = np.random.default_rng(seed)
    return (pt.to_tensor(rng.standard_normal((bs, in_dim), np.float32)),
            pt.to_tensor(rng.standard_normal((bs, out_dim), np.float32)))


# ---------------------------------------------------------------------------
# tracer: ring round-trip, capacity, chrome export + multi-rank merge
# ---------------------------------------------------------------------------
class TestTracer:
    def test_span_roundtrip_records_rank_tid_meta(self, traced):
        with tracing.span("outer", phase="x"):
            with tracing.span("inner"):
                pass
        spans = tracing.tail()
        names = [s["name"] for s in spans]
        assert names == ["inner", "outer"]      # completion order
        for s in spans:
            assert s["dur_ns"] >= 0 and s["t0_ns"] > 0
            assert s["rank"] == 0 and s["tid"] > 0
        assert spans[1]["meta"] == {"phase": "x"}
        # drain empties the ring
        assert len(tracing.drain()) == 2
        assert tracing.tail() == []

    def test_ring_capacity_drops_oldest(self):
        tracing.enable_tracing(capacity=4)
        try:
            for i in range(10):
                with tracing.span(f"s{i}"):
                    pass
            names = [s["name"] for s in tracing.tail()]
            assert names == ["s6", "s7", "s8", "s9"]
        finally:
            tracing.disable_tracing()
            tracing.clear()

    def test_disabled_span_is_shared_null(self):
        assert not tracing.tracing_enabled()
        # the ring also keeps what a profiler session recorded (a
        # Profiler of an earlier test on this worker): start empty
        tracing.clear()
        assert tracing.span("x") is tracing._NULL
        with tracing.span("x"):
            pass
        assert tracing.tail() == []

    def test_chrome_export_and_multirank_merge(self, traced, tmp_path):
        with tracing.span("work", bucket=3):
            pass
        d = str(tmp_path)
        part = tracing.write_rank_part(d)
        assert os.path.basename(part) == "trace.rank00000.json"
        # synthesize a second rank's part (what rank 1 would write)
        events = tracing.chrome_events(pid=99999, rank=1)
        with open(os.path.join(d, "trace.rank00001.json"), "w") as f:
            json.dump({"traceEvents": events}, f)
        merged = tracing.merge_rank_parts(d)
        doc = json.load(open(merged))
        evs = doc["traceEvents"]
        pids = {e["pid"] for e in evs if e["ph"] == "X"}
        assert len(pids) == 2                   # both ranks survived
        meta_names = {e["args"]["name"] for e in evs
                      if e["ph"] == "M" and e["name"] == "process_name"}
        assert any(n.startswith("rank 0") for n in meta_names)
        assert any(n.startswith("rank 1") for n in meta_names)
        for e in evs:
            if e["ph"] == "X":
                assert e["ts"] >= 0 and e["dur"] >= 0
                assert e["args"]["rank"] in (0, 1)

    def test_merge_without_parts_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            tracing.merge_rank_parts(str(tmp_path))

    def test_span_feeds_recording_profiler(self, tmp_path):
        """The bridge that subsumes the old RecordEvent call sites: a
        tracing.span lands in a recording Profiler's chrome export even
        with the tracer ring disabled."""
        import paddle_tpu.profiler as profiler
        assert not tracing.tracing_enabled()
        prof = profiler.Profiler(
            scheduler=(0, 100),
            on_trace_ready=profiler.export_chrome_tracing(
                str(tmp_path / "tr")))
        prof._start_device_trace = lambda: None
        prof.start()
        with tracing.span("bridged"):
            pass
        prof.step()
        prof.stop()
        data = profiler.load_profiler_result(prof._last_export)
        assert "bridged" in [e["name"] for e in data["traceEvents"]]

    def test_record_event_feeds_tracer_ring(self, traced):
        """...and the reverse bridge: legacy RecordEvent spans land in
        the tracer ring for merged multi-process traces."""
        from paddle_tpu.profiler import RecordEvent
        with RecordEvent("legacy"):
            pass
        assert "legacy" in [s["name"] for s in tracing.tail()]

    def test_disabled_span_overhead(self):
        """The near-zero-when-disabled contract, with the process_time
        pattern (blind to other-process load): a disabled span() call
        must stay in the sub-10us class."""
        assert not tracing.tracing_enabled()
        n = 50_000
        best = float("inf")
        for _ in range(3):
            t0 = time.process_time()
            for _ in range(n):
                with tracing.span("hot"):
                    pass
            best = min(best, (time.process_time() - t0) / n)
            if best < 10e-6:
                break
        assert best < 10e-6, f"disabled span costs {best * 1e6:.2f}us"


# ---------------------------------------------------------------------------
# attribution: ledger math, TrainStep/serve integration, the report tool
# ---------------------------------------------------------------------------
class TestLedger:
    def test_buckets_sum_to_wall_exactly(self):
        led = attribution.StepLedger("t")
        r1 = led.step(10.0, 11.0, compile_s=0.4, execute_s=0.5,
                      modeled_exposed_s=0.1)
        a = r1["attribution"]
        assert r1["wall_s"] == 1.0
        assert a["compile"] == 0.4
        assert a["grad_sync_exposed"] == 0.1   # carved out of execute
        assert a["execute"] == pytest.approx(0.4)
        assert a["dispatch"] == pytest.approx(0.1)
        assert sum(a.values()) == pytest.approx(r1["wall_s"], abs=1e-9)
        # second step: the inter-call gap becomes data_wait
        r2 = led.step(11.5, 12.0, execute_s=0.45)
        a2 = r2["attribution"]
        assert a2["data_wait"] == pytest.approx(0.5)
        assert sum(a2.values()) == pytest.approx(r2["wall_s"], abs=1e-9)
        s = led.summary()
        assert s["steps"] == 2
        assert s["wall_s"] == pytest.approx(2.0)

    def test_checkpoint_external_note_drains_into_gap(self, telemetry):
        led = attribution.StepLedger("t")
        led.step(0.0, 1.0)
        attribution.note_external("checkpoint", 0.2)
        r = led.step(1.5, 2.0)
        a = r["attribution"]
        assert a["checkpoint"] == pytest.approx(0.2)
        assert a["data_wait"] == pytest.approx(0.3)
        # drained: the next step doesn't re-bill it
        r3 = led.step(2.1, 2.2)
        assert r3["attribution"]["checkpoint"] == 0.0

    def test_checkpoint_carries_forward_beyond_gap(self, telemetry):
        """A 5 s save against a 0.5 s gap bills 0.5 now and pools the
        rest for later steps — never silently discarded."""
        attribution.drain_external()          # clear pooled leftovers
        led = attribution.StepLedger("t")
        led.step(0.0, 1.0)
        attribution.note_external("checkpoint", 5.0)
        r = led.step(1.5, 2.0)                # gap 0.5
        assert r["attribution"]["checkpoint"] == pytest.approx(0.5)
        r2 = led.step(2.3, 2.4)               # gap 0.3
        assert r2["attribution"]["checkpoint"] == pytest.approx(0.3)
        left = attribution.drain_external()["checkpoint"]
        assert left == pytest.approx(4.2)

    def test_exposed_clamped_to_execute(self):
        led = attribution.StepLedger("t")
        r = led.step(0.0, 1.0, execute_s=0.3, modeled_exposed_s=9.0)
        a = r["attribution"]
        assert a["grad_sync_exposed"] == pytest.approx(0.3)
        assert a["execute"] == 0.0
        assert sum(a.values()) == pytest.approx(1.0)

    def test_measured_phases_clamped_to_call_wall(self):
        # clock skew: compile+execute report longer than the call wall
        led = attribution.StepLedger("t")
        r = led.step(0.0, 1.0, compile_s=2.0, execute_s=2.0)
        a = r["attribution"]
        assert sum(a.values()) == pytest.approx(1.0)
        assert a["dispatch"] == pytest.approx(0.0)

    def test_note_external_validates_bucket(self, telemetry):
        with pytest.raises(ValueError):
            attribution.note_external("execute", 1.0)

    def test_modeled_exposed_shared_hlo_model(self):
        """The reconcile contract: exposure is priced by the SAME
        hlo_analysis report overlap_evidence gates on — a tail
        collective with no matmul behind it prices > 0, one with a dot
        scheduled after it prices 0."""
        tail = """HloModule m

ENTRY %main (p: f32[4096]) -> f32[4096] {
  %p = f32[4096] parameter(0)
  %ar = f32[4096] all-reduce(f32[4096] %p), replica_groups={{0,1,2,3}}
  ROOT %r = f32[4096] add(f32[4096] %ar, f32[4096] %ar)
}
"""
        assert attribution.modeled_exposed_seconds(tail) > 0
        hidden = tail.replace("add(", "dot(")
        assert attribution.modeled_exposed_seconds(hidden) == 0.0

    def test_train_step_emits_ledger(self, telemetry, tmp_path):
        path = str(tmp_path / "steps.jsonl")
        obs.set_jsonl_path(path)
        step = _tiny_step()
        for s in range(3):
            step(*_batch(4, seed=s))
        obs.set_jsonl_path(None)
        recs = [json.loads(l) for l in open(path)]
        attrs = [r for r in recs if r["event"] == "step_attribution"]
        assert len(attrs) == 3
        for r in attrs:
            a = r["attribution"]
            assert set(a) == set(attribution.BUCKETS)
            assert sum(a.values()) == pytest.approx(
                r["wall_s"], rel=0.02, abs=1e-6)
        assert attrs[0]["attribution"]["compile"] > 0
        assert all(r["attribution"]["execute"] > 0 for r in attrs)
        # the registry families aggregated the same steps
        reg = obs.registry()
        assert reg.counter("paddle_tpu_step_attribution_steps_total",
                           labelnames=("source",)).value(
                               source="train_step") == 3
        summ = step.attribution_summary()
        assert summ["steps"] == 3 and summ["wall_s"] > 0

    def test_serve_emits_ledger(self, telemetry, tmp_path):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.models.paged_decode import PagedDecoder
        pt.seed(5)
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=97, hidden_size=32, intermediate_size=64,
            num_hidden_layers=1, num_attention_heads=2,
            num_key_value_heads=2, max_position_embeddings=64,
            use_flash_attention=False))
        model.eval()
        path = str(tmp_path / "serve.jsonl")
        obs.set_jsonl_path(path)
        dec = PagedDecoder(model, max_len=32, block_size=16,
                           max_slots=2, num_blocks=9)
        rng = np.random.default_rng(3)
        out = dec.serve([(i, [int(t) for t in rng.integers(0, 97, 5)])
                         for i in range(3)], max_new_tokens=3, chunk=2)
        obs.set_jsonl_path(None)
        assert sorted(out) == [0, 1, 2]
        attrs = [json.loads(l) for l in open(path)]
        attrs = [r for r in attrs if r.get("event") == "step_attribution"
                 and r.get("source") == "serve"]
        assert attrs, "serve() emitted no ledger records"
        for r in attrs:
            a = r["attribution"]
            assert sum(a.values()) == pytest.approx(
                r["wall_s"], rel=0.02, abs=1e-6)
        # prefill-executable builds were classified as compile
        assert any(r["attribution"]["compile"] > 0 for r in attrs)
        assert all(r["attribution"]["execute"] > 0 for r in attrs)

    def test_report_tool_gates(self, telemetry, tmp_path):
        """tools/step_attribution.py: pass on an honest ledger, fail on
        a drifting one."""
        path = str(tmp_path / "ok.jsonl")
        obs.set_jsonl_path(path)
        led = attribution.StepLedger("train_step")
        led.step(0.0, 1.0, compile_s=0.5, execute_s=0.3)
        led.step(1.2, 2.0, execute_s=0.6)
        obs.set_jsonl_path(None)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(
            pt.__file__)))
        r = subprocess.run(
            [sys.executable, "tools/step_attribution.py",
             "--jsonl", path], capture_output=True, text=True,
            cwd=repo, timeout=120)
        row = json.loads(r.stdout.strip().splitlines()[-1])
        assert r.returncode == 0 and row["pass"], row
        src = row["sources"]["train_step"]
        assert src["steps"] == 2
        assert src["max_sum_err_frac"] <= 0.02
        # corrupt: a record whose buckets sum to half its wall
        bad = dict(json.loads(open(path).readline()))
        bad["wall_s"] = 123.0
        with open(str(tmp_path / "bad.jsonl"), "w") as f:
            f.write(json.dumps(bad) + "\n")
        r2 = subprocess.run(
            [sys.executable, "tools/step_attribution.py",
             "--jsonl", str(tmp_path / "bad.jsonl")],
            capture_output=True, text=True, cwd=repo, timeout=120)
        row2 = json.loads(r2.stdout.strip().splitlines()[-1])
        assert r2.returncode == 1 and not row2["pass"]
        assert row2["violations"][0]["kind"] == "sum_ne_wall"


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------
class TestStraggler:
    def test_mad_flags_50ms_outlier(self):
        digests = [{"rank": r, "wall_s": 0.010 + r * 1e-4}
                   for r in range(3)] + [{"rank": 3, "wall_s": 0.060}]
        rep = attribution.flag_stragglers(digests)
        assert rep["flagged"] == [3]
        assert rep["threshold_s"] < 0.05

    def test_uniform_mesh_flags_nothing(self):
        digests = [{"rank": r, "wall_s": 0.010 + r * 2e-4}
                   for r in range(8)]
        rep = attribution.flag_stragglers(digests)
        assert rep["flagged"] == []

    def test_floor_suppresses_noise_when_mad_zero(self):
        # MAD == 0 (identical walls) + one rank 1ms slower: under the
        # 4 * 2ms floor, not a straggler
        digests = [{"rank": r, "wall_s": 0.010} for r in range(3)]
        digests.append({"rank": 3, "wall_s": 0.011})
        rep = attribution.flag_stragglers(digests)
        assert rep["flagged"] == []

    def test_one_sided_fast_rank_not_flagged(self):
        digests = [{"rank": r, "wall_s": 0.010} for r in range(3)]
        digests.append({"rank": 3, "wall_s": 0.0001})   # fast, not slow
        rep = attribution.flag_stragglers(digests)
        assert rep["flagged"] == []

    def test_publish_single_controller_roundtrip(self, telemetry):
        """Single-process publish: every 'rank' shares the digest, so no
        flags — and the report lands on rank 0 with the JSONL event."""
        rep = attribution.publish_step_digest(
            attribution.step_digest(0, 0.01))
        assert rep is not None and rep["flagged"] == []
        assert attribution.last_straggler_report() is rep

    def test_tasks_per_rank_view(self):
        from paddle_tpu.observability import tasks
        rec = tasks.begin("probe")
        try:
            tasks.publish_remote(2, [{"name": "all_reduce",
                                      "age_s": 1.5}])
            view = tasks.per_rank_view()
            assert any(e["name"] == "probe" for e in view[0])
            assert view[2][0]["name"] == "all_reduce"
        finally:
            tasks.end(rec)
            tasks.publish_remote(2, [])


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_trip_writes_schema_valid_artifact(self, telemetry, traced,
                                               tmp_path):
        with tracing.span("pre-crash"):
            pass
        path = flight_recorder.arm(str(tmp_path / "fr.json"),
                                   install_signals=False)
        try:
            obs.registry().counter("fr_probe_total").inc(5)
            got = flight_recorder.trip("watchdog_stuck:probe",
                                       {"api_token": "x" * 64,
                                        "note": "fine"})
            assert got == path
            assert flight_recorder.validate(path) == []
            doc = json.load(open(path))
            assert doc["reason"] == "watchdog_stuck:probe"
            assert doc["counter_deltas"].get("fr_probe_total") == 5.0
            assert any(s["name"] == "pre-crash" for s in doc["spans"])
            # redaction: secret-shaped material never reaches disk
            assert doc["extra"]["api_token"] == "[REDACTED]"
            assert doc["extra"]["note"] == "fine"
        finally:
            flight_recorder.disarm()

    def test_trip_once_throttles_per_reason(self, tmp_path):
        flight_recorder.arm(str(tmp_path / "fr.json"),
                            install_signals=False)
        try:
            assert flight_recorder.trip_once("headroom_violation")
            assert flight_recorder.trip_once("headroom_violation") is None
            assert flight_recorder.trip_once("other_reason")
        finally:
            flight_recorder.disarm()

    def test_not_armed_is_noop(self):
        assert not flight_recorder.armed()
        assert flight_recorder.trip("x") is None

    def test_validate_rejects_malformed(self, tmp_path):
        assert flight_recorder.validate({"schema": "bogus"})
        p = str(tmp_path / "junk.json")
        open(p, "w").write("not json")
        assert flight_recorder.validate(p)

    def test_watchdog_stuck_trips_recorder(self, telemetry, tmp_path):
        """Simulated watchdog fire: a task outliving the timeout trips
        the black box with the stuck task named."""
        from paddle_tpu.distributed.comm_watchdog import CommTaskManager
        from paddle_tpu.framework.flags import set_flags, flag
        old_timeout = flag("comm_watchdog_timeout_s")
        path = flight_recorder.arm(str(tmp_path / "wd.json"),
                                   install_signals=False)
        mgr = CommTaskManager.instance()
        set_flags({"comm_watchdog_timeout_s": 0.05})
        t = mgr.begin("stuck_collective")
        try:
            mgr.start(interval=0.05)
            deadline = time.time() + 10
            while not os.path.exists(path) and time.time() < deadline:
                time.sleep(0.05)
        finally:
            mgr.end(t)
            mgr.stop()
            mgr._stuck.clear()
            set_flags({"comm_watchdog_timeout_s": old_timeout})
            flight_recorder.disarm()
        assert flight_recorder.validate(path) == []
        doc = json.load(open(path))
        assert doc["reason"] == "watchdog_stuck:stuck_collective"
        assert doc["extra"]["task"]["name"] == "stuck_collective"

    def test_headroom_violation_trips_recorder(self, telemetry,
                                               tmp_path):
        from paddle_tpu.framework.memory import HeadroomGuard
        path = flight_recorder.arm(str(tmp_path / "hg.json"),
                                   install_signals=False)
        try:
            g = HeadroomGuard(limit_bytes=1000)
            assert not g.check(10 ** 9)
        finally:
            flight_recorder.disarm()
        assert flight_recorder.validate(path) == []
        doc = json.load(open(path))
        assert doc["reason"] == "headroom_violation"
        assert doc["extra"]["requested_bytes"] == 10 ** 9


# ---------------------------------------------------------------------------
# JSONL sink hardening
# ---------------------------------------------------------------------------
class TestJsonlSink:
    def test_size_rotation_keeps_tail(self, telemetry, tmp_path):
        path = str(tmp_path / "rot.jsonl")
        obs.set_jsonl_path(path, max_bytes=400)
        for i in range(30):
            obs.log_step({"event": "tick", "i": i,
                          "pad": "x" * 40})
        obs.set_jsonl_path(None)
        assert os.path.exists(path + ".1"), "no rotation happened"
        rows = [json.loads(l) for l in open(path + ".1")] + \
               [json.loads(l) for l in open(path)]
        # the newest record always survives rotation
        assert rows[-1]["i"] == 29
        assert all(r["event"] == "tick" for r in rows)

    def test_flush_jsonl_safe_without_sink(self):
        obs.flush_jsonl()          # no sink: must not raise


# ---------------------------------------------------------------------------
# live scrape endpoint
# ---------------------------------------------------------------------------
class TestExporter:
    def test_metrics_endpoint_serves_scrape(self, telemetry):
        obs.registry().counter("exp_probe_total").inc(7)
        port = exporter.start_http_server(port=0, host="127.0.0.1")
        try:
            assert exporter.server_port() == port
            # idempotent: a second start returns the same port
            assert exporter.start_http_server(port=0) == port
            txt = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) \
                .read().decode()
            assert "exp_probe_total 7" in txt
            assert "# TYPE exp_probe_total counter" in txt
            hz = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10).read())
            assert hz["ok"] and hz["telemetry"]
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/nope", timeout=10)
        finally:
            exporter.stop_http_server()
        assert exporter.server_port() is None

    def test_flag_port_zero_means_disabled(self, telemetry):
        # default FLAGS_telemetry_port=0: enable() starts no server
        assert exporter.server_port() is None
