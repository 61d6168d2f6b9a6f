"""Observability subsystem: metrics registry, TrainStep step/recompile/MFU
telemetry, memory headroom guard, collective counters + chrome-trace spans,
autotune cache stats, hapi MetricsLogger, and the disabled-overhead gate.
"""
import json
import os
import re
import time

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.observability as obs


@pytest.fixture
def telemetry():
    obs.registry().reset()      # deterministic counts per test
    obs.enable()
    yield obs
    obs.disable()
    obs.set_jsonl_path(None)


def _tiny_step(in_dim=4, out_dim=3, lr=0.05):
    pt.seed(0)
    net = nn.Linear(in_dim, out_dim)
    opt = pt.optimizer.SGD(learning_rate=lr, parameters=net.parameters())
    return pt.jit.TrainStep(net, lambda o, l: ((o - l) ** 2).mean(), opt)


def _batch(bs, in_dim=4, out_dim=3, seed=0):
    rng = np.random.default_rng(seed)
    return (pt.to_tensor(rng.standard_normal((bs, in_dim), np.float32)),
            pt.to_tensor(rng.standard_normal((bs, out_dim), np.float32)))


# ---------------------------------------------------------------------------
# registry core
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("t_total", "help", ("op",))
        c.inc(op="a")
        c.inc(2.5, op="a")
        c.inc(op="b")
        assert c.value(op="a") == 3.5 and c.value(op="b") == 1.0
        with pytest.raises(ValueError):
            c.inc(-1, op="a")
        g = reg.gauge("t_gauge")
        g.set(4.0)
        g.inc()
        g.dec(2)
        assert g.value() == 3.0
        h = reg.histogram("t_seconds", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        assert h.value() == (3, 5.55)
        # same name returns the same object; kind mismatch raises
        assert reg.counter("t_total", labelnames=("op",)) is c
        with pytest.raises(TypeError):
            reg.gauge("t_total")

    def test_thread_safety(self):
        import threading
        reg = obs.MetricsRegistry()
        c = reg.counter("race_total")

        def work():
            for _ in range(1000):
                c.inc()

        ts = [threading.Thread(target=work) for _ in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert c.value() == 8000

    def test_scrape_is_valid_prometheus_text(self):
        reg = obs.MetricsRegistry()
        reg.counter("fam_total", "a counter", ("op",)).inc(op='x"y\\z')
        reg.gauge("fam_gauge", "a gauge").set(1.5)
        reg.histogram("fam_hist", "a histogram",
                      buckets=(0.5, 2)).observe(0.7)
        text = reg.scrape()
        _assert_prometheus_valid(text)
        assert 'fam_total{op="x\\"y\\\\z"} 1' in text
        assert "fam_hist_bucket" in text and 'le="+Inf"' in text

    def test_dump_histogram_shape(self):
        reg = obs.MetricsRegistry()
        reg.histogram("d_hist", buckets=(1.0,)).observe(0.5)
        d = reg.dump()["d_hist"]
        assert d["type"] == "histogram"
        assert d["values"][""]["count"] == 1
        assert d["values"][""]["buckets"]["1"] == 1


def _assert_prometheus_valid(text):
    """Minimal exposition-format 0.0.4 grammar check."""
    name = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
    label = r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*"'
    sample = re.compile(
        rf'^{name}(?:\{{{label}(?:,{label})*\}})?'
        r" (?:[+-]?(?:[0-9]*\.?[0-9]+(?:[eE][+-]?[0-9]+)?)|\+Inf|-Inf|NaN)"
        r"(?: [0-9]+)?$")
    meta = re.compile(rf"^# (?:HELP|TYPE) {name}(?: .*)?$")
    assert text.endswith("\n")
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            assert meta.match(line), f"bad metadata line: {line!r}"
        else:
            assert sample.match(line), f"bad sample line: {line!r}"


# ---------------------------------------------------------------------------
# TrainStep telemetry (acceptance: retrace -> counter + warning; scrape has
# step/memory/collective families)
# ---------------------------------------------------------------------------
class TestTrainStepTelemetry:
    def test_recompile_counter_and_warning(self, telemetry):
        step = _tiny_step()
        step(*_batch(4))
        step(*_batch(4, seed=1))          # same shapes: no retrace
        assert step.recompile_count == 0
        with pytest.warns(obs.RecompileWarning):
            step(*_batch(6))              # changed batch dim => retrace
        assert step.recompile_count == 1
        reg = obs.registry()
        assert reg.counter(
            "paddle_tpu_train_step_recompiles_total").value() == 1

    def test_step_metrics_and_mfu_gauges(self, telemetry, monkeypatch):
        # the CPU is in no peak table: give it a peak to read an MFU
        from paddle_tpu.observability import hardware
        monkeypatch.setitem(hardware.PEAK_FLOPS, "cpu", 1e12)
        step = _tiny_step()
        for _ in range(3):
            step(*_batch(8))
        reg = obs.registry()
        count, total = reg.histogram(
            "paddle_tpu_train_step_duration_seconds",
            labelnames=("phase",)).value(phase="execute")
        assert count == 3 and total > 0
        ccount, ctotal = reg.histogram(
            "paddle_tpu_train_step_compile_seconds").value()
        assert ccount >= 1 and ctotal > 0
        assert reg.counter(
            "paddle_tpu_train_step_tokens_total").value() == 24
        assert reg.gauge(
            "paddle_tpu_train_step_tokens_per_second").value() > 0
        # cost_analysis FLOPs are a count and stay (may be 0 on backends
        # that report no flops, but the gauge must exist); the MFU gauge
        # priced from them on the synced path is gone — a third count
        # that agreed with neither other one and that nothing read
        assert reg.get("paddle_tpu_train_step_flops_per_step") is not None
        assert reg.get("paddle_tpu_train_step_mfu_percent") is None

    def test_no_mfu_for_a_device_without_a_known_peak(self, telemetry,
                                                      tmp_path):
        """The CPU's peak is not known, so no MFU is reported for it —
        neither the gauge nor the step log's field — rather than one
        priced at some chip's peak."""
        import jax
        assert obs.peak_flops(jax.devices()[0]) is None
        path = str(tmp_path / "steps.jsonl")
        obs.set_jsonl_path(path)
        step = _tiny_step()
        step(*_batch(4))
        obs.set_jsonl_path(None)
        assert obs.registry().get(
            "paddle_tpu_train_step_mfu_percent") is None
        steps = [json.loads(l) for l in open(path)]
        steps = [r for r in steps if r.get("event") == "train_step"]
        assert steps and all("mfu_percent" not in r for r in steps)

    def test_telemetry_path_matches_disabled_path(self, telemetry):
        """Telemetry times the program the plain path runs: the same
        jitted callable with as many executables, so the same losses to
        the last bit; only the observed step has analysis records."""
        step_a = _tiny_step()
        losses_a = [float(step_a(*_batch(4, seed=s))) for s in range(3)]
        obs.disable()
        step_b = _tiny_step()
        losses_b = [float(step_b(*_batch(4, seed=s))) for s in range(3)]
        obs.enable()
        assert losses_a == losses_b
        assert step_a._jitted._cache_size() == step_b._jitted._cache_size()
        assert step_a.analysed_executables()
        assert step_b.analysed_executables() == {}

    def test_jsonl_step_log(self, telemetry, tmp_path):
        path = str(tmp_path / "steps.jsonl")
        obs.set_jsonl_path(path)
        step = _tiny_step()
        step(*_batch(4))
        step(*_batch(4))
        obs.set_jsonl_path(None)
        lines = [json.loads(l) for l in open(path)]
        # each step emits its wall record AND its attribution ledger;
        # each COMPILE additionally emits its HBM ledger (ISSUE 9)
        steps = [l for l in lines if l["event"] == "train_step"]
        attrs = [l for l in lines if l["event"] == "step_attribution"]
        mems = [l for l in lines if l["event"] == "memory_profile"]
        assert len(steps) == 2 and len(attrs) == 2
        assert mems and all(l["peak_bytes"] > 0 for l in mems)
        assert all("ts" in l for l in lines)
        assert all("wall_s" in l for l in steps + attrs)
        assert all(l["source"] == "train_step" for l in attrs + mems)

    def test_scrape_has_step_memory_collective_families(self, telemetry):
        from paddle_tpu.distributed import mesh as mesh_mod
        import paddle_tpu.distributed as dist
        step = _tiny_step()
        step(*_batch(4))
        old = mesh_mod.get_mesh()
        mesh_mod.set_mesh(mesh_mod.build_mesh(["world"], [8]))
        try:
            dist.all_reduce(pt.to_tensor(np.ones((8, 4), "float32")))
        finally:
            mesh_mod.set_mesh(old)
        text = obs.scrape()
        _assert_prometheus_valid(text)
        for family in ("paddle_tpu_train_step_duration_seconds",
                       "paddle_tpu_device_bytes_in_use",
                       "paddle_tpu_collective_calls_total"):
            assert f"\n# TYPE {family} " in "\n" + text, family


# ---------------------------------------------------------------------------
# disabled-overhead gate (tier-1): the telemetry hot path, when disabled,
# must add <3% to a small jitted train-step microbench
# ---------------------------------------------------------------------------
@pytest.mark.skipif(
    os.environ.get("PADDLE_TPU_TEST_SHARD") is not None,
    reason="serial-only: a <3% CPU-time A/B cannot gate under the "
           "sharded parallel suite's core contention — even "
           "process_time jitters when 8+ worker processes schedule "
           "against each other (documented parallel-shard-load "
           "artifact, PR 8 notes). The serial tier-1 command and the "
           "shuffled lane still run it.")
def test_disabled_telemetry_overhead_under_3pct():
    assert not obs.enabled()
    step = _tiny_step(in_dim=8, out_dim=8)
    x, y = _batch(8, in_dim=8, out_dim=8)
    for _ in range(5):                      # warm both executables
        step(x, y)

    def run(n=80):
        # process CPU time, not wall clock: the overhead under test is
        # pure single-threaded Python bookkeeping, and CPU time is
        # blind to OTHER processes' load — under the full parallel
        # suite this test used to fail on wall-clock scheduler noise
        # while passing solo (r8 tier-1 notes)
        t0 = time.process_time()
        for _ in range(n):
            loss = step(x, y)
        float(loss)                         # drain the dispatch queue
        return time.process_time() - t0

    # baseline strips the disabled-path bookkeeping from the SAME step
    # instance (shape-key build + retrace set lookup)
    def strip():
        step._shape_key = lambda *a, **k: ("stripped",)
        step._note_shape_key = lambda key: None

    def restore():
        for attr in ("_shape_key", "_note_shape_key"):
            step.__dict__.pop(attr, None)

    best_ratio = float("inf")
    for _attempt in range(3):
        # interleaved A/B; min-over-many filters scheduler/GC spikes
        # symmetrically from both arms, converging on the true floor
        instrumented, stripped = [], []
        for _ in range(12):
            restore()
            instrumented.append(run())
            strip()
            stripped.append(run())
        restore()
        ratio = min(instrumented) / min(stripped)
        best_ratio = min(best_ratio, ratio)
        if best_ratio < 1.03:
            break
    assert best_ratio < 1.03, (
        f"disabled telemetry adds {(best_ratio - 1) * 100:.1f}% "
        "to the train-step hot path (>3% budget)")


# ---------------------------------------------------------------------------
# memory headroom guard
# ---------------------------------------------------------------------------
class TestHeadroomGuard:
    def test_explicit_limit_and_callback(self, telemetry):
        from paddle_tpu.framework.memory import HeadroomGuard
        g = HeadroomGuard(limit_bytes=1000)
        fired = []
        g.on_violation(lambda nbytes, room: fired.append((nbytes, room)))
        assert g.check(10)                 # fits: no callback
        assert not fired
        assert not g.check(10**9)          # would exceed: fires BEFORE
        assert fired and fired[0][0] == 10**9
        assert g.violations == 1
        assert obs.registry().counter(
            "paddle_tpu_memory_headroom_violations_total").value() == 1

    def test_no_limit_is_permissive(self):
        from paddle_tpu.framework.memory import HeadroomGuard
        g = HeadroomGuard()                # CPU: no bytes_limit stat
        if g.limit_bytes() is None:
            assert g.check(10**15)
            assert g.headroom() is None

    def test_paged_admission_defers_under_pressure(self, telemetry):
        from paddle_tpu.framework.memory import HeadroomGuard
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.models.paged_decode import PagedDecoder
        pt.seed(5)
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=97, hidden_size=32, intermediate_size=64,
            num_hidden_layers=1, num_attention_heads=2,
            num_key_value_heads=2, max_position_embeddings=64,
            use_flash_attention=False))
        model.eval()
        guard = HeadroomGuard(limit_bytes=1)   # everything violates
        dec = PagedDecoder(model, max_len=32, block_size=16, max_slots=2,
                           num_blocks=5, headroom_guard=guard)
        rng = np.random.default_rng(3)
        reqs = [(i, [int(t) for t in rng.integers(0, 97, 5)])
                for i in range(3)]
        out = dec.serve(reqs, max_new_tokens=3, chunk=2)
        # progress is guaranteed (first admission bypasses the guard when
        # nothing is live), later admissions deferred + counted
        assert sorted(out) == [0, 1, 2]
        assert all(len(v) == 3 for v in out.values())
        assert dec.admission_deferrals > 0
        assert guard.violations > 0


# ---------------------------------------------------------------------------
# collective telemetry + watchdog-over-registry
# ---------------------------------------------------------------------------
class TestCollectiveTelemetry:
    def _with_world_mesh(self):
        from paddle_tpu.distributed import mesh as mesh_mod
        old = mesh_mod.get_mesh()
        mesh_mod.set_mesh(mesh_mod.build_mesh(["world"], [8]))
        return mesh_mod, old

    def test_eager_collective_counters(self, telemetry):
        import paddle_tpu.distributed as dist
        mesh_mod, old = self._with_world_mesh()
        reg = obs.registry()
        calls = reg.counter("paddle_tpu_collective_calls_total",
                            labelnames=("op",))
        before = calls.value(op="all_reduce")
        try:
            x = pt.to_tensor(np.ones((8, 16), "float32"))
            dist.all_reduce(x)
        finally:
            mesh_mod.set_mesh(old)
        assert calls.value(op="all_reduce") == before + 1
        moved = reg.counter("paddle_tpu_collective_bytes_total",
                            labelnames=("op",)).value(op="all_reduce")
        assert moved >= 8 * 16 * 4
        assert reg.counter("paddle_tpu_collective_seconds_total",
                           labelnames=("op",)).value(op="all_reduce") > 0
        assert reg.gauge(
            "paddle_tpu_collective_bus_bandwidth_bytes_per_second",
            labelnames=("op",)).value(op="all_reduce") > 0

    def test_chrome_trace_roundtrip_includes_collective_spans(
            self, telemetry, tmp_path):
        import paddle_tpu.distributed as dist
        import paddle_tpu.profiler as profiler
        mesh_mod, old = self._with_world_mesh()
        d = str(tmp_path / "traces")
        prof = profiler.Profiler(
            scheduler=(0, 100),
            on_trace_ready=profiler.export_chrome_tracing(d))
        prof._start_device_trace = lambda: None   # CPU test
        prof.start()
        try:
            with profiler.RecordEvent("step"):
                x = pt.to_tensor(np.ones((8, 4), "float32"))
                dist.all_reduce(x)
                dist.broadcast(x, src=0)
            prof.step()
        finally:
            mesh_mod.set_mesh(old)
            prof.stop()
        data = profiler.load_profiler_result(prof._last_export)
        names = [e["name"] for e in data["traceEvents"]]
        assert "step" in names
        assert "collective:all_reduce" in names
        assert "collective:broadcast" in names
        # chrome-trace invariants: complete events with numeric ts/dur
        for e in data["traceEvents"]:
            assert e["ph"] == "X"
            assert e["dur"] >= 0 and e["ts"] >= 0

    def test_watchdog_reads_registry_task_table(self):
        from paddle_tpu.distributed.comm_watchdog import CommTaskManager
        from paddle_tpu.observability import tasks
        mgr = CommTaskManager.instance()
        seq_before = tasks.seq()
        t = mgr.begin("probe_op")
        try:
            assert any(r.name == "probe_op" for r in tasks.in_flight())
            assert t.seq in mgr._tasks          # manager view IS the table
            assert mgr._seq == seq_before + 1
        finally:
            mgr.end(t)
        assert all(r.seq != t.seq for r in tasks.in_flight())

    def test_traced_collective_lowering_counter(self, telemetry):
        import jax
        import paddle_tpu.distributed as dist
        mesh_mod, old = self._with_world_mesh()
        reg = obs.registry()
        c = reg.counter("paddle_tpu_collective_traced_lowerings_total",
                        labelnames=("op",))
        before = c.value(op="all_reduce")
        try:
            from jax.sharding import PartitionSpec as P

            def body(x):
                return dist.all_reduce(pt.Tensor(x))._data

            f = jax.jit(jax.shard_map(
                body, mesh=mesh_mod.get_mesh(), in_specs=P("world"),
                out_specs=P("world"), check_vma=False))
            f(np.ones((8, 4), np.float32))
        finally:
            mesh_mod.set_mesh(old)
        assert c.value(op="all_reduce") == before + 1


# ---------------------------------------------------------------------------
# profiler: scheduler state transitions + SortedKeys parity (satellites)
# ---------------------------------------------------------------------------
class TestProfilerSatellites:
    def test_scheduler_full_transition_table(self):
        from paddle_tpu.profiler import make_scheduler, ProfilerState
        sch = make_scheduler(closed=2, ready=1, record=2, repeat=2,
                             skip_first=3)
        expect = {0: ProfilerState.CLOSED, 2: ProfilerState.CLOSED,
                  3: ProfilerState.CLOSED, 4: ProfilerState.CLOSED,
                  5: ProfilerState.READY, 6: ProfilerState.RECORD,
                  7: ProfilerState.RECORD_AND_RETURN,
                  8: ProfilerState.CLOSED, 10: ProfilerState.READY,
                  11: ProfilerState.RECORD,
                  12: ProfilerState.RECORD_AND_RETURN,
                  13: ProfilerState.CLOSED,    # repeat exhausted
                  99: ProfilerState.CLOSED}
        for step, state in expect.items():
            assert sch(step) == state, (step, sch(step), state)
        # repeat=0 cycles forever
        inf = make_scheduler(closed=0, ready=0, record=1, repeat=0)
        assert inf(10**6) == ProfilerState.RECORD_AND_RETURN

    def test_profiler_applies_scheduler_states(self, tmp_path):
        """closed=1 ready=1 record=1 over 4 steps: only step 2 (the
        RECORD_AND_RETURN step closing the single cycle) records, and the
        exported trace holds exactly that step's span."""
        from paddle_tpu.profiler import (Profiler, RecordEvent,
                                         make_scheduler,
                                         export_chrome_tracing,
                                         load_profiler_result)
        d = str(tmp_path / "sched")
        prof = Profiler(scheduler=make_scheduler(closed=1, ready=1,
                                                 record=1, repeat=1),
                        on_trace_ready=export_chrome_tracing(d))
        prof._start_device_trace = lambda: None
        prof.start()
        for i in range(4):
            with RecordEvent(f"tick{i}"):
                pass
            prof.step()
        prof.stop()
        files = [f for f in os.listdir(d) if f.endswith(".json")]
        assert len(files) == 1, files
        events = load_profiler_result(
            os.path.join(d, files[0]))["traceEvents"]
        assert [e["name"] for e in events] == ["tick2"]

    def test_sortedkeys_device_names_alias_gpu(self):
        from paddle_tpu.profiler import SortedKeys
        assert SortedKeys.DeviceTotal == SortedKeys.GPUTotal == 4
        assert SortedKeys.DeviceAvg == SortedKeys.GPUAvg == 5
        assert SortedKeys.DeviceMax == SortedKeys.GPUMax == 6
        assert SortedKeys.DeviceMin == SortedKeys.GPUMin == 7
        assert SortedKeys.CPUTotal == 0


# ---------------------------------------------------------------------------
# autotune cache counters + eviction (satellite)
# ---------------------------------------------------------------------------
class TestAutotuneTelemetry:
    def test_hit_miss_eviction_counters(self):
        from paddle_tpu.kernels.autotune import AutoTuneCache
        c = AutoTuneCache(capacity=2)
        assert c.get("k", (1,)) is None            # miss
        c.set("k", (1,), "a")
        c.set("k", (2,), "b")
        assert c.get("k", (1,)) == "a"             # hit, refreshes LRU
        c.set("k", (3,), "c")                      # evicts (2,)
        assert c.evictions == 1
        assert c.get("k", (2,)) is None            # miss (evicted)
        assert c.get("k", (1,)) == "a"             # survived (LRU)
        assert (c.hits, c.misses) == (2, 2)
        c.set_capacity(1)
        assert c.size() == 1 and c.evictions == 2

    def test_registry_exposes_autotune_stats(self, telemetry):
        from paddle_tpu.kernels.autotune import AutoTuneCache
        inst = AutoTuneCache.instance()
        inst.clear()
        inst.get("probe", (0,))                    # one miss
        inst.set("probe", (0,), "cfg")
        inst.get("probe", (0,))                    # one hit
        text = obs.scrape()
        assert "paddle_tpu_autotune_cache_hits_total 1" in text
        assert "paddle_tpu_autotune_cache_misses_total 1" in text
        assert "paddle_tpu_autotune_cache_evictions_total 0" in text
        assert "paddle_tpu_autotune_cache_size 1" in text
        inst.clear()


# ---------------------------------------------------------------------------
# hapi MetricsLogger callback
# ---------------------------------------------------------------------------
class TestMetricsLogger:
    def test_fit_pushes_registry_and_jsonl(self, telemetry, tmp_path):
        from paddle_tpu.hapi import MetricsLogger
        path = str(tmp_path / "hapi.jsonl")
        np.random.seed(0)
        X = np.random.randn(32, 4).astype(np.float32)
        Y = (X.sum(-1) > 0).astype(np.int64)[:, None]
        data = [(pt.to_tensor(X[i:i + 8]), pt.to_tensor(Y[i:i + 8]))
                for i in range(0, 32, 8)]
        net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        model = pt.Model(net)
        model.prepare(pt.optimizer.SGD(0.1, parameters=net.parameters()),
                      nn.CrossEntropyLoss())
        model.fit(data, epochs=2, verbose=0,
                  callbacks=[MetricsLogger(jsonl_path=path)])
        reg = obs.registry()
        assert reg.counter("paddle_tpu_hapi_steps_total",
                           labelnames=("stage",)).value(stage="train") == 8
        assert reg.gauge("paddle_tpu_hapi_loss",
                         labelnames=("stage",)).value(stage="train") != 0
        obs.set_jsonl_path(None)
        events = [json.loads(l)["event"] for l in open(path)]
        assert events.count("hapi_train_batch") == 8
        assert events.count("hapi_epoch") == 2

    def test_noop_when_disabled(self):
        from paddle_tpu.hapi import MetricsLogger
        assert not obs.enabled()
        cb = MetricsLogger()
        before = obs.registry().counter(
            "paddle_tpu_hapi_steps_total",
            labelnames=("stage",)).value(stage="train")
        cb.on_train_batch_end(0, {"loss": 1.0})
        after = obs.registry().counter(
            "paddle_tpu_hapi_steps_total",
            labelnames=("stage",)).value(stage="train")
        assert before == after
