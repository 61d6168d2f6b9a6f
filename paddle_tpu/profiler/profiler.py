"""Profiler implementation (see package docstring for reference map)."""
from __future__ import annotations

import enum
import json
import os
import time
from collections import defaultdict

__all__ = ["Profiler", "RecordEvent", "ProfilerState", "ProfilerTarget",
           "make_scheduler", "export_chrome_tracing",
           "load_profiler_result", "SummaryView"]


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class SummaryView(enum.Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6


# Host spans have ONE store: the observability tracer's ring. A
# recording Profiler arms it, RecordEvent lands there through
# record_span, and exports read it back with tail().
from ..observability import tracing as _obs_tracing


def _native():
    from ..framework import native_runtime
    return native_runtime.lib()


def _native_events():
    """The native tracer's events as (name, t0, t1, tid)."""
    events = []
    lib = _native()
    if lib is not None and lib.pht_event_count() > 0:
        import tempfile
        with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
            path = f.name
        try:
            if lib.pht_dump(path.encode()) == 0:
                with open(path) as f:
                    for ev in json.load(f).get("traceEvents", []):
                        t0 = ev["ts"] * 1e3
                        events.append((ev["name"], t0,
                                       t0 + ev["dur"] * 1e3, ev["tid"]))
        finally:
            os.unlink(path)
    return events


class RecordEvent:
    """Host span scope (reference: paddle.profiler.RecordEvent /
    phi::RecordEvent). Usable as context manager or begin()/end()."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._t0 = None

    def begin(self):
        lib = _native()
        if lib is not None and lib.pht_enabled():
            # native tracer scope (csrc/runtime.cc HostTracer): records
            # without touching Python-level locks
            lib.pht_begin(self.name.encode())
            self._t0 = -1
            return
        self._t0 = time.perf_counter_ns()

    def end(self):
        if self._t0 == -1:
            lib = _native()
            if lib is not None:
                lib.pht_end()
            self._t0 = None
            return
        if self._t0 is not None:
            _obs_tracing.record_span(self.name, self._t0,
                                     time.perf_counter_ns())
            self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Reference: paddle.profiler.make_scheduler — maps a step index to a
    ProfilerState with cycle [closed, ready, record]."""
    cycle = closed + ready + record

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name, worker_name=None):
    """Returns an on_trace_ready callback writing chrome-trace JSON
    (reference: chrometracing_logger.cc output format)."""
    os.makedirs(dir_name, exist_ok=True)

    def handler(prof):
        name = worker_name or f"worker_{os.getpid()}"
        path = os.path.join(dir_name,
                            f"{name}_step{prof._step}_{int(time.time())}.json")
        prof._export_chrome(path)
        return path

    return handler


def load_profiler_result(path):
    with open(path) as f:
        return json.load(f)


class Profiler:
    """Reference Profiler contract: targets, optional (start, end) batch
    range or scheduler, on_trace_ready; start/stop/step; summary()."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None, with_flops=False):
        if isinstance(scheduler, (tuple, list)) and len(scheduler) == 2:
            lo, hi = scheduler
            self._scheduler = lambda s: (
                ProfilerState.RECORD if lo <= s < hi else ProfilerState.CLOSED)
        else:
            self._scheduler = scheduler or (lambda s: ProfilerState.RECORD)
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._device_trace_dir = None
        self._device_tracing = False
        self._last_export = None
        # the ring's spans with a larger id are this cycle's; whether
        # this Profiler armed the ring (and so has to disarm it)
        self._mark = 0
        self._armed_ring = False

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        self._mark = _obs_tracing.new_span_id()
        lib = _native()
        if lib is not None:
            lib.pht_clear()
        self._state = self._scheduler(self._step)
        self._apply_state()

    def stop(self):
        if self._device_tracing:
            self._stop_device_trace()
        self._arm_ring(False)
        lib = _native()
        if lib is not None:
            lib.pht_enable(0)
        # export whatever the final (possibly partial) cycle recorded
        if self._on_trace_ready is not None and self._events():
            self._last_export = self._on_trace_ready(self)
        self._state = ProfilerState.CLOSED

    def step(self, num_samples=None):
        # a RECORD_AND_RETURN step closes a scheduler cycle: export that
        # cycle's events and reset the buffer so cycles don't bleed into
        # each other (reference contract: one trace per repeat cycle)
        if self._state is ProfilerState.RECORD_AND_RETURN:
            if self._on_trace_ready is not None and self._events():
                self._last_export = self._on_trace_ready(self)
            self._mark = _obs_tracing.new_span_id()
            lib = _native()
            if lib is not None:
                lib.pht_clear()
        prev = self._state
        self._step += 1
        self._state = self._scheduler(self._step)
        if prev != self._state:
            self._apply_state()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    def _apply_state(self):
        recording = self._state in (ProfilerState.RECORD,
                                    ProfilerState.RECORD_AND_RETURN)
        host_spans = recording and not self._timer_only
        self._arm_ring(host_spans)
        lib = _native()
        if lib is not None:
            lib.pht_enable(1 if host_spans else 0)
        if recording and not self._timer_only and not self._device_tracing:
            self._start_device_trace()
        elif not recording and self._device_tracing:
            self._stop_device_trace()

    def _arm_ring(self, on):
        """Arm the tracer's ring for a RECORD state and disarm it after,
        unless someone else had it armed already."""
        if on and not _obs_tracing.tracing_enabled():
            _obs_tracing.enable_tracing()
            self._armed_ring = True
        elif not on and self._armed_ring:
            _obs_tracing.disable_tracing()
            self._armed_ring = False

    def _events(self):
        """This cycle's host spans as (name, t0, t1, tid): the ring's
        since the cycle's watermark, plus the native tracer's."""
        return [(s["name"], s["t0_ns"], s["t0_ns"] + s["dur_ns"], s["tid"])
                for s in _obs_tracing.tail()
                if s["id"] > self._mark] + _native_events()

    def _start_device_trace(self):
        try:
            import jax
            self._device_trace_dir = os.environ.get(
                "PADDLE_TPU_TRACE_DIR", "/tmp/paddle_tpu_trace")
            jax.profiler.start_trace(self._device_trace_dir)
            self._device_tracing = True
        except Exception:
            self._device_tracing = False

    def _stop_device_trace(self):
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:
            pass
        self._device_tracing = False

    # -- output ------------------------------------------------------------
    def _export_chrome(self, path):
        events = []
        for name, t0, t1, tid in self._events():
            events.append({
                "name": name, "ph": "X", "cat": "host",
                "ts": t0 / 1e3, "dur": (t1 - t0) / 1e3,
                "pid": os.getpid(), "tid": tid,
            })
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "devicePlane": self._device_trace_dir}, f)
        return path

    def export(self, path, format="json"):
        return self._export_chrome(path)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Aggregated host-span table (profiler_statistic.py role)."""
        agg = defaultdict(lambda: [0, 0.0, 0.0])  # count, total, max
        for name, t0, t1, tid in self._events():
            d = (t1 - t0) / 1e6  # ms
            a = agg[name]
            a[0] += 1
            a[1] += d
            a[2] = max(a[2], d)
        total = sum(a[1] for a in agg.values()) or 1.0
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>12}"
                 f"{'Max(ms)':>12}{'Ratio':>8}"]
        lines.append("-" * 92)
        for name, (cnt, tot, mx) in sorted(agg.items(),
                                           key=lambda kv: -kv[1][1]):
            lines.append(f"{name[:39]:<40}{cnt:>8}{tot:>12.3f}"
                         f"{tot / cnt:>12.3f}{mx:>12.3f}"
                         f"{tot / total:>7.1%}")
        table = "\n".join(lines)
        print(table)
        return table
