"""Span tracer: near-zero-overhead-when-nothing-records, rank-tagged,
ring-buffered, and on the device trace's clock when a profiler runs.

The third observability layer (metrics -> **traces** -> attribution).
PR 1's registry answers *how much*; this answers *where the time went*.
The instrumented regions are the serve loop's phases (`serve:iteration`,
`:feed`, `:admit`, `:reserve`, `:prefill_inputs`, `:prefill`,
`:wait_first_token`, `:chunk`, `:wait_chunk`, `:commit`, ... and
`serve:starved`, which lies across them: `open_span`), the train step
(`train_step:call`, with `:execute` and `:analyse` under telemetry;
`serve:analyse`), eager collectives (`collective:<op>`), backend
compiles (`xla:compile`, from a `jax.monitoring` listener) and the
per-request tracks the `RequestLedger` writes (`req:queue`,
`req:prefill`, `req:decode`).

Design contract:

- **When a span records**: the ring is armed (`enable_tracing()` /
  FLAGS_enable_tracing) **or a JAX profiler session is recording**
  (`jax.profiler.start_trace` .. `stop_trace`, answered by
  `TraceAnnotation.is_enabled()`). `recording()` is that question.
- **Nothing records (default)**: `span()` is one bool read, one
  `is_enabled()` and a shared null context — no allocation, no lock, no
  clock. Gated by the per-call-overhead test in
  tests/test_tracing_attribution.py.
- **Recording**: a span (a) opens a `jax.profiler.TraceAnnotation` for
  its duration while a profiler session records, so it lies in the
  `.xplane.pb`'s `/host:CPU` plane on the device trace's own clock,
  metadata as event stats; (b) lands as `(id, parent, name, t0, t1, tid,
  rank, meta)` in a `deque(maxlen=capacity)` under one lock on
  `perf_counter_ns` — the oldest spans fall off, the ring IS the flight
  recorder's black-box window and what a metric reader in the same
  process reads after a traced window. `id` is a process-wide counter;
  `parent` is the id of the span open on the same thread when this one
  opened, None at the top.
- **One store**: the legacy `profiler.Profiler` arms and disarms this
  ring with its RECORD state and exports from `tail()`;
  `profiler.RecordEvent` lands here through `record_span`.

Multi-process export: perf-counter timestamps are rebased onto the unix
epoch at enable time, so per-rank part files written by
`write_rank_part(dir)` line up when `merge_rank_parts(dir)` folds them
into ONE chrome-trace JSON — each rank keeps its own pid lane, named by
`process_name`/`process_sort_index` metadata events (open the merged
file directly in Perfetto / chrome://tracing).
"""
from __future__ import annotations

import glob
import itertools
import json
import os
import threading
import time
from collections import deque

import jax
from jax.profiler import TraceAnnotation as _Annotation

from ..framework.flags import define_flag, flag

__all__ = [
    "span", "record_span", "recording", "tracing_enabled", "enable_tracing",
    "disable_tracing", "drain", "clear", "tail", "chrome_events",
    "export_chrome", "write_rank_part", "merge_rank_parts", "trace_rank",
    "set_track_name", "new_span_id", "compile_seconds", "open_span",
]

define_flag("enable_tracing", False,
            "Record instrumented spans into the observability trace ring "
            "(near-zero overhead when off).")
define_flag("trace_ring_capacity", 65536,
            "Max spans held in the trace ring buffer (oldest dropped).")

# RLock: the flight recorder's SIGTERM handler reads the ring (tail())
# on the main thread, which may be mid-append when the signal lands —
# a plain Lock would deadlock the handler against its own thread
_LOCK = threading.RLock()
_ACTIVE = [False]
_RING = deque(maxlen=65536)
# perf_counter_ns -> unix-epoch ns rebase, fixed at enable time so spans
# from different processes share a clock base in merged traces
_EPOCH_OFFSET_NS = [0]
_RANK = [None]
# is a JAX profiler session recording? One atomic read (TraceMe's
# recorder level), False before start_trace and after stop_trace
_profiling = _Annotation.is_enabled
# process-wide span ids (next() on a count is atomic under the GIL) and
# the per-thread stack of open span ids a new span takes its parent from
_IDS = itertools.count(1)
_OPEN = threading.local()


def trace_rank() -> int:
    """This process's rank tag. jax.process_index() once the distributed
    runtime is up; the launcher's env contract before that; 0 solo.
    The runtime check reads the coordination-service client handle, NOT
    jax.process_index() — the latter answers 0 (and force-initializes
    the backend) before jax.distributed.initialize, which would both
    mis-tag every pre-init span/artifact as rank 0 and break the
    upcoming distributed init."""
    if _RANK[0] is None:
        r = None
        try:
            from jax._src import distributed as _jax_dist
            if _jax_dist.global_state.client is not None:
                r = int(jax.process_index())
        except Exception:
            pass
        if r is None:
            try:
                r = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
            except ValueError:
                r = 0
        _RANK[0] = r
    return _RANK[0]


def tracing_enabled() -> bool:
    """Is the ring armed (`enable_tracing()`)? See `recording()` for
    "would a span record now"."""
    return _ACTIVE[0]


def recording() -> bool:
    """Would a span opened now be recorded: the ring is armed or a JAX
    profiler session is recording."""
    return _ACTIVE[0] or _profiling()


def enable_tracing(capacity=None):
    """Arm the tracer (also settable via FLAGS_enable_tracing at import).
    `capacity` resizes the ring (existing spans kept, newest-first)."""
    global _RING
    with _LOCK:
        cap = int(capacity or flag("trace_ring_capacity"))
        if cap != _RING.maxlen:
            _RING = deque(_RING, maxlen=cap)
        _EPOCH_OFFSET_NS[0] = time.time_ns() - time.perf_counter_ns()
        _RANK[0] = None          # re-resolve: jax.distributed may be up now
    _ACTIVE[0] = True


def disable_tracing():
    _ACTIVE[0] = False


def clear():
    with _LOCK:
        _RING.clear()


def new_span_id():
    """Take one id off the process-wide counter: every span opened
    later has a larger one (the legacy Profiler's cycle watermark)."""
    return next(_IDS)


# -- the span primitive ------------------------------------------------------
class _NullSpan:
    """Shared no-op context for the path on which nothing records."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **meta):
        return self

    def close(self, keep=True):
        pass


_NULL = _NullSpan()


def _open_ids():
    try:
        return _OPEN.ids
    except AttributeError:
        _OPEN.ids = []
        return _OPEN.ids


def _stats(meta):
    """Metadata as the profiler's event stats take it: numbers and
    strings (a request id may be any hashable)."""
    return {k: (v if isinstance(v, (int, float, str)) else str(v))
            for k, v in meta.items()}


class _Span:
    __slots__ = ("name", "meta", "id", "parent", "_t0", "_ann", "_nests")

    def __init__(self, name, meta, nests=True):
        self.name = name
        self.meta = meta
        self._nests = nests
        self.id = self.parent = self._t0 = self._ann = None

    def set(self, **meta):
        """Add metadata known only once the work is under way (a count
        of what was done, the slot that was picked)."""
        if self.meta is None:
            self.meta = meta
        else:
            self.meta.update(meta)
        if self._ann is not None:
            self._ann.set_metadata(**_stats(meta))
        return self

    def __enter__(self):
        self.id = next(_IDS)
        if self._nests:
            open_ids = _open_ids()
            self.parent = open_ids[-1] if open_ids else None
            open_ids.append(self.id)
        if _profiling():
            self._ann = _Annotation(self.name, **_stats(self.meta or {}))
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def close(self, keep=True):
        """End the span. `keep=False` leaves it out of the ring (a
        stretch that turned out not to be one); a profiler session's
        trace cannot take an opened event back and keeps it, marked
        `dropped`."""
        t1 = time.perf_counter_ns()
        if self._t0 is None:
            return
        if self._ann is not None:
            if not keep:
                self._ann.set_metadata(dropped=1)
            self._ann.__exit__(None, None, None)
        if self._nests:
            open_ids = _open_ids()
            if open_ids and open_ids[-1] == self.id:
                open_ids.pop()
            elif self.id in open_ids:        # closed out of order
                open_ids.remove(self.id)
        if keep:
            rec = (self.id, self.parent, self.name, self._t0, t1,
                   threading.get_ident(), trace_rank(), self.meta)
            with _LOCK:
                _RING.append(rec)
        self._t0 = None


# synthetic-track names: tid -> display name for tids that are NOT real
# thread idents (per-request Perfetto tracks from observability/requests
# use a synthetic tid per request so one request's queue/prefill/decode
# spans line up on ONE row). Bounded: oldest naming dropped past the cap
# — a long-running serve job must not grow this dict forever.
_TRACK_NAMES = {}
_TRACK_NAME_CAP = 8192


def set_track_name(tid, name, sort_index=None):
    """Name a (synthetic) tid lane in chrome-trace exports: emitted as
    thread_name / thread_sort_index metadata by chrome_events()."""
    with _LOCK:
        _TRACK_NAMES[int(tid)] = (str(name), sort_index)
        while len(_TRACK_NAMES) > _TRACK_NAME_CAP:
            _TRACK_NAMES.pop(next(iter(_TRACK_NAMES)))


def record_span(name, t0_ns, t1_ns, tid=None, meta=None):
    """Record an already-timed span into the ring (profiler.RecordEvent,
    the per-request tracks and the compile listener come through here).
    Its times are the caller's, so it cannot be put into a profiler
    session's trace: ring only. On the calling thread (`tid` None) its
    parent is the span open there; on a synthetic track it has none.
    No-op when nothing records."""
    if not recording():
        return
    parent = None
    if tid is None:
        tid = threading.get_ident()
        open_ids = _open_ids()
        parent = open_ids[-1] if open_ids else None
    rec = (next(_IDS), parent, name, int(t0_ns), int(t1_ns), tid,
           trace_rank(), meta)
    with _LOCK:
        _RING.append(rec)


def span(name, **meta):
    """Open a trace span: `with span("serve:admit", rid=7) as sp: ...;
    sp.set(tokens=1)`.

    With nothing recording this is one bool read, one `is_enabled()` and
    a shared null context. A span is recorded when the ring is armed or
    a JAX profiler session is recording (module docstring)."""
    if not (_ACTIVE[0] or _profiling()):
        return _NULL
    return _Span(name, meta or None)


def open_span(name, **meta):
    """Open a span that ends with `.close()` and not with a `with`
    block: one that straddles the spans around it (the serve loop's
    `serve:starved` begins inside one iteration and ends in a later
    one). It takes no parent and is never one: the thread's stack of
    open spans does not see it. Otherwise a span like any other: the
    ring and, while a profiler session records, the trace under its own
    name and extent. The shared null object when nothing records."""
    if not (_ACTIVE[0] or _profiling()):
        return _NULL
    return _Span(name, meta or None, nests=False).__enter__()


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILED_S = [0.0]


def compile_seconds():
    """Seconds this process has spent in backend compiles so far,
    counted whether or not anything records. Read before and after a
    call: the difference is the step ledgers' `compile` bucket."""
    return _COMPILED_S[0]


def _on_duration(event, seconds, fun_name="", **_):
    """`jax.monitoring` listener: every backend compile (or
    persistent-cache retrieval) counts into `compile_seconds()` and is
    one `xla:compile` span while the tracer records — a prefill bucket
    that compiles mid-serve is otherwise invisible from inside the
    program. The event arrives when the compile ends: start = end -
    seconds in the ring; in a profiler session's trace a zero-length
    marker at the end carries the seconds."""
    if event != _COMPILE_EVENT:
        return
    with _LOCK:
        _COMPILED_S[0] += float(seconds)
    if not recording():
        return
    t1 = time.perf_counter_ns()
    meta = {"fun_name": str(fun_name), "seconds": float(seconds)}
    if _profiling():
        with _Annotation("xla:compile", **meta):
            pass
    record_span("xla:compile", t1 - int(seconds * 1e9), t1, meta=meta)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


# -- introspection -----------------------------------------------------------
def _as_dict(rec):
    sid, parent, name, t0, t1, tid, rank, meta = rec
    d = {"id": sid, "parent": parent, "name": name, "t0_ns": t0,
         "dur_ns": t1 - t0, "tid": tid, "rank": rank}
    if meta:
        d["meta"] = meta
    return d


def drain():
    """Pop every buffered span as dicts (oldest first)."""
    with _LOCK:
        out = [_as_dict(r) for r in _RING]
        _RING.clear()
    return out


def tail(n=None):
    """Newest `n` spans (all if None) WITHOUT draining — the flight
    recorder's read."""
    with _LOCK:
        recs = list(_RING)
    if n is not None:
        recs = recs[-int(n):]
    return [_as_dict(r) for r in recs]


# -- chrome-trace export -----------------------------------------------------
def chrome_events(spans=None, pid=None, rank=None, include_metadata=True):
    """Buffered spans as chrome-trace 'X' events, timestamps rebased to
    unix-epoch microseconds so independently-written rank parts align.
    Metadata events name the pid lane 'rank N (pid ...)' and sort lanes
    by rank — the merge contract."""
    pid = os.getpid() if pid is None else pid
    rank = trace_rank() if rank is None else rank
    off = _EPOCH_OFFSET_NS[0]
    if spans is None:
        spans = tail()
    events = []
    if include_metadata:
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": f"rank {rank} "
                                                  f"(pid {pid})"}})
        events.append({"name": "process_sort_index", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"sort_index": rank}})
        # named synthetic tracks (per-request lanes): only tids that
        # actually appear in the exported spans get metadata rows
        with _LOCK:
            names = dict(_TRACK_NAMES)
        span_tids = {s["tid"] for s in spans}
        for tid in sorted(span_tids & names.keys()):
            tname, sort_index = names[tid]
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "args": {"name": tname}})
            if sort_index is not None:
                events.append({"name": "thread_sort_index", "ph": "M",
                               "pid": pid, "tid": tid,
                               "args": {"sort_index": sort_index}})
    for s in spans:
        ev = {"name": s["name"], "ph": "X", "cat": "host",
              "ts": (s["t0_ns"] + off) / 1e3, "dur": s["dur_ns"] / 1e3,
              "pid": pid, "tid": s["tid"],
              "args": {"rank": s.get("rank", rank)}}
        if s.get("id") is not None:
            ev["args"].update(id=s["id"], parent=s.get("parent"))
        if s.get("meta"):
            ev["args"].update(s["meta"])
        events.append(ev)
    return events


def export_chrome(path, spans=None):
    """One-process export: write buffered spans as a chrome-trace JSON."""
    doc = {"traceEvents": chrome_events(spans),
           "displayTimeUnit": "ms"}
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


_PART_FMT = "trace.rank{rank:05d}.json"
_PART_GLOB = "trace.rank*.json"
MERGED_NAME = "trace.merged.json"


def write_rank_part(dir_path):
    """Write THIS rank's spans as a part file (`trace.rankNNNNN.json`)
    under `dir_path`. Every rank writes its own part — no file is ever
    shared, so multi-process runs can't overwrite each other — then one
    rank calls merge_rank_parts() after a barrier."""
    os.makedirs(dir_path, exist_ok=True)
    path = os.path.join(dir_path, _PART_FMT.format(rank=trace_rank()))
    return export_chrome(path)


def merge_rank_parts(dir_path, out=None):
    """Fold every rank part in `dir_path` into ONE chrome-trace JSON
    (default `<dir>/trace.merged.json`). Ranks stay distinguishable by
    pid + the process_name/sort_index metadata each part carries."""
    events = []
    parts = sorted(glob.glob(os.path.join(dir_path, _PART_GLOB)))
    if not parts:
        raise FileNotFoundError(
            f"no {_PART_GLOB} part files under {dir_path}")
    for p in parts:
        with open(p) as f:
            events.extend(json.load(f).get("traceEvents", []))
    out = out or os.path.join(dir_path, MERGED_NAME)
    with open(out, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms",
                   "metadata": {"merged_parts": len(parts)}}, f)
    return out


# flag-driven arming (FLAGS_enable_tracing=1 in the environment)
if bool(flag("enable_tracing")):
    enable_tracing()
