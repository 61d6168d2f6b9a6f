"""Per-request serving lifecycle ledger: the FIFTH observability layer
(metrics -> traces -> attribution -> memory -> **requests**).

PRs 1/7/9 answer "where did the STEP's time/HBM go"; this module answers
the question at the granularity millions of users experience — a
request. `PagedDecoder.serve()` threads every request through a
`RequestLedger` — always, it is the serve loop's own accounting, so
`summary()` gives queue wait, TTFT and TPOT of the program users run —
which records the full lifecycle:

    arrival -> (guard deferrals) -> admit -> prefill -> first token
            -> decode chunks ... -> retire (cause)

and classifies each request's wall time into the four request buckets

    {queue_wait, prefill, decode, overhead}

Accounting contract (the sums-to-wall discipline of PR 7's step ledger,
applied per request and gated by tests + the servingload CI tier):
every bucket is accumulated INCREMENTALLY at event boundaries with the
same timestamps that delimit the neighbouring bucket, so the four
buckets telescope to `retire_ts - arrival_ts` exactly; the reconcile
residual (|wall - sum| / wall <= 2%) only moves when a segment is
double- or un-counted — which is precisely the accounting bug class the
gate exists to catch.

Derived SLO metrics (the terms the Ragged Paged Attention paper and the
Gemma-on-TPU serving comparison evaluate in):

- **TTFT** (time to first token): first_token_ts - arrival_ts. Includes
  queue wait — the user's clock starts at arrival, not admission.
- **TPOT** (time per output token): (last_token_ts - first_token_ts) /
  (tokens - 1), defined for requests with >= 2 tokens. Decode chunks
  fuse n greedy steps into one executable, so per-token times inside a
  chunk are not observable; TPOT is the honest chunk-granular rate.
- **goodput**: tokens/s from requests meeting BOTH SLOs (TTFT and TPOT
  thresholds) over the run's makespan — throughput that users actually
  experienced as responsive, the number the continuous-batching
  scheduler (ROADMAP 1) will be gated on.

Export is what stays conditional. Per retired request, telemetry on:

- one JSONL record (event "request_lifecycle") with timestamps, buckets,
  TTFT/TPOT, cause, and the guard-deferral count;
- registry counters (admitted/retired{cause}/tokens) and sliding-window
  `Quantile` series (paddle_tpu_request_{ttft,tpot,queue_wait,wall}_
  seconds) so p50/p99 are LIVE scrape()-able operational metrics;

and, while the span tracer records (`tracing.recording()`: its ring
armed or a JAX profiler session running), telemetry on or off:

- per-request Perfetto tracks: queue/prefill/decode spans recorded into
  the trace ring on a synthetic per-request tid (named "req <rid>" via
  tracing.set_track_name), so one merged trace shows a request's life
  across the queue, its prefill bucket, and every decode chunk it rode.

The live (in-flight) request table is the flight recorder's schema/3
"requests" section: a serving stall or OOM dump names the stuck
requests (ids, ages, tokens emitted, slot/block occupancy).
"""
from __future__ import annotations

import math
import threading
import time
import weakref

# NOTE: `from . import registry` would bind the package's re-exported
# registry() FUNCTION, not the submodule — import the names directly
from .registry import (enabled as _tel_enabled, log_step as _log_step,
                       registry as _registry)
from . import tracing as _tracing

__all__ = [
    "REQUEST_BUCKETS", "FINISH_CAUSES", "NON_COMPLETION_CAUSES",
    "RequestRecord", "RequestLedger",
    "in_flight_table", "requests_section", "http_snapshot",
    "percentile",
]

REQUEST_BUCKETS = ("queue_wait", "prefill", "decode", "overhead")

# retire causes the ledger recognises (ISSUE 14 made the fault-path
# causes real):
# - "evicted": HeadroomGuard-pressure eviction or a transient serve
#   fault — the incarnation's blocks were reclaimed and its tokens
#   retained for chunked-prefill replay; the SAME rid re-arrives and
#   (usually) retires again under a terminal cause
# - "quarantined": the slot's logits went non-finite (poisoned kernel,
#   corrupted KV) — slot recycled, request replayed like an eviction
# - "rejected_deferred": admission deferred past the max-deferral cap
#   (a guard-pressure storm degrades to rejection, not a wedged queue)
# - "rejected_draining": the watchdog declared a peer dead and serving
#   drained — queued work rejected so in-flight work retires cleanly
FINISH_CAUSES = ("eos", "budget_exhausted", "evicted", "quarantined",
                 "rejected_oversized", "rejected_timeout",
                 "rejected_deferred", "rejected_draining")

# causes that are NOT a terminal user-visible completion: excluded from
# goodput (an evicted-and-never-completed request served nobody) —
# rejections, plus the replayable interruptions
NON_COMPLETION_CAUSES = frozenset(
    c for c in FINISH_CAUSES
    if c.startswith("rejected") or c in ("evicted", "quarantined"))

# live ledgers, so the flight recorder / exporter can snapshot in-flight
# requests without holding serving engines alive
_LIVE_LEDGERS = weakref.WeakSet()

# synthetic chrome-trace tids for per-request tracks: far above any real
# thread ident's low bits mattering — uniqueness inside the trace is all
# that counts, and each request gets its own lane
_TRACK_LOCK = threading.Lock()
_TRACK_SEQ = [0]
_TRACK_BASE = 1 << 40


def _next_track_tid():
    with _TRACK_LOCK:
        _TRACK_SEQ[0] += 1
        return _TRACK_BASE + _TRACK_SEQ[0]


def percentile(values, q):
    """Exact linear-interpolated percentile (numpy's default method)
    over an unsorted iterable — shared with registry.Quantile."""
    from .registry import _percentile
    return _percentile(sorted(float(v) for v in values), q)


class RequestRecord:
    """One request's lifecycle. All timestamps are perf_counter seconds
    (the serve loop's clock); bucket seconds are accumulated at event
    boundaries so they telescope to the wall exactly."""

    __slots__ = (
        "rid", "prompt_tokens", "max_new", "arrival_ts", "admit_ts",
        "prefill_t0", "prefill_t1", "first_token_ts", "last_token_ts",
        "retire_ts", "slot", "blocks", "bucket", "tokens_generated",
        "deferred_admissions", "finish_reason", "chunks",
        "queue_wait_s", "prefill_s", "decode_s", "overhead_s",
        "prefill_cached_tokens", "_last_ts", "track_tid",
    )

    def __init__(self, rid, prompt_tokens, max_new, arrival_ts):
        self.rid = rid
        self.prompt_tokens = int(prompt_tokens)
        self.max_new = int(max_new)
        self.arrival_ts = float(arrival_ts)
        self.admit_ts = None
        self.prefill_t0 = None
        self.prefill_t1 = None
        self.first_token_ts = None
        self.last_token_ts = None
        self.retire_ts = None
        self.slot = None
        self.blocks = 0
        self.bucket = None
        # prompt tokens served from the prefix cache (ISSUE 18): the
        # warm-prefill fast path still telescopes into the same four
        # buckets — a cached prefill is just a SHORT prefill segment
        self.prefill_cached_tokens = 0
        self.tokens_generated = 0
        self.deferred_admissions = 0
        self.finish_reason = None
        self.chunks = []                 # [(tokens, dur_s), ...]
        self.queue_wait_s = 0.0
        self.prefill_s = 0.0
        self.decode_s = 0.0
        self.overhead_s = 0.0
        self._last_ts = None
        self.track_tid = None

    # -- derived metrics ---------------------------------------------------
    @property
    def state(self):
        if self.retire_ts is not None:
            return "retired"
        return "queued" if self.admit_ts is None else "live"

    def wall_s(self):
        if self.retire_ts is None:
            return None
        return self.retire_ts - self.arrival_ts

    def ttft_s(self):
        if self.first_token_ts is None:
            return None
        return self.first_token_ts - self.arrival_ts

    def tpot_s(self):
        """Chunk-granular time per output token past the first; None
        for requests that produced fewer than 2 tokens."""
        if (self.first_token_ts is None or self.last_token_ts is None
                or self.tokens_generated < 2):
            return None
        return ((self.last_token_ts - self.first_token_ts)
                / (self.tokens_generated - 1))

    def buckets(self):
        return {"queue_wait": self.queue_wait_s,
                "prefill": self.prefill_s,
                "decode": self.decode_s,
                "overhead": self.overhead_s}

    def reconcile_residual_frac(self):
        """|wall - sum(buckets)| / wall — the sums-to-wall gate's
        scalar. 0.0 for a zero-wall request (rejected instantly)."""
        wall = self.wall_s()
        if wall is None:
            return None
        total = sum(self.buckets().values())
        if wall <= 0.0:
            return abs(total)
        return abs(wall - total) / wall

    def to_dict(self):
        d = {"rid": str(self.rid), "prompt_tokens": self.prompt_tokens,
             "max_new": self.max_new, "tokens_generated":
                 self.tokens_generated,
             "finish_reason": self.finish_reason,
             "deferred_admissions": self.deferred_admissions,
             "slot": self.slot, "blocks": self.blocks,
             "prefill_bucket": self.bucket,
             "prefill_cached_tokens": self.prefill_cached_tokens,
             "arrival_ts": self.arrival_ts, "retire_ts": self.retire_ts,
             "wall_s": self.wall_s(), "ttft_s": self.ttft_s(),
             "tpot_s": self.tpot_s(), "chunks": len(self.chunks),
             "buckets": {b: round(v, 9)
                         for b, v in self.buckets().items()}}
        return d

    def in_flight_row(self, now=None):
        """The flight-recorder / exporter row for a live request."""
        now = time.perf_counter() if now is None else now
        return {"rid": str(self.rid), "state": self.state,
                "age_s": round(max(now - self.arrival_ts, 0.0), 6),
                "slot": self.slot, "blocks": self.blocks,
                "tokens_emitted": self.tokens_generated,
                "deferred_admissions": self.deferred_admissions}


class RequestLedger:
    """Per-engine request classifier. Methods take explicit `ts`
    (perf_counter seconds, default now) so tests can hand-time a
    lifecycle and assert the TTFT/TPOT/reconcile arithmetic."""

    def __init__(self, source="serve", keep=8192):
        self.source = source
        self._lock = threading.RLock()
        self._live = {}                 # rid -> RequestRecord
        self._completed = []            # bounded: newest `keep`
        self._keep = int(keep)
        self.by_cause = {}
        self.tokens_total = 0
        # monotone lifetime count: _completed is retention-bounded, so
        # len() of it undercounts on long-running servers
        self.completed_total = 0
        _LIVE_LEDGERS.add(self)

    @staticmethod
    def _now(ts):
        return time.perf_counter() if ts is None else float(ts)

    def _rec(self, rid):
        rec = self._live.get(rid)
        if rec is None:
            raise KeyError(f"unknown request {rid!r}")
        return rec

    # -- lifecycle events --------------------------------------------------
    def arrival(self, rid, prompt_tokens, max_new, ts=None):
        """Register a request at its (possibly scheduled-future) arrival
        timestamp. The user's clock — TTFT, queue wait — starts here."""
        rec = RequestRecord(rid, prompt_tokens, max_new, self._now(ts))
        with self._lock:
            self._live[rid] = rec
        return rec

    def defer(self, rid):
        """The HeadroomGuard deferred this (queued) request's admission."""
        with self._lock:
            self._rec(rid).deferred_admissions += 1

    def admit(self, rid, slot=None, blocks=0, ts=None):
        ts = self._now(ts)
        with self._lock:
            rec = self._rec(rid)
            rec.admit_ts = ts
            rec.queue_wait_s += max(ts - rec.arrival_ts, 0.0)
            rec._last_ts = ts
            rec.slot = slot
            rec.blocks = int(blocks)
        if _tracing.recording():
            rec.track_tid = _next_track_tid()
            _tracing.set_track_name(rec.track_tid, f"req {rec.rid}")
            self._track_span(rec, "req:queue", rec.arrival_ts, ts)
        if _tel_enabled():
            _registry().counter(
                "paddle_tpu_requests_admitted_total",
                "Requests admitted to a serving slot",
                ("source",)).inc(source=self.source)
        return rec

    def prefill(self, rid, t0, t1, bucket=None, cached_tokens=0):
        with self._lock:
            rec = self._rec(rid)
            rec.prefill_t0, rec.prefill_t1 = float(t0), float(t1)
            rec.overhead_s += max(float(t0) - rec._last_ts, 0.0)
            rec.prefill_s += max(float(t1) - float(t0), 0.0)
            rec._last_ts = float(t1)
            rec.bucket = bucket
            rec.prefill_cached_tokens = int(cached_tokens)
        self._track_span(rec, "req:prefill", t0, t1,
                         meta={"bucket": bucket,
                               "cached_tokens": int(cached_tokens)})

    def first_token(self, rid, ts=None):
        ts = self._now(ts)
        with self._lock:
            rec = self._rec(rid)
            rec.first_token_ts = ts
            rec.last_token_ts = ts
            rec.tokens_generated += 1

    def chunk(self, rid, t0, t1, tokens):
        """This request rode a decode chunk [t0, t1] and took `tokens`
        of it. The whole chunk wall is the request's decode cost (its
        slot is occupied for all of it, even when its budget gates it
        off mid-chunk on device)."""
        with self._lock:
            rec = self._rec(rid)
            rec.overhead_s += max(float(t0) - rec._last_ts, 0.0)
            rec.decode_s += max(float(t1) - float(t0), 0.0)
            rec._last_ts = float(t1)
            if tokens > 0:
                rec.tokens_generated += int(tokens)
                rec.last_token_ts = float(t1)
            rec.chunks.append((int(tokens), float(t1) - float(t0)))
        self._track_span(rec, "req:decode", t0, t1,
                         meta={"tokens": int(tokens)})

    def retire(self, rid, cause, ts=None):
        """Close the request's ledger entry and emit it. `cause` is one
        of FINISH_CAUSES."""
        if cause not in FINISH_CAUSES:
            raise ValueError(f"finish cause {cause!r} not in "
                             f"{FINISH_CAUSES}")
        ts = self._now(ts)
        with self._lock:
            rec = self._live.pop(rid)
            if rec._last_ts is not None:
                rec.overhead_s += max(ts - rec._last_ts, 0.0)
            rec.retire_ts = ts
            rec.finish_reason = cause
            self._completed.append(rec)
            del self._completed[:-self._keep]
            self.by_cause[cause] = self.by_cause.get(cause, 0) + 1
            self.tokens_total += rec.tokens_generated
            self.completed_total += 1
        self._emit(rec)
        return rec

    def reject(self, rid, cause, ts=None):
        """Retire a never-admitted request (overload shedding): its
        whole wall is queue_wait, by the same telescoping arithmetic."""
        ts = self._now(ts)
        with self._lock:
            rec = self._rec(rid)
            rec.queue_wait_s += max(ts - rec.arrival_ts, 0.0)
            rec._last_ts = ts
        return self.retire(rid, cause, ts=ts)

    def discard(self, rid):
        """Silently drop a live record WITHOUT emitting it — the
        serve-loop error path's cleanup: a request whose serve() call
        unwound mid-flight must not haunt the in-flight table (the
        flight recorder would name it 'stuck' forever). No-op for
        unknown/already-retired rids."""
        with self._lock:
            self._live.pop(rid, None)

    # -- emission ----------------------------------------------------------
    def _track_span(self, rec, name, t0, t1, meta=None):
        if rec.track_tid is None or not _tracing.recording():
            return
        m = {"rid": str(rec.rid)}
        if meta:
            m.update(meta)
        _tracing.record_span(name, int(float(t0) * 1e9),
                             int(float(t1) * 1e9), tid=rec.track_tid,
                             meta=m)

    def _emit(self, rec):
        if not _tel_enabled():
            return
        reg = _registry()
        reg.counter("paddle_tpu_requests_retired_total",
                    "Requests retired, by finish cause",
                    ("source", "cause")).inc(
                        source=self.source, cause=rec.finish_reason)
        if rec.finish_reason == "evicted":
            reg.counter("paddle_tpu_request_evictions_total",
                        "Serving slots evicted under pressure/faults "
                        "(blocks reclaimed, tokens retained for "
                        "replay)", ("source",)).inc(source=self.source)
        elif rec.finish_reason == "quarantined":
            reg.counter("paddle_tpu_request_quarantines_total",
                        "Serving slots quarantined on non-finite "
                        "logits", ("source",)).inc(source=self.source)
        if rec.tokens_generated:
            reg.counter("paddle_tpu_request_tokens_generated_total",
                        "Tokens generated across retired requests",
                        ("source",)).inc(rec.tokens_generated,
                                         source=self.source)
        if rec.deferred_admissions:
            reg.counter(
                "paddle_tpu_request_deferred_admissions_total",
                "Per-request HeadroomGuard admission deferrals",
                ("source",)).inc(rec.deferred_admissions,
                                 source=self.source)
        q = dict(window=4096, max_age_s=600.0,
                 quantiles=(0.5, 0.9, 0.99))
        ttft, tpot, wall = rec.ttft_s(), rec.tpot_s(), rec.wall_s()
        if ttft is not None:
            reg.quantile("paddle_tpu_request_ttft_seconds",
                         "Time to first token (sliding window)",
                         ("source",), **q).observe(ttft,
                                                   source=self.source)
        if tpot is not None:
            reg.quantile("paddle_tpu_request_tpot_seconds",
                         "Time per output token (sliding window)",
                         ("source",), **q).observe(tpot,
                                                   source=self.source)
        reg.quantile("paddle_tpu_request_queue_wait_seconds",
                     "Request queue wait (sliding window)",
                     ("source",), **q).observe(rec.queue_wait_s,
                                               source=self.source)
        if wall is not None:
            reg.quantile("paddle_tpu_request_wall_seconds",
                         "Request end-to-end wall (sliding window)",
                         ("source",), **q).observe(wall,
                                                   source=self.source)
        _log_step({"event": "request_lifecycle", "source": self.source,
                   **rec.to_dict()})

    # -- views -------------------------------------------------------------
    def in_flight(self):
        with self._lock:
            return list(self._live.values())

    def completed_records(self):
        with self._lock:
            return list(self._completed)

    def percentiles(self, field, qs=(0.5, 0.99)):
        """{q: value} over completed records' `field` ("ttft_s",
        "tpot_s", "wall_s", "queue_wait_s"); None-valued records (e.g.
        TPOT of a 1-token request) are excluded."""
        vals = []
        for rec in self.completed_records():
            v = getattr(rec, field)
            v = v() if callable(v) else v
            if v is not None:
                vals.append(float(v))
        if not vals:
            return {q: float("nan") for q in qs}
        return {q: percentile(vals, q) for q in qs}

    def goodput_tokens(self, slo_ttft_s, slo_tpot_s):
        """Tokens from requests that met BOTH SLOs (TPOT vacuous for
        <2-token requests). Divide by the run's makespan for goodput
        tokens/s. Non-completion retirements — rejections, evictions,
        quarantines — are excluded: an evicted-and-never-completed
        request served nobody, and its replay incarnation (same rid,
        terminal cause) is the one that counts."""
        good = 0
        for rec in self.completed_records():
            if rec.finish_reason in NON_COMPLETION_CAUSES:
                continue
            ttft, tpot = rec.ttft_s(), rec.tpot_s()
            if ttft is None or ttft > slo_ttft_s:
                continue
            if tpot is not None and tpot > slo_tpot_s:
                continue
            good += rec.tokens_generated
        return good

    def max_reconcile_residual_frac(self):
        worst = 0.0
        for rec in self.completed_records():
            r = rec.reconcile_residual_frac()
            if r is not None:
                worst = max(worst, r)
        return worst

    def summary(self, slo_ttft_s=None, slo_tpot_s=None):
        recs = self.completed_records()
        with self._lock:
            by_cause = dict(self.by_cause)
        out = {"source": self.source, "completed": len(recs),
               "in_flight": len(self.in_flight()),
               "by_cause": by_cause,
               "tokens_generated": self.tokens_total,
               "deferred_admissions": sum(
                   r.deferred_admissions for r in recs),
               "reconcile_max_residual_frac": round(
                   self.max_reconcile_residual_frac(), 9)}
        for field, key in (("ttft_s", "ttft"), ("tpot_s", "tpot"),
                           ("queue_wait_s", "queue_wait"),
                           ("wall_s", "wall")):
            ps = self.percentiles(field, qs=(0.5, 0.99))
            out[f"p50_{key}_s"] = ps[0.5]
            out[f"p99_{key}_s"] = ps[0.99]
        if slo_ttft_s is not None and slo_tpot_s is not None:
            out["slo"] = {"ttft_s": slo_ttft_s, "tpot_s": slo_tpot_s}
            out["goodput_tokens"] = self.goodput_tokens(
                slo_ttft_s, slo_tpot_s)
        return out


# -- module-level views (flight recorder schema/3, exporter /requests) -------
def in_flight_table(now=None):
    """Every live ledger's in-flight requests, oldest first — the table
    a serving stall or OOM dump names the stuck requests from."""
    rows = []
    for led in list(_LIVE_LEDGERS):
        rows.extend(r.in_flight_row(now=now) for r in led.in_flight())
    rows.sort(key=lambda r: -r["age_s"])
    return rows


def requests_section():
    """The flight recorder's schema/3 "requests" section."""
    completed = 0
    by_cause = {}
    for led in list(_LIVE_LEDGERS):
        # snapshot under the ledger lock: the serving thread may be
        # retiring a first-of-its-kind cause mid-iteration (the
        # exporter thread calls this on GET /requests)
        with led._lock:
            # the monotone counter, NOT len(completed_records()):
            # record retention is bounded, the tally must not be
            completed += led.completed_total
            causes = dict(led.by_cause)
        for c, n in causes.items():
            by_cause[c] = by_cause.get(c, 0) + n
    return {"in_flight": in_flight_table(),
            "completed_total": completed, "by_cause": by_cause}


def _json_safe(obj):
    """Non-finite floats -> None: the /requests body must stay STRICT
    JSON (json.dumps happily emits bare NaN, which jq / JSON.parse /
    every non-Python consumer rejects — and an age-pruned-empty
    quantile window snapshots to NaN)."""
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    return obj


def http_snapshot():
    """The exporter's GET /requests body: the live table plus the
    current sliding-window SLO percentiles. Strict-JSON-safe by
    construction (non-finite values are null)."""
    out = requests_section()
    reg = _registry()
    pct = {}
    for name, key in (("paddle_tpu_request_ttft_seconds", "ttft_s"),
                      ("paddle_tpu_request_tpot_seconds", "tpot_s"),
                      ("paddle_tpu_request_queue_wait_seconds",
                       "queue_wait_s"),
                      ("paddle_tpu_request_wall_seconds", "wall_s")):
        m = reg.get(name)
        if m is None:
            continue
        pct[key] = {lbl[0] if lbl else "": m.snapshot(
            **dict(zip(m.labelnames, lbl)))
            for lbl in m.labeled_values()}
    out["percentiles"] = pct
    return _json_safe(out)
