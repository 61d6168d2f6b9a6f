"""Step-time attribution: the goodput ledger + cross-rank straggler flags.

The top observability layer (metrics -> traces -> **attribution**).
BENCH r3->r5 sat flat at 19,232 tok/s/chip for two PRs because nobody
could say WHERE a step's wall time went — the exposed-collective
diagnosis had to be reverse-engineered from archived HLO. This module
classifies every step's wall time into a fixed bucket set:

    {data_wait, compile, dispatch, host_gap, execute,
     grad_sync_exposed, checkpoint, other}

and emits one ledger record per step to the JSONL sink (event
"step_attribution") plus monotone per-bucket registry counters.

Accounting contract (the sums-to-wall invariant, tier-1 tested and
gated by tools/step_attribution.py):

- a step's WALL is the interval from the previous step's end to this
  step's end (first step: just the in-call interval);
- the inter-call gap splits into `checkpoint` (externally-noted seconds,
  e.g. distributed/checkpoint saves, drained via note_external) and
  `data_wait` (the rest — the input pipeline's bill);
- the in-call interval splits into `compile` + `execute` (measured),
  `host_gap` (caller-measured device-idle seconds — the serve loop's
  `serve:starved` stretches that ended in the step: nothing was queued
  on the device from a blocking read to the next device call's return;
  ~0 between chunks when the pipelined decode overlaps the host's
  bookkeeping; carved OUT OF `dispatch`), and
  `dispatch` (in-call host time that is none of those — argument prep,
  result rebinds), with `grad_sync_exposed` carved OUT OF `execute`;
- buckets sum to wall EXACTLY by construction; `other` absorbs clock
  residue only (clamped >= 0).

Exposed-collective reconcile: `grad_sync_exposed` is priced from the
compiled executable's scheduled HLO by THE SAME analysis
`tools/overlap_evidence.py --mode gradsync/--mode mp` gate on —
utils/hlo_analysis.grad_sync_overlap_report (a collective with zero
matmul-class work scheduled after it is exposed) priced by
estimate_collective_seconds, weighted by while-loop trip counts. One
shared code path means the attribution ledger and the overlap-evidence
artifacts CANNOT silently disagree about what "exposed" means; the
ledger additionally records the raw `modeled_exposed_s` so
tools/step_attribution.py can re-verify the carve-out arithmetic.

Straggler detection: ranks publish per-step digests (wall + span sums +
in-flight collective entries) through the same jax.distributed-backed
all_gather_object the eager collectives ride; rank 0 flags ranks whose
step wall deviates from the median by more than k * MAD (with a floor so
a near-zero MAD doesn't flag scheduler noise) and mirrors peer in-flight
tables into observability/tasks for the watchdog's per-rank view.
"""
from __future__ import annotations

import threading
import time

# NOTE: `from . import registry` would bind the package's re-exported
# registry() FUNCTION, not the submodule — import the names directly
from .registry import (enabled as _tel_enabled, log_step as _log_step,
                       registry as _registry)
from . import tasks as _tasks
from . import tracing as _tracing

__all__ = [
    "BUCKETS", "StepLedger", "note_external", "drain_external",
    "modeled_exposed_seconds", "flag_stragglers", "publish_step_digest",
    "last_straggler_report",
]

BUCKETS = ("data_wait", "compile", "dispatch", "host_gap", "execute",
           "grad_sync_exposed", "checkpoint", "other")

# externally-noted seconds attributed to the NEXT step's gap
# (bucket -> seconds); only gap-classifiable buckets are accepted
_EXT_LOCK = threading.Lock()
_EXTERNAL = {"checkpoint": 0.0}


def note_external(bucket, seconds):
    """Attribute `seconds` of between-step host work (e.g. a checkpoint
    save) to the named gap bucket of upcoming ledger records: a step
    bills at most its own inter-call gap and the remainder CARRIES
    FORWARD (a 5 s save never silently vanishes into a 5 ms gap).
    No-op when telemetry is disabled."""
    if not _tel_enabled():
        return
    if bucket not in _EXTERNAL:
        raise ValueError(f"external attribution supports "
                         f"{sorted(_EXTERNAL)}, got {bucket!r}")
    with _EXT_LOCK:
        _EXTERNAL[bucket] += float(seconds)


def drain_external(gap=None):
    """Take externally-noted seconds, each capped at `gap` (None = all);
    the uncapped remainder stays pooled for the next ledger step."""
    with _EXT_LOCK:
        out = {}
        for k, v in _EXTERNAL.items():
            take = v if gap is None else min(v, float(gap))
            out[k] = take
            _EXTERNAL[k] = v - take
    return out


class StepLedger:
    """Per-source step classifier. One instance per TrainStep /
    PagedDecoder; all instances share the registry counter families
    (labelled by source)."""

    def __init__(self, source):
        self.source = source
        self._prev_end = None
        self.steps = 0
        self.last = None
        self.totals = {b: 0.0 for b in BUCKETS}
        self.wall_total = 0.0

    def step(self, call_start, call_end, compile_s=0.0, execute_s=0.0,
             modeled_exposed_s=0.0, host_gap_s=0.0, step_index=None,
             extra=None):
        """Classify the step that ran [call_start, call_end] (perf_counter
        seconds) and emit the ledger record. Returns the record.

        ``host_gap_s`` is caller-measured device-idle time (the serve
        loop's starved stretches that ended in this step); it is carved
        out of `dispatch` and clamped to the unmeasured in-call
        remainder so the sums-to-wall invariant holds unconditionally."""
        compile_s = max(float(compile_s), 0.0)
        execute_s = max(float(execute_s), 0.0)
        gap = 0.0
        if self._prev_end is not None:
            gap = max(call_start - self._prev_end, 0.0)
        ext = drain_external(gap=gap)
        checkpoint = ext["checkpoint"]
        data_wait = max(gap - checkpoint, 0.0)
        in_call = max(call_end - call_start, 0.0)
        # measured phases can't exceed the in-call wall (they nest in it);
        # clamp against clock skew rather than emit a negative dispatch
        measured = compile_s + execute_s
        if measured > in_call:
            scale = in_call / measured if measured > 0 else 0.0
            compile_s *= scale
            execute_s *= scale
            measured = in_call
        host_gap = min(max(float(host_gap_s), 0.0), in_call - measured)
        exposed = min(max(float(modeled_exposed_s), 0.0), execute_s)
        buckets = {
            "data_wait": data_wait,
            "compile": compile_s,
            "dispatch": in_call - measured - host_gap,
            "host_gap": host_gap,
            "execute": execute_s - exposed,
            "grad_sync_exposed": exposed,
            "checkpoint": checkpoint,
            "other": 0.0,
        }
        wall = gap + in_call
        # exact by construction; keep the invariant explicit
        buckets["other"] = max(wall - sum(buckets.values()), 0.0)
        self._prev_end = call_end
        self.steps += 1
        for b, v in buckets.items():
            self.totals[b] += v
        self.wall_total += wall
        rec = {"event": "step_attribution", "source": self.source,
               "step": self.steps if step_index is None else int(step_index),
               "wall_s": wall,
               "modeled_exposed_s": float(modeled_exposed_s),
               "attribution": {b: round(v, 9)
                               for b, v in buckets.items()}}
        if extra:
            rec.update(extra)
        if _tel_enabled():
            reg = _registry()
            sec = reg.counter(
                "paddle_tpu_step_attribution_seconds_total",
                "Step wall time attributed per goodput bucket",
                ("source", "bucket"))
            for b, v in buckets.items():
                if v:
                    sec.inc(v, source=self.source, bucket=b)
            reg.counter("paddle_tpu_step_attribution_steps_total",
                        "Steps classified by the attribution ledger",
                        ("source",)).inc(source=self.source)
            reg.gauge("paddle_tpu_step_attribution_last_wall_seconds",
                      "Last classified step wall time",
                      ("source",)).set(wall, source=self.source)
            _log_step(rec)
        self.last = rec
        return rec

    def summary(self):
        """Aggregate totals (what bench.py's telemetry line carries)."""
        return {"source": self.source, "steps": self.steps,
                "wall_s": round(self.wall_total, 6),
                "buckets": {b: round(v, 6)
                            for b, v in self.totals.items()}}


# -- exposed-collective pricing (shared with overlap_evidence) ---------------
def modeled_exposed_seconds(compiled_or_text):
    """Per-execution exposed collective seconds for a compiled
    executable, from its post-optimization scheduled HLO.

    THE shared definition: utils/hlo_analysis.grad_sync_overlap_report
    marks a collective exposed when NO matmul-class work is scheduled
    after it (nothing to hide under), and estimate_collective_seconds
    prices it with the same ICI ring roofline `tools/overlap_evidence.py
    --mode gradsync/--mode mp` use. While-loop bodies are weighted by
    trip count. Returns 0.0 when the HLO is unavailable (interpreters,
    backends without runtime_executable)."""
    from ..utils.hlo_analysis import (
        grad_sync_overlap_report, estimate_collective_seconds,
        computation_weights)
    if isinstance(compiled_or_text, str):
        txt = compiled_or_text
    else:
        try:
            txt = compiled_or_text.runtime_executable() \
                .hlo_modules()[0].to_string()
        except Exception:
            return 0.0
    try:
        rows = grad_sync_overlap_report(txt)
        if not rows:
            return 0.0
        weights = computation_weights(txt)
        total = 0.0
        for r in rows:
            if r["matmuls_after"] > 0:
                continue
            w = max(weights.get(r["computation"], 1), 1)
            total += w * estimate_collective_seconds(
                r["kind"], r["bytes"], max(r["group_size"], 2))
        return total
    except Exception:
        return 0.0


# -- cross-rank straggler detection ------------------------------------------
_LAST_REPORT = [None]


def flag_stragglers(digests, k=4.0, floor_s=0.002, field="wall_s"):
    """Flag ranks whose `field` deviates above the median by more than
    k * MAD (median absolute deviation), with `floor_s` as the MAD floor
    so a perfectly-uniform mesh (MAD ~ 0) doesn't flag scheduler noise.
    One-sided: only SLOW ranks are stragglers. Returns the report dict."""
    rows = [(int(d["rank"]), float(d.get(field, 0.0))) for d in digests]
    vals = sorted(v for _, v in rows)
    n = len(vals)
    if n == 0:
        return {"flagged": [], "ranks": 0}
    med = (vals[n // 2] if n % 2 else
           0.5 * (vals[n // 2 - 1] + vals[n // 2]))
    devs = sorted(abs(v - med) for v in vals)
    mad = (devs[n // 2] if n % 2 else
           0.5 * (devs[n // 2 - 1] + devs[n // 2]))
    thr = k * max(mad, float(floor_s))
    flagged = sorted(r for r, v in rows if v - med > thr)
    return {"flagged": flagged, "ranks": n, "field": field,
            "median_s": round(med, 6), "mad_s": round(mad, 6),
            "threshold_s": round(thr, 6), "k": k,
            "per_rank": {str(r): round(v, 6) for r, v in sorted(rows)}}


def step_digest(step, wall_s, extra=None):
    """This rank's per-step digest: wall, top span sums from the trace
    ring tail, and the in-flight collective table."""
    spans = {}
    for s in _tracing.tail(64):
        spans[s["name"]] = spans.get(s["name"], 0.0) + s["dur_ns"] / 1e9
    d = {"rank": _tracing.trace_rank(), "step": int(step),
         "wall_s": float(wall_s),
         "spans": {k: round(v, 6) for k, v in sorted(spans.items())},
         "in_flight": _tasks.local_digest()}
    if extra:
        d.update(extra)
    return d


def publish_step_digest(digest, group=None, k=4.0, floor_s=0.002,
                        field="wall_s"):
    """Exchange per-rank digests over the SAME jax.distributed-backed
    path the eager collectives ride (all_gather_object), mirror every
    peer's in-flight table into observability/tasks, and — on rank 0 —
    compute and emit the straggler report (JSONL event
    "straggler_report" + paddle_tpu_straggler_flags_total counter).
    Returns the report on rank 0, None elsewhere.

    `field` picks the digest scalar to deviation-test. "wall_s" catches
    ranks slow INSIDE the step; for a rank slow to REACH the step
    (straggling input pipeline, busy host) compare an entry-time field
    instead — the victims' step walls absorb the straggler's delay
    through the collective barrier, so wall skew alone under-reports."""
    from ..distributed import collective as _coll
    objs = []
    _coll.all_gather_object(objs, digest, group=group)
    me = _tracing.trace_rank()
    for d in objs:
        if isinstance(d, dict) and d.get("rank", me) != me:
            _tasks.publish_remote(d["rank"], d.get("in_flight"))
    if me != 0:
        return None
    report = flag_stragglers(objs, k=k, floor_s=floor_s, field=field)
    report["step"] = digest.get("step")
    report["ts"] = time.time()
    _LAST_REPORT[0] = report
    if _tel_enabled():
        reg = _registry()
        reg.gauge("paddle_tpu_straggler_ranks",
                  "Ranks currently flagged as stragglers").set(
                      len(report["flagged"]))
        if report["flagged"]:
            c = reg.counter("paddle_tpu_straggler_flags_total",
                            "Straggler flags raised, by rank", ("rank",))
            for r in report["flagged"]:
                c.inc(rank=str(r))
        _log_step({"event": "straggler_report", **report})
    return report


def last_straggler_report():
    return _LAST_REPORT[0]
