"""Per-executable roofline attribution: the SIXTH observability layer.

Metrics said how fast (PR 1), traces said where (PR 7 spans),
attribution said why slow (PR 7 goodput ledger), memory said where the
HBM goes (PR 9), requests said what each user experienced (PR 12) —
this module says **which ops eat the MFU**, per compiled executable:

- **pricing** (``utils/hlo_analysis.roofline_report``): every op of the
  scheduled module priced against the chip rooflines encoded in
  ``distributed/auto_tuner/cost_model.py`` (MXU rate, HBM bandwidth,
  ICI link bandwidth, host link), classified compute-/HBM-/ICI-/
  host-bound, weighted by while-trip counts;
- **waterfall**: per-``named_scope`` MFU-gap buckets whose seconds sum
  to the modeled step wall (the repo's sums-to-X contract —
  ``verify_record`` re-checks it, tools/roofline_report.py gates <= 2%);
- **drift gate** (``drift_vs_cost_model``): the recorded rates must
  equal the cost_model constants and every collective row must re-price
  through the SAME ``estimate_collective_seconds`` ring model the
  planner search uses — planner predictions and roofline measurements
  cannot silently disagree;
- **cross-check**: parsed flops vs the executable's own
  ``cost_analysis()`` flops (``flops_drift_frac``).

Recorded records land in a bounded in-process store, surface as gauges
``paddle_tpu_roofline_{hbm_bound_flops_frac,modeled_mfu,
modeled_step_seconds,mfu_gap_seconds}{source,executable}``, and emit
one ``roofline`` JSONL record each.

Producers: observability/programs.py (telemetry's analysis copy of
each TrainStep signature and each serve program, on its first call),
tools/roofline_report.py (the CI gate + mutation teeth).
"""
from __future__ import annotations

import os
import threading

from .registry import (enabled as _tel_enabled, log_step as _log_step,
                       registry as _registry)

__all__ = ["SCHEMA", "CLASSES", "chip_rates", "executable_roofline",
           "verify_record", "drift_vs_cost_model", "record_executable",
           "records", "top_hbm_bound_ops", "http_snapshot",
           "set_history_path", "reset"]

SCHEMA = "paddle_tpu.roofline/1"
CLASSES = ("compute", "hbm", "ici", "host")

_LOCK = threading.Lock()
_RECORDS: dict = {}
_MAX_RECORDS = 64
# bench-history tail surface for GET /roofline; default resolves the
# repo-layout path lazily against cwd, overridable for tests/daemons
_HISTORY_PATH = [None]


def chip_rates():
    """The roofline rates, read from cost_model's chip constants — the
    ONE source the planner search prices with. ``drift_vs_cost_model``
    pins recorded reports to these values."""
    from ..distributed.auto_tuner import cost_model as _cm
    return {
        "mxu_flops_per_sec": float(_cm.PEAK_FLOPS_TPU),
        # quantized-dot rates: bf16 peak x the planner's MXU_RATE table
        # (cost_model prices matmul_quant plans with the same
        # multiplier — the drift gate keeps both in lockstep)
        "mxu_int8_flops_per_sec": float(_cm.PEAK_FLOPS_TPU
                                        * _cm.MXU_RATE["int8"]),
        "mxu_fp8_flops_per_sec": float(_cm.PEAK_FLOPS_TPU
                                       * _cm.MXU_RATE["fp8"]),
        "hbm_bytes_per_sec": float(_cm.HBM_BW),
        "ici_bytes_per_sec": float(_cm.ICI_BW),
        "host_bytes_per_sec": float(_cm.OFFLOAD_DMA_BW),
    }


def _hlo_text_of(compiled):
    try:
        return compiled.runtime_executable().hlo_modules()[0].to_string()
    except Exception:
        return None


def cost_analysis_flops(compiled):
    """The executable's own FLOP count, or None where it reports none."""
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        return float(ca.get("flops", 0.0))
    except Exception:
        return None


def executable_roofline(compiled, top_k=8, hlo_text=None):
    """Roofline record for one AOT-compiled executable, or None when
    the scheduled HLO is unavailable. Never raises on analysis failure
    — a profiler must not take down the run it profiles."""
    text = hlo_text if hlo_text is not None else _hlo_text_of(compiled)
    if not text:
        return None
    try:
        from ..utils.hlo_analysis import roofline_report
        rec = roofline_report(text, rates=chip_rates(), top_k=top_k)
    except Exception:
        return None
    rec["schema"] = SCHEMA
    # modeled-vs-measured flops cross-check: the text-parsed dot/conv
    # arithmetic against the executable's own cost_analysis
    ca_flops = cost_analysis_flops(compiled)
    rec["cost_analysis_flops"] = ca_flops
    rec["flops_drift_frac"] = (
        abs(rec["flops_total"] - ca_flops) / max(ca_flops, 1.0)
        if ca_flops else None)
    return rec


def verify_record(rec, tol=0.02):
    """The sums-to-X contract checker (PR 7 sums-to-wall / PR 9
    sums-to-total style). Returns a list of problems; [] means the
    record telescopes:

    - class_time_s sums to total_modeled_s within ``tol``;
    - class_time_frac sums to 1 within ``tol`` (when the wall is
      nonzero);
    - by_scope seconds sum to total_modeled_s within ``tol`` — the
      per-layer waterfall reconciles to the modeled step wall;
    - ideal_compute_s + mfu_gap_s == total_modeled_s within ``tol``;
    - hbm_bound_flops_frac in [0, 1]."""
    errs = []
    if not isinstance(rec, dict) or "class_time_s" not in rec:
        return ["not a roofline record"]
    total = float(rec.get("total_modeled_s", 0.0))
    slack = tol * max(total, 1e-30)
    cls = sum(float(rec["class_time_s"].get(c, 0.0)) for c in CLASSES)
    if abs(cls - total) > slack:
        errs.append(f"class_time_s sum {cls} != total_modeled_s {total}")
    if total > 0:
        frac = sum(float(rec.get("class_time_frac", {}).get(c, 0.0))
                   for c in CLASSES)
        if abs(frac - 1.0) > tol:
            errs.append(f"class_time_frac sums to {frac}, not 1")
    scoped = sum(float(s.get("seconds", 0.0))
                 for s in (rec.get("by_scope") or {}).values())
    if abs(scoped - total) > slack:
        errs.append(f"by_scope seconds sum {scoped} != "
                    f"total_modeled_s {total} — the waterfall does not "
                    f"reconcile to the modeled step wall")
    ideal = float(rec.get("ideal_compute_s", 0.0))
    gap = float(rec.get("mfu_gap_s", 0.0))
    if abs((ideal + gap) - total) > slack:
        errs.append(f"ideal {ideal} + gap {gap} != total {total}")
    hb = rec.get("hbm_bound_flops_frac")
    if not (isinstance(hb, (int, float)) and 0.0 <= hb <= 1.0):
        errs.append(f"hbm_bound_flops_frac {hb!r} not in [0, 1]")
    return errs


def drift_vs_cost_model(rec, tol=0.02):
    """Modeled-vs-measured drift gate against cost_model's per-term
    pricing. Returns a list of problems; [] means the roofline record
    and the planner's cost model agree:

    - the record's rates equal the cost_model chip constants (a
      hardcoded bandwidth anywhere in the roofline path shows up here);
    - every collective row re-prices through the SAME
      estimate_collective_seconds ring model within ``tol``."""
    errs = []
    if not isinstance(rec, dict):
        return ["not a roofline record"]
    want = chip_rates()
    got = rec.get("rates") or {}
    for key, val in want.items():
        g = got.get(key)
        if not (isinstance(g, (int, float)) and g == val):
            errs.append(f"rate {key} = {g!r} drifted from cost_model's "
                        f"{val}")
    from ..utils.hlo_analysis import estimate_collective_seconds
    ici = want["ici_bytes_per_sec"]
    for row in rec.get("collectives") or ():
        model_s = estimate_collective_seconds(
            row.get("kind"), row.get("bytes", 0),
            row.get("group_size", 0),
            ici_bytes_per_sec=ici) * float(row.get("trips", 1))
        got_s = float(row.get("seconds", 0.0))
        if abs(got_s - model_s) > max(tol * model_s, 1e-12):
            errs.append(f"collective {row.get('name')} priced {got_s}s "
                        f"vs cost_model's {model_s}s")
    return errs


def record_executable(source, executable, compiled, top_k=8):
    """Price ``compiled`` and record the roofline under
    ``source:executable``: bounded store, per-executable gauges, one
    JSONL record. Called once per compile (the compile already cost
    seconds; the pricing costs milliseconds). Returns the record (None
    when the scheduled HLO is unavailable)."""
    rec = executable_roofline(compiled, top_k=top_k)
    if rec is None:
        return None
    key = f"{source}:{executable}"
    with _LOCK:
        _RECORDS.pop(key, None)
        _RECORDS[key] = rec
        while len(_RECORDS) > _MAX_RECORDS:
            _RECORDS.pop(next(iter(_RECORDS)))
    if _tel_enabled():
        reg = _registry()
        labels = {"source": source, "executable": executable}
        reg.gauge("paddle_tpu_roofline_hbm_bound_flops_frac",
                  "Fraction of modeled FLOPs living in HBM-bound ops",
                  ("source", "executable")).set(
                      rec["hbm_bound_flops_frac"], **labels)
        reg.gauge("paddle_tpu_roofline_modeled_mfu",
                  "Modeled MFU: MXU-ideal seconds / modeled step wall",
                  ("source", "executable")).set(rec["modeled_mfu"],
                                                **labels)
        reg.gauge("paddle_tpu_roofline_modeled_step_seconds",
                  "Modeled step wall from the per-op roofline sum",
                  ("source", "executable")).set(rec["total_modeled_s"],
                                                **labels)
        reg.gauge("paddle_tpu_roofline_mfu_gap_seconds",
                  "Modeled seconds away from MXU peak per step",
                  ("source", "executable")).set(rec["mfu_gap_s"],
                                                **labels)
        _log_step({"event": "roofline", "schema": SCHEMA,
                   "source": source, "executable": executable,
                   "total_modeled_s": rec["total_modeled_s"],
                   "ideal_compute_s": rec["ideal_compute_s"],
                   "modeled_mfu": rec["modeled_mfu"],
                   "mfu_gap_s": rec["mfu_gap_s"],
                   "class_time_frac": rec["class_time_frac"],
                   "hbm_bound_flops_frac": rec["hbm_bound_flops_frac"],
                   "flops_drift_frac": rec.get("flops_drift_frac"),
                   "top_ops": [
                       {k: o[k] for k in ("name", "op", "scope",
                                          "class", "seconds", "gap_s")}
                       for o in rec["top_ops"][:5]]})
    return rec


def records():
    """Snapshot of the recorded rooflines ({source:executable -> rec})."""
    with _LOCK:
        return dict(_RECORDS)


def top_hbm_bound_ops(n=3, source=None):
    """The top-``n`` HBM-bound ops by modeled seconds across recorded
    executables — the per-op bandwidth bill serving benchmarks attach
    to their telemetry lines ({executable, name, op, scope, seconds,
    bytes})."""
    rows = []
    for key, rec in records().items():
        if source is not None and not key.startswith(source + ":"):
            continue
        for o in rec.get("top_ops", ()):
            if o.get("class") == "hbm":
                rows.append({"executable": key, "name": o["name"],
                             "op": o["op"], "scope": o["scope"],
                             "seconds": o["seconds"],
                             "bytes": o["bytes"]})
    rows.sort(key=lambda r: (-r["seconds"], r["name"]))
    return rows[:n]


def set_history_path(path):
    """Point the /roofline bench-history tail at ``path`` (None restores
    the default repo-layout lookup)."""
    _HISTORY_PATH[0] = path


def _history_tail(limit=5):
    import json
    path = _HISTORY_PATH[0] or os.path.join(
        os.getcwd(), "tools", "artifacts", "bench_history.jsonl")
    try:
        with open(path) as f:
            lines = f.readlines()[-limit:]
    except OSError:
        return []
    rows = []
    for line in lines:
        try:
            rows.append(json.loads(line))
        except ValueError:
            continue
    return rows


def http_snapshot():
    """The GET /roofline payload: latest per-executable snapshot (wall,
    MFU, class fractions, top ops) plus the bench-history tail."""
    out = {}
    for key, rec in records().items():
        out[key] = {
            "total_modeled_s": rec["total_modeled_s"],
            "modeled_mfu": rec["modeled_mfu"],
            "mfu_gap_s": rec["mfu_gap_s"],
            "class_time_frac": rec["class_time_frac"],
            "hbm_bound_flops_frac": rec["hbm_bound_flops_frac"],
            "top_ops": [{k: o[k] for k in ("name", "op", "scope",
                                           "class", "seconds", "gap_s")}
                        for o in rec.get("top_ops", ())[:5]],
        }
    return {"schema": SCHEMA, "executables": out,
            "bench_history_tail": _history_tail()}


def reset():
    with _LOCK:
        _RECORDS.clear()
