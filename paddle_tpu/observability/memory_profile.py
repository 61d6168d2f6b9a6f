"""Compiled-executable HBM ledger: the fourth observability layer.

Metrics said how fast (PR 1), traces said where (PR 7 spans),
attribution said why slow (PR 7 goodput ledger) — this module says
**where the HBM goes**, per compiled executable:

- **buckets** (PJRT ``compiled.memory_analysis()``): argument / output /
  temp / alias / generated-code bytes. ``total_bytes`` is their sum by
  construction — the sums-to-total contract mirrors PR 7's
  sums-to-wall, kept explicit so the report tool can re-verify it.
- **live** (``utils/hlo_analysis.live_range_report``): the scheduled
  module's peak-live timeline, the top-K buffers live at the peak, and
  per-named-scope attribution (``by_scope`` sums to ``peak_live_bytes``
  exactly; "" collects unattributed values). The models thread
  ``jax.named_scope`` through their blocks, so the table names
  ``decoder.12/mlp/up`` instead of ``fusion.1847`` — OOM forensics that
  finally names the buffer that killed you.
- **contract**: the text model's argument/output reconstruction checked
  against the PJRT buckets (``io_err_frac``; the report tool and
  tests/test_memory_profile.py gate it at 2%).

Recorded ledgers land in a bounded in-process store, surface as gauges
``paddle_tpu_hbm_{args,temps,outputs,peak}_bytes{source,executable}``,
emit one ``memory_profile`` JSONL record each, and are snapshotted into
flight-recorder dumps + HeadroomGuard violation extras (the pre-OOM
black box carries the ledger of every live executable).

Producers: observability/programs.py (telemetry's analysis copy of
each TrainStep signature and each serve program, on its first call),
tools/memory_report.py (the registry-lane fingerprint + CI gate).
"""
from __future__ import annotations

import hashlib
import threading

from .registry import (enabled as _tel_enabled, log_step as _log_step,
                       registry as _registry)

__all__ = ["SCHEMA", "executable_ledger", "verify_ledger",
           "record_executable", "ledgers", "forensics", "sig_label",
           "reset"]

SCHEMA = "paddle_tpu.memory_profile/1"

# (bounded) ledger store: "source:executable" -> ledger dict. Bounded so
# a bucketed-prefill storm cannot grow host memory; eviction is FIFO —
# the newest executables are the ones an OOM dump needs.
_LOCK = threading.Lock()
_LEDGERS: dict = {}
_MAX_LEDGERS = 64

_BUCKET_ATTRS = (
    ("argument", "argument_size_in_bytes"),
    ("output", "output_size_in_bytes"),
    ("temp", "temp_size_in_bytes"),
    ("alias", "alias_size_in_bytes"),
    ("generated_code", "generated_code_size_in_bytes"),
)


def sig_label(sig):
    """Stable short label for an executable-cache signature tuple."""
    return hashlib.md5(repr(sig).encode()).hexdigest()[:10]


def _hlo_text_of(compiled):
    try:
        return compiled.runtime_executable().hlo_modules()[0].to_string()
    except Exception:
        return None


def executable_ledger(compiled, top_k=8, hlo_text=None):
    """Build the HBM ledger for one AOT-compiled executable.

    Always returns the PJRT buckets; the live-range section is None when
    the scheduled HLO is unavailable (interpreters, backends without
    runtime_executable). Never raises on analysis failure — a profiler
    must not take down the run it profiles."""
    ma = compiled.memory_analysis()
    buckets = {name: int(getattr(ma, attr, 0) or 0)
               for name, attr in _BUCKET_ATTRS}
    total = sum(buckets.values())
    # PJRT semantics (probed on this jaxlib): argument_size counts ALL
    # inputs including donated ones; alias_size books the donated bytes
    # AGAIN (they are both an input and an output). The full HBM bill of
    # one call therefore discounts the alias once: donated buffers serve
    # both sides of the call. This is the number HeadroomGuard budgeting
    # and the item-4 planner search over.
    peak = max(buckets["argument"] + buckets["output"] + buckets["temp"]
               + buckets["generated_code"] - buckets["alias"], 0)
    ledger = {
        "schema": SCHEMA,
        "buckets": buckets,
        "total_bytes": total,
        "peak_bytes": peak,
        "live": None,
        "contract": None,
    }
    text = hlo_text if hlo_text is not None else _hlo_text_of(compiled)
    if text:
        try:
            from ..utils.hlo_analysis import live_range_report
            live = live_range_report(text, top_k=top_k)
            ledger["live"] = live
            # argument_size already counts donated inputs (alias books
            # them a second time as outputs) — the header's parameter
            # list is the direct mirror
            errs = []
            for name, want, got in (
                    ("argument", buckets["argument"],
                     live["argument_bytes"]),
                    ("output", buckets["output"], live["output_bytes"])):
                errs.append({"bucket": name,
                             "pjrt_bytes": want, "hlo_bytes": got,
                             "err_bytes": abs(got - want),
                             "err_frac": round(abs(got - want)
                                               / max(want, 1), 6)})
            ledger["contract"] = {
                "io": errs,
                "io_err_frac": max(e["err_frac"] for e in errs),
            }
        except Exception:
            pass
    return ledger


def verify_ledger(ledger, tol=0.02, floor_bytes=256):
    """The sums-to-totals contract (same style as PR 7's sums-to-wall).
    Returns a list of problems; [] means the ledger honors it:

    - buckets sum to total_bytes within ``tol``;
    - live.by_scope sums to live.peak_live_bytes EXACTLY;
    - the HLO-text argument/output reconstruction matches the PJRT
      buckets within ``tol`` (when the live section exists).
      ``floor_bytes`` absorbs PJRT's per-output-leaf tuple metadata
      (~8 B/leaf, measured) so byte-small test modules don't fail a
      relative gate on constant overhead."""
    errs = []
    if not isinstance(ledger, dict) or "buckets" not in ledger:
        return ["not a ledger dict"]
    total = ledger.get("total_bytes", 0)
    s = sum(ledger["buckets"].values())
    if abs(s - total) > tol * max(total, 1):
        errs.append(f"buckets sum {s} != total_bytes {total}")
    live = ledger.get("live")
    if live:
        scoped = sum(live.get("by_scope", {}).values())
        if scoped != live.get("peak_live_bytes", 0):
            errs.append(f"by_scope sum {scoped} != peak_live_bytes "
                        f"{live.get('peak_live_bytes')}")
        contract = ledger.get("contract") or {}
        for e in contract.get("io", ()):
            if e["err_bytes"] > max(tol * e["pjrt_bytes"], floor_bytes):
                errs.append(f"hlo-vs-pjrt {e['bucket']} reconstruction "
                            f"drifted {e['err_bytes']} B "
                            f"(frac {e['err_frac']}) past "
                            f"max({tol} rel, {floor_bytes} B): {e}")
    return errs


def record_executable(source, executable, compiled, top_k=8):
    """Profile ``compiled`` and record the ledger under
    ``source:executable``: store for forensics, per-executable gauges,
    one JSONL record. Called once per compile (the compile already cost
    seconds; the profile costs milliseconds). Returns the ledger."""
    ledger = executable_ledger(compiled, top_k=top_k)
    key = f"{source}:{executable}"
    with _LOCK:
        _LEDGERS.pop(key, None)
        _LEDGERS[key] = ledger
        while len(_LEDGERS) > _MAX_LEDGERS:
            _LEDGERS.pop(next(iter(_LEDGERS)))
    if _tel_enabled():
        reg = _registry()
        labels = {"source": source, "executable": executable}
        b = ledger["buckets"]
        reg.gauge("paddle_tpu_hbm_args_bytes",
                  "Compiled-executable argument bytes (donated "
                  "inputs included)",
                  ("source", "executable")).set(b["argument"], **labels)
        reg.gauge("paddle_tpu_hbm_temps_bytes",
                  "Compiled-executable temp-allocation bytes",
                  ("source", "executable")).set(b["temp"], **labels)
        reg.gauge("paddle_tpu_hbm_outputs_bytes",
                  "Compiled-executable output bytes",
                  ("source", "executable")).set(b["output"], **labels)
        reg.gauge("paddle_tpu_hbm_peak_bytes",
                  "Compiled-executable full HBM bill "
                  "(args+outputs+temps+code, donated alias discounted)",
                  ("source", "executable")).set(ledger["peak_bytes"],
                                                **labels)
        live = ledger.get("live") or {}
        _log_step({"event": "memory_profile", "source": source,
                   "executable": executable,
                   "buckets": ledger["buckets"],
                   "total_bytes": ledger["total_bytes"],
                   "peak_bytes": ledger["peak_bytes"],
                   "peak_live_bytes": live.get("peak_live_bytes"),
                   "top_at_peak": live.get("top_at_peak")})
    return ledger


def ledgers():
    """Snapshot of the recorded ledgers ({source:executable -> ledger})."""
    with _LOCK:
        return dict(_LEDGERS)


def forensics(top_k=4):
    """Compact per-executable view for crash artifacts (flight-recorder
    dumps, HeadroomGuard violation extras): buckets, peak, and the
    top-K-at-peak table with scope attribution — small enough to embed
    in a dump written from a signal handler."""
    out = {}
    with _LOCK:
        items = list(_LEDGERS.items())
    for key, led in items:
        live = led.get("live") or {}
        out[key] = {
            "buckets": led["buckets"],
            "peak_bytes": led["peak_bytes"],
            "peak_live_bytes": live.get("peak_live_bytes"),
            "top_at_peak": [
                {k: t[k] for k in ("name", "bytes", "shape", "scope",
                                   "body_top") if k in t}
                for t in (live.get("top_at_peak") or [])[:top_k]],
            # the raw top is often unattributed parameters — the scoped
            # view names the LAYERS even then (drop the "" bucket)
            "by_scope": dict(list(
                (s, b) for s, b in (live.get("by_scope_total")
                                    or {}).items() if s)[:top_k]),
        }
    return out


def reset():
    with _LOCK:
        _LEDGERS.clear()
