#!/usr/bin/env python
"""Continuous perf-regression ledger over the bench telemetry lines
(ISSUE 16 — the BENCH_r*.json trajectory as an enforced gate).

Every `bench.py` / `benchmarks/*` run prints JSON metric lines
(`{"metric": ..., "value": ...}`). This tool flattens those lines into
one schema-versioned history row per run and appends it to
`tools/artifacts/bench_history.jsonl`; with `--gate` it first checks
the new run against the ROLLING BEST of its (lane, platform) history —
tolerance-banded and per-metric direction-aware:

- **direction registry**: throughput/goodput/accept-rate metrics must
  not drop, latency/byte-ratio metrics must not rise; metrics with no
  registered or inferable direction are record-only (a new metric never
  gates until someone declares what better means);
- **platform keying**: rows carry platform "tpu" or "cpu-smoke"
  (PT_BENCH_SMOKE) — a CPU smoke run NEVER gates against TPU history,
  and non-tpu platforms get a 10x tolerance band (CPU wall-clock noise
  only trips on catastrophic, >~2x, regressions);
- **rolling best**: the bound is the best value ever recorded for the
  metric in this (lane, platform) — hand-curated snapshots can go
  stale, the ledger cannot.

`--import-bench-r` seeds the ledger from the repo's committed
BENCH_r*.json artifacts ({n, cmd, rc, tail, parsed} — the tail holds
the metric lines), so round 1's 16,668.3 tok/s → round 5's 19,232.7
tok/s trajectory is the opening history. `--verify-teeth` proves the
gate bites (PR-13 style): a planted slower row must rc=1, an improved
row must pass, and direction-awareness must hold both ways.

Usage:
    python bench.py | python tools/bench_history.py --append - \\
        --lane train --gate
    python tools/bench_history.py --import-bench-r
    python tools/bench_history.py --verify-teeth
    tools/run_ci.sh roofline                      # the CI tier

Prints ONE JSON line; exit 0 iff no gated metric regressed. Stdlib
only — the ledger must work on a bare checkout.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

SCHEMA = "paddle_tpu.bench_history/1"

DEFAULT_HISTORY = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "artifacts", "bench_history.jsonl")

# -- direction registry -------------------------------------------------------
# explicit full flattened names first; the suffix heuristics catch the
# conventional spellings; anything else is record-only. "higher" means
# a drop below best*(1-tol) regresses; "lower" means a rise above
# best*(1+tol) does.
DIRECTIONS = {
    "llama_train_tokens_per_sec_per_chip": "higher",
    "serving_load_telemetry.goodput_tokens_per_sec": "higher",
    "serving_load_telemetry.slo_attainment": "higher",
    "serving_load_telemetry.p99_ttft_s": "lower",
    "serving_load_telemetry.p99_tpot_s": "lower",
    # serving lane (ISSUE 18): prefix-cache efficacy — more prompt
    # tokens served from mapped blocks, faster warm first tokens
    "serving_load_telemetry.cache_hit_ratio": "higher",
    "serving_load_telemetry.p50_ttft_warm_s": "lower",
    "llama_paged_kv_quant_hbm_ratio.kv_hbm_bytes_ratio": "lower",
    # long-context serving sweep (ISSUE 19): decode throughput up,
    # warm AND cold first tokens down — p50 over the context points
    # (p50_ttft_* is not covered by the suffix heuristics, which only
    # know the p99 spellings)
    "long_context_serving_summary.tok_s": "higher",
    "long_context_serving_summary.p50_ttft_warm_s": "lower",
    "long_context_serving_summary.p50_ttft_cold_s": "lower",
    "llama_spec_decode.accept_rate": "higher",
    "train_step_telemetry.checkpoint_async_exposed_s": "lower",
    "train_step_telemetry.recompiles": "lower",
    # the serve loop's hand-overs: the share of the wall in which the
    # loop knew the device's queue empty (the step ledger's host_gap,
    # fed by `serve:starved` since ISSUE 39: admissions count, which
    # the `host_gap_frac` of older rows, an estimate between chunks,
    # left out; that name is record-only now) and host->device
    # batch-state uploads per chunk; neither spelling is covered by the
    # suffix heuristics
    "serving_load_telemetry.starved_frac": "lower",
    "serving_load_telemetry.h2d_uploads_per_chunk": "lower",
    "llama_paged_request_latency.starved_frac": "lower",
    "llama_paged_request_latency.h2d_uploads_per_chunk": "lower",
}
# metrics whose rolling best can legitimately sit at 0.0 (a pipelined
# run with zero measured device-idle): a purely multiplicative band
# around a zero best flags ANY nonzero jitter as a regression, so
# these carry a small absolute slack on top of the tolerance band
ABS_SLACK = {
    "serving_load_telemetry.starved_frac": 0.01,
    "llama_paged_request_latency.starved_frac": 0.01,
}
_HIGHER_SUFFIXES = ("tokens_per_sec", "tokens_per_sec_per_chip",
                    "goodput_tokens_per_sec", "imgs_per_sec",
                    "accept_rate", "slo_attainment", "mfu_percent",
                    "step_ratio", "speedup")
_LOWER_SUFFIXES = ("p99_ttft_s", "p99_tpot_s", "p99_latency_s",
                   "latency_s", "kv_hbm_bytes_ratio", "hbm_ratio",
                   "bytes_ratio", "exposed_s", "recompiles")


def direction_of(name):
    """'higher' | 'lower' | None (record-only) for one flattened
    metric name."""
    if name in DIRECTIONS:
        return DIRECTIONS[name]
    leaf = name.rsplit(".", 1)[-1]
    for suf in _HIGHER_SUFFIXES:
        if leaf == suf or leaf.endswith("_" + suf):
            return "higher"
    for suf in _LOWER_SUFFIXES:
        if leaf == suf or leaf.endswith("_" + suf):
            return "lower"
    return None


def _numeric(v):
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def flatten_lines(lines):
    """Flatten bench stdout into {flat name: value}: every JSON line
    with a "metric" key contributes metric (its "value") plus
    metric.field for the other top-level numerics (one nested dict
    level deep: metric.field.subfield)."""
    metrics = {}
    for line in lines:
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            d = json.loads(line)
        except ValueError:
            continue
        base = d.get("metric")
        if not isinstance(base, str):
            continue
        if _numeric(d.get("value")):
            metrics[base] = float(d["value"])
        for k, v in d.items():
            if k in ("metric", "value", "unit", "schema"):
                continue
            if _numeric(v):
                metrics[f"{base}.{k}"] = float(v)
            elif isinstance(v, dict):
                for k2, v2 in v.items():
                    if _numeric(v2):
                        metrics[f"{base}.{k}.{k2}"] = float(v2)
    return metrics


def default_platform():
    """cpu-smoke under the smoke harness / a CPU jax, else tpu."""
    if os.environ.get("PT_BENCH_SMOKE"):
        return "cpu-smoke"
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        return "cpu-smoke"
    return "tpu"


def load_history(path):
    rows = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    r = json.loads(line)
                except ValueError:
                    continue
                if isinstance(r, dict) and r.get("schema") == SCHEMA:
                    rows.append(r)
    except OSError:
        pass
    return rows


def append_row(path, row):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")


def rolling_best(history, lane, platform):
    """{metric: best value} over rows of this (lane, platform), using
    each metric's direction ('best' is max for higher, min for lower;
    directionless metrics are omitted — nothing to gate)."""
    best = {}
    for r in history:
        if r.get("lane") != lane or r.get("platform") != platform:
            continue
        for name, v in (r.get("metrics") or {}).items():
            d = direction_of(name)
            if d is None or not _numeric(v):
                continue
            if name not in best:
                best[name] = float(v)
            elif d == "higher":
                best[name] = max(best[name], float(v))
            else:
                best[name] = min(best[name], float(v))
    return best


def gate_row(history, row, tol=0.05):
    """Regression violations of ``row`` against the rolling best of its
    (lane, platform) history. Non-tpu platforms widen the band 10x —
    CPU smoke wall-clock only fails on catastrophic regressions. Pure
    function; the teeth drive it with planted rows."""
    platform = row.get("platform", "tpu")
    if platform != "tpu":
        tol = tol * 10
    best = rolling_best(history, row.get("lane"), platform)
    violations = []
    for name, v in (row.get("metrics") or {}).items():
        d = direction_of(name)
        b = best.get(name)
        if d is None or b is None or not _numeric(v):
            continue
        slack = ABS_SLACK.get(name, 0.0)
        if d == "higher":
            bound = b * (1.0 - tol) - slack
            bad = v < bound and (b - v) > 1e-12
        else:
            bound = b * (1.0 + tol) + slack
            bad = v > bound and (v - b) > 1e-12
        if bad:
            violations.append({"metric": name, "direction": d,
                               "value": v, "rolling_best": b,
                               "bound": round(bound, 9),
                               "tol": tol})
    return violations


def build_row(lines, lane, platform, run):
    return {"schema": SCHEMA, "run": run, "lane": lane,
            "platform": platform, "metrics": flatten_lines(lines)}


def import_bench_r(pattern, history_path):
    """Seed the ledger from the committed BENCH_r*.json round artifacts
    ({n, cmd, tail, ...}): every metric line in each tail becomes part
    of that round's row (lane train, platform tpu — these were real
    device runs). Returns the rows appended; rounds already present
    (same run label) are skipped so the import is idempotent."""
    history = load_history(history_path)
    seen = {r.get("run") for r in history}
    appended = []
    for path in sorted(glob.glob(pattern)):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        n = doc.get("n")
        run = f"bench_r{int(n):02d}" if isinstance(n, int) else \
            os.path.splitext(os.path.basename(path))[0]
        if run in seen:
            continue
        lines = str(doc.get("tail", "")).splitlines()
        row = build_row(lines, lane="train", platform="tpu", run=run)
        if not row["metrics"]:
            continue
        append_row(history_path, row)
        appended.append(row)
    return appended


def verify_teeth(tol=0.05):
    """The gate must bite both ways on planted rows. Returns (ok,
    detail lines)."""
    out, ok = [], True
    hist = [build_row(['{"metric": "llama_train_tokens_per_sec_per_chip"'
                       ', "value": 19232.7}',
                       '{"metric": "serving_load_telemetry", "value": 1,'
                       ' "p99_tpot_s": 0.05}'],
                      lane="train", platform="tpu", run="r1")]

    def check(name, row, want_trip):
        nonlocal ok
        viol = gate_row(hist, row, tol=tol)
        hit = bool(viol)
        if hit == want_trip:
            out.append(f"PASS {name} -> "
                       f"{'trips' if hit else 'passes'}"
                       + (f" {viol[0]['metric']}" if hit else ""))
        else:
            out.append(f"FAIL {name} expected "
                       f"{'trip' if want_trip else 'pass'}, got {viol}")
            ok = False

    # a planted slower row must rc=1 (the acceptance criterion)
    check("planted 20% tok/s regression",
          build_row(['{"metric": "llama_train_tokens_per_sec_per_chip",'
                     ' "value": 15386.2}'],
                    "train", "tpu", "r2"), True)
    # direction-awareness: p99 latency RISING trips ...
    check("planted p99 latency rise",
          build_row(['{"metric": "serving_load_telemetry", "value": 1,'
                     ' "p99_tpot_s": 0.2}'],
                    "train", "tpu", "r2"), True)
    # ... and a faster run sails through (higher tok/s, lower p99)
    check("improved run",
          build_row(['{"metric": "llama_train_tokens_per_sec_per_chip",'
                     ' "value": 20001.0}',
                     '{"metric": "serving_load_telemetry", "value": 1,'
                     ' "p99_tpot_s": 0.04}'],
                    "train", "tpu", "r2"), False)
    # within-band jitter is not a regression
    check("within-tolerance jitter",
          build_row(['{"metric": "llama_train_tokens_per_sec_per_chip",'
                     f' "value": {19232.7 * (1 - tol / 2)}}}'],
                    "train", "tpu", "r2"), False)
    # platform keying: the same slow numbers on cpu-smoke gate against
    # NO tpu history (no cpu rows exist -> nothing to compare)
    check("cpu-smoke row vs tpu-only history",
          build_row(['{"metric": "llama_train_tokens_per_sec_per_chip",'
                     ' "value": 10.0}'],
                    "train", "cpu-smoke", "r2"), False)
    # 10x band off-tpu: -30% survives where tpu would trip...
    cpu_hist = [build_row(['{"metric": '
                           '"llama_train_tokens_per_sec_per_chip", '
                           '"value": 100.0}'],
                          "train", "cpu-smoke", "r1")]
    v = gate_row(cpu_hist, build_row(
        ['{"metric": "llama_train_tokens_per_sec_per_chip", '
         '"value": 70.0}'], "train", "cpu-smoke", "r2"), tol=tol)
    if v:
        out.append(f"FAIL cpu-smoke 30% drop should survive 10x band: {v}")
        ok = False
    else:
        out.append("PASS cpu-smoke 30% drop survives the widened band")
    # ... a catastrophic 60% drop does not
    v = gate_row(cpu_hist, build_row(
        ['{"metric": "llama_train_tokens_per_sec_per_chip", '
         '"value": 40.0}'], "train", "cpu-smoke", "r2"), tol=tol)
    if v:
        out.append("PASS cpu-smoke catastrophic drop trips")
    else:
        out.append("FAIL cpu-smoke catastrophic drop NOT caught")
        ok = False
    # a directionless metric never gates
    check("directionless metric is record-only",
          build_row(['{"metric": "serving_load_telemetry", "value": 1,'
                     ' "pool_blocks": 1}'], "train", "tpu", "r2"),
          False)
    return ok, out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--append", default=None, metavar="FILE",
                   help="bench stdout to flatten+append ('-' = stdin)")
    p.add_argument("--lane", default=None,
                   help="history lane key (train | decode | "
                        "servingload | ...; required with --append)")
    p.add_argument("--platform", default=None,
                   help="history platform key (default: cpu-smoke "
                        "under PT_BENCH_SMOKE/JAX_PLATFORMS=cpu, else "
                        "tpu)")
    p.add_argument("--run", default=None,
                   help="run label (default: r<history length + 1>)")
    p.add_argument("--gate", action="store_true",
                   help="rc=1 when a direction-registered metric "
                        "regresses past the rolling best's band")
    p.add_argument("--tol", type=float, default=0.05,
                   help="gate band fraction (default 0.05; non-tpu "
                        "platforms widen 10x)")
    p.add_argument("--history", default=DEFAULT_HISTORY,
                   help=f"ledger path (default {DEFAULT_HISTORY})")
    p.add_argument("--import-bench-r", nargs="?", metavar="GLOB",
                   const=os.path.join(os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))), "BENCH_r*.json"),
                   default=None,
                   help="seed the ledger from the committed round "
                        "artifacts (idempotent)")
    p.add_argument("--verify-teeth", action="store_true",
                   help="prove the gate catches planted regressions "
                        "(rc=1 when any check fails)")
    args = p.parse_args(argv)

    if args.verify_teeth:
        ok, lines = verify_teeth(tol=args.tol)
        for line in lines:
            print(f"[bench-history-teeth] {line}", file=sys.stderr)
        print(json.dumps({"metric": "bench_history_teeth",
                          "checks": lines, "pass": ok}))
        return 0 if ok else 1

    if args.import_bench_r:
        rows = import_bench_r(args.import_bench_r, args.history)
        print(json.dumps({"metric": "bench_history_import",
                          "schema": SCHEMA,
                          "appended": [r["run"] for r in rows],
                          "history": args.history, "pass": True}))
        return 0

    if not args.append:
        p.error("one of --append / --import-bench-r / --verify-teeth "
                "is required")
    if not args.lane:
        p.error("--append requires --lane")
    if args.append == "-":
        lines = sys.stdin.read().splitlines()
    else:
        with open(args.append) as f:
            lines = f.read().splitlines()
    history = load_history(args.history)
    platform = args.platform or default_platform()
    run = args.run or f"r{len(history) + 1}"
    row = build_row(lines, lane=args.lane, platform=platform, run=run)
    if not row["metrics"]:
        print(json.dumps({"metric": "bench_history_append",
                          "error": "no metric lines found",
                          "pass": False}))
        return 1
    violations = gate_row(history, row, tol=args.tol) if args.gate \
        else []
    # the row is appended even when it regresses: the ledger records
    # the trajectory, the rc records the verdict
    append_row(args.history, row)
    ok = not violations
    print(json.dumps({"metric": "bench_history_append",
                      "schema": SCHEMA, "run": run, "lane": args.lane,
                      "platform": platform,
                      "metrics_recorded": len(row["metrics"]),
                      "gated": bool(args.gate),
                      "violations": violations[:20],
                      "history": args.history,
                      "pass": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
