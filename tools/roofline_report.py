#!/usr/bin/env python
"""Roofline report: per-op bound-class attribution on the llama train
lane, contract- and drift-gated (the CI face of
observability/roofline.py — ISSUE 16).

Builds the tiny llama train lane (llama_tiny, 2 decoder layers, 3
telemetry-enabled steps — each signature's analysis executable records
its roofline on the signature's first call), then gates every recorded
executable:

- **telescoping** (roofline.verify_record): bound-class seconds sum to
  the modeled step wall within --tol (default 2%), class fractions sum
  to 1, the per-scope MFU-gap waterfall reconciles to the same wall —
  the repo's sums-to-X contract at op granularity;
- **cost-model drift** (roofline.drift_vs_cost_model): the recorded
  rates must equal distributed/auto_tuner/cost_model.py's chip
  constants and every collective row must re-price through the SAME
  estimate_collective_seconds ring model — planner predictions and
  roofline measurements cannot silently disagree;
- **attribution**: the top-5 ops by roofline-gap seconds carry scope
  paths, and at least one resolves to a real named scope (a report full
  of "" scopes means the PR-9 threading broke).

Prints ONE JSON line (the artifact-gated pattern of overlap_evidence /
step_attribution / memory_report) naming the top-5 gap ops with their
scope paths — the "write the int8 kernel HERE" list.

`--verify-teeth` proves the gates have teeth on a REAL record (the
PR-13 mutation pattern): a dropped waterfall bucket, a perturbed class
fraction, a drifted rate, and a mispriced collective row must each
trip their gate; rc=1 from the unmutated record failing or any
mutation NOT tripping.

Usage:
    python tools/roofline_report.py [--tol 0.02] [--out artifact.json]
    python tools/roofline_report.py --verify-teeth
    tools/run_ci.sh roofline                      # the CI tier
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCHEMA = "paddle_tpu.roofline_report/1"


def build_train_records(steps=3):
    """Run the tiny llama train lane with telemetry on; returns the
    roofline records its analysis compiles stored ({source:executable
    -> record})."""
    import numpy as np
    import paddle_tpu as pt
    import paddle_tpu.observability as obs
    from paddle_tpu.observability import roofline as rl
    from paddle_tpu.models import (LlamaForCausalLM,
                                   LlamaPretrainingCriterion)
    from paddle_tpu.models.llama import llama_tiny

    obs.reset()
    rl.reset()
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
    try:
        for _ in range(steps):
            step((ids,), (lab,))
    finally:
        obs.disable()
    return rl.records()


def gate_records(records, tol=0.02):
    """(report dict, violations list) over the recorded rooflines —
    pure given the records; the teeth drive it with mutants."""
    from paddle_tpu.observability import roofline as rl

    violations = []
    per_exec = {}
    all_ops = []
    for key, rec in sorted(records.items()):
        for p in rl.verify_record(rec, tol=tol):
            violations.append({"executable": key, "kind": "contract",
                               "detail": p})
        for p in rl.drift_vs_cost_model(rec, tol=tol):
            violations.append({"executable": key, "kind": "drift",
                               "detail": p})
        ops = sorted(rec.get("top_ops", ()),
                     key=lambda o: (-o["gap_s"], o["name"]))
        if not ops:
            violations.append({"executable": key, "kind": "no_ops"})
        if not any(s for s in rec.get("by_scope", {})):
            # the waterfall resolves NO named scope: the PR-9 threading
            # or scope_of_op_name resolution broke (the top gap ops can
            # legitimately be root-scoped optimizer fusions, but a
            # model executable with a scope-less waterfall is a
            # regression)
            violations.append({"executable": key, "kind": "no_scopes"})
        for o in ops:
            all_ops.append(dict(o, executable=key))
        frac = rec.get("class_time_frac", {})
        per_exec[key] = {
            "total_modeled_s": rec["total_modeled_s"],
            "modeled_mfu": round(rec["modeled_mfu"], 6),
            "mfu_gap_s": rec["mfu_gap_s"],
            "class_time_frac": {c: round(float(frac.get(c, 0.0)), 6)
                                for c in rl.CLASSES},
            "hbm_bound_flops_frac": round(
                rec["hbm_bound_flops_frac"], 6),
            "flops_drift_frac": rec.get("flops_drift_frac"),
            "scopes": sorted(rec.get("by_scope", {})),
        }
    top5 = sorted(all_ops, key=lambda o: (-o["gap_s"], o["name"]))[:5]
    top5 = [{"executable": o["executable"], "name": o["name"],
             "op": o["op"], "scope": o["scope"], "class": o["class"],
             "seconds": o["seconds"], "gap_s": o["gap_s"]}
            for o in top5]
    # the actionable layer view: named-scope waterfall buckets ranked
    # by summed gap seconds across executables ("" = root: optimizer /
    # unscoped glue)
    scope_gap = {}
    for rec in records.values():
        for s, v in rec.get("by_scope", {}).items():
            acc = scope_gap.setdefault(s, {"gap_s": 0.0, "seconds": 0.0,
                                           "bound": v.get("bound")})
            acc["gap_s"] += float(v.get("gap_s", 0.0))
            acc["seconds"] += float(v.get("seconds", 0.0))
    top_scopes = [
        {"scope": s, "gap_s": round(v["gap_s"], 9),
         "seconds": round(v["seconds"], 9), "bound": v["bound"]}
        for s, v in sorted(scope_gap.items(),
                           key=lambda kv: (-kv[1]["gap_s"], kv[0]))[:5]]
    ok = bool(records) and not violations
    report = {"metric": "roofline_report", "schema": SCHEMA,
              "executables": per_exec,
              "top_gap_ops": top5,
              "top_gap_scopes": top_scopes,
              "tolerance": tol,
              "violations": violations[:20],
              "note": "per-op roofline pricing vs cost_model chip "
                      "rates; gap_s = modeled seconds above the op's "
                      "MXU-ideal time — the biggest gap_s is where the "
                      "next kernel goes",
              "pass": ok}
    return report, violations


def verify_teeth(tol=0.02):
    """Every gate must bite on a mutated REAL record. Returns (ok,
    detail lines)."""
    import copy
    records = build_train_records(steps=2)
    base_report, base_viol = gate_records(records, tol=tol)
    out = []
    ok = True
    if not base_report["pass"]:
        return False, [f"FAIL unmutated lane does not pass: "
                       f"{base_viol[:3]}"]
    out.append("PASS unmutated llama train lane passes all gates")
    key = sorted(records)[0]

    def mutate(name, kinds, fn):
        nonlocal ok
        mut = copy.deepcopy(records)
        fn(mut[key])
        _, viol = gate_records(mut, tol=tol)
        hit = [v for v in viol if v.get("kind") in kinds]
        if hit:
            out.append(f"PASS {name} trips {sorted({v['kind'] for v in hit})}")
        else:
            out.append(f"FAIL {name} NOT caught (violations: {viol[:3]})")
            ok = False

    # 1. a dropped waterfall bucket breaks sums-to-wall (drop the
    # largest — a sub-slack sliver would survive the tolerance)
    mutate("dropped by_scope bucket", {"contract"},
           lambda r: r["by_scope"].pop(max(
               r["by_scope"], key=lambda s: r["by_scope"][s]["seconds"])))
    # 2. a perturbed class fraction breaks sums-to-1
    mutate("perturbed class_time_frac", {"contract"},
           lambda r: r["class_time_frac"].update(
               hbm=r["class_time_frac"]["hbm"] + 0.1))
    # 3. a hardcoded rate drifts from cost_model's constants
    mutate("drifted hbm rate", {"drift"},
           lambda r: r["rates"].update(hbm_bytes_per_sec=1e12))
    # 4. a collective row priced off the shared ring model
    mutate("mispriced collective row", {"drift"},
           lambda r: r.setdefault("collectives", []).append(
               {"name": "all-reduce.teeth", "kind": "all-reduce",
                "bytes": 1 << 20, "group_size": 4, "trips": 1,
                "seconds": 1.0}))
    return ok, out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--tol", type=float, default=0.02,
                   help="telescoping/drift tolerance fraction "
                        "(default 0.02)")
    p.add_argument("--steps", type=int, default=3,
                   help="telemetry-enabled train steps (default 3)")
    p.add_argument("--out", default=None,
                   help="also write the report JSON to this path")
    p.add_argument("--verify-teeth", action="store_true",
                   help="prove the gates catch mutated records "
                        "(rc=1 when any mutation slips through)")
    args = p.parse_args(argv)

    if args.verify_teeth:
        ok, lines = verify_teeth(tol=args.tol)
        for line in lines:
            print(f"[roofline-teeth] {line}", file=sys.stderr)
        print(json.dumps({"metric": "roofline_report_teeth",
                          "checks": lines, "pass": ok}))
        return 0 if ok else 1

    records = build_train_records(steps=args.steps)
    report, _ = gate_records(records, tol=args.tol)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
    print(json.dumps(report))
    return 0 if report["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
