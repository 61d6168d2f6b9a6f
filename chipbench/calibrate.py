"""Readings that a cell's limits are set from, taken on the chip at the
cell's own size: for each seed one short run of the cell, then the
comparison against the reference (the program's reading), against the
control (the reference in the nearest lower precision, put in the
program's place) and, for a training cell, against the half-batch fault
planted in the reference. One process for all seeds, because set-up is
most of a run. Not part of a benchmark run; `PERF.md` records what it read.

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--seconds 20] [--control fp8] [--no-program]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", default="fp8")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg = harness.load_json("configs", cell["config"] + ".json")
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("calibration reads the chip; JAX found no TPU")
    harness.enable_compile_cache()
    reference, adapter, kind = harness.load_modules(cfg, traffic)
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx = harness.Context(cfg, traffic, seed, args.seconds, False,
                              reference, adapter)
        session = kind.Session(ctx)
        out = session.run()
        session.release()
        jax.clear_caches()
        record = {"seed": seed, "end_to_end": out["end_to_end"],
                  "attempted": out["attempted"], "failed": out["failed"]}
        if traffic["kind"] == "train":
            record["program"] = rows_of(session.check())
            record["control_" + args.control] = rows_of(
                session.check(precision=args.control))
            keep = traffic["batch"] - traffic["batch"] // 2
            record["fault_half_batch"] = rows_of(
                session.check(rows=slice(0, keep)))
        else:
            record["program_and_control"] = rows_of(
                session.check(control=args.control))
        harness.say(**record)
        del session, ctx
        gc.collect()


def rows_of(rows):
    return {name: [value, detail] for name, value, _, detail in rows}


if __name__ == "__main__":
    main()
