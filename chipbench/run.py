"""The benchmark's one command.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of `BENCHMARK.json` on the machine it is started on and
prints, as the last line of standard output, one JSON object with the
keys `correct`, `attempted`, `failed`, `metrics`, `device`, with
`--trace 1` also `breakdown`, and last `compared` (each number that
decided `correct` beside its limit). Earlier lines, JSON too, name the
device, the cell's cuts, compile seconds and cache hits, and peak bytes.

Everything that belongs to one cell is found by name: the configuration
(`configs/<config>.json`) with its reference (`reference/<reference>.py`)
and adapter (`adapters/<adapter>.py`), the traffic (`traffic/<traffic>.json`)
whose `kind` picks the driver loop (`kinds/<kind>.py`), the cell's limits
(`cells/<workload>.json`) and one reader per per-layer metric
(`metrics/<metric>.py`). Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_SECONDS = 12      # a traced window is at most this long
TRACE_DIR = os.path.join(HERE, ".trace")
PROGRAM_FILES = ("batcher.py", "paged_decode.py", "train_step.py",
                 "scheduler.py", "train.py", "serve.py")


def say(**record):
    print(json.dumps(record), flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


def load_metric_reader(name):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class CompileMeter:
    """What JAX reports about its own compiles: when each backend compile
    (for a persistent-cache hit, its retrieval) ended and how long it
    took, cache hits and misses."""

    def __init__(self):
        import jax
        self.compiles = []          # (ended at, seconds, program)
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._secs)
        jax.monitoring.register_event_listener(self._event)

    def _secs(self, event, seconds, fun_name="", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.perf_counter(), seconds, fun_name))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def seconds(self):
        return sum(s for _, s, _ in self.compiles)

    def between(self, t0, t1):
        return [(s, name) for at, s, name in self.compiles if t0 <= at <= t1]


class Context:
    """What a driver loop gets from the harness."""

    def __init__(self, cfg, traffic, seed, seconds, trace, reference,
                 adapter):
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self.trace = bool(trace)
        self.seconds = min(seconds, TRACE_SECONDS) if trace else seconds
        self.reference, self.adapter = reference, adapter
        self.marks = {}
        self.window = [None, None]
        self._span = None

    def mark(self, name):
        self.marks[name] = time.perf_counter() - T_PROCESS

    def annotate(self, name):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def window_open(self):
        if self.trace:
            import jax
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR)
            self._span = jax.profiler.TraceAnnotation("chipbench.window")
            self._span.__enter__()
        self.window[0] = time.perf_counter()
        return self.window[0]

    def window_close(self):
        self.window[1] = time.perf_counter()
        if self.trace:
            import jax
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return self.window[1]


class View:
    """What a per-layer metric's reader gets."""

    def __init__(self, ctx, observed, summary, meter, peak):
        self.cfg, self.traffic = ctx.cfg, ctx.traffic
        self.observed, self.summary = observed, summary
        self.marks, self.window = ctx.marks, tuple(ctx.window)
        self.meter, self.peak = meter, peak


def enable_compile_cache():
    """JAX's persistent cache at `JAX_COMPILATION_CACHE_DIR` if that is
    set, else at a fixed path inside the checkout; every program is kept,
    however short its compile, so a second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def memory_of(device):
    stats = device.memory_stats() or {}
    return {"peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "peak_bytes_reserved": stats.get("peak_bytes_reserved"),
            "bytes_limit": stats.get("bytes_limit")}


def decide(rows, limits):
    """`correct` from the compared numbers: every one finite and at or
    under its limit (a row whose limit key is None is read, not
    compared). Returns (correct, {name: {"value", "limit"}})."""
    rows = [r for r in rows if r[2] is not None]
    compared, correct = {}, bool(rows)
    for name, value, limit_key, _detail in rows:
        limit = limits.get(limit_key)
        ok = limit is not None and value == value and value <= limit
        correct = correct and ok
        compared[name] = {"value": value, "limit": limit}
    return correct, compared


def load_modules(cfg, traffic):
    """(reference, adapter, driver loop) that a configuration and a
    traffic file name."""
    return (importlib.import_module("chipbench.reference." + cfg["reference"]),
            importlib.import_module("chipbench.adapters." + cfg["adapter"]),
            importlib.import_module("chipbench.kinds." + traffic["kind"]))


def run_cell(cfg, traffic, limits, end_to_end, per_layer, seed, seconds,
             trace, devices, peak):
    """Everything of a run after the look for a chip. `end_to_end` and
    `per_layer` are the cell's metric entries of `BENCHMARK.json`; the
    first are reported without `trace`, the second read with it. Returns
    the result object."""
    import jax
    reference, adapter, kind = load_modules(cfg, traffic)
    meter = CompileMeter()
    ctx = Context(cfg, traffic, seed, seconds, trace, reference, adapter)
    ctx.marks["chip_reached"] = time.perf_counter() - T_PROCESS
    session = kind.Session(ctx)
    out = session.run()
    t0, t1 = ctx.window
    setup_s = t0 - T_PROCESS
    memory = [memory_of(d) for d in devices]
    fullest = max(memory, key=lambda m: m["peak_bytes_in_use"] or 0)
    in_window = meter.between(t0, t1)
    say(phase="window", window_s=t1 - t0, setup_s=setup_s, marks=ctx.marks,
        compile_s=meter.seconds(), cache_hits=meter.hits,
        cache_misses=meter.misses, compiles_in_window=in_window,
        memory=memory, observed=out["observed"])

    # free the program's state and unload its executables (a loaded TPU
    # program keeps its scratch memory reserved) before the reference runs
    session.release()
    jax.clear_caches()
    t_check = time.perf_counter()
    rows = session.check()
    correct, compared = decide(rows, limits)
    correct = correct and out["failed"] == 0 and out["attempted"] > 0
    say(phase="check", seconds=time.perf_counter() - t_check,
        rows=[[n, v, d] for n, v, _, d in rows])

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": fullest["peak_bytes_in_use"]}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        from chipbench import trace as trace_mod
        t_read = time.perf_counter()
        summary = trace_mod.summarize(
            trace_mod.load(trace_mod.find_xplane(TRACE_DIR)),
            "chipbench.window", gap_files=PROGRAM_FILES, min_gap_s=1e-4)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        view = View(ctx, out["observed"], summary, meter, peak)
        metrics = {}
        for entry in per_layer:
            value = load_metric_reader(entry["name"]).read(view)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
        device["busy_s"], device["window_s"] = summary.busy_s, summary.window_s
        result.update(metrics=metrics, device=device,
                      breakdown=trace_mod.breakdown(summary))
        say(phase="trace", read_s=time.perf_counter() - t_read)
    else:
        values = dict(out["end_to_end"], setup_s=setup_s)
        metrics = {entry["name"]: {"value": values[entry["name"]],
                                   "unit": entry["unit"]}
                   for entry in end_to_end}
        result.update(metrics=metrics, device=device)
    result["compared"] = compared
    for name, value, limit_key, detail in rows:
        what = "read, not compared" if limit_key is None else \
            f"limit {limits.get(limit_key)!r}"
        print(f"compared {name} = {value!r} {what} ({detail})",
              file=sys.stderr)
    print(f"correct = {correct}", file=sys.stderr, flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        sys.exit(f"no workload {args.workload!r} in BENCHMARK.json; it has "
                 f"{sorted(cells)}")
    cell = cells[args.workload]
    cfg = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    limits = load_json("cells", cell["name"] + ".json")["limits"]

    def of_cell(metric):
        return cell["name"] in metric.get("workloads", [cell["name"]])
    end_to_end = [m for m in bench["end_to_end"] if of_cell(m)]
    per_layer = [m for m in bench["per_layer"] if of_cell(m)]

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chipbench needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < cell["chips"]:
        sys.exit(f"{cell['name']} needs {cell['chips']} chips; JAX found "
                 f"{len(devices)}")
    devices = devices[:cell["chips"]]
    from chipbench.peaks import peaks_for
    peak = peaks_for(devices[0].device_kind)
    cache_dir = enable_compile_cache()
    import jax.numpy as jnp
    jnp.zeros(1).block_until_ready()
    say(phase="device", platform=devices[0].platform,
        device_kind=devices[0].device_kind, device_count=len(devices),
        jax=jax.__version__, workload=cell["name"], config=cell["config"],
        traffic=cell["traffic"], seed=args.seed, seconds=args.seconds,
        trace=args.trace, reduced=cfg["reduced"],
        published=cfg.get("published"), assumed=cfg.get("assumed"),
        compile_cache=cache_dir, peaks=peak)

    result = run_cell(cfg, traffic, limits, end_to_end, per_layer, args.seed,
                      args.seconds, args.trace, devices, peak)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
