"""Analysis records of the programs that run.

Telemetry never chooses the program: `TrainStep` and the serve loop call
the same jitted callables whatever `enabled()` says. The memory, roofline
and goodput layers need *an* executable of that program to read, so
`profile_program` compiles a copy of it that is never called. With
`FLAGS_compile_cache_dir` unset the copy and the call share one
executable in the process (no compile more than a plain process pays).
With it set the copy goes through `compile_cache.get_or_compile`, whose
hit / miss counters say whether a restart found the program on disk, and
the call's own executable is a retrieval from JAX's cache beside it.
"""
from __future__ import annotations

# imported here, not at the first analysis: FLAGS_compile_cache_dir takes
# effect where its module is imported, and both entry points import this one
from ..distributed.resilience import compile_cache
from . import memory_profile, roofline
from .attribution import modeled_exposed_seconds
from .tracing import span

__all__ = ["profile_program"]


def profile_program(records, key, source, label, jitted, args):
    """Telemetry's callers, before the first call of the program `key`
    names: analyse `jitted` as it will run on `args` (lowered before the
    call, which donates them) and keep the record in `records[key]`:
    {label (`label()`, asked for only here), executable, cache
    (`get_or_compile`'s), flops (cost_analysis), exposed_s (modeled
    exposed-collective seconds), hbm (`memory_profile` ledger), roofline
    (record)}; the last two also land in their modules' stores, gauges
    and JSONL under `source:label`. Returns it: None, with no second
    attempt, if the analysis failed (a profiler must not take down the
    run it profiles)."""
    if key in records:
        return records[key]
    rec = None
    try:
        label = label()
        with span(f"{source}:analyse", what=label):
            compiled, info = compile_cache.get_or_compile(
                jitted.lower(*args), tag=f"{source}:{label}")
        rec = {"label": label, "executable": compiled,
               "cache": info["cache"],
               "flops": roofline.cost_analysis_flops(compiled) or 0.0,
               "exposed_s": modeled_exposed_seconds(compiled),
               "hbm": None, "roofline": None}
        for name, layer in (("hbm", memory_profile),
                            ("roofline", roofline)):
            try:
                rec[name] = layer.record_executable(source, label,
                                                    compiled)
            except Exception:
                pass
    except Exception:
        pass
    records[key] = rec
    return rec
