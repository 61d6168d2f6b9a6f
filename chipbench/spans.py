"""The program's own spans inside the window, for the per-layer metrics
of source `program_span`.

The program's tracer (`paddle_tpu.observability.tracing`) records a span
while a JAX profiler session is recording, into the trace and into a
ring in this process on `time.perf_counter_ns()`. A `--trace 1` run
records from `window_open()` to `window_close()`, and `view.window` is
those two instants on `time.perf_counter()`, so the ring's spans clipped
to the window are the window's. A program that records no span (the
tracer of an older commit follows only its own switch) leaves the ring
empty: `in_window` then returns None and a reader returns None, never 0.

Everything below `in_window` is plain arithmetic on
`{"id", "parent", "name", "start", "end", "meta"}` (seconds), so
`chipbench/tests/test_spans.py` can check it on a hand-made list.
"""
from __future__ import annotations

from chipbench import trace


def clip(spans, t0, t1):
    """`spans` cut to [t0, t1]; one that lies outside is left out."""
    out = []
    for s in spans:
        a, b = max(s["start"], t0), min(s["end"], t1)
        if b > a:
            out.append(dict(s, start=a, end=b))
    return out


def from_ring(records):
    """The tracer's `tail()` records (`t0_ns`, `dur_ns`) as spans."""
    return [{"id": r.get("id"), "parent": r.get("parent"), "name": r["name"],
             "start": r["t0_ns"] * 1e-9,
             "end": (r["t0_ns"] + r["dur_ns"]) * 1e-9,
             "meta": r.get("meta") or {}} for r in records]


def in_window(view):
    """The program's spans clipped to the window, or None if it recorded
    none there."""
    try:
        from paddle_tpu.observability import tracing
        records = tracing.tail()
    except Exception:
        return None
    return clip(from_ring(records), *view.window) or None


def durations(spans, name):
    return [s["end"] - s["start"] for s in spans if s["name"] == name]


def seconds(spans, name):
    return sum(durations(spans, name))


def self_seconds(spans, name):
    """The time of the spans called `name` less what their children
    cover (children that overlap are counted once)."""
    children = {}
    for s in spans:
        if s["parent"] is not None:
            children.setdefault(s["parent"], []).append(s)
    total = 0.0
    for s in spans:
        if s["name"] != name:
            continue
        kids = clip(children.get(s["id"], []), s["start"], s["end"])
        total += (s["end"] - s["start"]) - trace.busy_seconds(
            [trace.Event(k["name"], k["start"], k["end"]) for k in kids])
    return total
