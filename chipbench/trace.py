"""The one reduction from a profiler trace (`.xplane.pb`) to numbers.

What the trace of this chip holds (looked at by hand, PR 28): a plane
`/device:TPU:<n>` per chip with the lines `XLA Modules` (one event per
program run) and `XLA Ops` (one event per HLO instruction run, named by
the instruction's text, `%name = type opcode(operands...)`); a plane
`/host:CPU` whose `python` line holds `jax.profiler.TraceAnnotation`
spans under their own names and Python calls as `$file.py:line func`.
Host and device share one clock to within a few milliseconds.

Everything here is plain arithmetic on (start, end) pairs so that
`chipbench/tests/test_trace.py` can check it on a small recorded trace.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
_HLO = re.compile(r"^%(?P<name>[^ ]+) = (?P<type>.*?) (?P<op>[a-z][\w\-]*)\(")
_PYCALL = re.compile(r"^\$(?P<file>[\w.]+\.py):\d+ (?P<func>\w+)$")


@dataclass
class Event:
    name: str
    start: float  # seconds on the trace's clock
    end: float


@dataclass
class Trace:
    device_ops: dict = field(default_factory=dict)      # chip -> [Event]
    device_modules: dict = field(default_factory=dict)  # chip -> [Event]
    host: list = field(default_factory=list)            # [Event], all threads


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    """Read an `.xplane.pb` with nothing but JAX."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [Event(e.name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events]
                    target = (trace.device_ops if line.name == OPS_LINE
                              else trace.device_modules)
                    target.setdefault(chip, []).extend(evs)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                trace.host.extend(
                    Event(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
    return trace


def short_op_name(text):
    """`%fusion.3 = bf16[8,128]{...} fusion(...)` -> `fusion.3 fusion
    bf16[8,128]`: instruction name, opcode and result shape without its
    layout, short enough for a breakdown line."""
    m = _HLO.match(text)
    if not m:
        return text[:96]
    shape = re.sub(r"\{[^}]*\}", "", m.group("type"))
    return f"{m.group('name')} {m.group('op')} {shape}"[:96]


def op_instruction_name(text):
    m = _HLO.match(text)
    return m.group("name") if m else text


def op_opcode(text):
    m = _HLO.match(text)
    return m.group("op") if m else ""


def span_window(trace, name):
    """(start, end) of the host annotation `name` (the first one)."""
    spans = [e for e in trace.host if e.name == name]
    if not spans:
        raise ValueError(f"no host span named {name!r} in the trace")
    first = min(spans, key=lambda e: e.start)
    return first.start, first.end


def clip(events, t0, t1):
    out = []
    for e in events:
        s, t = max(e.start, t0), min(e.end, t1)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def union(events):
    """Merged, sorted (start, end) intervals covered by any event."""
    merged = []
    for s, t in sorted((e.start, e.end) for e in events):
        if merged and s <= merged[-1][1]:
            if t > merged[-1][1]:
                merged[-1][1] = t
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_seconds(events):
    return sum(t - s for s, t in union(events))


def gaps(events, t0, t1):
    """The intervals of [t0, t1] that no event covers."""
    out, cursor = [], t0
    for s, t in union(clip(events, t0, t1)):
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, t)
    if t1 > cursor:
        out.append((cursor, t1))
    return out


def op_seconds(events):
    """Summed duration per event name (overlaps counted per event)."""
    sums = {}
    for e in events:
        sums[e.name] = sums.get(e.name, 0.0) + (e.end - e.start)
    return sums


def host_label(name):
    """A host event's name as a gap label, or None if it is not one the
    gaps are named by: a `chipbench.*` annotation or a Python call
    (`$file.py:line func` -> `file.py:func`)."""
    if name.startswith("chipbench."):
        return name
    m = _PYCALL.match(name)
    if m:
        return f"{m.group('file')}:{m.group('func')}"
    return None


def attribute_gaps(gap_list, host_events, files=None):
    """Name each idle gap by what the host was doing in it: the shortest
    labelled host event that covers at least half of the gap (the
    innermost call), restricted to Python files in `files` when given.
    Returns {label: seconds}; a gap nothing covers goes to `(no span)`."""
    labelled = []
    for e in host_events:
        label = host_label(e.name)
        if label is None:
            continue
        if files is not None and not label.startswith("chipbench.") \
                and label.split(":")[0] not in files:
            continue
        labelled.append((e.start, e.end, label))
    labelled.sort()
    sums = {}
    for g0, g1 in gap_list:
        need = 0.5 * (g1 - g0)
        best = None
        for s, t, label in labelled:
            if s >= g1:
                break
            if min(t, g1) - max(s, g0) >= need:
                if best is None or (t - s) < best[0]:
                    best = (t - s, label)
        label = best[1] if best else "(no span)"
        sums[label] = sums.get(label, 0.0) + (g1 - g0)
    return sums


@dataclass
class Summary:
    window_s: float
    busy_s: float            # averaged over the chips used
    ops: dict                # op text -> seconds, summed over chips / chips
    idle_gaps: dict          # label -> seconds (chip 0)

    @property
    def idle_share(self):
        return 1.0 - self.busy_s / self.window_s


def summarize(trace, window_span, gap_files=None, min_gap_s=0.0):
    """Busy/idle, per-op time and named gaps inside the host span
    `window_span`."""
    t0, t1 = span_window(trace, window_span)
    chips = sorted(trace.device_ops)
    if not chips:
        raise ValueError("the trace holds no device plane with XLA ops")
    busy, ops = 0.0, {}
    for chip in chips:
        evs = clip(trace.device_ops[chip], t0, t1)
        busy += busy_seconds(evs)
        for name, secs in op_seconds(evs).items():
            ops[name] = ops.get(name, 0.0) + secs
    n = len(chips)
    first = clip(trace.device_ops[chips[0]], t0, t1)
    gap_list = [g for g in gaps(first, t0, t1) if g[1] - g[0] >= min_gap_s]
    return Summary(window_s=t1 - t0, busy_s=busy / n,
                   ops={k: v / n for k, v in ops.items()},
                   idle_gaps=attribute_gaps(gap_list, clip(trace.host, t0, t1),
                                            gap_files))


CONTAINERS = ("while", "conditional", "call")


def leaf_ops(summary):
    """Per-instruction seconds without the instructions that only
    contain others (a loop's event spans its body's events)."""
    return {k: v for k, v in summary.ops.items()
            if op_opcode(k) not in CONTAINERS}


def scope_seconds(summary, scope):
    """Device time of the instructions that carry a program scope's
    name. XLA names an instruction after the innermost
    `jax.named_scope` it was traced under (`decoder.3/attn` ->
    `%attn.17`, `decode.attend` -> `%decode.attend.4`), whatever
    implements it, so the time is found by the scope and not by a
    kernel's own name."""
    pattern = re.compile(r"^" + re.escape(scope) + r"(\.\d+)?$")
    return sum(secs for text, secs in leaf_ops(summary).items()
               if pattern.match(op_instruction_name(text)))


def breakdown(summary, top=10):
    ops = sorted(leaf_ops(summary).items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(summary.idle_gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[short_op_name(k), v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
