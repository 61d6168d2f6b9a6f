"""Structural analysis of post-optimization HLO modules.

Used by the zero-bubble pipeline evidence (tools/zb_evidence.py and
tests/test_pipeline_llama.py): instead of grepping loop-body TEXT for
dots — which breaks the moment the backend fuses them away — we parse
the module into its computations, follow the call graph through
fusion/call/while/to_apply edges, and count matmul-class ops (`dot`, and
`convolution`, which is what the TPU compiler rewrites small dots into)
reachable from each computation that performs a collective-permute.

Reference contract this evidences: the ZB scheduler pass
(distributed/passes/pipeline_scheduler_pass/pipeline_zero_bubble.py:32)
splits dW from dX so dW fills pipeline bubbles. Here the scan transpose
produces that structure directly: the backward ring's loop body holds
BOTH the dX and dW matmuls alongside its collective-permutes.
"""
from __future__ import annotations

import re

__all__ = ["parse_hlo_computations", "matmuls_reachable",
           "ring_body_matmul_counts", "collective_overlap_report",
           "grad_sync_overlap_report", "collective_independent_matmuls",
           "estimate_collective_seconds", "computation_weights",
           "scope_of_op_name", "entry_io_bytes", "live_range_report",
           "roofline_report", "ROOFLINE_CLASSES", "DEFAULT_ROOFLINE_RATES"]

_MATMUL = re.compile(r"\b(?:dot|convolution)\(")
_CALL_EDGE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)")


def parse_hlo_computations(text):
    """HLO text -> {name: {"matmuls": int, "permutes": int,
    "calls": set}}. Works on pre- and post-optimization dumps."""
    comps = {}
    cur = None
    for line in text.splitlines():
        if cur is None and line.endswith("{"):
            m = _HEADER.match(line.strip())
            if m:
                cur = m.group(1)
                comps[cur] = {"matmuls": 0, "permutes": 0, "calls": set()}
                continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            c = comps[cur]
            if _MATMUL.search(line):
                c["matmuls"] += 1
            if "collective-permute" in line:
                c["permutes"] += 1
            for m in _CALL_EDGE.finditer(line):
                c["calls"].add(m.group(1))
    return comps


def matmuls_reachable(comps, name, _seen=None):
    """Matmul-class ops in `name` plus everything it (transitively)
    calls — fusion bodies included."""
    seen = set() if _seen is None else _seen
    if name in seen or name not in comps:
        return 0
    seen.add(name)
    return comps[name]["matmuls"] + sum(
        matmuls_reachable(comps, callee, seen)
        for callee in comps[name]["calls"])


def ring_body_matmul_counts(text):
    """For every computation containing a collective-permute (the
    pipeline ring bodies): name -> (permute_count, reachable_matmuls)."""
    comps = parse_hlo_computations(text)
    return {name: (c["permutes"], matmuls_reachable(comps, name))
            for name, c in comps.items() if c["permutes"]}


# -- scheduled-order collective overlap analysis -----------------------------
#
# What the TPU compiler's post-optimization module actually shows about
# comm-compute overlap (all four observed in the north-star TrainStep
# compile, tools/overlap_evidence.py):
#
#  1. `frontend_attributes={async_collective_name="all-gather-start.N"}`
#     on an otherwise sync-looking collective: the compiler converted it
#     to an asynchronous backend op — direct evidence it is hidden.
#  2. computations named `*windowed_dot_general_body*`: XLA's collective
#     matmul — the all-gather/reduce-scatter is decomposed into
#     collective-permutes INTERLEAVED with matmul chunks inside one while
#     loop. Maximal overlap, by construction.
#  3. computations named `async_collective_fusion*`, invoked by fusions
#     carrying a `continuation_config`: the collective is fused with its
#     producer/consumer compute into one overlapped kernel.
#  4. explicit `<kind>-start` / `<kind>-done` pairs: classic async; the
#     matmul-class work scheduled between start and done is the overlap.
#
# Anything not in one of those forms is a synchronous instruction, and in
# an `is_scheduled=true` module its position is the schedule: the
# matmul-class work between it and its FIRST CONSUMER is the only
# latency-hiding headroom available. Zero headroom = provable
# serialization point.

_COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_NAME = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
_GROUPS = re.compile(r"replica_groups=\{\{([\d,]+)\}")
# iota form: replica_groups=[G,S]<=[d0,d1,...]T(p0,p1,...) or <=[N]
_GROUPS_IOTA = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


def _shape_bytes(line, kind=None):
    """Bytes of the instruction's output shape(s). Parses every
    dtype[dims] group on the left of the op name — for tuples that is each
    element exactly once (layout annotations {…} carry no brackets).
    `-start` forms carry (input, output, semaphores) tuples: the payload
    is the largest element, not the sum."""
    lhs = line.split(" = ", 1)[0] if " = " in line else line
    rhs = line.split(" = ", 1)[1] if " = " in line else ""
    # output shape tokens live after '=' up to the op name '('. A TUPLE
    # output starts with '(' itself (e.g. the CPU backend's decomposed
    # all-to-all), so for SYNC ops split on the op invocation when the
    # caller knows the kind, not on the first paren. `-start` lines keep
    # the first-paren split unchanged — their pricing (max element of
    # whatever parses, reduce-scatter normalization downstream) is
    # calibrated against the archived TPU modules.
    if rhs and kind is not None and f"{kind}(" in rhs \
            and "-start(" not in rhs:
        head = rhs.split(f"{kind}(", 1)[0]
    elif rhs:
        head = rhs.split("(", 1)[0]
    else:
        head = lhs
    sizes = []
    for dt, dims in _SHAPE.findall(head):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        sizes.append(n * _DTYPE_BYTES[dt])
    if not sizes:
        return 0
    return max(sizes) if "-start(" in line else sum(sizes)


def _first_group(line):
    m = _GROUPS.search(line)
    if m:
        return [int(x) for x in m.group(1).split(",")]
    m = _GROUPS_IOTA.search(line)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        n = 1
        for d in dims:
            n *= d
        import numpy as np
        flat = np.arange(n).reshape(dims)
        if m.group(4):
            flat = flat.transpose([int(x) for x in m.group(4).split(",")])
        return flat.reshape(g, s)[0].tolist()
    return []


_PAIRS = re.compile(r"source_target_pairs=\{\{(\d+),(\d+)\}")


def _split_computations(text):
    """text -> {computation: [instruction lines, in schedule order]}."""
    cur = None
    lines_by_comp: dict = {}
    for line in text.splitlines():
        if cur is None and line.endswith("{"):
            m = _HEADER.match(line.strip())
            if m:
                cur = m.group(1)
                lines_by_comp[cur] = []
                continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None and "=" in line:
            lines_by_comp[cur].append(line)
    return {comp: _with_operand_shapes(lines)
            for comp, lines in lines_by_comp.items()}


_OPERAND_NAME = re.compile(r"%([\w.\-]+)")
# an operand printed bare: its name follows the opening paren or a comma
# directly (a typed one follows its type's closing bracket)
_BARE_OPERAND = re.compile(r"(^|[(,])(\s*)%([\w.\-]+)")


def _op_spans(rhs):
    """(op, end of the output-shape region, start, end of the operand
    region inside the op's parens) for the text right of ' = '; None when
    no op token is found."""
    m_op = _OP_NAME.search(rhs)
    if not m_op:
        return None
    close = _matching_paren(rhs, m_op.end() - 1)
    return (m_op.group(1), m_op.start(), m_op.end(),
            close if close > 0 else len(rhs))


def _with_operand_shapes(lines):
    """One computation's lines with every operand's type written before
    its name, as older XLA printed them (`dot(f32[4,8]{1,0} %a, ...)`).
    This XLA prints operands bare (`dot(%a, %b)`), and the readers below
    take operand bytes, contracting sizes and quantized dtypes from the
    operand region. Types come from the defining lines of the same
    computation; operands that already carry one stay as they are."""
    types, parsed = {}, []
    for line in lines:
        lhs, sep, rhs = line.partition(" = ")
        spans = _op_spans(rhs) if sep else None
        nm = _INSTR_NAME.match(line)
        if nm and spans:
            types[nm.group(1)] = rhs[:spans[1]].strip()
        parsed.append((lhs + sep, rhs, spans))

    def typed(m):
        t = types.get(m.group(3))
        return f"{m.group(1)}{m.group(2)}{t} %{m.group(3)}" if t \
            else m.group(0)

    out = []
    for line, (lhs, rhs, spans) in zip(lines, parsed):
        if spans is None:
            out.append(line)
            continue
        _, _, start, end = spans
        out.append(lhs + rhs[:start]
                   + _BARE_OPERAND.sub(typed, rhs[start:end]) + rhs[end:])
    return out


def _collective_kind(line):
    """The collective this instruction line starts (its `-start` half
    included), or None — also for the `-done` half."""
    kind = next((k for k in _COLLECTIVE_KINDS
                 if re.search(rf"\b{k}(?:-start)?\(", line)), None)
    return None if kind is None or f"{kind}-done(" in line else kind


def _matmul_work(line, reach):
    """Matmul-class ops on this line plus everything it calls."""
    return (1 if _MATMUL.search(line) else 0) + sum(
        reach.get(cm.group(1), 0) for cm in _CALL_EDGE.finditer(line))


def collective_overlap_report(text):
    """For every collective op in every scheduled computation: its kind,
    payload bytes, replica-group (size, stride), overlap mechanism (see
    module comment), and the matmul-class overlap budget.

    Returns a list of dicts: {computation, name, kind, bytes, group_size,
    group_stride, mechanism, headroom_matmuls, consumer_distance}.
    mechanism: async-tagged | windowed-matmul | async-fusion |
    start-done | sync."""
    comps = parse_hlo_computations(text)
    lines_by_comp = _split_computations(text)
    report = []
    # memoized transitive matmul counts — the 7B module has thousands of
    # call edges; per-window re-walks would be quadratic
    reach = {name: matmuls_reachable(comps, name) for name in comps}

    for comp, lines in lines_by_comp.items():
        in_windowed = "windowed_dot_general_body" in comp
        in_async_fusion = comp.startswith("async_collective_fusion")
        for i, line in enumerate(lines):
            kind = next((k for k in _COLLECTIVE_KINDS
                         if re.search(rf"\b{k}(?:-start)?\(", line)), None)
            if kind is None or f"{kind}-done(" in line:
                continue
            nm = _INSTR_NAME.match(line)
            if not nm:
                continue
            name = nm.group(1)
            is_start = f"{kind}-start(" in line
            use = re.compile(rf"%{re.escape(name)}(?![\w.\-])")
            consumer = None
            for j in range(i + 1, len(lines)):
                if use.search(lines[j].split(" = ", 1)[-1]):
                    consumer = j
                    break
            end = consumer if consumer is not None else len(lines)
            headroom = 0
            for j in range(i + 1, end):
                lj = lines[j]
                if _MATMUL.search(lj):
                    headroom += 1
                for cm in _CALL_EDGE.finditer(lj):
                    headroom += reach.get(cm.group(1), 0)
            if in_windowed:
                mech = "windowed-matmul"
                headroom = max(headroom, reach.get(comp, 0))
            elif in_async_fusion:
                mech = "async-fusion"
                headroom = max(headroom, reach.get(comp, 0))
            elif "async_collective_name" in line:
                mech = "async-tagged"
            elif is_start:
                mech = "start-done"
            else:
                mech = "sync"
            grp = _first_group(line)
            stride = (grp[1] - grp[0]) if len(grp) > 1 else 0
            if not grp:
                pm = _PAIRS.search(line)
                if pm:
                    a, b = int(pm.group(1)), int(pm.group(2))
                    stride = abs(b - a)
                    grp = [a, b]
            nbytes = _shape_bytes(line, kind)
            if kind == "reduce-scatter" and is_start and len(grp) > 1:
                # the start tuple's max element is the FULL input;
                # estimate_collective_seconds prices reduce-scatter from
                # the scattered shard — normalize so both forms agree
                nbytes //= len(grp)
            report.append({
                "computation": comp, "name": name, "kind": kind,
                "bytes": nbytes, "group_size": len(grp),
                "group_stride": stride, "mechanism": mech,
                "headroom_matmuls": headroom,
                "consumer_distance": (consumer - i) if consumer is not None
                else -1,
            })
    return report


def grad_sync_overlap_report(text):
    """Backward-overlap evidence for gradient-sync collectives: for every
    collective in every scheduled computation, the matmul-class work
    scheduled AFTER it to the end of that computation.

    Rationale (the --mode gradsync analyzer, tools/overlap_evidence.py):
    a grad collective is issuable-while-compute-remains exactly when
    matmul work is scheduled after it — the backward's remaining layers.
    A monolithic tail sync has zero matmuls after it (provably exposed);
    a bucket anchored mid-backward has the rest of backward to hide
    under (the TPU backend's async DMA engine does the hiding; the
    schedule position proves the dependence structure allows it). This
    differs from collective_overlap_report's first-consumer headroom,
    which on the CPU scheduler is ~always zero because consumers are
    packed greedily.

    Returns [{computation, name, kind, bytes, group_size,
    matmuls_after}]."""
    comps = parse_hlo_computations(text)
    lines_by_comp = _split_computations(text)
    reach = {name: matmuls_reachable(comps, name) for name in comps}
    report = []
    for comp, lines in lines_by_comp.items():
        # suffix-sum of matmul work per schedule position (linear, not
        # quadratic in collectives x lines)
        after = [0] * (len(lines) + 1)
        for j in range(len(lines) - 1, -1, -1):
            after[j] = after[j + 1] + _matmul_work(lines[j], reach)
        for i, line in enumerate(lines):
            kind = _collective_kind(line)
            if kind is None:
                continue
            nm = _INSTR_NAME.match(line)
            if not nm:
                continue
            grp = _first_group(line)
            report.append({
                "computation": comp, "name": nm.group(1), "kind": kind,
                "bytes": _shape_bytes(line, kind),
                "group_size": len(grp),
                "matmuls_after": after[i + 1],
            })
    return report


def collective_independent_matmuls(text):
    """{(computation, collective): matmul-class work of that computation
    that neither feeds the collective nor needs its result}.

    The dependence structure itself, where grad_sync_overlap_report reads
    it off the schedule: work that is independent of a collective is what
    a backend with asynchronous collectives can run while it is on the
    wire, wherever this backend's scheduler happened to put the
    collective. A monolithic tail sync depends on every gradient matmul
    and reports 0. Quadratic in a computation's size: for tests and
    tools, not for the per-compile telemetry path."""
    comps = parse_hlo_computations(text)
    reach = {name: matmuls_reachable(comps, name) for name in comps}
    out = {}
    for comp, lines in _split_computations(text).items():
        index = {nm.group(1): i for i, line in enumerate(lines)
                 if (nm := _INSTR_NAME.match(line))}
        work = [_matmul_work(line, reach) for line in lines]
        feeds = [set() for _ in lines]          # i -> lines it reads
        users = [set() for _ in lines]
        for i, line in enumerate(lines):
            for m in _OPERAND_NAME.finditer(line.partition(" = ")[2]):
                j = index.get(m.group(1))
                if j is not None and j != i:
                    feeds[i].add(j)
                    users[j].add(i)

        def closure(start, edges):
            seen, todo = set(), [start]
            while todo:
                for j in edges[todo.pop()]:
                    if j not in seen:
                        seen.add(j)
                        todo.append(j)
            return seen

        for name, i in index.items():
            if _collective_kind(lines[i]) is None:
                continue
            tied = closure(i, feeds) | closure(i, users) | {i}
            out[(comp, name)] = sum(w for j, w in enumerate(work)
                                    if j not in tied)
    return out


_WHILE_EDGE = re.compile(
    r"while\(.*?\), condition=%?([\w.\-]+), body=%?([\w.\-]+)")
_ENTRY = re.compile(r"^ENTRY\s+%?([\w.\-]+)", re.M)


def while_trip_counts(text):
    """body computation -> static trip count, parsed from the loop
    condition's compare-against-constant (max constant in the condition —
    the induction bound; scheduled HLO keeps these as s32 constants)."""
    comps_lines = _split_computations(text)
    trips = {}
    for m in _WHILE_EDGE.finditer(text):
        cond, body = m.group(1), m.group(2)
        consts = []
        for line in comps_lines.get(cond, ()):
            consts += [int(x) for x in re.findall(r"constant\((\d+)\)",
                                                  line)]
        if consts:
            trips[body] = max(max(consts), 1)
    return trips


def computation_weights(text):
    """computation -> executions per program run: the product of trip
    counts of every enclosing while loop along the call chain (fusion /
    call / to_apply edges inherit the caller's weight; body= edges
    multiply by the loop's trip count). Conservative on multiple callers:
    the max weight wins."""
    comps = parse_hlo_computations(text)
    trips = while_trip_counts(text)
    entry_m = _ENTRY.search(text)
    entry = entry_m.group(1) if entry_m else None
    weights = {entry: 1} if entry else {}
    # iterate to fixpoint (call graph is a DAG; few passes suffice)
    for _ in range(64):
        changed = False
        for name, c in comps.items():
            w = weights.get(name)
            if w is None:
                continue
            for callee in c["calls"]:
                cw = w * trips.get(callee, 1)
                if cw > weights.get(callee, 0):
                    weights[callee] = cw
                    changed = True
        if not changed:
            break
    return weights


# -- compiled-memory live-range analysis -------------------------------------
#
# The structural HBM model behind observability/memory_profile.py: walk
# the ENTRY computation of a SCHEDULED post-optimization module (the
# instruction order IS the schedule on both the CPU and TPU backends),
# size every materialized value from its shape tokens via _shape_bytes,
# and compute the peak-live timeline. Only ENTRY-level values are
# counted — fusion internals never materialize in HBM, which is exactly
# why this approximates XLA's buffer assignment well enough to gate on:
# the big buffers (save stacks, KV pools, activation windows) all live
# at ENTRY or inside while bodies.
#
# Known approximations (documented, not hidden): input/output aliasing
# (donated buffers) is not modeled — the peak OVERCOUNTS by the aliased
# bytes; while-loop body internals are attributed to the while
# instruction's own (carry-sized) output; layout padding is ignored.
# The report tool therefore gates the text model's ARG/OUTPUT
# reconstruction hard against PJRT's memory_analysis (<= 2%) and treats
# peak-live as a fingerprinted structural quantity, not ground truth.

_METADATA_OP = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")

# transform wrappers jax layers around user named_scope annotations in
# op_name paths: jit(f)/transpose(jvp(decoder.0/mlp))/mul. jit/pjit
# frames name internal functions, not user scopes — dropped; the rest
# unwrap to the scope they decorate.
_DROP_FRAMES = ("jit", "pjit")
_UNWRAP_FRAMES = ("jvp", "vjp", "transpose", "remat", "checkpoint",
                  "rematted_computation", "custom_jvp", "custom_vjp",
                  "custom_vjp_call", "vmap", "shard_map", "named")


def _matching_paren(s, at):
    """Index of the ')' matching the '(' at ``at``; -1 if unbalanced."""
    depth = 0
    for i in range(at, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return i
    return -1


def scope_of_op_name(op_name):
    """HLO metadata op_name -> the user named_scope path, e.g.
    ``jit(f)/jit(main)/transpose(jvp(decoder.0/mlp))/dot_general`` ->
    ``decoder.0/mlp``. Transform frames unwrap to the scope they
    decorate (even with '/' inside the parens); jit/pjit frames name
    internal functions and drop whole. The trailing segment (the
    primitive) is dropped; returns "" when no user scope survives."""
    s = str(op_name)
    changed = True
    while changed:
        changed = False
        for w in _UNWRAP_FRAMES:
            at = s.find(w + "(")
            if at >= 0 and (at == 0 or not (s[at - 1].isalnum()
                                            or s[at - 1] == "_")):
                close = _matching_paren(s, at + len(w))
                if close > 0:
                    s = s[:at] + s[at + len(w) + 1:close] + s[close + 1:]
                    changed = True
                    break
    segs = []
    for raw in s.split("/"):
        seg = raw.strip()
        if seg and not any(seg.startswith(w + "(") and seg.endswith(")")
                           for w in _DROP_FRAMES):
            segs.append(seg)
    return "/".join(segs[:-1]) if len(segs) > 1 else ""


def _balanced_brace_span(text, start):
    """Index just past the '}' matching the '{' at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "{":
            depth += 1
        elif text[i] == "}":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _dims_bytes(head):
    total = 0
    for dt, dims in _SHAPE.findall(head):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _parse_instr(line):
    """One scheduled instruction line -> {name, bytes, shape, op,
    scope}, or None for non-instruction lines."""
    nm = _INSTR_NAME.match(line)
    if not nm:
        return None
    rhs = line.split(" = ", 1)[1] if " = " in line else ""
    # op name = first lowercase word directly followed by '(' — this
    # survives tuple-shaped outputs (the rhs then STARTS with '(') and
    # TPU tiled layouts ('{1,0:T(8,128)}')
    m_op = _OP_NAME.search(rhs)
    op = m_op.group(1) if m_op else "?"
    mm = _METADATA_OP.search(line)
    head = rhs[:m_op.start()] if m_op else rhs
    # display shape: the LARGEST shape token (a tuple's dominant
    # element — the s64[] loop counter must not label a 16 KB carry)
    best, best_bytes = "", -1
    for dt, dims in _SHAPE.findall(head):
        if dt not in _DTYPE_BYTES:
            continue
        n = _DTYPE_BYTES[dt]
        for d in dims.split(","):
            if d:
                n *= int(d)
        if n > best_bytes:
            best, best_bytes = f"{dt}[{dims}]", n
    return {
        "name": nm.group(1),
        # tuple/gte/bitcast ALIAS their operands — the producing
        # instruction carries the bytes, the alias carries zero (else
        # the ROOT tuple would double-book every output). Tuple-shaped
        # outputs (while carries — the save stacks!) sum their
        # elements; async -start tuples keep _shape_bytes's max-element
        # payload semantics.
        "bytes": 0 if op in ("tuple", "get-tuple-element", "bitcast")
        else (_shape_bytes(line) if "-start(" in rhs
              else _dims_bytes(head)),
        "shape": best,
        "op": op,
        "scope": scope_of_op_name(mm.group(1)) if mm else "",
    }


def entry_io_bytes(text):
    """(argument_bytes, output_bytes) reconstructed from the module
    header's ``entry_computation_layout={(args...)->outputs}`` — the
    text-side mirror of PJRT memory_analysis's argument/alias and
    output buckets (donated arguments count as arguments here; PJRT
    books them under alias_size_in_bytes)."""
    key = "entry_computation_layout="
    at = text.find(key)
    if at < 0:
        return 0, 0
    start = text.find("{", at)
    span = text[start:_balanced_brace_span(text, start)]
    arrow = span.find(")->")
    if arrow < 0:
        arrow = span.find("->")
        left, right = (span, "") if arrow < 0 else \
            (span[:arrow], span[arrow + 2:])
    else:
        left, right = span[:arrow + 1], span[arrow + 3:]
    return _dims_bytes(left), _dims_bytes(right)


def live_range_report(text, top_k=8):
    """Peak-live analysis of the scheduled ENTRY computation.

    Returns a dict:

    - ``argument_bytes`` / ``output_bytes``: the header reconstruction
      (see :func:`entry_io_bytes`);
    - ``peak_live_bytes`` / ``peak_position``: max over schedule
      positions of the bytes of values already defined and not yet past
      their last consumer (parameters live from position 0; the ROOT
      keeps outputs live to the end);
    - ``top_at_peak``: the ``top_k`` largest buffers live at the peak —
      ``{name, bytes, shape, op, scope, defined, last_use}`` with
      ``scope`` decoded from named_scope metadata (the OOM-forensics
      table: the buffer that killed you, by layer name);
    - ``by_scope``: peak-live bytes attributed per named scope;
      **sums to peak_live_bytes exactly by construction** ("" collects
      unattributed values — parameters, glue ops outside any scope);
    - ``by_scope_total``: bytes of every materialized value billed to
      its scope over the whole program (the per-layer attribution
      table; while bodies contribute via their top buffers).
    """
    lines_by_comp = _split_computations(text)
    entry_m = _ENTRY.search(text)
    entry = entry_m.group(1) if entry_m else None
    lines = lines_by_comp.get(entry, [])
    arg_bytes, out_bytes = entry_io_bytes(text)

    vals = []          # [{name, bytes, shape, op, scope, defined}]
    index = {}         # name -> position in vals
    last_use = {}      # name -> last schedule position referencing it
    for pos, line in enumerate(lines):
        v = _parse_instr(line)
        if v is None:
            continue
        v["defined"] = pos
        vals.append(v)
        index[v["name"]] = len(vals) - 1
        last_use[v["name"]] = pos       # a dead value dies where defined
        rhs = line.split(" = ", 1)[1] if " = " in line else ""
        for om in _OPERAND.finditer(rhs):
            if om.group(1) in index:
                last_use[om.group(1)] = pos
        if v["op"] == "while":
            # the carry tuple hides the big buffers (save stacks!) —
            # break the body computation down so forensics still names
            # pp.save_buffer instead of "while.8"
            bm = _WHILE_EDGE.search(line)
            body = bm.group(2) if bm else None
            inner = []
            for bl in lines_by_comp.get(body, ()):
                bv = _parse_instr(bl)
                if bv is not None and bv["bytes"]:
                    inner.append(bv)
            inner.sort(key=lambda b: (-b["bytes"], b["name"]))
            v["body_top"] = [
                {k: b[k] for k in ("name", "bytes", "shape", "scope")}
                for b in inner[:3]]

    n = len(lines)
    for v in vals:
        # parameters are caller-owned: live for the whole program; the
        # ROOT's operands (the outputs) stay live to the end likewise
        if v["op"] == "parameter":
            v["defined"] = 0
            last_use[v["name"]] = max(last_use[v["name"]], n - 1)
        v["last_use"] = last_use[v["name"]]

    # liveness timeline via +/- events (linear in instructions)
    delta = [0] * (n + 1)
    for v in vals:
        delta[v["defined"]] += v["bytes"]
        delta[v["last_use"] + 1] -= v["bytes"]
    peak, peak_pos, running = 0, 0, 0
    for pos in range(n):
        running += delta[pos]
        if running > peak:
            peak, peak_pos = running, pos
    at_peak = [v for v in vals
               if v["defined"] <= peak_pos <= v["last_use"]]
    at_peak.sort(key=lambda v: (-v["bytes"], v["name"]))
    by_scope = {}
    for v in at_peak:
        by_scope[v["scope"]] = by_scope.get(v["scope"], 0) + v["bytes"]
    # per-layer attribution over the WHOLE program (not just the peak
    # instant): every materialized value billed to its named scope —
    # the table that says how many bytes decoder.12/mlp produced. A
    # while's carry bytes are REASSIGNED to the named body buffers its
    # body_top breakdown identifies (remainder stays on the while's own
    # scope) — billing both would double-count every carried buffer.
    by_scope_total = {}
    for v in vals:
        billed = 0
        for b in v.get("body_top", ()):
            if b["scope"]:
                take = min(b["bytes"], v["bytes"] - billed)
                if take <= 0:
                    break
                by_scope_total[b["scope"]] = \
                    by_scope_total.get(b["scope"], 0) + take
                billed += take
        rem = v["bytes"] - billed
        if rem:
            by_scope_total[v["scope"]] = \
                by_scope_total.get(v["scope"], 0) + rem
    return {
        "computation": entry,
        "instructions": n,
        "argument_bytes": arg_bytes,
        "output_bytes": out_bytes,
        "peak_live_bytes": peak,
        "peak_position": peak_pos,
        "live_at_peak": len(at_peak),
        "top_at_peak": [
            {k: v[k] for k in ("name", "bytes", "shape", "op", "scope",
                               "defined", "last_use", "body_top")
             if k in v}
            for v in at_peak[:top_k]],
        "by_scope": dict(sorted(by_scope.items(),
                                key=lambda kv: -kv[1])),
        "by_scope_total": dict(sorted(by_scope_total.items(),
                                      key=lambda kv: -kv[1])),
    }


def estimate_collective_seconds(kind, nbytes, group_size,
                                ici_bytes_per_sec=45e9):
    """Ring-algorithm time estimate for one collective on an ICI ring
    (same model as distributed/auto_tuner/cost_model.py)."""
    n = max(int(group_size), 1)
    if n == 1:
        return 0.0
    if kind == "all-reduce":
        traffic = 2.0 * (n - 1) / n * nbytes
    elif kind in ("all-gather", "all-to-all"):
        # nbytes is the (full) output shape for all-gather
        traffic = (n - 1) / n * nbytes
    elif kind == "reduce-scatter":
        # nbytes is the SCATTERED output shard; each shard moves n-1 hops
        traffic = (n - 1) * nbytes
    else:  # collective-permute: one hop
        traffic = float(nbytes)
    return traffic / ici_bytes_per_sec


# -- roofline attribution -----------------------------------------------------
#
# The sixth observability layer's pricing pass (observability/roofline.py
# is the recorder around it): walk every SCHEDULED computation of a
# post-optimization module — ENTRY plus while bodies/conditions, each at
# its computation_weights trip count — and price every instruction
# against the chip rooflines:
#
#   t_compute = flops / MXU rate      (dot/conv flops from the printed
#                                      operand shapes + contracting dims;
#                                      fusion flops rolled up through the
#                                      call graph; elementwise ~1/elem)
#   t_hbm     = bytes / HBM bandwidth (operand + output bytes at the call
#                                      site: fusion internals stay in
#                                      registers/VMEM, so the call-site
#                                      traffic IS the HBM bill)
#   t_ici     = ring-model seconds    (estimate_collective_seconds — the
#                                      SAME pricer cost_model.py uses)
#   t_host    = bytes / host link     (infeed/outfeed/send/recv +
#                                      host custom-calls)
#
# An op's modeled time is the roofline max of its terms; its class is the
# binding term; its GAP is modeled time minus its own MXU-ideal time —
# the seconds the op spends away from compute peak. Summed per
# named_scope, the gaps are the per-layer MFU-gap waterfall, and the
# per-scope seconds sum to the modeled step wall by construction (the
# repo's sums-to-X contract; tools/roofline_report.py re-verifies <= 2%).

ROOFLINE_CLASSES = ("compute", "hbm", "ici", "host")

# mirror of distributed/auto_tuner/cost_model.py's chip constants
# (PEAK_FLOPS_TPU / HBM_BW / ICI_BW / OFFLOAD_DMA_BW for a v5e).
# observability/roofline.py passes the cost_model values explicitly and
# its drift gate fails if the two ever disagree — keep this copy only so
# the pass works standalone on raw HLO text.
DEFAULT_ROOFLINE_RATES = {
    "mxu_flops_per_sec": 197e12,
    # quantized-dot rates (cost_model.MXU_RATE x the bf16 peak): dots
    # with an int8/fp8 operand price their compute leg here, so a
    # quantized kernel's roofline credits the precision win the same
    # way the planner does
    "mxu_int8_flops_per_sec": 394e12,
    "mxu_fp8_flops_per_sec": 394e12,
    "hbm_bytes_per_sec": 819e9,
    "ici_bytes_per_sec": 45e9,
    "host_bytes_per_sec": 5e10,
}

# dtype tokens that mark a dot/convolution operand as quantized, mapped
# to the rate key its compute leg prices against
_QUANT_DOT_DTYPES = (("s8[", "mxu_int8_flops_per_sec"),
                     ("u8[", "mxu_int8_flops_per_sec"),
                     ("f8e4m3fn[", "mxu_fp8_flops_per_sec"),
                     ("f8e5m2[", "mxu_fp8_flops_per_sec"))

_CONTRACT_DIMS = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
# pure data-movement ops: zero flops, their cost is their traffic
_MOVEMENT_OPS = frozenset((
    "copy", "copy-start", "copy-done", "broadcast", "reshape",
    "transpose", "slice", "concatenate", "gather", "scatter", "select",
    "iota", "convert", "pad", "reverse", "dynamic-slice",
    "dynamic-update-slice", "constant", "parameter", "tuple",
    "get-tuple-element", "bitcast", "after-all", "partition-id",
    "replica-id", "opt-barrier", "rng-bit-generator"))
# ops priced elsewhere or free: aliases carry no traffic of their own,
# while bodies are priced separately at their trip weight
_SKIP_OPS = frozenset(("tuple", "get-tuple-element", "bitcast",
                       "parameter", "constant", "while", "after-all",
                       "opt-barrier"))
_HOST_OPS = frozenset(("infeed", "outfeed", "send", "recv",
                       "send-done", "recv-done"))


def _shape_elems(region):
    """Total elements over every dtype[dims] token in ``region``."""
    total = 0
    for dt, dims in _SHAPE.findall(region):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


def _first_shape_dims(region):
    """Dims list of the first dtype[dims] token in ``region``."""
    for dt, dims in _SHAPE.findall(region):
        if dt not in _DTYPE_BYTES:
            continue
        return [int(d) for d in dims.split(",") if d]
    return []


def _instr_flops(line, op, head, opargs):
    """Modeled FLOPs of one instruction line (no call-graph rollup).

    dot: 2 * out_elems * K with K the product of the lhs operand's
    contracting dims (both printed on post-optimization lines).
    convolution: 2 * out_elems * (rhs_elems / out_features) — exact for
    the 1x1 convs the TPU backend rewrites small dots into.
    Everything else: 1 flop per output element (movement ops: 0) —
    transcendental surcharge is noise next to the dots this pass ranks."""
    out_elems = _shape_elems(head)
    if op == "dot":
        k = 1
        lhs = _first_shape_dims(opargs)
        m = _CONTRACT_DIMS.search(line)
        if m and lhs:
            for d in m.group(1).split(","):
                if d and int(d) < len(lhs):
                    k *= lhs[int(d)]
        elif lhs:
            k = lhs[-1]
        return 2.0 * out_elems * max(k, 1)
    if op == "convolution":
        shapes = _SHAPE.findall(opargs)
        rhs_elems = 0
        if len(shapes) >= 2:
            dt, dims = shapes[1]
            if dt in _DTYPE_BYTES:
                rhs_elems = 1
                for d in dims.split(","):
                    if d:
                        rhs_elems *= int(d)
        out_dims = _first_shape_dims(head)
        feat = out_dims[-1] if out_dims else 1
        return 2.0 * out_elems * max(rhs_elems / max(feat, 1), 1.0)
    if op in _MOVEMENT_OPS:
        return 0.0
    return float(out_elems)


def _split_op_regions(line):
    """(op, head, opargs) for one instruction line: the op token, the
    output-shape region before it, and the operand region inside its
    parens (operand shapes are printed inline post-optimization)."""
    rhs = line.split(" = ", 1)[1] if " = " in line else ""
    spans = _op_spans(rhs)
    if spans is None:
        return "?", rhs, ""
    op, head_end, start, end = spans
    return op, rhs[:head_end], rhs[start:end]


def _reach_flops(comps, lines_by_comp, name, memo, _stack=None):
    """Sum of modeled flops over ``name``'s body and everything it
    (transitively) calls — the fusion/call rollup priced at call sites."""
    if name in memo:
        return memo[name]
    stack = set() if _stack is None else _stack
    if name in stack or name not in lines_by_comp:
        return 0.0
    stack.add(name)
    total = 0.0
    for line in lines_by_comp[name]:
        op, head, opargs = _split_op_regions(line)
        total += _instr_flops(line, op, head, opargs)
        for cm in _CALL_EDGE.finditer(line):
            total += _reach_flops(comps, lines_by_comp, cm.group(1),
                                  memo, stack)
    memo[name] = total
    return total


def roofline_report(text, rates=None, top_k=8):
    """Per-op roofline attribution of one scheduled module.

    Returns a dict with the sums-to-X contracts built in:

    - ``total_modeled_s``: the modeled step wall — sum of every op's
      roofline time (weighted by while-trip counts);
    - ``ideal_compute_s`` / ``modeled_mfu`` / ``mfu_gap_s``: total
      flops at MXU peak, its fraction of the wall, and the difference;
    - ``class_time_s`` / ``class_time_frac``: seconds per bound class
      (compute/hbm/ici/host); the seconds sum to the wall and the
      fractions to 1 exactly by construction;
    - ``by_scope``: the per-layer MFU-gap waterfall — named_scope ->
      {seconds, gap_s, flops, bytes, bound}; scope seconds sum to the
      wall ("" collects unscoped glue);
    - ``top_ops``: the ``top_k`` ops by roofline-gap seconds — the
      "write the int8 kernel HERE" list;
    - ``collectives``: each priced collective row (kind, bytes,
      group_size, trips, seconds) for the cost_model drift gate;
    - ``flops_total`` / ``bytes_total`` and the ``rates`` used.
    """
    r = dict(DEFAULT_ROOFLINE_RATES)
    if rates:
        r.update(rates)
    mxu = max(float(r["mxu_flops_per_sec"]), 1.0)
    hbm = max(float(r["hbm_bytes_per_sec"]), 1.0)
    ici = max(float(r["ici_bytes_per_sec"]), 1.0)
    host = max(float(r["host_bytes_per_sec"]), 1.0)

    comps = parse_hlo_computations(text)
    lines_by_comp = _split_computations(text)
    weights = computation_weights(text)
    entry_m = _ENTRY.search(text)
    entry = entry_m.group(1) if entry_m else None
    # scheduled levels: ENTRY + every while body/condition, each at its
    # trip weight. Fusion/call bodies are priced AT their call sites.
    scheduled = set()
    if entry in lines_by_comp:
        scheduled.add(entry)
    for m in _WHILE_EDGE.finditer(text):
        scheduled.update(m.groups())
    flops_memo: dict = {}

    ops = []
    n_instr = 0
    for comp in scheduled:
        w = float(weights.get(comp, 1))
        for line in lines_by_comp.get(comp, ()):
            nm = _INSTR_NAME.match(line)
            if not nm:
                continue
            n_instr += 1
            op, head, opargs = _split_op_regions(line)
            if op in _SKIP_OPS:
                continue
            mm = _METADATA_OP.search(line)
            scope = scope_of_op_name(mm.group(1)) if mm else ""
            kind = next((k for k in _COLLECTIVE_KINDS
                         if re.search(rf"\b{k}(?:-start)?\(", line)),
                        None)
            if kind is not None and f"{kind}-done(" not in line:
                nbytes = _shape_bytes(line, kind)
                grp = _first_group(line)
                if not grp:
                    pm = _PAIRS.search(line)
                    if pm:
                        grp = [int(pm.group(1)), int(pm.group(2))]
                if kind == "reduce-scatter" and f"{kind}-start(" in line \
                        and len(grp) > 1:
                    nbytes //= len(grp)
                sec = estimate_collective_seconds(
                    kind, nbytes, len(grp), ici_bytes_per_sec=ici)
                ops.append({"name": nm.group(1), "op": kind,
                            "computation": comp, "scope": scope,
                            "class": "ici", "trips": w,
                            "flops": 0.0, "bytes": float(nbytes) * w,
                            "seconds": sec * w, "compute_s": 0.0,
                            "group_size": len(grp),
                            "bytes_per_call": float(nbytes)})
                continue
            if kind is not None:
                continue                      # the -done half: priced at start
            nbytes = float(_dims_bytes(head) + _dims_bytes(opargs))
            if op in _HOST_OPS or (op == "custom-call"
                                   and "host" in line.lower()):
                sec = nbytes / host
                ops.append({"name": nm.group(1), "op": op,
                            "computation": comp, "scope": scope,
                            "class": "host", "trips": w, "flops": 0.0,
                            "bytes": nbytes * w, "seconds": sec * w,
                            "compute_s": 0.0})
                continue
            flops = _instr_flops(line, op, head, opargs)
            for cm in _CALL_EDGE.finditer(line):
                callee = cm.group(1)
                if callee in scheduled:
                    continue                  # while edges: priced directly
                flops += _reach_flops(comps, lines_by_comp, callee,
                                      flops_memo)
            # quantized GEMMs (a flop-carrying op consuming int8/fp8
            # operands — the dot itself, or the fusion wrapping the
            # in-register dequant) price their compute leg at the
            # 8-bit MXU rate; bytes already price at 1 byte/elem via
            # _DTYPE_BYTES, so both roofline legs credit the win
            op_mxu = mxu
            if flops > 0.0:
                for tok, key in _QUANT_DOT_DTYPES:
                    if tok in opargs or tok in head:
                        op_mxu = max(float(r.get(key, mxu)), mxu)
                        break
            t_c = flops / op_mxu
            t_m = nbytes / hbm
            sec = max(t_c, t_m)
            ops.append({"name": nm.group(1), "op": op,
                        "computation": comp, "scope": scope,
                        "class": "compute" if t_c >= t_m else "hbm",
                        "trips": w, "flops": flops * w,
                        "bytes": nbytes * w, "seconds": sec * w,
                        "compute_s": t_c * w})

    for o in ops:
        o["gap_s"] = o["seconds"] - o["compute_s"]
    class_time_s = {c: 0.0 for c in ROOFLINE_CLASSES}
    class_flops = {c: 0.0 for c in ROOFLINE_CLASSES}
    by_scope: dict = {}
    for o in ops:
        class_time_s[o["class"]] += o["seconds"]
        class_flops[o["class"]] += o["flops"]
        s = by_scope.setdefault(o["scope"],
                                {"seconds": 0.0, "gap_s": 0.0,
                                 "flops": 0.0, "bytes": 0.0,
                                 "class_s": {c: 0.0
                                             for c in ROOFLINE_CLASSES}})
        s["seconds"] += o["seconds"]
        s["gap_s"] += o["gap_s"]
        s["flops"] += o["flops"]
        s["bytes"] += o["bytes"]
        s["class_s"][o["class"]] += o["seconds"]
    # the telescoping total: the wall IS the sum of the class buckets,
    # so both the class and the scope tables reconcile to it
    total = sum(class_time_s.values())
    for s in by_scope.values():
        s["bound"] = max(ROOFLINE_CLASSES,
                         key=lambda c: s["class_s"][c])
        del s["class_s"]
    flops_total = sum(o["flops"] for o in ops)
    bytes_total = sum(o["bytes"] for o in ops)
    ideal = flops_total / mxu
    tops = sorted(ops, key=lambda o: (-o["gap_s"], o["name"]))[:top_k]
    return {
        "computation": entry,
        "instructions": n_instr,
        "rates": r,
        "total_modeled_s": total,
        "ideal_compute_s": ideal,
        "modeled_mfu": (ideal / total) if total > 0 else 0.0,
        "mfu_gap_s": total - ideal,
        "flops_total": flops_total,
        "bytes_total": bytes_total,
        "class_time_s": class_time_s,
        "class_time_frac": {c: (v / total if total > 0 else 0.0)
                            for c, v in class_time_s.items()},
        "hbm_bound_flops_frac": (class_flops["hbm"] / flops_total
                                 if flops_total > 0 else 0.0),
        "by_scope": dict(sorted(by_scope.items(),
                                key=lambda kv: -kv[1]["seconds"])),
        "top_ops": [{k: o[k] for k in ("name", "op", "computation",
                                       "scope", "class", "trips",
                                       "flops", "bytes", "seconds",
                                       "compute_s", "gap_s")}
                    for o in tops],
        "collectives": [{"name": o["name"], "kind": o["op"],
                         "bytes": o["bytes_per_call"],
                         "group_size": o["group_size"],
                         "trips": o["trips"], "seconds": o["seconds"]}
                        for o in ops if o["class"] == "ici"],
    }
