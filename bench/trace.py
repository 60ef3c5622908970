"""Reduction of a profiler trace to per-scope device time.

The run wraps its measured window in a host annotation ``bench.window`` and
each chunk's host steps in ``bench.*`` annotations. From the trace's device
planes this module takes every operation in the window, attributes it to
the innermost ``repro.*`` named scope of its HLO instruction (the
instruction's ``op_name`` metadata in the compiled program's text), and
sums each operation's own time (its duration less that of operations nested
in it on the same line). Busy time is the union of the operations'
intervals, per device, averaged over devices. Idle gaps are the spans of the
window in which no operation ran on the first device, named by the
innermost ``bench.*`` host annotation that covers their midpoint. The
spans of ``bench.check_copy``, the benchmark's own copies for its check,
are cut out of the window with the operations that start in them.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

WINDOW = "bench.window"
CHECK_COPY = "bench.check_copy"    # left out of the window

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


@dataclasses.dataclass
class OpEvent:
    device: str
    start_ns: float
    dur_ns: float
    name: str          # HLO instruction
    op_name: str       # jax op_name path, "" where unknown


@dataclasses.dataclass
class Summary:
    scope_ns: Dict[str, float]     # innermost repro scope path -> own ns,
                                   # averaged over devices ("" = none)
    busy_ns: float                 # union of op intervals, device average
    window_ns: float
    devices: int
    top_ops: List[Tuple[str, float]]     # (scope/primitive, seconds)
    idle_gaps: List[Tuple[str, float]]   # (host annotation, seconds)


def op_names(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its ``op_name`` metadata. An instruction the
    compiler added without metadata (copies, layout changes, async starts)
    takes the op_name of the first instruction with one in a computation
    it calls, else that of the nearest instruction that calls its own
    computation (the while loop or fusion it runs in)."""
    own: Dict[str, str] = {}
    comp_of: Dict[str, str] = {}
    calls: Dict[str, List[str]] = defaultdict(list)
    callers: Dict[str, List[str]] = defaultdict(list)
    members: Dict[str, List[str]] = defaultdict(list)
    comp = ""
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and " = " not in line:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        comp_of[name] = comp
        members[comp].append(name)
        meta = _OP_NAME.search(line)
        if meta:
            own[name] = meta.group(1)
        called = _CALLED.findall(line)
        for b in _BRANCHES.findall(line):
            called += [c.strip().lstrip("%") for c in b.split(",")]
        calls[name] = called
        for c in called:
            callers[c].append(name)

    def inner(name, seen):
        for c in calls[name]:
            if c in seen:
                continue
            seen.add(c)
            for m in members[c]:
                found = own.get(m) or inner(m, seen)
                if found:
                    return found
        return ""

    def outer(name, seen):
        for caller in callers[comp_of[name]]:
            if caller in seen:
                continue
            seen.add(caller)
            found = own.get(caller) or outer(caller, seen)
            if found:
                return found
        return ""

    return {n: own.get(n) or inner(n, set()) or outer(n, set())
            for n in comp_of}


def module_name(hlo_text: str) -> str:
    for line in hlo_text.splitlines():
        m = _MODULE.match(line)
        if m:
            return m.group(1)
    return ""


def repro_scope(op_name: str) -> str:
    """The ``repro.*`` components of an op_name path, outermost first,
    joined by '/'; "" when it has none."""
    parts = []
    for p in op_name.split("/"):
        if p.startswith("repro.") and p not in parts:
            parts.append(p)
    return "/".join(parts)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _instruction(event_name: str) -> str:
    """'%fusion.4 = f32[8] fusion(...)' -> 'fusion.4'."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def read_xplane(path: str, names: Dict[str, str], module: str):
    """(device op events, host annotations) of one trace file. An op is
    mapped to its ``op_name`` only while a run of ``module`` is on its
    device (the "XLA Modules" line); ops of other programs keep "". Host
    annotations are (name, start_ns, end_ns) of ``bench.*`` events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: List[OpEvent] = []
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        for line in lines.values():
            for ev in line.events:
                if ev.name.startswith("bench."):
                    host.append((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            runs = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in lines.get("XLA Modules", ()).events
                    if ev.name.split("(")[0] == module] \
                if "XLA Modules" in lines else []
            for ev in lines["XLA Ops"].events:
                name = _instruction(ev.name)
                inside = any(s <= ev.start_ns < t for s, t in runs)
                ops.append(OpEvent(plane.name, float(ev.start_ns),
                                   float(ev.duration_ns), name,
                                   names.get(name, "") if inside else ""))
    return ops, host


def _own_times(events: List[OpEvent]) -> List[float]:
    """Each event's duration less the parts covered by events nested in it
    (events of one device, sorted by start)."""
    own = [e.dur_ns for e in events]
    stack: List[int] = []
    for i, e in enumerate(events):
        end = e.start_ns + e.dur_ns
        while stack and events[stack[-1]].start_ns + \
                events[stack[-1]].dur_ns <= e.start_ns:
            stack.pop()
        if stack and end <= events[stack[-1]].start_ns + \
                events[stack[-1]].dur_ns:
            own[stack[-1]] -= e.dur_ns
        stack.append(i)
    return [max(o, 0.0) for o in own]


def _union(intervals: Iterable[Tuple[float, float]]):
    out: List[List[float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def reduce(ops: List[OpEvent], host: List[Tuple[str, float, float]],
           top: int = 10) -> Summary:
    """Per-scope own time, busy time and idle gaps inside ``bench.window``."""
    windows = [(s, t) for name, s, t in host if name == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    w0, w1 = windows[-1]
    cut = [(s, min(t, w1)) for name, s, t in host
           if name == CHECK_COPY and w0 <= s < w1]
    by_dev: Dict[str, List[OpEvent]] = defaultdict(list)
    for e in ops:
        if e.start_ns >= w0 and e.start_ns + e.dur_ns <= w1 and not any(
                s <= e.start_ns < t for s, t in cut):
            by_dev[e.device].append(e)
    n_dev = max(len(by_dev), 1)
    scope_ns: Dict[str, float] = defaultdict(float)
    op_ns: Dict[str, float] = defaultdict(float)
    busy = 0.0
    gaps: List[Tuple[float, float]] = []
    for k, dev in enumerate(sorted(by_dev)):
        evs = sorted(by_dev[dev], key=lambda e: (e.start_ns, -e.dur_ns))
        for e, own in zip(evs, _own_times(evs)):
            scope = repro_scope(e.op_name)
            scope_ns[scope] += own / n_dev
            prim = e.op_name.rsplit("/", 1)[-1] if e.op_name else e.name
            op_ns[f"{scope.rsplit('/', 1)[-1] or 'unscoped'}/{prim}"] += \
                own / n_dev
        spans = _union((e.start_ns, e.start_ns + e.dur_ns) for e in evs)
        busy += sum(t - s for s, t in spans) / n_dev
        if k == 0:
            covered = _union([(s, t) for s, t in spans] + cut)
            edges = [w0] + [x for s, t in covered for x in (s, t)] + [w1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges) - 1, 2)
                    if edges[i + 1] > edges[i]]
    notes = [(name, s, t) for name, s, t in host if name != WINDOW]

    def label(mid: float) -> str:
        covering = [(t - s, name) for name, s, t in notes if s <= mid <= t]
        return min(covering)[1] if covering else "host.unannotated"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return Summary(
        scope_ns=dict(scope_ns), busy_ns=busy,
        window_ns=w1 - w0 - sum(t - s for s, t in cut),
        devices=len(by_dev),
        top_ops=sorted(((k, v / 1e9) for k, v in op_ns.items()),
                       key=lambda kv: -kv[1])[:top],
        idle_gaps=[(label((s + t) / 2), (t - s) / 1e9) for s, t in longest])


def summarize(trace_dir: str, hlo_text: str) -> Summary:
    """The Summary of the newest trace under ``trace_dir``."""
    ops, host = read_xplane(find_xplane(trace_dir), op_names(hlo_text),
                            module_name(hlo_text))
    return reduce(ops, host)
