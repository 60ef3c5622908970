"""Roofline analysis from compiled HLO (no hardware required).

Parses the post-SPMD optimized HLO text (``compiled.as_text()``, per-device
shapes) and derives:

  * collective bytes by op kind (all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute), with while-loop bodies multiplied by
    their trip counts (scan-over-layers!), and converted to *wire bytes* with
    ring-algorithm factors over the parsed replica-group size;
  * dot FLOPs (trip-count aware, so scanned layers count L times);
  * the three roofline terms in seconds per step, against the published
    peaks of the device kind the caller names (``PEAKS``).

The memory term uses ``compiled.cost_analysis()`` "bytes accessed" when the
backend reports it, corrected for loop trip counts by the same multiplier
machinery, with an analytic floor of one full parameter+optimizer sweep.
"""
from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict
from typing import Dict, Optional

# Published per-chip peaks, keyed by jax's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s chip-to-chip interconnect = 4 links x 50 GB/s).
PEAKS = {
    "TPU v5 lite": {"peak_flops_bf16": 197e12, "hbm_bw": 819e9,
                    "ici_bw": 50e9, "hbm_bytes": 16e9},
}
# the target the analytic (CPU-compiled HLO) byte models are priced against
V5E = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """The ``PEAKS`` row of one device kind; an unknown kind is an error,
    never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_ASSIGN_RE = re.compile(r"^\s*(?:ROOT\s+)?([%\w\.\-]+)\s*=\s*(.*)$")


def _split_def(clean_line: str):
    """'%x = <shape> <opcode>(...)' -> (name, shape, opcode) or None.
    Handles tuple shapes by paren matching."""
    m = _ASSIGN_RE.match(clean_line)
    if not m:
        return None
    name, rhs = m.groups()
    rhs = rhs.strip()
    if rhs.startswith("("):
        depth = 0
        end = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i + 1
                    break
        shape, rest = rhs[:end], rhs[end:]
    else:
        sp = rhs.find(" ")
        if sp < 0:
            return None
        shape, rest = rhs[:sp], rhs[sp:]
    rest = rest.strip()
    par = rest.find("(")
    if par <= 0:
        return None
    kind = rest[:par].strip()
    if not re.fullmatch(r"[\w\-]+", kind):
        return None
    return name.lstrip("%"), shape, kind
_CALLED_RE = re.compile(r"(?:to_apply|body|condition|true_computation|calls|"
                        r"false_computation)=([%\w\.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([%\w\.\-, ]+)\}")
_COMMENT_RE = re.compile(r"/\*.*?\*/")
_REPL_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\})")
_REPL_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "all-gather-start", "all-reduce-start",
               "collective-permute-start")


def shape_bytes(shape_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO shape string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def shape_elems(shape_str: str):
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return None, []
    dt, dims = m.groups()
    return dt, [int(d) for d in dims.split(",") if d]


@dataclasses.dataclass
class HloOp:
    name: str
    shape: str
    kind: str
    line: str


def _parse_computations(hlo: str):
    """Split module text into computations: name -> list[HloOp].

    Computation headers sit at column 0 (optionally prefixed ENTRY) and end
    with '{'; ops are indented. Block comments (/*index=N*/) are stripped
    before op parsing — tuple shapes embed '=' inside them.
    """
    comps: Dict[str, list] = {}
    cur = None
    for line in hlo.splitlines():
        if not line.strip():
            continue
        if not line[0].isspace() and line.rstrip().endswith("{"):
            tok = line.split()
            name = tok[1] if tok[0] == "ENTRY" else tok[0]
            cur = name.lstrip("%")
            comps[cur] = []
            continue
        if cur is None:
            continue
        clean = _COMMENT_RE.sub("", line)
        d = _split_def(clean)
        if d:
            name, shape, kind = d
            comps[cur].append(HloOp(name, shape, kind, clean.strip()))
    return comps


def _trip_count(comps, cond_name: str) -> int:
    """Largest integer-typed constant in the while condition computation
    (scan lowers to `compare(counter, constant(L))`)."""
    best = 1
    for op in comps.get(cond_name, []):
        if op.kind == "constant" and re.match(r"^\(?[su](8|16|32|64)\[",
                                              op.shape.strip()):
            m = re.search(r"constant\((\d+)\)", op.line)
            if m:
                best = max(best, int(m.group(1)))
    return best


def _called_comps(op: HloOp):
    out = [m.group(1).lstrip("%") for m in _CALLED_RE.finditer(op.line)]
    for m in _BRANCHES_RE.finditer(op.line):
        out.extend(n.strip().lstrip("%") for n in m.group(1).split(","))
    return out


def _multipliers(comps) -> Dict[str, float]:
    """Execution-count multiplier per computation (entry=1; while bodies x trip)."""
    mult: Dict[str, float] = defaultdict(float)
    entries = set(comps)
    called = set()
    for ops in comps.values():
        for op in ops:
            for c in _called_comps(op):
                called.add(c)
    roots = entries - called
    for r in roots:
        mult[r] = max(mult[r], 1.0)

    # propagate in passes (call graph is a DAG of modest depth)
    for _ in range(32):
        changed = False
        for cname, ops in comps.items():
            base = mult.get(cname, 0.0)
            if base <= 0:
                continue
            for op in ops:
                if op.kind == "while":
                    mcond = re.search(r"condition=([%\w\.\-]+)", op.line)
                    mbody = re.search(r"body=([%\w\.\-]+)", op.line)
                    cond = mcond.group(1).lstrip("%") if mcond else None
                    trip = _trip_count(comps, cond) if cond else 1
                    for c in _called_comps(op):
                        nm = base * trip
                        if nm > mult.get(c, 0.0):
                            mult[c] = nm
                            changed = True
                else:
                    for c in _called_comps(op):
                        if base > mult.get(c, 0.0):
                            mult[c] = base
                            changed = True
        if not changed:
            break
    return mult


def _group_size(line: str, default: int) -> int:
    m = _REPL_GROUPS_V2_RE.search(line)
    if m:
        return int(m.group(2))
    m = _REPL_GROUPS_RE.search(line)
    if m:
        return len([x for x in m.group(1).strip("{}").split(",") if x.strip()])
    return default


def wire_factor(kind: str, n: int) -> float:
    """Ring-algorithm bytes-on-the-wire per participant, as a fraction of the
    op's result bytes."""
    if n <= 1:
        return 0.0
    if kind.startswith("all-reduce"):
        return 2.0 * (n - 1) / n
    if kind.startswith("all-gather"):
        return (n - 1) / n
    if kind.startswith("reduce-scatter"):
        return (n - 1) / n      # relative to the (larger) input; see below
    if kind.startswith("all-to-all"):
        return (n - 1) / n
    if kind.startswith("collective-permute"):
        return 1.0
    return 1.0


def analyze_hlo(hlo: str, num_devices: int):
    """Returns dict with collective bytes (logical + wire), dot flops, by-kind
    breakdown — all per device, trip-count aware."""
    comps = _parse_computations(hlo)
    mult = _multipliers(comps)

    # name -> shape within each computation for operand lookup
    coll_logical = defaultdict(float)
    coll_wire = defaultdict(float)
    dot_flops = 0.0
    for cname, ops in comps.items():
        m = mult.get(cname, 0.0)
        if m <= 0:
            continue
        shapes = {op.name: op.shape for op in ops}
        for op in ops:
            if op.kind in COLLECTIVES:
                base = op.kind.replace("-start", "")
                nbytes = shape_bytes(op.shape)
                if base == "reduce-scatter":
                    # wire cost relative to the unscattered input
                    grp = _group_size(op.line, num_devices)
                    coll_logical[base] += m * nbytes
                    coll_wire[base] += m * nbytes * (grp - 1)
                else:
                    grp = _group_size(op.line, num_devices)
                    coll_logical[base] += m * nbytes
                    coll_wire[base] += m * nbytes * wire_factor(base, grp)
            elif op.kind == "dot":
                dt, out_dims = shape_elems(op.shape)
                operands = re.search(r"dot\(([^)]*)\)", op.line)
                contracted = 1
                cdims = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.line)
                if operands and cdims:
                    first = operands.group(1)
                    # operands may be typed ('f32[8,8]{1,0} %x') — shapes
                    # embed commas, so find the inline shape or the %name
                    # instead of splitting on ','
                    mshape = _SHAPE_RE.search(first)
                    mname = re.search(r"%([\w\.\-]+)", first)
                    if mshape and first.lstrip().startswith(mshape.group(1)):
                        lhs_shape = mshape.group(0)
                    else:
                        lhs = mname.group(1) if mname else \
                            first.split(",")[0].strip().lstrip("%")
                        lhs_shape = shapes.get(lhs)
                    if lhs_shape:
                        _, ldims = shape_elems(lhs_shape)
                        for ci in cdims.group(1).split(","):
                            if ci and int(ci) < len(ldims):
                                contracted *= ldims[int(ci)]
                out_elems = 1
                for d in out_dims or []:
                    out_elems *= d
                dot_flops += m * 2.0 * out_elems * contracted
    return {
        "collective_logical_bytes": dict(coll_logical),
        "collective_wire_bytes": dict(coll_wire),
        "collective_bytes_total": float(sum(coll_wire.values())),
        "dot_flops": float(dot_flops),
        "n_computations": len(comps),
    }


# ops whose result is a view / control construct, not an HBM buffer write
_NON_MATERIAL = {
    "parameter", "constant", "iota", "tuple", "get-tuple-element", "bitcast",
    "while", "conditional", "call", "custom-call", "after-all", "domain",
    "partition-id", "replica-id", "rng-get-and-update-state",
}


def materialized_bytes(hlo: str) -> float:
    """Trip-count-aware sum of result-buffer bytes over every materializing
    op in the optimized HLO — a proxy for HBM write traffic of the lowering
    (each buffer is also read at least once downstream, so relative
    comparisons of two lowerings track total traffic).

    Ops inside fusion computations are skipped (the fusion's own result is
    the only materialized buffer); while bodies are multiplied by their trip
    counts, so a scan-over-steps counts every per-step temporary."""
    comps = _parse_computations(hlo)
    mult = _multipliers(comps)
    fused = set()
    for ops in comps.values():
        for op in ops:
            if op.kind == "fusion":
                fused.update(_called_comps(op))
    total = 0.0
    for cname, ops in comps.items():
        if cname in fused:
            continue
        m = mult.get(cname, 0.0)
        if m <= 0:
            continue
        for op in ops:
            if op.kind in _NON_MATERIAL:
                continue
            total += m * shape_bytes(op.shape)
    return total


def roofline_terms(dot_flops_per_dev: float, mem_bytes_per_dev: float,
                   coll_bytes_per_dev: float, *, device_kind: str,
                   ici_links: float = 4.0):
    """Three roofline terms in seconds (per device, per step) on the peaks
    of ``device_kind``."""
    hw = peaks(device_kind)
    t_compute = dot_flops_per_dev / hw["peak_flops_bf16"]
    t_memory = mem_bytes_per_dev / hw["hbm_bw"]
    t_coll = coll_bytes_per_dev / (hw["ici_bw"] * ici_links)
    dominant = max((t_compute, "compute"), (t_memory, "memory"),
                   (t_coll, "collective"))
    return {"t_compute_s": t_compute, "t_memory_s": t_memory,
            "t_collective_s": t_coll, "dominant": dominant[1],
            "roofline_fraction": t_compute / max(
                t_compute, t_memory, t_coll, 1e-30)}
