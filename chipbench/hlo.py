"""Bytes that cross the links per step, read from compiled HLO text.

Copied from the program's ``repro.analysis.hlo_stats`` (the trip-count
walk: ENTRY down, each ``while`` body weighted by the trip count of its
condition's s32 constant) and ``repro.analysis.roofline`` (the
per-collective rule, per device):

  all-gather          out_bytes * (N-1)/N
  reduce-scatter      out_bytes * (N-1)     (out is one peer's shard)
  all-reduce          2 * out_bytes * (N-1)/N
  all-to-all          out_bytes * (N-1)/N   (tuple forms summed)
  collective-permute  out_bytes

Kept here so that no change to the program moves the yardstick.
"""
from __future__ import annotations

import re

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
}
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COMP_HEAD_RE = re.compile(
    r"^(ENTRY\s+)?%?([\w.\-]+)\s+\((.*?)\)\s+->\s+(.+?)\s+\{\s*$")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.+?)\s+([\w\-]+)\((.*)$")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shapes(type_str: str):
    out = []
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt in _DTYPE_BYTES:
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            out.append((dt, n))
    return out


def parse(hlo: str):
    """({computation: [(name, result_type, opcode, rest)]}, entry)."""
    comps, entry, cur = {}, None, None
    for line in hlo.splitlines():
        m = _COMP_HEAD_RE.match(line)
        if m:
            comps[m.group(2)] = cur = []
            if m.group(1):
                entry = m.group(2)
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            mi = _INSTR_RE.match(line)
            if mi:
                cur.append(mi.groups())
    if entry is None:
        raise ValueError("no ENTRY computation in the HLO text")
    return comps, entry


def _trip_count(cond) -> int:
    best = 1
    for _, rtype, op, rest in cond:
        if op == "constant" and rtype.strip() == "s32[]":
            m = re.match(r"([\-0-9]+)\)", rest)
            if m:
                best = max(best, int(m.group(1)))
    return best


def _group_size(rest: str) -> int:
    g = _GROUPS_RE.search(rest)
    if g:
        return len(g.group(1).split(","))
    g2 = _GROUPS_V2_RE.search(rest)
    return int(g2.group(2)) if g2 else 1


def collective_bytes(op: str, result_type: str, rest: str) -> float:
    shapes = _shapes(result_type)
    if not shapes:
        return 0.0
    if op == "all-to-all":
        out_b = sum(n * _DTYPE_BYTES[dt] for dt, n in shapes)
    else:  # an async -start op's result is a tuple ending in the output
        dt, n = shapes[-1]
        out_b = n * _DTYPE_BYTES[dt]
    g = _group_size(rest)
    frac = (g - 1) / g if g > 1 else 0.0
    return {"all-gather": out_b * frac, "reduce-scatter": out_b * (g - 1),
            "all-reduce": 2 * out_b * frac, "all-to-all": out_b * frac,
            }.get(op, out_b)


def _callees(op: str, rest: str) -> list[str]:
    """Computations a call, conditional or async wrapper runs once."""
    if op == "call":
        m = re.search(r"to_apply=%?([\w.\-]+)", rest)
        return [m.group(1)] if m else []
    if op == "async-start":
        m = re.search(r"calls=%?([\w.\-]+)", rest)
        return [m.group(1)] if m else []
    if op == "conditional":
        m = re.search(r"branch_computations=\{([^}]*)\}", rest)
        if m:
            return [c.strip().lstrip("%") for c in m.group(1).split(",")]
        return re.findall(r"(?:true|false)_computation=%?([\w.\-]+)", rest)
    return []


def wire_bytes(hlo: str) -> float:
    """Trip-weighted bytes per device that collectives move in one run."""
    comps, entry = parse(hlo)
    memo: dict = {}

    def walk(name: str) -> float:
        if name in memo:
            return memo[name]
        memo[name] = 0.0
        total = 0.0
        for _, rtype, op, rest in comps[name]:
            base = op[:-len("-start")] if op.endswith("-start") else op
            if op.endswith("-done"):
                continue
            if base in COLLECTIVES:
                total += collective_bytes(base, rtype, rest)
            elif op == "while":
                mb = re.search(r"body=%?([\w.\-]+)", rest)
                mc = re.search(r"condition=%?([\w.\-]+)", rest)
                if mb and mb.group(1) in comps:
                    trips = (_trip_count(comps[mc.group(1)])
                             if mc and mc.group(1) in comps else 1)
                    total += trips * walk(mb.group(1))
            else:
                for callee in _callees(op, rest):
                    if callee in comps:
                        total += walk(callee)
        memo[name] = total
        return total

    return walk(entry)
