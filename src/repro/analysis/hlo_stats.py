"""Trip-count-aware static analysis of compiled (post-SPMD) HLO text.

Motivation: ``compiled.cost_analysis()`` counts each ``while`` body ONCE,
but our steps are scans over microbatches x layers x kv-blocks, so flops /
bytes / collective traffic are undercounted by the product of trip counts
(measured ~120x on a 24-layer model).  This module walks the computation
graph from ENTRY, multiplying every ``while`` body by its trip count
(recovered from the single s32 constant in the loop condition -- the form
``lax.scan`` lowers to), and accumulates:

* ``flops``     -- 2*prod(result)*K for every ``dot`` (contracting size K
                   from the lhs shape + lhs_contracting_dims);
                   elementwise/transcendental flops are NOT counted, so the
                   compute term is a slight lower bound (documented).
* ``bytes``     -- HBM-traffic estimate: materializing ops (fusions, dots,
                   copies, dynamic-(update-)slices, reduces, ...) count
                   operands + result; standalone elementwise ops count their
                   result only (a TPU lowering would fuse them into
                   neighbors, so charging their operand reads again would
                   double-count; CPU HLO fuses less aggressively than
                   Mosaic/XLA-TPU).  This makes the memory term an estimate,
                   not ground truth -- consistent across configs, which is
                   what the §Perf iteration needs.
* collectives   -- wire bytes per kind, with the same (N-1)/N accounting as
                   analysis/roofline.parse_collectives, x trip weights — and
                   per-kind LAUNCH counts (``coll_counts`` /
                   :func:`collective_launches`), the number the wire
                   coalescer [DESIGN.md §13] drives down while bytes stay
                   fixed.

Validated against cost_analysis on loop-free modules (test_analysis.py).
"""
from __future__ import annotations

import dataclasses
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
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_SKIP_OPS = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
             "after-all", "partition-id", "replica-id", "iota"}
# ops that materialize buffers in HBM on any backend: charge operands+result.
# everything else (standalone elementwise) charges its result only -- a TPU
# lowering fuses those into producers/consumers.
_MATERIALIZING = {"fusion", "dot", "convolution", "copy", "dynamic-slice",
                  "dynamic-update-slice", "reduce", "reduce-window", "sort",
                  "scatter", "gather", "concatenate", "pad", "reverse",
                  "select-and-scatter", "custom-call", "slice", "transpose",
                  "reshape", "broadcast"}
_COLLECTIVES = {"all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute"}


def _shape_elems(type_str: str):
    """All (dtype, numel) array shapes mentioned in a type string."""
    out = []
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        out.append((dt, n))
    return out


def _shape_bytes(type_str: str) -> float:
    return sum(n * _DTYPE_BYTES[dt] for dt, n in _shape_elems(type_str))


@dataclasses.dataclass
class Instr:
    name: str
    result_type: str
    opcode: str
    rest: str  # raw text after the opening paren


def parse_computations(hlo: str) -> tuple[dict, str]:
    """Returns ({comp_name: [Instr, ...]}, entry_name)."""
    comps: dict[str, list[Instr]] = {}
    entry = None
    cur: list[Instr] | None = None
    for line in hlo.splitlines():
        m = _COMP_HEAD_RE.match(line)
        if m:
            name = m.group(2)
            comps[name] = cur = []
            if m.group(1):
                entry = name
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        mi = _INSTR_RE.match(line)
        if mi:
            cur.append(Instr(mi.group(1), mi.group(2), mi.group(3), mi.group(4)))
    assert entry is not None, "no ENTRY computation found"
    return comps, entry


def _trip_count(cond_instrs: list[Instr]) -> int:
    best = 1
    for ins in cond_instrs:
        if ins.opcode == "constant" and ins.result_type.strip() == "s32[]":
            m = re.match(r"([\-0-9]+)\)", ins.rest)
            if m:
                best = max(best, int(m.group(1)))
    return best


_DOT_LHS_CONTRACT = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


@dataclasses.dataclass
class HloStats:
    flops: float = 0.0
    bytes: float = 0.0
    wire_bytes: float = 0.0
    coll_bytes: dict = dataclasses.field(default_factory=dict)
    coll_counts: dict = dataclasses.field(default_factory=dict)
    dot_flops_by_shape: dict = dataclasses.field(default_factory=dict)

    def add(self, other: "HloStats", w: float):
        self.flops += w * other.flops
        self.bytes += w * other.bytes
        self.wire_bytes += w * other.wire_bytes
        for k, v in other.coll_bytes.items():
            self.coll_bytes[k] = self.coll_bytes.get(k, 0.0) + w * v
        for k, v in other.coll_counts.items():
            self.coll_counts[k] = self.coll_counts.get(k, 0.0) + w * v
        for k, v in other.dot_flops_by_shape.items():
            self.dot_flops_by_shape[k] = self.dot_flops_by_shape.get(k, 0.0) + w * v


_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _group_size(rest: str) -> int:
    g = _GROUPS_RE.search(rest)
    if g:
        return len(g.group(1).split(","))
    g2 = _GROUPS_V2_RE.search(rest)
    if g2:
        return int(g2.group(2))
    return 1


def _collective_wire(opcode: str, result_type: str, rest: str) -> float:
    shapes = _shape_elems(result_type)
    if not shapes:
        return 0.0
    if opcode == "all-to-all":
        # XLA may lower all-to-all in TUPLE form: one result per peer; the
        # total exchanged payload is the sum of all tuple elements (the
        # array form has a single shape, so summing is correct for both).
        out_b = sum(n * _DTYPE_BYTES[dt] for dt, n in shapes)
    else:
        # async -start ops have tuple results; the last element is the output
        dt, n = shapes[-1]
        out_b = n * _DTYPE_BYTES[dt]
    g = _group_size(rest)
    frac = (g - 1) / g if g > 1 else 0.0
    if opcode == "all-gather":
        return out_b * frac
    if opcode == "reduce-scatter":
        return out_b * (g - 1)
    if opcode == "all-reduce":
        return 2 * out_b * frac
    if opcode == "all-to-all":
        return out_b * frac
    return out_b  # collective-permute


def _analyze_comp(name: str, comps: dict, memo: dict) -> HloStats:
    if name in memo:
        return memo[name]
    st = HloStats()
    memo[name] = st  # placeholder to guard recursion
    shape_of = {i.name: i.result_type for i in comps[name]}

    for ins in comps[name]:
        op = ins.opcode
        base = op.replace("-start", "") if op.endswith("-start") else op
        if op.endswith("-done"):
            continue
        if base in _COLLECTIVES:
            w = _collective_wire(base, ins.result_type, ins.rest)
            st.wire_bytes += w
            st.coll_bytes[base] = st.coll_bytes.get(base, 0.0) + w
            st.coll_counts[base] = st.coll_counts.get(base, 0) + 1
            st.bytes += _shape_bytes(ins.result_type)
            continue
        if op == "while":
            mb = re.search(r"body=%?([\w.\-]+)", ins.rest)
            mc = re.search(r"condition=%?([\w.\-]+)", ins.rest)
            if mb and mc and mb.group(1) in comps:
                trips = _trip_count(comps[mc.group(1)]) if mc.group(1) in comps else 1
                st.add(_analyze_comp(mb.group(1), comps, memo), trips)
            continue
        if op == "call":
            mt = re.search(r"to_apply=%?([\w.\-]+)", ins.rest)
            if mt and mt.group(1) in comps:
                st.add(_analyze_comp(mt.group(1), comps, memo), 1.0)
            continue
        if op == "conditional":
            for mt in re.finditer(r"(?:branch_computations=\{|true_computation=|"
                                  r"false_computation=)%?([\w.\-]+)", ins.rest):
                if mt.group(1) in comps:
                    st.add(_analyze_comp(mt.group(1), comps, memo), 1.0)
            continue
        if op in _SKIP_OPS:
            continue
        fl, b = _instr_cost(ins, shape_of)
        if op == "dot":
            key = ins.result_type.split(" ")[0]
            st.dot_flops_by_shape[key] = st.dot_flops_by_shape.get(key, 0.0) + fl
        st.flops += fl
        st.bytes += b
    memo[name] = st
    return st


def _instr_cost(ins: Instr, shape_of: dict) -> tuple[float, float]:
    """(flops, hbm_bytes) for one non-control, non-collective instruction."""
    op = ins.opcode
    fl = 0.0
    # ---- flops: dot --------------------------------------------------------
    if op == "dot":
        res = _shape_elems(ins.result_type)
        out_n = res[-1][1] if res else 0
        k = 1
        mlc = _DOT_LHS_CONTRACT.search(ins.rest)
        ops = _OPERAND_RE.findall(ins.rest.split("),")[0] + ")")
        if mlc and ops:
            lhs_type = shape_of.get(ops[0], "")
            lhs_shapes = _SHAPE_RE.findall(lhs_type)
            if lhs_shapes:
                dims = [int(d) for d in lhs_shapes[0][1].split(",")] if lhs_shapes[0][1] else []
                for ci in mlc.group(1).split(","):
                    if ci != "" and int(ci) < len(dims):
                        k *= dims[int(ci)]
        fl = 2.0 * out_n * k
    elif op == "convolution":
        res = _shape_elems(ins.result_type)
        out_n = res[-1][1] if res else 0
        fl = 2.0 * out_n  # lower bound; convs are tiny here
    # ---- bytes (HBM-traffic estimate; see module docstring) ----------------
    b = _shape_bytes(ins.result_type)
    # CPU HLO wraps single elementwise ops as `wrapped_*` kLoop fusions;
    # a TPU lowering would fuse those away -> result-only accounting.
    wrapped_elementwise = op == "fusion" and ins.name.startswith("wrapped_")
    if op in _MATERIALIZING and not wrapped_elementwise:
        arg_txt = ins.rest.split(")")[0]
        for opnd in _OPERAND_RE.findall(arg_txt):
            if opnd in shape_of:
                b += _shape_bytes(shape_of[opnd])
    return fl, b


def analyze(hlo_text: str) -> HloStats:
    comps, entry = parse_computations(hlo_text)
    memo: dict = {}
    return _analyze_comp(entry, comps, memo)


def collective_launches(hlo_text: str) -> dict[str, float]:
    """Trip-count-weighted collective LAUNCH counts per kind.

    Counts every ``all-gather`` / ``all-reduce`` / ``reduce-scatter`` /
    ``all-to-all`` / ``collective-permute`` instruction reachable from
    ENTRY, multiplying loop bodies by their trip counts; async
    ``-start``/``-done`` pairs count once.  This is the per-step *launch*
    number the wire coalescer (DESIGN.md §13) optimizes — wire BYTES are
    invariant under coalescing, so only this count shows the win.
    Validated against hand-countable modules in tests/test_analysis.py.
    """
    return dict(analyze(hlo_text).coll_counts)
