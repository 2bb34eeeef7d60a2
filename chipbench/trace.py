"""Reduce a profiler trace (``.xplane.pb``) to device-op intervals.

What a TPU v5e trace holds, as read by ``jax.profiler.ProfileData``: one
plane per chip (``/device:TPU:<n>``) whose ``XLA Ops`` line has one event
per executed HLO instruction, named by the instruction's text
(``%fusion.12 = bf16[...] fusion(...)``), with start and duration in ns on
the host's clock.  A ``while`` event spans its body's events, so only the
leaves count.  (The ``Async XLA Ops`` line, the transfers of async copies
and collectives while they are in flight, is not read: those overlap the
ops that hold the core.)  The host planes' ``python`` thread carries the
harness's own annotations (``bench/batch``, ``bench/dispatch``,
``bench/wait``).

The trace has no ``op_name`` of its own, so each device op is tagged with
the ``loco/<phase>`` scope (the program's ``telemetry/profiler.phase``
scopes) of its instruction's ``metadata={op_name=...}`` in the compiled
step's HLO text.  Instructions that the compiler adds without metadata
(copies, layout changes) carry no scope.

The traced window runs from the first harness annotation to the end of
the last device op.  ``busy_s`` is the union of the leaf op intervals on a
chip, averaged over the chips.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path

COLLECTIVE_RE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|async-collective|send|recv)")
CONTAINERS = ("while", "conditional", "call")
_EVENT_RE = re.compile(r"^%([\w.\-]+) = ")
SCOPE_RE = re.compile(r"(loco/[a-z]+)")
HOST_PREFIX = "bench/"
_OPNAME_RE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=.*?op_name="([^"]*)"')


@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    name: str
    start: int          # ns
    end: int            # ns
    scope: str          # "loco/<phase>" or ""
    what: str           # the tail of the op_name metadata
    collective: bool


def _union(iv: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(iv) -> int:
    return sum(b - a for a, b in iv)


@dataclasses.dataclass
class Reduced:
    ops: list[Op]
    host: list[tuple[str, int, int]]    # (annotation, start, end) in ns
    n_devices: int
    t0: int
    t1: int

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _by_device(self, pred=lambda op: True):
        out: dict[int, list] = {}
        for op in self.ops:
            if pred(op):
                out.setdefault(op.device, []).append((op.start, op.end))
        return {d: _union(iv) for d, iv in out.items()}

    @property
    def busy_s(self) -> float:
        per = self._by_device()
        if not per:
            return 0.0
        return sum(_length(iv) for iv in per.values()) / self.n_devices / 1e9

    def op_ms(self, pred) -> float:
        """Summed device ms of the ops ``pred`` selects, per chip."""
        ns = sum(op.end - op.start for op in self.ops if pred(op))
        return ns / self.n_devices / 1e6

    def breakdown(self, top: int = 10) -> dict:
        per_name: dict[str, int] = {}
        for op in self.ops:
            key = f"{op.name} {op.what}".strip()
            per_name[key] = per_name.get(key, 0) + op.end - op.start
        ops = sorted(per_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for d, iv in self._by_device().items():
            edges = [(self.t0, self.t0)] + iv + [(self.t1, self.t1)]
            for (_, e), (s, _) in zip(edges, edges[1:]):
                if s > e:
                    gaps.append((self._host_at((s + e) // 2), s - e))
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": [[k, v / self.n_devices / 1e9] for k, v in ops],
                "idle_gaps": [[k, v / 1e9] for k, v in gaps[:top]]}

    def _host_at(self, t: int) -> str:
        """The innermost harness annotation open on the host at ``t``."""
        best = None
        for name, s, e in self.host:
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0] if best else "host: outside the step loop"


def op_names_of_hlo(hlo: str) -> dict[str, str]:
    """{HLO instruction name: op_name metadata} of compiled HLO text."""
    out = {}
    for line in hlo.splitlines():
        m = _OPNAME_RE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def _instr(event_name: str) -> str:
    m = _EVENT_RE.match(event_name)
    return m.group(1) if m else event_name


def _kind(instr: str) -> str:
    return re.sub(r"\.\d+.*$", "", instr)


def reduce_file(path, hlo: str | None = None) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    meta = op_names_of_hlo(hlo) if hlo else {}
    ops, host = [], []
    devices = []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            dev = int(m.group(1))
            devices.append(dev)
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    name = _instr(ev.name)
                    kind = _kind(name)
                    if kind in CONTAINERS:
                        continue
                    op_name = meta.get(name, "")
                    sm = SCOPE_RE.search(op_name)
                    ops.append(Op(dev, name, int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns),
                                  sm.group(1) if sm else "",
                                  "/".join(op_name.split("/")[-3:]),
                                  bool(COLLECTIVE_RE.match(kind))))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns)))
    if not ops:
        raise ValueError(f"{path}: no device ops in the trace")
    t0 = min([s for _, s, _ in host] or [op.start for op in ops])
    t1 = max([op.end for op in ops] + [e for _, _, e in host])
    return Reduced(ops=ops, host=host, n_devices=max(len(devices), 1),
                   t0=t0, t1=t1)


def reduce_dir(trace_dir, hlo: str | None = None) -> Reduced:
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(files[-1], hlo)
