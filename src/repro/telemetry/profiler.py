"""Trace-time scopes for the step's phases and layers (DESIGN.md §14).

Two complementary mechanisms:

* :func:`phase` and :func:`layer` — ``jax.named_scope`` wrappers applied
  at *trace* time.  ``phase`` names the sync path (``loco/gather`` around
  the forward weight all-gather in core/hijack, ``encode`` -> ``exchange``
  -> ``decode`` in core/comm, ``apply``/``metrics`` in launch/steps);
  ``layer`` names the model's parts (``model/embed``, ``model/layers``,
  ``model/attention``, ``model/mlp``, ``model/head`` in
  models/transformer).  The names land in the lowered HLO metadata
  (``op_name=".../loco/encode/..."``), so XLA profiler traces and HLO
  dumps show the step's structure by name; an op belongs to the innermost
  scope on its path.  They are metadata only: opcode and instruction-name
  text are unchanged, so ``analysis.hlo_stats`` parses annotated modules
  identically (pinned in tests/test_metrics.py and tests/test_scopes.py).
* :class:`TraceSession` + :func:`parse_window` — host-side capture of a
  ``jax.profiler.start_trace`` dir for a step window (``--profile-steps
  N:M`` in launch/train.py).  A failed start or stop raises: a run asked
  for a trace and must not exit 0 without one.
"""
from __future__ import annotations

import jax

PHASES = ("gather", "encode", "exchange", "decode", "apply", "metrics",
          "probe")


def phase(name: str, group: int | None = None):
    """Named scope for one sync phase (trace-time; nestable).

    ``group`` tags the scope with an overlap-schedule stage index
    (``loco/encode/g0``, ``loco/exchange/g1``, ...), so profiler traces of
    the pipelined schedule (DESIGN.md §15) show which stage each
    encode/exchange/decode region belongs to — the interleaving
    ``encode/g1`` inside ``exchange/g0``'s window is the overlap itself.
    """
    if group is None:
        return jax.named_scope(f"loco/{name}")
    return jax.named_scope(f"loco/{name}/g{group}")


def layer(name: str):
    """Named scope for one part of the model (trace-time; nestable):
    ``embed``, ``layers`` (the layer scan), ``attention``, ``mlp`` and
    ``head`` make ``model/<name>``, ``model/attention`` nesting inside
    ``model/layers``."""
    return jax.named_scope(f"model/{name}")


def parse_window(spec: str) -> tuple[int, int]:
    """``"N:M"`` (inclusive step window) or ``"N"`` (single step)."""
    try:
        if ":" in spec:
            a, b = spec.split(":")
            lo, hi = int(a), int(b)
        else:
            lo = hi = int(spec)
    except ValueError:
        raise ValueError(
            f"--profile-steps expects 'N:M' or 'N', got {spec!r}") from None
    if lo < 0 or hi < lo:
        raise ValueError(f"--profile-steps window {spec!r} is empty")
    return lo, hi


class TraceSession:
    """Start/stop ``jax.profiler`` tracing around a step window."""

    def __init__(self, trace_dir: str, window: tuple[int, int]):
        self.trace_dir = trace_dir
        self.lo, self.hi = window
        self.active = False

    def maybe_start(self, step: int) -> None:
        if self.active or step != self.lo:
            return
        jax.profiler.start_trace(self.trace_dir)
        self.active = True
        print(f"profiler: tracing steps {self.lo}..{self.hi} "
              f"-> {self.trace_dir}", flush=True)

    def maybe_stop(self, step: int) -> None:
        if self.active and step >= self.hi:
            self.stop()

    def stop(self) -> None:
        if not self.active:
            return
        self.active = False
        jax.profiler.stop_trace()
        print(f"profiler: trace written to {self.trace_dir}", flush=True)
