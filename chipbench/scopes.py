"""Device time of a reduced trace by the program's trace-time scopes.

The program names the parts of its step with ``jax.named_scope``s
(``telemetry/profiler.py``): ``loco/<phase>`` for the sync path
(``gather``, ``encode``, ``exchange``, ``decode``, ``apply``, ...) and
``model/<part>`` for the model (``embed``, ``layers``, ``attention``,
``mlp``, ``head``).  They nest (``model/attention`` inside
``model/layers``, ``loco/gather`` inside ``model/layers``), and under
autodiff they appear inside ``jvp(...)`` and ``transpose(...)``, so an
op's scope is the innermost one on its ``op_name`` path: every op counts
to one scope or to none, and the forward, backward and rematerialised ops
of a part count to the same one.

A program without some scope (one built before the scope was placed) has
no op under it: the readers then find nothing and return None.

A collective op is one that ``chipbench/trace.py`` marks so, or whose
instruction is named for a collective with underscores: on a TPU v5e the
all-to-all of the exchange runs as ``all_to_all.<n>``.
"""
from __future__ import annotations

import re

from chipbench import trace as TR

SCOPE_RE = re.compile(r"(?:loco|model)/[a-z]+")
_COLLECTIVE_RE = re.compile(r"^(all_gather|all_reduce|reduce_scatter"
                            r"|all_to_all|collective_permute)")


def innermost(op_name: str) -> str:
    """The innermost ``loco/<phase>`` or ``model/<part>`` of an op_name,
    or "" where it has none."""
    found = SCOPE_RE.findall(op_name)
    return found[-1] if found else ""


def _minus(iv: list, cover: list) -> int:
    """ns of the sorted disjoint intervals ``iv`` outside those of
    ``cover`` (also sorted and disjoint)."""
    out, j = 0, 0
    for a, b in iv:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        t, k = a, j
        while k < len(cover) and cover[k][0] < b:
            out += max(0, cover[k][0] - t)
            t = max(t, cover[k][1])
            k += 1
        out += max(0, b - t)
    return out


class ByScope:
    """The ops of a reduced trace, each with its innermost scope."""

    def __init__(self, red: TR.Reduced, hlo: str, steps: int):
        meta = TR.op_names_of_hlo(hlo or "")
        self.red = red
        self.steps = steps
        self.scope = [innermost(meta.get(op.name, "")) for op in red.ops]
        self.collective = [op.collective or bool(_COLLECTIVE_RE.match(op.name))
                           for op in red.ops]

    def ms(self, scopes, compute_only: bool = False) -> float | None:
        """Device ms per step and chip of the ops whose innermost scope is
        one of ``scopes`` (with ``compute_only``, collectives left out);
        None where no op carries any of them."""
        ns, seen = 0, False
        for op, sc, coll in zip(self.red.ops, self.scope, self.collective):
            if sc in scopes and not (compute_only and coll):
                ns += op.end - op.start
                seen = True
        if not seen:
            return None
        return ns / self.red.n_devices / self.steps / 1e6

    def exposed_ms(self, scope: str) -> float | None:
        """Device ms per step and chip in which an op of ``scope`` runs
        and no compute op of another scope runs on that chip; None where
        no collective op carries the scope.

        The scope's own compute is not cover: a TPU runs an all-gather or
        an all-to-all as the collective plus ``reduce`` and fusion ops
        under the same ``op_name``, and those are the wire's cost too."""
        mine: dict[int, list] = {}
        cover: dict[int, list] = {}
        found = False
        for op, sc, is_coll in zip(self.red.ops, self.scope, self.collective):
            if sc == scope:
                mine.setdefault(op.device, []).append((op.start, op.end))
                found = found or is_coll
            elif not is_coll:
                cover.setdefault(op.device, []).append((op.start, op.end))
        if not found:
            return None
        ns = sum(_minus(TR._union(iv), TR._union(cover.get(d, [])))
                 for d, iv in mine.items())
        return ns / self.red.n_devices / self.steps / 1e6


def of(ctx: dict) -> ByScope:
    """The run's ``ByScope``, made once and kept in ``ctx``."""
    if "by_scope" not in ctx:
        ctx["by_scope"] = ByScope(ctx["trace"], ctx.get("hlo"), ctx["steps"])
    return ctx["by_scope"]
