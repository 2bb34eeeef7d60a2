"""The comparison that decides ``correct``.

Three numbers per run, each against the limit in ``limits/<cell>.json``:

* ``loss_gap``    largest gap, in nats, between the program's loss and the
  reference's over the first steps;
* ``grad_gap``    the worst leaf's gap between the norms of the first
  step's gradient as the optimizer got it (the program's is read from
  Adam's first moment after one step, ``m / (1 - b1)``) and the
  reference's clipped gradient;
* ``change_gap``  the worst leaf's gap between the norms of the
  parameters' change over the first steps.

A leaf is one weight tensor of one layer.  A gap is
``|norm_program - norm_reference| / max(norm_reference, median leaf norm)``.
Leaves whose reference gradient is under a thousandth of the median
leaf's move by round-off alone and are left out of ``change_gap``.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

NAMES = ("loss_gap", "grad_gap", "change_gap")
QUIET = 1e-3     # a leaf whose gradient is under this share of the median's


@dataclasses.dataclass(frozen=True)
class Number:
    name: str
    value: float
    limit: float | None
    worst: str = ""     # the leaf that set it

    @property
    def ok(self) -> bool:
        return (self.limit is not None and math.isfinite(self.value)
                and self.value <= self.limit)


def _leaves(norms: dict) -> dict:
    """{name: array or scalar} -> {leaf label: float}, one per layer."""
    out = {}
    for n, v in norms.items():
        v = np.asarray(v, dtype=np.float64)
        if v.ndim == 0:
            out[n] = float(v)
        else:
            out.update({f"{n}[{i}]": float(x) for i, x in enumerate(v)})
    return out


def worst_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    p, r = _leaves(prog), _leaves(ref)
    med = float(np.median(list(r.values())))
    worst, where = 0.0, ""
    for k, rv in r.items():
        if keep is not None and k not in keep:
            continue
        g = abs(p[k] - rv) / max(rv, med, 1e-30)
        if not math.isfinite(g):
            return math.inf, k
        if g > worst:
            worst, where = g, k
    return worst, where


def gaps(readings: dict, ref: dict) -> dict:
    """{name: (value, worst leaf)} of a program's readings."""
    lp = np.asarray(readings["losses"], dtype=np.float64)
    lr = np.asarray(ref["losses"], dtype=np.float64)
    d = np.abs(lp - lr)
    loss = (float(np.max(d)) if np.all(np.isfinite(d)) else math.inf,
            f"step {int(np.argmax(d))}" if np.all(np.isfinite(d)) else "")
    rg = _leaves(ref["grad"])
    med = float(np.median(list(rg.values())))
    moving = {k for k, v in rg.items() if v >= QUIET * med}
    return {"loss_gap": loss,
            "grad_gap": worst_gap(readings["grad"], ref["grad"]),
            "change_gap": worst_gap(readings["change"], ref["change"],
                                    keep=moving)}


def compare(readings: dict, ref: dict, limits: dict) -> list[Number]:
    g = gaps(readings, ref)
    return [Number(n, g[n][0], limits.get(n, {}).get("limit"), g[n][1])
            for n in NAMES]


def passed(numbers: list[Number]) -> bool:
    return all(x.ok for x in numbers)


def lines(numbers: list[Number]) -> list[str]:
    return [f"check {x.name} = {x.value!r} limit {x.limit!r} "
            f"({'ok' if x.ok else 'FAILED'}; worst {x.worst})"
            for x in numbers]


def as_json(numbers: list[Number]) -> dict:
    return {x.name: {"value": x.value, "limit": x.limit} for x in numbers}
