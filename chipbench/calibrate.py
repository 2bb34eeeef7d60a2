#!/usr/bin/env python3
"""Readings that a cell's limits are set from, in one process on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 12 \
        [--control-seeds 3] [--out readings.json]

For each seed it drives the cell's compiled step through its first steps
(as a run's set-up does), frees the program's state, follows the plain
reference, and records ``check.gaps`` (the lower readings).  On the first
``--control-seeds`` seeds it also records:

* ``control``   the reference computed in float8 put in the program's place
  (``reference.py``, ``precision="f8"``);
* ``half``      the program fed a batch whose second half repeats the first
  (half of the batch left out, the mean taken over the rest);
* ``exchange``  (more than one chip) the reference with the exchange
  between chips left out: each chip applies its own decoded gradient to
  the shard it owns.

A step that returns its state unchanged needs no run: Adam's first moment
stays zero and the parameters do not move, so ``grad_gap`` and
``change_gap`` read 1 by their definition.

The limits in ``limits/<cell>.json`` are set from these readings (see
PERF.md).  The benchmark's runs do not run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 7919)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from chipbench import check, harness
    from chipbench.spec import load_cell

    cell = load_cell(args.workload)
    harness.require_chips(cell.chips)
    b = harness.setup(cell)
    rows = []

    def record(kind, seed, g, secs):
        row = {"kind": kind, "seed": seed, "seconds": secs,
               **{k: v[0] for k, v in g.items()},
               **{f"{k}_at": v[1] for k, v in g.items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)

    def program(seed, fault=None):
        t = time.perf_counter()
        readings, state, batches, ring = harness.first_steps(b, seed, fault)
        del state, batches
        gc.collect()
        return readings, ring, time.perf_counter() - t

    for k in range(args.seeds):
        seed = args.first_seed + 1000003 * k
        readings, ring, t_prog = program(seed)
        t = time.perf_counter()
        ref = harness.follow_reference(b, seed, ring)
        record("program", seed, check.gaps(readings, ref),
               t_prog + time.perf_counter() - t)
        if k >= args.control_seeds:
            continue
        t = time.perf_counter()
        ctl = harness.follow_reference(b, seed, ring, precision="f8")
        record("control", seed, check.gaps(ctl, ref), time.perf_counter() - t)
        half, _, t_half = program(seed, fault="half")
        record("half", seed, check.gaps(half, ref), t_half)
        if cell.dp > 1:
            t = time.perf_counter()
            ex = harness.follow_reference(b, seed, ring, fault="exchange")
            record("exchange", seed, check.gaps(ex, ref),
                   time.perf_counter() - t)

    summary = {}
    for kind in sorted({r["kind"] for r in rows}):
        sel = [r for r in rows if r["kind"] == kind]
        summary[kind] = {n: (max if kind == "program" else min)(
            r[n] for r in sel) for n in check.NAMES}
        summary[kind]["seeds"] = len(sel)
    print("summary (program: largest; others: smallest)",
          json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"cell": cell.name, "rows": rows, "summary": summary},
                      f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
