"""Bucketed-sync sweep: step time, wire traffic AND collective launches.

Runs the real distributed train step (mesh dp=2 x tp=2 on CPU host devices)
under the bucketed scheduler at several bucket targets and per-class wire
policies, and reports measured step latency next to the static wire-byte /
launch accounting from repro.telemetry.wire.  On CPU the latency numbers
tell you about scheduling overhead (many small collectives vs one big
one) — which is exactly what the wire coalescer (DESIGN.md §13) removes —
while the wire/ratio columns are the hardware-independent signal.

Each row also carries the compiled step's trip-count-weighted collective
LAUNCH counts (repro.analysis.hlo_stats.collective_launches): bytes are
invariant under coalescing, launches are the thing that drops from
O(buckets x leaves) to O(comm groups).  The sweep asserts two acceptance
criteria: the coalesced bucketed step stays within 5% of monolithic, and
its all-to-all launch count equals the comm-group prediction.

Timing methodology: two warm steps per config, then the configs are
stepped round-robin (INTERLEAVED) and each reports the MEDIAN of its
per-step blocked timings plus the MIN (the acceptance ratio uses the
min: ambient load only ever adds time, so it isolates intrinsic cost).
The old schedule — 1 warm step, mean of 3, one config after another —
is where the phantom "mixed_64k 94% slower" outlier came from: the compiled HLO of the mixed plan is equivalent to
the uniform plan's (same collectives, same flops), steady-state
isolation shows no gap, and the retrace-count regression is pinned in
tests/test_wirepack.py; what the old numbers measured was host-load
drift across the sequential sweep, which interleaving cancels.

  PYTHONPATH=src python benchmarks/bench_buckets.py --quick
  -> BENCH_buckets.json  (+ name,us_per_call,derived CSV rows)
"""
from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import dataclasses
import statistics
import sys
import time

import jax
import jax.numpy as jnp

try:
    from benchmarks.common import csv_row, write_bench_json
except ModuleNotFoundError:  # invoked as `python benchmarks/bench_buckets.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks.common import csv_row, write_bench_json
from repro.analysis.hlo_stats import collective_launches
from repro.configs.base import ShapeConfig, get_arch, reduced
from repro.core import policy as POL
from repro.core import wirepack as WP
from repro.core.loco import SyncConfig
from repro.core.quantizer import QuantConfig
from repro.data.synthetic import DataConfig, make_batch_fn
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import RunConfig, make_init, make_train_step
from repro.telemetry import wire as WIRE

CFG = reduced(get_arch("llama2-400m"))
SHAPE = ShapeConfig("bench", seq_len=32, global_batch=8, kind="train")
SYNC = SyncConfig(strategy="loco", quant=QuantConfig(mode="block"))


def sweep_configs(quick: bool) -> dict[str, RunConfig]:
    base = RunConfig(sync=SYNC, optimizer="adam", microbatch=2,
                     total_steps=1000, warmup_steps=10, lr=1e-3)
    mixed = POL.parse_policy("embed=loco8,norm=fp,min=16384", SYNC)
    out = {
        "monolithic": base,
        # backward-overlapped stage schedule (the default, DESIGN.md §15)
        # vs the flat single-sync-region schedule vs per-bucket-leaf
        "bucket_64k": dataclasses.replace(base, bucket_bytes=64 << 10),
        "bucket_64k_legacy": dataclasses.replace(base, bucket_bytes=64 << 10,
                                                 overlap=False),
        "bucket_64k_percall": dataclasses.replace(base, bucket_bytes=64 << 10,
                                                  coalesce=False),
        "mixed_64k": dataclasses.replace(base, bucket_bytes=64 << 10,
                                         policy=mixed),
        # in-graph compression-health metrics (DESIGN.md §14): must ride the
        # existing collectives and stay within noise of the plain step
        "bucket_64k_metrics": dataclasses.replace(base, bucket_bytes=64 << 10,
                                                  telemetry=True),
    }
    if not quick:
        out.update({
            "bucket_256k": dataclasses.replace(base, bucket_bytes=256 << 10),
            "bucket_1m": dataclasses.replace(base, bucket_bytes=1 << 20),
            # min sits between the reduced model's bucket sizes (attention
            # projections: 32768 global elems -> fp; embed/head/ffn: 65536
            # -> loco), so the row actually measures skipping small buckets.
            "skip_small": dataclasses.replace(
                base, bucket_bytes=1 << 20,
                policy=POL.parse_policy("min=65536", SYNC)),
            "uniform_fp": dataclasses.replace(
                base, bucket_bytes=64 << 10,
                policy=POL.uniform(SyncConfig(strategy="fp"))),
        })
    return out


def expected_a2a_per_step(plan, topo, accum: int,
                          overlap: bool = False) -> int:
    """Coalesced all-to-all launches one optimizer step must compile to:
    one per a2a comm group per flat mesh axis, x stacked layers, x the
    gradient-accumulation microbatches.  Under the overlapped schedule
    each pipeline stage issues its own packed collectives, so groups cut
    by a stage boundary count once per stage they span."""
    axes = 2 if topo.pods > 1 else 1
    total = 0
    for pp in plan.params:
        D = pp.buckets[0].seg_elems // pp.buckets[0].chunk_elems
        if overlap:
            sched = WP.build_overlap_schedule(pp, D, pods=max(topo.pods, 1))
            gplans = [st.gplan for st in sched.stages]
        else:
            gplans = [WP.build_group_plan(pp, D, pods=max(topo.pods, 1))]
        for gp in gplans:
            for g in gp.groups:
                if g.kind == "a2a":
                    total += pp.layers * (axes if g.stage == "flat" else 1)
    return accum * total


class _Cell:
    """One sweep config's live step state (for the interleaved timing)."""

    def __init__(self, name: str, run: RunConfig, mesh):
        self.name = name
        self.run = run
        init_fn, _ = make_init(CFG, run, mesh)
        self.arrs = list(init_fn(jax.random.PRNGKey(0)))  # chunks/states/opt
        self.bundle = make_train_step(CFG, run, mesh, SHAPE)
        self.times: list[float] = []
        self.loss = None

    def step(self, i: int, batch, timed: bool) -> None:
        t0 = time.perf_counter()
        *self.arrs, m = self.bundle.fn(*self.arrs, jnp.int32(i), batch)
        jax.block_until_ready(m["loss"])
        if timed:
            self.times.append((time.perf_counter() - t0) * 1e3)
        self.loss = float(m["loss"])

    def row(self) -> dict:
        # trip-count-weighted collective launches of the compiled step
        bundle = self.bundle
        hlo = bundle.fn.lower(*bundle.input_shapes).compile().as_text()
        launches = {k: round(v) for k, v in collective_launches(hlo).items()}
        plan = bundle.helpers["plan"]
        topo = bundle.helpers["topo"]
        overlapped = bool(plan is not None and self.run.coalesce
                          and self.run.overlap)
        row = {"step_ms": statistics.median(self.times),
               "step_ms_min": min(self.times),
               "final_loss": self.loss,
               "n_buckets": 0, "wire_bytes": None, "ratio_vs_bf16": None,
               "launches": launches,
               "overlap": overlapped,
               "groups_inflight": bundle.helpers.get("groups_inflight", 1)}
        if plan is not None:
            rep = WIRE.plan_report(plan, pods=topo.pods)
            row.update(n_buckets=plan.n_buckets, wire_bytes=rep.total_wire,
                       ratio_vs_bf16=rep.ratio_vs_bf16,
                       state_bytes=rep.state_bytes,
                       by_class={k: v for k, v in rep.by_class().items()},
                       launches_static=WIRE.plan_launches(plan,
                                                          pods=topo.pods),
                       a2a_per_step_expected=expected_a2a_per_step(
                           plan, topo, bundle.helpers["accum"],
                           overlap=overlapped))
        csv_row(f"buckets/{self.name}", row["step_ms"] * 1e3,
                f"wire={row['wire_bytes']} ratio={row['ratio_vs_bf16']} "
                f"a2a={launches.get('all-to-all', 0)}")
        return row


def check(results: dict) -> None:
    """Acceptance criteria of the coalesced wire exchange (ISSUE 5)."""
    mono = results["monolithic"]
    coal = results["bucket_64k"]
    # launch count: all-to-all launches == coalesced comm-group prediction
    got = coal["launches"].get("all-to-all", 0)
    want = coal["a2a_per_step_expected"]
    assert got == want, (
        f"coalesced bucketed step compiled to {got} all-to-all launches, "
        f"expected {want} (one per a2a comm group x layers x accum)")
    seq = results.get("bucket_64k_percall")
    if seq is not None:
        got_seq = seq["launches"].get("all-to-all", 0)
        assert got_seq > got, (got_seq, got)
    legacy = results.get("bucket_64k_legacy")
    oratio = None
    if legacy is not None:
        # the legacy flat schedule's launch count must also match ITS
        # prediction (no stage splits)
        assert (legacy["launches"].get("all-to-all", 0)
                == legacy["a2a_per_step_expected"]), (
            legacy["launches"], legacy["a2a_per_step_expected"])
        # bit-exactness (ISSUE 7): the overlapped schedule reorders
        # launches but computes the SAME floats -- losses are identical
        # to the last bit, every run
        assert coal["final_loss"] == legacy["final_loss"], (
            "overlapped schedule diverged from the flat schedule",
            coal["final_loss"], legacy["final_loss"])
        # the schedule really pipelines (double-buffered, depth 2) and
        # pays at most the stage-split launches for it
        assert coal["groups_inflight"] == 2, coal["groups_inflight"]
        assert (coal["launches_static"]["overlapped"]
                >= coal["launches_static"]["coalesced"])
        # overlapping must not slow the step down (min-based ratio, same
        # host-load rationale as below); the latency WIN only shows on
        # backends with async collectives -- on CPU this is purely a
        # no-regression bound
        oratio = coal["step_ms_min"] / legacy["step_ms_min"]
        assert oratio <= 1.05, (
            f"overlapped step is {oratio:.3f}x the legacy flat schedule "
            f"({coal['step_ms_min']:.0f} vs {legacy['step_ms_min']:.0f} ms "
            f"min; medians {coal['step_ms']:.0f} vs {legacy['step_ms']:.0f})")
    # step time: coalesced bucketing within 5% of the monolithic step.
    # Compared on the per-step MIN: ambient host load only ever adds time,
    # so the min isolates each config's intrinsic cost (the medians are
    # reported alongside for context).
    ratio = coal["step_ms_min"] / mono["step_ms_min"]
    assert ratio <= 1.05, (
        f"coalesced bucketed step is {ratio:.3f}x monolithic "
        f"({coal['step_ms_min']:.0f} vs {mono['step_ms_min']:.0f} ms min; "
        f"medians {coal['step_ms']:.0f} vs {mono['step_ms']:.0f}); "
        "the coalescer should make per-bucket policies ~free")
    mixed = results.get("mixed_64k")
    if mixed is not None:
        # the old mixed_64k outlier (>1.5x) must stay gone
        assert mixed["step_ms_min"] / mono["step_ms_min"] <= 1.5, (
            mixed["step_ms_min"], mono["step_ms_min"])
    met = results.get("bucket_64k_metrics")
    mratio = None
    if met is not None:
        # in-graph metrics must not add collectives (they ride the loss
        # reduction -- DESIGN.md §14) and must stay cheap relative to the
        # plain step (min-based for the same host-load reason as above).
        # The probe's absolute cost is schedule-independent (grad_metrics
        # re-quantizes every unit either way), but the overlapped schedule
        # it is now measured against is ~20% faster than the flat one that
        # set the original 5% budget -- and has no idle slack to hide the
        # probe under -- so the same absolute cost reads as a larger
        # fraction: 10% on the min keeps the guard meaningful without
        # flagging the denominator shrink as a metrics regression.
        assert met["launches"] == coal["launches"], (
            "telemetry changed the collective schedule",
            met["launches"], coal["launches"])
        mratio = met["step_ms_min"] / coal["step_ms_min"]
        assert mratio <= 1.10, (
            f"metrics-enabled step is {mratio:.3f}x the plain step "
            f"({met['step_ms_min']:.0f} vs {coal['step_ms_min']:.0f} ms min; "
            f"medians {met['step_ms']:.0f} vs {coal['step_ms']:.0f})")
    print(f"# check ok: a2a launches {got} == {want} comm groups, "
          f"coalesced/monolithic step {ratio:.3f}x"
          + (f", overlapped/legacy {oratio:.3f}x" if oratio is not None
             else "")
          + (f", metrics overhead {mratio:.3f}x "
             f"(median {met['step_ms'] / coal['step_ms']:.3f}x)"
             if mratio is not None else ""))


def run(quick: bool = False, steps: int | None = None,
        out: str = "BENCH_buckets.json") -> dict:
    steps = steps or (7 if quick else 12)
    mesh = make_local_mesh(dp=2, tp=2)
    bf = make_batch_fn(DataConfig(vocab=CFG.vocab, seq_len=SHAPE.seq_len,
                                  global_batch=SHAPE.global_batch, seed=0))
    cells = [_Cell(name, rc, mesh) for name, rc in sweep_configs(quick).items()]
    # 2 warm steps each, then interleave the timed steps round-robin so
    # host-load drift hits every config equally (module docstring)
    for i in range(steps + 2):
        batch = bf(jnp.int32(i))
        for c in cells:
            c.step(i, batch, timed=i >= 2)
    results = {c.name: c.row() for c in cells}
    check(results)
    write_bench_json(out, "buckets", results)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="4 configs x 7 steps (CI smoke)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--out", default="BENCH_buckets.json")
    args = ap.parse_args()
    run(quick=args.quick, steps=args.steps, out=args.out)


if __name__ == "__main__":
    main()
