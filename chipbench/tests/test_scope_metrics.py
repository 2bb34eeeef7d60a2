"""Device time by the program's scopes (``chipbench/scopes.py``) and the
per-layer metrics that read it, on the CPU: a recorded trace of a program
that has none of the model and gather scopes, hand-made two-chip traces,
and a recorded trace of a program that has them all."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import metrics, model, scopes, spec  # noqa: E402
from chipbench import trace as TR  # noqa: E402

DATA = ROOT / "chipbench/tests/data"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SCOPED = ["attention_ms", "mlp_ms", "head_xent_ms", "embed_ms",
          "layer_scan_ms", "codec_ms", "exchange_exposed_ms",
          "gather_exposed_ms"]
MODEL = {"attention_ms": "model/attention", "mlp_ms": "model/mlp",
         "head_xent_ms": "model/head", "embed_ms": "model/embed",
         "layer_scan_ms": "model/layers"}


def _recorded(expected: str):
    rec = json.loads((DATA / expected).read_text())
    hlo = (DATA / rec["hlo"]).read_text()
    return rec, TR.reduce_file(DATA / rec["file"], hlo), hlo


def _ctx(red, hlo, cell_name="danube-cut.fp.1chip", steps=1):
    cell = spec.load_cell(cell_name)
    return dict(cell=cell, dims=model.Dims.from_config(cell.config),
                trace=red, steps=steps, hlo=hlo,
                device={"platform": "tpu", "kind": "TPU v5 lite",
                        "count": red.n_devices})


@pytest.mark.parametrize("name", SCOPED)
def test_reader_finds_nothing_in_a_program_without_its_scope(name):
    """The PR-12 recording: a danube-cut step built before the model and
    gather scopes, with no encode or exchange either (one chip, fp)."""
    _, red, hlo = _recorded("trace_expected.json")
    assert metrics.load(name).read(_ctx(red, hlo)) is None


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_read_all_leaves_out_what_the_program_lacks(cell):
    _, red, hlo = _recorded("trace_expected.json")
    got = metrics.read_all(_ctx(red, hlo, cell), cell)
    assert not set(got) & set(SCOPED)
    if cell.endswith("1chip"):
        assert {"device_idle_share", "step_mfu", "model_ms",
                "apply_ms"} <= set(got)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(body)/while/body/closed_call/jvp(model/layers)/while/body/"
     "closed_call/model/attention/bhqd,bhkd->bhqk/dot_general",
     "model/attention"),
    ("jit(body)/while/body/closed_call/transpose(jvp(model/layers))/while/"
     "body/closed_call/checkpoint/rematted_computation/model/mlp/mul",
     "model/mlp"),
    ("jit(body)/shard_map/while/body/closed_call/transpose(jvp(model/"
     "layers))/while/body/closed_call/checkpoint/loco/exchange/all_to_all",
     "loco/exchange"),
    ("jit(body)/shard_map/while/body/closed_call/jvp(model/layers)/while/"
     "body/closed_call/loco/gather/all_gather", "loco/gather"),
    ("jit(body)/shard_map/loco/encode/g1/loco_fused_compress/pallas_call",
     "loco/encode"),
    ("jit(body)/while/body/closed_call/transpose(jvp(model/embed))/"
     "scatter-add", "model/embed"),
    ("jit(body)/while/body/closed_call/jvp(model/layers)/while/body/"
     "dynamic_update_slice", "model/layers"),
    ("jit(body)/while/body/dynamic_update_slice", ""),
])
def test_an_op_belongs_to_its_innermost_scope(op_name, scope):
    assert scopes.innermost(op_name) == scope


def _two_chips(spans):
    """A reduced trace of two chips from ``(device, scope, start_ms,
    end_ms, collective)``, with the HLO text that names each op's scope."""
    ops, lines = [], []
    for i, (dev, scope, a, b, coll) in enumerate(spans):
        name = f"{'all-gather' if coll else 'fusion'}.{i}"
        ops.append(TR.Op(dev, name, int(a * 1e6), int(b * 1e6), "", "",
                         coll))
        lines.append(f'  %{name} = f32[] op(), metadata={{op_name='
                     f'"jit(step)/{scope}/op"}}')
    red = TR.Reduced(ops=ops, host=[], n_devices=2, t0=0,
                     t1=max(op.end for op in ops))
    return red, "\n".join(lines)


@pytest.mark.parametrize("case,spans,want", [
    ("hidden", [(0, "loco/gather", 1, 2, True),
                (0, "model/layers/model/mlp", 0.5, 2.5, False),
                (1, "loco/gather", 1, 2, True),
                (1, "model/attention", 1, 1.5, False),
                (1, "model/mlp", 1.5, 2, False)], 0.0),
    ("partly", [(0, "loco/gather", 0, 1, True),
                (0, "model/mlp", 0.5, 1.5, False),
                (1, "loco/gather", 0, 1, True),
                (1, "model/mlp", 0.8, 0.9, False)], (0.5 + 0.9) / 2),
    ("exposed", [(0, "loco/gather", 0, 1, True),
                 (0, "model/mlp", 1, 2, False),
                 (1, "loco/gather", 0, 0.4, True),
                 (1, "loco/gather", 0.3, 1, True)], 1.0),
    ("own_compute", [(0, "loco/gather", 0, 1, True),
                     (0, "loco/gather", 1, 2, False),
                     (0, "model/mlp", 1.5, 2.5, False),
                     (1, "loco/gather", 0, 1, True),
                     (1, "loco/gather", 0.5, 1, False)], (1.5 + 1) / 2),
])
def test_exposed_time_of_a_collective(case, spans, want):
    red, hlo = _two_chips(spans)
    got = scopes.ByScope(red, hlo, steps=1).exposed_ms("loco/gather")
    assert got == pytest.approx(want, abs=1e-12), case


def test_collectives_of_other_scopes_and_absent_scopes():
    red, hlo = _two_chips([(0, "loco/exchange", 0, 1, True),
                           (0, "loco/gather", 2, 3, True),
                           (0, "model/mlp", 2.5, 3, False),
                           (1, "loco/exchange", 0, 2, True)])
    by = scopes.ByScope(red, hlo, steps=2)
    assert by.exposed_ms("loco/exchange") == pytest.approx((1 + 2) / 2 / 2)
    assert by.exposed_ms("loco/gather") == pytest.approx(0.5 / 2 / 2)
    assert by.ms({"model/attention"}) is None
    assert by.exposed_ms("loco/decode") is None


def test_a_tpu_all_to_all_is_a_collective():
    """On a TPU v5e the exchange's all-to-all runs as the instruction
    ``all_to_all.<n>``, which the trace reduction does not mark, followed
    by a ``reduce`` under the same op_name: both are the exchange's."""
    ops = [TR.Op(0, "all_to_all.7", 0, 10**6, "", "", False),
           TR.Op(0, "reduce.3", 10**6, 3 * 10**6, "", "", False)]
    red = TR.Reduced(ops=ops, host=[], n_devices=1, t0=0, t1=3 * 10**6)
    hlo = "\n".join(f'  %{op.name} = f32[] op(), metadata={{op_name="jit('
                    f'step)/loco/exchange/all_to_all"}}' for op in ops)
    by = scopes.ByScope(red, hlo, steps=1)
    assert by.exposed_ms("loco/exchange") == pytest.approx(3.0)
    assert by.ms({"loco/exchange"}, compute_only=True) == pytest.approx(2.0)


def test_nested_scopes_count_once_and_scopes_sum_to_the_total():
    red, hlo = _two_chips([
        (0, "jvp(model/layers)/while/body/model/attention", 0, 1, False),
        (0, "jvp(model/layers)/while/body", 1, 1.25, False),
        (0, "transpose(jvp(model/layers))/while/body/model/mlp", 2, 3, False),
        (1, "model/layers/loco/gather", 0, 0.5, True),
        (1, "transpose(jvp(model/head))", 0.5, 1, False),
        (1, "transpose(jvp(model/layers))/loco/encode", 1, 1.5, False),
        (1, "while/body", 1.5, 1.75, False),
    ])
    by = scopes.ByScope(red, hlo, steps=1)
    assert by.ms({"model/attention"}) == pytest.approx(0.5)
    assert by.ms({"model/layers"}) == pytest.approx(0.125)
    assert by.ms({"model/mlp"}) == pytest.approx(0.5)
    assert by.ms({"loco/gather"}) == pytest.approx(0.25)
    assert by.ms({"loco/gather"}, compute_only=True) is None
    total = red.op_ms(lambda op: True)
    parts = sum(by.ms({s}) for s in set(by.scope))
    assert parts == pytest.approx(total, rel=1e-12)


def test_readers_share_one_reduction_per_run():
    _, red, hlo = _recorded("trace_expected.json")
    ctx = _ctx(red, hlo)
    assert scopes.of(ctx) is scopes.of(ctx)


def test_scope_reduction_of_a_recorded_chip_trace():
    """A danube-cut step's trace with all of this program's model scopes
    (TPU v5 lite), pruned to the first 12 events of each innermost scope,
    of none and of the loop containers on the chip's ``XLA Ops`` line;
    the expected numbers were worked out from the pruned events by
    hand-written interval arithmetic over the raw protobuf."""
    rec, red, hlo = _recorded("trace_scopes_expected.json")
    assert red.n_devices == rec["n_devices"]
    assert red.window_s == pytest.approx(rec["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(rec["busy_s"], rel=1e-9)
    by = scopes.ByScope(red, hlo, steps=1)
    assert set(by.scope) == set(rec["scope_ms"])
    for scope, ms in rec["scope_ms"].items():
        assert by.ms({scope}) == pytest.approx(ms, rel=1e-9), scope
    assert sum(rec["scope_ms"].values()) == pytest.approx(
        red.op_ms(lambda op: True), rel=1e-9)
    ctx = _ctx(red, hlo)
    for name, scope in MODEL.items():
        assert metrics.load(name).read(ctx) == pytest.approx(
            rec["scope_ms"][scope], rel=1e-9), name
    for name in ("codec_ms", "exchange_exposed_ms", "gather_exposed_ms"):
        assert metrics.load(name).read(ctx) is None, name
