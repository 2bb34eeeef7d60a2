"""CPU tests of the benchmark harness (seconds; no chip)."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import check, flops, hlo, model, spec  # noqa: E402
from chipbench.reference import Wire  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_and_names_a_registered_arch(name):
    import dataclasses

    from repro.configs.base import get_arch
    from chipbench import harness

    cell = spec.load_cell(name)
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    conf = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert conf["file"] == f"chipbench/configs/{conf['name']}.json"
    assert sorted(conf["reduced"]) == sorted(cell.config["reduced"])
    arch = dataclasses.replace(get_arch(cell.config["arch"]),
                               **cell.config["overrides"])
    harness.check_arch(arch, model.Dims.from_config(cell.config))
    assert cell.dp * cell.tp == entry["chips"]
    assert set(cell.limits) == set(check.NAMES)


def test_a_new_cell_config_and_metric_are_found_by_name(tmp_path):
    """A cell is a BENCHMARK.json entry plus files; nothing is edited."""
    from chipbench import metrics

    base = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (base / sub).mkdir(parents=True)
    conf = json.loads((ROOT / "chipbench/configs/danube-1.8b-cut.json")
                      .read_text())
    conf["num_hidden_layers"] = 3
    (base / "configs/new-config.json").write_text(json.dumps(conf))
    traffic = json.loads((ROOT / "chipbench/traffic/fp.dp1.s2048.b8.json")
                         .read_text())
    traffic["seq_len"] = 4096
    (base / "traffic/new-traffic.json").write_text(json.dumps(traffic))
    (base / "limits/new.cell.json").write_text(json.dumps(
        {n: {"limit": 0.5} for n in check.NAMES}))
    (base / "metrics/new_metric.py").write_text(
        "def read(ctx):\n    return ctx['steps'] * 2.0\n")
    bench = dict(BENCH)
    bench["configs"] = [{"name": "new-config", "source": "x",
                         "file": "chipbench/configs/new-config.json",
                         "reduced": ["num_hidden_layers"], "why": "x"}]
    bench["workloads"] = [{"name": "new.cell", "config": "new-config",
                           "traffic": "new-traffic", "chips": 1, "why": "x"}]
    bench["per_layer"] = [{"name": "new_metric", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "x", "moves": "tokens_per_s",
                           "workloads": ["new.cell"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("new.cell", benchmark=tmp_path / "BENCHMARK.json",
                          base=base)
    assert cell.config["num_hidden_layers"] == 3 and cell.seq_len == 4096
    assert cell.limits["loss_gap"]["limit"] == 0.5
    got = metrics.read_all({"steps": 3}, "new.cell",
                           benchmark=tmp_path / "BENCHMARK.json",
                           base=base / "metrics")
    assert got == {"new_metric": {"value": 6.0, "unit": "ms"}}


def test_flops_match_a_hand_count_for_danube_cut():
    cell = spec.load_cell("danube-cut.fp.1chip")
    dims = model.Dims.from_config(cell.config)
    L = dims.layers
    # per layer: wq 2560x2560, wk and wv 2560x640, wo 2560x2560,
    # w1 and w3 2560x6912, w2 6912x2560; head 2560x32000
    per_layer = 2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912
    assert per_layer == 69_468_160
    matmul = L * per_layer + 2560 * 32000
    # causal half of 2048 keys (window 4096 reaches back past the start):
    # mean keys = 2049 / 2; QK^T and PV: 4 * 32 heads * 80 per key, x3
    attn = L * 12 * 32 * 80 * 2049 / 2
    assert flops.mean_keys(2048, 4096) == 1024.5
    assert flops.per_token(dims, 2048) == pytest.approx(6 * matmul + attn,
                                                        rel=1e-12)
    assert flops.mean_keys(8, 4) == (1 + 2 + 3 + 4 * 5) / 8


def test_codec_bytes_for_a_small_layout():
    dims = model.Dims(d=256, heads=4, kv_heads=2, hd=64, ff=512, vocab=512,
                      layers=2, window=None, rope_theta=1e4, eps=1e-5,
                      tied=False, scale_emb=1.0, residual_scale=1.0,
                      logit_scale=1.0)
    wire = Wire(strategy="loco")
    # compressed (>= 65536 elements per layer): wq 256x256, wo 256x256,
    # w1/w3 256x512, w2 512x256 per layer; tok and head 512x256.  wk/wv
    # (256x128 = 32768) and the norms travel uncompressed.
    n = 2 * (2 * 65536 + 3 * 131072) + 2 * 131072
    ranks, accum = 4, 2
    want = accum * (6.5 * n + 0.5 * n + 4.0 * n / ranks)
    assert flops.codec_bytes_per_step(dims, wire, ranks, accum) == want
    assert flops.codec_bytes_per_step(dims, Wire(strategy="fp"), 1, 8) == 0


def test_hlo_wire_bytes_weights_loops_by_trip_count():
    text = """HloModule m

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %x = f32[8] get-tuple-element(%p), index=1
  %ag = f32[32] all-gather(%x), replica_groups={{0,1,2,3}}, dimensions={0}
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[8]) tuple(%i, %x)
}

%cond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(24)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %rs = f32[2] reduce-scatter(%a), replica_groups={{0,1,2,3}}, dimensions={0}
  %z = s32[] constant(0)
  %t = (s32[], f32[8]) tuple(%z, %a)
  %w = (s32[], f32[8]) while(%t), condition=%cond, body=%body
  ROOT %o = f32[8] get-tuple-element(%w), index=1
}
"""
    # 24 all-gathers of 32 f32 (3/4 from peers) + one reduce-scatter whose
    # 2-element shard is received from 3 peers
    assert hlo.wire_bytes(text) == 24 * 128 * 0.75 + 8 * 3


def test_trace_reduction_of_a_recorded_chip_trace():
    """A danube-cut step's trace (TPU v5 lite), pruned to its first 15 ms
    and the last 45 ms of its first step; the expected numbers were worked
    out from the pruned events by hand-written interval arithmetic."""
    from chipbench import trace

    data = ROOT / "chipbench/tests/data"
    rec = json.loads((data / "trace_expected.json").read_text())
    red = trace.reduce_file(data / rec["file"], (data / rec["hlo"]).read_text())
    assert red.n_devices == rec["n_devices"]
    assert red.window_s == pytest.approx(rec["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(rec["busy_s"], rel=1e-9)
    for scope, ms in rec["scope_ms"].items():
        got = red.op_ms(lambda op, s=scope: op.scope.startswith(s))
        assert got == pytest.approx(ms, rel=1e-9), scope
    assert 0.0 < 1.0 - red.busy_s / red.window_s < 1.0


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "danube-cut.fp.1chip", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "danube-cut.fp.1chip", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_large_seeds_keep_their_high_bits():
    import jax

    from chipbench.data import seed_key

    a, b = seed_key(7), seed_key(2**33 + 7)
    assert not bool((jax.random.key_data(a) == jax.random.key_data(b)).all()
                    if hasattr(jax.random, "key_data") else (a == b).all())


def test_benchmark_json_keeps_to_its_rules():
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names)) and all(map(name.match, names))
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).exists() and len(c["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (ROOT / f"chipbench/traffic/{w['traffic']}.json").exists()
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and unit.match(m["unit"])
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and unit.match(m["unit"])
        assert (ROOT / f"chipbench/metrics/{m['name']}.py").exists()
