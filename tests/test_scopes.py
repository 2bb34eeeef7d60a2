"""Trace-time scopes of the step (telemetry/profiler.py, DESIGN.md §14).

The model's parts (``model/embed``, ``model/layers``, ``model/attention``,
``model/mlp``, ``model/head``) and the forward weight all-gather
(``loco/gather``) reach the compiled step's ``op_name`` metadata, the
exchange's collectives stay under ``loco/exchange``, and the scopes are
metadata only: without them the compiled step is the same program.
"""
import contextlib
import re

import jax
import pytest

from repro.analysis.hlo_stats import collective_launches
from repro.configs.base import ShapeConfig, get_arch, reduced
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import make_train_step
from repro.launch.train import build_args, make_run
from repro.telemetry import profiler as PROF

SHAPE = ShapeConfig("tiny", seq_len=32, global_batch=4, kind="train")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+.*?"
                    r"\s([a-z][\w\-]*)\((.*)$")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:loco|model)/[a-z]+")
COLLECTIVES = ("all-gather", "all-to-all", "all-reduce", "reduce-scatter",
               "collective-permute")


def _step_hlo(sync: str, dp: int) -> str:
    run = make_run(build_args(["--arch", "llama2-400m", "--dp", str(dp),
                               "--tp", "1", "--sync", sync]))
    bundle = make_train_step(reduced(get_arch("llama2-400m")), run,
                             make_local_mesh(dp=dp, tp=1), SHAPE)
    return bundle.fn.lower(*bundle.input_shapes).compile().as_text()


def _ops(hlo: str):
    """(opcode, innermost scope, op_name) of each instruction with an
    op_name."""
    out = []
    for line in hlo.splitlines():
        m, n = _INSTR.match(line), _OPNAME.search(line)
        if m and n:
            found = _SCOPE.findall(n.group(1))
            out.append((m.group(2), found[-1] if found else "", n.group(1)))
    return out


@pytest.fixture(scope="module")
def fp_dp1():
    return _step_hlo("fp", 1)


@pytest.fixture(scope="module")
def loco_dp2():
    return _step_hlo("loco", 2)


@pytest.mark.parametrize("scope", ["model/embed", "model/layers",
                                   "model/attention", "model/mlp",
                                   "model/head"])
def test_model_scopes_reach_the_compiled_step(fp_dp1, scope):
    inner = {s for _, s, _ in _ops(fp_dp1)}
    assert scope in inner


@pytest.mark.parametrize("scope", ["model/attention", "model/mlp"])
def test_blocks_sit_inside_the_layer_scan(fp_dp1, scope):
    """Forward and backward both: the scan's scope encloses the block's,
    inside ``jvp(...)`` and ``transpose(jvp(...))``."""
    names = [n for _, s, n in _ops(fp_dp1) if s == scope]
    assert any("jvp(model/layers)" in n and "transpose" not in n
               for n in names)
    assert any("transpose(jvp(model/layers))" in n for n in names)


def test_forward_weight_gathers_carry_loco_gather(loco_dp2):
    ops = _ops(loco_dp2)
    gathers = [(s, n) for op, s, n in ops if op.startswith("all-gather")
               and "loco/gather" in n]
    assert gathers and all(s == "loco/gather" for s, _ in gathers)
    # the per-layer gathers run inside the layer scan, forward and remat
    assert any("model/layers" in n for _, n in gathers)


def test_exchange_collectives_are_not_tagged_gather(loco_dp2):
    a2a = [(s, n) for op, s, n in _ops(loco_dp2)
           if op.startswith("all-to-all")]
    assert a2a and all(s == "loco/exchange" for s, _ in a2a), a2a
    assert not any("loco/gather" in n for _, n in a2a)


def _strip_metadata(hlo: str) -> str:
    """The module's instructions without their metadata and without the
    source-location tables printed after them."""
    blocks = [b for b in hlo.split("\n\n") if not re.match(
        r"(FileNames|FunctionNames|FileLocations|StackFrames)\n", b)]
    return re.sub(r",? metadata=\{[^}]*\}", "", "\n\n".join(blocks))


@pytest.mark.parametrize("sync,dp", [("fp", 1), ("loco", 2)])
def test_scopes_leave_the_compiled_step_unchanged(monkeypatch, sync, dp):
    """Without any scope the step compiles to the same instructions (the
    metadata aside) and the same trip-weighted collective launches."""
    scoped = _step_hlo(sync, dp)
    monkeypatch.setattr(PROF, "layer", lambda name: contextlib.nullcontext())
    monkeypatch.setattr(PROF, "phase",
                        lambda name, group=None: contextlib.nullcontext())
    plain = _step_hlo(sync, dp)
    assert "model/" not in plain and "loco/" not in plain
    assert "model/attention" in scoped
    assert collective_launches(scoped) == collective_launches(plain)
    stripped = _strip_metadata(scoped)
    assert "ENTRY" in stripped and "StackFrames" not in stripped
    assert stripped == _strip_metadata(plain)


def test_each_train_step_is_a_host_step_span(tmp_path):
    """``--profile-steps`` traces carry one ``train`` step span per step
    (``jax.profiler.StepTraceAnnotation``), on the device trace's clock."""
    from jax.profiler import ProfileData

    from repro.launch import train as T

    T.main(["--arch", "llama2-400m", "--reduced", "--steps", "3",
            "--seq-len", "32", "--global-batch", "4", "--dp", "1",
            "--tp", "1", "--sync", "fp", "--log-every", "1",
            "--profile-steps", "1:2", "--profile-dir", str(tmp_path)])
    files = list(tmp_path.rglob("*.xplane.pb"))
    assert files
    pd = ProfileData.from_file(str(files[0]))
    steps = [ev for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name == "train"]
    assert len(steps) == 2
