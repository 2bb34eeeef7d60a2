"""The comparison that decides ``correct`` catches what it must.

A tiny cell runs the whole of a run on the CPU (the harness's look for a
chip skipped): clean, it comes out correct; with the float8 control or
one of the faults planted under the timed path, it comes out not correct.
The limits here are set for this tiny cell the way ``limits/<cell>.json``
are set for the real ones: above the clean readings, below the control's
and the faults' (clean: loss_gap <= 3e-3, grad_gap <= 1.6e-3, change_gap
<= 8e-4; control: grad_gap >= 0.024; faults: grad_gap >= 0.07).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import check, harness, peaks
from chipbench.tests import tiny

LIMITS = {"loss_gap": {"limit": 0.01}, "grad_gap": {"limit": 0.008},
          "change_gap": {"limit": 0.01}}
SEED = 2**32 + 11


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(peaks, "lookup", lambda kind: {"bf16_flops": 1e12})


def run(cell, fault=None):
    return harness.run(cell, SEED, 0.2, False, time.time(), fault=fault,
                       cache=False, log=lambda *a: None)


def test_clean_run_is_correct():
    res = run(tiny.cell(dp=1, sync="fp", limits=LIMITS))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_a_fault_under_the_timed_path_is_not_correct(fault):
    res = run(tiny.cell(dp=1, sync="fp", limits=LIMITS), fault=fault)
    assert not res["correct"], res["checks"]


def test_the_exchange_left_out_is_not_correct(monkeypatch):
    """Each chip decodes only its own wire: the mean over peers is gone."""
    from repro.core import comm

    def own_rows(x, axes):
        (axis,) = axes
        return jnp.broadcast_to(x[jax.lax.axis_index(axis)][None], x.shape)

    cell = tiny.cell(dp=2, sync="loco", limits=LIMITS)
    assert run(cell)["correct"]
    monkeypatch.setattr(comm, "all_to_all_chunks", own_rows)
    res = run(cell)
    assert not res["correct"], res["checks"]


def test_the_float8_control_is_not_correct():
    cell = tiny.cell(dp=2, sync="loco", limits=LIMITS)
    b = harness.setup(cell, cache=False)
    readings, state, batches, ring = harness.first_steps(b, SEED)
    ref = harness.follow_reference(b, SEED, ring)
    assert check.passed(check.compare(readings, ref, LIMITS))
    ctl = harness.follow_reference(b, SEED, ring, precision="f8")
    assert not check.passed(check.compare(ctl, ref, LIMITS))
