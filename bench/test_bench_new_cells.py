"""Whole runs, on the CPU at a tiny size, of the cell msr963-recovery
(Clay MSR(9,6,3), alpha 27, under node recovery): it is correct and
reports its cell's end-to-end metrics, the plain decode passes the check
and the GF(2) control fails it."""
from __future__ import annotations

import dataclasses
import time

import pytest

from yardstick import control, runner, spec

ROOT = spec.ROOT
SEED = 2**33 + 29  # more than 32 bits, as the benchmark's run seeds may be


def tiny(workload: str) -> spec.Cell:
    """The cell with 1 KiB sub-blocks."""
    cell = spec.load_cell(workload)
    dep = cell.deployment
    dep = dataclasses.replace(dep, sub_bytes=1024, block_bytes=dep.alpha * 1024)
    return dataclasses.replace(cell, deployment=dep)


@pytest.fixture(scope="module")
def tpu_peaks():
    """Let the CPU's device kind read the v5e's peaks."""
    real = spec.load_peaks
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spec, "load_peaks",
                   lambda kind, root=ROOT: real("TPU v5 lite", root))
        yield


def run(cell: spec.Cell, program=None) -> dict:
    import jax

    return runner.run_cell(cell, SEED, 0.2, False, jax.devices(),
                           time.perf_counter(), program=program)


@pytest.mark.parametrize("workload, answers", [("msr963-recovery", 1)])
def test_new_cell_tiny_run_is_correct_and_reports_its_metrics(
        workload, answers, tpu_peaks):
    cell = tiny(workload)
    res = run(cell)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert res["checks"]["mismatched_bytes"]["value"] == 0
    assert res["checks"]["answers_checked"]["value"] == answers
    assert res["device"]["count"] == 1


def test_msr_cell_metrics_are_those_of_the_recovery_cells():
    cell = spec.load_cell("msr963-recovery")
    assert cell.deployment.alpha == 27 and cell.deployment.sub_bytes == 2485632
    assert {m["name"] for m in cell.end_to_end} == {"recovery_MBps", "setup_s"}
    layer = {m["name"] for m in cell.per_layer}
    assert layer == {m["name"] for m in spec.load_cell("rs963-recovery").per_layer}
    assert "relayer_encode_ms_per_call.recovery" not in layer


@pytest.mark.parametrize("gf2, correct", [(False, True), (True, False)],
                         ids=["plain_decode", "control_gf2"])
def test_msr_plain_decode_passes_and_the_control_fails(gf2, correct,
                                                       tpu_peaks):
    import jax

    cell = tiny("msr963-recovery")
    res = run(cell, program=control.PlainDecode(cell.deployment, jax.devices(),
                                                gf2=gf2))
    assert res["correct"] is correct, res["checks"]
    assert (res["checks"]["mismatched_bytes"]["value"] > 0) is not correct
