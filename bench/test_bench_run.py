"""A whole run of a cell on the CPU at a tiny size, past the look for a
chip: correct as it stands, not correct with the timed path broken, not
correct under the control; and no result where there is no TPU."""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import textwrap
import time

import pytest

from yardstick import control, runner, spec

ROOT = spec.ROOT
SEED = 2**33 + 17  # more than 32 bits, as the driver's seeds are


def tiny(workload: str) -> spec.Cell:
    """The cell with 1 KiB sub-blocks and, for reads, 8 strips a block."""
    cell = spec.load_cell(workload)
    dep = cell.deployment
    dep = dataclasses.replace(dep, sub_bytes=1024, block_bytes=dep.alpha * 1024)
    mix = dict(cell.traffic)
    if "strip_bytes" in mix:
        mix["strip_bytes"] = dep.block_bytes // 8
    return dataclasses.replace(cell, deployment=dep, traffic=mix)


@pytest.fixture
def tpu_peaks(monkeypatch):
    """Let the CPU's device kind read the v5e's peaks."""
    real = spec.load_peaks
    monkeypatch.setattr(spec, "load_peaks",
                        lambda kind, root=ROOT: real("TPU v5 lite", root))


def run(cell: spec.Cell, program=None) -> dict:
    import jax

    return runner.run_cell(cell, SEED, 0.2, False, jax.devices(),
                           time.perf_counter(), program=program)


class Unchanged(runner.Program):
    """Returns its input: the state comes back unchanged."""

    def recover(self, x):
        return x + 0


class HalfLeftOut(runner.Program):
    """Leaves out the second half of the batch: its stripes, or, for a
    single strip, its second half of columns."""

    def recover(self, x):
        out = super().recover(x)
        if out.shape[0] > 1:
            return out.at[out.shape[0] // 2:].set(0)
        return out.at[..., out.shape[-1] // 2:].set(0)


class OneByteAltered(runner.Program):
    """Flips one bit of one rebuilt byte where it is produced."""

    def recover(self, x):
        out = super().recover(x)
        row = runner.collector(self.dep)
        return out.at[0, row, 0, 0].set(out[0, row, 0, 0] ^ 1)


@pytest.mark.parametrize("workload", ["drc963-recovery", "rs963-recovery",
                                      "drc963-read-1m"])
def test_tiny_run_is_correct_and_reports_the_cells_metrics(workload, tpu_peaks):
    cell = tiny(workload)
    res = run(cell)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(res)[-1] == "checks"
    assert res["checks"]["mismatched_bytes"]["value"] == 0
    assert res["device"]["count"] == 1


def test_traced_run_reports_the_per_layer_metrics_it_finds(tpu_peaks, capfd):
    import jax

    res = runner.run_cell(tiny("drc963-read-1m"), SEED, 0.2, True, jax.devices(),
                          time.perf_counter())
    assert res["correct"] is True
    # the CPU trace holds no TPU plane, so only the host metric is read
    assert set(res["metrics"]) == {"host_ms_per_call.read"}
    assert res["device"]["window_s"] > 0 and "breakdown" in res
    err = capfd.readouterr().err
    assert "slowest request" in err and "process CPU" in err


@pytest.mark.parametrize("fault", [Unchanged, HalfLeftOut, OneByteAltered])
@pytest.mark.parametrize("workload", ["drc963-recovery", "drc963-read-1m"])
def test_a_broken_timed_path_is_not_correct(workload, fault, tpu_peaks):
    import jax

    cell = tiny(workload)
    res = run(cell, program=fault(cell.deployment, jax.devices()))
    assert res["correct"] is False
    assert res["checks"]["mismatched_bytes"]["value"] > 0
    assert res["failed"] > 0


@pytest.mark.parametrize("workload", ["drc963-recovery", "rs963-recovery",
                                      "drc963-read-1m"])
def test_the_control_fails_and_the_plain_decode_passes(workload, tpu_peaks):
    import jax

    cell = tiny(workload)
    gf2 = run(cell, program=control.PlainDecode(cell.deployment, jax.devices(),
                                                gf2=True))
    assert gf2["correct"] is False
    assert gf2["checks"]["mismatched_bytes"]["value"] > 0
    full = run(cell, program=control.PlainDecode(cell.deployment, jax.devices(),
                                                 gf2=False))
    assert full["correct"] is True, full["checks"]


FOUR_CHIPS = textwrap.dedent("""
    import dataclasses, json, sys, time
    sys.path[:0] = [{bench!r}, {src!r}]
    import jax, jax.numpy as jnp
    from yardstick import runner, spec
    real = spec.load_peaks
    spec.load_peaks = lambda kind, root=spec.ROOT: real("TPU v5 lite", root)
    cell = spec.load_cell("drc864-recovery-4chip")
    dep = dataclasses.replace(cell.deployment, sub_bytes=1024, block_bytes=2048)
    cell = dataclasses.replace(cell, deployment=dep)
    if sys.argv[1] == "no-exchange":
        jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
    res = runner.run_cell(cell, {seed}, 0.2, False, jax.devices(),
                          time.perf_counter())
    print(json.dumps(res))
""")


@pytest.mark.parametrize("mode", ["as-is", "no-exchange"])
def test_four_chip_cell_needs_the_exchange_between_chips(mode):
    """On four CPU devices: the rack-per-chip cell is correct, and is not
    once the ppermute between chips delivers nothing."""
    code = FOUR_CHIPS.format(bench=str(ROOT / "bench"), src=str(ROOT / "src"),
                             seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code, mode], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    checks = res["checks"]
    if mode == "as-is":
        assert res["correct"] is True, checks
        assert checks["cross_pod_bytes_off_eq3"]["value"] == 0
    else:
        assert res["correct"] is False
        assert checks["mismatched_bytes"]["value"] > 0
        assert checks["cross_pod_bytes_off_eq3"]["value"] > 0


def _run_cell(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload", "drc963-recovery",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _printed_a_result(stdout: str) -> bool:
    return any(line.lstrip().startswith("{") and '"correct"' in line
               for line in stdout.splitlines())


def test_without_a_tpu_a_run_fails_and_prints_no_result():
    proc = _run_cell(ROOT)
    assert proc.returncode != 0
    assert not _printed_a_result(proc.stdout)
    assert "TPU" in proc.stderr


def test_with_only_the_benchmarks_files_a_run_fails_and_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cell(tmp_path)
    assert proc.returncode != 0
    assert not _printed_a_result(proc.stdout)
