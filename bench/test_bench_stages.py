"""The readings of the repair program's stage scopes and of the gaps
between the programs of a read: the reductions on made-up and recorded
traces, the op-to-stage map from compiled HLO, and the readers."""
from __future__ import annotations

import dataclasses
import gzip
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from yardstick import runner, spec, stages, trace

FIXTURES = Path(__file__).resolve().parent / "fixtures"
V5E = "TPU v5 lite"


def make_run(workload: str, t, **kw) -> runner.Run:
    return runner.Run(cell=spec.load_cell(workload), peaks=spec.load_peaks(V5E),
                      setup_s=1.0, trace=t, target_device="0", **kw)


def staged():
    """Window 0..100 ns: a while loop under relayer_encode holds a body op
    whose own op_name says decode (XLA shares the body computation
    between loops), then a decode op, an op the map does not know, a
    write op and an op with no stage."""
    return {
        "window": [0, 100],
        "devices": {"0": [["while.1", 0, 30],            # relayer_encode
                          ["fusion.2", 5, 20],           # decode, in the loop
                          ["fusion.3", 30, 10],          # decode
                          ["fusion.9", 40, 5],           # not in the map
                          ["copy.4", 60, 20],            # write
                          ["bitcast.5", 80, 10]]},       # no stage
        "host": [],
    }


STAGE_OF = {"while.1": "relayer_encode", "fusion.2": "decode",
            "fusion.3": "decode", "copy.4": "write", "bitcast.5": None,
            "fusion.7": "node_encode"}


def test_stage_time_goes_to_the_enclosing_ops_stage():
    t = staged()
    got, unscoped = stages.stage_seconds(t, "0", STAGE_OF)
    assert got == pytest.approx({"relayer_encode": 30e-9, "decode": 10e-9,
                                 "write": 20e-9, "node_encode": 0.0})
    assert unscoped == {"fusion.9": 5, "bitcast.5": 10}
    assert sum(got.values()) + sum(unscoped.values()) / 1e9 == pytest.approx(
        trace.busy_s(t, "0"))
    t["window"] = [0, 70]  # the write op is cut at the window's end
    got, unscoped = stages.stage_seconds(t, "0", STAGE_OF)
    assert got["write"] == pytest.approx(10e-9) and "bitcast.5" not in unscoped


def test_program_runs_and_the_gaps_between_them():
    t = {"window": [0, 100],
         "devices": {"0": [["x.9", 0, 1],             # before any run: dropped
                           ["t.1", 2, 3],             # take 2..5
                           ["b.1", 10, 10],           # repair 10..30
                           ["shared.1", 20, 2],       # in both programs
                           ["b.2", 22, 8],
                           ["t.1", 40, 5],            # take 40..45
                           ["b.1", 50, 10],           # repair 50..61
                           ["x.9", 60, 1]]}}          # in neither
    names = {stages.TAKE: {"t.1", "shared.1"},
             stages.REPAIR: {"b.1", "b.2", "shared.1"}}
    runs = stages.program_runs(t, "0", names)
    assert runs == [("take", 2, 5), ("repair", 10, 30), ("take", 40, 45),
                    ("repair", 50, 61)]
    assert stages.gaps_between(t, runs, stages.TAKE, stages.REPAIR) == [5, 5]
    assert stages.gaps_between(t, runs, stages.REPAIR, stages.TAKE) == [10]
    t["window"] = [3, 48]  # the first take ends in it, the last repair starts after
    assert stages.gaps_between(t, runs, stages.TAKE, stages.REPAIR) == [5]
    assert stages.mean_ms([]) is None and stages.mean_ms([2e6, 4e6]) == 3.0


HLO = """HloModule jit_body, is_scheduled=true, entry_computation_layout={(u8[2])->u8[2]}

%fused_computation (param_0.1: u8[2]) -> u8[2] {
  %param_0.1 = u8[2]{0} parameter(0)
  ROOT %xor.3 = u8[2]{0} xor(u8[2]{0} %param_0.1, u8[2]{0} %param_0.1), metadata={op_name="jit(body)/decode/jit(gf_matmul_jnp)/xor" source_file="x.py" source_line=1}
}

ENTRY %main.9 (x.1: u8[2]) -> u8[2] {
  %x.1 = u8[2]{0} parameter(0), metadata={op_name="x"}
  %fusion.2 = u8[2]{0} fusion(u8[2]{0} %x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(body)/node_encode/jit(gf_matmul_jnp)/xor"}
  ROOT %copy.4 = u8[2]{0} copy(u8[2]{0} %fusion.2), metadata={op_name="jit(body)/write/decode/stack"}
}
"""


def test_op_stages_take_the_first_stage_of_each_op_name():
    from repro.obs import STAGE_NAMES

    assert stages.op_stages(HLO, STAGE_NAMES) == {
        "param_0.1": None, "xor.3": "decode", "x.1": None,
        "fusion.2": "node_encode", "copy.4": "write"}
    assert set(stages.op_stages(HLO, ())) == {"param_0.1", "xor.3", "x.1",
                                               "fusion.2", "copy.4"}
    with pytest.raises(ValueError):
        stages.op_stages("ENTRY %main {}", STAGE_NAMES)


@pytest.fixture(scope="module")
def read_xplane(tmp_path_factory):
    """A 0.05 s window of the degraded-read cell (11 reads), traced on a
    TPU v5 lite by run_cell.py --trace 1: the extracted trace, and the
    TPU's ``XLA Modules`` line, as [module, start_ns, end_ns]."""
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("trace") / "read.xplane.pb"
    path.write_bytes(gzip.decompress(
        (FIXTURES / "read_trace.xplane.pb.gz").read_bytes()))
    modules = [[e.name.split("(", 1)[0], int(e.start_ns),
                int(e.start_ns + e.duration_ns)]
               for plane in ProfileData.from_file(str(path)).planes
               if plane.name == "/device:TPU:0"
               for line in plane.lines if line.name == "XLA Modules"
               for e in line.events]
    return trace.extract(str(path), runner.HOST_SPANS), modules


def module_names(t, modules) -> dict[str, set[str]]:
    """Each program's op names, from the modules the ops ran in."""
    label = {"jit_take": stages.TAKE, "jit_body": stages.REPAIR}
    names: dict[str, set[str]] = {stages.TAKE: set(), stages.REPAIR: set()}
    for name, s, _ in t["devices"]["0"]:
        (module,) = [m for m, a, b in modules if a <= s < b]
        names[label[module]].add(name)
    return names


def test_gap_readers_on_the_recorded_read_trace(read_xplane, monkeypatch):
    """On the device clock alone: 10 take-to-repair and 10 repair-to-take
    gaps inside the window (the first read's pair of programs ran before
    the window opened, the last repair has no take after it)."""
    t, modules = read_xplane
    names = module_names(t, modules)
    assert names[stages.TAKE] == {"constant_dynamic-slice_fusion"}
    runs = stages.program_runs(t, "0", names)
    # the runs the ops make are the module line's, to within a microsecond
    assert [r[0] for r in runs] == [
        stages.TAKE if m == "jit_take" else stages.REPAIR for m, _, _ in modules]
    assert all(abs(a - m[1]) < 1000 and abs(b - m[2]) < 1000
               for (_, a, b), m in zip(runs, modules))
    launch = stages.gaps_between(t, runs, stages.TAKE, stages.REPAIR)
    back = stages.gaps_between(t, runs, stages.REPAIR, stages.TAKE)
    assert len(launch) == len(back) == 10
    assert (min(launch), max(launch)) == (260237, 518167)
    assert (min(back), max(back)) == (1010305, 1289453)

    monkeypatch.setattr(stages, "read_program_names", lambda run: names)
    run = make_run("drc963-read-1m", t, latencies_s=[0.0046] * 11)
    assert spec.load_reader("launch_gap_ms_per_call.read")(run) == pytest.approx(
        0.3934301, abs=1e-12)
    assert spec.load_reader("return_gap_ms_per_call.read")(run) == pytest.approx(
        1.1867624, abs=1e-12)
    # the gaps and the idle inside the programs' runs are the window's idle
    # but for the stretches at its two ends: the window opens inside a
    # repair and closes 4.6 ms after the last one
    inside = stages.idle_inside(t, "0", runs)
    assert inside == 1053
    idle = (trace.window_s(t) - trace.busy_s(t, "0")) * 1e9
    lo, hi = t["window"]
    assert runs[1][1] < lo < runs[1][2] and runs[-1][2] < hi
    head = runs[1][2] - lo - trace.busy_s(dict(t, window=[lo, runs[1][2]]), "0") * 1e9
    tail = hi - runs[-1][2]
    assert sum(launch) + sum(back) + inside + head + tail == pytest.approx(idle, abs=1)
    # a recovery run, or a run with no trace, reads nothing
    assert spec.load_reader("launch_gap_ms_per_call.read")(
        make_run("drc963-recovery", t, latencies_s=[0.5])) is None
    assert spec.load_reader("return_gap_ms_per_call.read")(
        make_run("drc963-read-1m", None, latencies_s=[0.0046])) is None


@pytest.fixture(scope="module")
def drc_trace():
    """One call of the drc963-recovery cell (0.56 s), traced on a TPU v5
    lite by run_cell.py --trace 1 with the program's stage scopes: the
    extracted trace with the program's ``repair.*`` host spans, the
    op-to-stage map of the repair program's compiled HLO, and the
    repair module's name."""
    return json.loads(gzip.decompress(
        (FIXTURES / "drc_recovery_trace.json.gz").read_bytes()))


STAGE_METRICS = ("node_encode_ms_per_call.recovery",
                 "relayer_encode_ms_per_call.recovery",
                 "pool_ms_per_call.recovery", "decode_ms_per_call.recovery",
                 "write_ms_per_call.recovery", "unscoped_share.recovery")


def test_recorded_recovery_trace_splits_busy_time_by_stage(drc_trace, monkeypatch):
    t, stage_of = drc_trace["trace"], drc_trace["stage_of"]
    # the program's host spans, on the profiler's clock: plan and launch
    # inside the entry's span
    root, plan, launch = t["program"]
    assert [root[0], plan[0], launch[0]] == [
        "repair.spmd_node_recovery", "repair.plan", "repair.launch"]
    for _, s, d in (plan, launch):
        assert root[1] <= s and s + d <= root[1] + root[2]
    # every op of the repair module's run is in the map, and most carry a stage
    ((module, a, d),) = t["modules"]["0"]
    assert module.startswith(drc_trace["repair_module"] + "(")
    ran = [e for e in t["devices"]["0"] if a <= e[1] < a + d]
    assert ran and all(name in stage_of for name, _, _ in ran)
    got, unscoped = stages.stage_seconds(t, "0", stage_of)
    assert {st for st, secs in got.items() if secs} == {
        "node_encode", "relayer_encode", "decode", "write"}
    busy = trace.busy_s(t, "0")
    assert sum(got.values()) + sum(unscoped.values()) / 1e9 == pytest.approx(
        busy, abs=1e-9)

    # the readers; the stage metrics and the unscoped share sum to the busy
    # time per call
    monkeypatch.setattr(stages, "recovery_stage_map", lambda run: stage_of)
    run = make_run("drc963-recovery", t, latencies_s=drc_trace["latencies_s"])
    read = {m: spec.load_reader(m)(run) for m in STAGE_METRICS}
    assert read == pytest.approx({
        "node_encode_ms_per_call.recovery": 209.946798,
        "relayer_encode_ms_per_call.recovery": 151.84753,
        "pool_ms_per_call.recovery": 0.0,
        "decode_ms_per_call.recovery": 187.649959,
        "write_ms_per_call.recovery": 10.974888,
        "unscoped_share.recovery": 0.02486181015099732}, rel=1e-12, abs=1e-12)
    parts = sum(v for k, v in read.items() if k.endswith("ms_per_call.recovery"))
    parts += read["unscoped_share.recovery"] / 100 * busy * 1e3
    assert parts == pytest.approx(busy * 1e3, rel=1e-9)

    # a program with no relayer_encode scope, as RS's: that reader finds
    # nothing; a program with no scope at all: no stage reader finds anything
    no_relayers = {k: (None if v == "relayer_encode" else v)
                   for k, v in stage_of.items()}
    monkeypatch.setattr(stages, "recovery_stage_map", lambda run: no_relayers)
    run = make_run("drc963-recovery", t, latencies_s=drc_trace["latencies_s"])
    assert spec.load_reader("relayer_encode_ms_per_call.recovery")(run) is None
    assert spec.load_reader("node_encode_ms_per_call.recovery")(run) > 0
    monkeypatch.setattr(stages, "recovery_stage_map",
                        lambda run: dict.fromkeys(stage_of))
    run = make_run("drc963-recovery", t, latencies_s=drc_trace["latencies_s"])
    assert all(spec.load_reader(m)(run) is None for m in STAGE_METRICS)


def tiny(workload: str) -> spec.Cell:
    """The cell with 1 KiB sub-blocks and, for reads, 8 strips a block."""
    cell = spec.load_cell(workload)
    dep = cell.deployment
    dep = dataclasses.replace(dep, sub_bytes=1024, block_bytes=dep.alpha * 1024)
    mix = dict(cell.traffic)
    if "strip_bytes" in mix:
        mix["strip_bytes"] = dep.block_bytes // 8
    return dataclasses.replace(cell, deployment=dep, traffic=mix)


@pytest.mark.parametrize("workload,scopes", [
    ("drc963-recovery", {"node_encode", "relayer_encode", "decode", "write"}),
    ("rs963-recovery", {"node_encode", "decode", "write"})])
def test_stage_readers_compile_the_cells_program(workload, scopes):
    """The map from the tiny cell's program compiled on the CPU names the
    stages its code has (on one device the pool's concatenation and take
    fuse into their neighbours); a trace of one op under each reads
    through."""
    run = runner.Run(cell=tiny(workload), peaks={}, setup_s=1.0,
                     latencies_s=[1.0], target_device="0")
    stage_of = stages.recovery_stage_map(run)
    assert {st for st in stage_of.values() if st} == scopes
    one = {st: name for name, st in stage_of.items() if st}
    run.trace = {"window": [0, 10**6], "host": [], "devices": {"0": [
        [one["node_encode"], 0, 4000], [one["decode"], 5000, 2000],
        [one["write"], 8000, 1000], ["not.in.the.program", 9000, 1000]]}}
    assert spec.load_reader("node_encode_ms_per_call.recovery")(run) == 4e-3
    assert spec.load_reader("decode_ms_per_call.recovery")(run) == 2e-3
    assert spec.load_reader("write_ms_per_call.recovery")(run) == 1e-3
    assert spec.load_reader("pool_ms_per_call.recovery")(run) == 0.0
    assert spec.load_reader("unscoped_share.recovery")(run) == pytest.approx(12.5)
    relayer = spec.load_reader("relayer_encode_ms_per_call.recovery")(run)
    assert (relayer is None) == ("relayer_encode" not in scopes)


WARM = textwrap.dedent("""
    import contextlib, sys
    sys.path[:0] = [{bench!r}, {src!r}]
    import jax
    jax.config.update("jax_compilation_cache_dir", sys.argv[2])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import test_bench_stages as T
    import repro.dist.collectives as C
    from yardstick import runner, stages
    if sys.argv[1] == "unscoped":  # another version of the program
        C.stage_scope = lambda stage: contextlib.nullcontext()
    run = runner.Run(cell=T.tiny("drc963-recovery"), peaks={{}}, setup_s=1.0)
    program = runner.Program(run.cell.deployment, jax.devices())
    x = runner.make_stripes(run.cell.deployment, 5, program.sharding)
    jax.block_until_ready(program.recover(x))  # the warm-up
    print(sorted({{st for st in stages.recovery_stage_map(run).values() if st}}))
""")


def test_stage_map_of_an_unscoped_executable_from_the_cache(tmp_path):
    """A compilation cache shared with a version of the program that has
    no scopes hands that version's executable to the warm-up, since the
    cache's key leaves metadata out; the map is compiled again and names
    the stages all the same."""
    here = Path(__file__).resolve().parent
    script = tmp_path / "warm.py"
    script.write_text(WARM.format(bench=str(here), src=str(here.parent / "src")))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    said = [subprocess.run([sys.executable, str(script), mode, str(tmp_path / "cache")],
                           env=env, capture_output=True, text=True, timeout=300)
            for mode in ("unscoped", "scoped")]
    assert [r.returncode for r in said] == [0, 0], said[-1].stderr[-2000:]
    assert said[0].stdout.splitlines()[-1] == "[]"
    assert said[1].stdout.splitlines()[-1] == str(
        ["decode", "node_encode", "relayer_encode", "write"])
    assert "compiled again" in said[1].stderr


def test_read_program_names_compile_the_take_and_the_repair():
    run = runner.Run(cell=tiny("drc963-read-1m"), peaks={}, setup_s=1.0,
                     latencies_s=[1.0], target_device="0")
    names = stages.read_program_names(run)
    assert set(names) == {stages.TAKE, stages.REPAIR}
    assert names[stages.TAKE] and len(names[stages.REPAIR]) > len(names[stages.TAKE])


# Both kept traces as the benchmark's readers read them before the stage
# and gap readers came: those readings must hold.
READ_TOP = [["fusion.441", 0.003358442], ["fusion.439", 0.001184843],
            ["slice_reduce_fusion.212", 0.001157462],
            ["dynamic-slice_reduce_fusion.26", 0.00109248],
            ["slice_reduce_fusion.215", 0.001006874],
            ["slice_reduce_fusion.217", 0.000996132],
            ["slice_reduce_fusion.216", 0.000995947],
            ["slice_reduce_fusion.211", 0.000908046],
            ["slice_reduce_fusion.214", 0.000881051], ["fusion.440", 0.000833899]]
READ_GAPS = [["block_until_ready", 0.011036155], ["dispatch", 0.005101991],
             ["take_strip", 0.004096376], ["read.call", 0.000122797],
             ["no benchmark span", 2.174e-05]]
ICI_TOP = [["fusion.311", 0.020314803], ["fusion.308", 0.020314801],
           ["fusion.313", 0.013781044], ["fusion.312", 0.013127001],
           ["dynamic-slice_reduce_fusion.49", 0.011095607],
           ["dynamic-slice_reduce_fusion.50", 0.011066053],
           ["slice_reduce_fusion.321", 0.010907503],
           ["slice_reduce_fusion.312", 0.010830366],
           ["slice_reduce_fusion.315", 0.010765618],
           ["slice_reduce_fusion.322", 0.010590225]]
ICI_GAPS = [["block_until_ready", 0.006469787], ["no benchmark span", 0.00011874],
            ["recovery.call", 3.2e-06]]
EARLIER_METRICS = {
    "host_ms_per_call.recovery", "host_ms_per_call.recovery_4chip",
    "host_ms_per_call.read", "repair_hbm_roofline.recovery",
    "ici_permute_ms_per_call", "device_idle_share.recovery",
    "device_idle_share.recovery_4chip", "device_idle_share.read"}


def test_earlier_readings_are_unchanged_on_both_kept_traces(read_xplane):
    ici = json.loads(gzip.decompress(
        (FIXTURES / "ici_trace_events.json.gz").read_bytes()))

    def readings(workload, t, **kw):
        run = make_run(workload, t, **kw)
        return {m["name"]: spec.load_reader(m["name"])(run)
                for m in run.cell.per_layer if m["name"] in EARLIER_METRICS}

    t, _ = read_xplane
    assert trace.busy_s(t, "0") == 0.033138529
    assert trace.top_ops(t, "0") == READ_TOP
    assert trace.idle_gaps(t, "0") == READ_GAPS
    assert readings("drc963-read-1m", t, latencies_s=[0.0046] * 11,
                    dispatch_s=[0.0004] * 11) == {
        "host_ms_per_call.read": 0.4,
        "device_idle_share.read": 38.07918062376054}
    assert trace.busy_s(ici, "0") == 0.579758166
    assert trace.top_ops(ici, "0") == ICI_TOP
    assert trace.idle_gaps(ici, "0") == ICI_GAPS
    kw = dict(latencies_s=[0.29, 0.29], dispatch_s=[0.001] * 2,
              least_hbm_bytes=10**9)
    assert readings("drc864-recovery-4chip", ici, **kw) == {
        "host_ms_per_call.recovery_4chip": 1.0, "ici_permute_ms_per_call": 1.568499,
        "device_idle_share.recovery_4chip": 1.1241968453808537}
    assert readings("drc963-recovery", ici, **kw) == {
        "host_ms_per_call.recovery": 1.0,
        "repair_hbm_roofline.recovery": 0.21060526485127956,
        "device_idle_share.recovery": 1.1241968453808537}
