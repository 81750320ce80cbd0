"""The reduction from a profiler trace to busy time, idle share, op time
and idle gaps, and the count of cross-rack bytes in compiled HLO."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

from yardstick import hlo, trace


def synthetic():
    """Window 0..100 ns: a while loop with an op nested in it, ops after
    it, a permute's start and done, and an op outside the window; host
    spans."""
    return {
        "window": [0, 100],
        "devices": {"0": [["while.1", 10, 30],          # 10..40
                          ["fusion.2", 15, 10],         # nested: 15..25
                          ["fusion.3", 40, 15],         # 40..55, after it
                          ["collective-permute-start.1", 60, 2],  # 60..62
                          ["collective-permute-done.1", 68, 2],   # 68..70
                          ["fusion.4", 95, 20],         # 95..115, clipped at 100
                          ["fusion.5", 120, 5]]},       # outside the window
        "host": [["recovery.call", 0, 100], ["dispatch", 0, 9],
                 ["block_until_ready", 50, 45]],
    }


def test_busy_is_the_union_of_op_intervals_in_the_window():
    t = synthetic()
    assert trace.busy_intervals(t, "0") == [(10, 55), (60, 62), (68, 70), (95, 100)]
    assert trace.busy_s(t, "0") == pytest.approx(54e-9)
    assert trace.window_s(t) == pytest.approx(100e-9)
    assert trace.idle_share(t, "0") == pytest.approx(0.46)
    assert trace.has_ops(t, "0") and not trace.has_ops(t, "1")


def test_op_time_counts_overlapping_matches_once():
    t = synthetic()
    t["devices"]["0"].append(["collective-permute.9", 61, 4])  # 61..65
    assert trace.op_seconds(t, "0", "collective-permute") == pytest.approx(7e-9)
    assert trace.op_seconds(t, "0", "no-such-op") == 0


def test_top_ops_rank_by_self_time():
    top = dict(trace.top_ops(synthetic(), "0"))
    assert top == pytest.approx({"while.1": 20e-9, "fusion.3": 15e-9,
                                 "fusion.2": 10e-9, "fusion.4": 5e-9,
                                 "collective-permute-start.1": 2e-9,
                                 "collective-permute-done.1": 2e-9})


def test_idle_time_goes_to_the_innermost_open_span():
    gaps = dict(trace.idle_gaps(synthetic(), "0"))
    # 0..9 in dispatch, 9..10 in the call, 55..60, 62..68 and 70..95 in the wait
    assert gaps == pytest.approx({"dispatch": 9e-9, "block_until_ready": 36e-9,
                                  "recovery.call": 1e-9})
    t = synthetic()
    t["host"] = [["dispatch", 0, 5]]
    assert dict(trace.idle_gaps(t, "0")) == pytest.approx(
        {trace.IDLE_HOST: 41e-9, "dispatch": 5e-9})


def test_op_names_drop_the_instruction_text():
    assert trace.op_name("%while.201 = (s32[], u8[3,4]{1,0}) while(%t)") == "while.201"
    assert trace.op_name("jit_body(123)") == "jit_body(123)"


TPU_PERMUTE = (
    "  %collective-permute-start.1 = (u8[3,33554432]{1,0:T(4,128)}, "
    "u8[3,33554432]{1,0:T(4,128)}, u32[]{:S(2)}, u32[]{:S(2)}) "
    "collective-permute-start(u8[3,33554432]{1,0:T(4,128)} %slice.5), "
    "channel_id=7, source_target_pairs={{1,0}}")
CPU_PERMUTE = ("  %collective-permute.2 = u8[6,1024]{1,0} collective-permute("
               "u8[6,1024]{1,0} %concatenate.3), channel_id=8, "
               "source_target_pairs={{2,0},{3,1}}")
DONE = ("  %collective-permute-done.1 = u8[3,33554432]{1,0} "
        "collective-permute-done((u8[3,33554432]{1,0}, u8[3,33554432]{1,0}, "
        "u32[], u32[]) %collective-permute-start.1)")


def test_cross_pod_bytes_count_the_moved_buffer_once():
    text = "\n".join([TPU_PERMUTE, CPU_PERMUTE, DONE])
    assert hlo.cross_pod_bytes(text, 1) == 3 * 33554432 + 6 * 1024
    # with two devices to a pod, device 1 -> 0 stays inside pod 0
    assert hlo.cross_pod_bytes(text, 2) == 6 * 1024


FIXTURES = Path(__file__).resolve().parent / "fixtures"


def sweep_union_ns(intervals) -> int:
    """Covered length by a sweep over start and end points: another way
    to the union than trace.busy_intervals's merge."""
    points = sorted([(a, 1) for a, b in intervals] + [(b, -1) for a, b in intervals])
    depth = covered = 0
    prev = None
    for t, step in points:
        if depth > 0:
            covered += t - prev
        depth += step
        prev = t
    return covered


@pytest.fixture(scope="module")
def read_trace(tmp_path_factory):
    """A 0.05 s window of the degraded-read cell (11 reads), traced on a
    TPU v5 lite by run_cell.py --trace 1."""
    from yardstick.runner import HOST_SPANS

    path = tmp_path_factory.mktemp("trace") / "read.xplane.pb"
    path.write_bytes(gzip.decompress(
        (FIXTURES / "read_trace.xplane.pb.gz").read_bytes()))
    return trace.extract(str(path), HOST_SPANS)


def test_recorded_trace_extracts_device_ops_and_host_spans(read_trace):
    ops = read_trace["devices"]["0"]
    assert len(ops) == 2761  # the XLA Ops line; the async line is left out
    spans = [h[0] for h in read_trace["host"]]
    assert spans.count("read.call") == 11  # the run's attempted reads
    assert spans.count("dispatch") == spans.count("take_strip") == 11
    assert all(not name.startswith("%") for name, _, _ in ops)


def test_recorded_trace_reduces_to_the_numbers_its_run_printed(read_trace):
    t = read_trace
    lo, hi = t["window"]
    clipped = [(max(s, lo), min(s + d, hi)) for _, s, d in t["devices"]["0"]
               if min(s + d, hi) > max(s, lo)]
    busy = trace.busy_s(t, "0")
    assert busy == pytest.approx(sweep_union_ns(clipped) / 1e9, abs=1e-12)
    # the run printed window_s 0.053517588 and, counting the async copies
    # in flight as busy too, busy_s 0.033139041 (idle 38.078%)
    assert trace.window_s(t) == pytest.approx(0.053517588, abs=1e-12)
    assert busy <= 0.033139041
    assert trace.idle_share(t, "0") == pytest.approx(
        1 - busy / trace.window_s(t))
    idle = sum(s for _, s in trace.idle_gaps(t, "0", n=100))
    assert idle == pytest.approx(trace.window_s(t) - busy, abs=1e-12)
    self_time = sum(s for _, s in trace.top_ops(t, "0", n=10**6))
    assert self_time == pytest.approx(busy, abs=1e-9)


@pytest.fixture(scope="module")
def ici_trace():
    """Two recovery calls of the four-chip cell (0.59 s), traced on four
    TPU v5 lite chips: the target rack's chip "0" and a sending rack's
    chip "1", as extracted."""
    return json.loads(gzip.decompress(
        (FIXTURES / "ici_trace_events.json.gz").read_bytes()))


def test_recorded_four_chip_trace_reduces_to_its_runs_numbers(ici_trace):
    t = ici_trace
    lo, hi = t["window"]
    for chip in ("0", "1"):
        permutes = [(max(s, lo), min(s + d, hi)) for name, s, d in t["devices"][chip]
                    if "collective-permute" in name]
        ici = trace.op_seconds(t, chip, r"collective-permute")
        assert ici == pytest.approx(sweep_union_ns(permutes) / 1e9, abs=1e-12)
    # the sending rack waits in its permutes for 1.6 ms a call; the
    # target, whose data has arrived when it needs it, for microseconds
    assert trace.op_seconds(t, "1", "collective-permute") / 2 * 1e3 == pytest.approx(
        1.568499)
    assert trace.op_seconds(t, "0", "collective-permute") < 1e-5
    # the run printed device_idle_share.recovery 1.1241794496242852 with
    # the async ops counted as busy; they lay inside the chip's own work
    assert trace.idle_share(t, "0") * 100 == pytest.approx(1.1241968453808537)
    assert trace.busy_s(t, "0") < trace.window_s(t)


def test_readers_find_nothing_where_the_trace_has_nothing(ici_trace):
    from yardstick import spec
    from yardstick.runner import Run

    cell = spec.load_cell("drc864-recovery-4chip")
    run = Run(cell=cell, peaks=spec.load_peaks("TPU v5 lite"), setup_s=1.0,
              latencies_s=[0.29, 0.29], trace=ici_trace, target_device="0")
    ici = spec.load_reader("ici_permute_ms_per_call")
    assert ici(run) == pytest.approx(1.568499)
    idle = spec.load_reader("device_idle_share.recovery_4chip")
    assert idle(run) == pytest.approx(1.1241968453808537)
    run.target_device = "7"  # no plane for this chip in the trace
    assert idle(run) is None
    run.trace = dict(ici_trace, devices={})
    assert ici(run) is None
    run.trace = None
    assert spec.load_reader("repair_hbm_roofline.recovery")(run) is None
