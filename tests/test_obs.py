"""Tests for the `repro.obs` tracing/metrics subsystem.

Covers span nesting, counter/gauge aggregation, the Chrome trace_event
export round-trip, zero-op behaviour when disabled, the simulator's
stage-span schema, and the load-bearing cross-check: traced inner-/
cross-rack bytes from an *executed* RepairPlan equal the plan's
symbolic bandwidth accounting for every deployed plan shape.
"""
import json
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.core.codes import make_code
from repro.storage import ClusterSim, StageTimes


# ----------------------------------------------------------------- spans
def test_span_nesting_and_timing():
    with obs.tracing("t") as tr:
        with obs.span("outer", cat="x", tag="a") as outer:
            with obs.span("inner", cat="x"):
                time.sleep(0.005)
            outer.set_attr("post", 1)
    o = tr.spans_named("outer")[0]
    i = tr.spans_named("inner")[0]
    assert i.parent_id == o.span_id and o.parent_id is None
    assert i.dur_us >= 5000
    assert o.dur_us >= i.dur_us
    assert i.start_us >= o.start_us
    assert o.attrs == {"tag": "a", "post": 1}


def test_sibling_spans_share_parent():
    with obs.tracing("t") as tr:
        with obs.span("p") as p:
            with obs.span("a"):
                pass
            with obs.span("b"):
                pass
    a, b = tr.spans_named("a")[0], tr.spans_named("b")[0]
    assert a.parent_id == b.parent_id == p.span_id
    assert b.start_us >= a.start_us + a.dur_us


def test_synthetic_spans_lay_out_on_track_cursor():
    with obs.tracing("t") as tr:
        obs.record_span("s1", 0.5, cat="stage", track="sim:1")
        obs.record_span("s2", 0.25, cat="stage", track="sim:1")
        obs.record_span("other", 1.0, cat="stage", track="sim:2")
    s1, s2 = tr.spans_named("s1")[0], tr.spans_named("s2")[0]
    assert (s1.start_us, s1.dur_us) == (0.0, 500_000.0)
    assert (s2.start_us, s2.dur_us) == (500_000.0, 250_000.0)
    assert tr.spans_named("other")[0].start_us == 0.0  # independent track


def test_threads_get_independent_stacks():
    with obs.tracing("t") as tr:
        def work():
            with obs.span("child"):
                pass
        with obs.span("main_parent"):
            th = threading.Thread(target=work, name="worker")
            th.start()
            th.join()
    child = tr.spans_named("child")[0]
    assert child.track == "worker"
    assert child.parent_id is None  # not nested under another thread's span


# --------------------------------------------------------------- metrics
def test_counter_aggregation_across_labels():
    with obs.tracing("t") as tr:
        obs.counter_add("bytes", 100, scope="inner")
        obs.counter_add("bytes", 50, scope="inner")
        obs.counter_add("bytes", 30, scope="cross")
    assert tr.counter_value("bytes", scope="inner") == 150
    assert tr.counter_value("bytes", scope="cross") == 30
    assert tr.counter_value("bytes") == 180  # unlabelled query sums
    assert tr.counter_value("missing") == 0


def test_counter_rejects_negative():
    with obs.tracing("t") as tr:
        with pytest.raises(ValueError):
            tr.counter_add("c", -1)


def test_gauge_last_write_wins():
    with obs.tracing("t") as tr:
        obs.gauge_set("gbps", 1.0, path="ref")
        obs.gauge_set("gbps", 2.5, path="ref")
    assert tr.metrics.gauge_value("gbps", path="ref") == 2.5
    d = tr.metrics.as_dict()
    assert d["gauges"]["gbps"]["path=ref"] == 2.5


def test_disabled_is_noop():
    assert not obs.enabled()
    assert obs.current() is None
    s = obs.span("nope")
    assert s is obs.NULL_SPAN
    with s:
        s.set_attr("k", "v")  # must not raise
    obs.counter_add("nope", 1)
    obs.gauge_set("nope", 1)
    assert obs.record_span("nope", 1.0) is None


# ---------------------------------------------------------------- export
def test_chrome_trace_roundtrip(tmp_path):
    with obs.tracing("rt") as tr:
        with obs.span("a", cat="c1", n=3):
            obs.counter_add("k", 7, scope="x")
            with obs.span("b"):
                pass
        obs.record_span("sim_stage", 0.125, cat="stage", track="sim:1",
                        code="DRC(9,6,3)")
    path = tmp_path / "trace.json"
    obs.write_chrome_trace(tr, str(path))
    loaded = json.loads(path.read_text())
    spans = obs.spans_from_chrome(loaded)
    orig = sorted(tr.spans, key=lambda s: s.span_id)
    assert [s.name for s in spans] == [s.name for s in orig]
    for got, want in zip(spans, orig):
        assert got.span_id == want.span_id
        assert got.parent_id == want.parent_id
        assert got.cat == want.cat
        assert got.track == want.track
        assert got.start_us == pytest.approx(want.start_us)
        assert got.dur_us == pytest.approx(want.dur_us)
        assert got.attrs == {k: v for k, v in want.attrs.items()}
    counters = [e for e in loaded["traceEvents"] if e.get("ph") == "C"]
    assert counters and counters[0]["name"] == "k"
    assert counters[0]["args"] == {"scope=x": 7.0}


def test_summary_aggregates(tmp_path):
    with obs.tracing("s") as tr:
        for _ in range(3):
            obs.record_span("stage_x", 0.1, cat="stage", track="sim:1")
        obs.counter_add("c", 5)
    summ = obs.summary(tr)
    agg = summ["spans"]["stage_x"]
    assert agg["count"] == 3
    assert agg["total_us"] == pytest.approx(300_000.0)
    assert agg["mean_us"] == pytest.approx(100_000.0)
    assert summ["counters"]["c"][""] == 5
    p = tmp_path / "summary.json"
    obs.write_summary(tr, str(p))
    assert json.loads(p.read_text())["trace"] == "s"


# ----------------------------------------------- repair plan cross-check
PLAN_SHAPES = [
    ("DRC", 9, 6, 3),   # family 1: NodeEncode + RelayerEncode
    ("DRC", 9, 5, 3),   # family 2: repair-by-transfer
    ("RS", 9, 5, 3),    # no layering, direct cross-rack sends
    ("MSR", 6, 3, 3),   # regenerating baseline
]


@pytest.mark.parametrize("fam,n,k,r", PLAN_SHAPES)
def test_traced_bytes_match_symbolic_accounting(fam, n, k, r):
    """Bytes moved by the instrumented executor == traffic_blocks()."""
    code = make_code(fam, n, k, r)
    plan = code.repair_plan(0)
    sub = 128
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, size=(code.k * code.alpha, sub), dtype=np.uint8)
    nodes = code.encode(data)
    with obs.tracing("xcheck") as tr:
        rebuilt = plan.execute({i: nodes[i] for i in plan.participants()})
    assert np.array_equal(rebuilt, nodes[0])
    symbolic = plan.traffic_blocks()
    block_bytes = code.alpha * sub
    for scope in ("inner", "cross"):
        traced = tr.counter_value(f"repair.bytes.{scope}_rack")
        assert traced == pytest.approx(
            symbolic[f"{scope}_rack_blocks"] * block_bytes
        ), f"{code!r} {scope}-rack bytes diverge from symbolic accounting"
    # per-relayer unit counters reconcile with the plan's relayer sends
    for relayer in plan.relayers:
        _, sent = plan.relayer_io_blocks(relayer)
        traced_units = tr.counter_value("repair.units_cross",
                                        relayer=str(relayer))
        if traced_units:  # only cross-rack relayer sends are counted
            assert traced_units == sent * plan.alpha


def test_repair_span_structure():
    code = make_code("DRC", 9, 6, 3)
    plan = code.repair_plan(0)
    sub = 64
    data = np.zeros((code.k * code.alpha, sub), dtype=np.uint8)
    nodes = code.encode(data)
    with obs.tracing("spans") as tr:
        plan.execute({i: nodes[i] for i in plan.participants()})
    root = tr.spans_named("repair.execute")[0]
    stages = [s for s in tr.spans if s.parent_id == root.span_id]
    assert len(tr.spans_named("repair.node_encode")) == len(plan.node_sends)
    assert len(tr.spans_named("repair.relayer_encode")) == len(plan.relayer_sends)
    assert len(tr.spans_named("repair.decode")) == 1
    assert all(s.cat == "repair" for s in stages)


# ------------------------------------------------- the profiler's sink
def test_span_with_no_tracer_and_no_profiler_is_the_null_span():
    assert obs.current() is None
    assert obs.span("repair.plan", cat="repair", k=1) is obs.NULL_SPAN
    with obs.span("x") as s:
        s.set_attr("k", 1)  # a no-op, as traced code expects


def _profiled_spans(fn):
    """Run ``fn`` inside a JAX profiler session and read back the host
    events whose names start with ``repair.``, as [name, start, end]."""
    import glob
    import tempfile

    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            fn()
        path, = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
        return [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                for plane in ProfileData.from_file(path).planes
                if plane.name.startswith("/host:")
                for line in plane.lines for e in line.events
                if e.name.startswith("repair.")]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("with_tracer", [False, True],
                         ids=["profiler_alone", "profiler_and_tracer"])
def test_program_spans_reach_the_profiler_and_nest(with_tracer):
    """Under a JAX profiler session the SPMD entry's spans are written to
    the profiler's trace by their bare names, with or without a Tracer:
    ``repair.plan`` then ``repair.launch`` inside
    ``repair.spmd_node_recovery``."""
    jax = pytest.importorskip("jax")
    jnp = pytest.importorskip("jax.numpy")
    from repro.dist.collectives import spmd_node_recovery
    from repro.launch.mesh import make_repair_mesh

    code = make_code("DRC", 9, 6, 3)
    mesh = make_repair_mesh(3, 3, jax.devices()[:1])
    x = jnp.zeros((1, code.n, code.alpha, 256), jnp.uint8)
    jax.block_until_ready(spmd_node_recovery(code, 0, x, mesh)[0])  # compile

    def recover():
        jax.block_until_ready(spmd_node_recovery(code, 0, x, mesh)[0])

    if with_tracer:
        with obs.tracing("both") as tr:
            events = _profiled_spans(recover)
        names = [s.name for s in tr.spans]
        assert sorted(names) == ["repair.launch", "repair.plan",
                                 "repair.spmd_node_recovery"]
    else:
        events = _profiled_spans(recover)
    by_name = {e[0]: e for e in events}
    assert sorted(by_name) == ["repair.launch", "repair.plan",
                               "repair.spmd_node_recovery"]
    assert len(events) == 3  # bare names, one event each
    root, plan, launch = (by_name[n] for n in (
        "repair.spmd_node_recovery", "repair.plan", "repair.launch"))
    assert _inside(plan, root) and _inside(launch, root)
    assert plan[2] <= launch[1]
    assert obs.span("after") is obs.NULL_SPAN  # the session has ended


@pytest.mark.parametrize("entry", ["spmd_repair", "spmd_node_recovery"])
def test_spmd_entry_spans_hold_plan_and_launch(entry):
    """Each SPMD entry opens its root span around ``repair.plan`` (the
    plan and spec rebuild) and ``repair.launch`` (the dispatch): no span
    is left around no work."""
    jax = pytest.importorskip("jax")
    jnp = pytest.importorskip("jax.numpy")
    from repro.dist import collectives
    from repro.launch.mesh import make_repair_mesh

    code = make_code("DRC", 9, 6, 3)
    mesh = make_repair_mesh(3, 3, jax.devices()[:1])
    shape = (code.n, code.alpha, 128)
    x = jnp.zeros(shape if entry == "spmd_repair" else (2, *shape), jnp.uint8)
    with obs.tracing(entry) as tr:
        jax.block_until_ready(getattr(collectives, entry)(code, 0, x, mesh)[0])
    root, = tr.spans_named("repair.spmd" if entry == "spmd_repair"
                           else "repair.spmd_node_recovery")
    children = [s.name for s in tr.spans if s.parent_id == root.span_id]
    assert children == ["repair.plan", "repair.launch"]
    assert len(tr.spans) == 3 and root.attrs["family"] == "DRC"
    stripes = 1 if entry == "spmd_repair" else 2
    want = code.repair_plan(0).traffic_blocks()["cross_rack_blocks"]
    assert tr.counter_value("repair.bytes.cross_rack") == pytest.approx(
        stripes * want * code.alpha * 128)


# ------------------------------------------------------- simulator schema
def test_simulator_stage_spans_match_schema():
    code = make_code("DRC", 9, 5, 3)
    sim = ClusterSim()
    with obs.tracing("sim") as tr:
        t = sim.stage_times(code, code.repair_plan(0), 64.0, 1.0)
    stage_spans = tr.spans_in_cat("stage")
    schema = set(StageTimes(0, 0, 0, 0, 0, 0, 0).as_dict())
    assert {s.name for s in stage_spans} == schema == set(obs.STAGE_NAMES)
    # simulated durations survive the span encoding exactly
    by_name = {s.name: s for s in stage_spans}
    for name, dur in t.as_dict().items():
        assert by_name[name].dur_us == pytest.approx(dur * 1e6)
    # spans tile the track back-to-back in pipeline order
    ordered = sorted(stage_spans, key=lambda s: s.start_us)
    assert [s.name for s in ordered] == list(obs.STAGE_NAMES)


def test_simulator_untouched_without_tracer():
    code = make_code("DRC", 9, 5, 3)
    sim = ClusterSim()
    t = sim.stage_times(code, code.repair_plan(0), 64.0, 1.0)
    assert t.total > 0  # and no tracer state was created
    assert obs.current() is None


# ------------------------------------------------------------- kernels
def test_kernel_span_records_path_and_rate():
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.ops import gf_matmul

    m = np.eye(3, dtype=np.uint8) * 7
    x = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (3, 64), dtype=np.uint8)
    )
    with obs.tracing("k") as tr:
        gf_matmul(m, x)
    s = tr.spans_named("kernel.gf_matmul")[0]
    assert s.cat == "kernel" and s.attrs["path"] == "ref"
    # the span times the dispatch; it neither waits for the result nor
    # claims a rate: the device trace times the kernel
    assert s.dur_us >= 0 and "gbps" not in s.attrs
    assert tr.metrics.gauge_value("kernel.gf_matmul.gbps", path="ref") is None
    assert tr.counter_value("kernel.gf_matmul.bytes", path="ref") == (3 + 3) * 64
    assert tr.counter_value("kernel.gf_matmul.calls", path="ref") == 1


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
