"""The benchmark's files: BENCHMARK.json's shape, lookup by name, the
configurations against the program's codes, the traffic generator."""
from __future__ import annotations

import json
import re
import shutil

import numpy as np
import pytest

from yardstick import spec, traffic

ROOT = spec.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keys_names_and_files():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"] and BENCH["command"][1].startswith("bench/")
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [e["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for e in BENCH[key]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
        # the cuts from the source, with their reasons, are in the file
        assert sorted(c["reduced"]) == sorted(
            json.loads((ROOT / c["file"]).read_text())["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert "\n" not in m["layer"] and m["layer"]


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_setup_another_end_to_end_and_a_layer(workload):
    cell = spec.load_cell(workload)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    moved = {m["moves"] for m in cell.per_layer}
    assert moved <= e2e


def test_added_files_are_found_by_name_with_no_edit(tmp_path):
    """A new deployment, traffic mix and metric are only new files and
    new entries of BENCHMARK.json."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((ROOT / "bench/configs/rs-9-6-3.json").read_text())
    cfg.update(name="rs-9-6-3-s2", stripes=2)
    (tmp_path / "bench/configs/rs-9-6-3-s2.json").write_text(json.dumps(cfg))
    mix = {"kind": "degraded_read", "arrival": {"process": "closed", "clients": 1},
           "strip_bytes": 65536, "keys": "scrambled_zipfian_0.99"}
    (tmp_path / "bench/traffic/degraded_read_64k.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/reads_done.py").write_text(
        "def read(run):\n    return len(run.latencies_s)\n")
    bench = dict(BENCH)
    bench["configs"] = BENCH["configs"] + [dict(
        BENCH["configs"][1], name="rs-9-6-3-s2",
        file="bench/configs/rs-9-6-3-s2.json")]
    bench["workloads"] = BENCH["workloads"] + [dict(
        name="rs963s2-read-64k", config="rs-9-6-3-s2",
        traffic="degraded_read_64k", chips=1, why="test")]
    bench["per_layer"] = BENCH["per_layer"] + [dict(
        name="reads_done", unit="reads", better="higher", source="host_clock",
        layer="entry host path", moves="read_p50_ms",
        workloads=["rs963s2-read-64k"])]
    bench["end_to_end"] = [dict(m, workloads=m["workloads"] + ["rs963s2-read-64k"])
                           if m["name"].startswith("read_") else m
                           for m in BENCH["end_to_end"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("rs963s2-read-64k", tmp_path)
    assert cell.deployment.stripes == 2 and cell.traffic["strip_bytes"] == 65536
    assert [m["name"] for m in cell.per_layer] == ["reads_done"]
    assert {m["name"] for m in cell.end_to_end} == {
        "read_p50_ms", "read_p95_ms", "setup_s"}
    run = type("Run", (), {"latencies_s": [0.1, 0.2]})()
    assert spec.load_reader("reads_done", tmp_path)(run) == 2
    traffic.check(cell.traffic)


def test_unknown_workload_and_device_kind_are_errors():
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")
    with pytest.raises(KeyError, match="no peaks"):
        spec.load_peaks("cpu")
    peaks = spec.load_peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9 and peaks["bf16_flops_per_s"] == 197e12
    assert peaks["int8_ops_per_s"] == 393e12 and peaks["ici_bits_per_s"] == 1600e9


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_configuration_matches_the_programs_code(config):
    """The pinned generator is the program's, the helper count is the
    nodes repair_plan reads for every relayer rotation, and the cross
    bytes are the plan's own count."""
    from repro.core.codes import make_code
    from repro.dist.collectives import expected_cross_units

    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    dep = spec.Deployment.from_dict(json.loads((ROOT / entry["file"]).read_text()))
    code = make_code(dep.family, dep.n, dep.k, dep.r)
    assert code.alpha == dep.alpha
    assert np.array_equal(code.generator, dep.generator)
    for rotation in range(dep.stripes):
        plan = code.repair_plan(dep.failed, rotation=rotation)
        assert len(plan.participants()) == dep.helpers_read
        assert expected_cross_units(plan) == round(dep.cross_rack_blocks * dep.alpha)
    assert -(-dep.block_bytes // dep.alpha // 128) * 128 == dep.sub_bytes


def test_zipfian_keys_are_seeded_skewed_and_in_range():
    a = traffic.draw_keys(192, np.random.default_rng([2**40 + 7]), 20000)
    b = traffic.draw_keys(192, np.random.default_rng([2**40 + 7]), 20000)
    c = traffic.draw_keys(192, np.random.default_rng([2**40 + 8]), 20000)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 192
    counts = np.bincount(a, minlength=192)
    assert counts.max() > 5 * np.median(counts)  # a few strips are hot
    # the hottest strip is YCSB's rank 0, hashed: fnv64(0) mod the strips
    assert counts.argmax() == traffic._fnv64(np.zeros(1, np.int64))[0] % 192


def test_strip_layout_covers_every_block_in_128_lane_strips():
    width, offsets = traffic.strip_layout(3, 22369664, 1 << 26, 1 << 20)
    assert width == 349568 and width % 128 == 0 and len(offsets) == 64
    assert offsets[-1] + width == 22369664 and np.all(offsets % 128 == 0)
    assert np.all(np.diff(offsets) <= width)  # no byte of the block is skipped
    width, reads = traffic.read_sequence(
        json.loads((ROOT / "bench/traffic/degraded_read_1m.json").read_text()),
        3, 3, 22369664, 1 << 26, 2**33 + 1)
    assert set(np.unique(reads[:, 0])) <= {0, 1, 2} and len(reads) == traffic.KEYS_AHEAD


@pytest.mark.parametrize("bad", [
    {"kind": "scan"},
    {"arrival": {"process": "open", "rate": 10}},
    {"keys": "uniform"},
])
def test_traffic_the_generator_cannot_make_is_refused(bad):
    mix = json.loads((ROOT / "bench/traffic/degraded_read_1m.json").read_text())
    mix.update(bad)
    with pytest.raises(ValueError):
        traffic.check(mix)


def test_seed_keys_do_not_collide_for_large_seeds():
    from yardstick.runner import seed_key_data

    assert not np.array_equal(seed_key_data(2**33), seed_key_data(0))
    assert not np.array_equal(seed_key_data(2**31 + 5), seed_key_data(5))
    assert np.array_equal(seed_key_data(12345), seed_key_data(12345))


def test_gf256_inverse_and_product_agree_with_numpy_algebra():
    import jax.numpy as jnp

    from yardstick import gf256

    rng = np.random.default_rng(3)
    dep = spec.Deployment.from_dict(
        json.loads((ROOT / "bench/configs/drc-9-6-3.json").read_text()))
    rows = dep.generator[3:21]  # nodes 1..6: any k nodes of an MDS code
    inv = gf256.inverse(rows)
    assert np.array_equal(gf256.matmul(inv, rows), np.eye(18, dtype=np.uint8))
    x = rng.integers(0, 256, (18, 640), dtype=np.uint8)
    got = np.asarray(gf256.product(dep.parity, jnp.asarray(x)))
    assert np.array_equal(got, gf256.matmul(dep.parity, x))
    old = gf256.TILE_ELEMS
    try:  # the tiled walk, with a last tile that overlaps the one before
        gf256.TILE_ELEMS = 9 * 18 * 256
        got = np.asarray(gf256.product(dep.parity, jnp.asarray(x)))
    finally:
        gf256.TILE_ELEMS = old
    assert np.array_equal(got, gf256.matmul(dep.parity, x))
