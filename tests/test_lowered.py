"""Tests for `repro.check.lowered` — the lowered-layer static analyzer.

Three families (SPMD schedule, sharding rules, Pallas kernels), each
with: the full sweep PASSing on the real artifacts, every mutation
caught by *exactly* its owning rule, and targeted unit checks of the
trickier rule semantics.
"""
import dataclasses

import numpy as np
import pytest

from repro.check.lowered import (
    LOWERED_MUTATIONS,
    LOWERED_RULES,
    PALLAS_FAMILY,
    SHARD_FAMILY,
    SPMD_FAMILY,
    fail_rules,
    pallas,
    run_lowered_sweep,
    self_test_lowered,
    shard_rules,
    spmd,
)
from repro.check.report import FAIL, PASS
from repro.core.codes import make_code
from repro.dist.collectives import plan_to_spmd
from repro.dist.sharding import MODES, make_rules, resolve_spec
from repro.kernels.gf_matmul import gf_matmul_geometry

_CODES: dict = {}


def get_code(family, n, k, r):
    key = (family, n, k, r)
    if key not in _CODES:
        _CODES[key] = make_code(family, n, k, r)
    return _CODES[key]


def get_lowering(family, n, k, r, failed=0):
    code = get_code(family, n, k, r)
    plan = code.repair_plan(failed)
    return code, plan, plan_to_spmd(code, plan)


# ----------------------------------------------------------------- registry


def test_rule_registry_namespacing_and_families():
    for rule_id, (family, _fn) in LOWERED_RULES.items():
        assert rule_id.startswith("lowered."), rule_id
        assert family in (SPMD_FAMILY, SHARD_FAMILY, PALLAS_FAMILY)
    assert len(LOWERED_RULES) >= 12


def test_every_rule_owns_at_least_one_mutation_family():
    owned = {owner for _family, owner in LOWERED_MUTATIONS.values()}
    # every registered rule is exercised by some mutation
    assert owned == set(LOWERED_RULES), (
        set(LOWERED_RULES) - owned, owned - set(LOWERED_RULES)
    )


# -------------------------------------------------------------- full sweep


def test_lowered_sweep_all_pass_and_covers_all_families():
    records = run_lowered_sweep()
    assert len(records) >= 100
    assert {r.family for r in records} == {
        SPMD_FAMILY, SHARD_FAMILY, PALLAS_FAMILY
    }
    bad = [r for r in records if r.status != PASS]
    assert bad == [], [
        (r.label, r.artifact, [f.message for f in r.findings]) for r in bad
    ]


@pytest.mark.parametrize("mutation", sorted(LOWERED_MUTATIONS))
def test_mutation_caught_by_exactly_owning_rule(mutation):
    rows = {m: (owner, caught, exclusive)
            for m, owner, caught, exclusive in self_test_lowered()}
    owner, caught, exclusive = rows[mutation]
    assert caught, f"{mutation} not caught by {owner}"
    assert exclusive, f"{mutation} caught by more than just {owner}"


# ------------------------------------------------------------ SPMD schedule


@pytest.mark.parametrize("shape", [
    ("DRC", 6, 4, 3), ("DRC", 8, 6, 4), ("RS", 9, 6, 3),
])
def test_spmd_real_lowerings_pass_every_rule(shape):
    fam, n, k, r = shape
    code = get_code(fam, n, k, r)
    for rec in spmd.verify_spmd_lowering(code):
        assert rec.status == PASS, (
            rec.artifact, [f.message for f in rec.findings]
        )


def test_spmd_self_send_finding_names_the_pod():
    code, plan, spec = get_lowering("DRC", 6, 4, 3)
    mutated = spmd.mutate_spmd(code, plan, spec, "spmd_self_send")
    findings = spmd.check_permute_partial(code, plan, mutated)
    assert findings and findings[0].severity == FAIL
    assert findings[0].witness["pod"] == spec.target_pod


def test_spmd_in_bounds_padding_row_is_caught():
    """A scheduled row can be in bounds yet point at the zero padding of
    the stacked relayer matrices — a bounds check alone misses it."""
    code, plan, spec = get_lowering("DRC", 6, 4, 3)
    assert spec.ru > 0
    rel_units = spmd._relayer_units(plan)
    padding = None
    for q in range(spec.r):
        if q == spec.target_pod or not spec.cross_idx[q]:
            continue
        for slot in range(spec.w):
            node = q * spec.w + slot
            have = rel_units.get(node, 0)
            if have < spec.ru:  # first padding offset of this node
                padding = (q, spec.w * spec.nu + slot * spec.ru + have)
                break
        if padding:
            break
    assert padding is not None, "no padding row in this lowering"
    q, row = padding
    assert 0 <= row < spec.pool_rows  # in bounds — that's the point
    cross = list(spec.cross_idx)
    cross[q] = (row, *cross[q][1:])  # swap, preserving per-pod counts
    mutated = dataclasses.replace(spec, cross_idx=tuple(cross))
    assert fail_rules(
        spmd.analyze_spmd_spec(code, plan, mutated)
    ) == {spmd.R_LS_ROWS}


def test_spmd_byte_accounting_matches_traffic_blocks():
    code, plan, spec = get_lowering("DRC", 9, 6, 3)
    t = plan.traffic_blocks()
    scheduled = sum(
        len(rows) for q, _dst, rows in spec.permute_steps()
        if q != spec.target_pod
    )
    assert scheduled == round(float(t["cross_rack_blocks"]) * plan.alpha)
    assert spmd.check_byte_accounting(code, plan, spec) == []


def test_spmd_rotation_balance_detects_stuck_rotation():
    code, plan, spec = get_lowering("DRC", 6, 4, 3)
    stuck = spmd.mutate_spmd(code, plan, spec, "spmd_stuck_rotation")
    findings = spmd.check_rotation_balance(code, plan.failed, stuck)
    assert findings, "stuck rotation not flagged"
    assert all(f.rule == spmd.R_LS_ROTATION for f in findings)
    # the real rotation cycle is balanced
    good = spmd.rotation_specs(code, plan.failed)
    assert spmd.check_rotation_balance(code, plan.failed, good) == []


# ------------------------------------------------------------ shard rules


@pytest.mark.parametrize("mode", sorted(MODES))
def test_shard_tables_pass_for_every_mode(mode):
    from repro.configs import get_config

    rec = shard_rules.verify_shard_rules(get_config("minicpm_2b"), mode)
    assert rec.status == PASS, [f.message for f in rec.findings]


def test_shard_prime_dimension_must_replicate():
    from repro.configs import get_config

    art = shard_rules.ShardArtifact(
        rules=make_rules("tp"),
        config=get_config("minicpm_2b"),
        meshes=shard_rules.CANONICAL_MESHES,
        resolver=resolve_spec,
    )
    assert shard_rules.check_divisibility(art) == []
    # the greedy resolver shards the prime probe -> caught
    bad = shard_rules.mutate_shard(art, "shard_greedy_resolver")
    findings = shard_rules.check_divisibility(bad)
    assert any("fallback unreachable" in f.message or "does not divide"
               in f.message for f in findings)


def test_shard_pod_leak_message_explains_repair_cost():
    from repro.configs import get_config

    art = shard_rules.ShardArtifact(
        rules=make_rules("tp", multi_pod=True),
        config=get_config("minicpm_2b"),
        meshes=shard_rules.MULTI_POD_MESHES,
        resolver=resolve_spec,
    )
    bad = shard_rules.mutate_shard(art, "shard_pod_leak")
    findings = shard_rules.check_multi_pod(bad)
    assert findings and findings[0].witness["logical"] == "embed"


# ----------------------------------------------------------- pallas kernels


@pytest.mark.parametrize("shape", list(pallas.GEOMETRY_SHAPES))
def test_kernel_geometry_in_bounds_and_write_disjoint(shape):
    geom = gf_matmul_geometry(*shape)
    assert pallas.analyze_geometry(geom) == []


def test_kernel_geometry_is_what_pallas_call_consumes():
    """The verifier sweeps the same object the kernel builds specs from."""
    geom = gf_matmul_geometry(3, 6, 4096, 512)
    assert geom.grid == (8,)
    in_specs = geom.in_specs()
    assert len(in_specs) == 2
    assert in_specs[0].block_shape == (3, 48)  # the coefficient masks
    assert geom.out_spec().block_shape == (3, 512)


def test_kernel_geometry_rejects_indivisible_payload():
    """A payload width that is no multiple of the tile gets a ragged
    grid, its last block clipped at the end, which the rules prove in
    bounds and write-disjoint; a tile that is no multiple of 512 bytes
    (four lane-rows of 32-bit words) is still refused."""
    geom = gf_matmul_geometry(3, 6, 1000, 512)
    assert geom.grid == (2,) and geom.clipped_dim == 1
    assert pallas.analyze_geometry(geom) == []
    with pytest.raises(ValueError, match="not a multiple"):
        gf_matmul_geometry(3, 6, 1000, 384)
    # a tile wider than the payload shrinks to it, rounded up to 512
    geom = gf_matmul_geometry(3, 6, 200, 4096)
    assert geom.out_block == (3, 512) and geom.grid == (1,)
    assert pallas.analyze_geometry(geom) == []


def test_geometry_sweep_tiles_the_cells_as_the_kernel_does():
    """The swept shapes at the cells' widths carry the tile the kernel
    takes for them, so the sweep proves the geometry that runs."""
    from repro.kernels.ops import choose_block_b

    real = [s for s in pallas.GEOMETRY_SHAPES if s[2] >= 349568]
    assert len(real) == 6
    for r, k, b, tile in real:
        assert tile == choose_block_b(k, r), (r, k, b)
        assert gf_matmul_geometry(r, k, b, tile).grid[0] > 1


def test_pallas_oob_clips_only_the_declared_dim():
    """The clip is a property the geometry declares, not a looser rule:
    the same ragged blocks without ``clipped_dim`` run out of bounds."""
    geom = gf_matmul_geometry(3, 6, 1000, 512)
    strict = dataclasses.replace(geom, clipped_dim=None)
    findings = pallas.check_pallas_oob(strict)
    assert findings and findings[0].witness["start"] == 512
    assert findings[0].witness["extent"] == 1000
    # and a clipped dim still keeps every block's start inside the array
    assert pallas.check_pallas_oob(geom) == []


@pytest.mark.parametrize("mutation, owner", [
    ("pallas_clip_past_end", pallas.R_PL_OOB),
    ("pallas_clip_overlap", pallas.R_PL_ALIAS),
])
def test_clipped_geometry_mutation_caught_by_owner_alone(mutation, owner):
    """At a cell's real width (DRC(9,6,3) Decode over a 22,369,664-byte
    sub-block, 128 x a prime), a last block shifted past the end fails
    the oob rule alone, and two overlapping blocks the alias rule alone."""
    from repro.kernels.ops import choose_block_b

    geom = gf_matmul_geometry(3, 12, 22369664, choose_block_b(12, 3))
    assert 22369664 % geom.out_block[1] and pallas.analyze_geometry(geom) == []
    kind, bad = pallas.mutate_pallas(geom, "", mutation)
    assert kind == "geometry"
    findings = pallas.analyze_geometry(bad)
    assert {f.rule for f in findings} == {owner}
    if owner == pallas.R_PL_OOB:
        assert findings[0].witness["start"] >= 22369664
    else:
        assert findings[0].witness["second"] == [geom.grid[0] - 1]


def test_pallas_oob_witness_names_grid_point_and_extent():
    geom = gf_matmul_geometry(2, 4, 1024, 512)
    bad = dataclasses.replace(
        geom,
        in_index_maps=(geom.in_index_maps[0], lambda j: (0, j + 1)),
    )
    findings = pallas.check_pallas_oob(bad)
    assert findings and findings[0].severity == FAIL
    assert findings[0].witness["extent"] == 1024


def test_pallas_alias_detects_constant_out_map():
    geom = gf_matmul_geometry(2, 4, 1024, 512)
    bad = dataclasses.replace(geom, out_index_map=lambda j: (0, 0))
    findings = pallas.check_pallas_out_alias(bad)
    assert findings and "write-write race" in findings[0].message


def test_gf_dtype_pass_clean_on_real_kernels():
    for path in pallas.kernel_source_paths():
        with open(path) as f:
            assert pallas.check_gf_dtype(path, f.read()) == [], path


def test_gf_dtype_flags_uint8_addition():
    src = (
        "def _k(x_ref, o_ref):\n"
        "    a = x_ref[...]\n"
        "    o_ref[...] = a + a\n"  # GF addition is XOR, not +
    )
    findings = pallas.check_gf_dtype("k.py", src)
    assert [f.rule for f in findings] == [pallas.R_PL_DTYPE]


def test_gf_dtype_explicit_cast_clears_taint():
    src = (
        "import jax.numpy as jnp\n"
        "def _k(x_ref, o_ref):\n"
        "    a = x_ref[...].astype(jnp.int32)\n"
        "    o_ref[...] = a + a\n"
    )
    assert pallas.check_gf_dtype("k.py", src) == []


def test_gf_dtype_flags_reduction_without_dtype():
    src = (
        "import jax.numpy as jnp\n"
        "def _k(x_ref, o_ref):\n"
        "    o_ref[...] = jnp.sum(x_ref[...], axis=0)\n"
    )
    findings = pallas.check_gf_dtype("k.py", src)
    assert findings and "wraps mod 256" in findings[0].message


def test_gf_dtype_flags_matmul_without_preferred_type():
    src = (
        "import jax\n"
        "def _k(a, b):\n"
        "    return jax.lax.dot_general(a, b, dimension_numbers=None)\n"
    )
    findings = pallas.check_gf_dtype("k.py", src)
    assert findings and "preferred_element_type" in findings[0].message


# ------------------------------------------------------------- report model


def test_lowered_record_json_roundtrip(tmp_path):
    import json

    from repro.check.report import CheckReport

    code = get_code("DRC", 6, 4, 3)
    report = CheckReport(lowered_records=spmd.verify_spmd_lowering(code))
    path = report.write_json(str(tmp_path / "lowered.json"))
    with open(path) as f:
        obj = json.load(f)
    assert obj["version"] == 3
    rec = obj["lowered_records"][0]
    assert {"label", "family", "artifact", "status", "findings",
            "info"} <= set(rec)
    assert rec["family"] == SPMD_FAMILY
    assert obj["summary"]["FAIL"] == 0


def test_mutations_do_not_touch_the_original_spec():
    code, plan, spec = get_lowering("DRC", 6, 4, 3)
    before = (
        tuple(tuple(r) for r in spec.cross_idx),
        np.asarray(spec.node_mats).copy(),
        tuple(spec.target_idx),
    )
    for mutation, (family, _owner) in LOWERED_MUTATIONS.items():
        if family != SPMD_FAMILY:
            continue
        spmd.mutate_spmd(code, plan, spec, mutation)
    assert tuple(tuple(r) for r in spec.cross_idx) == before[0]
    np.testing.assert_array_equal(np.asarray(spec.node_mats), before[1])
    assert tuple(spec.target_idx) == before[2]


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
