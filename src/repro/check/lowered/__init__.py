"""Static analysis over *lowered* artifacts (``repro.check.lowered``).

``repro.check.plan`` proves repair plans optimal at the DAG level; this
package proves the lowering layers preserved that optimality:

* :mod:`.spmd` — the static SPMD collective-permute schedule
  (``SpmdRepairSpec``): partial-permutation validity, row liveness,
  dead-device silence, decode-gather consistency, exact per-pod byte
  accounting against Eq. (3), rotation balance.
* :mod:`.shard_rules` — sharding-rule tables resolved against every
  model config: axis hygiene, divisibility/fallback guarantees, pod-
  axis containment.
* :mod:`.pallas` — Pallas kernel geometry swept symbolically over the
  full grid (in-bounds, write-disjoint) plus a GF(2^8) dtype-safety
  AST pass over the kernel sources.

Every rule has a paired mutation in ``LOWERED_MUTATIONS``;
:func:`self_test_lowered` corrupts a known-good artifact per mutation
and demands the corruption is caught by *exactly* its owning rule —
stronger than the plan-layer self-test, which only demands the owner
fires.  ``python -m tools.run_check --self-test`` runs both.
"""
from __future__ import annotations

from typing import Any, Callable

from ..report import FAIL, CheckReport, Finding, LoweredRecord
from . import pallas, shard_rules, spmd
from .base import (
    LOWERED_FAMILIES,
    LOWERED_RULES,
    PALLAS_FAMILY,
    SHARD_FAMILY,
    SPMD_FAMILY,
    fail_rules,
    rules_for,
)

# ------------------------------------------------------------------- sweep
# family -> artifact parameters; mirrors plan.REGISTRY_SWEEP in spirit.
LOWERED_SWEEP: dict[str, Any] = {
    SPMD_FAMILY: [
        ("DRC", 6, 4, 3),
        ("DRC", 9, 6, 3),
        ("DRC", 9, 5, 3),
        ("DRC", 8, 6, 4),
        ("RS", 9, 6, 3),
    ],
    SHARD_FAMILY: "ARCHS x MODES",  # resolved at sweep time
    PALLAS_FAMILY: list(pallas.GEOMETRY_SHAPES),
}


def run_lowered_sweep() -> list[LoweredRecord]:
    """Analyze every registered lowered artifact; one record each."""
    from repro.configs import ARCHS, get_config
    from repro.core.codes.registry import make_code
    from repro.dist.sharding import MODES
    from repro.kernels.gf_matmul import gf_matmul_geometry, operand_geometry

    records: list[LoweredRecord] = []
    for fam, n, k, r in LOWERED_SWEEP[SPMD_FAMILY]:
        code = make_code(fam, n, k, r=r)
        records.extend(spmd.verify_spmd_lowering(code))
    for arch in ARCHS:
        config = get_config(arch)
        for mode in MODES:
            records.append(shard_rules.verify_shard_rules(config, mode))
    for shape in LOWERED_SWEEP[PALLAS_FAMILY]:
        records.append(
            pallas.verify_kernel_geometry(gf_matmul_geometry(*shape))
        )
    for shape in pallas.OPERAND_SHAPES:
        records.append(
            pallas.verify_kernel_geometry(operand_geometry(*shape))
        )
    for path in pallas.kernel_source_paths():
        records.append(pallas.verify_kernel_source(path))
    return records


def lowered_report() -> CheckReport:
    """A CheckReport holding only the lowered sweep."""
    return CheckReport(lowered_records=run_lowered_sweep())


# --------------------------------------------------------------- self-test
# mutation name -> (family, owning rule id)
LOWERED_MUTATIONS: dict[str, tuple[str, str]] = {
    **{m: (SPMD_FAMILY, r) for m, r in spmd.SPMD_MUTATIONS.items()},
    **{m: (SHARD_FAMILY, r) for m, r in shard_rules.SHARD_MUTATIONS.items()},
    **{m: (PALLAS_FAMILY, r) for m, r in pallas.PALLAS_MUTATIONS.items()},
}


def _spmd_mutation_fails(mutation: str) -> set[str]:
    from repro.core.codes.registry import make_code
    from repro.dist.collectives import plan_to_spmd

    code = make_code("DRC", 6, 4, r=3)
    plan = code.repair_plan(0)
    spec = plan_to_spmd(code, plan)
    mutated = spmd.mutate_spmd(code, plan, spec, mutation)
    return fail_rules(spmd.spmd_mutation_findings(code, plan, mutated))


def _shard_mutation_fails(mutation: str) -> set[str]:
    from repro.configs import get_config
    from repro.dist.sharding import make_rules, resolve_spec

    art = shard_rules.ShardArtifact(
        rules=make_rules("tp", multi_pod=True),
        config=get_config("command_r_35b"),
        meshes=(
            *shard_rules.MULTI_POD_MESHES,
            *shard_rules.CANONICAL_MESHES,
        ),
        resolver=resolve_spec,
    )
    mutated = shard_rules.mutate_shard(art, mutation)
    return fail_rules(shard_rules.analyze_shard_artifact(mutated))


def _pallas_mutation_fails(mutation: str) -> set[str]:
    from repro.kernels.gf_matmul import gf_matmul_geometry

    # a ragged grid: the last of its 9 blocks is clipped at the payload's end
    geom = gf_matmul_geometry(3, 6, 4096 + 384, 512)
    path = pallas.kernel_source_paths()[0]
    with open(path) as f:
        source = f.read()
    return fail_rules(
        pallas.pallas_mutation_findings(geom, source, path, mutation)
    )


_MUTATION_RUNNERS: dict[str, Callable[[str], set[str]]] = {
    SPMD_FAMILY: _spmd_mutation_fails,
    SHARD_FAMILY: _shard_mutation_fails,
    PALLAS_FAMILY: _pallas_mutation_fails,
}


def self_test_lowered() -> list[tuple[str, str, bool, bool]]:
    """Corrupt one known-good artifact per mutation.

    Returns (mutation, owning rule, caught, exclusive) rows; the gate
    demands caught AND exclusive — the corruption must FAIL exactly the
    rule that owns it, proving both coverage and rule independence.
    """
    rows: list[tuple[str, str, bool, bool]] = []
    for mutation, (family, owner) in LOWERED_MUTATIONS.items():
        fails = _MUTATION_RUNNERS[family](mutation)
        rows.append((mutation, owner, owner in fails, fails == {owner}))
    return rows


__all__ = [
    "LOWERED_FAMILIES", "LOWERED_MUTATIONS", "LOWERED_RULES",
    "LOWERED_SWEEP", "PALLAS_FAMILY", "SHARD_FAMILY", "SPMD_FAMILY",
    "FAIL", "Finding", "LoweredRecord", "fail_rules", "lowered_report",
    "pallas", "rules_for", "run_lowered_sweep", "self_test_lowered",
    "shard_rules", "spmd",
]
